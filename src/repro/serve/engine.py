"""Batched serving engine: continuous batching over a paged KV cache.

The engine is a thin client of :mod:`repro.runtime`: construct a compiled
``Runtime`` first and hand it over —

    rt = repro.runtime.compile(cfg, params, quant_state=qs)
    engine = ServeEngine(rt, max_batch=8, max_len=512)

the Runtime owns the execution context (backend, per-layer SAR registers,
weight-stationary plan, mesh/placement) and the jit'd prefill /
prefill_cont / decode steps; the engine owns scheduling, the paged block
pool, and per-request attribution of each call's ``AdOpsReport``.  The old
``ServeEngine(cfg, apply_fn, cache_fn, params, ...)`` signature remains as
a deprecated shim that compiles a temporary Runtime (one warning).

Production shape (vLLM-style, sized down to what a dry-runnable JAX core
needs):

* a block pool (``serve.kvcache.PagedKVCache``): ``block_size``-token pages
  with a free list, per-request block tables, refcounts, and hash-consed
  prompt-prefix pages shared copy-on-write across requests — a common
  system prompt is prefilled (and A/D-converted) once;
* admission: queued requests are prefilled one-at-a-time with a batch=1
  forward (bucketed to powers of two so the number of prefill compilations
  is O(log max_prompt)); the resulting KV blocks are scattered into pool
  pages and the O(1) recurrent state (mamba/rwkv/len counters) into the
  request's slot row.  When a prompt's leading blocks hit the prefix index,
  only the un-cached suffix is prefilled (``mode="prefill_cont"``);
* one ``decode_step`` advances *all* active slots a token: the dense cache
  view is gathered from the pool through the block tables (page 0 is
  permanently zero, so unallocated tails materialize as exact zeros), the
  jit'd step runs unchanged model code on it, and the one written block per
  slot is scattered back.  Gather/scatter is pure data movement, which is
  why paged decode is bitwise-identical to the dense slot engine
  (``paged=False``), kept as the reference for the equivalence suite;
* weight-stationary plan cache: ``repro.runtime.compile`` programs the
  crossbars once (``prepare_params``) and the Runtime threads the frozen
  ``PimPlan`` through every jit'd prefill/decode step, so per-token work
  is activations-only — no max-|w| rescan, re-cast, or re-slicing per
  layer per token.  Bitwise identical to the dynamic path; compile with
  ``plan=False`` to A/B it;
* per-request A/D-energy metering: every Runtime call returns an
  ``AdOpsReport`` with the summed ``PimOut.ad_ops`` of its ``pim_mvm``
  calls; the engine attributes them to requests (prefill ops exactly,
  decode ops split over the slots that stepped) so ``stats()`` reports
  per-request conversion counts and SAR energy (Eq. 6) next to tokens/s
  and TTFT.

The engine is mesh-agnostic: under ``use_mesh`` the same code paths run
pjit'd with the KV-cache shardings from ``serve.kvcache``.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.energy import adc_energy_pj
from repro.core.quant_state import QuantState
from repro.dist.sharding import _ACTIVE as _MESH_ACTIVE
from repro.telemetry.trace import program, span
from .kvcache import PagedKVCache, ZERO_PAGE, pool_pspecs

# legacy-signature shim state: ServeEngine(cfg, apply_fn, cache_fn, params)
# warns exactly once per process before compiling a temporary Runtime
_LEGACY_WARNED = False


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # (S,) int32 prompt tokens
    max_new_tokens: int = 16
    temperature: float = 0.0            # 0 = greedy
    # filled by the engine
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    submit_t: float = 0.0
    prefill_t: float = 0.0              # host time its prefill started
    first_token_t: float = 0.0
    finish_t: float = 0.0
    # energy metering (SAR comparator cycles attributed to this request)
    ad_ops: float = 0.0
    prefill_ad_ops: float = 0.0
    reused_tokens: int = 0              # prompt tokens served from the
    #                                     prefix cache (not re-converted)
    # paged-cache bookkeeping
    cache_len: int = 0                  # resident tokens (incl. padding)
    block_table: list = dataclasses.field(default_factory=list)

    @property
    def tokens(self) -> list:
        return list(self.prompt) + self.generated

    @property
    def ad_energy_pj(self) -> float:
        """SAR conversion energy this request cost (Eq. 6)."""
        return float(adc_energy_pj(self.ad_ops))

    @property
    def decode_ad_ops(self) -> float:
        return self.ad_ops - self.prefill_ad_ops


def _batch_axis(big_shape: tuple, small_shape: tuple) -> int:
    """The axis where a batch=1 cache leaf differs from the slot cache."""
    for i, (b, s) in enumerate(zip(big_shape, small_shape)):
        if b != s:
            return i
    raise ValueError(f"no batch axis between {big_shape} and {small_shape}")

def scatter_cache(big, small, slot: int):
    """Insert a batch=1 cache pytree into slot ``slot`` of the big cache.
    Scalar (dummy) leaves pass through — the paged engine's state trees
    carry placeholder 0s where the pooled seq leaves were stripped."""
    def one(b, s):
        if b.ndim == 0:
            return b
        ax = _batch_axis(b.shape, s.shape)
        idx = [0] * b.ndim
        idx[ax] = slot
        return jax.lax.dynamic_update_slice(b, s.astype(b.dtype), tuple(idx))
    return jax.tree.map(one, big, small)


def reset_rows(big, small, idle):
    """Every row of the big cache that the ``(max_batch,)`` bool mask
    ``idle`` marks takes the batch=1 cache ``small``: the same values
    ``scatter_cache(big, small, slot)`` leaves there, for all marked slots
    at once and in one shape whatever the mask holds.  Scalar (dummy)
    leaves pass through."""
    def one(b, s):
        if b.ndim == 0:
            return b
        ax = _batch_axis(b.shape, s.shape)
        mask = jnp.reshape(idle, [-1 if i == ax else 1
                                  for i in range(b.ndim)])
        return jnp.where(mask, s.astype(b.dtype), b)
    return jax.tree.map(one, big, small)


def sample_tokens(logits, temps, key):
    """Tokens for ``(B, vocab)`` logits at ``(B,)`` temperatures (0 =
    greedy: argmax), and the next key: ``key`` splits into the next key
    and the one that draws this call's sampled rows."""
    key, k = jax.random.split(key)
    greedy = jnp.argmax(logits, -1)
    sampled = jax.random.categorical(
        k, logits / jnp.maximum(temps[:, None], 1e-6))
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32), key


def _attn_only(cfg) -> bool:
    """Prefix reuse needs every mixer to be attention: K/V blocks are a
    pure function of the prefix, while recurrent (mamba/rwkv) prefixes
    would need chunk-aligned state snapshots whose scan boundaries change
    the float associativity (not bitwise vs the monolithic prefill)."""
    try:
        kinds = {cfg.layer_kind(i)[0] for i in range(cfg.period)}
    except (AttributeError, TypeError):
        return False
    return (kinds == {"attn"} and cfg.encoder_layers == 0
            and cfg.frontend == "none")


class ServeEngine:
    """Continuous-batching serving loop around a compiled ``Runtime``.

    ``ServeEngine(rt, max_batch=..., max_len=...)`` — the Runtime carries
    the execution context (backend / QuantState / plan / mesh); bake
    overrides in with ``repro.runtime.compile`` or ``rt.with_overrides``
    before constructing the engine.

    ``paged=True`` (default) runs the block-pool cache with prefix reuse;
    ``paged=False`` keeps the dense slot cache — the reference
    implementation the paged path is tested bitwise against.
    """

    def __init__(self, runtime, apply_fn=None, cache_fn=None, params=None, *,
                 max_batch: int = 8, max_len: int = 512,
                 extra_inputs: Optional[Callable[[int, int], dict]] = None,
                 quant_state: Optional[QuantState] = None,
                 plan=True,
                 paged: bool = True, block_size: int = 16,
                 prefix_reuse: bool = True,
                 num_blocks: Optional[int] = None,
                 rng_seed: int = 0):
        from repro.runtime import Runtime
        from repro.runtime import compile as rt_compile
        if isinstance(runtime, Runtime):
            if apply_fn is not None or cache_fn is not None \
                    or params is not None:
                raise TypeError("ServeEngine(runtime) takes no "
                                "apply_fn/cache_fn/params — the Runtime "
                                "owns them")
            if quant_state is not None or plan is not True:
                raise TypeError(
                    "quant_state/plan are Runtime state now; bake them in "
                    "with repro.runtime.compile(cfg, params, "
                    "quant_state=..., plan=...) or rt.with_overrides(...)")
            rt = runtime
        else:
            # legacy signature: ServeEngine(cfg, apply_fn, cache_fn, params,
            # quant_state=..., plan=...) — forwards into a temporary Runtime
            global _LEGACY_WARNED
            if not _LEGACY_WARNED:
                _LEGACY_WARNED = True
                warnings.warn(
                    "ServeEngine(cfg, apply_fn, cache_fn, params, ...) is "
                    "deprecated; compile a Runtime first — "
                    "rt = repro.runtime.compile(cfg, params, "
                    "quant_state=..., plan=...); ServeEngine(rt, ...)",
                    DeprecationWarning, stacklevel=2)
            rt = rt_compile(runtime, params, quant_state=quant_state,
                            plan=plan, fns=(None, apply_fn, cache_fn),
                            place=False)
        # the Runtime is the execution context: cfg/params/quant_state/plan
        # are mirrored as attributes for reporting (telemetry reads them)
        self.rt = rt
        self.cfg = cfg = rt.cfg
        self.apply_fn = rt.apply_fn
        self.params = rt.params
        self.quant_state = rt.quant_state
        self.plan = rt.plan
        cache_fn = rt.cache_fn
        self.max_batch = max_batch
        self.max_len = max_len
        # extra_inputs(batch, seq) -> dict of extra batch entries (modality
        # stubs: 'embeds' for vlm/audio frontends)
        self.extra_inputs = extra_inputs or (lambda b, s: {})
        self.paged = paged
        self.prefix_reuse = prefix_reuse and paged and _attn_only(cfg)
        if paged:
            block_size = min(block_size, max_len)
            self.kv = PagedKVCache(cache_fn, max_batch, max_len,
                                   block_size=block_size,
                                   num_blocks=num_blocks)
            self.block_size = self.kv.block_size
            self.state_cache = self.kv.make_state(max_batch)
            mesh = rt.mesh or _MESH_ACTIVE.get("mesh")
            if mesh is not None and self.kv.pools:
                self.kv.pools = jax.device_put(
                    self.kv.pools, pool_pspecs(mesh, cfg, self.kv.pools))
            self.cache = None
        else:
            self.kv = None
            self.block_size = 0
            self.cache = cache_fn(max_batch, max_len)
        self.slots: list[Optional[Request]] = [None] * max_batch
        self._zero_small = None
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.total_ad_ops = 0.0
        self.prefill_ad_ops = 0.0
        # work counted where it happens (rows are batch rows of the calls
        # into the Runtime: padded prefill rows, the decode program's batch
        # rows, live or idle, per decode step)
        self.counters = {"prefill_calls": 0, "prefill_rows": 0,
                         "prefill_real_rows": 0, "decode_steps": 0,
                         "decode_rows": 0, "decode_active_rows": 0,
                         "slot_resets": 0, "reset_calls": 0,
                         "installs": 0}
        self._uid = 0
        self._key = jax.random.PRNGKey(rng_seed)
        self._prefill_cache_fn = cache_fn
        self._scatter_jit = program("kv.slot_scatter", scatter_cache,
                                   static_argnames=())
        self._reset_jit = program("kv.slot_reset", reset_rows)
        self._sample_jit = program("sample", sample_tokens)

    # -- request lifecycle ---------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 16,
               temperature: float = 0.0) -> Request:
        r = Request(self._uid, np.asarray(prompt, np.int32),
                    max_new_tokens=max_new_tokens, temperature=temperature,
                    submit_t=time.perf_counter())
        self._uid += 1
        self.queue.append(r)
        return r

    # -- jit'd step functions: Runtime entry points ---------------------------
    # (the old _prefill_step/_prefill_cont_step/_decode_step collapsed into
    # rt.prefill / rt.prefill_cont / rt.decode — the Runtime installs the
    # execution context and returns each call's AdOpsReport)

    def _sample(self, logits: jax.Array, temps: np.ndarray) -> np.ndarray:
        with span("serve.sample"):
            toks, self._key = self._sample_jit(
                logits, np.asarray(temps, np.float32), self._key)
            return np.asarray(toks)

    # -- scheduler -------------------------------------------------------------

    def _bucket(self, n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _meter(self, r: Request, ops, prefill: bool = False) -> None:
        ops = float(ops)
        r.ad_ops += ops
        if prefill:
            r.prefill_ad_ops += ops
            self.prefill_ad_ops += ops
        self.total_ad_ops += ops

    def _finalize(self, r: Request) -> None:
        r.done = True
        r.finish_t = time.perf_counter()
        if r.first_token_t == 0.0:
            # prefill-only request: the "first token" event is prefill
            # completion (consistent TTFT even when max_new_tokens == 0)
            r.first_token_t = r.finish_t
        self.finished.append(r)
        if self.paged and r.block_table:
            self.kv.release(r.block_table)
            r.block_table = []

    def _zero_slot(self, idle: np.ndarray) -> None:
        """Zero the cache rows of every idle slot (``idle``: a
        ``(max_batch,)`` bool mask) in one program.  Idle rows still ride
        through every batched decode step (which garbage-writes their K/V
        at position 0 and evolves their recurrent state), and their content
        leaks into ACTIVE rows through batch-coupled ops — the dynamic
        max-abs quantization scales of the fake_quant/pallas datapaths and
        MoE capacity dispatch.  Keeping idle rows deterministically zero
        makes serving results independent of slot-reuse history, and the
        paged engine (whose freed pages revert to the zero page) bitwise-
        comparable to the dense one."""
        if self._zero_small is None:
            if self.paged:
                self._zero_small = self.kv.make_state(1)
            else:
                self._zero_small = jax.tree.map(
                    jnp.zeros_like, self._prefill_cache_fn(1, self.max_len))
        rows = int(idle.sum())
        self.counters["slot_resets"] += rows
        self.counters["reset_calls"] += 1
        with span("serve.zero_slot", rows=rows):
            if self.paged:
                self.state_cache = self._reset_jit(self.state_cache,
                                                   self._zero_small, idle)
            else:
                self.cache = self._reset_jit(self.cache, self._zero_small,
                                             idle)

    def _prefill(self, r: Request):
        """Prefill ``r`` (reusing cached prefix blocks when possible),
        install its cache (pool pages + state slot row comes later via
        ``_install``), sample the first token, meter ops/TTFT.
        Returns the batch=1 small cache (or None in paged mode where blocks
        are already written)."""
        r.prefill_t = time.perf_counter()
        plen = int(min(len(r.prompt), self.max_len - r.max_new_tokens))
        padded = self._bucket(plen)
        toks = np.zeros((1, padded), np.int32)
        toks[0, -plen:] = r.prompt[-plen:]   # left-pad into the bucket
        extra = self.extra_inputs(1, padded)
        n_extra = int(extra["embeds"].shape[1]) if "embeds" in extra else 0
        # frontend embeds prepend to the DECODER sequence for vlm/audio LMs;
        # for enc-dec they feed the encoder (cross-KV rows) instead
        encdec = self.cfg.encoder_layers > 0
        n_front = 0 if encdec else n_extra
        total_len = padded + n_front          # cache rows the prefill writes
        seq_valid = max(total_len, n_extra if encdec else 0)
        bs = self.block_size

        reuse_n, keys = 0, []
        if self.prefix_reuse and n_front == 0 and padded >= bs:
            # only FULL blocks are shareable; always leave >=1 suffix token
            # so the first-token logits are recomputed, never snapshotted
            cap = min((padded - 1) // bs, self.kv.pages_per_slot)
            keys = self.kv.prefix_keys(padded, toks[0], bs, cap)
            reuse_n, shared = self.kv.lookup_prefix(keys)

        rows = padded - reuse_n * bs
        real = min(plen, rows)
        self.counters["prefill_calls"] += 1
        self.counters["prefill_rows"] += rows
        self.counters["prefill_real_rows"] += real
        with span("serve.prefill", uid=r.uid, rows=rows, real=real,
                  reused=reuse_n * bs):
            if reuse_n:
                self.kv.incref(shared)
                r.block_table = list(shared)
                L = reuse_n * bs
                state1 = self.kv.make_state(1, fill_len=L)
                table1 = np.full((1, padded // bs), ZERO_PAGE, np.int32)
                table1[0, :reuse_n] = shared
                dense1 = self.kv.assemble(state1, table1)
                positions = np.arange(L, padded, dtype=np.int32)[None]
                (last_logits, small), rep = self.rt.prefill_cont(
                    jnp.asarray(toks[:, L:]), jnp.asarray(positions), dense1)
                r.reused_tokens = L
            else:
                (last_logits, small), rep = self.rt.prefill(
                    jnp.asarray(toks), extra, max_len=self.max_len)
            self._meter(r, rep.ad_ops, prefill=True)

            if self.paged and self.kv.specs:
                n_blk = min(-(-seq_valid // bs), self.kv.pages_per_slot)
                new_blks = np.arange(reuse_n, n_blk, dtype=np.int32)
                if len(new_blks):
                    new_pages = self.kv.alloc_pages(len(new_blks))
                    r.block_table.extend(new_pages)
                    self.kv.write_blocks(small, np.zeros(len(new_blks)),
                                         new_blks, new_pages)
                if keys:
                    self.kv.register_prefix(keys, r.block_table)
            r.cache_len = total_len

            nxt = self._sample(last_logits, np.array([r.temperature]))
            r.first_token_t = time.perf_counter()
            if r.max_new_tokens > 0:
                r.generated.append(int(nxt[0]))
            return small

    def _install(self, r: Request, small, slot: int) -> None:
        """Scatter the batch=1 prefill cache into decode residency."""
        self.counters["installs"] += 1
        with span("serve.install", uid=r.uid, slot=slot):
            if self.paged:
                self.state_cache = self._scatter_jit(
                    self.state_cache, self.kv.state_only(small), slot)
            else:
                self.cache = self._scatter_jit(self.cache, small, slot)

    def _admit(self):
        with span("serve.admit"):
            for slot in range(self.max_batch):
                if self.slots[slot] is not None:
                    continue
                while self.queue:
                    r = self.queue.pop(0)
                    small = self._prefill(r)
                    if len(r.generated) >= r.max_new_tokens:
                        # prefill-only (max_new_tokens <= 1): finished at
                        # admission — never occupies a decode slot.
                        # Reused / registered prefix pages stay cached for
                        # later requests (prefix warming).
                        self._finalize(r)
                        continue
                    self._install(r, small, slot)
                    self.slots[slot] = r
                    break

    def _decode_cache(self):
        """The dense cache view for this decode step (+ per-slot tables)."""
        if not self.paged:
            return self.cache
        with span("serve.decode_cache"):
            tables = np.full((self.max_batch, self.kv.pages_per_slot),
                             ZERO_PAGE, np.int32)
            for i, r in enumerate(self.slots):
                if r is None:
                    continue
                if self.kv.specs:
                    blk = self._write_blk(r)
                    while len(r.block_table) <= blk:
                        r.block_table.extend(self.kv.alloc_pages(1))
                    self.kv.ensure_private(r.block_table, blk)
                    tables[i, :len(r.block_table)] = r.block_table
            return self.kv.assemble(self.state_cache, tables)

    def _write_blk(self, r: Request) -> int:
        """Block index this decode step writes: the model's cache scatter
        clamps at the buffer end, so a run-over request keeps rewriting the
        last row of the last block (same semantics as the dense engine)."""
        return min(r.cache_len, self.max_len - 1) // self.block_size

    def _writeback(self, new_cache, active: list) -> None:
        """Persist what the decode step wrote: the one touched block per
        active slot back into its pool page; recurrent state wholesale."""
        if not self.paged:
            self.cache = new_cache
            return
        with span("serve.writeback"):
            self.state_cache = self.kv.state_only(new_cache)
            if not self.kv.specs:
                return
            slots = np.asarray(active, np.int32)
            blks = np.asarray([self._write_blk(self.slots[i])
                               for i in active], np.int32)
            pages = np.asarray([self.slots[i].block_table[b]
                                for i, b in zip(active, blks)], np.int32)
            self.kv.write_blocks(new_cache, slots, blks, pages,
                                 skip_static=True)

    def step(self) -> int:
        """Admit + one decode step for all active slots.  Returns #active."""
        with span("serve.step", queued=len(self.queue),
                  active=self.max_batch - self.slots.count(None)):
            self._admit()
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if not active:
                return 0
            toks = np.zeros((self.max_batch, 1), np.int32)
            temps = np.zeros((self.max_batch,), np.float32)
            for i in active:
                toks[i, 0] = self.slots[i].generated[-1]
                temps[i] = self.slots[i].temperature
            extra = self.extra_inputs(self.max_batch, 1)
            cache = self._decode_cache()
            c = self.counters
            with span("serve.decode", step=c["decode_steps"],
                      active=len(active)):
                (logits, new_cache), rep = self.rt.decode(jnp.asarray(toks),
                                                          cache, extra)
            c["decode_steps"] += 1
            c["decode_rows"] += toks.shape[0]
            c["decode_active_rows"] += len(active)
            self._writeback(new_cache, active)
            # batched MVMs convert all resident rows together; attribute
            # the step's conversions evenly across the slots that stepped
            # (total is conserved: sum over requests == sum of per-call
            # PimOut.ad_ops)
            share = float(rep.ad_ops) / len(active)
            self.total_ad_ops += float(rep.ad_ops)
            nxt = self._sample(logits, temps)
            for i in active:
                r = self.slots[i]
                r.ad_ops += share
                r.generated.append(int(nxt[i]))
                r.cache_len += 1
                if len(r.generated) >= r.max_new_tokens:
                    self._finalize(r)
                    self.slots[i] = None
            idle = np.array([r is None for r in self.slots])
            if idle.any():
                self._zero_slot(idle)
            return len(active)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    # -- metrics ---------------------------------------------------------------

    def stats(self) -> dict:
        if not self.finished:
            return {}
        ttft = [r.first_token_t - r.submit_t for r in self.finished]
        lat = [r.finish_t - r.submit_t for r in self.finished]
        toks = sum(len(r.generated) for r in self.finished)
        span = max(r.finish_t for r in self.finished) - \
            min(r.submit_t for r in self.finished)
        out = {"requests": len(self.finished),
               "mean_ttft_s": float(np.mean(ttft)),
               "mean_latency_s": float(np.mean(lat)),
               "decode_tokens": toks,
               "tokens_per_s": toks / max(span, 1e-9),
               # A/D-conversion metering (SAR cycles, Eq. 6)
               "total_ad_ops": self.total_ad_ops,
               "prefill_ad_ops": self.prefill_ad_ops,
               "decode_ad_ops": self.total_ad_ops - self.prefill_ad_ops,
               "mean_ad_ops_per_request": float(np.mean(
                   [r.ad_ops for r in self.finished])),
               "total_ad_energy_pj": float(adc_energy_pj(self.total_ad_ops)),
               "mean_ad_energy_pj_per_request": float(adc_energy_pj(np.mean(
                   [r.ad_ops for r in self.finished]))),
               "reused_prompt_tokens": sum(r.reused_tokens
                                           for r in self.finished)}
        out["counters"] = dict(self.counters)
        if self.paged:
            out["paged"] = {
                "block_size": self.block_size,
                "num_blocks": self.kv.num_blocks,
                "pages_in_use": int((self.kv.refcount > 0).sum()) - 1,
                "prefix_nodes": len(self.kv.prefix_index),
                **self.kv.stats}
        return out
