"""KV/SSM cache layout for serving: sharding policy + the paged block pool.

Sharding policy (``kv_pspec`` / ``cache_pspecs`` / ``pool_pspecs``)
-------------------------------------------------------------------
Standard decode (batch >= data axis): batch -> ('pod','data'), and the KV
head dim -> 'model' when divisible, else the head_dim -> 'model' (splitting
head_dim makes the score/value einsums partial-sum over 'model' — two small
all-reduces per layer, but a full 16-way cache split even for kv_heads < 16).

Long-context decode (batch=1): the cache *sequence* dim -> 'data'
(sequence-parallel cache); XLA lowers the softmax reductions to the
flash-decode combine across 'data'.

Paged block pool (``PagedKVCache``)
-----------------------------------
vLLM-style paging for the serve engine.  Every cache leaf that scales with
``max_len`` (attention K/V, enc-dec self- and cross-KV) is backed by a pool
shaped ``(num_blocks, *block)`` where a block holds ``block_size`` tokens of
that leaf across all layers; requests own per-slot block tables of page ids.
Recurrent leaves (mamba ``h``/``conv``, rwkv ``s``/``x_prev``) and the
``len`` counters are O(1) per request and stay slot-resident — paging them
as 1-token pages would add copies for zero benefit.

Page 0 is a permanently-zero page: block tables are padded with it, so the
gather materializes exact zeros for unallocated tail pages (this is what
makes paged decode bitwise-identical to the dense slot engine — see
tests/test_paged.py).  Gathers go through ``jnp.take`` on the page-id table;
block extraction/write-back uses ``lax.dynamic_slice`` /
``dynamic_update_slice`` so XLA can alias the pool update in place.

Prefix reuse: full prompt blocks are hash-consed — the index maps
``(bucket, sha1(padded_tokens[:k*block_size]))`` to the pages holding that
prefix's K/V, shared copy-on-write across requests (refcounted; LRU-evicted
when the pool runs dry).  A shared system prompt is therefore prefilled —
and A/D-converted — once.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.telemetry.trace import program


def _axis_size(mesh: Mesh, axes) -> int:
    axes = axes if isinstance(axes, tuple) else (axes,)
    return int(np.prod([mesh.shape[a] for a in axes if a in mesh.axis_names]))


def kv_pspec(mesh: Mesh, cfg: ModelConfig, batch: int, stacked: bool = True):
    """PartitionSpec for a (B, S, KV, hd) cache leaf (+ leading layer-stack
    dim when ``stacked``)."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model = "model" if "model" in mesh.axis_names else None
    n_dp = _axis_size(mesh, dp)
    n_m = mesh.shape[model] if model else 1

    batch_ok = batch % n_dp == 0 if n_dp > 1 else True
    if batch_ok and batch >= n_dp:
        b_ax, s_ax = dp, None
    else:
        b_ax, s_ax = None, ("data" if "data" in mesh.axis_names else None)

    if model and cfg.n_kv_heads % n_m == 0:
        kv_ax, hd_ax = model, None
    elif model and cfg.hd % n_m == 0:
        kv_ax, hd_ax = None, model
    else:
        kv_ax, hd_ax = None, None
    spec = (b_ax, s_ax, kv_ax, hd_ax)
    return P(*((None,) + spec)) if stacked else P(*spec)


def cache_pspecs(mesh: Mesh, cfg: ModelConfig, cache, batch: int):
    """Pytree of NamedShardings matching an init_cache(...) pytree."""
    kv = kv_pspec(mesh, cfg, batch)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b_ax = dp if (batch % max(_axis_size(mesh, dp), 1) == 0 and batch >= _axis_size(mesh, dp)) else None
    model = "model" if "model" in mesh.axis_names else None

    def visit(path, leaf):
        names = [str(getattr(k, "key", k)) for k in path]
        leaf_name = names[-1]
        nd = leaf.ndim
        if leaf_name in ("k", "v"):
            return NamedSharding(mesh, kv if nd == 5 else
                                 P(*kv[1:]) if nd == 4 else P())
        if leaf_name == "len":
            return NamedSharding(mesh, P(None, b_ax) if nd == 2 else P(b_ax))
        if leaf_name == "len0":
            return NamedSharding(mesh, P(b_ax))
        if leaf_name == "h":          # mamba state (P?, B, di, ds)
            spec = [None] * nd
            spec[-3] = b_ax
            spec[-2] = model if True else None
            return NamedSharding(mesh, P(*spec))
        if leaf_name == "conv":       # (P?, B, dc-1, di)
            spec = [None] * nd
            spec[-3] = b_ax
            spec[-1] = model
            return NamedSharding(mesh, P(*spec))
        if leaf_name == "s":          # rwkv state (P?, B, H, hs, hs)
            spec = [None] * nd
            spec[-4] = b_ax
            spec[-3] = model
            return NamedSharding(mesh, P(*spec))
        if leaf_name == "x_prev":
            spec = [None] * nd
            spec[-3] = b_ax
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(visit, cache)


def pool_pspecs(mesh: Mesh, cfg: ModelConfig, pools: dict):
    """NamedShardings for ``PagedKVCache.pools`` leaves under ``use_mesh``.

    The page axis stays replicated — allocation/eviction is host-driven and
    pages must be addressable from every data row — while the head dims
    split over 'model' exactly like the dense ``kv_pspec`` policy, so a
    paged cache costs the same per-device HBM as the dense one."""
    model = "model" if "model" in mesh.axis_names else None
    n_m = mesh.shape[model] if model else 1
    if model and cfg.n_kv_heads % n_m == 0:
        kv_ax, hd_ax = model, None
    elif model and cfg.hd % n_m == 0:
        kv_ax, hd_ax = None, model
    else:
        kv_ax, hd_ax = None, None

    out = {}
    for key, leaf in pools.items():
        spec = [None] * leaf.ndim
        if key.split("/")[-1] in ("k", "v") and leaf.ndim >= 4:
            # pool block layout is (nb, P?, bs, KV, hd) for k/v leaves
            spec[-2], spec[-1] = kv_ax, hd_ax
        out[key] = NamedSharding(mesh, P(*spec))
    return out


# ---------------------------------------------------------------------------
# paged block pool
# ---------------------------------------------------------------------------

ZERO_PAGE = 0               # permanently zero; backs unallocated table slots


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Geometry of one paged cache leaf."""
    key: str
    shape: tuple                # at (max_batch, max_len)
    dtype: object
    batch_ax: int
    seq_ax: int
    static: bool                # written at prefill only (enc-dec cross-KV)


@dataclasses.dataclass
class _PrefixNode:
    pages: tuple                # page ids for blocks [0, k)
    bucket: int


class PagedKVCache:
    """Block pool + page bookkeeping for one (arch, max_batch, max_len).

    Array-side operations (assemble/write/copy/zero) are jitted closures
    over the leaf geometry; python-side bookkeeping (free list, refcounts,
    prefix index, LRU eviction) is host state.  The engine owns request
    block tables; this class owns pages.
    """

    def __init__(self, cache_fn: Callable, max_batch: int, max_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None):
        if max_len % block_size:
            raise ValueError(f"max_len={max_len} must divide by "
                             f"block_size={block_size}")
        if block_size & (block_size - 1):
            # prefill buckets are powers of two, so a power-of-two block
            # size guarantees every reuse-eligible bucket is block-aligned
            # (the continued-prefill scatter would silently clamp otherwise)
            raise ValueError(f"block_size={block_size} must be a power of 2")
        self.block_size = block_size
        self.max_batch = max_batch
        self.max_len = max_len
        self.pages_per_slot = max_len // block_size
        self._cache_fn = cache_fn

        # --- leaf classification by shape probing (no allocation) ---------
        probe = jax.eval_shape(lambda: cache_fn(1, block_size))
        probe_s = jax.eval_shape(lambda: cache_fn(1, 2 * block_size))
        probe_b = jax.eval_shape(lambda: cache_fn(2, block_size))
        self.skeleton = jax.eval_shape(lambda: cache_fn(max_batch, max_len))

        specs: dict[str, LeafSpec] = {}

        def classify(path, a, b_seq, b_bat, full):
            key = _path_str(path)
            seq_ax = next((i for i, (x, y) in
                           enumerate(zip(a.shape, b_seq.shape)) if x != y),
                          None)
            bat_ax = next((i for i, (x, y) in
                           enumerate(zip(a.shape, b_bat.shape)) if x != y),
                          None)
            if seq_ax is None:
                return None                      # slot-resident state leaf
            if bat_ax is None or bat_ax >= seq_ax:
                raise ValueError(f"unsupported cache layout for {key}: "
                                 f"batch axis {bat_ax}, seq axis {seq_ax}")
            specs[key] = LeafSpec(key=key, shape=full.shape,
                                  dtype=full.dtype, batch_ax=bat_ax,
                                  seq_ax=seq_ax, static="xkv" in key)
            return None

        jax.tree_util.tree_map_with_path(classify, probe, probe_s, probe_b,
                                         self.skeleton)
        self.specs = specs

        if num_blocks is None:
            # residency for every slot + prefix-cache headroom + zero page
            num_blocks = 1 + (max_batch + 2) * self.pages_per_slot
        self.num_blocks = num_blocks

        # --- pools (page 0 = permanent zeros) -----------------------------
        self.pools = {k: jnp.zeros((num_blocks,) + self._block_shape(s),
                                   s.dtype) for k, s in specs.items()}
        self.refcount = np.zeros((num_blocks,), np.int64)
        self.refcount[ZERO_PAGE] = 1            # never allocatable
        self.free: list[int] = list(range(1, num_blocks))
        self.prefix_index: "collections.OrderedDict[tuple, _PrefixNode]" = \
            collections.OrderedDict()
        self.stats = {"reused_blocks": 0, "reused_tokens": 0,
                      "prefix_evictions": 0, "cow_copies": 0,
                      "peak_pages_in_use": 0}

        # --- jitted array ops --------------------------------------------
        self._assemble_jit = program("kv.assemble", self._assemble)
        self._write_jit = program("kv.write_blocks", self._write_blocks,
                                  static_argnames=("skip_static",))
        self._zero_jit = program("kv.zero_pages", self._zero_pages)
        self._copy_jit = program("kv.copy_page", self._copy_page)

    # -- geometry -------------------------------------------------------------

    def _block_shape(self, spec: LeafSpec) -> tuple:
        shp = [d for i, d in enumerate(spec.shape) if i != spec.batch_ax]
        shp[spec.seq_ax - 1] = self.block_size      # batch_ax < seq_ax
        return tuple(shp)

    # -- jitted pool <-> dense transforms --------------------------------------

    def _gather_leaf(self, spec: LeafSpec, pool, tables):
        """pool (nb, *block) gathered by tables (B, n_pages) into the dense
        (…, B, S=n_pages*bs, …) layout the model's decode step expects."""
        g = jnp.take(pool, tables, axis=0)          # (B, np, *block)
        bi, si = spec.batch_ax, spec.seq_ax
        perm, out_shape = [], []
        for d in range(len(spec.shape)):
            pos_in_block = d if d < bi else d - 1   # block dims skip batch
            if d == bi:
                perm.append(0)
                out_shape.append(tables.shape[0])
            elif d == si:
                perm.extend([1, 2 + pos_in_block])
                out_shape.append(tables.shape[1] * self.block_size)
            else:
                perm.append(2 + pos_in_block)
                out_shape.append(spec.shape[d])
        return jnp.transpose(g, perm).reshape(out_shape)

    def _extract_block(self, spec: LeafSpec, leaf, slot, blk):
        """One (slot, block) window of a dense leaf -> (*block,) data."""
        starts = [0] * leaf.ndim
        starts[spec.batch_ax] = slot
        starts[spec.seq_ax] = blk * self.block_size
        sizes = list(leaf.shape)
        sizes[spec.batch_ax] = 1
        sizes[spec.seq_ax] = self.block_size
        out = jax.lax.dynamic_slice(leaf, starts, sizes)
        return jnp.squeeze(out, axis=spec.batch_ax)

    def _assemble(self, pools, state, tables):
        """Materialize the dense cache pytree the decode step consumes:
        seq leaves gathered through the block tables, state leaves passed
        through.  ``state`` shares the full cache treedef with dummy int
        leaves at seq positions (see ``state_only``)."""
        def visit(path, skel, st):
            key = _path_str(path)
            if key in self.specs:
                return self._gather_leaf(self.specs[key], pools[key], tables)
            return st
        return jax.tree_util.tree_map_with_path(visit, self.skeleton, state)

    def _write_blocks(self, pools, cache, slots, blks, pages, *,
                      skip_static: bool):
        """Scatter (slot, blk) windows of a dense cache into pool pages.
        slots/blks/pages: (A,) arrays — unique pages (``.at[].set``)."""
        out = dict(pools)
        for key, spec in self.specs.items():
            if skip_static and spec.static:
                continue
            leaf = self._cache_leaf(cache, key)
            data = jax.vmap(lambda s, b, l=leaf, sp=spec:
                            self._extract_block(sp, l, s, b))(slots, blks)
            out[key] = out[key].at[pages].set(data.astype(out[key].dtype))
        return out

    def _zero_pages(self, pools, pages):
        return {k: p.at[pages].set(jnp.zeros((), p.dtype))
                for k, p in pools.items()}

    def _copy_page(self, pools, src, dst):
        return {k: jax.lax.dynamic_update_slice(
                    p, jax.lax.dynamic_slice(
                        p, (src,) + (0,) * (p.ndim - 1),
                        (1,) + p.shape[1:]),
                    (dst,) + (0,) * (p.ndim - 1))
                for k, p in pools.items()}

    @staticmethod
    def _cache_leaf(cache, key: str):
        node = cache
        for part in key.split("/"):
            node = node[part]
        return node

    # -- engine-facing array API ----------------------------------------------

    def make_state(self, batch: int, fill_len: Optional[int] = None):
        """Concrete state pytree (full cache treedef, dummy 0 at seq leaves).
        ``fill_len`` seeds the attention 'len' counters — the continued-
        prefill entry state for a reused prefix of that many tokens."""
        skel = jax.eval_shape(lambda: self._cache_fn(batch, self.max_len))

        def visit(path, leaf):
            key = _path_str(path)
            if key in self.specs:
                return jnp.int32(0)
            if fill_len is not None and key.split("/")[-1] == "len":
                return jnp.full(leaf.shape, fill_len, leaf.dtype)
            return jnp.zeros(leaf.shape, leaf.dtype)
        return jax.tree_util.tree_map_with_path(visit, skel)

    def state_only(self, cache):
        """Strip seq leaves (replaced by dummy 0s) — the slot-resident part."""
        def visit(path, leaf):
            return jnp.int32(0) if _path_str(path) in self.specs else leaf
        return jax.tree_util.tree_map_with_path(visit, cache)

    def assemble(self, state, tables: np.ndarray):
        """Dense cache for a decode/continued-prefill step.  ``tables``
        (B, n_pages) int32, padded with ZERO_PAGE."""
        return self._assemble_jit(self.pools, state,
                                  jnp.asarray(tables, jnp.int32))

    def write_blocks(self, cache, slots, blks, pages,
                     skip_static: bool = False) -> None:
        if not len(pages) or not self.specs:
            return
        self.pools = self._write_jit(
            self.pools, cache, jnp.asarray(slots, jnp.int32),
            jnp.asarray(blks, jnp.int32), jnp.asarray(pages, jnp.int32),
            skip_static=skip_static)
        self._track_peak()

    # -- page bookkeeping -------------------------------------------------------

    def _track_peak(self):
        in_use = int((self.refcount > 0).sum()) - 1
        self.stats["peak_pages_in_use"] = max(
            self.stats["peak_pages_in_use"], in_use)

    def alloc_pages(self, n: int) -> list:
        """Allocate ``n`` zeroed pages, LRU-evicting cached prefixes when
        the free list runs dry."""
        while len(self.free) < n:
            if not self._evict_one():
                raise RuntimeError(
                    f"KV block pool exhausted ({self.num_blocks} pages, "
                    f"{n - len(self.free)} short) — raise num_blocks or "
                    f"lower max_batch/max_len")
        pages = [self.free.pop() for _ in range(n)]
        for p in pages:
            self.refcount[p] += 1
        if pages and self.pools:
            self.pools = self._zero_jit(self.pools,
                                        jnp.asarray(pages, jnp.int32))
        self._track_peak()
        return pages

    def incref(self, pages) -> None:
        for p in pages:
            self.refcount[p] += 1

    def release(self, pages) -> None:
        for p in pages:
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self.free.append(p)
            assert self.refcount[p] >= 0, f"page {p} over-released"

    def _evict_one(self) -> bool:
        """Drop the least-recently-used prefix node; True if one was freed."""
        for key in list(self.prefix_index):
            node = self.prefix_index[key]
            del self.prefix_index[key]
            self.release(node.pages)
            self.stats["prefix_evictions"] += 1
            return True
        return False

    # -- prefix hash-consing ----------------------------------------------------

    @staticmethod
    def prefix_keys(bucket: int, padded_tokens: np.ndarray,
                    block_size: int, cap: int) -> list:
        """Hash keys for the first ``cap`` full blocks of a padded prompt.
        The hash covers ALL tokens up to the block end (prefix semantics —
        RoPE positions and causal context are part of the identity), and the
        bucket keys the positional frame the blocks were computed in."""
        return [(bucket, hashlib.sha1(
                    padded_tokens[:k * block_size].tobytes()).digest())
                for k in range(1, cap + 1)]

    def lookup_prefix(self, keys: list):
        """Longest cached prefix among ``keys`` -> (n_blocks, pages)."""
        for k in range(len(keys), 0, -1):
            node = self.prefix_index.get(keys[k - 1])
            if node is not None:
                self.prefix_index.move_to_end(keys[k - 1])   # MRU
                self.stats["reused_blocks"] += k
                self.stats["reused_tokens"] += k * self.block_size
                return k, list(node.pages)
        return 0, []

    def register_prefix(self, keys: list, table: list) -> None:
        """Hash-cons the full prompt blocks of a freshly admitted request
        (each node holds a refcount on all its pages)."""
        for k, key in enumerate(keys, start=1):
            if key in self.prefix_index:
                self.prefix_index.move_to_end(key)
                continue
            pages = tuple(table[:k])
            self.incref(pages)
            self.prefix_index[key] = _PrefixNode(pages=pages, bucket=key[0])

    def ensure_private(self, table: list, blk: int) -> int:
        """Copy-on-write guard: the page a decode step writes must not be
        shared.  Returns the (possibly fresh) page id."""
        page = table[blk]
        # node-held pages always carry a second ref (register_prefix), so
        # the refcount alone detects sharing by requests AND by the index
        if self.refcount[page] <= 1:
            return page
        [fresh] = self.alloc_pages(1)
        self.pools = self._copy_jit(self.pools, jnp.int32(page),
                                    jnp.int32(fresh))
        self.release([page])
        table[blk] = fresh
        self.stats["cow_copies"] += 1
        return fresh
