"""Unified decoder-only LM covering dense / GQA / MoE / hybrid(Mamba) /
SSM(RWKV6) / VLM / audio-backbone families.

The layer stack is scanned over *periods* (the repeating layer pattern —
jamba's is 8 layers, homogeneous archs' is 1), so HLO size and compile time
are O(period), not O(n_layers).  KV/SSM caches are pytrees stacked along the
period axis and threaded through the same scan.

Modes:
  train   — full-seq forward, no cache, returns (logits, aux_loss)
  prefill — full-seq forward, writes caches, returns (logits, cache)
  decode  — single token with cache, returns (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard
from repro.pim.backend import reemit_ad_ops, traced_ad_ops
from repro.pim.plan import PimPlan, subplan
from .attention import apply_attention, init_attention
from .layers import cdtype, embed, init_embed, init_linear, init_mlp, \
    init_rmsnorm, apply_mlp, pim_linear, rmsnorm
from .mamba import apply_mamba, init_mamba, d_inner
from .moe import apply_moe, init_moe
from .rwkv6 import apply_rwkv, init_rwkv, _dims as rwkv_dims


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, idx_in_period: int):
    mixer, ffn = cfg.layer_kind(idx_in_period)
    ks = jax.random.split(key, 4)
    p = {"norm1": init_rmsnorm(cfg.d_model), "norm2": init_rmsnorm(cfg.d_model)}
    if mixer == "attn":
        p["attn"] = init_attention(ks[0], cfg)
    elif mixer == "mamba":
        p["mamba"] = init_mamba(ks[0], cfg)
    else:
        p["rwkv"] = init_rwkv(ks[0], cfg)
    if ffn in ("mlp", "moe+mlp"):
        p["mlp"] = init_mlp(ks[1], cfg)
    if ffn in ("moe", "moe+mlp"):
        p["moe"] = init_moe(ks[2], cfg)
    return p


def init_lm(key, cfg: ModelConfig):
    kp, ke, kh, kf = jax.random.split(key, 4)
    params = {"embed": init_embed(ke, cfg),
              "final_norm": init_rmsnorm(cfg.d_model)}
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(kh, cfg.d_model, cfg.vocab_size, cfg)
    if cfg.frontend in ("patch", "frames"):
        name = "patch_proj" if cfg.frontend == "patch" else "frame_proj"
        params["frontend"] = {name: init_linear(kf, cfg.d_model, cfg.d_model, cfg)}

    def init_period(k):
        ks = jax.random.split(k, cfg.period)
        return {f"layer_{i}": _init_layer(ks[i], cfg, i)
                for i in range(cfg.period)}

    pkeys = jax.random.split(kp, cfg.n_periods)
    params["periods"] = jax.vmap(init_period)(pkeys)
    return params


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Empty per-layer caches stacked along the period axis."""
    def one_layer(i):
        mixer, _ = cfg.layer_kind(i)
        if mixer == "attn":
            kv = {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.hd), dtype),
                  "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.hd), dtype),
                  "len": jnp.zeros((batch,), jnp.int32)}
            return kv
        if mixer == "mamba":
            return {"h": jnp.zeros((batch, d_inner(cfg), cfg.ssm_d_state),
                                   jnp.float32),
                    "conv": jnp.zeros((batch, cfg.ssm_d_conv - 1, d_inner(cfg)),
                                      dtype)}
        h, hs = rwkv_dims(cfg)
        return {"s": jnp.zeros((batch, h, hs, hs), jnp.float32),
                "x_prev": jnp.zeros((batch, 1, cfg.d_model), dtype)}

    period_cache = {f"layer_{i}": one_layer(i) for i in range(cfg.period)}
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape),
        period_cache)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _apply_layer(p, x, cfg: ModelConfig, idx: int, positions,
                 cache: Optional[dict], aux, depth0: int = 0,
                 cont: bool = False, plan=None):
    mixer, ffn = cfg.layer_kind(idx)
    # per-layer name prefix for QuantState register lookup.  idx is the
    # position inside the repeating period (static under the period scan),
    # depth0 the absolute depth of the period's first layer: the scan path
    # names layers period-locally (periods share registers), the unrolled
    # path (scan_layers=False) names every depth distinctly.
    lname = f"layer_{depth0 + idx}"
    new_cache = None
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        o, new_cache = apply_attention(p["attn"], h, cfg, positions,
                                       cache=cache, cont=cont,
                                       prefix=f"{lname}/attn",
                                       plan=subplan(plan, "attn"))
    elif mixer == "mamba":
        o, new_cache = apply_mamba(p["mamba"], h, cfg, cache=cache,
                                   prefix=f"{lname}/mamba",
                                   plan=subplan(plan, "mamba"))
    else:
        o, new_cache = apply_rwkv(p["rwkv"], h, cfg, cache=cache,
                                  prefix=f"{lname}/rwkv",
                                  plan=subplan(plan, "rwkv"))
    if cfg.remat == "names":
        # checkpoint the mixer OUTPUT: backward reuses it instead of
        # re-running the flash kv scan (seq-sharded -> ~25MB/layer/device)
        from jax.ad_checkpoint import checkpoint_name
        o = checkpoint_name(o, "mixer_out")
    x = x + o
    x = shard(x, "batch", "seq", None)

    h = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if ffn == "mlp":
        x = x + apply_mlp(p["mlp"], h, cfg, prefix=f"{lname}/mlp",
                          plan=subplan(plan, "mlp"))
    elif ffn == "moe":
        mo, a = apply_moe(p["moe"], h, cfg)
        x, aux = x + mo, aux + a
    else:                                   # moe+mlp (arctic parallel)
        mo, a = apply_moe(p["moe"], h, cfg)
        x = x + mo + apply_mlp(p["mlp"], h, cfg, prefix=f"{lname}/mlp",
                               plan=subplan(plan, "mlp"))
        aux = aux + a
    x = shard(x, "batch", "seq", None)
    return x, new_cache, aux


def _embed_inputs(params, batch: dict, cfg: ModelConfig, plan=None):
    """tokens (+ optional frontend embeds as a sequence prefix) -> (B,S,D)."""
    x = embed(params["embed"], batch["tokens"])
    if cfg.frontend in ("patch", "frames") and "embeds" in batch:
        name = "patch_proj" if cfg.frontend == "patch" else "frame_proj"
        fe = pim_linear(params["frontend"][name],
                        batch["embeds"].astype(x.dtype), cfg,
                        name=f"frontend/{name}",
                        plan=subplan(subplan(plan, "frontend"), name))
        x = jnp.concatenate([fe, x], axis=1)
    return x


def apply_lm(params, batch: dict, cfg: ModelConfig, *,
             cache: Optional[dict] = None, mode: str = "train",
             plan: Optional[PimPlan] = None):
    """batch: {'tokens': (B,S) int32, optional 'embeds': (B,F,D),
    optional 'positions': (B,S)}.

    Modes: train | prefill | decode | prefill_cont.  ``prefill_cont``
    continues a prefill from a warm cache (prefix-reuse serving): the s
    tokens append at cache['len'] instead of position 0, so callers must
    supply absolute 'positions'.  Recurrent mixers (mamba/rwkv) continue
    from the cached state on the ordinary prefill path already; only
    attention needs the explicit flag.

    ``plan`` threads a :class:`~repro.pim.plan.PimPlan` (the crossbar
    programming cache) alongside the params: its stacked subtrees ride the
    period scan with them, so every ``pim_linear`` sees its own programmed
    ``LayerPlan``.  Returns (logits, new_cache, aux_loss)."""
    cont = mode == "prefill_cont"
    pl = plan.layers if isinstance(plan, PimPlan) else plan
    x = _embed_inputs(params, batch, cfg, plan=pl).astype(cdtype(cfg))
    b, s, _ = x.shape
    x = shard(x, "batch", "seq", None)
    if "positions" in batch:
        positions = batch["positions"]
    elif mode == "decode" and cache is not None:
        positions = _first_len(cache, cfg)[:, None]     # (B,1)
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def period_body(carry, inputs, depth0: int = 0):
        # the ops carry keeps per-layer A/D counts meterable through the
        # period scan: pim_linear emissions inside this body are tracers of
        # the scan trace, so they are drained into the carry here and
        # re-emitted to the enclosing traced_ad_ops tally after the scan
        x_, aux_, ops_ = carry
        pp, pc, ppl = inputs
        new_pc = {}
        with traced_ad_ops() as tally:
            for i in range(cfg.period):
                lp = pp[f"layer_{i}"]
                lc = pc[f"layer_{i}"] if pc is not None else None
                x_, nc, aux_ = _apply_layer(lp, x_, cfg, i, positions, lc,
                                            aux_, depth0=depth0, cont=cont,
                                            plan=subplan(ppl, f"layer_{i}"))
                new_pc[f"layer_{i}"] = nc
        return (x_, aux_, ops_ + tally.value), \
            (new_pc if pc is not None else 0)

    def wrap(fn):
        if cfg.remat not in ("block", "full", "names"):
            return fn
        if cfg.remat == "full":
            policy = None
        elif cfg.remat == "names":
            policy = jax.checkpoint_policies.save_only_these_names(
                "mixer_out")
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        return jax.checkpoint(fn, policy=policy)

    plan_periods = subplan(pl, "periods")
    if cfg.scan_layers:
        # the scan's own work (each layer's slice of the weights and the
        # cache, the stacked new cache) runs under this scope
        with jax.named_scope("layer_scan"):
            (x, aux, ops), new_cache = jax.lax.scan(
                wrap(period_body), (x, jnp.float32(0), jnp.float32(0)),
                (params["periods"], cache, plan_periods))
    else:
        new_caches = []
        aux = jnp.float32(0)
        ops = jnp.float32(0)
        for pi in range(cfg.n_periods):
            pp = jax.tree.map(lambda t: t[pi], params["periods"])
            pc = jax.tree.map(lambda t: t[pi], cache) if cache is not None else None
            ppl = jax.tree.map(lambda t: t[pi], plan_periods) \
                if plan_periods is not None else None
            body = wrap(functools.partial(period_body,
                                          depth0=pi * cfg.period))
            (x, aux, ops), nc = body((x, aux, ops), (pp, pc, ppl))
            new_caches.append(nc)
        new_cache = jax.tree.map(lambda *ts: jnp.stack(ts), *new_caches) \
            if cache is not None else 0
    reemit_ad_ops(ops)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if mode in ("decode", "prefill", "prefill_cont"):
        # serving paths only need next-token logits; skipping the full-seq
        # lm_head matmul keeps 32k-prefill logits O(B·V), not O(B·S·V)
        x = x[:, -1:]
    # unshard seq before the vocab matmul: seq and vocab both map to
    # 'model', and leaving both sharded makes GSPMD all-gather the (B,S,V)
    # gradient in backward (EXPERIMENTS.md §Perf iter 1)
    x = shard(x, "batch", None, None)
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = x.astype(jnp.float32) @ params["embed"]["tok"].astype(
                jnp.float32).T
        else:
            logits = pim_linear(params["lm_head"], x, cfg, name="lm_head",
                                plan=subplan(pl, "lm_head")
                                ).astype(jnp.float32)
    logits = shard(logits, "batch", None, "vocab")
    return logits, (new_cache if cache is not None else None), aux


def _first_len(cache, cfg: ModelConfig):
    """Current position from the first attention layer's cache.  Attention-
    free archs (rwkv6) don't use positions: return zeros."""
    for i in range(cfg.period):
        lc = cache[f"layer_{i}"]
        if isinstance(lc, dict) and "len" in lc:
            return lc["len"][0] if lc["len"].ndim > 1 else lc["len"]
    b = jax.tree_util.tree_leaves(cache)[0].shape[1]
    return jnp.zeros((b,), jnp.int32)
