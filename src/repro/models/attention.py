"""GQA attention: chunked (flash-style online-softmax) for train/prefill,
single-step cached decode, cross-attention for enc-dec.

Memory discipline: full (Sq, Sk) score matrices never materialize — the
kv dimension is processed by a lax.scan with running (max, sum, acc)
accumulators, so live bytes are O(chunk_q * chunk_k) per (batch, head).
Heads are tensor-parallel ('heads' -> 'model'); for long-context decode the
KV cache may instead be sequence-parallel (see serve/kvcache.py) and XLA
turns the softmax reductions into the flash-decode combine.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.trq import TRQParams
from repro.dist.sharding import shard
from repro.pim.plan import subplan
from repro.telemetry.trace import scoped
from .layers import apply_rope, init_linear, pim_linear

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, bias: Optional[bool] = None):
    bias = cfg.attn_bias if bias is None else bias
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], cfg.d_model, cfg.n_heads * hd, cfg, bias=bias),
        "wk": init_linear(ks[1], cfg.d_model, cfg.n_kv_heads * hd, cfg, bias=bias),
        "wv": init_linear(ks[2], cfg.d_model, cfg.n_kv_heads * hd, cfg, bias=bias),
        "wo": init_linear(ks[3], cfg.n_heads * hd, cfg.d_model, cfg, bias=bias),
    }


def _qkv(p, x, cfg: ModelConfig, positions, trq, rope: bool = True,
         prefix: str = "attn", plan=None):
    b, s, _ = x.shape
    hd = cfg.hd
    q = pim_linear(p["wq"], x, cfg, trq, name=f"{prefix}/wq",
                   plan=subplan(plan, "wq")).reshape(b, s, cfg.n_heads, hd)
    k = pim_linear(p["wk"], x, cfg, trq, name=f"{prefix}/wk",
                   plan=subplan(plan, "wk")).reshape(b, s, cfg.n_kv_heads, hd)
    v = pim_linear(p["wv"], x, cfg, trq, name=f"{prefix}/wv",
                   plan=subplan(plan, "wv")).reshape(b, s, cfg.n_kv_heads, hd)
    if rope:
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)
    return q, k, v


def _group_q(q: jax.Array, n_kv: int) -> jax.Array:
    """(B,S,H,hd) -> (B,S,KV,G,hd) for GQA."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, hd)


@scoped("attention.core")
def full_attention(q, k, v, causal: bool, q_off=0) -> jax.Array:
    """Reference path for short sequences. q: (B,Sq,KV,G,hd), k/v: (B,Sk,KV,hd).

    ``q_off`` shifts the causal mask by the absolute position of q row 0 —
    a python int, or a (B,) array for per-row offsets (continued prefill
    against a cache holding ``q_off`` earlier tokens)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        off = jnp.asarray(q_off, jnp.int32).reshape(-1, 1, 1)     # (B|1,1,1)
        mask = (jnp.arange(sq)[None, :, None] + off) >= \
            jnp.arange(sk)[None, None, :]                         # (B|1,Sq,Sk)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", a, v.astype(jnp.float32))
    return o.astype(q.dtype)


@scoped("attention.core")
def chunked_attention(q, k, v, causal: bool, chunk_q: int, chunk_k: int,
                      context_parallel: bool = False) -> jax.Array:
    """Flash-style online-softmax attention, q-chunks BATCHED.

    q: (B,S,KV,G,hd); k/v: (B,S,KV,hd).  S must divide by both chunks
    (callers pad).  All q chunks ride through the kv scan together as a
    batch axis — under ``context_parallel`` that axis is sharded over
    'model' (each device owns S/tp query rows; k/v replicate), which keeps
    attention collective-free regardless of head counts (EXPERIMENTS.md
    §Perf iter 2: llama's 24 q / 8 kv heads don't divide a 16-way axis).
    Causal masking is by absolute position; fully-masked kv chunks still
    run — the skip is a further §Perf candidate."""
    b, sq, kv, g, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5
    nq, nk = sq // chunk_q, sk // chunk_k

    qc = q.reshape(b, nq, chunk_q, kv, g, hd).astype(jnp.float32) * scale
    if context_parallel:
        qc = shard(qc, "batch", "seq", None, None, None, None)
    kc = jnp.moveaxis(k.reshape(b, nk, chunk_k, kv, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nk, chunk_k, kv, hd), 1, 0)

    def kv_block(carry, args2):
        m, l, acc = carry                     # (b, nq, kv, g, cq[, hd])
        kj, vj, j = args2                     # (b, ck, kv, hd)
        sc = jnp.einsum("bnqkgd,bskd->bnkgqs", qc, kj.astype(jnp.float32))
        if causal:
            qpos = (jnp.arange(nq)[:, None] * chunk_q
                    + jnp.arange(chunk_q)[None, :])         # (nq, cq)
            kpos = j * chunk_k + jnp.arange(chunk_k)         # (ck,)
            mask = qpos[..., None] >= kpos[None, None, :]    # (nq, cq, ck)
            sc = jnp.where(mask[None, :, None, None], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bnkgqs,bskd->bnkgqd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, nq, kv, g, chunk_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nq, kv, g, chunk_q), jnp.float32)
    a0 = jnp.zeros((b, nq, kv, g, chunk_q, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        kv_block, (m0, l0, a0), (kc, vc, jnp.arange(nk)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]   # (b, nq, kv, g, cq, hd)
    out = jnp.moveaxis(o, 4, 2).reshape(b, sq, kv, g, hd)
    return out.astype(q.dtype)


@scoped("attention.core")
def decode_attention(q, k_cache, v_cache, cache_len) -> jax.Array:
    """One-token attention against a cache.

    q: (B,1,KV,G,hd); caches: (B,S,KV,hd); cache_len: (B,) valid entries
    (the new token's k/v must already be written).  Softmax reductions over
    the cache S dim work under any cache sharding (XLA inserts the
    flash-decode style combine when S is sequence-parallel).

    The cache is dotted in ITS OWN dtype with f32 accumulation
    (preferred_element_type): upcasting the (B,S,KV,hd) cache to f32 was
    the dominant decode temp (§Perf iter 5 — 2x cache-sized f32 copies per
    layer)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs",
                   (q.astype(jnp.float32) * scale).astype(k_cache.dtype),
                   k_cache, preferred_element_type=jnp.float32)
    mask = jnp.arange(k_cache.shape[1])[None, :] < cache_len[:, None]
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", a.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def apply_attention(p, x, cfg: ModelConfig, positions, *, causal=True,
                    cache: Optional[dict] = None, trq: Optional[TRQParams] = None,
                    rope: bool = True, cont: bool = False,
                    prefix: str = "attn", plan=None):
    """Returns (out, new_cache).  cache=None -> stateless (training).

    Prefill (x seq > 1 with cache) writes k/v at [0, S); decode (seq == 1)
    scatters at position cache['len'].  ``cont`` (continued prefill, the
    prefix-reuse path) instead appends the s new tokens at cache['len'] and
    attends over the WHOLE cache buffer — callers pass a buffer trimmed to
    len+s so the softmax reduction has exactly the same extent as the
    monolithic prefill it replaces (bitwise parity; see serve/engine.py)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions, trq, rope=rope, prefix=prefix,
                   plan=plan)
    qg = _group_q(q, cfg.n_kv_heads)
    cp = cfg.parallelism == "fsdp_cp"
    if cp:
        # context-parallel: q rows seq-sharded, k/v replicated (one AG per
        # layer, prefetchable); no head-count divisibility constraints
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
    else:
        qg = shard(qg, "batch", None, "kv", None, None)
    new_cache = None
    ck = min(s, cfg.attn_chunk_k)

    if cont and cache is not None and s > 1:
        idx = cache["len"]                     # (B,) tokens already resident
        k_cache = _scatter_time(cache["k"], k, idx)
        v_cache = _scatter_time(cache["v"], v, idx)
        new_cache = {"k": k_cache, "v": v_cache, "len": idx + s}
        o = full_attention(qg, k_cache, v_cache, causal, q_off=idx)
    elif cache is None:
        if s > cfg.attn_chunk_q and s % cfg.attn_chunk_q == 0 and \
                s % ck == 0:
            o = chunked_attention(qg, k, v, causal, cfg.attn_chunk_q,
                                  ck, context_parallel=cp)
        else:
            o = full_attention(qg, k, v, causal)
    elif s == 1:
        idx = cache["len"]                     # (B,)
        k_cache = _scatter_time(cache["k"], k, idx)
        v_cache = _scatter_time(cache["v"], v, idx)
        new_cache = {"k": k_cache, "v": v_cache, "len": idx + 1}
        o = decode_attention(qg, k_cache, v_cache, idx + 1)
    else:
        # prefill into the cache
        with jax.named_scope("attention.cache_write"):
            pad = cache["k"].shape[1] - s
            k_full = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_full = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            new_cache = {"k": k_full.astype(cache["k"].dtype),
                         "v": v_full.astype(cache["v"].dtype),
                         "len": jnp.full((b,), s, jnp.int32)}
        if s > cfg.attn_chunk_q and s % cfg.attn_chunk_q == 0 and \
                s % ck == 0:
            o = chunked_attention(qg, k, v, causal, cfg.attn_chunk_q,
                                  ck, context_parallel=cp)
        else:
            o = full_attention(qg, k, v, causal)

    o = o.reshape(b, s, cfg.n_heads * cfg.hd)
    o = shard(o, "batch", "seq", None) if cp else \
        shard(o, "batch", None, "heads")
    return pim_linear(p["wo"], o, cfg, trq, name=f"{prefix}/wo",
                      plan=subplan(plan, "wo")), new_cache


@scoped("attention.cache_write")
def _scatter_time(cache: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Write new (B,1,KV,hd) at per-batch time index idx into (B,S,KV,hd).

    vmapped dynamic_update_slice (not a one-hot where): XLA aliases the
    update in place inside the layer scan — the where-based rewrite forced
    whole-cache copies every step (§Perf iter 5, decode temp 5x cache)."""
    def one(c, n, i):
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (i, 0, 0))
    return jax.vmap(one)(cache, new, idx)


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: ModelConfig):
    return init_attention(key, cfg, bias=cfg.attn_bias)


def apply_cross_attention(p, x, enc_kv: dict, cfg: ModelConfig,
                          trq: Optional[TRQParams] = None,
                          prefix: str = "xattn", plan=None):
    """x: (B,Sd,D); enc_kv: {'k','v'} (B,Se,KV,hd) precomputed from encoder."""
    b, s, _ = x.shape
    hd = cfg.hd
    q = pim_linear(p["wq"], x, cfg, trq, name=f"{prefix}/wq",
                   plan=subplan(plan, "wq")).reshape(b, s, cfg.n_heads, hd)
    qg = _group_q(q, cfg.n_kv_heads)
    se = enc_kv["k"].shape[1]
    if s % cfg.attn_chunk_q == 0 and se % cfg.attn_chunk_k == 0 and \
            (s > cfg.attn_chunk_q or se > cfg.attn_chunk_k):
        o = chunked_attention(qg, enc_kv["k"], enc_kv["v"], False,
                              cfg.attn_chunk_q, cfg.attn_chunk_k)
    else:
        o = full_attention(qg, enc_kv["k"], enc_kv["v"], causal=False)
    o = o.reshape(b, s, cfg.n_heads * hd)
    return pim_linear(p["wo"], o, cfg, trq, name=f"{prefix}/wo",
                      plan=subplan(plan, "wo"))


def encoder_kv(p, enc_out: jax.Array, cfg: ModelConfig,
               trq: Optional[TRQParams] = None,
               prefix: str = "xattn", plan=None) -> dict:
    b, s, _ = enc_out.shape
    hd = cfg.hd
    k = pim_linear(p["wk"], enc_out, cfg, trq, name=f"{prefix}/wk",
                   plan=subplan(plan, "wk")).reshape(b, s, cfg.n_kv_heads, hd)
    v = pim_linear(p["wv"], enc_out, cfg, trq, name=f"{prefix}/wv",
                   plan=subplan(plan, "wv")).reshape(b, s, cfg.n_kv_heads, hd)
    return {"k": k, "v": v}
