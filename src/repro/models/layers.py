"""Shared layers: norms, embeddings, RoPE, PimLinear, MLP."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TRQConfig
from repro.core.quant_state import active_quant_state
from repro.core.trq import TRQParams
from repro.pim.backend import active_backend, get_backend, record_ad_ops
from repro.pim.plan import LayerPlan, run_prepared, subplan
from repro.telemetry.trace import scoped
from repro.dist.sharding import shard


def cdtype(cfg: ModelConfig):
    """Compute dtype (activations)."""
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def pdtype(cfg: ModelConfig):
    """Parameter storage dtype (f32 master weights for training; serving
    configs flip to bf16)."""
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# PimLinear — the paper's technique as a first-class layer (DESIGN.md §4)
# ---------------------------------------------------------------------------

def trq_params_from_cfg(t: TRQConfig) -> TRQParams:
    return TRQParams(delta_r1=jnp.float32(t.delta_r1), bias=jnp.float32(t.bias),
                     n_r1=t.n_r1, n_r2=t.n_r2, m=t.m, signed=t.signed)


def init_linear(key, d_in: int, d_out: int, cfg: ModelConfig,
                bias: bool = False, scale: Optional[float] = None):
    std = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * std
               ).astype(pdtype(cfg))}
    if bias:
        p["b"] = jnp.zeros((d_out,), pdtype(cfg))
    return p


def pim_linear(p: dict, x: jax.Array, cfg: ModelConfig,
               trq: Optional[TRQParams] = None,
               name: Optional[str] = None,
               plan: Optional[LayerPlan] = None) -> jax.Array:
    """x @ w on the selected PIM execution backend.

    The datapath is a name in the ``repro.pim.backend`` registry (exact |
    fake_quant | pallas | bit_exact | anything registered later), chosen by
    an ambient ``use_backend(...)`` context, else ``cfg.pim_backend``.

    Per-layer SAR registers resolve in priority order: the explicit ``trq``
    argument, then the active :class:`~repro.core.quant_state.QuantState`
    looked up by ``name`` (Algorithm-1 calibration output), then the
    model-wide ``cfg.trq`` default (with auto-ranging — calibrated registers
    are exact and disable it).  Every backend's A/D-operation count is
    forwarded to any enclosing ``ad_ops_tally()``.

    ``plan`` (a :class:`~repro.pim.plan.LayerPlan` from ``prepare_params``)
    runs the prepared fast path instead — bitwise identical, but with all
    weight-side work done once at programming time.  The plan is used only
    when it was built for the selected backend and no explicit ``trq``
    overrides it, so ``use_backend(...)`` A/B sweeps still work with a plan
    threaded; a plan whose geometry mismatches ``p['w']`` raises (stale
    guard).
    """
    w = p["w"]
    backend_name = active_backend() or cfg.pim_backend
    # device scopes name the datapath in the trace: pim_linear/<backend>
    with jax.named_scope("pim_linear"), jax.named_scope(backend_name):
        if plan is not None and isinstance(plan, LayerPlan) and \
                plan.backend == backend_name and trq is None:
            if tuple(w.shape[-2:]) != (plan.k, plan.n):
                raise ValueError(
                    f"stale plan at {name!r}: programmed for "
                    f"({plan.k}, {plan.n}) but params have "
                    f"{tuple(w.shape[-2:])}; re-run prepare_params")
            out = run_prepared(x, plan, ste=True)
        else:
            if cfg.parallelism == "fsdp_cp" and w.ndim == 2:
                # ZeRO-3-style: gather the (sharded) weight, compute seq-local.
                # The AG has no dependence on the previous layer's activations,
                # so the latency-hiding scheduler prefetches it under compute.
                w = shard(w, None, None)

            t = trq
            if t is None:
                qs = active_quant_state()
                if qs is not None:
                    t = qs.lookup(name)
            auto_range = t is None and cfg.trq.auto_range
            if t is None:
                t = trq_params_from_cfg(cfg.trq)

            out = get_backend(backend_name)(
                x, w.astype(x.dtype), t, ste=True, auto_range=auto_range,
                delta_grid=cfg.trq.delta_grid)

    record_ad_ops(name, out.ad_ops)
    y = out.y
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


@scoped("norm")
def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    n = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (n * p["scale"]).astype(x.dtype)


def init_layernorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


@scoped("norm")
def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return (((xf - mu) * jax.lax.rsqrt(var + eps)) * p["scale"] + p["bias"]
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / RoPE
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig):
    tok = jax.random.normal(key, (cfg.vocab_size, cfg.d_model), jnp.float32)
    return {"tok": (tok * cfg.d_model ** -0.5).astype(pdtype(cfg))}


@scoped("embed")
def embed(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0)


def rope_freqs(cfg: ModelConfig) -> jax.Array:
    hd = cfg.hd
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))


@scoped("rope")
def apply_rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    ang = positions[..., None].astype(jnp.float32) * rope_freqs(cfg)   # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def sinusoid_pos(seq: int, d: int, dtype=jnp.float32) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, jnp.float32) / d))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


# ---------------------------------------------------------------------------
# MLP (gated-SiLU llama-style, or GELU whisper-style)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None,
             bias: bool = False):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": init_linear(ks[1], cfg.d_model, d_ff, cfg, bias=bias),
         "w_down": init_linear(ks[2], d_ff, cfg.d_model, cfg, bias=bias)}
    if cfg.mlp_act == "silu":
        p["w_gate"] = init_linear(ks[0], cfg.d_model, d_ff, cfg, bias=bias)
    return p


def apply_mlp(p: dict, x: jax.Array, cfg: ModelConfig,
              trq: Optional[TRQParams] = None,
              prefix: str = "mlp", plan=None) -> jax.Array:
    up = pim_linear(p["w_up"], x, cfg, trq, name=f"{prefix}/w_up",
                    plan=subplan(plan, "w_up"))
    if cfg.mlp_act == "silu":
        gate = pim_linear(p["w_gate"], x, cfg, trq, name=f"{prefix}/w_gate",
                          plan=subplan(plan, "w_gate"))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    if h.ndim == 3:
        h = shard(h, "batch", "seq", None) if cfg.parallelism == "fsdp_cp" \
            else shard(h, "batch", None, "ffn")
    return pim_linear(p["w_down"], h, cfg, trq, name=f"{prefix}/w_down",
                      plan=subplan(plan, "w_down"))
