"""Pallas TPU kernel: K-blocked matmul with per-128-row-group signed TRQ.

This is the *deployable* form of the paper's technique for LM-scale layers
(DESIGN.md §4, mode ``fake_quant``): each 128-row group of the contraction
corresponds to one crossbar; its full-precision partial-sum tile is passed
through the signed TRQ quantizer (the behavioral SAR-ADC) while still in
VMEM, then accumulated.  Compared to ``xbar_mvm`` (64 bit-plane matmuls per
group) this runs ONE matmul per group — the abstraction the paper itself
introduces in §III-B.

Fusion argument (roofline): an unfused implementation materializes the
(M, G, N) partial-sum tensor in HBM (G = K/128 extra reads+writes of the
output tile).  Fusing the quantizer into the matmul's K-loop keeps traffic
at the plain-matmul level — the technique becomes FLOP-bound, not
bandwidth-bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.trq import TRQParams, trq_ad_ops, trq_quant

XBAR = 128


def _kernel(scalars_ref, a_ref, w_ref, out_ref, ops_ref=None, *,
            n_r1, n_r2, m, nu, mode):
    """One body for both variants: ``ops_ref`` (present only when the call
    site requests the fused SAR-cycle count, Eq. 6) accumulates over the k
    grid axis exactly like the partial sums do — each 128-row group is one
    A/D conversion per output element."""
    p = TRQParams(delta_r1=scalars_ref[0], bias=scalars_ref[1],
                  n_r1=n_r1, n_r2=n_r2, m=m, nu=nu, mode=mode, signed=True)
    grid_scale = scalars_ref[2]       # a_scale * w_scale (ADC integer grid)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        if ops_ref is not None:
            ops_ref[...] = jnp.zeros_like(ops_ref)

    a = a_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    psum = jax.lax.dot(a, w, precision=jax.lax.Precision.HIGHEST)
    scaled = psum / grid_scale
    out_ref[...] += trq_quant(scaled, p) * grid_scale
    if ops_ref is not None:
        ops_ref[...] += trq_ad_ops(scaled, p).astype(jnp.float32)


def trq_group_mvm_tiles(a: jax.Array, w: jax.Array, p: TRQParams,
                        grid_scale, *, block_m: int = 128,
                        block_n: int = 128, interpret: bool = True,
                        with_ops: bool = False):
    """a: (M, 128*G) f32; w: (128*G, N) f32.  Per-group TRQ matmul.

    ``with_ops`` adds a second (M, N) f32 output holding the total SAR
    comparator cycles spent on each output element's G conversions."""
    mm, kk = a.shape
    nn = w.shape[1]
    grid = (mm // block_m, nn // block_n, kk // XBAR)
    scalars = jnp.stack([jnp.asarray(p.delta_r1, jnp.float32),
                         jnp.asarray(p.bias, jnp.float32),
                         jnp.asarray(grid_scale, jnp.float32)])
    kernel = functools.partial(_kernel, n_r1=p.n_r1, n_r2=p.n_r2, m=p.m,
                               nu=p.nu, mode=p.mode)
    out_block = pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j))
    out_shape = jax.ShapeDtypeStruct((mm, nn), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, XBAR), lambda i, j, k: (i, k)),
            pl.BlockSpec((XBAR, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=[out_block, out_block] if with_ops else out_block,
        out_shape=[out_shape, out_shape] if with_ops else out_shape,
        interpret=interpret,
        name="trq_group_mvm",
    )(scalars, a, w)
