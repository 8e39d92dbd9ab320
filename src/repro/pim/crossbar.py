"""Bit-exact simulation of the ISAAC-style sliced crossbar datapath (§II-A).

Datapath being modeled (Fig. 1 / Fig. 5):

* int8 weights are offset-encoded to unsigned and stored as ``k_w`` 1-bit
  cells on ``k_w`` adjacent bit-lines (R_cell = 1).
* uint8 inputs are fed by 1-bit DACs as ``k_i`` bit-slices, cycle by cycle
  (R_DA = 1).
* Rows are partitioned into groups of ``xbar`` (= 128); each (input-slice,
  weight-column, row-group) produces one analog bit-line partial sum in
  ``[0, xbar]`` which the (TRQ-modified) SAR ADC digitizes — one A/D
  *conversion* each.
* The S+A module decodes the compact TRQ code and accumulates with the
  ``<< (input_bit + weight_bit)`` significance; the offset-encoding
  correction term is computed exactly in the digital domain.

Everything is vectorized jnp: the b,j loops become tensor axes so the whole
sim is a handful of matmuls — the same structure the Pallas kernel tiles.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.trq import TRQParams, trq_quant, trq_ad_ops
from repro.telemetry.trace import scoped


@dataclasses.dataclass(frozen=True)
class PimConfig:
    xbar: int = 128          # crossbar rows (= columns) per array
    k_w: int = 8             # weight bit-width (1-bit cells -> k_w columns)
    k_i: int = 8             # input bit-width (1-bit DAC -> k_i slices)
    r_adc: int = 8           # native ADC resolution


def offset_encode(w_int: jax.Array, k_w: int = 8) -> tuple[jax.Array, int]:
    """Signed int weights -> unsigned cell conductances: u = w + 2**(k_w-1).

    Returns (u, zero_point).  The MVM correction term
    ``y = a @ w = a @ u - zp * sum(a)`` is applied digitally."""
    zp = 2 ** (k_w - 1)
    return (w_int.astype(jnp.int32) + zp), zp


def bitplanes(x_uint: jax.Array, bits: int, axis: int = 0) -> jax.Array:
    """Unsigned integer tensor -> stacked 0/1 planes, LSB first."""
    shifts = jnp.arange(bits, dtype=jnp.int32)
    shifts = shifts.reshape((bits,) + (1,) * x_uint.ndim)
    planes = (jnp.expand_dims(x_uint.astype(jnp.int32), 0) >> shifts) & 1
    return jnp.moveaxis(planes, 0, axis)


def _group(x: jax.Array, xbar: int, axis: int) -> jax.Array:
    """Split a contraction axis into (groups, xbar), zero-padding the tail."""
    k = x.shape[axis]
    pad = (-k) % xbar
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    new_shape = x.shape[:axis] + (x.shape[axis] // xbar, xbar) + x.shape[axis + 1:]
    return x.reshape(new_shape)


def _bl_partial_sums(a_uint: jax.Array, u_uint: jax.Array, cfg: PimConfig):
    """All analog bit-line partial sums of an MVM.

    a_uint: (M, K) unsigned inputs;  u_uint: (K, N) unsigned (offset-encoded)
    weights.  Returns int32 partials of shape (k_i, k_w, G, M, N) with values
    in [0, xbar] — exactly what each ADC sees."""
    a_b = bitplanes(a_uint, cfg.k_i)                   # (k_i, M, K)
    u_b = bitplanes(u_uint, cfg.k_w)                   # (k_w, K, N)
    a_g = _group(a_b, cfg.xbar, axis=2)                # (k_i, M, G, X)
    u_g = _group(u_b, cfg.xbar, axis=1)                # (k_w, G, X, N)
    # analog accumulation along each 128-row bit-line: contract X per group
    p = jnp.einsum("imgx,jgxn->ijgmn",
                   a_g.astype(jnp.float32), u_g.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return p                                           # (k_i,k_w,G,M,N)


def _shift_add(y_q: jax.Array, cfg: PimConfig) -> jax.Array:
    """Digital S+A merge over input-slice and weight-column significance."""
    bi = 2.0 ** jnp.arange(cfg.k_i, dtype=jnp.float32)
    bj = 2.0 ** jnp.arange(cfg.k_w, dtype=jnp.float32)
    return jnp.einsum("ijgmn,i,j->mn", y_q, bi, bj)


def weight_planes(w_int: jax.Array, cfg: PimConfig = PimConfig()) -> jax.Array:
    """Offset-encode + bit-slice + group a signed weight matrix ONCE.

    w_int: (..., K, N) signed ints -> (..., k_w, G, X, N) 0/1 planes (int8):
    the exact cell conductance pattern a crossbar programming pass writes.
    This is the weight-stationary precompute — ``bit_exact_mvm`` consumes it
    via ``u_planes`` so per-call work is activations-only (one batched
    einsum over the stacked slices, no per-plane matmul loop)."""
    u, _ = offset_encode(w_int, cfg.k_w)
    u_b = bitplanes(u, cfg.k_w, axis=u.ndim - 2)       # (..., k_w, K, N)
    u_g = _group(u_b, cfg.xbar, axis=u_b.ndim - 2)     # (..., k_w, G, X, N)
    return u_g.astype(jnp.int8)


def bit_exact_mvm(a_uint: jax.Array, w_int: Optional[jax.Array],
                  trq: Optional[TRQParams], cfg: PimConfig = PimConfig(),
                  with_ops: bool = False, u_planes: Optional[jax.Array] = None):
    """Full sliced-datapath MVM with per-conversion (TRQ-)ADC quantization.

    a_uint: (M, K) unsigned ints in [0, 2**k_i);  w_int: (K, N) signed ints
    in [-2**(k_w-1), 2**(k_w-1)).  ``trq=None`` -> lossless (native R_ADC
    covers [0, xbar]).  Returns float32 (M, N) integer-valued result, plus
    total A/D operations when ``with_ops``.

    ``u_planes`` short-circuits the weight-side slicing with the grouped
    cell planes from :func:`weight_planes` (the crossbar-programming cache):
    ``w_int`` may then be None — only the activation planes are built per
    call and the partial sums come from one batched einsum over the stacked
    slices.  Bitwise identical to the dynamic path.
    """
    if u_planes is not None:
        a_b = bitplanes(a_uint, cfg.k_i)               # (k_i, M, K)
        a_g = _group(a_b, cfg.xbar, axis=2)            # (k_i, M, G, X)
        p = jnp.einsum("imgx,jgxn->ijgmn",
                       a_g.astype(jnp.float32),
                       u_planes.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    else:
        u, _ = offset_encode(w_int, cfg.k_w)
        p = _bl_partial_sums(a_uint, u, cfg)
    if trq is None:
        y_q, ops = p, jnp.full(p.shape, cfg.r_adc, jnp.int32)
    else:
        y_q, ops = trq_quant(p, trq), trq_ad_ops(p, trq)
    acc = _shift_add(y_q, cfg)
    zp = 2 ** (cfg.k_w - 1)
    corr = zp * jnp.sum(a_uint.astype(jnp.float32), axis=1, keepdims=True)
    out = acc - corr
    if with_ops:
        # float32 accumulation: op totals feed energy *ratios*; int64 is
        # unavailable without jax_enable_x64
        return out, jnp.sum(ops.astype(jnp.float32))
    return out


def collect_bl_samples(a_uint: jax.Array, w_int: jax.Array,
                       cfg: PimConfig = PimConfig()) -> jax.Array:
    """Raw (pre-ADC) bit-line partial sums — the calibration samples ``y``
    that Algorithm 1 and the Fig. 3a distribution analysis consume."""
    u, _ = offset_encode(w_int, cfg.k_w)
    return _bl_partial_sums(a_uint, u, cfg)


def group_activations(a: jax.Array, cfg: PimConfig = PimConfig()) -> jax.Array:
    """(..., K) activations -> (..., G, X) per-crossbar row groups."""
    return _group(a, cfg.xbar, axis=a.ndim - 1)


def group_weights(w: jax.Array, cfg: PimConfig = PimConfig()) -> jax.Array:
    """(..., K, N) weights -> (..., G, X, N) per-crossbar row groups — the
    weight-stationary half of the fake-quant datapath, precomputable once
    per layer (see ``repro.pim.plan``)."""
    return _group(w, cfg.xbar, axis=w.ndim - 2)


def _group_psums(a_g: jax.Array, w_g: jax.Array) -> jax.Array:
    """All per-group partial sums at once: (..., G, X) x (G, X, N) ->
    (..., G, N) f32 — each [..., g, :] is what crossbar ``g``'s ADCs see."""
    return jnp.einsum("...gx,gxn->...gn", a_g, w_g,
                      preferred_element_type=jnp.float32)


@scoped("range_fit")
def auto_range_fit_grouped(a_g: jax.Array, w_g: jax.Array, trq: TRQParams,
                           grid) -> TRQParams:
    """:func:`auto_range_fit` on pre-grouped operands (plan fast path)."""
    vmax = jnp.max(jnp.abs(_group_psums(a_g, w_g)))
    span = vmax / jnp.asarray(grid, jnp.float32)
    reach = 2.0 ** (trq.n_r2 + trq.m)
    scale = jnp.maximum(span / reach, 1e-6)
    return trq.replace(delta_r1=trq.delta_r1 * scale)


def auto_range_fit(a: jax.Array, w: jax.Array, trq: TRQParams, grid,
                   cfg: PimConfig = PimConfig()) -> TRQParams:
    """Uncalibrated layers: scale ``delta_r1`` so the coarse range
    2^(n_r2+m)*delta_r1 covers the observed per-group |psum| max (the fused
    kernel keeps a running max in VMEM and requantizes; the sim takes one
    extra reduction pass).  Calibrated layers (Algorithm 1) have exact
    registers and skip this.  Shared by the jnp path and the Pallas backend
    so both quantize on the identical grid (max is order-independent, so
    the batched reduction here matches the old per-group running max
    bit-for-bit)."""
    return auto_range_fit_grouped(group_activations(a, cfg),
                                  group_weights(w, cfg), trq, grid)


def fake_quant_mvm_grouped(a_g: jax.Array, w_g: jax.Array, trq: TRQParams,
                           grid, out_dtype, ste: bool = False,
                           auto_range: bool = False, with_ops: bool = False):
    """Grouped-operand core of :func:`fake_quant_mvm` — weight side comes
    pre-grouped (per-call from ``group_weights`` or once per layer from the
    plan cache).  Quantize/accumulate runs in f32 with ONE cast to
    ``out_dtype`` at the end — exactly the Pallas kernel's accumulator
    discipline, so the two datapaths stay bit-aligned in bf16 too."""
    grid = jnp.asarray(grid, jnp.float32)
    if auto_range:
        trq = auto_range_fit_grouped(a_g, w_g, trq, grid)
    return _convert(a_g, w_g, trq, grid, out_dtype, ste, with_ops)


@scoped("convert")
def _convert(a_g, w_g, trq, grid, out_dtype, ste, with_ops):
    """The TRQ conversion of every group's partial sums, then the sum over
    groups (see :func:`fake_quant_mvm_grouped`)."""
    p = _group_psums(a_g, w_g)                          # (..., G, N) f32
    scaled = p / grid
    q = trq_quant(scaled, trq) * grid                   # f32, all groups
    if ste:
        # straight-through: forward is exactly q, gradient flows through p
        q = p + jax.lax.stop_gradient(q - p)
    acc = jnp.sum(q, axis=-2).astype(out_dtype)         # (..., N)
    if with_ops:
        ops = jnp.sum(jax.lax.stop_gradient(
            trq_ad_ops(scaled, trq)).astype(jnp.float32))
        return acc, ops
    return acc


def fake_quant_mvm(a: jax.Array, w: jax.Array, trq: TRQParams,
                   a_scale, w_scale, cfg: PimConfig = PimConfig(),
                   ste: bool = False, auto_range: bool = False,
                   with_ops: bool = False):
    """Fast per-group abstraction (paper §III-B: the quantizer *is* the
    behavioral abstraction of A/D conversion at the BLs).

    Instead of 1-bit slicing (k_i*k_w conversions per group), quantize the
    full-precision per-128-row-group partial sum once with a signed TRQ.
    This is the LM-scale integration path; it preserves the error *locality*
    (per-BL-group) while being a single matmul per group.

    Implementation: one batched (..., G, N) einsum with the quantizer
    applied to every group tile at once, then a sum over the group axis.
    The former per-group ``lax.scan`` kept live memory at one (..., N)
    tile but paid a Python-dispatched scan step per group — a 30x
    wall-clock cliff on the CPU/QAT path.  The trade is explicit: the
    (..., G, N) psum tensor now materializes, i.e. G x the output tile of
    extra live bytes — fine for the behavioral oracle and smoke-scale QAT,
    but a large-K/large-batch QAT step that used to fit under the scan's
    bounded-memory invariant may need a smaller microbatch (or remat)
    after this change.  Deployment is unaffected: the trq_group_mvm
    Pallas kernel keeps the fusion in VMEM on real hardware.

    a: (..., K) float;  w: (K, N) float;  scales map partial sums onto the
    ADC integer grid.  ``ste=True`` makes it differentiable (QAT-style).
    ``with_ops=True`` additionally returns the total A/D operations (SAR
    comparator cycles, f32 scalar, Eq. 6) spent on the G conversions behind
    every output element.
    """
    grid = (jnp.asarray(a_scale, jnp.float32)
            * jnp.asarray(w_scale, jnp.float32))
    return fake_quant_mvm_grouped(group_activations(a, cfg),
                                  group_weights(w, cfg), trq, grid, a.dtype,
                                  ste=ste, auto_range=auto_range,
                                  with_ops=with_ops)
