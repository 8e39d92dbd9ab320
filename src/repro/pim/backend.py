"""Unified PIM execution-backend API.

Every weight-stationary matmul in the stack routes through one contract:

    backend(x, w, trq, **knobs) -> PimOut(y, ad_ops)

where ``y`` is the (quantized) MVM result and ``ad_ops`` the total A/D
operations (SAR comparator cycles, Eq. 6) the conversion spent — so the
energy accounting of Eq. 9 flows out of *every* datapath, not just the
bit-exact simulator.  Four backends ship:

``exact``       plain matmul — training / FP reference (the paper trains
                digitally; ad_ops = 0, nothing converts).
``fake_quant``  per-128-row-group signed TRQ on partial sums via a jnp
                ``lax.scan`` (paper §III-B behavioral abstraction;
                differentiable with STE — the QAT/serve CPU path).
``pallas``      the fused ``trq_group_mvm`` Pallas kernel — same math as
                ``fake_quant`` with the quantizer applied in VMEM inside the
                matmul K-loop (compiled on TPU, interpreted elsewhere).
``bit_exact``   the full ISAAC sliced datapath (1-bit DAC slices x 1-bit
                cells, per-BL conversion) on dynamically int-quantized
                inputs — the audit path for small layers.

Selection mirrors ``use_mesh``: a ``use_backend("pallas")`` context
overrides the per-model ``ModelConfig.pim_backend`` string; new datapaths
(int8 XLA, multi-chip, real hardware) register with
:func:`register_backend` and become reachable from every model without
touching model code.
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.trq import TRQParams
from .crossbar import (PimConfig, auto_range_fit, bit_exact_mvm,
                       fake_quant_mvm)


class PimOut(NamedTuple):
    """Uniform backend result: MVM output + total A/D operations."""
    y: jax.Array                # (..., N), x.dtype
    ad_ops: jax.Array           # scalar f32, SAR comparator cycles (Eq. 6)


@runtime_checkable
class PimBackend(Protocol):
    """A PIM datapath: ``(x, w, trq, **knobs) -> PimOut``.

    ``x``: (..., K) float activations; ``w``: (K, N) float weights (already
    in compute dtype); ``trq``: per-layer SAR registers or None (lossless /
    exact).  Knobs (all keyword, all optional — backends ignore what they
    don't use): ``a_scale``/``w_scale`` (None -> dynamic max-abs),
    ``delta_grid``, ``ste``, ``auto_range``, ``pim`` (PimConfig),
    ``interpret``."""

    def __call__(self, x: jax.Array, w: jax.Array,
                 trq: Optional[TRQParams], **knobs) -> PimOut: ...


# ---------------------------------------------------------------------------
# registry + ambient selection (mirrors repro.dist.sharding.use_mesh)
# ---------------------------------------------------------------------------

_BACKENDS: dict[str, PimBackend] = {}
_ACTIVE: dict = {"backend": None}


def register_backend(name: str, backend: Optional[PimBackend] = None):
    """Register a datapath under ``name`` (also usable as a decorator).
    Re-registering a name overwrites it — tests swap in probes this way."""
    def _register(fn: PimBackend) -> PimBackend:
        _BACKENDS[name] = fn
        return fn
    return _register(backend) if backend is not None else _register


def get_backend(name: str) -> PimBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown PIM backend {name!r}; registered: "
                       f"{sorted(_BACKENDS)}") from None


def list_backends() -> tuple:
    return tuple(sorted(_BACKENDS))


@contextlib.contextmanager
def use_backend(name: Optional[str]):
    """Force every ``pim_linear`` in the dynamic extent onto backend
    ``name``, overriding ``ModelConfig.pim_backend``.  ``None`` is a no-op
    passthrough.  Nestable; restores the outer selection."""
    if name is not None:
        get_backend(name)                      # fail fast on typos
    prev = _ACTIVE["backend"]
    if name is not None:
        _ACTIVE["backend"] = name
    try:
        yield name
    finally:
        _ACTIVE["backend"] = prev


def active_backend() -> Optional[str]:
    return _ACTIVE["backend"]


# ---------------------------------------------------------------------------
# A/D-operation tally (energy accounting hook)
# ---------------------------------------------------------------------------

class AdOpsTally:
    """Accumulates per-layer ``ad_ops`` emitted by ``pim_linear``.

    Eager-mode instrumentation: values produced inside a ``jit``/``scan``/
    ``vmap`` trace are tracers that must not escape, so ``record_ad_ops``
    drops them — run the model unrolled (``scan_layers=False``,
    ``remat='none'``) to collect every layer.  Layers that only exist under
    an internal ``vmap`` (e.g. enc-dec ``cross_kv``) are skipped."""

    def __init__(self):
        self.by_layer: dict[str, jax.Array] = {}

    def add(self, name: str, ops) -> None:
        self.by_layer[name] = self.by_layer.get(name, 0.0) + ops

    def total(self) -> float:
        if not self.by_layer:
            return 0.0          # keep the empty tally float-typed
        return float(sum(jnp.asarray(v) for v in self.by_layer.values()))


_TALLY: list[AdOpsTally] = []


@contextlib.contextmanager
def ad_ops_tally():
    """Collect every layer's A/D-operation count from the enclosing forward
    pass:  ``with ad_ops_tally() as t: model(...); t.total()``."""
    t = AdOpsTally()
    _TALLY.append(t)
    try:
        yield t
    finally:
        _TALLY.remove(t)


class TracedAdOps:
    """In-trace A/D-ops accumulator: ``value`` is a jnp scalar built from the
    tracers of exactly one trace level, so it can be RETURNED from the traced
    function (unlike :class:`AdOpsTally`, which must drop tracers).

    Scan/vmap discipline: a value accumulated inside a ``lax.scan``/``vmap``
    body belongs to that body's trace and must not leak outward.  Model code
    therefore pushes a *fresh* ``traced_ad_ops()`` around each scan/vmap body,
    drains it into the carry / a stacked output, and re-emits the reduced
    total into the enclosing tally with :func:`reemit_ad_ops` at the outer
    trace level (see ``apply_lm`` / ``apply_encdec``)."""

    def __init__(self):
        self.value = jnp.float32(0.0)

    def add(self, ops) -> None:
        self.value = self.value + jnp.asarray(ops, jnp.float32)


_TRACED: list[TracedAdOps] = []


@contextlib.contextmanager
def traced_ad_ops():
    """A/D-ops accounting that works INSIDE ``jit``: enter within the traced
    function and return ``t.value`` as one of its outputs.

        @jax.jit
        def step(params, batch):
            with traced_ad_ops() as t:
                logits, cache, _ = apply_fn(params, batch, ...)
            return logits, cache, t.value            # scalar f32 ad_ops

    This is how the serve engine meters conversions per prefill/decode call
    without unrolling the layer scan."""
    t = TracedAdOps()
    _TRACED.append(t)
    try:
        yield t
    finally:
        _TRACED.remove(t)


def reemit_ad_ops(ops) -> None:
    """Forward an already-reduced ops total (e.g. a scan carry drained at a
    trace boundary) into the innermost ``traced_ad_ops`` tally only.  Never
    touches the eager per-layer tally — the per-layer values were already
    recorded there by ``record_ad_ops`` when running un-jitted."""
    if _TRACED:
        _TRACED[-1].add(ops)


def record_ad_ops(name: Optional[str], ops) -> None:
    # every pim_linear emission point lands here.  The traced tally (if one
    # is active) absorbs tracers — by construction it lives in the same
    # trace as the emission.  The eager tally must still drop tracers
    # (scan/vmap/jit bodies) — they poison every later sum with an
    # UnexpectedTracerError.
    if _TRACED:
        _TRACED[-1].add(ops)
    if _TALLY and not isinstance(ops, jax.core.Tracer):
        _TALLY[-1].add(name or "<unnamed>", ops)


# ---------------------------------------------------------------------------
# the four stock backends
# ---------------------------------------------------------------------------

def _stable_recip(s):
    """1/s rounded to bf16 then widened back to f32: a determinism barrier.
    XLA lowers f32 division differently between eager and fused contexts
    (true divide vs refined reciprocal, last-ulp differences); the bf16
    rounding absorbs that jitter so ``x * _stable_recip(s)`` — an EXACT f32
    multiply — quantizes identically everywhere.  Scale precision is 8
    mantissa bits, irrelevant next to the k-bit integer grid it feeds."""
    return jnp.asarray(1.0 / jnp.asarray(s, jnp.float32),
                       jnp.bfloat16).astype(jnp.float32)


def _dynamic_scales(x, w, a_scale, w_scale, levels: float = 127.0):
    """Max-abs per-tensor scales mapping partial sums onto the ADC integer
    grid (None -> dynamic; explicit values pass through for calibrated or
    test-pinned grids)."""
    if a_scale is None:
        a_scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-6) / levels
    if w_scale is None:
        w_scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-6) / levels
    return a_scale, w_scale


@register_backend("exact")
def exact_backend(x, w, trq=None, **_) -> PimOut:
    """Digital FP matmul: no crossbar, no conversion, zero A/D operations."""
    return PimOut(x @ w.astype(x.dtype), jnp.float32(0.0))


@register_backend("fake_quant")
def fake_quant_backend(x, w, trq, *, a_scale=None, w_scale=None,
                       delta_grid: float = 1.0, ste: bool = False,
                       auto_range: bool = False,
                       pim: PimConfig = PimConfig(), **_) -> PimOut:
    a_s, w_s = _dynamic_scales(x, w, a_scale, w_scale)
    grid = (jnp.asarray(a_s, jnp.float32) * jnp.asarray(w_s, jnp.float32)
            * delta_grid)
    y, ops = fake_quant_mvm(x, w.astype(x.dtype), trq, grid, 1.0, pim,
                            ste=ste, auto_range=auto_range, with_ops=True)
    return PimOut(y, ops)


@register_backend("pallas")
def pallas_backend(x, w, trq, *, a_scale=None, w_scale=None,
                   delta_grid: float = 1.0, auto_range: bool = False,
                   pim: PimConfig = PimConfig(), interpret=None,
                   **_) -> PimOut:
    """Inference datapath: ``pallas_call`` has no VJP, so this backend is
    not differentiable — train with ``fake_quant`` (same math + STE) and
    deploy on ``pallas``."""
    from repro.kernels import trq_group_mvm_pallas
    a_s, w_s = _dynamic_scales(x, w, a_scale, w_scale)
    grid = (jnp.asarray(a_s, jnp.float32) * jnp.asarray(w_s, jnp.float32)
            * delta_grid)
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    if auto_range:
        # same pre-fit as the scan path (the TPU kernel's in-VMEM running
        # max is future work); keeps pallas/fake_quant bit-aligned
        trq = auto_range_fit(xf, wf, trq, grid, pim)
    with jax.named_scope("convert"):
        y, ops = trq_group_mvm_pallas(xf, wf, trq, grid, 1.0,
                                      interpret=interpret, with_ops=True)
    return PimOut(y.astype(x.dtype), ops)


@register_backend("bit_exact")
def bit_exact_backend(x, w, trq, *, a_scale=None, w_scale=None,
                      pim: PimConfig = PimConfig(), **_) -> PimOut:
    """Full sliced-datapath audit: activations/weights are PTQ-quantized to
    k_i/k_w-bit ints (max-abs, symmetric), the ISAAC sim converts every
    bit-line partial sum through the (TRQ-)ADC, and the result is rescaled.
    O(k_i * k_w * G) matmuls — small layers / audit runs only.

    NOTE: ``trq`` here acts on the *raw BL integer grid* ([0, xbar] partial
    sums), i.e. registers calibrated by Algorithm 1 on ``collect_bl_samples``
    output.  Registers scaled for the signed per-group grid of
    ``fake_quant``/``pallas`` are a different quantity; ``trq=None`` runs
    the lossless native-R_ADC datapath."""
    lead = x.shape[:-1]
    half_a = 2 ** (pim.k_i - 1)
    half_w = 2 ** (pim.k_w - 1)
    # The PTQ quantizer must be CONTEXT-STABLE: the programming cache
    # (repro.pim.plan) precomputes the weight side eagerly, while this
    # dynamic path runs fused inside jit/scan — and XLA's division lowering
    # (and bf16 intermediate rounding) differ between those contexts,
    # flipping whole integer steps at rounding boundaries.  So the chain is
    # f32 end-to-end, scales come from EXACT multiplies by reciprocal
    # constants, and the elementwise step divides via a bf16-rounded
    # reciprocal (exact f32 multiply after a deterministic barrier).
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    a_s = a_scale if a_scale is not None else \
        jnp.maximum(jnp.max(jnp.abs(x2)), 1e-6) * (1.0 / (half_a - 1))
    w_s = w_scale if w_scale is not None else \
        jnp.maximum(jnp.max(jnp.abs(wf)), 1e-6) * (1.0 / (half_w - 1))

    a_int = jnp.clip(jnp.floor(x2 * _stable_recip(a_s) + 0.5),
                     -half_a, half_a - 1).astype(jnp.int32)
    w_int = jnp.clip(jnp.floor(wf * _stable_recip(w_s) + 0.5),
                     -half_w, half_w - 1).astype(jnp.int32)
    # the 1-bit DACs feed unsigned slices: offset-encode the activations and
    # correct digitally, exactly like the weight zero-point in the sim
    a_u = a_int + half_a
    out, ops = bit_exact_mvm(a_u, w_int, trq, pim, with_ops=True)
    corr = half_a * jnp.sum(w_int.astype(jnp.float32), axis=0, keepdims=True)
    y = (out - corr) * (jnp.asarray(a_s, jnp.float32)
                        * jnp.asarray(w_s, jnp.float32))
    return PimOut(y.reshape(*lead, w.shape[1]).astype(x.dtype), ops)


# ---------------------------------------------------------------------------
# functional entry point
# ---------------------------------------------------------------------------

def pim_mvm(x: jax.Array, w: Optional[jax.Array] = None,
            trq: Optional[TRQParams] = None,
            backend: Optional[str] = None, *, plan=None,
            **knobs) -> PimOut:
    """Run ``x @ w`` on a named datapath (default: the ambient
    ``use_backend`` selection, else ``exact``) and return ``PimOut``.

    Prepared fast path: ``pim_mvm(x, plan=<LayerPlan>)`` executes against a
    crossbar image programmed once by ``repro.pim.plan`` — bitwise
    identical to the dynamic call, with all weight-side work (max-|w| grid
    scale, dtype cast, bit-plane slicing, tile padding) hoisted out of the
    call.  Knob precedence with ``plan``:

    * ``w`` and ``trq`` must be ``None`` — the plan IS the weight-side
      state (passing either raises, so a stale call site can't silently
      shadow the programmed registers);
    * ``backend=`` may be given but must equal ``plan.backend`` (prepared
      payloads are backend-specific; mismatch raises);
    * plan-frozen knobs — ``w_scale``, ``auto_range``, ``delta_grid``,
      ``pim``, tile geometry — come from the plan; explicit
      ``w_scale=``/``a_scale=`` still override for test-pinned grids
      (except ``bit_exact``, whose programmed cell planes are a function
      of the weight scale — a ``w_scale`` override there raises);
    * per-call knobs (``a_scale``, ``ste``, ``interpret``) pass through
      unchanged.
    """
    if plan is not None:
        if w is not None or trq is not None:
            raise ValueError("pim_mvm(plan=...) carries the weight-side "
                             "state; pass w=None and trq=None (explicit "
                             "per-call registers would shadow the "
                             "programmed plan)")
        from .plan import run_prepared      # lazy: plan imports this module
        return run_prepared(x, plan, backend=backend, **knobs)
    name = backend or active_backend() or "exact"
    return get_backend(name)(x, w, trq, **knobs)
