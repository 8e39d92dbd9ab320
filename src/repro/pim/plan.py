"""Crossbar programming cache: weight-stationary PIM execution plans.

In a real ReRAM accelerator the weights are programmed into the crossbars
ONCE and stay there — that is the whole point of processing-in-memory
(paper §II).  Yet a dynamic ``pim_mvm`` call re-derives every piece of
weight-side state per call: the max-|w| reduction behind the ADC grid
scale, the compute-dtype cast, and (for the bit-exact datapath) the full
offset-encode/bit-slice/group pass.  On the serve decode path that work
repeats every token for every layer.

``prepare_params`` walks a model's parameter pytree once — resolving each
layer's SAR registers through the same param-path names the
:class:`~repro.core.quant_state.QuantState` rule table uses — and emits a
:class:`PimPlan`: the static image of the crossbar programming pass.  Every
backend then has a prepared fast path (``pim_mvm(x, plan=...)``) that is
bitwise identical to the dynamic call but touches only activations at call
time.  This is the layer a real-hardware / multi-chip backend programs
against: the plan IS the device state.

Plan fields -> paper quantities
-------------------------------
``w_scale``     the weight half of the ADC integer grid Δ (partial sums are
                expressed as ``a_scale*w_scale`` grid units before
                conversion) — the denominator of Eq. 6's input ``y``.
``trq``         the per-layer modified-SAR register file (n_r1, n_r2, m,
                bias, delta_r1 of Eq. 7/8) resolved from Algorithm-1 output;
                it decides the per-conversion comparator cycles
                ``N_AD = nu + (n_r1 | n_r2)`` of Eq. 6 and therefore the
                conversion energy of Eq. 9.
``w_g``         (fake_quant) weights pre-split into 128-row crossbar groups
                — one group = one ADC conversion per output element.
``w_f32``       (pallas) the pre-cast, pre-padded tile image the fused
                kernel streams from HBM.
``w_planes``    (bit_exact) the programmed 1-bit cell conductances
                (k_w planes x groups x rows x bit-lines) — literally the
                crossbar contents after the programming pass.
``w_colsum``    (bit_exact) per-column Σw_int for the digital offset
                correction term.
``k``/``n``     the layer's logical MVM geometry (stale-plan guard) —
                padded tile geometry derives from it per backend.

Knob precedence on the prepared path: the plan freezes everything
weight-side (``w_scale``, ``trq``, ``auto_range``, ``delta_grid``, tile
geometry); per-call knobs (``a_scale``, ``ste``, ``interpret``) still pass
through; an explicit ``backend=`` must agree with ``plan.backend`` (each
payload is backend-specific) and explicit ``w``/``trq`` arguments are
rejected — see :func:`repro.pim.backend.pim_mvm`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.trq import TRQParams
from repro.kernels.trq_group_mvm.kernel import XBAR
from .backend import PimOut, _dynamic_scales, _stable_recip, get_backend
from .crossbar import (PimConfig, auto_range_fit_grouped,
                       bit_exact_mvm, fake_quant_mvm_grouped,
                       group_activations, group_weights, weight_planes)

_TRQ_STATIC = ("n_r1", "n_r2", "m", "nu", "mode", "signed")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """Frozen weight-side state of ONE planned linear (one crossbar tile
    set).  Exactly one of the payload fields is populated, matching
    ``backend``; all traced leaves may carry a leading stack axis when the
    layer lives under a scanned period / layer stack."""

    # --- traced leaves ---
    w_scale: Optional[jax.Array] = None     # frozen max-|w| grid scale
    trq: Optional[TRQParams] = None         # resolved SAR registers
    w: Optional[jax.Array] = None           # exact: compute-dtype weights
    w_g: Optional[jax.Array] = None         # fake_quant: (..., G, X, N)
    w_f32: Optional[jax.Array] = None       # pallas: f32, K/N tile-padded
    w_planes: Optional[jax.Array] = None    # bit_exact/noisy: cell planes, int8
    w_colsum: Optional[jax.Array] = None    # bit_exact/noisy: per-col sum w_int
    w_analog: Optional[jax.Array] = None    # noisy: faulted conductances, f32
    adc_off: Optional[jax.Array] = None     # noisy: fixed-pattern ADC offsets
    # --- static metadata ---
    backend: str = dataclasses.field(metadata=dict(static=True),
                                     default="exact")
    auto_range: bool = dataclasses.field(metadata=dict(static=True),
                                         default=False)
    delta_grid: float = dataclasses.field(metadata=dict(static=True),
                                          default=1.0)
    k: int = dataclasses.field(metadata=dict(static=True), default=0)
    n: int = dataclasses.field(metadata=dict(static=True), default=0)
    pim: PimConfig = dataclasses.field(metadata=dict(static=True),
                                       default=PimConfig())

    def replace(self, **kw) -> "LayerPlan":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class PimPlan:
    """A whole model's programming cache: a pytree mirroring the parameter
    tree with a :class:`LayerPlan` at every ``pim_linear`` weight node
    (stacked subtrees — ``periods`` / ``enc`` / ``dec`` — keep their leading
    layer axis so plans thread through the layer scans exactly like
    params).  ``qs_token`` fingerprints the QuantState the registers were
    resolved from, so a consumer (e.g. ``ServeEngine``) can reject a plan
    programmed against different calibration than it would serve
    dynamically.  ``cm_token`` does the same for the device non-ideality
    model (fault seed + device-side field values — see
    ``repro.pim.noise``): a plan with baked faults must not execute
    against a different simulated device."""

    layers: dict
    backend: str = "exact"
    qs_token: Optional[str] = None
    cm_token: Optional[str] = None

    def __len__(self) -> int:
        return len(_iter_layer_plans(self.layers))

    def replace(self, **kw) -> "PimPlan":
        return dataclasses.replace(self, **kw)


jax.tree_util.register_pytree_node(
    PimPlan,
    lambda p: ((p.layers,), (p.backend, p.qs_token, p.cm_token)),
    lambda aux, ch: PimPlan(layers=ch[0], backend=aux[0], qs_token=aux[1],
                            cm_token=aux[2]))


def quant_state_token(qs) -> Optional[str]:
    """Stable fingerprint of a QuantState's rule table (None for None) —
    what :func:`prepare_params` stamps into ``PimPlan.qs_token``."""
    if qs is None:
        return None
    import hashlib
    import json
    from repro.core.quant_state import quant_state_to_dict
    blob = json.dumps(quant_state_to_dict(qs), sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _iter_layer_plans(node, prefix=""):
    out = []
    if isinstance(node, LayerPlan):
        return [(prefix, node)]
    if isinstance(node, dict):
        for k in sorted(node):
            out.extend(_iter_layer_plans(node[k],
                                         f"{prefix}/{k}" if prefix else k))
    return out


def subplan(plan, key: str):
    """Child subtree of a plan node, ``None``-propagating — the threading
    helper model code uses to walk the plan alongside its params."""
    if plan is None:
        return None
    if isinstance(plan, PimPlan):
        plan = plan.layers
    if isinstance(plan, dict):
        return plan.get(key)
    return None


# ---------------------------------------------------------------------------
# single-layer preparation (the unit the tree walk vmap-stacks)
# ---------------------------------------------------------------------------

def prepare_linear(w: jax.Array, trq: Optional[TRQParams] = None, *,
                   backend: str = "exact", auto_range: bool = False,
                   delta_grid: float = 1.0, pim: PimConfig = PimConfig(),
                   dtype=None, block_n: int = 128,
                   crossbar_model=None) -> LayerPlan:
    """Program ONE linear's weights for ``backend``.

    ``w``: (K, N) — or (L, K, N) for a stacked layer family, in which case
    every traced leaf of the result carries the leading L axis (``trq``
    leaves must then be pre-stacked to (L,) by the caller; scalars are
    broadcast).  ``dtype`` is the compute dtype the runtime will call with
    (``pim_linear`` hands backends ``w.astype(x.dtype)``, so the frozen
    scale must be computed on the SAME cast weights to stay bitwise
    identical to the dynamic path).  ``crossbar_model`` (a
    ``repro.pim.noise.CrossbarModel``) reaches backends whose programming
    recipe bakes device-side faults (``@register_prepare_hook``); the
    stock ideal backends ignore it."""
    get_backend(backend)                       # fail fast on typos
    stacked = w.ndim == 3
    if w.ndim not in (2, 3):
        raise ValueError(f"prepare_linear wants (K,N) or (L,K,N), got "
                         f"{w.shape}")
    k, n = int(w.shape[-2]), int(w.shape[-1])
    w_cast = w.astype(dtype) if dtype is not None else w
    if stacked and trq is not None and not _trq_is_stacked(trq):
        trq = _stack_trq([trq], w.shape[0])
    kw = dict(trq=trq, backend=backend, auto_range=auto_range,
              delta_grid=float(delta_grid), k=k, n=n, pim=pim)

    if backend == "exact":
        return LayerPlan(w=w_cast, **kw)

    if backend in ("fake_quant", "pallas"):
        w_scale = jnp.maximum(
            jnp.max(jnp.abs(w_cast), axis=(-2, -1)), 1e-6) / 127.0
        if backend == "fake_quant":
            return LayerPlan(w_scale=w_scale,
                             w_g=group_weights(w_cast, pim), **kw)
        wf = w_cast.astype(jnp.float32)
        # auto-ranged layers also keep the UNPADDED grouped image: the
        # pre-fit must see operands shaped exactly like the dynamic path's
        # (same einsum shapes -> bit-identical |psum| max -> identical
        # fitted delta_r1); calibrated layers skip the fit and the copy
        w_g = group_weights(wf, pim) if auto_range else None
        pad_k = (-k) % XBAR
        pad_n = (-n) % block_n
        if pad_k or pad_n:
            widths = [(0, 0)] * (wf.ndim - 2) + [(0, pad_k), (0, pad_n)]
            wf = jnp.pad(wf, widths)
        return LayerPlan(w_scale=w_scale, w_f32=wf, w_g=w_g, **kw)

    if backend == "bit_exact":
        half_w = 2 ** (pim.k_w - 1)
        # context-stable PTQ chain, mirroring bit_exact_backend exactly:
        # f32 end-to-end, reciprocal-multiply scales, bf16-barrier step
        wf = w_cast.astype(jnp.float32)
        w_scale = jnp.maximum(
            jnp.max(jnp.abs(wf), axis=(-2, -1)), 1e-6) * (1.0 / (half_w - 1))
        w_s = w_scale[..., None, None] if stacked else w_scale
        w_int = jnp.clip(jnp.floor(wf * _stable_recip(w_s) + 0.5),
                         -half_w, half_w - 1).astype(jnp.int32)
        return LayerPlan(w_scale=w_scale,
                         w_planes=weight_planes(w_int, pim),
                         w_colsum=jnp.sum(w_int.astype(jnp.float32),
                                          axis=-2), **kw)

    hook = _PREPARE_HOOKS.get(backend)
    if hook is not None:
        return hook(w_cast, kw, crossbar_model)

    raise ValueError(f"backend {backend!r} has no prepared payload; "
                     f"register one with @register_prepared (+ a recipe "
                     f"via @register_prepare_hook), or serve dynamically "
                     f"(ServeEngine(plan=False))")


# programming recipes for non-stock backends: ``fn(w_cast, kw, crossbar_
# model) -> LayerPlan`` where ``kw`` carries the common LayerPlan kwargs
# (trq/backend/auto_range/delta_grid/k/n/pim).  Keeps the dependency
# direction plan <- noise (the noisy recipe registers itself on import).
_PREPARE_HOOKS: dict = {}

_STOCK_PREPARE = frozenset({"exact", "fake_quant", "pallas", "bit_exact"})


def register_prepare_hook(name: str):
    """Register the ``prepare_linear`` programming recipe for backend
    ``name`` (decorator) — pair it with ``@register_prepared`` so
    ``has_prepared`` holds."""
    def _register(fn):
        _PREPARE_HOOKS[name] = fn
        return fn
    return _register


def has_prepared(backend: str) -> bool:
    """True when ``backend`` has both a programming recipe and a prepared
    execution path — i.e. ``prepare_params``/``pim_mvm(plan=...)`` work."""
    return backend in _PREPARED and (backend in _STOCK_PREPARE
                                     or backend in _PREPARE_HOOKS)


def _trq_is_stacked(t: TRQParams) -> bool:
    return getattr(t.delta_r1, "ndim", 0) > 0


def _stack_trq(ts, n_stack: int) -> TRQParams:
    """Per-slice register files -> one TRQParams with (L,) traced leaves.
    A single entry broadcasts; static register geometry must be uniform
    (it selects hardware search depth — one plan programs one ADC mode)."""
    ts = list(ts)
    if len(ts) == 1:
        ts = ts * n_stack
    ref = ts[0]
    for t in ts[1:]:
        bad = [f for f in _TRQ_STATIC if getattr(t, f) != getattr(ref, f)]
        if bad:
            raise ValueError(
                "cannot stack per-depth TRQParams with differing static "
                f"register geometry ({bad}) into one scanned plan; align "
                "the QuantState rules across the period")
    return ref.replace(
        delta_r1=jnp.stack([jnp.asarray(t.delta_r1, jnp.float32)
                            for t in ts]),
        bias=jnp.stack([jnp.asarray(t.bias, jnp.float32) for t in ts]))


# ---------------------------------------------------------------------------
# whole-model preparation
# ---------------------------------------------------------------------------

# param subtrees whose matmuls bypass pim_linear by design (MoE expert-FFN
# einsums and the router — see models/moe.py)
_SKIP_KEYS = frozenset({"moe"})


def _is_linear(node, stacked: bool) -> bool:
    if not isinstance(node, dict) or "w" not in node:
        return False
    w = node["w"]
    return getattr(w, "ndim", 0) == (3 if stacked else 2)


def prepare_params(params: dict, cfg, quant_state=None,
                   backend: Optional[str] = None,
                   pim: PimConfig = PimConfig(), dtype=None,
                   crossbar_model=None) -> PimPlan:
    """Walk a model parameter pytree once and program every ``pim_linear``
    weight for ``backend`` (default ``cfg.pim_backend``).

    Per-layer SAR registers resolve through ``quant_state`` with the SAME
    param-path names ``pim_linear`` uses at runtime (``layer_3/attn/wq``,
    ``dec/mlp/w_up``, ...); layers with no matching rule freeze the
    model-wide ``cfg.trq`` default and keep auto-ranging enabled, exactly
    mirroring the dynamic resolution order.  Under the period scan
    (``cfg.scan_layers``) names are period-local (periods share registers);
    unrolled models resolve one register file per absolute depth and stack
    them along the period axis.  Pure jnp — safe under ``jax.eval_shape``
    for allocation-free cell building."""
    backend = backend or getattr(cfg, "pim_backend", "exact")
    cdt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    pdt = jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32
    # the lm modality frontend is the one pim_linear that runs BEFORE
    # apply_lm's compute-dtype cast: its activations come straight out of
    # embed() at param dtype, so its weights must be frozen at param dtype
    # to stay bitwise with the dynamic path.  (The enc-dec frontend casts
    # frames to compute dtype first — it plans at compute dtype like every
    # other layer.)  An explicit ``dtype=`` overrides both.
    lm_frontend_dtype = dtype if dtype is not None else (
        cdt if cfg.encoder_layers else pdt)
    dtype = dtype if dtype is not None else cdt
    default_trq = TRQParams(
        delta_r1=jnp.float32(cfg.trq.delta_r1),
        bias=jnp.float32(cfg.trq.bias), n_r1=cfg.trq.n_r1,
        n_r2=cfg.trq.n_r2, m=cfg.trq.m, signed=cfg.trq.signed)

    def resolve(name: str):
        t = quant_state.lookup(name) if quant_state is not None else None
        auto = t is None and cfg.trq.auto_range
        return (t if t is not None else default_trq), auto

    def one(node, names, dt):
        """Plan one linear.  ``names`` has one entry per stack slice (or a
        single entry for an unstacked node)."""
        stacked = node["w"].ndim == 3
        resolved = [resolve(nm) for nm in dict.fromkeys(names)]
        autos = {a for _, a in resolved}
        if len(autos) != 1:
            # only reachable on unrolled models (scan_layers=False): the
            # scan path resolves ONE period-local name per node
            raise ValueError(
                f"mixed calibrated/auto-ranged depths under one stacked "
                f"plan node ({sorted(dict.fromkeys(names))}); give every "
                f"depth of the period a QuantState rule (or none), or "
                f"serve dynamically (plan=False)")
        if stacked:
            trq = _stack_trq([resolve(nm)[0] for nm in names], len(names))
        else:
            trq = resolved[0][0]
        return prepare_linear(node["w"], trq, backend=backend,
                              auto_range=autos.pop(),
                              delta_grid=cfg.trq.delta_grid, pim=pim,
                              dtype=dt, crossbar_model=crossbar_model)

    def walk(tree, prefixes, stacked, dt):
        out = {}
        for key, val in tree.items():
            if key in _SKIP_KEYS or not isinstance(val, dict):
                continue
            names = [f"{px}/{key}" if px else key for px in prefixes]
            if _is_linear(val, stacked):
                out[key] = one(val, names, dt)
            else:
                sub = walk(val, names, stacked, dt)
                if sub:
                    out[key] = sub
        return out

    layers = {}
    for key, val in params.items():
        if not isinstance(val, dict):
            continue
        if key == "periods":
            sub = {}
            for lkey, lval in val.items():
                idx = int(lkey.rsplit("_", 1)[1])
                if cfg.scan_layers:
                    prefixes = [f"layer_{idx}"] * cfg.n_periods
                else:
                    prefixes = [f"layer_{p * cfg.period + idx}"
                                for p in range(cfg.n_periods)]
                r = walk(lval, prefixes, stacked=True, dt=dtype)
                if r:
                    sub[lkey] = r
            if sub:
                layers[key] = sub
        elif key in ("enc", "dec"):
            depth = cfg.encoder_layers if key == "enc" else cfg.n_layers
            r = walk(val, [key] * depth, stacked=True, dt=dtype)
            if r:
                layers[key] = r
        elif _is_linear(val, stacked=False):
            layers[key] = one(val, [key], dtype)
        else:
            dt = lm_frontend_dtype if key == "frontend" else dtype
            r = walk(val, [key], stacked=False, dt=dt)
            if r:
                layers[key] = r
    # the device fingerprint rides the plan like qs_token does — duck-typed
    # (any model exposing .plan_token() works) so plan never imports noise
    cm_token = None
    if crossbar_model is not None:
        tok = getattr(crossbar_model, "plan_token", None)
        cm_token = tok() if callable(tok) else None
    return PimPlan(layers=layers, backend=backend,
                   qs_token=quant_state_token(quant_state),
                   cm_token=cm_token)


def check_plan(plan: PimPlan, params: dict) -> PimPlan:
    """Stale-plan guard: verify every planned node still has a matching
    weight (same tree position, same logical (K, N)) in ``params``.  A plan
    built against different parameters (resized model, different arch)
    raises instead of silently computing on the wrong crossbar image."""
    def walk(pnode, tree, path):
        if isinstance(pnode, LayerPlan):
            w = tree.get("w") if isinstance(tree, dict) else None
            if w is None:
                raise ValueError(f"stale plan: no weight at {path!r}")
            if tuple(w.shape[-2:]) != (pnode.k, pnode.n):
                raise ValueError(
                    f"stale plan: {path!r} programmed for "
                    f"({pnode.k}, {pnode.n}) but params have "
                    f"{tuple(w.shape[-2:])}")
            return
        for key, sub in pnode.items():
            if not isinstance(tree, dict) or key not in tree:
                raise ValueError(f"stale plan: params have no subtree "
                                 f"{path + '/' + key!r}")
            walk(sub, tree[key], f"{path}/{key}" if path else key)
    walk(plan.layers, params, "")
    return plan


# ---------------------------------------------------------------------------
# prepared execution (the per-backend fast paths)
# ---------------------------------------------------------------------------

_PREPARED: dict = {}


def register_prepared(name: str):
    """Register the prepared fast path for backend ``name`` (decorator).
    Signature: ``fn(x, lp: LayerPlan, **knobs) -> PimOut``."""
    def _register(fn):
        _PREPARED[name] = fn
        return fn
    return _register


def run_prepared(x: jax.Array, lp: LayerPlan,
                 backend: Optional[str] = None, **knobs) -> PimOut:
    """Execute ``x @ w`` against a programmed crossbar image.  ``backend``
    (if given) must agree with ``lp.backend`` — prepared payloads are
    backend-specific."""
    if not isinstance(lp, LayerPlan):
        raise TypeError(f"plan= wants a LayerPlan, got {type(lp).__name__} "
                        "(pass the per-layer node, or thread a PimPlan "
                        "through the model apply_fn)")
    if backend is not None and backend != lp.backend:
        raise ValueError(f"plan was programmed for backend "
                         f"{lp.backend!r}, not {backend!r}; re-run "
                         f"prepare_params for the new datapath")
    try:
        fn = _PREPARED[lp.backend]
    except KeyError:
        raise KeyError(f"no prepared path registered for backend "
                       f"{lp.backend!r}; known: {sorted(_PREPARED)}") \
            from None
    if x.shape[-1] != lp.k:
        raise ValueError(f"stale plan: programmed K={lp.k}, activations "
                         f"have K={x.shape[-1]}")
    return fn(x, lp, **knobs)


@register_prepared("exact")
def _prepared_exact(x, lp: LayerPlan, **_) -> PimOut:
    # hoists only the dtype cast — which astype makes a free alias when
    # param and compute dtype already agree (the serving config), so an
    # exact plan never duplicates weights there
    return PimOut(x @ lp.w.astype(x.dtype), jnp.float32(0.0))


@register_prepared("fake_quant")
def _prepared_fake_quant(x, lp: LayerPlan, *, a_scale=None, w_scale=None,
                         ste: bool = False, **_) -> PimOut:
    # activation half of the dynamic scales; weight half frozen in the plan
    a_s, w_s = _dynamic_scales(x, None, a_scale,
                               w_scale if w_scale is not None
                               else lp.w_scale)
    grid = (jnp.asarray(a_s, jnp.float32) * jnp.asarray(w_s, jnp.float32)
            * lp.delta_grid)
    y, ops = fake_quant_mvm_grouped(
        group_activations(x, lp.pim), lp.w_g.astype(x.dtype), lp.trq, grid,
        x.dtype, ste=ste, auto_range=lp.auto_range, with_ops=True)
    return PimOut(y, ops)


@functools.partial(jax.jit, static_argnames=("block_m", "n", "interpret"))
def _pallas_prepared_exec(x2, w_f32, trq, grid, *, block_m: int, n: int,
                          interpret: bool):
    """jit'd tile launch for the prepared pallas path — eager callers would
    otherwise re-trace the Pallas interpreter per call (the dynamic wrapper
    is jitted the same way); inside an enclosing jit this inlines."""
    from repro.kernels.trq_group_mvm.kernel import trq_group_mvm_tiles
    m = x2.shape[0]
    pad_m = (-m) % block_m
    pad_k = w_f32.shape[0] - x2.shape[1]
    if pad_m or pad_k:
        x2 = jnp.pad(x2, ((0, pad_m), (0, pad_k)))
    y, ops = trq_group_mvm_tiles(x2, w_f32, trq, grid, block_m=block_m,
                                 block_n=128, interpret=interpret,
                                 with_ops=True)
    return y[:m, :n], jnp.sum(ops[:m, :n])


@register_prepared("pallas")
def _prepared_pallas(x, lp: LayerPlan, *, a_scale=None, w_scale=None,
                     interpret=None, **_) -> PimOut:
    from repro.kernels.runtime import resolve_interpret
    from repro.kernels.trq_group_mvm.ops import pick_block_m
    a_s, w_s = _dynamic_scales(x, None, a_scale,
                               w_scale if w_scale is not None
                               else lp.w_scale)
    grid = (jnp.asarray(a_s, jnp.float32) * jnp.asarray(w_s, jnp.float32)
            * lp.delta_grid)
    lead = x.shape[:-1]
    xf = x.astype(jnp.float32)
    trq = lp.trq
    if lp.auto_range:
        # pre-fit exactly like the dynamic backend, on the UNPADDED grouped
        # image and the un-flattened activations — identical einsum shapes
        # keep the fitted delta_r1 bit-identical to the dynamic fit
        trq = auto_range_fit_grouped(group_activations(xf, lp.pim), lp.w_g,
                                     trq, grid)
    x2 = xf.reshape(-1, lp.k)
    with jax.named_scope("convert"):
        y, ops = _pallas_prepared_exec(x2, lp.w_f32, trq, grid,
                                       block_m=pick_block_m(x2.shape[0]),
                                       n=lp.n,
                                       interpret=resolve_interpret(interpret))
    return PimOut(y.reshape(*lead, lp.n).astype(x.dtype), ops)


@register_prepared("bit_exact")
def _prepared_bit_exact(x, lp: LayerPlan, *, a_scale=None, w_scale=None,
                        **_) -> PimOut:
    if w_scale is not None:
        raise ValueError(
            "bit_exact plans cannot take a per-call w_scale override: the "
            "programmed cell planes ARE a function of the weight scale; "
            "re-run prepare_linear/prepare_params (or call the dynamic "
            "backend) for a pinned grid")
    pim = lp.pim
    half_a = 2 ** (pim.k_i - 1)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, lp.k).astype(jnp.float32)
    a_s = a_scale if a_scale is not None else \
        jnp.maximum(jnp.max(jnp.abs(x2)), 1e-6) * (1.0 / (half_a - 1))
    w_s = lp.w_scale
    a_int = jnp.clip(jnp.floor(x2 * _stable_recip(a_s) + 0.5),
                     -half_a, half_a - 1).astype(jnp.int32)
    out, ops = bit_exact_mvm(a_int + half_a, None, lp.trq, pim,
                             with_ops=True, u_planes=lp.w_planes)
    y = (out - half_a * lp.w_colsum) * (jnp.asarray(a_s, jnp.float32)
                                        * jnp.asarray(w_s, jnp.float32))
    return PimOut(y.reshape(*lead, lp.n).astype(x.dtype), ops)
