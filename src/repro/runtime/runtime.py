"""One explicit, compiled execution context: the ``Runtime`` object.

The paper's co-design story — flexible quantization algorithms riding on a
lightweight SAR-ADC datapath — used to live in four separately-threaded
pieces of ambient state (``use_mesh``, ``use_backend``, ``use_quant_state``,
``traced_ad_ops``) plus the explicitly-threaded ``PimPlan``, and every
consumer re-stacked those context managers by hand in a slightly different
order.  :func:`compile` folds all of it into ONE object:

    rt = repro.runtime.compile(cfg, params)        # resolve + program once
    (logits, cache, aux), report = rt.apply(batch) # report.ad_ops = Eq. 6

A ``Runtime`` owns the resolved mesh, the backend name (a
``repro.pim.backend`` registry entry), the per-layer ``QuantState`` register
file, the frozen weight-stationary ``PimPlan`` (the programmed crossbar
image), the sharded/placed parameters, and the entry points — the jit'd
``prefill`` / ``prefill_cont`` / ``decode`` / ``train_step`` / ``apply``
plus the eager single-layer ``mvm`` — each returning ``(out, AdOpsReport)``
so A/D-energy metering is a first-class output instead of a context-manager
side channel.

Internally the model code keeps its current contracts (``pim_linear`` still
resolves ambient state); the Runtime installs that ambient state in exactly
one audited place (:meth:`Runtime._ambient`), *force*-installing its own
backend/QuantState so explicit Runtime state always wins over any
``use_backend``/``use_quant_state`` a caller nested around an entry point.

``rt.with_overrides(backend=..., quant_state=...)`` returns a cheap derived
Runtime for A/B sweeps: parameters are shared, and the plan is shared too
when its (backend, QuantState, CrossbarModel) fingerprint still matches —
anything
plan-relevant that changed re-prepares (``check_plan``-guarded) instead of
running a stale crossbar image.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.energy import adc_energy_pj
from repro.core.quant_state import _ACTIVE as _QS_ACTIVE
from repro.core.quant_state import QuantState, active_quant_state
from repro.dist.sharding import param_pspecs, use_mesh
from repro.dist.sharding import _ACTIVE as _MESH_ACTIVE
from repro.pim.backend import _ACTIVE as _BACKEND_ACTIVE
from repro.pim.backend import active_backend, get_backend, traced_ad_ops
from repro.pim.noise import _ACTIVE as _CM_ACTIVE
from repro.pim.noise import (CrossbarModel, active_crossbar_model,
                             crossbar_token, is_noise_aware)
from repro.pim.plan import (PimPlan, check_plan, has_prepared,
                            prepare_params, quant_state_token, subplan)

_UNSET = object()


class AdOpsReport(NamedTuple):
    """First-class A/D-conversion accounting: the second half of every
    Runtime entry point's ``(out, AdOpsReport)`` return.  ``ad_ops`` is the
    summed SAR comparator-cycle count (Eq. 6) of every ``pim_mvm`` in the
    traced call — what ``traced_ad_ops`` used to smuggle out sideways."""

    ad_ops: jax.Array               # scalar f32

    def total(self) -> float:
        return float(self.ad_ops)

    @property
    def ad_energy_pj(self) -> float:
        """SAR conversion energy of the call (Eq. 6/9)."""
        return float(adc_energy_pj(float(self.ad_ops)))


class Runtime:
    """A compiled execution context (see module docstring).

    Construct through :func:`compile` (which resolves ambient defaults,
    validates/programs the plan, and places parameters) — ``__init__``
    itself is dumb on purpose so pytree unflattening never re-validates.
    Registered as a pytree: traced leaves are ``(params, plan,
    quant_state, crossbar_model)``; everything else is static aux data.
    """

    def __init__(self, cfg: ModelConfig, params, *, backend: str,
                 quant_state: Optional[QuantState], plan: Optional[PimPlan],
                 mesh=None, donate: bool = False,
                 tc: Optional[TrainConfig] = None,
                 fns: Optional[tuple] = None, plan_enabled: bool = True,
                 crossbar_model: Optional[CrossbarModel] = None):
        self.cfg = cfg
        self.params = params
        self.backend = backend
        self.quant_state = quant_state
        self.plan = plan
        self.crossbar_model = crossbar_model
        self.mesh = mesh
        self.donate = donate
        self.tc = tc
        self._plan_enabled = plan_enabled
        if fns is None:
            from repro.models.registry import build_model
            fns = build_model(cfg)
        self._fns = tuple(fns)
        self.init_fn, self.apply_fn, self.cache_fn = self._fns
        self._jits: dict = {}

    # -- identity / bookkeeping ---------------------------------------------

    @property
    def abstract(self) -> bool:
        """True when params are ShapeDtypeStructs (cell building / dry-run):
        entry points can only be lowered, not executed."""
        leaves = jax.tree_util.tree_leaves(self.params)
        return bool(leaves) and isinstance(leaves[0], jax.ShapeDtypeStruct)

    def __repr__(self) -> str:
        return (f"Runtime({self.cfg.name}, backend={self.backend!r}, "
                f"plan={'yes' if self.plan is not None else 'no'}, "
                f"quant_state={'yes' if self.quant_state is not None else 'no'}, "
                f"crossbar_model="
                f"{'yes' if self.crossbar_model is not None else 'no'}, "
                f"mesh={dict(self.mesh.shape) if self.mesh is not None else None})")

    # -- THE one audited ambient installation -------------------------------

    @contextlib.contextmanager
    def _ambient(self):
        """Install this Runtime's execution context for the dynamic extent.

        This is the single place the stack's ambient state gets stacked:
        the mesh (when the Runtime owns one), then the backend name and the
        QuantState — both FORCE-installed (``None`` included), so a
        ``use_backend``/``use_quant_state`` nested around a Runtime entry
        point never leaks into its trace: explicit Runtime state wins."""
        with contextlib.ExitStack() as stack:
            if self.mesh is not None:
                stack.enter_context(use_mesh(self.mesh))
            prev_b = _BACKEND_ACTIVE["backend"]
            prev_q = _QS_ACTIVE["qs"]
            prev_c = _CM_ACTIVE["cm"]
            _BACKEND_ACTIVE["backend"] = self.backend
            _QS_ACTIVE["qs"] = self.quant_state
            _CM_ACTIVE["cm"] = self.crossbar_model
            try:
                yield self
            finally:
                _BACKEND_ACTIVE["backend"] = prev_b
                _QS_ACTIVE["qs"] = prev_q
                _CM_ACTIVE["cm"] = prev_c

    def _jit(self, key, make: Callable):
        fn = self._jits.get(key)
        if fn is None:
            fn = self._jits[key] = make()
        return fn

    # -- jit'd entry points (each returns (out, AdOpsReport)) ---------------

    def _apply_jit(self, mode: str):
        def make():
            def step(params, plan, batch, cache):
                with self._ambient(), traced_ad_ops() as tally:
                    logits, new_cache, aux = self.apply_fn(
                        params, batch, cache=cache, mode=mode, plan=plan)
                    return logits, new_cache, aux, tally.value
            return jax.jit(step)
        return self._jit(("apply", mode), make)

    def apply(self, batch: dict, cache=None, mode: str = "train"):
        """The model forward: ``((logits, cache, aux), AdOpsReport)``."""
        logits, new_cache, aux, ops = self._apply_jit(mode)(
            self.params, self.plan, batch, cache)
        return (logits, new_cache, aux), AdOpsReport(ops)

    def prefill(self, tokens, extra: Optional[dict] = None, *, max_len: int):
        """Prompt forward writing a fresh ``max_len``-deep cache:
        ``((last_logits, cache), AdOpsReport)``.  ``tokens``: (B, plen)."""
        extra = extra or {}
        def make():
            def step(params, plan, tokens, extra):
                with self._ambient(), traced_ad_ops() as tally, \
                        jax.named_scope("runtime.prefill"):
                    cache = self.cache_fn(tokens.shape[0], max_len)
                    batch = {"tokens": tokens, **extra}
                    logits, cache, _ = self.apply_fn(
                        params, batch, cache=cache, mode="prefill", plan=plan)
                    return logits[:, -1], cache, tally.value
            return jax.jit(step)
        last, cache, ops = self._jit(("prefill", max_len), make)(
            self.params, self.plan, tokens, extra)
        return (last, cache), AdOpsReport(ops)

    def prefill_cont(self, tokens, positions, cache):
        """Continued prefill against a warm cache (prefix-reuse path):
        ``((last_logits, cache), AdOpsReport)``."""
        def make():
            def step(params, plan, tokens, positions, cache):
                with self._ambient(), traced_ad_ops() as tally, \
                        jax.named_scope("runtime.prefill_cont"):
                    batch = {"tokens": tokens, "positions": positions}
                    logits, cache, _ = self.apply_fn(
                        params, batch, cache=cache, mode="prefill_cont",
                        plan=plan)
                    return logits[:, -1], cache, tally.value
            return jax.jit(step)
        last, new_cache, ops = self._jit(("prefill_cont",), make)(
            self.params, self.plan, tokens, positions, cache)
        return (last, new_cache), AdOpsReport(ops)

    def decode(self, tokens, cache, extra: Optional[dict] = None):
        """One token for every sequence in ``cache``:
        ``((last_logits, new_cache), AdOpsReport)``."""
        extra = extra or {}
        def make():
            def step(params, plan, cache, tokens, extra):
                with self._ambient(), traced_ad_ops() as tally, \
                        jax.named_scope("runtime.decode"):
                    batch = {"tokens": tokens, **extra}
                    logits, cache, _ = self.apply_fn(
                        params, batch, cache=cache, mode="decode", plan=plan)
                    return logits[:, -1], cache, tally.value
            return jax.jit(step)
        last, new_cache, ops = self._jit(("decode",), make)(
            self.params, self.plan, cache, tokens, extra)
        return (last, new_cache), AdOpsReport(ops)

    # -- training -----------------------------------------------------------

    def _train_pair(self):
        """(pure step(params, opt, batch, i) -> (params, opt, metrics),
        opt_init) — metrics carries ``ad_ops`` so training meters too."""
        pair = self._jits.get(("train_pair",))
        if pair is None:
            from repro.train.loop import make_train_step
            tc = self.tc or TrainConfig()
            train_step, opt_init = make_train_step(self.apply_fn, self.cfg,
                                                   tc)

            def step(params, opt_state, batch, step_idx):
                with self._ambient(), traced_ad_ops() as tally:
                    params, opt_state, metrics = train_step(
                        params, opt_state, batch, step_idx)
                    return params, opt_state, dict(metrics,
                                                   ad_ops=tally.value)
            pair = self._jits[("train_pair",)] = (step, opt_init)
        return pair

    def opt_init(self, params=None):
        """Optimizer state for the Runtime's ``TrainConfig``."""
        return self._train_pair()[1](
            self.params if params is None else params)

    def train_step(self, params, opt_state, batch, step):
        """One optimizer step: ``((params, opt_state, metrics),
        AdOpsReport)``.  Functional in ``params`` so the caller (e.g.
        ``train.loop.Trainer``) owns the buffer lifecycle; ``donate=True``
        at compile donates params/opt_state."""
        def make():
            donate = (0, 1) if self.donate else ()
            return jax.jit(self._train_pair()[0], donate_argnums=donate)
        p, o, m = self._jit(("train_step",), make)(params, opt_state, batch,
                                                   step)
        return (p, o, m), AdOpsReport(m["ad_ops"])

    def train_setup(self, *, moe_ffn_shard_data: bool = False):
        """Sharded training assembly for the launchers: returns
        ``(jitted_step, opt_init, p_sh, o_sh)`` with ZeRO-1 optimizer
        shardings and (when ``donate``) donated params/opt buffers.  The
        jitted step keeps the classic ``(params, opt, batch, i) ->
        (params, opt, metrics)`` contract; ``metrics['ad_ops']`` carries
        the step's conversion count."""
        if self.mesh is None:
            raise ValueError("train_setup needs a mesh-owning Runtime; "
                             "compile(..., mesh=...) or enter use_mesh first")
        from repro.train.loop import shardings_for
        step, opt_init = self._train_pair()
        with self._ambient():
            opt_s = jax.eval_shape(opt_init, self.params)
            p_sh, o_sh = shardings_for(self.mesh, self.params, opt_s,
                                       self.tc or TrainConfig(),
                                       moe_ffn_shard_data=moe_ffn_shard_data)
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, None, None),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1) if self.donate else ())
        return jitted, opt_init, p_sh, o_sh

    # -- cell derivation (launch.steps / dry-run) ---------------------------

    def serve_cell_step(self, kind: str, batch_size: int, seq_len: int):
        """Pure ``(params, plan, [cache,] batch)`` step function for a
        launch-cell: same contract the dry-run lowers, with the ambient
        contexts installed by the Runtime's one audited place."""
        if kind == "prefill":
            def step(params, plan, batch):
                with self._ambient():
                    cache = self.cache_fn(batch_size, seq_len)
                    logits, new_cache, _ = self.apply_fn(
                        params, batch, cache=cache, mode="prefill", plan=plan)
                    return jnp.argmax(logits[:, -1], -1), new_cache
            return step

        def step(params, plan, cache, batch):
            with self._ambient():
                logits, new_cache, _ = self.apply_fn(
                    params, batch, cache=cache, mode="decode", plan=plan)
                return jnp.argmax(logits[:, -1], -1), new_cache
        return step

    def train_cell_step(self, tc: TrainConfig):
        """Pure ``(params, opt_state, batch, step)`` train-cell step +
        ``opt_init`` (no ad-ops plumbing: the lowered HLO matches the
        pre-Runtime cells exactly)."""
        from repro.train.loop import make_train_step
        train_step, opt_init = make_train_step(self.apply_fn, self.cfg, tc)

        def step(params, opt_state, batch, step_idx):
            with self._ambient():
                return train_step(params, opt_state, batch, step_idx)
        return step, opt_init

    def lower(self, batch: dict, cache=None, mode: str = "train"):
        """Lower the jit'd ``apply`` entry for (possibly abstract) inputs —
        what the launch cells are derived from; works on an ``abstract``
        Runtime built from ``jax.eval_shape`` parameter stand-ins."""
        return self._apply_jit(mode).lower(self.params, self.plan, batch,
                                           cache)

    # -- single-layer MVM ----------------------------------------------------

    def mvm(self, x, layer: str):
        """Run ONE layer's MVM on the Runtime's datapath: ``(y,
        AdOpsReport)``.  ``layer`` is the param-path name the QuantState
        rule table uses (``layer_3/attn/wq``, ``dec/mlp/w_up``,
        ``lm_head``); scanned layer stacks resolve ``layer_<depth>`` to the
        right period slice.  Uses the prepared ``LayerPlan`` when the plan
        holds one, else the dynamic path with QuantState-resolved
        registers — the two are bitwise identical for activations in the
        model's compute dtype (the plan freezes weights at that dtype,
        exactly like the in-model call).

        Executes EAGERLY (matching the eager reference paths the parity
        suite pins it against); wrap in ``jax.jit`` yourself when sweeping
        one layer at volume."""
        from repro.models.layers import pim_linear
        node, lp = self._layer_node(layer)
        with self._ambient(), traced_ad_ops() as tally:
            y = pim_linear(node, x, self.cfg, name=layer, plan=lp)
            return y, AdOpsReport(tally.value)

    def _layer_node(self, name: str):
        """Resolve a QuantState-style layer name to its (param node,
        LayerPlan) pair, slicing stacked (scanned) families by depth."""
        parts = name.split("/")
        params, pl, depth = self.params, self.plan, 0
        if parts[0].startswith("layer_") and "periods" in params:
            idx = int(parts[0].split("_", 1)[1])
            lkey = f"layer_{idx % self.cfg.period}"
            depth = idx // self.cfg.period
            params = params["periods"][lkey]
            pl = subplan(subplan(pl, "periods"), lkey)
            parts = parts[1:]
        elif parts[0] in ("enc", "dec") and parts[0] in params:
            params, pl = params[parts[0]], subplan(pl, parts[0])
            parts = parts[1:]
        for part in parts:
            if not isinstance(params, dict) or part not in params:
                raise KeyError(f"no layer {name!r} in the parameter tree")
            params, pl = params[part], subplan(pl, part)
        if not isinstance(params, dict) or "w" not in params:
            raise KeyError(f"{name!r} does not name a pim_linear weight node")
        node = params
        if node["w"].ndim == 3:                       # stacked layer family
            node = jax.tree.map(lambda t: t[depth], node)
            if pl is not None:
                pl = jax.tree.map(lambda t: t[depth], pl)
        return node, pl

    # -- derivation / persistence -------------------------------------------

    def with_overrides(self, *, backend: Optional[str] = None,
                       quant_state=_UNSET, plan=_UNSET,
                       mesh=_UNSET, donate: Optional[bool] = None,
                       crossbar_model=_UNSET) -> "Runtime":
        """A cheap derived Runtime for A/B sweeps: parameters are shared,
        and the programmed plan is shared when its (backend,
        QuantState-fingerprint, CrossbarModel-fingerprint) still matches —
        otherwise it re-prepares (``check_plan``-guarded) instead of
        executing a stale crossbar image.  This replaces re-entering
        ``use_backend`` around every sweep arm.

        Overrides here are taken LITERALLY — ``quant_state=None`` means "no
        registers" and ``crossbar_model=None`` means "ideal device" (never
        re-resolved from an ambient context; omit the argument to keep
        this Runtime's state)."""
        new_backend = backend or self.backend
        if backend is not None:
            get_backend(new_backend)               # fail fast on typos
        new_qs = self.quant_state if quant_state is _UNSET else quant_state
        new_cm = self.crossbar_model if crossbar_model is _UNSET \
            else crossbar_model
        _check_model_backend(new_backend, new_cm)
        if plan is _UNSET:
            plan_enabled = self._plan_enabled
            if (self.plan is not None and self.plan.backend == new_backend
                    and self.plan.qs_token == quant_state_token(new_qs)
                    and self.plan.cm_token == crossbar_token(new_cm)):
                built = check_plan(self.plan, self.params)   # still valid
            elif self._plan_enabled:
                built = _build_plan(self.cfg, self.params, new_backend,
                                    new_qs, True, self.abstract, new_cm)
            else:
                built = None
        else:
            plan_enabled = plan is True or isinstance(plan, PimPlan)
            built = _build_plan(self.cfg, self.params, new_backend, new_qs,
                                plan, self.abstract, new_cm)
        return Runtime(self.cfg, self.params,
                       backend=new_backend, quant_state=new_qs, plan=built,
                       mesh=self.mesh if mesh is _UNSET else mesh,
                       donate=self.donate if donate is None else donate,
                       tc=self.tc, fns=self._fns, plan_enabled=plan_enabled,
                       crossbar_model=new_cm)

    def save(self, path: str) -> Optional[str]:
        """Snapshot the Runtime's register file next to a checkpoint
        (versioned ``quant_state.json``); returns the written path, or
        ``None`` when the Runtime carries no QuantState."""
        if self.quant_state is None:
            return None
        from repro.core.quant_state import save_quant_state
        return save_quant_state(path, self.quant_state)

    def _aux(self):
        return (self.cfg, self.backend, self.mesh, self.donate, self.tc,
                self._plan_enabled, self._fns)


def _rt_flatten(rt: Runtime):
    return (rt.params, rt.plan, rt.quant_state,
            rt.crossbar_model), rt._aux()


def _rt_unflatten(aux, children):
    cfg, backend, mesh, donate, tc, plan_enabled, fns = aux
    params, plan, qs, cm = children
    return Runtime(cfg, params, backend=backend, quant_state=qs, plan=plan,
                   mesh=mesh, donate=donate, tc=tc, fns=fns,
                   plan_enabled=plan_enabled, crossbar_model=cm)


jax.tree_util.register_pytree_node(Runtime, _rt_flatten, _rt_unflatten)


def _check_model_backend(backend: str, crossbar_model) -> None:
    """A non-null CrossbarModel on a noise-blind backend would be silently
    ignored — every MVM would run ideal while the caller believes faults
    are injected.  Reject the combination loudly."""
    if (crossbar_model is not None and not crossbar_model.is_null
            and not is_noise_aware(backend)):
        raise ValueError(
            f"crossbar_model carries non-idealities but backend "
            f"{backend!r} is not noise-aware (it would silently ignore "
            f"them); use backend='noisy' (or register_noise_aware)")


def _build_plan(cfg, params, backend: str, quant_state, plan, abstract: bool,
                crossbar_model=None):
    """Resolve the ``plan`` argument for a (backend, quant_state,
    crossbar_model) triple: ``True`` programs (best-effort, eval-shaped when
    abstract), a prebuilt ``PimPlan`` is validated against backend /
    QuantState fingerprint / CrossbarModel fingerprint / geometry, anything
    else is dynamic (``None``)."""
    if plan is True:
        if not has_prepared(backend):
            return None
        prep = lambda p: prepare_params(p, cfg, quant_state=quant_state,
                                        backend=backend,
                                        crossbar_model=crossbar_model)  # noqa: E731
        return jax.eval_shape(prep, params) if abstract else prep(params)
    if isinstance(plan, PimPlan):
        if plan.backend != backend:
            raise ValueError(
                f"plan was programmed for backend {plan.backend!r} but the "
                f"Runtime executes {backend!r} — every pim_linear would "
                f"silently fall back to the dynamic path; re-run "
                f"prepare_params (or compile with plan=True)")
        if plan.qs_token != quant_state_token(quant_state):
            raise ValueError(
                "plan was programmed against a different QuantState than "
                "this Runtime executes — prepared registers would silently "
                "diverge from the dynamic datapath; re-run prepare_params "
                "with the Runtime's register file")
        if plan.cm_token != crossbar_token(crossbar_model):
            raise ValueError(
                "plan was programmed against a different CrossbarModel "
                "(or fault seed) than this Runtime executes — the baked "
                "fault image would be stale; re-run prepare_params with "
                "the Runtime's crossbar_model")
        return check_plan(plan, params)
    return None


def compile(cfg: ModelConfig, params, *, mesh=None, backend: Optional[str] = None,
            quant_state: Optional[QuantState] = None, plan: Any = True,
            donate: bool = False, tc: Optional[TrainConfig] = None,
            fns: Optional[tuple] = None, place: bool = True,
            moe_ffn_shard_data: bool = False,
            crossbar_model: Optional[CrossbarModel] = None) -> Runtime:
    """Build a :class:`Runtime`: resolve the execution context once,
    program the crossbars once, return jit'd entry points.

    Resolution (explicit argument > ambient context > config default):

    * ``mesh``        — argument, else the active ``use_mesh`` mesh, else
      none (single-host; ``shard()`` no-ops).
    * ``backend``     — argument, else the active ``use_backend`` name,
      else ``cfg.pim_backend``.  Must name a registered datapath.
    * ``quant_state`` — argument, else the active ``use_quant_state``
      register file, else none (model-wide ``cfg.trq`` default).
    * ``crossbar_model`` — argument, else the active ``use_crossbar_model``
      device model, else none (ideal crossbars).  A non-null model
      requires a noise-aware backend (``noisy``); weight-side faults are
      baked into the plan (fingerprinted via ``cm_token``), read/ADC
      noise draws per call.
    * ``plan``        — ``True`` (default) programs a weight-stationary
      ``PimPlan`` for the resolved backend/registers (best-effort: a
      custom backend without a prepared path serves dynamically);
      a prebuilt ``PimPlan`` is validated against the resolved backend,
      QuantState fingerprint, and parameter geometry; ``False``/``None``
      serves dynamically.

    ``params`` may be ``jax.eval_shape`` ShapeDtypeStructs, giving an
    ``abstract`` Runtime whose entry points can be lowered but not run
    (cell building / 256-chip dry-run).  Concrete params are placed onto
    the mesh's parameter shardings unless ``place=False``.
    """
    if mesh is None:
        mesh = _MESH_ACTIVE["mesh"]
    backend = backend or active_backend() or cfg.pim_backend
    get_backend(backend)                           # fail fast on typos
    if quant_state is None:
        quant_state = active_quant_state()
    if crossbar_model is None:
        crossbar_model = active_crossbar_model()
    _check_model_backend(backend, crossbar_model)

    leaves = jax.tree_util.tree_leaves(params)
    abstract = bool(leaves) and isinstance(leaves[0], jax.ShapeDtypeStruct)

    plan_enabled = plan is True or isinstance(plan, PimPlan)
    built = _build_plan(cfg, params, backend, quant_state, plan, abstract,
                        crossbar_model)

    if place and mesh is not None and not abstract:
        from jax.sharding import NamedSharding
        with use_mesh(mesh):
            pspecs = param_pspecs(params,
                                  moe_ffn_shard_data=moe_ffn_shard_data)
        params = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs))

    return Runtime(cfg, params, backend=backend, quant_state=quant_state,
                   plan=built, mesh=mesh, donate=donate, tc=tc, fns=fns,
                   plan_enabled=plan_enabled, crossbar_model=crossbar_model)
