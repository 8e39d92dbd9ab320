#!/usr/bin/env python3
"""Chip smoke: run the serving path on one TPU at llama3.2-3b's widths.

    python chip_smoke.py             # one chip: serve + one-layer agreement
    python chip_smoke.py --chips 4   # four chips: DP + ZeRO-1 train step only

One chip runs four phases in this one process:

  (a) device check: the platform must be ``tpu`` (no CPU fallback);
  (b) serve on ``fake_quant`` through ``launch.serve``'s engine builder at
      all 28 layers: 4 requests x 128-token prompts x 16 new tokens;
  (c) the same on ``pallas`` with the compiled Mosaic kernel, depth cut to
      what one chip holds (:data:`PALLAS_LAYERS`), widths kept;
  (d) one attention and one MLP projection through ``rt.mvm`` on
      ``exact``, ``fake_quant`` and ``pallas``, checked against each other.

``--chips 4`` runs only ``launch.train``'s data-parallel + ZeRO-1 step on a
(4, 1) mesh and the same steps on one device, and compares the losses.

Every reading printed is a smoke reading, not a benchmark metric.  Any
failed check raises, so the process exits non-zero; the last line of
stdout is ``{"ok": true, "device": {...}}`` only when every phase passed.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs to /tmp else

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import runtime  # noqa: E402
from repro.dist.sharding import use_mesh  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import build_engine, submit_random  # noqa: E402
from repro.launch.steps import make_train_config  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models.registry import build_model, get_config  # noqa: E402

ARCH = "llama3.2-3b"

# The pallas plan keeps float32 weight images (the K/N-padded tile image and,
# for auto-ranged layers, a grouped copy): 8 B per linear weight beside the
# 2 B bf16 params.  Compiled for a described v5e (15.75 GB HBM), the
# 128-token prefill / 4-slot decode steps give
#   layers  params  plan   temp   (GB)
#     12     3.20   9.66   2.52   -> 15.4, no headroom
#     10     2.80   8.05   2.10   -> 13.0
#     28     6.43  22.5    --     -> compiler: 27 GB used of 15.75
# so the pallas phase keeps 10 of 28 layers at full width.
PALLAS_LAYERS = 10

# f32 params + AdamW moments + activations at batch 8 x 128 tokens, compiled
# for one described v5e: 2 layers -> 7.1 GB args + 3.7 GB temp; 4 layers ->
# 9.6 + 5.3 GB.  The one-device reference must hold it all, so 2 layers.
TRAIN_LAYERS = 2

# pallas vs fake_quant on one layer: the same quantizer on the same grid,
# but the kernel accumulates each crossbar group's partial sum in its own
# f32 order, so a sum on a quantizer boundary can land one step apart (a
# few elements move by one grid step, a few conversions change cycle
# count).  Relative L2 of y, and relative A/D-op count:
PALLAS_Y_RTOL = 1e-3
PALLAS_OPS_RTOL = 1e-4
# both vs exact: the TRQ error tests/test_models.py accepts (corr > 0.98)
EXACT_MIN_CORR = 0.98
# DP over 4 chips vs one device, same global batch: sharded bf16 reductions
# (the forward pass's and the gradient all-reduce) sum in another order.  On
# a v5e 2x2 host the losses already differ by 2.1e-4 relative at step 0.
LOSS_RTOL = 2e-3

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def require(ok: bool, msg: str) -> None:
    """A smoke check: raise (and so exit non-zero) when it fails."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


@contextlib.contextmanager
def compile_clock():
    """Seconds JAX spends tracing, lowering and compiling in the block
    (``clock["s"]``), from its own monitoring events."""
    clock = {"s": 0.0}

    def listen(event, secs, **_):
        if event in _COMPILE_EVENTS:
            clock["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield clock
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _mem(key: str, dev=None):
    stats = (dev or jax.devices()[0]).memory_stats() or {}
    return stats.get(key, "n/a")


def check_device(chips: int = 1) -> dict:
    """Phase (a): the device JAX found, as the last line reports it."""
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev['platform']!r}); this script has no CPU fallback")
    if dev["count"] < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX found {dev['count']}")
    return dev


def serve_phase(cfg, *, requests: int = 4, prompt_len: int = 128,
                max_new: int = 16, max_len: int = 512, max_batch: int = 4,
                kernel: bool = False) -> dict:
    """Phases (b)/(c): serve ``requests`` prompts through ``launch.serve``'s
    engine builder and check every answer.  ``kernel`` also requires the
    decode step's compiled text to hold the Mosaic kernel."""
    label = f"serve[{cfg.pim_backend}]"
    finite = []

    def checked(entry):
        def call(*a, **kw):
            (last, cache), rep = entry(*a, **kw)
            finite.append(bool(jnp.isfinite(last).all()))
            return (last, cache), rep
        return call

    t0 = time.perf_counter()
    with compile_clock() as clock, use_mesh(make_host_mesh()):
        engine = build_engine(cfg, max_batch=max_batch, max_len=max_len)
        rt = engine.rt
        # the logits the engine samples from come out of these entry points
        for name in ("prefill", "prefill_cont", "decode"):
            setattr(rt, name, checked(getattr(rt, name)))
        submit_random(engine, requests=requests, prompt_len=prompt_len,
                      max_new=max_new)
        done = engine.run()
    st = engine.stats()
    out = {"layers": cfg.n_layers, "tokens": st["decode_tokens"],
           "mean_ttft_s": st["mean_ttft_s"], "total_ad_ops": st[
               "total_ad_ops"], "compile_s": clock["s"],
           "wall_s": time.perf_counter() - t0,
           "bytes_in_use": _mem("bytes_in_use"),
           "process_peak_bytes_in_use": _mem("peak_bytes_in_use")}
    print(f"{label} smoke reading (not a benchmark): " + json.dumps(out),
          flush=True)
    require(len(done) == requests, f"{label}: {len(done)}/{requests} done")
    short = [len(r.generated) for r in done if len(r.generated) != max_new]
    require(not short, f"{label}: requests returned {short} tokens, "
                       f"not {max_new}")
    require(finite and all(finite), f"{label}: non-finite logits in "
                                    f"{finite.count(False)} engine calls")
    ops = out["total_ad_ops"]
    require(np.isfinite(ops) and ops > 0, f"{label}: total_ad_ops={ops}")
    if kernel:
        tokens = jax.ShapeDtypeStruct((max_batch, 1), jnp.int32)
        cache = jax.eval_shape(lambda: rt.cache_fn(max_batch, max_len))
        hlo = rt.lower({"tokens": tokens}, cache, mode="decode") \
            .compile().as_text()
        require("tpu_custom_call" in hlo,
                f"{label}: decode step holds no tpu_custom_call (the "
                f"Pallas kernel was interpreted, not compiled)")
        print(f"{label}: decode HLO contains tpu_custom_call", flush=True)
    return out


def pallas_cut(cfg):
    """The pallas phase's config: every width kept, depth cut to fit."""
    kept = min(cfg.n_layers, PALLAS_LAYERS)
    print(f"pallas depth cut: n_layers {kept} of {cfg.n_layers} kept "
          f"(float32 plan images: 28 layers need ~27 GB of one chip's "
          f"15.75 GB; 10 layers compile to ~13 GB)", flush=True)
    return cfg.replace(n_layers=kept, pim_backend="pallas")


def agreement_phase(cfg, *, rows: int = 8, seed: int = 0) -> dict:
    """Phase (d): one attention and one MLP projection through ``rt.mvm``
    on exact, fake_quant and pallas at ``cfg``'s widths (one layer)."""
    cfg1 = cfg.replace(n_layers=1)
    params = build_model(cfg1)[0](jax.random.PRNGKey(seed))
    rts = {b: runtime.compile(cfg1.replace(pim_backend=b), params)
           for b in ("exact", "fake_quant", "pallas")}
    cdt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(seed)
    out = {}
    for layer, k in (("layer_0/attn/wq", cfg.d_model),
                     ("layer_0/mlp/w_down", cfg.d_ff)):
        x = jnp.asarray(rng.normal(0, 1, (rows, k)), cdt)
        res = {b: rt.mvm(x, layer=layer) for b, rt in rts.items()}
        y = {b: np.asarray(r[0], np.float32) for b, r in res.items()}
        ops = {b: float(r[1].ad_ops) for b, r in res.items()}
        y_err = float(np.linalg.norm(y["pallas"] - y["fake_quant"])
                      / np.linalg.norm(y["fake_quant"]))
        ops_err = abs(ops["pallas"] - ops["fake_quant"]) / ops["fake_quant"]
        corr = {b: float(np.corrcoef(y[b].ravel(), y["exact"].ravel())[0, 1])
                for b in ("fake_quant", "pallas")}
        rec = {"shape": [rows, k, int(y["exact"].shape[-1])],
               "y_rel_l2_pallas_vs_fq": y_err,
               "ad_ops_rel_pallas_vs_fq": ops_err,
               "ad_ops_fake_quant": ops["fake_quant"],
               "corr_vs_exact": corr}
        print(f"agree[{layer}] smoke reading: " + json.dumps(rec),
              flush=True)
        require(y_err <= PALLAS_Y_RTOL, f"{layer}: pallas y off fake_quant "
                f"by {y_err:.3g} > {PALLAS_Y_RTOL}")
        require(ops_err <= PALLAS_OPS_RTOL, f"{layer}: pallas ad_ops off "
                f"fake_quant by {ops_err:.3g} > {PALLAS_OPS_RTOL}")
        for b, c in corr.items():
            require(c > EXACT_MIN_CORR, f"{layer}: {b} vs exact corr "
                    f"{c:.4f} <= {EXACT_MIN_CORR}")
        out[layer] = rec
    return out


def train_phase(cfg, *, chips: int = 4, steps: int = 3, batch: int = 8,
                seq: int = 128) -> dict:
    """``--chips 4``: ``launch.train``'s DP + ZeRO-1 step on a (chips, 1)
    mesh, then the same steps on one device; per-step losses must agree."""
    tc = make_train_config(cfg.name, total_steps=steps, warmup_steps=1)
    losses, opt_bytes = {}, {}
    for n in (chips, 1):
        with compile_clock() as clock:
            _, opt_state, report = train(
                cfg, tc, make_host_mesh(jax.devices()[:n]), steps=steps,
                batch=batch, seq=seq, log_every=1)
        losses[n] = [row["loss"] for row in report["history"]]
        held = {}
        for leaf in jax.tree.leaves(opt_state):
            for sh in leaf.addressable_shards:
                held[sh.device.id] = held.get(sh.device.id, 0) \
                    + sh.data.nbytes
        opt_bytes[n] = held
        in_use = {d.id: _mem("bytes_in_use", d) for d in jax.devices()[:n]}
        print(f"train[{n} device(s)] smoke reading: " + json.dumps(
            {"losses": losses[n], "compile_s": clock["s"],
             "opt_state_bytes_by_device": held,
             "bytes_in_use_by_device": in_use}), flush=True)
        del opt_state, report
        gc.collect()
    rel = [abs(a - b) / abs(b) for a, b in zip(losses[chips], losses[1])]
    print(f"train loss agreement: max rel diff {max(rel):.3g} "
          f"(limit {LOSS_RTOL})", flush=True)
    require(len(losses[chips]) == len(losses[1]) == steps, str(losses))
    require(all(np.isfinite(losses[chips])), str(losses))
    require(max(rel) <= LOSS_RTOL, f"losses disagree: {losses}")
    # ZeRO-1: each device holds its 1/chips slice (norm scales replicate)
    one = sum(opt_bytes[1].values())
    require(max(opt_bytes[chips].values()) <= 1.01 * one / chips,
            f"optimizer state not spread over the data axis: {opt_bytes}")
    return {"losses": losses, "opt_state_bytes": opt_bytes}


def release() -> None:
    """Drop the previous phase's Runtime, plan and executables."""
    jax.clear_caches()
    gc.collect()
    print(f"released: bytes_in_use={_mem('bytes_in_use')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the DP + ZeRO-1 train path")
    args = ap.parse_args(argv)
    dev = check_device(args.chips)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        train_phase(get_config(ARCH).replace(pim_backend="fake_quant",
                                             n_layers=TRAIN_LAYERS))
    else:
        # exactly the config launch.serve builds for --arch llama3.2-3b
        cfg = get_config(ARCH).replace(param_dtype="bfloat16", remat="none")
        serve_phase(cfg.replace(pim_backend="fake_quant"))
        release()
        serve_phase(pallas_cut(cfg), kernel=True)
        release()
        agreement_phase(cfg)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
