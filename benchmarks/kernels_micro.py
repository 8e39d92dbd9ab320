"""Kernel micro-benchmarks: Pallas kernels vs jnp oracle.

The kernels run with their default ``interpret=None``: compiled on a TPU,
in Pallas interpret mode everywhere else.  Off the TPU the timings measure
Python emulation, NOT TPU performance — the numbers exist to (a) prove the
kernels run, and (b) regression-track the oracle path.  The record names
(``*.pallas_interp``) are stable keys of the committed baselines."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.trq import make_params, trq_quant
from repro.kernels import (trq_group_mvm_pallas, trq_quant_pallas,
                           xbar_mvm_pallas)
from repro.pim import list_backends, pim_mvm, prepare_linear
from repro.pim.crossbar import bit_exact_mvm, fake_quant_mvm

from .common import emit, timeit


def run(quick: bool = False) -> dict:
    """Prints the CSV lines and returns JSON-ready records:
    ``{name: {"us": float, "derived": str, "mean_ad_ops": float?}}`` —
    the kernels lane of the CI regression gate (mean_ad_ops is
    deterministic; the interpret-mode timings are trajectory-only)."""
    records: dict = {}

    def rec(name, us, derived="", **extra):
        emit(name, us, derived)
        records[name] = {"us": float(us), "derived": derived, **extra}

    rng = np.random.default_rng(0)
    p = make_params(delta_r1=1.0, n_r1=4, n_r2=4, m=3, signed=True)

    x = jnp.asarray(rng.normal(0, 30, (256, 256)).astype(np.float32))
    us = timeit(lambda v: trq_quant_pallas(v, p), x,
                iters=3 if quick else 5)
    us_ref = timeit(lambda v: trq_quant(v, p), x, iters=3 if quick else 5)
    rec("kernel.trq_quant.pallas_interp", us, "shape=256x256")
    rec("kernel.trq_quant.jnp_oracle", us_ref, "shape=256x256")

    a = jnp.asarray(rng.normal(0, 1, (128, 512)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 1, (512, 128)).astype(np.float32))
    us = timeit(lambda aa, ww: trq_group_mvm_pallas(aa, ww, p, 0.05, 1.0),
                a, w, iters=2 if quick else 4)
    us_ref = timeit(lambda aa, ww: fake_quant_mvm(aa, ww, p, 0.05, 1.0),
                    a, w, iters=2 if quick else 4)
    rec("kernel.trq_group_mvm.pallas_interp", us, "m128.k512.n128")
    rec("kernel.trq_group_mvm.jnp_oracle", us_ref, "m128.k512.n128")

    # -- decode-shaped sweeps: M = active batch (single-token serving) -----
    # auto block_m picks the {8,16,32,64}-row tile covering M instead of
    # padding to 128; the pad128 record is the pre-plan-cache equivalent,
    # kept as the speedup denominator (identical numerics, only padding)
    wd = jnp.asarray(rng.normal(0, 1, (512, 128)).astype(np.float32))
    for m in (1, 8, 16):
        ad = jnp.asarray(rng.normal(0, 1, (m, 512)).astype(np.float32))
        us = timeit(lambda a_, w_: trq_group_mvm_pallas(a_, w_, p, 0.05, 1.0),
                    ad, wd, iters=3 if quick else 5)
        rec(f"kernel.trq_group_mvm.decode_m{m}", us, f"m{m}.k512.n128.auto")
        if m == 8:
            us = timeit(
                lambda a_, w_: trq_group_mvm_pallas(a_, w_, p, 0.05, 1.0,
                                                    block_m=128),
                ad, wd, iters=3 if quick else 5)
            rec("kernel.trq_group_mvm.decode_m8_pad128", us,
                "m8.k512.n128.block_m128")

    ai = jnp.asarray(rng.integers(0, 256, (16, 128)).astype(np.int32))
    wi = jnp.asarray(rng.integers(-128, 128, (128, 16)).astype(np.int32))
    us = timeit(lambda aa, ww: xbar_mvm_pallas(aa, ww, p)[0],
                ai, wi, iters=2 if quick else 3)
    us_ref = timeit(lambda aa, ww: bit_exact_mvm(aa, ww, p), ai, wi,
                    iters=2 if quick else 3)
    rec("kernel.xbar_mvm.pallas_interp", us, "m16.k128.n16.8x8planes")
    rec("kernel.xbar_mvm.jnp_oracle", us_ref, "m16.k128.n16.8x8planes")

    # -- registered-backend sweep: one shape, every datapath ---------------
    # same MVM through the whole repro.pim.backend registry so BENCH_*.json
    # tracks the fast path (pallas) against the oracle paths over time.
    # bit_exact (and noisy, which wraps its datapath) runs lossless (its
    # registers live on the raw BL grid) and a smaller shape — it is
    # O(k_i*k_w*G) matmuls by design.
    mb, kb, nb = (32, 256, 32) if quick else (64, 512, 64)
    ab = jnp.asarray(rng.normal(0, 1, (mb, kb)).astype(np.float32))
    wb = jnp.asarray(rng.normal(0, 1, (kb, nb)).astype(np.float32))
    ab_s = ab[: mb // 2, :128]
    wb_s = wb[:128, : nb // 2]
    shape_note = f"m{mb}.k{kb}.n{nb}"
    for name in list_backends():
        small = name in ("bit_exact", "noisy")
        aa, ww = (ab_s, wb_s) if small else (ab, wb)
        trq = None if small else p
        us = timeit(lambda a_, w_: pim_mvm(a_, w_, trq, backend=name).y,
                    aa, ww, iters=2 if quick else 3)
        out = pim_mvm(aa, ww, trq, backend=name)
        conv = (aa.shape[0] * ww.shape[1]
                * -(-aa.shape[1] // 128) * (64 if small else 1))
        mean_ops = float(out.ad_ops) / conv
        note = (f"m{aa.shape[0]}.k{aa.shape[1]}.n{ww.shape[1]}"
                if small else shape_note)
        rec(f"backend.{name}.mvm", us,
            f"{note}.mean_ad_ops={mean_ops:.2f}", mean_ad_ops=mean_ops)
        # prepared fast path: weight-side state frozen by the plan cache.
        # Bitwise-identical to the dynamic record above (mean_ad_ops must
        # match exactly — gated by check_regression)
        lp = prepare_linear(ww, trq, backend=name)
        us = timeit(lambda a_, l_=lp: pim_mvm(a_, plan=l_).y,
                    aa, iters=2 if quick else 3)
        pout = pim_mvm(aa, plan=lp)
        rec(f"backend.{name}.mvm_prepared", us,
            f"{note}.plan.mean_ad_ops={float(pout.ad_ops) / conv:.2f}",
            mean_ad_ops=float(pout.ad_ops) / conv)

    # -- Runtime front door: rt.mvm through a compiled execution context ---
    # the same prepared datapath as backend.fake_quant.mvm_prepared, but
    # reached through repro.runtime (plan lookup + ambient install + report
    # wrapping) — tracks the public-API overhead over the raw call
    import jax as _jax
    from repro import runtime as _runtime
    from repro.models.registry import build_model, get_config
    lm_cfg = get_config("llama3.2-3b", smoke=True).replace(
        remat="none", pim_backend="fake_quant")
    lm_params = build_model(lm_cfg)[0](_jax.random.PRNGKey(0))
    rt = _runtime.compile(lm_cfg, lm_params)
    xr = jnp.asarray(rng.normal(0, 1, (8, lm_cfg.d_model)).astype(np.float32))
    us = timeit(lambda a_: rt.mvm(a_, layer="layer_0/attn/wq")[0],
                xr, iters=2 if quick else 3)
    rout, _rep = rt.mvm(xr, layer="layer_0/attn/wq")
    conv = xr.shape[0] * rout.shape[-1] * -(-xr.shape[1] // 128)
    rec("runtime.mvm.fake_quant", us,
        f"m8.k{lm_cfg.d_model}.n{rout.shape[-1]}.plan."
        f"mean_ad_ops={float(_rep.ad_ops) / conv:.2f}",
        mean_ad_ops=float(_rep.ad_ops) / conv)

    # -- robustness lane: Monte-Carlo accuracy-under-noise records ---------
    # (zero-noise bitwise-identity + divergence curves; same JSON, same
    # check_regression gate — see benchmarks/noise_sweep.py)
    from . import noise_sweep
    records.update(noise_sweep.run(quick))
    return records


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the records as JSON "
                         "(e.g. BENCH_kernels.json)")
    args = ap.parse_args(argv)
    records = run(args.quick)
    if args.json:
        import os
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"bench": "kernels", "quick": args.quick,
                       "records": records}, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
