#!/usr/bin/env python3
"""Compile a configuration's serving programs for a described TPU v5e, with
no chip attached, and print their memory analysis.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py \
        --config glm4-9b.fake_quant --bucket 1024 --max-len 1536

It compiles the largest prefill bucket and the decode step at
``max_batch`` rows, the two programs that bound a cell's memory, exactly as
``repro.runtime`` builds them (weights and plan as shapes only).  A program
that does not fit is refused here by the chip's compiler.  Nothing runs, so
this gives no time.  Parameters that the plan shadows are dropped from the
compiled program's arguments, so the resident bytes are added up from the
shapes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import cells  # noqa: E402


def _bytes(tree) -> int:
    return int(sum(x.size * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree_util.tree_leaves(tree)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--bucket", type=int, required=True)
    ap.add_argument("--max-len", type=int, required=True)
    ap.add_argument("--layers", type=int, default=None,
                    help="override the file's depth")
    args = ap.parse_args(argv)

    from jax.experimental import topologies
    import repro.kernels.runtime as kr
    from repro import runtime
    from repro.pim.backend import traced_ad_ops

    kr.default_interpret = lambda: False        # compile the Mosaic kernel
    spec = cells.load_config(args.config)
    if args.layers:
        spec["dims"]["n_layers"] = args.layers
    cfg = cells.program_config(spec)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev), t)

    import weights
    params = jax.eval_shape(lambda: weights.make_params(
        spec["dims"], 0, cfg.param_dtype))
    rt = runtime.compile(cfg, params)
    B = spec["engine"]["max_batch"]

    def prefill(params, plan, tokens):
        with rt._ambient(), traced_ad_ops() as tally:
            cache = rt.cache_fn(tokens.shape[0], args.max_len)
            logits, cache, _ = rt.apply_fn(params, {"tokens": tokens},
                                           cache=cache, mode="prefill",
                                           plan=plan)
            return logits[:, -1], cache, tally.value

    def decode(params, plan, cache, tokens):
        with rt._ambient(), traced_ad_ops() as tally:
            logits, cache, _ = rt.apply_fn(params, {"tokens": tokens},
                                           cache=cache, mode="decode",
                                           plan=plan)
            return logits[:, -1], cache, tally.value

    cache = jax.eval_shape(lambda: rt.cache_fn(B, args.max_len))
    progs = {
        f"prefill_1x{args.bucket}": (prefill, (params, rt.plan, jax.ShapeDtypeStruct((1, args.bucket), jnp.int32))),
        f"decode_{B}x{args.max_len}": (decode, (params, rt.plan, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32))),
    }
    bs = spec["engine"]["block_size"]
    n_blocks = 1 + (B + 2) * (args.max_len // bs)   # PagedKVCache default
    out = {"config": args.config, "n_layers": cfg.n_layers,
           "params_bytes": _bytes(params), "plan_bytes": _bytes(rt.plan),
           "kv_pool_bytes": _bytes(jax.eval_shape(
               lambda: rt.cache_fn(n_blocks, bs))),
           "decode_cache_bytes": _bytes(cache)}
    for name, (fn, a) in progs.items():
        compiled = jax.jit(fn).lower(*on(a)).compile()
        ma = compiled.memory_analysis()
        out[name] = {"argument_bytes": ma.argument_size_in_bytes,
                     "output_bytes": ma.output_size_in_bytes,
                     "temp_bytes": ma.temp_size_in_bytes,
                     "tpu_custom_call": "tpu_custom_call" in compiled.as_text()}
        print(name, json.dumps(out[name]), flush=True)
    temp = max(out[n]["temp_bytes"] for n in progs)
    out["resident_plus_largest_temp_bytes"] = (
        out["params_bytes"] + out["plan_bytes"] + out["kv_pool_bytes"]
        + out["decode_cache_bytes"] + temp)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
