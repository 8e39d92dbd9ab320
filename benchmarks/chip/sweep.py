#!/usr/bin/env python3
"""Find a cell's knee: step one engine through offered rates in one process.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds 20 \
        --rates 1 2 3 4

The cell's mix is offered at each rate in turn for ``--seconds`` (the
engine is built and warmed once, and drained between rates).  Each rate
prints one JSON line: completed tokens/s, TTFT and TPOT percentiles, and
the backlog left when the window closed.  The knee is the highest rate
whose backlog stays flat; a cell's rate is then fixed in its traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import cells  # noqa: E402
import loadgen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--config", help="sweep this configuration and mix "
                    "instead of a cell's (a cell not yet declared)")
    ap.add_argument("--traffic")
    args = ap.parse_args(argv)
    if args.config:
        w = {"config": args.config, "traffic": args.traffic, "chips": 1}
    else:
        w = cells.workload(args.workload)
    spec = cells.load_config(w["config"])
    mix = cells.load_traffic(w["traffic"])
    run.device_info(w["chips"])
    import jax
    from repro.dist.sharding import use_mesh
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t = time.perf_counter()
    engine, mesh = run.build(spec, mix, args.seed)
    with use_mesh(mesh):
        run.warm_up(engine, mix)
        print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
        for rate in args.rates:
            m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
            arrivals = loadgen.schedule(m, args.seed, args.seconds,
                                        engine.cfg.vocab_size)
            log = {"records": {}, "calls": []}
            win = run.drive(engine, arrivals, args.seconds, log)
            e2e = run.end_to_end(win, log["records"], args.seconds, 0.0)
            backlog = len(engine.queue)
            active = sum(s is not None for s in engine.slots)
            print(json.dumps({
                "rate_per_s": rate, "tokens_per_s": e2e["tokens_per_s"],
                "ttft_p50_ms": loadgen.percentile(
                    [r.times[0] - r.due if r.times else float("inf")
                     for r in log["records"].values()], 50) * 1e3,
                "ttft_p90_ms": e2e["ttft_p90_ms"],
                "tpot_p90_ms": e2e["tpot_p90_ms"],
                "queued_at_close": backlog, "active_at_close": active,
                "no_first_token": e2e["failed"],
                "attempted": e2e["attempted"]}), flush=True)
            engine.queue.clear()          # drop the backlog, drain the slots
            while any(s is not None for s in engine.slots):
                engine.step()
            engine.finished.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
