#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process:

    python3 benchmarks/chip/control.py --workload <cell> --seconds 12 \
        --seeds 101 102 ... --control-seeds 3

For each seed it runs the cell's window at its own load and compares the
served tokens with the reference's replay (the program's reading); on the
first ``--control-seeds`` seeds it also reads what the controls give: the
reference replayed in the program's place on the same calls and tokens,
with float8 (e4m3) or int8 matmul operands, weights and KV cache, the
steps below the configuration's bfloat16.  That sets each limit's upper
reading.  One JSON line per seed, then a summary with the largest program
reading and the smallest reading of each control.  The benchmark's own
runs never run a control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402

CONTROLS = ("fp8", "int8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    program, control = {}, {c: {} for c in CONTROLS}
    for i, seed in enumerate(args.seeds):
        ctl = CONTROLS if i < args.control_seeds else ()
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           controls=ctl, t_start=time.perf_counter())
        info = res["_info"]
        row = {"seed": seed, "program": {k: c["value"] for k, c in
                                         res["checks"].items()},
               "controls": info["controls"],
               "tokens": info["checked_tokens"],
               "flipped": info["checked_flipped"],
               "setup_s": res["metrics"]["setup_s"]["value"],
               "reference_s": info["reference_s"]}
        print(json.dumps(row), flush=True)
        for k, v in row["program"].items():
            program[k] = max(program.get(k, v), v)
        for c, nums in info["controls"].items():
            for k in row["program"]:
                control[c][k] = min(control[c].get(k, nums[k]), nums[k])
    print(json.dumps({"program_largest": program,
                      "control_smallest": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
