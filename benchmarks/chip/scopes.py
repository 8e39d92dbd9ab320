#!/usr/bin/env python3
"""Run a cell's window with the profiler on and print the program's own
account of it.

    python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \
        --seconds <s> [--trace-seconds 8]

The cell is built, warmed up and driven as ``run.py`` does, with the
profiler on for the last ``--trace-seconds`` of the window; nothing of the
engine is wrapped, so every host span in the trace is the program's own.
The trace is reduced by ``scope_reduce``: device self time by program and
named scope, the ``serve.*`` spans and the device's idle time inside
``serve.step`` by the innermost of them.  ``ServeEngine.counters`` over the
window and ``Request.prefill_t`` give the scheduler's numbers.  No served
token is checked against the reference (``run.py`` does that).  The last
line of stdout is one JSON object:

* ``per_step``: ``decode_step_ms`` (the ``runtime.decode`` program),
  ``decode_pim_ms`` (self time under ``pim_linear`` in it),
  ``decode_attention_ms`` (``attention.core``, ``attention.cache_write``
  and the layer scan's slicing and stacking of the cache),
  ``kv_paging_ms_per_step`` (the ``kv.*`` programs) and
  ``decode_idle_rows_per_step`` (batch rows with no request), each per
  decode step; ``prefill_pim_ms_per_ktok`` per 1000 real prompt rows;
  ``prefill_pad_share`` (%) from the counters; ``queue_wait_p90_ms`` from
  due time to ``Request.prefill_t``;
* ``scopes`` (``scope_reduce.table``), ``spans``, ``idle``, ``counters``
  and the window's end-to-end numbers with the profiler on.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import cells  # noqa: E402
import loadgen  # noqa: E402
import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402

DECODE = "runtime.decode"
# the attention's work in a decode step: the core, the new token's K/V
# write, and each layer's K/V sliced out of the stacked cache and back
ATTENTION = ("attention.core", "attention.cache_write", "layer_scan")


def readings(reduced: dict, counters: dict, waits: list) -> dict:
    """The per-step numbers of one window (see the module docstring); a
    number the window holds nothing for is None."""
    dec = reduced["programs"].get(DECODE, {})
    steps = dec.get("runs")

    def per_step(s):
        return 1e3 * s / steps if steps and s else None

    pre_pim = sum(scope_reduce.scope_s(reduced, k, "pim_linear")
                  for k in scope_reduce.PREFILL_KINDS)
    real = reduced["spans"]["prefill_real_rows"]
    c = counters
    return {
        "decode_step_ms": per_step(dec.get("device_s")),
        "decode_pim_ms": scope_reduce.per_run_ms(reduced, DECODE,
                                                 "pim_linear"),
        "decode_attention_ms": per_step(sum(
            scope_reduce.scope_s(reduced, DECODE, k) for k in ATTENTION)),
        "kv_paging_ms_per_step": per_step(sum(
            p["device_s"] for k, p in reduced["programs"].items()
            if k.startswith("kv."))),
        "prefill_pim_ms_per_ktok": 1e6 * pre_pim / real
        if real and pre_pim else None,
        "decode_idle_rows_per_step":
            (c["decode_rows"] - c["decode_active_rows"]) / c["decode_steps"]
            if c["decode_steps"] else None,
        "prefill_pad_share": 100.0 * (c["prefill_rows"]
                                      - c["prefill_real_rows"])
            / c["prefill_rows"] if c["prefill_rows"] else None,
        "queue_wait_p90_ms": loadgen.percentile(waits, 90) * 1e3
        if waits else None,
    }


def run_window(workload: str, seed: int, seconds: float, trace_s: float, *,
               layout: cells.Layout = cells.Layout(),
               require_tpu: bool = True, compile_cache: bool = True) -> dict:
    """Build, warm up and drive the cell once with the profiler on for the
    last ``trace_s`` seconds; returns the result object."""
    w = cells.workload(workload, layout)
    spec = cells.load_config(w["config"], layout)
    mix = cells.load_traffic(w["traffic"], layout)
    dev = run.device_info(w["chips"], require_tpu)
    import jax
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    # the scopes are HLO metadata, which the compile cache's key leaves out
    # by default: an executable cached from another build of the same
    # arithmetic would bring that build's names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from repro.dist.sharding import use_mesh
    engine, mesh = run.build(spec, mix, seed)
    loadgen.check_fits(mix, run.bucket_of(engine))
    log = {"records": {}}
    trace_dir = Path(tempfile.mkdtemp(prefix="chip_scopes_"))
    try:
        with use_mesh(mesh):
            run.warm_up(engine, mix)
            arrivals = loadgen.schedule(mix, seed, seconds,
                                        engine.cfg.vocab_size)
            c0 = dict(engine.counters)
            win = run.drive(engine, arrivals, seconds, log, trace_dir,
                            min(trace_s, seconds / 2))
            counters = {k: v - c0[k] for k, v in engine.counters.items()}
        reduced = scope_reduce.reduce(scope_reduce.load(
            trace_reduce.find_xplane(trace_dir)))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    records = log["records"]
    e2e = run.end_to_end(win, records, seconds, 0.0)
    waits = [r.req.prefill_t - r.due for r in records.values()
             if r.req.prefill_t]
    spans = dict(reduced["spans"])
    uids = spans.pop("prefill_uids")
    spans["prefill_uids_not_in_window"] = sum(1 for u in uids
                                              if u not in records)
    steps = reduced["programs"].get(DECODE, {}).get("runs")
    spans["per_decode_step"] = sum(spans["counts"].values()) / steps \
        if steps else None
    return {"device": dev,
            "traced": {k: e2e[k] for k in ("tokens_per_s", "ttft_p90_ms",
                                           "tpot_p90_ms")},
            "per_step": readings(reduced, counters, waits),
            "counters": counters, "spans": spans, "idle": reduced["idle"],
            "scopes": scope_reduce.table(reduced)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, default=run.TRACE_SECONDS)
    args = ap.parse_args(argv)
    try:
        res = run_window(args.workload, args.seed, args.seconds,
                         args.trace_seconds)
    except run.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
