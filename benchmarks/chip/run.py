#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.  The
harness makes the weights from the seed on the device, builds the program's
``Runtime`` and ``ServeEngine`` through its normal path, warms up every
shape the mix can produce (counted in ``setup_s``), then offers the mix's
open-loop arrivals for ``--seconds`` and measures.  It logs what each
Runtime call ran (prefill tokens; which request sat in which decode slot).
Afterwards it frees the program and replays those calls in the plain
reference, which reads every served token.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
same window with the profiler on for part of it and prints the per-layer
metrics.  The last line of stdout is one JSON object; the numbers compared
for ``correct`` are the last lines of stderr and the last key of that
object.  Off a TPU, or with fewer chips than the cell asks for, it prints
no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs to /tmp else

import numpy as np  # noqa: E402

import cells  # noqa: E402
import loadgen  # noqa: E402
import work  # noqa: E402

# host spans the traced run records around the harness's calls into the
# engine (``step`` is open whenever the engine had work)
SPANS = ("step", "admit", "prefill", "install", "decode_cache", "decode",
         "writeback", "sample", "zero_slot")
# device ops to total apart in the trace: every Mosaic (Pallas) kernel.  The
# trq_group_mvm kernel has no name of its own in the trace (its op is named
# after the jitted wrapper around it), so no per-kernel roofline is read.
KERNELS = {"tpu_custom_call": r'custom_call_target="tpu_custom_call"'}
# the traced run profiles this many seconds at the end of its window
TRACE_SECONDS = 8.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    pass


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"run.py: JAX found no TPU (platform "
                     f"{info['platform']!r}); the benchmark has no CPU "
                     f"fallback")
    if info["count"] < chips:
        raise NoChip(f"run.py: the cell needs {chips} chips, JAX found "
                     f"{info['count']}")
    return info


# ---------------------------------------------------------------------------
# build and warm up
# ---------------------------------------------------------------------------

def build(spec: dict, mix: dict, seed: int):
    """Weights from the seed, then ``repro.runtime.compile`` (which
    programs the crossbar plan) and a ``ServeEngine`` — launch.serve's
    path, on a one-device mesh."""
    import jax
    from repro import runtime
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import ServeEngine
    import weights

    cfg = cells.program_config(spec)
    eng = spec["engine"]
    mesh = make_host_mesh(jax.devices()[:1])
    with use_mesh(mesh):
        params = weights.make_params(spec["dims"], seed, cfg.param_dtype)
        rt = runtime.compile(cfg, params)
        del params
        engine = ServeEngine(rt, max_batch=eng["max_batch"],
                             max_len=mix["max_len"],
                             block_size=eng["block_size"],
                             prefix_reuse=eng["prefix_reuse"])
    return engine, mesh


def bucket_of(engine):
    """The engine's prefill bucket of a prompt length: its own rule, or
    the power-of-two rule where the engine no longer names one."""
    fn = getattr(engine, "_bucket", None)
    if fn is not None:
        return fn

    def pow2(n):
        b = 8
        while b < n:
            b *= 2
        return min(b, engine.max_len)
    return pow2


def warm_buckets(engine, mix: dict) -> list:
    """Every prefill bucket the mix's prompt lengths can land in."""
    lo, hi = loadgen.prompt_range(mix)
    bucket = bucket_of(engine)
    return sorted({bucket(n) for n in range(lo, hi + 1)})


def warm_up(engine, mix: dict) -> dict:
    """Compile every shape the window can use: each prefill bucket, the
    decode step, and the block writes for every count of active slots
    (max_batch requests admitted together, finishing one per step from the
    third step on).  The second step runs with every slot full and none
    freed before it, so the cache assembly also sees the state as the
    decode step left it, and not only as a slot reset left it: the two
    differ in sharding, and each is a program of its own."""
    vocab = engine.cfg.vocab_size
    buckets = warm_buckets(engine, mix)
    rng = np.random.default_rng(0)
    for i in range(engine.max_batch):
        n = buckets[i % len(buckets)]
        engine.submit(rng.integers(1, vocab, n, dtype=np.int32),
                      max_new_tokens=3 + i)
    steps = 0
    while engine.queue or any(s is not None for s in engine.slots):
        engine.step()
        steps += 1
    engine.finished.clear()
    return {"buckets": buckets, "steps": steps}


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

class Record:
    __slots__ = ("req", "due", "submit", "prefill_start", "times", "plen",
                 "padded")

    def __init__(self, req, due, submit):
        self.req, self.due, self.submit = req, due, submit
        self.prefill_start = None
        self.times = []
        self.plen = self.padded = None


def instrument(engine, log: dict, trace: bool):
    """Log what the window runs, through the Runtime's public entry points
    and the engine's public state (host clock, cheap): each prefill's
    tokens (copied to the host in the background) and, for each decode
    step, the (slot, request, tokens so far) of every active row.  The
    reference replays both.  With ``trace`` also open a profiler span
    around the Runtime calls and around each engine method named in
    ``SPANS`` that the engine still has, so the trace can attribute device
    time and idle gaps."""
    rt = engine.rt
    from jax.profiler import TraceAnnotation

    def span(name, fn):
        def call(*a, **kw):
            with TraceAnnotation(name):
                return fn(*a, **kw)
        return call

    prefill, decode = rt.prefill, rt.decode

    def prefill_logged(tokens, *a, **kw):
        t = time.perf_counter()
        copy = getattr(tokens, "copy_to_host_async", None)
        if copy is not None:
            copy()
        log["prefills"].append((t, tokens))
        log["calls"].append(["prefill", t, len(log["prefills"]) - 1])
        return prefill(tokens, *a, **kw)

    def decode_logged(*a, **kw):
        log["steps"].append([(i, r.uid, len(r.generated))
                             for i, r in enumerate(engine.slots)
                             if r is not None])
        log["calls"].append(["decode", time.perf_counter(), 0])
        return decode(*a, **kw)

    rt.prefill, rt.decode = prefill_logged, decode_logged
    if not trace:
        return
    rt.decode = span("decode", rt.decode)
    for name in SPANS:
        attr = "step" if name == "step" else f"_{name}"
        fn = getattr(engine, attr, None)
        if callable(fn):
            setattr(engine, attr, span(name, fn))


def resolve(log: dict, records: dict) -> dict:
    """After the window: match each prefill call to its request by the
    tokens it fed (the prompt after its left padding), set the request's
    prefill start and padded length, and turn each logged prefill call
    into (rows, real prompt tokens).  Returns uid -> the tokens fed."""
    by_prompt: dict = {}
    for rec in sorted(records.values(), key=lambda r: r.req.uid):
        by_prompt.setdefault(np.asarray(rec.req.prompt, np.int32).tobytes(),
                             []).append(rec)
    fed, rows = {}, []
    for t, tokens in log["prefills"]:
        toks = np.asarray(tokens, np.int32).reshape(-1)
        nz = np.flatnonzero(toks)
        real = toks[nz[0]:] if nz.size else toks[:0]
        recs = by_prompt.get(real.tobytes())
        if not recs:
            raise RuntimeError(f"a prefill of {toks.size} rows fed a prompt "
                               f"that no request in the window sent")
        rec = recs.pop(0)
        rec.prefill_start, rec.padded = t, int(toks.size)
        fed[rec.req.uid] = toks
        rows.append((int(toks.size), int(real.size)))
    for c in log["calls"]:
        if c[0] == "prefill":
            c[2:] = rows[c[2]]
        else:
            c[2:] = [0, 0]
    return fed


@contextlib.contextmanager
def compile_log():
    """Names of the functions JAX compiles in the block."""
    import logging
    import jax
    names = []

    class Grab(logging.Handler):
        def emit(self, record):
            names.append(record.getMessage()[:160])

    h = Grab(logging.WARNING)
    log = logging.getLogger("jax")
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    log.addHandler(h)
    try:
        yield names
    finally:
        log.removeHandler(h)
        jax.config.update("jax_log_compiles", prev)


@contextlib.contextmanager
def compile_counter():
    """Backend compilations in the block (count and seconds)."""
    import jax
    c = {"n": 0, "s": 0.0}

    def listen(event, secs, **_):
        if event == COMPILE_EVENT:
            c["n"] += 1
            c["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield c
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def drive(engine, arrivals: list, seconds: float, log: dict,
          trace_dir=None, trace_s: float = 0.0) -> dict:
    """Offer ``arrivals`` open-loop for ``seconds``: each request is
    submitted once due, the engine steps while it has work, and every
    served token gets the host time of the step that produced it.  With
    ``trace_dir`` the profiler records the last ``trace_s`` seconds and
    stops once the window has closed: writing the trace stalls the host
    for seconds, which inside the window would back up the queue."""
    import jax
    records = log["records"]
    live = []
    t0 = time.perf_counter()
    end = t0 + seconds
    t_on = end - trace_s
    tracing = None
    i, steps = 0, 0
    while True:
        now = time.perf_counter()
        if trace_dir is not None and tracing is None and now >= t_on:
            jax.profiler.start_trace(str(trace_dir))
            tracing = [time.perf_counter(), None]
        if now >= end:
            break
        while i < len(arrivals) and t0 + arrivals[i].due_s <= now:
            a = arrivals[i]
            r = engine.submit(a.prompt, max_new_tokens=a.max_new)
            records[r.uid] = rec = Record(r, t0 + a.due_s, now)
            rec.plen = len(a.prompt)
            live.append(rec)
            i += 1
        if engine.queue or any(s is not None for s in engine.slots):
            engine.step()
            steps += 1
            t = time.perf_counter()
            still = []
            for rec in live:
                r = rec.req
                new = len(r.generated) - len(rec.times)
                if new > 0 and not rec.times:
                    rec.times.append(r.first_token_t)
                    new -= 1
                rec.times.extend([t] * new)
                if not r.done:
                    still.append(rec)
            live = still
        else:
            wake = [end]
            if i < len(arrivals):
                wake.append(t0 + arrivals[i].due_s)
            if trace_dir is not None and tracing is None:
                wake.append(t_on)
            time.sleep(max(0.0, min(wake) - now))
    if tracing is not None:
        jax.profiler.stop_trace()
        tracing[1] = time.perf_counter()
    return {"end": end, "steps": steps, "trace_host": tracing}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(win: dict, records: dict, seconds: float, ad_ops: float,
               ttft_judged: bool = True) -> dict:
    """The window's end-to-end numbers.  Where the cell judges TTFT, a
    request due in the window with no first token by its close has
    failed; above the knee, where the queue grows by design, such a
    request is still queued: it is neither attempted nor failed."""
    end = win["end"]
    recs = list(records.values())
    due = [r for r in recs if r.due < end]
    ttft, unanswered = [], 0
    for r in due:
        ft = r.times[0] if r.times else math.inf
        if ft > end:
            unanswered += 1
            ttft.append(math.inf)
        else:
            ttft.append(ft - r.due)
    tokens = sum(1 for r in recs for t in r.times if t <= end)
    tpot = []
    for r in recs:
        ts = [t for t in r.times if t <= end]
        # a host-clock time must span 250 ms or more
        if len(ts) >= 2 and ts[-1] - ts[0] >= 0.25:
            tpot.append((ts[-1] - ts[0]) / (len(ts) - 1))
    # A/D ops over every call in the window, per token those calls served
    served = sum(r.plen for r in recs if r.times) + \
        sum(len(r.times) for r in recs)
    late = [r.submit - r.due for r in recs]
    prefills = [r for r in recs if r.prefill_start is not None]
    return {
        "attempted": len(due) - (0 if ttft_judged else unanswered),
        "failed": unanswered if ttft_judged else 0,
        "tokens_per_s": tokens / seconds,
        "ttft_p90_ms": loadgen.percentile(ttft, 90) * 1e3 if ttft else None,
        "tpot_p90_ms": loadgen.percentile(tpot, 90) * 1e3 if tpot else None,
        "ad_ops_per_token": ad_ops / served if served else None,
        "info": {
            "requests_due": len(due), "requests_with_first_token":
                len(due) - unanswered, "tokens_in_window": tokens,
            "tpot_requests": len(tpot),
            "generator_late_ms_p50": loadgen.percentile(late, 50) * 1e3
            if late else None,
            "generator_late_ms_max": max(late) * 1e3 if late else None,
            "prefills": len(prefills), "steps": win["steps"],
        },
    }


def layer_context(win, records, log, dims, peak, reduced, seconds,
                  max_batch, ad_ops_per_token) -> dict:
    """What the per-layer readers read."""
    recs = list(records.values())
    end = win["end"]
    pre = [r for r in recs if r.prefill_start is not None]
    decode_ctx = []
    for r in recs:
        n = sum(1 for t in r.times[1:] if t <= end)
        decode_ctx.extend(r.plen + k for k in range(1, n + 1))
    ctx = {
        "dims": dims, "peak": peak, "seconds": seconds,
        "max_batch": max_batch,
        "prefill_real": [r.plen for r in pre],
        "prefill_padded": [r.padded for r in pre],
        "queue_wait_s": [r.prefill_start - r.due for r in pre],
        "ad_ops_per_token": ad_ops_per_token,
        "served_flops": work.served_flops(
            dims, [r.plen for r in pre if r.times and r.times[0] <= end],
            decode_ctx),
        "trace": reduced,
    }
    if reduced and win["trace_host"]:
        on, off = win["trace_host"]
        calls = [c for c in log["calls"] if on <= c[1] < off]
        runs = [r for r in reduced.get("module_runs", [])
                if r[0] == "jit_step"]
        # one jit_step program per Runtime call, executed in launch order;
        # every call launched while tracing has finished before it stops
        n = min(len(calls), len(runs))
        matched = list(zip(calls[len(calls) - n:], runs[len(runs) - n:]))
        ctx["traced_calls"] = {
            "matched": n, "calls": len(calls), "runs": len(runs),
            "prefill_rows": [c[2] for c, _ in matched if c[0] == "prefill"],
            "prefill_s": sum(r[1] for c, r in matched if c[0] == "prefill"),
            "prefill_real": [c[3] for c, _ in matched if c[0] == "prefill"],
            "decode_steps": sum(1 for c, _ in matched if c[0] == "decode"),
            "decode_s": sum(r[1] for c, r in matched if c[0] == "decode"),
        }
    return ctx


# ---------------------------------------------------------------------------
# correctness: every served token against the plain reference's replay
# ---------------------------------------------------------------------------

def reference_gaps(spec: dict, cfg, seed: int, log: dict, records: dict,
                   fed: dict, max_batch: int, max_len: int,
                   controls=()) -> dict:
    """Replay the window's calls in the reference (``reference.replay``)
    over the weights made again from the seed."""
    import jax
    import reference
    import weights
    served = {uid: np.asarray(records[uid].req.generated, np.int32)
              for uid in fed}
    out = reference.replay(
        lambda: weights.make_params(spec["dims"], seed, cfg.param_dtype),
        dims=spec["dims"], arith=spec["arithmetic"], cdtype=cfg.dtype,
        prompts=fed, served=served, steps=log["steps"], max_batch=max_batch,
        max_len=max_len, controls=controls)
    jax.clear_caches()
    return out


def gap_numbers(allg) -> dict:
    """The numbers compared, and how many compared tokens were not the
    reference's best (``flipped``: what the mean gap is made of)."""
    allg = np.asarray(allg)
    if not allg.size:
        return {"logit_gap_max": math.inf, "logit_gap_mean": math.inf,
                "tokens": 0, "flipped": 0}
    return {"logit_gap_max": float(allg.max()),
            "logit_gap_mean": float(allg.mean()), "tokens": int(allg.size),
            "flipped": int((allg > 0).sum())}


def load_limits(workload: str, layout: cells.Layout) -> dict:
    path = layout.configs.parent / "limits" / f"{workload}.json"
    return json.loads(path.read_text())["limits"]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             layout: cells.Layout = cells.Layout(), require_tpu: bool = True,
             compile_cache: bool = True, fault=None, t_start=None,
             controls: tuple = ()) -> dict:
    """Run the cell once; returns the result object (the last line).
    ``fault`` (tests only) breaks the engine after warm-up; ``controls``
    (the control script only) also reads the reference's gaps of the
    tokens that each of these lower precisions would serve."""
    t_start = T_START if t_start is None else t_start
    w = cells.workload(workload, layout)
    spec = cells.load_config(w["config"], layout)
    mix = cells.load_traffic(w["traffic"], layout)
    dev = device_info(w["chips"], require_tpu)
    peak = work.peaks(dev["kind"]) if require_tpu else None
    import jax
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    engine, mesh = build(spec, mix, seed)
    cfg = engine.cfg
    loadgen.check_fits(mix, bucket_of(engine))
    from repro.dist.sharding import use_mesh
    log = {"records": {}, "calls": [], "prefills": [], "steps": []}
    with use_mesh(mesh):
        warm = warm_up(engine, mix)
        instrument(engine, log, trace)
        if fault is not None:
            fault(engine)
        arrivals = loadgen.schedule(mix, seed, seconds, engine.cfg.vocab_size)
        ad0 = engine.total_ad_ops
        setup_s = time.perf_counter() - t_start
        trace_dir = Path(tempfile.mkdtemp(prefix="chip_trace_")) \
            if trace else None
        with compile_counter() as comp, compile_log() as compiled:
            win = drive(engine, arrivals, seconds, log, trace_dir,
                        min(TRACE_SECONDS, seconds / 2))
        ad_ops = engine.total_ad_ops - ad0
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    max_batch = engine.max_batch
    e2e_names = [m["name"] for m in w["end_to_end"]]
    e2e = end_to_end(win, log["records"], seconds, ad_ops,
                     ttft_judged="ttft_p90_ms" in e2e_names)
    fed = resolve(log, log["records"])
    info = dict(e2e["info"], compiles_in_window=comp["n"],
                compile_s_in_window=comp["s"], compiled=compiled[:5],
                warm_up=warm,
                total_ad_ops=ad_ops,
                ttft_p90_ms=e2e["ttft_p90_ms"])
    if e2e["ttft_p90_ms"] is not None and math.isinf(e2e["ttft_p90_ms"]):
        info["ttft_p90_ms"] = "more than 10% of requests got no token"

    # free the program before the reference runs
    held = (getattr(engine.rt, "params", None), getattr(engine.rt, "plan", None),
            getattr(getattr(engine, "kv", None), "pools", None),
            getattr(engine, "state_cache", None))
    for leaf in jax.tree_util.tree_leaves(held):
        if isinstance(leaf, jax.Array):
            leaf.delete()
    del engine
    jax.clear_caches()
    gc.collect()

    reduced = None
    if trace:
        import trace_reduce
        events = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        reduced = trace_reduce.reduce(events, spans=SPANS, kernels=KERNELS)
        del events
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    ref = reference_gaps(spec, cfg, seed, log, log["records"], fed,
                         max_batch, mix["max_len"], controls)
    nums = gap_numbers(ref["served"])
    info["controls"] = {c: gap_numbers(ref[c]) for c in controls}
    info["reference_s"] = time.perf_counter() - t_ref
    info["checked_requests"] = len(fed)
    info["checked_tokens"] = nums["tokens"]
    info["checked_flipped"] = nums["flipped"]
    limits = load_limits(workload, layout)
    checks = {k: {"value": nums[k], "limit": lim}
              for k, lim in limits.items()}
    correct = bool(fed) and all(c["value"] <= c["limit"]
                                for c in checks.values())

    if trace:
        ctx = layer_context(win, log["records"], log, spec["dims"], peak,
                            reduced, seconds, max_batch,
                            e2e["ad_ops_per_token"])
        info["traced_calls"] = ctx.get("traced_calls")
        for k in ("kernels", "programs", "span_counts"):
            info[k] = reduced.get(k) if reduced else None
        metrics = {}
        for m in w["per_layer"]:
            v = cells.metric_reader(m["name"], layout)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = reduced.get("busy_s", 0.0) if reduced else 0.0
        dev["window_s"] = reduced.get("window_s", 0.0) if reduced else 0.0
    else:
        vals = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in w["end_to_end"]}
    result = {"correct": correct, "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics, "device": dev}
    if trace and reduced and reduced.get("devices"):
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    result["_info"] = info
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    info = res.pop("_info")
    print(json.dumps({"info": info}, default=str), flush=True)
    for name, c in res["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
