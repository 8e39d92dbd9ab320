"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load(path)`` flattens an ``.xplane.pb`` (``jax.profiler.ProfileData``)
into plain event records ``{"plane", "line", "name", "t0", "dur"}``, with
times in nanoseconds on the trace's one clock.  ``reduce(events, window)``
then gives, for the traced window:

* device busy time: the union of the intervals in which an XLA op ran on a
  device (``XLA Ops`` lines of ``/device:TPU:n`` planes), averaged over the
  devices that ran any;
* device time per jitted program (``XLA Modules`` lines), by the host span
  open at its midpoint (the harness names its calls into the engine with
  ``jax.profiler.TraceAnnotation``), and every program run in time order
  (``module_runs``) so that runs can be matched to the calls that
  launched them;
* device time per op name (the top ops) and the events of named kernels;
* the idle gaps between device ops, each labelled by the innermost harness
  span open at its midpoint, and the idle time inside ``busy`` host spans
  (the time in which the engine had work).

The events are kept plain so that a small recorded trace can be committed
and the reduction tested without a chip: ``tests/data/trace_excerpt.json.gz``
holds the device ops and modules and the harness spans of the first 0.15 s
after the first ``step`` span of a traced chat run, op names cut to
``OP_CHARS``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# device op names are whole HLO instructions; the breakdown keeps their head
OP_CHARS = 120


def load(path) -> list:
    """Events of an ``.xplane.pb`` file as plain dicts."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "t0": float(ev.start_ns),
                            "dur": float(ev.duration_ns)})
    return out


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def _total(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def _intersect(a: list, b: list) -> list:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def module_name(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``: the program, not its run."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(events: list, spans: tuple = (), busy_span: str = "step",
           kernels: dict = None, window=None) -> dict:
    """See the module docstring.  ``spans``: the harness's host span names;
    ``kernels``: {label: regex} of device op names to collect; ``window``:
    (t0, t1) in trace nanoseconds, default the extent of the device ops."""
    kernels = kernels or {}
    dev_planes = sorted({e["plane"] for e in events
                         if DEVICE_PLANE.match(e["plane"])
                         and e["line"] == OPS_LINE})
    ops = [e for e in events if e["plane"] in dev_planes
           and e["line"] == OPS_LINE]
    mods = [e for e in events if e["plane"] in dev_planes
            and e["line"] == MODULES_LINE]
    host = [e for e in events if not DEVICE_PLANE.match(e["plane"])
            and e["name"] in spans]
    if not ops:
        return {"devices": 0}
    if window is None:
        window = (min(e["t0"] for e in ops),
                  max(e["t0"] + e["dur"] for e in ops))
    lo, hi = window

    busy_by_dev = {}
    for p in dev_planes:
        iv = _union([[e["t0"], e["t0"] + e["dur"]] for e in ops
                     if e["plane"] == p])
        busy_by_dev[p] = _clip(iv, lo, hi)
    busy_any = _union([iv for ivs in busy_by_dev.values() for iv in ivs])
    busy_s = sum(_total(v) for v in busy_by_dev.values()) \
        / len(busy_by_dev) * 1e-9

    # host spans: name -> intervals; innermost-at-a-time lookup for gaps
    span_iv = defaultdict(list)
    for e in host:
        span_iv[e["name"]].append([e["t0"], e["t0"] + e["dur"]])
    engine_busy = _clip(_union(span_iv.get(busy_span, [])), lo, hi)
    busy_in_engine = _total(_intersect(busy_any, engine_busy))
    engine_s = _total(engine_busy)

    host_sorted = sorted(host, key=lambda e: e["t0"])
    starts = [e["t0"] for e in host_sorted]

    def label(t: float) -> str:
        """Innermost (latest-starting) span open at time ``t``."""
        i = bisect.bisect_right(starts, t)
        for e in reversed(host_sorted[max(0, i - 512):i]):
            if t < e["t0"] + e["dur"]:
                return e["name"]
        return "outside harness spans"

    # each idle gap, cut at the harness spans' edges, goes to the
    # innermost span open in each piece
    bounds = sorted({x for e in host for x in (e["t0"], e["t0"] + e["dur"])})
    gaps = defaultdict(float)
    clipped = _clip(busy_any, lo, hi)
    edges = [lo] + [x for iv in clipped for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        i, j = bisect.bisect_right(bounds, a), bisect.bisect_left(bounds, b)
        cuts = [a] + bounds[i:j] + [b]
        for x, y in zip(cuts, cuts[1:]):
            gaps[label((x + y) / 2)] += (y - x) * 1e-9

    by_op = defaultdict(float)
    for e in ops:
        if lo <= e["t0"] < hi:
            by_op[e["name"][:OP_CHARS]] += e["dur"] * 1e-9

    # program time by the host span it ran under (midpoint attribution)
    by_prog = defaultdict(float)
    for e in mods:
        if lo <= e["t0"] < hi:
            key = f"{module_name(e['name'])}@{label(e['t0'] + e['dur'] / 2)}"
            by_prog[key] += e["dur"] * 1e-9 / len(dev_planes)

    module_runs = [[module_name(e["name"]), e["dur"] * 1e-9]
                   for e in sorted(mods, key=lambda e: e["t0"])
                   if lo <= e["t0"] < hi and e["plane"] == dev_planes[0]]

    kern = {k: {"s": 0.0, "n": 0} for k in kernels}
    for e in ops:
        if lo <= e["t0"] < hi:
            for k, rx in kernels.items():
                if re.search(rx, e["name"]):
                    kern[k]["s"] += e["dur"] * 1e-9 / len(dev_planes)
                    kern[k]["n"] += 1

    return {
        "devices": len(dev_planes),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "engine_s": engine_s * 1e-9,
        "engine_idle_s": (engine_s - busy_in_engine) * 1e-9,
        "span_counts": {k: len(_clip(v, lo, hi)) for k, v in span_iv.items()},
        "programs": dict(by_prog),
        "module_runs": module_runs,
        "kernels": kern,
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:10],
    }
