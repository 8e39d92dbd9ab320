"""Reduce a profiler trace by the program's own names: device time per
named scope, and the program's host spans.

The program names its device work with ``jax.named_scope`` (HLO ``op_name``
metadata: ``runtime.decode``, ``pim_linear/<backend>/range_fit``,
``attention.core``, ``lm_head``, ``kv.assemble``, ...) and its host work
with ``serve.*`` profiler spans (``repro.telemetry.trace``).
``load(path)`` keeps, from an ``.xplane.pb``, the device ops and programs,
each op with its ``op_name``, and the ``serve.*`` host spans with their
args.  A TPU trace names an op event by its HLO instruction text and keeps
the ``op_name`` as the ``tf_op`` stat of the event's *metadata*, which
``jax.profiler.ProfileData`` does not expose; ``op_names`` reads those
from the file's protobuf directly.  ``reduce(events)`` then gives:

* ``programs``: each program kind (the program scope its ops carry, such as
  ``runtime.decode`` or ``kv.write_blocks``; a program without one goes by
  its module name) with its runs, its device seconds and the device
  **self** seconds of its ops by scope path.  An op's self time is its
  duration less the part of it that ops nested in it cover, so a ``while``
  counts only its own time and its body ops count once.  The scope path is
  the chain of the program's known scopes in the op's ``op_name``, a
  ``runtime.*`` program scope left out
  (``lm_head/pim_linear/fake_quant/range_fit``, ``kv.assemble``); an op
  under none of them goes under ``unscoped``.
* ``spans``: how many ``serve.*`` spans there were, by name, and how many
  did not nest in a ``serve.step``; and the real prompt rows of the
  ``serve.prefill`` spans in which a prefill program ran, so that prefill
  time can be set against them.
* ``idle``: the time in which a ``serve.step`` was open and no op ran on
  the device, each piece of it given to the innermost ``serve.*`` span
  open in it (``serve.step`` itself where no inner span is), so that the
  host's share of a step is told by the engine's own names.

Events are plain dicts, as in ``trace_reduce``, so that a recorded excerpt
can be committed and the reduction tested without a chip:
``tests/data/scope_excerpt.json.gz``.  ``scopes.py`` runs a cell's window
under the profiler and prints the reduction.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

from trace_reduce import _union

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "serve."
STEP_SPAN = "serve.step"
PREFILL_SPAN = "serve.prefill"
# scopes that name a whole program (the outermost the program sets); a KV
# program does one thing, so its scope also names its ops
KV_SCOPES = ("kv.assemble", "kv.write_blocks", "kv.slot_scatter",
             "kv.zero_pages", "kv.copy_page")
PROGRAM_SCOPES = ("runtime.prefill", "runtime.prefill_cont",
                  "runtime.decode") + KV_SCOPES
PREFILL_KINDS = ("runtime.prefill", "runtime.prefill_cont")
# scopes inside a program; the component after ``pim_linear`` (the
# backend) is known too
INNER_SCOPES = ("pim_linear", "range_fit", "convert", "trq_group_mvm",
                "attention.core", "attention.cache_write", "lm_head", "norm",
                "rope", "embed")
# scopes that hold others: named only where an op sits under no inner scope
# (the layer scan's slicing of weights and cache, its stacked new cache)
OUTER_SCOPES = ("layer_scan",)
UNSCOPED = "unscoped"
# the event-metadata stat of a TPU op that holds its HLO op_name
OP_NAME_STAT = "tf_op"


def _varint(b: bytes, i: int):
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return r, i


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of a protobuf message in ``b[i:end]``; the
    value of a length-delimited field is its (start, end) in ``b``."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def op_names(path) -> dict:
    """{device plane: {op event name: op_name}} from an ``.xplane.pb``
    (``XSpace.planes`` = 1; ``XPlane``: name = 2, event_metadata = 4,
    stat_metadata = 5; ``XEventMetadata``: name = 2, stats = 5;
    ``XStat``: metadata_id = 1, str_value = 5; map entries: key = 1,
    value = 2).  A name that carries two op_names in one plane (an
    instruction text that two programs share) maps to None."""
    b = Path(path).read_bytes()
    out = {}
    for f, plane in _fields(b, 0, len(b)):
        if f != 1:
            continue
        name, metas, stat_ids = None, [], {}
        for pf, v in _fields(b, *plane):
            if pf == 2:
                name = b[v[0]:v[1]].decode()
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                for ef, ev in _fields(b, *v):
                    if ef == 2:
                        sm = dict(_fields(b, *ev))
                        if 2 in sm:
                            stat_ids[sm[1]] = b[sm[2][0]:sm[2][1]].decode()
        if not name or not DEVICE_PLANE.match(name):
            continue
        want = {k for k, v in stat_ids.items() if v == OP_NAME_STAT}
        names = out[name] = {}
        for entry in metas:
            for ef, ev in _fields(b, *entry):
                if ef != 2:
                    continue
                op_name, ev_name = None, None
                for mf, mv in _fields(b, *ev):
                    if mf == 2:
                        ev_name = b[mv[0]:mv[1]].decode(errors="replace")
                    elif mf == 5:
                        st = dict(_fields(b, *mv))
                        if st.get(1) in want and 5 in st:
                            op_name = b[st[5][0]:st[5][1]].decode(
                                errors="replace").rstrip(":")
                if ev_name is None:
                    continue
                if ev_name in names and names[ev_name] != op_name:
                    op_name = None
                names[ev_name] = op_name
    return out


def load(path) -> list:
    """Device op and program events (ops with their ``op_name``) and the
    ``serve.*`` host spans (with their args) of an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    scopes = op_names(path)
    out = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(SPAN_PREFIX):
                    continue
                e = {"plane": plane.name, "line": line.name, "name": name,
                     "t0": float(ev.start_ns), "dur": float(ev.duration_ns)}
                if not device:
                    e["args"] = {k: v for k, v in dict(ev.stats).items()
                                 if not k.startswith("_")}
                elif line.name == OPS_LINE:
                    e["op_name"] = scopes.get(plane.name, {}).get(name)
                out.append(e)
    return out


def scope_path(op_name: str | None) -> tuple:
    """(program scope or None, path of inner known scopes) of an op."""
    if not op_name:
        return None, ()
    parts = op_name.split("/")
    prog, path, outer = None, [], None
    for i, p in enumerate(parts):
        if p in PROGRAM_SCOPES and prog is None:
            prog = p
            if p in KV_SCOPES:
                path.append(p)
        elif p in OUTER_SCOPES:
            outer = p
        elif p in INNER_SCOPES:
            path.append(p)
            if p == "pim_linear" and i + 1 < len(parts):
                path.append(parts[i + 1])
    if not path and outer:
        path = [outer]
    return prog, tuple(path)


def self_times(ops: list) -> list:
    """Each op's duration less what the ops nested in it cover (one
    device's ops, any order)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["t0"],
                                                   -ops[i]["dur"]))
    own = [e["dur"] for e in ops]
    stack = []
    for i in order:
        t0, t1 = ops[i]["t0"], ops[i]["t0"] + ops[i]["dur"]
        while stack and ops[stack[-1]]["t0"] + ops[stack[-1]]["dur"] <= t0:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(t1, ops[p]["t0"] + ops[p]["dur"]) - t0
        stack.append(i)
    return [max(0.0, s) for s in own]


def _kind(module_name: str, progs: dict) -> str:
    if progs:
        return max(progs, key=progs.get)
    return re.sub(r"\(\d+\)$", "", module_name)


def reduce(events: list) -> dict:
    """See the module docstring."""
    planes = sorted({e["plane"] for e in events
                     if DEVICE_PLANE.match(e["plane"])
                     and e["line"] == OPS_LINE})
    programs: dict = {}
    prefill_mids = []
    for plane in planes:
        ops = [e for e in events if e["plane"] == plane
               and e["line"] == OPS_LINE]
        mods = sorted((e for e in events if e["plane"] == plane
                       and e["line"] == MODULES_LINE),
                      key=lambda e: e["t0"])
        starts = [m["t0"] for m in mods]
        selfs = self_times(ops)
        per_mod = [defaultdict(float) for _ in mods]
        prog_votes = [defaultdict(int) for _ in mods]
        loose = defaultdict(float)
        for e, s in zip(ops, selfs):
            prog, path = scope_path(e.get("op_name"))
            key = "/".join(path) or UNSCOPED
            # the program run that holds the op's start
            m = bisect.bisect_right(starts, e["t0"]) - 1
            if m < 0 or e["t0"] >= mods[m]["t0"] + mods[m]["dur"]:
                loose[key] += s
                continue
            per_mod[m][key] += s
            if prog:
                prog_votes[m][prog] += 1
        for m, mod in enumerate(mods):
            kind = _kind(mod["name"], prog_votes[m])
            if plane == planes[0] and kind in PREFILL_KINDS:
                prefill_mids.append(mod["t0"] + mod["dur"] / 2)
            p = programs.setdefault(kind, {"runs": 0, "device_s": 0.0,
                                           "scopes": defaultdict(float)})
            p["runs"] += 1
            p["device_s"] += mod["dur"] * 1e-9
            for key, s in per_mod[m].items():
                p["scopes"][key] += s * 1e-9
        if loose:
            p = programs.setdefault("outside_programs", {
                "runs": 0, "device_s": 0.0, "scopes": defaultdict(float)})
            for key, s in loose.items():
                p["scopes"][key] += s * 1e-9
    n = max(1, len(planes))
    for p in programs.values():
        p["device_s"] /= n
        p["scopes"] = {k: v / n for k, v in sorted(
            p["scopes"].items(), key=lambda kv: -kv[1])}
        p["runs"] = p["runs"] // n
    return {"devices": len(planes), "programs": programs,
            "spans": spans(events, prefill_mids), "idle": idle(events)}


def scope_s(reduced: dict, kind: str, scope: str) -> float:
    """Self seconds of the ops of program ``kind`` whose scope path holds
    ``scope``."""
    p = reduced["programs"].get(kind)
    if not p:
        return 0.0
    return sum(s for key, s in p["scopes"].items()
               if scope in key.split("/"))


def per_run_ms(reduced, kind: str, scope: str):
    """:func:`scope_s` per run of ``kind``, in ms; None where the trace
    has no run of ``kind`` or nothing under ``scope``."""
    if not reduced:
        return None
    runs = reduced["programs"].get(kind, {}).get("runs")
    s = scope_s(reduced, kind, scope)
    if not runs or not s:
        return None
    return 1e3 * s / runs


def table(reduced: dict) -> dict:
    """The scope table as ``scopes.py`` prints it: for each
    program kind its runs, device seconds, the self seconds of its ops, the
    share of those under a named scope, and the seconds by scope path."""
    out = {}
    for kind, p in reduced["programs"].items():
        ops_s = sum(p["scopes"].values())
        named = ops_s - p["scopes"].get(UNSCOPED, 0.0)
        out[kind] = {"runs": p["runs"], "device_s": p["device_s"],
                     "ops_s": ops_s,
                     "named_pct": 100.0 * named / ops_s if ops_s else None,
                     "scopes": p["scopes"]}
    return out


def spans(events: list, prefill_mids: list) -> dict:
    """The ``serve.*`` host spans: counts by name, those outside any
    ``serve.step`` of their thread, the uids of ``serve.prefill`` spans,
    and the real rows of those that hold the midpoint of a prefill program
    run (``prefill_mids``, in time order).  A ``serve.prefill`` span ends
    after the host has read the first token, so its program runs inside
    it; the midpoint leaves room for the offset between the host's and the
    device's clocks, about 1 ms on a v5e, the gap between the span's start
    and the program's."""
    host = [e for e in events if e["name"].startswith(SPAN_PREFIX)
            and not DEVICE_PLANE.match(e["plane"])]
    steps = defaultdict(list)
    for e in host:
        if e["name"] == STEP_SPAN:
            steps[(e["plane"], e["line"])].append((e["t0"],
                                                   e["t0"] + e["dur"]))
    for iv in steps.values():
        iv.sort()
    counts, outside = defaultdict(int), 0
    for e in host:
        counts[e["name"]] += 1
        if e["name"] == STEP_SPAN:
            continue
        a, b = e["t0"], e["t0"] + e["dur"]
        iv = steps.get((e["plane"], e["line"]), [])
        # the latest step that opened at or before the span (steps of one
        # thread do not overlap)
        i = bisect.bisect_right(iv, (a, float("inf"))) - 1
        if i < 0 or b > iv[i][1]:
            outside += 1
    prefills = [e for e in host if e["name"] == PREFILL_SPAN]
    real = 0
    for e in prefills:
        i = bisect.bisect_left(prefill_mids, e["t0"])
        if i < len(prefill_mids) and prefill_mids[i] < e["t0"] + e["dur"]:
            real += int(e.get("args", {}).get("real", 0))
    return {"counts": dict(counts), "outside_step": outside,
            "prefill_uids": [e.get("args", {}).get("uid")
                             for e in prefills],
            "prefill_real_rows": real}


def idle(events: list):
    """Device idle time while a ``serve.step`` was open, in seconds: in
    all (``idle_s`` of ``step_s``) and by the innermost ``serve.*`` span
    open in each piece (``by_span``), with the count of ``serve.decode``
    spans to put it per decode step.  An idle gap is cut at the spans'
    edges; a piece goes to the latest-opened span that holds its midpoint.
    None where the trace has no device op."""
    busy = _union([[e["t0"], e["t0"] + e["dur"]] for e in events
                   if DEVICE_PLANE.match(e["plane"])
                   and e["line"] == OPS_LINE])
    if not busy:
        return None
    host = sorted((e for e in events if e["name"].startswith(SPAN_PREFIX)
                   and not DEVICE_PLANE.match(e["plane"])),
                  key=lambda e: e["t0"])
    starts = [e["t0"] for e in host]
    bounds = sorted({x for e in host for x in (e["t0"], e["t0"] + e["dur"])})

    def label(t: float) -> str:
        i = bisect.bisect_right(starts, t)
        for e in reversed(host[max(0, i - 512):i]):
            if t < e["t0"] + e["dur"]:
                return e["name"]
        return STEP_SPAN

    steps = _union([[e["t0"], e["t0"] + e["dur"]] for e in host
                    if e["name"] == STEP_SPAN])
    by_span = defaultdict(float)
    j = 0
    for lo, hi in steps:
        # the gaps between busy intervals inside [lo, hi)
        while j < len(busy) and busy[j][1] <= lo:
            j += 1
        t, k = lo, j
        while t < hi:
            if k < len(busy) and busy[k][0] <= t:
                t = max(t, busy[k][1])
                k += 1
                continue
            end = min(hi, busy[k][0]) if k < len(busy) else hi
            a, b = bisect.bisect_right(bounds, t), \
                bisect.bisect_left(bounds, end)
            cuts = [t] + bounds[a:b] + [end]
            for x, y in zip(cuts, cuts[1:]):
                by_span[label((x + y) / 2)] += (y - x) * 1e-9
            t = end
    return {"step_s": sum(b - a for a, b in steps) * 1e-9,
            "idle_s": sum(by_span.values()),
            "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
            "decode_spans": sum(1 for e in host
                                if e["name"] == "serve.decode")}
