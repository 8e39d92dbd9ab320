"""The reduction by the program's own names, on hand-made events and on a
recorded excerpt of a chip trace."""
import gzip
import json
from pathlib import Path

import pytest

import cells
import loadgen
import scope_reduce as sr
import scopes
import trace_reduce

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6
DEC = "jit(step)/runtime.decode/while/body"
PRE = "jit(step)/runtime.prefill/while/body"


def op(name, t0, dur, op_name):
    return {"plane": DEV, "line": sr.OPS_LINE, "name": name, "t0": t0 * MS,
            "dur": dur * MS, "op_name": op_name}


def mod(name, t0, dur):
    return {"plane": DEV, "line": sr.MODULES_LINE, "name": name,
            "t0": t0 * MS, "dur": dur * MS}


def span(name, t0, dur, **args):
    return {"plane": HOST, "line": "python", "name": name, "t0": t0 * MS,
            "dur": dur * MS, "args": args}


def _toy():
    """A prefill program (0-10 ms) and two decode programs, each a layer
    ``while`` whose body ops sit inside it, then a KV program."""
    ev = [span("serve.step", 0, 40, queued=0, active=1),
          span("serve.prefill", 0, 11, uid=5, rows=16, real=12, reused=0),
          span("serve.decode", 12, 8, step=0, active=1),
          span("serve.zero_slot", 38, 1, slot=1),
          span("serve.sample", 45, 1),           # outside every step
          mod("jit_step(1)", 0, 10),
          op("while.1", 0, 9, "jit(step)/runtime.prefill/while"),
          op("fusion.1", 0, 4, PRE + "/pim_linear/fake_quant/convert"),
          op("fusion.2", 4, 2, PRE + "/attention.core/exp"),
          op("fusion.3", 9, 1, "jit(step)/runtime.prefill/lm_head/"
             "pim_linear/fake_quant/range_fit/reduce_max"),
          mod("jit__assemble(2)", 10.5, 1),
          op("gather.1", 10.5, 1, "jit(_assemble)/kv.assemble/gather")]
    for t in (12, 24):
        ev += [mod("jit_step(3)", t, 10),
               op("while.2", t, 8, "jit(step)/runtime.decode/while"),
               op("fusion.4", t, 3, DEC + "/pim_linear/fake_quant/convert"),
               op("fusion.5", t + 3, 1, DEC + "/attention.core/dot"),
               op("copy.1", t + 4, 1, None),
               op("fusion.6", t + 8, 2, "jit(step)/runtime.decode/lm_head/"
                  "pim_linear/fake_quant/range_fit/abs")]
    return ev


def test_self_time_of_a_while_leaves_out_its_body():
    ops = [op("while", 0, 10, None), op("a", 1, 3, None),
           op("b", 5, 2, None), op("inner_while", 7, 3, None),
           op("c", 8, 1, None)]
    assert [s / MS for s in sr.self_times(ops)] == [2, 3, 2, 2, 1]
    assert sum(sr.self_times(ops)) == ops[0]["dur"]


def test_scope_path_keeps_known_scopes_and_the_backend():
    assert sr.scope_path("jit(step)/runtime.decode/while/body/lm_head/"
                         "pim_linear/pallas/convert/trq_group_mvm/"
                         "pallas_call") == (
        "runtime.decode",
        ("lm_head", "pim_linear", "pallas", "convert", "trq_group_mvm"))
    assert sr.scope_path("jit(step)/runtime.decode/layer_scan/while/body/"
                         "closed_call/attention.core/dot") == (
        "runtime.decode", ("attention.core",))
    assert sr.scope_path("jit(step)/runtime.decode/layer_scan/while/body/"
                         "squeeze") == ("runtime.decode", ("layer_scan",))
    assert sr.scope_path("jit(_assemble)/kv.assemble/gather") == (
        "kv.assemble", ("kv.assemble",))
    assert sr.scope_path(None) == (None, ())
    assert sr.scope_path("jit(step)/while/body/add") == (None, ())


def test_programs_scopes_and_no_double_count():
    r = sr.reduce(_toy())
    dec = r["programs"]["runtime.decode"]
    assert dec["runs"] == 2
    assert dec["device_s"] == pytest.approx(20e-3)
    sc = dec["scopes"]
    assert sc["pim_linear/fake_quant/convert"] == pytest.approx(6e-3)
    assert sc["attention.core"] == pytest.approx(2e-3)
    assert sc["lm_head/pim_linear/fake_quant/range_fit"] == \
        pytest.approx(4e-3)
    # the while's own time (8 - 5 ms of body per run) and the copy
    assert sc[sr.UNSCOPED] == pytest.approx(8e-3)
    assert sum(sc.values()) == pytest.approx(20e-3)
    assert r["programs"]["runtime.prefill"]["runs"] == 1
    assert r["programs"]["kv.assemble"]["device_s"] == pytest.approx(1e-3)
    assert sr.per_run_ms(r, "runtime.decode", "pim_linear") == \
        pytest.approx(5.0)
    assert sr.per_run_ms(r, "runtime.decode", "attention.core") == \
        pytest.approx(1.0)
    assert sr.scope_s(r, "runtime.prefill", "pim_linear") == \
        pytest.approx(5e-3)
    t = sr.table(r)
    assert t["runtime.decode"]["named_pct"] == pytest.approx(60.0)


def test_spans_nesting_and_prefill_rows():
    sp = sr.reduce(_toy())["spans"]
    assert sp["counts"]["serve.step"] == 1
    assert sp["outside_step"] == 1                     # the late sample
    assert sp["prefill_uids"] == [5]
    assert sp["prefill_real_rows"] == 12


def test_prefill_rows_tolerate_the_clock_offset():
    """The device clock may put a prefill program's start a little before
    its span's: the program's midpoint still lies inside the span."""
    ev = [dict(e, t0=e["t0"] + 0.5 * MS) if e["name"] == "serve.prefill"
          else e for e in _toy()]
    assert sr.reduce(ev)["spans"]["prefill_real_rows"] == 12


def test_without_program_scopes_programs_go_by_module_name():
    """A program built without the scopes (an older program) reduces to
    module names, and the readings of scopes find nothing."""
    ev = [dict(e, op_name=None) if "op_name" in e else e for e in _toy()]
    r = sr.reduce(ev)
    assert "runtime.decode" not in r["programs"]
    assert r["programs"]["jit_step"]["runs"] == 3
    assert sr.per_run_ms(r, "runtime.decode", "pim_linear") is None
    assert sr.reduce([]) == {"devices": 0, "programs": {},
                             "spans": {"counts": {}, "outside_step": 0,
                                       "prefill_uids": [],
                                       "prefill_real_rows": 0},
                             "idle": None}


def test_idle_goes_to_the_innermost_span():
    """Device busy 0-10, 10.5-11.5, 12-22 and 24-34 ms inside a step open
    0-40 ms: the gap at 10 ms falls in the prefill span, the gap at 38 ms
    partly in a slot reset, the rest in the step alone; the late sample
    lies outside every step and gets nothing."""
    idle = sr.reduce(_toy())["idle"]
    assert idle["step_s"] == pytest.approx(40e-3)
    assert idle["idle_s"] == pytest.approx(9e-3)
    assert idle["by_span"] == pytest.approx({"serve.step": 7.5e-3,
                                             "serve.zero_slot": 1e-3,
                                             "serve.prefill": 0.5e-3})
    assert idle["decode_spans"] == 1


def test_idle_is_silent_without_device_ops():
    ev = [e for e in _toy() if e["plane"] == HOST]
    assert sr.reduce(ev)["idle"] is None


def _msg(*fields):
    """Protobuf bytes of (field number, int | bytes | str) pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _var(f << 3) + _var(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _var(f << 3 | 2) + _var(len(v)) + v
    return out


def _var(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def test_op_names_read_from_the_event_metadata(tmp_path):
    """A TPU op event's op_name is the ``tf_op`` stat of its metadata; a
    name that two programs give different op_names to maps to None."""
    def meta(i, name, tf_op):
        stat = _msg((1, 7), (5, tf_op))
        return _msg((1, i), (2, _msg((1, i), (2, name), (5, stat))))

    dev = _msg((1, 1), (2, DEV),
               (5, _msg((1, 7), (2, _msg((1, 7), (2, "tf_op"))))),
               (5, _msg((1, 8), (2, _msg((1, 8), (2, "flops"))))),
               (4, meta(1, "%fusion.1 = f32[8]", "jit(step)/runtime.decode/"
                        "pim_linear/fake_quant/convert/dot_general:")),
               (4, meta(2, "%copy.1 = s32[]", "jit(step)/a:")),
               (4, meta(3, "%copy.1 = s32[]", "jit(_assemble)/b:")),
               (3, _msg((2, sr.OPS_LINE))))
    host = _msg((1, 2), (2, HOST),
                (5, _msg((1, 7), (2, _msg((1, 7), (2, "tf_op"))))),
                (4, meta(1, "serve.step", "x:")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, dev), (1, host)))
    assert sr.op_names(path) == {DEV: {
        "%fusion.1 = f32[8]":
            "jit(step)/runtime.decode/pim_linear/fake_quant/convert/"
            "dot_general",
        "%copy.1 = s32[]": None}}


RECORDED = Path(__file__).parent / "data" / "scope_excerpt.json.gz"


def test_recorded_chip_excerpt():
    """0.15 s of a traced window of the glm4 cell on one TPU v5e, from the
    start of an engine step that admitted a 488-token prompt: its 512-row
    prefill, the KV programs, one 32-row decode step and the sampling ops.
    The events are those ``load`` keeps, op names cut to 160 characters."""
    with gzip.open(RECORDED, "rt") as f:
        r = sr.reduce(json.load(f))
    t = sr.table(r)
    dec, pre = t["runtime.decode"], t["runtime.prefill"]
    assert dec["runs"] == 1 and pre["runs"] == 1
    assert dec["device_s"] == pytest.approx(0.01279516)
    assert pre["device_s"] == pytest.approx(0.085585902)
    # the scopes account for the programs' device time
    assert dec["named_pct"] > 90 and pre["named_pct"] > 90
    assert dec["ops_s"] == pytest.approx(dec["device_s"], rel=1e-3)
    pim = sr.per_run_ms(r, "runtime.decode", "pim_linear")
    attn = sr.per_run_ms(r, "runtime.decode", "attention.core")
    assert pim == pytest.approx(10.186103)
    assert attn == pytest.approx(0.17227)
    assert pim + attn < 1e3 * dec["device_s"]
    assert dec["scopes"]["lm_head/pim_linear/fake_quant/convert"] > 0
    assert {k for k in t if k.startswith("kv.")} == {
        "kv.assemble", "kv.write_blocks", "kv.slot_scatter", "kv.zero_pages"}
    sp = r["spans"]
    assert sp["outside_step"] == 0
    assert sp["prefill_uids"] == [111] and sp["prefill_real_rows"] == 488
    assert sp["counts"]["serve.zero_slot"] == 14
    # the host's gaps: slot resets and sampling hold most of them
    idle = r["idle"]
    assert idle["idle_s"] == pytest.approx(0.024348441)
    assert idle["idle_s"] == pytest.approx(sum(idle["by_span"].values()))
    assert list(idle["by_span"])[:2] == ["serve.zero_slot", "serve.sample"]
    assert idle["by_span"]["serve.zero_slot"] == pytest.approx(0.009670532)


def test_readings_of_the_recorded_decode_step():
    """``scopes.readings`` on the recorded excerpt: the attention holds its
    core, the new token's K/V write and the layer scan's slicing and
    stacking of the cache, and no ``pim_linear`` time; the counters give
    the idle rows and the padding, ``Request.prefill_t`` the queue wait."""
    with gzip.open(RECORDED, "rt") as f:
        r = sr.reduce(json.load(f))
    c = {"decode_steps": 4, "decode_rows": 128, "decode_active_rows": 70,
         "prefill_rows": 1024, "prefill_real_rows": 768}
    got = scopes.readings(r, c, [0.1, 0.2, 0.3])
    dec_ms = 1e3 * sr.table(r)["runtime.decode"]["device_s"]
    assert got["decode_step_ms"] == pytest.approx(dec_ms)
    assert got["decode_pim_ms"] == pytest.approx(10.186103)
    assert got["decode_attention_ms"] == pytest.approx(
        0.17227 + 0.918839 + 1.338999)
    assert got["decode_pim_ms"] + got["decode_attention_ms"] < dec_ms
    assert got["kv_paging_ms_per_step"] == pytest.approx(1e3 * sum(
        p["device_s"] for k, p in r["programs"].items()
        if k.startswith("kv.")))
    assert got["prefill_pim_ms_per_ktok"] > 0
    assert got["decode_idle_rows_per_step"] == pytest.approx(14.5)
    assert got["prefill_pad_share"] == pytest.approx(25.0)
    assert got["queue_wait_p90_ms"] == pytest.approx(
        1e3 * loadgen.percentile([0.1, 0.2, 0.3], 90))
    none = scopes.readings(sr.reduce([]), dict.fromkeys(c, 0), [])
    assert set(none.values()) == {None}


def _kv_trace(modules):
    """A device trace of two decode steps and the programs ``modules``,
    reduced as ``run.py`` reduces it."""
    ev = []
    for i, name in enumerate(["jit_step"] * 2 + list(modules)):
        ev += [mod(f"{name}({i})", 10 * i, 2), op("fusion.1", 10 * i, 2, None)]
    return trace_reduce.reduce(ev)


def test_kv_paging_reads_the_kv_programs_per_decode_step():
    read = cells.metric_reader("kv_paging_ms_per_step")
    t = _kv_trace(["jit_kv.assemble", "jit_kv.write_blocks",
                   "jit_kv.slot_scatter"])
    ctx = {"trace": t, "traced_calls": {"decode_steps": 2}}
    assert read(ctx) == pytest.approx(3 * 2.0 / 2)
    # a program that does not name its KV programs reads nothing
    old = _kv_trace(["jit__assemble", "jit__write_blocks", "jit_scatter_cache"])
    assert read(dict(ctx, trace=old)) is None
    assert read({"trace": {"devices": 0}, "traced_calls": None}) is None
    assert read({"trace": None}) is None
