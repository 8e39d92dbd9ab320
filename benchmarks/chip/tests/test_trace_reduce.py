"""The trace reduction on hand-made events and on a recorded excerpt of a
chip trace."""
import gzip
import json
from pathlib import Path

import pytest

import trace_reduce as tr

DEV = "/device:TPU:0"
MS = 1e6


def ev(plane, line, name, t0_ms, dur_ms):
    return {"plane": plane, "line": line, "name": name, "t0": t0_ms * MS,
            "dur": dur_ms * MS}


def _toy():
    return [
        ev("/host:CPU", "python", "step", 0, 10),
        ev("/host:CPU", "python", "decode", 0, 1),
        ev("/host:CPU", "python", "sample", 6, 3),
        ev("/host:CPU", "python", "step", 12, 8),
        ev(DEV, "XLA Modules", "jit_step(1)", 1, 4),
        ev(DEV, "XLA Ops", "fusion.1", 1, 2),
        ev(DEV, "XLA Ops", "_kernel", 3, 2),
        ev(DEV, "XLA Modules", "jit_step(2)", 13, 5),
        ev(DEV, "XLA Ops", "_kernel", 13, 5),
    ]


def test_busy_idle_and_gaps():
    r = tr.reduce(_toy(), spans=("step", "decode", "sample"),
                  kernels={"k": r"_kernel"}, window=(0, 20 * MS))
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(9e-3)
    assert r["window_s"] == pytest.approx(20e-3)
    # engine work spans 18 ms, device busy 9 ms of them
    assert r["engine_s"] == pytest.approx(18e-3)
    assert r["engine_idle_s"] == pytest.approx(9e-3)
    gaps = dict(r["idle_gaps"])
    assert gaps["decode"] == pytest.approx(1e-3)       # 0-1 ms
    assert gaps["sample"] == pytest.approx(3e-3)       # 6-9 ms
    assert gaps["step"] == pytest.approx(5e-3)   # 5-6, 9-10, 12-13, 18-20
    assert gaps["outside harness spans"] == pytest.approx(2e-3)  # 10-12
    assert sum(gaps.values()) == pytest.approx(20e-3 - r["busy_s"])
    assert r["kernels"]["k"] == {"s": pytest.approx(7e-3), "n": 2}
    assert [m[0] for m in r["module_runs"]] == ["jit_step", "jit_step"]
    assert dict(r["device_ops"])["_kernel"] == pytest.approx(7e-3)


def test_no_device_ops_reduces_to_nothing():
    assert tr.reduce([ev("/host:CPU", "python", "step", 0, 1)],
                     spans=("step",)) == {"devices": 0}


RECORDED = Path(__file__).parent / "data" / "trace_excerpt.json.gz"
SPANS = ("step", "admit", "prefill", "install", "decode_cache", "decode",
         "writeback", "sample", "zero_slot")


def test_recorded_chip_excerpt():
    """0.15 s of a traced chat window on one TPU v5e: two engine steps, one
    with a 1024-row prefill, and the slot resets after each."""
    with gzip.open(RECORDED, "rt") as f:
        events = json.load(f)
    r = tr.reduce(events, spans=SPANS)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.151732025)
    assert r["busy_s"] == pytest.approx(0.123658072)
    assert r["engine_idle_s"] == pytest.approx(0.028073953)
    assert r["span_counts"]["step"] == 2 and r["span_counts"]["prefill"] == 1
    gaps = dict(r["idle_gaps"])
    assert max(gaps, key=gaps.get) == "zero_slot"
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    runs = [m for m in r["module_runs"] if m[0] == "jit_step"]
    assert len(runs) == 3 and all(d > 0 for _, d in runs)
