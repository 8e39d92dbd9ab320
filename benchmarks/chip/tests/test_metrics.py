"""The end-to-end arithmetic on hand-made request records."""
import math
import types

import pytest

import run

def _rec(due, times, plen=10, uid=0):
    req = types.SimpleNamespace(uid=uid, generated=[1] * len(times),
                                done=True, prompt=[1] * plen)
    r = run.Record(req, due, due)
    r.times, r.plen, r.padded = list(times), plen, 16
    return r


def _win(seconds=10.0):
    return {"end": seconds, "steps": 0}


def _steady(n_req=10, gap=0.05, tokens=40, stall_at=None, stall=0.0):
    """Requests due every second; each then gets a token every ``gap`` s,
    with one ``stall`` of the whole engine at time ``stall_at``."""
    recs = {}
    for i in range(n_req):
        due = float(i)
        ts, t = [], due + 0.1
        for _ in range(tokens):
            if stall_at is not None and t >= stall_at and \
                    (not ts or ts[-1] < stall_at):
                t += stall
            ts.append(t)
            t += gap
        recs[i] = _rec(due, ts, uid=i)
    return recs


def test_ttft_is_measured_from_the_due_time():
    recs = {0: _rec(1.0, [1.3, 1.4]), 1: _rec(2.0, [2.1, 2.2], uid=1)}
    e = run.end_to_end(_win(), recs, 10.0, 0.0)
    # nearest-rank p90 of {300 ms, 100 ms}
    assert math.isclose(e["ttft_p90_ms"], 300.0, rel_tol=1e-9)
    assert e["attempted"] == 2 and e["failed"] == 0


def test_a_request_without_a_first_token_fails():
    recs = {0: _rec(1.0, [1.2]), 1: _rec(9.5, [], uid=1),
            2: _rec(9.9, [10.3], uid=2)}            # answered after the close
    e = run.end_to_end(_win(), recs, 10.0, 0.0)
    assert e["attempted"] == 3 and e["failed"] == 2
    assert math.isinf(e["ttft_p90_ms"])


def test_requests_due_after_the_window_are_not_attempted():
    recs = {0: _rec(1.0, [1.2]), 1: _rec(10.5, [10.6], uid=1)}
    e = run.end_to_end(_win(), recs, 10.0, 0.0)
    assert e["attempted"] == 1 and e["failed"] == 0


def test_tokens_per_s_counts_tokens_inside_the_window():
    recs = {0: _rec(0.0, [1.0, 2.0, 9.0, 11.0])}
    e = run.end_to_end(_win(), recs, 10.0, 0.0)
    assert e["tokens_per_s"] == pytest.approx(0.3)


def test_a_stall_moves_tpot_and_tokens_per_s():
    base = run.end_to_end(_win(), _steady(), 10.0, 0.0)
    stalled = run.end_to_end(_win(), _steady(stall_at=5.0, stall=2.0),
                             10.0, 0.0)
    assert stalled["tpot_p90_ms"] > 1.5 * base["tpot_p90_ms"]
    assert stalled["tokens_per_s"] < base["tokens_per_s"]


def test_tpot_needs_a_quarter_second_of_tokens():
    recs = {0: _rec(0.0, [0.1, 0.15, 0.2])}           # 100 ms of decode
    e = run.end_to_end(_win(), recs, 10.0, 0.0)
    assert e["tpot_p90_ms"] is None


def test_ad_ops_per_token_divides_by_prompt_and_generated_tokens():
    recs = {0: _rec(0.0, [0.1, 0.2, 0.3], plen=7)}
    e = run.end_to_end(_win(), recs, 10.0, 1000.0)
    assert e["ad_ops_per_token"] == pytest.approx(100.0)
