"""``scopes.py`` on the toy cell on the CPU: the engine's counters give
the idle decode rows and the padding, ``Request.prefill_t`` the queue
wait, and every ``serve.*`` span of the trace nests in a ``serve.step``
and names a request of the window.  A CPU trace has no TPU device plane,
so the readings of device scopes and the idle table stay silent."""
from pathlib import Path

import pytest

import cells
import scopes

HERE = Path(__file__).resolve().parent
DATA = HERE / "data"
LAYOUT = cells.Layout(benchmark_json=DATA / "BENCHMARK.json",
                      configs=DATA / "configs", traffic=DATA / "traffic",
                      metrics=HERE.parent / "metrics")


def test_toy_window_reads_the_counters_and_spans():
    res = scopes.run_window("tiny-glm.fake_quant.tiny_chat", 2 ** 31 + 5,
                            3.0, 1.5, layout=LAYOUT, require_tpu=False,
                            compile_cache=False)
    c, got = res["counters"], res["per_step"]
    assert c["decode_steps"] > 0 and c["prefill_calls"] > 0
    assert got["decode_idle_rows_per_step"] == pytest.approx(
        (c["decode_rows"] - c["decode_active_rows"]) / c["decode_steps"])
    assert 0 <= got["decode_idle_rows_per_step"] \
        < c["decode_rows"] / c["decode_steps"]
    assert 0 <= got["prefill_pad_share"] < 100
    assert got["queue_wait_p90_ms"] >= 0
    sp = res["spans"]
    assert sp["counts"]["serve.step"] > 0 and sp["counts"]["serve.prefill"] > 0
    assert sp["outside_step"] == 0 and sp["prefill_uids_not_in_window"] == 0
    assert res["idle"] is None and res["scopes"] == {}
    assert got["decode_pim_ms"] is None and got["kv_paging_ms_per_step"] is None
    assert res["traced"]["tokens_per_s"] > 0
