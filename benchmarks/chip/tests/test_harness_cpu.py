"""Whole runs of a toy cell on the CPU: a cell added as files only, the
reference catching each fault the serving path can have, and the refusal
to run off a TPU or outside a checkout."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import cells
import run

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
DATA = HERE / "data"
CELL = "tiny-glm.fake_quant.tiny_chat"


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    """The toy cell's files plus a cell added with nothing but new files:
    a traffic mix, a limits file, a metric reader and a workload entry."""
    d = tmp_path_factory.mktemp("bench")
    shutil.copytree(DATA, d, dirs_exist_ok=True)
    shutil.copytree(CHIP / "metrics", d / "metrics")
    mix = json.loads((d / "traffic" / "tiny_chat.json").read_text())
    mix.update(shared_prefix=None, arrivals={"process": "poisson",
                                             "rate_per_s": 4.0})
    (d / "traffic" / "dummy_mix.json").write_text(json.dumps(mix))
    shutil.copy(d / "limits" / f"{CELL}.json",
                d / "limits" / "tiny-glm.fake_quant.dummy_mix.json")
    (d / "metrics" / "dummy_rows.py").write_text(
        "def read(ctx):\n    return float(sum(ctx['prefill_padded']))\n")
    bench = json.loads((d / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-glm.fake_quant.dummy_mix",
                               "config": "tiny-glm.fake_quant",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "added as files only"})
    bench["per_layer"].append({"name": "dummy_rows", "unit": "rows",
                               "better": "lower", "source": "program_counter",
                               "layer": "scheduler", "moves": "tokens_per_s",
                               "workloads": ["tiny-glm.fake_quant.dummy_mix"]})
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    return cells.Layout(benchmark_json=d / "BENCHMARK.json",
                        configs=d / "configs", traffic=d / "traffic",
                        metrics=d / "metrics")


def _run(layout, cell=CELL, seed=2 ** 31 + 3, trace=False, fault=None):
    return run.run_cell(cell, seed, 3.0, trace, layout=layout,
                        require_tpu=False, compile_cache=False, fault=fault,
                        t_start=time.perf_counter())


def test_cell_added_as_files_runs_and_is_correct(layout):
    res = _run(layout, "tiny-glm.fake_quant.dummy_mix", trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 10 and res["failed"] <= 1
    m = res["metrics"]
    assert m["dummy_rows"]["value"] > 0
    assert 0 <= m["prefill_pad_share"]["value"] < 100
    assert m["ad_ops_per_token"]["value"] > 0
    assert list(res)[-2:] == ["checks", "_info"]
    assert res["_info"]["compiles_in_window"] == 0


def test_end_to_end_line(layout):
    res = _run(layout)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "ttft_p90_ms",
                                   "tpot_p90_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert res["_info"]["checked_tokens"] >= 50


def _alter_tokens(engine):
    sample = engine._sample

    def wrong(logits, temps):
        return (sample(logits, temps) + 1) % engine.cfg.vocab_size
    engine._sample = wrong


def _freeze_cache(engine):
    engine._writeback = lambda new_cache, active: None


def _drop_half_rows(engine):
    decode = engine.rt.decode

    def half(tokens, cache, extra=None):
        t = np.asarray(tokens).copy()
        t[1::2] = 0
        return decode(t, cache, extra)
    engine.rt.decode = half


@pytest.mark.parametrize("fault", [_alter_tokens, _freeze_cache,
                                   _drop_half_rows],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_reference_catches_fault(layout, fault):
    res = _run(layout, fault=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("control", ["fp8", "int8"])
def test_control_precision_fails_the_cell(layout, control):
    """The control at toy size, on prompts of 200-480 tokens: the
    reference replayed with float8 or int8 matmul operands in the
    program's place serves tokens that fail one of the cell's limits,
    while the program, which the replay follows exactly on the CPU,
    reads no gap at all."""
    res = run.run_cell("tiny-glm.fake_quant.tiny_long", 11, 3.0, False,
                       layout=layout,
                       require_tpu=False, compile_cache=False,
                       t_start=time.perf_counter(), controls=(control,))
    assert res["correct"], res["checks"]
    assert res["checks"]["logit_gap_max"]["value"] == 0.0
    ctl = res["_info"]["controls"][control]
    assert any(ctl[k] > c["limit"] for k, c in res["checks"].items()), ctl


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _first_cell():
    return json.loads((ROOT / "BENCHMARK.json").read_text())[
        "workloads"][0]["name"]


def test_off_the_tpu_there_is_no_result():
    p = _cli(ROOT, "--workload", _first_cell(), "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, "--workload", _first_cell(), "--seed",
             "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
