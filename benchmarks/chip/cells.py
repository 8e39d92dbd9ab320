"""Find a cell's pieces by name: ``BENCHMARK.json`` names a cell, the cell
names a configuration and a traffic mix, and each per-layer metric has a
reader of its own.  All of them are files found by their names:

    configs/<config>.json    sizes, backend, engine settings, source
    traffic/<traffic>.json   the mix, read by ``loadgen``
    metrics/<metric>.py      ``read(ctx) -> float | None``

A later change adds a model, a mix or a metric by adding such files and
entries in ``BENCHMARK.json``; nothing here names one of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where the benchmark's files live (tests point it at a copy)."""
    benchmark_json: Path = HERE.parents[1] / "BENCHMARK.json"
    configs: Path = HERE / "configs"
    traffic: Path = HERE / "traffic"
    metrics: Path = HERE / "metrics"


def _json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_benchmark(layout: Layout = Layout()) -> dict:
    return _json(layout.benchmark_json)


def workload(name: str, layout: Layout = Layout()) -> dict:
    """The ``workloads`` entry of ``name``, with its metric declarations."""
    bench = load_benchmark(layout)
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in {layout.benchmark_json}; "
                       f"known: {[w['name'] for w in bench['workloads']]}")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in e2e_names]
    return dict(w, end_to_end=e2e, per_layer=per_layer)


def load_config(name: str, layout: Layout = Layout()) -> dict:
    return _json(layout.configs / f"{name}.json")


def load_traffic(name: str, layout: Layout = Layout()) -> dict:
    return _json(layout.traffic / f"{name}.json")


def metric_reader(name: str, layout: Layout = Layout()):
    """``metrics/<name>.py``'s ``read`` function."""
    path = layout.metrics / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chip_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(spec: dict):
    """The program's ``ModelConfig`` for a configuration file, checked
    against the file's sizes so that a drift in the program's model file
    is an error and not a silent change of the cell."""
    from repro.models.registry import get_config
    prog = spec["program"]
    cfg = get_config(prog["arch"], smoke=prog.get("smoke", False))
    d = spec["dims"]
    cfg = cfg.replace(n_layers=d["n_layers"], pim_backend=prog["backend"],
                      param_dtype=prog["param_dtype"], dtype=prog["dtype"],
                      remat="none")
    got = {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
           "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd,
           "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
           "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps}
    a, t = spec["arithmetic"], cfg.trq
    got.update({"arithmetic." + k: v for k, v in (
        ("n_r1", t.n_r1), ("n_r2", t.n_r2), ("m", t.m),
        ("delta_r1", t.delta_r1), ("delta_grid", t.delta_grid),
        ("auto_range", t.auto_range), ("attn_chunk_q", cfg.attn_chunk_q),
        ("attn_chunk_k", cfg.attn_chunk_k))})
    want = dict(d, **{"arithmetic." + k: v for k, v in a.items()})
    bad = {k: (v, want[k]) for k, v in got.items() if v != want[k]}
    if t.bias != 0 or not t.signed:
        bad["arithmetic.signed_bias0"] = ((t.signed, t.bias), (True, 0))
    if bad:
        raise ValueError(f"program config {prog['arch']} differs from "
                         f"the benchmark's file (program, file): {bad}")
    if cfg.family != "dense" or cfg.mlp_act != "silu" or cfg.tie_embeddings \
            or cfg.attn_bias or cfg.period != 1:
        raise ValueError(f"{prog['arch']}: the benchmark's weights and "
                         f"reference cover dense gated-MLP decoders only")
    return cfg
