"""chip_smoke.py on the CPU: its phase functions at the smoke config, its
refusal to run without a TPU, and the compile-cache helper it calls.

The script itself only runs on the chip; here the tests steer the config
(smoke widths, float32 compute: XLA's CPU runtime lacks the BF16 x BF16 ->
F32 dot the crossbar-group einsum needs) and call the phases directly."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.models.registry import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**over):
    return get_config("llama3.2-3b", smoke=True).replace(
        param_dtype="bfloat16", remat="none", dtype="float32", **over)


def test_serve_phase_fake_quant_serves_every_request(smoke):
    out = smoke.serve_phase(_cfg(pim_backend="fake_quant"), requests=3,
                            prompt_len=8, max_new=3, max_len=32, max_batch=2)
    assert out["tokens"] == 9 and out["layers"] == 2
    assert out["total_ad_ops"] > 0 and out["compile_s"] > 0


def test_serve_phase_pallas_rejects_an_interpreted_kernel(smoke):
    """Serving passes its checks in interpret mode, but the kernel check
    must refuse a decode step without the compiled Mosaic call."""
    cfg = smoke.pallas_cut(_cfg())
    assert cfg.pim_backend == "pallas" and cfg.n_layers == 2
    assert cfg.d_model == _cfg().d_model
    with pytest.raises(RuntimeError, match="tpu_custom_call"):
        smoke.serve_phase(cfg, requests=2, prompt_len=8, max_new=2,
                          max_len=32, max_batch=2, kernel=True)


def test_agreement_phase_within_stated_tolerances(smoke):
    out = smoke.agreement_phase(_cfg())
    assert set(out) == {"layer_0/attn/wq", "layer_0/mlp/w_down"}
    for rec in out.values():
        assert rec["y_rel_l2_pallas_vs_fq"] <= smoke.PALLAS_Y_RTOL
        assert rec["ad_ops_fake_quant"] > 0
        assert min(rec["corr_vs_exact"].values()) > smoke.EXACT_MIN_CORR


def test_train_phase_compares_losses_across_meshes(smoke):
    """On one CPU device both meshes are (1, 1): the comparison and the
    optimizer-spread check still run end to end."""
    out = smoke.train_phase(_cfg(pim_backend="fake_quant"), chips=1,
                            steps=2, batch=2, seq=16)
    assert len(out["losses"][1]) == 2


def _run(code_or_script, env_over, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **env_over)
    return subprocess.run([sys.executable, *code_or_script], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_script_exits_nonzero_without_a_tpu():
    res = _run(["chip_smoke.py"], {})
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr


CACHE_PROBE = """
import jax, jax.numpy as jnp
import repro.launch.compile_cache as cc
cc.DEFAULT_DIR = cc.Path({default!r})
print(cc.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_writes_only_where_told(tmp_path, env_set):
    env_dir, default = tmp_path / "env", tmp_path / "default"
    over = {"JAX_COMPILATION_CACHE_DIR": str(env_dir)} if env_set else {}
    res = _run(["-c", CACHE_PROBE.format(default=str(default))], over)
    assert res.returncode == 0, res.stderr
    want, other = (env_dir, default) if env_set else (default, env_dir)
    assert res.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir())
    assert not other.exists()
