"""Extra distribution-layer coverage beyond tests/test_sharding.py (the
frozen spec): shard()/logical() under rules overrides, duplicate-axis
dedupe, and param_pspecs on MoE archs (experts axis, arctic weight-FSDP)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (logical, param_pspecs, shard, use_mesh,
                                 zero1_upgrade)
from repro.models.registry import build_model, get_config


def _mesh_1d():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _flat_specs(specs):
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): spec
            for path, spec in
            jax.tree_util.tree_flatten_with_path(specs)[0]}


# ---------------------------------------------------------------------------
# shard / logical under rules overrides
# ---------------------------------------------------------------------------

def test_logical_override_disables_model_axes():
    with use_mesh(_mesh_1d(), rules={"ffn": None, "heads": None}):
        assert logical("batch", None, "ffn") == P(("data",), None, None)
        assert logical("batch", None, "heads") == P(("data",), None, None)
        # untouched rules still resolve
        assert logical(None, None, "vocab") == P(None, None, "model")


def test_logical_override_remaps_axis():
    # a context can point a logical axis at a different mesh axis entirely
    with use_mesh(_mesh_1d(), rules={"seq": "data", "batch": None}):
        assert logical("batch", "seq", None) == P(None, "data", None)


def test_shard_under_rules_override_runs_and_keeps_shape():
    with use_mesh(_mesh_1d(), rules={"seq": None}):
        x = jnp.ones((2, 6, 8))
        y = shard(x, "batch", "seq", "ffn")
        assert y.shape == x.shape
        assert bool(jnp.all(y == x))


def test_shard_dedupes_repeated_mesh_axes():
    """'seq' and 'ffn' both resolve to 'model'; shard must keep only the
    first occurrence instead of emitting an invalid duplicate-axis spec."""
    with use_mesh(_mesh_1d()):
        x = jnp.zeros((2, 4, 8))
        y = shard(x, None, "seq", "ffn")     # would be P(None,'model','model')
        assert y.shape == x.shape

        @jax.jit
        def f(t):
            return shard(t, None, "seq", "ffn")
        assert f(x).shape == x.shape         # valid under jit too


def test_shard_inside_jit_noop_without_mesh():
    @jax.jit
    def f(t):
        return shard(t, "batch", "seq", None) * 2
    x = jnp.ones((2, 3, 4))
    assert f(x).shape == x.shape


# ---------------------------------------------------------------------------
# param_pspecs on MoE archs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def moe_shapes():
    cfg = get_config("granite-moe-3b-a800m", smoke=True)
    init_fn, _, _ = build_model(cfg)
    return jax.eval_shape(init_fn, jax.random.PRNGKey(0))


def test_param_pspecs_moe_experts_axis(moe_shapes):
    with use_mesh(_mesh_1d()):
        flat = _flat_specs(param_pspecs(moe_shapes))
    expert_leaves = {p: s for p, s in flat.items()
                     if p.endswith(("moe/w_gate", "moe/w_up", "moe/w_down"))}
    assert expert_leaves, "MoE arch produced no expert FFN weights"
    for p, s in expert_leaves.items():
        # (n_periods, E, d1, d2): experts dim -> 'model', rest replicated
        assert s == P(None, "model", None, None), (p, s)
    assert flat["periods/layer_0/moe/router/w"] == P(None, None, None)


def test_param_pspecs_moe_experts_override(moe_shapes):
    with use_mesh(_mesh_1d(), rules={"experts": None}):
        flat = _flat_specs(param_pspecs(moe_shapes))
    for p, s in flat.items():
        if p.endswith(("moe/w_gate", "moe/w_up", "moe/w_down")):
            assert s == P(None, None, None, None), (p, s)


def test_param_pspecs_moe_ffn_shard_data(moe_shapes):
    """arctic-style weight-FSDP: the expert d_ff dim additionally spreads
    over 'data' — and ZeRO-1 must then refuse to reuse 'data'."""
    with use_mesh(_mesh_1d()):
        flat = _flat_specs(param_pspecs(moe_shapes, moe_ffn_shard_data=True))
    up = flat["periods/layer_0/moe/w_up"]
    down = flat["periods/layer_0/moe/w_down"]
    assert up == P(None, "model", None, "data")
    assert down == P(None, "model", "data", None)

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    upgraded = zero1_upgrade(up, (2, 16, 128, 128), FakeMesh())
    used = [a for dim in upgraded for a in
            ((dim,) if isinstance(dim, str) else (dim or ()))]
    assert used.count("data") == 1


def test_param_pspecs_errors_on_unknown_path():
    with pytest.raises(KeyError, match="no sharding rule"):
        param_pspecs({"mystery_param": jax.ShapeDtypeStruct((4, 4),
                                                            jnp.float32)})
