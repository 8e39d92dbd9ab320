"""Sharding-rule and distribution-plumbing tests."""
import re

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (_PARAM_RULES, logical, param_pspecs, shard,
                                 use_mesh, zero1_upgrade)
from repro.models.registry import ARCHS, build_model, get_config


def _mesh_1d():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_every_param_path_matches_a_rule():
    unmatched = set()
    for arch in ARCHS:
        cfg = get_config(arch, smoke=True)
        init_fn, _, _ = build_model(cfg)
        ps = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        for path, _ in jax.tree_util.tree_flatten_with_path(ps)[0]:
            p = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                         for k in path)
            if not any(re.search(pat, p) for pat, _ in _PARAM_RULES):
                unmatched.add(p)
    assert not unmatched, f"params with no sharding rule: {sorted(unmatched)}"


def test_param_pspecs_shard_big_dims():
    mesh = _mesh_1d()
    cfg = get_config("llama3.2-3b", smoke=True)
    init_fn, _, _ = build_model(cfg)
    ps = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    with use_mesh(mesh):
        specs = param_pspecs(ps)
    flat = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): spec
            for path, spec in jax.tree_util.tree_flatten_with_path(specs)[0]}
    # model axis size 1 -> everything resolves but specs still have shape
    assert all(isinstance(s, P) for s in flat.values())


def test_indivisible_dims_dropped():
    """whisper's 51865 vocab must NOT be sharded on a 16-way axis."""
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # fake a 16-way mesh via rule check at the logical level instead:
    # use a real 1x1 mesh but call _drop_indivisible directly
    from repro.dist.sharding import _drop_indivisible, _ACTIVE
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    old = _ACTIVE["mesh"]
    _ACTIVE["mesh"] = FakeMesh()
    try:
        spec = _drop_indivisible(P("model", None), (51865, 1024))
        assert spec == P(None, None)
        spec2 = _drop_indivisible(P("model", None), (51200, 1024))
        assert spec2 == P("model", None)
    finally:
        _ACTIVE["mesh"] = old


def test_zero1_no_duplicate_axes():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    # dim3 already uses 'data' -> must not add it again on dim2
    spec = zero1_upgrade(P(None, "model", None, "data"),
                         (1, 128, 7168, 4864), FakeMesh())
    used = [a for dim in spec for a in
            ((dim,) if isinstance(dim, str) else (dim or ()))]
    assert used.count("data") <= 1


def test_zero1_upgrades_first_divisible_dim():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = zero1_upgrade(P(None, "model"), (4096, 1024), FakeMesh())
    assert spec == P("data", "model")


def test_shard_noop_unmeshed():
    x = jnp.zeros((4, 8))
    y = shard(x, "batch", "seq")
    assert y.shape == x.shape


def test_shard_skips_indivisible_dims():
    mesh = _mesh_1d()
    with use_mesh(mesh):
        x = jnp.zeros((3, 5, 7))
        y = shard(x, "batch", "seq", "ffn")   # nothing divides -> no crash
        assert y.shape == x.shape


def test_logical_resolution_under_rules_override():
    mesh = _mesh_1d()
    with use_mesh(mesh, rules={"seq": None}):
        assert logical("batch", "seq") == P(("data",), None)


def test_kvcache_pspecs_cover_all_leaves():
    from repro.serve.kvcache import cache_pspecs
    mesh = _mesh_1d()
    for arch in ("llama3.2-3b", "jamba-v0.1-52b", "rwkv6-7b",
                 "whisper-medium"):
        cfg = get_config(arch, smoke=True)
        _, _, cache_fn = build_model(cfg)
        cache = jax.eval_shape(lambda: cache_fn(4, 64))
        specs = cache_pspecs(mesh, cfg, cache, 4)
        assert jax.tree.structure(cache) == jax.tree.structure(specs)
