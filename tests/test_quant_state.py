"""Per-layer QuantState: regex resolution, Algorithm-1 packaging, JSON /
checkpoint round-trip, and the acceptance-criterion end-to-end: calibrated
per-layer registers change what at least two model families compute in a
serve step."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.calibrate import calibrate_layer, to_quant_state
from repro.core.quant_state import (QuantState, active_quant_state,
                                    load_quant_state,
                                    save_quant_state, use_quant_state)
from repro.core.trq import make_params
from repro.models.registry import build_model, get_config

KEY = jax.random.PRNGKey(0)

# XLA's CPU runtime has no BF16 x BF16 -> F32 dot for the batched
# crossbar-group einsum once a layer spans more than one 128-row group; the
# TPU runs it natively.  These CPU tests therefore compute in float32.
CPU_DTYPE = "float32"


def _params(**kw):
    base = dict(delta_r1=1.0, n_r1=4, n_r2=4, m=3, signed=True)
    base.update(kw)
    return make_params(**base)


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------

def test_lookup_first_match_wins_and_default():
    fine = _params(n_r1=6)
    coarse = _params(n_r1=1)
    fallback = _params(n_r1=3)
    qs = QuantState(rules=((r"attn/wq$", fine), (r"attn/", coarse)),
                    default=fallback)
    assert qs.lookup("layer_0/attn/wq").n_r1 == 6
    assert qs.lookup("layer_0/attn/wk").n_r1 == 1
    assert qs.lookup("layer_0/mlp/w_up").n_r1 == 3
    assert qs.lookup(None).n_r1 == 3
    assert QuantState().lookup("anything") is None


def test_use_quant_state_nesting_and_none_passthrough():
    qs = QuantState(rules=((r".", _params()),))
    assert active_quant_state() is None
    with use_quant_state(qs):
        assert active_quant_state() is qs
        with use_quant_state(None):          # None keeps the outer state
            assert active_quant_state() is qs
    assert active_quant_state() is None


def test_quant_state_is_a_pytree():
    qs = QuantState(rules=((r"a$", _params(n_r1=2)),
                           (r"b$", _params(n_r1=5))),
                    default=_params())
    leaves = jax.tree_util.tree_leaves(qs)
    assert len(leaves) == 6                  # (delta_r1, bias) x 3
    qs2 = jax.tree.map(lambda x: x * 2.0, qs)
    assert isinstance(qs2, QuantState)
    assert float(qs2.lookup("a").delta_r1) == 2.0 * float(
        qs.lookup("a").delta_r1)
    assert qs2.lookup("a").n_r1 == 2         # statics survive as aux data


# ---------------------------------------------------------------------------
# Algorithm-1 packaging + serialization round-trip
# ---------------------------------------------------------------------------

def _calibrated_state(rng):
    y1 = np.abs(rng.normal(0, 2.0, 4096)).round()
    y2 = np.abs(rng.normal(0, 9.0, 4096)).round()
    cal = {"layer_0/attn/wq": calibrate_layer(y1, n_max=5),
           "layer_0/mlp/w_up": calibrate_layer(y2, n_max=5)}
    return cal, to_quant_state(cal, signed=True)


def test_from_calibration_exact_names(rng):
    cal, qs = _calibrated_state(rng)
    assert len(qs) == 2
    got = qs.lookup("layer_0/attn/wq")
    want = cal["layer_0/attn/wq"].params
    assert (got.n_r1, got.n_r2, got.m) == (want.n_r1, want.n_r2, want.m)
    assert got.signed is True                # override applied
    # exact-match anchors: a superstring name must not resolve
    assert qs.lookup("layer_0/attn/wq/extra") is None


def test_json_round_trip(tmp_path, rng):
    _, qs = _calibrated_state(rng)
    path = save_quant_state(str(tmp_path / "qs.json"), qs)
    qs2 = load_quant_state(path)
    assert len(qs2) == len(qs)
    for (pat, p), (pat2, p2) in zip(qs.rules, qs2.rules):
        assert pat == pat2
        assert float(p.delta_r1) == float(p2.delta_r1)
        assert float(p.bias) == float(p2.bias)
        for f in ("n_r1", "n_r2", "m", "nu", "mode", "signed"):
            assert getattr(p, f) == getattr(p2, f)


def test_corrupt_json_raises_value_error_naming_path(tmp_path):
    """A truncated/corrupt register file fails as a ``ValueError`` that
    names the offending path and points at recalibration — not as a raw
    ``JSONDecodeError`` from inside the json module."""
    import json
    # local stream: don't advance the session ``rng`` mid-module (later
    # modules' bitwise-parity inputs must match the pre-existing sequence)
    _, qs = _calibrated_state(np.random.default_rng(20260808))
    path = save_quant_state(str(tmp_path / "qs.json"), qs)
    blob = open(path).read()

    truncated = str(tmp_path / "truncated.json")
    with open(truncated, "w") as f:
        f.write(blob[: len(blob) // 2])          # torn mid-write copy
    with pytest.raises(ValueError, match="truncated.json.*recalibrate") as ei:
        load_quant_state(truncated)
    assert isinstance(ei.value.__cause__, json.JSONDecodeError)

    garbage = str(tmp_path / "garbage.json")
    with open(garbage, "w") as f:
        f.write("not json at all {{{")
    with pytest.raises(ValueError, match="garbage.json"):
        load_quant_state(garbage)

    # a missing file is still a plain FileNotFoundError, not wrapped
    with pytest.raises(FileNotFoundError):
        load_quant_state(str(tmp_path / "nope.json"))


def test_json_schema_is_versioned(tmp_path, rng):
    """Saved states stamp the schema version; pre-versioning files load as
    schema 1; a snapshot from a NEWER schema fails loudly instead of
    silently misparsing the registers."""
    import json
    from repro.core.quant_state import QUANT_STATE_VERSION
    _, qs = _calibrated_state(rng)
    path = save_quant_state(str(tmp_path / "qs.json"), qs)
    with open(path) as f:
        d = json.load(f)
    assert d["version"] == QUANT_STATE_VERSION == 1

    legacy = dict(d)
    del legacy["version"]                       # pre-versioning file
    p2 = str(tmp_path / "legacy.json")
    with open(p2, "w") as f:
        json.dump(legacy, f)
    assert len(load_quant_state(p2)) == len(qs)

    future = dict(d, version=QUANT_STATE_VERSION + 1)
    p3 = str(tmp_path / "future.json")
    with open(p3, "w") as f:
        json.dump(future, f)
    with pytest.raises(ValueError, match="version"):
        load_quant_state(p3)


def test_checkpoint_dir_round_trip(tmp_path, rng):
    """A quant state saved next to a checkpoint restores from the dir."""
    from repro.ckpt.checkpoint import save, restore
    _, qs = _calibrated_state(rng)
    tree = {"w": np.ones((4, 4), np.float32)}
    save(str(tmp_path), 3, tree)
    save_quant_state(str(tmp_path), qs)      # <ckpt>/quant_state.json
    restored_tree = restore(str(tmp_path), tree)
    qs2 = load_quant_state(str(tmp_path))
    assert np.allclose(restored_tree["w"], tree["w"])
    assert [pat for pat, _ in qs2.rules] == [pat for pat, _ in qs.rules]


# ---------------------------------------------------------------------------
# end-to-end: per-layer registers drive serving (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["llama3.2-3b", "rwkv6-7b"])
def test_quant_state_changes_per_layer_registers_in_serve_step(
        tmp_path, arch, rng):
    """Two model families, a real serve step (prefill): a QuantState that
    pins one layer's registers to a degenerate 1-bit ADC changes the logits
    relative to the default registers; a round-trip through save/load
    reproduces the state bit-for-bit."""
    cfg = get_config(arch, smoke=True).replace(
        pim_backend="fake_quant", param_dtype="bfloat16", remat="none",
        dtype=CPU_DTYPE)
    init_fn, apply_fn, cache_fn = build_model(cfg)
    params = init_fn(KEY)
    b, s = 1, 8
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)}

    def serve_step(qs):
        with use_quant_state(qs):
            cache = cache_fn(b, 16)
            logits, _, _ = apply_fn(params, batch, cache=cache,
                                    mode="prefill")
            return np.asarray(logits)

    base = serve_step(None)
    crush_q = QuantState(rules=(
        (r"layer_0/(attn/wq|rwkv/w_r)$",
         _params(n_r1=1, n_r2=1, m=0, delta_r1=8.0)),))
    crush_o = QuantState(rules=(
        (r"layer_0/(attn/wo|rwkv/w_o)$",
         _params(n_r1=1, n_r2=1, m=0, delta_r1=8.0)),))

    got_q = serve_step(crush_q)
    got_o = serve_step(crush_o)
    assert not np.allclose(got_q, base), "per-layer registers ignored"
    assert not np.allclose(got_o, base)
    assert not np.allclose(got_q, got_o), \
        "different layer rules produced identical logits"

    path = save_quant_state(str(tmp_path / f"{arch.replace('/', '_')}.json"),
                            crush_q)
    np.testing.assert_array_equal(serve_step(load_quant_state(path)), got_q)


def test_unrolled_model_exposes_per_depth_names(rng):
    """scan_layers=False names every depth distinctly (layer_0, layer_1,
    ...), so per-depth calibrated registers are reachable; the scan path
    shares period-local names by design."""
    from repro.pim import ad_ops_tally
    cfg = get_config("llama3.2-3b", smoke=True).replace(
        pim_backend="fake_quant", scan_layers=False, remat="none",
        dtype=CPU_DTYPE)
    assert cfg.n_layers == 2 and cfg.period == 1
    init_fn, apply_fn, _ = build_model(cfg)
    params = init_fn(KEY)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)}
    with ad_ops_tally() as t:
        apply_fn(params, batch, mode="train")
    prefixes = {n.split("/")[0] for n in t.by_layer if n.startswith("layer")}
    assert prefixes == {"layer_0", "layer_1"}

    # and a depth-1-only rule changes logits while leaving depth 0 alone
    base, _, _ = apply_fn(params, batch, mode="train")
    qs = QuantState(rules=((r"^layer_1/attn/wq$",
                            _params(n_r1=1, n_r2=1, m=0, delta_r1=8.0)),))
    with use_quant_state(qs):
        got, _, _ = apply_fn(params, batch, mode="train")
    assert not np.allclose(np.asarray(got), np.asarray(base))


def test_serve_engine_applies_quant_state(rng):
    """The engine's Runtime carries quant_state into its jit'd
    prefill/decode steps."""
    from repro import runtime
    from repro.serve.engine import ServeEngine
    cfg = get_config("llama3.2-3b", smoke=True).replace(
        pim_backend="fake_quant", param_dtype="bfloat16", remat="none",
        dtype=CPU_DTYPE)
    init_fn, apply_fn, cache_fn = build_model(cfg)
    params = init_fn(KEY)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)

    def prefill_logits(qs):
        eng = ServeEngine(runtime.compile(cfg, params, quant_state=qs),
                          max_batch=2, max_len=32)
        (logits, _), _rep = eng.rt.prefill(toks, {}, max_len=32)
        return np.asarray(logits)

    base = prefill_logits(None)
    crush = QuantState(rules=((r".", _params(n_r1=1, n_r2=1, m=0,
                                             delta_r1=16.0)),))
    got = prefill_logits(crush)
    assert not np.allclose(got, base), \
        "quant_state did not reach the engine's jit'd prefill"