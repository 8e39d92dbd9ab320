"""Per-arch smoke tests (task deliverable (f)): reduced same-family config,
one forward/train step on CPU, output shapes + no NaNs; plus decode-path
consistency checks."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.models.registry import ARCHS, build_model, get_config

KEY = jax.random.PRNGKey(0)

# XLA's CPU runtime has no BF16 x BF16 -> F32 dot for the batched
# crossbar-group einsum once a layer spans more than one 128-row group; the
# TPU runs it natively.  These CPU tests therefore compute in float32.
CPU_DTYPE = "float32"

# heaviest smoke configs (8-layer hybrid period / enc-dec stack): these
# dominate suite wall-clock, so they carry the 'slow' mark for the CI fast
# lane (-m "not slow"); every arch still runs in the full tier-1 suite
SLOW_ARCHS = {"jamba-v0.1-52b", "whisper-medium"}


def _arch_params(archs=ARCHS):
    return [pytest.param(a, marks=pytest.mark.slow) if a in SLOW_ARCHS else a
            for a in archs]


def _batch(cfg, b=2, s=32, with_labels=False):
    rng = np.random.default_rng(0)
    out = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                                 jnp.int32)}
    if cfg.encoder_layers:
        out["embeds"] = jnp.asarray(rng.normal(0, 1, (b, s, cfg.d_model)),
                                    jnp.float32)
    elif cfg.frontend in ("patch", "frames"):
        out["embeds"] = jnp.asarray(rng.normal(0, 1, (b, 8, cfg.d_model)),
                                    jnp.float32)
    if with_labels:
        s_total = s + (8 if (cfg.frontend != "none"
                             and not cfg.encoder_layers) else 0)
        out["labels"] = jnp.zeros((b, s_total), jnp.int32)
    return out


@pytest.mark.parametrize("arch", _arch_params())
def test_arch_smoke_forward(arch):
    cfg = get_config(arch, smoke=True)
    init_fn, apply_fn, _ = build_model(cfg)
    params = init_fn(KEY)
    batch = _batch(cfg)
    logits, _, aux = apply_fn(params, batch, mode="train")
    b, s = batch["tokens"].shape
    s_total = s + (batch.get("embeds").shape[1]
                   if ("embeds" in batch and not cfg.encoder_layers) else 0)
    assert logits.shape == (b, s_total, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert float(aux) >= 0.0


@pytest.mark.parametrize("arch", _arch_params())
def test_arch_smoke_train_step(arch):
    """One real optimizer step: finite loss, finite grad norm, params move."""
    from repro.configs.base import TrainConfig
    from repro.train.loop import make_train_step
    cfg = get_config(arch, smoke=True)
    init_fn, apply_fn, _ = build_model(cfg)
    train_step, opt_init = make_train_step(apply_fn, cfg, TrainConfig())
    params = init_fn(KEY)
    opt = opt_init(params)
    batch = _batch(cfg, with_labels=True)
    # step=5: inside warmup so lr > 0 (lr(0) == 0 by schedule)
    params2, opt2, metrics = jax.jit(train_step)(params, opt, batch, 5)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["gnorm"]))
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved, "optimizer step did not change any parameter"


@pytest.mark.parametrize("arch", _arch_params())
def test_arch_prefill_decode_no_nan(arch):
    cfg = get_config(arch, smoke=True)
    init_fn, apply_fn, cache_fn = build_model(cfg)
    params = init_fn(KEY)
    b, s, max_len = 2, 16, 32
    batch = _batch(cfg, b=b, s=s)
    cache = cache_fn(b, max_len)
    logits, cache, _ = apply_fn(params, batch, cache=cache, mode="prefill")
    assert logits.shape[0] == b and logits.shape[1] == 1
    step = {"tokens": jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)}
    logits2, cache, _ = apply_fn(params, step, cache=cache, mode="decode")
    assert logits2.shape[1] == 1
    assert not bool(jnp.any(jnp.isnan(logits2)))


@pytest.mark.parametrize("arch", _arch_params(["llama3.2-3b", "rwkv6-7b",
                                               "jamba-v0.1-52b"]))
def test_decode_matches_teacher_forcing(arch):
    """Sequential decode with cache == full-sequence forward at every
    position (the cache path is mathematically the same function).

    capacity_factor is raised so MoE drops no tokens: capacity-bounded
    dispatch makes outputs depend on the co-batched token set, which is
    expected MoE behaviour, not a cache bug."""
    cfg = get_config(arch, smoke=True).replace(remat="none",
                                               capacity_factor=16.0)
    init_fn, apply_fn, cache_fn = build_model(cfg)
    params = init_fn(KEY)
    b, s = 1, 8
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)

    # full forward logits at last position
    full_logits, _, _ = apply_fn(params, {"tokens": toks}, mode="train")

    # prefill s-1 then decode token s-1
    cache = cache_fn(b, s + 4)
    _, cache, _ = apply_fn(params, {"tokens": toks[:, :-1]}, cache=cache,
                           mode="prefill")
    dec_logits, _, _ = apply_fn(params, {"tokens": toks[:, -1:]}, cache=cache,
                                mode="decode")
    # decode dots the bf16 cache directly (f32 accumulation): probs round
    # to bf16 (eps ~8e-3) before the PV dot, so ~1% logit noise is the
    # serving datapath's numerical contract, not a cache bug
    np.testing.assert_allclose(np.asarray(dec_logits[0, 0]),
                               np.asarray(full_logits[0, -1]),
                               rtol=5e-2, atol=5e-2)


def test_moe_router_balance_aux():
    """MoE aux loss is positive and finite; top-k dispatch respects capacity."""
    cfg = get_config("granite-moe-3b-a800m", smoke=True)
    init_fn, apply_fn, _ = build_model(cfg)
    params = init_fn(KEY)
    _, _, aux = apply_fn(params, _batch(cfg), mode="train")
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_pim_fake_quant_mode_close_to_exact():
    """TRQ fake-quant inference stays close to the exact datapath (the
    paper's accuracy-preservation claim, model-level)."""
    cfg = get_config("llama3.2-3b", smoke=True).replace(dtype=CPU_DTYPE)
    init_fn, apply_fn, _ = build_model(cfg)
    params = init_fn(KEY)
    batch = _batch(cfg)
    exact, _, _ = apply_fn(params, batch, mode="train")

    cfg_q = cfg.replace(pim_backend="fake_quant")
    _, apply_q, _ = build_model(cfg_q)
    quant, _, _ = apply_q(params, batch, mode="train")
    # logits correlate strongly (not exact — ADC quantization is real)
    a, b = np.asarray(exact).ravel(), np.asarray(quant).ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr > 0.98
    assert not np.allclose(a, b)                  # quantization DID happen


def test_scan_vs_unrolled_same_function():
    cfg = get_config("deepseek-7b", smoke=True).replace(remat="none")
    init_fn, apply_fn, _ = build_model(cfg)
    params = init_fn(KEY)
    batch = _batch(cfg)
    scan_logits, _, _ = apply_fn(params, batch, mode="train")
    cfg_u = cfg.replace(scan_layers=False)
    _, apply_u, _ = build_model(cfg_u)
    unroll_logits, _, _ = apply_u(params, batch, mode="train")
    # bf16 compute: scan and unrolled layers schedule reductions
    # differently; 0.05 absolute on ~1.0-rms logits is accumulation noise
    np.testing.assert_allclose(np.asarray(scan_logits),
                               np.asarray(unroll_logits), rtol=5e-2,
                               atol=5e-2)


def test_long_context_archs_use_constant_state():
    """rwkv6: cache size is independent of sequence length (what makes
    long_500k feasible)."""
    cfg = get_config("rwkv6-7b", smoke=True)
    _, _, cache_fn = build_model(cfg)
    c1 = cache_fn(1, 128)
    c2 = cache_fn(1, 4096)
    s1 = sum(np.prod(l.shape) for l in jax.tree.leaves(c1))
    s2 = sum(np.prod(l.shape) for l in jax.tree.leaves(c2))
    assert s1 == s2
