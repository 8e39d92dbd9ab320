"""The program's own tracing: host spans (``repro.telemetry.trace.span``)
around the serving engine's work, device scopes (``jax.named_scope``) in
its compiled programs, and the engine's counters.

A smoke engine serves a few requests under ``jax.profiler.start_trace``;
the spans are read back from the trace the profiler wrote."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import runtime
from repro.models.registry import build_model, get_config
from repro.serve.engine import ServeEngine
from repro.telemetry import trace

MAX_BATCH, MAX_LEN = 2, 64
# prompt lengths: 12 and 7 pad into the 16- and 8-row buckets, 30 into 32
PROMPTS = (12, 30, 7)
MAX_NEW = 4


def _engine():
    # float32: XLA's CPU runtime lacks the crossbar-group einsum's
    # BF16 x BF16 -> F32 dot; the program's bf16 path is unchanged
    cfg = get_config("glm4-9b", smoke=True).replace(
        remat="none", pim_backend="fake_quant", dtype="float32")
    init_fn, _, _ = build_model(cfg)
    rt = runtime.compile(cfg, init_fn(jax.random.PRNGKey(0)))
    return ServeEngine(rt, max_batch=MAX_BATCH, max_len=MAX_LEN)


def _serve(engine, seed=0):
    rng = np.random.default_rng(seed)
    reqs = [engine.submit(rng.integers(1, engine.cfg.vocab_size, n),
                          max_new_tokens=MAX_NEW) for n in PROMPTS]
    engine.run()
    return reqs


def _spans(trace_dir) -> list:
    """(name, start, end, args) of every ``serve.*`` host span."""
    [path] = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One smoke engine warmed up, then serving under the profiler, with
    the rows of every Runtime call logged."""
    engine = _engine()
    _serve(engine, seed=1)                        # compile outside the trace
    fed = {"prefill": [], "decode": []}
    prefill, decode = engine.rt.prefill, engine.rt.decode

    def log_prefill(tokens, *a, **kw):
        fed["prefill"].append(np.asarray(tokens))
        return prefill(tokens, *a, **kw)

    def log_decode(tokens, *a, **kw):
        fed["decode"].append(np.asarray(tokens))
        return decode(tokens, *a, **kw)

    engine.rt.prefill, engine.rt.decode = log_prefill, log_decode
    c0 = dict(engine.counters)
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    try:
        reqs = _serve(engine, seed=2)
    finally:
        jax.profiler.stop_trace()
    counters = {k: v - c0[k] for k, v in engine.counters.items()}
    return engine, reqs, fed, counters, _spans(d)


def test_span_is_a_shared_no_op_with_the_profiler_off(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("an annotation was built with tracing off")
    monkeypatch.setattr(trace, "TraceAnnotation", refuse)
    a, b = trace.span("serve.x"), trace.span("serve.y", uid=3, rows=16)
    assert a is b
    with a:
        pass


def test_span_records_name_and_args_when_on(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("serve.probe", uid=7, rows=16):
            pass
    finally:
        jax.profiler.stop_trace()
    got = [s for s in _spans(tmp_path) if s[0] == "serve.probe"]
    assert len(got) == 1
    assert got[0][3]["uid"] == 7 and got[0][3]["rows"] == 16


def test_scoped_opens_a_fresh_scope_per_call():
    """A nested call of a scoped function leaves the name stack as it
    found it, so the ops after it are not under the scope."""
    @trace.scoped("layer")
    def f(x, depth):
        return x * 2.0 if depth == 0 else f(x + 1.0, depth - 1)

    def g(x):
        return jnp.sin(f(x, 1))

    names = _op_names(jax.jit(g).lower(1.0).compile())
    assert "layer/layer/mul" in names
    assert re.search(r"^jit\(g\)/sin$", names, re.M)
    assert f.__name__ == "f"


SPAN_ARGS = {"serve.step": {"queued", "active"}, "serve.admit": set(),
             "serve.prefill": {"uid", "rows", "real", "reused"},
             "serve.install": {"uid", "slot"}, "serve.decode_cache": set(),
             "serve.decode": {"step", "active"}, "serve.writeback": set(),
             "serve.sample": set(), "serve.zero_slot": {"rows"}}


def test_every_span_nests_in_a_step_and_carries_its_args(traced):
    engine, reqs, _, counters, spans = traced
    assert {s[0] for s in spans} == set(SPAN_ARGS)
    steps = [(a, b) for name, a, b, _ in spans if name == "serve.step"]
    for name, a, b, args in spans:
        assert SPAN_ARGS[name] <= set(args), (name, args)
        if name != "serve.step":
            assert any(s <= a and b <= t for s, t in steps), name
    uids = {r.uid for r in reqs}
    pre = [args for name, _, _, args in spans if name == "serve.prefill"]
    assert sorted(p["uid"] for p in pre) == sorted(uids)
    assert {args["uid"] for name, _, _, args in spans
            if name == "serve.install"} <= uids
    assert len(steps) == counters["decode_steps"]
    resets = [args for name, _, _, args in spans if name == "serve.zero_slot"]
    assert len(resets) == counters["reset_calls"]
    assert sum(a["rows"] for a in resets) == counters["slot_resets"]
    for r in reqs:
        assert r.submit_t <= r.prefill_t <= r.first_token_t


def test_counters_conserve(traced):
    engine, reqs, fed, c, _ = traced
    # rows fed to the Runtime: padded bucket rows, and the prompt's own
    # (nonzero) tokens among them
    assert c["prefill_calls"] == len(fed["prefill"]) == len(PROMPTS)
    assert c["prefill_rows"] == sum(t.shape[1] for t in fed["prefill"])
    assert c["prefill_real_rows"] == sum(int((t != 0).sum())
                                         for t in fed["prefill"]) \
        == sum(PROMPTS)
    assert c["decode_steps"] == len(fed["decode"])
    assert c["decode_rows"] == sum(t.shape[0] for t in fed["decode"]) \
        == MAX_BATCH * c["decode_steps"]
    # every token after a request's first came from a decode step
    assert c["decode_active_rows"] == sum(len(r.generated) - 1
                                          for r in reqs)
    assert c["installs"] == len(reqs)
    assert engine.stats()["counters"] == engine.counters


def _op_names(compiled) -> str:
    return "\n".join(re.findall(r'op_name="([^"]+)"', compiled.as_text()))


@pytest.fixture(scope="module")
def lowered(traced):
    """The compiled decode and 16-row prefill steps of the smoke engine."""
    engine = traced[0]
    rt = engine.rt
    cache = engine.kv.assemble(
        engine.state_cache,
        np.zeros((MAX_BATCH, engine.kv.pages_per_slot), np.int32))
    toks = jnp.zeros((MAX_BATCH, 1), jnp.int32)
    dec = rt._jits[("decode",)].lower(rt.params, rt.plan, cache, toks,
                                      {}).compile()
    pre = rt._jits[("prefill", MAX_LEN)].lower(
        rt.params, rt.plan, jnp.zeros((1, 16), jnp.int32), {}).compile()
    return _op_names(dec), _op_names(pre)


@pytest.mark.parametrize("scope", [
    "pim_linear/fake_quant/range_fit", "pim_linear/fake_quant/convert",
    "attention.core", "lm_head/pim_linear"])
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_compiled_steps_carry_the_scopes(lowered, kind, scope):
    names = lowered[0] if kind == "decode" else lowered[1]
    assert f"runtime.{kind}/" in names
    assert scope in names
    # every op of the step runs under the program's scope
    assert all(f"runtime.{kind}" in n for n in names.split("\n")
               if n.startswith("jit("))


def test_kv_programs_carry_their_scopes_and_names(traced):
    """Each KV program, and the sampler, runs under its scope and compiles
    as a module of the same name, which is how a trace names its runs."""
    engine = traced[0]
    kv = engine.kv
    table = np.zeros((MAX_BATCH, kv.pages_per_slot), np.int32)
    page = jnp.int32(1)
    compiled = {
        "kv.assemble": kv._assemble_jit.lower(
            kv.pools, engine.state_cache, jnp.asarray(table)),
        "kv.slot_scatter": engine._scatter_jit.lower(
            engine.state_cache, kv.make_state(1), 0),
        "kv.slot_reset": engine._reset_jit.lower(
            engine.state_cache, kv.make_state(1),
            np.ones((MAX_BATCH,), bool)),
        "sample": engine._sample_jit.lower(
            jnp.zeros((MAX_BATCH, engine.cfg.vocab_size)),
            np.zeros((MAX_BATCH,), np.float32), jax.random.PRNGKey(0)),
        "kv.zero_pages": kv._zero_jit.lower(kv.pools,
                                            jnp.zeros((1,), jnp.int32)),
        "kv.copy_page": kv._copy_jit.lower(kv.pools, page, page)}
    for name, low in compiled.items():
        c = low.compile()
        assert c.as_text().startswith(f"HloModule jit_{name},"), name
        assert name in _op_names(c), name
