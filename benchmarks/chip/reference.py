"""The plain reference: the served model's stated arithmetic, replayed call
by call over what the window served.

The configuration states the system's arithmetic (its file's
``arithmetic``): bfloat16 weights, activations and KV cache, and every
linear layer run on crossbars of ``xbar`` rows whose partial sums are
converted by a twin-range SAR ADC (TRQ, the paper's Eq. 7 with the
registers ``n_r1``, ``n_r2``, ``m``, auto-ranged per call).  A conversion's
range is fitted to the largest partial sum of the whole call, so a row's
output depends on the rows that ran beside it.  The reference therefore
replays the window as the engine ran it: each prompt's prefill alone, and
each decode step over all ``max_batch`` rows, the idle ones included (token
0 at position 0 over an empty cache), teacher-forced on the served tokens.
It is written in straightforward ``jax.numpy`` with a dense per-slot cache,
imports nothing of the program and is given only the benchmark's own
weights (``weights.make_params``, made again from the seed) and the token
ids the harness logged.

At each position that produced a served token it reads by how much that
token's reference logit lies below the reference's best.  A server that
computes the stated arithmetic serves the reference's best token, or one
within rounding of it.

``controls`` replay the same steps at a precision below the stated one:
``"fp8"`` (float8 e4m3) or ``"int8"`` matmul operands, weights and KV
cache, each with a power-of-two max-abs scale per tensor and call.  At
each position the token such a model puts first is read against the
reference's logits the same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
FORMATS = {"fp8": 448.0, "int8": 127.0}        # largest code of each format


# ---------------------------------------------------------------------------
# number formats
# ---------------------------------------------------------------------------

def _round(x, fmt: str):
    """``x`` rounded to ``fmt`` with a power-of-two max-abs scale; the
    result keeps ``x``'s dtype, which holds the rounded values exactly.
    ``"ref"`` is the stated precision itself: no rounding."""
    if fmt == "ref":
        return x
    top = FORMATS[fmt]
    xf = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30)
    s = jnp.exp2(jnp.ceil(jnp.log2(amax / top)))
    if fmt == "fp8":
        q = (xf / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        q = jnp.clip(jnp.round(xf / s), -top, top)
    return (q * s).astype(x.dtype)


# ---------------------------------------------------------------------------
# the crossbar linear layer: per-group partial sums through the TRQ ADC
# ---------------------------------------------------------------------------

def _adc(s, delta, a):
    """Signed twin-range conversion (Eq. 7, bias 0) of partial sums ``s``
    given in ADC grid units: |s| inside R1 = [0, 2^n_r1 * delta) is rounded
    on the fine step ``delta`` with n_r1 bits, the rest on the coarse step
    ``delta * 2^m`` with n_r2 bits; codes round half up and saturate."""
    mag = jnp.abs(s)
    coarse_step = delta * (2.0 ** a["m"])
    fine = jnp.clip(jnp.floor(mag / delta + 0.5), 0,
                    2 ** a["n_r1"] - 1).astype(jnp.float32) * delta
    coarse = jnp.clip(jnp.floor(mag / coarse_step + 0.5), 0,
                      2 ** a["n_r2"] - 1).astype(jnp.float32) * coarse_step
    in_r1 = (mag >= 0.0) & (mag < (2.0 ** a["n_r1"]) * delta)
    return jnp.sign(s) * jnp.where(in_r1, fine, coarse)


def _linear(x, w, w_scale, a, fmt):
    """``x @ w`` on crossbars of ``a["xbar"]`` rows.  The ADC grid is the
    product of the activations' and the weights' max-abs scales; the
    coarse range is auto-fitted to the call's largest |partial sum|; the
    converted partial sums are summed in float32 and rounded once to the
    activations' dtype."""
    x = _round(x, fmt)
    k, n = w.shape
    xbar = a["xbar"]
    pad = (-k) % xbar
    xg = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x
    wg = jnp.pad(w, ((0, pad), (0, 0))) if pad else w
    xg = xg.reshape(*x.shape[:-1], (k + pad) // xbar, xbar)
    wg = wg.reshape((k + pad) // xbar, xbar, n)
    a_scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-6) / a["levels"]
    grid = (jnp.asarray(a_scale, jnp.float32)
            * jnp.asarray(w_scale, jnp.float32) * a["delta_grid"])
    p = jnp.einsum("...gx,gxn->...gn", xg, wg,
                   preferred_element_type=jnp.float32)
    delta = a["delta_r1"]
    if a["auto_range"]:
        reach = 2.0 ** (a["n_r2"] + a["m"])
        vmax = jnp.max(jnp.abs(p))
        delta = delta * jnp.maximum(vmax / grid / reach, 1e-6)
    q = _adc(p / grid, delta, a) * grid
    return jnp.sum(q, axis=-2).astype(x.dtype)


def _weight_scale(w, a):
    """The weights' max-abs grid scale, in their own dtype."""
    return jnp.maximum(jnp.max(jnp.abs(w)), 1e-6) / a["levels"]


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    n = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (n * scale).astype(x.dtype)


def _rope(x, positions, theta):
    """x: (B, S, H, hd) rotated by ``positions`` (B, S); halves paired."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _prefill_attention(q, k, v, a, fmt):
    """Causal attention of one prompt, in float32.  A prompt of more than
    ``attn_chunk_q`` rows that splits evenly runs as online softmax over
    key blocks of up to ``attn_chunk_k`` (the values summed before the
    normalisation); a shorter one as one softmax."""
    b, s, kv, g, hd = q.shape
    scale = hd ** -0.5
    cq, ck = a["attn_chunk_q"], min(s, a["attn_chunk_k"])
    if not (s > cq and s % cq == 0 and s % ck == 0):
        sc = jnp.einsum("bqkgd,bskd->bkgqs",
                        _round(q.astype(jnp.float32) * scale, fmt),
                        _round(k.astype(jnp.float32), fmt))
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(mask[None, None, None], sc, NEG_INF)
        o = jnp.einsum("bkgqs,bskd->bqkgd",
                       _round(jax.nn.softmax(sc, axis=-1), fmt),
                       _round(v.astype(jnp.float32), fmt))
        return o.astype(q.dtype)
    nq, nk = s // cq, s // ck
    qc = _round(q.reshape(b, nq, cq, kv, g, hd).astype(jnp.float32) * scale,
                fmt)
    qpos = jnp.arange(nq)[:, None] * cq + jnp.arange(cq)[None, :]

    def block(carry, args):
        m, l, acc = carry
        kj, vj, j = args
        sc = jnp.einsum("bnqkgd,bskd->bnkgqs", qc,
                        _round(kj.astype(jnp.float32), fmt))
        mask = qpos[..., None] >= (j * ck + jnp.arange(ck))[None, None, :]
        sc = jnp.where(mask[None, :, None, None], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        acc = acc * corr[..., None] + jnp.einsum(
            "bnkgqs,bskd->bnkgqd", _round(p, fmt),
            _round(vj.astype(jnp.float32), fmt))
        return (m_new, l * corr + p.sum(-1), acc), None

    m0 = jnp.full((b, nq, kv, g, cq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nq, kv, g, cq), jnp.float32)
    a0 = jnp.zeros((b, nq, kv, g, cq, hd), jnp.float32)
    kb = jnp.moveaxis(k.reshape(b, nk, ck, kv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nk, ck, kv, hd), 1, 0)
    (_, l, acc), _ = jax.lax.scan(block, (m0, l0, a0), (kb, vb, jnp.arange(nk)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(o, 4, 2).reshape(b, s, kv, g, hd).astype(q.dtype)


def _decode_attention(q, k_cache, v_cache, length, fmt):
    """One token per row against its cache, read in the cache's dtype with
    float32 sums; ``length`` (B,) valid entries, the new one included."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs",
                   _round((q.astype(jnp.float32) * scale)
                          .astype(k_cache.dtype), fmt),
                   k_cache, preferred_element_type=jnp.float32)
    mask = jnp.arange(k_cache.shape[1])[None, :] < length[:, None]
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    p = _round(jax.nn.softmax(s, axis=-1).astype(v_cache.dtype), fmt)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def _block(x, lw, ls, positions, d, a, fmt, kv_in=None):
    """One decoder layer.  ``kv_in`` = (k_cache, v_cache) decodes one token
    per row at ``positions`` (B, 1); without it the rows are one prompt.
    Returns (x, k, v): the whole caches when decoding, else the prompt's
    keys and values."""
    b, s, _ = x.shape
    h, kv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    cache_dtype = jnp.dtype(a["kv_cache_dtype"])

    def lin(name, inp):
        return _linear(inp, lw[name], ls[name], a, fmt)

    y = _rmsnorm(x, lw["norm1"], d["norm_eps"])
    q = _rope(lin("wq", y).reshape(b, s, h, hd), positions, d["rope_theta"])
    k = _rope(lin("wk", y).reshape(b, s, kv, hd), positions, d["rope_theta"])
    v = lin("wv", y).reshape(b, s, kv, hd)
    qg = q.reshape(b, s, kv, h // kv, hd)
    if kv_in is None:
        o = _prefill_attention(qg, k, v, a, fmt)
        k_out = _round(k, fmt).astype(cache_dtype)
        v_out = _round(v, fmt).astype(cache_dtype)
    else:
        pos = positions[:, 0]

        def put(c, new):
            return jax.vmap(lambda c1, n1, i: jax.lax.dynamic_update_slice(
                c1, n1, (i, 0, 0)))(c, _round(new, fmt).astype(c.dtype), pos)
        k_out, v_out = put(kv_in[0], k), put(kv_in[1], v)
        o = _decode_attention(qg, k_out, v_out, pos + 1, fmt)
    x = x + lin("wo", o.reshape(b, s, h * hd))
    y = _rmsnorm(x, lw["norm2"], d["norm_eps"])
    up = lin("w_up", y)
    gate = lin("w_gate", y)
    x = x + lin("w_down", jax.nn.silu(gate) * up)
    return x, k_out, v_out


def _head(x, w, d, a, fmt):
    """Final norm and vocabulary projection of each row's last position."""
    x = _rmsnorm(x, w["final_norm"], d["norm_eps"])[:, -1:]
    return _linear(x, w["lm_head"], w["lm_head_scale"], a, fmt)[:, 0] \
        .astype(jnp.float32)


LAYER_KEYS = ("norm1", "norm2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
              "w_down")


@functools.partial(jax.jit, static_argnames=("fmt", "arith", "cdtype"),
                   donate_argnames=("params",))
def prepare(params, *, fmt: str, arith, cdtype: str):
    """The weights as the datapath holds them: compute dtype, rounded to
    ``fmt``, with each matrix's (per layer) grid scale.  Takes ``params``
    over, so that one copy of the weights is held."""
    a = dict(arith)
    dt = jnp.dtype(cdtype)
    per = params["periods"]["layer_0"]
    mats = {"wq": per["attn"]["wq"]["w"], "wk": per["attn"]["wk"]["w"],
            "wv": per["attn"]["wv"]["w"], "wo": per["attn"]["wo"]["w"],
            "w_gate": per["mlp"]["w_gate"]["w"],
            "w_up": per["mlp"]["w_up"]["w"],
            "w_down": per["mlp"]["w_down"]["w"]}
    lw = {n: jax.vmap(lambda m: _round(m.astype(dt), fmt))(m)
          for n, m in mats.items()}
    ls = {n: jax.vmap(lambda m: _weight_scale(m, a))(m)
          for n, m in lw.items()}
    lw["norm1"] = per["norm1"]["scale"]
    lw["norm2"] = per["norm2"]["scale"]
    head = _round(params["lm_head"]["w"].astype(dt), fmt)
    return {"embed": _round(params["embed"]["tok"], fmt),
            "final_norm": params["final_norm"]["scale"],
            "lm_head": head, "lm_head_scale": _weight_scale(head, a),
            "layers": lw, "scales": ls}


@functools.partial(jax.jit, static_argnames=("dims", "arith", "fmt"))
def prefill(w, tokens, *, dims, arith, fmt: str):
    """One prompt (S,) alone: its keys and values (L, S, kv, hd) and the
    logits (vocab,) of its last position."""
    d, a = dict(dims), dict(arith)
    x = jnp.take(w["embed"], tokens[None], axis=0) \
        .astype(w["layers"]["wq"].dtype)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)[None]

    def body(x, lw_ls):
        x, k, v = _block(x, *lw_ls, positions, d, a, fmt)
        return x, (k[0], v[0])

    x, (k, v) = jax.lax.scan(body, x, (w["layers"], w["scales"]))
    return k, v, _head(x, w, d, a, fmt)[0]


@functools.partial(jax.jit, static_argnames=("dims", "arith", "fmt"),
                   donate_argnames=("k_cache", "v_cache"))
def decode(w, k_cache, v_cache, tokens, positions, *, dims, arith, fmt: str):
    """One token for every row: ``tokens`` (B,) at ``positions`` (B,) over
    caches (L, B, T, kv, hd).  Returns logits (B, vocab) and the caches."""
    d, a = dict(dims), dict(arith)
    x = jnp.take(w["embed"], tokens[:, None], axis=0) \
        .astype(w["layers"]["wq"].dtype)

    def body(x, args):
        lw, ls, kc, vc = args
        x, kc, vc = _block(x, lw, ls, positions[:, None], d, a, fmt,
                           kv_in=(kc, vc))
        return x, (kc, vc)

    x, (k_cache, v_cache) = jax.lax.scan(
        body, x, (w["layers"], w["scales"], k_cache, v_cache))
    return _head(x, w, d, a, fmt), k_cache, v_cache


@functools.partial(jax.jit, donate_argnames=("k_cache", "v_cache"))
def install(k_cache, v_cache, k, v, slot):
    """A prompt's keys and values into rows [0, S) of ``slot``."""
    def put(c, new):
        return jax.lax.dynamic_update_slice(
            c, new[:, None].astype(c.dtype), (0, slot, 0, 0, 0))
    return put(k_cache, k), put(v_cache, v)


@jax.jit
def gap(logits, targets):
    """Per row: best logit minus the logit of ``targets``."""
    at = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - at


# ---------------------------------------------------------------------------
# the replay
# ---------------------------------------------------------------------------

def _pass(w, fmt, *, dk, ak, cache_dtype, prompts, served, steps, max_batch,
          max_len, picks=None):
    """One replay of the window in ``fmt``.  Its events, in a fixed order,
    are each prompt's prefill (when its slot first decodes it, or at the
    end for one that never decoded) and each decode step.  For a control
    it returns the token it puts first at each event's rows; for the
    reference, per event, (gap of the served tokens, {control: gap of its
    picks}, rows compared)."""
    d = dict(dk)
    shape = (d["n_layers"], max_batch, max_len, d["n_kv_heads"],
             d["head_dim"])
    kc, vc = jnp.zeros(shape, cache_dtype), jnp.zeros(shape, cache_dtype)
    lens = np.zeros(max_batch, np.int32)
    owner = [None] * max_batch
    seen = set()
    events = []

    def read(logits, targets, live):
        if picks is None:
            events.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
            return
        e = len(events)
        events.append((gap(logits, jnp.asarray(targets, jnp.int32)),
                       {c: gap(logits, p[e]) for c, p in picks.items()},
                       live))

    def first_token(uid, slot=None):
        nonlocal kc, vc
        k, v, logits = prefill(w, jnp.asarray(prompts[uid], jnp.int32),
                               dims=dk, arith=ak, fmt=fmt)
        if slot is not None:
            kc, vc = install(kc, vc, k, v, slot)
        read(logits[None], [served[uid][0]], np.ones(1, bool))
        seen.add(uid)

    for rows in steps:
        for slot, uid, _ in rows:
            if owner[slot] != uid:
                owner[slot] = uid
                first_token(uid, slot)
                lens[slot] = len(prompts[uid])
        toks = np.zeros(max_batch, np.int32)
        nxt = np.zeros(max_batch, np.int32)
        live = np.zeros(max_batch, bool)
        pos = np.zeros(max_batch, np.int32)
        for slot, uid, n in rows:
            toks[slot] = served[uid][n - 1]
            pos[slot] = lens[slot]
            if n < len(served[uid]):
                nxt[slot], live[slot] = served[uid][n], True
        logits, kc, vc = decode(w, kc, vc, jnp.asarray(toks), jnp.asarray(pos),
                                dims=dk, arith=ak, fmt=fmt)
        read(logits, nxt, live)
        for slot, _, _ in rows:
            lens[slot] += 1
    for uid in prompts:
        if uid not in seen:
            first_token(uid)
    return events


def replay(make_params, *, dims: dict, arith: dict, cdtype: str,
           prompts: dict, served: dict, steps: list, max_batch: int,
           max_len: int, controls=()) -> dict:
    """Replay the window and read the gaps.

    ``make_params()`` makes the weights anew (one pass holds one copy);
    ``prompts``: uid -> the prompt as prefilled (S,), padding included;
    ``served``: uid -> the served tokens; ``steps``: per decode call, the
    (slot, uid, n) of each active row, whose input is served token n - 1
    and whose output is served token n.  Each control is replayed first
    for the tokens it puts first, then the reference reads the gaps.
    Returns, for ``"served"`` and for each control, the gaps of every
    compared position (1-D float32)."""
    kw = dict(dk=tuple(sorted(dims.items())),
              ak=tuple(sorted(arith.items())),
              cache_dtype=jnp.dtype(arith["kv_cache_dtype"]),
              prompts=prompts, served=served, steps=steps,
              max_batch=max_batch, max_len=max_len)

    def weights(fmt):
        return prepare(make_params(), fmt=fmt, arith=kw["ak"], cdtype=cdtype)

    picks = {c: _pass(weights(c), c, **kw) for c in controls}
    events = _pass(weights("ref"), "ref", picks=picks, **kw)
    live = np.concatenate([e[2] for e in events]) if events \
        else np.zeros(0, bool)

    def gather(arrays):
        if not arrays:
            return np.zeros(0, np.float32)
        return np.asarray(jnp.concatenate(arrays))[live]

    out = {"served": gather([e[0] for e in events])}
    for c in controls:
        out[c] = gather([e[1][c] for e in events])
    return out
