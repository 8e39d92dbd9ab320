"""Random weights of a dense decoder from ``--seed``, made on the device in
one jitted call, in the dtype they are served in.

The tree has the layout the program's dense decoder takes (``embed/tok``,
``final_norm/scale``, ``lm_head/w``, and ``periods/layer_0/...`` stacked
over depth).  The weights are the benchmark's input: the program receives
them, and the reference makes the same ones again from the same seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_shapes(d: dict) -> dict:
    """path -> (shape, init std); None std means ones (norm scales)."""
    L, dm, hd = d["n_layers"], d["d_model"], d["head_dim"]
    h, kv, ff, v = d["n_heads"], d["n_kv_heads"], d["d_ff"], d["vocab"]
    return {
        "embed/tok": ((v, dm), dm ** -0.5),
        "final_norm/scale": ((dm,), None),
        "lm_head/w": ((dm, v), dm ** -0.5),
        "periods/layer_0/norm1/scale": ((L, dm), None),
        "periods/layer_0/norm2/scale": ((L, dm), None),
        "periods/layer_0/attn/wq/w": ((L, dm, h * hd), dm ** -0.5),
        "periods/layer_0/attn/wk/w": ((L, dm, kv * hd), dm ** -0.5),
        "periods/layer_0/attn/wv/w": ((L, dm, kv * hd), dm ** -0.5),
        "periods/layer_0/attn/wo/w": ((L, h * hd, dm), (h * hd) ** -0.5),
        "periods/layer_0/mlp/w_gate/w": ((L, dm, ff), dm ** -0.5),
        "periods/layer_0/mlp/w_up/w": ((L, dm, ff), dm ** -0.5),
        "periods/layer_0/mlp/w_down/w": ((L, ff, dm), ff ** -0.5),
    }


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _make(key, dims, dtype):
    d = dict(dims)
    flat = {}
    for i, (path, (shape, std)) in enumerate(sorted(leaf_shapes(d).items())):
        if std is None:
            flat[path] = jnp.ones(shape, jnp.float32)
        else:
            flat[path] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                            jnp.float32) * std).astype(dtype)
    return _nest(flat)


def make_params(dims: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The whole parameter tree for ``dims`` from ``seed``."""
    return _make(base_key(seed), tuple(sorted(dims.items())),
                 jnp.dtype(dtype).name)
