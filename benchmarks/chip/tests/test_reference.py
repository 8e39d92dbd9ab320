"""The reference's crossbar arithmetic against the program's own fake_quant
datapath at toy sizes (float32 on the CPU), and its lower-precision
rounding for the controls."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference

ARITH = {"xbar": 128, "levels": 127.0, "delta_grid": 1.0, "n_r1": 6,
         "n_r2": 6, "m": 4, "delta_r1": 1.0, "auto_range": True}


@pytest.mark.parametrize("shape", [(4, 256), (1, 9, 384), (3, 200)],
                         ids=["two_groups", "prompt_rows", "padded_group"])
def test_linear_matches_the_programs_fake_quant(shape):
    from repro.configs.base import TRQConfig
    from repro.models.layers import trq_params_from_cfg
    from repro.pim.backend import fake_quant_backend
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, shape, jnp.float32)
    w = jax.random.normal(kw, (shape[-1], 200), jnp.float32) * 0.05
    want = fake_quant_backend(x, w, trq_params_from_cfg(TRQConfig()),
                              auto_range=True).y
    got = reference._linear(x, w, reference._weight_scale(w, ARITH), ARITH,
                            "ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_range_follows_the_rows_beside():
    """A row's output depends on its batchmates: a large row widens the
    conversion range of the whole call."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 64), jnp.float32)
    s = reference._weight_scale(w, ARITH)
    alone = reference._linear(x[:1], w, s, ARITH, "ref")
    beside = reference._linear(x.at[1].mul(50.0), w, s, ARITH, "ref")[:1]
    assert not np.array_equal(np.asarray(alone), np.asarray(beside))


@pytest.mark.parametrize("fmt", ["fp8", "int8"])
def test_control_rounding_is_lossy_and_held_exactly(fmt):
    x = (jax.random.normal(jax.random.PRNGKey(3), (64, 64)) * 3) \
        .astype(jnp.bfloat16)
    r = reference._round(x, fmt)
    assert r.dtype == jnp.bfloat16
    assert not np.array_equal(np.asarray(r), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(reference._round(r, fmt)),
                                  np.asarray(r))
    assert reference._round(x, "ref") is x
