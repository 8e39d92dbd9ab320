"""The main-path kernel compiled for a described TPU v5e, at llama3.2-3b's
widths, with no chip attached.

JAX ships the TPU compiler; it compiles for a topology that is described
rather than present.  Mosaic refuses here what Pallas interpret mode
cannot see: tiles that break the layout, fast memory a kernel may not use.
The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every test worker imports this file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.trq import make_params
from repro.kernels.trq_group_mvm.kernel import trq_group_mvm_tiles
from repro.kernels.trq_group_mvm.ops import pick_block_m
from repro.pim.plan import _pallas_prepared_exec

# (d_model -> d_ff) up/gate and (d_ff -> d_model) down projections
WIDTHS = [(3072, 8192), (8192, 3072)]


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        jnp.shape(x), jnp.result_type(x), sharding=one_chip), tree)


def _args(one_chip, m, k, n):
    a = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=one_chip)
    trq = _spec(one_chip, make_params(delta_r1=1.0, signed=True))
    grid = _spec(one_chip, jnp.float32(0.05))
    return a, w, trq, grid


@pytest.mark.parametrize("m", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", WIDTHS, ids=["3072x8192", "8192x3072"])
def test_trq_group_mvm_compiles_for_v5e(one_chip, m, k, n):
    def tiles(a, w, trq, grid):
        return trq_group_mvm_tiles(a, w, trq, grid, block_m=pick_block_m(m),
                                   block_n=128, interpret=False,
                                   with_ops=True)
    compiled = jax.jit(tiles).lower(*_args(one_chip, m, k, n)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's own name, which a device trace shows it under
    assert "trq_group_mvm" in compiled.as_text()


def test_prepared_pallas_exec_compiles_for_v5e(one_chip):
    """The plan's jit'd tile launch: row padding + kernel + op sum."""
    m, k, n = 4, 3072, 8192
    compiled = _pallas_prepared_exec.lower(
        *_args(one_chip, m, k, n), block_m=pick_block_m(m), n=n,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "trq_group_mvm" in compiled.as_text()
