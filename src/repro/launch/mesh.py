"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run launcher sets XLA_FLAGS before any jax import.

Every axis is ``Auto``: ``shard()`` places activations with
``with_sharding_constraint``, which refers only to Auto axes (JAX >= 0.7
makes ``jax.make_mesh`` axes Explicit unless told otherwise).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(devices=None):
    """``devices`` (default: all of this host's) as an (n, 1) data-parallel
    mesh: smoke tests, CPU examples and one-host chip runs."""
    devices = jax.devices() if devices is None else list(devices)
    return _auto_mesh((len(devices), 1), ("data", "model"), devices)
