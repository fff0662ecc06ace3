"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before the first jax call.

The meshes use ``Auto`` axes: the train/serve steps place their inputs with
``NamedSharding`` and leave the propagation of intermediate shardings to the
compiler.  ``jax.make_mesh`` defaults to ``Explicit`` axes, under which every
gather and matmul on a sharded operand must name its output sharding.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis is the
    DCN/cross-pod dimension (batch shards across it)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_dev_mesh(n_data: int = 2, n_model: int = 4, *, multi_pod: bool = False):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if multi_pod:
        return _auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))
