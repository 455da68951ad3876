"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The dry-run entry point (launch/dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so these meshes can be built on the CPU container.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes: the model places activations
    through ``ShardingRules`` constraints, not explicit-axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh():
    """1x1 mesh with production axis names — used by CPU tests/examples."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_chips(mesh) -> int:
    import math
    return math.prod(mesh.shape.values())
