"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else (tests, benches) sees the single real CPU device.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    # Auto axes: the model code places arrays with with_sharding_constraint,
    # which only accepts Auto axes (make_mesh defaults to Explicit).
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh for CPU tests (axis sizes 1 keep collectives trivial)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
