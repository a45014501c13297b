"""Production meshes (TPU v5e): single pod 16x16 = 256 chips, multi-pod
2x16x16 = 512 chips.

A FUNCTION, not a module constant: importing this module never touches
jax device state (device count is locked at first jax init, and smoke
tests must see 1 device).

Axes are Auto: the model code places intermediates with
``with_sharding_constraint`` (``repro.utils.shardctx.maybe_shard``),
which only accepts Auto axes, while ``jax.make_mesh`` defaults to
Explicit ones."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(model: int = 2, data: int = 2, pod: int = 0):
    """Small mesh for CI-scale sharding tests (requires enough host
    devices, see tests/test_sharding.py which sets XLA_FLAGS in a
    subprocess)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
