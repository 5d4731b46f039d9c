"""Production mesh builders.

A function (not a module constant) so importing never touches jax device
state — the dry-run sets XLA_FLAGS *before* any jax initialization.

Every mesh uses Auto axes: the step code places values with
``lax.with_sharding_constraint``, which only accepts Auto axes
(``jax.make_mesh`` defaults to Explicit ones).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(dp: int = 1, tp: int = 1, pods: int = 1):
    """Arbitrary (pod, data, model) mesh for trials / tests / smoke runs."""
    if pods > 1:
        return _auto_mesh((pods, dp, tp), ("pod", "data", "model"))
    return _auto_mesh((dp, tp), ("data", "model"))


def single_device_mesh():
    return make_mesh(1, 1)
