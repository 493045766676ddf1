"""Production mesh definitions.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state. The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* importing jax.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh


def _make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with explicit Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_for(devices: Optional[int] = None, *, model_axis: int = 1) -> Mesh:
    """Elastic mesh over the first `devices` available devices (defaults to
    all): shape (devices // model_axis, model_axis) as (data, model)."""
    n = devices if devices is not None else len(jax.devices())
    assert n % model_axis == 0, (n, model_axis)
    return _make_mesh((n // model_axis, model_axis), ("data", "model"))


def make_serve_mesh(dp: int = 1, tp: int = 1) -> Mesh:
    """(data, model) serving mesh for the tensor-parallel analog plane
    (``repro.parallel.sharding``; the ``serve --mesh DP,TP`` flag)."""
    from repro.parallel.sharding import serve_mesh
    return serve_mesh(dp, tp)
