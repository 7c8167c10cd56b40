"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The dry-run entry
point (dryrun.py) sets XLA_FLAGS to fabricate 512 host devices *before* any
jax import; everything else (tests, benches) sees the real single device.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Mesh over whatever devices exist (tests/examples on 1 CPU)."""
    n = len(jax.devices())
    model = min(model, n)
    data = max(1, min(data, n // model))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Axes the global batch shards over (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")
