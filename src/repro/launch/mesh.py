"""Production mesh construction.

Target: TPU v5e pods, 256 chips each. Single pod = (data=16, model=16);
multi-pod = (pod=2, data=16, model=16) — 512 chips. Built as FUNCTIONS so
importing this module never touches jax device state (required: the
dry-run forces 512 host devices via XLA_FLAGS *before* first jax init,
while smoke tests must see 1 device).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _mesh_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (small-mesh tests, elastic re-meshing)."""
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes carrying the batch dimension: ('pod','data') when multi-pod."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def axis_size(mesh, names) -> int:
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    out = 1
    for n in names:
        out *= mesh.shape[n]
    return out
