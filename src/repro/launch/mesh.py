"""Production meshes.

All constructors are FUNCTIONS so importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).

Axes:
* (pod, data, model): multi-pod production: 2 pods x 16 x 16 = 512 chips.
* (data, model): single-pod 16 x 16 = 256 chips.
* GP runs flatten everything into one 'workers' axis — the paper's P MPI
  ranks; its only hot-path collective is a scalar psum.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_worker_mesh(n_workers: int | None = None) -> Mesh:
    """1-D mesh for the SBV GP runtime (axis name 'workers')."""
    n = n_workers or len(jax.devices())
    return _mesh((n,), ("workers",))


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    return _mesh(shape, axes)
