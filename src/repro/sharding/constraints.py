"""Activation sharding constraints that degrade to no-ops.

``constrain(x, *axes)`` applies ``with_sharding_constraint`` when an
ambient mesh (``jax.set_mesh``) is present, mapping each logical axis spec
onto mesh axes that exist AND divide the dimension; anything else
replicates. Model code can therefore annotate the intended production
sharding (Megatron activation placement) while unit tests and single-
device runs execute the identical code with zero ceremony.

Axis spec entries: None (replicate), a mesh-axis name, a tuple of names,
or BATCH (shorthand for the data-parallel axes ('pod', 'data'))."""
from __future__ import annotations

import os

import jax
from jax.sharding import PartitionSpec as P

from jax.sharding import get_abstract_mesh

# A/B kill switch for §Perf: REPRO_NO_CONSTRAINTS=1 disables every
# activation constraint so the un-annotated model can be re-measured
# under the same cost instrument.
_DISABLED = os.environ.get("REPRO_NO_CONSTRAINTS", "") == "1"

BATCH = ("pod", "data")
FULL_BATCH = ("pod", "data", "model")  # batch over EVERY axis (recurrent blocks)


def _resolve(mesh, dim: int, entry):
    """Longest prefix of the requested axes that exists and divides dim."""
    if entry is None:
        return None
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    names = tuple(n for n in names if n in mesh.axis_names)
    best: tuple = ()
    size = 1
    for n in names:
        size *= mesh.shape[n]
        if dim % size == 0:
            best = best + (n,)
        else:
            break
    if not best or all(mesh.shape[n] == 1 for n in best):
        return None
    return best if len(best) > 1 else best[0]


def constrain(x, *axes):
    mesh = get_abstract_mesh()
    if _DISABLED or mesh.empty:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"constrain: {len(axes)} axes for rank-{x.ndim} array")
    spec = P(*[_resolve(mesh, d, a) for d, a in zip(x.shape, axes)])
    return jax.lax.with_sharding_constraint(x, spec)


def model_divides(dim: int) -> bool:
    """True if ``dim`` is shardable over the full 'model' axis."""
    mesh = get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return True
    size = mesh.shape["model"]
    return size == 1 or dim % size == 0
