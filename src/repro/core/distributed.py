"""Distributed SBV likelihood via shard_map (paper Alg. 1 steps 4-5).

Worker p's blocks live on shard p of the mesh axis; each shard computes its
batched local likelihood and a single scalar ``psum`` replaces the paper's
MPI_Allreduce — communication per optimization iteration is O(1) scalars,
the property that makes SBV scale near-linearly (paper Fig. 9).

Host-side preprocessing already grouped blocks by owner (Alg. 2's
MPI_Alltoall locality), so sharding the packed arrays on the leading block
axis IS the paper's data distribution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .kernels_math import KernelParams
from .packing import PackedBlocks, PackedPrediction
from .vecchia import batched_block_loglik


def shard_blocks_by_owner(packed: PackedBlocks, n_workers: int) -> PackedBlocks:
    """Reorder blocks so each worker's blocks are contiguous, then pad the
    block count to a multiple of n_workers with fully-masked dummy blocks
    (identity padding => zero likelihood contribution)."""
    order = np.argsort(packed.owners, kind="stable")
    def g(a):
        return a[order]
    packed = PackedBlocks(
        blk_x=g(packed.blk_x), blk_y=g(packed.blk_y), blk_mask=g(packed.blk_mask),
        nn_x=g(packed.nn_x), nn_y=g(packed.nn_y), nn_mask=g(packed.nn_mask),
        owners=g(packed.owners),
    )
    bc = packed.n_blocks
    target = ((bc + n_workers - 1) // n_workers) * n_workers
    if target != bc:
        packed = packed.pad_to_blocks(target)
    # Round-robin interleave is NOT used: contiguous-by-owner matches the
    # paper's locality. But padding must land per-worker; with quantile
    # partitioning worker loads are near-equal so tail padding suffices.
    return packed


def distributed_loglik(
    params: KernelParams,
    packed: PackedBlocks,
    mesh: Mesh,
    axis: str = "workers",
    nu: float = 3.5,
):
    """Total log-likelihood with blocks sharded over ``axis`` of ``mesh``."""
    spec = P(axis)
    sharding = NamedSharding(mesh, spec)
    arrs = [
        jnp.asarray(a)
        for a in (packed.blk_x, packed.blk_y, packed.blk_mask,
                  packed.nn_x, packed.nn_y, packed.nn_mask)
    ]
    arrs = [jax.device_put(a, sharding) for a in arrs]

    def local(p, bx, by, bm, nx, ny, nm):
        ll = batched_block_loglik(p, bx, by, bm, nx, ny, nm, nu=nu)
        return jax.lax.psum(ll, axis)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), spec, spec, spec, spec, spec, spec),
        out_specs=P(),
    )
    return jax.jit(fn)(params, *arrs)


def shard_prediction_by_owner(packed: PackedPrediction, n_workers: int) -> PackedPrediction:
    """Prediction-side twin of ``shard_blocks_by_owner``: contiguous-by-owner
    block order + fully-masked padding to a multiple of n_workers. Padded
    blocks produce mu=0/var=prior and are dropped at scatter time, so the
    reorder is free of correctness constraints — it only preserves the
    paper's locality (a worker serves the query blocks whose neighbors it
    already owns)."""
    order = np.argsort(packed.owners, kind="stable")
    g = lambda a: a[order]
    packed = PackedPrediction(
        q_x=g(packed.q_x), q_mask=g(packed.q_mask), q_idx=g(packed.q_idx),
        nn_x=g(packed.nn_x), nn_y=g(packed.nn_y), nn_mask=g(packed.nn_mask),
        owners=g(packed.owners),
    )
    bc = packed.n_blocks
    target = ((bc + n_workers - 1) // n_workers) * n_workers
    if target != bc:
        packed = packed.pad_to_blocks(target)
    return packed


@functools.lru_cache(maxsize=None)
def _predict_shard_fn(mesh: Mesh, axis: str, nu: float, backend: str):
    """Cached jitted shard_map for prediction — chunked serving calls
    ``distributed_predict`` once per chunk and must hit the same compiled
    program (Mesh is hashable; the cache key is the full config)."""
    from .predict import batched_block_predict

    spec = P(axis)

    def local(p, qx, qm, nx, ny, nm):
        return batched_block_predict(p, qx, qm, nx, ny, nm, nu=nu, backend=backend)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(),) + (spec,) * 5,
        out_specs=(spec, spec),
        # pallas_call has no varying-axes rule; outputs are per-shard anyway
        check_vma=False,
    ))


def distributed_predict(
    params: KernelParams,
    packed: PackedPrediction,
    mesh: Mesh,
    axis: str = "workers",
    nu: float = 3.5,
    backend: str = "ref",
):
    """Batched block prediction with blocks sharded over ``axis``.

    Each shard computes the conditionals of its own blocks; unlike the
    likelihood there is NO collective — per-block outputs stay sharded
    (out_specs = blocks axis) and the host gathers them for the scatter.
    Returns ``(mu, var)`` as (bc, bs_pred) arrays in the order of
    ``packed`` (call ``shard_prediction_by_owner`` first so bc divides)."""
    sharding = NamedSharding(mesh, P(axis))
    arrs = [
        jax.device_put(jnp.asarray(a), sharding) for a in packed.arrays()
    ]
    mu, var = _predict_shard_fn(mesh, axis, nu, backend)(params, *arrs)
    return mu, var


def sharded_packed_predict(
    params: KernelParams,
    packed: PackedPrediction,
    mesh: Mesh,
    axis: str = "workers",
    nu: float = 3.5,
    backend: str = "ref",
):
    """One sharded micro-batch: owner-contiguous reorder + padded sharding +
    distributed block conditionals.

    The serving pipeline's per-chunk compute when a mesh is attached.
    Returns ``(packed, mu, var)`` — the REORDERED packed (its ``q_idx``
    matches the output block order) so the caller scatters with the right
    indices. The shard_map program is cached per (mesh, axis, nu, backend),
    so successive micro-batches of the same padded shape hit one compiled
    executable."""
    n_shards = int(np.prod([mesh.shape[a] for a in
                            (axis if isinstance(axis, tuple) else (axis,))]))
    packed = shard_prediction_by_owner(packed, n_shards)
    mu, var = distributed_predict(params, packed, mesh, axis=axis, nu=nu,
                                  backend=backend)
    return packed, mu, var


def distributed_bucketed_loglik(
    params: KernelParams,
    bucketed,
    mesh: Mesh,
    axis: str = "workers",
    nu: float = 3.5,
):
    """Total loglik of a ``BucketedBlocks`` with each bucket sharded over
    ``axis``: per-bucket owner-contiguous reorder + masked padding to the
    worker count, one psum per bucket.

    Sharding bucket-by-bucket is what balances *work*, not block counts:
    under the uniform layout an equal-count split can hand one shard the
    outlier blocks (its true Sigma bs*(bs+m)^2 dwarfs the others'), but
    here every shard receives an equal slice of EVERY bucket, and within
    a bucket block sizes agree to the geometric-ceiling width — so
    per-shard true work is near-equal by construction, no explicit
    balancer needed.

    One-shot convenience (traces and compiles each bucket's program per
    call, like ``distributed_loglik``); optimizer loops should use
    ``distributed_neg_loglik_fn``, which builds, places, and jits every
    bucket program once."""
    n_workers = int(np.prod([mesh.shape[a] for a in
                             (axis if isinstance(axis, tuple) else (axis,))]))
    total = None
    for pk in bucketed.buckets:
        ll = distributed_loglik(params, shard_blocks_by_owner(pk, n_workers),
                                mesh, axis=axis, nu=nu)
        total = ll if total is None else total + ll
    return total


def distributed_neg_loglik_fn(packed, nu, mesh, axis="workers"):
    """Loss closure for fit_sbv(distributed=(mesh, axis)).

    Accepts a uniform ``PackedBlocks`` or a ``BucketedBlocks``; bucketed
    inputs are sharded bucket-by-bucket (each bucket one shard_map'd
    psum), which balances per-shard work — see
    ``distributed_bucketed_loglik``."""
    from .buckets import BucketedBlocks

    n_workers = int(np.prod([mesh.shape[a] for a in (axis if isinstance(axis, tuple) else (axis,))]))
    if isinstance(packed, BucketedBlocks):
        return _bucketed_neg_loglik_fn(packed, nu, mesh, axis, n_workers)
    packed = shard_blocks_by_owner(packed, n_workers)
    spec = P(axis)
    sharding = NamedSharding(mesh, spec)
    arrs = [
        jax.device_put(jnp.asarray(a), sharding)
        for a in (packed.blk_x, packed.blk_y, packed.blk_mask,
                  packed.nn_x, packed.nn_y, packed.nn_mask)
    ]
    n = packed.n_points

    local = lambda p, bx, by, bm, nx, ny, nm: jax.lax.psum(
        batched_block_loglik(p, bx, by, bm, nx, ny, nm, nu=nu), axis
    )
    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(),) + (spec,) * 6, out_specs=P())

    def loss(params):
        return -fn(params, *arrs) / n

    return jax.jit(loss)


def _bucketed_neg_loglik_fn(bucketed, nu, mesh, axis, n_workers):
    """Per-bucket sharded arrays are placed once; the jitted loss sums one
    shard_map'd psum per bucket shape."""
    spec = P(axis)
    sharding = NamedSharding(mesh, spec)
    n = bucketed.n_points

    per_bucket = []
    for pk in bucketed.buckets:
        pk = shard_blocks_by_owner(pk, n_workers)
        arrs = [
            jax.device_put(jnp.asarray(a), sharding)
            for a in (pk.blk_x, pk.blk_y, pk.blk_mask,
                      pk.nn_x, pk.nn_y, pk.nn_mask)
        ]
        local = lambda p, bx, by, bm, nx, ny, nm: jax.lax.psum(
            batched_block_loglik(p, bx, by, bm, nx, ny, nm, nu=nu), axis
        )
        fn = jax.shard_map(local, mesh=mesh, in_specs=(P(),) + (spec,) * 6,
                       out_specs=P())
        per_bucket.append((fn, arrs))

    def loss(params):
        total = None
        for fn, arrs in per_bucket:
            ll = fn(params, *arrs)
            total = ll if total is None else total + ll
        return -total / n

    return jax.jit(loss)
