"""MLE parameter estimation for SBV (paper Alg. 1 outer loop).

The paper optimizes the likelihood with derivative-free NLopt (BOBYQA).
The JAX build gets an *analytic gradient* through the whole batched
likelihood (beyond-paper improvement — typically 5-20x fewer iterations),
with the paper's scheme available as ``method='neldermead'`` for parity.

Scaled-Vecchia alternation: the block/neighbor structure is built with the
current beta estimate and refreshed every ``rescale_every`` outer rounds
(Katzfuss et al. 2022 do the same; structure refresh is the one step that
cannot be differentiated through).
"""
from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import adam_init, adam_update
from repro.spans import span

from .kernels_math import KernelParams
from .pipeline import SBVConfig, preprocess
from .vecchia import packed_loglik


@dataclass
class FitResult:
    params: KernelParams  # or MultiOutputParams (multi-output fits)
    history: list = field(default_factory=list)  # (outer, inner, -loglik/n)
    packed: object = None
    stream_stats: dict | None = None  # set by the streaming (out-of-core) path
    precision_tiers: list | None = None  # per-bucket ladder tiers (last round)


def neg_loglik_fn(packed, nu: float, backend: str):
    n = packed.n_points

    def f(params):
        return -packed_loglik(params, packed, nu=nu, backend=backend) / n

    return f


_MAP_BATCH = 16  # blocks vmapped per lax.map step of the streaming grad


def _chunk_loglik(nu: float, backend: str):
    """Total loglik of one packed chunk — the body shared by the serial
    and the shard_map'd streaming gradients.

    Device residency is the streaming fit's real memory ceiling: a
    vmapped value_and_grad over the whole chunk materializes O(10)
    buffers of (bc_chunk, bs+m, bs+m) during the backward pass — ~1GB at
    a 32k-row chunk — so the 'ref' path runs the CHECKPOINTED
    joint-assembly block likelihood under ``lax.map`` in ``_MAP_BATCH``-
    block steps: residuals per step are just the block inputs, recompute
    happens one mini-batch at a time, and the live set stays at a few
    ``_MAP_BATCH x (bs+m)^2`` buffers however large the chunk is."""
    from .vecchia import _block_loglik_joint_one

    def ll(params, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask):
        if backend == "ref":
            from .kernels_math import cast_params

            # Precision ladder: the piece's observation dtype is its
            # accumulation dtype (docs/precision.md); a no-op for the
            # default f64 spool layout.
            p = cast_params(params, jnp.asarray(blk_y).dtype)
            body = jax.checkpoint(
                lambda a: _block_loglik_joint_one(p, nu, *a)
            )
            per_block = jax.lax.map(
                body, (blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask),
                batch_size=_MAP_BATCH,
            )
            return jnp.sum(per_block)
        from repro.kernels import ops as kops

        return kops.sbv_loglik(params, blk_x, blk_y, blk_mask,
                               nn_x, nn_y, nn_mask, nu=nu)

    return ll


@functools.lru_cache(maxsize=64)
def _chunk_grad_fn(nu: float, backend: str, n_points: int, mesh=None,
                   axis: str | None = None):
    """jitted value_and_grad of one packed chunk's -loglik/n contribution.

    CACHED on (nu, backend, n, mesh, axis) — the structure refresh of a
    new outer round usually lands on the identical padded shapes, and a
    fresh ``jax.jit`` wrapper would discard the compiled executable even
    then. With the wrapper cached, per-shape compilation caching is
    jit's own (one compile per piece shape across ALL rounds and fits).
    The key includes the dataset size, so the cache is BOUNDED (a
    long-lived process sweeping many dataset sizes would otherwise pin a
    wrapper + executables per size forever); eviction just recompiles.

    With ``mesh``/``axis``, the chunk's block axis is shard_map'd over
    the mesh and the per-shard loglik is ``psum``'d before the global
    ``-ll/n`` — O(1) scalars of communication per chunk per step, the
    paper's Alg. 1 property — and the returned gradient is replicated,
    so chunked accumulation proceeds exactly as in the serial loop. Pass
    arrays already placed with ``NamedSharding(mesh, P(axis))`` on the
    leading (block) axis (the spool's device tier and H2D stage both
    do)."""
    ll = _chunk_loglik(nu, backend)
    if mesh is None:
        def f(params, *arrs):
            return -ll(params, *arrs) / n_points

        return jax.jit(jax.value_and_grad(f))

    from jax.sharding import PartitionSpec as P

    spec = P(axis)

    def local(params, *arrs):
        return jax.lax.psum(ll(params, *arrs), axis)

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(P(),) + (spec,) * 6, out_specs=P(),
        # pallas_call has no varying-axes rule (same caveat as the
        # prediction shard_map); the psum output is replicated anyway
        check_vma=backend == "ref",
    )

    def f(params, *arrs):
        return -fn(params, *arrs) / n_points

    return jax.jit(jax.value_and_grad(f))


def _fit_sbv_multi(
    x, y, cfg, init, nu, lr, inner_steps, outer_rounds, backend, verbose,
    n_buckets, precision=None,
):
    """Monolithic multi-output fit (docs/multioutput.md).

    One structure pass per outer round shared by all p outputs; Adam
    minimizes the pooled profile likelihood over (log_beta, log_tau2)
    through the shared-Cholesky stats; per-output sigma2 are profiled in
    closed form at the end (their gradient in the pooled objective is
    identically zero, so they simply ride along in the pytree).

    ``precision`` applies the ladder tier CAST-ONLY (docs/precision.md):
    ``cast_packed`` narrows coordinates to the tier's storage dtype and
    the (bc, bs, p) observation columns to its accumulation dtype — the
    multi-RHS layout rides the same dtype fields, and the stats kernels
    already cast params to the data's accumulation dtype. The per-bucket
    nll probe is single-output-only, so ``probe`` is ignored here;
    budget enforcement is the tier's documented bound."""
    from .multioutput import (
        as_multi_params, MultiOutputParams, multi_profile_neg_loglik_fn,
        with_profiled_sigma2,
    )

    d = x.shape[1]
    p = y.shape[1]
    if init is None:
        params = MultiOutputParams.create(
            sigma2=np.maximum(np.var(y, axis=0), 1e-12), beta=0.5, tau2=1e-3,
            d=d, p=p,
        )
    else:
        params = as_multi_params(init, p, d)
    history = []
    packed = None
    tier = None
    if precision is not None:
        from .buckets import as_policy

        pol = as_policy(precision)
        if pol.tier != "f64":
            tier = pol.tier

    for outer in range(outer_rounds):
        beta_np = np.asarray(params.beta)
        packed, _ = preprocess(x, y, beta_np, cfg)
        if n_buckets:
            from .buckets import bucket_blocks

            packed = bucket_blocks(packed, n_buckets=n_buckets)
        if tier:
            from .buckets import apply_precision, BucketedBlocks, cast_packed

            packed = (apply_precision(packed, tier)
                      if isinstance(packed, BucketedBlocks)
                      else cast_packed(packed, tier))
        grad_fn = jax.jit(jax.value_and_grad(
            multi_profile_neg_loglik_fn(packed, nu, backend)))

        state = adam_init(params)
        for it in range(inner_steps):
            loss, g = grad_fn(params)
            params, state = adam_update(g, state, params, lr)
            history.append((outer, it, float(loss)))
            if verbose and it % 10 == 0:
                print(f"[fit-multi] outer={outer} it={it} "
                      f"nll/np={float(loss):.6f} p={p}")
    params = with_profiled_sigma2(params, packed, nu=nu, backend=backend)
    return FitResult(params=params, history=history, packed=packed)


@functools.lru_cache(maxsize=64)
def _multi_stats_chunk_fn(nu: float, backend: str):
    """jitted (params, *arrs) -> (logdet0, q0) of one spooled chunk.

    Ref backend mirrors ``_chunk_loglik``'s memory ceiling: the
    checkpointed per-block stats run under ``lax.map`` in _MAP_BATCH
    steps, so the live set never scales with the chunk block count."""
    from .multioutput import _block_multi_stats_one

    def f(params, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask):
        from .kernels_math import cast_params

        p0 = cast_params(params.structure_params(), jnp.asarray(blk_y).dtype)
        if backend == "ref":
            body = jax.checkpoint(lambda a: _block_multi_stats_one(p0, nu, *a))
            ld, q = jax.lax.map(
                body, (blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask),
                batch_size=_MAP_BATCH,
            )
            return jnp.sum(ld), jnp.sum(q, axis=0)
        from repro.kernels import ops as kops

        return kops.sbv_multi_stats(p0, blk_x, blk_y, blk_mask,
                                    nn_x, nn_y, nn_mask, nu=nu)

    return jax.jit(f)


@functools.lru_cache(maxsize=64)
def _multi_wgrad_chunk_fn(nu: float, backend: str, n_points: int, p: int):
    """jitted grad of one chunk's weighted-stats scalar.

    The pooled profile objective takes logs of GLOBAL sums, so chunked
    accumulation is two passes per step: pass A sums (logdet0, q0) values
    over the chunks; pass B accumulates the gradient of
    ``(p*ld_c/2 + n/2 * sum_j q_cj / Q_j) / (n*p)`` with the weights
    1/Q_j frozen at pass A's totals — by the chain rule the sum over
    chunks is the EXACT gradient of the pooled objective."""
    stats = _multi_stats_chunk_fn(nu, backend)

    def f(params, w, *arrs):
        ld_c, q_c = stats(params, *arrs)
        s = 0.5 * p * ld_c + 0.5 * n_points * jnp.sum(w * q_c)
        return s / (n_points * p)

    return jax.jit(jax.grad(f))


def _fit_sbv_multi_streaming(
    store, cfg, init, nu, lr, inner_steps, outer_rounds, backend, verbose,
    stream_chunk, spool_dir, device_cache=None, prefetch: int = 2,
    precision=None,
):
    """Out-of-core multi-output fit: ``_fit_sbv_streaming``'s spool plan
    with the two-pass chunk accumulation of ``_multi_wgrad_chunk_fn``.
    Every pass holds ~stream_chunk data rows; blk_y/nn_y spool with their
    (…, p) output axis through the same npz tiers. ``precision`` is
    UNIFORM cast-only like the single-output streaming fit: every chunk
    is ``cast_packed`` to the tier before spooling (no per-piece probe),
    so the spool and H2D stage carry the narrow layout."""
    import shutil
    import tempfile

    from repro.data.streaming import (
        device_cache_budget, pack_block_chunk, PackedChunkSpool,
        streaming_preprocess,
    )

    from .multioutput import (
        as_multi_params, MultiOutputParams, pooled_objective, profile_sigma2,
    )

    n = store.n_rows
    d = store.d
    y0 = np.asarray(store.read_slice(0, 1)[1])
    if y0.ndim != 2:
        raise ValueError("multi-output streaming fit needs (n, p) store rows")
    p = int(y0.shape[1])
    if init is None:
        params = MultiOutputParams.create(sigma2=1.0, beta=0.5, tau2=1e-3,
                                          d=d, p=p)
    else:
        params = as_multi_params(init, p, d)
    tier = None
    if precision is not None:
        from .buckets import as_policy

        pol = as_policy(precision)
        if pol.tier != "f64":
            tier = pol.tier
    history = []
    stats = {"n_chunks": 0, "n_pieces": 0, "packed_chunk_bytes_max": 0,
             "spool_bytes": 0, "bs_max": 0, "bc": 0, "n_shards": 1,
             "n_outputs": p, "inner_steps_total": 0, "inner_time_s": 0.0,
             "precision": tier or "f64"}
    final_q = None

    for outer in range(outer_rounds):
        beta_np = np.asarray(params.beta)
        struct = streaming_preprocess(store, beta_np, cfg, stream_chunk)
        bc_pad = max(len(r) for r in struct.plan)

        if device_cache is None:
            acc_bytes = int(np.dtype(cfg.dtype).itemsize)
            reserve = 16 * _MAP_BATCH * (struct.bs_max + cfg.m) ** 2 * acc_bytes
            budget = device_cache_budget(reserve_bytes=reserve)
        else:
            budget = int(device_cache)
        work_dir = spool_dir or tempfile.mkdtemp(prefix="sbv-spool-")
        spool = PackedChunkSpool(os.path.join(work_dir, f"round{outer}"),
                                 device_budget=budget)
        try:
            for ranks in struct.plan:
                packed = pack_block_chunk(
                    store, struct.blocks, struct.neigh, ranks,
                    m=cfg.m, bs_max=struct.bs_max, dtype=cfg.dtype,
                )
                if tier:
                    from .buckets import cast_packed

                    packed = cast_packed(packed, tier)
                spool.add(packed.pad_to_blocks(bc_pad),
                          tag=_piece_backend(backend, packed))
            stats.update(
                n_chunks=len(struct.plan), n_pieces=len(spool),
                packed_chunk_bytes_max=max(stats["packed_chunk_bytes_max"],
                                           spool.packed_bytes_max),
                spool_bytes=max(stats["spool_bytes"], spool.packed_bytes_total),
                bs_max=struct.bs_max, bc=struct.blocks.n_blocks,
            )

            def chunk_stats(prms):
                ld = None
                q = None
                for arrs, tag in spool.iter_arrays(prefetch=prefetch):
                    ld_c, q_c = _multi_stats_chunk_fn(nu, tag)(prms, *arrs)
                    ld = ld_c if ld is None else ld + ld_c
                    q = q_c if q is None else q + q_c
                return ld, q

            state = adam_init(params)
            t_inner = time.perf_counter()
            for it in range(inner_steps):
                ld, q = chunk_stats(params)
                loss = pooled_objective(ld, q, n)
                w = 1.0 / jnp.maximum(q, 1e-300)
                grad = None
                for arrs, tag in spool.iter_arrays(prefetch=prefetch):
                    g = _multi_wgrad_chunk_fn(nu, tag, n, p)(params, w, *arrs)
                    grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
                params, state = adam_update(grad, state, params, lr)
                history.append((outer, it, float(loss)))
                if verbose and it % 10 == 0:
                    print(f"[fit-multi-stream] outer={outer} it={it} "
                          f"nll/np={float(loss):.6f} pieces={len(spool)}")
            # Profile the per-output scales at the ROUND-FINAL params (one
            # extra values pass; the last round's result is the fit's).
            _, final_q = chunk_stats(params)
            stats["inner_time_s"] += time.perf_counter() - t_inner
            stats["inner_steps_total"] += inner_steps
        finally:
            spool.cleanup()
            if spool_dir is None:
                shutil.rmtree(work_dir, ignore_errors=True)
    s2 = jnp.maximum(
        profile_sigma2(jnp.asarray(final_q, jnp.float64), n), 1e-300)
    params = params._replace(log_sigma2=jnp.log(s2))
    return FitResult(params=params, history=history, packed=None,
                     stream_stats=stats)


def _piece_backend(backend: str, piece) -> str:
    """Resolve ``backend='auto'`` per spooled piece shape, exactly like the
    bucketed in-core path (``kernels.ops.select_backend``)."""
    if backend != "auto":
        return backend
    from repro.kernels import ops as kops

    return kops.select_backend(piece.bs_max, piece.m, kind="loglik",
                               dtype=piece.blk_x.dtype)


def _fit_sbv_streaming(
    store, cfg, init, nu, lr, inner_steps, outer_rounds, backend, verbose,
    stream_chunk, n_buckets, spool_dir, distributed=None,
    device_cache: int | None = None, prefetch: int = 2, multihost=None,
    precision=None,
):
    """Out-of-core fit: every pass holds ~``stream_chunk`` data rows.

    Per outer round: streaming structure (mini-batch k-means + store-backed
    filtered NNS), then the rank-ordered blocks are packed into
    ``stream_chunk``-row chunks (gather-and-remap from the store), padded
    to ONE shared shape, and handed to the two-tier ``PackedChunkSpool``.
    Each inner step accumulates value+grad over the pieces IN SPOOL
    ORDER — the likelihood is a sum over blocks, so chunked accumulation
    differs from the monolithic in-core program only in float summation
    order (pinned <= 1e-10 in tests/test_streaming.py), and the memory
    tier a piece lives in (HBM cache / prefetched H2D / cold disk)
    changes nothing bitwise.

    ``device_cache``: bytes of HBM for the device-resident tier — pieces
    within the budget are transferred once per round instead of once per
    step. ``None`` sizes it automatically from free device memory minus
    the gradient's live-set reserve; ``0`` disables (every piece re-reads
    from disk, the pre-tier behavior). ``prefetch``: disk-tier pieces
    staged ahead on a producer thread (0 = synchronous reads).

    ``distributed=(mesh, axis)`` shards every piece's block axis over the
    mesh (owner-contiguous, masked padding to the shard count) and runs
    the chunk gradient under ``shard_map`` with a scalar ``psum`` — the
    streaming twin of the in-core distributed likelihood. The block
    reorder changes only the summation order vs. the serial streaming
    fit (<= 1e-8 over an optimization run).

    ``multihost`` (a ``repro.multihost`` host comm) runs the
    MULTI-PROCESS mode: this process constructs, packs, and spools only
    its own partition (``multihost_preprocess`` over a
    ``PartitionedStore``), and each inner step walks the hosts' pieces in
    lockstep with one all-reduce of ``[loss, grad]`` per chunk per step —
    the same O(1)-scalars-per-chunk comms contract as the in-process
    ``distributed`` path, so optimizer state stays replicated and every
    host finishes with identical parameters. With a ``LoopbackComm`` the
    mode is bitwise the serial streaming fit; across P processes it
    differs only in float summation order (<= 1e-8, like chunking).
    """
    import shutil
    import tempfile

    from repro.data.streaming import (
        device_cache_budget, pack_block_chunk, PackedChunkSpool,
        streaming_moments, streaming_preprocess,
    )

    from .packing import round_up

    if multihost is not None:
        if distributed is not None:
            raise ValueError("multihost and in-process distributed= are "
                             "mutually exclusive (one device per host)")
        if n_buckets:
            raise NotImplementedError("bucketed piece shapes are not wired "
                                      "into the multihost mode yet")
        return _fit_sbv_multihost(
            store, cfg, init, nu, lr, inner_steps, outer_rounds, backend,
            verbose, stream_chunk, spool_dir, multihost,
            device_cache=device_cache, prefetch=prefetch, precision=precision,
        )

    # Streaming precision is UNIFORM (no per-piece probing: the probe's
    # f64 reference would double every round's disk traffic); pieces are
    # cast to the policy tier before spooling, so the spool, the H2D
    # stage, and the device cache all carry the narrow layout.
    tier = None
    if precision is not None:
        from .buckets import as_policy

        pol = as_policy(precision)
        if pol.tier != "f64":
            tier = pol.tier

    mesh = axis = sharding = None
    n_shards = 1
    if distributed is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .distributed import shard_blocks_by_owner

        mesh, axis = distributed
        n_shards = int(np.prod([mesh.shape[a] for a in
                                (axis if isinstance(axis, tuple) else (axis,))]))
        sharding = NamedSharding(mesh, P(axis))
    n = store.n_rows
    d = store.d
    if init is None:
        _, var_y = streaming_moments(store)
        params = KernelParams.create(sigma2=var_y, beta=0.5, nugget=1e-3, d=d)
    else:
        params = init
    history = []
    stats = {"n_chunks": 0, "n_pieces": 0, "packed_chunk_bytes_max": 0,
             "spool_bytes": 0, "bs_max": 0, "bc": 0, "n_shards": n_shards,
             "device_cached_pieces": 0, "device_cached_bytes": 0,
             "h2d_bytes_per_step": 0, "inner_steps_total": 0,
             "inner_time_s": 0.0, "precision": tier or "f64",
             "device_cache_budget": 0, "struct_time_s": 0.0,
             "struct_kmeans_s": 0.0, "struct_nns_s": 0.0,
             "struct_pack_s": 0.0, "nns_scored": 0, "nns_kept": 0,
             "step_times_s": [], "backends": []}

    for outer in range(outer_rounds):
        spool = work_dir = None
        try:
            # struct_time_s: the round's structure, packing and spooling
            with span("sbv.fit.structure", stats, "struct_time_s"):
                beta_np = np.asarray(params.beta)
                struct = streaming_preprocess(store, beta_np, cfg, stream_chunk)
                for key, v in struct.stats.items():
                    stats[key] += v
                bc_pad = max(len(r) for r in struct.plan)
                if n_shards > 1:
                    # every piece's block count must divide the shard count; pad
                    # the SHARED shape so all pieces still hit one compiled program
                    bc_pad = round_up(bc_pad, n_shards)

                if n_buckets:
                    # GLOBAL bucket ceilings + per-cell bc padding: every chunk's
                    # pieces land on one of <= occupied-cells shapes, so the
                    # round compiles a bounded program set (per-chunk ceilings
                    # would compile — and grow the XLA arena — per chunk).
                    from .buckets import _group, bucket_ceilings

                    bs_true = np.asarray(
                        [struct.blocks.members[b].size for b in struct.blocks.order])
                    m_true = np.asarray(
                        [min(len(struct.neigh[b]), cfg.m) for b in struct.blocks.order])
                    bs_ceils = bucket_ceilings(bs_true, n_buckets, 8)
                    m_ceils = bucket_ceilings(m_true, n_buckets, 8)
                    cell_bc: dict = {}
                    for ranks in struct.plan:
                        for bs_c, m_c, idx in _group(bs_true[ranks], m_true[ranks],
                                                     bs_ceils, m_ceils):
                            # Same clamp bucket_blocks applies to piece shapes.
                            key = (min(bs_c, struct.bs_max), min(m_c, cfg.m))
                            cell_bc[key] = max(cell_bc.get(key, 0), round_up(idx.size, 8))
                    if n_shards > 1:
                        cell_bc = {k: round_up(v, n_shards) for k, v in cell_bc.items()}

                if device_cache is None:
                    # Auto budget: free device memory minus the grad live-set
                    # reserve (the working_set_model device_grad term). The
                    # reserve is PRECISION-AWARE: reduced tiers accumulate in
                    # f32, so the backward live set is half the f64 bytes — the
                    # freed reserve goes straight to the device-resident cache.
                    acc_bytes = 4 if tier else int(np.dtype(cfg.dtype).itemsize)
                    reserve = 16 * _MAP_BATCH * (struct.bs_max + cfg.m) ** 2 * acc_bytes
                    budget = device_cache_budget(reserve_bytes=reserve)
                else:
                    budget = int(device_cache)
                stats["device_cache_budget"] = max(stats["device_cache_budget"], budget)
                work_dir = spool_dir or tempfile.mkdtemp(prefix="sbv-spool-")
                spool = PackedChunkSpool(os.path.join(work_dir, f"round{outer}"),
                                         device_budget=budget, sharding=sharding)
                backends = set()
                with span("sbv.fit.struct.pack", stats, "struct_pack_s"):
                    for ranks in struct.plan:
                        packed = pack_block_chunk(
                            store, struct.blocks, struct.neigh, ranks,
                            m=cfg.m, bs_max=struct.bs_max, dtype=cfg.dtype,
                        )
                        if n_buckets:
                            from .buckets import bucket_blocks

                            bucketed = bucket_blocks(packed, ceilings=(bs_ceils, m_ceils))
                            groups = _group(bs_true[ranks], m_true[ranks],
                                            bs_ceils, m_ceils)
                            pieces = [
                                p.pad_to_blocks(cell_bc[(min(bs_c, packed.bs_max),
                                                         min(m_c, packed.m))])
                                for (bs_c, m_c, _), p in zip(groups, bucketed.buckets)
                            ]
                        else:
                            pieces = [packed.pad_to_blocks(bc_pad)]
                        for p in pieces:
                            if tier:
                                from .buckets import cast_packed

                                p = cast_packed(p, tier)
                            if n_shards > 1:
                                # owner-contiguous reorder; bc already divides the
                                # shard count, so the shape is unchanged
                                p = shard_blocks_by_owner(p, n_shards)
                            piece_backend = _piece_backend(backend, p)
                            backends.add(piece_backend)
                            spool.add(p, tag=piece_backend)
            stats["backends"] = sorted(backends)
            stats.update(
                n_chunks=len(struct.plan), n_pieces=len(spool),
                piece_blocks=bc_pad,  # uniform-layout piece block count
                packed_chunk_bytes_max=max(stats["packed_chunk_bytes_max"],
                                           spool.packed_bytes_max),
                spool_bytes=max(stats["spool_bytes"], spool.packed_bytes_total),
                bs_max=struct.bs_max, bc=struct.blocks.n_blocks,
                # last-round values, consistent with n_pieces/n_chunks ...
                device_cached_pieces=spool.n_device,
                h2d_bytes_per_step=spool.disk_bytes_total,
                # ... except the cached-bytes PEAK across rounds, which is
                # what the working_set_model RSS ceiling has to cover
                device_cached_bytes=max(stats["device_cached_bytes"],
                                        spool.device_bytes),
            )

            if mesh is not None:
                # Replicate the params over the mesh up front: the sharded
                # step returns a replicated gradient, so after the first
                # update they would carry that placement and the chunk
                # step would compile a second time.
                params = jax.device_put(params, NamedSharding(mesh, P()))
            state = adam_init(params)
            with span("sbv.fit.steps", stats, "inner_time_s"):
                for it in range(inner_steps):
                    step = stats["inner_steps_total"] + it
                    # step_times_s: first piece to the loss on the host
                    with span("sbv.fit.step", stats, "step_times_s",
                              step=step):
                        loss = None
                        grad = None
                        for piece, (arrs, piece_backend) in enumerate(
                                spool.iter_arrays(prefetch=prefetch)):
                            with span("sbv.fit.piece", step=step, piece=piece):
                                grad_fn = _chunk_grad_fn(nu, piece_backend, n, mesh, axis)
                                v, g = grad_fn(params, *arrs)
                                loss = v if loss is None else loss + v
                                grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
                        with span("sbv.fit.adam_update", step=step):
                            params, state = adam_update(grad, state, params, lr)
                        with span("sbv.fit.sync", step=step):
                            history.append((outer, it, float(loss)))  # float() syncs
                    if verbose and it % 10 == 0:
                        print(f"[fit-stream] outer={outer} it={it} "
                              f"nll/n={float(loss):.6f} pieces={len(spool)} "
                              f"(device-cached {spool.n_device})")
            stats["inner_steps_total"] += inner_steps
        finally:
            if spool is not None:
                spool.cleanup()
            if spool_dir is None and work_dir is not None:
                shutil.rmtree(work_dir, ignore_errors=True)
    return FitResult(params=params, history=history, packed=None,
                     stream_stats=stats)


def _fit_sbv_multihost(
    store, cfg, init, nu, lr, inner_steps, outer_rounds, backend, verbose,
    stream_chunk, spool_dir, comm, device_cache: int | None = None,
    prefetch: int = 2, precision=None,
):
    """Multi-process streaming fit: one `jax.distributed` host per
    partition, construction and packing per host, one `[loss, grad]`
    all-reduce per chunk per step (see `_fit_sbv_streaming`)."""
    import shutil
    import tempfile

    from jax.flatten_util import ravel_pytree

    from repro.data.store import PartitionedStore
    from repro.data.streaming import (
        device_cache_budget, multihost_preprocess, pack_block_chunk,
        PackedChunkSpool, streaming_moments,
    )

    pstore = (store if isinstance(store, PartitionedStore)
              else PartitionedStore(store, comm.size, comm.rank))
    tier = None
    if precision is not None:
        from .buckets import as_policy

        pol = as_policy(precision)
        if pol.tier != "f64":
            tier = pol.tier
    n, d = pstore.n_rows, pstore.d
    if init is None:
        _, var_y = streaming_moments(pstore, comm=comm)
        params = KernelParams.create(sigma2=var_y, beta=0.5, nugget=1e-3, d=d)
    else:
        params = init
    _, unravel = ravel_pytree(params)
    n_param = int(np.asarray(ravel_pytree(params)[0]).size)
    history = []
    stats = {"n_chunks": 0, "n_pieces": 0, "packed_chunk_bytes_max": 0,
             "spool_bytes": 0, "bs_max": 0, "bc": 0, "n_shards": 1,
             "device_cached_pieces": 0, "device_cached_bytes": 0,
             "h2d_bytes_per_step": 0, "inner_steps_total": 0,
             "inner_time_s": 0.0, "n_hosts": comm.size, "rank": comm.rank,
             "lockstep_chunks": 0, "allreduce_scalars_per_chunk": 1 + n_param,
             "precision": tier or "f64", "device_cache_budget": 0}

    for outer in range(outer_rounds):
        beta_np = np.asarray(params.beta)
        struct = multihost_preprocess(pstore, beta_np, cfg, stream_chunk, comm)
        # Pad every LOCAL piece to one shared shape; hosts may compile
        # different shapes — nothing cross-host depends on them (the
        # lockstep all-reduce carries only the [loss, grad] vector).
        bc_pad = max((len(r) for r in struct.plan), default=1)

        if device_cache is None:
            acc_bytes = 4 if tier else int(np.dtype(cfg.dtype).itemsize)
            reserve = 16 * _MAP_BATCH * (struct.bs_max + cfg.m) ** 2 * acc_bytes
            budget = device_cache_budget(reserve_bytes=reserve)
        else:
            budget = int(device_cache)
        stats["device_cache_budget"] = max(stats["device_cache_budget"], budget)
        work_dir = spool_dir or tempfile.mkdtemp(prefix="sbv-spool-")
        spool = PackedChunkSpool(
            os.path.join(work_dir, f"rank{comm.rank}-round{outer}"),
            device_budget=budget)
        try:
            for ranks in struct.plan:
                packed = pack_block_chunk(
                    struct.table, struct.blocks, struct.neigh, ranks,
                    m=cfg.m, bs_max=struct.bs_max, dtype=cfg.dtype,
                )
                piece = packed.pad_to_blocks(bc_pad)
                if tier:
                    from .buckets import cast_packed

                    piece = cast_packed(piece, tier)
                spool.add(piece, tag=_piece_backend(backend, piece))
            # Hosts iterate the SAME number of lockstep chunk slots per
            # step; hosts out of local pieces contribute zeros.
            n_lock = int(comm.allreduce_scalar(float(len(spool)), op="max"))
            stats.update(
                n_chunks=len(struct.plan), n_pieces=len(spool),
                packed_chunk_bytes_max=max(stats["packed_chunk_bytes_max"],
                                           spool.packed_bytes_max),
                spool_bytes=max(stats["spool_bytes"], spool.packed_bytes_total),
                bs_max=struct.bs_max, bc=struct.blocks.n_blocks,
                device_cached_pieces=spool.n_device,
                h2d_bytes_per_step=spool.disk_bytes_total,
                device_cached_bytes=max(stats["device_cached_bytes"],
                                        spool.device_bytes),
                lockstep_chunks=n_lock,
                **{k: v for k, v in struct.stats.items()},
            )

            state = adam_init(params)
            t_inner = time.perf_counter()
            zeros_vec = np.zeros(1 + n_param)
            for it in range(inner_steps):
                loss = 0.0
                gsum = np.zeros(n_param)
                pieces = spool.iter_arrays(prefetch=prefetch)
                for _ in range(n_lock):
                    entry = next(pieces, None)
                    if entry is not None:
                        arrs, piece_backend = entry
                        grad_fn = _chunk_grad_fn(nu, piece_backend, n)
                        v, g = grad_fn(params, *arrs)
                        gflat = np.asarray(ravel_pytree(g)[0], np.float64)
                        vec = np.concatenate([[float(v)], gflat])
                    else:
                        vec = zeros_vec
                    red = comm.allreduce(vec)
                    loss += float(red[0])
                    gsum = gsum + red[1:]
                grad = jax.tree.map(
                    jnp.asarray, unravel(jnp.asarray(gsum)))
                params, state = adam_update(grad, state, params, lr)
                history.append((outer, it, float(loss)))
                if verbose and it % 10 == 0:
                    print(f"[fit-mh] rank={comm.rank} outer={outer} it={it} "
                          f"nll/n={float(loss):.6f} "
                          f"pieces={len(spool)}/{n_lock}")
            stats["inner_time_s"] += time.perf_counter() - t_inner
            stats["inner_steps_total"] += inner_steps
        finally:
            spool.cleanup()
            if spool_dir is None:
                shutil.rmtree(work_dir, ignore_errors=True)
    return FitResult(params=params, history=history, packed=None,
                     stream_stats=stats)


def fit_sbv(
    x: np.ndarray,
    y: np.ndarray = None,
    cfg: SBVConfig = None,
    init: KernelParams | None = None,
    nu: float = 3.5,
    lr: float = 0.05,
    inner_steps: int = 60,
    outer_rounds: int = 3,
    backend: str = "ref",
    verbose: bool = False,
    distributed=None,   # optional (mesh, axis) for shard_map likelihood
    n_buckets: int | None = None,
    stream_chunk: int | None = None,
    spool_dir: str | None = None,
    device_cache: int | None = None,
    prefetch: int = 2,
    multihost=None,  # host comm (repro.multihost) for the multi-process fit
    precision=None,  # ladder tier name or core.buckets.PrecisionPolicy
    tuning=None,     # TuningRecord (or its directory/path) from repro.tuning
) -> FitResult:
    """Maximum-likelihood fit of (sigma^2, beta, nugget) with fixed nu.

    ``n_buckets`` runs the likelihood on the bucketed layout
    (docs/packing.md). Each Scaled-Vecchia structure refresh re-clusters
    with the current beta, which reshapes the block-size distribution —
    so the packing is RE-bucketed every outer round, keeping bucket
    ceilings matched to the refreshed skew.

    Out-of-core: pass ``x`` as a row store (``repro.data.ArrayStore`` /
    ``MemoryStore``, with ``y=None``) and/or set ``stream_chunk`` to fit
    through the streaming path (docs/streaming.md) — structure, packing
    and likelihood all run in bounded ~``stream_chunk``-row passes. An
    in-core ``(x, y)`` with ``stream_chunk`` set takes the identical code
    path over a ``MemoryStore``, so store-backed and in-core streaming
    fits agree bitwise on the same rows. In-core arrays WITHOUT
    ``stream_chunk`` keep the original monolithic fast path.
    ``device_cache`` (bytes; None = auto, 0 = off) and ``prefetch``
    control the streaming inner loop's memory tiers — see
    ``_fit_sbv_streaming`` and docs/streaming.md. ``distributed=`` works
    with BOTH paths: in-core it shards the monolithic packed likelihood;
    streaming it shards every spooled piece (the 2.56B-point scaling
    configuration). ``multihost=`` (a host comm from
    ``repro.multihost``) runs the MULTI-PROCESS streaming fit: each
    ``jax.distributed`` process builds, packs, and spools only its own
    row partition and the hosts all-reduce ``[loss, grad]`` once per
    chunk per step (docs/streaming.md "multi-host construction").

    ``precision`` selects the mixed-precision ladder (docs/precision.md):
    a tier name (``'bf16'``/``'f32'``/``'f64'``) or a
    ``core.buckets.PrecisionPolicy``. In-core fits probe each bucket
    against the f64 reference every structure refresh and demote
    over-budget buckets; streaming fits cast uniformly to the policy
    tier. ``tuning`` pre-loads an autotuned configuration (a
    ``repro.tuning.TuningRecord`` or a checkpoint directory holding one):
    it fills ``n_buckets``/``stream_chunk``/``precision`` when the caller
    left them unset, and ``backend`` when it is ``'auto'``."""
    from repro.data.store import as_store, is_store

    if cfg is None:
        raise TypeError("fit_sbv requires an SBVConfig")
    if tuning is not None:
        from repro.tuning import as_record

        rec = as_record(tuning)
        if n_buckets is None:
            n_buckets = rec.n_buckets
        if stream_chunk is None and rec.stream_chunk:
            stream_chunk = rec.stream_chunk
        if precision is None and rec.precision:
            precision = rec.precision_policy()
        if backend == "auto" and rec.backend:
            backend = rec.backend
    if multihost is not None and not (is_store(x) or stream_chunk is not None):
        raise ValueError("multihost= requires the streaming path: pass a "
                         "row store and/or set stream_chunk")

    # -- Multi-output routing (docs/multioutput.md). A 2-D y with p >= 2
    # takes the shared-structure VPPE path; (n, 1) squeezes to the
    # single-output program so p=1 stays BITWISE-identical to a 1-D y.
    if not is_store(x) and y is not None and np.asarray(y).ndim == 2:
        y2 = np.asarray(y)
        if y2.shape[1] == 1:
            from .multioutput import MultiOutputParams

            init1 = (init.output_params(0)
                     if isinstance(init, MultiOutputParams) else init)
            return fit_sbv(
                x, y2[:, 0], cfg, init=init1, nu=nu, lr=lr,
                inner_steps=inner_steps, outer_rounds=outer_rounds,
                backend=backend, verbose=verbose, distributed=distributed,
                n_buckets=n_buckets, stream_chunk=stream_chunk,
                spool_dir=spool_dir, device_cache=device_cache,
                prefetch=prefetch, multihost=multihost, precision=precision,
            )
        if multihost is not None or distributed is not None:
            raise NotImplementedError("multi-output fits do not support "
                                      "multihost=/distributed= yet")
        if stream_chunk is not None:
            if n_buckets:
                raise NotImplementedError("bucketed piece shapes are not "
                                          "wired into the multi-output "
                                          "streaming fit yet")
            return _fit_sbv_multi_streaming(
                as_store(x, y2), cfg, init, nu, lr, inner_steps, outer_rounds,
                backend, verbose, stream_chunk, spool_dir,
                device_cache=device_cache, prefetch=prefetch,
                precision=precision,
            )
        return _fit_sbv_multi(x, y2, cfg, init, nu, lr, inner_steps,
                              outer_rounds, backend, verbose, n_buckets,
                              precision=precision)
    if is_store(x) and np.asarray(as_store(x, y).read_slice(0, 1)[1]).ndim == 2:
        if multihost is not None or distributed is not None:
            raise NotImplementedError("multi-output fits do not support "
                                      "multihost=/distributed= yet")
        if n_buckets:
            raise NotImplementedError("bucketed piece shapes are not wired "
                                      "into the multi-output streaming fit "
                                      "yet")
        from repro.data.streaming import DEFAULT_STRUCT_BATCH

        return _fit_sbv_multi_streaming(
            as_store(x, y), cfg, init, nu, lr, inner_steps, outer_rounds,
            backend, verbose, stream_chunk or DEFAULT_STRUCT_BATCH, spool_dir,
            device_cache=device_cache, prefetch=prefetch, precision=precision,
        )

    if is_store(x) or stream_chunk is not None:
        from repro.data.streaming import DEFAULT_STRUCT_BATCH

        store = as_store(x, y)
        return _fit_sbv_streaming(
            store, cfg, init, nu, lr, inner_steps, outer_rounds, backend,
            verbose, stream_chunk or DEFAULT_STRUCT_BATCH, n_buckets, spool_dir,
            distributed=distributed, device_cache=device_cache,
            prefetch=prefetch, multihost=multihost, precision=precision,
        )
    policy = None
    if precision is not None:
        from .buckets import as_policy

        policy = as_policy(precision)
        if policy.tier == "f64" and not policy.probe:
            policy = None
    d = x.shape[1]
    params = init or KernelParams.create(sigma2=float(np.var(y)), beta=0.5, nugget=1e-3, d=d)
    history = []
    packed = None
    tiers = None

    for outer in range(outer_rounds):
        beta_np = np.asarray(params.beta)
        packed, _ = preprocess(x, y, beta_np, cfg)
        if n_buckets:
            from .buckets import bucket_blocks

            packed = bucket_blocks(packed, n_buckets=n_buckets)
        if policy is not None:
            # Probe-and-demote at the CURRENT params, re-assigned every
            # structure refresh (re-clustering reshapes the buckets).
            from .buckets import (apply_precision, assign_precision,
                                  BucketedBlocks, cast_packed)

            tiers = assign_precision(params, packed, policy, nu=nu,
                                     backend=backend)
            if isinstance(packed, BucketedBlocks):
                packed = apply_precision(packed, tiers)
            else:
                packed = cast_packed(packed, tiers[0])
        if distributed is not None:
            from .distributed import distributed_neg_loglik_fn

            loss_fn = distributed_neg_loglik_fn(packed, nu, *distributed)
        else:
            loss_fn = jax.jit(neg_loglik_fn(packed, nu, backend))
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))

        state = adam_init(params)
        for it in range(inner_steps):
            loss, g = grad_fn(params)
            params, state = adam_update(g, state, params, lr)
            history.append((outer, it, float(loss)))
            if verbose and it % 10 == 0:
                print(f"[fit] outer={outer} it={it} nll/n={float(loss):.6f}")
    return FitResult(params=params, history=history, packed=packed,
                     precision_tiers=tiers)


def fit_neldermead(
    x, y, cfg: SBVConfig, init: KernelParams | None = None,
    nu: float = 3.5, maxiter: int = 400, backend: str = "ref",
) -> FitResult:
    """Derivative-free MLE (paper-faithful optimizer path, via scipy)."""
    from scipy.optimize import minimize

    d = x.shape[1]
    params = init or KernelParams.create(sigma2=float(np.var(y)), beta=0.5, nugget=1e-3, d=d)
    packed, _ = preprocess(x, y, np.asarray(params.beta), cfg)
    loss = jax.jit(neg_loglik_fn(packed, nu, backend))

    def unpack(v):
        return KernelParams(
            log_sigma2=jnp.asarray(v[0]), log_beta=jnp.asarray(v[1 : 1 + d]),
            log_nugget=jnp.asarray(v[1 + d]),
        )

    v0 = np.concatenate([[float(params.log_sigma2)], np.asarray(params.log_beta), [float(params.log_nugget)]])
    res = minimize(lambda v: float(loss(unpack(v))), v0, method="Nelder-Mead",
                   options={"maxiter": maxiter, "xatol": 1e-4, "fatol": 1e-7})
    return FitResult(params=unpack(res.x), history=[(0, res.nit, float(res.fun))], packed=packed)
