"""Block prediction with conditional simulation (paper Eq. 3 + §5.1.5).

Serving-side mirror of the likelihood stack:

    pack   -- test points are clustered into prediction blocks (bs_pred);
              each block is conditioned on its m_pred nearest TRAINING
              points (no ordering constraint — Eq. 3 conditions on the
              full training vector y). Blocks + neighbors are packed into
              fixed-size padded arrays (``PackedPrediction``).
    predict - ONE vmapped/jitted call over the packed arrays computes every
              block conditional, with the per-point simulation draws
              (paper §5.1.5: 1000 samples of N(mu_j, sigma_j^2)) taken
              inside the same jitted program via ``jax.random``.
              ``backend='pallas'`` dispatches the conditional to the fused
              kernel in ``repro/kernels/sbv_predict.py``.
    scatter - padded per-block results land back in test-point order via
              the packed scatter indices (vectorized, no Python loop).

``chunk_size`` bounds device memory for arbitrary n_test: the training
index is built once, then fixed-shape chunks stream through the jitted
predict program (shapes are rounded up so the jit cache is reused).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.spans import span

from .blocks import BlockStructure, build_blocks, scale_inputs
from .kernels_math import KernelParams
from .nns import _FlatBlocks, filtered_knn_points
from .packing import PackedPrediction, pack_prediction, round_up
from .vecchia import _masked_cov


@dataclass
class Prediction:
    """Prediction fields are (n*,) for single-output training data and
    (n*, p) when the training observations were (n, p) multi-output
    (docs/multioutput.md)."""

    mean: np.ndarray       # conditional mean mu_new
    var: np.ndarray        # conditional marginal variance
    sim_mean: np.ndarray   # conditional-simulation sample mean
    ci_low: np.ndarray     # 95% CI bounds from simulation
    ci_high: np.ndarray
    # What the call ran: ``backends`` (the concrete predict programs),
    # ``shapes`` ((bc, bs_pred, m_pred) per piece), ``host_s`` (everything
    # but the chunks' dispatch and fetch: training index, query NNS and
    # packing, casts), within it ``index_s`` (the training index) and
    # ``query_s`` (query NNS and packing), and ``fetch_s`` (per piece: the
    # wait for the device, the copy to the host and the scatter).
    stats: dict = field(default_factory=dict)


@dataclass
class TrainIndex:
    """Host-side training-set structure reused across prediction chunks.

    In-core indexes hold the raw/scaled arrays; store-backed indexes (see
    ``build_train_index(..., stream_chunk=)``) hold lazy row views with
    ``xs=None``, a store handle, and the cached scaled-domain volume the
    filtered kNN needs (the one quantity otherwise derived from the full
    scaled array)."""

    x: np.ndarray          # (n, d) raw training inputs (or lazy row view)
    y: np.ndarray          # (n,) training observations (or lazy row view)
    xs: np.ndarray | None  # (n, d) scaled inputs; None when store-backed
    beta: np.ndarray       # (d,) structure scaling
    blocks: BlockStructure # coarse blocks for the filtered kNN
    flat: _FlatBlocks | None = None  # flattened block members, built once
    store: object = None             # row store behind a streaming index
    domain_volume: float | None = None


def build_train_index(
    x_train: np.ndarray,
    y_train: np.ndarray,
    beta: np.ndarray,
    m_pred: int,
    n_workers: int = 1,
    seed: int = 0,
    stream_chunk: int | None = None,
) -> TrainIndex:
    """Scale + coarse-block the training set once; reused per chunk.

    The flattened block index (``_FlatBlocks``) is cached here: it holds
    the full n x d gather of block members that ``filtered_knn_points``
    would otherwise rebuild on every query chunk.

    Pass ``x_train`` as a row store (``y_train=None``) and/or set
    ``stream_chunk`` for the out-of-core index: structure comes from
    mini-batch k-means passes and the flat index serves candidate gathers
    from the store with a bounded cache (docs/streaming.md). An in-core
    ``(x, y)`` with ``stream_chunk`` runs the identical code over a
    ``MemoryStore``, so the two agree bitwise on the same rows."""
    from repro.data.store import as_store, is_store

    if is_store(x_train) or stream_chunk is not None:
        from repro.data.streaming import (
            DEFAULT_STRUCT_BATCH, LazyFlatBlocks, streaming_kmeans_blocks,
        )

        store = as_store(x_train, y_train)
        beta = np.broadcast_to(np.asarray(beta, dtype=np.float64), (store.d,))
        bc_train = max(1, store.n_rows // max(4 * m_pred, 64))
        # Structure passes use the FIXED batch size (like the fit): the
        # index must not depend on the caller's packing window.
        blocks, radii, vol = streaming_kmeans_blocks(
            store, beta, bc_train, n_workers=n_workers, seed=seed,
            batch_rows=DEFAULT_STRUCT_BATCH,
        )
        flat = LazyFlatBlocks(blocks, radii, store, beta)
        return TrainIndex(x=store.x_rows, y=store.y_rows, xs=None, beta=beta,
                          blocks=blocks, flat=flat, store=store,
                          domain_volume=vol)
    x_train = np.asarray(x_train, dtype=np.float64)
    y_train = np.asarray(y_train, dtype=np.float64)
    beta = np.broadcast_to(np.asarray(beta, dtype=np.float64), (x_train.shape[1],))
    xs = scale_inputs(x_train, beta)
    bc_train = max(1, x_train.shape[0] // max(4 * m_pred, 64))
    blocks = build_blocks(xs, bc_train, n_workers, beta, seed=seed)
    return TrainIndex(x=x_train, y=y_train, xs=xs, beta=beta, blocks=blocks,
                      flat=_FlatBlocks(xs, blocks))


def scatter_packed(packed: PackedPrediction, *pairs) -> None:
    """Vectorized scatter: for each ``(padded_values, out)`` pair write
    ``out[q_idx[mask]] = padded_values[mask]`` (drops padding)."""
    msk = packed.q_mask
    idx = packed.q_idx[msk]
    for values, out in pairs:
        out[idx] = np.asarray(values)[msk]


def pack_queries(
    index: TrainIndex,
    x_test: np.ndarray,
    bs_pred: int,
    m_pred: int,
    alpha: float = 100.0,
    seed: int = 0,
    n_workers: int = 1,
    offset: int = 0,
    pad_shapes: bool = False,
    dtype=np.float64,
) -> PackedPrediction:
    """Cluster test points into prediction blocks, find each block's m_pred
    nearest training points, pack. ``offset`` shifts the scatter indices
    (chunked serving). ``pad_shapes`` rounds bs/bc up to multiples of 8 so
    successive chunks hit the same jit cache entry. ``dtype`` controls the
    packed array precision (use float32 for the compiled TPU Pallas path;
    float64 is fine in interpret mode / on CPU)."""
    x_test = np.asarray(x_test, dtype=np.float64)
    n_test = x_test.shape[0]
    xs_test = scale_inputs(x_test, index.beta)
    bc_pred = max(1, n_test // bs_pred)
    test_blocks = build_blocks(xs_test, bc_pred, n_workers, index.beta, seed=seed + 1)
    neigh = filtered_knn_points(index.xs, index.blocks, test_blocks.centers,
                                m_pred, alpha, flat=index.flat,
                                domain_volume=index.domain_volume)

    if index.store is not None:
        # Store-backed index: gather the union of neighbor rows once and
        # remap, instead of per-block fancy-indexing the full training set
        # (values and order preserved — packed arrays are bit-identical).
        from repro.data.streaming import localize_neighbors

        x_tr, y_tr, neigh = localize_neighbors(index.store, neigh)
    else:
        x_tr, y_tr = index.x, index.y

    bs_max = max(mb.size for mb in test_blocks.members)
    if pad_shapes:
        bs_max = round_up(bs_max, 8)
    packed = pack_prediction(
        x_test, x_tr, y_tr, test_blocks, neigh, m_pred, bs_max=bs_max,
        dtype=dtype,
    )
    if offset:
        packed.q_idx[packed.q_mask] += offset
    if pad_shapes:
        packed = packed.pad_to_blocks(round_up(packed.n_blocks, 8))
    return packed


def iter_query_chunks(
    index: TrainIndex,
    x_test: np.ndarray,
    bs_pred: int,
    m_pred: int,
    alpha: float = 100.0,
    seed: int = 0,
    n_workers: int = 1,
    chunk_size: int | None = None,
    dtype=np.float64,
    stats: dict | None = None,
):
    """Yield ``(chunk_id, PackedPrediction)`` over the test set.

    The single chunking protocol shared by ``predict_sbv`` and the serving
    driver: step clamped to >= bs_pred, per-chunk seed variation, scatter
    offsets, and jit-stable padded shapes in chunked mode all live HERE so
    the two paths cannot drift. ``x_test`` may be a row store, in which
    case each window is read on demand (``chunk_size`` is then required —
    reading an out-of-core test set whole would defeat the store).
    ``stats["query_s"]`` gets the seconds of the chunks' packing."""
    from repro.data.store import is_store

    if is_store(x_test):
        if chunk_size is None:
            raise ValueError("x_test is a store: pass chunk_size to bound "
                             "the per-window read")
        n_test = x_test.n_rows
        window = lambda a, b: x_test.read_slice(a, b)[0]
    else:
        x_test = np.asarray(x_test, dtype=np.float64)
        n_test = x_test.shape[0]
        window = lambda a, b: x_test[a:b]
    step = n_test if chunk_size is None else max(int(chunk_size), bs_pred)
    for ci, start in enumerate(range(0, n_test, step)):
        stop = min(n_test, start + step)
        with span("sbv.predict.query", stats, "query_s", chunk=ci):
            packed = pack_queries(
                index, window(start, stop), bs_pred, m_pred, alpha=alpha,
                seed=seed + ci, n_workers=n_workers, offset=start,
                pad_shapes=chunk_size is not None, dtype=dtype,
            )
        yield ci, packed


def _predict_multi_one(params, nu, qx, qmask, nx, ny, nmask):
    """Multi-output block conditional (docs/multioutput.md).

    ``ny`` is (m, p). One Cholesky of the shared unit-variance
    conditioning covariance serves all outputs: the mean is sigma2-free
    (the per-output scale cancels in cross @ con^-1 @ y), so the p means
    are just extra solve columns; the variance scales the shared
    unit-variance conditional by each output's sigma2."""
    p0 = params.structure_params()
    sigma_con = _masked_cov(nx, nx, nmask, nmask, p0, nu, identity=True)
    sigma_cross = _masked_cov(nx, qx, nmask, qmask, p0, nu, identity=False)
    ynn = jnp.where(nmask[:, None], ny, 0.0)
    chol = jnp.linalg.cholesky(sigma_con)
    a = jax.scipy.linalg.solve_triangular(chol, sigma_cross, lower=True)
    z = jax.scipy.linalg.solve_triangular(chol, ynn, lower=True)  # (m, p)
    mu = a.T @ z                                                  # (bs, p)
    var0 = (1.0 + params.tau2) - jnp.sum(a * a, axis=0)           # (bs,)
    var = var0[:, None] * params.sigma2[None, :]
    return mu, jnp.maximum(var, 1e-12)


def _predict_one(params, nu, qx, qmask, nx, ny, nmask):
    sigma_con = _masked_cov(nx, nx, nmask, nmask, params, nu, identity=True)
    sigma_cross = _masked_cov(nx, qx, nmask, qmask, params, nu, identity=False)
    ynn = jnp.where(nmask, ny, 0.0)
    chol = jnp.linalg.cholesky(sigma_con)
    a = jax.scipy.linalg.solve_triangular(chol, sigma_cross, lower=True)
    z = jax.scipy.linalg.solve_triangular(chol, ynn, lower=True)
    mu = a.T @ z
    prior = params.sigma2 + params.nugget
    var = prior - jnp.sum(a * a, axis=0)
    return mu, jnp.maximum(var, 1e-12)


def resolve_backend(backend: str, params, q_x, nn_x) -> str:
    """The concrete program ``batched_block_predict`` runs for ``backend``
    on these operands: multi-output params always take the vmapped
    program, and ``'auto'`` resolves per block shape and coordinate dtype
    (``kernels.ops.select_backend``)."""
    from .multioutput import MultiOutputParams

    if isinstance(params, MultiOutputParams):
        return "ref"
    if backend == "auto":
        from repro.kernels import ops as kops

        return kops.select_backend(q_x.shape[1], nn_x.shape[1],
                                   kind="predict", dtype=q_x.dtype)
    return backend


@partial(jax.jit, static_argnames=("nu", "backend"))
def batched_block_predict(
    params: KernelParams,
    q_x, q_mask, nn_x, nn_y, nn_mask,
    nu: float = 3.5,
    backend: str = "ref",
):
    """Conditional mean/variance for every prediction block in one jitted
    call on packed arrays: (bc, bs_pred) each. Padded query slots carry
    mu=0 / var=prior; drop them with the mask.

    Backends: ``ref`` (vmapped jnp, differentiable), ``pallas`` (fused
    kernel on the given shapes), ``pallas_tiled`` (fused kernel on
    8x128-aligned tiles — the compiled f32 TPU serving path), ``auto``
    (resolved per batch shape by ``kernels.ops.select_backend`` — the
    bucketed execution layer uses this to mix backends across buckets).

    ``MultiOutputParams`` (with (bc, m, p) ``nn_y``) dispatches to the
    shared-Cholesky multi-output conditional and returns (bc, bs, p)
    mean/variance; the fused predict kernels stay single-output, so every
    backend resolves to the vmapped program there (the shared solve is
    already the dominant cost — see docs/multioutput.md)."""
    from .multioutput import MultiOutputParams

    if isinstance(params, MultiOutputParams):
        return jax.vmap(
            lambda a, b, c, d, e: _predict_multi_one(params, nu, a, b, c, d, e)
        )(q_x, q_mask, nn_x, nn_y, nn_mask)
    backend = resolve_backend(backend, params, q_x, nn_x)
    if backend == "ref":
        return jax.vmap(
            lambda a, b, c, d, e: _predict_one(params, nu, a, b, c, d, e)
        )(q_x, q_mask, nn_x, nn_y, nn_mask)
    if backend in ("pallas", "pallas_tiled"):
        from repro.kernels import ops as kops

        return kops.sbv_predict(params, q_x, q_mask, nn_x, nn_y, nn_mask, nu=nu,
                                tiled=backend == "pallas_tiled")
    raise ValueError(f"unknown backend {backend!r}")


def packed_predict(
    params: KernelParams,
    packed: PackedPrediction,
    nu: float = 3.5,
    backend: str = "ref",
):
    """Mean/variance of a PackedPrediction (padded (bc, bs_pred) arrays)."""
    q_x, q_mask, nn_x, nn_y, nn_mask = (jnp.asarray(a) for a in packed.arrays())
    return batched_block_predict(
        params, q_x, q_mask, nn_x, nn_y, nn_mask, nu=nu, backend=backend
    )


@partial(jax.jit, static_argnames=("nu", "backend", "n_sims"))
def _predict_and_simulate(
    params, q_x, q_mask, nn_x, nn_y, nn_mask, key,
    nu: float, backend: str, n_sims: int,
):
    """End-to-end jitted per-chunk math: block conditionals + vectorized
    conditional simulation (paper §5.1.5) in one device program."""
    mu, var = batched_block_predict(
        params, q_x, q_mask, nn_x, nn_y, nn_mask, nu=nu, backend=backend
    )
    eps = jax.random.normal(key, (n_sims,) + mu.shape, dtype=mu.dtype)
    draws = mu[None] + jnp.sqrt(var)[None] * eps
    sim_mean = jnp.mean(draws, axis=0)
    sim_std = jnp.std(draws, axis=0, ddof=1)
    return mu, var, sim_mean, sim_std


@partial(jax.jit, static_argnames=("nu", "backend", "n_sims", "lo", "bc_full"))
def _predict_and_simulate_span(
    params, q_x, q_mask, nn_x, nn_y, nn_mask, key,
    nu: float, backend: str, n_sims: int, lo: int, bc_full: int,
):
    """One rank's block span of a chunk, with the FULL chunk's sim-draw
    stream: eps is generated at the whole-chunk ``(n_sims, bc_full, ...)``
    shape from the chunk's key and sliced to this rank's ``[lo, lo+bc)``
    block rows, so every block receives exactly the draws the serial
    ``_predict_and_simulate`` would hand it. Per-block conditionals are
    independent, so mean/var shard bitwise; the simulation columns agree
    to ~1 ulp (XLA fuses the eps slice into the sample reductions
    differently per span shape) — far inside the 1e-8 multi-host parity
    gate. A full-span slice (``lo=0, bc_full=bc``) is bitwise
    everywhere, which is the LoopbackComm contract."""
    mu, var = batched_block_predict(
        params, q_x, q_mask, nn_x, nn_y, nn_mask, nu=nu, backend=backend
    )
    eps = jax.random.normal(key, (n_sims, bc_full) + mu.shape[1:],
                            dtype=mu.dtype)[:, lo:lo + mu.shape[0]]
    draws = mu[None] + jnp.sqrt(var)[None] * eps
    sim_mean = jnp.mean(draws, axis=0)
    sim_std = jnp.std(draws, axis=0, ddof=1)
    return mu, var, sim_mean, sim_std


def _slice_prediction_blocks(p: PackedPrediction, lo: int,
                             hi: int) -> PackedPrediction:
    """A contiguous block-row view of a packed chunk (every field's
    leading axis is the block count; ``q_idx`` stays global, so the
    scatter of a slice lands in the right test rows)."""
    return PackedPrediction(
        q_x=p.q_x[lo:hi], q_mask=p.q_mask[lo:hi], q_idx=p.q_idx[lo:hi],
        nn_x=p.nn_x[lo:hi], nn_y=p.nn_y[lo:hi], nn_mask=p.nn_mask[lo:hi],
        owners=p.owners[lo:hi],
    )


@span("sbv.predict")
def predict_sbv(
    params: KernelParams,
    x_train: np.ndarray,
    y_train: np.ndarray,
    x_test: np.ndarray,
    bs_pred: int = 25,
    m_pred: int = 200,
    nu: float = 3.5,
    alpha: float = 100.0,
    n_sims: int = 1000,
    seed: int = 0,
    n_workers: int = 1,
    beta_struct: np.ndarray | None = None,
    backend: str = "ref",
    chunk_size: int | None = None,
    dtype=np.float64,
    n_buckets: int | None = None,
    stream_chunk: int | None = None,
    precision=None,
    tuning=None,
    multihost=None,
) -> Prediction:
    """Packed block prediction over the full test set.

    ``beta_struct`` overrides the scaling used for clustering/NNS only
    (paper Fig. 4 isolates structure quality: BV = isotropic structure +
    true kernel; SBV = scaled structure + true kernel). ``chunk_size``
    streams the test set through fixed-shape device programs so memory
    stays bounded for arbitrary n_test. ``n_buckets`` executes each chunk
    as size-buckets padded to their own ceilings (docs/packing.md) instead
    of one uniformly-padded batch; mean/var are unchanged (<=1e-10), only
    padding waste drops.

    Out-of-core: ``x_train`` (with ``y_train=None``) and/or ``x_test``
    may be row stores; ``stream_chunk`` selects the streaming training
    index (docs/streaming.md). In-core arrays with ``stream_chunk`` take
    the identical code path, so store-backed and in-core streaming
    predictions agree bitwise on the same rows.

    ``precision`` picks a ladder tier (str or PrecisionPolicy,
    docs/precision.md): coordinates pack at the tier's storage dtype and
    all conditional math runs at its accumulation dtype. Unlike the fit
    there is no per-chunk probe — budget enforcement happens at fit/tune
    time (``assign_precision`` / the autotuner); pass the fitted tier.
    ``tuning`` (TuningRecord / dict / checkpoint path) fills n_buckets,
    stream_chunk, and precision when unset, and backend when 'auto'.

    ``multihost`` (a ``MultihostContext`` / ``LoopbackComm``,
    repro/multihost.py) shards every chunk's prediction BLOCKS by owner
    rank: each rank computes its contiguous block span with the full
    chunk's simulation-draw stream (``_predict_and_simulate_span``),
    scatters into zero-filled result columns, and ONE allreduce-sum per
    call merges the disjoint columns (x + 0 is exact in any order, so
    the sum IS an allgather). Every rank must pass identical training
    and test data; all ranks return the full result — mean/var bitwise
    equal to the serial call, simulation columns to ~1 ulp (<= 1e-8
    gated). A ``LoopbackComm`` reproduces the serial call bitwise."""
    from repro.data.store import is_store

    if tuning is not None:
        from repro.tuning import as_record

        rec = as_record(tuning)
        if n_buckets is None:
            n_buckets = rec.n_buckets
        if stream_chunk is None and rec.stream_chunk:
            stream_chunk = rec.stream_chunk
        if precision is None and rec.precision:
            precision = rec.precision
        if backend == "auto" and rec.backend:
            backend = rec.backend

    tier = None
    if precision is not None:
        from .buckets import acc_dtype, as_policy

        pol = as_policy(precision)
        if pol.tier != "f64":
            tier = pol.tier
            dtype = acc_dtype(tier)  # queries pack at the accumulation width

    # -- Multi-output routing (docs/multioutput.md): a 2-D training y
    # keeps ONE training index / structure pass and scatters per-output
    # columns. (n, 1) squeezes to the single-output program so p=1 stays
    # BITWISE-identical to a 1-D y; p >= 2 coerces the params to the
    # shared-structure MultiOutputParams form.
    from .multioutput import as_multi_params, MultiOutputParams

    n_outputs = 1
    squeeze_back = False
    if not is_store(x_train) and y_train is not None:
        y_train = np.asarray(y_train)
        if y_train.ndim == 2:
            if y_train.shape[1] == 1:
                y_train = y_train[:, 0]
                squeeze_back = True
                if isinstance(params, MultiOutputParams):
                    params = params.output_params(0)
            else:
                n_outputs = y_train.shape[1]
    elif is_store(x_train):
        from repro.data.store import as_store

        y0 = np.asarray(as_store(x_train, y_train).read_slice(0, 1)[1])
        if y0.ndim == 2:
            n_outputs = y0.shape[1]
    if n_outputs > 1:
        params = as_multi_params(params, n_outputs,
                                 np.asarray(params.beta).shape[0])
    elif isinstance(params, MultiOutputParams):
        params = params.output_params(0)

    stats = {"backends": set(), "shapes": set(), "host_s": 0.0,
             "index_s": 0.0, "query_s": 0.0, "fetch_s": []}
    t_mark = time.perf_counter()
    beta = np.asarray(params.beta if beta_struct is None else beta_struct)
    if is_store(x_test):
        n_test = x_test.n_rows
        if chunk_size is None:
            chunk_size = stream_chunk  # bound the test-window reads too
    else:
        x_test = np.asarray(x_test, dtype=np.float64)
        n_test = x_test.shape[0]
    with span("sbv.predict.train_index", stats, "index_s"):
        index = build_train_index(x_train, y_train, beta, m_pred, n_workers,
                                  seed, stream_chunk=stream_chunk)

    out_shape = (n_test,) if n_outputs == 1 else (n_test, n_outputs)
    mean = np.zeros(out_shape)
    var = np.zeros(out_shape)
    sim_mean = np.zeros(out_shape)
    sim_std = np.zeros(out_shape)
    key = jax.random.PRNGKey(seed)

    for ci, packed in iter_query_chunks(
        index, x_test, bs_pred, m_pred, alpha=alpha, seed=seed,
        n_workers=n_workers, chunk_size=chunk_size, dtype=dtype, stats=stats,
    ):
        if n_buckets:
            from .buckets import bucket_mults, bucket_prediction

            bs_mult, m_mult = bucket_mults(backend, precision=tier)
            pieces = bucket_prediction(
                packed, n_buckets=n_buckets, bs_mult=bs_mult, m_mult=m_mult,
            ).buckets
        else:
            pieces = [packed]
        if tier is not None:
            from .buckets import cast_prediction

            pieces = [cast_prediction(p, tier) for p in pieces]
        key_c = jax.random.fold_in(key, ci)
        # host_s is the complement of the chunks' dispatch and fetch, a
        # stretch that resumes the chunk generator: no span can hold it
        t_dev = time.perf_counter()
        stats["host_s"] += t_dev - t_mark
        for bi, piece in enumerate(pieces):
            # Uniform path keeps the pre-bucketing key stream (bit-stable
            # sim draws); buckets get independent per-bucket streams.
            key_b = key_c if not n_buckets else jax.random.fold_in(key_c, bi)
            piece_backend = resolve_backend(backend, params, piece.q_x,
                                            piece.nn_x)
            stats["backends"].add(piece_backend)
            stats["shapes"].add((piece.n_blocks, piece.bs_pred, piece.m_pred))
            if multihost is None:
                mu_b, var_b, sm_b, ss_b = _predict_and_simulate(
                    params, *(jnp.asarray(a) for a in piece.arrays()),
                    key_b, nu=nu, backend=piece_backend, n_sims=n_sims,
                )
                with span("sbv.predict.fetch", stats, "fetch_s", chunk=ci):
                    scatter_packed(piece, (mu_b, mean), (var_b, var),
                                   (sm_b, sim_mean), (ss_b, sim_std))
                continue
            # Multi-host: this rank computes only its contiguous block
            # span; the full-chunk eps stream is sliced inside the jit so
            # the draws match the serial path bitwise.
            from repro.multihost import partition_blocks

            bc_full = piece.n_blocks
            lo, hi = partition_blocks(bc_full, multihost.size)[multihost.rank]
            if hi > lo:
                sub = _slice_prediction_blocks(piece, lo, hi)
                mu_b, var_b, sm_b, ss_b = _predict_and_simulate_span(
                    params, *(jnp.asarray(a) for a in sub.arrays()),
                    key_b, nu=nu, backend=piece_backend, n_sims=n_sims,
                    lo=lo, bc_full=bc_full,
                )
                with span("sbv.predict.fetch", stats, "fetch_s", chunk=ci):
                    scatter_packed(sub, (mu_b, mean), (var_b, var),
                                   (sm_b, sim_mean), (ss_b, sim_std))
        t_mark = time.perf_counter()

    if multihost is not None:
        # Ranks filled disjoint result rows (block spans own disjoint
        # query indices); one allreduce-sum of the zero-initialized
        # columns is an exact allgather — x + 0 in any reduction order.
        merged = multihost.allreduce(np.stack([mean, var, sim_mean, sim_std]))
        mean, var, sim_mean, sim_std = (merged[i] for i in range(4))

    if squeeze_back:
        # (n, 1) input: single-output math, multi-output result shape.
        mean, var, sim_mean, sim_std = (
            a[:, None] for a in (mean, var, sim_mean, sim_std))
    z975 = 1.959963984540054
    stats.update(backends=sorted(stats["backends"]),
                 shapes=sorted(stats["shapes"]))
    return Prediction(
        mean=mean, var=var, sim_mean=sim_mean,
        ci_low=sim_mean - z975 * sim_std, ci_high=sim_mean + z975 * sim_std,
        stats=stats,
    )


def mspe(pred: np.ndarray, truth: np.ndarray) -> float:
    return float(np.mean((pred - truth) ** 2))


def rmspe(pred: np.ndarray, truth: np.ndarray) -> float:
    """Root Mean Squared Percentage Error (paper §6.2)."""
    denom = np.where(np.abs(truth) > 1e-12, truth, 1.0)
    return float(np.sqrt(np.mean(((pred - truth) / denom) ** 2)) * 100.0)
