"""Double-buffered chunk pipeline: host packing overlaps device compute.

The one-shot serving loop is strictly serial per chunk:

    pack k -> dispatch k -> block on k -> scatter k -> pack k+1 -> ...

but packing is host-side numpy (block assembly + filtered kNN + the
``PackedPrediction`` gather) and compute is a jitted device program that
JAX dispatches ASYNCHRONOUSLY — the call returns before the result is
ready. The pipeline exploits that:

* a producer thread runs ``iter_query_chunks`` and keeps up to
  ``prefetch`` packed chunks in a bounded queue (double buffer);
* the consumer dispatches chunk k's device program, then — while the
  device crunches — scatters chunk k-1's now-ready results and the
  producer packs chunk k+1.

Steady state: packing cost and scatter cost disappear behind device
compute; per-chunk wall time approaches max(pack, compute) instead of
pack + compute. Results are BITWISE identical to the synchronous loop
(same ``iter_query_chunks`` protocol, same jitted program, same scatter).

The producer-thread machinery itself lives in ``repro.prefetch``
(``Prefetcher``) — it is shared with the streaming fit's H2D spool
reader, so both overlap paths run one tested implementation.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core.kernels_math import KernelParams
from repro.core.predict import (
    TrainIndex, iter_query_chunks, pack_queries, packed_predict,
    resolve_backend, scatter_packed,
)
from repro.prefetch import Prefetcher

from .telemetry import ServerStats


@dataclass
class PipelineConfig:
    """Knobs of the chunked prediction read path (shared by the sync and
    double-buffered drivers so the two cannot drift)."""

    bs_pred: int = 25
    m_pred: int = 120
    nu: float = 3.5
    alpha: float = 100.0
    backend: str = "ref"      # 'ref' | 'pallas' | 'pallas_tiled' | 'auto'
    dtype: type = np.float64  # float32 for the compiled TPU kernel
    chunk_size: int | None = 4096
    n_workers: int = 1
    prefetch: int = 2         # packed chunks in flight (2 = double buffer)
    n_buckets: int | None = None  # size-bucketed micro-batches (docs/packing.md)
    stream_chunk: int | None = None  # out-of-core train index (docs/streaming.md)
    precision: str | None = None  # ladder tier (docs/precision.md); None = f64

    def __post_init__(self):
        # Normalize the precision knob once: a PrecisionPolicy or tier
        # string collapses to the tier name, f64 collapses to None, and
        # a narrow tier pins dtype to its accumulation width (queries
        # pack at acc; coordinates drop to storage in the chunk split).
        if self.precision is not None:
            from repro.core.buckets import acc_dtype, as_policy

            tier = as_policy(self.precision).tier
            if tier == "f64":
                self.precision = None
            else:
                self.precision = tier
                self.dtype = acc_dtype(tier)


def tuned_config(tuning, **overrides) -> PipelineConfig:
    """Build a ``PipelineConfig`` from a persisted autotuner record
    (TuningRecord / dict / checkpoint path — see ``repro.tuning``),
    with explicit ``overrides`` winning over the record. This is how
    ``serve gp --tuning-record`` starts pre-tuned."""
    from repro.tuning import as_record

    rec = as_record(tuning)
    kw = {}
    if rec.n_buckets:
        kw["n_buckets"] = rec.n_buckets
    if rec.stream_chunk:
        kw["stream_chunk"] = rec.stream_chunk
    if rec.precision:
        kw["precision"] = rec.precision
    if rec.backend:
        kw["backend"] = rec.backend
    kw.update(overrides)
    return PipelineConfig(**kw)


def _n_rows(x_test) -> int:
    """Row count of an in-core array OR a row store."""
    from repro.data.store import is_store

    if is_store(x_test):
        return x_test.n_rows
    return int(np.asarray(x_test).shape[0])


def n_outputs_of(params) -> int:
    """Output count of a parameter set: 1 for ``KernelParams``, ``p`` for
    ``MultiOutputParams`` (core/multioutput.py). The serving layer sizes
    its result buffers off this so multi-output models flow through the
    same chunk engine with ``(n, p)`` mean/var."""
    from repro.core.multioutput import MultiOutputParams

    if isinstance(params, MultiOutputParams):
        return params.n_outputs
    return 1


def _result_zeros(n: int, n_outputs: int) -> tuple[np.ndarray, np.ndarray]:
    shape = (n,) if n_outputs == 1 else (n, n_outputs)
    return np.zeros(shape), np.zeros(shape)


def make_chunk_split(cfg: PipelineConfig):
    """Return ``split(packed) -> [packed_piece, ...]`` — the host-side
    bucketing step of one chunk (the uniform layout is the one-piece
    special case). Pure numpy: the pipelined driver runs it on the
    PRODUCER thread so the slice copies overlap device compute like the
    rest of packing. The precision tier's storage cast also lands here
    (host numpy, overlapped) — queries pack at the accumulation dtype
    and coordinates drop to the storage dtype per piece."""
    tier = cfg.precision
    if not cfg.n_buckets:
        if tier is None:
            return lambda packed: [packed]
        from repro.core.buckets import cast_prediction

        return lambda packed: [cast_prediction(packed, tier)]

    from repro.core.buckets import bucket_mults, bucket_prediction, cast_prediction
    from repro.core.packing import round_up

    # Serving quantizes bucket shapes harder than the one-shot path:
    # ceilings to multiples of 8 and block counts padded to multiples
    # of 8 (masked dummies, inert), so steady-state traffic converges
    # to a bounded set of compile-cache keys just like the uniform
    # `pad_shapes` protocol.
    bs_mult, m_mult = (max(v, 8)
                       for v in bucket_mults(cfg.backend, precision=tier))

    def split(packed):
        pieces = bucket_prediction(packed, n_buckets=cfg.n_buckets,
                                   bs_mult=bs_mult, m_mult=m_mult).buckets
        pieces = [p.pad_to_blocks(round_up(p.n_blocks, 8)) for p in pieces]
        if tier is not None:
            pieces = [cast_prediction(p, tier) for p in pieces]
        return pieces

    return split


def make_chunk_compute(params: KernelParams, cfg: PipelineConfig, mesh=None,
                       axis: str = "workers", stats: ServerStats | None = None):
    """Return ``compute(pieces) -> [(packed_piece, mu, var), ...]`` over
    the (already split) pieces of one chunk; every piece is dispatched
    asynchronously through the jitted predict program. With a mesh, each
    piece's blocks are sharded by owner first (which reorders them —
    hence every piece is returned alongside its outputs so the scatter
    uses matching indices). ``cfg.backend`` is resolved per piece before
    dispatch, and ``stats`` records the concrete backend that ran."""
    def backend_of(piece):
        backend = resolve_backend(cfg.backend, params, piece.q_x, piece.nn_x)
        if stats is not None:
            stats.record_backend(backend)
        return backend

    if mesh is None:
        def compute(pieces):
            out = []
            for piece in pieces:
                mu, var = packed_predict(params, piece, nu=cfg.nu,
                                         backend=backend_of(piece))
                out.append((piece, mu, var))
            return out
        return compute

    from repro.core.distributed import sharded_packed_predict

    def compute(pieces):
        return [
            sharded_packed_predict(params, piece, mesh, axis=axis,
                                   nu=cfg.nu, backend=backend_of(piece))
            for piece in pieces
        ]

    return compute


def _record_pieces(stats: ServerStats | None, pieces) -> None:
    """Per-piece shape + padding-occupancy telemetry for ONE chunk (the
    chunk counter advances once however many bucket pieces it split into).
    One key is recorded PER PIECE, tagged with the piece's precision tier
    — each bucket shape at each dtype is its own compiled program, and
    the affinity router reads this set as the warm-cache signal."""
    if stats is None:
        return
    from repro.core.buckets import dtype_tier, prediction_work

    for i, (piece, _, _) in enumerate(pieces):
        stats.record_chunk_shape(piece.n_blocks, piece.bs_pred, piece.m_pred,
                                 count_chunk=i == 0,
                                 tier=dtype_tier(piece.q_x.dtype))
    stats.record_occupancy(*prediction_work([p for p, _, _ in pieces]))


def _chunks(index: TrainIndex, x_test: np.ndarray, cfg: PipelineConfig,
            seed: int):
    return iter_query_chunks(
        index, x_test, cfg.bs_pred, cfg.m_pred, alpha=cfg.alpha, seed=seed,
        n_workers=cfg.n_workers, chunk_size=cfg.chunk_size, dtype=cfg.dtype,
    )


def request_chunk_bounds(n: int, chunk_size: int | None,
                         bs_pred: int) -> list[tuple[int, int]]:
    """Per-request chunk bounds — the EXACT stepping of
    ``iter_query_chunks`` (``core/predict.py``), extracted so the
    continuous scheduler can enumerate a request's chunks up front.
    Chunk ``ci`` covering rows ``[start, stop)`` must be packed with
    ``pack_scheduled`` below; together they guarantee the scheduler's
    per-request results are those of a per-request ``predict_sbv`` call,
    no matter how admission interleaves requests."""
    step = n if chunk_size is None else max(int(chunk_size), bs_pred)
    return [(start, min(n, start + step)) for start in range(0, n, step)]


def pack_scheduled(index: TrainIndex, cfg: PipelineConfig, item,
                   seed: int = 0):
    """Pack one scheduled (request, chunk) unit with the per-request
    ``iter_query_chunks`` protocol: the request's own array is the test
    set, ``offset``/``seed`` advance within the request. The scheduler
    only ever reorders WHICH of these units runs when — what each unit
    computes is pinned here, which is the whole 1e-12 parity contract."""
    return pack_queries(
        index, item.entry.req.x[item.start:item.stop], cfg.bs_pred,
        cfg.m_pred, alpha=cfg.alpha, seed=seed + item.ci,
        n_workers=cfg.n_workers, offset=item.start,
        pad_shapes=cfg.chunk_size is not None, dtype=cfg.dtype,
    )


def run_chunk_stream(
    params: KernelParams,
    cfg: PipelineConfig,
    jobs,
    emit,
    mesh=None,
    stats: ServerStats | None = None,
) -> None:
    """The double-buffered chunk engine, decoupled from any one request.

    ``jobs`` yields ``(tag, pack_fn)`` pairs; ``pack_fn()`` runs on the
    producer thread (host packing overlaps device compute), the consumer
    dispatches each chunk's device program asynchronously and calls
    ``emit(tag, piece, mu, var)`` one chunk LATER — i.e. while the device
    crunches chunk k, chunk k-1's results are landed. Because ``jobs`` is
    a generator pulled lazily (bounded queue of depth ``cfg.prefetch``),
    every pull is a chunk boundary: a scheduler-backed ``jobs`` can admit
    newly arrived requests and honor cancellations between any two
    chunks.

    A job with ``pack_fn=None`` is a BARRIER: it lands whatever is still
    in flight without computing anything. An endless jobs source (the
    continuous scheduler) MUST emit barriers when it idles, otherwise
    the one-chunk-delayed emit strands the last chunk of a burst until
    the next arrival. ``predict_pipelined`` is a thin wrapper over this
    function, so the drain-mode and continuous-mode paths run one engine
    and cannot drift."""
    split = make_chunk_split(cfg)
    compute = make_chunk_compute(params, cfg, mesh, stats=stats)

    inflight = None  # (tag, [(piece, mu_dev, var_dev), ...]) — not yet forced

    def land(slot):
        tag, pieces = slot
        for piece, mu, vr in pieces:
            emit(tag, piece, mu, vr)

    with Prefetcher(jobs, depth=cfg.prefetch,
                    stage=lambda job: (
                        job[0], None if job[1] is None else split(job[1]())),
                    name="sbv-packer") as staged:
        for tag, host_pieces in staged:
            if host_pieces is None:        # barrier: flush the delayed emit
                if inflight is not None:
                    land(inflight)
                    inflight = None
                continue
            pieces = compute(host_pieces)  # async dispatch, returns early
            _record_pieces(stats, pieces)
            if inflight is not None:
                land(inflight)
            inflight = (tag, pieces)
        if inflight is not None:
            land(inflight)


def predict_synchronous(
    params: KernelParams,
    index: TrainIndex,
    x_test: np.ndarray,
    cfg: PipelineConfig,
    seed: int = 0,
    mesh=None,
    stats: ServerStats | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """The strictly serial chunk loop (pack -> compute -> block -> scatter).

    Kept as the pipeline's correctness twin and benchmark baseline.
    ``x_test`` may be a row store; windows are then read on demand inside
    ``iter_query_chunks``."""
    n_test = _n_rows(x_test)
    mean, var = _result_zeros(n_test, n_outputs_of(params))
    split = make_chunk_split(cfg)
    compute = make_chunk_compute(params, cfg, mesh, stats=stats)
    for _, packed in _chunks(index, x_test, cfg, seed):
        pieces = compute(split(packed))
        _record_pieces(stats, pieces)
        for piece, mu, vr in pieces:
            scatter_packed(piece, (mu, mean), (vr, var))  # forces the result
    return mean, var


def predict_pipelined(
    params: KernelParams,
    index: TrainIndex,
    x_test: np.ndarray,
    cfg: PipelineConfig,
    seed: int = 0,
    mesh=None,
    stats: ServerStats | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Double-buffered chunk loop: identical results, overlapped phases.

    While the device computes chunk k, the host scatters chunk k-1 and the
    producer thread packs chunk k+1 (numpy releases the GIL in the hot
    gathers, so the threads genuinely overlap). With a store-backed
    ``x_test`` the producer also does the window READS off the critical
    path — IO overlaps device compute exactly like packing does."""
    n_test = _n_rows(x_test)
    mean, var = _result_zeros(n_test, n_outputs_of(params))
    if n_test == 0:
        return mean, var

    # The packed chunk is built lazily on the PRODUCER thread: the jobs
    # generator itself is iterated there (Prefetcher contract), so
    # wrapping each already-packed chunk in a thunk keeps the exact
    # pack/split/compute/scatter ordering of the original inline loop —
    # results stay bitwise identical to predict_synchronous.
    jobs = ((ci, (lambda p=packed: p))
            for ci, packed in _chunks(index, x_test, cfg, seed))

    def emit(_tag, piece, mu, vr):
        scatter_packed(piece, (mu, mean), (vr, var))  # forces the result

    run_chunk_stream(params, cfg, jobs, emit, mesh=mesh, stats=stats)
    return mean, var


class SpoolResultSink:
    """Disk-backed per-request result sink (the backpressure story's
    out-of-core leg): each completed chunk's (index, mean, var) triple is
    spooled through ``PackedChunkSpool`` (``data/streaming.py``) with a
    zero device budget, so a bulk sweep's full result never lives in
    server RAM. ``float64`` ``.npz`` round-trips are bit-exact, so
    ``materialize()`` reproduces the in-RAM result identically — the
    parity contract survives the disk hop."""

    def __init__(self, path: str, n_points: int, n_outputs: int = 1):
        from repro.data.streaming import PackedChunkSpool

        self.n_points = int(n_points)
        self.n_outputs = int(n_outputs)
        self._spool = PackedChunkSpool(path, device_budget=0,
                                       device_stage=False)
        self._n_added = 0

    def add(self, piece, mu, var) -> None:
        """Spool one computed chunk piece (masked rows only)."""
        msk = np.asarray(piece.q_mask)
        self._spool.add_arrays(
            {"idx": np.asarray(piece.q_idx)[msk],
             "mean": np.asarray(mu)[msk],
             "var": np.asarray(var)[msk]},
            tag=self._n_added,
        )
        self._n_added += 1

    @property
    def n_chunks(self) -> int:
        return self._n_added

    @property
    def spooled_bytes(self) -> int:
        return self._spool.disk_bytes_total

    def iter_chunks(self):
        """Yield ``(idx, mean, var)`` per spooled piece, in spool order —
        the bounded-memory read path (one piece resident at a time)."""
        for arrays, _tag in self._spool.iter_arrays(prefetch=0):
            yield arrays["idx"], arrays["mean"], arrays["var"]

    def materialize(self) -> tuple[np.ndarray, np.ndarray]:
        """Assemble the full (mean, var) in RAM — convenience for callers
        that decide the result fits after all."""
        mean, var = _result_zeros(self.n_points, self.n_outputs)
        for idx, mu, vr in self.iter_chunks():
            mean[idx] = mu
            var[idx] = vr
        return mean, var

    def cleanup(self) -> None:
        self._spool.cleanup()
