"""Serving equivalence: the persistent server is a rearrangement of the
packed prediction pipeline, not a new numerical path.

Contracts (ISSUE satellite):
(a) micro-batched multi-request results == single-call ``predict_sbv`` on
    the concatenated queries (coalescing is concatenation);
(b) double-buffered pipeline == synchronous chunk loop, bitwise;
(c) tile-padded (8x128) kernel output == untiled ref to <= 1e-5;
(d) the max-points policy splits oversized windows into multiple batches
    and every request still gets exact-GP-quality answers;
(e) latency smoke: a batch is answered under a generous wall-clock bound
    (the CI serving gate).
"""
import threading

import numpy as np
import pytest

from repro.core import exact_predict, packed_predict, predict_sbv
from repro.core.packing import tile_predict_shapes
from repro.core.predict import build_train_index, pack_queries
from repro.data.gp_sim import paper_synthetic
from repro.serving import (
    BatchingPolicy, GPServer, GPServerConfig, PipelineConfig,
    predict_pipelined, predict_synchronous,
)

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def problem():
    x, y, params = paper_synthetic(seed=0, n=400, d=4)
    rng = np.random.default_rng(7)
    requests = [rng.uniform(size=(n, 4)) for n in (33, 5, 80, 1, 41)]
    return params, x, y, requests


def test_microbatched_requests_match_single_predict_sbv(problem):
    params, x, y, requests = problem
    concat = np.concatenate(requests, axis=0)
    cfg = GPServerConfig(
        pipeline=PipelineConfig(bs_pred=8, m_pred=32, chunk_size=64),
        policy=BatchingPolicy(max_points=100_000, max_wait_s=30.0),
        seed=3,
    )
    server = GPServer(params, x, y, cfg)
    with server:
        futs = [server.submit(r) for r in requests]
        server.flush()  # everything queued -> ONE micro-batch
        results = [f.result(timeout=300) for f in futs]

    ref = predict_sbv(params, x, y, concat, bs_pred=8, m_pred=32, seed=3,
                      chunk_size=64, n_sims=2)
    got_mean = np.concatenate([r.mean for r in results])
    got_var = np.concatenate([r.var for r in results])
    np.testing.assert_allclose(got_mean, ref.mean, rtol=0, atol=1e-12)
    np.testing.assert_allclose(got_var, ref.var, rtol=0, atol=1e-12)

    stats = server.stats.summary()
    assert stats["n_batches"] == 1
    assert stats["n_requests"] == len(requests)
    assert stats["n_points"] == concat.shape[0]


def test_pipelined_equals_synchronous(problem):
    params, x, y, requests = problem
    xt = np.concatenate(requests, axis=0)
    index = build_train_index(x, y, np.asarray(params.beta), 32, seed=1)
    cfg = PipelineConfig(bs_pred=8, m_pred=32, chunk_size=48)
    m_sync, v_sync = predict_synchronous(params, index, xt, cfg, seed=1)
    m_pipe, v_pipe = predict_pipelined(params, index, xt, cfg, seed=1)
    np.testing.assert_array_equal(m_pipe, m_sync)
    np.testing.assert_array_equal(v_pipe, v_sync)


def test_tiled_kernel_matches_untiled_ref(problem):
    params, x, y, requests = problem
    xt = np.concatenate(requests, axis=0)
    index = build_train_index(x, y, np.asarray(params.beta), 24, seed=2)
    packed = pack_queries(index, xt, bs_pred=8, m_pred=24, seed=2)

    mu_r, var_r = packed_predict(params, packed, backend="ref")

    # In-jit tiling (the compiled TPU entry point, interpret mode here).
    mu_t, var_t = packed_predict(params, packed, backend="pallas_tiled")
    assert np.asarray(mu_t).shape == packed.q_mask.shape  # sliced back
    np.testing.assert_allclose(np.asarray(mu_t), np.asarray(mu_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(var_t), np.asarray(var_r),
                               rtol=1e-5, atol=1e-5)

    # Host-side tile padding: lane-aligned shapes, padded slots inert.
    tiled = packed.pad_to_tiles()
    bs_t, m_t = tile_predict_shapes(packed.bs_pred, packed.m_pred)
    assert (tiled.bs_pred, tiled.m_pred) == (bs_t, m_t)
    assert bs_t % 8 == 0 and m_t % 128 == 0
    assert tiled.n_queries == packed.n_queries
    mu_h, var_h = packed_predict(params, tiled, backend="pallas")
    msk = packed.q_mask
    np.testing.assert_allclose(
        np.asarray(mu_h)[:, : packed.bs_pred][msk], np.asarray(mu_r)[msk],
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(var_h)[:, : packed.bs_pred][msk], np.asarray(var_r)[msk],
        rtol=1e-5, atol=1e-5)


def test_max_points_policy_splits_batches_and_stays_exact():
    """Oversized windows split into several micro-batches; every request
    still matches the exact GP (m_pred >= n_train makes the block
    conditional THE exact conditional, so correctness is checkable
    per-request regardless of how the batcher grouped them)."""
    x, y, params = paper_synthetic(seed=4, n=60, d=3)
    rng = np.random.default_rng(5)
    requests = [rng.uniform(size=(n, 3)) for n in (20, 20, 20, 20)]
    cfg = GPServerConfig(
        pipeline=PipelineConfig(bs_pred=8, m_pred=80, chunk_size=None),
        policy=BatchingPolicy(max_points=40, max_wait_s=30.0),
        seed=4,
    )
    server = GPServer(params, x, y, cfg)
    with server:
        futs = [server.submit(r) for r in requests]
        server.flush()
        results = [f.result(timeout=300) for f in futs]
    assert server.stats.summary()["n_batches"] >= 2
    assert server.stats.summary()["backends"] == ["ref"]
    for req, res in zip(requests, results):
        em, ev = exact_predict(params, x, y, req)
        np.testing.assert_allclose(res.mean, np.asarray(em), atol=1e-4, rtol=0)
        np.testing.assert_allclose(res.var, np.asarray(ev), atol=1e-4, rtol=0)


def test_stop_timeout_fails_queued_futures():
    """Regression: stop() used to raise TimeoutError while still-queued
    requests kept their futures pending forever. Now every queued future
    is failed BEFORE the TimeoutError propagates, so no client blocks on
    a request the wedged dispatcher will never pick up."""
    x, y, params = paper_synthetic(seed=9, n=40, d=2)
    cfg = GPServerConfig(
        pipeline=PipelineConfig(bs_pred=4, m_pred=16, chunk_size=None),
        # max_points=1: every submit trips the window -> one request per
        # batch, so the second submit stays queued behind the wedged first.
        policy=BatchingPolicy(max_points=1, max_wait_s=30.0),
        seed=9,
    )
    server = GPServer(params, x, y, cfg)
    entered, release = threading.Event(), threading.Event()

    def wedged_process(batch):
        entered.set()
        release.wait(timeout=60.0)
        for req in batch:
            if req.future.set_running_or_notify_cancel():
                req.future.set_result("late")

    server._process = wedged_process
    server.start()
    rng = np.random.default_rng(0)
    fut1 = server.submit(rng.uniform(size=(2, 2)))
    assert entered.wait(timeout=30.0)          # dispatcher wedged on req 1
    fut2 = server.submit(rng.uniform(size=(2, 2)))

    with pytest.raises(TimeoutError):
        server.stop(timeout_s=0.2)
    # The queued future fails promptly instead of hanging forever.
    with pytest.raises(RuntimeError, match="timed out"):
        fut2.result(timeout=5.0)

    release.set()                              # un-wedge; clean shutdown
    server.stop(timeout_s=60.0)
    assert fut1.result(timeout=5.0) == "late"


def test_latency_smoke_and_telemetry(problem):
    """CI serving gate: a warmed server answers a batch well under a
    generous wall-clock bound and reports sane telemetry."""
    params, x, y, requests = problem
    cfg = GPServerConfig(
        pipeline=PipelineConfig(bs_pred=8, m_pred=32, chunk_size=64),
        policy=BatchingPolicy(max_points=4096, max_wait_s=0.005),
        seed=6,
    )
    server = GPServer(params, x, y, cfg)
    with server:
        server.warmup()
        res = server.predict(requests[0], timeout_s=60.0)
    assert res.latency_s < 60.0
    assert res.queue_wait_s <= res.latency_s
    assert np.all(np.isfinite(res.mean)) and np.all(res.var > 0)
    stats = server.stats.summary()
    assert stats["n_requests"] == 2  # warmup + request
    assert stats["n_compiled_shapes"] >= 1
    assert stats["latency_p95_s"] > 0


def test_adaptive_window_scales_with_interarrival_ema():
    """Deterministic fake-clock check of the adaptive batching window:
    dense arrivals shrink the wait toward window_factor * EMA; sparse
    arrivals clamp it back at max_wait_s; adaptive=False is inert."""
    from concurrent.futures import Future

    from repro.serving.batching import MicroBatcher, PredictRequest

    t = [0.0]
    clock = lambda: t[0]
    mk = lambda: PredictRequest(x=np.zeros((1, 2)), future=Future())

    pol = BatchingPolicy(max_wait_s=0.010, adaptive=True,
                         window_factor=4.0, ema_alpha=0.5)
    b = MicroBatcher(pol, clock=clock)
    # no observations yet -> full window
    assert b.effective_wait_s() == pytest.approx(0.010)
    b.put(mk())  # first arrival: still no gap sample
    assert b.effective_wait_s() == pytest.approx(0.010)

    # dense traffic: 1ms gaps -> EMA=1ms -> window = 4ms < max_wait
    for _ in range(6):
        t[0] += 0.001
        b.put(mk())
    assert b.effective_wait_s() == pytest.approx(0.004, rel=1e-6)

    # one sparse gap (1s) with alpha=0.5 blows the EMA past the cap
    t[0] += 1.0
    b.put(mk())
    assert b.effective_wait_s() == pytest.approx(0.010)

    # exact EMA arithmetic: gaps 2ms then 4ms from a fresh batcher
    b2 = MicroBatcher(pol, clock=clock)
    b2.put(mk())
    t[0] += 0.002
    b2.put(mk())   # EMA = 2ms
    t[0] += 0.004
    b2.put(mk())   # EMA = 0.5*2 + 0.5*4 = 3ms -> window = min(10, 12) ms
    assert b2.effective_wait_s() == pytest.approx(0.010)
    assert b2._ema_gap_s == pytest.approx(0.003)

    # adaptive off: window pinned at max_wait_s regardless of traffic
    b3 = MicroBatcher(BatchingPolicy(max_wait_s=0.010, adaptive=False),
                      clock=clock)
    for _ in range(5):
        t[0] += 0.0001
        b3.put(mk())
    assert b3.effective_wait_s() == pytest.approx(0.010)


def test_adaptive_deadline_drives_next_batch():
    """next_batch's deadline runs on the batcher's (injectable) clock:
    once the fake clock passes t_arrival + effective_wait, the dispatcher
    returns the partial batch immediately instead of sleeping out
    max_wait_s in real time."""
    import time
    from concurrent.futures import Future

    from repro.serving.batching import MicroBatcher, PredictRequest

    t = [0.0]
    b = MicroBatcher(
        BatchingPolicy(max_points=10_000, max_wait_s=30.0, adaptive=True,
                       window_factor=2.0, ema_alpha=1.0),
        clock=lambda: t[0],
    )
    # Establish a 1ms-gap EMA -> window = 2ms (vs the 30s hard cap).
    for _ in range(3):
        b.put(PredictRequest(x=np.zeros((1, 2)), future=Future()))
        t[0] += 0.001
    assert b.effective_wait_s() == pytest.approx(0.002)
    # Clock is now past every arrival's deadline: next_batch must drain
    # the queue and return without waiting out the 30s cap in real time.
    t[0] += 1.0
    t0 = time.monotonic()
    batch = b.next_batch()
    assert len(batch) == 3
    assert time.monotonic() - t0 < 5.0  # returned immediately, not in 30s


def test_bucketed_serving_matches_uniform(problem):
    """PipelineConfig(n_buckets=K): bucketed micro-batches reproduce the
    uniform path to 1e-10 and report padding occupancy in (0, 1]."""
    params, x, y, requests = problem
    from repro.core.predict import build_train_index
    from repro.serving.telemetry import ServerStats

    index = build_train_index(x, y, np.asarray(params.beta), 32, seed=0)
    xt = np.concatenate(requests, axis=0)
    cfg_u = PipelineConfig(bs_pred=8, m_pred=32, chunk_size=64)
    cfg_b = PipelineConfig(bs_pred=8, m_pred=32, chunk_size=64, n_buckets=4)
    stats = ServerStats()
    m_u, v_u = predict_synchronous(params, index, xt, cfg_u, seed=0)
    m_b, v_b = predict_synchronous(params, index, xt, cfg_b, seed=0,
                                   stats=stats)
    np.testing.assert_allclose(m_b, m_u, atol=1e-10, rtol=0)
    np.testing.assert_allclose(v_b, v_u, atol=1e-10, rtol=0)
    # double-buffered bucketed == sync bucketed, bitwise
    m_p, v_p = predict_pipelined(params, index, xt, cfg_b, seed=0)
    assert np.array_equal(m_p, m_b) and np.array_equal(v_p, v_b)
    occ = stats.summary()["padding_occupancy"]
    assert 0.0 < occ <= 1.0
