"""Direct unit coverage for ``serving/telemetry.py`` (ISSUE satellite):
percentile snapshot math, occupancy accounting, compile-shape set growth
(per-bucket, tier-tagged keys — the router's affinity signal), and the
counter reset semantics. Unmarked on purpose: pure-python, tier-1."""
import numpy as np

from repro.serving.telemetry import RequestTrace, ServerStats, _percentile


def _trace(n_points, submit, dispatch, done):
    t = RequestTrace(n_points=n_points, t_submit=submit)
    t.t_dispatch = dispatch
    t.t_done = done
    return t


# -- percentile snapshot math ----------------------------------------------


def test_percentile_nearest_rank_math():
    vals = [0.1, 0.2, 0.3, 0.4, 0.5]
    assert _percentile([], 0.99) == 0.0
    assert _percentile(vals, 0.0) == 0.1
    assert _percentile(vals, 0.5) == 0.3
    assert _percentile(vals, 1.0) == 0.5
    # q*(len-1) rounds to the nearest rank and clamps at the top
    assert _percentile(vals, 0.95) == 0.5
    assert _percentile([7.0], 0.99) == 7.0


def test_latency_percentiles_and_class_windows():
    stats = ServerStats(window=8)
    lat = [0.010, 0.020, 0.030, 0.040, 0.100]
    for i, el in enumerate(lat):
        stats.record_request(_trace(5, t0 := float(i), t0 + 0.001, t0 + el),
                             slo="interactive" if i < 4 else "bulk")
    s = stats.summary()
    assert s["n_requests"] == 5
    assert s["n_points"] == 25
    assert abs(s["latency_p50_s"] - 0.030) < 1e-12
    assert abs(s["latency_p99_s"] - 0.100) < 1e-12
    assert abs(s["queue_wait_p50_s"] - 0.001) < 1e-12
    assert s["by_class"]["interactive"]["n"] == 4
    assert s["by_class"]["bulk"]["n"] == 1
    assert abs(s["by_class"]["bulk"]["latency_p99_s"] - 0.100) < 1e-12


def test_window_bounds_percentile_samples_not_counters():
    stats = ServerStats(window=4)
    for i in range(10):
        stats.record_request(_trace(1, 0.0, 0.0, float(i + 1)))
    s = stats.summary()
    assert s["n_requests"] == 10           # counters are lifetime-exact
    assert len(stats.latencies_s) == 4     # samples are windowed
    assert s["latency_p50_s"] >= 8.0       # only the newest 4 remain


# -- occupancy accounting --------------------------------------------------


def test_occupancy_accumulates_ratio_terms():
    stats = ServerStats()
    assert stats.summary()["padding_occupancy"] == 1.0  # no data = no waste
    stats.record_occupancy(30.0, 60.0)
    stats.record_occupancy(10.0, 20.0)
    assert abs(stats.summary()["padding_occupancy"] - 0.5) < 1e-12
    assert stats.true_flops == 40.0
    assert stats.padded_flops == 80.0


# -- compile-shape set growth (the affinity signal) ------------------------


def test_compiled_shapes_one_key_per_bucket_piece():
    """Regression for the bucketed-dispatch undercount: every bucket
    piece records its own key, and n_chunks still counts chunks."""
    stats = ServerStats()
    # one chunk that split into three bucket pieces
    stats.record_chunk_shape(8, 16, 32, count_chunk=True, tier="f64")
    stats.record_chunk_shape(8, 8, 64, count_chunk=False, tier="f64")
    stats.record_chunk_shape(16, 24, 96, count_chunk=False, tier="f64")
    assert stats.n_chunks == 1
    assert stats.summary()["n_compiled_shapes"] == 3


def test_compiled_shapes_key_includes_precision_tier():
    """Same (bc, bs, m) at two tiers is two compiled programs — and two
    keys."""
    stats = ServerStats()
    stats.record_chunk_shape(8, 16, 32, tier="f64")
    stats.record_chunk_shape(8, 16, 32, tier="f32")
    stats.record_chunk_shape(8, 16, 32, tier="f32")  # dedup within a tier
    assert stats.compiled_shape_keys() == {(8, 16, 32, "f64"),
                                           (8, 16, 32, "f32")}
    assert stats.summary()["n_compiled_shapes"] == 2


def test_pipeline_records_tier_tagged_keys_per_piece():
    """End-to-end: the chunk split's pieces land tier-tagged keys derived
    from their actual packed dtypes."""
    from repro.core.buckets import dtype_tier

    assert dtype_tier(np.float64) == "f64"
    assert dtype_tier(np.float32) == "f32"
    import jax.numpy as jnp

    assert dtype_tier(jnp.bfloat16) == "bf16"


def test_compiled_shape_keys_returns_a_snapshot():
    stats = ServerStats()
    stats.record_chunk_shape(8, 16, 32)
    snap = stats.compiled_shape_keys()
    stats.record_chunk_shape(16, 16, 32)
    assert len(snap) == 1
    assert len(stats.compiled_shape_keys()) == 2


def test_backends_recorded_and_cleared_on_reset():
    stats = ServerStats()
    stats.record_backend("pallas")
    stats.record_backend("ref")
    stats.record_backend("pallas")
    assert stats.summary()["backends"] == ["pallas", "ref"]
    stats.reset()
    assert stats.summary()["backends"] == []


# -- reset semantics -------------------------------------------------------


def test_reset_zeroes_counters_and_windows():
    stats = ServerStats()
    stats.record_request(_trace(10, 0.0, 0.1, 0.2), slo="interactive")
    stats.record_batch(2, 20)
    stats.record_chunk_shape(8, 16, 32, tier="f32")
    stats.record_occupancy(1.0, 2.0)
    stats.record_cancelled()
    stats.record_preemption()
    stats.record_rejected()
    stats.record_queue_depth(64)
    t0 = stats.t_start
    stats.reset()
    s = stats.summary()
    for k in ("n_requests", "n_points", "n_batches", "n_chunks",
              "n_cancelled", "n_preempted", "n_rejected",
              "queue_depth_points", "queue_depth_peak"):
        assert s[k] == 0, k
    assert s["latency_p50_s"] == 0.0
    assert s["by_class"] == {}
    assert s["padding_occupancy"] == 1.0
    assert stats.t_start >= t0  # qps clock restarted


def test_reset_preserves_compiled_shapes_by_default():
    """The process jit cache survives a stats reset, so the shape keys do
    too — unless explicitly cleared (fresh-server accounting)."""
    stats = ServerStats()
    stats.record_chunk_shape(8, 16, 32, tier="f64")
    stats.reset()
    assert stats.summary()["n_compiled_shapes"] == 1
    stats.reset(preserve_shapes=False)
    assert stats.summary()["n_compiled_shapes"] == 0
