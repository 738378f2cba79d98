"""Out-of-core store + streaming construction invariants (docs/streaming.md).

The load-bearing contract: WHERE the rows live must be invisible to the
math. A disk-backed ``ArrayStore`` and an in-RAM ``MemoryStore`` holding
the same rows must produce bit-identical structures, fits and
predictions (the IO layer adds zero numerical change), and the chunked
likelihood dispatch must match the monolithic in-core program to 1e-10
(only float summation ORDER differs). The same invisibility extends to
the inner-loop memory TIERS: a piece served from the device-resident
spool cache, through the prefetched H2D pipeline, or from cold disk
must produce the identical fit bitwise. Plus: store round-trip/manifest
integrity, chunk-iterator boundary cases, single-batch mini-batch
k-means == Lloyd, a bounded-RSS 200k-point smoke fit, and the
subprocess 8-device distributed streaming fit.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.fit import fit_sbv
from repro.core.pipeline import SBVConfig
from repro.core.predict import predict_sbv
from repro.data.gp_sim import paper_synthetic
from repro.data.store import ArrayStore, MemoryStore, as_store, is_store

pytestmark = pytest.mark.streaming


@pytest.fixture(scope="module")
def small():
    x, y, params = paper_synthetic(seed=0, n=1500, d=4)
    return x, y, params


def _params_equal(a, b):
    return max(
        np.abs(np.asarray(getattr(a, f)) - np.asarray(getattr(b, f))).max()
        for f in ("log_sigma2", "log_beta", "log_nugget")
    )


# -- store round-trip and manifest integrity ------------------------------


def test_store_roundtrip_and_gather(tmp_path, small):
    x, y, _ = small
    st = ArrayStore.from_arrays(str(tmp_path / "s"), x, y, shard_rows=400)
    assert (st.n_rows, st.d, st.n_shards) == (1500, 4, 4)
    st.verify()
    xa, ya = st.read_all()
    assert np.array_equal(xa, x) and np.array_equal(ya, y)
    # Order-preserving gather across shards, duplicates included.
    idx = np.array([1499, 0, 401, 400, 399, 401])
    xg, yg = st.read_rows(idx)
    assert np.array_equal(xg, x[idx]) and np.array_equal(yg, y[idx])
    with pytest.raises(IndexError):
        st.read_rows(np.array([1500]))
    assert is_store(st) and is_store(MemoryStore(x, y)) and not is_store(x)
    assert as_store(st) is st


def test_writer_appends_span_shards(tmp_path, small):
    x, y, _ = small
    with ArrayStore.create(str(tmp_path / "w"), 4, shard_rows=512) as w:
        for a in range(0, 1500, 613):  # deliberately shard-misaligned
            w.append(x[a:a + 613], y[a:a + 613])
    st = ArrayStore(str(tmp_path / "w"))
    assert st.n_rows == 1500 and st.n_shards == 3
    xa, ya = st.read_all()
    assert np.array_equal(xa, x) and np.array_equal(ya, y)


def test_manifest_integrity_checks(tmp_path, small):
    x, y, _ = small
    path = str(tmp_path / "m")
    ArrayStore.from_arrays(path, x, y, shard_rows=400)
    with open(os.path.join(path, "manifest.json")) as f:
        m = json.load(f)
    m["n_rows"] = 9999
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(m, f)
    with pytest.raises(ValueError, match="corrupt manifest"):
        ArrayStore(path)
    m["n_rows"] = 1500
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(m, f)
    os.remove(os.path.join(path, "x_00002.npy"))
    with pytest.raises(FileNotFoundError, match="missing shards"):
        ArrayStore(path)
    with pytest.raises(FileNotFoundError):
        ArrayStore(str(tmp_path / "not-a-store"))


def test_iter_chunks_boundaries(tmp_path, small):
    x, y, _ = small
    st = ArrayStore.from_arrays(str(tmp_path / "c"), x, y, shard_rows=400)
    # Ragged last window, windows spanning shard boundaries.
    ws = list(st.iter_chunks(700))
    assert [w[0] for w in ws] == [0, 700, 1400]
    assert [w[1].shape[0] for w in ws] == [700, 700, 100]
    assert np.array_equal(np.concatenate([w[1] for w in ws]), x)
    # Degenerate single-chunk case (rows >= n).
    ws = list(st.iter_chunks(10_000))
    assert len(ws) == 1 and ws[0][1].shape[0] == 1500
    # Default window = manifest shard size.
    assert [w[1].shape[0] for w in st.iter_chunks()] == [400, 400, 400, 300]
    # MemoryStore speaks the same protocol (same windows, same rows).
    ws_d = list(st.iter_chunks(700))
    ws_m = list(MemoryStore(x, y).iter_chunks(700))
    assert len(ws_d) == len(ws_m)
    assert all(a[0] == b[0] and np.array_equal(a[1], b[1])
               for a, b in zip(ws_d, ws_m))


# -- streaming k-means ----------------------------------------------------


def test_single_batch_streaming_kmeans_is_lloyd(small):
    """With batch_rows >= n and per-epoch count resets, every epoch must
    reduce exactly to a Lloyd iteration (same partition of the data).
    The reference below re-implements Lloyd's M-step independently but
    shares the tiled assignment helper, so the claim under test is the
    mini-batch update algebra (per-epoch resets), not f32 tie-breaking."""
    from repro.data.streaming import _assign_chunk, streaming_kmeans_blocks

    x, y, _ = small
    beta = np.full(4, 0.5)
    k, epochs, seed = 24, 3, 3
    blocks, radii, vol = streaming_kmeans_blocks(
        MemoryStore(x, y), beta, k, seed=seed, epochs=epochs,
        batch_rows=10_000,
    )

    # Reference Lloyd with the identical init draw.
    xs = x / beta
    rng = np.random.default_rng(seed)
    centers = xs[rng.choice(len(xs), size=k, replace=False)]
    for _ in range(epochs):
        lab = _assign_chunk(xs, centers, np.sum(centers * centers, 1))
        for j in range(k):
            if np.any(lab == j):
                centers[j] = xs[lab == j].mean(axis=0)
    lab = _assign_chunk(xs, centers, np.sum(centers * centers, 1))

    # Same partition up to the coordinate relabeling the streaming path
    # applies for gather locality.
    for j in np.unique(lab):
        assert np.unique(blocks.labels[lab == j]).size == 1
    assert blocks.n_blocks == np.unique(lab).size
    # Radii bound every member distance to its final center.
    for b in range(blocks.n_blocks):
        mb = blocks.members[b]
        r = np.sqrt(np.max(np.sum((xs[mb] - blocks.centers[b]) ** 2, axis=1)))
        assert r <= radii[b] + 1e-12
    assert vol > 0


def test_streaming_kmeans_disk_equals_memory(tmp_path, small):
    from repro.data.streaming import streaming_kmeans_blocks

    x, y, _ = small
    st = ArrayStore.from_arrays(str(tmp_path / "k"), x, y, shard_rows=317)
    beta = np.asarray([0.05, 0.05, 5.0, 5.0])
    a = streaming_kmeans_blocks(MemoryStore(x, y), beta, 30, seed=1,
                                batch_rows=256)
    b = streaming_kmeans_blocks(st, beta, 30, seed=1, batch_rows=256)
    assert np.array_equal(a[0].labels, b[0].labels)
    assert np.array_equal(a[0].order, b[0].order)
    assert np.array_equal(a[0].centers, b[0].centers)
    assert np.array_equal(a[1], b[1]) and a[2] == b[2]


# -- fit parity ------------------------------------------------------------


def test_streaming_fit_store_equals_incore(tmp_path, small):
    """Disk-backed == RAM-backed, bit for bit (covers the spool round-trip
    and the gather/remap packing)."""
    x, y, _ = small
    st = ArrayStore.from_arrays(str(tmp_path / "f"), x, y, shard_rows=412)
    cfg = SBVConfig(n_blocks=24, m=20, seed=0)
    kw = dict(inner_steps=8, outer_rounds=2, stream_chunk=400)
    r_disk = fit_sbv(st, None, cfg, **kw)
    r_mem = fit_sbv(x, y, cfg, **kw)
    assert _params_equal(r_disk.params, r_mem.params) == 0.0
    assert [h[2] for h in r_disk.history] == [h[2] for h in r_mem.history]
    assert r_disk.stream_stats["n_chunks"] > 1


def test_stream_stats_split_the_structure_and_time_every_step(small):
    """The structure's stages lie inside ``struct_time_s``, the NNS
    counters are filled, and every inner step of every round has one
    ``step_times_s`` entry."""
    x, y, _ = small
    cfg = SBVConfig(n_blocks=24, m=20, seed=0)
    st = fit_sbv(x, y, cfg, inner_steps=3, outer_rounds=2,
                 stream_chunk=400).stream_stats
    parts = [st["struct_kmeans_s"], st["struct_nns_s"], st["struct_pack_s"]]
    assert all(p > 0 for p in parts)
    assert sum(parts) <= st["struct_time_s"]
    assert st["nns_scored"] >= st["nns_kept"] > 0
    assert len(st["step_times_s"]) == 6
    assert sum(st["step_times_s"]) <= st["inner_time_s"]


def test_chunked_fit_matches_monolithic_1e10(small):
    """Chunked grad accumulation vs the single-chunk program: identical
    structure (struct batch is decoupled from stream_chunk), so only the
    float summation order differs."""
    x, y, _ = small
    cfg = SBVConfig(n_blocks=24, m=20, seed=0)
    r_one = fit_sbv(x, y, cfg, inner_steps=10, outer_rounds=2,
                    stream_chunk=100_000)
    r_many = fit_sbv(x, y, cfg, inner_steps=10, outer_rounds=2,
                     stream_chunk=300)
    assert r_many.stream_stats["n_chunks"] > 3
    assert _params_equal(r_one.params, r_many.params) <= 1e-10


def test_bucketed_streaming_fit_matches_uniform(small):
    """Per-chunk bucketed dispatch (docs/packing.md) rides the streaming
    path unchanged: identity padding keeps per-block terms exact."""
    x, y, _ = small
    cfg = SBVConfig(n_blocks=24, m=20, seed=0)
    r_u = fit_sbv(x, y, cfg, inner_steps=6, outer_rounds=1, stream_chunk=400)
    r_b = fit_sbv(x, y, cfg, inner_steps=6, outer_rounds=1, stream_chunk=400,
                  n_buckets=3)
    assert _params_equal(r_u.params, r_b.params) <= 1e-10


# -- inner-loop memory tiers (device cache / prefetch / disk) --------------


def test_device_cache_matches_disk_spool_bitwise(small):
    """Pieces held in the device-resident spool tier across all inner
    steps must produce the identical fit to pieces re-read from the disk
    spool every step — the tier is pure residency, zero numerics."""
    x, y, _ = small
    cfg = SBVConfig(n_blocks=24, m=20, seed=0)
    kw = dict(inner_steps=6, outer_rounds=2, stream_chunk=300)
    r_dev = fit_sbv(x, y, cfg, device_cache=1 << 30, prefetch=0, **kw)
    r_disk = fit_sbv(x, y, cfg, device_cache=0, prefetch=0, **kw)
    st_dev, st_disk = r_dev.stream_stats, r_disk.stream_stats
    assert st_dev["n_pieces"] > 1
    assert st_dev["device_cached_pieces"] == st_dev["n_pieces"]
    assert st_dev["h2d_bytes_per_step"] == 0
    assert st_disk["device_cached_pieces"] == 0
    assert st_disk["h2d_bytes_per_step"] > 0
    assert _params_equal(r_dev.params, r_disk.params) == 0.0
    assert [h[2] for h in r_dev.history] == [h[2] for h in r_disk.history]


def test_prefetched_pipeline_matches_sync_bitwise(small):
    """The H2D producer thread stages disk pieces ahead of the device but
    preserves accumulation order — prefetched == synchronous, bitwise."""
    x, y, _ = small
    cfg = SBVConfig(n_blocks=24, m=20, seed=0)
    kw = dict(inner_steps=6, outer_rounds=2, stream_chunk=300, device_cache=0)
    r_pre = fit_sbv(x, y, cfg, prefetch=2, **kw)
    r_sync = fit_sbv(x, y, cfg, prefetch=0, **kw)
    assert r_pre.stream_stats["n_pieces"] > 1
    assert _params_equal(r_pre.params, r_sync.params) == 0.0
    assert [h[2] for h in r_pre.history] == [h[2] for h in r_sync.history]


def test_mixed_tier_spool_matches_disk_bitwise(small):
    """A budget that fits only part of the round: leading pieces stay on
    device, the overflow spools to disk — same fit, bitwise."""
    x, y, _ = small
    cfg = SBVConfig(n_blocks=24, m=20, seed=0)
    kw = dict(inner_steps=4, outer_rounds=1, stream_chunk=300)
    probe = fit_sbv(x, y, cfg, inner_steps=1, outer_rounds=1,
                    stream_chunk=300, device_cache=0)
    budget = probe.stream_stats["spool_bytes"] // 2
    r_mix = fit_sbv(x, y, cfg, device_cache=budget, **kw)
    r_disk = fit_sbv(x, y, cfg, device_cache=0, **kw)
    st = r_mix.stream_stats
    assert 0 < st["device_cached_pieces"] < st["n_pieces"]
    assert 0 < st["h2d_bytes_per_step"] < st["spool_bytes"]
    assert _params_equal(r_mix.params, r_disk.params) == 0.0


def test_streaming_auto_backend_resolves(small):
    """backend='auto' no longer raises: each spooled piece resolves
    through kernels.ops.select_backend; at these small shapes that is
    'ref', so the fit must match the explicit-ref fit bitwise."""
    from repro.kernels.ops import select_backend

    x, y, _ = small
    cfg = SBVConfig(n_blocks=48, m=10, seed=0)
    kw = dict(inner_steps=4, outer_rounds=1, stream_chunk=300)
    r_auto = fit_sbv(x, y, cfg, backend="auto", **kw)
    r_ref = fit_sbv(x, y, cfg, backend="ref", **kw)
    bs_max = r_auto.stream_stats["bs_max"]
    assert select_backend(bs_max, cfg.m, kind="loglik") == "ref"
    assert _params_equal(r_auto.params, r_ref.params) == 0.0


def test_chunk_grad_fn_cached_across_rounds():
    """The jitted chunk-grad wrapper is shared across outer rounds (and
    fits): same key -> same wrapper object -> one jit compile cache."""
    from repro.core.fit import _chunk_grad_fn

    assert _chunk_grad_fn(3.5, "ref", 1234) is _chunk_grad_fn(3.5, "ref", 1234)
    assert _chunk_grad_fn(3.5, "ref", 1234) is not _chunk_grad_fn(3.5, "ref", 999)
    assert _chunk_grad_fn(3.5, "ref", 1234) is not _chunk_grad_fn(3.5, "pallas", 1234)


def test_prefetcher_propagates_errors_and_closes():
    """The shared double-buffer primitive surfaces producer exceptions in
    the consumer and joins its thread on early exit."""
    import threading

    from repro.prefetch import Prefetcher

    def boom():
        yield 1
        raise RuntimeError("producer failed")

    with Prefetcher(boom(), depth=1) as pf:
        it = iter(pf)
        assert next(it) == 1
        with pytest.raises(RuntimeError, match="producer failed"):
            next(it)

    # early close unblocks a producer stuck on a full queue
    pf = Prefetcher(iter(range(100)), depth=1, stage=lambda i: i * 2)
    got = [next(iter(pf))]
    pf.close()
    assert got == [0]
    assert not any(t.name == "prefetch" and t.is_alive()
                   for t in threading.enumerate())


# -- distributed streaming (subprocess, 8 virtual devices) -----------------


STREAM_DIST_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.core.fit import fit_sbv
    from repro.core.pipeline import SBVConfig
    from repro.data.gp_sim import paper_synthetic

    assert jax.device_count() == 8, jax.device_count()
    mesh = jax.make_mesh((8,), ("workers",))

    x, y, _ = paper_synthetic(seed=0, n=600, d=4)
    cfg = SBVConfig(n_blocks=24, m=16, n_workers=8, seed=0)
    kw = dict(inner_steps=8, outer_rounds=2, stream_chunk=200)

    def dparams(a, b):
        return max(np.abs(np.asarray(getattr(a.params, f)) -
                          np.asarray(getattr(b.params, f))).max()
                   for f in ("log_sigma2", "log_beta", "log_nugget"))

    r_ser = fit_sbv(x, y, cfg, **kw)
    r_dist = fit_sbv(x, y, cfg, distributed=(mesh, "workers"), **kw)
    d = dparams(r_ser, r_dist)
    assert d <= 1e-8, d
    assert r_dist.stream_stats["n_shards"] == 8
    assert r_dist.stream_stats["n_pieces"] > 1

    # the H2D pipeline stages sharded pieces too: disk tier + prefetch
    # under the mesh == device-cached under the mesh, bitwise
    r_disk = fit_sbv(x, y, cfg, distributed=(mesh, "workers"),
                     device_cache=0, prefetch=2, **kw)
    assert dparams(r_dist, r_disk) == 0.0

    losses = [h[2] for h in r_dist.history]
    assert losses[-1] < losses[0], losses
    print("STREAM_DIST_OK", d)
    """
)


def test_distributed_streaming_fit_matches_serial():
    """fit_sbv(stream_chunk=..., distributed=(mesh, axis)) on an 8-device
    mesh matches the serial streaming fit (same harness as
    tests/test_distributed_gp.py — the main process must keep 1 device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", STREAM_DIST_SCRIPT], capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=600,
    )
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "STREAM_DIST_OK" in r.stdout


STEP_COMPILE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    from repro.core.fit import _chunk_grad_fn, fit_sbv
    from repro.core.pipeline import SBVConfig
    from repro.data.gp_sim import paper_synthetic
    from repro.launch.mesh import make_worker_mesh

    x, y, _ = paper_synthetic(seed=0, n=600, d=4)
    mesh = make_worker_mesh(4)
    res = fit_sbv(x, y, SBVConfig(n_blocks=24, m=16, n_workers=4, seed=0),
                  inner_steps=3, outer_rounds=1, stream_chunk=200,
                  precision="f32", distributed=(mesh, "workers"))
    (backend,) = res.stream_stats["backends"]
    assert res.stream_stats["n_pieces"] > 1
    step = _chunk_grad_fn(3.5, backend, 600, mesh, "workers")
    assert step._cache_size() == 1, step._cache_size()
    print("STEP_COMPILE_OK")
    """
)


def test_distributed_streaming_step_compiles_once():
    """The sharded chunk step compiles once for a fit's piece shape. Its
    gradient comes back replicated over the mesh, so params placed
    elsewhere would change placement after the first update and compile
    the step again (4 virtual devices, in a subprocess)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", STEP_COMPILE_SCRIPT], capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=300,
    )
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "STEP_COMPILE_OK" in r.stdout


# -- predict parity --------------------------------------------------------


def test_streaming_predict_store_equals_incore(tmp_path, small):
    x, y, params = small
    st = ArrayStore.from_arrays(str(tmp_path / "p"), x, y, shard_rows=412)
    rng = np.random.default_rng(5)
    xt = rng.uniform(size=(300, 4))
    kw = dict(bs_pred=16, m_pred=48, n_sims=4, chunk_size=128,
              stream_chunk=400, seed=0)
    p_disk = predict_sbv(params, st, None, xt, **kw)
    p_mem = predict_sbv(params, x, y, xt, **kw)
    for f in ("mean", "var", "sim_mean", "ci_low", "ci_high"):
        assert np.array_equal(getattr(p_disk, f), getattr(p_mem, f)), f
    # Store-backed x_test rides the same chunk protocol.
    st_t = ArrayStore.from_arrays(str(tmp_path / "pt"), xt, np.zeros(300),
                                  shard_rows=90)
    p_both = predict_sbv(params, st, None, st_t, **kw)
    assert np.array_equal(p_both.mean, p_mem.mean)


def test_streaming_predict_matches_exact_gp(small):
    """m_pred >= n: every block conditions on the whole training set, so
    the streaming index must reproduce the exact GP like the in-core path
    does (the oracle test for the store-backed kNN + gather/remap)."""
    from repro.core.exact_gp import exact_predict

    x, y, params = small
    x, y = x[:400], y[:400]
    rng = np.random.default_rng(2)
    xt = rng.uniform(size=(60, 4))
    pred = predict_sbv(params, x, y, xt, bs_pred=8, m_pred=400, n_sims=2,
                       stream_chunk=150, chunk_size=60)
    em, ev = exact_predict(params, x, y, xt)
    np.testing.assert_allclose(pred.mean, np.asarray(em), atol=1e-4, rtol=0)
    np.testing.assert_allclose(pred.var, np.asarray(ev), atol=1e-4, rtol=0)


def test_pipeline_store_producer_matches_sync(tmp_path, small):
    """Serving pipeline with a store-backed test set: the producer thread
    reads windows from disk; results must equal the in-core sync loop
    bitwise (same chunk protocol underneath)."""
    from repro.core.predict import build_train_index
    from repro.serving import PipelineConfig, predict_pipelined, predict_synchronous

    x, y, params = small
    rng = np.random.default_rng(9)
    xt = rng.uniform(size=(500, 4))
    st_t = ArrayStore.from_arrays(str(tmp_path / "q"), xt, np.zeros(500),
                                  shard_rows=128)
    index = build_train_index(x, y, np.asarray(params.beta), 48, seed=0)
    cfg = PipelineConfig(bs_pred=16, m_pred=48, chunk_size=160)
    m_sync, v_sync = predict_synchronous(params, index, xt, cfg, seed=0)
    m_disk, v_disk = predict_pipelined(params, index, st_t, cfg, seed=0)
    assert np.array_equal(m_sync, m_disk) and np.array_equal(v_sync, v_disk)


# -- bounded-memory smoke fit ---------------------------------------------


def _vmrss_kb():
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        return None
    return None


@pytest.mark.slow
def test_rss_bounded_200k_fit(tmp_path):
    """200k-point store-backed smoke fit under a working-set RSS ceiling
    derived from the run's own streaming state (the small sibling of
    benchmarks/fig_streaming_scale.py's 1M gate)."""
    if _vmrss_kb() is None:
        pytest.skip("no /proc/self/status on this platform")
    import threading

    n, d, stream_chunk = 200_000, 16, 32_768
    rng = np.random.default_rng(0)
    with ArrayStore.create(str(tmp_path / "big"), d) as w:
        for _ in range(n // 20_000):
            xw = rng.uniform(size=(20_000, d))
            yw = np.sin(3 * xw[:, 0]) + xw[:, 1] ** 2 + 0.05 * rng.standard_normal(20_000)
            w.append(xw, yw)
    st = ArrayStore(str(tmp_path / "big"))

    peak = {"kb": _vmrss_kb()}
    base_kb = peak["kb"]
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            kb = _vmrss_kb()
            if kb and kb > peak["kb"]:
                peak["kb"] = kb
            stop.wait(0.005)

    th = threading.Thread(target=poll, daemon=True)
    th.start()
    try:
        cfg = SBVConfig(n_blocks=n // 128, m=12, alpha=8.0, seed=0)
        res = fit_sbv(st, None, cfg, inner_steps=2, outer_rounds=1,
                      stream_chunk=stream_chunk)
    finally:
        stop.set()
        th.join(timeout=5)

    assert np.all(np.isfinite([h[2] for h in res.history]))
    from repro.data.streaming import working_set_model

    ws = working_set_model(res.stream_stats, n, d, cfg.m, stream_chunk,
                           n_caches=1)  # fit only — no predict index here
    budget = 2 * ws["total"]
    incore = ws["incore_total"]
    assert budget < incore, "ceiling must undercut the in-core footprint"
    delta = (peak["kb"] - base_kb) * 1024
    assert delta <= budget, (
        f"peak RSS delta {delta / 2**20:.0f}MB exceeded the 2x working-set "
        f"ceiling {budget / 2**20:.0f}MB (in-core would be ~{incore / 2**20:.0f}MB)"
    )


# -- streaming data-plane regression fixes ---------------------------------
# Each test below pins a latent bug found in the PR-6 sweep; each FAILED
# on the pre-fix code.


def test_lazy_flat_blocks_duplicate_ids_accounted_once(tmp_path, small):
    """Duplicate uncached block ids in ONE call are gathered and accounted
    once. Pre-fix, each duplicate re-gathered the block's rows and bumped
    ``_cache_bytes`` for a copy the cache never retained — the counter
    inflated permanently and drove the LRU into premature eviction."""
    from repro.data.streaming import LazyFlatBlocks, streaming_kmeans_blocks

    x, y, _ = small
    st = ArrayStore.from_arrays(str(tmp_path / "lz"), x, y, shard_rows=400)
    beta = np.full(4, 0.5)
    blocks, radii, _ = streaming_kmeans_blocks(st, beta, 12, seed=0)
    flat = LazyFlatBlocks(blocks, radii, st, beta)

    out = flat.points_of_blocks(np.array([3, 3, 5, 3]))
    # The stacked result still repeats block 3 per request...
    assert out.shape == (3 * flat.sizes[3] + flat.sizes[5], 4)
    # ...but each miss was read from the store exactly once,
    assert flat.gathered_rows == flat.sizes[3] + flat.sizes[5]
    # and the byte counter equals what the cache actually retains.
    assert flat._cache_bytes == sum(v.nbytes for v in flat._cache.values())

    # Accounting stays exact across repeats and cache hits.
    flat.points_of_blocks(np.array([5, 3, 5]))
    assert flat._cache_bytes == sum(v.nbytes for v in flat._cache.values())
    assert flat.gathered_rows == flat.sizes[3] + flat.sizes[5]


def test_lazy_flat_blocks_call_larger_than_cache(tmp_path, small):
    """One call whose blocks outgrow the byte cap still returns every
    block. Pre-fix, eviction ran before the result was assembled and
    dropped blocks of the same call (KeyError) — hit by the filtered NNS
    at 10k blocks, where one candidate set exceeds the default 32 MB."""
    from repro.data.streaming import LazyFlatBlocks, streaming_kmeans_blocks

    x, y, _ = small
    st = ArrayStore.from_arrays(str(tmp_path / "lz"), x, y, shard_rows=400)
    beta = np.full(4, 0.5)
    blocks, radii, _ = streaming_kmeans_blocks(st, beta, 12, seed=0)
    ids = np.arange(12)
    cap = 8 * 4 * 10  # ten rows: smaller than any one block
    flat = LazyFlatBlocks(blocks, radii, st, beta, cache_bytes=cap)
    out = flat.points_of_blocks(ids)
    want = LazyFlatBlocks(blocks, radii, st, beta).points_of_blocks(ids)
    np.testing.assert_array_equal(out, want)
    assert len(flat._cache) == 1  # evicted back down once the call returned


def _tiny_packed():
    from repro.core.packing import PackedBlocks

    bc, bs, m, d = 2, 3, 2, 2
    return PackedBlocks(
        blk_x=np.zeros((bc, bs, d)), blk_y=np.zeros((bc, bs)),
        blk_mask=np.ones((bc, bs), bool), nn_x=np.zeros((bc, m, d)),
        nn_y=np.zeros((bc, m)), nn_mask=np.ones((bc, m), bool),
        owners=np.zeros(bc, np.int32))


def test_spool_reusable_after_cleanup(tmp_path):
    """A spool must accept adds again after ``cleanup()``: the multi-round
    fit reuses per-round spool paths. Pre-fix, ``cleanup`` removed the
    directory but left ``_made_dir`` set, so the next overflow-to-disk
    ``add`` crashed in ``np.savez`` with FileNotFoundError — and the tier
    gauges kept counting entries that no longer existed."""
    from repro.data.streaming import PackedChunkSpool

    sp = PackedChunkSpool(str(tmp_path / "sp"), device_budget=0)
    sp.add(_tiny_packed())
    assert sp.n_disk == 1 and sp.disk_bytes_total > 0
    sp.cleanup()
    assert len(sp) == 0
    assert sp.device_bytes == 0 and sp.disk_bytes_total == 0

    sp.add(_tiny_packed())  # pre-fix: FileNotFoundError here
    pieces = list(sp.iter_arrays(prefetch=0))
    assert len(pieces) == 1
    assert np.asarray(pieces[0][0][0]).shape == (2, 3, 2)
    sp.cleanup()
    assert not os.path.exists(sp.path)


def test_streaming_moments_survive_large_offset(tmp_path):
    """Variance of y with ``|mean| >> std`` (a 1e8 offset leaves ~1e-1
    significant digits in the one-pass ``E[y^2] - mean^2`` form, which
    pre-fix collapsed to the clamp at 0 and silently initialized
    ``sigma2 ~ 0``). The shifted two-pass form keeps full precision, and
    both store backends still agree bitwise."""
    from repro.data.streaming import streaming_moments

    rng = np.random.default_rng(0)
    x = rng.uniform(size=(4000, 3))
    y = 1e8 + rng.standard_normal(4000)
    mean, var = streaming_moments(MemoryStore(x, y), batch_rows=700)
    assert np.isclose(mean, y.mean(), rtol=1e-12)
    assert np.isclose(var, y.var(), rtol=1e-9)

    st = ArrayStore.from_arrays(str(tmp_path / "mo"), x, y, shard_rows=512)
    m_disk, v_disk = streaming_moments(st, batch_rows=700)
    assert mean == m_disk and var == v_disk


def test_prefetcher_iteration_terminates_after_close():
    """Iterating a closed (or exception-drained) Prefetcher must return,
    not block forever on an empty queue. Pre-fix, ``__iter__`` sat in a
    bare ``q.get()`` with no producer left to feed it — a consumer that
    resumed iteration after ``close()`` hung the fit."""
    import threading

    from repro.prefetch import Prefetcher

    pf = Prefetcher(iter(range(100)), depth=1)
    it = iter(pf)
    assert next(it) == 0
    pf.close()

    got = {"done": False}

    def drain():
        list(it)  # pre-fix: blocks forever
        got["done"] = True

    th = threading.Thread(target=drain, daemon=True)
    th.start()
    th.join(timeout=10.0)
    assert got["done"], "iteration did not terminate after close()"

    # An exception consumed mid-stream leaves the thread dead and the
    # queue empty — later iteration must also terminate (idempotent).
    def boom():
        raise RuntimeError("producer failed")
        yield  # pragma: no cover

    pf2 = Prefetcher(boom(), depth=1)
    with pytest.raises(RuntimeError, match="producer failed"):
        next(iter(pf2))
    assert list(iter(pf2)) == []
    pf2.close()


def test_rows_view_scalar_indexing(tmp_path, small):
    """``view[5]`` must follow ndarray semantics and drop the row axis —
    pre-fix it returned ``(1, d)``/``(1,)``, which silently broadcast
    wrong shapes into consumers written against in-core arrays."""
    x, y, _ = small
    st = ArrayStore.from_arrays(str(tmp_path / "rv"), x, y, shard_rows=400)
    xv, yv = st.x_rows, st.y_rows

    assert xv[5].shape == (4,)
    assert np.array_equal(xv[5], x[5])
    assert np.ndim(yv[5]) == 0 and yv[5] == y[5]
    # negative indices normalize like ndarray
    assert np.array_equal(xv[-1], x[-1]) and yv[-1] == y[-1]
    # array/slice paths keep the row axis
    assert xv[np.array([5])].shape == (1, 4)
    assert xv[10:12].shape == (2, 4)
    with pytest.raises(IndexError):
        xv[len(xv)]
    with pytest.raises(IndexError):
        yv[-len(yv) - 1]


def test_working_set_model_terms(small):
    """The RSS-gate model must stay tied to real run state: every term
    positive, and the streaming budget strictly under the in-core cost
    for the shapes the gates actually use."""
    from repro.data.streaming import working_set_model

    x, y, _ = small
    cfg = SBVConfig(n_blocks=24, m=20, seed=0)
    res = fit_sbv(x, y, cfg, inner_steps=2, outer_rounds=1, stream_chunk=300)
    ws = working_set_model(res.stream_stats, len(y), 4, cfg.m, 300)
    assert all(v > 0 for v in ws["terms"].values())
    assert ws["total"] == sum(ws["terms"].values())


def test_device_cache_budget_needs_accelerator_memory_stats(monkeypatch):
    """On an accelerator the budget comes from the device's own memory
    stats; host RAM is never a stand-in for HBM."""
    import jax

    from repro.data.streaming import device_cache_budget

    class _Dev:
        platform = "tpu"

        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    gib = 1 << 30
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(
        {"bytes_limit": 16 * gib, "bytes_in_use": 2 * gib})])
    assert device_cache_budget(frac=0.5, reserve_bytes=gib) == 6 * gib
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(None)])
    with pytest.raises(RuntimeError, match="memory_stats"):
        device_cache_budget()
