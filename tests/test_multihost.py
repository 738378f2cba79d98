"""Multi-host streaming construction + fit (docs/streaming.md).

The contract under test: K `jax.distributed` rank processes, each owning
one row-partition of a shared store, must produce the SAME fit as the
single-process streaming path — the partitioned k-means allreduce, the
halo NNS exchange and the lockstep per-chunk loss/grad allreduce add
parallelism, not numerics. Fast in-process layers (partition geometry,
``PartitionedStore`` pass-through, ``LoopbackComm`` bitwise parity) run
everywhere; the ``multihost``-marked tests spawn real rank subprocesses
through ``repro.launch.fit_gp --distributed-hosts`` and pin nll parity
plus the per-host peak-RSS ceiling.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.data.gp_sim import paper_synthetic
from repro.data.store import (ArrayStore, PartitionedStore,
                              partition_bounds)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One shared configuration for every serial-vs-distributed comparison in
# this file (the rank CLI flags below must mirror these).
BLOCKS, M, INNER, OUTER, CHUNK, SEED = 24, 8, 4, 2, 600, 0


# -- partition geometry -----------------------------------------------------


def test_partition_bounds_alignment_and_coverage():
    b = partition_bounds(1000, 3, align=128)
    assert b[0] == 0 and b[-1] == 1000
    assert np.all(np.diff(b) >= 0)
    # interior boundaries snap to the alignment; the final one never does
    assert all(v % 128 == 0 for v in b[1:-1])
    assert np.array_equal(b, [0, 384, 768, 1000])


def test_partition_bounds_empty_tail_parts():
    # n_rows < n_parts * align: tail parts collapse to zero rows and the
    # union still covers every row exactly once.
    b = partition_bounds(100, 4, align=64)
    assert np.array_equal(b, [0, 64, 100, 100, 100])
    widths = np.diff(b)
    assert widths.sum() == 100 and np.all(widths >= 0)


def test_partitioned_store_rejects_bad_part(tmp_path):
    x, y, _ = paper_synthetic(seed=0, n=300, d=3)
    st = ArrayStore.from_arrays(str(tmp_path / "pp"), x, y, shard_rows=128)
    with pytest.raises(ValueError):
        PartitionedStore(st, 2, 2)
    with pytest.raises(ValueError):
        PartitionedStore(st, 2, -1)


def test_partitioned_store_union_matches_serial(tmp_path):
    """The union of all parts' chunk windows IS the serial window
    sequence — same global grid, same rows, nothing duplicated."""
    x, y, _ = paper_synthetic(seed=1, n=1500, d=3)
    st = ArrayStore.from_arrays(str(tmp_path / "un"), x, y, shard_rows=256)
    serial = [(s, xw.copy(), yw.copy()) for s, xw, yw in st.iter_chunks(400)]
    for n_parts in (2, 3):
        parts = [PartitionedStore(st, n_parts, p) for p in range(n_parts)]
        assert sum(p.n_local for p in parts) == st.n_rows
        # partition boundaries snap to whole shards (shard_rows=256)
        for p in parts[:-1]:
            assert p.stop % 256 == 0 or p.stop == st.n_rows
        got = sorted(
            (s, xw, yw) for p in parts for s, xw, yw in p.iter_chunks(400))
        # windows re-assemble the serial pass exactly (a window split by a
        # partition boundary appears as adjacent clipped pieces)
        cat_x = np.concatenate([xw for _, xw, _ in got])
        ser_x = np.concatenate([xw for _, xw, _ in serial])
        assert np.array_equal(cat_x, ser_x)
        cat_y = np.concatenate([yw for _, _, yw in got])
        assert np.array_equal(cat_y, np.concatenate(
            [yw for _, _, yw in serial]))
        # every piece sits on the global [k*rows, (k+1)*rows) grid,
        # clipped to its partition
        for (s, xw, _), p in [(c, p) for p in parts
                              for c in p.iter_chunks(400)]:
            assert s % 400 == 0 or s == p.start
            assert p.start <= s < p.stop


def test_partitioned_store_passthrough_and_telemetry(tmp_path):
    """Random access passes through to the parent store (shared-FS
    semantics) while ``remote_rows_read`` counts exactly the rows served
    from outside the partition."""
    x, y, _ = paper_synthetic(seed=2, n=600, d=3)
    st = ArrayStore.from_arrays(str(tmp_path / "tm"), x, y, shard_rows=128)
    p = PartitionedStore(st, 2, 0)
    assert (p.n_rows, p.d) == (600, 3)

    inside = np.arange(p.start, min(p.start + 10, p.stop))
    xi, yi = p.read_rows(inside)
    assert np.array_equal(xi, x[inside]) and np.array_equal(yi, y[inside])
    assert p.remote_rows_read == 0

    outside = np.array([p.stop, p.stop + 1, p.start])  # 2 remote, 1 local
    p.read_rows(outside)
    assert p.remote_rows_read == 2

    p2 = PartitionedStore(st, 2, 1)
    xs, _ = p2.read_slice(p2.start - 5, p2.start + 5)  # 5 remote rows
    assert np.array_equal(xs, x[p2.start - 5:p2.start + 5])
    assert p2.remote_rows_read == 5


# -- single-process comm parity --------------------------------------------


def test_loopback_fit_is_bitwise_serial(tmp_path):
    """``multihost=LoopbackComm()`` must be the identity on the fit: the
    multi-host code path with one host reproduces the plain streaming
    fit BITWISE (allreduce is a copy, exchange a loopback)."""
    from repro.core.fit import fit_sbv
    from repro.core.pipeline import SBVConfig
    from repro.multihost import LoopbackComm

    x, y, _ = paper_synthetic(seed=0, n=900, d=3)
    st = ArrayStore.from_arrays(str(tmp_path / "lb"), x, y, shard_rows=256)
    cfg = SBVConfig(n_blocks=16, m=M, seed=SEED)
    kw = dict(inner_steps=3, outer_rounds=2, stream_chunk=400,
              device_cache=0, backend="ref")
    ref = fit_sbv(st, None, cfg, **kw)
    mh = fit_sbv(st, None, cfg, multihost=LoopbackComm(), **kw)
    assert [h[:2] for h in ref.history] == [h[:2] for h in mh.history]
    assert all(a[2] == b[2] for a, b in zip(ref.history, mh.history))
    for f in ("sigma2", "nugget"):
        assert float(getattr(ref.params, f)) == float(getattr(mh.params, f))
    assert np.array_equal(np.asarray(ref.params.beta),
                          np.asarray(mh.params.beta))


def test_partition_blocks_spans():
    from repro.multihost import partition_blocks

    assert partition_blocks(10, 3) == [(0, 4), (4, 7), (7, 10)]
    assert partition_blocks(2, 4) == [(0, 1), (1, 2), (2, 2), (2, 2)]
    for n, k in ((1, 1), (17, 5), (64, 8)):
        spans = partition_blocks(n, k)
        assert spans[0][0] == 0 and spans[-1][1] == n
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
        widths = [hi - lo for lo, hi in spans]
        assert max(widths) - min(widths) <= 1


def test_loopback_predict_is_bitwise_serial():
    """``predict_sbv(multihost=LoopbackComm())`` owns every block span,
    so the sharded path must reproduce the plain predict BITWISE (the
    full-span eps slice is the identity and allreduce is a copy)."""
    from repro.core.predict import predict_sbv
    from repro.multihost import LoopbackComm

    x, y, params = paper_synthetic(seed=0, n=400, d=3)
    rng = np.random.default_rng(1)
    xq = rng.uniform(size=(111, 3))
    kw = dict(bs_pred=8, m_pred=24, seed=3, n_sims=3, chunk_size=64)
    ref = predict_sbv(params, x, y, xq, **kw)
    mh = predict_sbv(params, x, y, xq, multihost=LoopbackComm(), **kw)
    for f in ("mean", "var", "sim_mean", "ci_low", "ci_high"):
        assert np.array_equal(np.asarray(getattr(ref, f)),
                              np.asarray(getattr(mh, f))), f


# -- real rank subprocesses -------------------------------------------------


@pytest.fixture(scope="module")
def mh_store(tmp_path_factory):
    x, y, _ = paper_synthetic(seed=0, n=2000, d=4)
    path = str(tmp_path_factory.mktemp("mh") / "store")
    return ArrayStore.from_arrays(path, x, y, shard_rows=512)


@pytest.fixture(scope="module")
def serial_nll(mh_store):
    from repro.core.fit import fit_sbv
    from repro.core.pipeline import SBVConfig

    cfg = SBVConfig(n_blocks=BLOCKS, m=M, seed=SEED)
    res = fit_sbv(mh_store, None, cfg, inner_steps=INNER,
                  outer_rounds=OUTER, backend="ref", stream_chunk=CHUNK,
                  device_cache=0)
    return float(res.history[-1][2])


def _run_distributed(mh_store, tmp_path, hosts: int) -> dict:
    """Launch the real multi-rank fit through the fit_gp driver."""
    result = str(tmp_path / "result.json")
    cmd = [sys.executable, "-m", "repro.launch.fit_gp",
           "--store", mh_store.path, "--distributed-hosts", str(hosts),
           "--blocks", str(BLOCKS), "--m", str(M),
           "--inner-steps", str(INNER), "--outer-rounds", str(OUTER),
           "--stream-chunk", str(CHUNK), "--device-cache-mb", "0",
           "--seed", str(SEED), "--backend", "ref",
           "--result-json", result]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, (
        f"distributed fit failed:\n{proc.stdout}\n{proc.stderr}")
    with open(result) as f:
        return json.load(f)


def _check_parity_and_memory(merged, serial_nll, hosts):
    assert merged["n_hosts"] == hosts
    assert len(merged["ranks"]) == hosts
    # lockstep allreduce: every rank lands on the SAME nll ...
    assert merged["max_nll_spread"] == 0.0
    # ... and it matches the single-process streaming fit (the local
    # piece count differs per rank, so only summation ORDER may change)
    assert abs(merged["nll"] - serial_nll) <= 1e-8
    for rk in merged["ranks"]:
        # per-host memory contract: peak RSS within 2x the partitioned
        # working-set model (skip where /proc is unreadable)
        if rk["peak_rss_bytes"] is not None:
            assert rk["peak_rss_bytes"] <= 2 * rk["working_set_bytes"], (
                f"rank {rk['rank']}: peak {rk['peak_rss_bytes']} > 2x "
                f"working set {rk['working_set_bytes']}")


@pytest.mark.multihost
def test_two_host_fit_matches_serial(mh_store, serial_nll, tmp_path):
    merged = _run_distributed(mh_store, tmp_path, hosts=2)
    _check_parity_and_memory(merged, serial_nll, hosts=2)


@pytest.mark.multihost
def test_four_host_fit_matches_serial(mh_store, serial_nll, tmp_path):
    merged = _run_distributed(mh_store, tmp_path, hosts=4)
    _check_parity_and_memory(merged, serial_nll, hosts=4)


# -- one process per chip ---------------------------------------------------


def test_rank_launch_refused_on_accelerator_host(monkeypatch):
    """Rank processes would contend for a chip the launcher already holds:
    on a non-CPU backend the launcher refuses and points to --workers."""
    import jax

    from repro.multihost import require_cpu_ranks

    require_cpu_ranks()  # the CPU backend launches ranks as before
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="--workers"):
        require_cpu_ranks()
