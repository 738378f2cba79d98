"""Property tests: the filtered NNS is EXACT, not approximate.

The two-stage coarse/fine filter (paper Alg. 4 + Eq. 7, with the
radius-augmented coarse admission of DESIGN.md §3) must return exactly
the brute-force answer for every geometry:

* random anisotropic betas (the scaled space the filter operates in),
* degenerate/duplicate points (distance ties),
* n < m and tiny-alpha settings (the doubling-fallback path in
  ``_one_block``, previously untested).

Ties are compared by neighbor DISTANCE multisets (a tie can be broken
either way depending on candidate order); index sets are compared
whenever distances are unique.
"""
import numpy as np
import pytest

from repro.core.blocks import build_blocks, scale_inputs
from repro.core.nns import (
    _FlatBlocks, brute_force_nns, filtered_knn_points, filtered_nns,
)


def _beta(rng, d):
    """Random anisotropic scaling over ~3 orders of magnitude."""
    return 10.0 ** rng.uniform(-1.5, 1.0, size=d)


def _dists(xs, center, idx):
    return np.sqrt(np.sum((xs[idx] - center) ** 2, axis=1))


def _assert_same_neighbors(xs, center, got, want):
    """Equal neighbor count + equal sorted distances; equal index sets
    when distances are unique (ties may break either way)."""
    assert got.size == want.size
    dg = _dists(xs, center, got)
    dw = _dists(xs, center, want)
    np.testing.assert_allclose(dg, dw, rtol=0, atol=1e-9)
    if np.unique(np.round(dw, 9)).size == dw.size:
        assert set(got.tolist()) == set(want.tolist())


def _brute_knn_points(xs, queries, m):
    """O(n)-per-query oracle for the unconstrained prediction kNN."""
    out = []
    for q in queries:
        d2 = np.sum((xs - q) ** 2, axis=1)
        k = min(m, xs.shape[0])
        part = np.argpartition(d2, k - 1)[:k] if xs.shape[0] > k else np.arange(xs.shape[0])
        part = part[np.argsort(d2[part], kind="stable")]
        out.append(part.astype(np.int64))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("d", [2, 5])
@pytest.mark.parametrize("alpha", [100.0, 1.5])
def test_filtered_nns_equals_brute_force(seed, d, alpha):
    """alpha=1.5 starves the initial ball so the doubling fallback runs."""
    rng = np.random.default_rng(seed)
    n, m = 160, 12
    x = rng.uniform(size=(n, d))
    beta = _beta(rng, d)
    xs = scale_inputs(x, beta)
    blocks = build_blocks(xs, 20, 1, beta, seed=seed)
    got = filtered_nns(xs, blocks, m, alpha=alpha)
    want = brute_force_nns(xs, blocks, m)
    for b in range(blocks.n_blocks):
        _assert_same_neighbors(xs, blocks.centers[b], got[b], want[b])


@pytest.mark.parametrize("seed", [0, 5])
def test_filtered_nns_duplicate_points(seed):
    """Exactly-duplicated rows (tied distances) still give exact answers."""
    rng = np.random.default_rng(seed)
    base = rng.uniform(size=(30, 3))
    x = np.concatenate([base, base, base + 1e-12])  # 90 pts, heavy ties
    beta = np.asarray([0.1, 1.0, 10.0])
    xs = scale_inputs(x, beta)
    blocks = build_blocks(xs, 10, 1, beta, seed=seed)
    got = filtered_nns(xs, blocks, 8, alpha=3.0)
    want = brute_force_nns(xs, blocks, 8)
    for b in range(blocks.n_blocks):
        assert got[b].size == want[b].size
        np.testing.assert_allclose(
            _dists(xs, blocks.centers[b], got[b]),
            _dists(xs, blocks.centers[b], want[b]),
            rtol=0, atol=1e-9,
        )


def test_filtered_nns_fewer_points_than_m():
    """n < m: every block must receive ALL preceding points."""
    rng = np.random.default_rng(7)
    x = rng.uniform(size=(15, 2))
    beta = np.asarray([0.5, 2.0])
    xs = scale_inputs(x, beta)
    blocks = build_blocks(xs, 5, 1, beta, seed=7)
    got = filtered_nns(xs, blocks, 50, alpha=1.0)
    want = brute_force_nns(xs, blocks, 50)
    ranks = blocks.rank_of_block
    pt_rank = ranks[blocks.labels]
    for b in range(blocks.n_blocks):
        n_prec = int(np.sum(pt_rank < ranks[b]))
        assert got[b].size == n_prec  # everything preceding, nothing more
        _assert_same_neighbors(xs, blocks.centers[b], got[b], want[b])


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("alpha", [100.0, 1.5])
def test_filtered_knn_points_equals_brute_force(seed, alpha):
    rng = np.random.default_rng(seed)
    n, d, m, nq = 180, 4, 15, 37
    x = rng.uniform(size=(n, d))
    beta = _beta(rng, d)
    xs = scale_inputs(x, beta)
    blocks = build_blocks(xs, 16, 1, beta, seed=seed)
    queries = scale_inputs(rng.uniform(size=(nq, d)), beta)
    got = filtered_knn_points(xs, blocks, queries, m, alpha=alpha)
    want = _brute_knn_points(xs, queries, m)
    for qi in range(nq):
        _assert_same_neighbors(xs, queries[qi], got[qi], want[qi])


def test_filtered_knn_points_m_exceeds_n():
    rng = np.random.default_rng(3)
    x = rng.uniform(size=(12, 3))
    beta = np.ones(3)
    xs = scale_inputs(x, beta)
    blocks = build_blocks(xs, 4, 1, beta, seed=3)
    queries = scale_inputs(rng.uniform(size=(5, 3)), beta)
    got = filtered_knn_points(xs, blocks, queries, 40, alpha=1.0)
    want = _brute_knn_points(xs, queries, 40)
    for qi in range(5):
        assert got[qi].size == 12  # the whole training set, sorted
        _assert_same_neighbors(xs, queries[qi], got[qi], want[qi])


def test_prebuilt_flat_index_gives_identical_results():
    """The cached ``_FlatBlocks`` (TrainIndex.flat) is a pure reuse: passing
    it must not change a single neighbor."""
    rng = np.random.default_rng(11)
    x = rng.uniform(size=(120, 3))
    beta = _beta(rng, 3)
    xs = scale_inputs(x, beta)
    blocks = build_blocks(xs, 12, 1, beta, seed=11)
    flat = _FlatBlocks(xs, blocks)
    queries = scale_inputs(rng.uniform(size=(20, 3)), beta)

    a = filtered_knn_points(xs, blocks, queries, 10, flat=flat)
    b = filtered_knn_points(xs, blocks, queries, 10)
    for ga, gb in zip(a, b):
        np.testing.assert_array_equal(ga, gb)
    a = filtered_nns(xs, blocks, 10, flat=flat)
    b = filtered_nns(xs, blocks, 10)
    for ga, gb in zip(a, b):
        np.testing.assert_array_equal(ga, gb)


@pytest.mark.parametrize("alpha", [1.5, 100.0])
def test_filtered_nns_counters(alpha):
    """``nns_kept`` counts the neighbours returned and ``nns_scored`` the
    candidate points scored, doubling passes included; counting changes
    no answer."""
    rng = np.random.default_rng(11)
    x = rng.uniform(size=(200, 3))
    beta = np.asarray([0.3, 1.0, 4.0])
    xs = scale_inputs(x, beta)
    blocks = build_blocks(xs, 20, 1, beta, seed=11)
    stats = {}
    got = filtered_nns(xs, blocks, 10, alpha=alpha, stats=stats)
    assert stats["nns_kept"] == sum(nb.size for nb in got) > 0
    assert stats["nns_scored"] >= stats["nns_kept"]
    plain = filtered_nns(xs, blocks, 10, alpha=alpha)
    assert all(np.array_equal(a, b) for a, b in zip(got, plain))
    # a second call adds to the same counters
    filtered_nns(xs, blocks, 10, alpha=alpha, stats=stats)
    assert stats["nns_kept"] == 2 * sum(nb.size for nb in got)
