"""Random Anchor Clustering's assignment pass (``core/blocks.py: rac_cluster``):
the tiled pass gives the full-matrix formula's labels bit for bit, leaves the
rng where the formula leaves it, and keeps its working set to one tile."""
import tracemalloc

import numpy as np
import pytest

from repro.core.blocks import rac_cluster


def _rac_reference(x_scaled, n_blocks, rng):
    """The assignment as one full n x n_blocks distance matrix."""
    n = x_scaled.shape[0]
    n_blocks = min(n_blocks, n)
    anchors = x_scaled[rng.choice(n, size=n_blocks, replace=False)]
    a2 = np.sum(anchors * anchors, axis=1)
    d2 = np.sum(x_scaled * x_scaled, axis=1)[:, None] - 2.0 * x_scaled @ anchors.T + a2[None, :]
    return np.argmin(d2, axis=1)


def _points(n, d, seed, grid):
    rng = np.random.default_rng(seed)
    if grid:  # integer lattice: many exactly tied distances
        return rng.integers(0, 4, size=(n, d)).astype(np.float64)
    return rng.uniform(size=(n, d)) / rng.uniform(0.1, 2.0, size=d)


@pytest.mark.parametrize(
    "n, n_blocks, d, grid",
    [
        (50, 5, 3, False),       # n smaller than one tile
        (1000, 100, 10, False),  # n not a multiple of the tile's rows (327)
        (6001, 5000, 2, False),  # n_blocks larger than the tile's rows (8)
        (300, 300, 4, False),    # n_blocks == n: every point is an anchor
        (4000, 37, 1, False),    # d = 1
        (20000, 520, 10, False), # d = 10, the UQ sweep's anchors per row
        (2000, 20, 2, True),     # tied distances: argmin's first index
        (40, 60, 3, False),      # n_blocks > n is clipped to n
    ],
)
def test_rac_labels_and_rng_match_full_matrix(n, n_blocks, d, grid):
    x = _points(n, d, seed=n + d, grid=grid)
    rng, rng_ref = np.random.default_rng(7), np.random.default_rng(7)
    labels = rac_cluster(x, n_blocks, rng)
    want = _rac_reference(x, n_blocks, rng_ref)
    assert labels.dtype == np.int64 and labels.shape == (n,)
    np.testing.assert_array_equal(labels, want)
    assert rng.bit_generator.state == rng_ref.bit_generator.state


def test_rac_working_set_is_one_tile():
    """131,072 x 10 points, 256 anchors: a full-matrix pass would hold
    ~270 MB of distances; the tiled one holds its row norms, labels and
    one buffer."""
    x = _points(131072, 10, seed=3, grid=False)
    rng = np.random.default_rng(0)
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        base = tracemalloc.get_traced_memory()[0]
        labels = rac_cluster(x, 256, rng)
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    assert peak < 32 * 2**20, f"assignment peaked at {peak / 2**20:.1f} MiB"
    assert np.bincount(labels, minlength=256).sum() == x.shape[0]
