"""The benchmark's operation and byte counts at known shapes, and their
independence of padding."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))
import work  # noqa: E402

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_loglik_block_at_the_papers_widths():
    # n = 500: Cholesky 500^3/3, assembly 500*501/2 pairs x (3*10 + 10),
    # substitution 500^2, log-det and quadratic form 2*100
    want = 500 ** 3 / 3 + 125250 * 40 + 250000 + 200
    assert work.loglik_forward_flops([100], [400], 10) == pytest.approx(want)
    assert work.fit_step_flops([100], [400], 10) == pytest.approx(3 * want)
    assert work.loglik_bytes([100], [400], 10) == 500 * 12 * 4 + 4


def test_predict_block_at_the_serving_widths():
    bs, m, d, sims = 25, 120, 10, 1000
    want = ((m * (m + 1) / 2 + m * bs) * 40 + m ** 3 / 3 + m ** 2 * bs
            + m ** 2 + 4 * m * bs + 4 * sims * bs)
    assert work.predict_flops([bs], [m], d, sims) == pytest.approx(want)
    assert work.predict_bytes([bs], [m], d) == (bs * 11 + m * 12 + 4 * bs) * 4


def test_counts_add_over_blocks():
    bs, m = np.array([90, 100, 130]), np.array([400, 250, 400])
    one = sum(work.loglik_forward_flops([b], [k], 10) for b, k in zip(bs, m))
    assert work.loglik_forward_flops(bs, m, 10) == pytest.approx(one)


def test_padding_a_block_leaves_its_count_unchanged():
    """Counts come from mask counts: a block padded to a tile (or to the
    largest block) counts as its true size."""
    rng = np.random.default_rng(0)
    true_bs = rng.integers(60, 170, size=32)
    true_m = rng.integers(300, 401, size=32)
    for pad_bs, pad_m in ((170, 400), (176, 512)):
        blk_mask = np.arange(pad_bs)[None] < true_bs[:, None]
        nn_mask = np.arange(pad_m)[None] < true_m[:, None]
        padded = work.loglik_forward_flops(blk_mask.sum(1), nn_mask.sum(1), 10)
        assert padded == pytest.approx(
            work.loglik_forward_flops(true_bs, true_m, 10))
        assert padded < work.loglik_forward_flops(
            np.full(32, pad_bs), np.full(32, pad_m), 10)


def test_roofline_share():
    # bound by operations: 197e12 flops take 1 s at peak
    assert work.roofline_share(197e12, 1.0, 2.0, PEAK) == pytest.approx(50.0)
    # bound by bytes: 819e9 bytes take 1 s
    assert work.roofline_share(1.0, 819e9, 4.0, PEAK) == pytest.approx(25.0)
    # four chips share the work
    assert work.roofline_share(4 * 197e12, 1.0, 1.0, PEAK, chips=4) == \
        pytest.approx(100.0)
    assert work.roofline_share(1.0, 1.0, 0.0, PEAK) is None
