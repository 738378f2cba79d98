"""Packed prediction pipeline vs the exact-GP oracle and the fused kernel.

Three contracts (ISSUE acceptance criteria):
(a) when every training point is a neighbor (m_pred >= n_train) the block
    conditional IS the exact GP conditional — mean/var match exact_predict;
(b) backend='pallas' (interpret mode on CPU) matches backend='ref';
(c) identity padding is inert: dummy blocks / padded rows change nothing.
"""
import numpy as np
import pytest

from repro.core import KernelParams, exact_predict, predict_sbv
from repro.core.packing import PackedPrediction, pack_prediction
from repro.core.predict import (
    batched_block_predict, build_train_index, pack_queries, packed_predict,
)
from repro.data.gp_sim import paper_synthetic


def _setup(seed=0, n_train=60, n_test=40, d=3):
    x, y, params = paper_synthetic(seed=seed, n=max(n_train, 200), d=d)
    x, y = x[:n_train], y[:n_train]
    rng = np.random.default_rng(seed + 1)
    xt = rng.uniform(size=(n_test, d))
    return params, x, y, xt


def test_predict_matches_exact_gp_when_all_neighbors():
    params, x, y, xt = _setup()
    # m_pred >= n_train: every block conditions on the full training set.
    pred = predict_sbv(params, x, y, xt, bs_pred=8, m_pred=80, seed=0)
    em, ev = exact_predict(params, x, y, xt)
    np.testing.assert_allclose(pred.mean, np.asarray(em), atol=1e-4, rtol=0)
    np.testing.assert_allclose(pred.var, np.asarray(ev), atol=1e-4, rtol=0)


def test_predict_chunked_matches_exact_gp():
    params, x, y, xt = _setup(seed=2)
    pred = predict_sbv(params, x, y, xt, bs_pred=8, m_pred=80, seed=2,
                       chunk_size=16)
    em, ev = exact_predict(params, x, y, xt)
    np.testing.assert_allclose(pred.mean, np.asarray(em), atol=1e-4, rtol=0)
    np.testing.assert_allclose(pred.var, np.asarray(ev), atol=1e-4, rtol=0)


def test_pallas_backend_matches_ref():
    params, x, y, xt = _setup(seed=1)
    index = build_train_index(x, y, np.asarray(params.beta), 24, seed=1)
    packed = pack_queries(index, xt, bs_pred=8, m_pred=24, seed=1)
    mu_r, var_r = packed_predict(params, packed, backend="ref")
    mu_p, var_p = packed_predict(params, packed, backend="pallas")
    np.testing.assert_allclose(np.asarray(mu_p), np.asarray(mu_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(var_p), np.asarray(var_r),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_masked_padding_is_inert(backend):
    """Dummy blocks + extra padded query/neighbor slots change nothing."""
    params, x, y, xt = _setup(seed=3)
    index = build_train_index(x, y, np.asarray(params.beta), 24, seed=3)
    packed = pack_queries(index, xt, bs_pred=8, m_pred=24, seed=3)

    # Repack the same structure with wider padding + 3 dummy blocks.
    bs = packed.bs_pred
    pad = lambda a, w: np.concatenate(
        [a, np.zeros(a.shape[:1] + (w,) + a.shape[2:], dtype=a.dtype)], axis=1)
    wider = PackedPrediction(
        q_x=pad(packed.q_x, 5), q_mask=pad(packed.q_mask, 5),
        q_idx=pad(packed.q_idx, 5),
        nn_x=pad(packed.nn_x, 7), nn_y=pad(packed.nn_y, 7),
        nn_mask=pad(packed.nn_mask, 7),
        owners=packed.owners,
    ).pad_to_blocks(packed.n_blocks + 3)

    mu_a, var_a = packed_predict(params, packed, backend=backend)
    mu_b, var_b = packed_predict(params, wider, backend=backend)
    msk = packed.q_mask
    np.testing.assert_allclose(
        np.asarray(mu_b)[: packed.n_blocks, :bs][msk], np.asarray(mu_a)[msk],
        rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(var_b)[: packed.n_blocks, :bs][msk], np.asarray(var_a)[msk],
        rtol=1e-12, atol=1e-12)


def test_scatter_covers_every_test_point_once():
    params, x, y, xt = _setup(seed=4, n_test=37)
    index = build_train_index(x, y, np.asarray(params.beta), 16, seed=4)
    packed = pack_queries(index, xt, bs_pred=5, m_pred=16, seed=4)
    idx = packed.q_idx[packed.q_mask]
    assert sorted(idx.tolist()) == list(range(37))


def test_backend_and_chunking_consistent_with_loop_free_path():
    """predict_sbv with pallas backend equals ref end to end (simulation
    uses the same key stream, so sim outputs agree too)."""
    params, x, y, xt = _setup(seed=5)
    a = predict_sbv(params, x, y, xt, bs_pred=8, m_pred=32, seed=5,
                    n_sims=64, backend="ref")
    b = predict_sbv(params, x, y, xt, bs_pred=8, m_pred=32, seed=5,
                    n_sims=64, backend="pallas")
    np.testing.assert_allclose(b.mean, a.mean, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(b.var, a.var, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(b.ci_low, a.ci_low, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("backend", ["ref", "pallas", "auto"])
def test_predict_stats_record_what_ran(backend):
    """``Prediction.stats`` names the concrete program each piece ran
    (``auto`` resolved by select_backend) and times every chunk."""
    from repro.kernels.ops import select_backend

    params, x, y, xt = _setup(seed=6)
    pred = predict_sbv(params, x, y, xt, bs_pred=8, m_pred=32, seed=6,
                       n_sims=4, backend=backend, chunk_size=16)
    st = pred.stats
    assert len(st["fetch_s"]) == 3 and st["host_s"] > 0
    assert all(bs % 8 == 0 and m == 32 for _, bs, m in st["shapes"])
    want = {select_backend(8, 32, kind="predict", dtype=np.float64)
            if backend == "auto" else backend}
    assert set(st["backends"]) == want
