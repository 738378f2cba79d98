"""Bucketed variable-size block execution == the uniform-padded path.

Invariants (ISSUE 3 acceptance):
(a) bucketed loglik == single-bucket ``packed_loglik`` to 1e-10 (f64),
    across skewed block-size distributions and both extremes (all blocks
    in one bucket, one block per bucket);
(b) bucketed predict == ``predict_sbv`` to 1e-10;
(c) occupancy (true FLOPs / padded FLOPs) never decreases under
    bucketing and strictly improves on a skewed distribution;
(d) pack_blocks rejects sentinel-padded neighbor lists instead of
    silently gathering them as real masked-True rows (regression);
(e) per-bucket backend dispatch resolves 'auto' sanely.
"""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (
    KernelParams, SBVConfig, bucket_blocks, bucket_prediction, packed_loglik,
    predict_sbv, preprocess,
)
from repro.core.blocks import build_blocks, scale_inputs
from repro.core.buckets import (
    BucketedBlocks, assign_buckets, bucket_ceilings, bucket_mults,
)
from repro.core.nns import filtered_nns
from repro.core.packing import pack_blocks

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

pytestmark = pytest.mark.buckets

PAR = KernelParams.create(sigma2=1.3, beta=[0.3, 0.5, 2.0], nugget=1e-2, d=3)


def skewed_data(seed=0, n_clusters=10, d=3):
    """Clustered inputs whose k-means/RAC blocks come out size-skewed."""
    rng = np.random.default_rng(seed)
    centers = rng.uniform(size=(n_clusters, d))
    sizes = rng.lognormal(3.0, 0.9, size=n_clusters).astype(int) + 5
    x = np.concatenate(
        [c + 0.04 * rng.normal(size=(s, d)) for c, s in zip(centers, sizes)]
    )
    y = rng.normal(size=x.shape[0])
    return x, y


@pytest.fixture(scope="module")
def skewed_packed():
    x, y = skewed_data()
    cfg = SBVConfig(n_blocks=20, m=25, clustering="kmeans")
    packed, blocks = preprocess(x, y, PAR.beta, cfg)
    return x, y, packed, blocks


# -- (a) likelihood equivalence ---------------------------------------

@pytest.mark.parametrize("n_buckets", [1, 2, 4, 10_000])
def test_bucketed_loglik_matches_uniform(skewed_packed, n_buckets):
    """K=1 (all blocks one bucket) through K>=bc (one block per realized
    size) all reproduce the uniform-padded likelihood."""
    _, _, packed, _ = skewed_packed
    ll_u = float(packed_loglik(PAR, packed))
    bucketed = bucket_blocks(packed, n_buckets=n_buckets)
    ll_b = float(packed_loglik(PAR, bucketed))
    np.testing.assert_allclose(ll_b, ll_u, rtol=1e-10)


def test_bucketed_loglik_tile_aligned(skewed_packed):
    """Tile-aligned ceilings (the pallas_tiled rules) stay exact."""
    _, _, packed, _ = skewed_packed
    bs_mult, m_mult = bucket_mults("pallas_tiled")
    bucketed = bucket_blocks(packed, n_buckets=4, bs_mult=bs_mult, m_mult=m_mult)
    np.testing.assert_allclose(
        float(packed_loglik(PAR, bucketed)), float(packed_loglik(PAR, packed)),
        rtol=1e-10,
    )


def test_single_bucket_is_identity(skewed_packed):
    """n_buckets=1 keeps every block in one batch at the global ceilings."""
    _, _, packed, _ = skewed_packed
    bucketed = bucket_blocks(packed, n_buckets=1)
    assert bucketed.n_buckets == 1
    assert bucketed.n_blocks == packed.n_blocks
    assert bucketed.n_points == packed.n_points
    pk = bucketed.buckets[0]
    # max true sizes, not the (possibly larger) source padding
    assert pk.bs_max == int(packed.blk_mask.sum(1).max())
    np.testing.assert_array_equal(np.sort(bucketed.ranks[0]),
                                  np.arange(packed.n_blocks))


def test_bucketed_preserves_blocks_and_points(skewed_packed):
    _, _, packed, _ = skewed_packed
    bucketed = bucket_blocks(packed, n_buckets=4)
    assert bucketed.n_blocks == packed.n_blocks
    assert bucketed.n_points == packed.n_points
    all_ranks = np.concatenate(bucketed.ranks)
    np.testing.assert_array_equal(np.sort(all_ranks), np.arange(packed.n_blocks))


# -- (b) prediction equivalence ---------------------------------------

@pytest.mark.parametrize("n_buckets", [2, 4, 10_000])
def test_bucketed_predict_matches_uniform(n_buckets):
    x, y = skewed_data(seed=3)
    rng = np.random.default_rng(4)
    xt = np.concatenate([
        rng.uniform(size=(150, 3)),
        x[:40] + 0.01 * rng.normal(size=(40, 3)),  # clustered queries: skew
    ])
    p_u = predict_sbv(PAR, x, y, xt, bs_pred=8, m_pred=40, seed=0, n_sims=2)
    p_b = predict_sbv(PAR, x, y, xt, bs_pred=8, m_pred=40, seed=0, n_sims=2,
                      n_buckets=n_buckets)
    np.testing.assert_allclose(p_b.mean, p_u.mean, atol=1e-10, rtol=0)
    np.testing.assert_allclose(p_b.var, p_u.var, atol=1e-10, rtol=0)


def test_bucketed_predict_chunked_matches_uniform():
    x, y = skewed_data(seed=5)
    xt = np.random.default_rng(6).uniform(size=(300, 3))
    p_u = predict_sbv(PAR, x, y, xt, bs_pred=8, m_pred=30, seed=1, n_sims=2,
                      chunk_size=128)
    p_b = predict_sbv(PAR, x, y, xt, bs_pred=8, m_pred=30, seed=1, n_sims=2,
                      chunk_size=128, n_buckets=4)
    np.testing.assert_allclose(p_b.mean, p_u.mean, atol=1e-10, rtol=0)
    np.testing.assert_allclose(p_b.var, p_u.var, atol=1e-10, rtol=0)


# -- (c) occupancy ----------------------------------------------------

def test_occupancy_improves_on_skew(skewed_packed):
    _, _, packed, _ = skewed_packed
    occ1 = bucket_blocks(packed, n_buckets=1).occupancy()
    occ4 = bucket_blocks(packed, n_buckets=4).occupancy()
    assert 0.0 < occ1 <= 1.0 and 0.0 < occ4 <= 1.0
    assert occ4 > occ1, (occ1, occ4)


def test_prediction_occupancy_improves():
    x, y = skewed_data(seed=7)
    from repro.core.predict import build_train_index, pack_queries

    index = build_train_index(x, y, np.asarray(PAR.beta), 30, seed=0)
    xt = np.random.default_rng(8).uniform(size=(250, 3))
    packed = pack_queries(index, xt, bs_pred=8, m_pred=30, seed=0)
    occ1 = bucket_prediction(packed, n_buckets=1).occupancy()
    occ4 = bucket_prediction(packed, n_buckets=4).occupancy()
    assert occ4 >= occ1
    assert 0.0 < occ4 <= 1.0


# -- bucket-boundary policy -------------------------------------------

def test_bucket_ceilings_cover_and_align():
    sizes = np.asarray([3, 7, 9, 20, 50, 200])
    for mult in (1, 8, 128):
        ceils = bucket_ceilings(sizes, 4, mult=mult)
        assert np.all(np.diff(ceils) > 0)
        assert ceils[-1] >= sizes.max()
        assert np.all(ceils % mult == 0)
        idx = assign_buckets(sizes, ceils)
        assert np.all(ceils[idx] >= sizes)
        # smallest admissible ceiling: the one below (if any) is too small
        prev = np.where(idx > 0, ceils[np.maximum(idx - 1, 0)], -1)
        assert np.all(prev < sizes)


def test_bucket_ceilings_uniform_sizes_collapse():
    ceils = bucket_ceilings(np.full(10, 17), 4, mult=1)
    assert ceils.tolist() == [17]


if HAVE_HYPOTHESIS:
    size_dists = st.lists(st.integers(min_value=1, max_value=60),
                          min_size=2, max_size=12)
else:  # stub strategies; tests below skip via @given
    size_dists = None


@given(sizes=size_dists, n_buckets=st.integers(min_value=1, max_value=6))
@settings(max_examples=15, deadline=None)
def test_property_bucketed_loglik_matches(sizes, n_buckets):
    """Random block-size distributions: bucketed == uniform likelihood."""
    rng = np.random.default_rng(sum(sizes) + n_buckets)
    d = 3
    x = np.concatenate([
        rng.uniform(size=(1, d)) + 0.05 * rng.normal(size=(s, d))
        for s in sizes
    ])
    y = rng.normal(size=x.shape[0])
    beta = np.asarray(PAR.beta)
    xs = scale_inputs(x, beta)
    blocks = build_blocks(xs, n_blocks=len(sizes), n_workers=1, beta=beta,
                          seed=0, method="kmeans")
    m = min(20, x.shape[0])
    neigh = filtered_nns(xs, blocks, m)
    packed = pack_blocks(x, y, blocks, neigh, m=m)
    ll_u = float(packed_loglik(PAR, packed))
    ll_b = float(packed_loglik(PAR, bucket_blocks(packed, n_buckets=n_buckets)))
    np.testing.assert_allclose(ll_b, ll_u, rtol=1e-10)


# -- (d) pack_blocks neighbor-validation regression -------------------

def test_pack_blocks_rejects_sentinel_padded_neighbors(skewed_packed):
    """A fixed-width neighbor array padded with -1 must raise, not wrap
    around to the last training point with nn_mask=True."""
    x, y, _, blocks = skewed_packed
    xs = scale_inputs(x, np.asarray(PAR.beta))
    neigh = filtered_nns(xs, blocks, 25)
    bad = list(neigh)
    short = next(i for i in range(len(bad)) if 0 < bad[i].size < 25)
    bad[short] = np.concatenate(
        [bad[short], np.full(25 - bad[short].size, -1, dtype=np.int64)]
    )
    with pytest.raises(ValueError, match="neighbor indices outside"):
        pack_blocks(x, y, blocks, bad, m=25)
    # repeat-of-last-index padding is in-range but just as corrupting:
    # duplicate conditioning rows -> near-singular covariance
    rep = list(neigh)
    rep[short] = np.concatenate(
        [rep[short], np.full(25 - rep[short].size, rep[short][-1])]
    )
    with pytest.raises(ValueError, match="duplicate neighbor indices"):
        pack_blocks(x, y, blocks, rep, m=25)


def test_pack_blocks_underfull_neighbors_masked(skewed_packed):
    """A block with fewer than m true neighbors packs a short masked row;
    the mask sum equals the true neighbor count, tail rows stay zero."""
    x, y, packed, blocks = skewed_packed
    xs = scale_inputs(x, np.asarray(PAR.beta))
    neigh = filtered_nns(xs, blocks, 25)
    for rank, b in enumerate(blocks.order):
        k = min(neigh[b].size, 25)
        assert packed.nn_mask[rank].sum() == k
        assert not packed.nn_mask[rank, k:].any()
        assert np.all(packed.nn_x[rank, k:] == 0.0)


# -- (e) backend dispatch ---------------------------------------------

def test_select_backend_policy():
    from repro.kernels.ops import select_backend

    # tile-aligned f32 predict shapes take the compiled tiled kernel
    assert select_backend(8, 128, "predict", np.float32) == "pallas_tiled"
    assert select_backend(16, 256, "predict", np.float32) == "pallas_tiled"
    # unaligned-but-big shapes use the fused kernel; small ones stay ref
    assert select_backend(25, 120, "predict", np.float64) == "pallas"
    assert select_backend(4, 16, "predict", np.float32) == "ref"
    # the loglik kernel has no tiled variant; big shapes go fused, small ref
    assert select_backend(16, 128, "loglik", np.float32) == "pallas"
    assert select_backend(2, 8, "loglik", np.float64) == "ref"
    # bf16-assembly buckets tile at the doubled (16, 128) sublane: 8-row
    # f32-aligned shapes are NOT tiled-eligible at bf16, 16-row ones are
    import jax.numpy as jnp
    assert select_backend(8, 256, "predict", jnp.bfloat16) == "pallas"
    assert select_backend(16, 128, "predict", jnp.bfloat16) == "pallas_tiled"
    assert select_backend(32, 256, "predict", jnp.bfloat16) == "pallas_tiled"
    # f64 never takes the compiled tiled kernel, whatever the alignment
    assert select_backend(8, 256, "predict", np.float64) == "pallas"
    assert select_backend(16, 128, "predict", np.float64) == "pallas"
    # bf16 loglik has no tiled variant either; sizes route as usual
    assert select_backend(16, 128, "loglik", jnp.bfloat16) == "pallas"
    assert select_backend(4, 8, "loglik", jnp.bfloat16) == "ref"


def test_select_backend_keeps_f64_off_compiled_kernels(monkeypatch):
    """On an accelerator the compiled kernels take f32/bf16 only, so f64
    (and an unknown dtype) resolves to ref at any shape; f32/bf16 keep
    the CPU routing."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import select_backend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for kind in ("predict", "loglik"):
        assert select_backend(100, 400, kind, np.float64) == "ref"
        assert select_backend(16, 128, kind, np.float64) == "ref"
        assert select_backend(100, 400, kind) == "ref"
    assert select_backend(100, 400, "loglik", np.float32) == "pallas"
    assert select_backend(25, 120, "predict", np.float32) == "pallas"
    assert select_backend(8, 128, "predict", np.float32) == "pallas_tiled"
    assert select_backend(16, 128, "predict", jnp.bfloat16) == "pallas_tiled"


def test_packed_loglik_pallas_backend_per_bucket(skewed_packed):
    """Bucketed execution with the fused kernel matches ref per bucket."""
    _, _, packed, _ = skewed_packed
    bucketed = bucket_blocks(packed, n_buckets=3)
    ll_ref = float(packed_loglik(PAR, bucketed, backend="ref"))
    ll_pal = float(packed_loglik(PAR, bucketed, backend="pallas"))
    np.testing.assert_allclose(ll_pal, ll_ref, rtol=1e-6)


# -- distributed work-balanced sharding -------------------------------

def test_bucket_sharding_balances_true_work(skewed_packed):
    """Per-bucket equal-count splits give every shard an equal slice of
    every bucket, so per-shard TRUE work (Sigma bs*(bs+m)^2) is balanced
    to within a bucket's geometric width — unlike an equal-count split of
    the uniform layout, where one shard can end up holding the outliers."""
    from repro.core.buckets import block_flops
    from repro.core.distributed import shard_blocks_by_owner

    _, _, packed, _ = skewed_packed
    n_workers = 4

    def shard_loads(pieces):
        loads = np.zeros(n_workers)
        for pk in pieces:
            pk = shard_blocks_by_owner(pk, n_workers)
            per_shard = pk.n_blocks // n_workers
            w = block_flops(pk.blk_mask.sum(1), pk.nn_mask.sum(1))
            for p in range(n_workers):
                loads[p] += float(w[p * per_shard:(p + 1) * per_shard].sum())
        return loads

    # Sort blocks by size so the uniform contiguous split is maximally
    # skewed (the adversarial case bucket-by-bucket sharding defuses).
    order = np.argsort(packed.blk_mask.sum(1))
    sorted_packed = type(packed)(
        blk_x=packed.blk_x[order], blk_y=packed.blk_y[order],
        blk_mask=packed.blk_mask[order], nn_x=packed.nn_x[order],
        nn_y=packed.nn_y[order], nn_mask=packed.nn_mask[order],
        owners=packed.owners[order],
    )
    uniform_loads = shard_loads([sorted_packed])
    bucket_loads = shard_loads(bucket_blocks(sorted_packed, n_buckets=4).buckets)
    imbalance = lambda l: l.max() / max(l.mean(), 1.0)
    assert imbalance(bucket_loads) < imbalance(uniform_loads), (
        bucket_loads, uniform_loads)


@pytest.mark.slow
def test_distributed_bucketed_matches_serial():
    """Bucket-by-bucket sharded loglik == serial, in a subprocess with 8
    virtual devices (same pattern as test_distributed_gp)."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax
        from repro.core import KernelParams, SBVConfig, preprocess, bucket_blocks
        from repro.core.vecchia import packed_loglik
        from repro.core.distributed import (
            distributed_bucketed_loglik, distributed_neg_loglik_fn,
        )
        from repro.data.gp_sim import paper_synthetic

        assert jax.device_count() == 8, jax.device_count()
        mesh = jax.make_mesh((8,), ("workers",))
        x, y, params = paper_synthetic(seed=0, n=400, d=4)
        cfg = SBVConfig(n_blocks=48, m=20, n_workers=8, seed=0)
        packed, _ = preprocess(x, y, np.asarray(params.beta), cfg)
        bucketed = bucket_blocks(packed, n_buckets=4)

        ll_serial = float(packed_loglik(params, packed))
        ll_dist = float(distributed_bucketed_loglik(params, bucketed, mesh))
        np.testing.assert_allclose(ll_dist, ll_serial, rtol=1e-10)

        loss = distributed_neg_loglik_fn(bucketed, 3.5, mesh)
        np.testing.assert_allclose(
            float(loss(params)), -ll_serial / packed.n_points, rtol=1e-10)
        print("BUCKET_DIST_OK")
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BUCKET_DIST_OK" in out.stdout


# -- fit re-buckets per structure refresh -----------------------------

def test_fit_sbv_bucketed_smoke():
    x, y = skewed_data(seed=9, n_clusters=6)
    from repro.core.fit import fit_sbv

    res = fit_sbv(x, y, SBVConfig(n_blocks=8, m=15), inner_steps=4,
                  outer_rounds=2, n_buckets=3)
    losses = [h[2] for h in res.history]
    assert losses[-1] < losses[0]
    assert isinstance(res.packed, BucketedBlocks)  # re-bucketed each refresh


# -- mixed-precision ladder (docs/precision.md) -----------------------

tuning = pytest.mark.tuning


@tuning
def test_cast_packed_dtype_contract(skewed_packed):
    """Tier cast touches coordinates (storage) and observations (acc)
    only; boolean masks and integer owners pass through untouched."""
    import jax.numpy as jnp
    from repro.core.buckets import acc_dtype, cast_packed, storage_dtype

    _, _, packed, _ = skewed_packed
    for tier in ("bf16", "f32", "f64"):
        pk = cast_packed(packed, tier)
        assert pk.blk_x.dtype == storage_dtype(tier)
        assert pk.nn_x.dtype == storage_dtype(tier)
        assert pk.blk_y.dtype == acc_dtype(tier)
        assert pk.nn_y.dtype == acc_dtype(tier)
        np.testing.assert_array_equal(pk.blk_mask, packed.blk_mask)
        np.testing.assert_array_equal(pk.owners, packed.owners)
    assert storage_dtype("bf16") == jnp.bfloat16
    assert acc_dtype("bf16") == jnp.float32


@tuning
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_ladder_nll_within_tier_budget(skewed_packed, backend):
    """Per-bucket nll at each bucket's PROBED rung stays inside that
    rung's documented error budget relative to the f64 reference — the
    deployed-ladder contract ``assign_precision`` enforces by demotion
    (docs/precision.md). Checked independently of the probe here: the
    assigned tiers are re-evaluated bucket by bucket."""
    from repro.core.buckets import (
        PrecisionPolicy, assign_precision, cast_packed,
    )

    _, _, packed, _ = skewed_packed
    bucketed = bucket_blocks(packed, n_buckets=3)
    for want in ("bf16", "f32"):
        pol = PrecisionPolicy(tier=want)
        tiers = assign_precision(PAR, bucketed, pol, backend=backend)
        assert len(tiers) == len(bucketed.buckets)
        for pk, tier in zip(bucketed.buckets, tiers):
            ll_ref = float(packed_loglik(PAR, cast_packed(pk, "f64"),
                                         backend=backend))
            ll_t = float(packed_loglik(PAR, cast_packed(pk, tier),
                                       backend=backend))
            rel = abs(ll_t - ll_ref) / max(1.0, abs(ll_ref))
            assert np.isfinite(ll_t)
            assert rel <= pol.budget_for(tier), (want, tier, backend, rel)


@tuning
def test_assign_precision_demotes_over_budget(skewed_packed):
    """A vanishing budget forces every bucket down to f64; a loose one
    keeps the requested rung. Tiers align with the bucket list."""
    from repro.core.buckets import (
        PrecisionPolicy, apply_precision, assign_precision,
    )

    _, _, packed, _ = skewed_packed
    bucketed = bucket_blocks(packed, n_buckets=3)
    strict = assign_precision(
        PAR, bucketed, PrecisionPolicy(tier="bf16", error_budget=0.0))
    assert strict == ["f64"] * len(bucketed.buckets)
    loose = assign_precision(
        PAR, bucketed, PrecisionPolicy(tier="bf16", error_budget=1.0))
    assert loose == ["bf16"] * len(bucketed.buckets)
    mixed = apply_precision(bucketed, loose)
    ll = float(packed_loglik(PAR, mixed))
    assert np.isfinite(ll)


@tuning
def test_precision_fit_and_predict_mspe(skewed_packed):
    """bf16-assembly end to end: the fit converges with per-bucket
    probed tiers and prediction MSPE stays within the tier's budget of
    the f64 prediction."""
    from repro.core.fit import fit_sbv

    x, y, _, _ = skewed_packed
    cfg = SBVConfig(n_blocks=12, m=15)
    res = fit_sbv(x, y, cfg, inner_steps=4, outer_rounds=1, n_buckets=3,
                  precision="bf16")
    losses = [h[2] for h in res.history]
    assert losses[-1] < losses[0]
    assert res.precision_tiers is not None
    assert set(res.precision_tiers) <= {"bf16", "f32", "f64"}

    rng = np.random.default_rng(11)
    xt = rng.uniform(x.min(0), x.max(0), size=(120, x.shape[1]))
    p64 = predict_sbv(res.params, x, y, xt, bs_pred=10, m_pred=30, n_sims=2)
    p16 = predict_sbv(res.params, x, y, xt, bs_pred=10, m_pred=30, n_sims=2,
                      precision="bf16")
    assert np.all(np.isfinite(p16.mean)) and np.all(p16.var > 0)
    scale = float(np.sqrt(np.mean(p64.mean ** 2))) + 1e-12
    rel = float(np.sqrt(np.mean((p16.mean - p64.mean) ** 2))) / scale
    assert rel < 0.1, rel  # bf16 coords round at ~4e-3; keep headroom


@tuning
def test_autotune_record_reproduces_choices(tmp_path):
    """The autotuner's persisted record reloads to the same execution
    choices (ISSUE acceptance: TuningRecord reproduces choices on
    reload) and drives fit_sbv without re-measuring."""
    from repro.core.fit import fit_sbv
    from repro.tuning import TuningRecord, as_record, autotune_loglik

    x, y = skewed_data(seed=5, n_clusters=5)
    cfg = SBVConfig(n_blocks=10, m=12)
    rec = autotune_loglik(x, y, cfg, params=PAR, bucket_grid=(0, 2),
                          tiers=("bf16", "f64"), repeats=1,
                          save_dir=str(tmp_path))
    back = TuningRecord.load(str(tmp_path))
    assert back.to_dict() == rec.to_dict()
    assert (back.n_buckets, back.precision, back.bucket_tiers) == \
        (rec.n_buckets, rec.precision, rec.bucket_tiers)
    assert len(rec.candidates) == 4  # 2 bucket levels x 2 tiers measured
    assert as_record(str(tmp_path)).to_dict() == rec.to_dict()

    res = fit_sbv(x, y, cfg, inner_steps=3, outer_rounds=1,
                  tuning=str(tmp_path))
    losses = [h[2] for h in res.history]
    assert losses[-1] < losses[0]
