"""Checkpoint layer: roundtrip, atomicity, keep-k GC, elastic restore."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import (
    CheckpointManager, load_checkpoint, restore_train_state, save_checkpoint,
)
from repro.ckpt.checkpoint import latest_checkpoint
from repro.optim import adam_init


def make_state(seed=0):
    k = jax.random.key(seed)
    params = {
        "a": jax.random.normal(k, (4, 8), jnp.float32),
        "nested": {"w": jax.random.normal(k, (3, 3), jnp.bfloat16)},
    }
    return {"params": params, "opt": adam_init(params),
            "step": jnp.int32(7)}


def assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype, (x.dtype, y.dtype)
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_roundtrip(tmp_path):
    state = make_state()
    path = save_checkpoint(str(tmp_path), 7, state, extras={"stream": {"i": 3}})
    restored, manifest = restore_train_state(path, state)
    assert manifest["step"] == 7
    assert manifest["extras"]["stream"]["i"] == 3
    assert_tree_equal(state, restored)


def test_bf16_preserved(tmp_path):
    state = make_state()
    path = save_checkpoint(str(tmp_path), 1, state)
    flat, _ = load_checkpoint(path)
    assert flat["params.nested.w"].dtype == jnp.bfloat16


def test_latest_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = make_state()
    for step in (1, 2, 3, 4):
        mgr.save(step, state, block=True)
    kept = sorted(os.listdir(tmp_path))
    assert kept == ["step_00000003", "step_00000004"], kept
    assert latest_checkpoint(str(tmp_path)).endswith("step_00000004")


def test_async_save_then_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = make_state()
    mgr.save(10, state)            # async
    mgr.save(11, state)            # waits for 10, then async 11
    mgr.close()
    names = sorted(os.listdir(tmp_path))
    assert names == ["step_00000010", "step_00000011"], names


def test_elastic_restore_new_sharding(tmp_path):
    """Restore works with device_put onto a (different) sharding tree."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    state = make_state()
    path = save_checkpoint(str(tmp_path), 3, state)
    from repro.launch.mesh import make_worker_mesh

    mesh = make_worker_mesh(1)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    restored, _ = restore_train_state(path, state, sh)
    assert_tree_equal(state, restored)


def test_crash_mid_save_leaves_no_partial(tmp_path):
    """A .tmp directory must never be visible as a valid checkpoint."""
    state = make_state()
    save_checkpoint(str(tmp_path), 1, state)
    os.makedirs(tmp_path / "step_00000002.tmp0/")  # simulated dead save
    assert latest_checkpoint(str(tmp_path)).endswith("step_00000001")


def test_tuning_record_roundtrip(tmp_path):
    """TuningRecord persists next to the checkpoints and reloads to the
    exact same execution choices (dict-identical), from the directory or
    the json file path; absent records read as None, newer versions
    refuse to load."""
    import pytest

    from repro.ckpt import load_tuning_record
    from repro.tuning import TuningRecord, as_record

    assert load_tuning_record(str(tmp_path)) is None
    assert TuningRecord.load(str(tmp_path)) is None

    rec = TuningRecord(
        n_buckets=4, bs_ceilings=[16, 32], m_ceilings=[30, 30],
        bs_mult=16, m_mult=128, backend="auto", precision="bf16",
        bucket_tiers=["bf16", "f64"], error_budget=None, stream_chunk=65536,
        device_cache_budget=1 << 30, occupancy=0.71,
        histogram={"bs": {"min": 3, "p50": 12, "max": 31, "mean": 13.0}},
        candidates=[{"n_buckets": 4, "precision": "bf16", "time_s": 0.01}],
        meta={"device": "cpu", "n_rows": 100000},
    )
    path = rec.save(str(tmp_path))
    assert os.path.basename(path) == "tuning_record.json"

    for src in (str(tmp_path), path):
        back = TuningRecord.load(src)
        assert back is not None and back.to_dict() == rec.to_dict()
        assert as_record(src).to_dict() == rec.to_dict()
    # a crashed write never corrupts the record: only the final name loads
    assert not any(f.endswith(".tmp") for f in os.listdir(tmp_path))

    newer = dict(rec.to_dict(), version=rec.version + 1)
    with pytest.raises(ValueError):
        TuningRecord.from_dict(newer)
    with pytest.raises(FileNotFoundError):
        as_record(str(tmp_path / "nope"))
