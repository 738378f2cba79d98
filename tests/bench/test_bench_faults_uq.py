"""A UQ sweep run on the CPU comes out correct, and with the timed path
broken underneath it comes out not correct: one answer altered where it
is produced, and half of the simulation draws left out."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_cpu  # noqa: E402

CELL = "metarvm.uq_sweep"


def test_sound_sweep_is_correct():
    line = bench_cpu.run_cell(CELL)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 2 * 256
    assert set(line["metrics"]) == {"setup_s", "uq_points_per_s"}


def test_altered_answer_is_caught(monkeypatch):
    import jax.numpy as jnp

    import repro.core.predict as predmod

    orig = predmod._predict_and_simulate

    def altered(*a, **k):
        mu, var, sm, ss = orig(*a, **k)
        return mu.at[0, 0].add(jnp.sqrt(var[0, 0])), var, sm, ss

    monkeypatch.setattr(predmod, "_predict_and_simulate", altered)
    line = bench_cpu.run_cell(CELL)
    assert not line["correct"]
    assert line["checks"]["mean_gap_sd"]["value"] > 0.5


def test_half_draws_are_caught(monkeypatch):
    import repro.core.predict as predmod

    orig = predmod._predict_and_simulate

    def half(*a, n_sims, **k):
        return orig(*a, n_sims=n_sims // 2, **k)

    monkeypatch.setattr(predmod, "_predict_and_simulate", half)
    line = bench_cpu.run_cell(CELL)
    assert not line["correct"]
    assert line["checks"]["sim_z2_se"]["value"] > 10
