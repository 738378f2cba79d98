"""The numbers that decide ``correct``, on inputs with known answers."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))
import checks  # noqa: E402


def test_fit_gaps_per_leaf_and_round_off_leaves():
    theta0 = [np.float64(0.0), np.zeros(3), np.float64(0.0)]
    grad = [np.float64(2.0), np.array([1.0, 1.0, 1.0]), np.float64(1e-9)]
    ref_theta = [np.float64(0.15), np.full(3, 0.15), np.float64(0.0)]
    # the nugget leaf's reference gradient is a round-off 1e-9: its change
    # (here 0.15 against 0) is left out by the rule, not by name
    prog_theta = [np.float64(0.15), np.full(3, 0.15), np.float64(0.15)]
    gaps = checks.fit_gaps([1.0, 0.9], grad, prog_theta, [1.0, 0.9001],
                           grad, ref_theta, theta0)
    assert gaps["loss_gap"] == pytest.approx(1e-4)
    assert gaps["grad_norm_gap"] == 0.0
    assert gaps["change_norm_gap"] == 0.0
    # a state left unchanged reads 1
    assert checks.fit_gaps([1.0], grad, theta0, [1.0], grad, ref_theta,
                           theta0)["change_norm_gap"] == pytest.approx(1.0)


def test_grad_from_adam_first_moment():
    class State:
        mu = [np.float32(0.1), np.float32(-0.2)]
    assert checks.grad_from_adam(State) == pytest.approx([1.0, -2.0])


def test_uq_gaps_sound_and_faulty_draws():
    rng = np.random.default_rng(0)
    n, sims = 20000, 1000
    mean = rng.standard_normal(n)
    var = rng.uniform(0.5, 2.0, n)
    gaps = lambda s: checks.uq_gaps(mean, var, s, mean, var, sims)
    sound = gaps(checks.sims_of(mean, var, sims, rng))
    assert sound["mean_gap_sd"] == 0.0 and sound["var_gap_rel"] == 0.0
    assert sound["sim_z2_se"] < 4.5
    # half of the draws, and draws 10% too wide, each move mean z^2 past the
    # limit of ten standard errors
    assert gaps(checks.sims_of(mean, var, sims // 2, rng))["sim_z2_se"] > 50
    assert gaps(checks.sims_of(
        mean, var, sims, rng,
        sd_of_var=lambda v: 1.10 * np.sqrt(v)))["sim_z2_se"] > 10
