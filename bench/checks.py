"""The comparisons that decide ``correct``, shared by the benchmark's runs
and by ``bench/control.py`` (which reads the control and the planted
faults through the same numbers).

Fit cells (the training bullet of the benchmark's contract): the loss of
each of the first ``compared_steps`` steps, the norm of the first
gradient as the optimizer received it (worked out from Adam's first
moment after one step), and the norm of the parameters' change after
those steps, each against the plain reference that follows the same
steps from the same start. Norms are compared per leaf (log sigma^2, log
beta, log nugget): the gap between the two norms over the larger of the
reference's norm of that leaf and of the median leaf; the worst leaf
counts. The block structure is checked beside it: a partition of the
points, and neighbour sets that are the m nearest earlier points.

UQ cells: every test point of every sweep, against the reference's
conditional on the m_pred training points nearest its block's centroid.
The mean's gap in predictive standard deviations and the variance's
relative gap, worst point; the simulations by the mean over the N points
of z^2, z = (sim mean - mean) / (sd / sqrt(n_sims)), which is 1 with
standard error sqrt(2/N) for correct draws, given in standard errors so
that its limit does not depend on N. Draws of the wrong spread (too few,
or scaled by the variance) move it by tens of standard errors.
"""
from __future__ import annotations

import math

import numpy as np

import reference as ref

ADAM_B1 = 0.9


def _norms(leaves) -> list:
    return [float(np.linalg.norm(np.ravel(a))) for a in leaves]


def leaf_gap(prog, refr, keep) -> float:
    """Worst kept leaf's gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median one."""
    pn, rn = _norms(prog), _norms(refr)
    med = float(np.median(rn))
    return max(abs(p - r) / max(r, med)
               for p, r, k in zip(pn, rn, keep) if k)


def fit_gaps(losses, grad1, theta_last, r_losses, r_grad1, r_theta_last,
             theta0) -> dict:
    """The three numbers of a fit cell, program (or control) against the
    reference: losses per step, first gradient, change after the steps.
    A leaf whose reference gradient is under a thousandth of the median
    leaf's moves under Adam by round-off alone and is left out."""
    gn = _norms(r_grad1)
    keep = [g >= 1e-3 * float(np.median(gn)) for g in gn]
    d_prog = [np.asarray(a, np.float64) - b for a, b in zip(theta_last, theta0)]
    d_ref = [np.asarray(a, np.float64) - b for a, b in zip(r_theta_last, theta0)]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, r_losses)),
        "grad_norm_gap": leaf_gap(grad1, r_grad1, keep),
        "change_norm_gap": leaf_gap(d_prog, d_ref, keep),
    }


def grad_from_adam(state) -> list:
    """The gradient Adam received at its first step, from its first
    moment: mu_1 = (1 - b1) g."""
    return [np.asarray(mu, np.float64) / (1.0 - ADAM_B1) for mu in state.mu]


def fit_start(y, d: int, beta: float = 0.5, nugget: float = 1e-3) -> list:
    """The streaming fit's starting point: sigma^2 = var(y), beta and
    nugget fixed (theta in log space)."""
    return [np.log(np.var(y)), np.log(np.full(d, beta)), np.log(nugget)]


def check_structure(x, order, members, neigh, beta, m: int, rng,
                    n_check: int):
    """Partition and neighbour sets of the fit's blocks, in conditioning
    order: every point in exactly one block, and for a seeded sample of
    blocks the neighbours are the m points of earlier blocks nearest the
    block's centroid in the space scaled by ``beta``. Returns the count
    of faults of each kind."""
    mem = [np.asarray(members[b]) for b in order]
    allm = np.sort(np.concatenate(mem))
    partition_bad = int(len(allm) != len(x)
                        or not np.array_equal(allm, np.arange(len(x))))
    xs = x / beta
    wrong = 0
    ranks = rng.choice(np.arange(1, len(mem)), size=min(n_check, len(mem) - 1),
                       replace=False)
    for r in ranks:
        pool = np.concatenate(mem[:r])
        want = ref.nearest(xs[pool], pool, xs[mem[r]].mean(axis=0), m)
        got = np.sort(np.asarray(neigh[order[r]])[:m])
        wrong += int(not np.array_equal(want, got))
    return partition_bad, wrong


def uq_blocks(q_idx, q_mask):
    """Member index arrays of a chunk's prediction blocks."""
    return [q_idx[r][q_mask[r]] for r in range(q_idx.shape[0])
            if q_mask[r].any()]


def uq_reference(theta, x_train, y_train, x_test, blocks, m_pred: int, nu,
                 dtype, precision, device, chunk: int = 32):
    """Reference mean and variance of every test point in ``blocks``,
    each block conditioned on the m_pred training points nearest its
    centroid in the space scaled by beta. Returns (mean, var) over the
    test points (NaN where no block holds a point)."""
    import jax

    beta = np.exp(np.asarray(theta[1], np.float64))
    xs = x_train / beta
    ids = np.arange(len(x_train))
    bs = max(len(b) for b in blocks)
    mean = np.full(len(x_test), np.nan)
    var = np.full(len(x_test), np.nan)
    for s in range(0, len(blocks), chunk):
        part = blocks[s:s + chunk]
        q = np.zeros((chunk, bs, x_test.shape[1]))
        qm = np.zeros((chunk, bs), bool)
        nx = np.zeros((chunk, m_pred, x_test.shape[1]))
        ny = np.zeros((chunk, m_pred))
        nm = np.zeros((chunk, m_pred), bool)
        for i, b in enumerate(part):
            nn = ref.nearest(xs, ids, (x_test[b] / beta).mean(axis=0), m_pred)
            q[i, :len(b)], qm[i, :len(b)] = x_test[b], True
            nx[i, :len(nn)], ny[i, :len(nn)], nm[i, :len(nn)] = (
                x_train[nn], y_train[nn], True)
        with jax.default_device(device):
            mu, v = jax.device_get(ref.block_predict(
                tuple(theta), q, qm, nx, ny, nm, nu=nu, dtype=dtype,
                precision=precision))
        for i, b in enumerate(part):
            mean[b], var[b] = mu[i, :len(b)], v[i, :len(b)]
    return mean, var


def uq_gaps(mean, var, sim_mean, r_mean, r_var, n_sims: int) -> dict:
    """The three numbers of a UQ cell over all points."""
    sd = np.sqrt(r_var)
    z = (sim_mean - r_mean) / (sd / math.sqrt(n_sims))
    return {
        "mean_gap_sd": float(np.max(np.abs(mean - r_mean) / sd)),
        "var_gap_rel": float(np.max(np.abs(var - r_var) / r_var)),
        "sim_z2_se": float(abs(np.mean(z * z) - 1.0) / math.sqrt(2.0 / len(sd))),
    }


def sims_of(mean, var, n_sims: int, rng, sd_of_var=np.sqrt):
    """Simulation sample means drawn on the host from a conditional (for
    the planted faults): ``n_sims`` draws per point, ``sd_of_var`` turning
    a variance into the draws' scale."""
    eps = rng.standard_normal((n_sims, len(mean)))
    return (mean[None] + sd_of_var(var)[None] * eps).mean(axis=0)
