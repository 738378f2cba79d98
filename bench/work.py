"""The algorithm's operations and bytes, from the true block sizes.

These are the numerators of every roofline share. They count what the
SBV algorithm needs, not what an implementation runs: each block counts
at its true member count ``bs`` and true neighbour count ``m`` (mask
counts), never at a padded or tiled shape, and a Cholesky counts its
n^3/3 however it is computed. A change that pads to tiles, recomputes a
factor, or fuses a backward pass then moves time and not work.

Terms per Vecchia block, n = bs + m points over d inputs:

* covariance assembly of the lower triangle, n (n + 1) / 2 entries, each
  ``3 d`` operations for the scaled squared distance (subtract, scale,
  accumulate per input) plus ``MATERN_OPS`` for sqrt, exp and the
  polynomial of the half-integer Matérn;
* the joint Cholesky, n^3 / 3;
* the forward substitution of the observations, n^2;
* the log-determinant and quadratic form over the block's own rows, 2 bs.

The likelihood gradient is fixed at ``GRAD_FACTOR`` times the forward, so
that a fused or reused backward is measured against the same work.

A prediction block of ``bs`` query points on ``m`` neighbours:

* assembly of the m x m conditioning covariance (lower triangle) and the
  m x bs cross covariance;
* the Cholesky, m^3 / 3; the triangular solves of the cross covariance
  and of the observations, m^2 bs + m^2;
* the conditional means and variances, 2 m bs each;
* the conditional simulation: per draw and query point a scale and shift
  and the two moment sums, ``SIM_OPS`` (the random bits are not counted).

Bytes are the least the device must read and write in float32: every
input coordinate, observation and mask once, and the outputs once.
"""
from __future__ import annotations

import numpy as np

MATERN_OPS = 10
GRAD_FACTOR = 2.0
SIM_OPS = 4
F32 = 4


def _assembly(pairs, d):
    return pairs * (3 * d + MATERN_OPS)


def loglik_forward_flops(bs, m, d) -> float:
    """Operations of the block log-likelihood, summed over blocks."""
    bs = np.asarray(bs, dtype=np.float64)
    n = bs + np.asarray(m, dtype=np.float64)
    per = _assembly(n * (n + 1) / 2, d) + n ** 3 / 3 + n ** 2 + 2 * bs
    return float(np.sum(per))


def loglik_bytes(bs, m, d) -> float:
    """Bytes read (coordinates, observation, mask per point) and written
    (one value per block) by the block log-likelihood."""
    n = np.asarray(bs, dtype=np.float64) + np.asarray(m, dtype=np.float64)
    return float(np.sum(n * (d + 2) * F32 + F32))


def fit_step_flops(bs, m, d) -> float:
    """Forward plus gradient of one likelihood step over all blocks."""
    return (1.0 + GRAD_FACTOR) * loglik_forward_flops(bs, m, d)


def fit_step_bytes(bs, m, d, n_params) -> float:
    """Inputs read by the forward and again by the gradient, plus one
    gradient per parameter."""
    return 2 * loglik_bytes(bs, m, d) + n_params * F32


def predict_flops(bs, m, d, n_sims) -> float:
    """Operations of the block conditionals and their simulations."""
    bs = np.asarray(bs, dtype=np.float64)
    m = np.asarray(m, dtype=np.float64)
    per = (_assembly(m * (m + 1) / 2 + m * bs, d) + m ** 3 / 3
           + m ** 2 * bs + m ** 2 + 4 * m * bs + SIM_OPS * n_sims * bs)
    return float(np.sum(per))


def predict_bytes(bs, m, d) -> float:
    """Inputs read once (query and neighbour coordinates and masks,
    neighbour observations) and four outputs per query point written."""
    bs = np.asarray(bs, dtype=np.float64)
    m = np.asarray(m, dtype=np.float64)
    return float(np.sum((bs * (d + 1) + m * (d + 2) + 4 * bs) * F32))


def roofline_share(flops: float, nbytes: float, seconds: float, peak: dict,
                   chips: int = 1) -> float | None:
    """100 x the least time the chips could take for the work, over
    ``seconds``: the larger of operations over the peak rate and bytes
    over the peak bandwidth, the work spread over ``chips``. None where
    no time was measured."""
    if not seconds or seconds <= 0:
        return None
    t_min = max(flops / (chips * peak["flops_per_s"]),
                nbytes / (chips * peak["hbm_bytes_per_s"]))
    return 100.0 * t_min / seconds
