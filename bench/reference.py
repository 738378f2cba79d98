"""Plain reference of the SBV likelihood, its gradient, Adam, and the
block prediction, written from the paper's equations with nothing taken
from the program under test.

Vecchia block likelihood (paper Eq. 2, joint form): for block B with
conditioning set N, the log-density of y_B given y_N is

    log N([y_N; y_B]; 0, K) - log N(y_N; 0, K_NN)
      = -bs/2 log 2pi - sum_{i in B} log L_ii - 1/2 sum_{i in B} v_i^2,

with K = sigma^2 Matern_nu(r) + nugget I over [N; B], r^2 = sum_k
((x_k - x'_k) / beta_k)^2, L = chol(K) and v = L^-1 [y_N; y_B]. The fit
minimises -sum_B log p(y_B | y_N) / n over theta = (log sigma^2, log beta,
log nugget) by Adam.

Block prediction (paper Eq. 3): for query points Q on neighbours N,
mean = K_QN K_NN^-1 y_N and var = sigma^2 + nugget - diag(K_QN K_NN^-1
K_NQ), with K_NN including the nugget.

Padding is exact: a padded row has unit diagonal, no covariance and a
zero observation, so it adds nothing. Every function takes the compute
dtype and the matmul precision, so that the same code gives the
reference and the lower-precision control.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LOG2PI = math.log(2.0 * math.pi)


def matern(r, nu: float):
    polys = {0.5: lambda r: 1.0, 1.5: lambda r: 1.0 + r,
             2.5: lambda r: 1.0 + r + r * r / 3.0,
             3.5: lambda r: 1.0 + r + 0.4 * r * r + r * r * r / 15.0}
    return polys[nu](r) * jnp.exp(-r)


def _cov(theta, xa, xb, ma, mb, nu, same: bool):
    """Covariance of two padded point sets; ``same`` adds the nugget on
    the diagonal and a unit diagonal on padded rows."""
    log_s2, log_beta, log_nug = theta
    za = xa / jnp.exp(log_beta)
    zb = xb / jnp.exp(log_beta)
    d2 = jnp.sum((za[:, None, :] - zb[None, :, :]) ** 2, axis=-1)
    pair = ma[:, None] & mb[None, :]
    if same:
        eye = jnp.eye(xa.shape[0], dtype=bool)
        # sqrt has no derivative at 0: keep r off the diagonal only
        r = jnp.sqrt(jnp.where(eye | ~pair, 1.0, d2))
        k = jnp.where(eye, 1.0, matern(r, nu)) * jnp.exp(log_s2)
        k = jnp.where(pair, k, 0.0)
        diag = jnp.where(ma, jnp.exp(log_nug), 1.0)
        return k + jnp.diag(diag)
    r = jnp.sqrt(jnp.where(pair, d2, 1.0))
    return jnp.where(pair, jnp.exp(log_s2) * matern(r, nu), 0.0)


def _block_ll(theta, x, y, mask, is_blk, nu):
    k = _cov(theta, x, x, mask, mask, nu, same=True)
    chol = jnp.linalg.cholesky(k)
    v = jax.scipy.linalg.solve_triangular(chol, jnp.where(mask, y, 0.0),
                                          lower=True)
    own = is_blk & mask
    return (-0.5 * LOG2PI * jnp.sum(own)
            - jnp.sum(jnp.where(own, jnp.log(jnp.diag(chol)), 0.0))
            - 0.5 * jnp.sum(jnp.where(own, v * v, 0.0)))


@partial(jax.jit, static_argnames=("nu", "dtype", "precision"))
def batch_loglik_grad(theta, x, y, mask, is_blk, *, nu, dtype, precision):
    """Summed log-likelihood of a batch of padded joint blocks and its
    gradient in theta; x (B, n, d), y/mask/is_blk (B, n)."""
    def total(th):
        th = tuple(jnp.asarray(t).astype(dtype) for t in th)
        per = jax.vmap(lambda a, b, c, e: _block_ll(th, a, b, c, e, nu))(
            x.astype(dtype), y.astype(dtype), mask, is_blk)
        return jnp.sum(per.astype(jnp.float32))

    with jax.default_matmul_precision(precision):
        return jax.value_and_grad(total)(theta)


class JointBlocks:
    """The fit's blocks in the reference's own padded joint layout,
    [neighbours; members] per block, made from the raw data and the
    block and neighbour index sets, batched ``batch`` blocks at a time."""

    def __init__(self, x, y, members, neighbours, m: int, batch: int = 64,
                 device=None):
        # the largest block rounded up to 64 rows, so that most seeds share
        # one compiled shape (padding adds nothing to the likelihood)
        bs_max = -(-max(len(b) for b in members) // 64) * 64
        n_pad = m + bs_max
        nb = len(members)
        n_batches = -(-nb // batch)
        d = x.shape[1]
        xs = np.zeros((n_batches * batch, n_pad, d), np.float32)
        ys = np.zeros((n_batches * batch, n_pad), np.float32)
        mk = np.zeros((n_batches * batch, n_pad), bool)
        for i, (mem, nn) in enumerate(zip(members, neighbours)):
            nn = np.asarray(nn)[:m]
            rows = np.concatenate([nn, np.asarray(mem)])
            pos = np.concatenate([np.arange(len(nn)),
                                  m + np.arange(len(mem))])
            xs[i, pos] = x[rows]
            ys[i, pos] = y[rows]
            mk[i, pos] = True
        is_blk = np.zeros(n_pad, bool)
        is_blk[m:] = True
        put = (lambda a: jax.device_put(a, device)) if device else jax.device_put
        self.batches = [tuple(put(a[s:s + batch]) for a in (xs, ys, mk))
                        for s in range(0, n_batches * batch, batch)]
        self.is_blk = put(np.broadcast_to(is_blk, (batch, n_pad)).copy())
        sizes = np.zeros(n_batches * batch, np.int64)
        sizes[:nb] = [len(b) for b in members]
        self.batch_points = sizes.reshape(n_batches, batch).sum(axis=1)
        self.n_points = int(sizes.sum())

    def nll_grad(self, theta, nu, dtype, precision, keep=None,
                 over_kept=False):
        """(-loglik / n, gradient) at theta, accumulated in float64, over
        the batches that ``keep(i, n_batches)`` selects (all by default);
        n counts the kept points where ``over_kept``, else all."""
        kept = [i for i in range(len(self.batches))
                if keep is None or keep(i, len(self.batches))]
        n = (int(self.batch_points[kept].sum()) if over_kept
             else self.n_points)
        loss, grad = 0.0, [np.zeros(np.shape(t)) for t in theta]
        for i in kept:
            x, y, mk = self.batches[i]
            v, g = batch_loglik_grad(tuple(theta), x, y, mk, self.is_blk,
                                     nu=nu, dtype=dtype, precision=precision)
            v, g = jax.device_get((v, g))
            loss -= float(v) / n
            grad = [a - np.asarray(b, np.float64) / n
                    for a, b in zip(grad, g)]
        return loss, grad


def adam(theta, grads, state, lr: float, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam step with float32 moments and update, the parameters
    kept in float64 after a float32 update."""
    step, mu, nu = state
    step += 1
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    new_t, new_m, new_v = [], [], []
    for p, g, m, v in zip(theta, grads, mu, nu):
        g32 = np.asarray(g, np.float32)
        m = np.float32(b1) * m + np.float32(1.0 - b1) * g32
        v = np.float32(b2) * v + np.float32(1.0 - b2) * g32 * g32
        upd = (m / np.float32(bc1)) / (np.sqrt(v / np.float32(bc2))
                                       + np.float32(eps))
        p32 = np.asarray(p, np.float32) - np.float32(lr) * upd
        new_t.append(p32.astype(np.float64))
        new_m.append(m)
        new_v.append(v)
    return new_t, (step, new_m, new_v)


def adam_init(theta):
    zeros = [np.zeros(np.shape(t), np.float32) for t in theta]
    return (0, zeros, [z.copy() for z in zeros])


def follow(blocks: JointBlocks, theta0, steps: int, lr: float, nu, dtype,
           precision, keep=None, over_kept=False):
    """The reference fit's first ``steps`` steps from theta0: the loss at
    each step, the first gradient, and the parameters after the last."""
    theta = [np.asarray(t, np.float64) for t in theta0]
    state = adam_init(theta)
    losses, first_grad = [], None
    for _ in range(steps):
        loss, grad = blocks.nll_grad(theta, nu, dtype, precision, keep,
                                     over_kept)
        losses.append(loss)
        if first_grad is None:
            first_grad = grad
        theta, state = adam(theta, grad, state, lr)
    return losses, first_grad, theta


@partial(jax.jit, static_argnames=("nu", "dtype", "precision"))
def block_predict(theta, xq, qmask, xn, yn, nmask, *, nu, dtype, precision):
    """Conditional mean and variance of padded query blocks,
    xq (B, bs, d) and xn (B, m, d)."""

    th = tuple(jnp.asarray(t).astype(dtype) for t in theta)

    def one(q, qm, n, y, nm):
        k_nn = _cov(th, n, n, nm, nm, nu, same=True)
        k_nq = _cov(th, n, q, nm, qm, nu, same=False)
        chol = jnp.linalg.cholesky(k_nn)
        a = jax.scipy.linalg.solve_triangular(chol, k_nq, lower=True)
        z = jax.scipy.linalg.solve_triangular(chol, jnp.where(nm, y, 0.0),
                                              lower=True)
        prior = jnp.exp(th[0]) + jnp.exp(th[2])
        return a.T @ z, prior - jnp.sum(a * a, axis=0)

    with jax.default_matmul_precision(precision):
        return jax.vmap(one)(xq.astype(dtype), qmask, xn.astype(dtype),
                             yn.astype(dtype), nmask)


def nearest(x_scaled_pool, pool_ids, center, m: int) -> np.ndarray:
    """The ``m`` points of the pool nearest to ``center`` (brute force,
    squared distance in the scaled space), as a sorted id array."""
    d2 = np.sum((x_scaled_pool - center) ** 2, axis=1)
    k = min(m, len(pool_ids))
    idx = np.argpartition(d2, k - 1)[:k] if k < len(pool_ids) else np.arange(k)
    return np.sort(pool_ids[idx])
