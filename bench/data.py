"""Seeded data of the benchmark's deployments, made on the host.

Copies of the program's generators (``repro.data.gp_sim``), kept here so
that the yardstick does not move when the program's copy does:

* ``metarvm``: the paper's §6.3 MetaRVM respiratory-disease simulator, a
  deterministic S/V/E/P/A/I/H/R daily-step model over the 10 Table-4
  parameters; the output is accumulated hospitalisations over 100 days,
  normalised to mean 1.
* ``paper_synthetic``: the paper's §6.1/Fig. 9 problem, x ~ U[0,1]^10 and
  one draw of a Matérn-3.5 GP with beta = (0.05, 0.05, 5, ..., 5),
  sigma^2 = 1, made by random Fourier features in row chunks that share
  one set of features (one realisation, not independent pieces). The
  random draws are made on the host; the n x 4096 feature projection,
  the bulk of the work, runs on the default device in float32 (one
  compiled program over fixed row chunks), so the same seed gives the
  same data on the same platform.

Every generator takes a ``numpy.random.Generator``-compatible seed (any
non-negative int) and returns ``(x (n, d), y (n,))`` in float64.
"""
from __future__ import annotations

import numpy as np

METARVM_BOUNDS = {
    "ts": (0.1, 0.9), "tv": (0.1, 0.9), "dv": (30.0, 90.0), "de": (1.0, 5.0),
    "dp": (1.0, 3.0), "da": (1.0, 9.0), "ds": (1.0, 9.0), "dh": (1.0, 5.0),
    "dr": (30.0, 90.0), "ve": (0.3, 0.8),
}


def _metarvm_simulate(theta: np.ndarray, days: int = 100) -> np.ndarray:
    ts, tv, dv, de, dp, da, ds, dh, dr, ve = (theta[:, i] for i in range(10))
    nb = theta.shape[0]
    contact, p_asym, p_hosp, vax_rate = 0.55, 0.4, 0.12, 0.01
    s = np.full(nb, 0.989)
    v = np.zeros(nb)
    e = np.full(nb, 0.001)
    p = np.zeros(nb)
    a = np.zeros(nb)
    i_ = np.full(nb, 0.01)
    h = np.zeros(nb)
    r = np.zeros(nb)
    cum_h = np.zeros(nb)
    for _ in range(days):
        infectious = p + a + i_
        foi_s = 1.0 - np.exp(-contact * ts * infectious)
        foi_v = 1.0 - np.exp(-contact * tv * (1.0 - ve) * infectious)
        new_e = s * foi_s + v * foi_v
        e_out, p_out, a_out = e / de, p / dp, a / da
        i_out, h_out, r_out = i_ / ds, h / dh, r / dr
        v_wane = v / dv
        new_v = vax_rate * s
        new_h = p_hosp * i_out
        s = s - s * foi_s - new_v + r_out + v_wane
        v = v + new_v - v * foi_v - v_wane
        e = e + new_e - e_out
        p = p + e_out - p_out
        a = a + p_asym * p_out - a_out
        i_ = i_ + (1.0 - p_asym) * p_out - i_out
        h = h + new_h - h_out
        r = r + a_out + (1.0 - p_hosp) * i_out + h_out - r_out
        cum_h = cum_h + new_h
    return cum_h


def metarvm(seed: int, n: int):
    """MetaRVM inputs scaled to the unit cube and the normalised output."""
    rng = np.random.default_rng(seed)
    lo = np.array([b[0] for b in METARVM_BOUNDS.values()])
    hi = np.array([b[1] for b in METARVM_BOUNDS.values()])
    theta = lo + (hi - lo) * rng.uniform(size=(n, 10))
    y = _metarvm_simulate(theta)
    return (theta - lo) / (hi - lo), y / max(y.mean(), 1e-12)


def paper_synthetic(seed: int, n: int, d: int = 10, gen_rows: int = 16384,
                    n_features: int = 4096):
    """One Matérn-3.5 GP realisation over U[0,1]^d by random Fourier
    features (the spectral measure of Matérn-nu is a Student-t with 2 nu
    degrees of freedom), generated ``gen_rows`` at a time."""
    rng = np.random.default_rng(seed)
    nu, sigma2, nugget = 3.5, 1.0, 1e-8
    beta = np.full(d, 5.0)
    beta[:2] = 0.05
    z = rng.standard_normal((n_features, d))
    g = rng.gamma(shape=nu, scale=1.0 / nu, size=(n_features, 1))
    omega = z / np.sqrt(g) / beta[None, :]
    phase = rng.uniform(0.0, 2.0 * np.pi, size=n_features)
    w = rng.standard_normal(n_features)
    feats = _features(omega, phase, w * np.sqrt(2.0 * sigma2 / n_features))
    xs, ys = [], []
    for start in range(0, n, gen_rows):
        k = min(n - start, gen_rows)
        x = rng.uniform(size=(k, d))
        pad = np.zeros((gen_rows, d), np.float32)
        pad[:k] = x
        y = np.asarray(feats(pad), np.float64)[:k]
        xs.append(x)
        ys.append(y + np.sqrt(nugget) * rng.standard_normal(k))
    return np.concatenate(xs), np.concatenate(ys)


def _features(omega, phase, w):
    """x -> sum_j w_j cos(omega_j . x + phase_j), compiled once."""
    import jax
    import jax.numpy as jnp

    om, ph, ww = (jnp.asarray(a, jnp.float32) for a in (omega, phase, w))

    @jax.jit
    def f(x):
        proj = jnp.dot(x, om.T, precision="highest") + ph
        return jnp.dot(jnp.cos(proj), ww, precision="highest")

    return f


GENERATORS = {"metarvm": metarvm, "paper_synthetic": paper_synthetic}


def make(generator: str, seed: int, n: int):
    """``(x, y)`` of ``n`` rows from the named generator; y is centred,
    as the emulator fits a zero-mean GP."""
    x, y = GENERATORS[generator](seed, n)
    return x, y - y.mean()
