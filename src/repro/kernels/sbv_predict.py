"""Fused SBV block prediction Pallas TPU kernel (paper Eq. 3).

Mirror of ``sbv_loglik.py`` for the serving side: ONE grid cell per
prediction block runs the whole conditional on a VMEM-resident working
set —

    scaled distances -> Matern(nu) -> chol(m x m)
    -> joint triangular solve against [K_cross | y_nn]
    -> mu = A^T z,  var = (sigma2 + nugget) - colsum(A * A)

HBM traffic per block is one read of the coordinates (O((m + bs) d)) and
one (bs,) mean + (bs,) variance write, replacing the POTRF/TRSM/TRSV/
GEMV round-trip chain a batched-BLAS backend pays per prediction batch.

Identity padding (packing.pack_prediction) needs no branches: padded
neighbor rows factor through the m x m Cholesky as the identity and
contribute nothing to the solve; padded query columns have zero
cross-covariance, yielding mu = 0 and var = prior, both discarded at
scatter time by the query mask.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from repro.core.packing import tile_predict_shapes

from .sbv_loglik import (
    _block_factor, _block_spec, _check_compiled_dtypes, _forward_sub,
    _param_operands, _rows,
)


def _sbv_predict_kernel(
    beta_ref, scal_ref,
    q_x_ref, q_m_ref, nn_x_ref, nn_y_ref, nn_m_ref,
    mu_ref, var_ref,
    *, nu: float, narrow_gemm: bool = False,
):
    # Same assembly/accumulation split and tier-aware pivot clamp as the
    # likelihood kernel (_block_factor).
    f = _block_factor(beta_ref, scal_ref, q_x_ref, q_m_ref, nn_x_ref,
                      nn_m_ref, nu, narrow_gemm)
    bs = f["k_cross"].shape[1]
    yn = (nn_y_ref[0] * f["mn"]).T    # (m, 1)
    # Joint solve against [K_cross | y_nn]: one substitution pass.
    sol = _forward_sub(f["l_con"], jnp.concatenate([f["k_cross"], yn], axis=1))
    a = sol[:, :bs]                   # (m, bs)
    z = sol[:, bs:]                   # (m, 1)

    mu = jnp.sum(a * z, axis=0, keepdims=True)                  # (1, bs)
    var = (f["sigma2"] + f["nugget"]) - jnp.sum(a * a, axis=0, keepdims=True)
    mu_ref[0] = mu * f["mb"]
    var_ref[0] = jnp.maximum(var, 1e-12)


@functools.partial(jax.jit, static_argnames=("nu", "interpret"))
def sbv_predict_pallas(
    beta, sigma2, nugget,
    q_x, q_mask, nn_x, nn_y, nn_mask,
    nu: float = 3.5,
    interpret: bool | None = None,
):
    """Per-block conditional means and marginal variances, each (bc, bs).

    Observations/masks set the ACCUMULATION dtype (f32 on TPU; f64 ok in
    interpret mode); coordinates may arrive one ladder rung narrower
    (bf16) for reduced-precision covariance assembly — docs/precision.md.
    Masks are float (1.0 real / 0.0 pad).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bc, bs, d = q_x.shape
    m = nn_x.shape[1]
    dtype = nn_y.dtype  # accumulation dtype; q_x/nn_x may be narrower
    _check_compiled_dtypes(interpret, q_x, nn_x, nn_y)
    params, param_specs = _param_operands(beta, sigma2, nugget, dtype)
    # Narrow MXU GEMM operands on hardware, f32-upcast in interpret mode
    # (faithful MXU accumulation emulation — see _masked_cov_tile).
    kernel = functools.partial(_sbv_predict_kernel, nu=nu,
                               narrow_gemm=not interpret)
    row = jax.ShapeDtypeStruct((bc, 1, bs), dtype)
    mu, var = pl.pallas_call(
        kernel,
        grid=(bc,),
        in_specs=param_specs + [
            _block_spec(bs, d),
            _block_spec(1, bs),
            _block_spec(m, d),
            _block_spec(1, m),
            _block_spec(1, m),
        ],
        out_specs=(_block_spec(1, bs), _block_spec(1, bs)),
        out_shape=(row, row),
        interpret=interpret,
        name="sbv_predict_pallas",
    )(*params, q_x, _rows(q_mask), nn_x, _rows(nn_y), _rows(nn_mask))
    return mu[:, 0, :], var[:, 0, :]


@functools.partial(jax.jit, static_argnames=("nu", "interpret"))
def sbv_predict_tiled(
    beta, sigma2, nugget,
    q_x, q_mask, nn_x, nn_y, nn_mask,
    nu: float = 3.5,
    interpret: bool | None = None,
):
    """Tile-aligned predict: pad bs -> multiple of 8 (sublane) and
    m -> multiple of 128 (lane), run the fused kernel on the aligned f32
    tiles, slice the outputs back to the caller's (bc, bs).

    This is the compiled (non-interpret) TPU entry point: Mosaic lays the
    per-block (m, m)/(m, bs) working set on native (8, 128) f32 tiles with
    no relayout, and the MXU contractions run at full-lane occupancy. The
    identity-padding contract keeps the added lanes inert (zero masks =>
    unit-diagonal Cholesky rows, zero cross-covariance), so outputs match
    the unaligned shapes exactly; padding happens INSIDE the jit so the
    caller's shapes stay the cache key.

    On TPU the coordinate inputs must be f32 or bf16 (the compiled
    kernel's native MXU dtypes; bf16 assembly pads bs to the doubled
    16-sublane tile — see docs/precision.md); interpret mode (CPU)
    accepts f64 as well.
    """
    bc, bs, _ = q_x.shape
    m = nn_x.shape[1]
    # bf16 min tile is (16, 128): the sublane side doubles vs f32's (8, 128).
    sublane = 16 if q_x.dtype == jnp.bfloat16 else 8
    bs_t, m_t = tile_predict_shapes(bs, m, bs_mult=sublane)

    pad1 = lambda a, width: jnp.pad(a, ((0, 0), (0, width - a.shape[1]))
                                    + ((0, 0),) * (a.ndim - 2))
    mu, var = sbv_predict_pallas(
        beta, sigma2, nugget,
        pad1(q_x, bs_t), pad1(q_mask, bs_t),
        pad1(nn_x, m_t), pad1(nn_y, m_t), pad1(nn_mask, m_t),
        nu=nu, interpret=interpret,
    )
    return mu[:, :bs], var[:, :bs]
