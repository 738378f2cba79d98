"""Jitted public wrappers around the Pallas kernels.

``sbv_loglik`` is differentiable: the forward pass runs the fused Pallas
kernel; the backward pass is the VJP of the pure-jnp reference (the
likelihood is a scalar, so the cotangent is a scalar — the rebuild is one
extra likelihood-shaped pass, exactly what MAGMA-based codes pay for finite
differences, but here it is an analytic gradient).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.kernels_math import KernelParams, cast_params
from repro.core.vecchia import batched_block_loglik

from .matern_cov import matern_cov_pallas
from .sbv_loglik import sbv_loglik_pallas, sbv_multi_stats_pallas
from .sbv_predict import sbv_predict_pallas, sbv_predict_tiled


def ladder_dtypes(dtype):
    """(assembly, accumulation) dtypes for a storage dtype on the ladder.

    bf16 coordinates assemble at bf16 and accumulate in f32 (the MXU's
    native mixed-precision GEMM); f32/f64 storage accumulates at its own
    width. See docs/precision.md for the ladder contract."""
    import numpy as _np

    if _np.dtype(dtype) == _np.dtype(jnp.bfloat16):
        return jnp.bfloat16, jnp.float32
    return dtype, dtype


def _ref_total(params: KernelParams, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask, nu):
    return batched_block_loglik(
        params, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask, nu=nu
    )


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def sbv_loglik(params: KernelParams, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask, nu=3.5):
    """Total SBV log-likelihood via the fused Pallas kernel.

    The coordinate dtype selects the precision tier: bf16 coords run
    bf16-assembly with f32 accumulation (params/observations/masks cast
    to f32); f32/f64 inputs run the legacy single-dtype kernel."""
    _, acc = ladder_dtypes(blk_x.dtype)
    per_block = sbv_loglik_pallas(
        params.beta.astype(acc),
        params.sigma2.astype(acc),
        params.nugget.astype(acc),
        blk_x, blk_y.astype(acc), blk_mask.astype(acc),
        nn_x, nn_y.astype(acc), nn_mask.astype(acc),
        nu=nu,
    )
    return jnp.sum(per_block)


def _fwd(params, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask, nu):
    out = sbv_loglik(params, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask, nu)
    return out, (params, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask)


def _bwd(nu, res, g):
    params, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask = res
    # The reference VJP runs at the forward's accumulation width: f64
    # master params would otherwise promote it to f64, which a TPU only
    # emulates (slowly, at twice the memory). The cast is differentiable,
    # so the gradient still arrives in the params' own dtype.
    _, acc = ladder_dtypes(blk_x.dtype)
    grad_fn = jax.grad(
        lambda p, by, ny: _ref_total(
            cast_params(p, acc), blk_x, by, blk_mask.astype(bool), nn_x, ny,
            nn_mask.astype(bool), nu
        ),
        argnums=(0, 1, 2),
    )
    # f32 matmuls default to one bf16 pass on TPU, and XLA's Cholesky and
    # triangular solves are built from them: at that precision the
    # near-nugget Schur complements factor to NaN. Full f32 keeps them
    # finite (a no-op on the CPU).
    with jax.default_matmul_precision("highest"):
        gp, gby, gny = grad_fn(params, blk_y, nn_y)
    scale = lambda t: jax.tree.map(lambda a: a * g, t)
    zeros_like = lambda a: jnp.zeros_like(a)
    return (
        scale(gp), zeros_like(blk_x), scale(gby), zeros_like(blk_mask),
        zeros_like(nn_x), scale(gny), zeros_like(nn_mask),
    )


sbv_loglik.defvjp(_fwd, _bwd)


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def sbv_multi_stats(params0: KernelParams, blk_x, blk_y, blk_mask,
                    nn_x, nn_y, nn_mask, nu=3.5):
    """Multi-output dataset stats ``(logdet0, q0 (p,))`` via the fused
    kernel: one Cholesky per block, all p outputs as extra RHS columns.

    ``params0`` is the UNIT-VARIANCE correlation (sigma2=1, nugget=tau2,
    see ``core.multioutput``). Differentiable like ``sbv_loglik``: the
    forward pass is the fused kernel, the backward pass the VJP of the
    pure-jnp reference."""
    _, acc = ladder_dtypes(blk_x.dtype)
    per_block = sbv_multi_stats_pallas(
        params0.beta.astype(acc),
        params0.sigma2.astype(acc),
        params0.nugget.astype(acc),
        blk_x, blk_y.astype(acc), blk_mask.astype(acc),
        nn_x, nn_y.astype(acc), nn_mask.astype(acc),
        nu=nu,
    )
    return jnp.sum(per_block[:, 0]), jnp.sum(per_block[:, 1:], axis=0)


def _ms_fwd(params0, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask, nu):
    out = sbv_multi_stats(params0, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask, nu)
    return out, (params0, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask)


def _ms_bwd(nu, res, g):
    from repro.core.multioutput import batched_multi_stats

    params0, blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask = res
    g_ld, g_q = g
    _, acc = ladder_dtypes(blk_x.dtype)  # same width contract as _bwd

    def combo(p, by, ny):
        ld, q = batched_multi_stats(
            cast_params(p, acc), blk_x, by, blk_mask.astype(bool), nn_x, ny,
            nn_mask.astype(bool), nu=nu,
        )
        return g_ld * ld + jnp.sum(g_q * q)

    with jax.default_matmul_precision("highest"):  # see _bwd
        gp, gby, gny = jax.grad(combo, argnums=(0, 1, 2))(params0, blk_y, nn_y)
    zeros_like = lambda a: jnp.zeros_like(a)
    return (
        gp, zeros_like(blk_x), gby, zeros_like(blk_mask),
        zeros_like(nn_x), gny, zeros_like(nn_mask),
    )


sbv_multi_stats.defvjp(_ms_fwd, _ms_bwd)


def select_backend(bs: int, m: int, kind: str = "predict", dtype=None) -> str:
    """Resolve ``backend='auto'`` to a concrete kernel per batch shape.

    The bucketed execution layer calls this once per bucket, so one packed
    dataset can mix backends: big tile-aligned f32 buckets take the
    compiled ``pallas_tiled`` path, mid-size buckets the fused ``pallas``
    kernel, and small ragged buckets the vmapped ``ref`` program (where
    kernel launch overhead would dominate). ``kind`` is ``'predict'`` or
    ``'loglik'`` (the loglik kernel has no tiled variant).

    Dtype policy (the full matrix is pinned in tests/test_buckets.py):
    the compiled tiled path takes f32 buckets aligned to the native
    (8, 128) tile and bf16-assembly buckets aligned to bf16's doubled
    (16, 128) sublane tile; unaligned/narrow shapes fall through to the
    fused ``pallas`` kernel or the vmapped ``ref`` program by size. f64
    reaches a Pallas kernel only on the CPU, where kernels run in
    interpret mode: the compiled TPU kernels take f32/bf16 alone, so on
    an accelerator f64 (and an unknown dtype) resolves to ``ref``.
    """
    import numpy as _np

    dt = None if dtype is None else _np.dtype(dtype)
    bf16 = dt is not None and dt == _np.dtype(jnp.bfloat16)
    tiled_ok = bf16 or (dt is not None and dt == _np.float32)
    if not tiled_ok and jax.default_backend() != "cpu":
        return "ref"
    sublane = 16 if bf16 else 8
    if kind == "predict" and tiled_ok and bs % sublane == 0 and m % 128 == 0:
        return "pallas_tiled"
    if bs * m >= 2048:
        return "pallas"
    return "ref"


def sbv_predict(params: KernelParams, q_x, q_mask, nn_x, nn_y, nn_mask, nu=3.5,
                tiled: bool = False):
    """Batched block conditional mean/variance via the fused Pallas kernel.

    Returns ``(mu, var)`` each shaped (bc, bs_pred); padded query slots
    carry mu=0 / var=prior and must be dropped by the caller's mask.
    ``tiled=True`` routes through ``sbv_predict_tiled`` (bs/m rounded to
    the native 8x128 f32 tile — the compiled non-interpret TPU path).
    Serving-only path: not differentiable (prediction conditions on fixed
    fitted parameters; use the ref backend to differentiate). bf16 query/
    neighbor coords run bf16-assembly with f32 accumulation."""
    _, acc = ladder_dtypes(q_x.dtype)
    fn = sbv_predict_tiled if tiled else sbv_predict_pallas
    return fn(
        params.beta.astype(acc),
        params.sigma2.astype(acc),
        params.nugget.astype(acc),
        q_x, q_mask.astype(acc),
        nn_x, nn_y.astype(acc), nn_mask.astype(acc),
        nu=nu,
    )


def matern_cov(xa, xb, params: KernelParams, nu: float = 3.5, tile: int = 128):
    """Batched scaled-Matern covariance via the tiled Pallas kernel."""
    _, acc = ladder_dtypes(xa.dtype)
    return matern_cov_pallas(
        xa, xb, params.beta.astype(acc), params.sigma2.astype(acc),
        nu=nu, tile_n=tile, tile_m=tile,
    )


# flash attention: fwd-fused kernel; see kernels/flash_attention.py
from .flash_attention import flash_attention  # noqa: E402,F401
