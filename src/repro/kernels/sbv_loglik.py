"""Fused SBV block log-likelihood Pallas TPU kernel.

The paper's hot loop is five MAGMA batched BLAS launches per likelihood
evaluation (POTRF, TRSM, TRSV, GEMM, GEMV), each round-tripping GPU HBM.
TPU adaptation (DESIGN.md §3): ONE grid cell per block runs the whole
pipeline on a VMEM-resident working set —

    scaled distances -> Matern(nu) -> chol(m x m) -> joint triangular solve
    -> Schur complement -> chol(bs x bs) -> solve -> logdet + quadratic form

HBM traffic per block drops from O(m^2) x 5 round trips to one read of the
coordinates (O((m+bs) d)) and one scalar write.

Layout (what the TPU compiler accepts):
* Every value inside the kernel is 2-D: vectors are (n, 1) columns or
  (1, n) rows, indices come from 2-D ``broadcasted_iota``, and scalars
  are (1, 1) arrays.
* A block's mask and observations reach the kernel as a (1, 1, n) block
  of a (bc, 1, n) array, so the block's last two dimensions equal the
  array's; the wrappers reshape the callers' (bc, n) arrays for free.
* ``beta`` is one (1, d) VMEM tile shared by every grid step; sigma2 and
  the nugget are SMEM scalars.

Numerical notes:
* Cholesky is a left-looking column loop; rows and columns are read by
  masked reductions and written by mask-selects (no dynamic slicing).
* Identity padding (packing.py) means padded rows factor through as the
  identity: no branches needed inside the kernel.
* Working set at the paper's MetaRVM setting (m=400, bs=100, f32): a few
  (m, m) tiles of 0.8 MB each (lanes pad to 512), well inside the 16 MB
  default scoped VMEM of a v5e core.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LOG2PI = 1.8378770664093453  # log(2*pi)
_HIGHEST = jax.lax.Precision.HIGHEST
_EXP_CUT = 87.0  # exp(-87) is about f32's smallest normal
# Cody-Waite split of ln 2: k * _LN2_HI is exact in f32 for k <= 126.
_LN2_HI, _LN2_LO = 0.693145751953125, 1.4286068203094172e-06
_EXP_TAYLOR = tuple((-1.0) ** i / math.factorial(i)
                    for i in range(10))  # e^-g, |g| < ln 2: ~1e-8 rel


def _exp_neg(r):
    """exp(-r) for r >= 0.

    f32 takes range reduction (r = k ln2 + g) and a polynomial on the
    VPU, good to a few ulp. The TPU's own f32 exp is coarser: with it the
    f32 loglik of 64 MetaRVM blocks was 2.6e-4 off f64 on a v5e, against
    2.5e-5 with this one (the same order as XLA's f32 on a CPU). Other
    dtypes use ``jnp.exp``."""
    if r.dtype != jnp.float32:
        return jnp.exp(-r)
    rc = jnp.minimum(r, _EXP_CUT)
    k = jnp.floor(rc * 1.4426950408889634)
    g = (rc - k * _LN2_HI) - k * _LN2_LO
    p = _EXP_TAYLOR[-1]
    for c in _EXP_TAYLOR[-2::-1]:
        p = p * g + c
    two_k = jax.lax.bitcast_convert_type((127 - k.astype(jnp.int32)) << 23,
                                         jnp.float32)      # 2^-k
    return jnp.where(r < _EXP_CUT, p * two_k, 0.0)


def _matern_poly(r, nu: float):
    if nu == 0.5:
        poly = jnp.ones_like(r)
    elif nu == 1.5:
        poly = 1.0 + r
    elif nu == 2.5:
        poly = 1.0 + r + r * r / 3.0
    elif nu == 3.5:
        poly = 1.0 + r + 0.4 * (r * r) + (r * r * r) / 15.0
    else:
        raise ValueError(f"unsupported nu={nu}")
    return poly * _exp_neg(r)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _dot(a, b, contract, acc):
    """``dot_general`` contracting ``contract = (a_dims, b_dims)``.

    HIGHEST keeps f32 operands at full f32 on the MXU (the default would
    round them to bf16); bf16 operands are exact at the default."""
    precision = None if a.dtype == jnp.bfloat16 else _HIGHEST
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                               preferred_element_type=acc)


def _masked_cov_tile(za, zb, mask_a, mask_b, sigma2, nugget, nu, identity: bool,
                     acc=None, narrow_gemm: bool = False):
    """Covariance tile between pre-scaled coords; masked, optional unit-diag pad.

    ``mask_a`` is an (na, 1) column and ``mask_b`` a (1, nb) row.
    ``acc`` is the accumulation dtype of the precision ladder
    (docs/precision.md): norms, sqrt/exp, and everything downstream run
    in ``acc``; the distance GEMM accumulates in ``acc`` via
    ``preferred_element_type``. ``narrow_gemm=True`` feeds the GEMM its
    operands at the coords' own storage width — the MXU's native bf16
    mode (exact bf16xbf16 products, f32 accumulation). Interpret mode
    must pass False: its dot ignores the accumulation request and rounds
    at the operand width, injecting an unstructured O(eps_bf16 |z|^2)
    error that breaks positive-definiteness of the assembled covariance.
    Upcasting the operands reproduces the hardware MXU numerics exactly
    (bf16 products are representable in f32), so both paths compute the
    true kernel matrix of the bf16-rounded points — PD by construction.
    ``acc=None`` is the single-dtype path."""
    acc = za.dtype if acc is None else acc
    za_a = za.astype(acc)
    zb_a = zb.astype(acc)
    ga, gb = (za, zb) if narrow_gemm else (za_a, zb_a)
    sq_a = jnp.sum(za_a * za_a, axis=1, keepdims=True)       # (na, 1)
    sq_b = jnp.sum(zb_a * zb_a, axis=1, keepdims=True).T     # (1, nb)
    d2 = sq_a + sq_b - 2.0 * _dot(ga, gb, ((1,), (1,)), acc)
    r = jnp.sqrt(jnp.maximum(d2, 0.0) + 1e-30)
    k = sigma2 * _matern_poly(r, nu) * (mask_a * mask_b)
    if identity:
        eye = (_iota(k.shape, 0) == _iota(k.shape, 1)).astype(k.dtype)
        k = k + (nugget * mask_a + (1.0 - mask_a)) * eye
    return k


def _cholesky_inplace(a, floor=1e-30):
    """Left-looking Cholesky of SPD ``a`` via mask-select column writes.

    ``floor`` is the pivot clamp. The 1e-30 default only guards exact
    zeros; reduced-precision assembly (bf16 tier) passes an
    eps(storage)-scaled floor instead, because its unstructured GEMM
    error can push Schur-complement eigenvalues slightly negative — a
    tiny clamped pivot would otherwise amplify into overflow/NaN. The
    clamp turns an indefinite direction into a bounded, *measurable*
    likelihood error, which the precision ladder's probe-and-demote
    harness then judges against the tier budget (docs/precision.md)."""
    n = a.shape[0]
    rows, cols = _iota((n, n), 0), _iota((n, n), 1)
    rvec, cvec = _iota((n, 1), 0), _iota((1, n), 1)

    def body(j, l):
        # Row j over the finished columns k < j; then one lane reduction
        # against (e_j - that row) gives v_i = a_ij - sum_{k<j} L_ik L_jk.
        lj = jnp.sum(jnp.where((rows == j) & (cols < j), l, 0.0),
                     axis=0, keepdims=True)                         # (1, n)
        v = jnp.sum(l * ((cvec == j).astype(l.dtype) - lj),
                    axis=1, keepdims=True)                          # (n, 1)
        vjj = jnp.sum(jnp.where(rvec == j, v, 0.0), axis=0, keepdims=True)
        djj = jnp.sqrt(jnp.maximum(vjj, floor))                     # (1, 1)
        col = jnp.where(rvec < j, 0.0, jnp.where(rvec == j, djj, v / djj))
        return jnp.where(cols == j, col, l)

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), body, a)


def _forward_sub(l, b):
    """Solve L X = B (L lower-triangular, B (n, c)) by masked row substitution."""
    n = l.shape[0]
    rows = _iota((n, n), 0)
    rvec, cvec = _iota((n, 1), 0), _iota((1, n), 1)

    def body(i, x):
        li = jnp.sum(jnp.where(rows == i, l, 0.0), axis=0, keepdims=True)  # (1, n)
        lii = jnp.sum(jnp.where(cvec == i, li, 0.0), axis=1, keepdims=True)
        li_col = jnp.where(cvec < i, li, 0.0).T                             # (n, 1)
        acc = jnp.sum(li_col * x, axis=0, keepdims=True)                    # (1, c)
        xi = jnp.sum(jnp.where(rvec == i, x, 0.0), axis=0, keepdims=True)
        return jnp.where(rvec == i, (xi - acc) / lii, x)

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), body, b)


def _diag_row(l):
    """Diagonal of a square tile as a (1, n) row."""
    n = l.shape[0]
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), l, 0.0),
                   axis=0, keepdims=True)


def _pivot_floor(x_dtype, acc, sigma2):
    """Tier-aware Cholesky pivot clamp.

    Narrow-assembly tiers clamp pivots at the assembly round-off scale
    (eps * sigma2): the bf16 GEMM's unstructured error can make the Schur
    complement slightly indefinite, and the default 1e-30 floor would let
    a clamped pivot blow up the substitution."""
    if x_dtype == acc:
        return 1e-30
    return jnp.finfo(x_dtype).eps * sigma2


def _block_factor(beta_ref, scal_ref, x_ref, m_ref, nn_x_ref, nn_m_ref,
                  nu: float, narrow_gemm: bool):
    """Shared front half of every kernel: scale the block and its
    neighbors, assemble the neighbor and cross covariance tiles, factor
    the neighbor tile. Returns the pieces the callers solve against."""
    beta = beta_ref[...]              # (1, d) accumulation dtype
    sigma2 = scal_ref[0]
    nugget = scal_ref[1]
    acc = beta.dtype                  # ladder accumulation dtype

    # Coordinate scaling stays at the coords' own storage width so a
    # bf16-assembly bucket's distance GEMM sees narrow operands; the
    # contraction accumulates in ``acc`` inside _masked_cov_tile.
    xb = x_ref[0]
    xn = nn_x_ref[0]
    zb = xb / beta.astype(xb.dtype)   # (bs, d) scaled block coords
    zn = xn / beta.astype(xn.dtype)   # (m, d)
    mb = m_ref[0]                     # (1, bs) float mask, acc dtype
    mn = nn_m_ref[0]                  # (1, m)
    mn_c = mn.T                       # (m, 1)

    k_con = _masked_cov_tile(zn, zn, mn_c, mn, sigma2, nugget, nu, identity=True,
                             acc=acc, narrow_gemm=narrow_gemm)
    k_cross = _masked_cov_tile(zn, zb, mn_c, mb, sigma2, nugget, nu,
                               identity=False, acc=acc, narrow_gemm=narrow_gemm)
    floor = _pivot_floor(xb.dtype, acc, sigma2)
    l_con = _cholesky_inplace(k_con, floor=floor)
    return dict(zb=zb, mb=mb, mn=mn, mn_c=mn_c, k_cross=k_cross, l_con=l_con,
                floor=floor, sigma2=sigma2, nugget=nugget, acc=acc)


def _schur_solve(f, rhs_b, nu: float, narrow_gemm: bool, nn_y):
    """Back half of the likelihood kernels.

    Joint substitution against ``[K_cross | Y_nn]`` (Y_nn (m, p)), the
    Schur complement and its Cholesky, then the whitened block residual.
    Returns ``(v (bs, p), logdet (1, 1))``."""
    bs = f["k_cross"].shape[1]
    acc = f["acc"]
    mb = f["mb"]
    mb_c = mb.T
    k_lk = _masked_cov_tile(f["zb"], f["zb"], mb_c, mb, f["sigma2"], f["nugget"],
                            nu, identity=True, acc=acc, narrow_gemm=narrow_gemm)
    sol = _forward_sub(f["l_con"], jnp.concatenate([f["k_cross"], nn_y], axis=1))
    a = sol[:, :bs]                   # (m, bs)
    z = sol[:, bs:]                   # (m, p)
    sigma_new = k_lk - _dot(a, a, ((0,), (0,)), acc)
    mu = _dot(a, z, ((0,), (0,)), acc)                          # (bs, p)
    l_new = _cholesky_inplace(sigma_new, floor=f["floor"])
    v = _forward_sub(l_new, rhs_b * mb_c - mu)                  # (bs, p)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.maximum(_diag_row(l_new), 1e-30)) * mb,
                           axis=1, keepdims=True)               # (1, 1)
    return v, logdet


def _sbv_kernel(
    beta_ref, scal_ref,
    blk_x_ref, blk_y_ref, blk_m_ref, nn_x_ref, nn_y_ref, nn_m_ref,
    out_ref,
    *, nu: float, narrow_gemm: bool = False,
):
    f = _block_factor(beta_ref, scal_ref, blk_x_ref, blk_m_ref, nn_x_ref,
                      nn_m_ref, nu, narrow_gemm)
    yn = (nn_y_ref[0] * f["mn"]).T    # (m, 1)
    yb = blk_y_ref[0].T               # (bs, 1)
    v, logdet = _schur_solve(f, yb, nu, narrow_gemm, yn)
    n_real = jnp.sum(f["mb"], axis=1, keepdims=True)
    quad = jnp.sum(v * v, axis=0, keepdims=True)
    out_ref[0] = -0.5 * n_real * _LOG2PI - 0.5 * logdet - 0.5 * quad


def _sbv_multi_kernel(
    beta_ref, scal_ref,
    blk_x_ref, blk_y_ref, blk_m_ref, nn_x_ref, nn_y_ref, nn_m_ref,
    out_ref,
    *, nu: float, narrow_gemm: bool = False,
):
    """Multi-output per-block stats: ONE Cholesky, (m, bs+p) joint solve.

    The single-output kernel's RHS ``[K_cross | y_nn]`` widens to
    ``[K_cross | Y_nn]`` with Y (m, p) — the per-output work rides the
    same substitution passes as extra columns (docs/multioutput.md).
    Runs on the UNIT-VARIANCE correlation (sigma2=1, nugget=tau2); the
    per-output scales re-enter in closed form outside the kernel.
    Output row: [logdet0, q_1 .. q_p]."""
    f = _block_factor(beta_ref, scal_ref, blk_x_ref, blk_m_ref, nn_x_ref,
                      nn_m_ref, nu, narrow_gemm)
    yn = nn_y_ref[0] * f["mn_c"]      # (m, p)
    v, logdet = _schur_solve(f, blk_y_ref[0], nu, narrow_gemm, yn)
    q = jnp.sum(v * v, axis=0, keepdims=True)                   # (1, p)
    out_ref[0] = jnp.concatenate([logdet, q], axis=1)


def _check_compiled_dtypes(interpret: bool, *arrays):
    """The compiled TPU kernels take f32/bf16 only (the chip has no f64);
    say so plainly instead of letting the kernel compiler abort."""
    if interpret:
        return
    bad = sorted({str(a.dtype) for a in arrays
                  if a.dtype not in (jnp.float32, jnp.bfloat16)})
    if bad:
        raise TypeError("compiled TPU Pallas kernels need float32 or bfloat16 "
                        f"operands, got {', '.join(bad)}")


def _rows(a):
    """(bc, n) -> (bc, 1, n): one block's row is then a whole (1, n) tile."""
    return a.reshape(a.shape[0], 1, a.shape[1])


def _param_operands(beta, sigma2, nugget, dtype):
    scal = jnp.stack([jnp.asarray(sigma2, dtype), jnp.asarray(nugget, dtype)])
    beta = jnp.asarray(beta, dtype).reshape(1, -1)
    specs = [
        pl.BlockSpec((1, beta.shape[1]), lambda i: (0 * i, 0 * i)),  # beta (shared)
        pl.BlockSpec((2,), lambda i: (0 * i,),                 # sigma2, nugget
                     memory_space=pltpu.SMEM),
    ]
    return (beta, scal), specs


def _block_spec(*tail):
    """Spec of grid step i's slab of a (bc, *tail) array.

    Index maps return ``0 * i``, not ``0``: block indices must be i32 on
    TPU, and a bare 0 becomes i64 under x64."""
    return pl.BlockSpec((1,) + tail, lambda i: (i,) + (0 * i,) * len(tail))


def _loglik_call(kernel, beta, sigma2, nugget, blk_x, blk_y, blk_mask,
                 nn_x, nn_y, nn_mask, y_shape, out_cols, nu, interpret, name):
    """``pallas_call`` over one grid step per block; shared by the single-
    and multi-output likelihood kernels. ``name`` names the kernel's
    instruction in compiled programs and device traces, whatever
    transformation (jvp, shard_map) calls it."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bc, bs, d = blk_x.shape
    m = nn_x.shape[1]
    dtype = blk_y.dtype  # accumulation dtype; blk_x may be narrower
    _check_compiled_dtypes(interpret, blk_x, blk_y, nn_x)
    params, param_specs = _param_operands(beta, sigma2, nugget, dtype)
    # Compiled TPU runs feed the MXU narrow (bf16) GEMM operands;
    # interpret mode upcasts them to reproduce the MXU's f32 accumulation
    # (its dot otherwise rounds at the operand width — see
    # _masked_cov_tile).
    out = pl.pallas_call(
        functools.partial(kernel, nu=nu, narrow_gemm=not interpret),
        grid=(bc,),
        in_specs=param_specs + [
            _block_spec(bs, d),
            _block_spec(*y_shape(bs)),
            _block_spec(1, bs),
            _block_spec(m, d),
            _block_spec(*y_shape(m)),
            _block_spec(1, m),
        ],
        out_specs=_block_spec(1, out_cols),
        out_shape=jax.ShapeDtypeStruct((bc, 1, out_cols), dtype),
        interpret=interpret,
        name=name,
    )(*params, blk_x, blk_y, _rows(blk_mask), nn_x, nn_y, _rows(nn_mask))
    return out[:, 0, :]


@functools.partial(jax.jit, static_argnames=("nu", "interpret"))
def sbv_multi_stats_pallas(
    beta, sigma2, nugget,
    blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask,
    nu: float = 3.5,
    interpret: bool | None = None,
):
    """Per-block multi-output stats, shape (bc, 1+p): column 0 is the
    unit-variance logdet, columns 1..p the per-output quadratics. Same
    dtype/precision contract as ``sbv_loglik_pallas``; observations are
    (bc, bs, p) / (bc, m, p)."""
    p = blk_y.shape[2]
    return _loglik_call(_sbv_multi_kernel, beta, sigma2, nugget, blk_x, blk_y,
                        blk_mask, nn_x, nn_y, nn_mask, lambda n: (n, p), 1 + p,
                        nu, interpret, "sbv_multi_stats_pallas")


@functools.partial(jax.jit, static_argnames=("nu", "interpret"))
def sbv_loglik_pallas(
    beta, sigma2, nugget,
    blk_x, blk_y, blk_mask, nn_x, nn_y, nn_mask,
    nu: float = 3.5,
    interpret: bool | None = None,
):
    """Per-block log-likelihoods, shape (bc,). Sum for the total.

    Observations/masks set the ACCUMULATION dtype (f32 on TPU; f64 ok in
    interpret mode); coordinates may additionally arrive one ladder rung
    narrower (bf16) for reduced-precision covariance assembly — see
    docs/precision.md. Masks are float (1.0 real / 0.0 pad).
    """
    out = _loglik_call(_sbv_kernel, beta, sigma2, nugget, blk_x, _rows(blk_y),
                       blk_mask, nn_x, _rows(nn_y), nn_mask, lambda n: (1, n), 1,
                       nu, interpret, "sbv_loglik_pallas")
    return out[:, 0]
