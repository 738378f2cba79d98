"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the installed TPU compiler compiles for a chip that is
described, not attached, and refuses what the chip would refuse (block
shapes off the (8, 128) tiling, 64-bit values inside a kernel, too much
VMEM). Widths are the paper's MetaRVM fit (``SBV_GP_SHAPES['fit_50m']``:
d=10, bs=100, m=400) and the serving defaults (bs_pred=25, m_pred=120).
Each test asserts the compiled program holds the Mosaic kernel
(``tpu_custom_call``), so no interpret-mode or ``ref`` path can stand in.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.specs import SBV_GP_SHAPES

FIT = SBV_GP_SHAPES["fit_50m"]
D, BS, M = FIT["d"], FIT["bs"], FIT["m"]
BS_PRED, M_PRED = 25, 120
BC = 64  # blocks per call; the kernel grid runs one block per step


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _loglik_args(s, coord_dtype=jnp.float32, p=None):
    y = (lambda n: (BC, n)) if p is None else (lambda n: (BC, n, p))
    return (_spec(s, (D,)), _spec(s, ()), _spec(s, ()),
            _spec(s, (BC, BS, D), coord_dtype), _spec(s, y(BS)),
            _spec(s, (BC, BS)), _spec(s, (BC, M, D), coord_dtype),
            _spec(s, y(M)), _spec(s, (BC, M)))


def _predict_args(s, coord_dtype=jnp.float32):
    return (_spec(s, (D,)), _spec(s, ()), _spec(s, ()),
            _spec(s, (BC, BS_PRED, D), coord_dtype), _spec(s, (BC, BS_PRED)),
            _spec(s, (BC, M_PRED, D), coord_dtype), _spec(s, (BC, M_PRED)),
            _spec(s, (BC, M_PRED)))


def test_loglik_f32_compiles(one_chip):
    from repro.kernels.sbv_loglik import sbv_loglik_pallas

    compiled = _compile(lambda *a: sbv_loglik_pallas(*a, interpret=False),
                        *_loglik_args(one_chip))
    # One grid step per block: a (bc,) float output.
    assert compiled.memory_analysis().output_size_in_bytes <= 4 * BC * 128


def test_multi_output_stats_f32_compiles(one_chip):
    from repro.kernels.sbv_loglik import sbv_multi_stats_pallas

    _compile(lambda *a: sbv_multi_stats_pallas(*a, interpret=False),
             *_loglik_args(one_chip, p=4))


@pytest.mark.parametrize("tiled", [False, True], ids=["untiled", "tiled"])
def test_predict_f32_compiles(one_chip, tiled):
    from repro.kernels.sbv_predict import sbv_predict_pallas, sbv_predict_tiled

    fn = sbv_predict_tiled if tiled else sbv_predict_pallas
    _compile(lambda *a: fn(*a, interpret=False), *_predict_args(one_chip))


@pytest.mark.parametrize("tiled", [False, True], ids=["untiled", "tiled"])
def test_predict_bf16_assembly_compiles(one_chip, tiled):
    from repro.kernels.sbv_predict import sbv_predict_pallas, sbv_predict_tiled

    fn = sbv_predict_tiled if tiled else sbv_predict_pallas
    _compile(lambda *a: fn(*a, interpret=False),
             *_predict_args(one_chip, coord_dtype=jnp.bfloat16))


def test_f64_operands_are_refused_before_the_kernel_compiler(one_chip):
    from repro.kernels.sbv_loglik import sbv_loglik_pallas

    args = _loglik_args(one_chip)
    args = args[:3] + tuple(_spec(one_chip, a.shape, jnp.float64)
                            for a in args[3:])
    with pytest.raises(TypeError, match="float32 or bfloat16"):
        jax.jit(lambda *a: sbv_loglik_pallas(*a, interpret=False)).lower(*args)


def test_fit_chunk_gradient_compiles(one_chip, monkeypatch):
    """The streaming fit's whole likelihood + gradient step at f32: the
    Pallas forward plus the reference VJP, for a 64-block piece."""
    from repro.core.fit import _chunk_grad_fn
    from repro.core.kernels_math import KernelParams

    # The step resolves interpret mode from the default backend, which
    # is the CPU here; steer it to the chip being compiled for.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = one_chip
    f64 = jnp.float64
    params = KernelParams(log_sigma2=_spec(s, (), f64),
                          log_beta=_spec(s, (D,), f64),
                          log_nugget=_spec(s, (), f64))
    bs_max = 160  # k-means blocks of ~100 points peak near this
    arrs = (_spec(s, (BC, bs_max, D)), _spec(s, (BC, bs_max)),
            _spec(s, (BC, bs_max), jnp.bool_), _spec(s, (BC, M, D)),
            _spec(s, (BC, M)), _spec(s, (BC, M), jnp.bool_))
    # n = the paper's 50M: a wrapper of its own, which no CPU test shares.
    fn = _chunk_grad_fn(3.5, "pallas", FIT["n"])
    compiled = fn.lower(params, *arrs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # The gradient's temporaries set how many blocks a piece may hold.
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
