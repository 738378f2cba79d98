#!/usr/bin/env python3
"""Run the SBV emulator's main path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: fit -> predict -> serve
    python chip_smoke.py --four-chips   # distributed fit + predict, 4 chips

Workload: the MetaRVM respiratory-disease emulator (paper §6.3) at the
paper's widths, d=10, bs=100, m=400, with serving widths bs_pred=25,
m_pred=120. Data come from ``repro.data.gp_sim.metarvm_dataset(--seed)``.
Everything runs through the library entry points (``fit_sbv``,
``build_train_index``/``predict_sbv``, ``GPServer``) at the f32 precision
tier with ``backend="auto"``, which routes these shapes to the compiled
Pallas kernels.

One chip, phases in order (each prints one JSON line):

* ``loglik``  f32 Pallas log-likelihood on a subset of blocks against the
  f64 reference (host CPU), within ``LOGLIK_BOUND`` (the f32 tier budget
  is reported beside it);
* ``fit``     streaming ``fit_sbv``: a few likelihood + gradient steps over
  pieces of ``STREAM_CHUNK`` rows;
* ``predict`` ``predict_sbv`` over ``N_TEST`` points with ``N_SIMS``
  conditional simulations;
* ``serve``   a ``GPServer`` answering ``REQUESTS`` concurrent requests,
  equal to a lone ``predict_sbv`` of the same points;
* ``exact``   SBV with m_pred >= n_train against the exact GP
  (``core/exact_gp.py``) at f32-class tolerance.

Every phase reports the backends the library ran (its own stats); where
they are Pallas, the program lowered from the same jitted function at the
run's shapes, dtypes and backend must contain a Mosaic kernel
(``tpu_custom_call``).

``--four-chips`` runs only the distributed fit step
(``fit_sbv(distributed=(mesh, "workers"))``), the sharded chunk gradient
of one piece, and a distributed predict over ``make_worker_mesh(4)``, each
compared with the same call on one chip.

Times are host wall clock around work that ends in a device sync; compile
and host preprocessing are reported apart from run time. The last line of
standard output is ``{"ok": true, "device": {...}}``. The script exits
non-zero, and prints no such line, when JAX finds no TPU or any phase
fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

D, BS, M = 10, 100, 400          # MetaRVM fit widths (paper §6.3)
BS_PRED, M_PRED = 25, 120        # serving widths (launch/serve.py defaults)
NU = 3.5
TIER = "f32"
N_TRAIN = 1_000_000              # 10k blocks
N_TEST = 10_000
N_SIMS = 1000
FIT_STEPS = 3
# Rows per fit piece: ~525 blocks, whose gradient needs ~3.5 GB of
# temporaries at bs_max ~170 (the compiled step's memory_analysis), so a
# piece fits 16 GB of HBM beside the device-cached pieces.
STREAM_CHUNK = 262_144
CHUNK = 4096                     # test points per prediction chunk
REQUESTS, SERVE_POINTS = 8, 2048
LOGLIK_BLOCKS = 64
EXACT_N = 512
# f32 Pallas loglik vs f64, relative to max(1, |ll|). The f32 tier budget
# (1e-6) is out of reach of any f32 evaluation of MetaRVM blocks at the
# fit's start point (XLA's own f32 path: 2e-5..6e-5); the kernel measured
# 3.2e-5 on a v5e, and 2.6e-4..2.9e-4 with the TPU's own f32 exp.
LOGLIK_BOUND = 1e-4
F32_CLASS = 1e-3  # f32 SBV vs the f64 exact GP, over sd resp. prior
# Sharded vs one-chip piece gradient, over max |grad|: f32 rounding and
# summation order; a gradient off by a shard factor or a sign is O(1) off.
GRAD_BOUND = 1e-4
PALLAS = ("pallas", "pallas_tiled")


def emit(record: dict) -> None:
    print(json.dumps(record, default=float), flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-train", type=int, default=N_TRAIN,
                    help="training points (the paper's cell holds 50M)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip distributed fit + predict")
    return ap.parse_args(argv)


# -- checks ------------------------------------------------------------------


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def kernel_in(compiled) -> bool:
    """True when the compiled program contains a Mosaic (Pallas) kernel."""
    return "tpu_custom_call" in compiled.as_text()


def peak_hbm(device) -> dict:
    """Peak device memory so far. On a v5e, buffers count in
    ``peak_bytes_in_use`` and a program's temporaries in
    ``peak_bytes_reserved`` (a 512 MiB temporary moved only the latter)."""
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use",
                                      "peak_bytes_reserved")}


class CompileClock:
    """Seconds JAX spent lowering and compiling, from its own monitoring
    events (a persistent-cache load counts as compile); read before and
    after the library call of a phase."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event in self.EVENTS:
            self.total += secs


def sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def predict_avals(bc, bs, m):
    """Operands of the predict programs at one chunk shape (f32 tier)."""
    f32, b = np.float32, np.bool_
    return (sds((bc, bs, D), f32), sds((bc, bs), b), sds((bc, m, D), f32),
            sds((bc, m), f32), sds((bc, m), b))


def check_backends(phase: str, backends, programs) -> None:
    """Every backend the phase ran is Pallas, and each program it ran
    (lowered again at the run's shapes) holds the kernel."""
    check(bool(backends) and all(b in PALLAS for b in backends),
          f"{phase} ran {backends}")
    check(all(programs), f"{phase}: Pallas kernel in HLO per program: "
                         f"{programs}")


# -- data --------------------------------------------------------------------


def make_data(seed: int, n_train: int):
    from repro.data.gp_sim import metarvm_dataset

    x, y = metarvm_dataset(seed, n_train)
    x_test, y_test = metarvm_dataset(seed + 1, N_TEST)
    mu = y.mean()
    return x, y - mu, x_test, y_test - mu


def init_params(y):
    """The streaming fit's own starting point (``_fit_sbv_streaming``)."""
    from repro.core.kernels_math import KernelParams

    return KernelParams.create(sigma2=float(np.var(y)), beta=0.5, nugget=1e-3,
                               d=D)


def packed_subset(x, y, n_blocks: int, seed: int):
    """The first ``n_blocks * BS`` points, preprocessed like the fit."""
    from repro.core import SBVConfig, preprocess

    n = n_blocks * BS
    packed, _ = preprocess(x[:n], y[:n], np.full(D, 0.5),
                           SBVConfig(n_blocks=n_blocks, m=M, seed=seed))
    return packed


# -- one-chip phases ---------------------------------------------------------


def phase_loglik(x, y, seed, cpu):
    """f32 Pallas loglik on the chip vs the f64 reference on the host."""
    import jax

    from repro.core.buckets import _TIER_BUDGETS, cast_packed
    from repro.core.packing import PackedBlocks
    from repro.core.vecchia import packed_loglik
    from repro.kernels.ops import select_backend

    params = init_params(y)
    t0 = time.perf_counter()
    packed = packed_subset(x, y, LOGLIK_BLOCKS, seed)
    host_s = time.perf_counter() - t0
    pk32 = cast_packed(packed, TIER)
    backend = select_backend(pk32.bs_max, pk32.m, kind="loglik",
                             dtype=pk32.blk_x.dtype)
    fn = jax.jit(lambda p, *a: packed_loglik(
        p, PackedBlocks(*a, owners=pk32.owners), nu=NU, backend=backend))
    arrs = (pk32.blk_x, pk32.blk_y, pk32.blk_mask, pk32.nn_x, pk32.nn_y,
            pk32.nn_mask)
    t0 = time.perf_counter()
    compiled = fn.lower(params, *arrs).compile()
    compile_s = time.perf_counter() - t0
    compiled(params, *arrs).block_until_ready()   # warm-up run
    t0 = time.perf_counter()
    got = float(compiled(params, *arrs).block_until_ready())
    run_s = time.perf_counter() - t0
    with jax.default_device(cpu):
        p_cpu = jax.device_put(params, cpu)
        ref = float(packed_loglik(p_cpu, packed, nu=NU, backend="ref"))
        ref32 = float(packed_loglik(p_cpu, pk32, nu=NU, backend="ref"))
    denom = max(1.0, abs(ref))     # the ladder's own metric (assign_precision)
    rel = abs(got - ref) / denom
    budget = _TIER_BUDGETS[TIER]
    rec = dict(phase="loglik", blocks=packed.n_blocks, bs_max=packed.bs_max,
               m=packed.m, backend=backend, pallas_in_hlo=kernel_in(compiled),
               loglik_f32_pallas=got, loglik_f64_ref_host=ref, rel_err=rel,
               bound=LOGLIK_BOUND, rel_err_xla_f32_host=abs(ref32 - ref) / denom,
               tier_budget=budget, within_tier_budget=rel <= budget,
               host_s=host_s, compile_s=compile_s, run_s=run_s)
    emit(rec)
    check_backends("loglik", [backend], [rec["pallas_in_hlo"]])
    check(np.isfinite(got) and rel <= LOGLIK_BOUND,
          f"f32 Pallas loglik {got} vs f64 ref {ref}: rel {rel} > "
          f"{LOGLIK_BOUND}")


def phase_fit(x, y, seed, device, clock):
    import jax

    from repro.core import SBVConfig
    from repro.core.fit import _chunk_grad_fn, fit_sbv

    cfg = SBVConfig(n_blocks=len(y) // BS, m=M, seed=seed)
    t0, c0 = time.perf_counter(), clock.total
    res = fit_sbv(x, y, cfg, inner_steps=FIT_STEPS, outer_rounds=1,
                  backend="auto", stream_chunk=STREAM_CHUNK, precision=TIER)
    wall_s, compile_s = time.perf_counter() - t0, clock.total - c0
    st = res.stream_stats
    steps = st["step_times_s"]
    losses = [h[2] for h in res.history]
    # The chunk step each backend ran, lowered again at the pieces' shapes
    # and dtypes. The wrapper is the fit's own (lru-cached on the fit's
    # exact arguments), and on the CPU a call at these avals hits the
    # fit's compiled entry, so this HLO and memory_analysis are those of
    # the program the fit timed.
    bc, bsm = st["piece_blocks"], st["bs_max"]
    f32, b = np.float32, np.bool_
    avals = (sds((bc, bsm, D), f32), sds((bc, bsm), f32), sds((bc, bsm), b),
             sds((bc, M, D), f32), sds((bc, M), f32), sds((bc, M), b))
    compiled = [_chunk_grad_fn(NU, be, len(y), None, None)
                .lower(res.params, *avals).compile() for be in st["backends"]]
    warm = steps[1:] or steps
    rec = dict(phase="fit", n_train=len(y), blocks=st["bc"],
               pieces=st["n_pieces"], piece_blocks=bc, bs_max=bsm, m=M,
               backends=st["backends"],
               pallas_in_hlo=[kernel_in(c) for c in compiled],
               precision=st["precision"], host_preprocess_s=st["struct_time_s"],
               step_s=steps, step_s_median_warm=float(np.median(warm)),
               compile_s=compile_s, wall_s=wall_s, nll_per_n=losses,
               piece_temp_bytes=[c.memory_analysis().temp_size_in_bytes
                                 for c in compiled],
               device_cached_pieces=st["device_cached_pieces"],
               **peak_hbm(device))
    emit(rec)
    check_backends("fit", st["backends"], rec["pallas_in_hlo"])
    check(all(np.isfinite(losses)), f"non-finite fit loss {losses}")
    check(all(np.isfinite(np.asarray(v)).all()
              for v in jax.tree.leaves(res.params)), "non-finite params")
    return res.params


def predict_kw(seed, **over):
    return dict(dict(bs_pred=BS_PRED, m_pred=M_PRED, n_sims=N_SIMS, seed=seed,
                     backend="auto", chunk_size=CHUNK, precision=TIER), **over)


def simulate_programs(params, pred, n_sims):
    """Kernel check of each predict program ``predict_sbv`` ran."""
    import jax

    from repro.core.predict import _predict_and_simulate

    st = pred.stats
    return [kernel_in(_predict_and_simulate.lower(
        params, *predict_avals(*shape), jax.random.PRNGKey(0), nu=NU,
        backend=be, n_sims=n_sims).compile())
        for be in st["backends"] for shape in st["shapes"]]


def phase_predict(params, x, y, x_test, y_test, seed, device, clock):
    from repro.core.predict import predict_sbv

    t0, c0 = time.perf_counter(), clock.total
    pred = predict_sbv(params, x, y, x_test, **predict_kw(seed))
    wall_s, compile_s = time.perf_counter() - t0, clock.total - c0
    st = pred.stats
    mspe = float(np.mean((pred.mean - y_test) ** 2))
    cover = float(np.mean((y_test >= pred.ci_low) & (y_test <= pred.ci_high)))
    rec = dict(phase="predict", n_test=len(x_test), n_sims=N_SIMS,
               chunks=len(st["fetch_s"]), chunk_shapes=st["shapes"],
               backends=st["backends"],
               pallas_in_hlo=simulate_programs(params, pred, N_SIMS),
               wall_s=wall_s, host_preprocess_s=st["host_s"],
               compile_s=compile_s, chunk_fetch_s=st["fetch_s"],
               run_s=wall_s - st["host_s"] - compile_s,
               points_per_s=len(x_test) / wall_s,
               mspe=mspe, ci95_coverage=cover, var_y_test=float(np.var(y_test)),
               **peak_hbm(device))
    emit(rec)
    check_backends("predict", st["backends"], rec["pallas_in_hlo"])
    for name in ("mean", "var", "sim_mean", "ci_low", "ci_high"):
        a = getattr(pred, name)
        check(a.shape == (len(x_test),) and np.isfinite(a).all(),
              f"predict {name}: shape {a.shape}, finite {np.isfinite(a).all()}")
    check(bool((pred.var > 0).all()), "non-positive predictive variance")


def phase_serve(params, x, y, x_test, seed, device, clock):
    from repro.core.predict import batched_block_predict, predict_sbv
    from repro.serving import (
        BatchingPolicy, GPServer, GPServerConfig, PipelineConfig,
    )

    xs = x_test[:SERVE_POINTS]
    cfg = GPServerConfig(
        pipeline=PipelineConfig(bs_pred=BS_PRED, m_pred=M_PRED, backend="auto",
                                chunk_size=CHUNK, precision=TIER),
        policy=BatchingPolicy(max_points=len(xs) + 1, max_wait_s=60.0),
        seed=seed,
    )
    t0 = time.perf_counter()
    server = GPServer(params, x, y, cfg)
    index_s = time.perf_counter() - t0
    with server:
        t0, c0 = time.perf_counter(), clock.total
        futs = [server.submit(r) for r in np.array_split(xs, REQUESTS)]
        server.flush()          # everything queued -> one micro-batch
        results = [f.result(timeout=600) for f in futs]
        wall_s, compile_s = time.perf_counter() - t0, clock.total - c0
    summ = server.stats.summary()
    programs = [kernel_in(batched_block_predict.lower(
        params, *predict_avals(bc, bs, m), nu=NU, backend=be).compile())
        for be in summ["backends"]
        for bc, bs, m, _ in sorted(server.stats.compiled_shape_keys())]
    lone = predict_sbv(params, x, y, xs, **predict_kw(seed))
    mean = np.concatenate([r.mean for r in results])
    var = np.concatenate([r.var for r in results])
    d_mean = float(np.max(np.abs(mean - lone.mean)))
    d_var = float(np.max(np.abs(var - lone.var)))
    lat = sorted(r.latency_s for r in results)
    rec = dict(phase="serve", requests=len(results), points=len(xs),
               batches=summ["n_batches"], backends=summ["backends"],
               pallas_in_hlo=programs, index_s=index_s, wall_s=wall_s,
               compile_s=compile_s,
               points_per_s=len(xs) / wall_s,
               latency_s_min=lat[0], latency_s_max=lat[-1],
               max_abs_diff_mean=d_mean, max_abs_diff_var=d_var,
               parity_bound=1e-12, compiled_shapes=summ["n_compiled_shapes"],
               **peak_hbm(device))
    emit(rec)
    check_backends("serve", summ["backends"], programs)
    check(d_mean <= 1e-12 and d_var <= 1e-12,
          f"served results differ from the lone predict_sbv: {d_mean}, {d_var}")


def phase_exact(params, x, y, x_test, seed, cpu):
    """m_pred >= n_train makes every block conditional the exact GP one."""
    import jax

    from repro.core.exact_gp import exact_predict
    from repro.core.predict import predict_sbv

    n = EXACT_N
    xs, ys, xt = x[:n], y[:n], x_test[:256]
    t0 = time.perf_counter()
    pred = predict_sbv(params, xs, ys, xt, **predict_kw(
        seed, m_pred=n, n_sims=2, chunk_size=None))
    run_s = time.perf_counter() - t0
    with jax.default_device(cpu):
        em, ev = exact_predict(jax.device_put(params, cpu), xs, ys, xt)
    em, ev = np.asarray(em), np.asarray(ev)
    scale = float(params.sigma2 + params.nugget)
    err_mean = float(np.max(np.abs(pred.mean - em)) / np.sqrt(scale))
    err_var = float(np.max(np.abs(pred.var - ev)) / scale)
    rec = dict(phase="exact", n_train=n, n_test=len(xt), m_pred=n,
               backends=pred.stats["backends"],
               pallas_in_hlo=simulate_programs(params, pred, 2),
               max_err_mean_over_sd=err_mean, max_err_var_over_prior=err_var,
               tolerance=F32_CLASS, wall_s=run_s)
    emit(rec)
    check_backends("exact", pred.stats["backends"], rec["pallas_in_hlo"])
    check(err_mean <= F32_CLASS and err_var <= F32_CLASS,
          f"SBV(m_pred=n) vs exact GP: mean {err_mean}, var {err_var} > "
          f"{F32_CLASS}")


def run_one_chip(args, device, cpu) -> None:
    t0 = time.perf_counter()
    x, y, x_test, y_test = make_data(args.seed, args.n_train)
    emit(dict(phase="data", source="metarvm_dataset", seed=args.seed,
              n_train=args.n_train, n_test=N_TEST, d=D,
              host_s=time.perf_counter() - t0))
    clock = CompileClock()
    phase_loglik(x, y, args.seed, cpu)
    params = phase_fit(x, y, args.seed, device, clock)
    phase_predict(params, x, y, x_test, y_test, args.seed, device, clock)
    phase_serve(params, x, y, x_test, args.seed, device, clock)
    phase_exact(params, x, y, x_test, args.seed, cpu)


# -- four chips --------------------------------------------------------------


def grad_compare(x, y, seed, mesh, devices) -> None:
    """One piece's chunk gradient, sharded over the mesh vs on one chip,
    at the fit's start point."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.buckets import cast_packed
    from repro.core.distributed import shard_blocks_by_owner
    from repro.core.fit import _chunk_grad_fn

    piece = shard_blocks_by_owner(
        cast_packed(packed_subset(x, y, LOGLIK_BLOCKS, seed), TIER), 4)
    arrs = (piece.blk_x, piece.blk_y, piece.blk_mask, piece.nn_x, piece.nn_y,
            piece.nn_mask)
    params = init_params(y)
    n = piece.n_blocks * BS
    one = _chunk_grad_fn(NU, "pallas", n)(
        jax.device_put(params, devices[0]),
        *(jax.device_put(a, devices[0]) for a in arrs))
    four = _chunk_grad_fn(NU, "pallas", n, mesh, "workers")(
        jax.device_put(params, NamedSharding(mesh, P())),
        *(jax.device_put(a, NamedSharding(mesh, P("workers"))) for a in arrs))
    (v1, g1), (v4, g4) = jax.device_get(one), jax.device_get(four)
    g1, g4 = (np.concatenate([np.ravel(a) for a in jax.tree.leaves(g)])
              for g in (g1, g4))
    rel_g = float(np.max(np.abs(g4 - g1)) / np.max(np.abs(g1)))
    rel_v = float(abs(v4 - v1) / max(1.0, abs(v1)))
    emit(dict(phase="grad_compare", blocks=piece.n_blocks, rel_diff_grad=rel_g,
              rel_diff_loss=rel_v, bound=GRAD_BOUND,
              grad_max_abs=float(np.max(np.abs(g1)))))
    check(np.isfinite(g1).all() and rel_g <= GRAD_BOUND and
          rel_v <= GRAD_BOUND,
          f"4-chip piece gradient differs from 1 chip: grad {rel_g}, "
          f"loss {rel_v} > {GRAD_BOUND}")


def run_four_chips(args, devices) -> None:
    """Distributed fit step and distributed predict, each against one chip."""
    import jax

    from repro.core import SBVConfig
    from repro.core.buckets import _TIER_BUDGETS, cast_prediction
    from repro.core.distributed import sharded_packed_predict
    from repro.core.fit import fit_sbv
    from repro.core.predict import (
        build_train_index, iter_query_chunks, packed_predict, scatter_packed,
    )
    from repro.launch.mesh import make_worker_mesh

    x, y, x_test, _ = make_data(args.seed, args.n_train)
    mesh = make_worker_mesh(4)
    grad_compare(x, y, args.seed, mesh, devices)
    cfg = SBVConfig(n_blocks=len(y) // BS, m=M, seed=args.seed, n_workers=4)
    kw = dict(inner_steps=FIT_STEPS, outer_rounds=1, backend="auto",
              stream_chunk=STREAM_CHUNK, precision=TIER)
    out = {}
    for name, dist in (("one_chip", None), ("four_chips", (mesh, "workers"))):
        t0 = time.perf_counter()
        with jax.default_device(devices[0]):
            res = fit_sbv(x, y, cfg, distributed=dist, **kw)
        st = res.stream_stats
        out[name] = res
        emit(dict(phase="fit_" + name, wall_s=time.perf_counter() - t0,
                  step_s=st["step_times_s"], backends=st["backends"],
                  shards=st["n_shards"], host_preprocess_s=st["struct_time_s"],
                  nll_per_n=[h[2] for h in res.history]))
    # Step 0 evaluates both fits at the same start params, so its loss
    # differs only in f32 summation order (its gradient is held to that by
    # grad_compare). Later steps start from params that Adam's first,
    # sign-like update may have split apart.
    a = np.asarray([h[2] for h in out["one_chip"].history])
    b = np.asarray([h[2] for h in out["four_chips"].history])
    rel = float(abs(a[0] - b[0]) / max(1.0, abs(a[0])))
    budget = _TIER_BUDGETS[TIER]
    emit(dict(phase="fit_compare", rel_diff_nll_step0=rel, budget=budget,
              rel_diff_nll_per_step=(np.abs(a - b) / np.maximum(1.0, np.abs(a)))
              .tolist()))
    check(out["four_chips"].stream_stats["backends"] == ["pallas"],
          f"distributed fit ran {out['four_chips'].stream_stats['backends']}")
    check(rel <= budget, f"4-chip fit nll differs from 1 chip: {rel} > {budget}")

    params = out["one_chip"].params
    index = build_train_index(x, y, np.asarray(params.beta), M_PRED,
                              seed=args.seed)
    n = len(x_test)
    one = [np.zeros(n), np.zeros(n)]
    four = [np.zeros(n), np.zeros(n)]
    t_one = t_four = 0.0
    for _, packed in iter_query_chunks(index, x_test, BS_PRED, M_PRED,
                                       seed=args.seed, chunk_size=CHUNK,
                                       dtype=np.float32):
        piece = cast_prediction(packed, TIER)
        t0 = time.perf_counter()
        with jax.default_device(devices[0]):
            mu, var = packed_predict(params, piece, nu=NU, backend="auto")
            scatter_packed(piece, (mu, one[0]), (var, one[1]))
        t_one += time.perf_counter() - t0
        t0 = time.perf_counter()
        # Owner-sharded blocks come back reordered with their own q_idx.
        reordered, mu, var = sharded_packed_predict(
            params, piece, mesh, nu=NU, backend="auto")
        scatter_packed(reordered, (mu, four[0]), (var, four[1]))
        t_four += time.perf_counter() - t0
    d_mean = float(np.max(np.abs(one[0] - four[0])))
    d_var = float(np.max(np.abs(one[1] - four[1])))
    emit(dict(phase="predict_compare", n_test=len(x_test),
              one_chip_s=t_one, four_chips_s=t_four,
              max_abs_diff_mean=d_mean, max_abs_diff_var=d_var))
    check(d_mean <= 1e-5 and d_var <= 1e-5,
          f"4-chip predict differs from 1 chip: {d_mean}, {d_var}")


# -- entry -------------------------------------------------------------------


def main(argv=None) -> int:
    args = parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    import jax

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    cpu = jax.devices("cpu")[0]
    emit(dict(phase="config", device_kind=dev.device_kind,
              device_count=len(devices), jax=jax.__version__,
              compile_cache=cache_dir,
              compile_cache_files_at_start=(len(os.listdir(cache_dir))
                                            if os.path.isdir(cache_dir) else 0),
              d=D, bs=BS, m=M, bs_pred=BS_PRED,
              m_pred=M_PRED, tier=TIER, n_train=args.n_train,
              n_train_cut=("cut from the paper's 50M: the smoke must finish "
                           "within one 1200 s chip call, and its host "
                           "preprocessing (k-means + filtered NNS) grows "
                           "faster than n")))
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(args, devices)
    else:
        run_one_chip(args, dev, cpu)
    emit(dict(phase="total", wall_s=time.perf_counter() - t0,
              **peak_hbm(dev)))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
