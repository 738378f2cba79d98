"""The general generator: one phase per kind of traffic mix.

A traffic file's ``phase`` picks the function here; everything else in
the file is data (sizes, the nominal time of one unit of work, how many
results the check samples). Each phase drives the library's public entry
point (``fit_sbv`` or ``predict_sbv``) as a user does, and observes a few
of the program's own call sites without changing what they return
(``Hooks``): the optimizer's inputs and outputs, the fit's block
structure, the prediction blocks, and host spans around program layers
that name the device's idle gaps in a trace.

Each phase returns the run's end-to-end metrics, the numbers its check
compared with their limits, the per-layer context that the readers in
``bench/metrics/`` take their numbers from, and the trace breakdown.
"""
from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time

import numpy as np

import checks
import data
import reference as ref
import trace_metrics as tr
import work


class Hooks:
    """Wrap attributes of program modules for the length of a ``with``
    block; each wrapper calls the original and returns what it returned."""

    def __init__(self):
        self._saved: list = []

    def wrap(self, module, name: str, make):
        orig = getattr(module, name)
        self._saved.append((module, name, orig))
        setattr(module, name, make(orig))

    def span(self, module, name: str, label: str, factory: bool = False):
        """Time every call of ``module.name`` as the host span ``label``;
        with ``factory``, every call of the function it returns."""
        def make(orig):
            if factory:
                return lambda *a, **k: _spanned(orig(*a, **k), label)
            return _spanned(orig, label)

        self.wrap(module, name, make)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for module, name, orig in reversed(self._saved):
            setattr(module, name, orig)
        self._saved.clear()


def _spanned(fn, label: str):
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(label):
            return fn(*a, **k)
    return wrapped


class Window:
    """The measured window in the profiler's clock: a ``bench.window``
    span, and the trace itself when ``--trace 1``."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        self.t0 = self.t1 = None
        self._span = None

    def open(self):
        import jax

        if self.traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def close(self):
        import jax

        self.t1 = time.perf_counter()
        if self._span is not None:
            self._span.__exit__(None, None, None)
        if self.traced:
            jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        if not self.traced:
            return None
        try:
            t = tr.load(self.dir)
            win = t.span("bench.window")
            lo, hi = (win.start_ns, win.end_ns) if win else (0.0, math.inf)
            return tr.reduce(t, lo, hi)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak(devices) -> int:
    """Peak bytes on the fullest chip: buffers in use plus the reserved
    space of program temporaries, which a v5e counts apart."""
    peaks = []
    for dev in devices:
        st = dev.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0))
                     + int(st.get("peak_bytes_reserved", 0)))
    return max(peaks)


# -- fit --------------------------------------------------------------------


def run_fit(cell, args, devices, clock, t_start):
    import jax

    import repro.core.fit as fitmod
    import repro.data.streaming as stmod
    from repro.core import SBVConfig
    from repro.core.fit import fit_sbv
    from repro.launch.mesh import make_worker_mesh

    cfg, traffic, lim = cell["config"], cell["traffic"], cell["limits"]
    nu, m, d = cfg["nu"], cfg["m"], cfg["d"]
    n_cmp = int(traffic["compared_steps"])
    x, y = data.make(cfg["generator"], args.seed, cfg["n_train"])
    k = max(n_cmp, math.ceil(args.seconds / traffic["nominal_step_s"]))
    win = Window(bool(args.trace))
    seen = {"struct": None, "adam": []}

    def on_structure(orig):
        def wrapped(*a, **kw):
            seen["struct"] = orig(*a, **kw)
            return seen["struct"]
        return wrapped

    def on_adam(orig):
        def wrapped(grads, state, params, *a, **kw):
            with jax.profiler.TraceAnnotation("bench.fit.adam_update"):
                out = orig(grads, state, params, *a, **kw)
            if len(seen["adam"]) < n_cmp:
                seen["adam"].append((params, out))
            if len(seen["adam"]) == 1 and win.t0 is None:
                # the first step ends here: its work is done before the
                # window (and the trace) opens
                jax.block_until_ready(out)
                win.open()
            return out
        return wrapped

    sbv = SBVConfig(n_blocks=cfg["n_train"] // cfg["bs"], m=m, seed=args.seed,
                    n_workers=cell["chips"])
    dist = ((make_worker_mesh(cell["chips"]), "workers")
            if cell["chips"] > 1 else None)
    with Hooks() as hooks, jax.profiler.TraceAnnotation("bench.fit_sbv"):
        hooks.wrap(stmod, "streaming_preprocess", on_structure)
        hooks.wrap(fitmod, "adam_update", on_adam)
        hooks.span(fitmod, "_chunk_grad_fn", "bench.fit.piece_dispatch",
                   factory=True)
        res = fit_sbv(x, y, sbv, nu=nu, lr=traffic["lr"], inner_steps=k + 1,
                      outer_rounds=1, backend="auto",
                      stream_chunk=traffic["stream_chunk"],
                      precision=cfg["precision"]["fit"], distributed=dist)
        win.close()
    steps = res.stream_stats["step_times_s"]
    window_s = float(sum(steps[1:]))
    losses = [h[2] for h in res.history]
    e2e = {"fit_points_per_s": cfg["n_train"] * k / window_s,
           "setup_s": win.t1 - window_s - t_start}
    mem = memory_peak(devices)
    struct = seen["struct"]
    bs_true = np.asarray([struct.blocks.members[b].size
                          for b in struct.blocks.order])
    m_true = np.asarray([min(len(struct.neigh[b]), m)
                         for b in struct.blocks.order])
    n_params = 2 + d
    run = dict(
        phase="fit", chips=len(devices),
        peak=cell["peaks"]["devices"][devices[0].device_kind],
        window_t0=win.t0, setup_compile_s=clock.seconds(t_start, win.t0),
        window_compiles=clock.compiles(win.t0, win.t1),
        struct_s=res.stream_stats["struct_time_s"],
        work=dict(flops=k * work.fit_step_flops(bs_true, m_true, d),
                  bytes=k * work.fit_step_bytes(bs_true, m_true, d, n_params),
                  kernel_flops=k * work.loglik_forward_flops(bs_true, m_true, d),
                  kernel_bytes=k * work.loglik_bytes(bs_true, m_true, d)),
        trace=win.reduce())
    prog = dict(losses=losses[:n_cmp], adam=jax.device_get(seen["adam"]))
    del res
    gc.collect()

    # -- check, once the window has closed and its memory was read
    rng = np.random.default_rng([args.seed, 7])
    theta0 = checks.fit_start(y, d)
    order = struct.blocks.order
    partition_bad, nn_wrong = checks.check_structure(
        x, order, struct.blocks.members, struct.neigh,
        np.exp(theta0[1]), m, rng, int(traffic["checked_blocks"]))
    blocks = ref.JointBlocks(x, y, [struct.blocks.members[b] for b in order],
                             [struct.neigh[b] for b in order], m,
                             device=devices[0])
    r = ref.follow(blocks, theta0, n_cmp, traffic["lr"], nu, np.float32,
                   "highest")
    gaps = checks.fit_gaps(prog["losses"],
                           checks.grad_from_adam(prog["adam"][0][1][1]),
                           prog["adam"][-1][1][0], *r, theta0)
    checks_ = {"partition_wrong": (float(partition_bad), 0.0),
               "neighbour_sets_wrong": (float(nn_wrong), 0.0)}
    checks_.update({name: (v, lim[name]) for name, v in gaps.items()})
    failed = sum(1 for v in losses[1:] if not math.isfinite(v))
    out = dict(e2e=e2e, checks=checks_, attempted=k, failed=failed,
               memory_peak=mem, run=run)
    if run["trace"] is not None:
        out["breakdown"] = tr.breakdown(run["trace"])
    return out


# -- UQ sweep ---------------------------------------------------------------


def run_uq(cell, args, devices, clock, t_start):
    import jax

    import repro.core.predict as predmod
    from repro.core.predict import predict_sbv

    cfg, traffic, lim = cell["config"], cell["traffic"], cell["limits"]
    nu, d = cfg["nu"], cfg["d"]
    n_test, n_sims = traffic["n_test"], traffic["n_sims"]
    m_pred = cfg["m_pred"]
    x, y = data.make(cfg["generator"], args.seed, cfg["n_train"])
    n_sweeps = max(int(traffic["min_sweeps"]),
                   math.ceil(args.seconds / traffic["nominal_sweep_s"]))
    tests = uq_tests(cfg, args.seed, n_sweeps, n_test)
    params = uq_params(cfg)
    kw = dict(bs_pred=cfg["bs_pred"], m_pred=m_pred, nu=nu, n_sims=n_sims,
              seed=args.seed, backend="auto", chunk_size=traffic["chunk_size"],
              precision=cfg["precision"]["uq"])
    # Warm-up: the chunk shapes follow the test points' blocks alone, so
    # each sweep's own test set over a small training prefix compiles (or
    # loads) its programs, and the window compiles nothing.
    warm = int(traffic["warm_train_rows"])
    for xt in tests:
        predict_sbv(params, x[:warm], y[:warm], xt, **kw)

    win = Window(bool(args.trace))
    preds, blocks = [], [[] for _ in tests]
    with Hooks() as hooks:
        hooks.wrap(predmod, "pack_queries",
                   lambda orig: _recording(orig, blocks, preds))
        hooks.span(predmod, "build_train_index", "bench.uq.train_index")
        hooks.span(predmod, "scatter_packed", "bench.uq.device_and_scatter")
        win.open()
        for xt in tests:
            with jax.profiler.TraceAnnotation("bench.uq.sweep"):
                preds.append(predict_sbv(params, x, y, xt, **kw))
        win.close()
    window_s = win.t1 - win.t0
    e2e = {"uq_points_per_s": n_sweeps * n_test / window_s,
           "setup_s": win.t0 - t_start}
    mem = memory_peak(devices)
    bs_true = np.asarray([len(b) for sweep in blocks for b in sweep])
    m_true = np.full(bs_true.shape, min(m_pred, len(x)))
    run = dict(
        phase="uq", chips=len(devices),
        peak=cell["peaks"]["devices"][devices[0].device_kind],
        window_t0=win.t0, setup_compile_s=clock.seconds(t_start, win.t0),
        window_compiles=clock.compiles(win.t0, win.t1),
        host_s=sum(p.stats["host_s"] for p in preds), window_s=window_s,
        work=dict(flops=work.predict_flops(bs_true, m_true, d, n_sims),
                  bytes=work.predict_bytes(bs_true, m_true, d)),
        trace=win.reduce())

    # -- check every point of every sweep
    theta = [np.asarray(a, np.float64) for a in params]
    cpu = jax.devices("cpu")[0]
    got, want, failed = [], [], 0
    for xt, sweep, p in zip(tests, blocks, preds):
        got.append((p.mean, p.var, p.sim_mean))
        ok = (np.isfinite(np.stack([p.mean, p.var, p.sim_mean, p.ci_low,
                                    p.ci_high])).all(axis=0) & (p.var > 0))
        failed += int((~ok).sum())
        want.append(checks.uq_reference(theta, x, y, xt, sweep, m_pred, nu,
                                        np.float64, "highest", cpu))
    g = [np.concatenate(a) for a in zip(*got)]
    r_mean, r_var = (np.concatenate(a) for a in zip(*want))
    gaps = checks.uq_gaps(*g, r_mean, r_var, n_sims)
    out = dict(e2e=e2e, checks={k: (v, lim[k]) for k, v in gaps.items()},
               attempted=n_sweeps * n_test, failed=failed, memory_peak=mem,
               run=run)
    if run["trace"] is not None:
        out["breakdown"] = tr.breakdown(run["trace"])
    return out


def uq_tests(cfg, seed: int, n_sweeps: int, n_test: int) -> list:
    """Fresh test inputs of each sweep, drawn from the seed."""
    gen = data.GENERATORS[cfg["generator"]]
    return [gen(np.random.SeedSequence([seed, 1 + i]), n_test)[0]
            for i in range(n_sweeps)]


def uq_params(cfg):
    """The kernel parameters the sweeps predict under, as the
    configuration states them."""
    from repro.core.kernels_math import KernelParams

    up = cfg["uq_params"]
    return KernelParams.create(sigma2=up["sigma2"], beta=up["beta"],
                               nugget=up["nugget"], d=cfg["d"])


def _recording(orig, blocks, preds):
    """``pack_queries`` that also keeps each chunk's prediction blocks,
    filed under the sweep in progress (the count of finished sweeps)."""
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation("bench.uq.pack_queries"):
            p = orig(*a, **k)
        blocks[len(preds)].extend(
            checks.uq_blocks(np.asarray(p.q_idx), np.asarray(p.q_mask)))
        return p
    return wrapped


PHASES = {"fit": run_fit, "uq": run_uq}
