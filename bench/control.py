#!/usr/bin/env python3
"""Readings of the control and of planted faults, for setting the limits
of ``bench/limits/<cell>.json``. Not run by the benchmark's own runs.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed it prints one JSON line of readings of the cell's compared
numbers, each against the plain reference as the benchmark's check
computes it, at the cell's own size:

* ``control``: the cell's phase run as in the benchmark, with the
  program's own next lower precision tier switched on (f64 -> f32,
  f32 -> bf16). The reference at float32 ``high`` in the program's place
  is no control on the chip: XLA's TPU Cholesky and triangular solve do
  not take the matmul precision, and the reference's distances use no
  matmul, so it reads exactly what ``highest`` reads.
* fit cells, planted in the reference put in the program's place on the
  block structure the program builds for the seed: ``half_batch``, every
  other batch of blocks with the mean taken over those; on several chips
  ``no_exchange``, the first quarter of the blocks over all n (one chip's
  share with the psum left out). A state left unchanged reads 1 on
  ``grad_norm_gap`` and ``change_norm_gap`` and needs no run.
* UQ cells, planted in the reference's conditional on one sweep's
  blocks: ``half_draws``, half of the simulation draws; ``var_as_sd``,
  draws scaled by the variance in place of the standard deviation.

The lower readings come from the benchmark's own runs (their
``checks``); these give the upper ones. Needs the cell's chips.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

LOWER = {"f64": "f32", "f32": "bf16"}


def program_control(cell, seed: int, devices) -> dict:
    """The cell's phase at one rung lower precision; its checks."""
    import phases
    import run

    low = copy.deepcopy(cell)
    phase = low["traffic"]["phase"]
    low["config"]["precision"][phase] = LOWER[cell["config"]["precision"][phase]]
    args = argparse.Namespace(workload=cell["name"], seed=seed, seconds=0.0,
                              trace=0)
    out = phases.PHASES[phase](low, args, devices, run.CompileClock(),
                               time.perf_counter())
    return {k: v for k, (v, _) in out["checks"].items()}


def fit_faults(cell, seed: int, device) -> dict:
    import numpy as np

    import checks
    import data
    import reference as ref
    from repro.core import SBVConfig
    from repro.data.store import as_store
    from repro.data.streaming import streaming_preprocess

    cfg, traffic = cell["config"], cell["traffic"]
    nu, m, d = cfg["nu"], cfg["m"], cfg["d"]
    steps, lr = int(traffic["compared_steps"]), traffic["lr"]
    x, y = data.make(cfg["generator"], seed, cfg["n_train"])
    theta0 = checks.fit_start(y, d)
    sbv = SBVConfig(n_blocks=cfg["n_train"] // cfg["bs"], m=m, seed=seed,
                    n_workers=cell["chips"])
    st = streaming_preprocess(as_store(x, y), np.exp(theta0[1]), sbv,
                              traffic["stream_chunk"])
    order = st.blocks.order
    blocks = ref.JointBlocks(x, y, [st.blocks.members[b] for b in order],
                             [st.neigh[b] for b in order], m, device=device)
    r = ref.follow(blocks, theta0, steps, lr, nu, np.float32, "highest")
    variants = {"half_batch": dict(keep=lambda i, n: i % 2 == 0,
                                   over_kept=True)}
    if cell["chips"] > 1:
        q = cell["chips"]
        variants["no_exchange"] = dict(keep=lambda i, n: i < -(-n // q))
    out = {}
    for name, kw in variants.items():
        v = ref.follow(blocks, theta0, steps, lr, nu, np.float32, "highest",
                       **kw)
        out[name] = checks.fit_gaps(*v, *r, theta0)
    out["unchanged_state"] = {"grad_norm_gap": 1.0, "change_norm_gap": 1.0}
    return out


def uq_faults(cell, seed: int, device) -> dict:
    import jax
    import numpy as np

    import checks
    import data
    import phases
    import repro.core.predict as predmod
    from repro.core.predict import predict_sbv

    cfg, traffic = cell["config"], cell["traffic"]
    nu, n_sims, m_pred = cfg["nu"], traffic["n_sims"], cfg["m_pred"]
    x, y = data.make(cfg["generator"], seed, cfg["n_train"])
    (xt,) = phases.uq_tests(cfg, seed, 1, traffic["n_test"])
    params = phases.uq_params(cfg)
    blocks, preds = [[]], []
    with phases.Hooks() as hooks:
        hooks.wrap(predmod, "pack_queries",
                   lambda orig: phases._recording(orig, blocks, preds))
        predict_sbv(params, x, y, xt, bs_pred=cfg["bs_pred"], m_pred=m_pred,
                    nu=nu, n_sims=n_sims, seed=seed, backend="auto",
                    chunk_size=traffic["chunk_size"],
                    precision=cfg["precision"]["uq"])
    theta = [np.asarray(a, np.float64) for a in params]
    r_mean, r_var = checks.uq_reference(theta, x, y, xt, blocks[0], m_pred,
                                        nu, np.float64, "highest",
                                        jax.devices("cpu")[0])
    rng = np.random.default_rng([seed, 3])
    gaps = lambda sims: checks.uq_gaps(r_mean, r_var, sims, r_mean, r_var,
                                       n_sims)
    return {
        "half_draws": gaps(checks.sims_of(r_mean, r_var, n_sims // 2, rng)),
        "var_as_sd": gaps(checks.sims_of(r_mean, r_var, n_sims, rng,
                                         sd_of_var=lambda v: v)),
    }


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.ROOT / ".jax_cache")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        devices = run.require_chips(cell["chips"], cell["peaks"])
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    faults = {"fit": fit_faults, "uq": uq_faults}[cell["traffic"]["phase"]]
    for seed in args.seeds:
        out = {"control": program_control(cell, seed, devices),
               **faults(cell, seed, devices[0])}
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
