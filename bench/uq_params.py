#!/usr/bin/env python3
"""Fit the kernel parameters a configuration's UQ sweep predicts under.

    JAX_PLATFORMS=cpu python3 bench/uq_params.py metarvm-50m

A UQ sweep serves a fitted emulator, so its configuration states the
fitted parameters (``uq_params``). This script makes them: the program's
``fit_sbv`` in float64 on the host CPU over ``uq_params_fit["n"]`` rows of
the configuration's generator (data seed ``uq_params_fit["seed"]``), with
the configuration's own block size and neighbour count, for
``outer_rounds`` x ``inner_steps`` Adam steps. It prints the object to
put under ``uq_params``, rounded to 4 significant digits. It is run once
when a configuration is made, never by a benchmark run.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def main(name: str):
    import numpy as np

    import data
    from repro.core import SBVConfig
    from repro.core.fit import fit_sbv

    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    fit = cfg["uq_params_fit"]
    x, y = data.make(cfg["generator"], fit["seed"], fit["n"])
    res = fit_sbv(x, y, SBVConfig(n_blocks=fit["n"] // cfg["bs"], m=cfg["m"],
                                  seed=fit["seed"]),
                  nu=cfg["nu"], lr=fit["lr"], inner_steps=fit["inner_steps"],
                  outer_rounds=fit["outer_rounds"], backend="ref",
                  verbose=True)
    p = res.params

    def sig4(v):
        return float(f"{float(v):.4g}")

    print(f"final nll / n = {res.history[-1][2]!r}", file=sys.stderr)
    print(json.dumps({"sigma2": sig4(p.sigma2),
                      "beta": [sig4(b) for b in np.asarray(p.beta)],
                      "nugget": sig4(p.nugget)}))


if __name__ == "__main__":
    main(sys.argv[1])
