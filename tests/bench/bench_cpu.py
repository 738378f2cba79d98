"""Drive one benchmark run on the CPU at a tiny size: the harness's look
for a chip is skipped (the CPU devices are handed in), everything else
runs as on the chip. Used by the fault tests, and as a script by the one
that needs four devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python tests/bench/bench_cpu.py synth128m.fit-4chip [no_exchange]
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

import run  # noqa: E402

TINY = {"n_train": 2000, "bs": 20, "m": 16, "bs_pred": 10, "m_pred": 16}
TINY_TRAFFIC = {"nominal_step_s": 1e9, "nominal_sweep_s": 1e9, "n_test": 256,
                "chunk_size": 256, "min_sweeps": 2, "warm_train_rows": 200,
                "checked_blocks": 8}


def run_cell(name: str, seed: int = 2**31 + 11) -> dict:
    """One run of the cell at the tiny size; its result line."""
    import jax

    cell = run.load_cell(name)
    cell["config"].update(TINY)
    cell["traffic"].update(TINY_TRAFFIC)
    kind = jax.devices()[0].device_kind
    cell["peaks"]["devices"][kind] = {"flops_per_s": 1e12,
                                      "hbm_bytes_per_s": 1e11}
    args = argparse.Namespace(workload=name, seed=seed, seconds=1.0, trace=0)
    return run.execute(cell, args, find_chips=lambda n, p: jax.devices(),
                       t_start=time.perf_counter())


def no_exchange(monkeypatch_setattr):
    """Fault: the sharded chunk gradient returns one chip's share (the
    first quarter of each piece's blocks, over all n) as if the psum had
    been left out."""
    import repro.core.fit as fitmod

    orig = fitmod._chunk_grad_fn

    def factory(nu, backend, n, mesh=None, axis=None):
        if mesh is None:
            return orig(nu, backend, n)
        serial = orig(nu, backend, n)
        q = mesh.shape[axis]
        return lambda p, *arrs: serial(
            p, *(a[:a.shape[0] // q] for a in arrs))

    monkeypatch_setattr(fitmod, "_chunk_grad_fn", factory)


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[2] == "no_exchange":
        no_exchange(setattr)
    print(json.dumps(run_cell(sys.argv[1])["correct"]))
