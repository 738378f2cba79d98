"""A fit run on the CPU comes out correct, and with the timed path broken
underneath it comes out not correct: a step that leaves the state
unchanged, half of the blocks left out with the mean over the rest, and
(on four devices) the exchange between chips left out."""
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import bench_cpu  # noqa: E402

CELL = "metarvm.fit"


def test_sound_fit_is_correct():
    line = bench_cpu.run_cell(CELL)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 3
    assert set(line["metrics"]) == {"setup_s", "fit_points_per_s"}
    assert list(line)[-1] == "checks"


def test_unchanged_state_is_caught(monkeypatch):
    import repro.core.fit as fitmod

    monkeypatch.setattr(fitmod, "adam_update",
                        lambda grads, state, params, *a, **k: (params, state))
    line = bench_cpu.run_cell(CELL)
    assert not line["correct"]
    assert line["checks"]["change_norm_gap"]["value"] == 1.0


def test_half_batch_is_caught(monkeypatch):
    import jax

    import repro.core.fit as fitmod

    orig = fitmod._chunk_grad_fn

    def factory(*a, **k):
        fn = orig(*a, **k)

        def half(params, *arrs):
            v, g = fn(params, *(x[: x.shape[0] // 2] for x in arrs))
            return 2 * v, jax.tree.map(lambda t: 2 * t, g)
        return half

    monkeypatch.setattr(fitmod, "_chunk_grad_fn", factory)
    line = bench_cpu.run_cell(CELL)
    assert not line["correct"]
    assert line["checks"]["loss_gap"]["value"] > line["checks"]["loss_gap"]["limit"]


def test_exchange_left_out_is_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = str(HERE / "bench_cpu.py")
    out = [subprocess.run([sys.executable, script, "synth128m.fit-4chip", *f],
                          env=env, capture_output=True, text=True, timeout=600)
           for f in ([], ["no_exchange"])]
    for p in out:
        assert p.returncode == 0, p.stderr[-3000:]
    sound, fault = (p.stdout.strip().splitlines()[-1] for p in out)
    assert (sound, fault) == ("true", "false")
