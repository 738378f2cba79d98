#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. It names a
configuration (``bench/configs/<name>.json``, the deployment's sizes) and a
traffic mix (``bench/traffic/<name>.json``, read by the phase its
``phase`` key names in ``bench/phases.py``); its correctness limits are in
``bench/limits/<cell>.json`` and each per-layer metric has a reader,
``bench/metrics/<metric>.py``. Nothing here names a cell, so a new cell is
new files and a new entry.

A run makes its data from ``--seed``, sets up (data, host structure or
index, compiles or persistent-cache loads, warm-up), measures about
``--seconds`` seconds, then checks what the measured calls produced
against the plain reference (``bench/reference.py``). With ``--trace 0``
it reports the cell's end-to-end metrics; with ``--trace 1`` it profiles
the window and reports the per-layer metrics. The last line of standard
output is one JSON object; the numbers compared, each beside its limit,
are the last lines of standard error and the last key of that object.

It exits 2, printing no result, where JAX finds no TPU, fewer chips than
the cell asks for, or a chip kind missing from ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]


class NoChip(RuntimeError):
    """No accelerator of the kind and count the cell needs."""


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic, limits and metrics."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"unknown workload {name!r}")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    bench = root / "bench"

    def reports(metric):
        return name in metric.get("workloads", [name])

    return dict(
        name=name, chips=int(cell["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                           .read_text()),
        limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if reports(m)],
        per_layer=[m for m in spec["per_layer"] if reports(m)],
        peaks=json.loads((bench / "peaks.json").read_text()),
    )


def require_chips(chips: int, peaks: dict):
    """The JAX devices, or NoChip: the run measures a TPU or nothing."""
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    if kind not in peaks["devices"]:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devices


class CompileClock:
    """JAX's own compile events: lowering to MLIR and the backend compile,
    which includes a persistent-cache load. Each event is kept with the
    host-clock time at which it ended."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    CACHE = ("/jax/compilation_cache/cache_hits",
             "/jax/compilation_cache/cache_misses")

    def __init__(self):
        import jax

        self.events: list = []
        self.cache: dict = {e.rsplit("/", 1)[-1]: 0 for e in self.CACHE}
        self.modules: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, secs, fun_name="", **_):
        if event in self.EVENTS:
            self.events.append((event, secs, time.perf_counter()))
            if event == self.EVENTS[1]:
                self.modules[fun_name] = self.modules.get(fun_name, 0.0) + secs

    def _on_count(self, event, **_):
        if event in self.CACHE:
            self.cache[event.rsplit("/", 1)[-1]] += 1

    def seconds(self, lo: float, hi: float) -> float:
        return sum(s for _, s, t in self.events if lo <= t < hi)

    def split(self, lo: float, hi: float) -> dict:
        """Seconds of each kind of compile event that ended in [lo, hi)."""
        return {e.rsplit("/", 1)[-1]: sum(s for e2, s, t in self.events
                                          if e2 == e and lo <= t < hi)
                for e in self.EVENTS}

    def compiles(self, lo: float, hi: float) -> int:
        """Backend compiles (or cache loads) that ended in [lo, hi)."""
        return sum(1 for e, _, t in self.events
                   if e == self.EVENTS[1] and lo <= t < hi)


def read_metric(name: str, run: dict):
    """Value of one per-layer metric from its reader, or None."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def result_line(cell: dict, out: dict, devices, trace: bool) -> dict:
    """The contract's last line; ``checks`` comes last."""
    kind = devices[0].device_kind
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices), "memory_peak_bytes": out["memory_peak"]}
    metrics = {}
    if trace:
        red = out["run"]["trace"]
        dev.update(busy_s=red["busy_s_mean"], window_s=red["window_s"])
        for m in cell["per_layer"]:
            v = read_metric(m["name"], out["run"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    checks = out["checks"]
    correct = (out["failed"] == 0 and bool(checks) and all(
        math.isfinite(v) and v <= lim for v, lim in checks.values()))
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": dev}
    if trace:
        line["breakdown"] = out["breakdown"]
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    return line


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(cell: dict, args, find_chips=require_chips,
            t_start: float = T_START) -> dict:
    """One run of the cell: its result line (raises NoChip)."""
    devices = find_chips(cell["chips"], cell["peaks"])
    import phases

    clock = CompileClock()
    out = phases.PHASES[cell["traffic"]["phase"]](
        cell, args, devices[:cell["chips"]], clock, t_start)
    line = result_line(cell, out, devices, bool(args.trace))
    top = sorted(clock.modules.items(), key=lambda kv: -kv[1])[:5]
    print("set-up compile seconds " + json.dumps(
        clock.split(t_start, out["run"]["window_t0"])) + " persistent cache "
        + json.dumps(clock.cache) + " slowest " + json.dumps(top),
        file=sys.stderr)
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload)
    # The persistent compile cache lives at one fixed path inside the
    # checkout, whatever the machine sets: the path is part of the key.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        line = execute(cell, args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in line["checks"].items():
        print(f"check {k} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
