"""Reduce a JAX profiler trace to device metrics.

The profiler writes an XSpace (``*.xplane.pb``). On a TPU, each chip is a
plane named ``/device:TPU:<k>`` whose line ``XLA Ops`` holds one event per
operation executed on the chip's compute stream, named by its HLO
instruction (``%name = type opcode(...), custom_call_target="..."``).
Host threads are planes under ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` of this harness is an event there with
the annotation's name. Host and device events share one clock
(nanoseconds from the start of the trace).

Definitions, all clipped to a window [lo, hi) of that clock:

* busy: the union of the ``XLA Ops`` intervals of one chip (asynchronous
  copies, on their own line, are not counted);
* idle share: 1 - busy / window, averaged over chips;
* op time: the summed durations of the events of one operation;
* collective time: op time of the cross-chip operations (all-reduce,
  all-gather, reduce-scatter, collective-permute, all-to-all);
* idle gaps: the stretches between busy intervals of each chip, each
  named by the innermost harness span that covers its midpoint.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_SUFFIX = re.compile(r"\.\d+$")


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)  # plane name -> [Event]
    spans: list = field(default_factory=list)    # harness host spans

    def span(self, name: str) -> Event | None:
        """The first harness span of that name."""
        return next((s for s in self.spans if s.name == name), None)


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Read a trace file (``.xplane.pb``, optionally gzipped) or the
    newest one under a profile directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    trace = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(Event(e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
            trace.devices[plane.name] = sorted(evs, key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.spans.extend(
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events if e.name.startswith(span_prefix))
    trace.spans.sort(key=lambda e: e.start_ns)
    return trace


def op_name(event_name: str) -> str:
    """A stable short name of an ``XLA Ops`` event: the HLO instruction
    name without its numeric suffix, with the custom-call target for
    custom calls (``custom-call [Cholesky]``, ``jvp_jit_sbv_loglik_
    pallas__ [tpu_custom_call]``); other instructions keep their full
    name (``fusion.24``)."""
    head = event_name.split(" = ", 1)[0].lstrip("%").strip()
    target = _TARGET.search(event_name)
    if target:
        return f"{_SUFFIX.sub('', head)} [{target.group(1)}]"
    return head


def is_collective(event_name: str) -> bool:
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return head.startswith(COLLECTIVES)


def _clip(events, lo, hi):
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            yield e, s, t


def busy_intervals(events, lo, hi) -> list:
    """Merged [start, end) intervals in which some operation ran."""
    merged: list = []
    for _, s, t in _clip(events, lo, hi):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return merged


def reduce(trace: Trace, lo: float, hi: float) -> dict:
    """Device metrics of the window [lo, hi) in nanoseconds.

    Returns seconds: ``window_s``; per chip ``busy_s``, ``collective_s``;
    ``busy_s_mean``, ``collective_s_mean``; ``op_s`` (short op name ->
    seconds summed over chips); ``gaps`` ((label, seconds) per idle gap
    of every chip, longest first)."""
    if not trace.devices:
        raise ValueError("the trace holds no device plane: nothing ran on a "
                         "device, or the trace is not from an accelerator")
    busy, coll, op_s, gaps = {}, {}, {}, []
    for dev, events in trace.devices.items():
        ivals = busy_intervals(events, lo, hi)
        busy[dev] = sum(t - s for s, t in ivals) / 1e9
        c = 0.0
        for e, s, t in _clip(events, lo, hi):
            name = op_name(e.name)
            op_s[name] = op_s.get(name, 0.0) + (t - s) / 1e9
            if is_collective(e.name):
                c += (t - s) / 1e9
        coll[dev] = c
        edges = [lo] + [x for iv in ivals for x in iv] + [hi]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t > s:
                gaps.append((label_at(trace.spans, (s + t) / 2), (t - s) / 1e9))
    n = len(trace.devices)
    gaps.sort(key=lambda g: -g[1])
    return dict(window_s=(hi - lo) / 1e9, busy_s=busy, collective_s=coll,
                busy_s_mean=sum(busy.values()) / n,
                collective_s_mean=sum(coll.values()) / n,
                op_s=op_s, gaps=gaps, n_devices=n)


def label_at(spans, t_ns: float) -> str:
    """Name of the shortest harness span covering ``t_ns``."""
    best = None
    for s in spans:
        if s.start_ns <= t_ns < s.end_ns and (best is None
                                              or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best else "outside harness spans"


def kernel_s(reduced: dict, pattern: str) -> float:
    """Seconds, summed over chips, of the ops whose short name matches
    the regular expression ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["op_s"].items() if rx.search(k))


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device ops that took most time
    (seconds per chip, averaged over chips) and the longest idle gaps."""
    n = reduced["n_devices"]
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / n] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in reduced["gaps"][:top]]}
