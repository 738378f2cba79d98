"""Every idle nanosecond of every chip, attributed to the program's spans.

The program marks its layers with ``sbv.*`` host spans (``repro.spans``):
the fit's structure stages, each step with its pieces, Adam update and
loss sync, and ``predict_sbv`` with its training index, query packing and
fetches. Host and device events share the trace's clock, so each idle
stretch of a chip (the window less the union of its ``XLA Ops``, as
``trace_metrics.reduce`` defines it) splits at span boundaries into parts
that lie under one innermost ``sbv.`` span, or under none
(``OUTSIDE``).

    trace = load(profile_dir)            # harness and program spans
    win = trace.span("bench.window")
    idle = program_idle_s(trace, win.start_ns, win.end_ns)

``idle`` maps span names to idle seconds per chip, averaged over chips;
its values sum to ``window_s - busy_s_mean`` of ``trace_metrics.reduce``.
Spans of one thread nest, so the innermost covering span is the shortest.
"""
from __future__ import annotations

import trace_metrics as tm

PREFIX = "sbv."
OUTSIDE = "outside program spans"


def load(path: str) -> tm.Trace:
    """``trace_metrics.load`` keeping the harness's and the program's
    spans; ``program_spans`` picks the program's."""
    return tm.load(path, span_prefix=("bench.", PREFIX))


def program_spans(trace: tm.Trace) -> list:
    return [s for s in trace.spans if s.name.startswith(PREFIX)]


def segments(spans) -> list:
    """``[start, end, name]`` stretches of the clock, each under one
    innermost span; stretches under no span are left out."""
    edges = sorted({t for s in spans for t in (s.start_ns, s.end_ns)})
    by_start = sorted(spans, key=lambda s: s.start_ns)
    out, active, i = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while i < len(by_start) and by_start[i].start_ns <= lo:
            active.append(by_start[i])
            i += 1
        active = [s for s in active if s.end_ns > lo]
        if active:
            inner = min(active, key=lambda s: s.dur_ns)
            if out and out[-1][2] == inner.name and out[-1][1] == lo:
                out[-1][1] = hi
            else:
                out.append([lo, hi, inner.name])
    return out


def idle_intervals(events, lo: float, hi: float) -> list:
    """The window less the union of the chip's operations."""
    edges = [lo] + [x for iv in tm.busy_intervals(events, lo, hi)
                    for x in iv] + [hi]
    return [(s, t) for s, t in zip(edges[0::2], edges[1::2]) if t > s]


def program_idle_s(trace: tm.Trace, lo: float, hi: float) -> dict:
    """Idle seconds per chip, averaged over chips, keyed by the innermost
    ``sbv.`` span covering them (``OUTSIDE`` where none does)."""
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    segs = segments(program_spans(trace))
    out: dict = {}
    for events in trace.devices.values():
        j = 0
        for s, t in idle_intervals(events, lo, hi):
            # segments are sorted and disjoint, as are the idle stretches
            while j < len(segs) and segs[j][1] <= s:
                j += 1
            k, cur = j, s
            while cur < t:
                if k < len(segs) and segs[k][0] < t:
                    a, b, name = segs[k]
                    if a > cur:
                        out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (a - cur)
                        cur = a
                    end = min(b, t)
                    out[name] = out.get(name, 0.0) + (end - cur)
                    cur = end
                    if b <= t:
                        k += 1
                else:
                    out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (t - cur)
                    cur = t
    n = len(trace.devices)
    return {k: v / 1e9 / n for k, v in sorted(out.items(), key=lambda kv: -kv[1])}
