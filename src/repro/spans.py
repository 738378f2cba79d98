"""Program spans: named stretches of host work on the profiler's clock.

``span(name, stats, key, **ids)`` marks its body as a
``jax.profiler.TraceAnnotation`` (host and device events of a
``jax.profiler.trace`` share one clock, so every idle stretch of a chip
lies under some span or under none) and adds the body's wall time into
``stats[key]``: summed into a float, appended to a list. ``ids`` tie the
spans of one unit of work together (``step=``, ``piece=``, ``chunk=``) and
appear as the event's arguments in the trace.

Names start with ``sbv.`` (docs/streaming.md lists them). A span costs
about a microsecond when no profiler runs, so spans mark layer
boundaries (a structure stage, a fit step, a prediction chunk), never a
per-block or per-point loop.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax


@contextmanager
def span(name: str, stats: dict | None = None, key: str | None = None,
         **ids):
    """Annotate the body as ``name`` and add its seconds to ``stats[key]``
    (also when the body raises). Usable as a decorator."""
    with jax.profiler.TraceAnnotation(name, **ids):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if stats is not None:
                dt = time.perf_counter() - t0
                if isinstance(stats.get(key), list):
                    stats[key].append(dt)
                else:
                    stats[key] = stats.get(key, 0.0) + dt
