"""``repro.spans.span``: the program's host spans and the stats they feed."""
import glob

import jax
import pytest

from repro import spans
from repro.spans import span


class _Clock:
    """A fake ``time.perf_counter`` that the test advances by hand."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(spans.time, "perf_counter", c)
    return c


@pytest.mark.parametrize("start, want", [(0.0, 2.5), (1.0, 3.5),
                                         ([0.5], [0.5, 2.5]), (None, 2.5)])
def test_span_sums_floats_and_appends_to_lists(clock, start, want):
    stats = {} if start is None else {"k": start}
    with span("sbv.test", stats, "k"):
        clock.now += 2.5
    assert stats["k"] == want


def test_nested_spans_each_time_their_own_body(clock):
    stats = {"outer": 0.0, "inner": []}
    with span("sbv.outer", stats, "outer"):
        clock.now += 1.0
        for _ in range(2):
            with span("sbv.inner", stats, "inner"):
                clock.now += 0.25
        clock.now += 0.5
    assert stats == {"outer": 2.0, "inner": [0.25, 0.25]}


def test_exception_closes_span_and_records_time(clock, monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **ids):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name, exc[0]))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    stats = {}
    with pytest.raises(ValueError):
        with span("sbv.fails", stats, "k"):
            clock.now += 1.5
            raise ValueError("boom")
    assert stats == {"k": 1.5}
    assert seen == [("enter", "sbv.fails"), ("exit", "sbv.fails", ValueError)]


def test_without_stats_only_annotates(monkeypatch):
    seen = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name, **ids: seen.append((name, ids))
                        or _Null())
    with span("sbv.bare", step=3):
        pass
    assert seen == [("sbv.bare", {"step": 3})]


def test_decorator_spans_every_call(clock):
    stats = {"k": []}

    @span("sbv.call", stats, "k")
    def work(dt):
        clock.now += dt
        return dt

    assert work(1.0) == 1.0 and work(2.0) == 2.0
    assert stats["k"] == [1.0, 2.0]


def test_ids_reach_the_profiler_trace(tmp_path):
    """In a real profiler trace the span is a host event of its own name,
    its ids are the event's arguments, and nesting is kept."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with span("sbv.fit.step", step=7):
        with span("sbv.fit.piece", step=7, piece=2):
            jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    events = {e.name: e for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events if e.name.startswith("sbv.")}
    step, piece = events["sbv.fit.step"], events["sbv.fit.piece"]
    assert dict(step.stats) == {"step": 7}
    assert dict(piece.stats) == {"step": 7, "piece": 2}
    assert step.start_ns <= piece.start_ns
    assert piece.start_ns + piece.duration_ns <= step.start_ns + step.duration_ns
