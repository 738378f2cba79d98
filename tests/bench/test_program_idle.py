"""Idle time attributed to the program's ``sbv.*`` spans: hand-made events
with known answers, and a trace recorded on a TPU v5e (a traced run of
the fit phase at 20,000 MetaRVM points) whose spans come from the
program itself."""
import gzip
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "bench"))
import program_idle as pi  # noqa: E402
import trace_metrics as tm  # noqa: E402

E = tm.Event


def synthetic():
    t = tm.Trace()
    t.devices["/device:TPU:0"] = [E("%a", 100, 200), E("%b", 350, 100),
                                  E("%c", 700, 100)]
    t.devices["/device:TPU:1"] = [E("%a", 100, 500), E("%b", 800, 100)]
    t.spans = [E("bench.window", 0, 1000), E("bench.fit.adam_update", 390, 270),
               E("sbv.fit.step", 50, 700), E("sbv.fit.piece", 80, 240),
               E("sbv.fit.adam_update", 400, 250), E("sbv.fit.sync", 650, 90)]
    return t


def test_known_answer_two_chips_nested_spans_and_a_gap_under_none():
    t = synthetic()
    idle = pi.program_idle_s(t, 0, 1000)
    # chip 0 idle: 0-100, 300-350, 450-700, 800-1000; chip 1: 0-100,
    # 600-800, 900-1000. The piece nests in the step; harness spans
    # name nothing here.
    want = {pi.OUTSIDE: 225, "sbv.fit.adam_update": 125, "sbv.fit.sync": 70,
            "sbv.fit.step": 50, "sbv.fit.piece": 30}
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert list(idle) == list(want)  # largest first
    r = tm.reduce(t, 0, 1000)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s_mean"])


def test_window_clips_and_segments_merge():
    t = synthetic()
    idle = pi.program_idle_s(t, 320, 760)
    # chip 0: 320-350 step, 450-650 adam, 650-700 sync; chip 1: 600-650
    # adam, 650-740 sync, 740-750 step, 750-760 outside
    want = {"sbv.fit.adam_update": 125, "sbv.fit.sync": 70,
            "sbv.fit.step": 20, pi.OUTSIDE: 5}
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    segs = pi.segments(pi.program_spans(t))
    assert [s[2] for s in segs] == ["sbv.fit.step", "sbv.fit.piece",
                                    "sbv.fit.step", "sbv.fit.adam_update",
                                    "sbv.fit.sync", "sbv.fit.step"]
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))


def test_no_program_spans_is_all_outside():
    t = synthetic()
    t.spans = [s for s in t.spans if s.name.startswith("bench.")]
    idle = pi.program_idle_s(t, 0, 1000)
    assert idle == {pi.OUTSIDE: pytest.approx(500e-9)}
    with pytest.raises(ValueError):
        pi.program_idle_s(tm.Trace(), 0, 1)


def test_recorded_tpu_trace_every_idle_second_has_an_owner():
    """Recorded on one chip with the program's spans: the idle time sums
    to the window less the busy time, every owner is a program span or
    none, each window step opens its spans once (step, pieces, update,
    sync), and the likelihood kernel keeps its name under the jvp."""
    from jax.profiler import ProfileData

    path = HERE / "data" / "fit_spans.xplane.pb.gz"
    t = pi.load(str(path))
    win = t.span("bench.window")
    r = tm.reduce(t, win.start_ns, win.end_ns)
    idle = pi.program_idle_s(t, win.start_ns, win.end_ns)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s_mean"],
                                               rel=1e-9)
    names = {s.name for s in pi.program_spans(t)}
    assert {"sbv.fit.step", "sbv.fit.piece", "sbv.fit.adam_update",
            "sbv.fit.sync"} <= names
    assert set(idle) <= names | {pi.OUTSIDE}
    assert tm.kernel_s(r, r"sbv_loglik_pallas \[tpu_custom_call\]") > 0

    with gzip.open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    per_step: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("sbv.fit.") and e.name != "sbv.fit.steps":
                        step = dict(e.stats)["step"]
                        per_step.setdefault(step, []).append(e.name)
    # the profiler starts inside step 0's update: spans opened before it
    # are not in the trace
    whole = [v for v in per_step.values() if "sbv.fit.step" in v]
    assert len(whole) >= 2
    for spans in whole:
        pieces = spans.count("sbv.fit.piece")
        assert pieces >= 1
        assert sorted(set(spans) - {"sbv.fit.piece"}) == [
            "sbv.fit.adam_update", "sbv.fit.step", "sbv.fit.sync"]
        assert len(spans) == 3 + pieces
