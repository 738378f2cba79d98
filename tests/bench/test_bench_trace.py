"""The trace-to-metrics reduction: on hand-made events with known answers,
and on a small trace recorded on a TPU v5e (a traced run of the fit
phase at 20,000 MetaRVM points, two window steps)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "bench"))
import trace_metrics as tm  # noqa: E402

E = tm.Event
LOGLIK = ('%jvp_jit_sbv_loglik_pallas__.1 = f32[200,1,1]{2,1,0} custom-call('
          'f32[1,10]{1,0} %a), custom_call_target="tpu_custom_call"')
CHOL = ('%custom-call.62 = f32[200,128,128]{2,1,0} custom-call(f32[200,128,'
        '128]{2,1,0} %slice.135), custom_call_target="Cholesky"')
PSUM = "%all-reduce.3 = f32[] all-reduce(f32[] %x), replica_groups={}"


def synthetic():
    t = tm.Trace()
    t.devices["/device:TPU:0"] = [E(LOGLIK, 100, 300), E(CHOL, 350, 100),
                                  E("%fusion.4 = f32[] fusion()", 420, 60),
                                  E(PSUM, 700, 100)]
    t.devices["/device:TPU:1"] = [E(LOGLIK, 100, 500), E(PSUM, 800, 100)]
    t.spans = [E("bench.window", 0, 1000), E("bench.fit.adam_update", 480, 200)]
    return t


def test_busy_union_and_window_clip():
    t = synthetic()
    ivals = tm.busy_intervals(t.devices["/device:TPU:0"], 0, 1000)
    assert ivals == [[100, 480], [700, 800]]
    assert tm.busy_intervals(t.devices["/device:TPU:0"], 150, 750) == \
        [[150, 480], [700, 750]]


def test_reduce_known_answers():
    r = tm.reduce(synthetic(), 0, 1000)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"]["/device:TPU:0"] == pytest.approx(480e-9)
    assert r["busy_s"]["/device:TPU:1"] == pytest.approx(600e-9)
    assert r["busy_s_mean"] == pytest.approx(540e-9)
    assert r["collective_s_mean"] == pytest.approx(100e-9)
    assert r["op_s"]["jvp_jit_sbv_loglik_pallas__ [tpu_custom_call]"] == \
        pytest.approx(800e-9)
    assert r["op_s"]["custom-call [Cholesky]"] == pytest.approx(100e-9)
    assert tm.kernel_s(r, r"sbv_loglik_pallas.*\[tpu_custom_call\]") == \
        pytest.approx(800e-9)
    # the gap 480..700 on chip 0 lies under the Adam span, the rest only
    # under the window span
    labels = dict((round(s * 1e9), lab) for lab, s in r["gaps"])
    assert labels[220] == "bench.fit.adam_update"
    assert labels[100] == "bench.window"
    b = tm.breakdown(r)
    assert b["device_ops"][0] == ["jvp_jit_sbv_loglik_pallas__ [tpu_custom_call]",
                                  pytest.approx(400e-9)]
    assert len(b["idle_gaps"]) <= 10 and b["idle_gaps"][0][1] >= b["idle_gaps"][-1][1]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tm.reduce(tm.Trace(), 0, 1)


def test_recorded_tpu_trace():
    t = tm.load(str(HERE / "data" / "fit_small.xplane.pb.gz"))
    assert list(t.devices) == ["/device:TPU:0"]
    win = t.span("bench.window")
    assert win is not None and win.dur_ns > 0
    r = tm.reduce(t, win.start_ns, win.end_ns)
    assert 0 < r["busy_s_mean"] <= r["window_s"]
    assert r["collective_s_mean"] == 0.0
    kern = tm.kernel_s(r, r"sbv_loglik_pallas.*\[tpu_custom_call\]")
    assert 0 < kern < r["busy_s_mean"]
    assert any(k.endswith("[Cholesky]") for k in r["op_s"])
    spans = {s.name for s in t.spans}
    assert {"bench.window", "bench.fit.adam_update",
            "bench.fit.piece_dispatch"} <= spans
    b = tm.breakdown(r)
    assert 1 <= len(b["device_ops"]) <= 10 and 1 <= len(b["idle_gaps"]) <= 10
    # a union of intervals is no longer than their sum
    assert sum(r["op_s"].values()) >= r["busy_s_mean"] * (1 - 1e-9)
