"""BENCHMARK.json and the files it names: names, units, paths, budget,
and that every per-layer metric's end-to-end target is reported where
the metric is."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
CELLS = [w["name"] for w in SPEC["workloads"]]


def reported_by(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32
    for word in cmd:
        assert not word.startswith("/") and ".." not in word
    assert (ROOT / cmd[1]).is_file()
    assert any(cmd[1].startswith(p + "/") for p in SPEC["paths"])
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("obj", SPEC["configs"] + SPEC["workloads"] + METRICS,
                         ids=lambda o: o["name"])
def test_names_and_units(obj):
    assert NAME.match(obj["name"])
    if "unit" in obj:
        assert UNIT.match(obj["unit"])
        assert obj["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in obj:
            assert 1 <= len(obj[key]) <= 200
            assert "\n" not in obj[key] and "\t" not in obj[key]


def test_names_unique():
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        names = [o["name"] for o in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    path = ROOT / conf["file"]
    assert any(conf["file"].startswith(p + "/") for p in SPEC["paths"])
    body = json.loads(path.read_text())
    assert len(conf["reduced"]) <= 16
    for key in conf["reduced"]:
        assert NAME.match(key)
        assert body[key] != body["published"][key]
    for key, value in body["published"].items():
        if key not in conf["reduced"]:
            assert body[key] == value
    assert any(w["config"] == conf["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(cell):
    bench = ROOT / "bench"
    assert NAME.match(cell["traffic"]) and cell["chips"] in (1, 4)
    assert (bench / "traffic" / f"{cell['traffic']}.json").is_file()
    limits = json.loads((bench / "limits" / f"{cell['name']}.json").read_text())
    assert all(v >= 0 for v in limits.values())
    e2e = {m["name"] for m in SPEC["end_to_end"] if cell["name"] in reported_by(m)}
    layer = [m for m in SPEC["per_layer"] if cell["name"] in reported_by(m)]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], cell["name"])
        assert (bench / "metrics" / f"{m['name']}.py").is_file()


def test_pairs_chips_and_sources():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert all(1 <= len(k) <= 200 for k in layers)


def test_run_seconds_fits_a_full_check():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    total = (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200
    assert total <= 43200
    assert len(json.dumps(SPEC)) <= 64 * 1024
