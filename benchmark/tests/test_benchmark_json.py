"""BENCHMARK.json against the contract's letter rules and against the
files it names."""

import json
import os
import re

import pytest

import run as harness

ROOT = harness.ROOT
HERE = harness.HERE
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _names():
    out = [("config", c["name"]) for c in BENCH["configs"]]
    out += [("workload", w["name"]) for w in BENCH["workloads"]]
    out += [("traffic", w["traffic"]) for w in BENCH["workloads"]]
    out += [("metric", m["name"]) for m in METRICS]
    out += [("reduced", k) for c in BENCH["configs"] for k in c["reduced"]]
    return out


@pytest.mark.parametrize("kind,name", _names())
def test_name_is_of_the_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    extra = set(metric) - {"name", "unit", "better", "bound", "source", "layer", "moves", "workloads"}
    assert not extra, extra
    for w in metric.get("workloads", []):
        assert w in {c["name"] for c in BENCH["workloads"]}


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_its_reader(metric):
    mod = harness.load_metric(metric["name"])
    assert callable(mod.read)
    for key in ("layer", "unit", "source", "better"):
        assert mod.META[key] == metric[key], key
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"].split("_"):
        assert metric["unit"] == "%"


def test_top_level_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(BENCH["workloads"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    for key in ("source", "why"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"] and cfg["reduced"] == entry["reduced"]
    assert cfg["source"] == entry["source"]
    assert not [k for k in entry["reduced"] if k.endswith(("_dim", "_rank"))]
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    assert os.path.exists(os.path.join(HERE, "reference", cfg["reference"] + ".py"))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert os.path.exists(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        assert json.load(f)["chips"] == cell["chips"]
    reported = [m for m in BENCH["per_layer"] if "workloads" not in m or cell["name"] in m["workloads"]]
    assert reported


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, _, files in os.walk(HERE):
        if "__pycache__" in base:
            continue
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(base, f), ROOT))
