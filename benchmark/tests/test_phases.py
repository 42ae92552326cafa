"""The phase readers on a trace written by hand (`lib/phases.py`): two
programs a step that share `fusion.1` and `copy.1` under different
phases, two devices, an operation no record holds, a module with no
record — each reader's value, the sum against the reduced trace's busy
time, and nothing read where there is nothing to read."""

import json
import os

import pytest

import run as harness
from lib import phases, trace

NAMES = trace.load_names(harness.HERE)
GRAD = {"fusion.1": "rows", "copy.1": "scatter", "gather.9": "gather", "gather.10": "scatter",
        "all-to-all.3": "exchange", "sort.2": "exchange", "fusion.4": "ffm_pair", "while.2": "rows"}
UPDATE = {"fusion.1": "update", "copy.1": "update", "scatter_optimizer.1": "scatter_optimizer",
          "norms.1": "health", "add.7": ""}
STEPS = 2


def _device(scale: float) -> dict:
    """One device plane: two steps of (grad program, update program),
    then a small program of the harness's own."""
    mods, ops = [], []
    t = 1000.0
    for _ in range(STEPS):
        mods.append(["jit_grad_part(11400714819323198485)", t, 100 * scale])
        ops.append(["while.2", t, 100 * scale])  # a wrapper: it covers its children and is skipped
        for name, at, dur in (("fusion.1", 10, 30), ("copy.1", 40, 10), ("gather.9[pallas]", 50, 20),
                              ("gather.10[pallas]", 70, 10), ("all-to-all.3", 80, 5), ("sort.2", 85, 5),
                              ("fusion.4", 90, 10)):
            ops.append([name, t + at * scale, dur * scale])
        t += 100 * scale
        mods.append(["jit_update_part(42)", t, 60 * scale])
        for name, at, dur in (("fusion.1", 0, 30), ("copy.1", 30, 10), ("scatter_optimizer.1[pallas]", 40, 8),
                              ("mystery.5", 48, 2), ("norms.1", 50, 4), ("add.7", 54, 1),
                              ("fusion.1", 20, 5)):  # the last lies inside the first: an instant counts once
            ops.append([name, t + at * scale, dur * scale])
        t += 70 * scale  # 5 idle, then
    mods.append(["jit_iota(7)", t, 10 * scale])
    ops.append(["fusion.1", t + 2 * scale, 6 * scale])  # a module with no record
    return {"lines": [{"name": "Steps", "events": [["0", 1000.0, t]]},
                      {"name": "XLA Modules", "events": mods},
                      {"name": "XLA Ops", "events": ops},
                      {"name": "Async XLA Ops", "events": [["copy-start.1", 1000.0, 50.0]]}]}


TRACE = {"planes": [
    {"name": "/device:TPU:0", **_device(1.0)},
    {"name": "/device:TPU:1", **_device(3.0)},
    {"name": "/host:CPU", "lines": [{"name": "python3", "events": [["bench:fit_pass", 0.0, 9e3], ["fusion.1", 0.0, 9e3]]}]},
]}
MAPS = {"jit_grad_part": GRAD, "jit_update_part": UPDATE}
MEAN = 2.0  # (1 + 3) / 2: every time below is a device's of scale 1, times this


def _run(**over):
    summary = trace.summarize(TRACE, NAMES, 2)
    return {"records": [], "window": {}, "trace": summary, "trace_steps": STEPS, "chips": 2, **over}


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    """A traced run's directory as the harness leaves it, with the
    hand-written trace where the profile would be."""
    monkeypatch.setattr(phases, "traced_run_dir", lambda: str(tmp_path))
    monkeypatch.setattr(trace, "load", lambda profile_dir: TRACE)

    def write(records):
        with open(tmp_path / "metrics.jsonl", "w") as f:
            f.write("not json\n")
            f.write(json.dumps({"step": 3, "step_time_p50_ms": 1.0}) + "\n")
            for module, scopes in records:
                f.write(json.dumps({"kind": "compile", "program": "x", "hlo_module": module,
                                    "op_scopes": scopes}) + "\n")

    return write


def test_attribute_books_shared_names_by_module():
    got = phases.attribute(TRACE, NAMES, MAPS, 2)
    ns = {k: v / MEAN / STEPS for k, v in got["labels"].items()}  # a step of the scale-1 device
    assert ns == pytest.approx({
        "rows": 30.0, "scatter": 20.0, "gather": 20.0, "exchange": 10.0, "ffm_pair": 10.0,
        "update": 40.0,  # fusion.1 30 + copy.1 10; the nested fusion.1 adds nothing
        "scatter_optimizer": 8.0,
        "unscoped": 2.0 + 4.0 + 1.0 + 6.0 / STEPS,  # no entry, health, "", no record
    })
    assert got["phases"]["rows"] == pytest.approx((30.0 + 10.0) * MEAN * STEPS)
    assert got["phases"]["update"] == pytest.approx(48.0 * MEAN * STEPS)
    assert got["events"]["update"] == 4 * STEPS * 2 and got["events"]["unscoped"] == (3 * STEPS + 1) * 2


def test_phases_and_the_remainder_sum_to_the_busy_time():
    got = phases.attribute(TRACE, NAMES, MAPS, 2)
    busy_ns = trace.busy_seconds(trace.device_ops(TRACE, NAMES, 2)) * 1e9
    assert sum(got["phases"].values()) + got["unscoped"] == pytest.approx(busy_ns, rel=1e-9)
    assert got["busy"] == pytest.approx(busy_ns, rel=1e-9)
    one = phases.attribute(TRACE, NAMES, MAPS, 1)  # the cell's chips, as `device_ops` cuts them
    assert one["busy"] == pytest.approx(trace.busy_seconds(trace.device_ops(TRACE, NAMES, 1)) * 1e9, rel=1e-9)


@pytest.mark.parametrize("metric,want", [
    ("phase_gather_ms", 20.0), ("phase_rows_ms", 40.0), ("phase_scatter_ms", 20.0),
    ("phase_update_ms", 48.0), ("phase_exchange_ms", 10.0),
])
def test_reader_gives_ms_a_step_and_chip(metric, want, run_dir):
    run_dir(MAPS.items())
    run = _run()
    assert harness.load_metric(metric).read(run) == pytest.approx(want * MEAN / 1e6)
    assert "_phases" in run  # the trace is loaded once a run


def test_unscoped_reader_and_the_sum_of_all_six(run_dir):
    run_dir(MAPS.items())
    run = _run()
    per_step = [harness.load_metric(f"phase_{p}_ms").read(run) for p in phases.PHASES]
    pct = harness.load_metric("phase_unscoped_pct").read(run)
    assert pct == pytest.approx(100.0 * (7.0 * STEPS + 6.0) / (145.0 * STEPS + 6.0))
    busy_ms = run["trace"]["busy_s"] * 1e3 / STEPS
    assert sum(per_step) + busy_ms * pct / 100.0 == pytest.approx(busy_ms, rel=1e-9)


def test_newest_record_of_a_module_wins(run_dir):
    run_dir([("jit_update_part", {"fusion.1": "rows"}), *MAPS.items()])
    assert harness.load_metric("phase_update_ms").read(_run()) == pytest.approx(48.0 * MEAN / 1e6)


@pytest.mark.parametrize("case", ["no trace", "no metrics file", "no compile record", "another vocabulary",
                                  "no module of the trace"])
def test_nothing_to_read(case, run_dir, tmp_path, monkeypatch):
    run = _run()
    if case == "no trace":
        run_dir(MAPS.items())
        run = _run(trace=None)
    elif case == "no metrics file":
        monkeypatch.setattr(phases, "traced_run_dir", lambda: None)
    elif case == "no compile record":
        run_dir([])
    elif case == "another vocabulary":  # a program from before the one vocabulary
        run_dir([("jit_grad_part", {"fusion.1": "grad", "gather.9": "gather"}),
                 ("jit_update_part", {"fusion.1": "optimizer"})])
    else:
        run_dir([("jit_train_step", GRAD)])
    for name in ("phase_gather_ms", "phase_rows_ms", "phase_scatter_ms", "phase_update_ms",
                 "phase_exchange_ms", "phase_unscoped_pct"):
        assert harness.load_metric(name).read(run) is None, name


def test_run_dir_is_the_newest_metrics_file(tmp_path, monkeypatch):
    monkeypatch.setattr(phases, "ROOT", str(tmp_path))
    assert phases.traced_run_dir() is None
    for i, cell in enumerate(("a", "b")):
        os.makedirs(tmp_path / "bench_run" / cell)
        path = tmp_path / "bench_run" / cell / "metrics.jsonl"
        path.write_text("{}\n")
        os.utime(path, (1000 + i, 1000 + i))
    assert phases.traced_run_dir() == str(tmp_path / "bench_run" / "b")


def test_benchmark_json_lists_the_six(run_dir):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    got = {m["name"]: m for m in bench["per_layer"] if m["name"].startswith("phase_")}
    assert list(got) == ["phase_gather_ms", "phase_rows_ms", "phase_scatter_ms", "phase_update_ms",
                         "phase_exchange_ms", "phase_unscoped_pct"]
    assert got["phase_scatter_ms"]["workloads"] == ["lr-s29.text-zipf", "fm-v10-s27-x4.text-zipf"]
    assert got["phase_exchange_ms"]["workloads"] == ["fm-v10-s27-x4.text-zipf"]
    for name, entry in got.items():
        assert set(entry.get("workloads", cells)) <= set(cells)
        assert entry["moves"] == "train_examples_per_s" and entry["source"] == "device_trace"
        assert harness.load_metric(name).META["layer"] == entry["layer"]
