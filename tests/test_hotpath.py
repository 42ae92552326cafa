"""Hot-path attribution layer (round 11): PipelineProfiler unit
coverage, the prefetch queue counters + starvation detection under the
fault injectors' pacing, the no-pipeline-surface-when-off contract (no
pipeline records/counters without train.pipeline_metrics), the step
records' `host` window against the pipeline record's, trainer-integrated
kind="pipeline" windows through metrics_report --check/--health,
tools/pipeline_attrib.py's table/verdict/host-gap record, the
bench_lab core sweep + probe-wrapper CLIs, perf_ledger's BENCH_LAB /
BENCH_PIPELINE folding with the measured-gather roofline citation and
downward gating, and the tools/smoke_hotpath.sh CI gate end to end.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.telemetry import (
    HOST_STAGES,
    PIPELINE_CONSUMER_STAGES,
    PIPELINE_PRODUCER_STAGES,
    PIPELINE_STAGES,
    PipelineProfiler,
    Registry,
    host_field,
    pipeline_fields,
    pipeline_verdict,
    span,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tool(name: str) -> str:
    return os.path.join(REPO_ROOT, "tools", name)


def run_tool(args, **kw):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable] + args, capture_output=True, text=True, env=env, **kw
    )


# ------------------------------------------------------- PipelineProfiler


def test_profiler_stages_and_window():
    prof = PipelineProfiler(registry=Registry())
    prof.start()
    prof.add("parse", 0.25)
    prof.add_many({"read": 0.05, "hash": 0.1})
    with span("plan", prof):
        time.sleep(0.01)
    prof.count_batch(64)
    prof.observe_queue(2, 2)
    rec = pipeline_fields(prof.take_window())
    for s in PIPELINE_STAGES:
        assert f"{s}_s" in rec
    assert rec["parse_s"] == pytest.approx(0.25)
    assert rec["read_s"] == pytest.approx(0.05)
    assert rec["plan_s"] > 0
    assert rec["batches"] == 1 and rec["rows"] == 64
    assert rec["queue_depth"] == 2 and rec["queue_cap"] == 2
    assert rec["wall_s"] > 0
    # the window reset: a second flush with no activity is empty
    assert prof.take_window() == {}
    # run totals survive the window reset
    totals, elapsed = prof.totals()
    assert totals["parse"] == pytest.approx(0.25)
    assert elapsed > 0


def test_profiler_registry_gauges():
    reg = Registry()
    prof = PipelineProfiler(registry=reg)
    prof.start()
    snap = reg.snapshot()
    # pre-registered at start() so profiled runs always carry them
    assert snap["pipeline.queue_depth"] == 0
    assert snap["pipeline.producer_blocked_s"] == 0.0
    prof.add("producer_wait", 1.5)
    prof.observe_queue(1, 4)
    snap = reg.snapshot()
    assert snap["pipeline.producer_blocked_s"] == pytest.approx(1.5)
    assert snap["pipeline.queue_depth"] == 1


def test_unpublished_profiler_accumulates_without_gauges():
    """Armed by the metrics stream alone (publish=False): the window
    fills, the registry stays free of pipeline.* gauges."""
    reg = Registry()
    prof = PipelineProfiler(registry=reg, publish=False)
    prof.start()
    prof.add("producer_wait", 1.5)
    prof.observe_queue(1, 4)
    assert reg.snapshot() == {}
    win = prof.take_window()
    assert win["producer_wait"] == pytest.approx(1.5)
    assert win["queue_depth"] == 1 and win["queue_cap"] == 4
    assert prof.take_window() == {}


def test_pipeline_verdict_directions():
    # consumer starved + parse dominant -> host-bound in parse
    v = pipeline_verdict({"queue_wait": 6.0, "parse": 6.1, "read": 0.5}, 10.0)
    assert v.startswith("host-bound in parse: 61%")
    # producer blocked -> device-bound
    v = pipeline_verdict({"producer_wait": 9.0, "dispatch": 8.0}, 10.0)
    assert v.startswith("device-bound")
    # neither -> balanced
    v = pipeline_verdict({"parse": 0.5, "device": 0.5}, 10.0)
    assert v.startswith("balanced")
    assert pipeline_verdict({}, 0.0) == "no pipeline windows"


# ------------------------------------------------- prefetch queue counters


@pytest.mark.parametrize("kind", ["plain", "carried"])
def test_prefetch_counters_slow_consumer(kind):
    """A slow consumer must show up as producer-blocked time and a full
    queue — the starvation signature the satellite asks for. The same
    of a pass of the producer fit() carries between passes, whose
    read-ahead, waiting on a full queue for a pass nobody has begun,
    adds nothing."""
    from xflow_tpu.data.pipeline import PassProducer, PassSpec, prefetch

    reg = Registry()
    prof = PipelineProfiler(registry=reg)
    prof.start()

    def gen():
        for i in range(8):
            yield i

    if kind == "plain":
        stream = prefetch(gen(), depth=2, profiler=prof)
    else:
        spec = PassSpec(shards=(), skips=())
        producer = PassProducer(lambda spec, defer: gen(), depth=2, profiler=prof)
        producer.start(spec, then=spec)
        stream = producer.batches()
    got = []
    for item in stream:
        time.sleep(0.02)  # artificially slow consumer
        got.append(item)
    assert got == list(range(8))
    totals, _ = prof.totals()
    # the producer spent most of its life blocked on the full queue
    assert totals["producer_wait"] > 0.05
    snap = reg.snapshot()
    assert snap["pipeline.producer_blocked_s"] == pytest.approx(
        totals["producer_wait"], abs=1e-5
    )
    assert "pipeline.queue_depth" in snap
    if kind == "carried":
        deadline = time.time() + 10
        while producer._head < 3 and time.time() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)  # the read-ahead waits, full, for its adoption
        assert prof.totals()[0]["producer_wait"] == totals["producer_wait"]
        assert producer.adopt(spec, spec) == 3
        assert list(producer.batches()) == list(range(8))
        producer.close()


def test_prefetch_without_profiler_unchanged():
    from xflow_tpu.data.pipeline import prefetch

    assert list(prefetch(iter(range(5)))) == list(range(5))


def test_parse_line_matches_profiled_halves():
    """parse_line keeps its fused single-pass hot loop; the profiled
    path goes through split_line + hash_ids. The two must agree on
    every token-rule corner or the profiled stream would differ from
    the stream it claims to attribute."""
    from xflow_tpu.data.libffm import hash_ids, parse_line, split_line

    lines = [
        "1\t0:abc:1 3:def:1",
        "0 2:xyz:1",  # space-separated label
        "junk\t5:q:1",  # strtod junk label -> 0
        "1\tgarbage novalue",  # all tokens malformed: zero features
        "",  # empty: not a row
        "1",  # label only: not a row
        "0.5\t1e2:tok:1 nan:other:1",  # strtod fgid corners
    ]
    for line in lines:
        full = parse_line(line, 12, salt=7)
        halves = split_line(line)
        if full is None:
            assert halves is None or not line.strip()
            if halves is None:
                continue
        label, fields, ids = halves
        assert full is not None
        assert full[0] == label
        np.testing.assert_array_equal(
            full[1], np.asarray(fields, dtype=np.int32)
        )
        np.testing.assert_array_equal(full[2], hash_ids(ids, 12, salt=7))


# ------------------------------------------------- trainer integration


def _train_tiny(tmp_path, run_name="run", rows=320, **extra):
    from xflow_tpu.data.synth import generate_shards
    from xflow_tpu.train.trainer import Trainer

    data = str(tmp_path / "train")
    if not os.path.exists(data + "-00000"):
        generate_shards(data, 1, rows, num_fields=6, ids_per_field=50, seed=0)
    cfg = override(Config(), **{
        "model.name": "lr",
        "data.train_path": data,
        "data.log2_slots": 12,
        "data.max_nnz": 8,
        "data.batch_size": 64,
        "model.num_fields": 6,
        "train.epochs": 1,
        "train.pred_dump": False,
        "train.log_every": 2,
        "train.metrics_path": str(tmp_path / run_name / "metrics_rank0.jsonl"),
        **extra,
    })
    trainer = Trainer(cfg)
    res = trainer.fit()
    from xflow_tpu.jsonl import read_jsonl

    return res, read_jsonl(str(tmp_path / run_name / "metrics_rank0.jsonl"))


def test_trainer_pipeline_records(tmp_path):
    res, recs = _train_tiny(
        tmp_path, **{"train.pipeline_metrics": True}
    )
    assert res.steps == 5
    pipe = [r for r in recs if r.get("kind") == "pipeline"]
    assert pipe, "no kind=pipeline records from a profiled run"
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    from metrics_report import PIPELINE_KEYS

    for r in pipe:
        for key in PIPELINE_KEYS:
            assert key in r, f"pipeline record lacks {key}"
        wall = r["wall_s"]
        assert wall > 0
        # the per-thread concurrency invariant (with the flush slack
        # the --check gate allows)
        prod = sum(r[f"{s}_s"] for s in PIPELINE_PRODUCER_STAGES)
        cons = sum(r[f"{s}_s"] for s in PIPELINE_CONSUMER_STAGES)
        assert prod <= wall * 1.25 + 0.05, pipe
        assert cons <= wall * 1.25 + 0.05
    # rows were counted (320 rows over the windows)
    assert sum(r["rows"] for r in pipe) == 320
    # profiled runs carry the prefetch gauges in their counters
    assert any(
        "pipeline.queue_depth" in (r.get("counters") or {}) for r in recs
    )
    # the full --check gate (pipeline schema included) passes
    r = run_tool([tool("metrics_report.py"),
                  str(tmp_path / "run"), "--check"])
    assert r.returncode == 0, r.stderr
    # --health prints the bottleneck verdict
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "run"),
                  "--health"])
    assert r.returncode == 0, r.stderr
    assert "input pipeline" in r.stdout


def test_profiler_off_stream_is_pipeline_free(tmp_path):
    """train.pipeline_metrics off: the stream holds no pipeline records
    and no pipeline.* counters, though the metrics stream arms the host
    timeline (`host` in the window records)."""
    from xflow_tpu.telemetry import default_registry

    default_registry().reset()  # a prior profiled test must not leak gauges
    res, recs = _train_tiny(tmp_path)
    assert res.steps == 5
    assert not any(r.get("kind") == "pipeline" for r in recs)
    for r in recs:
        for key in r.get("counters") or {}:
            assert not key.startswith("pipeline."), f"leaked counter {key}"


def test_armed_by_metrics_path_has_host_and_no_pipeline_surface(tmp_path):
    """train.metrics_path alone arms the host timeline: every window
    record carries `host` (one field a stage), the first `boundary` —
    and train.pipeline_metrics keeps its one meaning: without it, no
    kind="pipeline" record and no pipeline.* gauge."""
    from xflow_tpu.telemetry import default_registry

    default_registry().reset()
    res, recs = _train_tiny(tmp_path)
    assert res.steps == 5
    wins = [r for r in recs if "step_time_p50_ms" in r]
    assert len(wins) == 3  # steps 2 and 4, and the final record's tail
    for w in wins:
        assert set(w["host"]) == {host_field(s) for s in HOST_STAGES} | {"batches"}
    assert sum(w["host"]["batches"] for w in wins) == 5
    assert "fit_open_ms" in wins[0]["boundary"]
    assert not any("boundary" in w for w in wins[1:])
    assert not any(r.get("kind") == "pipeline" for r in recs)
    assert not any(
        k.startswith("pipeline.") for r in recs for k in r.get("counters") or {}
    )
    # and the stream passes the full --check gate with the new fields
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "run"), "--check"])
    assert r.returncode == 0, r.stderr


def test_host_sums_equal_pipeline_windows(tmp_path):
    """Both carriers on: `host` and the kind="pipeline" record are two
    formats of the same window, so stage for stage their sums agree
    (the four consumer stages of the pipeline record fold the five the
    loop stamps)."""
    _, recs = _train_tiny(tmp_path, **{"train.pipeline_metrics": True})
    hosts = [r["host"] for r in recs if "host" in r]
    pipe = [r for r in recs if r.get("kind") == "pipeline"]
    assert len(hosts) == len(pipe) == 3
    host = lambda key: sum(h[key] for h in hosts)
    legacy = lambda key: sum(p[key] for p in pipe) * 1e3
    tol = dict(abs=1e-3 * len(pipe) * 2)  # both sides rounded
    for s in PIPELINE_PRODUCER_STAGES + ("transfer",):
        assert host(host_field(s)) == pytest.approx(legacy(f"{s}_s"), **tol), s
    assert host("data_wait_ms") == pytest.approx(legacy("queue_wait_s"), **tol)
    assert host("prev_ready_ms") == pytest.approx(legacy("device_s"), **tol)
    assert host("dispatch_call_ms") + host("loop_other_ms") == pytest.approx(
        legacy("dispatch_s"), **tol
    )
    assert sum(h["batches"] for h in hosts) == sum(p["batches"] for p in pipe) == 5


def test_profiled_then_off_run_no_gauge_leak(tmp_path):
    """The zero-overhead contract is per-RUN: a profiled fit followed
    by an off fit in the SAME process must leave no pipeline.* gauges
    in the off run's counters (fit() drops them at teardown) — no
    manual registry reset here on purpose."""
    _train_tiny(tmp_path, run_name="run_on",
                **{"train.pipeline_metrics": True})
    _, recs = _train_tiny(tmp_path, run_name="run_off2")
    assert not any(r.get("kind") == "pipeline" for r in recs)
    for r in recs:
        for key in r.get("counters") or {}:
            assert not key.startswith("pipeline."), f"leaked gauge {key}"


def test_starvation_detection_slow_consumer(tmp_path, monkeypatch):
    """Regression: an artificially slow consumer (the fault injectors'
    fit-loop pacing, testing/faults.fit_delays_from_env) must read as
    producer-blocked in the pipeline windows — the device-bound
    signature, never host-bound."""
    monkeypatch.setenv("XFLOW_FAULT_STEP_DELAY_S", "0.02")
    res, recs = _train_tiny(
        tmp_path, run_name="run_slow", **{"train.pipeline_metrics": True}
    )
    assert res.steps == 5
    pipe = [r for r in recs if r.get("kind") == "pipeline"]
    assert pipe
    wall = sum(r["wall_s"] for r in pipe)
    blocked = sum(r["producer_wait_s"] for r in pipe)
    host = sum(
        r[f"{s}_s"] for r in pipe
        for s in ("read", "parse", "hash", "batch", "pad", "plan")
    )
    # the producer spent most of the run blocked on the full queue,
    # dwarfing its actual host work
    assert blocked > 0.05
    assert blocked > host
    assert blocked / wall > 0.3
    # and the shared verdict names the right side
    stages = {
        s: sum(r[f"{s}_s"] for r in pipe) for s in PIPELINE_STAGES
    }
    assert pipeline_verdict(stages, wall).startswith("device-bound")


# ------------------------------------------------- metrics_report gates


def _stamped(i, **kw):
    return {"ts": float(i), "rank": 0, "run_id": "r", "gen": 0, **kw}


def _pipe_rec(i, step, **overrides):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    from metrics_report import PIPELINE_KEYS

    rec = _stamped(i, kind="pipeline", step=step)
    for key in PIPELINE_KEYS:
        rec.setdefault(key, 0.001)
    rec["wall_s"] = 1.0
    rec["batches"] = 2
    rec["rows"] = 128
    rec["queue_depth"] = 1
    rec["queue_cap"] = 2
    rec.update(overrides)
    return rec


def _write_jsonl(path, records):
    path.write_text("".join(json.dumps(r) + "\n" for r in records))


def test_metrics_report_pipeline_gate_ok(tmp_path):
    _write_jsonl(tmp_path / "m.jsonl", [_pipe_rec(1, 10), _pipe_rec(2, 20)])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl"),
                  "--check"])
    assert r.returncode == 0, r.stderr


def test_metrics_report_pipeline_gate_missing_key(tmp_path):
    bad = _pipe_rec(1, 10)
    del bad["queue_depth"]
    _write_jsonl(tmp_path / "m.jsonl", [bad])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl"),
                  "--check"])
    assert r.returncode == 2
    assert "pipeline keys" in r.stderr


def test_metrics_report_pipeline_gate_sum_exceeds_wall(tmp_path):
    # one thread claiming 3x the wall is impossible — the gate fires
    bad = _pipe_rec(1, 10, parse_s=3.0)
    _write_jsonl(tmp_path / "m.jsonl", [bad])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl"),
                  "--check"])
    assert r.returncode == 2
    assert "producer-side stage times sum" in r.stderr
    bad = _pipe_rec(1, 10, device_s=3.0)
    _write_jsonl(tmp_path / "m.jsonl", [bad])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl"),
                  "--check"])
    assert r.returncode == 2
    assert "consumer-side stage times sum" in r.stderr


def test_metrics_report_pipeline_gate_nonpositive_wall(tmp_path):
    _write_jsonl(tmp_path / "m.jsonl", [_pipe_rec(1, 10, wall_s=0.0)])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl"),
                  "--check"])
    assert r.returncode == 2
    assert "non-positive wall_s" in r.stderr


# ------------------------------------------------------- pipeline_attrib


def test_pipeline_attrib_report_and_bench(tmp_path):
    _, _ = _train_tiny(
        tmp_path, rows=640, **{"train.pipeline_metrics": True,
                               "train.log_every": 4}
    )
    out = tmp_path / "attrib.json"
    bench = tmp_path / "BENCH_PIPELINE.json"
    r = run_tool([tool("pipeline_attrib.py"), str(tmp_path / "run"),
                  "--json", str(out), "--bench-json", str(bench),
                  "--round", "11"])
    assert r.returncode == 0, r.stderr
    assert "verdict:" in r.stdout and "% of wall" in r.stdout
    att = json.loads(out.read_text())
    assert att["windows"] >= 2
    assert att["rows"] == 640
    # the consumer stages tile the fit loop: high coverage even on the
    # tiny CPU run (the smoke script pins the >= 95% acceptance bar on
    # a longer run; this bound just guards against gross regression)
    assert att["attributed_pct"] > 60.0
    rec = json.loads(bench.read_text())
    assert rec["metric"] == "pipeline_e2e_examples_per_sec"
    assert rec["value"] > 0
    assert rec["round"] == 11
    assert rec["host_gap_ratio"] >= 1.0
    assert rec["device_bound_examples_per_sec"] >= rec["value"]
    assert set(rec["stage_pct"]) == set(PIPELINE_STAGES)


def test_pipeline_attrib_unprofiled_run_exits_1(tmp_path):
    _write_jsonl(tmp_path / "m.jsonl", [_stamped(1, step=1, loss=0.5)])
    r = run_tool([tool("pipeline_attrib.py"), str(tmp_path / "m.jsonl")])
    assert r.returncode == 1
    assert "train.pipeline_metrics" in r.stderr


def test_pipeline_attrib_missing_path_exits_2(tmp_path):
    r = run_tool([tool("pipeline_attrib.py"), str(tmp_path / "nope")])
    assert r.returncode == 2


# ------------------------------------------------------------- bench_lab


def test_bench_lab_core_sweep_cpu(tmp_path):
    out = tmp_path / "BENCH_LAB.json"
    r = run_tool(["-m", "xflow_tpu.tools.bench_lab", "--suite", "core",
                  "--table-log2", "8,9", "--nnz-log2", "7",
                  "--row-width", "4", "--iters", "1", "--inner", "2",
                  "--round", "3", "--out", str(out)])
    assert r.returncode == 0, r.stderr
    d = json.loads(out.read_text())
    assert d["kind"] == "bench_lab"
    assert d["metric"] == "lab_gather_ns_per_element"
    assert d["unit"] == "ns/element" and d["value"] > 0
    assert d["round"] == 3
    # the full matrix: 3 ops x 2 table sizes x 1 nnz
    assert len(d["cells"]) == 6
    ops = {c["op"] for c in d["cells"]}
    assert ops == {"gather", "scatter_add", "segment_sum"}
    for c in d["cells"]:
        assert c["ns_per_element"] > 0 and c["time_ms"] > 0
    # CompileRecorder cost stamps ride along on CPU
    assert any(c.get("bytes_accessed") for c in d["cells"])
    assert any(c.get("achieved_gbps") for c in d["cells"])


def test_bench_lab_headline_is_largest_gather(tmp_path):
    out = tmp_path / "BENCH_LAB.json"
    r = run_tool(["-m", "xflow_tpu.tools.bench_lab", "--suite", "core",
                  "--table-log2", "7,9", "--nnz-log2", "6,7",
                  "--ops", "gather", "--row-width", "2",
                  "--iters", "1", "--inner", "2", "--out", str(out)])
    assert r.returncode == 0, r.stderr
    d = json.loads(out.read_text())
    assert d["headline_cell"] == "lab_gather_s9_n7_f32"


def test_bench_lab_unknown_suite_errors():
    r = run_tool(["-m", "xflow_tpu.tools.bench_lab", "--suite", "nope"])
    assert r.returncode == 2


def test_probe_wrappers_delegate_to_bench_lab():
    """The six retired probes keep their CLIs as thin wrappers over the
    lab (satellite: one entry point for the kernel arc). --help must
    resolve through the wrapper without importing jax-heavy paths."""
    for name in ("microbench_tpu.py", "layout_probe.py", "mosaic_probe.py",
                 "scatter_experiment.py", "rowsum_probe.py",
                 "hostplane_bench.py"):
        src = open(tool(name)).read()
        assert "bench_lab" in src, f"{name} does not delegate to bench_lab"
        r = run_tool([tool(name), "--help"])
        assert r.returncode == 0, f"{name} --help failed: {r.stderr}"
        assert "suite" in r.stdout


# ------------------------------------------------------------ perf_ledger


def _lab_record(value_scale=1.0, rnd=1):
    return {
        "kind": "bench_lab", "device": "cpu0", "host_cores": 1,
        "metric": "lab_gather_ns_per_element", "value": 100.0 * value_scale,
        "unit": "ns/element", "headline_cell": "lab_gather_s10_n8_f32",
        "row_width": 4, "iters": 1, "inner": 2, "seed": 0, "round": rnd,
        "cells": [
            {"op": "gather", "table_log2": 10, "nnz_log2": 8, "dtype": "f32",
             "row_width": 4, "time_ms": 0.1 * value_scale,
             "ns_per_element": 100.0 * value_scale,
             "flops": 10.0, "bytes_accessed": 2000.0, "achieved_gbps": 0.02,
             "compile_time_s": 0.05},
            {"op": "scatter_add", "table_log2": 10, "nnz_log2": 8,
             "dtype": "f32", "row_width": 4, "time_ms": 0.2 * value_scale,
             "ns_per_element": 200.0 * value_scale},
        ],
    }


def test_perf_ledger_folds_lab_and_pipeline(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "metric": "lr_examples_per_sec", "value": 1000.0,
        "unit": "examples/sec"}))
    (tmp_path / "BENCH_LAB.json").write_text(json.dumps(_lab_record()))
    (tmp_path / "BENCH_PIPELINE.json").write_text(json.dumps({
        "metric": "pipeline_e2e_examples_per_sec", "value": 5000.0,
        "unit": "examples/sec", "round": 1,
        "device_bound_examples_per_sec": 20000.0, "host_gap_ratio": 4.0}))
    out = tmp_path / "ledger.json"
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--json", str(out)])
    assert r.returncode == 0, r.stderr
    assert "Sparse-primitive lab" in r.stdout
    assert "measured gather random-access latency" in r.stdout
    got = json.loads(out.read_text())
    metrics = {e["metric"] for e in got["entries"]}
    assert {"lab_gather_ns_per_element", "lab_gather_s10_n8_f32",
            "lab_scatter_add_s10_n8_f32", "pipeline_e2e_examples_per_sec",
            "device_bound_examples_per_sec"} <= metrics
    labs = [e for e in got["entries"] if e["series"] == "lab"]
    assert all(e["round"] == 1 for e in labs)
    # the roofline block cites the MEASURED gather cell
    roof = got["roofline"]
    assert roof["measured_gather_ns_per_element"] == 100.0
    assert roof["gather_cell"] == "lab_gather_s10_n8_f32"


def test_perf_ledger_pipeline_never_roofline_headline(tmp_path):
    """A round-stamped host-gap record must NOT become the roofline's
    per-chip headline — its e2e rate is the host-limited number, not
    the device bench."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "metric": "lr_examples_per_sec", "value": 1000.0,
        "unit": "examples/sec"}))
    (tmp_path / "BENCH_PIPELINE.json").write_text(json.dumps({
        "metric": "pipeline_e2e_examples_per_sec", "value": 50.0,
        "unit": "examples/sec", "round": 99}))
    out = tmp_path / "ledger.json"
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--json", str(out), "--markdown", ""])
    assert r.returncode == 0, r.stderr
    roof = json.loads(out.read_text())["roofline"]
    assert roof["metric"] == "lr_examples_per_sec"


def test_bench_lab_rejects_unknown_dtype(tmp_path):
    r = run_tool(["-m", "xflow_tpu.tools.bench_lab", "--suite", "core",
                  "--table-log2", "7", "--nnz-log2", "6", "--dtypes", "f16",
                  "--row-width", "2", "--iters", "1", "--inner", "1",
                  "--out", str(tmp_path / "o.json")])
    assert r.returncode != 0
    assert "f16" in (r.stderr + r.stdout)


def test_perf_ledger_lab_gates_downward(tmp_path):
    (tmp_path / "BENCH_LAB_r01.json").write_text(
        json.dumps(_lab_record(1.0, rnd=1)))
    (tmp_path / "BENCH_LAB_r02.json").write_text(
        json.dumps(_lab_record(0.9, rnd=2)))  # faster: no regression
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--regress", "--markdown", ""])
    assert r.returncode == 0, r.stderr
    (tmp_path / "BENCH_LAB_r02.json").write_text(
        json.dumps(_lab_record(10.0, rnd=2)))  # 10x slower: regression
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--regress", "--markdown", ""])
    assert r.returncode == 3
    assert "lab_gather" in r.stderr


# -------------------------------------------------------------- smoke gate


def test_smoke_hotpath_script(tmp_path):
    """The hot-path CI gate end to end (tools/smoke_hotpath.sh):
    profiled run -> --check/--health -> pipeline_attrib coverage >= 95%
    -> zero-overhead-off -> lab sweep -> both records through the
    ledger -> lab regression mechanics."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "smoke_hotpath.sh"),
         str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "smoke_hotpath: OK" in r.stdout
    # the datapoints stayed in the workdir (never the repo root from a
    # test run) and went through the ledger path
    assert (tmp_path / "BENCH_PIPELINE.json").exists()
    assert (tmp_path / "BENCH_LAB.json").exists()
    assert (tmp_path / "ledger.md").exists()
