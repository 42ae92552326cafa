"""Performance-attribution layer (round 9): CompileRecorder unit
coverage on fake lowered/compiled seams and real jax, the CPU-backend
memory_stats guard, the window records' `host` fields, tools/trace_attrib.py
on device planes written by hand, tools/perf_ledger.py
consolidation + regression-gate exit codes, the metrics_report
compile-schema / exactly-once-recompile gates, and the
tools/smoke_perf.sh CI gate end to end.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.telemetry import (
    CompileRecorder,
    Registry,
    StepTimer,
    device_memory_stats,
    hbm_window_fields,
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


# --------------------------------------------------- CompileRecorder (fakes)


HLO_TEXT = """\
HloModule jit_step

ENTRY %main (a: f32[8], b: f32[]) -> f32[] {
  %a = f32[8]{0} parameter(0)
  %b = f32[] parameter(1)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/jit(main)/transpose(jvp(rows))/gather/scatter-add" source_file="x.py"}
  %add.2 = f32[] add(%b, %b), metadata={op_name="jit(step)/jit(main)/update/add"}
  ROOT %noise.3 = f32[] add(%add.2, %b), metadata={op_name="jit(step)/jit(main)/mul"}
}
"""


class FakeCompiled:
    def __init__(self):
        self.calls = 0

    def cost_analysis(self):
        # the list-of-dicts shape jax 0.4.x returns
        return [{"flops": 10.0, "bytes accessed": 100.0}]

    def memory_analysis(self):
        return SimpleNamespace(
            argument_size_in_bytes=11,
            output_size_in_bytes=22,
            temp_size_in_bytes=33,
            generated_code_size_in_bytes=44,
        )

    def as_text(self):
        return HLO_TEXT

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return "compiled-ran"


class FakeLowered:
    def __init__(self, compiled):
        self._compiled = compiled

    def compile(self):
        return self._compiled


class FakeJitted:
    """The .lower().compile() seam without jax."""

    def __init__(self, fail=False):
        self.compiled = FakeCompiled()
        self.lowers = 0
        self.direct_calls = 0
        self.fail = fail

    def lower(self, *args, **kwargs):
        self.lowers += 1
        if self.fail:
            raise RuntimeError("no AOT for you")
        return FakeLowered(self.compiled)

    def __call__(self, *args, **kwargs):
        self.direct_calls += 1
        return "jit-ran"


class ListSink:
    def __init__(self):
        self.records = []

    def append(self, rec):
        self.records.append(rec)


def test_compile_recorder_records_and_caches():
    sink = ListSink()
    rec = CompileRecorder(sink=sink, registry=Registry())
    fake = FakeJitted()
    fn = rec.wrap("train_step", fake)
    x = np.zeros((4, 2), np.float32)
    assert fn(x) == "compiled-ran"
    assert fn(x) == "compiled-ran"  # same signature: cache hit
    assert fake.lowers == 1 and fake.compiled.calls == 2
    assert len(sink.records) == 1
    r = sink.records[0]
    assert r["kind"] == "compile" and r["program"] == "train_step"
    assert r["compile_time_s"] >= 0 and r["compiles"] == 1
    assert r["flops"] == 10.0 and r["bytes_accessed"] == 100.0
    assert r["argument_bytes"] == 11 and r["temp_bytes"] == 33
    # op_scopes: the LAST scope component wins (a `gather` under
    # `transpose(` is the scatter), the primitive (final component) never
    # matches, an operation whose path names no phase is there with ""
    assert r["op_scopes"] == {"fusion.1": "scatter", "add.2": "update", "noise.3": ""}
    assert r["hlo_module"] == "jit_step"  # the trace-join key
    assert r["analysis_s"] >= 0 and "op_scopes_dropped" not in r
    assert rec.recompiles == 0


def test_compile_recorder_new_signature_is_not_a_recompile():
    sink = ListSink()
    rec = CompileRecorder(sink=sink, registry=Registry())
    fn = rec.wrap("train_step", FakeJitted())
    fn(np.zeros((4, 2), np.float32))
    fn(np.zeros((8, 2), np.float32))  # new shape: new program
    assert len(sink.records) == 2
    assert [r["compiles"] for r in sink.records] == [1, 2]
    assert sink.records[0]["sig"] != sink.records[1]["sig"]
    assert rec.recompiles == 0


def test_compile_recorder_recompile_counted():
    reg = Registry()
    rec = CompileRecorder(sink=ListSink(), registry=reg)
    fake = FakeJitted()
    x = np.zeros((2,), np.float32)
    rec.record("train_step", fake, x)
    rec.record("train_step", fake, x)  # same (program, sig) twice
    assert rec.recompiles == 1
    snap = reg.snapshot()
    assert snap["compile.recompiles"] == 1
    assert snap["compile.programs"] == 1


def test_compile_recorder_raises_what_the_compiler_raised():
    """A refused lower/compile is the run's error: no stderr note, no
    second attempt under plain jit with no record."""
    rec = CompileRecorder(sink=ListSink(), registry=Registry())
    fake = FakeJitted(fail=True)
    fn = rec.wrap("train_step", fake)
    x = np.zeros((2,), np.float32)
    with pytest.raises(RuntimeError, match="no AOT for you"):
        fn(x)
    assert fake.lowers == 1 and fake.direct_calls == 0
    assert rec.records == []


def test_compile_recorder_real_jax():
    import jax
    import jax.numpy as jnp

    sink = ListSink()
    rec = CompileRecorder(sink=sink, registry=Registry())
    fn = rec.wrap("train_step.real", jax.jit(lambda a, b: (a @ b).sum()))
    x = jnp.ones((16, 16))
    got = fn(x, x)
    assert float(got) == float((np.ones((16, 16)) @ np.ones((16, 16))).sum())
    assert fn(x, x) is not None  # cache hit, no second record
    assert len(sink.records) == 1
    r = sink.records[0]
    assert r["compile_time_s"] > 0
    assert r["flops"] and r["flops"] > 0
    assert r["bytes_accessed"] and r["bytes_accessed"] > 0
    # tracing + lowering and XLA's compile are timed apart, and tile
    # the one number the record always had
    assert r["lower_s"] > 0 and r["xla_compile_s"] > 0
    assert r["lower_s"] + r["xla_compile_s"] == pytest.approx(
        r["compile_time_s"], abs=2e-6
    )


# ------------------------------------------------------------- HBM gauges


def test_device_memory_stats_cpu_guard():
    # the CPU allocator reports nothing: the guard yields {} (never a
    # raise), so window records simply omit the HBM fields
    assert device_memory_stats() == {}
    assert hbm_window_fields(Registry()) == {}


def test_device_memory_stats_fake_device():
    dev = SimpleNamespace(
        memory_stats=lambda: {
            "bytes_in_use": 1000,
            "peak_bytes_in_use": 2000,
            "bytes_limit": 4000,
            "irrelevant": "x",
        }
    )
    stats = device_memory_stats(dev)
    assert stats == {"bytes_in_use": 1000, "peak_bytes_in_use": 2000,
                     "bytes_limit": 4000}
    reg = Registry()
    fields = hbm_window_fields(reg, device=dev)
    assert fields["hbm_bytes_in_use"] == 1000
    assert fields["hbm_peak_bytes"] == 2000
    assert fields["hbm_bytes_limit"] == 4000
    snap = reg.snapshot()
    assert snap["hbm.bytes_in_use"] == 1000
    assert snap["hbm.peak_bytes"] == 2000


def test_device_memory_stats_erroring_device():
    def boom():
        raise RuntimeError("allocator exploded")

    assert device_memory_stats(SimpleNamespace(memory_stats=boom)) == {}


# --------------------------------------- trainer integration (end to end)


def _train_tiny(tmp_path, **extra):
    from xflow_tpu.data.synth import generate_shards
    from xflow_tpu.train.trainer import Trainer

    data = str(tmp_path / "train")
    generate_shards(data, 1, 320, num_fields=6, ids_per_field=50, seed=0)
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
        "train.metrics_path": str(tmp_path / "run" / "metrics_rank0.jsonl"),
        **extra,
    })
    trainer = Trainer(cfg)
    res = trainer.fit()
    from xflow_tpu.jsonl import read_jsonl

    return res, read_jsonl(str(tmp_path / "run" / "metrics_rank0.jsonl"))


def test_trainer_emits_compile_records(tmp_path):
    res, recs = _train_tiny(tmp_path)
    assert res.steps == 5
    comp = [r for r in recs if r.get("kind") == "compile"]
    assert len(comp) == 1  # one train program, compiled exactly once
    c = comp[0]
    assert c["program"] == "train_step"
    assert c["compile_time_s"] > 0 and c["flops"] > 0 and c["bytes_accessed"] > 0
    assert c["op_scopes"]  # the trace-attribution join map
    assert c["lower_s"] + c["xla_compile_s"] == pytest.approx(
        c["compile_time_s"], abs=2e-6
    )
    # the metrics stream arms the host timeline: every window record
    # carries the profiler's window as `host`, one field a stage
    from xflow_tpu.telemetry import HOST_STAGES, host_field

    wins = [r for r in recs if "step_time_p50_ms" in r and not r.get("final")]
    assert wins
    for w in wins:
        assert set(w["host"]) == {host_field(s) for s in HOST_STAGES} | {"batches"}
        assert all(v >= 0 for v in w["host"].values())
    assert not any("achieved_flops_per_s" in r for r in recs)
    # CPU: no HBM fields (the guard)
    assert not any("hbm_bytes_in_use" in r for r in recs)
    # the run passes the full --check gate including the compile rules
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "run"), "--check"])
    assert r.returncode == 0, r.stderr
    # and the bench record carries the compile context
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "run"),
                  "--bench-json", "-"])
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["compiled_programs"] == 1
    assert rec["compile_time_s"] > 0


def test_trainer_compile_metrics_off(tmp_path):
    res, recs = _train_tiny(tmp_path, **{"train.compile_metrics": False})
    assert res.steps == 5
    assert not any(r.get("kind") == "compile" for r in recs)


# ------------------------------------------------------------ trace_attrib
# tools/trace_attrib.py is ONE join: a device plane's operations ->
# instruction name -> the module whose interval on the same plane holds
# the event -> that module's `op_scopes` -> phase. The planes here are
# written by hand in the form benchmark/tests/data holds a real one in.


def _planes(tmp_path, ops, modules, name="t.planes.json", extra_planes=()) -> str:
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "Steps", "events": [["0", 0.0, 1e6]]},
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}, *extra_planes]
    path = tmp_path / name
    path.write_text(json.dumps({"planes": planes}))
    return str(path)


def _compile_jsonl(tmp_path, records) -> str:
    run_dir = tmp_path / "run"
    run_dir.mkdir(exist_ok=True)
    base = {"ts": 1.0, "rank": 0, "run_id": "fix", "kind": "compile", "sig": "abc",
            "compile_time_s": 0.1, "flops": 1.0, "bytes_accessed": 2.0}
    (run_dir / "metrics_rank0.jsonl").write_text(
        "".join(json.dumps({**base, **r}) + "\n" for r in records))
    return str(run_dir)


STEP_OPS = [  # two executions of one step program, ns
    ["gather.5", 0.0, 100e3], ["fusion.1", 100e3, 300e3], ["scatter_optimizer.1", 400e3, 50e3],
    ["mystery.9", 450e3, 25e3],
    ["gather.5", 500e3, 100e3], ["fusion.1", 600e3, 300e3], ["scatter_optimizer.1", 900e3, 50e3],
    ["mystery.9", 950e3, 25e3],
]
STEP_MODULES = [["jit_train_step(4001266711181281872)", 0.0, 480e3],
                ["jit_train_step(4001266711181281872)", 500e3, 480e3]]
STEP_RECORD = {"program": "train_step", "hlo_module": "jit_train_step", "op_scopes": {
    "gather.5": "gather", "fusion.1": "ffm_pair", "scatter_optimizer.1": "scatter_optimizer",
    "add.3": ""}}


def test_trace_attrib_joins_the_trace_with_the_map(tmp_path):
    trace = _planes(tmp_path, STEP_OPS, STEP_MODULES)
    out = tmp_path / "attrib.json"
    r = run_tool([tool("trace_attrib.py"), trace,
                  "--run-dir", _compile_jsonl(tmp_path, [STEP_RECORD]), "--json", str(out)])
    assert r.returncode == 0, r.stderr
    got = json.loads(out.read_text())
    assert got["steps"] == 2 and got["devices"] == 1
    phases = got["phases"]
    # ms a step: a label inside a phase has its own row and counts as the phase
    assert phases["gather"]["ms"] == pytest.approx(0.1)
    assert phases["rows"]["ms"] == pytest.approx(0.3) and phases["ffm_pair"]["ms"] == pytest.approx(0.3)
    assert phases["update"]["ms"] == pytest.approx(0.05)
    assert phases["scatter_optimizer"]["events"] == 2
    assert got["busy_ms"] == pytest.approx(0.475)
    assert "rows" in r.stdout and "ms/step" in r.stdout  # the table rendered


def test_trace_attrib_unscoped_share_where_the_map_lacks_an_operation(tmp_path):
    # an operation the record does not hold is `unscoped`, by name and
    # with its share — never guessed at from its name
    trace = _planes(tmp_path, STEP_OPS, STEP_MODULES)
    out = tmp_path / "a.json"
    r = run_tool([tool("trace_attrib.py"), trace,
                  "--run-dir", _compile_jsonl(tmp_path, [STEP_RECORD]), "--json", str(out)])
    assert r.returncode == 0, r.stderr
    unscoped = json.loads(out.read_text())["phases"]["unscoped"]
    assert unscoped["ms"] == pytest.approx(0.025) and unscoped["events"] == 2
    assert unscoped["pct"] == pytest.approx(100 * 25 / 475)
    # with no record at all every operation is: the tool says so and
    # does not fall back to the names (a `gather.5` is not "gather")
    r = run_tool([tool("trace_attrib.py"), trace, "--steps", "2", "--json", str(out)])
    assert r.returncode == 0 and "every operation is unscoped" in r.stderr
    phases = json.loads(out.read_text())["phases"]
    assert list(phases) == ["unscoped"] and phases["unscoped"]["pct"] == pytest.approx(100.0)


def test_trace_attrib_module_keyed_join(tmp_path):
    # two programs reuse the names `fusion.1` and `copy.1` (instruction
    # names are unique within a module only): the module whose interval
    # holds the event picks ITS program's map, never the other's — and an
    # operation missing from its own module's map is unscoped instead of
    # borrowing a colliding entry
    run_dir = _compile_jsonl(tmp_path, [
        {"program": "train_step.fullshard.fm", "hlo_module": "jit_grad_part",
         "op_scopes": {"fusion.1": "rows", "copy.1": "scatter", "only_grad.2": "gather"}},
        {"program": "update_step.fullshard.fm", "hlo_module": "jit_update_part",
         "op_scopes": {"fusion.1": "rows"}},  # superseded by the next record of the module
        {"program": "update_step.fullshard.fm", "hlo_module": "jit_update_part",
         "op_scopes": {"fusion.1": "update", "copy.1": "update"}},
    ])
    trace = _planes(tmp_path, [
        ["fusion.1", 0.0, 100e3], ["copy.1", 100e3, 60e3],
        ["fusion.1", 200e3, 40e3], ["copy.1", 240e3, 30e3], ["only_grad.2", 270e3, 7e3],
    ], [["jit_grad_part(1)", 0.0, 190e3], ["jit_update_part(2)", 200e3, 90e3]])
    out = tmp_path / "a.json"
    r = run_tool([tool("trace_attrib.py"), trace, "--run-dir", run_dir, "--json", str(out)])
    assert r.returncode == 0, r.stderr
    phases = json.loads(out.read_text())["phases"]
    assert phases["rows"]["ms"] == pytest.approx(0.1)
    assert phases["scatter"]["ms"] == pytest.approx(0.06)
    assert phases["update"]["ms"] == pytest.approx(0.07)
    assert phases["unscoped"]["ms"] == pytest.approx(0.007)
    assert "gather" not in phases


def test_trace_attrib_leaves_out_summary_rows_and_host_events(tmp_path):
    # a device plane's `XLA Modules` / `Steps` lines span the same wall
    # time as every operation on `XLA Ops`, a control-flow wrapper covers
    # its children, and the host's plane is not the device's: counting any
    # of them would double the total and halve every share
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["fusion.1", 0.0, 1e6], ["xflow:fit", 0.0, 1e6]]}]}
    trace = _planes(tmp_path, [["while.3", 0.0, 140e3], ["fusion.1", 0.0, 100e3]],
                    [["jit_train_step(7)", 0.0, 140e3]], extra_planes=[host])
    out = tmp_path / "a.json"
    r = run_tool([tool("trace_attrib.py"), trace, "--json", str(out), "--run-dir", _compile_jsonl(
        tmp_path, [{"program": "train_step", "hlo_module": "jit_train_step",
                    "op_scopes": {"fusion.1": "rows", "while.3": "rows"}}])])
    assert r.returncode == 0, r.stderr
    got = json.loads(out.read_text())
    assert got["busy_ms"] == pytest.approx(0.1)  # the module span, the wrapper and the host are out
    assert got["phases"]["rows"]["pct"] == pytest.approx(100.0) and got["phases"]["rows"]["events"] == 1


def test_trace_attrib_empty_trace_exits_1(tmp_path):
    # a CPU capture: host planes only
    empty = tmp_path / "empty.planes.json"
    empty.write_text(json.dumps({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [["fusion.1", 0.0, 5.0]]}]}]}))
    r = run_tool([tool("trace_attrib.py"), str(empty)])
    assert r.returncode == 1
    assert "no device operation" in r.stderr


def test_trace_attrib_missing_trace_exits_2(tmp_path):
    r = run_tool([tool("trace_attrib.py"), str(tmp_path)])
    assert r.returncode == 2


# ------------------------------------------------------------- perf_ledger


def _ledger_corpus(root):
    (root / "BENCH_r01.json").write_text(json.dumps({
        "n": 1, "cmd": "bench", "rc": 0,
        "parsed": {"metric": "lr_examples_per_sec", "value": 1000.0,
                   "unit": "examples/sec", "vs_baseline": 1.28,
                   "fm_examples_per_sec": 700.0, "fm_vs_baseline": 0.9},
    }))
    (root / "BENCH_r02.json").write_text(json.dumps({
        "metric": "lr_examples_per_sec", "value": 1200.0,
        "unit": "examples/sec", "vs_baseline": 1.54,
        "fm_examples_per_sec": 900.0,
        "bytes_per_example": 1500.0,
    }))
    (root / "BENCH_SCALE.json").write_text(json.dumps({
        "models": {"lr": {"examples_per_sec_e2e": 62534.0,
                          "test_auc": 0.674}},
    }))
    (root / "MULTICHIP_r01.json").write_text(json.dumps({
        "n_devices": 8, "ok": True, "skipped": False,
    }))
    (root / "BENCH_SERVE.json").write_text(json.dumps({
        "metric": "serve_qps", "value": 322.98, "unit": "requests/sec",
        "p50_ms": 10.9, "p99_ms": 27.7,
    }))


def test_perf_ledger_consolidates(tmp_path):
    _ledger_corpus(tmp_path)
    out = tmp_path / "ledger.json"
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--json", str(out)])
    assert r.returncode == 0, r.stderr
    md = r.stdout
    for section in ("Bench trajectory", "Multichip dryrun", "Scale run",
                    "Serving", "Roofline extrapolation"):
        assert section in md, f"missing section {section!r}:\n{md}"
    got = json.loads(out.read_text())
    series = {e["series"] for e in got["entries"]}
    assert series == {"bench", "multichip", "scale", "serve"}
    # both rounds of both bench metrics normalized
    lr = [e for e in got["entries"] if e["metric"] == "lr_examples_per_sec"]
    assert [e["round"] for e in lr] == [1, 2]
    roof = got["roofline"]
    assert roof["metric"] == "lr_examples_per_sec" and roof["round"] == 2
    assert roof["pct_of_pod_target"] == round(100.0 * 1200 * 64 / 50_000_000, 1)
    # the HBM conversion runs off the bytes_per_example stamp
    assert roof["target_pct_of_hbm_bw"] > 0


def test_perf_ledger_regress_gate(tmp_path):
    _ledger_corpus(tmp_path)
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--regress", "--markdown", ""])
    assert r.returncode == 0, r.stderr
    # a collapsed newest round trips the gate with exit 3
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "metric": "lr_examples_per_sec", "value": 100.0,
        "unit": "examples/sec",
    }))
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--regress", "--markdown", ""])
    assert r.returncode == 3
    assert "REGRESSION" in r.stderr and "lr_examples_per_sec" in r.stderr
    # --metrics scopes the gate away from the regressed group
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--regress", "--markdown", "", "--metrics", "^fm_"])
    assert r.returncode == 0, r.stderr


def test_perf_ledger_multichip_flip_gates(tmp_path):
    _ledger_corpus(tmp_path)
    (tmp_path / "MULTICHIP_r02.json").write_text(json.dumps({
        "n_devices": 8, "ok": False, "skipped": False,
    }))
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--regress", "--markdown", ""])
    assert r.returncode == 3
    assert "multichip" in r.stderr
    # a SKIPPED round (no devices on this rig) never gates
    (tmp_path / "MULTICHIP_r02.json").write_text(json.dumps({
        "n_devices": 0, "ok": False, "skipped": True,
    }))
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--regress", "--markdown", ""])
    assert r.returncode == 0, r.stderr


def test_perf_ledger_folds_decompose_jsonl(tmp_path):
    # step_decompose --json writes JSONL (one record per slice): an
    # explicit file folds every line in as its own ledger entry
    _ledger_corpus(tmp_path)
    jsonl = tmp_path / "decomp.jsonl"
    jsonl.write_text(
        json.dumps({"metric": "decompose_lr_fwd_ms", "value": 0.3,
                    "unit": "ms/step", "model": "lr", "slice": "fwd"}) + "\n"
        + json.dumps({"metric": "decompose_lr_step_ms", "value": 1.1,
                      "unit": "ms/step", "model": "lr", "slice": "step",
                      "bytes_per_example": 1366.0}) + "\n")
    out = tmp_path / "ledger.json"
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--json", str(out), str(jsonl)])
    assert r.returncode == 0, r.stderr
    metrics = {e["metric"] for e in json.loads(out.read_text())["entries"]}
    assert {"decompose_lr_fwd_ms", "decompose_lr_step_ms"} <= metrics


def test_perf_ledger_ms_metrics_gate_downward(tmp_path):
    # latency-shaped *_ms metrics improve downward: a rising newest
    # round regresses, a falling one never trips the gate, and "best"
    # renders the LOWEST value
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "metric": "decompose_lr_step_ms", "value": 1.0, "unit": "ms/step"}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "metric": "decompose_lr_step_ms", "value": 5.0, "unit": "ms/step"}))
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--regress", "--markdown", ""])
    assert r.returncode == 3
    assert "decompose_lr_step_ms" in r.stderr
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "metric": "decompose_lr_step_ms", "value": 0.4, "unit": "ms/step"}))
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path),
                  "--regress", "--markdown", ""])
    assert r.returncode == 0, r.stderr
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path)])
    assert "0.4 (r2)" in r.stdout


def test_perf_ledger_empty_root_exits_2(tmp_path):
    r = run_tool([tool("perf_ledger.py"), "--root", str(tmp_path)])
    assert r.returncode == 2


# --------------------------------------- metrics_report compile gates


def _stamped(i, **kw):
    return {"ts": float(i), "rank": 0, "run_id": "r", "gen": 0, **kw}


def _compile_rec(i, program="train_step", sig="s1", **kw):
    return _stamped(i, kind="compile", program=program, sig=sig,
                    compile_time_s=0.5, flops=10.0, bytes_accessed=20.0, **kw)


def _write_jsonl(path, records):
    path.write_text("".join(json.dumps(r) + "\n" for r in records))


def test_metrics_report_compile_gate_ok(tmp_path):
    _write_jsonl(tmp_path / "m.jsonl", [
        _compile_rec(1),
        _compile_rec(2, program="predict", sig="s2"),
    ])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl"),
                  "--check"])
    assert r.returncode == 0, r.stderr


def test_metrics_report_compile_gate_recompile(tmp_path):
    _write_jsonl(tmp_path / "m.jsonl", [
        _compile_rec(1),
        _compile_rec(2),  # same (program, sig): a recompile
    ])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl"),
                  "--check"])
    assert r.returncode == 2
    assert "compiled twice" in r.stderr


def test_metrics_report_compile_gate_schema(tmp_path):
    bad = _compile_rec(1)
    del bad["flops"]
    _write_jsonl(tmp_path / "m.jsonl", [bad])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl"),
                  "--check"])
    assert r.returncode == 2
    assert "compile keys" in r.stderr
    zero = _compile_rec(1)
    zero["compile_time_s"] = 0.0
    _write_jsonl(tmp_path / "m.jsonl", [zero])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl"),
                  "--check"])
    assert r.returncode == 2
    assert "non-positive compile_time_s" in r.stderr


def test_metrics_report_renders_compile_table(tmp_path):
    _write_jsonl(tmp_path / "m.jsonl", [_compile_rec(1)])
    r = run_tool([tool("metrics_report.py"), str(tmp_path / "m.jsonl")])
    assert r.returncode == 0, r.stderr
    assert "compiles (kind=compile):" in r.stdout
    assert "train_step" in r.stdout


# -------------------------------------------------------------- smoke gate


def test_smoke_perf_script(tmp_path):
    """The perf CI gate end to end (tools/smoke_perf.sh): instrumented
    run -> compile-record gates -> trace attribution -> BENCH_r09
    through the ledger -> regression-mode mechanics."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "smoke_perf.sh"),
         str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "smoke_perf: OK" in r.stdout
    # the datapoint stayed in the workdir (never the repo root from
    # a test run) and went through the ledger path
    assert (tmp_path / "BENCH_r09.json").exists()
    assert (tmp_path / "ledger.md").exists()
