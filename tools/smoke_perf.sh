#!/usr/bin/env bash
# Perf-observability smoke gate (docs/OBSERVABILITY.md "Compile
# accounting", docs/PERF.md "Bench trajectory"): one instrumented
# 50-step synthetic CPU train proving the whole measurement layer end
# to end —
#   1. kind="compile" records for every compiled program, with nonzero
#      compile_time/flops/bytes and the op->scope map, gated by
#      metrics_report --check (schema + the exactly-once recompile rule);
#   2. the host timeline in the window records (`host`: one field a
#      stage; `boundary` on the first) and the compile record's
#      lower/compile split;
#   3. tools/trace_attrib.py joining the run's compile records with a
#      device plane (the CPU's TraceWindow capture has none: the tool
#      says so; the plane is written from the records' own operations);
#   4. the round's BENCH_r09.json datapoint rendered through
#      tools/perf_ledger.py (markdown + JSON);
#   5. the ledger's regression mode exiting 3 on a controlled
#      regressed corpus (and 0 on a healthy one).
#
# Standalone:    bash tools/smoke_perf.sh [workdir]
# From pytest:   tests/test_perf_tools.py::test_smoke_perf_script
#
# With no workdir argument a temp dir is created and cleaned up.
set -euo pipefail
cd "$(dirname "$0")/.."
ROOT="$(pwd)"

WORK="${1:-}"
# bench datapoint destination: the repo root ONLY standalone (the
# per-PR record); pytest runs keep it in the workdir so test runs
# never rewrite the committed BENCH_r09.json with machine-local numbers
BENCH_OUT="$ROOT/BENCH_r09.json"
if [ -z "$WORK" ]; then
    WORK="$(mktemp -d)"
    trap 'rm -rf "$WORK"' EXIT
else
    BENCH_OUT="$WORK/BENCH_r09.json"
fi

export JAX_PLATFORMS=cpu

# ---- 1. instrumented run: compile accounting + host timeline + trace window
# 3200 rows / batch 64 = 50 steps; the trace window [10, 20) sits in
# the steady state, after the train program compiled
python -m xflow_tpu gen-data "$WORK/train" --shards 1 --rows 3200 \
    --fields 6 --ids-per-field 50 --seed 0 >/dev/null

python -m xflow_tpu train \
    --train "$WORK/train" --model lr --epochs 1 \
    --batch-size 64 --log2-slots 12 --no-mesh \
    --set model.num_fields=6 \
    --set data.max_nnz=8 \
    --set train.pred_dump=false \
    --set train.log_every=10 \
    --set "train.metrics_path=$WORK/run/metrics_rank0.jsonl" \
    --set "train.profile_dir=$WORK/prof" \
    --set train.trace_start_step=10 \
    --set train.trace_num_steps=10 \
    >/dev/null

# ---- 2. compile-record schema + exactly-once recompile gate ---------------
python tools/metrics_report.py "$WORK/run" --check
python - "$WORK/run/metrics_rank0.jsonl" <<'EOF'
import json, sys
recs = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
comp = [r for r in recs if r.get("kind") == "compile"]
assert comp, "no kind=compile records in the run"
for c in comp:
    assert c["compile_time_s"] > 0, f"zero compile time: {c['program']}"
    assert c["flops"] and c["flops"] > 0, f"no flops: {c['program']}"
    assert c["bytes_accessed"] and c["bytes_accessed"] > 0, \
        f"no bytes: {c['program']}"
    assert c.get("op_scopes"), f"no op_scopes map: {c['program']}"
    assert abs(c["lower_s"] + c["xla_compile_s"] - c["compile_time_s"]) < 1e-5, \
        f"lower_s + xla_compile_s != compile_time_s: {c['program']}"
wins = [r for r in recs if "step_time_p50_ms" in r and not r.get("kind")]
assert wins, "no window records in the run"
for w in wins:
    host = w.get("host")
    assert host, f"window record at step {w.get('step')} has no host fields"
    for key in ("parse_ms", "plan_ms", "producer_wait_ms", "transfer_ms",
                "dispatch_call_ms", "prev_ready_ms", "batches"):
        assert key in host, f"host lacks {key}"
assert "fit_open_ms" in wins[0].get("boundary", {}), "first window has no boundary"
print(f"smoke_perf: {len(comp)} compile record(s), "
      f"host timeline in {len(wins)} window(s)")
EOF

# ---- 3. trace attribution: the run's compile records joined by module ------
# A CPU capture has host planes only (the operations run on host
# threads): the tool reads the run's .xplane.pb and says so, exit 1.
rc=0
python tools/trace_attrib.py "$WORK/prof" --run-dir "$WORK/run" 2>"$WORK/attrib.err" || rc=$?
[ "$rc" -eq 1 ] || {
    echo "smoke_perf: trace_attrib on a CPU capture expected exit 1, got $rc"
    cat "$WORK/attrib.err"; exit 1; }
grep -q "no device operation" "$WORK/attrib.err"
# The join itself, on a device plane written from the step record's own
# operations (one event each, under a module event of the record's name)
python - "$WORK/run/metrics_rank0.jsonl" "$WORK/planes.json" <<'EOF'
import json, sys
recs = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
step = [r for r in recs if r.get("kind") == "compile" and r["program"] == "train_step"][-1]
ops = [[op, 10.0 * i, 10.0] for i, op in enumerate(step["op_scopes"])]
planes = [{"name": "/device:TPU:0", "lines": [
    {"name": "XLA Modules", "events": [[step["hlo_module"] + "(1)", 0.0, 10.0 * len(ops)]]},
    {"name": "XLA Ops", "events": ops}]}]
json.dump({"planes": planes}, open(sys.argv[2], "w"))
EOF
python tools/trace_attrib.py "$WORK/planes.json" --run-dir "$WORK/run" \
    --json "$WORK/attrib.json"
python - "$WORK/attrib.json" <<'EOF'
import json, sys
d = json.load(open(sys.argv[1]))
assert d["busy_ms"] > 0, "trace attributed zero device time"
named = [s for s in d["phases"] if s != "unscoped"]
assert {"gather", "rows", "scatter", "update"} <= set(named), f"phases missing: {d}"
assert d["phases"].get("unscoped", {"pct": 0.0})["pct"] < 10.0, f"too much unscoped: {d}"
print(f"smoke_perf: trace attributed ({d['busy_ms']} ms device time, phases: {named})")
EOF

# ---- 4. the round's bench datapoint through the ledger path ---------------
# emitted from a CLEAN (untraced) run: the instrumented run above
# carries profiler overhead, and the trajectory datapoint must be the
# steady state, not the measurement's own cost
python -m xflow_tpu train \
    --train "$WORK/train" --model lr --epochs 1 \
    --batch-size 64 --log2-slots 12 --no-mesh \
    --set model.num_fields=6 \
    --set data.max_nnz=8 \
    --set train.pred_dump=false \
    --set train.log_every=10 \
    --set "train.metrics_path=$WORK/run_clean/metrics_rank0.jsonl" \
    >/dev/null
python tools/metrics_report.py "$WORK/run_clean" --check
python tools/metrics_report.py "$WORK/run_clean" --bench-json "$BENCH_OUT"
python tools/perf_ledger.py "$BENCH_OUT" \
    --markdown "$WORK/ledger.md" --json "$WORK/ledger.json"
grep -q "Bench trajectory" "$WORK/ledger.md"
grep -q "telemetry_examples_per_sec" "$WORK/ledger.md"

# ---- 5. regression-gate mechanics on a controlled corpus ------------------
# (the real trajectory mixes machines — tolerance judgments there are
# the operator's; the MECHANICS are what CI pins: healthy -> 0,
# regressed -> 3)
mkdir -p "$WORK/series"
echo '{"metric": "smoke_examples_per_sec", "value": 1000.0, "unit": "examples/sec"}' \
    > "$WORK/series/BENCH_r01.json"
echo '{"metric": "smoke_examples_per_sec", "value": 990.0, "unit": "examples/sec"}' \
    > "$WORK/series/BENCH_r02.json"
python tools/perf_ledger.py --root "$WORK/series" --regress --markdown '' >/dev/null
echo '{"metric": "smoke_examples_per_sec", "value": 100.0, "unit": "examples/sec"}' \
    > "$WORK/series/BENCH_r03.json"
rc=0
python tools/perf_ledger.py --root "$WORK/series" --regress --markdown '' \
    >/dev/null 2>&1 || rc=$?
[ "$rc" -eq 3 ] || {
    echo "smoke_perf: ledger regression mode expected exit 3, got $rc"; exit 1; }

# repo-root hygiene: running the tools from the root must leave no
# stray artifact dirs behind (tools/__pycache__ and friends)
rm -rf "$ROOT/tools/__pycache__" "$ROOT/__pycache__"

echo "smoke_perf: OK"
