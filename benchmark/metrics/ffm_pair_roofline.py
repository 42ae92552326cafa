"""Field-aware FM's row side against its roofline: the device time a
step of the operations in the program's `ffm_pair` scope (the field
crossing as a block transposition, the multiply-and-sum, the
hand-written backward; no dot since PR 37) and `ffm_place` scope (the
placement of the gathered occurrences by row and field, and its reverse
for the cotangent), against the least time the algorithm needs — the
larger of its operations at the chip's peak FLOP/s (one multiply-add for
each ordered pair of fields and factor forward, twice that backward:
6 * nf^2 * k a row) and its bytes at the HBM peak (one read of the
gathered occurrences and one write of their cotangent, at the row's
width). A lower bound, so the share cannot pass 100%.

The device trace names an XLA fusion after what it fuses (`fusion.3`,
`multiply_reduce_fusion`), not after the scope it came from. Which
operations are a scope's is said by the program: the step's
`kind="compile"` record holds `op_scopes`, {operation -> scope}, read
from the compiled module. This reader takes that record from the traced
run's metrics file, where the harness left it
(`bench_run/<cell>/metrics.jsonl`), and the times from the reduced
trace. Nothing to read where the program names no such scope, or the
run has no trace."""

import glob
import json
import os
import re

META = {"layer": "step program", "unit": "%", "source": "device_trace", "better": "higher"}
SCOPES = ("ffm_pair", "ffm_place")
PROGRAM = "train_step"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pair_needs(rows: float, occurrences: float, nf: int, k: int, width: int) -> dict:
    return {"flops": rows * 6.0 * nf * nf * k, "bytes": occurrences * width * 4 * 2}


def scope_ops(metrics_path: str) -> set:
    """The step program's operations in the two scopes, by the newest
    compile record that names any."""
    found: set = set()
    with open(metrics_path) as f:
        for line in f:
            if '"op_scopes"' not in line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "compile" and rec.get("program") == PROGRAM:
                ops = {op for op, scope in rec["op_scopes"].items() if scope in SCOPES}
                found = ops or found
    return found


def read(run: dict):
    from lib import counts, trace

    tr = run.get("trace")
    if not tr or not tr.get("devices") or not run.get("peak") or not run["trace_steps"]:
        return None
    hits = glob.glob(os.path.join(ROOT, "bench_run", "*", "metrics.jsonl"))
    ops = scope_ops(max(hits, key=os.path.getmtime)) if hits else set()
    if not ops:
        return None
    pattern = "^(" + "|".join(re.escape(op) for op in sorted(ops)) + ")$"
    seconds = trace.op_seconds(tr["ops"], pattern) / run["trace_steps"]
    if seconds <= 0:
        return None
    shape = run["shape"]()
    rows = float(run["window"]["examples"]) / run["window"]["steps"]
    nf = round(shape["occurrences"] / rows)
    k = (run["width"] - 1) // nf
    needs = pair_needs(rows / run["chips"], shape["occurrences"] / run["chips"], nf, k, run["width"])
    return 100.0 * counts.least_seconds(needs, run["peak"])[0] / seconds
