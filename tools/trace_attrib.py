#!/usr/bin/env python3
"""A device trace's time by the step phase the program says it is in
(docs/OBSERVABILITY.md "Trace attribution").

The step builders wrap their phases in `jax.named_scope`s of one
vocabulary (`telemetry.PHASE_LABELS`: exchange / gather / rows / scatter
/ update / health, with `ffm_place`, `ffm_pair`, `scatter_optimizer`
inside them), and the CompileRecorder writes, into each program's
`kind="compile"` record, `op_scopes`: {operation of the compiled module
-> label, "" where the program names none}. A device trace names an
event by its HLO instruction and nothing else (no scope, no path, no
`hlo_op` statistic on this chip), so the record is the only place the
two meet, and this tool is that ONE join:

    python tools/trace_attrib.py /runs/exp1/prof --run-dir /runs/exp1

    an event on a device plane's `XLA Ops` line
      -> the instruction's name (the event's text up to " = ")
      -> the module whose interval on the SAME plane's `XLA Modules`
         line holds the event's start: instruction names are unique
         within a module only, and a step of two programs (the fullshard
         engine's gradient and update) has two `copy.1`
      -> that module's `op_scopes` (the compile record under --run-dir
         with the same `hlo_module`, the newest winning)
      -> label -> phase.

What has no phase is `unscoped`: an operation the map gives "", one the
record does not hold, one of a module with no record. An instant counts
once, to the operation that started first, so the rows sum to the time
an operation ran on the device. Times are a step's and a chip's: the
mean over the device planes, over the executions of the most-run
recorded module (or --steps).

The trace is the profiler's `.xplane.pb` (what `train.profile_dir` /
`train.trace_start_step` and the benchmark's `--trace 1` both leave on
disk), read with `jax.profiler.ProfileData`; a `.json` / `.json.gz` file
holds the same planes as plain data, {"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]} (the
tests' form, and `benchmark/lib/trace.py`'s).

`attribute` is a twin of `benchmark/lib/phases.py`'s: the benchmark
imports nothing of the program, so the yardstick keeps its own copy of
these thirty lines and this one serves any `xflow train` capture.

Exit codes: 0 = table printed; 1 = no device operation in the trace (a
CPU capture has no device plane); 2 = no trace found / unreadable input.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from xflow_tpu.jsonl import read_jsonl  # noqa: E402
from xflow_tpu.telemetry import PHASE_LABELS  # noqa: E402

PHASE_OF = dict(PHASE_LABELS)
# how the profiler names things on this chip (benchmark/trace_names.json
# holds the same, read from a trace of a v5e by hand)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
SKIP_OPS = re.compile(r"^(while|conditional|call)([.\d]*)$")  # they cover their children


def load_planes(path: str) -> list:
    """The trace's planes as plain data: `path` a plain-data JSON file,
    or a directory whose newest `.xplane.pb` is read."""
    if os.path.isfile(path) and not path.endswith(".pb"):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return json.load(f)["planes"]
    hits = [path] if os.path.isfile(path) else glob.glob(
        os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path!r}")
    from jax.profiler import ProfileData

    data = ProfileData.from_file(max(hits, key=os.path.getmtime))
    return [{"name": plane.name, "lines": [
        {"name": line.name,
         "events": [[e.name.split(" = ", 1)[0].lstrip("%"), float(e.start_ns), float(e.duration_ns)]
                    for e in line.events]}
        for line in plane.lines]} for plane in data.planes]


def load_op_scopes(run_dir: str) -> dict:
    """{hlo_module: op_scopes} over every kind="compile" record in the
    run dir's JSONL files, the newest record of a module winning."""
    maps: dict = {}
    for path in sorted(glob.glob(os.path.join(run_dir, "*.jsonl"))):
        for rec in read_jsonl(path, warn=False):
            if rec.get("kind") == "compile" and rec.get("hlo_module") and isinstance(
                    rec.get("op_scopes"), dict):
                maps[rec["hlo_module"]] = rec["op_scopes"]
    return maps


def attribute(planes: list, maps: dict) -> dict:
    """{"labels": {label or "unscoped": ns}, "events": {label: count},
    "busy": ns, "steps": executions of the most-run recorded module,
    "devices": n}, each time the mean over the device planes."""
    labels: dict = {}
    events: dict = {}
    runs: dict = {}
    devices = 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = sorted((e for e in lines.get(OP_LINE, ()) if e[2] > 0 and not SKIP_OPS.search(e[0])),
                     key=lambda e: e[1])
        if not ops:
            continue
        devices += 1
        mods = sorted(([re.sub(r"\(\d+\)$", "", m[0]), m[1], m[2]]
                       for m in lines.get(MODULE_LINE, ()) if m[2] > 0), key=lambda m: m[1])
        if devices == 1:
            for m in mods:
                if m[0] in maps:
                    runs[m[0]] = runs.get(m[0], 0) + 1
        starts = [m[1] for m in mods]
        covered = ops[0][1]
        for name, start, dur in ops:
            end = start + dur
            counted = max(0.0, end - max(start, covered))
            covered = max(covered, end)
            i = bisect.bisect_right(starts, start) - 1
            module = mods[i][0] if i >= 0 and start < mods[i][1] + mods[i][2] else None
            label = maps.get(module, {}).get(name.removesuffix("[pallas]"))
            key = label if label in PHASE_OF else "unscoped"
            labels[key] = labels.get(key, 0.0) + counted
            events[key] = events.get(key, 0) + 1
    labels = {k: v / devices for k, v in labels.items()} if devices else {}
    return {"labels": labels, "events": events, "busy": sum(labels.values()),
            "steps": max(runs.values(), default=0), "devices": devices}


def rows_of(got: dict, steps: int) -> list:
    """[(row name, ms a step, share %, events)]: a row a phase, under it
    a row for each label inside it, `unscoped` last."""
    by_phase: dict = {}
    for label in got["labels"]:
        if label != "unscoped":
            by_phase.setdefault(PHASE_OF[label], []).append(label)
    out = []
    cell = lambda name, labels: (
        name, sum(got["labels"][x] for x in labels) / steps / 1e6,
        100.0 * sum(got["labels"][x] for x in labels) / got["busy"],
        sum(got["events"][x] for x in labels))
    for phase in dict.fromkeys(PHASE_OF.values()):
        if phase not in by_phase:
            continue
        out.append(cell(phase, by_phase[phase]))
        out.extend(cell("  " + x, [x]) for x in by_phase[phase] if x != phase)
    if "unscoped" in got["labels"]:
        out.append(cell("unscoped", ["unscoped"]))
    return out


def render(rows: list, got: dict, steps: int) -> str:
    lines = ["phase                 ms/step       %   events",
             "-----                 -------       -   ------"]
    lines += [f"{name:<20}  {ms:>8.3f}  {pct:>6.2f}   {n:>6}" for name, ms, pct, n in rows]
    lines.append(f"{'busy':<20}  {got['busy'] / steps / 1e6:>8.3f}  100.00   {sum(got['events'].values()):>6}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="a device trace's time by the step phase the program says it is in")
    ap.add_argument("trace", help="profile dir (train.profile_dir), an .xplane.pb, or the planes "
                                  "as plain-data .json(.gz)")
    ap.add_argument("--run-dir", default="",
                    help="run dir holding metrics JSONL with kind=\"compile\" records: their "
                         "op_scopes, keyed by hlo_module, are the join")
    ap.add_argument("--steps", type=int, default=0,
                    help="steps the trace holds (default: the executions of the most-run "
                         "recorded module on the first device)")
    ap.add_argument("--json", default="", metavar="OUT",
                    help="also write {steps, devices, busy_ms, phases: {row: {ms, pct, events}}} "
                         "('-' = stdout)")
    args = ap.parse_args(argv)
    try:
        planes = load_planes(args.trace)
    except (OSError, ValueError, KeyError) as e:
        print(f"trace_attrib: {e}", file=sys.stderr)
        return 2
    maps = load_op_scopes(args.run_dir) if args.run_dir else {}
    if not maps:
        print("trace_attrib: warning: no kind=\"compile\" record with op_scopes"
              + (f" under {args.run_dir!r}" if args.run_dir else " (no --run-dir)")
              + ": every operation is unscoped", file=sys.stderr)
    got = attribute(planes, maps)
    if got["busy"] <= 0:
        print(f"trace_attrib: no device operation in {args.trace!r} (a CPU capture has no "
              "device plane; a window before the first step has no operation)", file=sys.stderr)
        return 1
    steps = args.steps or got["steps"] or 1
    rows = rows_of(got, steps)
    print(f"# trace: {args.trace}  devices: {got['devices']}  steps: {steps}")
    print(f"# phase maps: {sorted(maps)} from {args.run_dir!r}")
    print(render(rows, got, steps))
    if args.json:
        out = json.dumps({
            "steps": steps, "devices": got["devices"], "busy_ms": got["busy"] / steps / 1e6,
            "phases": {name.strip(): {"ms": ms, "pct": pct, "events": n} for name, ms, pct, n in rows},
        })
        if args.json == "-":
            print(out)
        else:
            with open(args.json, "w") as f:
                f.write(out + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
