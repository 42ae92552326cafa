"""The step's device time by the phase the program says it is in.

The program wraps its step's phases in `jax.named_scope`s of one
vocabulary (`PHASE_OF` below is the benchmark's own copy of it) and
writes, into each `kind="compile"` record of a run with
`train.metrics_path`, `op_scopes`: {operation of the compiled module ->
label, or "" where the program names none}, under the module's name
(`hlo_module`). A device trace names an event by its HLO instruction and
nothing else, so the two meet here:

    an event on a device's operations line
      -> the instruction's name (`lib/trace.py short_name`'s rule)
      -> the module whose interval on the SAME plane's `XLA Modules`
         line holds the event's start (instruction names are unique
         within a module only: a step of two programs has two `copy.1`)
      -> that module's `op_scopes` (the newest record of that name)
      -> label -> phase.

What has no phase — an operation the map gives "", one of a module with
no record (the harness's own small programs between passes), one the
record does not hold, and the `health` phase — is the unscoped
remainder. An instant counts once, to the operation that started first,
so the phases and the remainder sum to the reduced trace's `busy_s`.

`run.py` hands a reader step records and the reduced trace only, so this
module fetches the rest from where the traced run left it: the compile
records from the newest `bench_run/*/metrics.jsonl`, the trace from the
`profile/` beside it (as `metrics/ffm_pair_roofline.py` fetches its
records). The trace is loaded once a run and kept in the run's dict.

A twin of `tools/trace_attrib.py`'s `attribute`, the operator's tool for
any `xflow train` capture: the same join, kept twice because the
benchmark imports nothing of the program — this copy is the yardstick's
own and changes only with the benchmark.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# label -> the phase it counts as (the program's telemetry.PHASE_LABELS)
PHASE_OF = {
    "exchange": "exchange", "gather": "gather", "rows": "rows", "ffm_place": "rows",
    "ffm_pair": "rows", "scatter": "scatter", "update": "update",
    "scatter_optimizer": "update", "health": "health",
}
PHASES = ("exchange", "gather", "rows", "scatter", "update")  # `health` counts as unscoped
MODULE_LINE = "XLA Modules"  # a device plane's line of whole program executions


def traced_run_dir() -> str | None:
    """The directory the traced run left its records and profile in."""
    hits = glob.glob(os.path.join(ROOT, "bench_run", "*", "metrics.jsonl"))
    return os.path.dirname(max(hits, key=os.path.getmtime)) if hits else None


def compile_maps(metrics_path: str) -> dict:
    """{hlo_module: op_scopes} of the file's compile records, the
    newest record of a module winning."""
    maps: dict = {}
    with open(metrics_path) as f:
        for line in f:
            if '"op_scopes"' not in line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "compile" and rec.get("hlo_module"):
                maps[rec["hlo_module"]] = rec["op_scopes"]
    return maps


def attribute(trace: dict, names: dict, maps: dict, chips: int | None = None) -> dict:
    """{"phases": {phase: ns}, "labels": {label: ns}, "unscoped": ns,
    "busy": ns, "events": {phase or "unscoped": count}}, each time the
    mean over the device planes."""
    skip = re.compile(names["skip_ops"]) if names.get("skip_ops") else None
    planes = [p for p in trace["planes"] if re.match(names["device_plane"], p["name"])]
    planes = planes[:chips] if chips else planes
    labels: dict = {}
    events: dict = {}
    devices = 0
    for plane in planes:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = sorted((e for e in lines.get(names["op_line"], ())
                      if e[2] > 0 and not (skip and skip.search(e[0]))), key=lambda e: e[1])
        if not ops:
            continue
        devices += 1
        mods = sorted((e for e in lines.get(MODULE_LINE, ()) if e[2] > 0), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        covered = ops[0][1]
        for name, start, dur in ops:
            end = start + dur
            counted = max(0.0, end - max(start, covered))
            covered = max(covered, end)
            i = bisect.bisect_right(starts, start) - 1
            inside = i >= 0 and start < mods[i][1] + mods[i][2]
            module = re.sub(r"\(\d+\)$", "", mods[i][0]) if inside else None
            label = maps.get(module, {}).get(name.removesuffix("[pallas]"))
            key = label if label in PHASE_OF and PHASE_OF[label] != "health" else "unscoped"
            labels[key] = labels.get(key, 0.0) + counted
            phase = PHASE_OF.get(key, key)
            events[phase] = events.get(phase, 0) + 1
    if not devices:
        return {}
    labels = {k: v / devices for k, v in labels.items()}
    phases = {p: 0.0 for p in PHASES}
    for label, ns in labels.items():
        if label != "unscoped":
            phases[PHASE_OF[label]] += ns
    return {"phases": phases, "labels": labels, "unscoped": labels.get("unscoped", 0.0),
            "busy": sum(labels.values()), "events": events}


def of_run(run: dict) -> dict | None:
    """`attribute` of the traced run, once a run (kept in `run`). None
    where there is nothing to read: no trace, no compile record that
    names a module on it, or records of another vocabulary (a program
    from before the one vocabulary writes `loss`, `grad`, `optimizer`)."""
    if "_phases" not in run:
        run["_phases"] = _of_run(run)
    return run["_phases"]


def _of_run(run: dict) -> dict | None:
    from lib import trace

    tr = run.get("trace")
    if not tr or not tr.get("devices") or not run.get("trace_steps"):
        return None
    where = traced_run_dir()
    if where is None:
        return None
    maps = compile_maps(os.path.join(where, "metrics.jsonl"))
    if not maps or any(label and label not in PHASE_OF for m in maps.values() for label in m.values()):
        return None
    try:
        loaded = trace.load(os.path.join(where, "profile"))
    except FileNotFoundError:
        return None
    got = attribute(loaded, trace.load_names(HERE), maps, run.get("chips"))
    return got if got and got["busy"] > got["unscoped"] else None


def phase_ms(run: dict, phase: str) -> float | None:
    """ms a step and chip of one phase (0.0 where the step has none)."""
    got = of_run(run)
    return None if got is None else got["phases"][phase] / run["trace_steps"] / 1e6


def unscoped_pct(run: dict) -> float | None:
    got = of_run(run)
    return None if got is None else 100.0 * got["unscoped"] / got["busy"]
