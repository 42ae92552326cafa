"""Telemetry: the counters/gauges/timers registry, step-time
decomposition, and programmatic profiler trace windows.

The reference prints only loss/AUC lines to stdout
(`/root/reference/src/model/lr/lr.cc` train loop), which is unusable for
diagnosing a TPU trainer: async dispatch deliberately hides where the
time goes (data-wait? host dispatch? device step?), and the stdout
stream carries no rank identity, no timestamps, and nothing a tool can
aggregate. This module is the first-class instrumentation layer:

- `Registry` / `Counter` / `Gauge` / `Timer`: process-wide named
  metrics. The data pipeline and the quarantine path report through the
  default registry (data/pipeline.py, data/libffm.py); the trainer
  snapshots it into every metrics-JSONL window record.
- `StepTimer`: decomposes each train step into data-wait (iterator
  next), host dispatch (plan resolve + transfer + async dispatch), and
  device time — the device side measured ONE STEP BEHIND via a
  block-until-ready on the *previous* step's metrics right after the
  current step's dispatch, the same hide-under-device-time trick the
  non-finite guard's flag read uses (train/trainer.py check_pending),
  so the instrumentation adds no sync bubble to the pipeline.
- `span`: the ONE seam the host side of `xflow train` is timed
  through. `with span("parse", profiler):` opens a
  `jax.profiler.TraceAnnotation("xflow:parse")` — which puts the span
  on the profiler's own clock, beside the device's operations, whenever
  a trace session is open, and is a flag test when none is — and, when
  a `PipelineProfiler` is given (the run is armed: somebody listens),
  adds the `perf_counter` duration to its window. `HOST_SPANS` is the
  vocabulary; the step records' `host` fields come from the same
  strings.
- `TraceWindow`: a programmatic xprof trace window
  (`train.trace_start_step` / `train.trace_num_steps`) captured mid-run
  after compilation settles, replacing the blunt whole-run
  start/stop-trace (which buried the steady state under compile noise).
- `CompileRecorder`: compile accounting. Every step/predict program in
  the engine builders (train/step.py, parallel/train_step.py,
  parallel/sorted_fullshard.py) and the
  serve runner routes its jit through `wrap()`, which lowers and
  compiles explicitly (`.lower()`, then `.compile()`, timed apart as
  `lower_s` / `xla_compile_s`), and extracts XLA's cost analysis
  (FLOPs, bytes accessed) and memory analysis
  (argument/output/temp/generated bytes) into one stamped
  `kind="compile"` JSONL record per program — plus `op_scopes`, the
  {operation -> label of the ONE vocabulary of step phases,
  `PHASE_LABELS`} map that tools/trace_attrib.py and the benchmark's
  readers join with a device trace, by program.
- `device_memory_stats` / `hbm_window_fields`: runtime HBM gauges
  polled from the device allocator into the log-window records,
  guarded for backends that don't report (CPU).

Timing convention (docs/OBSERVABILITY.md): durations come from
`time.perf_counter()` (monotonic — wall-clock `time.time()` jumps under
NTP slew); the `ts` field every JSONL record carries (xflow_tpu/jsonl.py)
is wall-clock, for cross-stream/cross-host log correlation only.
"""

from __future__ import annotations

import os
import re
import threading
import time
import uuid
from collections import deque
from typing import Iterable, Iterator, Optional

import numpy as np

_RUN_ID: Optional[str] = None


def new_run_id() -> str:
    """A fresh launch-scoped id honoring an operator-exported
    XFLOW_RUN_ID — the one place the env-var name and id format live
    (launchers mint one per launch and export it to every rank)."""
    return os.environ.get("XFLOW_RUN_ID") or uuid.uuid4().hex[:12]


def resolve_run_id() -> str:
    """One id per training run, identical on every rank: XFLOW_RUN_ID
    when a launcher exported it (launch/local.py, launch/dist.py),
    else one random id minted per process — cached so every sink in the
    process (metrics stream, quarantine stream) stamps the SAME id and
    the streams stay joinable."""
    global _RUN_ID
    rid = os.environ.get("XFLOW_RUN_ID")
    if rid:
        return rid
    if _RUN_ID is None:
        _RUN_ID = new_run_id()
    return _RUN_ID


def resolve_restart_gen() -> int:
    """This process's restart generation: 0 on a first launch, k after
    the k-th supervised auto-restart (launch/supervise.py exports
    XFLOW_RESTART_GEN to every rank). Stamped as `gen` into every JSONL
    record (jsonl.JsonlAppender) so one run's multi-generation streams
    segment cleanly — step counts restart from 0 inside each generation,
    and metrics_report.py keys its per-stream gates on (run_id, rank,
    kind, gen)."""
    try:
        return int(os.environ.get("XFLOW_RESTART_GEN", "0") or 0)
    except ValueError:
        return 0


def resolve_rank() -> int:
    """This process's rank for record stamping. The launcher env
    (XFLOW_PROCESS_ID) is authoritative and avoids touching jax from
    sinks that open before distributed init; fall back to
    jax.process_index() (0 single-process) once jax is importable."""
    env = os.environ.get("XFLOW_PROCESS_ID")
    if env is not None:
        try:
            return int(env)
        except ValueError:
            pass
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


def resolve_replica() -> Optional[int]:
    """This process's serving-replica index, or None outside a fleet.
    `xflow serve-fleet` (serve/fleet.py) exports XFLOW_REPLICA to every
    replica it spawns — the same env machinery as XFLOW_RESTART_GEN —
    and the JSONL appender stamps it (with the replica's port) into
    every record, so a multi-replica run's serve streams stay distinct
    even if the rank stamp collides (docs/OBSERVABILITY.md)."""
    v = os.environ.get("XFLOW_REPLICA")
    if v is None:
        return None
    try:
        return int(v)
    except ValueError:
        return None


def resolve_slice() -> Optional[int]:
    """This process's slice index in an emulated multi-slice run, or
    None outside one. `xflow launch-multislice`
    (parallel/multislice.py) exports XFLOW_SLICE to every slice it
    spawns — the same env machinery as XFLOW_REPLICA — and the JSONL
    appender stamps it into every record, so the sync-tier streams of
    an N-slice run stay attributable even though each slice is its own
    single-process world (docs/OBSERVABILITY.md)."""
    v = os.environ.get("XFLOW_SLICE")
    if v is None:
        return None
    try:
        return int(v)
    except ValueError:
        return None


def resolve_num_slices() -> int:
    """The slice count of this launch (XFLOW_NUM_SLICES, exported by
    launch-multislice alongside XFLOW_SLICE); 1 outside a multi-slice
    run — the single-slice degenerate case every sync mode must treat
    as a structural no-op (docs/DISTRIBUTED.md)."""
    v = os.environ.get("XFLOW_NUM_SLICES")
    if not v:
        return 1
    try:
        return max(int(v), 1)
    except ValueError:
        return 1


def resolve_replica_port() -> Optional[int]:
    """The serving port the fleet assigned this replica (stable across
    its supervised restarts — the router's backend address never moves),
    or None outside a fleet."""
    v = os.environ.get("XFLOW_REPLICA_PORT")
    if v is None:
        return None
    try:
        return int(v)
    except ValueError:
        return None


def resolve_world_size() -> int:
    """The launch world size for record stamping (`world` in every
    JSONL record). Under degraded-mode supervision (--allow-shrink,
    docs/ROBUSTNESS.md) a relaunch after a lost host runs with FEWER
    ranks under the same run_id — the per-generation world stamp is how
    report tools tell a shrunk-away rank (`retired@genK`) from a dead
    one. The launcher env (XFLOW_NUM_PROCESSES) is authoritative, same
    pattern as resolve_rank; falls back to jax.process_count()."""
    env = os.environ.get("XFLOW_NUM_PROCESSES")
    if env:
        try:
            n = int(env)
            if n > 0:
                return n
        except ValueError:
            pass
    try:
        import jax

        return int(jax.process_count())
    except Exception:
        return 1


_WARNED: set = set()
_WARNED_LOCK = threading.Lock()


def warn_once(key: str, message: str) -> None:
    """Print `message` to stderr the first time this process sees
    `key`. For stand-ins that would otherwise take over in silence
    (the Python parser and planner where the native library cannot be
    built): one line with the reason, never one per batch."""
    with _WARNED_LOCK:
        if key in _WARNED:
            return
        _WARNED.add(key)
    import sys

    print(f"xflow: warning: {message}", file=sys.stderr)


# ------------------------------------------------------------------ registry


class Counter:
    """Monotonically increasing count. Thread-safe (the prefetch worker
    increments data counters while the fit loop snapshots)."""

    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> None:
        if n < 0:
            raise ValueError(f"Counter.inc({n}): counters are monotone, use a Gauge")
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        with self._lock:
            return self._value


class Gauge:
    """Last-write-wins instantaneous value."""

    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Timer:
    """Duration accumulator with window percentiles.

    `observe(seconds)` (or the `timing()` context manager) feeds both
    the run totals (count / total_s — monotone, snapshot-friendly) and
    the current window, which `percentile(q)` reads and
    `window_reset()` clears — the StepTimer and the trainer's log
    window share this reset cadence. The window is a bounded deque
    (newest WINDOW_CAP observations) so a consumer that never resets —
    a run with train.log_every=0 — cannot grow host memory for the
    life of a pod-scale job."""

    WINDOW_CAP = 8192

    __slots__ = ("_lock", "count", "total_s", "_window")

    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.total_s = 0.0
        self._window: deque = deque(maxlen=self.WINDOW_CAP)

    def observe(self, seconds: float) -> None:
        with self._lock:
            self.count += 1
            self.total_s += float(seconds)
            self._window.append(float(seconds))

    def timing(self):
        timer = self

        class _Ctx:
            def __enter__(self):
                self._t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                timer.observe(time.perf_counter() - self._t0)
                return False

        return _Ctx()

    def percentile(self, q: float) -> float:
        """q-th percentile (0..100) over the CURRENT window; NaN when
        the window is empty."""
        with self._lock:
            if not self._window:
                return float("nan")
            return float(np.percentile(np.asarray(self._window), q))

    def window_reset(self) -> list:
        """Return and clear the current window's observations."""
        with self._lock:
            out = list(self._window)
            self._window.clear()
            return out


class Registry:
    """Create-or-get named metrics. One flat namespace; a name is
    permanently one kind (asking for a counter where a gauge lives is a
    bug, reported loudly)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict = {}

    def _get(self, name: str, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls()
            elif not isinstance(m, cls):
                raise TypeError(
                    f"telemetry metric {name!r} is a {type(m).__name__}, "
                    f"not a {cls.__name__}"
                )
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def timer(self, name: str) -> Timer:
        return self._get(name, Timer)

    def snapshot(self) -> dict:
        """Flat {name: value} of every metric — counters/gauges by
        value, timers as `<name>.count` / `<name>.total_s`. Values are
        run totals (monotone for counters/timers), so consumers join
        across windows by diffing."""
        with self._lock:
            items = list(self._metrics.items())
        out: dict = {}
        for name, m in items:
            if isinstance(m, Timer):
                out[f"{name}.count"] = m.count
                out[f"{name}.total_s"] = round(m.total_s, 6)
            else:  # Counter / Gauge
                out[name] = m.value
        return out

    def discard(self, name: str) -> None:
        """Remove one metric if present (subsystem teardown: a profiled
        fit's gauges must not leak into a later off-run's snapshots)."""
        with self._lock:
            self._metrics.pop(name, None)

    def reset(self) -> None:
        """Drop every metric (tests; a fresh fit() keeps run totals)."""
        with self._lock:
            self._metrics.clear()


_DEFAULT = Registry()


def default_registry() -> Registry:
    """The process-wide registry the pipeline/quarantine counters and
    the trainer's window snapshots share."""
    return _DEFAULT


# ------------------------------------------------------------ HBM gauges


def device_triple() -> dict:
    """The devices this process computes on, as JAX reports them — what
    `xflow train`'s summary and `xflow serve`'s ready line name, so a
    run that landed on the CPU cannot pass for one on the chip."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def device_memory_stats(device=None) -> dict:
    """The default (or given) device's allocator stats, as a plain dict
    of the fields worth gauging: bytes_in_use, peak_bytes_in_use,
    bytes_limit, largest_alloc_size. Returns {} on backends whose
    allocator doesn't report (CPU's memory_stats() is None) and on any
    failure — HBM gauging must never be able to kill a training step."""
    try:
        if device is None:
            import jax

            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return {}
    if not stats:
        return {}
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_alloc_size"):
        v = stats.get(key)
        if isinstance(v, (int, float)):
            out[key] = int(v)
    return out


def hbm_window_fields(registry: Optional[Registry] = None, device=None) -> dict:
    """The HBM fields one metrics-JSONL window record carries
    (docs/OBSERVABILITY.md "HBM gauges"): live + peak device bytes,
    mirrored into hbm.* gauges. {} on backends that don't report, so a
    CPU smoke run's records simply omit them."""
    stats = device_memory_stats(device)
    if not stats:
        return {}
    reg = registry or default_registry()
    out: dict = {}
    if "bytes_in_use" in stats:
        out["hbm_bytes_in_use"] = stats["bytes_in_use"]
        reg.gauge("hbm.bytes_in_use").set(stats["bytes_in_use"])
    if "peak_bytes_in_use" in stats:
        out["hbm_peak_bytes"] = stats["peak_bytes_in_use"]
        reg.gauge("hbm.peak_bytes").set(stats["peak_bytes_in_use"])
    if "bytes_limit" in stats:
        out["hbm_bytes_limit"] = stats["bytes_limit"]
    return out


# ------------------------------------------------------- CompileRecorder

# The ONE vocabulary of step phases (docs/OBSERVABILITY.md "Step
# phases"): every `jax.named_scope` a step builder opens is a label of
# this tuple, (label, the phase it counts as), and means the same in
# every engine. Six phases; three labels sit INSIDE a phase and count as
# it, because a reader keys on them (`ffm_place` / `ffm_pair`:
# benchmark/metrics/ffm_pair_roofline.py; `scatter_optimizer`: the
# fused kernel's name in the trace).
#   exchange  what a chip does to receive its work: the occurrences'
#             all_to_all, the merge's sort, the row aggregates' return —
#             and every collective operation, XLA's own included
#   gather    table rows -> occurrence space, forward only
#   rows      everything between the gathered occurrences and their
#             cotangent: row sums, row math, the loss, their backward
#   scatter   the gather's transpose where it is an operation of its
#             own (never scoped: JAX writes `transpose(...)` into the
#             path of a `gather`, and `phase_of_path` reads that)
#   update    the gradient -> the new state: the guard's read and
#             select, the optimizer's sweep or the fused scatter+FTRL
#   health    `health_norms` (train.health_metrics; default off)
# A device trace names an operation by its HLO instruction, never by
# its scope: a compile record's `op_scopes` is the only place the two
# meet, and tools/trace_attrib.py and benchmark/lib/phases.py join on it.
PHASE_LABELS = (
    ("exchange", "exchange"),
    ("gather", "gather"),
    ("rows", "rows"),
    ("ffm_place", "rows"),
    ("ffm_pair", "rows"),
    ("scatter", "scatter"),
    ("update", "update"),
    ("scatter_optimizer", "update"),
    ("health", "health"),
)
_PHASE_OF = dict(PHASE_LABELS)
# a path component is a label as the program wrote it or as autodiff
# wrapped it: `gather`, `jvp(gather)`, `transpose(jvp(gather))`
_COMPONENT = re.compile(r"(?:[\w.]+\()*(\w+)\)*")
# operations that are no work of their own on a device's operations line
_NO_OP = frozenset(
    ("parameter", "tuple", "get-tuple-element", "bitcast", "constant",
     "after-all", "partition-id", "replica-id")
)
_COLLECTIVE = re.compile(
    r"(all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute)"
    r"(-start|-done)?$"
)
# what a trace shows by a name a reader would look for: kept first when
# a record's map has to be cut to OP_SCOPES_CAP
_NAMED_ON_A_TRACE = re.compile(r"(fusion|custom-call|copy|sort)(-start|-done)?$")


def phase_of_path(op_name: str) -> str:
    """The label an `op_name` path gives its operation, "" where it
    names none. The path is `jit(step)/jit(main)/<scopes>/<primitive>`:
    the last component is the primitive and never a label; the label is
    the LAST scope component that is in the vocabulary, read through
    autodiff's wrapping. One rule tells a phase's forward from its
    transpose: where a `transpose(` stands in the path at or before the
    label, `gather` reads `scatter` (the gather's transpose is the
    scatter-add); every other label is its own transpose. JAX writes the
    wrapper on the label itself under plain autodiff
    (`transpose(jvp(rows))/gather`, `transpose(jvp(gather))`) and on the
    component before it inside a `shard_map`
    (`transpose(jvp())/shard_map/rows/gather`): all read the same."""
    comps = op_name.split("/")[:-1]
    for i in range(len(comps) - 1, -1, -1):
        m = _COMPONENT.fullmatch(comps[i])
        if m and m.group(1) in _PHASE_OF:
            label = m.group(1)
            if label == "gather" and any("transpose(" in c for c in comps[: i + 1]):
                return "scatter"
            return label
    return ""


def _operations(text: str) -> dict:
    """{instruction -> (opcode, operand names, op_name or None)} of a
    compiled module's text, for every computation that runs as
    operations of its own: the entry computation and what it reaches
    through `while` / `conditional` / `call`. Fused computations and
    reducers are left out: their instructions never show on a device's
    operations line."""
    comps: dict = {}  # computation -> [(name, opcode, operands, op_name, callees)]
    entry, current = None, None
    for line in text.splitlines():
        if current is None:
            head = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
            if head and " = " not in line.split("(", 1)[0]:
                current = comps.setdefault(head.group(2), [])
                if head.group(1):
                    entry = head.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        inst = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", line)
        if not inst:
            continue
        rest = inst.group(2)
        op = re.search(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(", rest)
        if not op:
            continue
        depth, end = 0, len(rest)
        for j in range(op.end() - 1, len(rest)):
            depth += (rest[j] == "(") - (rest[j] == ")")
            if depth == 0:
                end = j
                break
        path = re.search(r'op_name="([^"]*)"', rest)
        callees = re.findall(
            r"(?:body|condition|to_apply|true_computation|false_computation)=%?([\w.\-]+)",
            rest[end:],
        ) if op.group(1) in ("while", "conditional", "call") else []
        branches = re.search(r"branch_computations=\{([^}]*)\}", rest[end:])
        if branches:
            callees += re.findall(r"%?([\w.\-]+)", branches.group(1))
        current.append((
            inst.group(1), op.group(1),
            re.findall(r"%([\w.\-]+)", rest[op.end():end]),
            path.group(1) if path else None, callees,
        ))
    out: dict = {}
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, opcode, operands, path, callees in comps[comp]:
            out[name] = (opcode, operands, path)
            todo.extend(callees)
    return out


def op_phases(text: str) -> dict:
    """{operation -> label or ""} for every operation of a compiled
    module that can show on a device's operations line (fusions, custom
    calls, copies, sorts, collectives, slices, …; not parameters, tuples,
    bitcasts, constants).

    The label is the path's (`phase_of_path`), "" where the path names
    none — the program gave that operation no phase, and a reader counts
    it as unscoped. A collective operation is `exchange` whatever scope
    it stands in (the compiler's own, in a GSPMD step, come with the
    path of what they serve). An operation WITHOUT a path of its own — a
    layout `copy` XLA put in, the halves of an async pair, the name of a
    parameter where a path would be — takes the label its consumers
    agree on, else the one its producers agree on, else "". A fusion
    that holds operations of two phases is booked to its root's: the
    path on the fusion instruction is its root's."""
    ops = _operations(text)
    label: dict = {}
    pathless = []
    for name, (opcode, _, path) in ops.items():
        if opcode in _NO_OP:
            continue
        if _COLLECTIVE.match(opcode):
            label[name] = "exchange"
        elif path and "/" in path:
            label[name] = phase_of_path(path)
        else:
            pathless.append(name)
    consumers: dict = {}
    for name, (_, operands, _) in ops.items():
        for src in operands:
            consumers.setdefault(src, []).append(name)
    # through views (a bitcast, a tuple element) to the operations behind
    def behind(names, step):
        found, todo, seen = set(), list(names), set()
        while todo:
            n = todo.pop()
            if n in seen or n not in ops:
                continue
            seen.add(n)
            if ops[n][0] in ("bitcast", "get-tuple-element", "tuple"):
                todo.extend(step(n))
            else:
                found.add(n)
        return found

    uses = lambda n: consumers.get(n, [])
    feeds = lambda n: ops[n][1]
    todo = pathless
    while todo:
        left = []
        for name in todo:
            for side in (behind(uses(name), uses), behind(feeds(name), feeds)):
                known = {label[n] for n in side if label.get(n)}
                if len(known) == 1:
                    label[name] = known.pop()
                    break
            else:
                left.append(name)
        if len(left) == len(todo):
            break
        todo = left
    for name in todo:
        label[name] = ""
    return {name: label[name] for name in ops if name in label}  # in the text's order


class CompileRecorder:
    """Compile accounting for jitted programs (docs/OBSERVABILITY.md
    "Compile accounting").

    `wrap(name, jitted)` returns a drop-in callable: the first call
    with a given abstract signature (shapes/dtypes/shardings) lowers
    and compiles EXPLICITLY — the same executable jit would have built
    lazily on that call — timing the compile and extracting

    - `cost_analysis()`: model FLOPs and bytes accessed per execution,
    - `memory_analysis()`: argument/output/temp/generated code bytes
      (the compile-time HBM footprint),
    - `op_scopes`: {optimized-HLO operation -> label of `PHASE_LABELS`,
      or "" where the program names none} for every operation a device
      trace can show (`op_phases`), which tools/trace_attrib.py and the
      benchmark's readers join with a trace's events, by program, to
      give device time by step phase

    into ONE `kind="compile"` JSONL record through `sink` (anything
    with .append(dict) — the trainer's MetricsLogger, so records land
    stamped ts/rank/run_id/gen/world in the same stream). Later calls
    with the same signature hit the cached executable. Compiling the
    SAME (program, sig) twice is a recompile — counted in the registry
    (compile.recompiles) and flagged by metrics_report --check's
    exactly-once gate; a NEW signature for a known program (a second
    batch shape, a resharded table) is a legitimate second program, not
    a recompile.

    A lower/compile failure propagates: what the compiler refused is
    the error of the run, not a note on stderr followed by a second,
    unrecorded compile under plain jit. Only the cost/memory/op-scope
    ANALYSIS of a program that did compile stays soft (a backend
    without it leaves those fields out); it is timed (`analysis_s`, the
    `xflow:analyze` span).
    """

    # entries per record: bounds the JSONL line. A record that hit it
    # says how many it left out (`op_scopes_dropped`)
    OP_SCOPES_CAP = 2048

    def __init__(self, sink=None, registry: Optional[Registry] = None):
        self._sink = sink
        self._reg = registry or default_registry()
        self._lock = threading.Lock()
        self._compiled: dict = {}  # (name, sig) -> compile count
        self._latest: dict = {}  # name -> latest record
        self.records: list = []

    def bind(self, sink) -> None:
        """Attach/replace the JSONL sink (serve wires it after the app
        owns the metrics appender)."""
        self._sink = sink

    # ------------------------------------------------------- signatures
    @staticmethod
    def signature(args: tuple, kwargs: Optional[dict] = None) -> str:
        """Short stable hash of the call's abstract signature: pytree
        structure + per-leaf shape/dtype/sharding, and the on-device
        layout of a leaf that has one (a jit without a layout of its own
        compiles for its argument's). Two calls with the same signature
        would reuse one executable; a changed signature is a new
        program, never a recompile."""
        import hashlib

        import jax

        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
        parts = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is not None and dtype is not None:
                desc = f"{dtype}{list(shape)}"
                sh = getattr(leaf, "sharding", None)
                if sh is not None:
                    desc += "@" + str(sh)
                fmt = getattr(leaf, "format", None)
                if fmt is not None and fmt.layout is not None:
                    desc += "/" + str(fmt.layout)
            else:
                desc = f"{type(leaf).__name__}:{leaf!r}"
            parts.append(desc)
        blob = str(treedef) + "|" + ";".join(parts)
        return hashlib.sha1(blob.encode()).hexdigest()[:12]

    @staticmethod
    def _fast_key(args: tuple, kwargs: Optional[dict] = None):
        """Per-call cache key for `wrap`: treedef + per-leaf
        shape/dtype and format (sharding and on-device layout: an
        executable compiled for one layout refuses an array in another)
        or, of a leaf that has none, sharding. Hashing these objects is
        far cheaper than the str()'d repr `signature` sha1s — that one
        runs once per compile (for the record), never per step."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
        return treedef, tuple(
            (leaf.shape, leaf.dtype,
             getattr(leaf, "format", None) or getattr(leaf, "sharding", None))
            if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
            else (type(leaf), repr(leaf))
            for leaf in leaves
        )

    # ---------------------------------------------------------- analysis
    @staticmethod
    def _num(v) -> Optional[float]:
        try:
            f = float(v)
        except (TypeError, ValueError):
            return None
        return f if f == f and abs(f) != float("inf") else None

    def _cost_fields(self, compiled) -> dict:
        """FLOPs/bytes + memory footprint of one compiled executable;
        every field None when the backend's analysis is unavailable."""
        out: dict = {"flops": None, "bytes_accessed": None}
        try:
            ca = compiled.cost_analysis()
            # jax returns one properties dict per device computation on
            # some versions, a bare dict on others
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                out["flops"] = self._num(ca.get("flops"))
                out["bytes_accessed"] = self._num(ca.get("bytes accessed"))
        except Exception:
            pass
        try:
            ma = compiled.memory_analysis()
            for attr, key in (
                ("argument_size_in_bytes", "argument_bytes"),
                ("output_size_in_bytes", "output_bytes"),
                ("temp_size_in_bytes", "temp_bytes"),
                ("generated_code_size_in_bytes", "generated_code_bytes"),
            ):
                v = getattr(ma, attr, None)
                if v is not None:
                    out[key] = int(v)
        except Exception:
            pass
        return out

    def _op_scopes(self, compiled) -> tuple:
        """(HLO module name or None, {operation -> label or ""}, entries
        left out at the cap, Pallas kernel count) from the compiled
        module's text. The map is `op_phases`'s, cut to `OP_SCOPES_CAP`
        entries with the ones a trace shows by a reader's name (fusions,
        custom calls, copies, sorts, collectives) kept first. The kernel
        count is the number of Mosaic custom calls the program really
        contains — the proof that a step runs the Pallas kernels and not
        their XLA stand-ins. The module name keys the map: instruction
        names are unique WITHIN one compiled module only, and a trace's
        events are joined per module (the device plane's `XLA Modules`
        line) so two programs' `fusion.1` never collide."""
        try:
            text = compiled.as_text()
        except Exception:
            return None, {}, 0, 0
        mod = re.match(r"\s*HloModule\s+([\w.\-]+)", text)
        phases = op_phases(text)
        dropped = max(0, len(phases) - self.OP_SCOPES_CAP)
        if dropped:
            ops = _operations(text)
            named = lambda n: not (
                _NAMED_ON_A_TRACE.match(ops[n][0]) or _COLLECTIVE.match(ops[n][0])
            )
            keep = sorted(phases, key=named)[: self.OP_SCOPES_CAP]  # stable: text order
            phases = {n: phases[n] for n in keep}
        return (
            mod.group(1) if mod else None, phases, dropped,
            text.count('custom_call_target="tpu_custom_call"'),
        )

    # ---------------------------------------------------------- recording
    def record(self, name: str, jitted, *args, static_fields=None, **kwargs):
        """Lower + compile `jitted` for these args, emit the compile
        record, and return the compiled executable. Raises whatever
        lower/compile raised. `static_fields`: what the builder knows
        about the program and the compiled module does not say
        (`wrap`'s keywords), copied into the record. A `jitted` that
        says `persistent_cache = False` of itself
        (`compile_cache.past_cache`) has that in its record."""
        import jax

        sig = self.signature(args, kwargs)
        # JAX announces a persistent-compile-cache hit as a monitoring
        # event; listening for the length of this compile tells a
        # deserialized executable from a compiled one
        hits: list = []

        def on_event(event: str, **_) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                hits.append(event)

        jax.monitoring.register_event_listener(on_event)
        try:
            # tracing and lowering are Python's and happen on every run;
            # the compile is XLA's, or a read from the persistent cache
            with span("lower") as lowering:
                lowered = jitted.lower(*args, **kwargs)
            with span("compile") as compiling:
                compiled = lowered.compile()
        finally:
            jax.monitoring.unregister_event_listener(on_event)
        dt = lowering.seconds + compiling.seconds
        rec = {
            "kind": "compile",
            "program": name,
            "sig": sig,
            "compile_time_s": round(dt, 6),
            "lower_s": round(lowering.seconds, 6),
            "xla_compile_s": round(compiling.seconds, 6),
            "cache_hit": bool(hits),
        }
        if getattr(jitted, "persistent_cache", True) is False:
            rec["persistent_cache"] = False  # compiled past it, by the builder's wrapper
        # what this record costs beyond the compile: XLA's cost and
        # memory analysis, the module's text and the phase map
        with span("analyze") as analyzing:
            rec.update(self._cost_fields(compiled))
            module, scopes, dropped, rec["pallas_calls"] = self._op_scopes(compiled)
        rec["analysis_s"] = round(analyzing.seconds, 6)
        with self._lock:
            seen = self._compiled.get((name, sig), 0)
            self._compiled[(name, sig)] = seen + 1
            rec["compiles"] = sum(
                n for (nm, _), n in self._compiled.items() if nm == name
            )
        if seen:
            self._reg.counter("compile.recompiles").inc()
        else:
            self._reg.counter("compile.programs").inc()
        self._reg.timer("compile.time").observe(dt)
        rec.update(static_fields or {})
        if module:
            rec["hlo_module"] = module
        if scopes:
            rec["op_scopes"] = scopes
        if dropped:
            rec["op_scopes_dropped"] = dropped
        with self._lock:
            self.records.append(rec)
            self._latest[name] = rec
        if self._sink is not None:
            self._sink.append(rec)
        return compiled

    def wrap(self, name: str, jitted, **static_fields):
        """Drop-in replacement for a jitted callable that compiles
        through `record` once per signature; keywords are fields the
        builder adds to each of the program's compile records."""
        cache: dict = {}

        def call(*args, **kwargs):
            try:
                key = self._fast_key(args, kwargs)
                compiled = cache.get(key)
            except TypeError:  # an unhashable leaf: the sha1 path
                key = self.signature(args, kwargs)
                compiled = cache.get(key)
            if compiled is None:
                compiled = cache[key] = self.record(
                    name, jitted, *args, static_fields=static_fields, **kwargs
                )
            return compiled(*args, **kwargs)

        call.program_name = name  # introspection (tests)
        return call

    # -------------------------------------------------------- introspection
    def latest(self, name: str) -> Optional[dict]:
        with self._lock:
            return self._latest.get(name)

    @property
    def recompiles(self) -> int:
        with self._lock:
            return sum(n - 1 for n in self._compiled.values() if n > 1)


# ----------------------------------------------------------------- StepTimer


def _block(tree) -> None:
    """block_until_ready on a pytree of (possibly jax) values; host
    numpy passes through untouched so StepTimer is testable without a
    device."""
    try:
        import jax

        jax.block_until_ready(tree)
    except ImportError:
        pass


class StepTimer:
    """One-step-behind step-time decomposition.

    Per step i the fit loop calls:

      for batch in st.batches(iterator):   # data-wait = time inside next()
          ... resolve/shard/dispatch ...   # host dispatch
          st.dispatched(metrics_i, rows)   # blocks on step i-1's metrics

    `dispatched` records step i's host-side timings, then finishes step
    i-1 by blocking on its (async) metrics — the block overlaps step i's
    device execution, so no sync bubble is added; the cost model is the
    non-finite guard's (train/trainer.py check_pending). Consequently a
    step's record lands one call later, and the LAST step needs
    `flush()` after the loop.

    Per finished step:
      - data_wait_s: time spent inside the iterator's next()
      - dispatch_s:  fetch end -> dispatch return (plan resolve, host
        transfer, async dispatch)
      - device_s:    dispatch return -> metrics ready. HOST time: when
        the device is the bottleneck it is the device step time; when
        the host is, the block returns immediately and it degrades to
        the pipeline interval — an upper bound, never an undercount.
      - step_s:      completion-to-completion interval. These telescope,
        so their sum over a run equals the elapsed wall time (the
        decomposition tests' invariant).

    One timer lives for one `fit()`. What lies outside its intervals —
    `_fit` entry to `first_fetch`, and `last_ready` to `fit()`'s return
    — the trainer stamps itself and reports as the step records'
    `boundary` (docs/OBSERVABILITY.md "The pass boundary").
    """

    def __init__(self, registry: Optional[Registry] = None):
        self._reg = registry or default_registry()
        self._pending = None  # (metrics, rows, wait_s, dispatch_s, dispatch_end)
        self._last_ready: Optional[float] = None
        self._last_wait = 0.0
        self._wait_end: Optional[float] = None
        self._win_rows = 0
        self._win: dict = {"step": [], "wait": [], "dispatch": [], "device": []}
        self._win_start = time.perf_counter()
        self.steps = 0
        self.rows = 0
        # perf_counter instant the iterator's first next() was called:
        # where the first step's interval starts
        self.first_fetch: Optional[float] = None
        # seconds inside the terminating next() calls (one an epoch):
        # the take of the end-of-pass mark; a pass with a worker of its
        # own joins it there
        self.iter_end_s = 0.0

    def batches(self, iterable: Iterable) -> Iterator:
        """Wrap the batch iterator so time spent INSIDE next() — and
        only that — is the step's data-wait (`xflow:data_wait`; a
        pass's first is the pipeline's cold start, its last the
        terminating call, which yields no step and counts as
        `iter_end_s`). Abandonment (an early break / exception in the
        consuming loop) closes the wrapped iterator promptly, preserving
        the producer's close-cascade contract (data/pipeline.py
        PassProducer)."""
        it = iter(iterable)
        end = object()
        try:
            while True:
                with span("data_wait") as wait:
                    item = next(it, end)
                if self.first_fetch is None:
                    self.first_fetch = wait.t0
                if item is end:
                    self.iter_end_s += wait.seconds
                    return
                self._wait_end = wait.t1
                self._last_wait = wait.seconds
                yield item
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()

    @property
    def last_wait(self) -> float:
        """The just-fetched batch's data-wait seconds (time inside the
        iterator's next())."""
        return self._last_wait

    @property
    def last_wait_end(self) -> Optional[float]:
        """perf_counter timestamp of the just-fetched batch's fetch end
        (None before the first fetch)."""
        return self._wait_end

    @property
    def last_ready(self) -> Optional[float]:
        """perf_counter instant the newest finished step's metrics were
        ready (None before a step has finished): where the step
        intervals end."""
        return self._last_ready

    def dispatched(self, metrics, rows: int) -> None:
        """Call right after the step's async dispatch returns. Finishes
        the PREVIOUS step (block-until-ready overlapping this step's
        device execution) and stages this one."""
        now = time.perf_counter()
        wait_end = self._wait_end if self._wait_end is not None else now
        cur = (metrics, int(rows), self._last_wait, now - wait_end, now)
        self._finish_pending()
        self._pending = cur

    def flush(self) -> None:
        """Finish the final in-flight step (its metrics have no
        successor to hide behind — the one sync this class adds, at
        end of data)."""
        self._finish_pending()

    def _finish_pending(self) -> None:
        if self._pending is None:
            return
        metrics, rows, wait_s, dispatch_s, dispatch_end = self._pending
        self._pending = None
        _block(metrics)
        t_ready = time.perf_counter()
        device_s = t_ready - dispatch_end
        # first step: anchor on its own fetch start so intervals telescope
        base = (
            self._last_ready
            if self._last_ready is not None
            else dispatch_end - dispatch_s - wait_s
        )
        self._last_ready = t_ready
        self.steps += 1
        self.rows += rows
        self._win_rows += rows
        w = self._win
        w["step"].append(t_ready - base)
        w["wait"].append(wait_s)
        w["dispatch"].append(dispatch_s)
        w["device"].append(device_s)
        self._reg.timer("step.time").observe(t_ready - base)
        self._reg.timer("step.data_wait").observe(wait_s)

    def window_record(self) -> dict:
        """Stats over the steps finished since the last call, then reset
        the window. Empty dict when no step has finished yet (the very
        first log tick under log_every=1 — timing runs one behind)."""
        w = self._win
        n = len(w["step"])
        if n == 0:
            return {}
        now = time.perf_counter()
        elapsed = max(now - self._win_start, 1e-9)
        step_ms = np.asarray(w["step"]) * 1e3
        rec = {
            "steps_per_s": round(n / elapsed, 3),
            "rows_per_s": round(self._win_rows / elapsed, 1),
            "step_time_p50_ms": round(float(np.percentile(step_ms, 50)), 3),
            "step_time_p99_ms": round(float(np.percentile(step_ms, 99)), 3),
            "data_wait_ms": round(float(np.mean(w["wait"])) * 1e3, 3),
            "dispatch_ms": round(float(np.mean(w["dispatch"])) * 1e3, 3),
            "device_ms": round(float(np.mean(w["device"])) * 1e3, 3),
        }
        self._win = {"step": [], "wait": [], "dispatch": [], "device": []}
        self._win_rows = 0
        self._win_start = now
        # shared cadence: the registry timers' percentile windows clear
        # with the log window (their run totals are monotone and survive)
        self._reg.timer("step.time").window_reset()
        self._reg.timer("step.data_wait").window_reset()
        return rec


# ------------------------------------------- host timeline: spans + profiler

# the stage vocabulary of the kind="pipeline" window record
# (train.pipeline_metrics, docs/OBSERVABILITY.md "Input-pipeline
# attribution"). Two CONCURRENT timelines — the prefetch/producer
# thread and the fit-loop/consumer thread — so the schema invariant
# metrics_report --check enforces is per-THREAD: each group's stage
# times sum to at most the window wall, never the two groups combined.
PIPELINE_PRODUCER_STAGES = (
    "read",  # file readline (Python parser; the native parser folds it into parse)
    "parse",  # token split / native next_batch (read+parse+hash+pad in C)
    "hash",  # fnv1a64 + slot fold (Python parser)
    "batch",  # batch assembly: per-example row accumulation
    "pad",  # make_batch: padded-array fill
    "cache_read",  # packed-shard-cache mmap batch slicing (data/shardcache.py
    # — replaces read/parse/hash/batch/pad when a shard streams from cache)
    "plan",  # batch -> step arrays (validation, sorted-plan build, dedup)
    "producer_wait",  # blocked in the bounded prefetch queue's put()
)
PIPELINE_CONSUMER_STAGES = (
    "queue_wait",  # the batch's full data-wait (time inside next(); the
    # prefetch get() is the starved part — producer starvation signal)
    "transfer",  # host->device staging (train/engine.py `Engine.shard_batch`)
    "dispatch",  # every other host-side slice of the step: the
    # train_step call plus the loop's bookkeeping between spans, so the
    # four consumer stages TILE the fit loop
    "device",  # block on the PREVIOUS step's metrics (one-step-behind)
)
PIPELINE_STAGES = PIPELINE_PRODUCER_STAGES + PIPELINE_CONSUMER_STAGES

# the `xflow:` spans, by the thread that opens them (docs/OBSERVABILITY.md
# "The host timeline" says what each covers). The annotation's name and
# the step records' `host` field come from the same string (`span`,
# `host_field`), so the trace and the records cannot drift.
HOST_SPANS_FIT = (
    "fit", "fit_open", "data_wait", "transfer", "dispatch", "prev_ready",
    "iter_end", "fit_flush", "occupancy", "fit_close",
)
HOST_SPANS_PREFETCH = (
    "parse", "cache_read", "plan", "producer_wait", "read_ahead", "ffm_place",
)
HOST_SPANS_INIT = ("init_state", "place_state", "lower", "compile", "analyze")
HOST_SPANS = HOST_SPANS_FIT + HOST_SPANS_PREFETCH + HOST_SPANS_INIT
_SPAN_NAMES = {s: "xflow:" + s for s in HOST_SPANS}

# what an armed profiler's window accumulates: the producer's stages
# (the per-batch prefetch spans and the Python parser's per-line sums,
# which have no annotation) and the fit loop's per-step spans, plus
# `loop_other` — the loop's own bookkeeping between them (health reads,
# guard checks, log writes), by subtraction, so the five tile the loop
HOST_CONSUMER_STAGES = (
    "data_wait", "transfer", "dispatch", "prev_ready", "loop_other",
)
# spans that run INSIDE another stage's span (FFM's placement
# permutation, inside `plan`): the window keeps their sums and the step
# records' `host` names them where they ran, but they are a part of
# their stage, so no sum over the stages above counts them again
HOST_NESTED_STAGES = ("ffm_place",)
HOST_STAGES = PIPELINE_PRODUCER_STAGES + HOST_CONSUMER_STAGES
# what the planner counts for the window's batches beside its seconds
# (`ops/sorted_table.chunk_chain_counts`, booked with the batch's plan
# span): the sorted kernels' (window, chunk) visits and the chunks they
# load. The step records' `host` names them where a plan had them, as
# they are; a fit()'s sums are its final record's
HOST_COUNTERS = ("chunk_visits", "chunk_loads")

# the host-side stages a bottleneck verdict can name, and the
# benchmark's producer_busy_pct sums (producer work; producer_wait is
# the device-bound signal, not a host cost).
# cache_read is here on purpose: a run whose producer wall is dominated
# by cache slicing is cache-bound — the verdict should say so, both as
# the healthy after-picture of the packed-shard-cache path and as the
# signal when the cache medium itself becomes the constraint
_PIPELINE_HOST_STAGES = (
    "read", "parse", "hash", "batch", "pad", "cache_read", "plan",
)

# jax.profiler.TraceAnnotation, looked up on the first span; a test puts
# a recorder here (the seam TraceWindow's `profiler` argument is for it)
_annotation = None


def _resolve_annotation():
    global _annotation
    if _annotation is None:
        import jax

        _annotation = jax.profiler.TraceAnnotation
    return _annotation


class span:
    """`with span("plan", profiler):` — one occurrence of a named stage
    of the host timeline.

    Always: a `jax.profiler.TraceAnnotation("xflow:<stage>")`, which
    lands in the profiler's host plane on the same clock as the
    device's operations whenever a trace session is open
    (`TraceWindow`, or anybody's `jax.profiler.start_trace`), and is a
    flag test otherwise; and the two `perf_counter` stamps `t0` / `t1`
    (`seconds` after exit), taken inside the annotation. With a
    `profiler` (an armed run's `PipelineProfiler`) the duration is also
    added to its window. Never hold one open across a `yield`: the
    annotation stack is the thread's."""

    __slots__ = ("stage", "_prof", "_ann", "t0", "t1")

    def __init__(self, stage: str, profiler: "Optional[PipelineProfiler]" = None):
        self.stage = stage
        self._prof = profiler
        self._ann = (_annotation or _resolve_annotation())(_SPAN_NAMES[stage])

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self._prof is not None:
            self._prof.add(self.stage, self.t1 - self.t0)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def host_field(stage: str) -> str:
    """The step records' `host` field of a stage. The train_step call's
    is `dispatch_call_ms`: `dispatch_ms` is taken, one level up, by the
    StepTimer's wider interval (fetch end -> dispatch return)."""
    return ("dispatch_call" if stage == "dispatch" else stage) + "_ms"


def host_fields(win: dict) -> dict:
    """A profiler window as the step records' `host` payload: the
    window's sum of every stage, in ms, and its batches."""
    out = {host_field(s): round(win[s] * 1e3, 3) for s in HOST_STAGES}
    out.update(
        (host_field(s), round(win[s] * 1e3, 3)) for s in HOST_NESTED_STAGES if win[s] > 0.0
    )
    out.update((c, win[c]) for c in HOST_COUNTERS if win[c])
    out["batches"] = win["batches"]
    return out


def pipeline_fields(win: dict) -> dict:
    """A profiler window as the kind="pipeline" payload: the consumer's
    stages folded into the four that tile the fit loop."""
    rec = {"wall_s": round(win["wall_s"], 6)}
    for s in PIPELINE_PRODUCER_STAGES:
        rec[f"{s}_s"] = round(win[s], 6)
    rec["queue_wait_s"] = round(win["data_wait"], 6)
    rec["transfer_s"] = round(win["transfer"], 6)
    rec["dispatch_s"] = round(win["dispatch"] + win["loop_other"], 6)
    rec["device_s"] = round(win["prev_ready"], 6)
    for k in ("batches", "rows", "queue_depth", "queue_cap"):
        rec[k] = win[k]
    return rec


def pipeline_verdict(stages: dict, wall_s: float) -> str:
    """One-line bottleneck verdict over aggregated stage seconds
    (tools/pipeline_attrib.py and metrics_report --health print it).

    The two starvation signals point in opposite directions: the
    consumer blocked in get() (`queue_wait`) means the HOST cannot feed
    the device — name the dominant producer stage; the producer blocked
    in put() (`producer_wait`) means the host outruns the consumer —
    the run is device/dispatch-bound and the input pipeline is not the
    problem."""
    if wall_s <= 0:
        return "no pipeline windows"
    pct = lambda k: 100.0 * float(stages.get(k, 0.0)) / wall_s
    starved = pct("queue_wait")
    blocked = pct("producer_wait")
    top = max(_PIPELINE_HOST_STAGES, key=pct)
    if starved >= blocked and starved > 10.0:
        return (
            f"host-bound in {top}: {pct(top):.0f}% of wall "
            f"(consumer starved {starved:.0f}%)"
        )
    if blocked > starved and blocked > 10.0:
        return (
            f"device-bound: producer blocked {blocked:.0f}% of wall "
            f"(dispatch+device {pct('dispatch') + pct('device'):.0f}%)"
        )
    return (
        f"balanced: top host stage {top} {pct(top):.0f}%, "
        f"device {pct('device'):.0f}%, queue-wait {starved:.0f}%"
    )


class PipelineProfiler:
    """The window an armed run's spans accumulate into: per-stage wall
    time of the input pipeline and the fit loop (docs/OBSERVABILITY.md
    "Input-pipeline attribution").

    The trainer builds one when somebody listens — `train.metrics_path`
    (the step records' `host`), `train.profile_dir`, or
    `train.pipeline_metrics` — and hands it to the seams: `span(stage,
    profiler)` on both threads, `add_many` from the Python parser's
    per-line loop (data/libffm.py, data/pipeline.py), `count_batch` /
    `observe_queue` from the parser and the prefetch queue. Unarmed
    there is no profiler, the same spans run with `None`, and nothing
    accumulates. `publish` (train.pipeline_metrics) is the one switch
    for the stream's pipeline surface: the `pipeline.*` registry gauges
    here and the trainer's kind="pipeline" window records; without it a
    run has neither.

    Same one-step-behind philosophy as the StepTimer: nothing here ever
    blocks the device — `prev_ready` is the fit loop's existing block
    on the PREVIOUS step's metrics, and all other stages time host work
    that was already on the critical path.

    Threading: producer stages are fed from the prefetch thread,
    consumer stages from the fit loop; `add`/`take_window` take one
    lock, and the hot per-line Python-parser path batches its
    accumulations (`add_many` every few hundred lines) so the lock is
    touched per batch, not per row.
    """

    def __init__(self, registry: Optional[Registry] = None, publish: bool = True):
        self._reg = registry or default_registry()
        self.publish = bool(publish)
        self._lock = threading.Lock()
        self._win = self._empty_window()
        self._total = self._empty_window()
        self._win_batches = 0
        self._win_rows = 0
        self.batches = 0
        self.rows = 0
        self._queue_depth = 0
        self._queue_cap = 0
        self._start = time.perf_counter()
        self._win_start = self._start

    def start(self) -> None:
        """Re-anchor the clock at fit() start (the profiler is built at
        Trainer construction; state init must not count as pipeline
        wall) and pre-register the prefetch gauges so a published run's
        counters snapshot carries them even before the first sample."""
        with self._lock:
            now = time.perf_counter()
            self._start = now
            self._win_start = now
        if self.publish:
            self._reg.gauge("pipeline.queue_depth").set(0)
            self._reg.gauge("pipeline.producer_blocked_s").set(0.0)

    @staticmethod
    def _empty_window() -> dict:
        win = {s: 0.0 for s in HOST_STAGES + HOST_NESTED_STAGES}
        win.update({c: 0 for c in HOST_COUNTERS})
        return win

    # ------------------------------------------------------- accumulation
    def add(self, stage: str, seconds: float) -> None:
        with self._lock:
            self._win[stage] += seconds
            total = self._total[stage] = self._total[stage] + seconds
        if stage == "producer_wait" and self.publish:
            # the producer-blocked run total as a live gauge: the
            # consumer-is-the-bottleneck signal an operator can watch
            # without waiting for a window record
            self._reg.gauge("pipeline.producer_blocked_s").set(round(total, 6))

    def add_many(self, stages: dict) -> None:
        with self._lock:
            for stage, seconds in stages.items():
                self._win[stage] += seconds
                self._total[stage] += seconds

    def count_batch(self, rows: int) -> None:
        with self._lock:
            self._win_batches += 1
            self._win_rows += int(rows)
            self.batches += 1
            self.rows += int(rows)

    def close(self) -> None:
        """Drop this profiler's registry gauges (fit() teardown): the
        no-pipeline-surface contract is per-RUN, not per-process — a
        later unpublished fit in the same process must snapshot no
        pipeline.* metrics. start() re-registers them on the next
        published fit."""
        self._reg.discard("pipeline.queue_depth")
        self._reg.discard("pipeline.producer_blocked_s")

    def observe_queue(self, depth: int, cap: int) -> None:
        """Prefetch queue-depth sample (both the worker after put and
        the consumer after get sample it): depth == cap means the
        producer is ahead; a persistent 0 means the consumer starves."""
        with self._lock:
            self._queue_depth = int(depth)
            self._queue_cap = int(cap)
        if self.publish:
            self._reg.gauge("pipeline.queue_depth").set(int(depth))

    # --------------------------------------------------------- reporting
    def totals(self) -> tuple[dict, float]:
        """(run-total stage seconds, elapsed seconds since start()) —
        totals survive window resets; the producer-blocked gauge and
        any end-of-run introspection read from here."""
        with self._lock:
            return dict(self._total), time.perf_counter() - self._start

    def take_window(self) -> dict:
        """Per-stage seconds since the last call, with `wall_s`,
        `batches`, `rows` and the queue's last sample, then reset:
        what `host_fields` and `pipeline_fields` format. Empty dict
        when the window saw no activity at all (nothing to attribute —
        the trainer skips the fields rather than writing zeros)."""
        with self._lock:
            if self._win_batches == 0 and not any(
                v > 0.0 for v in self._win.values()
            ):
                return {}
            now = time.perf_counter()
            win = dict(self._win)
            win["wall_s"] = max(now - self._win_start, 1e-9)
            win["batches"] = self._win_batches
            win["rows"] = self._win_rows
            win["queue_depth"] = self._queue_depth
            win["queue_cap"] = self._queue_cap
            self._win = self._empty_window()
            self._win_batches = 0
            self._win_rows = 0
            self._win_start = now
        return win


# ------------------------------------------------------------- HealthMonitor


def estimate_collision_rate(distinct_slots: int, num_slots: int) -> float:
    """Live collision-rate estimate from slot saturation.

    The offline tool (xflow_tpu/tools/collisions.py) computes the exact
    rate from distinct feature tokens, which the trainer never sees
    (the parser hands it post-fold slots). But under uniform hashing the
    expected distinct-slot count for n distinct keys is
    d = S·(1 − (1 − 1/S)^n); inverting gives n̂ = ln(1 − d/S)/ln(1 − 1/S)
    and the estimated rate 1 − d/n̂ — the same birthday math, driven by
    what the trainer CAN observe. Exact at d→0, conservative near
    saturation (d→S ⇒ rate→1)."""
    S, d = int(num_slots), int(distinct_slots)
    if d <= 0 or S <= 1:
        return 0.0
    if d >= S:
        return 1.0
    import math

    n_hat = math.log1p(-d / S) / math.log1p(-1.0 / S)
    return max(0.0, 1.0 - d / n_hat)


class HealthMonitor:
    """Host side of the model-health pipeline (train.health_metrics).

    The step builders fuse grad/update/param norms into each step's
    metrics dict (train/step.py health_norms); this class consumes them
    ONE STEP BEHIND — `collect()` runs right after `StepTimer.dispatched`
    has block_until_ready'd the previous step's metrics, so every read
    here is a ready-buffer host copy, never a sync — and maintains what
    only the host can: the loss EMA, the touched-slot bitmap behind the
    occupancy/collision gauges, and the per-window values the trainer
    folds into its metrics-JSONL records.

    Thread-safety: `observe_batch` is one of a training batch's
    deferred calls (trainer._BatchPrep): it runs where the batch is
    consumed — the fit loop, inside the batch's `next()`, so a batch
    read ahead and never trained marks nothing — or on the plan thread
    for a caller that defers nothing; `collect`/`window_record` run on
    the fit loop. The bitmap and window state are lock-protected.
    """

    KEYS = ("grad_norm", "update_norm", "param_norm")

    def __init__(
        self,
        mode: str = "off",
        ema_decay: float = 0.99,
        registry: Optional[Registry] = None,
        num_slots: int = 0,
    ):
        if mode not in ("off", "norms", "full"):
            raise ValueError(f"health mode {mode!r}: expected off|norms|full")
        self.enabled = mode != "off"
        self.mode = mode
        self._decay = float(ema_decay)
        self._reg = registry or default_registry()
        self._lock = threading.Lock()
        self.loss_ema = float("nan")
        self._pending = None  # a step's metrics awaiting the one-behind read
        self._last: dict = {}  # last observed health floats
        self._win_grad_max = float("nan")
        self._seen = (
            np.zeros(int(num_slots), dtype=bool)
            if self.enabled and num_slots > 0
            else None
        )
        self._num_slots = int(num_slots)

    # ------------------------------------------------- step-metrics side
    def staged(self, metrics) -> None:
        """Stage a just-dispatched step's (async) metrics for the next
        collect — mirrors the trainer's pending_ok bookkeeping."""
        if self.enabled:
            self._pending = metrics

    def collect(self) -> None:
        """Finish the PREVIOUS step: read its (ready) health scalars and
        loss, fold the EMA, refresh the gauges. Call right after
        StepTimer.dispatched — the block there made these reads free."""
        if self._pending is None:
            return
        m = self._pending
        self._pending = None
        loss = float(m["loss"]) if "loss" in m else float("nan")
        if loss == loss and abs(loss) != float("inf"):
            self.loss_ema = (
                loss
                if self.loss_ema != self.loss_ema
                else self._decay * self.loss_ema + (1.0 - self._decay) * loss
            )
            self._reg.gauge("health.loss_ema").set(self.loss_ema)
        vals = {}
        for key in self.KEYS:
            if key in m:
                vals[key] = float(m[key])
                self._reg.gauge(f"health.{key}").set(vals[key])
        if self.mode == "full":
            for key in m:
                if isinstance(key, str) and "." in key and key.split(".")[0] in (
                    "grad_norm", "update_norm", "param_norm",
                ):
                    vals[key] = float(m[key])
        with self._lock:
            if vals:
                self._last = vals
                g = vals.get("grad_norm")
                if g is not None and (
                    self._win_grad_max != self._win_grad_max or g > self._win_grad_max
                ):
                    self._win_grad_max = g

    def flush(self) -> None:
        """End-of-data: the final step's metrics were just blocked on by
        StepTimer.flush(), so this collect is still sync-free."""
        self.collect()

    # --------------------------------------------------- occupancy side
    def observe_batch(self, slots, mask) -> None:
        """Mark a training batch's masked slots as touched (as the batch
        is consumed; the previous step's device time still hides it).
        Drives the occupancy + collision-estimate gauges."""
        if self._seen is None:
            return
        idx = np.asarray(slots)[np.asarray(mask) > 0]
        with self._lock:
            self._seen[idx] = True

    # ------------------------------------------------------- windowing
    def window_record(self) -> dict:
        """The health fields for one metrics-JSONL window record: last
        norm values + the window's grad-norm max, the loss EMA, and the
        occupancy/collision gauges. Empty dict when nothing was
        collected yet (step 1 under log_every=1 — the health read runs
        one behind, like the StepTimer)."""
        if not self.enabled:
            return {}
        with self._lock:
            if not self._last and self.loss_ema != self.loss_ema:
                return {}
            fin = lambda v: round(v, 6) if v == v and abs(v) != float("inf") else None
            rec = {
                "grad_norm": fin(self._last.get("grad_norm", float("nan"))),
                "grad_norm_max": fin(self._win_grad_max),
                "update_norm": fin(self._last.get("update_norm", float("nan"))),
                "param_norm": fin(self._last.get("param_norm", float("nan"))),
                "loss_ema": fin(self.loss_ema),
            }
            if self.mode == "full":
                tables: dict = {}
                for key, v in self._last.items():
                    if "." in key:
                        kind, tname = key.split(".", 1)
                        tables.setdefault(tname, {})[kind] = fin(v)
                if tables:
                    rec["health_tables"] = tables
            self._win_grad_max = float("nan")
            if self._seen is not None:
                touched = int(np.count_nonzero(self._seen))
                occ = touched / self._num_slots
                est = estimate_collision_rate(touched, self._num_slots)
                rec["slots_touched"] = touched
                rec["table_occupancy"] = round(occ, 6)
                rec["est_collision_rate"] = round(est, 6)
                self._reg.gauge("health.slots_touched").set(touched)
                self._reg.gauge("health.table_occupancy").set(occ)
                self._reg.gauge("health.est_collision_rate").set(est)
        return rec


# ----------------------------------------------------------- liveness hooks


def install_stack_dump_handler():
    """Register faulthandler on SIGUSR1 so an operator can get all-thread
    stack dumps from a live (or wedged) trainer with plain `kill -USR1`
    — the standard "why is this rank stuck" tool. Returns a restore
    callable; a no-op off the main thread (signal handlers can only be
    installed there; non-main callers keep training, just without the
    hook) and on platforms without SIGUSR1."""
    try:
        import faulthandler
        import signal

        if threading.current_thread() is not threading.main_thread():
            return lambda: None
        sig = getattr(signal, "SIGUSR1", None)
        if sig is None:
            return lambda: None
        faulthandler.register(sig, all_threads=True)
        return lambda: faulthandler.unregister(sig)
    except Exception:
        return lambda: None


class HangWatchdog:
    """No-progress watchdog (train.hang_timeout_s): a daemon thread that
    dumps ALL thread stacks to stderr (faulthandler) when `tick()` has
    not been called for `timeout_s` — one dump per stall, re-armed by
    the next tick, so a recovered pipeline can trip it again later.
    A hang in an SPMD trainer usually means a peer died mid-collective
    (docs/ROBUSTNESS.md); the dump shows exactly which collective."""

    def __init__(self, timeout_s: float, out=None):
        self._timeout = float(timeout_s)
        self._out = out  # test seam; defaults to sys.stderr at dump time
        # tick() runs on the training thread, _run on the watchdog
        # thread — _last/_dumped are cross-thread state (xflowlint XF301)
        self._lock = threading.Lock()
        self._last = time.perf_counter()
        self._dumped = False
        self._stop = threading.Event()
        self._thread = None
        self.dumps = 0
        if self._timeout > 0:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="xflow-hang-watchdog"
            )
            self._thread.start()

    def tick(self) -> None:
        with self._lock:
            self._last = time.perf_counter()
            self._dumped = False

    def _run(self) -> None:
        import faulthandler
        import sys as _sys

        poll = min(max(self._timeout / 4.0, 0.05), 5.0)
        while not self._stop.wait(poll):
            with self._lock:
                idle = time.perf_counter() - self._last
                stalled = idle > self._timeout and not self._dumped
                if stalled:
                    self._dumped = True
                    self.dumps += 1
            if stalled:
                out = self._out or _sys.stderr
                print(
                    f"xflow: hang watchdog: no step progress for "
                    f"{idle:.1f}s (> train.hang_timeout_s="
                    f"{self._timeout}); dumping all thread stacks",
                    file=out,
                )
                try:
                    faulthandler.dump_traceback(file=out, all_threads=True)
                except Exception:
                    pass

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)


# --------------------------------------------------------------- TraceWindow


class TraceWindow:
    """Programmatic xprof trace window.

    `train.trace_start_step >= 1` (with `train.profile_dir` set) starts
    the trace just before that step's dispatch — after compilation has
    settled, so the window shows the steady state instead of burying it
    under compile noise — and stops it once `train.trace_num_steps`
    steps have dispatched. `trace_start_step = 0` keeps the legacy
    whole-run trace. `close()` (the fit loop's finally) stops a trace
    still running when the data ends inside the window.

    `profiler` is a test seam; the default is `jax.profiler`.
    """

    def __init__(
        self,
        profile_dir: str,
        start_step: int = 0,
        num_steps: int = 0,
        profiler=None,
    ):
        self._dir = profile_dir
        self._start = max(int(start_step), 0)
        self._num = max(int(num_steps), 1)
        self._running = False
        self._done = not profile_dir
        self._prof = profiler

    def _profiler(self):
        if self._prof is None:
            import jax

            self._prof = jax.profiler
        return self._prof

    def maybe_start_run(self) -> None:
        """Pre-loop hook: whole-run mode (start_step=0) starts here."""
        if not self._done and not self._running and self._start == 0:
            self._profiler().start_trace(self._dir)
            self._running = True

    def before_step(self, step: int) -> None:
        """Window mode: called with the 1-based step about to dispatch."""
        if self._done or self._start == 0:
            return
        if not self._running and step == self._start:
            self._profiler().start_trace(self._dir)
            self._running = True
        elif self._running and step >= self._start + self._num:
            self._stop()

    def _stop(self) -> None:
        if self._running:
            self._profiler().stop_trace()
            self._running = False
        self._done = True

    def close(self) -> None:
        """Stop a still-running trace (end of data / abnormal exit)."""
        if self._running:
            self._profiler().stop_trace()
            self._running = False
        self._done = True
