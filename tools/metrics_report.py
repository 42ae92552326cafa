#!/usr/bin/env python3
"""Summarize xflow telemetry JSONL runs (docs/OBSERVABILITY.md).

Loads one or more metrics JSONL files (or run directories — every
`*.jsonl` inside), groups records by (run_id, rank, kind) — `kind`
separates the metrics, heartbeat, and watchdog streams a run dir
holds — and prints a throughput / loss / bad-step summary table.
Reading is truncation-tolerant (xflow_tpu.jsonl.read_jsonl_counted):
a crash mid-append leaves a partial last line, which is skipped with
a warning, never an exception.

    python tools/metrics_report.py runs/exp1/               # summary table
    python tools/metrics_report.py a.jsonl b.jsonl          # multiple files
    python tools/metrics_report.py runs/exp1 --check        # schema gate (CI)
    python tools/metrics_report.py runs/exp1 --health       # health summary
    python tools/metrics_report.py runs/exp1 --bench-json - # BENCH-style JSON
    python tools/metrics_report.py runs/exp1 --regress BENCH_r05.json

`--check` validates the telemetry schema — every record stamped with
ts/rank/run_id, step numbers monotone per stream, window records
carrying the full decomposition key set, health fields all-or-none,
eval and heartbeat records complete, `world` stamps agreeing within
each generation (the rank SET may change ACROSS generations: a
degraded --allow-shrink relaunch is legitimate, not corruption) — and
exits nonzero on any violation (tools/smoke_telemetry.sh gates on it).

`--health` renders the model-health view: norm trends, loss EMA, the
AUC trajectory, occupancy/collision gauges, and a per-rank heartbeat
table (straggler/dead classification via launch/watchdog.py, with
"now" = the newest heartbeat seen, so a finished run reads as
finished, not dead; a rank the supervisor shrank away reads as
`retired@genK`, not dead).

`--bench-json` emits a BENCH-style perf-trajectory record (the shape
bench.py prints) computed from the run's own telemetry, so a training
run doubles as a benchmark sample without a separate bench invocation.

`--regress BASELINE.json` compares this run's bench record (and AUC,
when both sides have one) against a previously saved baseline and
exits 3 on regression beyond `--regress-tol` / `--auc-tol` — the CI
gate that keeps the bench trajectory honest.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from xflow_tpu.jsonl import read_jsonl_counted  # noqa: E402
from xflow_tpu.tracing import (  # noqa: E402
    BATCH_SPAN_NAME,
    REQUEST_SPAN_NAMES,
)

# the step-decomposition keys every window record carries (telemetry
# .StepTimer.window_record); --check enforces all-or-none
WINDOW_KEYS = (
    "steps_per_s",
    "rows_per_s",
    "step_time_p50_ms",
    "step_time_p99_ms",
    "data_wait_ms",
    "dispatch_ms",
    "device_ms",
)
# the host timeline's share of a window record (docs/OBSERVABILITY.md
# "The host timeline"; telemetry.host_fields and Trainer._boundary are
# the writers): `host` is all-or-none like the window keys, every value
# a non-negative number; `boundary` holds the open's three parts always
# and the previous fit()'s four together or not at all, and since the
# producer reads ahead over a pass's end `adopted` (whether this fit()
# opened on its head start). Both are absent from an unarmed run's and
# from a pre-upgrade writer's records.
HOST_KEYS = (
    "read_ms", "parse_ms", "hash_ms", "batch_ms", "pad_ms", "cache_read_ms",
    "plan_ms", "producer_wait_ms", "data_wait_ms", "transfer_ms",
    "dispatch_call_ms", "prev_ready_ms", "loop_other_ms", "batches",
)
# what a `host` holds besides, only where it ran or was counted: the
# stages nested in another (telemetry.HOST_NESTED_STAGES) and the
# planner's counts (telemetry.HOST_COUNTERS)
HOST_OPTIONAL_KEYS = {"ffm_place_ms", "chunk_visits", "chunk_loads"}
BOUNDARY_OPEN_KEYS = ("fit_open_ms", "first_batch_ms", "first_dispatch_ms")
BOUNDARY_TAIL_KEYS = ("fit_tail_ms", "occupancy_ms", "close_ms", "between_fits_ms")
BOUNDARY_FLAGS = {"adopted"}  # a bool beside the milliseconds; older writers have none
# the health keys a health-enabled window record carries (telemetry
# .HealthMonitor.window_record); --check enforces all-or-none too
HEALTH_KEYS = ("grad_norm", "update_norm", "param_norm", "loss_ema")
STAMP_KEYS = ("ts", "rank", "run_id")
# the key set every kind="compile" record carries (telemetry
# .CompileRecorder.record — docs/OBSERVABILITY.md "Compile accounting");
# --check enforces presence, a positive compile time, and the
# exactly-once rule: the same (program, sig) never compiles twice in
# one stream (a recompile means a jit cache is thrashing)
COMPILE_KEYS = ("program", "sig", "compile_time_s", "flops", "bytes_accessed")
# the compile time's two parts (tracing + lowering; XLA's compile or a
# cache read), added after runs were archived: present they come
# together and add up to compile_time_s
COMPILE_SPLIT_KEYS = ("lower_s", "xla_compile_s")
# the key set every kind="serve" window record carries (serve/metrics
# .ServeMetrics.maybe_flush — SERVE_WINDOW_KEYS there is the writer's
# copy); --check enforces all-or-none plus monotone model generation
SERVE_KEYS = (
    "requests",
    "rows",
    "qps",
    "rows_per_s",
    "batches",
    "batch_fill",
    "queue_wait_p50_ms",
    "queue_wait_p99_ms",
    "device_p50_ms",
    "device_p99_ms",
    "total_p50_ms",
    "total_p99_ms",
    "window_s",
    "bad_requests",
    "shed_requests",
    "generation",
    "step",
    "data_freshness_s",
)
# serve window keys added AFTER runs were already archived: absence
# means a pre-upgrade writer (or a mid-upgrade fleet mixing binaries),
# not a schema violation — present they ride the all-or-none gate.
# data_freshness_s is doubly optional: it only exists while the served
# generation carries a publication sidecar (train.publish_every), so a
# window without it means "not measurable", never a violation
OPTIONAL_SERVE_KEYS = ("shed_requests", "data_freshness_s")
# the key set every kind="autotune" decision record carries (serve
# /autotune.py controller applied by server.ServeApp._autotune —
# docs/OBSERVABILITY.md "SLO autotuning"); --check enforces
# all-or-none, a known knob name, and monotone ts within a stream (one
# controller = one replica = one ordered decision trail; out-of-order
# ts means two controllers wrote one file)
AUTOTUNE_KEYS = (
    "knob",
    "old",
    "new",
    "reason",
    "slo_p99_ms",
    "total_p99_ms",
    "queue_wait_p99_ms",
    "device_p99_ms",
    "batch_fill",
)
# the only knobs the controller steers (autotune.AUTOTUNE_KNOBS is the
# writer's copy) — an unknown name means a forged or drifted record
AUTOTUNE_KNOB_NAMES = ("window_ms", "rung")
# the key set every kind="pipeline" window record carries (telemetry
# .pipeline_fields + the trainer's step stamp —
# docs/OBSERVABILITY.md "Input-pipeline attribution"); --check enforces
# all-or-none, a positive wall, and the CONCURRENCY invariant: the
# producer (prefetch thread) and consumer (fit loop) stage groups each
# sum to at most the window wall — never the two groups combined, they
# overlap by design
PIPELINE_KEYS = (
    "wall_s",
    "read_s",
    "parse_s",
    "hash_s",
    "batch_s",
    "pad_s",
    "cache_read_s",
    "plan_s",
    "producer_wait_s",
    "queue_wait_s",
    "transfer_s",
    "dispatch_s",
    "device_s",
    "batches",
    "rows",
    "queue_depth",
    "queue_cap",
)
# pipeline keys added after runs were already archived (the round-12
# packed-shard-cache stage): absence means a pre-upgrade writer, not a
# schema violation — present they join the all-or-none gate and the
# producer sum below (the OPTIONAL_SERVE_KEYS convention)
OPTIONAL_PIPELINE_KEYS = ("cache_read_s",)
PIPELINE_PRODUCER_SUM = (
    "read_s", "parse_s", "hash_s", "batch_s", "pad_s", "cache_read_s",
    "plan_s", "producer_wait_s",
)
PIPELINE_CONSUMER_SUM = ("queue_wait_s", "transfer_s", "dispatch_s", "device_s")
# slack on the per-thread sum gate: stage accumulations batch on the
# producer side (a few hundred lines per flush), so a window boundary
# can carry a sliver of the previous window's time
PIPELINE_SUM_SLACK = 1.25
# the key set every kind="span" record carries (xflow_tpu/tracing.py —
# docs/OBSERVABILITY.md "Request tracing"); `parent` is optional (the
# root has none), everything else is the assembly contract
# tools/request_trace.py depends on
SPAN_KEYS = ("trace", "span", "name", "t0", "dur_ms")
# the key set every kind="ingest" record carries (data/pipeline
# .TailFollower.segments — docs/OBSERVABILITY.md "Freshness tracing"):
# one record per sealed streaming segment, `trace` is the ingest trace
# id the publish/reload/serve_first spans later link to; --check
# enforces all-or-none, non-negative finite rows/bytes/offset, and a
# strictly increasing seq per stream (the follower numbers segments
# 0, 1, 2, ... — a repeat or regression means two followers wrote one
# stream)
INGEST_KEYS = (
    "trace",
    "seq",
    "source",
    "offset",
    "rows",
    "bytes",
    "cache",
    "ingest_ts",
)
# the key set every kind="publish" record carries (train/trainer
# ._publish_checkpoint): one per in-run committed publication
# (train.publish_every), stamped with the newest contributing ingest
# trace; --check enforces all-or-none, monotone seq, and
# published_ts >= ingest_ts (a publication cannot predate the data it
# trained on). `step` rides the generic step-monotonicity gate.
PUBLISH_KEYS = ("step", "seq", "trace", "ingest_ts", "published_ts")
# the key set every kind="sync" record carries (parallel/multislice
# .SliceSyncer.sync — docs/OBSERVABILITY.md "Multi-slice sync
# records"); --check enforces all-or-none, a strictly increasing round
# per stream (each sync bumps by one; a rejoin generation is its own
# stream), the membership ledger (this round's live set must equal the
# previous round's minus `left` plus `joined` — a silent membership
# jump means a sync record was lost or forged), and the staleness
# arithmetic (stale = live peers lagging > k; lag_max = max lag)
SYNC_KEYS = (
    "round",
    "k",
    "mode",
    "live",
    "joined",
    "left",
    "bytes_out",
    "bytes_in",
    "applied",
    "stale",
    "timeouts",
    "lag_max",
    "lags",
    "dur_ms",
)
SYNC_MODES = ("sync", "bounded", "async")
# the key set every kind="ckpt" record carries (train/checkpoint
# .AsyncCheckpointWriter._record — docs/OBSERVABILITY.md "Checkpoint
# records"): one per async save outcome per tier (train.ckpt_async).
# --check enforces all-or-none, the tier/event vocabularies, finite
# non-negative timings, committed_ts >= queued_ts, a non-decreasing
# skip counter, and the at-most-one-in-flight contract: per stream and
# tier, a committed save's queued_ts must not precede the previous
# committed save's committed_ts (overlapping queued→committed intervals
# mean two writers raced one checkpoint dir)
CKPT_KEYS = (
    "step",
    "tier",
    "event",
    "queued_ts",
    "committed_ts",
    "queue_ms",
    "write_ms",
    "bytes",
    "skips",
    "degraded",
)
CKPT_TIERS = ("primary", "replica")
CKPT_EVENTS = ("committed", "skipped", "failed")
# request-path span names come from xflow_tpu.tracing (the source of
# truth): the cross-stream parenting gates below apply to those;
# operational spans — reload/checkpoint_save/… — are one-span traces
# and exempt


def expand_paths(paths: list[str]) -> list[str]:
    """Files stay files; directories expand to their sorted *.jsonl."""
    out: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            found = sorted(glob.glob(os.path.join(p, "*.jsonl")))
            if not found:
                raise FileNotFoundError(f"{p!r}: directory holds no *.jsonl files")
            out.extend(found)
        elif not os.path.exists(p):
            # caught in main(): a clean message + exit 2, not a traceback
            raise FileNotFoundError(f"{p!r}: no such file")
        else:
            out.append(p)
    return out


def _gen_of(rec: dict) -> int:
    """Restart generation of a record (0 for pre-elastic streams and
    for damaged values — a string or NaN gen must group, not raise)."""
    g = rec.get("gen", 0)
    try:
        return int(g) if isinstance(g, (int, float)) else 0
    except (ValueError, OverflowError):  # NaN/inf floats
        return 0


def load_streams(files: list[str]) -> tuple[dict, int]:
    """{(run_id, rank, kind, gen): [records in file order]} across all
    files, plus the total damaged-line count. `kind` defaults to
    "metrics" for unstamped legacy streams; heartbeat/watchdog records
    stamp theirs. `gen` is the restart generation (elastic recovery):
    a supervised auto-restart relaunches the job under the SAME run_id
    with step counters back at 0, so every per-stream gate (step
    monotonicity above all) keys on the generation — one launch's
    restarts segment instead of reading as corruption."""
    streams: dict = {}
    skipped_total = 0
    for path in files:
        records, skipped = read_jsonl_counted(path)
        skipped_total += skipped
        for rec in records:
            key = (
                str(rec.get("run_id", "?")),
                rec.get("rank", "?"),
                str(rec.get("kind", "metrics")),
                _gen_of(rec),
            )
            streams.setdefault(key, []).append(rec)
    return streams, skipped_total


def metrics_streams(streams: dict) -> dict:
    """The (run_id, rank, gen) -> records subset holding trainer metrics."""
    return {
        (rid, rank, gen): recs
        for (rid, rank, kind, gen), recs in streams.items()
        if kind == "metrics"
    }


def serve_streams(streams: dict) -> dict:
    """The (run_id, rank, gen) -> records subset holding serving
    telemetry (kind="serve": QPS/latency windows + reload events)."""
    return {
        (rid, rank, gen): recs
        for (rid, rank, kind, gen), recs in streams.items()
        if kind == "serve"
    }


def compile_records(streams: dict, run_id: str = "") -> list[dict]:
    """Every kind="compile" record (optionally one run's), in file
    order — the CompileRecorder's per-program compile accounting."""
    out = []
    for (rid, _rank, kind, _gen), recs in sorted(streams.items(), key=str):
        if kind == "compile" and (not run_id or rid == run_id):
            out.extend(recs)
    return out


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def summarize_stream(records: list[dict]) -> dict:
    """One summary row for a (run_id, rank) metrics stream."""
    steps_recs = [r for r in records if "step" in r and "loss" in r]
    windows = [r for r in records if "rows_per_s" in r]
    counters = [r["counters"] for r in records if isinstance(r.get("counters"), dict)]
    final = next((r for r in records if r.get("final")), None)

    steps = max(
        [r["step"] for r in steps_recs if _finite(r.get("step"))]
        + ([final["steps"]] if final and _finite(final.get("steps")) else [0])
        or [0]
    )
    examples = max(
        (r["examples"] for r in records if _finite(r.get("examples"))), default=0
    )
    elapsed = max(
        (r["elapsed_s"] for r in records if _finite(r.get("elapsed_s"))), default=0.0
    )
    losses = [r["loss"] for r in steps_recs if _finite(r.get("loss"))]
    p50s = [r["step_time_p50_ms"] for r in windows if _finite(r.get("step_time_p50_ms"))]
    p99s = [r["step_time_p99_ms"] for r in windows if _finite(r.get("step_time_p99_ms"))]
    waits = [r["data_wait_ms"] for r in windows if _finite(r.get("data_wait_ms"))]
    rates = [r["rows_per_s"] for r in windows if _finite(r.get("rows_per_s"))]
    evals = [r["eval_auc"] for r in records if _finite(r.get("eval_auc"))]
    bad_steps = max(
        (r["bad_steps"] for r in records if _finite(r.get("bad_steps"))), default=0
    )
    bad_rows = max((c.get("data.bad_rows", 0) for c in counters), default=0)

    def series(key):
        return [r[key] for r in records if _finite(r.get(key))]

    grads = series("grad_norm")
    grad_maxes = series("grad_norm_max")
    emas = series("loss_ema")
    occs = series("table_occupancy")
    colls = series("est_collision_rate")

    med = lambda xs: sorted(xs)[len(xs) // 2] if xs else float("nan")
    last = lambda xs: xs[-1] if xs else float("nan")
    return {
        "steps": int(steps),
        "examples": int(examples),
        "elapsed_s": float(elapsed),
        "examples_per_s": examples / elapsed if elapsed > 0 else float("nan"),
        "rows_per_s": med(rates),
        "p50_ms": med(p50s),
        "p99_ms": max(p99s) if p99s else float("nan"),
        "data_wait_ms": sum(waits) / len(waits) if waits else float("nan"),
        "last_loss": losses[-1] if losses else float("nan"),
        "bad_steps": int(bad_steps),
        "bad_rows": int(bad_rows),
        "eval_auc": last(evals),
        "windows": len(windows),
        # health trajectory (docs/OBSERVABILITY.md "Health metrics")
        "grad_norm_first": grads[0] if grads else float("nan"),
        "grad_norm_last": last(grads),
        "grad_norm_max": max(grad_maxes) if grad_maxes else float("nan"),
        "update_norm_last": last(series("update_norm")),
        "param_norm_last": last(series("param_norm")),
        "loss_ema_last": last(emas),
        "occupancy_last": last(occs),
        "est_collision_rate_last": last(colls),
        "auc_trajectory": evals,
    }


def summarize_serve_stream(records: list[dict]) -> dict:
    """One summary row for a (run_id, rank) kind="serve" stream:
    traffic totals over the window records, latency aggregated across
    windows (p50 = median of window p50s, p99 = max of window p99s —
    conservative for a tail), the reload-event count, and the
    generation trail."""
    windows = [r for r in records if "qps" in r]
    total_rows = sum(r.get("rows", 0) for r in windows if _finite(r.get("rows")))
    total_reqs = sum(
        r.get("requests", 0) for r in windows if _finite(r.get("requests"))
    )
    total_s = sum(
        r.get("window_s", 0.0) for r in windows if _finite(r.get("window_s"))
    )
    p50s = [r["total_p50_ms"] for r in windows if _finite(r.get("total_p50_ms"))]
    p99s = [r["total_p99_ms"] for r in windows if _finite(r.get("total_p99_ms"))]
    fills = [
        (r["batch_fill"], r["batches"])
        for r in windows
        if _finite(r.get("batch_fill")) and _finite(r.get("batches"))
    ]
    fill_w = sum(n for _, n in fills)
    gens = []
    for r in records:
        g = r.get("generation")
        if _finite(g) and (not gens or gens[-1] != g):
            gens.append(g)
    med = lambda xs: sorted(xs)[len(xs) // 2] if xs else float("nan")
    return {
        "windows": len(windows),
        "requests": int(total_reqs),
        "rows": int(total_rows),
        "window_seconds": float(total_s),
        "qps": total_reqs / total_s if total_s > 0 else float("nan"),
        "rows_per_s": total_rows / total_s if total_s > 0 else float("nan"),
        "p50_ms": med(p50s),
        "p99_ms": max(p99s) if p99s else float("nan"),
        "batch_fill": (
            sum(f * n for f, n in fills) / fill_w if fill_w else float("nan")
        ),
        "bad_requests": int(
            sum(r.get("bad_requests", 0) for r in windows
                if _finite(r.get("bad_requests")))
        ),
        "shed_requests": int(
            sum(r.get("shed_requests", 0) for r in windows
                if _finite(r.get("shed_requests")))
        ),
        "replica": next(
            (r["replica"] for r in records if _finite(r.get("replica"))),
            None,
        ),
        "reloads": sum(1 for r in records if r.get("event") == "reload"),
        "reload_failures": sum(
            1 for r in records if r.get("event") == "reload_failed"
        ),
        "generations": gens,
        "last_step": next(
            (r["step"] for r in reversed(records) if _finite(r.get("step"))),
            -1,
        ),
    }


def check_fleet_identity(streams: dict) -> list[str]:
    """Serving-fleet identity gates (docs/SERVING.md "Fleet"), active
    only where records carry a `replica` stamp (solo serving is
    untouched):

    - one stream = one replica: a (run_id, rank, gen) serve stream
      mixing two replica stamps means two processes appended to one
      file — exactly the interleaving the per-replica layout exists to
      prevent;
    - distinct replicas stay distinct: two streams sharing (run_id,
      rank) but stamping different replicas collide — the fleet failed
      to give them distinct rank identities and their metrics would
      merge in every per-rank view;
    - per-replica restart generations are monotone in time: replica
      k's `gen` stamps, ordered by ts, never go backwards (a
      regression means a stale pre-restart process kept writing after
      its supersessor came up — two live processes on one identity).
    """
    problems: list[str] = []
    # (run_id, rank) -> {replica stamps seen}, and per-(run_id, replica)
    # the (ts, gen) trail. Span and autotune streams ride the same
    # identity gates: "no span crosses replica stamps" is this
    # one-stream-one-replica rule applied to kind="span", and an
    # autotune decision trail mixing replicas means two controllers
    # steered one coalescer's record file.
    rank_replicas: dict = {}
    gen_trail: dict = {}
    for (run_id, rank, kind, gen), records in sorted(streams.items(), key=str):
        if kind not in ("serve", "span", "autotune"):
            continue
        reps = {
            r["replica"] for r in records
            if isinstance(r.get("replica"), int)
        }
        if not reps:
            continue
        if len(reps) > 1:
            problems.append(
                f"run {run_id} rank {rank} [{kind}] gen {gen}: one stream "
                f"mixes replica stamps {sorted(reps)}"
            )
        rank_replicas.setdefault((run_id, rank), set()).update(reps)
        for r in records:
            rep = r.get("replica")
            if isinstance(rep, int) and _finite(r.get("ts")):
                gen_trail.setdefault((run_id, rep), []).append(
                    (r["ts"], gen)
                )
    for (run_id, rank), reps in sorted(rank_replicas.items(), key=str):
        if len(reps) > 1:
            problems.append(
                f"run {run_id} rank {rank}: distinct replicas "
                f"{sorted(reps)} collide on one rank stamp — their serve "
                "streams would merge in every per-rank view"
            )
    for (run_id, rep), trail in sorted(gen_trail.items(), key=str):
        trail.sort(key=lambda tg: tg[0])
        last = -1
        for ts, g in trail:
            if g < last:
                problems.append(
                    f"run {run_id} replica {rep}: restart generation went "
                    f"backwards ({last} -> {g}) — a stale pre-restart "
                    "process is still writing"
                )
                break
            last = g
    return problems


def check_spans(streams: dict) -> list[str]:
    """Request-tracing gates (docs/OBSERVABILITY.md "Request tracing"),
    active only where kind="span" records exist (untraced runs are
    untouched). Cross-STREAM by design: one request's spans live in the
    router's file and 1-2 replicas' files, and the whole point of the
    trace id is that they join back up.

    - every sampled request parents to ONE root: a trace holding two
      parentless request-path spans is a split tree (two processes both
      thought they were the request's origin — id reuse or a broken
      parent header). A trace with NO parentless span is a partial
      capture (one hop force-emitted while the origin's verdict said
      drop) — tolerated, request_trace.py reports it as incomplete;
    - device-batch spans are referenced by >= 1 request span: an
      unreferenced batch span can never be reached from any request
      tree — the batch-membership link broke (the dedup emitted the
      batch but dropped every member's device span);
    - "no span crosses replica stamps" rides check_fleet_identity
      (span streams obey the same one-stream-one-replica rule).
    """
    problems: list[str] = []
    # run_id -> {trace: [parentless request spans]}, and the batch-link
    # reference sets
    roots: dict = {}
    batch_ids: dict = {}
    batch_refs: dict = {}
    for (run_id, _rank, kind, _gen), records in sorted(streams.items(), key=str):
        if kind != "span":
            continue
        for rec in records:
            name = rec.get("name")
            trace = rec.get("trace")
            if name == BATCH_SPAN_NAME and "span" in rec:
                batch_ids.setdefault(run_id, {})[rec["span"]] = trace
                continue
            if name not in REQUEST_SPAN_NAMES:
                continue  # operational spans: one-span traces, exempt
            if "batch" in rec:
                batch_refs.setdefault(run_id, set()).add(rec["batch"])
            if not rec.get("parent"):
                roots.setdefault(run_id, {}).setdefault(trace, []).append(rec)
    for run_id, traces in sorted(roots.items(), key=str):
        for trace, rs in sorted(traces.items(), key=str):
            if len(rs) > 1:
                problems.append(
                    f"run {run_id} trace {trace}: {len(rs)} parentless "
                    f"request spans ({[r.get('name') for r in rs]}) — a "
                    "sampled request's spans must parent to one root"
                )
    for run_id, ids in sorted(batch_ids.items(), key=str):
        refs = batch_refs.get(run_id, set())
        for bid, trace in sorted(ids.items(), key=str):
            if bid not in refs:
                problems.append(
                    f"run {run_id} trace {trace}: device_batch span {bid} "
                    "is referenced by no request span — the "
                    "batch-membership link broke"
                )
    return problems


def check_streams(streams: dict, files: list[str]) -> list[str]:
    """Schema violations ([] = clean). The contract checked here is the
    one docs/OBSERVABILITY.md documents — keep the three in sync.

    Topology elasticity: the rank SET may legitimately change across
    restart generations (--allow-shrink relaunches a degraded world
    under the same run_id), so nothing here requires generation k+1 to
    carry generation k's ranks. What IS enforced: within one (run_id,
    generation), every `world` stamp agrees, and no training rank's id
    is >= its generation's world size (the launcher's watchdog stream
    stamps rank -1 and is exempt)."""
    problems: list[str] = []
    if not streams:
        problems.append(f"no records in {', '.join(files)}")
    # (run_id, gen) -> set of world stamps seen (rank-set/world gate)
    worlds: dict = {}
    for (run_id, rank, kind, gen), records in sorted(streams.items(), key=str):
        rank_flagged = False  # one problem per stream, but keep
        # collecting its world stamps — the intra-generation
        # disagreement below is the more diagnostic signal
        for rec in records:
            w = rec.get("world")
            if isinstance(w, int) and w > 0:
                # multi-slice runs stamp `slice`: the rank is the
                # slice's id in the SYNC GROUP while `world` is the
                # slice's own (ICI) world size — two different
                # topologies, so the rank<world gate keys per slice
                sl = rec.get("slice")
                worlds.setdefault((run_id, gen, sl), set()).add(w)
                if (
                    not rank_flagged
                    and sl is None
                    and isinstance(rank, int)
                    and rank >= w
                ):
                    rank_flagged = True
                    problems.append(
                        f"run {run_id} rank {rank} [{kind}] gen {gen}: "
                        f"rank id >= its generation's world size {w}"
                    )
    for (run_id, gen, sl), seen in sorted(worlds.items(), key=str):
        if len(seen) > 1:
            where = f"gen {gen}" + (f" slice {sl}" if sl is not None else "")
            problems.append(
                f"run {run_id} {where}: world stamp disagrees across "
                f"streams ({sorted(seen)}) — ranks of one generation "
                "launched with different world sizes"
            )
    problems.extend(check_fleet_identity(streams))
    problems.extend(check_spans(streams))
    for (run_id, rank, kind, gen), records in sorted(streams.items(), key=str):
        tag = f"run {run_id} rank {rank} [{kind}]" + (
            f" gen {gen}" if gen else ""
        )
        last_step = -1
        step_recs = 0
        window_recs = 0
        last_model_gen = -1  # serve streams: the model generation a
        # record answered with must never regress (hot reload only
        # moves forward; a regression means a swap raced or went back)
        seen_programs: dict = {}  # compile streams: (program, sig) ->
        # record index — the exactly-once recompile gate
        last_round = 0  # sync streams: rounds count 1, 2, 3, ... within
        # a generation — a repeat or skip means a lost or forged record
        prev_live = None  # sync streams: membership ledger
        last_at_ts = float("-inf")  # autotune streams: decision trail
        # stays time-ordered (one controller per stream)
        last_ingest_seq = -1  # ingest streams: the follower's segment
        # counter only moves forward within a stream
        last_pub_seq = -1  # publish streams: publication counter ditto
        last_ckpt_end: dict = {}  # ckpt streams: tier -> committed_ts of
        # the last COMMITTED save — the at-most-one-in-flight gate
        last_ckpt_skips = -1  # ckpt streams: skip counter only grows
        for i, rec in enumerate(records, 1):
            for key in STAMP_KEYS:
                if key not in rec:
                    problems.append(f"{tag}: record {i} lacks {key!r}")
            if not _finite(rec.get("ts", 0.0)):
                problems.append(f"{tag}: record {i} has non-numeric ts")
            if "step" in rec and kind != "ckpt":
                # ckpt streams are exempt: the fit thread's skip
                # records interleave with the writer thread's commit
                # records (a step-10 skip can land before step 5's
                # replica commit), so their ordering contract is the
                # per-tier queued→committed interval gate below instead
                step_recs += 1
                if _finite(rec["step"]):
                    if rec["step"] < last_step:
                        problems.append(
                            f"{tag}: step went backwards "
                            f"({last_step} -> {rec['step']}) at record {i}"
                        )
                    last_step = max(last_step, rec["step"])
            # the StepTimer window contract is the TRAINER stream's
            # ("rows_per_s" also lives in serve windows, which have
            # their own key set below)
            present = [k for k in WINDOW_KEYS if k in rec] if kind == "metrics" else []
            if present:
                window_recs += 1
                missing = [k for k in WINDOW_KEYS if k not in rec]
                if missing:
                    problems.append(
                        f"{tag}: record {i} has window keys {present} but "
                        f"lacks {missing}"
                    )
            for group, want in (
                ("host", (HOST_KEYS,)),
                ("boundary", (BOUNDARY_OPEN_KEYS, BOUNDARY_OPEN_KEYS + BOUNDARY_TAIL_KEYS)),
            ):
                if group not in rec:
                    continue
                got = rec[group]
                if not isinstance(got, dict) or not any(
                    set(got) - BOUNDARY_FLAGS - HOST_OPTIONAL_KEYS == set(keys)
                    for keys in want
                ):
                    problems.append(
                        f"{tag}: record {i} has a {group} that is not one "
                        f"of the key sets {[list(k) for k in want]}"
                    )
                elif not all(_finite(v) and v >= 0 for v in got.values()):
                    problems.append(
                        f"{tag}: record {i} has a non-numeric or negative "
                        f"{group} value"
                    )
            # health fields are all-or-none per record (null allowed for
            # a not-yet-available value, absence is the violation)
            h_present = [k for k in HEALTH_KEYS if k in rec]
            if h_present:
                h_missing = [k for k in HEALTH_KEYS if k not in rec]
                if h_missing:
                    problems.append(
                        f"{tag}: record {i} has health keys {h_present} "
                        f"but lacks {h_missing}"
                    )
            # an eval record carries BOTH quality numbers
            if ("eval_auc" in rec) != ("eval_logloss" in rec):
                problems.append(
                    f"{tag}: record {i} has one of eval_auc/eval_logloss "
                    "without the other"
                )
            if kind == "heartbeat" and "step" not in rec and "event" not in rec:
                problems.append(
                    f"{tag}: record {i} is neither a step heartbeat nor "
                    "an event"
                )
            if kind == "compile":
                c_missing = [k for k in COMPILE_KEYS if k not in rec]
                if c_missing:
                    problems.append(
                        f"{tag}: record {i} lacks compile keys {c_missing}"
                    )
                    continue
                if not _finite(rec["compile_time_s"]) or rec["compile_time_s"] <= 0:
                    problems.append(
                        f"{tag}: record {i} ({rec['program']!r}) has "
                        "non-positive compile_time_s"
                    )
                split = [rec.get(k) for k in COMPILE_SPLIT_KEYS if k in rec]
                if split and (
                    len(split) != len(COMPILE_SPLIT_KEYS)
                    or not all(_finite(v) and v >= 0 for v in split)
                    or abs(sum(split) - rec["compile_time_s"]) > 1e-5
                ):
                    problems.append(
                        f"{tag}: record {i} ({rec['program']!r}) has "
                        f"{list(COMPILE_SPLIT_KEYS)} that do not add up to "
                        "compile_time_s"
                    )
                prog_key = (rec["program"], rec["sig"])
                if prog_key in seen_programs:
                    problems.append(
                        f"{tag}: program {rec['program']!r} sig "
                        f"{rec['sig']} compiled twice (records "
                        f"{seen_programs[prog_key]} and {i}) — each "
                        "program compiles exactly once per run"
                    )
                else:
                    seen_programs[prog_key] = i
            if kind == "pipeline":
                pl_missing = [
                    k for k in PIPELINE_KEYS
                    if k not in rec and k not in OPTIONAL_PIPELINE_KEYS
                ]
                if pl_missing:
                    problems.append(
                        f"{tag}: record {i} lacks pipeline keys {pl_missing}"
                    )
                elif not _finite(rec["wall_s"]) or rec["wall_s"] <= 0:
                    problems.append(
                        f"{tag}: record {i} has non-positive wall_s"
                    )
                else:
                    wall = rec["wall_s"]
                    for side, keys in (
                        ("producer", PIPELINE_PRODUCER_SUM),
                        ("consumer", PIPELINE_CONSUMER_SUM),
                    ):
                        vals = [rec[k] for k in keys if k in rec]
                        if not all(_finite(v) and v >= 0 for v in vals):
                            problems.append(
                                f"{tag}: record {i} has a non-numeric or "
                                f"negative {side} stage time"
                            )
                            continue
                        ssum = sum(vals)
                        if ssum > wall * PIPELINE_SUM_SLACK + 0.05:
                            problems.append(
                                f"{tag}: record {i} {side}-side stage times "
                                f"sum {ssum:.3f}s > window wall "
                                f"{wall:.3f}s — one thread cannot spend "
                                "more than the wall"
                            )
            if kind == "span":
                sp_missing = [k for k in SPAN_KEYS if k not in rec]
                if sp_missing:
                    problems.append(
                        f"{tag}: record {i} lacks span keys {sp_missing}"
                    )
                elif not (_finite(rec["t0"]) and _finite(rec["dur_ms"])
                          and rec["dur_ms"] >= 0):
                    problems.append(
                        f"{tag}: record {i} ({rec.get('name')!r}) has "
                        "non-numeric t0 or negative dur_ms"
                    )
            if kind == "serve":
                s_present = [k for k in SERVE_KEYS if k in rec]
                if "event" in rec:
                    if not isinstance(rec["event"], str):
                        problems.append(
                            f"{tag}: record {i} has a non-string event"
                        )
                elif s_present:
                    s_missing = [
                        k for k in SERVE_KEYS
                        if k not in rec and k not in OPTIONAL_SERVE_KEYS
                    ]
                    if s_missing:
                        problems.append(
                            f"{tag}: record {i} has serve keys "
                            f"{s_present[:3]}... but lacks {s_missing}"
                        )
                else:
                    problems.append(
                        f"{tag}: record {i} is neither a serve window "
                        "nor an event"
                    )
                mg = rec.get("generation")
                if _finite(mg):
                    if mg < last_model_gen:
                        problems.append(
                            f"{tag}: model generation went backwards "
                            f"({last_model_gen} -> {mg}) at record {i}"
                        )
                    last_model_gen = max(last_model_gen, mg)
                fresh = rec.get("data_freshness_s")
                if fresh is not None and (not _finite(fresh) or fresh < 0):
                    problems.append(
                        f"{tag}: record {i} has non-numeric or negative "
                        "data_freshness_s"
                    )
            if kind == "ingest":
                in_missing = [k for k in INGEST_KEYS if k not in rec]
                if in_missing:
                    problems.append(
                        f"{tag}: record {i} lacks ingest keys {in_missing}"
                    )
                    continue
                for key in ("offset", "rows", "bytes"):
                    if not _finite(rec[key]) or rec[key] < 0:
                        problems.append(
                            f"{tag}: record {i} has non-numeric or "
                            f"negative {key}"
                        )
                if not isinstance(rec["trace"], str) or not rec["trace"]:
                    problems.append(
                        f"{tag}: record {i} has an empty ingest trace id"
                    )
                if not _finite(rec["ingest_ts"]):
                    problems.append(
                        f"{tag}: record {i} has non-numeric ingest_ts"
                    )
                sq = rec["seq"]
                if not _finite(sq) or sq <= last_ingest_seq:
                    problems.append(
                        f"{tag}: ingest seq {last_ingest_seq} -> {sq} at "
                        f"record {i} — segment numbering must strictly "
                        "increase (two followers wrote one stream?)"
                    )
                if _finite(sq):
                    last_ingest_seq = max(last_ingest_seq, int(sq))
            if kind == "publish":
                pb_missing = [k for k in PUBLISH_KEYS if k not in rec]
                if pb_missing:
                    problems.append(
                        f"{tag}: record {i} lacks publish keys {pb_missing}"
                    )
                    continue
                if not isinstance(rec["trace"], str) or not rec["trace"]:
                    problems.append(
                        f"{tag}: record {i} has an empty publication "
                        "trace id"
                    )
                if not (_finite(rec["ingest_ts"]) and _finite(rec["published_ts"])):
                    problems.append(
                        f"{tag}: record {i} has non-numeric "
                        "ingest_ts/published_ts"
                    )
                elif rec["published_ts"] < rec["ingest_ts"]:
                    problems.append(
                        f"{tag}: record {i} published_ts "
                        f"{rec['published_ts']} < ingest_ts "
                        f"{rec['ingest_ts']} — a publication cannot "
                        "predate the data it trained on"
                    )
                sq = rec["seq"]
                if not _finite(sq) or sq <= last_pub_seq:
                    problems.append(
                        f"{tag}: publish seq {last_pub_seq} -> {sq} at "
                        f"record {i} — publication numbering must "
                        "strictly increase"
                    )
                if _finite(sq):
                    last_pub_seq = max(last_pub_seq, int(sq))
            if kind == "ckpt":
                ck_missing = [k for k in CKPT_KEYS if k not in rec]
                if ck_missing:
                    problems.append(
                        f"{tag}: record {i} lacks ckpt keys {ck_missing}"
                    )
                    continue
                if rec["tier"] not in CKPT_TIERS:
                    problems.append(
                        f"{tag}: record {i} has unknown ckpt tier "
                        f"{rec['tier']!r} (known: {', '.join(CKPT_TIERS)})"
                    )
                    continue
                if rec["event"] not in CKPT_EVENTS:
                    problems.append(
                        f"{tag}: record {i} has unknown ckpt event "
                        f"{rec['event']!r} (known: {', '.join(CKPT_EVENTS)})"
                    )
                    continue
                bad_num = [
                    k for k in ("queued_ts", "committed_ts", "queue_ms",
                                "write_ms", "bytes", "skips")
                    if not _finite(rec[k]) or rec[k] < 0
                ]
                if bad_num:
                    problems.append(
                        f"{tag}: record {i} has non-numeric or negative "
                        f"{bad_num}"
                    )
                    continue
                if not isinstance(rec["degraded"], bool):
                    problems.append(
                        f"{tag}: record {i} has a non-boolean degraded flag"
                    )
                if rec["committed_ts"] < rec["queued_ts"]:
                    problems.append(
                        f"{tag}: record {i} committed_ts "
                        f"{rec['committed_ts']} < queued_ts "
                        f"{rec['queued_ts']} — a save cannot commit "
                        "before it was queued"
                    )
                if rec["skips"] < last_ckpt_skips:
                    problems.append(
                        f"{tag}: skip counter went backwards "
                        f"({last_ckpt_skips} -> {rec['skips']}) at "
                        f"record {i}"
                    )
                last_ckpt_skips = max(last_ckpt_skips, int(rec["skips"]))
                if rec["event"] == "committed":
                    # at most one save in flight: this save's queued
                    # instant must not precede the previous committed
                    # save's commit instant on the same tier (the
                    # replica interval shares the job's queued_ts with
                    # its primary, so the gate keys per tier)
                    prev_end = last_ckpt_end.get(rec["tier"])
                    if prev_end is not None and rec["queued_ts"] < prev_end:
                        problems.append(
                            f"{tag}: record {i} ({rec['tier']} step "
                            f"{rec['step']}) queued at {rec['queued_ts']} "
                            f"before the previous save committed at "
                            f"{prev_end} — two saves in flight"
                        )
                    last_ckpt_end[rec["tier"]] = rec["committed_ts"]
            if kind == "autotune":
                a_present = [k for k in AUTOTUNE_KEYS if k in rec]
                a_missing = [k for k in AUTOTUNE_KEYS if k not in rec]
                if a_missing:
                    problems.append(
                        f"{tag}: record {i} has autotune keys "
                        f"{a_present[:3]}... but lacks {a_missing}"
                    )
                    continue
                if rec["knob"] not in AUTOTUNE_KNOB_NAMES:
                    problems.append(
                        f"{tag}: record {i} steers unknown knob "
                        f"{rec['knob']!r} (known: "
                        f"{', '.join(AUTOTUNE_KNOB_NAMES)})"
                    )
                if not (_finite(rec["old"]) and _finite(rec["new"])):
                    problems.append(
                        f"{tag}: record {i} has non-numeric old/new "
                        "knob values"
                    )
                ts = rec.get("ts")
                if _finite(ts):
                    if ts < last_at_ts:
                        problems.append(
                            f"{tag}: decision ts went backwards "
                            f"({last_at_ts} -> {ts}) at record {i} — "
                            "two controllers wrote one stream?"
                        )
                    last_at_ts = max(last_at_ts, ts)
            if kind == "sync":
                sy_missing = [k for k in SYNC_KEYS if k not in rec]
                if sy_missing:
                    problems.append(
                        f"{tag}: record {i} lacks sync keys {sy_missing}"
                    )
                    continue
                if rec["mode"] not in SYNC_MODES:
                    problems.append(
                        f"{tag}: record {i} has unknown sync mode "
                        f"{rec['mode']!r}"
                    )
                if rec["mode"] == "sync" and rec["k"] != 0:
                    problems.append(
                        f"{tag}: record {i} stamps mode=sync with "
                        f"k={rec['k']} — lockstep mode is k=0 by definition"
                    )
                rnd = rec["round"]
                # a stream's FIRST round may start anywhere >= 1: a
                # rejoined generation continues the slice's numbering
                # past its snapshot catch-up point. After that, +1 each
                # record — a repeat or skip means a lost or forged one.
                bad_first = last_round == 0 and (not _finite(rnd) or rnd < 1)
                bad_next = last_round > 0 and (
                    not _finite(rnd) or rnd != last_round + 1
                )
                if bad_first or bad_next:
                    problems.append(
                        f"{tag}: round {last_round} -> {rnd} at record "
                        f"{i} — rounds increment by one within a "
                        "generation (a repeat or skip means a lost or "
                        "forged sync record)"
                    )
                if _finite(rnd):
                    last_round = max(last_round, int(rnd))
                live, joined, left = rec["live"], rec["joined"], rec["left"]
                if not all(isinstance(v, list) for v in (live, joined, left)):
                    problems.append(
                        f"{tag}: record {i} live/joined/left are not lists"
                    )
                else:
                    if prev_live is not None and set(live) != (
                        (prev_live - set(left)) | set(joined)
                    ):
                        problems.append(
                            f"{tag}: record {i} membership ledger broken: "
                            f"live {sorted(prev_live)} - left {left} + "
                            f"joined {joined} != live {live}"
                        )
                    prev_live = set(live)
                lags = rec["lags"]
                if not isinstance(lags, dict) or not all(
                    _finite(v) and v >= 0 for v in lags.values()
                ):
                    problems.append(
                        f"{tag}: record {i} lags is not a dict of "
                        "non-negative rounds-behind counts"
                    )
                else:
                    want_max = max(lags.values(), default=0)
                    want_stale = sum(
                        1 for v in lags.values() if _finite(rec["k"]) and v > rec["k"]
                    )
                    if rec["lag_max"] != want_max:
                        problems.append(
                            f"{tag}: record {i} lag_max {rec['lag_max']} != "
                            f"max(lags) {want_max}"
                        )
                    if rec["stale"] != want_stale:
                        problems.append(
                            f"{tag}: record {i} stale {rec['stale']} != "
                            f"count of lags > k ({want_stale})"
                        )
                for key in ("bytes_out", "bytes_in", "applied", "timeouts",
                            "dur_ms"):
                    if not _finite(rec[key]) or rec[key] < 0:
                        problems.append(
                            f"{tag}: record {i} has non-numeric or "
                            f"negative {key}"
                        )
        if kind == "metrics" and step_recs >= 2 and window_recs == 0:
            problems.append(
                f"{tag}: {step_recs} step records but no window record — "
                "StepTimer stats never landed"
            )
    return problems


def render_table(rows: list[tuple]) -> str:
    header = (
        "run_id", "rank", "gen", "steps", "examples", "elapsed_s", "ex/s",
        "rows/s", "p50_ms", "p99_ms", "wait_ms", "loss", "bad_steps",
        "bad_rows", "auc",
    )

    def fmt(v) -> str:
        if isinstance(v, float):
            if not math.isfinite(v):
                return "-"
            return f"{v:.4g}" if abs(v) < 1000 else f"{v:,.0f}"
        return str(v)

    cells = [header] + [tuple(fmt(c) for c in row) for row in rows]
    widths = [max(len(r[i]) for r in cells) for i in range(len(header))]
    lines = []
    for i, row in enumerate(cells):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def _newest_run(streams: dict) -> str:
    """run_id whose records carry the largest ts."""
    def run_ts(run_id: str) -> float:
        return max(
            (r.get("ts", 0.0) for (rid, _, _, _), recs in streams.items()
             if rid == run_id for r in recs if _finite(r.get("ts"))),
            default=0.0,
        )

    run_ids = {rid for rid, _, _, _ in streams}
    return max(run_ids, key=run_ts) if run_ids else "?"


def bench_record(streams: dict) -> dict:
    """BENCH-style perf record over the newest run: per GENERATION, the
    summed per-rank examples over the longest rank elapsed (the honest
    cross-rank aggregate — ranks run the same global steps; examples
    counters are per-rank local rows); across generations of one
    supervised run, examples/steps/elapsed SUM (each restart's fit
    restarts its clock and counters at the resumed stream position).
    Carries the last streaming-eval AUC when the run logged one, so
    --regress can gate quality too."""
    if not streams:
        return {}
    newest = _newest_run(streams)
    by_gen: dict = {}
    for (rid, rank, gen), recs in metrics_streams(streams).items():
        if rid == newest:
            by_gen.setdefault(gen, {})[rank] = summarize_stream(recs)
    if not by_gen:
        return {}
    examples = sum(s["examples"] for rows in by_gen.values() for s in rows.values())
    elapsed = sum(
        max((s["elapsed_s"] for s in rows.values()), default=0.0)
        for rows in by_gen.values()
    )
    steps = sum(
        max((s["steps"] for s in rows.values()), default=0)
        for rows in by_gen.values()
    )
    value = examples / elapsed if elapsed > 0 else 0.0
    rec = {
        "metric": "telemetry_examples_per_sec",
        "value": round(value, 1),
        "unit": "examples/sec",
        "run_id": newest,
        "ranks": len({rank for rows in by_gen.values() for rank in rows}),
        "steps": int(steps),
        "examples": int(examples),
        "elapsed_s": round(elapsed, 3),
        "bad_steps": int(
            sum(s["bad_steps"] for rows in by_gen.values() for s in rows.values())
        ),
    }
    if len(by_gen) > 1:
        rec["generations"] = len(by_gen)
    # quality comes from the NEWEST generation that logged an eval: the
    # final restart's model is what ships, and a superseded earlier
    # generation's (possibly better) AUC must not satisfy --regress.
    # Within one generation max-across-ranks is dedup, not choice — the
    # eval is collective, every rank logs the same value.
    for gen in sorted(by_gen, reverse=True):
        aucs = [
            s["eval_auc"] for s in by_gen[gen].values() if _finite(s["eval_auc"])
        ]
        if aucs:
            rec["auc"] = round(max(aucs), 6)
            break
    # compile context (telemetry.CompileRecorder): total compile
    # seconds and program count, so a BENCH datapoint carries the
    # cost-accounting trail alongside its throughput
    comps = compile_records(streams, run_id=newest)
    if comps:
        rec["compiled_programs"] = len(comps)
        rec["compile_time_s"] = round(
            sum(c["compile_time_s"] for c in comps
                if _finite(c.get("compile_time_s"))), 3
        )
    return rec


def serve_bench_record(streams: dict) -> dict:
    """BENCH-style SERVE perf record over the newest run (the shape
    tools/serve_bench.py emits, computed from the server's own
    telemetry instead of the client's) — the --bench-json fallback
    when a run dir holds serving streams but no trainer metrics, so a
    serving run feeds the BENCH_SERVE.json trajectory without a
    separate loadgen pass."""
    if not streams:
        return {}
    newest = _newest_run(streams)
    rows = {
        key: summarize_serve_stream(recs)
        for key, recs in serve_streams(streams).items()
        if key[0] == newest
    }
    rows = {k: s for k, s in rows.items() if s["windows"]}
    if not rows:
        return {}
    reqs = sum(s["requests"] for s in rows.values())
    total_rows = sum(s["rows"] for s in rows.values())
    # QPS: ranks serve CONCURRENTLY (their rates add); one rank's
    # restart generations run SEQUENTIALLY (they time-weight, never
    # add — summing would double a restarted server's trajectory)
    per_rank: dict = {}
    for (rid, rank, gen), s in rows.items():
        agg = per_rank.setdefault(rank, [0, 0.0])
        agg[0] += s["requests"]
        agg[1] += s["window_seconds"]
    qps = sum(r / t for r, t in per_rank.values() if t > 0)
    p50s = [s["p50_ms"] for s in rows.values() if _finite(s["p50_ms"])]
    p99s = [s["p99_ms"] for s in rows.values() if _finite(s["p99_ms"])]
    fills = [s["batch_fill"] for s in rows.values() if _finite(s["batch_fill"])]
    gens = sorted({g for s in rows.values() for g in s["generations"]})
    return {
        "metric": "serve_qps",
        "value": round(qps, 2),
        "unit": "requests/sec",
        "source": "serve_telemetry",
        "run_id": newest,
        "requests": int(reqs),
        "rows": int(total_rows),
        "p50_ms": round(sorted(p50s)[len(p50s) // 2], 3) if p50s else None,
        "p99_ms": round(max(p99s), 3) if p99s else None,
        "batch_fill": round(sum(fills) / len(fills), 4) if fills else None,
        "bad_requests": int(sum(s["bad_requests"] for s in rows.values())),
        "reloads": int(sum(s["reloads"] for s in rows.values())),
        "generations": gens,
    }


def render_compile_table(streams: dict) -> str:
    """The compile-accounting block: one row per kind="compile" record
    (program, compile seconds, model GFLOP and MB accessed per
    execution, temp bytes — docs/OBSERVABILITY.md "Compile
    accounting")."""
    recs = compile_records(streams)
    if not recs:
        return ""
    header = ("run_id", "rank", "program", "compile_s", "GFLOP", "MB_acc",
              "MB_temp", "n")

    def fmt(v):
        if v is None:
            return "-"
        if isinstance(v, float):
            return "-" if not math.isfinite(v) else f"{v:.4g}"
        return str(v)

    rows = []
    for r in recs:
        rows.append((
            r.get("run_id", "?"), r.get("rank", "?"),
            r.get("program", "?"), r.get("compile_time_s"),
            r["flops"] / 1e9 if _finite(r.get("flops")) else None,
            r["bytes_accessed"] / 1e6 if _finite(r.get("bytes_accessed")) else None,
            r["temp_bytes"] / 1e6 if _finite(r.get("temp_bytes")) else None,
            r.get("compiles", 1),
        ))
    cells = [header] + [tuple(fmt(c) for c in row) for row in rows]
    widths = [max(len(r[i]) for r in cells) for i in range(len(header))]
    lines = ["compiles (kind=compile):"]
    for i, row in enumerate(cells):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def render_serve_table(streams: dict) -> str:
    """The serving summary block: one row per (run_id, rank, gen)
    serve stream."""
    header = (
        "run_id", "rank", "gen", "windows", "requests", "rows", "qps",
        "p50_ms", "p99_ms", "fill", "bad", "shed", "reloads", "step",
    )

    def fmt(v):
        if isinstance(v, float):
            return "-" if not math.isfinite(v) else f"{v:.4g}"
        return str(v)

    rows = []
    for (run_id, rank, gen), recs in sorted(serve_streams(streams).items(), key=str):
        s = summarize_serve_stream(recs)
        rows.append((
            run_id, rank, gen, s["windows"], s["requests"], s["rows"],
            s["qps"], s["p50_ms"], s["p99_ms"], s["batch_fill"],
            s["bad_requests"], s["shed_requests"], s["reloads"],
            s["last_step"],
        ))
    if not rows:
        return ""
    cells = [header] + [tuple(fmt(c) for c in row) for row in rows]
    widths = [max(len(r[i]) for r in cells) for i in range(len(header))]
    lines = ["serving (kind=serve):"]
    for i, row in enumerate(cells):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


# ----------------------------------------------------------------- --health


def heartbeat_rows(streams: dict, run_id: str) -> list[dict]:
    """Straggler/dead classification over the run's heartbeat streams,
    via the same fold + classifier the live launcher watchdog uses —
    with "now" anchored to the newest heartbeat anywhere in the run
    (offline post-mortem: wall-clock now would read every finished run
    as dead).

    Topology elasticity: a rank the --allow-shrink supervisor dropped
    stops beating at its last generation and never writes a final
    event — wall-clock classification would call it dead forever. When
    the run's NEWEST generation stamps a smaller world, ranks outside
    that world whose beats stop at an older generation are relabeled
    ``retired@genK`` (K = the last generation they served in)."""
    from xflow_tpu.launch.watchdog import classify, fold_heartbeats

    beats: dict = {}
    latest_gen = 0
    world_by_gen: dict = {}
    for (rid, _rank, kind, gen), recs in streams.items():
        if rid != run_id:
            continue
        latest_gen = max(latest_gen, gen)
        if kind == "heartbeat":
            # generations fold together: the newest beat per rank wins,
            # so a rank that died in gen k and finished in gen k+1
            # correctly reads as finished
            fold_heartbeats(recs, beats)
        for r in recs:
            w = r.get("world")
            if isinstance(w, int) and w > 0:
                world_by_gen[gen] = max(world_by_gen.get(gen, 0), w)
    if not beats:
        return []
    now = max(b["ts"] for b in beats.values())
    rows = classify(beats, now)
    cur_world = world_by_gen.get(latest_gen, 0)
    for row in rows:
        beat_gen = beats.get(row["rank"], {}).get("gen", 0)
        if (
            cur_world
            and row["rank"] >= cur_world
            and beat_gen < latest_gen
            and row["status"] not in ("finished",)
        ):
            row["status"] = f"retired@gen{beat_gen}"
    return rows


def render_health(streams: dict) -> str:
    """The --health view for the newest run, one block per
    (rank, generation) — a supervised run's restarts segment here."""
    newest = _newest_run(streams)
    lines = [f"health report — run {newest}"]
    gens = sorted(
        {gen for (rid, _, gen) in metrics_streams(streams) if rid == newest}
    )
    if len(gens) > 1:
        lines.append(
            f"  restart generations: {len(gens)} "
            f"({len(gens) - 1} auto-restart(s); gen {gens[0]}..{gens[-1]})"
        )
    fmt = lambda v: f"{v:.4g}" if _finite(v) else "-"
    for (rid, rank, gen), recs in sorted(metrics_streams(streams).items(), key=str):
        if rid != newest:
            continue
        s = summarize_stream(recs)
        gen_tag = f" gen {gen}" if len(gens) > 1 else ""
        lines.append(
            f"  rank {rank}{gen_tag}: steps {s['steps']}  "
            f"loss {fmt(s['last_loss'])}  "
            f"loss_ema {fmt(s['loss_ema_last'])}"
        )
        lines.append(
            f"    norms: grad {fmt(s['grad_norm_first'])} -> "
            f"{fmt(s['grad_norm_last'])} (max {fmt(s['grad_norm_max'])})  "
            f"update {fmt(s['update_norm_last'])}  "
            f"param {fmt(s['param_norm_last'])}"
        )
        lines.append(
            f"    table: occupancy {fmt(s['occupancy_last'])}  "
            f"est_collision_rate {fmt(s['est_collision_rate_last'])}"
        )
        traj = s["auc_trajectory"]
        if traj:
            lines.append(
                f"    auc trajectory ({len(traj)} evals): "
                f"{fmt(traj[0])} -> {fmt(traj[-1])}"
                + ("  [declining]" if traj[-1] < traj[0] else "")
            )
        else:
            lines.append("    auc trajectory: none (train.eval_every off?)")
    hb = heartbeat_rows(streams, newest)
    if hb:
        lines.append("  heartbeats (lowest step first = the culprit ordering):")
        for row in hb:
            # retired@genK is a NEUTRAL state (the supervisor shrank
            # that rank away on purpose), not an alert like dead
            neutral = row["status"] in ("ok", "finished") or row[
                "status"
            ].startswith("retired")
            flag = "" if neutral else "  <-- " + row["status"].upper()
            lines.append(
                f"    rank {row['rank']}: step {row['step']}/{row['max_step']}"
                f"  last beat {row['age_s']:.1f}s before run end"
                f"  [{row['status']}]{flag}"
            )
    else:
        lines.append("  heartbeats: none (train.heartbeat_path off?)")
    pipe_lines = render_pipeline_verdict(streams, newest)
    if pipe_lines:
        lines.extend(pipe_lines)
    serve_lines = render_serve_latency_split(streams, newest)
    if serve_lines:
        lines.extend(serve_lines)
    at_lines = render_autotune_trajectory(streams, newest)
    if at_lines:
        lines.extend(at_lines)
    sync_lines = render_sync_staleness(streams, newest)
    if sync_lines:
        lines.extend(sync_lines)
    fresh_lines = render_freshness(streams, newest)
    if fresh_lines:
        lines.extend(fresh_lines)
    ckpt_lines = render_ckpt(streams, newest)
    if ckpt_lines:
        lines.extend(ckpt_lines)
    return "\n".join(lines)


def render_ckpt(streams: dict, run_id: str) -> list[str]:
    """The async-checkpoint section for the --health view
    (docs/ROBUSTNESS.md "Async tiered checkpointing"): last committed
    step per tier, committed/skip/failure counts, and whether the run
    ever degraded to replica-only saves — the first durability question
    an operator asks after an incident: what is the newest restorable
    step, and on which volume? Empty when the run carries no
    kind="ckpt" records (train.ckpt_async off)."""
    last_by_tier: dict = {}  # tier -> (ts, step)
    committed = 0
    failed = 0
    skips = 0
    degraded = False
    seen = False
    for (rid, _rank, kind, _gen), recs in sorted(streams.items(), key=str):
        if kind != "ckpt" or rid != run_id:
            continue
        for r in recs:
            seen = True
            skips = max(skips, r.get("skips", 0) or 0)
            if r.get("degraded") is True:
                degraded = True
            if r.get("event") == "failed":
                failed += 1
            if r.get("event") != "committed":
                continue
            committed += 1
            tier = r.get("tier", "?")
            cand = (r.get("committed_ts", 0.0), r.get("step"))
            if tier not in last_by_tier or cand > last_by_tier[tier]:
                last_by_tier[tier] = cand
    if not seen:
        return []
    out = ["  checkpoints (kind=ckpt, train.ckpt_async):"]
    for tier in CKPT_TIERS:
        if tier in last_by_tier:
            out.append(
                f"    {tier}: last committed step {last_by_tier[tier][1]}"
            )
        else:
            out.append(f"    {tier}: no committed saves")
    out.append(
        f"    committed {committed}  skipped {skips}  failed {failed}"
    )
    if degraded:
        out.append(
            "    DEGRADED: primary tier failed — saves land replica-only"
            "  <-- DEGRADED"
        )
    return out


def render_freshness(streams: dict, run_id: str) -> list[str]:
    """The data-freshness section for the --health view (docs/SERVING.md
    "Freshness"): publication cadence from the trainer's kind="publish"
    stream, then each serving replica's NEWEST data_freshness_s window
    gauge, and the stalest replica named — the first question a
    streaming run answers: how old is the data behind the predictions,
    and who is serving the oldest model? Empty when the run carries no
    publish records and no freshness-stamped serve windows
    (train.publish_every off, or a non-streaming run)."""
    pubs = 0
    last_pub = None  # (ts, step)
    for (rid, _rank, kind, _gen), recs in sorted(streams.items(), key=str):
        if kind != "publish" or rid != run_id:
            continue
        pubs += len(recs)
        for r in recs:
            if _finite(r.get("published_ts")):
                cand = (r["published_ts"], r.get("step"))
                if last_pub is None or cand > last_pub:
                    last_pub = cand
    # newest freshness-stamped window per serve stream; fold replicas
    # by rank (restart generations of one rank collapse, newest wins)
    by_rank: dict = {}  # rank -> (ts, freshness, model_gen)
    for (rid, rank, kind, _gen), recs in sorted(streams.items(), key=str):
        if kind != "serve" or rid != run_id:
            continue
        for r in recs:
            f = r.get("data_freshness_s")
            if not _finite(f):
                continue
            cand = (r.get("ts", 0.0), f, r.get("generation"))
            if rank not in by_rank or cand[0] > by_rank[rank][0]:
                by_rank[rank] = cand
    if not pubs and not by_rank:
        return []
    out = ["  freshness (kind=publish + serve data_freshness_s):"]
    if pubs:
        tail = ""
        if last_pub is not None:
            tail = f"  last at step {last_pub[1]}"
        out.append(f"    publications: {pubs}{tail}")
    else:
        out.append(
            "    publications: none in this run's streams "
            "(serving a checkpoint published elsewhere)"
        )
    stalest = None  # (freshness, rank)
    for rank, (_ts, f, mgen) in sorted(by_rank.items(), key=str):
        out.append(
            f"    replica rank {rank}: data_freshness_s {f:.3f} "
            f"(model generation {mgen})"
        )
        if stalest is None or f > stalest[0]:
            stalest = (f, rank)
    if stalest is not None:
        out.append(
            f"    stalest replica: rank {stalest[1]} "
            f"({stalest[0]:.3f}s behind the newest ingested row)"
        )
    elif pubs:
        out.append(
            "    no serving replica reported data_freshness_s "
            "(fleet not running, or windows predate the publication)"
        )
    return out


def render_sync_staleness(streams: dict, run_id: str) -> list[str]:
    """The multi-slice staleness-lag table for the --health view
    (docs/DISTRIBUTED.md "Multi-slice bounded staleness"): one line per
    slice's sync stream (newest generation wins — a rejoined slice
    reports its post-catch-up stream), then the most-stale peer across
    every slice's FINAL round, named. The first question a bounded-
    staleness run answers: who is holding the fleet back, and did
    anyone breach k? Empty when the run carries no sync records
    (sync.mode=off)."""
    by_rank: dict = {}  # rank -> (gen, records), newest gen wins
    for (rid, rank, kind, gen), recs in sorted(streams.items(), key=str):
        if kind != "sync" or rid != run_id or not recs:
            continue
        if rank not in by_rank or gen > by_rank[rank][0]:
            by_rank[rank] = (gen, recs)
    if not by_rank:
        return []
    last0 = next(iter(sorted(by_rank.items())))[1][1][-1]
    out = [
        f"  sync tier (kind=sync, mode={last0.get('mode')} "
        f"k={last0.get('k')}):"
    ]
    worst = None  # (lag, peer_slice, reporter_rank, reporter_round)
    for rank, (gen, recs) in sorted(by_rank.items(), key=str):
        last = recs[-1]
        stale_total = sum(r.get("stale", 0) for r in recs)
        timeout_total = sum(r.get("timeouts", 0) for r in recs)
        left_events = sum(len(r.get("left", ())) for r in recs)
        join_events = sum(len(r.get("joined", ())) for r in recs)
        out.append(
            f"    rank {rank}: rounds {last.get('round')}  "
            f"stale {stale_total}  timeouts {timeout_total}  "
            f"membership -{left_events}/+{join_events}  "
            f"last live {last.get('live')}"
        )
        lags = last.get("lags")
        if isinstance(lags, dict):
            for peer, lag in lags.items():
                if _finite(lag) and (worst is None or lag > worst[0]):
                    worst = (lag, peer, rank, last.get("round"))
    if worst and worst[0] > 0:
        out.append(
            f"    most-stale peer: slice {worst[1]} "
            f"({worst[0]} round(s) behind rank {worst[2]} at its final "
            f"round {worst[3]})"
        )
    elif worst is not None:
        out.append(
            "    most-stale peer: none (every peer caught up at the "
            "final round)"
        )
    return out


def render_pipeline_verdict(streams: dict, run_id: str) -> list[str]:
    """The input-pipeline bottleneck verdict for the --health view
    (docs/OBSERVABILITY.md "Input-pipeline attribution"), printed next
    to the queue-wait/device splits: aggregated kind="pipeline" stage
    seconds + the shared verdict line (telemetry.pipeline_verdict —
    the same one tools/pipeline_attrib.py prints). Empty when the run
    carries no pipeline records (train.pipeline_metrics off)."""
    from xflow_tpu.telemetry import PIPELINE_STAGES, pipeline_verdict

    stages = {s: 0.0 for s in PIPELINE_STAGES}
    wall = 0.0
    windows = 0
    for (rid, _rank, kind, _gen), recs in sorted(streams.items(), key=str):
        if kind != "pipeline" or rid != run_id:
            continue
        for r in recs:
            if not _finite(r.get("wall_s")):
                continue
            windows += 1
            wall += r["wall_s"]
            for s in stages:
                v = r.get(f"{s}_s")
                if _finite(v):
                    stages[s] += v
    if not windows:
        return []
    fmt = lambda s: f"{s} {100.0 * stages[s] / wall:.0f}%" if wall > 0 else s
    return [
        f"  input pipeline ({windows} window(s)): "
        + pipeline_verdict(stages, wall),
        "    stages: "
        + " | ".join(fmt(s) for s in ("parse", "cache_read", "plan",
                                      "producer_wait", "queue_wait",
                                      "dispatch", "device")),
    ]


def render_serve_latency_split(streams: dict, run_id: str) -> list[str]:
    """The per-replica queue-wait vs device p99 split (docs/SERVING.md
    "Telemetry + bench"): the first question request tracing answers in
    aggregate — is a replica's tail the COALESCER's backlog (queue-wait
    dominant: shrink the window, add replicas) or the DEVICE (device
    dominant: batch sizing, model cost)? One line per serve stream of
    the newest run, with the dominant side named."""
    fmt = lambda v: f"{v:.4g}" if _finite(v) else "-"
    out: list[str] = []
    for (rid, rank, gen), recs in sorted(serve_streams(streams).items(), key=str):
        if rid != run_id:
            continue
        windows = [r for r in recs if "qps" in r]
        q99s = [r["queue_wait_p99_ms"] for r in windows
                if _finite(r.get("queue_wait_p99_ms"))]
        d99s = [r["device_p99_ms"] for r in windows
                if _finite(r.get("device_p99_ms"))]
        if not q99s and not d99s:
            continue
        rep = next(
            (r["replica"] for r in recs if _finite(r.get("replica"))), None
        )
        q99 = max(q99s) if q99s else float("nan")
        d99 = max(d99s) if d99s else float("nan")
        dominant = (
            "queue-wait" if _finite(q99) and (not _finite(d99) or q99 >= d99)
            else "device"
        )
        label = f"replica {rep}" if rep is not None else f"rank {rank}"
        out.append(
            f"    {label} gen {gen}: queue_wait_p99 {fmt(q99)} ms | "
            f"device_p99 {fmt(d99)} ms  [{dominant}-bound]"
        )
    if out:
        out.insert(0, "  serving latency split (queue-wait vs device p99):")
    return out


def render_autotune_trajectory(streams: dict, run_id: str) -> list[str]:
    """The SLO-autotuner verdict for the --health view (docs/SERVING.md
    "Autotuning"): per controller stream, each knob's trajectory
    (start -> end over N decisions) plus a one-word verdict — did the
    closed loop CONVERGE (few direction reversals, settled), is it
    OSCILLATING (the damping failed to kill a flip-flop between the
    band edges), or is it PINNED AT FLOOR (the SLO is unattainable at
    this load and the controller gave up shrinking — raise the SLO or
    add replicas)? Empty when the run carries no autotune records
    (serve.autotune off)."""
    fmt = lambda v: f"{v:.4g}" if _finite(v) else "-"
    out: list[str] = []
    for (rid, rank, kind, gen), recs in sorted(streams.items(), key=str):
        if kind != "autotune" or rid != run_id:
            continue
        decisions = [r for r in recs if "knob" in r]
        if not decisions:
            continue
        rep = next(
            (r["replica"] for r in decisions if _finite(r.get("replica"))),
            None,
        )
        label = f"replica {rep}" if rep is not None else f"rank {rank}"
        parts = []
        verdict = "converged"
        for knob in AUTOTUNE_KNOB_NAMES:
            trail = [r for r in decisions if r.get("knob") == knob]
            if not trail:
                continue
            signs = [
                1 if r["new"] > r["old"] else -1
                for r in trail
                if _finite(r.get("old")) and _finite(r.get("new"))
                and r["new"] != r["old"]
            ]
            reversals = sum(
                1 for a, b in zip(signs, signs[1:]) if a != b
            )
            parts.append(
                f"{knob} {fmt(trail[0]['old'])} -> {fmt(trail[-1]['new'])} "
                f"({len(trail)} decision(s), {reversals} reversal(s))"
            )
            # oscillating: most moves undo the previous one — the
            # damping never settled the loop inside the band
            if len(signs) >= 4 and reversals > len(signs) // 2:
                verdict = "oscillating"
        if any(r.get("reason") == "floor_pinned" for r in decisions[-2:]):
            verdict = "pinned at floor (SLO unattainable at this load)"
        slo = decisions[-1].get("slo_p99_ms")
        out.append(
            f"    {label} gen {gen} (slo_p99_ms {fmt(slo)}): "
            + "  ".join(parts)
            + f"  [{verdict}]"
        )
    if out:
        out.insert(0, "  autotune trajectory (kind=autotune):")
    return out


# ---------------------------------------------------------------- --regress


def check_regression(
    current: dict, baseline: dict, tol: float, auc_tol: float
) -> list[str]:
    """Failures ([] = pass) comparing this run's bench record against a
    saved BENCH-style baseline. Throughput gates when both sides carry a
    value; AUC gates when both sides carry one."""
    problems = []
    base_v = baseline.get("value")
    cur_v = current.get("value")
    if _finite(base_v) and base_v > 0:
        if not _finite(cur_v):
            problems.append("current run has no throughput value")
        elif cur_v < (1.0 - tol) * base_v:
            problems.append(
                f"throughput regressed: {cur_v:.1f} < (1-{tol})*baseline "
                f"{base_v:.1f} {baseline.get('unit', '')}"
            )
    base_auc = baseline.get("auc")
    cur_auc = current.get("auc")
    if _finite(base_auc) and _finite(cur_auc) and cur_auc < base_auc - auc_tol:
        problems.append(
            f"AUC regressed: {cur_auc:.6f} < baseline {base_auc:.6f} - "
            f"{auc_tol}"
        )
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarize / schema-check xflow telemetry JSONL runs"
    )
    ap.add_argument("paths", nargs="+", help="JSONL file(s) and/or run dir(s)")
    ap.add_argument("--check", action="store_true",
                    help="schema-validate and exit nonzero on violation")
    ap.add_argument("--health", action="store_true",
                    help="model-health summary: norm trends, AUC trajectory, "
                         "occupancy, heartbeat/straggler table")
    ap.add_argument("--bench-json", default="",
                    help="write a BENCH-style perf JSON here ('-' = stdout)")
    ap.add_argument("--regress", default="", metavar="BASELINE.json",
                    help="gate against a saved BENCH-style baseline; exit 3 "
                         "on throughput/AUC regression")
    ap.add_argument("--regress-tol", type=float, default=0.2,
                    help="allowed fractional throughput drop (default 0.2)")
    ap.add_argument("--auc-tol", type=float, default=0.01,
                    help="allowed absolute AUC drop (default 0.01)")
    args = ap.parse_args(argv)

    try:
        files = expand_paths(args.paths)
    except FileNotFoundError as e:
        print(f"metrics_report: {e}", file=sys.stderr)
        return 2
    streams, skipped = load_streams(files)

    if args.check:
        problems = check_streams(streams, files)
        if problems:
            for p in problems:
                print(f"metrics_report: FAIL: {p}", file=sys.stderr)
            return 2
        total = sum(len(v) for v in streams.values())
        print(
            f"metrics_report: OK: {len(files)} file(s), {len(streams)} "
            f"stream(s), {total} record(s), {skipped} damaged line(s) skipped"
        )
        return 0

    if not streams:
        # both views: an empty/wrong directory must not read as passing
        print("metrics_report: no records found", file=sys.stderr)
        return 1

    if args.health:
        # the health view replaces the summary table; --bench-json and
        # --regress below still run (a CI line can combine them)
        print(render_health(streams))
    else:
        rows = []
        for (run_id, rank, gen), records in sorted(
            metrics_streams(streams).items(), key=str
        ):
            s = summarize_stream(records)
            rows.append((
                run_id, rank, gen, s["steps"], s["examples"],
                round(s["elapsed_s"], 1),
                s["examples_per_s"], s["rows_per_s"], s["p50_ms"], s["p99_ms"],
                s["data_wait_ms"], s["last_loss"], s["bad_steps"], s["bad_rows"],
                s["eval_auc"],
            ))
        serve_table = render_serve_table(streams)
        compile_table = render_compile_table(streams)
        if rows:
            print(render_table(rows))
        if serve_table:
            print(serve_table)
        if compile_table:
            print(compile_table)
        if not rows and not serve_table and not compile_table:
            print("metrics_report: no records found", file=sys.stderr)
            return 1
    if skipped:
        print(f"# {skipped} damaged line(s) skipped (truncated append?)")

    if args.bench_json:
        # trainer record when the run trained; else the serving record
        # (a serve-only run dir feeds the BENCH_SERVE.json trajectory)
        rec = bench_record(streams) or serve_bench_record(streams)
        out = json.dumps(rec)
        if args.bench_json == "-":
            print(out)
        else:
            with open(args.bench_json, "w") as f:
                f.write(out + "\n")

    if args.regress:
        try:
            with open(args.regress) as f:
                baseline = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"metrics_report: cannot read baseline: {e}", file=sys.stderr)
            return 2
        problems = check_regression(
            bench_record(streams), baseline, args.regress_tol, args.auc_tol
        )
        if problems:
            for p in problems:
                print(f"metrics_report: REGRESSION: {p}", file=sys.stderr)
            return 3
        print(f"metrics_report: no regression vs {args.regress}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
