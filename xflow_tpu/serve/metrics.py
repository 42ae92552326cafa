"""Serving telemetry: kind="serve" JSONL windows + reload events.

Plugs into the same registry/appender plumbing training uses
(xflow_tpu/telemetry.py, xflow_tpu/jsonl.py): every record is stamped
ts/rank/run_id/gen/world by the shared appender and kind="serve" keys
the stream, so one run dir can hold training metrics, heartbeats, AND
serving windows and tools/metrics_report.py tells them apart.

Window records (one per `every_s`, only when traffic flowed) carry the
serving SLO view: QPS, rows/s, batch-fill ratio (how well the
coalescer amortizes the device), and the latency decomposition —
queue-wait (coalescing delay), device (predict step), total
(submit -> response ready) p50/p99 — and which parser read the
window's answered rows (`parse_native_rows`, `parse_python_rows`: they
sum to `rows`), and how many of its batches the native assembler packed
(`assemble_native_batches`, at most `batches`). `generation`/`step`
carry the newest model generation this sink has recorded at flush time — a
monotone high-water mark shared with the event path, so a window
flushed right after a reload event can never stamp the pre-swap
pair (the stream stays monotone in file order even though the
watcher and metrics threads race). Event records ({"event": "reload"|
"reload_failed"|"start"|"final"}) mark the hot-reload timeline.
docs/OBSERVABILITY.md documents the schema; metrics_report --check
gates it (all-or-none keys, monotone generation).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

from xflow_tpu.jsonl import JsonlAppender
from xflow_tpu.telemetry import Registry, default_registry

# the key set every serve window record carries (metrics_report --check
# enforces all-or-none via its SERVE_KEYS copy of this tuple; keep
# docs/OBSERVABILITY.md in sync)
SERVE_WINDOW_KEYS = (
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
    "parse_native_rows",
    "parse_python_rows",
    "assemble_native_batches",
    "generation",
    "step",
)
# optional window key (the OPTIONAL_SERVE_KEYS convention in
# metrics_report): present only while the served generation carries a
# publication sidecar (train.publish_every) — seconds between the
# model's newest ingested row and the flush (docs/SERVING.md
# "Freshness"). Absent = not measurable, never a fake 0.
SERVE_FRESHNESS_KEY = "data_freshness_s"


class ServeMetrics:
    """Thread-safe window aggregator -> JSONL sink. `observe_batch`
    runs on the device-worker thread, `observe_bad_request` on HTTP
    handler threads, `event` on the watcher thread."""

    def __init__(
        self,
        path: str = "",
        every_s: float = 5.0,
        batch_size: int = 1,
        registry: Optional[Registry] = None,
        max_bytes: int = 0,
    ):
        # lazy rank/run_id stamp; max_bytes (serve.metrics_max_bytes)
        # rolls long-running fleets' streams instead of growing forever
        self._app = JsonlAppender(path, stamp=None, max_bytes=max_bytes)
        self._kind = {"kind": "serve"}
        self._every = max(float(every_s), 0.05)
        self._batch_size = max(int(batch_size), 1)
        self._reg = registry or default_registry()
        self._lock = threading.Lock()
        self._win_start = time.perf_counter()
        # monotone high-water mark of (generation, step) across EVERY
        # record this sink emitted: the reload event (watcher thread)
        # and the window flush (metrics thread) race on the appender,
        # and a window computed against a pre-swap snapshot must not
        # land AFTER the reload event stamped with the pre-swap pair —
        # metrics_report --check reads the stream in file order and
        # gates generation/step monotonicity per restart generation
        self._seen_gen = -1
        self._seen_step = -1
        self._reset_window_locked()

    @property
    def appender(self) -> JsonlAppender:
        """The underlying stamped sink — serve_main binds the compile
        recorder to it so kind="compile" records join this stream."""
        return self._app

    def _reset_window_locked(self) -> None:
        self._requests = 0
        self._rows = 0
        self._batches = 0
        self._capacity = 0  # sum of per-batch padded shapes (ladder rungs)
        self._bad = 0
        self._shed = 0
        self._native_rows = 0
        self._native_batches = 0
        self._queue_waits: list = []
        self._device: list = []
        self._totals: list = []

    # ------------------------------------------------------------ observing
    def observe_batch(
        self,
        n_requests: int,
        n_rows: int,
        queue_waits_s: list,
        device_s: float,
        totals_s: list,
        batch_size: Optional[int] = None,
        native_rows: int = 0,
        native_assembly: bool = False,
    ) -> None:
        """`batch_size` is the PADDED shape this batch shipped at — the
        ladder rung (serve/autotune.py). None (the pre-ladder callers)
        falls back to the constructor's fixed batch size, so batch_fill
        keeps meaning rows/padded-capacity either way. `native_rows` of
        the `n_rows` were read by the native parser, the rest by the
        Python one (serve/runner.py parse_request). `native_assembly`:
        the batch was packed by `xf_assemble_rows`
        (serve/coalescer.py assemble_packed)."""
        with self._lock:
            self._requests += n_requests
            self._rows += n_rows
            self._native_rows += native_rows
            self._native_batches += int(native_assembly)
            self._batches += 1
            self._capacity += int(batch_size) if batch_size else self._batch_size
            self._queue_waits.extend(queue_waits_s)
            self._device.append(device_s)
            self._totals.extend(totals_s)
        self._reg.counter("serve.requests").inc(n_requests)
        self._reg.counter("serve.rows").inc(n_rows)
        self._reg.counter("serve.batches").inc()
        self._reg.counter("serve.parse_native_rows").inc(native_rows)
        self._reg.counter("serve.parse_python_rows").inc(n_rows - native_rows)
        self._reg.counter("serve.assemble_native_batches").inc(int(native_assembly))

    def observe_bad_request(self) -> None:
        with self._lock:
            self._bad += 1
        self._reg.counter("serve.bad_requests").inc()

    def observe_shed(self) -> None:
        """A brownout priority shed (admission control) — counted apart
        from bad_requests: a shed is the SERVER's choice under load, a
        retry-later signal, not a malformed or cliff-rejected request."""
        with self._lock:
            self._shed += 1
        self._reg.counter("serve.shed_requests").inc()

    def _advance_seen_locked(self, generation, step) -> tuple:
        """Fold (generation, step) into the high-water mark and return
        the folded pair. The pair moves together: a newer model
        generation carries its own step; within one generation the
        runner never regresses the step."""
        if generation is not None and int(generation) > self._seen_gen:
            self._seen_gen = int(generation)
            self._seen_step = int(step) if step is not None else self._seen_step
        elif step is not None and int(generation or -1) == self._seen_gen:
            self._seen_step = max(self._seen_step, int(step))
        return self._seen_gen, self._seen_step

    def event(self, name: str, **extra) -> None:
        """Append an event record immediately (reload timeline). Held
        under the window lock so the high-water fold and the append are
        one atomic step relative to `maybe_flush`."""
        with self._lock:
            self._advance_seen_locked(extra.get("generation"), extra.get("step"))
            self._app.append({**self._kind, "event": name, **extra})

    # ------------------------------------------------------------- flushing
    def maybe_flush(
        self,
        generation: int,
        step: int,
        force: bool = False,
        freshness_s: Optional[float] = None,
    ) -> Optional[dict]:
        """Emit a window record when the window elapsed (or `force`) and
        traffic flowed; returns the record (tests) or None.
        `freshness_s` (Generation.freshness_s) adds the optional
        data_freshness_s key — None (unpublished checkpoint) leaves the
        record byte-identical to a pre-freshness build."""
        now = time.perf_counter()
        with self._lock:
            elapsed = now - self._win_start
            if not force and elapsed < self._every:
                return None
            if self._batches == 0 and self._bad == 0 and self._shed == 0:
                self._win_start = now  # idle window: emit nothing
                return None
            pct = lambda xs, q: (
                round(float(np.percentile(np.asarray(xs) * 1e3, q)), 3)
                if xs
                else None
            )
            rec = {
                **self._kind,
                "requests": self._requests,
                "rows": self._rows,
                "qps": round(self._requests / max(elapsed, 1e-9), 2),
                "rows_per_s": round(self._rows / max(elapsed, 1e-9), 1),
                "batches": self._batches,
                "batch_fill": round(self._rows / max(self._capacity, 1), 4),
                "queue_wait_p50_ms": pct(self._queue_waits, 50),
                "queue_wait_p99_ms": pct(self._queue_waits, 99),
                "device_p50_ms": pct(self._device, 50),
                "device_p99_ms": pct(self._device, 99),
                "total_p50_ms": pct(self._totals, 50),
                "total_p99_ms": pct(self._totals, 99),
                "window_s": round(elapsed, 3),
                "bad_requests": self._bad,
                "shed_requests": self._shed,
                "parse_native_rows": self._native_rows,
                "parse_python_rows": self._rows - self._native_rows,
                "assemble_native_batches": self._native_batches,
            }
            # stamp the high-water (generation, step): the caller's pair
            # is a snapshot that may predate a reload event already in
            # the file; the append stays under the lock so no fresher
            # event can slip in between the fold and the write
            g, s = self._advance_seen_locked(generation, step)
            rec["generation"], rec["step"] = g, s
            if freshness_s is not None:
                rec[SERVE_FRESHNESS_KEY] = round(max(float(freshness_s), 0.0), 3)
            self._reset_window_locked()
            self._win_start = now
            self._app.append(rec)
        self._reg.gauge("serve.qps").set(rec["qps"])
        if rec["batches"]:
            self._reg.gauge("serve.batch_fill").set(rec["batch_fill"])
        if freshness_s is not None:
            self._reg.gauge("serve.data_freshness_s").set(
                rec[SERVE_FRESHNESS_KEY]
            )
        return rec

    def close(
        self,
        generation: int = -1,
        step: int = -1,
        freshness_s: Optional[float] = None,
    ) -> None:
        self.maybe_flush(generation, step, force=True, freshness_s=freshness_s)
        self._app.append({**self._kind, "event": "final"})
        self._app.close()
