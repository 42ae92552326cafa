"""Request microbatching: the coalescing window.

A TPU answers one 256-row padded batch in roughly the time it answers
one 1-row batch — per-request dispatch wastes the device. The
MicroBatcher queues concurrent requests and releases them as ONE
group when either (a) the queued rows reach `max_rows` (size flush) or
(b) the OLDEST queued request has waited `window_s` (deadline flush) —
so an idle server adds at most one window of latency and a busy server
fills its batches. The reference's closest analog is the worker's
per-minibatch unique-key Pull (`lr_worker.cc:150-165`): amortize the
parameter-plane round trip over many rows.

Requests stay WHOLE: a group never splits a request across two device
batches (its rows would otherwise answer at two generations mid-swap).
A request larger than `max_rows` is rejected at submit — the client
splits, the server's compiled batch shape stays fixed.

Everything here is socket-free and clock-injectable: the HTTP layer
(serve/server.py) calls `submit`, the device worker calls `take`, and
the unit tests (tests/test_serve.py) drive both with a fake clock.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from collections.abc import Mapping
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable, Optional

import numpy as np

if TYPE_CHECKING:  # config type only — no runtime import cycle
    from xflow_tpu.config import ServeConfig


class RejectedRequest(Exception):
    """A request the coalescer will not queue. `client_error` carries
    the HTTP status class explicitly (serve/server.py): True = the
    CLIENT's mistake (empty/oversized — 400, don't retry unchanged);
    False = load shedding (backlog full, brownout shed, shutting down —
    503, retry later). `shed` marks the brownout's priority shed so the
    server can count it apart from the hard backlog cliff. Either way a
    visible signal, never a crash."""

    def __init__(self, message: str, client_error: bool = False,
                 shed: bool = False):
        super().__init__(message)
        self.client_error = client_error
        self.shed = shed


@dataclass(frozen=True)
class BrownoutPolicy:
    """Admission-control thresholds (docs/SERVING.md "Brownout").

    The hard `max_queue_rows` 503 is a cliff: every submit beyond it
    fails, whatever its priority, and by the time the backlog is there
    the p99 is already blown. Brownout is the graded slope before it:
    backlog >= `high_rows` sustained `after_s` enters brownout — the
    coalescing window shrinks by `window_factor` (smaller batches,
    drained sooner) and low-priority submits shed with a retryable 503
    — and backlog <= `low_rows` sustained `after_s` exits. The
    hysteresis band (high != low) plus the sustain window keep a bursty
    backlog from flapping the mode per request."""

    high_rows: int
    low_rows: int
    after_s: float = 0.25
    window_factor: float = 0.25

    @staticmethod
    def from_config(scfg: "ServeConfig") -> "BrownoutPolicy":
        q = int(scfg.max_queue_rows)
        return BrownoutPolicy(
            high_rows=max(int(q * scfg.brownout_high_frac), 1),
            low_rows=max(int(q * scfg.brownout_low_frac), 0),
            after_s=float(scfg.brownout_after_s),
            window_factor=float(scfg.brownout_window_factor),
        )


@dataclass
class RowBlock:
    """A request's rows, parsed and padded to the batch's row width —
    what the coalescer queues and `assemble_batch` copies whole.

    `slots`, `fields` int32 and `mask` float32 are [n, max_nnz]: a row
    keeps the prefix of its first max_nnz features (the training
    parser's truncation contract, data/schema.make_batch) and its mask
    is 1 there. `nnz` int32 [n] counts a row's parseable features before
    that cut. `native` says which parser read the rows
    (serve/runner.py parse_request)."""

    slots: np.ndarray
    fields: np.ndarray
    mask: np.ndarray
    nnz: np.ndarray
    native: bool = False

    @property
    def num_rows(self) -> int:
        return int(self.slots.shape[0])

    @classmethod
    def from_rows(cls, fields_rows: list, slots_rows: list, max_nnz: int) -> "RowBlock":
        """Pad ragged per-row (fields, slots) arrays into a block."""
        n = len(slots_rows)
        slots = np.zeros((n, max_nnz), np.int32)
        fields = np.zeros((n, max_nnz), np.int32)
        mask = np.zeros((n, max_nnz), np.float32)
        nnz = np.zeros((n,), np.int32)
        for i, (rf, rs) in enumerate(zip(fields_rows, slots_rows)):
            k = min(len(rs), max_nnz)
            slots[i, :k] = rs[:k]
            fields[i, :k] = rf[:k]
            mask[i, :k] = 1.0
            nnz[i] = len(rs)
        return cls(slots, fields, mask, nnz)

    def part(self, lo: int, hi: int) -> "RowBlock":
        """Rows [lo, hi) as a block of views."""
        return RowBlock(self.slots[lo:hi], self.fields[lo:hi],
                        self.mask[lo:hi], self.nnz[lo:hi], self.native)


@dataclass
class PendingRequest:
    """One queued request: its parsed rows awaiting a device batch."""

    block: RowBlock
    future: Future = field(default_factory=Future)
    t_submit: float = 0.0
    priority: int = 0  # < 0 = sheddable under brownout (request header)
    # request tracing (xflow_tpu/tracing.py): the trace id and the
    # server-side parent span id ride the queue so the device worker
    # can emit this request's queue/device spans and link them to the
    # shared device_batch span. "" = untraced (zero worker-side cost).
    trace: str = ""
    span: str = ""

    @property
    def num_rows(self) -> int:
        return self.block.num_rows


class MicroBatcher:
    def __init__(
        self,
        max_rows: int,
        window_s: float,
        max_queue_rows: int = 8192,
        clock: Callable[[], float] = time.perf_counter,
        brownout: Optional[BrownoutPolicy] = None,
        on_brownout: Optional[Callable[[bool, int], None]] = None,
    ):
        if max_rows <= 0:
            raise ValueError(f"max_rows={max_rows}: need >= 1")
        self.max_rows = int(max_rows)
        self.window_s = float(window_s)
        # release target (<= max_rows): the rows that trigger a size
        # flush and cap a popped group. Distinct from max_rows — the
        # autotuner moves THIS (the active ladder rung) while the
        # per-request row cap (and the compiled top-rung shape) stays
        # max_rows, so no in-flight client contract changes under it.
        self._release_rows = int(max_rows)
        self.max_queue_rows = int(max_queue_rows)
        self._clock = clock
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._q: deque = deque()
        self._queued_rows = 0
        self._closed = False
        # brownout admission control (docs/SERVING.md "Brownout"):
        # None = off (solo-server default keeps the original cliff-only
        # behavior); `on_brownout(active, queued_rows)` fires OUTSIDE
        # the lock on each mode change (telemetry events)
        self._brownout_policy = brownout
        self._on_brownout = on_brownout
        self._brownout = False
        self._over_since: Optional[float] = None
        self._under_since: Optional[float] = None

    @property
    def queued_rows(self) -> int:
        with self._lock:
            return self._queued_rows

    @property
    def brownout(self) -> bool:
        with self._lock:
            return self._brownout

    def _update_brownout_locked(self, now: float) -> Optional[bool]:
        """Advance the brownout state machine; returns the new mode on
        a transition (for the callback), else None. Hysteresis: enter
        at >= high_rows sustained after_s, exit at <= low_rows
        sustained after_s — a single burst or a single drained batch
        must not flap the mode."""
        p = self._brownout_policy
        if p is None:
            return None
        q = self._queued_rows
        if not self._brownout:
            self._under_since = None
            if q >= p.high_rows:
                if self._over_since is None:
                    self._over_since = now
                if now - self._over_since >= p.after_s:
                    self._brownout = True
                    self._over_since = None
                    return True
            else:
                self._over_since = None
        else:
            self._over_since = None
            if q <= p.low_rows:
                if self._under_since is None:
                    self._under_since = now
                if now - self._under_since >= p.after_s:
                    self._brownout = False
                    self._under_since = None
                    return False
            else:
                self._under_since = None
        return None

    def _effective_window_locked(self) -> float:
        if self._brownout and self._brownout_policy is not None:
            return self.window_s * self._brownout_policy.window_factor
        return self.window_s

    @property
    def effective_window_s(self) -> float:
        """The coalescing window currently in force — brownout shrinks
        it by window_factor. Read-only snapshot for telemetry/tracing
        (the device-batch span's flush classification must judge a
        deadline flush against the window that actually applied)."""
        with self._lock:
            return self._effective_window_locked()

    @property
    def release_rows(self) -> int:
        with self._lock:
            return self._release_rows

    # ------------------------------------------------- autotuner setters
    # (serve/autotune.py): the controller runs on the device-worker
    # thread while submit/take touch the same fields from handler
    # threads — both setters hold the lock and wake the worker, since a
    # shrink can make the oldest queued request releasable RIGHT NOW
    def set_window_s(self, window_s: float) -> None:
        with self._lock:
            self.window_s = max(float(window_s), 0.0)
            self._cv.notify_all()

    def set_release_rows(self, rows: int) -> None:
        """Move the active release rung; clamped to [1, max_rows]."""
        with self._lock:
            self._release_rows = max(1, min(int(rows), self.max_rows))
            self._cv.notify_all()

    def submit(self, block: RowBlock,
               priority: int = 0, trace: str = "", span: str = "") -> Future:
        """Queue one request's rows; returns the Future its caller
        blocks on. Raises RejectedRequest (never queues half a request)
        when the request is empty/oversized, the backlog is full, the
        batcher is closed, or brownout is shedding its priority class
        (priority < 0 while the backlog runs hot). `trace`/`span` carry
        the request's tracing identity to the device worker."""
        n = block.num_rows
        if n == 0:
            raise RejectedRequest("request has no rows", client_error=True)
        if n > self.max_rows:
            raise RejectedRequest(
                f"request has {n} rows > serve.max_batch={self.max_rows}; "
                "split the request",
                client_error=True,
            )
        now = self._clock()
        req = PendingRequest(
            block=block, t_submit=now, priority=int(priority),
            trace=trace, span=span,
        )
        flipped = None
        try:
            with self._lock:
                if self._closed:
                    raise RejectedRequest("server is shutting down")
                flipped = self._update_brownout_locked(now)
                if self._brownout and req.priority < 0:
                    raise RejectedRequest(
                        f"brownout: shedding low-priority requests "
                        f"({self._queued_rows} rows backlogged); retry later",
                        shed=True,
                    )
                if self._queued_rows + n > self.max_queue_rows:
                    raise RejectedRequest(
                        f"queue full ({self._queued_rows} rows backlogged, "
                        f"limit {self.max_queue_rows}); retry later"
                    )
                self._q.append(req)
                self._queued_rows += n
                if flipped is None:
                    # the append itself may push the backlog over the
                    # high-water line — start the sustain timer NOW, not
                    # at the next submit's pre-check
                    flipped = self._update_brownout_locked(now)
                self._cv.notify_all()
        finally:
            # the mode-change callback runs OUTSIDE the lock (it
            # appends telemetry), and fires even when this submit shed
            if flipped is not None and self._on_brownout is not None:
                self._on_brownout(flipped, self.queued_rows)
        return req.future

    def take(self, timeout: Optional[float] = None) -> Optional[list]:
        """Block until a group is releasable, then pop and return it
        ([PendingRequest]). Returns None on timeout with nothing queued,
        or when closed and drained — the device worker's exit signal.

        Release rule: queued rows >= max_rows (size flush), the oldest
        request has aged past window_s (deadline flush; under brownout
        the window shrinks by the policy's window_factor — drain the
        backlog in smaller, sooner batches), or the batcher closed
        (drain everything pending). The popped group is the longest
        whole-request prefix fitting max_rows."""
        deadline = None if timeout is None else self._clock() + timeout
        flipped = None
        with self._lock:
            while True:
                now = self._clock()
                if flipped is None:
                    flipped = self._update_brownout_locked(now)
                if self._q:
                    flush_at = self._q[0].t_submit + self._effective_window_locked()
                    if (
                        self._queued_rows >= self._release_rows
                        or now >= flush_at
                        or self._closed
                    ):
                        group = self._pop_group_locked()
                        break
                    if deadline is not None and now >= deadline:
                        group = None  # caller's timeout: window still open
                        break
                    # sleep until the window deadline (or the caller's
                    # timeout, or a submit that fills the batch)
                    wake = flush_at if deadline is None else min(flush_at, deadline)
                    self._cv.wait(max(wake - now, 0.0))
                    continue
                if self._closed:
                    group = None
                    break
                if deadline is not None:
                    left = deadline - now
                    if left <= 0:
                        group = None
                        break
                    self._cv.wait(left)
                else:
                    self._cv.wait()
        # mode changes observed here (e.g. the backlog draining below
        # low_rows) report outside the lock, same as submit's
        if flipped is not None and self._on_brownout is not None:
            self._on_brownout(flipped, self.queued_rows)
        return group

    def _pop_group_locked(self) -> list:
        # cap at the release rung, but ALWAYS pop the head request: a
        # request legitimately bigger than the current rung (but within
        # max_rows, the submit contract) releases alone and simply
        # assembles at the next rung that fits — never wedges the queue
        cap = max(self._release_rows,
                  self._q[0].num_rows if self._q else 0)
        group = []
        rows = 0
        while self._q and rows + self._q[0].num_rows <= cap:
            req = self._q.popleft()
            rows += req.num_rows
            group.append(req)
        self._queued_rows -= rows
        return group

    def close(self) -> None:
        """Stop accepting; wake the worker so it drains the backlog
        (every queued future still resolves) and then sees None."""
        with self._lock:
            self._closed = True
            self._cv.notify_all()


def assemble_batch(
    group: list, batch_size: int, max_nnz: int
) -> tuple[dict, list]:
    """Pack a group's requests into ONE padded row-major batch: each
    request's block lands with one slice assignment an array.

    Returns (arrays, spans): `arrays` is the {slots, fields, mask,
    row_mask} dict the predict step consumes — fixed [batch_size,
    max_nnz] shape so the jitted program compiles ONCE — and `spans`
    is [(request, start, stop)] mapping each request back to its row
    slice of the pctr output. Blocks come `max_nnz` wide from the
    parser (rows already cut to their prefix); padding rows are fully
    masked.
    """
    total = sum(req.num_rows for req in group)
    if total > batch_size:
        raise ValueError(f"group rows {total} > batch_size {batch_size} (bug)")
    slots = np.zeros((batch_size, max_nnz), dtype=np.int32)
    fields = np.zeros((batch_size, max_nnz), dtype=np.int32)
    mask = np.zeros((batch_size, max_nnz), dtype=np.float32)
    row_mask = np.zeros((batch_size,), dtype=np.float32)
    spans = []
    i = 0
    for req in group:
        b = req.block
        n = b.num_rows
        slots[i : i + n] = b.slots
        fields[i : i + n] = b.fields
        mask[i : i + n] = b.mask
        row_mask[i : i + n] = 1.0
        spans.append((req, i, i + n))
        i += n
    return (
        {"slots": slots, "fields": fields, "mask": mask, "row_mask": row_mask},
        spans,
    )


class PackedBatch(Mapping):
    """One device batch as ONE int32 vector `buf` of 3·rung·max_nnz + 1,
    planar: the slots plane [rung, max_nnz], the fields plane, the mask
    plane (the float32 mask's bits) and, last, the count of real rows,
    which fill the first rows; the padding rows are zero. What the solo
    runner's predict program takes (serve/runner.py unpack_batch): one
    argument, one transfer. It reads as the dict `assemble_batch` makes
    (views of `buf`; `row_mask` from the count), for callers that want
    the arrays."""

    def __init__(self, buf: np.ndarray, rung: int, max_nnz: int):
        self.buf, self.rung, self.max_nnz = buf, int(rung), int(max_nnz)

    @property
    def num_rows(self) -> int:
        return int(self.buf[-1])

    def __getitem__(self, key: str) -> np.ndarray:
        plane = self.rung * self.max_nnz
        shape = (self.rung, self.max_nnz)
        if key == "slots":
            return self.buf[:plane].reshape(shape)
        if key == "fields":
            return self.buf[plane:2 * plane].reshape(shape)
        if key == "mask":
            return self.buf[2 * plane:3 * plane].view(np.float32).reshape(shape)
        if key == "row_mask":
            return (np.arange(self.rung) < self.num_rows).astype(np.float32)
        raise KeyError(key)

    def __iter__(self):
        return iter(("slots", "fields", "mask", "row_mask"))

    def __len__(self) -> int:
        return 4


def assemble_packed(
    group: list, batch_size: int, max_nnz: int
) -> tuple[PackedBatch, list]:
    """`assemble_batch`'s batch as one `PackedBatch`, filled by ONE
    native call that keeps the GIL (data/native.py
    native_assemble_rows): no buffer zeroed by numpy, no copy per
    request and plane, each of which would hand the GIL to the request
    threads and wait to win it back. Returns (batch, spans) as
    `assemble_batch` does."""
    from xflow_tpu.data.native import native_assemble_rows

    buf = np.empty((3 * batch_size * max_nnz + 1,), np.int32)  # np.zeros would drop the GIL
    blocks, spans = [], []
    i = 0
    for req in group:
        b = req.block
        blocks.append((np.ascontiguousarray(b.slots, np.int32),
                       np.ascontiguousarray(b.fields, np.int32),
                       np.ascontiguousarray(b.mask, np.float32)))
        spans.append((req, i, i + b.num_rows))
        i += b.num_rows
    if i > batch_size:
        raise ValueError(f"group rows {i} > batch_size {batch_size} (bug)")
    native_assemble_rows(buf, batch_size, max_nnz, blocks)
    return PackedBatch(buf, batch_size, max_nnz), spans


def pack_arrays(arrays: dict) -> PackedBatch:
    """A batch dict as a `PackedBatch` (numpy; direct `predict` callers):
    its real rows are the `row_mask` rows, which lead."""
    slots = np.asarray(arrays["slots"], np.int32)
    rung, max_nnz = slots.shape
    n = int(np.count_nonzero(arrays["row_mask"]))
    buf = np.concatenate([
        slots.ravel(),
        np.asarray(arrays["fields"], np.int32).ravel(),
        np.asarray(arrays["mask"], np.float32).view(np.int32).ravel(),
        np.asarray([n], np.int32),
    ])
    return PackedBatch(buf, rung, max_nnz)
