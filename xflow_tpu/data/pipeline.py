"""Batching pipeline: libffm examples → padded SparseBatch stream.

The reference couples its minibatch size to the IO block size (however
many lines fit in a 2 MiB fread block, `lr_worker.cc:184-188`) and then
silently drops remainder rows when the block doesn't divide by the
thread count (`lr_worker.cc:190-194`). Here batches are a fixed
``batch_size`` rows (static XLA shapes) and the final partial batch is
padded and masked rather than dropped (configurable via
``drop_remainder`` for strict reference emulation).

`PassProducer` (and `prefetch`, one pass of it) overlaps host parsing
with device compute — the TPU analog of the reference's double-duty
IO/compute threads — and reads ahead over the end of a pass.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import subprocess
import threading
import time
import weakref
from typing import Iterable, Iterator, Optional

import sys

import numpy as np

from xflow_tpu.config import DataConfig
from xflow_tpu.data.schema import SparseBatch, make_batch
from xflow_tpu.data.libffm import QuarantineWriter, iter_examples
from xflow_tpu.jsonl import JsonlAppender


class BadRecordError(RuntimeError):
    """A file pass produced more feature-less rows than data.max_bad_rows
    allows — the input is likely garbage (wrong format, truncated upload,
    corrupted shard) and training on it would silently learn nothing from
    those rows. Raised BEFORE the epoch completes (docs/ROBUSTNESS.md)."""


def run_now(fn, *args) -> None:
    """The `defer` of a stream whose effects are nobody's to take back:
    run it on the spot."""
    fn(*args)


def bad_row_indices(batch: SparseBatch):
    """Rows that are REAL (row_mask on) but parsed to ZERO features.

    Both parsers keep such rows (a labeled line is an example even when
    every feature token is malformed — reference parity,
    `load_data_from_disk.cc:150-153`), so this batch-level predicate is
    parser-agnostic by construction: the Python and native paths count
    bad rows identically because the count is taken from the batches
    they both emit, not from their internal line handling."""
    rm = np.asarray(batch.row_mask) > 0
    has_feature = np.asarray(batch.mask).max(axis=1) > 0 if batch.mask.size else rm
    return np.nonzero(rm & ~has_feature)[0]


def monitor_bad_rows(
    batches: Iterator[SparseBatch],
    cfg: DataConfig,
    path: str,
    enforce: bool = True,
    quarantine: bool = True,
    defer=run_now,
) -> Iterator[SparseBatch]:
    """Count (and optionally quarantine) feature-less rows in a batch
    stream; with `enforce`, raise BadRecordError the moment the budget
    is exceeded.

    Everything this leaves behind besides the batches — the counters,
    the quarantine records, the summary line — goes through `defer(fn,
    *args)`: run on the spot by default, and under a `PassProducer`
    handed to the consumer with the batch, so that a batch nobody
    consumed (a read-ahead that was discarded) leaves nothing.

    Bad rows are NOT dropped — dropping would break the row-counter /
    parser parity the multi-process step coordination depends on
    (`count_batches` counts every labeled line). They are counted,
    appended to data.quarantine_path when set (and `quarantine` is on —
    the trainer quarantines only the FIRST training pass over a path, so
    the file holds one record per bad row, not one per epoch), and a
    one-line stderr summary fires at end of stream. `enforce=False`
    (eval/predict passes) still counts and warns but never raises: the
    budget exists to stop garbage from TRAINING in, not to destroy a
    finished model's eval. Multi-process note: the budget check runs on
    each rank's own shard, so an over-budget shard aborts that rank
    loudly (and the job with it) — a garbage shard is a data bug, not a
    condition to coordinate around."""
    from xflow_tpu.telemetry import default_registry

    budget = cfg.max_bad_rows
    qw = QuarantineWriter(cfg.quarantine_path if quarantine else "")
    # pipeline counters (telemetry registry): run totals the trainer
    # snapshots into every metrics-JSONL window record, so batch/row
    # progress and bad-row counts ride the same stream the step
    # decomposition does. Counter is lock-protected: eval streams
    # increment on the prefetch thread, fit's stream where it consumes.
    reg = default_registry()
    c_batches = reg.counter("data.batches")
    c_rows = reg.counter("data.rows")
    c_bad = reg.counter("data.bad_rows")
    total = 0
    try:
        for bi, batch in enumerate(batches):
            defer(c_batches.inc)
            defer(c_rows.inc, batch.num_rows)
            idx = bad_row_indices(batch)
            if idx.size:
                defer(c_bad.inc, int(idx.size))
                if qw.enabled:
                    labels = np.asarray(batch.labels)
                    for r in idx:
                        defer(qw.write, path, bi, int(r), float(labels[r]))
                total += int(idx.size)
                if enforce and 0 <= budget < total:
                    raise BadRecordError(
                        f"{path!r}: {total} feature-less row(s) exceed "
                        f"data.max_bad_rows={budget} — the shard is likely "
                        "malformed (wrong format / truncation / corruption); "
                        "inspect it (data.quarantine_path records the bad "
                        "rows) or raise the budget"
                    )
            yield batch
        if total:
            defer(lambda: print(
                f"xflow: warning: {path}: {total} row(s) parsed to zero "
                f"features (budget data.max_bad_rows={budget})"
                + (f"; quarantined to {cfg.quarantine_path}" if qw.written else ""),
                file=sys.stderr,
            ))
    finally:
        # now (an abandoned pass), and again behind the last deferred
        # write, which reopens the file
        qw.close()
        defer(qw.close)


def examples_to_batches(
    examples: Iterable[tuple[float, np.ndarray, np.ndarray]],
    batch_size: int,
    max_nnz: int,
    drop_remainder: bool = False,
    profiler=None,
) -> Iterator[SparseBatch]:
    if profiler is not None:
        yield from _profiled_examples_to_batches(
            examples, batch_size, max_nnz, drop_remainder, profiler
        )
        return
    labels: list[float] = []
    fields: list[np.ndarray] = []
    slots: list[np.ndarray] = []
    for label, f, s in examples:
        labels.append(label)
        fields.append(f)
        slots.append(s)
        if len(labels) == batch_size:
            yield make_batch(fields, slots, labels, batch_size, max_nnz)
            labels, fields, slots = [], [], []
    if labels and not drop_remainder:
        yield make_batch(fields, slots, labels, batch_size, max_nnz)


def _profiled_examples_to_batches(
    examples, batch_size: int, max_nnz: int, drop_remainder: bool, profiler
) -> Iterator[SparseBatch]:
    """`examples_to_batches` with the batch-assembly ("batch": the
    per-example row accumulation) and padding ("pad": make_batch's
    padded-array fill) stages attributed (telemetry.PipelineProfiler).
    The pull of each example from the iterator is NOT timed here — that
    wall belongs to the upstream read/parse/hash stages."""
    pc = time.perf_counter
    labels: list[float] = []
    fields: list[np.ndarray] = []
    slots: list[np.ndarray] = []
    acc = 0.0
    for label, f, s in examples:
        t0 = pc()
        labels.append(label)
        fields.append(f)
        slots.append(s)
        acc += pc() - t0
        if len(labels) == batch_size:
            t0 = pc()
            b = make_batch(fields, slots, labels, batch_size, max_nnz)
            profiler.add("pad", pc() - t0)
            profiler.add("batch", acc)
            acc = 0.0
            profiler.count_batch(b.num_rows)
            labels, fields, slots = [], [], []
            yield b
    profiler.add("batch", acc)
    if labels and not drop_remainder:
        t0 = pc()
        b = make_batch(fields, slots, labels, batch_size, max_nnz)
        profiler.add("pad", pc() - t0)
        profiler.count_batch(b.num_rows)
        yield b


def assign_shards(
    prefix: str, rank: int, world: int, num_shards: int = 0
) -> list[tuple[int, str]]:
    """Round-robin shard ownership for a topology-elastic world:
    [(shard index, path)] for rank `rank` of `world`.

    `num_shards` is the shard set in play — for a fresh run it equals
    the world size, so rank k owns exactly shard k and this degrades to
    the legacy one-shard-per-rank contract (`lr_worker.cc:210`)
    byte-for-byte. On an elastic resume the trainer passes the
    checkpoint data_state's `num_shards` (the ORIGINAL record set):

    - shrink (world M < num_shards N): rank k owns shards k, k+M,
      k+2M, ... — the surviving ranks cover the full record set, each
      shard resuming at its own stored offset (`skip_batches`), so no
      record trains twice and none is dropped;
    - grow (world M > num_shards N): ranks N..M-1 own the shard of
      their own index, which joins the record set if its file exists
      (a missing shard is the existing ragged-shard tolerance: the
      rank pads with empty batches).

    Shard files need not exist — the batch counters treat a missing
    path as 0 batches, matching the reference's idle-worker behavior.
    """
    n = max(int(num_shards), int(world), 1)
    from xflow_tpu.data.libffm import shard_path

    return [(s, shard_path(prefix, s)) for s in range(int(rank), n, int(world))]


def skip_batches(
    batches: Iterator[SparseBatch], n: int
) -> Iterator[SparseBatch]:
    """Fast-skip the first `n` batches of a stream — the exact-resume
    seam (docs/ROBUSTNESS.md "Elastic recovery"): a resumed run
    re-parses the already-trained prefix (parsing is the cheap part)
    but the skipped batches bypass EVERYTHING downstream — the
    bad-record monitor (no duplicate quarantine records, no double
    budget counting), sorted-plan building, health bitmaps, and the
    device transfer — so the stream continues at the stored offset
    instead of replaying it. Placed UNDER monitor_bad_rows on purpose;
    the generator form keeps prefetch's close() cascade intact."""
    for i, batch in enumerate(batches):
        if i >= n:
            yield batch


def batch_iterator(
    path: str,
    cfg: DataConfig,
    batch_size: Optional[int] = None,
    enforce_bad_rows: bool = True,
    quarantine: bool = True,
    skip: int = 0,
    profiler=None,
    defer=run_now,
) -> Iterator[SparseBatch]:
    """Stream padded batches from a libffm file, preferring the native
    parser. Every batch passes through the bad-record monitor
    (`monitor_bad_rows`): feature-less rows are counted/quarantined
    identically for both parser paths, and exceeding data.max_bad_rows
    raises before an epoch of garbage trains in (eval passes set
    `enforce_bad_rows=False`: count and warn, never kill a finished
    model's predict pass). `skip` fast-forwards the stream past its
    first `skip` batches (checkpointed data_state resume,
    `skip_batches`) — skipped batches are neither monitored nor
    quarantined; they were already, in the run being resumed.
    `profiler` (an armed run's telemetry.PipelineProfiler) accumulates
    per-stage wall time; the `xflow:` spans open with or without it.
    `defer` takes what the stream leaves behind besides its batches
    (`monitor_bad_rows`; the counters and records of opening a shard)."""
    raw = _raw_batch_iterator(path, cfg, batch_size, profiler=profiler, defer=defer)
    if skip > 0:
        raw = skip_batches(raw, skip)
    yield from monitor_bad_rows(
        raw, cfg, path,
        enforce=enforce_bad_rows, quarantine=quarantine, defer=defer,
    )


def _cache_batch_iterator(
    path: str, cfg: DataConfig, bs: int, profiler=None, defer=run_now
) -> Optional[Iterator[SparseBatch]]:
    """The packed-shard-cache fast path (data.cache, docs/DATA.md):
    the verified cache's zero-copy batch iterator for text shard
    `path`, or None to take the text path.

    Failure routing is the quarantine philosophy (docs/ROBUSTNESS.md):
    a cache that fails its digest check — or cannot even be opened —
    is recorded to data.quarantine_path (source/cache/reason/section,
    the same stamped JSONL stream bad rows land in), counted
    (`data.cache_fallbacks`), logged to stderr, and the shard falls
    back to read/parse/hash — NEVER a crash, even under data.cache=on.
    Only a MISSING or config-stale cache under "on" raises (the
    operator asserted cached input; silently re-parsing text would
    un-measure the very gap they forced the cache for)."""
    if cfg.cache not in ("auto", "on"):
        if cfg.cache != "off":
            raise ValueError(
                f"data.cache={cfg.cache!r}: expected auto|on|off"
            )
        return None
    from xflow_tpu.data.shardcache import (
        ShardCacheDigestError,
        ShardCacheError,
        ShardCacheStale,
        cache_path_for,
        resolve_cache,
    )
    from xflow_tpu.telemetry import default_registry

    reg = default_registry()
    try:
        sc = resolve_cache(path, cfg)
    except ShardCacheStale:
        # only reaches here under cache=on (auto folds staleness into
        # a warn-and-return-None inside resolve_cache): the operator
        # asserted cached input and the cache is stale — loud, never a
        # silent text fallback (it would re-measure the very path the
        # cache was forced to replace). Staleness is not corruption:
        # no quarantine record.
        raise
    except ShardCacheError as e:
        record = {
            "source": path,
            "cache": cache_path_for(path, cfg.cache_dir),
            "reason": (
                "cache_digest_mismatch"
                if isinstance(e, ShardCacheDigestError)
                else "cache_unreadable"
            ),
            "section": getattr(e, "section", "?"),
        }
        warning = (
            f"xflow: warning: shard cache for {path!r} failed integrity "
            f"({e}); quarantined, falling back to the text path"
        )

        def fell_back() -> None:
            reg.counter("data.cache_fallbacks").inc()
            qw = JsonlAppender(cfg.quarantine_path)
            qw.append(record)
            qw.close()
            print(warning, file=sys.stderr)

        defer(fell_back)
        return None
    if sc is None:
        return None
    defer(reg.counter("data.cache_shards").inc)
    return sc.iter_batches(bs, cfg.drop_remainder, profiler=profiler)


def _warn_python_parser(err: BaseException) -> None:
    """The native parser could not be built or loaded: say so once,
    with the reason — the Python parser that takes over is the ~28×
    host gap docs/PERF.md measured, and nothing else in a run's output
    would show it."""
    from xflow_tpu.telemetry import warn_once

    warn_once(
        "native_parser",
        f"native parser unavailable ({type(err).__name__}: {err}); "
        "the Python parser runs instead (far slower)",
    )


def _raw_batch_iterator(
    path: str,
    cfg: DataConfig,
    batch_size: Optional[int] = None,
    profiler=None,
    defer=run_now,
) -> Iterator[SparseBatch]:
    from xflow_tpu.telemetry import default_registry, span

    bs = batch_size or cfg.batch_size
    cached = _cache_batch_iterator(path, cfg, bs, profiler=profiler, defer=defer)
    if cached is not None:
        yield from cached
        return
    if cfg.use_native_parser:
        native_iter = None
        try:
            # only import/construction is guarded: a failure mid-iteration
            # must surface, not silently restart the file with the Python
            # parser (which would duplicate already-yielded batches)
            from xflow_tpu.data.native import native_batch_iterator

            native_iter = native_batch_iterator(path, cfg, bs)
        except FileNotFoundError:
            raise  # a missing input is the user's error, not a fallback case
        except (ImportError, OSError, RuntimeError, subprocess.SubprocessError) as e:
            _warn_python_parser(e)
        if native_iter is not None:
            defer(default_registry().counter("data.parser_native_shards").inc)
            # the C parser does read+parse+hash+assembly+pad inside one
            # next_batch call — one "parse" span a batch, the honest
            # resolution this path offers (docs/OBSERVABILITY.md)
            while True:
                with span("parse", profiler):
                    b = next(native_iter, None)
                if b is None:
                    return
                if profiler is not None:
                    profiler.count_batch(b.num_rows)
                yield b
    defer(default_registry().counter("data.parser_python_shards").inc)
    yield from examples_to_batches(
        iter_examples(path, cfg.log2_slots, cfg.hash_salt, profiler=profiler),
        bs,
        cfg.max_nnz,
        cfg.drop_remainder,
        profiler=profiler,
    )


def count_batches(path: str, cfg: DataConfig, batch_size: Optional[int] = None) -> int:
    """Number of batches `batch_iterator` will yield for `path`.

    Uses the row counter matching the parser that will actually run
    (native predicate for the native path, parse_line predicate for the
    Python path) so multi-process step coordination can be computed with
    ONE collective per epoch instead of one allgather per step.
    """
    bs = batch_size or cfg.batch_size
    rows = None
    if cfg.use_native_parser:
        try:
            from xflow_tpu.data.native import native_count_rows

            rows = native_count_rows(path, cfg.block_bytes)
        except FileNotFoundError:
            raise
        except (ImportError, OSError, RuntimeError, subprocess.SubprocessError) as e:
            _warn_python_parser(e)  # toolchain missing: the Python parser will run
    if rows is None:
        from xflow_tpu.data.libffm import count_rows

        rows = count_rows(path)
    return rows // bs if cfg.drop_remainder else -(-rows // bs)


@dataclasses.dataclass(frozen=True)
class PassSpec:
    """What one pass of the trainer's batch stream is opened with
    (`Trainer._coordinated_batches`' arguments, normalised): equal
    specs over unchanged shards give the same batches in the same
    order."""

    shards: tuple  # ((shard index, path), ...) in streaming order
    skips: tuple  # ((shard index, batches to fast-forward), ...), one a shard
    enforce_bad_rows: bool = True
    quarantine: bool = True
    track_health: bool = True
    profiled: bool = False


def shard_stats(shards: tuple) -> tuple:
    """What `os.stat` says of each shard now — (exists, size, mtime_ns,
    inode) — the half of a read-ahead's key that the arguments do not
    hold: a shard that appeared, went, grew, shrank or was replaced
    since reads differently."""
    out = []
    for _, path in shards:
        try:
            st = os.stat(path)
            out.append((True, st.st_size, st.st_mtime_ns, st.st_ino))
        except OSError:
            out.append((False, 0, 0, 0))
    return tuple(out)


class _Deferred:
    """A pass's `defer` under a `PassProducer`: what building an item
    would leave behind is collected, in order, and travels with the
    item; the consumer runs it when it takes the item."""

    def __init__(self):
        self._calls: list = []

    def defer(self, fn, *args) -> None:
        self._calls.append((fn, args))

    def take(self) -> list:
        calls, self._calls = self._calls, []
        return calls


class DeferredProfiler:
    """The profiler a deferred pass's spans and parsers see: each
    accumulation becomes one of the item's deferred calls on the real
    `PipelineProfiler`."""

    def __init__(self, profiler, defer):
        self._prof, self._defer = profiler, defer

    def add(self, stage: str, seconds: float) -> None:
        self._defer(self._prof.add, stage, seconds)

    def add_many(self, stages: dict) -> None:
        self._defer(self._prof.add_many, stages)

    def count_batch(self, rows: int) -> None:
        self._defer(self._prof.count_batch, rows)


_END = object()  # the end-of-pass mark in a producer's queue


class PassProducer:
    """One warm thread that builds the batch stream pass after pass
    into a bounded queue, and reads ahead over the end of a pass.

    `open_pass(spec, defer)` gives one pass's items. The worker builds
    the pass the consumer is on (`start`), hands it the end-of-pass
    mark, and — when that pass was begun with a `then` — goes straight
    on to build `then`, the pass that comes next if nothing changes,
    until the queue is full: `depth` items ready and one in hand, host
    arrays only. That head start is the `xflow:read_ahead` span. The
    consumer's next pass takes it over (`adopt`) if and only if it
    would have opened the same stream — an equal `PassSpec`, and
    `shard_stats` now equal to what they were before the read-ahead
    opened its first shard — and otherwise stops the producer (`stop`:
    a signal, no join) and builds its own.

    Nothing a discarded read-ahead did stays: items reach the consumer
    as (item, deferred calls) and the calls — the bad-row monitor's
    counters and quarantine records, health tracking, the profiler's
    stage sums — run on the consumer's thread as it takes the item
    (`batches`), never before. An error raised while building travels
    the same way and is raised where a fresh iterator would raise it.

    Without a `then` the producer is a plain prefetch of one pass
    (`prefetch`): its worker ends with the pass and `batches` joins it.
    Abandonment-safe as before: a consumer that drops `batches()`
    mid-pass (an exception in the fit loop, an early break) stops the
    worker, which closes the pass's iterator — native parser handles,
    the quarantine file — and exits. So does the death of `owner`.

    Time the worker spends blocked on a full queue is the
    `producer_wait` span; with a `profiler` it is accumulated — by the
    consumer, whose take ends the wait, so it falls into the window it
    ended in; not while a read-ahead waits to be adopted: nobody is
    consuming then — as are both sides' queue-depth samples. The
    consumer-side starvation signal (`data_wait`) is the fit loop's,
    not here."""

    def __init__(self, open_pass, depth: int = 2, profiler=None, owner=None):
        self._open = open_pass
        self._depth = depth
        self._prof = profiler
        # no thread outlives `owner` (the trainer that carries this
        # producer between passes; `open_pass` must not hold it either)
        self._owned = weakref.finalize(owner, self.close) if owner is not None else None
        self._cv = threading.Condition()
        self._buf: collections.deque = collections.deque()  # (item, deferred calls)
        self._stopped = False
        self._live = 0  # the pass the consumer is on
        self._pass = 0  # the pass the worker is building: _live, or _live + 1 read ahead
        self._then: Optional[PassSpec] = None  # what follows the live pass
        self._key: Optional[tuple] = None  # (spec, shard_stats) the read-ahead opened on
        self._head = 0  # batches the read-ahead has built
        self._blocked_at: Optional[float] = None  # since when the worker waits for room
        self._thread: Optional[threading.Thread] = None
        # the worker's own: what the item it is building leaves behind,
        # and the open xflow:read_ahead span
        self._fx = _Deferred()
        self._ahead = contextlib.ExitStack()

    # ------------------------------------------------------- the consumer's
    def start(self, spec: Optional[PassSpec], then: Optional[PassSpec] = None) -> None:
        with self._cv:
            self._then = then
        self._thread = threading.Thread(
            target=self._work, args=(spec,), daemon=True, name="xflow-prefetch"
        )
        self._thread.start()

    def adopt(self, spec: PassSpec, then: Optional[PassSpec]) -> Optional[int]:
        """Take over the read-ahead as the pass `spec`: the batches it
        had built by now, or None where it is not the stream a new
        iterator over `spec` would give (then `stop` it)."""
        key = (spec, shard_stats(spec.shards))
        with self._cv:
            if self._stopped or self._pass != self._live + 1 or self._key != key:
                return None
            self._live += 1
            self._then = then
            self._cv.notify_all()
            return self._head

    def stop(self) -> int:
        """Signal the worker to close its pass and exit, and drop what
        is queued. Returns the batches of an unadopted read-ahead that
        go with it."""
        with self._cv:
            dropped = self._head if self._pass > self._live and not self._stopped else 0
            self._stopped = True
            self._buf.clear()
            self._cv.notify_all()
        if self._owned is not None:
            self._owned.detach()
        return dropped

    def close(self) -> None:
        """`stop`, and wait for the thread to be gone (the owner's
        finalizer; a pass abandoned or ended for good)."""
        self.stop()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=10.0)

    def batches(self) -> Iterator:
        """The live pass's items, each after its deferred calls."""
        from xflow_tpu.telemetry import span

        ended = False
        try:
            while True:
                with self._cv:
                    while not self._buf and not self._stopped:
                        self._cv.wait()
                    if self._stopped:
                        raise RuntimeError("the batch producer was stopped under its consumer")
                    item, calls = self._buf.popleft()
                    depth = len(self._buf)
                    blocked_at, self._blocked_at = self._blocked_at, None
                    self._cv.notify_all()
                for fn, args in calls:
                    fn(*args)
                if self._prof is not None:
                    if blocked_at is not None:
                        self._prof.add("producer_wait", time.perf_counter() - blocked_at)
                    self._prof.observe_queue(depth, self._depth)
                if item is _END:
                    ended = True
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            with span("iter_end"):
                if not ended or self._then is None:
                    self.close()

    # --------------------------------------------------------- the worker's
    def _put(self, item) -> bool:
        """Queue `item` with what building it deferred; False once
        stopped. The head start's span is over when the pass is
        adopted, or when nothing but adoption can make room: the queue
        full of its own items."""
        from xflow_tpu.telemetry import span

        calls = self._fx.take()
        with self._cv:
            live = self._pass <= self._live
            full = len(self._buf) >= self._depth
            if not live and item is not _END and not isinstance(item, BaseException):
                self._head += 1
            if live or (full and not any(i is _END for i, _ in self._buf)):
                self._ahead.close()
            with span("producer_wait"):
                if full and live:
                    # the consumer's next take ends this wait and books
                    # it, into the window it ends in
                    self._blocked_at = time.perf_counter()
                while len(self._buf) >= self._depth and not self._stopped:
                    self._cv.wait()
            if self._stopped:
                return False
            self._buf.append((item, calls))
            depth = len(self._buf)
            self._cv.notify_all()
        if live and self._prof is not None:
            self._prof.observe_queue(depth, self._depth)
        return True

    def _work(self, spec: Optional[PassSpec]) -> None:
        from xflow_tpu.telemetry import span

        it = None
        try:
            while True:
                it = self._open(spec, self._fx.defer)
                for item in it:
                    if not self._put(item):
                        return
                it = None
                # the pass is built: once it is the consumer's, mark its
                # end and read ahead over it
                with self._cv:
                    while self._pass > self._live and not self._stopped:
                        self._cv.wait()
                    if self._stopped:
                        return
                    spec = self._then  # stands until the next adoption
                if spec is not None:
                    # the key BEFORE the mark: nobody can ask for the
                    # read-ahead before its key stands, and a shard
                    # that changes from here on changes the key
                    key = (spec, shard_stats(spec.shards))
                    with self._cv:
                        self._key = key
                        self._pass += 1
                        self._head = 0
                if not self._put(_END) or spec is None:
                    return
                self._ahead.enter_context(span("read_ahead"))
        except BaseException as e:  # re-raised in the consumer
            self._put(e)
        finally:
            self._ahead.close()
            close = getattr(it, "close", None)
            if close is not None:
                close()


def prefetch(
    iterator: Iterator[SparseBatch], depth: int = 2, profiler=None
) -> Iterator[SparseBatch]:
    """Run the parse/batch pipeline in a background thread with a
    bounded queue: one pass of a `PassProducer`, which see. The thread
    starts at the consumer's first `next()`; the pass's terminating
    `next()` joins it (`iter_end`)."""
    producer = PassProducer(lambda spec, defer: iterator, depth, profiler)
    producer.start(None)
    yield from producer.batches()


# --------------------------------------------------------------- streaming
@dataclasses.dataclass(frozen=True)
class IngestSegment:
    """One sealed unit of tail-followed input (data.stream=tail): the
    newly COMPLETED lines of a watched shard, spooled into an immutable
    segment file (plus its .xfc cache when conversion is on) and
    stamped with the ingest trace context the freshness tooling follows
    across the train/serve boundary (docs/SERVING.md "Freshness")."""

    trace: str       # 16-hex ingest trace id (tracing.new_id)
    seq: int         # monotone segment number within this follower
    source: str      # the watched text shard the bytes came from
    offset: int      # byte offset of the segment's start in `source`
    rows: int        # labeled examples in the segment
    bytes: int       # segment length in bytes
    path: str        # the sealed spool file (immutable once yielded)
    cache: str       # its .xfc sidecar ("" = text path)
    ingest_ts: float # wall anchor: when the segment sealed


def stream_dir_for(prefix: str, cfg: DataConfig) -> str:
    """Where a tail follower spools segments: data.stream_dir, or an
    `.xfstream` dir next to the watched shards."""
    if cfg.stream_dir:
        return cfg.stream_dir
    return os.path.join(os.path.dirname(prefix) or ".", ".xfstream")


class TailFollower:
    """Follow-the-tail streaming source (data.stream=tail).

    Watches the `<prefix>-NNNNN` shard set (or `prefix` itself when it
    is a file) for new or growing libffm files. Each poll cuts every
    shard's newly completed lines — a trailing row without its newline
    is DEFERRED until more bytes land, never quarantined: a writer
    mid-append is the normal case, not a malformed input — into one
    immutable spool segment, converts it on arrival into a packed .xfc
    cache (data.cache auto/on) so streamed data rides the same
    device-rate path batch training does, and stamps it with a fresh
    ingest trace id + wall anchor carried as a `kind="ingest"` record.
    Consumers iterate sealed segments only, so the batch-count drift
    guard downstream never sees a file change mid-pass.

    Rotation: a shard whose size SHRANK below the follower's offset was
    rotated/recreated — the offset resets to 0 and the new contents
    stream from the top. `data.stream_idle_s` bounds the follow: no new
    complete rows for that long ends the stream (0 = follow forever).

    `clock`/`wall` are injectable for tests (monotonic pacing vs the
    wall anchor stamped into records)."""

    def __init__(
        self,
        prefix: str,
        cfg: DataConfig,
        appender: Optional[JsonlAppender] = None,
        clock=time.monotonic,
        wall=time.time,
    ):
        self._prefix = prefix
        self._cfg = cfg
        self._app = appender
        self._poll_s = max(float(cfg.stream_poll_s), 0.01)
        self._idle_s = max(float(cfg.stream_idle_s), 0.0)
        self._dir = stream_dir_for(prefix, cfg)
        self._clock = clock
        self._wall = wall
        self._offsets: dict[str, int] = {}
        self._seq = 0
        self._stop = threading.Event()

    def _sources(self) -> list[str]:
        from xflow_tpu.data.libffm import available_shards

        if os.path.isfile(self._prefix):
            return [self._prefix]
        return available_shards(self._prefix)

    def poll(self) -> list[IngestSegment]:
        """One directory scan: seal and return every shard's newly
        completed lines (possibly empty)."""
        segs: list[IngestSegment] = []
        for src in self._sources():
            try:
                size = os.path.getsize(src)
            except OSError:
                continue  # raced a rotation; next poll sees the truth
            off = self._offsets.get(src, 0)
            if size < off:
                # rotation/truncation: the file restarted under us —
                # follow the NEW contents from the top
                off = self._offsets[src] = 0
            if size <= off:
                continue
            with open(src, "rb") as f:
                f.seek(off)
                data = f.read(size - off)
            nl = data.rfind(b"\n")
            if nl < 0:
                continue  # truncated tail row: defer, never quarantine
            chunk = data[: nl + 1]
            seg = self._seal(src, off, chunk)
            self._offsets[src] = off + len(chunk)
            if seg is not None:
                segs.append(seg)
        return segs

    def _seal(self, src: str, off: int, chunk: bytes) -> Optional[IngestSegment]:
        from xflow_tpu.data.libffm import count_rows
        from xflow_tpu.telemetry import default_registry
        from xflow_tpu.tracing import new_id

        os.makedirs(self._dir, exist_ok=True)
        spool = os.path.join(self._dir, "segment-%06d" % self._seq)
        seq, self._seq = self._seq, self._seq + 1
        tmp = spool + ".tmp"
        with open(tmp, "wb") as f:
            f.write(chunk)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, spool)
        rows = count_rows(spool)
        if rows == 0:
            return None  # blank/label-less lines: the offset still advances
        cache = ""
        if self._cfg.cache in ("auto", "on"):
            from xflow_tpu.data.shardcache import cache_path_for, write_shard_cache

            try:
                write_shard_cache(spool, self._cfg)
                cache = cache_path_for(spool, self._cfg.cache_dir)
            except Exception as e:
                # conversion is an optimization: a failed build logs
                # and the segment trains through the text path
                print(
                    f"xflow: warning: convert-on-arrival failed for "
                    f"{spool!r} ({e}); training the segment from text",
                    file=sys.stderr,
                )
        seg = IngestSegment(
            trace=new_id(), seq=seq, source=src, offset=off, rows=rows,
            bytes=len(chunk), path=spool, cache=cache,
            ingest_ts=round(self._wall(), 6),
        )
        reg = default_registry()
        reg.counter("data.ingest_segments").inc()
        reg.counter("data.ingest_rows").inc(rows)
        if self._app is not None:
            self._app.append({
                "kind": "ingest",
                "trace": seg.trace,
                "seq": seg.seq,
                "source": seg.source,
                "offset": seg.offset,
                "rows": seg.rows,
                "bytes": seg.bytes,
                "cache": seg.cache,
                "ingest_ts": seg.ingest_ts,
            })
        return seg

    def segments(self) -> Iterator[IngestSegment]:
        """The blocking segment stream: polls at stream_poll_s, ends on
        close() or after stream_idle_s without new complete rows."""
        last_new = self._clock()
        while not self._stop.is_set():
            segs = self.poll()
            if segs:
                last_new = self._clock()
                for seg in segs:
                    yield seg
                continue
            if self._idle_s and self._clock() - last_new >= self._idle_s:
                return
            self._stop.wait(self._poll_s)

    def close(self) -> None:
        self._stop.set()
