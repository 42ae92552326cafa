"""Training orchestration.

The reference's worker loop (`LRWorker::batch_training`,
`/root/reference/src/model/lr/lr_worker.cc:179-205`: epochs → IO blocks
→ thread fan-out → Pull/compute/Push) and its rank-0 predict pass
(`lr_worker.cc:207-217`) become: epochs → prefetched padded batches →
one jitted SPMD step; then an eval pass that dumps
``pred_<rank>_<block>.txt`` rows (``pctr\\t1-label\\tlabel``,
`lr_worker.cc:67`) and prints logloss/AUC like `base.h:101-108`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from xflow_tpu.config import Config
from xflow_tpu.jsonl import JsonlAppender
from xflow_tpu.data.pipeline import (
    DeferredProfiler,
    PassProducer,
    PassSpec,
    run_now,
    assign_shards,
    batch_iterator,
    count_batches,
    prefetch,
)
from xflow_tpu.metrics import auc_logloss
from xflow_tpu.models import get_model
from xflow_tpu.telemetry import (
    HOST_COUNTERS,
    HangWatchdog,
    HealthMonitor,
    PipelineProfiler,
    StepTimer,
    TraceWindow,
    default_registry,
    hbm_window_fields,
    host_fields,
    install_stack_dump_handler,
    pipeline_fields,
    resolve_restart_gen,
    resolve_run_id,
    span,
)
from xflow_tpu.optim import get_optimizer
from xflow_tpu.train.engine import resolve_engine
from xflow_tpu.train.state import TrainState, build_state
from xflow_tpu.train.step import nonfinite_guard_on


class NonFiniteHalt(RuntimeError):
    """Raised by fit() when the non-finite guard aborts the run
    (train.nonfinite_guard=halt, or nonfinite_max_consecutive discarded
    steps in a row under skip). A checkpoint of the last GOOD state was
    committed before raising whenever train.checkpoint_dir is set."""


@dataclass
class TrainResult:
    steps: int = 0
    epochs: int = 0
    examples: int = 0
    seconds: float = 0.0
    last_loss: float = float("nan")
    auc: float = float("nan")
    logloss: float = float("nan")
    occupancy: dict = field(default_factory=dict)
    interrupted: int = 0  # signal number when preempted mid-run (A3)
    bad_steps: int = 0  # non-finite updates discarded by the guard
    # train batches the fullshard engine handed to the GSPMD row-major
    # step (too skewed for data.fullshard_slack)
    fullshard_overflow_batches: int = 0
    # train batches FFM's sorted engine handed to the row-major step (a
    # row with two occurrences of one field: no placement exists)
    ffm_rowmajor_batches: int = 0
    # state leaves re-laid into the engine's layout during this fit():
    # a state that came in from outside a step, once (`_place_state`)
    state_leaves_placed: int = 0
    # the pass boundary's read-ahead (`Trainer._carried_pass`): passes
    # this fit() took over from the running producer, the batches that
    # producer had built for them by then, and the batches of read-aheads
    # this fit() had to throw away (another stream than they assumed)
    read_ahead_passes: int = 0
    read_ahead_batches: int = 0
    read_ahead_discarded: int = 0
    # what the sorted kernels did with this fit()'s plans, summed from
    # the step records' `host` (`telemetry.HOST_COUNTERS`; a run with the
    # host timeline armed): (window, chunk) pairs computed on, chunks loaded
    chunk_visits: int = 0
    chunk_loads: int = 0

    @property
    def examples_per_sec(self) -> float:
        return self.examples / self.seconds if self.seconds > 0 else 0.0


def resolve_eval_buckets(value: int, multiproc: bool) -> int:
    """train.eval_buckets -1 = auto: exact single-process; bucketed
    (65536) multi-process, so the default pod-scale config has ZERO
    per-batch host collectives (the exact path allgathers a stacked
    [B, 3] array per eval batch — round-2 weak #5). Depends only on
    config + process count, identical on every process — a per-rank
    choice would mismatch the collective sequences and deadlock."""
    return value if value >= 0 else (65536 if multiproc else 0)


class MetricsLogger(JsonlAppender):
    """Structured per-step metrics: JSONL to a file, or quiet.

    Lifecycle (lazy open with parent-dir creation, flush-per-record,
    reopen-safe close) comes from the shared appender (xflow_tpu/jsonl.py)
    — fit() closes the logger in its finally, and a later record (a
    second fit() on the same Trainer) transparently reopens in append
    mode."""

    log = JsonlAppender.append


class _BatchPrep:
    """Batch -> (batch, step-input arrays), and a `PassSpec` -> its
    stream of them: what the prefetch thread runs. It holds the
    trainer's host-side parts and never the `Trainer` or its state, so
    a producer carried from pass to pass keeps neither alive."""

    def __init__(self, cfg: Config, engine, health: HealthMonitor, profiler):
        self._data = cfg.data
        # MVM and FFM key their views/blocks on the field id: a field >=
        # num_fields would be silently dropped by the one-hot, so reject
        # it loudly
        self._num_fields = (
            cfg.model.num_fields if cfg.model.name in ("mvm", "ffm") else 0
        )
        self._batch_arrays = engine.batch_arrays
        self._health = health
        self._prof = profiler

    def __call__(self, batch, track_health: bool = True, profiler=None, defer=run_now):
        """Validation + sorted-plan building happen HERE so that, on
        the prefetch thread, the host-side sort overlaps device compute
        instead of serializing with dispatch. Training batches also
        feed the health monitor's touched-slot bitmap (through `defer`:
        where the batch is consumed; eval passes skip it). The array
        build — sorted plan, dedup — is the "plan" span, which
        `profiler` (armed runs) accumulates."""
        if self._num_fields:
            max_field = int(np.max(batch.fields)) if batch.fields.size else 0
            if max_field >= self._num_fields:
                raise ValueError(
                    f"libffm field id {max_field} >= model.num_fields="
                    f"{self._num_fields}; raise model.num_fields"
                )
        if track_health:
            defer(self._health.observe_batch, batch.slots, batch.mask)
        with span("plan", profiler):
            arrays = self._batch_arrays(batch, profiler)
        return batch, arrays

    def open_pass(self, spec: PassSpec, defer=run_now):
        """One pass over `spec`'s shards. A REAL generator (map objects
        have no close): the producer's abandonment path close()s it,
        which cascades into batch_iterator's finally — native parser
        handles and the quarantine file release promptly, not at some
        later GC."""
        prof = (
            DeferredProfiler(self._prof, defer)
            if spec.profiled and self._prof is not None else None
        )
        skips = dict(spec.skips)
        for idx, p in spec.shards:
            if not os.path.exists(p):
                continue  # ragged/elastic worlds: a missing shard idles
            for b in batch_iterator(
                p, self._data,
                enforce_bad_rows=spec.enforce_bad_rows, quarantine=spec.quarantine,
                skip=skips.get(idx, 0), profiler=prof, defer=defer,
            ):
                bb, arrays = self(b, spec.track_health, prof, defer)
                arrays["_shard"] = idx
                yield bb, arrays


class Trainer:
    def __init__(self, cfg: Config, mesh=None, process_index: int = 0):
        self.cfg = cfg
        self.model = get_model(cfg.model.name)
        self.optimizer = get_optimizer(cfg.optim.name)
        self.mesh = mesh
        self.rank = process_index
        # provenance stamp: every metrics record carries ts/rank/run_id
        # (jsonl.JsonlAppender) so per-rank streams from one run join.
        # Built BEFORE the engines: the compile recorder below is the
        # seam every step/predict jit routes through, and its
        # kind="compile" records land in the same stamped stream.
        self.run_id = resolve_run_id()
        # multi-slice identity: slice j stamps rank j (XFLOW_PROCESS_ID,
        # exported by launch-multislice) even though each slice is
        # process 0 of its own single-process world — the shared
        # watchdog and metrics_report key per-slice streams on the rank
        # stamp. Everyone else keeps the process index, byte-identical.
        self._stamp_rank = self.rank
        if os.environ.get("XFLOW_SLICE") is not None:
            from xflow_tpu.telemetry import resolve_rank

            self._stamp_rank = resolve_rank()
        self.metrics = MetricsLogger(
            cfg.train.metrics_path,
            stamp={"rank": self._stamp_rank, "run_id": self.run_id},
            max_bytes=cfg.train.metrics_max_bytes,
        )
        # lazily-started background checkpoint writer (train.ckpt_async)
        self._ckpt_writer = None
        # compile accounting (train.compile_metrics, docs/OBSERVABILITY.md
        # "Compile accounting"): explicit timed .lower().compile() per
        # program with XLA cost/memory analysis; recompiles counted
        from xflow_tpu.telemetry import CompileRecorder

        self.compile_recorder = (
            CompileRecorder(sink=self.metrics)
            if cfg.train.compile_metrics
            else None
        )
        # packed shard cache (data/shardcache.py, docs/DATA.md):
        # validated at CONSTRUCTION like the guard/dedup modes (identical
        # config on every rank → rank-symmetric), not on the first shard
        # open deep inside the prefetch thread
        if cfg.data.cache not in ("auto", "on", "off"):
            raise ValueError(
                f"data.cache={cfg.data.cache!r}: expected auto|on|off"
            )
        # which step program runs a batch, how a batch becomes its input
        # and the per-batch fallback are the engine's (train/engine.py);
        # the state is born in the engine's shardings
        self._engine = resolve_engine(
            cfg, mesh, self.model, self.optimizer, self.compile_recorder
        )
        self._build_state(self._engine.state_shardings)
        # the loops call the step programs through these two names:
        # tests and the benchmark's planted faults replace them
        self.train_step = self._engine.train_step
        self.eval_step = self._engine.eval_step
        # model-health monitor (train.health_metrics, docs/OBSERVABILITY.md
        # "Health metrics"): consumes the step builders' fused norm
        # scalars one step behind, owns the loss EMA and the
        # occupancy/collision gauges. Validated at CONSTRUCTION like the
        # guard mode (identical config on every rank → rank-symmetric).
        from xflow_tpu.train.step import health_mode

        self._health = HealthMonitor(
            mode=health_mode(cfg),
            ema_decay=cfg.train.health_ema_decay,
            num_slots=cfg.num_slots,
        )
        # the host timeline's window (docs/OBSERVABILITY.md "The host
        # timeline"): armed when somebody listens — the metrics stream
        # (step records' `host` and `boundary`), a profile, or the
        # opt-in kind="pipeline" surface, which alone publishes
        # pipeline records and gauges. None otherwise: the same spans
        # run and nothing accumulates. Threaded through the TRAINING
        # stream only (fit passes profiled=True to _coordinated_batches;
        # eval streams stay unprofiled so a mid-run holdout pass never
        # muddies the training attribution).
        self.pipeline_prof = (
            PipelineProfiler(publish=cfg.train.pipeline_metrics)
            if cfg.train.pipeline_metrics
            or cfg.train.metrics_path
            or cfg.train.profile_dir
            else None
        )
        # the previous fit()'s tail — last step ready, its parts, its
        # return instant — for the next fit()'s `boundary`
        self._prev_fit: Optional[dict] = None
        # what the prefetch thread runs, and the training stream's
        # producer: kept running over the end of a pass, so the next
        # pass (of this fit() or the next) opens on a full queue
        # (`_carried_pass`). It goes when the trainer goes.
        self._prep = _BatchPrep(cfg, self._engine, self._health, self.pipeline_prof)
        self._read_ahead: Optional[PassProducer] = None
        # liveness heartbeat (train.heartbeat_path): tiny {step} records
        # the launcher watchdog and metrics_report --health read to flag
        # dead ranks and stragglers; kind="heartbeat" keeps the stream
        # distinct from metrics when both land in one run dir
        self.heartbeat = JsonlAppender(
            cfg.train.heartbeat_path,
            stamp={
                "rank": self._stamp_rank,
                "run_id": self.run_id,
                "kind": "heartbeat",
            },
        )
        # cross-slice bounded-staleness sync tier (sync.mode, parallel/
        # multislice.py, docs/DISTRIBUTED.md "Multi-slice bounded
        # staleness"): the fit loop publishes/gathers additive table
        # deltas every sync.every_steps steps, OUTSIDE the jit programs.
        # None when off — the default path stays byte-identical.
        self._syncer = None
        if cfg.sync.mode != "off":
            from xflow_tpu.parallel.multislice import SliceSyncer
            from xflow_tpu.telemetry import resolve_num_slices, resolve_slice

            self._syncer = SliceSyncer(
                cfg.sync,
                slice_id=resolve_slice() or 0,
                num_slices=resolve_num_slices(),
            )
        # data-stream position for exact resume (elastic recovery,
        # docs/ROBUSTNESS.md): (epoch, batches consumed within it) plus
        # the TOPOLOGY-INDEPENDENT truth — per-SHARD consumed-batch
        # counts (_shard_pos) and the shard set in play (_num_shards) —
        # maintained by the fit loop and snapshotted into every
        # checkpoint's data_state, so a run checkpointed at N ranks
        # resumes at M ranks with exact record-set coverage.
        # _examples_seen counts THIS process's rows this generation;
        # _examples_base carries the restored GLOBAL total forward.
        # _resume_data_state holds what maybe_restore read back,
        # consumed by the next fit().
        self._epoch_pos = (0, 0)
        self._shard_pos: dict = {}
        self._num_shards = 0
        self._examples_seen = 0
        self._examples_base = 0
        self._resume_data_state: Optional[dict] = None
        # time-decayed eval window (train.eval_window_decay): the
        # (BucketAUC, ll_sum, n_rows) accumulator the streaming eval
        # passes decay-and-fold into; None until the first decayed pass
        self._eval_window: Optional[tuple] = None
        # validate the guard mode at CONSTRUCTION (identical config on
        # every rank → rank-symmetric), not on the first bad batch
        self._guarded = nonfinite_guard_on(cfg)

    def _build_state(self, shardings=None) -> None:
        """The run's first state, born in its shardings (`build_state`),
        under `xflow:init_state`; the span's kind="init_state" record
        says how large the state is, whole and on each device."""
        with span("init_state") as built:
            self.state = build_state(self.model, self.optimizer, self.cfg, shardings)
        if self.metrics.enabled:
            leaves = jax.tree.leaves(self.state)
            self.metrics.log({
                "kind": "init_state",
                "dur_ms": round(built.seconds * 1e3, 3),
                "state_bytes_total": sum(x.nbytes for x in leaves),
                # every device holds one shard of every leaf
                "state_bytes_per_device": sum(
                    math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
                    for x in leaves
                ),
            })

    def _place_state(self, res: Optional[TrainResult] = None) -> None:
        """Put `self.state` into the layout the engine's step programs
        are compiled to take it in (`Engine.place_state`). Called where
        a state that came in from outside a step — `build_state`'s, a
        restored checkpoint, an adopted or synced snapshot, leaves a
        caller assigned — next meets a program: the entry of `fit()` and
        `evaluate()`, and after a sync round. Leaves already placed cost
        a comparison each; a placement that moved bytes writes one
        kind="place_state" record and counts into `res`."""
        with span("place_state") as placed:
            self.state, moved, nbytes = self._engine.place_state(self.state)
        if not moved:
            return
        if res is not None:
            res.state_leaves_placed += moved
        self.metrics.log({
            "kind": "place_state",
            "leaves_moved": moved,
            "bytes": nbytes,
            "dur_ms": round(placed.seconds * 1e3, 3),
        })

    def _count_fallback(self, res: TrainResult, arrays: dict) -> None:
        """Count a train batch that left the engine's own step, under
        the name its engine has for that (`Engine.fallback_counter`)."""
        if self._engine.fell_back(arrays):
            name = self._engine.fallback_counter
            setattr(res, name, getattr(res, name) + 1)

    def _fallback_fields(self, res: TrainResult) -> dict:
        """The final record's fallback count, on the engines that have one."""
        name = self._engine.fallback_counter
        return {name: getattr(res, name)} if name else {}

    @property
    def engine(self) -> str:
        """The table engine the step dispatches to (train/engine.py):
        "sorted" (windowed Pallas kernels on a TPU) or "row_major" (XLA
        gather/scatter) on one device; "fullshard" or "gspmd" on a mesh."""
        return self._engine.name

    @property
    def planner(self) -> Optional[str]:
        """What builds the sorted plans ("native" | "python"); None on
        the row-major engines, which plan nothing."""
        return self._engine.planner

    # -------------------------------------------------------- multi-process IO
    def _empty_batch(self):
        from xflow_tpu.data.schema import SparseBatch

        B, F = self.cfg.data.batch_size, self.cfg.data.max_nnz
        return SparseBatch(
            slots=np.zeros((B, F), np.int32),
            fields=np.zeros((B, F), np.int32),
            mask=np.zeros((B, F), np.float32),
            labels=np.zeros((B,), np.float32),
            row_mask=np.zeros((B,), np.float32),
        )

    def _epoch_batch_count(
        self, shards: list, skips: dict
    ) -> tuple[int, int]:
        """(global_steps, local_batches) for one pass over this rank's
        assigned `shards` ([(shard index, path)]), with each shard's
        stored `skips` offset fast-forwarded (data_state resume; the
        skip map comes from the checkpoint so it is identical on every
        rank, and each rank subtracts only its OWN shards' offsets —
        rank-symmetric by construction).

        SPMD steps are collective: if process A has 10 batches and process
        B has 9 (ragged shards — the reference tolerates this because its
        async workers never synchronize), B would deadlock A. Instead of
        a per-step host allgather (which dominates at µs-scale step times,
        round-1 weak #5), each process counts its local batches with the
        parser-matched row counter, and ONE allgather per epoch pass
        fixes the global step count = max over processes. Re-counted every
        pass (not cached) so shards that appear, grow, or shrink between
        epochs are picked up. A missing shard counts as 0 batches
        (reference: rank k simply finds no `<prefix>-%05d` file and its
        workers idle).
        """
        local = 0
        for idx, path in shards:
            try:
                n = count_batches(path, self.cfg.data)
            except FileNotFoundError:
                n = 0
            local += max(n - max(int(skips.get(idx, 0)), 0), 0)
        if jax.process_count() == 1:
            return local, local
        from jax.experimental import multihost_utils

        counts = np.asarray(multihost_utils.process_allgather(np.int32(local)))
        return int(counts.max()), local

    def _with_arrays(self, batch, track_health: bool = True):
        """(batch, step-input arrays), on the caller's thread
        (`_BatchPrep`): a padding batch of a multi-process pass."""
        return self._prep(batch, track_health)

    def _carried_pass(self, spec: PassSpec, then: PassSpec, res: Optional[TrainResult]):
        """The (batch, arrays) stream of the training pass `spec`, from
        the producer that is kept running from pass to pass.

        The producer the previous pass left behind has been reading
        ahead on the assumption that nothing changes (`then`, as that
        pass gave it). This pass takes it over if and only if that is
        the stream a new iterator would give now (`PassProducer.adopt`:
        equal arguments, every shard's `stat` as it was); otherwise it
        signals it to stop — no join — and starts its own, as every pass
        did before. Counted into `res`."""
        ra = self._read_ahead
        head = ra.adopt(spec, then) if ra is not None else None
        if head is None:
            dropped = ra.stop() if ra is not None else 0
            ra = self._read_ahead = PassProducer(
                self._prep.open_pass,
                profiler=self.pipeline_prof if spec.profiled else None,
                owner=self,  # no thread outlives its trainer
            )
            ra.start(spec, then)
        if res is not None:
            if head is None:
                res.read_ahead_discarded += dropped
            else:
                res.read_ahead_passes += 1
                res.read_ahead_batches += head
        return ra.batches()

    def _coordinated_batches(
        self,
        path: "str | list",
        enforce_bad_rows: bool = True,
        quarantine: bool = True,
        track_health: bool = True,
        skip: int = 0,
        skips: Optional[dict] = None,
        profiled: bool = False,
        then: Optional[dict] = None,
        res: Optional[TrainResult] = None,
    ):
        """Yield exactly the globally-agreed number of (batch, arrays)
        pairs for this rank's shard stream, padding with fully-masked
        empty batches once local input is exhausted.

        `path` is a single file (legacy single-shard contract, shard
        index = this rank) or a [(shard index, path)] assignment
        (`data/pipeline.assign_shards` — an elastic world where one
        rank may own several shards of the original record set); shards
        are streamed sequentially. One counting allgather per epoch
        pass — re-counted every pass so shards that appear, grow, or
        shrink between epochs are picked up (`_epoch_batch_count`); the
        batch stream itself adds no host collectives (the fullshard
        overflow flag, when that engine is on, is the fit loop's, not
        this iterator's). `enforce_bad_rows`/`quarantine` thread through to the bad-record monitor (eval passes count but
        never raise; only the first training pass quarantines).
        `skips` ({shard index -> batches}, or the legacy scalar `skip`)
        fast-forwards each shard past its stored offset (checkpointed
        data_state resume, data/pipeline.skip_batches) — the skipped
        prefix is neither planned, monitored, nor counted toward this
        pass's coordinated step total. Every REAL pair's arrays carry a
        `_shard` marker (popped by the consuming loop before the device
        transfer) so the fit loop can maintain the per-shard position
        the next checkpoint's data_state pins; padding pairs carry
        none. `profiled` threads the pipeline profiler through the
        parser/prefetch/plan seams (fit's training stream only).
        `then` (fit's training stream only) says how the pass that
        follows this one differs if nothing changes — `{"quarantine":
        ...}`; no resume offsets — and makes this a pass of the carried
        producer (`_carried_pass`, counted into `res`); without it the
        pass has a prefetch thread of its own, gone with the pass."""
        shards = [(self.rank, path)] if isinstance(path, str) else list(path)
        skips = dict(skips) if skips else {idx: skip for idx, _ in shards}
        spec = PassSpec(
            shards=tuple((idx, p) for idx, p in shards),
            skips=tuple((idx, max(int(skips.get(idx, 0)), 0)) for idx, _ in shards),
            enforce_bad_rows=enforce_bad_rows, quarantine=quarantine,
            track_health=track_health, profiled=profiled,
        )

        def stream():
            if then is not None:
                ahead = dataclasses.replace(
                    spec, skips=tuple((idx, 0) for idx, _ in shards), **then
                )
                return self._carried_pass(spec, ahead, res)
            return prefetch(
                self._prep.open_pass(spec),
                profiler=self.pipeline_prof if profiled else None,
            )

        if jax.process_count() == 1:
            if not any(os.path.exists(p) for _, p in shards):
                # legacy loudness: a single process with NO input at all
                # is a user error, not an idle elastic rank
                raise FileNotFoundError(shards[0][1] if shards else "<no shards>")
            yield from stream()
            return
        global_steps, local = self._epoch_batch_count(shards, skips)
        # open the real iterator whenever any shard exists (even if
        # counted 0) so the drift check below can catch a counter that
        # under-reads
        have_any = any(os.path.exists(p) for _, p in shards)
        it = iter(stream()) if have_any else iter(())
        produced = 0
        for _ in range(global_steps):
            pair = next(it, None)
            if pair is None:
                # padding batches are built on the CONSUMER thread, so
                # their plan time must NOT be attributed (it would land
                # in the producer group while simultaneously counting
                # as the consumer's data-wait — double attribution)
                pair = self._with_arrays(
                    self._empty_batch(), track_health=track_health
                )
            else:
                produced += 1
            yield pair
        # loud drift check: if the counter mispredicted, data would be
        # silently dropped (under-count) or phantom empty steps run
        # (over-count) — either means the counter/parser predicates split
        if next(it, None) is not None or produced != local:
            names = ", ".join(repr(p) for _, p in shards)
            raise RuntimeError(
                f"batch count drift on {names}: counted {local}, parser "
                f"produced {produced}{'+' if produced == local else ''} — "
                "a file changed while this pass was reading it, or the "
                "row-counter and parser predicates disagree (bug)"
            )

    # ------------------------------------------------------------------ train
    def _install_signal_checkpoint(self):
        """Preemption hook (train.ckpt_on_signal): SIGTERM/SIGINT set a
        flag; the fit loop saves a checkpoint at the next COORDINATION
        point and returns early. Single-process coordinates every step;
        multi-process ranks agree through the `signal_sync_every` flag
        allgather (`_coordinated_signal`) so everyone stops — and saves,
        collectively — at the same step. Main-thread only; the second
        signal falls through to the previous handler, so a double Ctrl-C
        still kills a stuck run. Reference comparison (SURVEY.md §5 A3):
        any termination loses all server-side weights."""
        import signal
        import threading

        cfg = self.cfg
        multiproc_ok = jax.process_count() == 1 or cfg.train.signal_sync_every > 0
        if not (
            cfg.train.ckpt_on_signal and cfg.train.checkpoint_dir and multiproc_ok
        ):
            # config-off is RANK-SYMMETRIC (identical config everywhere),
            # so returning None — which skips the coordination allgathers
            # entirely — is safe
            return None, lambda: None
        if threading.current_thread() is not threading.main_thread():
            # cannot install handlers here, but MUST keep participating
            # in the flag allgathers: thread placement can differ across
            # ranks, and a rank that skipped them would desync the rest
            return {}, lambda: None
        flag = {}
        prev = {}

        def handler(signum, frame):
            flag["sig"] = signum
            # restore immediately: a second signal acts normally
            for s, h in prev.items():
                signal.signal(s, h)

        for s in (signal.SIGTERM, signal.SIGINT):
            prev[s] = signal.signal(s, handler)

        def restore():
            if "sig" not in flag:
                for s, h in prev.items():
                    signal.signal(s, h)

        return flag, restore

    def fit(self, train_path: Optional[str] = None) -> TrainResult:
        entered = time.perf_counter()
        with span("fit"):
            try:
                return self._fit(train_path, entered)
            except BaseException:
                # whatever pass was open, or read ahead, goes now and not
                # when the traceback lets go of the loop's frame
                if self._read_ahead is not None:
                    self._read_ahead.close()
                raise
            finally:
                # abnormal exits land here with the sinks still open
                # (the normal path closed them inside its fit_close span)
                self._close_sinks()
                if self._prev_fit is not None:
                    self._prev_fit["returned"] = time.perf_counter()

    def _close_sinks(self) -> None:
        """End-of-fit teardown; a second call is a no-op."""
        # drain + stop the async checkpoint writer BEFORE the metrics
        # sink closes: its final kind="ckpt" records must land, and
        # fit() returning implies the last submitted save is durable (or
        # its failure logged)
        if self._ckpt_writer is not None:
            self._ckpt_writer.close()
            self._ckpt_writer = None
        # release the metrics/heartbeat handles even on abnormal exit;
        # a later log() on this Trainer transparently reopens in append
        # mode
        self.metrics.close()
        self.heartbeat.close()
        if self.pipeline_prof is not None:
            # drop the pipeline.* gauges from the (process-global)
            # registry so a later unpublished fit in this process
            # snapshots no pipeline metrics; the next published fit's
            # start() re-registers them
            self.pipeline_prof.close()

    def _fit(self, train_path: Optional[str], entered: float) -> TrainResult:
        cfg = self.cfg
        prev_fit, self._prev_fit = self._prev_fit, None
        if cfg.data.stream not in ("off", "tail"):
            raise ValueError(
                f"data.stream={cfg.data.stream!r}: expected 'off' or 'tail'"
            )
        if cfg.data.stream == "tail":
            # follow-the-tail streaming fit (docs/DATA.md "Streaming
            # ingest"): its own loop — the epoch-coordinated path counts
            # batches per pass up front, which is meaningless over a
            # growing input. stream=off never reaches this branch, so
            # every existing stream stays byte-identical (the PR 9
            # zero-overhead discipline; pinned by tests/test_freshness).
            return self._fit_tail(train_path)
        # `_fit` entry -> the batch iterator's first next(): closed where
        # the first epoch's loop starts
        fit_open = contextlib.ExitStack()
        fit_open.enter_context(span("fit_open"))
        res = TrainResult()
        # perf_counter for every DURATION (monotonic — wall clock jumps
        # under NTP slew); the records' `ts` field (JsonlAppender) is the
        # wall-clock correlation handle
        start = time.perf_counter()
        trace = TraceWindow(
            cfg.train.profile_dir,
            cfg.train.trace_start_step,
            cfg.train.trace_num_steps,
        )
        trace.maybe_start_run()
        steptimer = StepTimer()
        registry = default_registry()
        health = self._health
        # the host timeline's window: re-anchor its clock at fit start
        # so Trainer construction (state init) never reads as pipeline
        # wall. None when nobody listens: the loop below runs the same
        # spans and stamps, and only the accumulation falls away
        prof = self.pipeline_prof
        if prof is not None:
            prof.start()
        # the out-of-step parts of this fit(), for its records: `boundary`
        # rides the first record that carries step timings
        boundary: Optional[dict] = None
        # operator stack dumps: `kill -USR1 <pid>` prints every thread's
        # stack (main-thread-only; restored in the finally), and the
        # optional no-progress watchdog dumps them automatically when no
        # step completes for train.hang_timeout_s
        dump_restore = install_stack_dump_handler()
        hang = HangWatchdog(cfg.train.hang_timeout_s)
        # straggler/stall/kill drill injectors (testing/faults.py):
        # env-gated, resolved ONCE here — zero per-step cost in real runs
        from xflow_tpu.testing.faults import fit_delays_from_env, kill_step_from_env

        step_delay_s, stall_step, stall_s = fit_delays_from_env(self.rank)
        kill_step = kill_step_from_env(self.rank)
        hb_every = cfg.train.heartbeat_every
        if cfg.train.eval_every and not cfg.data.test_path:
            # the eval_every gate below requires a holdout; say so once
            # instead of silently never producing eval_auc records
            print(
                "xflow: warning: train.eval_every is set but "
                "data.test_path is empty — no streaming eval will run",
                file=sys.stderr,
            )
        self.heartbeat.append({"event": "start", "step": 0})
        last_metrics = None
        sig_flag, sig_restore = self._install_signal_checkpoint()
        multiproc = jax.process_count() > 1
        sync_every = cfg.train.signal_sync_every
        guard_halt = cfg.train.nonfinite_guard == "halt"
        max_consec = cfg.train.nonfinite_max_consecutive
        bad_run = 0  # consecutive discarded steps
        halted = False
        pending_ok = None  # (metrics, step index) awaiting the flag check
        pending_rec = None  # a log-cadence step's payload, written one behind

        def take_window() -> dict:
            """The profiler's window since the last take, its counters
            added to this fit()'s sums."""
            win = prof.take_window() if prof is not None else {}
            for c in HOST_COUNTERS:
                setattr(res, c, getattr(res, c) + win.get(c, 0))
            return win

        def log_window(rec: dict, at_step: int, win: Optional[dict] = None) -> None:
            """Write a record that carries step timings, with the host
            timeline's share of it: the profiler's window (taken now,
            unless the caller took it earlier) as `host` (armed runs),
            this fit()'s `boundary` on the first such record, and —
            where train.pipeline_metrics publishes — the same window
            again as its OWN kind="pipeline" record
            (docs/OBSERVABILITY.md "Input-pipeline attribution")."""
            nonlocal boundary
            if win is None:
                win = take_window()
            if win:
                rec["host"] = host_fields(win)
            if boundary is not None and "step_time_p50_ms" in rec:
                rec["boundary"], boundary = boundary, None
            self.metrics.log(rec)
            if win and prof.publish:
                self.metrics.log(
                    {"kind": "pipeline", "step": at_step, **pipeline_fields(win)}
                )

        def emit_pending_record() -> None:
            """Write the staged metrics-JSONL record for the last
            log-cadence step. Called right after the NEXT step's
            dispatch (or the end-of-data flush) has block_until_ready'd
            the staged step's metrics, so every float() here is a
            ready-buffer host copy — never a device sync. Reading the
            loss at staging time instead stalled the device once per
            train.log_every steps (the XF110 sync-bubble class; same
            one-step-behind discipline as telemetry.StepTimer)."""
            nonlocal pending_rec
            if pending_rec is None:
                return
            pm, at_step, at_epoch, at_examples, at_elapsed, counters = \
                pending_rec
            pending_rec = None
            loss = float(pm["loss"])
            # under the guard a bad step's NaN loss belongs to a
            # DISCARDED update: last_loss tracks the last loss that
            # actually trained in, and the JSONL record stays
            # strict-JSON (None, not a bare NaN literal)
            finite = loss == loss and abs(loss) != float("inf")
            if finite or not self._guarded:
                res.last_loss = loss
            # step/examples/elapsed_s/counters were all captured at the
            # staging step (host-only reads — no sync), so every
            # rate a consumer derives from them (pipeline_attrib's
            # e2e_examples_per_sec, host_gap_ratio) stays internally
            # consistent; only the device-value reads wait for the
            # one-behind block
            rec = {
                "step": at_step,
                "epoch": at_epoch,
                "loss": loss if finite else None,
                "examples": at_examples,
                "elapsed_s": at_elapsed,
            }
            # window stats: rows/s, steps/s, p50/p99 step time,
            # data-wait/dispatch/device decomposition (telemetry.
            # StepTimer) — emitted one step behind, the window now
            # covers exactly the cadence's finished steps
            rec.update(steptimer.window_record())
            # live HBM gauges (guarded: CPU allocators report nothing
            # and the fields simply stay out)
            rec.update(hbm_window_fields(registry))
            # health window: norms, loss EMA, occupancy / collision
            # gauges (one behind, like the timer)
            rec.update(health.window_record())
            if counters:
                rec["counters"] = counters
            log_window(rec, at_step)

        def check_pending() -> bool:
            """Consume the PREVIOUS step's update_ok flag. Called right
            AFTER the next step's async dispatch, so the host read
            overlaps that step's device execution instead of inserting a
            sync bubble before it (the flag is replicated, so the read
            is collective-free and every rank computes the same
            skip/halt decision). Returns True when the guard demands an
            abort."""
            nonlocal pending_ok, bad_run
            if pending_ok is None:
                return False
            m, at_step = pending_ok
            pending_ok = None
            if "update_ok" not in m or bool(m["update_ok"]):
                bad_run = 0
                return False
            res.bad_steps += 1
            bad_run += 1
            self.metrics.log(
                {
                    "step": at_step,
                    "nonfinite_skipped": True,
                    "bad_steps": res.bad_steps,
                }
            )
            print(
                f"nonfinite update at step {at_step} discarded "
                f"(total {res.bad_steps}, {bad_run} consecutive)",
                file=sys.stderr,
            )
            return guard_halt or (0 < max_consec <= bad_run)

        def run_sync_round() -> None:
            """One cross-slice sync boundary (parallel/multislice.py):
            same bracketing discipline as the checkpoint cadence — flush
            the staged record first (the exchange is a durability
            window: a peer may SIGKILL us believing our delta landed),
            beat around the possibly bounded-wait-long exchange so a
            watchful launcher never reads it as death, tick the hang
            watchdog after. The kind="sync" record + span land in the
            same stamped stream as everything else."""
            emit_pending_record()
            self.heartbeat.append({"step": res.steps, "event": "sync"})
            t0_wall, t0 = time.time(), time.perf_counter()
            self.state, sync_rec = self._syncer.sync(self.state)
            self._place_state(res)  # the synced leaves are new arrays
            if self.metrics.enabled:
                # the GLOBAL step (restored base + this generation's
                # progress) — checkpoint spans stamp the same counter,
                # so a rejoined slice's stream stays step-monotone
                gstep = int(self.state.step)
                self.metrics.log({"step": gstep, **sync_rec})
                from xflow_tpu.tracing import emit_op_span

                emit_op_span(
                    self.metrics, "slice_sync", t0_wall,
                    time.perf_counter() - t0,
                    step=gstep,
                    round=sync_rec["round"],
                    bytes=sync_rec["bytes_out"] + sync_rec["bytes_in"],
                )
            self.heartbeat.append({"step": res.steps})
            hang.tick()  # a bounded staleness wait is progress, not a hang

        def pending_signal() -> int:
            return int(sig_flag["sig"]) if sig_flag and "sig" in sig_flag else 0

        def coordinated_signal() -> int:
            """The stop decision every rank computes IDENTICALLY: local
            flag single-process; the max over all ranks' flags multi-
            process (one [1]-int32 host allgather), called at the same
            step on every rank — so a signal on ANY rank stops ALL ranks
            at the same step and the collective save stays symmetric."""
            if sig_flag is None:
                return 0
            if not multiproc:
                return pending_signal()
            from jax.experimental import multihost_utils

            got = int(
                np.asarray(
                    multihost_utils.process_allgather(np.int32(pending_signal()))
                ).max()
            )
            if got and not pending_signal():
                sig_flag["sig"] = got  # adopt the peer's signal for reporting
            return got

        # exact data resume (elastic recovery, docs/ROBUSTNESS.md): a
        # restored checkpoint's data_state pins the stream position the
        # run stopped at — PER SHARD, so the position survives a
        # topology change; this fit continues there instead of replaying
        # already-trained records from row 0
        start_epoch, resume_skips = self._consume_resume_position()
        world = jax.process_count()
        # the shard set in play: a fresh run covers exactly one shard
        # per rank (the legacy contract, unchanged); an elastic resume
        # covers the ORIGINAL record set round-robin over the CURRENT
        # world (assign_shards), so a run checkpointed at N ranks keeps
        # training every shard at M ranks. TWO carriers of the original
        # set size: the checkpoint data_state (num_shards, consumed in
        # _consume_resume_position) AND the supervisor's XFLOW_ORIG_WORLD
        # env (the launch's original rank count) — the env covers the
        # shrink-before-first-checkpoint window and completed-checkpoint
        # continuation, where there is no (usable) data_state to carry it
        try:
            orig_world = int(os.environ.get("XFLOW_ORIG_WORLD", 0) or 0)
        except ValueError:
            orig_world = 0
        self._num_shards = max(self._num_shards, world, orig_world)
        if train_path:
            epoch_shards = [(self.rank, train_path)]
        else:
            epoch_shards = assign_shards(
                cfg.data.train_path, self.rank, world, self._num_shards
            )
        # a RESUMED shard (nonzero stored offset — the previous world
        # was mid-way through it) whose file this host cannot see is
        # DATA LOSS, not the benign ragged-shard idle: per-host shard
        # files do not follow a lost host's reassignment — say so
        # loudly (elastic shrink wants a shared filesystem)
        for idx, p in epoch_shards:
            if resume_skips.get(idx, 0) > 0 and not os.path.exists(p):
                print(
                    f"xflow: warning: resumed shard {idx} ({p!r}) is "
                    "missing from this host — its remaining records "
                    "will NOT be trained (per-host shard files are not "
                    "visible to the surviving ranks; keep shards on a "
                    "shared filesystem for elastic shrink)",
                    file=sys.stderr,
                )
        self._epoch_pos = (start_epoch, max(resume_skips.values(), default=0))
        # cross-slice sync tier attach (sync.mode != off): a RELAUNCHED
        # slice (gen > 0) first catches up from the freshest published
        # table snapshot — its own checkpoint restore above already
        # pinned step/data position (the zero-lost-examples half of the
        # rejoin), the snapshot brings the peers' table contributions
        # its dead generation missed. attach() then fixes the delta
        # base, so the first sync publishes exactly this fit's progress.
        if self._syncer is not None:
            if resolve_restart_gen() > 0:
                t0_wall, t0 = time.time(), time.perf_counter()
                self.state, adopted = self._syncer.adopt_latest_snapshot(
                    self.state
                )
                if adopted is not None:
                    print(
                        f"multislice: slice {self._syncer.slice_id} caught "
                        f"up from snapshot round {adopted[0]} "
                        f"(published by slice {adopted[1]})",
                        file=sys.stderr,
                    )
                    self._ckpt_span(
                        "sync_catchup", t0_wall, t0, int(self.state.step)
                    )
            self._syncer.attach(self.state)
        # whatever handed this fit() its state (construction, a restore,
        # the snapshot above, a caller's assignment), the steps meet it
        # placed; from here on each step hands the next its own output
        self._place_state(res)
        stop_sig = 0
        try:
            for epoch in range(start_epoch, cfg.train.epochs):
                # the resume offsets apply to the FIRST (partially
                # consumed) epoch only; later epochs read from row 0
                skips = resume_skips if epoch == start_epoch else {}
                self._shard_pos = {
                    idx: max(int(skips.get(idx, 0)), 0) for idx, _ in epoch_shards
                }
                steps_in_epoch = max(self._shard_pos.values(), default=0)
                # consumer tiling: the end-of-iteration mark the next
                # step's `loop_other` continues from (None = no gap to
                # claim: epoch start, or a checkpoint/eval just spent
                # wall that is NOT per-step host work)
                lap_mark = None
                fit_open.close()  # a no-op from the second epoch on
                # quarantine on the FIRST pass only: later epochs see the
                # same bad rows again (still counted/enforced), and one
                # record per bad row beats epochs× duplicates
                for batch, arrays in steptimer.batches(
                    self._coordinated_batches(
                        epoch_shards, quarantine=epoch == 0, skips=skips,
                        profiled=True,
                        # what comes next if nothing changes: the next
                        # epoch, or a new fit()'s first over this path
                        then={"quarantine": epoch + 1 == cfg.train.epochs},
                        res=res,
                    )
                ):
                    # which shard fed this step (None = a padding batch):
                    # popped BEFORE overflow resolution / device transfer
                    shard_idx = arrays.pop("_shard", None)
                    trace.before_step(res.steps + 1)
                    if step_delay_s:  # drill injector (testing/faults.py)
                        time.sleep(step_delay_s)
                    arrays = self._engine.agree(batch, arrays)
                    self._count_fallback(res, arrays)
                    with span("transfer", prof) as moved:
                        arrays = self._engine.shard_batch(arrays)
                    with span("dispatch", prof) as called:
                        self.state, m = self.train_step(self.state, arrays)
                    # finish the PREVIOUS step's timing: the block on its
                    # metrics overlaps this step's device execution, so
                    # neither the timer, the health read, nor the guard
                    # below adds a bubble
                    with span("prev_ready", prof) as ready:
                        steptimer.dispatched(m, batch.num_rows)
                    if prof is not None:
                        # the spans TILE the fit loop under the
                        # StepTimer's own definitions: data_wait = the
                        # time inside next(), loop_other = every host-
                        # side slice between the spans (fetch end ->
                        # transfer, and the previous iteration's tail
                        # bookkeeping: health reads, guard checks, log
                        # writes — claimed via lap_mark). Tiling is what
                        # makes the attribution coverage hit its >= 95%
                        # bar.
                        waited = steptimer.last_wait
                        lap_start = (
                            lap_mark
                            if lap_mark is not None
                            else steptimer.last_wait_end - waited
                        )
                        prof.add_many({
                            "data_wait": waited,
                            "loop_other": max(
                                (ready.t1 - lap_start) - waited - moved.seconds
                                - called.seconds - ready.seconds,
                                0.0,
                            ),
                        })
                        if res.steps == 0:
                            boundary = self._boundary(
                                prev_fit, entered, steptimer, called.t1
                            )
                            boundary["adopted"] = res.read_ahead_passes > 0
                    lap_mark = ready.t1
                    # the previous step's metrics are ready now — the
                    # health scalars (norms, loss for the EMA) read free
                    health.collect()
                    health.staged(m)
                    # ... and so is the previous log-cadence step's
                    # staged record: its reads hide under THIS step's
                    # device time (one-behind discipline, XF110)
                    emit_pending_record()
                    hang.tick()
                    last_metrics = m
                    res.steps += 1
                    res.examples += batch.num_rows
                    steps_in_epoch += 1
                    self._examples_seen += batch.num_rows
                    # the position the NEXT checkpoint's data_state pins:
                    # the global coordinated offset AND this shard's own
                    # consumed count (the topology-independent truth)
                    self._epoch_pos = (epoch, steps_in_epoch)
                    if shard_idx is not None:
                        self._shard_pos[shard_idx] = (
                            self._shard_pos.get(shard_idx, 0) + 1
                        )
                    if hb_every and res.steps % hb_every == 0:
                        self.heartbeat.append({"step": res.steps})
                    if stall_s and res.steps == stall_step:
                        # one-shot stall (straggler drill): this rank
                        # stops progressing while peers run ahead
                        time.sleep(stall_s)
                        stall_s = 0.0
                    # consume the PREVIOUS step's flag now that this
                    # step is dispatched — its device time hides the
                    # host read, so the guard adds no pipeline bubble
                    if check_pending():
                        halted = True
                        break
                    if self._guarded:
                        pending_ok = (m, res.steps)
                    if cfg.train.log_every and res.steps % cfg.train.log_every == 0:
                        # stage, don't read: float(m["loss"]) here would
                        # block on the step JUST dispatched — the exact
                        # sync bubble XF110 exists to catch. The record
                        # is written next iteration (or at the end-of-
                        # data flush), when the one-behind block has
                        # already made its reads free. elapsed_s and the
                        # counter snapshot are host-only and captured
                        # NOW so they pair with this step's examples.
                        pending_rec = (
                            m, res.steps, epoch, res.examples,
                            round(time.perf_counter() - start, 3),
                            registry.snapshot(),
                        )
                    if (
                        cfg.train.checkpoint_dir
                        and cfg.train.checkpoint_every
                        and res.steps % cfg.train.checkpoint_every == 0
                    ):
                        # a record staged THIS step must be durable
                        # before the kill window a checkpoint boundary
                        # opens (the elastic drills SIGKILL right after
                        # the save — SIGKILL bypasses every salvage
                        # net); the save below is itself a full state
                        # sync, so these reads hide under it
                        emit_pending_record()
                        # bracket the (possibly minutes-long collective)
                        # save with beats: no train step completes inside
                        # it, and under a supervised launch a false dead
                        # verdict is a TEARDOWN, not just a warning —
                        # operators still must keep dead_after_s above
                        # the save duration itself
                        self.heartbeat.append(
                            {"step": res.steps, "event": "checkpoint"}
                        )
                        self.save_checkpoint()
                        self.heartbeat.append({"step": res.steps})
                        hang.tick()  # a slow collective save is progress
                        # a (possibly minutes-long) save is NOT per-step
                        # host work: drop the tiling mark so the next
                        # step's loop_other never claims it
                        lap_mark = None
                    if (
                        self._syncer is not None
                        and cfg.sync.every_steps
                        and res.steps % cfg.sync.every_steps == 0
                    ):
                        # the K-step scan-block boundary: exchange table
                        # deltas with the other slices (AFTER the
                        # checkpoint cadence, so a sync-round kill drill
                        # leaves a boundary-committed checkpoint behind)
                        run_sync_round()
                        # a bounded wait is not per-step host work either
                        lap_mark = None
                    if kill_step and res.steps == kill_step:
                        # elastic-recovery drill (testing/faults.py):
                        # SIGKILL AFTER the checkpoint cadence above, so
                        # a kill on a boundary leaves that step committed
                        from xflow_tpu.testing.faults import hard_kill

                        print(
                            f"xflow: fault injector: hard-killing rank "
                            f"{self.rank} at step {res.steps} "
                            "(XFLOW_FAULT_KILL_STEP)",
                            file=sys.stderr, flush=True,
                        )
                        hard_kill()
                    if not multiproc or (sync_every and res.steps % sync_every == 0):
                        stop_sig = coordinated_signal()
                        if stop_sig:
                            break
                if halted:
                    break
                if not stop_sig:
                    # epoch consumed in full: the stream position rolls
                    # over (an interrupted epoch keeps its mid-epoch pos)
                    self._epoch_pos = (epoch + 1, 0)
                    self._shard_pos = {}
                res.epochs = epoch + (0 if stop_sig else 1)
                if not stop_sig:
                    if (epoch + 1) % 30 == 0:
                        print(f"epoch : {epoch}", file=sys.stderr)
                    if (
                        cfg.train.eval_every
                        and cfg.data.test_path
                        and (epoch + 1) % cfg.train.eval_every == 0
                    ):
                        # mid-training holdout pass: STREAMING by default
                        # (BucketAUC histograms, no global score sort —
                        # the giant-eval-set path) so quality lands in
                        # the metrics JSONL while the run is still going
                        # an eval pass makes no train-step progress:
                        # bracket it with ticks so a long (healthy)
                        # holdout doesn't read as a hang — at most one
                        # dump can fire, and only if the eval ITSELF
                        # exceeds the timeout. Same bracketing for the
                        # heartbeat stream: a quiet holdout pass must
                        # not age into a dead verdict (which a
                        # supervised launcher acts on, not just logs)
                        hang.tick()
                        self.heartbeat.append({"step": res.steps, "event": "eval"})
                        auc, ll = self.evaluate(dump=False, streaming=True)
                        self.heartbeat.append({"step": res.steps})
                        hang.tick()
                        # strict JSON: a one-class shard's NaN AUC logs
                        # as null, same convention as the guarded loss
                        self.metrics.log(
                            {
                                "step": res.steps,
                                "epoch": epoch,
                                "eval_auc": auc if auc == auc else None,
                                "eval_logloss": ll if ll == ll else None,
                            }
                        )
                        # gauges only for finite values: a one-class eval
                        # shard yields NaN AUC, and a NaN in the registry
                        # snapshot would leak into the (strict-JSON)
                        # counters dict
                        if auc == auc:
                            registry.gauge("health.eval_auc").set(auc)
                        if ll == ll:
                            registry.gauge("health.eval_logloss").set(ll)
                    # re-check AFTER the epoch eval too (an end-of-epoch
                    # coordination point): a signal landing there, or
                    # between sync cadences, must not be lost
                    stop_sig = coordinated_signal()
                if stop_sig:
                    res.interrupted = stop_sig
                    self.metrics.log({"interrupted": res.interrupted, "step": res.steps})
                    self.heartbeat.append({"event": "interrupted", "step": res.steps})
                    # flush-and-close BOTH sinks here, before the (slow,
                    # collective) checkpoint save: if the grace period
                    # expires mid-save and the process is KILLed, the
                    # metrics/heartbeat tails are already on disk. Later
                    # appends transparently reopen (JsonlAppender).
                    self.metrics.close()
                    self.heartbeat.close()
                    print(
                        f"signal {res.interrupted}: checkpointing at step "
                        f"{res.steps} and exiting",
                        file=sys.stderr,
                    )
                    break
            # the final step's timing is still in flight (one behind);
            # this block is the single end-of-data sync the timer adds —
            # the guard's flag, the loss and the health monitor's tail
            # collect below read ready buffers behind it
            fit_open.close()  # still open after a resume past the last epoch
            with span("fit_flush") as flushed:
                steptimer.flush()
            if prof is not None:
                # the last step's metrics block is a wait for the device
                # like every step's before it
                prof.add("prev_ready", flushed.seconds)
            # the last step's flag is still pending after the data ends
            if not halted and check_pending():
                halted = True
            if halted:
                # a record staged on the halting step is the run's most
                # diagnostic line — write it before aborting (the abort
                # path can afford its one sync; the eager pre-XF110
                # code always wrote it)
                emit_pending_record()
                self.metrics.log(
                    {
                        "nonfinite_halt": True,
                        "step": res.steps,
                        "bad_steps": res.bad_steps,
                    }
                )
                if cfg.train.checkpoint_dir:
                    # the bad updates were discarded on device, so the
                    # live state IS the last good state — commit it
                    # before aborting, like the preemption path
                    self.save_checkpoint(wait=True)
                raise NonFiniteHalt(
                    f"non-finite guard aborted at step {res.steps}: "
                    f"{res.bad_steps} bad step(s), {bad_run} consecutive "
                    f"(train.nonfinite_guard={cfg.train.nonfinite_guard}, "
                    f"train.nonfinite_max_consecutive={max_consec})"
                    + (
                        f"; last good state committed under "
                        f"{cfg.train.checkpoint_dir!r}"
                        if cfg.train.checkpoint_dir
                        else ""
                    )
                )
            if last_metrics is not None:
                loss = float(last_metrics["loss"])
                # a discarded final step keeps the last GOOD loss (the
                # state never took the bad update)
                if (loss == loss and abs(loss) != float("inf")) or not self._guarded:
                    res.last_loss = loss
        except BaseException:
            # ANY crash between staging and the next emit (quarantine
            # exhaustion, a checkpoint IOError, SIGINT) must not lose
            # the staged log record — before the XF110 staging it was
            # already on disk, and it is the line that explains the
            # crash. Never let a failing emit mask the real exception —
            # not even a second Ctrl+C while the salvage read blocks on
            # a wedged device (hence BaseException here too).
            try:
                emit_pending_record()
            except BaseException:
                pass
            raise
        finally:
            sig_restore()
            dump_restore()
            hang.close()
            trace.close()
        health.flush()
        # a record staged on the run's final step has no successor
        # dispatch to hide behind; the flush above paid the one
        # end-of-data sync, so these reads are free too
        emit_pending_record()
        # what the window holds past the last log tick, for the final
        # record: taken BEFORE the occupancy sweep below — post-loop host
        # work is not pipeline wall
        tail_win = take_window()
        res.seconds = time.perf_counter() - start
        # final sync boundary: publish the tail block's delta and fold
        # in whatever peers have landed, so the state this fit returns
        # (and evaluates / checkpoints below) carries every slice's
        # contribution. Skipped on preemption/halt — the grace window
        # must not fund a bounded staleness wait; the rejoin snapshot
        # path covers catch-up instead.
        if self._syncer is not None and res.steps and not stop_sig and not halted:
            run_sync_round()
        # table occupancy: fraction of slots ever touched by a gradient —
        # the sparse-model health metric (SURVEY.md §5 "table-occupancy").
        # FTRL's n accumulator (n>0 ⇔ slot was pushed) is the reliable
        # signal; untouched slots keep their build-time init, so a
        # nonzero count would read ~1.0 for randomly-initialized v tables.
        specs = self.model.table_specs(cfg)
        with span("occupancy") as swept:
            for name, t in self.state.tables.items():
                st = self.state.opt_state.get(name)
                if isinstance(st, dict) and "n" in st:
                    touched = st["n"] > 0
                else:
                    # stateless optimizer (SGD): a touched slot has moved
                    # off its build-time init (0 for scalar tables,
                    # v_init_sgd for vector tables — models/base.py
                    # init_tables)
                    touched = t != (cfg.optim.v_init_sgd if t.ndim > 1 else 0.0)
                if touched.ndim > 1:
                    touched = _slot_any(touched, specs[name][0])
                res.occupancy[name] = float(jnp.mean(touched))
        with span("fit_close") as closed:
            final_rec = {
                "final": True,
                "steps": res.steps,
                "examples": res.examples,
                "elapsed_s": round(res.seconds, 3),
                "occupancy": res.occupancy,
                "state_leaves_placed": res.state_leaves_placed,
                "read_ahead_passes": res.read_ahead_passes,
                "read_ahead_batches": res.read_ahead_batches,
                "read_ahead_discarded": res.read_ahead_discarded,
            }
            final_rec.update(self._fallback_fields(res))
            final_rec.update((c, getattr(res, c)) for c in HOST_COUNTERS if getattr(res, c))
            # tail window (steps since the last log tick) + run-total counters
            final_rec.update(steptimer.window_record())
            final_rec.update(hbm_window_fields(registry))
            final_rec.update(health.window_record())
            if prof is not None:
                # this fit()'s own end: the terminating next(), the wait
                # for the last step, the sweep (the close is still
                # running; the next fit()'s `boundary` has it)
                final_rec["iter_end_ms"] = round(steptimer.iter_end_s * 1e3, 3)
                final_rec["fit_flush_ms"] = round(flushed.seconds * 1e3, 3)
                final_rec["occupancy_ms"] = round(swept.seconds * 1e3, 3)
            counters = registry.snapshot()
            if counters:
                final_rec["counters"] = counters
            log_window(final_rec, res.steps, tail_win)
            self.heartbeat.append({"event": "final", "step": res.steps})
            if cfg.train.checkpoint_dir:
                # the run's terminal state must be durable when fit returns
                self.save_checkpoint(wait=True)
            self._close_sinks()
        if prof is not None:
            self._prev_fit = {
                "ready": steptimer.last_ready or flushed.t1,
                "occupancy_ms": swept.seconds * 1e3,
                "close_ms": closed.seconds * 1e3,
            }
        return res

    @staticmethod
    def _boundary(prev_fit: Optional[dict], entered: float, steptimer: StepTimer,
                  dispatched: float) -> dict:
        """The step records' `boundary` (docs/OBSERVABILITY.md "The pass
        boundary"): the parts, in ms, that tile the stretch from the
        previous fit()'s last step ready to this one's first step
        dispatched. `fit_tail_ms` (with its parts `occupancy_ms`,
        `close_ms`) and `between_fits_ms` need a previous fit() on this
        Trainer; `first_batch_ms` and `first_dispatch_ms` lie inside the
        first step's interval, the others outside every interval."""
        out: dict = {}
        if prev_fit is not None and "returned" in prev_fit:
            out["fit_tail_ms"] = (prev_fit["returned"] - prev_fit["ready"]) * 1e3
            out["occupancy_ms"] = prev_fit["occupancy_ms"]
            out["close_ms"] = prev_fit["close_ms"]
            out["between_fits_ms"] = (entered - prev_fit["returned"]) * 1e3
        out["fit_open_ms"] = (steptimer.first_fetch - entered) * 1e3
        out["first_batch_ms"] = steptimer.last_wait * 1e3
        out["first_dispatch_ms"] = (dispatched - steptimer.last_wait_end) * 1e3
        return {k: round(v, 3) for k, v in out.items()}

    # ---------------------------------------------------------- streaming fit
    def _fit_tail(self, train_path: Optional[str] = None) -> TrainResult:
        """Follow-the-tail streaming fit (`data.stream=tail`, docs/DATA.md
        "Streaming ingest"): train on sealed ingest segments as a
        TailFollower spools them off the growing input, and publish
        committed checkpoints every `train.publish_every` steps — each
        publication stamped with the NEWEST ingest trace whose rows a
        completed step consumed, so the serve tier (and
        tools/freshness_report.py) can measure data freshness end to
        end.

        Deliberately leaner than the epoch loop: single-process only
        (the counting allgather the coordinated path leans on has no
        meaning over an unbounded stream), no epochs (the stream IS one
        open-ended pass), no profiler tiling or fault injectors. What
        it keeps: the one-behind metrics staging (XF110), the
        non-finite guard, heartbeat/hang bracketing around saves, and
        signal-checkpoint handling — the operational contracts every
        fit honors."""
        cfg = self.cfg
        if jax.process_count() > 1:
            raise ValueError(
                "data.stream=tail is single-process only: the tail "
                "follower has no cross-rank batch coordination (shard "
                "the stream upstream instead)"
            )
        from xflow_tpu.data.pipeline import TailFollower

        res = TrainResult()
        start = time.perf_counter()
        steptimer = StepTimer()
        registry = default_registry()
        health = self._health
        dump_restore = install_stack_dump_handler()
        hang = HangWatchdog(cfg.train.hang_timeout_s)
        sig_flag, sig_restore = self._install_signal_checkpoint()
        hb_every = cfg.train.heartbeat_every
        guard_halt = cfg.train.nonfinite_guard == "halt"
        max_consec = cfg.train.nonfinite_max_consecutive
        bad_run = 0
        halted = False
        pending_ok = None
        pending_rec = None
        self.heartbeat.append({"event": "start", "step": 0})
        self._place_state(res)
        follower = TailFollower(
            train_path or cfg.data.train_path, cfg.data,
            appender=self.metrics if self.metrics.enabled else None,
        )

        def emit_pending_record() -> None:
            # the same one-step-behind staging as _fit (XF110): reads
            # happen after the NEXT dispatch made them free
            nonlocal pending_rec
            if pending_rec is None:
                return
            pm, at_step, at_examples, at_elapsed, counters = pending_rec
            pending_rec = None
            loss = float(pm["loss"])
            finite = loss == loss and abs(loss) != float("inf")
            if finite or not self._guarded:
                res.last_loss = loss
            rec = {
                "step": at_step,
                "epoch": 0,
                "loss": loss if finite else None,
                "examples": at_examples,
                "elapsed_s": at_elapsed,
            }
            rec.update(steptimer.window_record())
            rec.update(hbm_window_fields(registry))
            rec.update(health.window_record())
            if counters:
                rec["counters"] = counters
            self.metrics.log(rec)

        def check_pending() -> bool:
            nonlocal pending_ok, bad_run
            if pending_ok is None:
                return False
            m, at_step = pending_ok
            pending_ok = None
            if "update_ok" not in m or bool(m["update_ok"]):
                bad_run = 0
                return False
            res.bad_steps += 1
            bad_run += 1
            self.metrics.log(
                {
                    "step": at_step,
                    "nonfinite_skipped": True,
                    "bad_steps": res.bad_steps,
                }
            )
            print(
                f"nonfinite update at step {at_step} discarded "
                f"(total {res.bad_steps}, {bad_run} consecutive)",
                file=sys.stderr,
            )
            return guard_halt or (0 < max_consec <= bad_run)

        # freshness bookkeeping: the newest (trace, ingest_ts,
        # consumed_ts) triple whose segment a completed step trained on
        # — what the next publication stamps
        newest: Optional[tuple] = None
        pub_seq = 0
        publish_every = cfg.train.publish_every
        last_metrics = None
        stop_sig = 0
        try:
            for seg in follower.segments():
                seg_consumed = False
                for batch, arrays in steptimer.batches(
                    self._coordinated_batches([(0, seg.path)], quarantine=True)
                ):
                    arrays.pop("_shard", None)
                    arrays = self._engine.agree(batch, arrays)
                    self._count_fallback(res, arrays)
                    arrays = self._engine.shard_batch(arrays)
                    self.state, m = self.train_step(self.state, arrays)
                    steptimer.dispatched(m, batch.num_rows)
                    health.collect()
                    health.staged(m)
                    emit_pending_record()
                    hang.tick()
                    last_metrics = m
                    res.steps += 1
                    res.examples += batch.num_rows
                    self._examples_seen += batch.num_rows
                    self._epoch_pos = (0, res.steps)
                    if not seg_consumed:
                        # the first step over a segment marks its rows
                        # as consumed; the wall clock here is the
                        # ingest-to-train edge of the freshness Δ
                        seg_consumed = True
                        newest = (seg.trace, seg.ingest_ts, time.time())
                    if hb_every and res.steps % hb_every == 0:
                        self.heartbeat.append({"step": res.steps})
                    if check_pending():
                        halted = True
                        break
                    if self._guarded:
                        pending_ok = (m, res.steps)
                    if cfg.train.log_every and res.steps % cfg.train.log_every == 0:
                        pending_rec = (
                            m, res.steps, res.examples,
                            round(time.perf_counter() - start, 3),
                            registry.snapshot(),
                        )
                    if (
                        cfg.train.checkpoint_dir
                        and publish_every
                        and res.steps % publish_every == 0
                        and newest is not None
                    ):
                        emit_pending_record()
                        self.heartbeat.append(
                            {"step": res.steps, "event": "checkpoint"}
                        )
                        # the seq number is consumed only when the
                        # publication landed (an async skip retries at
                        # the next cadence with the SAME next seq)
                        if self._publish_checkpoint(newest, pub_seq + 1):
                            pub_seq += 1
                        self.heartbeat.append({"step": res.steps})
                        hang.tick()  # a slow publish is progress
                        if (
                            cfg.train.eval_every
                            and cfg.data.test_path
                            and pub_seq % cfg.train.eval_every == 0
                        ):
                            # in stream mode eval_every counts
                            # PUBLICATIONS (there are no epochs); with
                            # train.eval_window_decay the repeated
                            # passes form the time-decayed window
                            hang.tick()
                            self.heartbeat.append(
                                {"step": res.steps, "event": "eval"}
                            )
                            auc, ll = self.evaluate(dump=False, streaming=True)
                            self.heartbeat.append({"step": res.steps})
                            hang.tick()
                            self.metrics.log(
                                {
                                    "step": res.steps,
                                    "epoch": 0,
                                    "eval_auc": auc if auc == auc else None,
                                    "eval_logloss": ll if ll == ll else None,
                                }
                            )
                    elif (
                        cfg.train.checkpoint_dir
                        and not publish_every
                        and cfg.train.checkpoint_every
                        and res.steps % cfg.train.checkpoint_every == 0
                    ):
                        # publish_every=0: plain checkpoint cadence,
                        # no publication sidecar — freshness stays off
                        emit_pending_record()
                        self.heartbeat.append(
                            {"step": res.steps, "event": "checkpoint"}
                        )
                        self.save_checkpoint()
                        self.heartbeat.append({"step": res.steps})
                        hang.tick()
                    stop_sig = (
                        int(sig_flag["sig"])
                        if sig_flag and "sig" in sig_flag
                        else 0
                    )
                    if stop_sig:
                        break
                if halted or stop_sig:
                    break
            if not halted and check_pending():
                halted = True
            if halted:
                emit_pending_record()
                self.metrics.log(
                    {
                        "nonfinite_halt": True,
                        "step": res.steps,
                        "bad_steps": res.bad_steps,
                    }
                )
                if cfg.train.checkpoint_dir:
                    self.save_checkpoint(wait=True)
                raise NonFiniteHalt(
                    f"non-finite guard aborted at step {res.steps}: "
                    f"{res.bad_steps} bad step(s), {bad_run} consecutive"
                )
            if stop_sig:
                res.interrupted = stop_sig
                self.metrics.log(
                    {"interrupted": res.interrupted, "step": res.steps}
                )
                self.heartbeat.append(
                    {"event": "interrupted", "step": res.steps}
                )
        except BaseException:
            try:
                emit_pending_record()
            except BaseException:
                pass
            raise
        finally:
            sig_restore()
            dump_restore()
            hang.close()
            follower.close()
        steptimer.flush()
        health.flush()
        emit_pending_record()
        if last_metrics is not None:
            loss = float(last_metrics["loss"])
            if (loss == loss and abs(loss) != float("inf")) or not self._guarded:
                res.last_loss = loss
        res.seconds = time.perf_counter() - start
        res.epochs = 1 if res.steps else 0
        final_rec = {
            "final": True,
            "steps": res.steps,
            "examples": res.examples,
            "elapsed_s": round(res.seconds, 3),
            "occupancy": res.occupancy,
            "state_leaves_placed": res.state_leaves_placed,
        }
        final_rec.update(self._fallback_fields(res))
        final_rec.update(steptimer.window_record())
        final_rec.update(hbm_window_fields(registry))
        final_rec.update(health.window_record())
        counters = registry.snapshot()
        if counters:
            final_rec["counters"] = counters
        self.metrics.log(final_rec)
        self.heartbeat.append({"event": "final", "step": res.steps})
        if cfg.train.checkpoint_dir and res.steps:
            # the tail commit publishes too when a publication cadence
            # is on: the stream's last rows must become servable even
            # when the idle timeout lands mid-cadence
            if publish_every and newest is not None:
                # wait=True drains any in-flight save first, so the
                # final publication is never skipped
                if self._publish_checkpoint(newest, pub_seq + 1, wait=True):
                    pub_seq += 1
            else:
                self.save_checkpoint(wait=True)
        return res

    def _publish_checkpoint(self, newest: tuple, seq: int,
                            wait: bool = False) -> bool:
        """One in-run checkpoint PUBLICATION (docs/SERVING.md
        "Freshness"): a normal committed save plus the publication.json
        sidecar binding this step to the newest ingest trace whose rows
        it trained on, a `kind="publish"` record, and a `publish` span
        CARRYING that ingest trace id (tracing.emit_linked_span) — the
        link freshness_report follows across the train/serve boundary.
        The sidecar lands before the COMMITTED marker (checkpoint.save),
        so a watcher never sees a committed step whose publication is
        still in flight. Under train.ckpt_async the save may be SKIPPED
        (previous save still in flight) — then no publication happened:
        no record, no span, the seq number is not consumed, and the
        caller retries at the next cadence. Returns whether the
        publication was accepted."""
        from xflow_tpu.tracing import emit_linked_span, new_id

        trace, ingest_ts, consumed_ts = newest
        t0_wall, t0 = time.time(), time.perf_counter()
        step = int(self.state.step)
        pub = {
            "step": step,
            "seq": int(seq),
            "trace": trace,
            "span": new_id(),
            "ingest_ts": round(float(ingest_ts), 6),
            "consumed_ts": round(float(consumed_ts), 6),
            "published_ts": round(t0_wall, 6),
        }
        if not self.save_checkpoint(publication=pub, wait=wait):
            return False
        if self.metrics.enabled:
            self.metrics.log(
                {
                    "kind": "publish",
                    "step": step,
                    "seq": int(seq),
                    "trace": trace,
                    "ingest_ts": pub["ingest_ts"],
                    "published_ts": pub["published_ts"],
                }
            )
            # record + span symmetry (the run_sync_round idiom): the
            # span's end is the publication's commit instant — the
            # publish edge of the freshness Δ decomposition
            emit_linked_span(
                self.metrics, "publish", t0_wall,
                time.perf_counter() - t0,
                trace=trace, span=pub["span"], step=step, seq=int(seq),
            )
        return True

    # ------------------------------------------------------------------- eval
    def _local_pctrs(self, p_dev) -> np.ndarray:
        """This process's rows of the (possibly cross-process) pctr array."""
        if isinstance(p_dev, jax.Array) and not p_dev.is_fully_addressable:
            shards = sorted(p_dev.addressable_shards, key=lambda s: s.index[0].start or 0)
            return np.concatenate([np.asarray(s.data) for s in shards])
        return np.asarray(p_dev)

    def evaluate(
        self,
        test_path: Optional[str] = None,
        dump: Optional[bool] = None,
        block: int = 0,
        streaming: bool = False,
    ) -> tuple[float, float]:
        """Predict pass. Returns (auc, logloss); optionally dumps pred file.

        Two paths (round-1 verdict item 7):

        - exact (default): collect every (pctr, label); multi-process
          gathers ONE stacked [B, 3] array per batch (the round-1 code
          issued three separate allgathers) and rank-sorts on the host.
          Reference parity: `base.h:84-110`.
        - bucketed (``train.eval_buckets > 0``): histogram positives /
          negatives by score bucket locally (`metrics.BucketAUC`), ONE
          collective at the end — no host ever materializes the global
          pctr vector, so Criteo-1TB-scale eval streams. AUC error is
          bounded by bucket width (±~1/buckets).

        The exact-vs-bucketed choice depends only on config (identical on
        every process), never on rank — a per-rank choice would mismatch
        the collective sequences across processes and deadlock. With
        buckets on, each rank dumps its OWN rows to ``pred_<rank>_*.txt``
        (the reference's per-worker files, `lr_worker.cc:74-78`).

        `streaming=True` (the trainer's mid-training `eval_every` pass)
        upgrades the auto default to the bucketed path even
        single-process — a holdout pass DURING training should stream
        rather than sort a growing global score vector — while an
        explicit `train.eval_buckets` setting still wins (it's config,
        hence rank-symmetric either way).
        """
        cfg = self.cfg
        world = jax.process_count()
        self._place_state()
        if test_path:
            shards: "str | list" = test_path
        else:
            # the same elastic assignment as training: after a shrink
            # the surviving ranks cover the full test record set too
            shards = assign_shards(
                cfg.data.test_path, self.rank, world,
                max(self._num_shards, world),
            )
        dump = cfg.train.pred_dump if dump is None else dump
        multiproc = world > 1
        buckets = resolve_eval_buckets(cfg.train.eval_buckets, multiproc)
        if streaming and buckets == 0 and cfg.train.eval_buckets < 0:
            buckets = 65536
        if buckets:
            return self._evaluate_bucketed(shards, buckets, dump, block)
        dump = dump and (not multiproc or self.rank == 0)
        fout = open(f"pred_{self.rank}_{block}.txt", "w") if dump else None
        pctrs, labels = [], []
        for batch, arrays in self._coordinated_batches(
            shards, enforce_bad_rows=False, quarantine=False, track_health=False,
        ):
            arrays.pop("_shard", None)
            arrays = self._engine.agree(batch, arrays)
            arrays = self._engine.shard_batch(arrays)
            p_dev = self.eval_step(self.state.tables, arrays)
            if multiproc:
                # ONE allgather of the stacked local rows per batch
                from jax.experimental import multihost_utils

                local = np.stack(
                    [
                        self._local_pctrs(p_dev),
                        np.asarray(batch.labels, np.float32),
                        np.asarray(batch.row_mask, np.float32),
                    ],
                    axis=1,
                )
                gathered = np.asarray(
                    multihost_utils.process_allgather(local, tiled=True)
                )
                p, y_all, rm = gathered[:, 0], gathered[:, 1], gathered[:, 2] > 0
            else:
                p = np.asarray(p_dev)
                rm = np.asarray(batch.row_mask) > 0
                y_all = np.asarray(batch.labels)
            p, y = p[rm], y_all[rm]
            pctrs.append(p)
            labels.append(y)
            if fout:
                for pi, yi in zip(p, y):
                    # reference row format: pctr \t 1-label \t label (lr_worker.cc:67)
                    fout.write(f"{pi:.6f}\t{int(1 - yi)}\t{int(yi)}\n")
        if fout:
            fout.close()
        if not pctrs:
            return float("nan"), float("nan")
        auc, ll = auc_logloss(np.concatenate(pctrs), np.concatenate(labels))
        return auc, ll

    def _evaluate_bucketed(
        self, shards, num_buckets: int, dump: bool = False, block: int = 0
    ) -> tuple[float, float]:
        """Streaming eval: local bucket histograms, one collective at the end.

        With `dump`, each rank writes its own local rows (reference
        per-worker pred files) — no cross-rank gather is needed for it.
        """
        from xflow_tpu.metrics import BucketAUC

        st = BucketAUC.init(num_buckets)
        ll_sum, n_rows = 0.0, 0.0
        fout = open(f"pred_{self.rank}_{block}.txt", "w") if dump else None
        for batch, arrays in self._coordinated_batches(
            shards, enforce_bad_rows=False, quarantine=False, track_health=False,
        ):
            arrays.pop("_shard", None)
            arrays = self._engine.agree(batch, arrays)
            arrays = self._engine.shard_batch(arrays)
            p = self._local_pctrs(self.eval_step(self.state.tables, arrays))
            rm = np.asarray(batch.row_mask) > 0
            y = np.asarray(batch.labels)[rm]
            p = np.asarray(p, np.float64)[rm]
            st = st.update(p, y)
            eps = 1e-15
            pc = np.clip(p, eps, 1.0 - eps)
            ll_sum += float((y * np.log(pc) + (1.0 - y) * np.log(1.0 - pc)).sum())
            n_rows += float(rm.sum())
            if fout:
                for pi, yi in zip(p, y):
                    fout.write(f"{pi:.6f}\t{int(1 - yi)}\t{int(yi)}\n")
        if fout:
            fout.close()
        stats = np.concatenate([st.pos, st.neg, [ll_sum, n_rows]])
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            # hi/lo float32 split keeps counts beyond 2^24 exact through
            # the (float32-only without x64) allgather: x = hi + lo with
            # hi = f32(x), lo = f32(x - hi); summed back in float64
            hi = stats.astype(np.float32)
            lo = (stats - hi.astype(np.float64)).astype(np.float32)
            gathered = np.asarray(
                multihost_utils.process_allgather(np.stack([hi, lo]))
            ).astype(np.float64)
            stats = gathered.reshape(-1, 2, stats.shape[0]).sum(axis=(0, 1))
        pos, neg = stats[:num_buckets], stats[num_buckets : 2 * num_buckets]
        ll_sum, n_rows = float(stats[-2]), float(stats[-1])
        decay = float(self.cfg.train.eval_window_decay)
        if decay > 0:
            # time-decayed sliding window (train.eval_window_decay):
            # fold the decayed accumulator from earlier eval passes into
            # this pass's counts (BucketAUC.decay — counts are plain
            # sums, so the fold is addition), then persist the folded
            # state for the next pass. Runs AFTER the cross-process
            # merge above, on identical allgathered stats, so every rank
            # holds the same window. A bucket-count change resets the
            # window (the histograms are not comparable).
            prev = self._eval_window
            if prev is not None and prev[0].pos.shape[0] == num_buckets:
                pst = prev[0].decay(decay)
                pos = pos + pst.pos
                neg = neg + pst.neg
                ll_sum += prev[1] * decay
                n_rows += prev[2] * decay
            self._eval_window = (BucketAUC(pos=pos, neg=neg), ll_sum, n_rows)
        if n_rows == 0:
            return float("nan"), float("nan")
        auc = BucketAUC(pos=pos, neg=neg).compute()
        return auc, ll_sum / n_rows

    # ------------------------------------------------------------- checkpoint
    def _data_state_record(self) -> dict:
        """The host-side data-pipeline position saved alongside every
        checkpoint (elastic recovery, docs/ROBUSTNESS.md) — the
        TOPOLOGY-INDEPENDENT v2 form: epoch index, the global
        coordinated batch offset (informational), per-SHARD consumed
        batch counts (`shard_batches` — the truth a resume at ANY world
        size reshards from), the shard set in play (`num_shards`), the
        GLOBAL cumulative example count, and the quarantine count.
        Per-rank example counts ride along as information only — they
        are meaningless across a topology change. `completed` marks a
        checkpoint written after the configured epochs all ran — a
        resume of a completed run is continuation training and starts a
        fresh pass instead of training nothing. The stream itself is
        deterministic file order (no shuffle stage yet); when one
        lands, its RNG state joins this record — the version field
        exists for exactly that."""
        from xflow_tpu.train.checkpoint import DATA_STATE_VERSION

        epoch, batches = self._epoch_pos
        reg = default_registry()
        world = jax.process_count()
        num_shards = max(self._num_shards, world, 1)
        local_shards = np.zeros(num_shards, np.int32)
        for idx, n in self._shard_pos.items():
            if 0 <= int(idx) < num_shards:
                local_shards[int(idx)] = min(int(n), 2**31 - 1)
        local_ex = np.int32(min(self._examples_seen, 2**31 - 1))
        if world > 1:
            from jax.experimental import multihost_utils

            # collective-safe: save_checkpoint is itself collective, so
            # every rank reaches this allgather at the same step. ONE
            # stacked [1 + num_shards]-int32 allgather carries both the
            # example counters and the shard offsets (each shard is
            # owned by exactly one rank, so the per-shard MAX is the
            # owner's count). int32: jax without x64 silently truncates
            # int64 inputs.
            stacked = np.concatenate([[local_ex], local_shards]).astype(np.int32)
            got = np.asarray(
                multihost_utils.process_allgather(stacked)
            ).reshape(world, -1)
            per_rank = [int(x) for x in got[:, 0]]
            shard_batches = got[:, 1:].max(axis=0)
            examples = int(self._examples_base) + sum(per_rank)
        else:
            per_rank = [int(local_ex)]
            shard_batches = local_shards
            examples = int(self._examples_base) + int(local_ex)
        return {
            "version": DATA_STATE_VERSION,
            "epoch": int(epoch),
            "batches": int(batches),
            "completed": bool(epoch >= self.cfg.train.epochs),
            "examples": examples,
            "examples_per_rank": per_rank,
            "shard_batches": {str(i): int(v) for i, v in enumerate(shard_batches)},
            "num_shards": int(num_shards),
            "world_size": int(world),
            "quarantined_rows": int(reg.counter("data.quarantined_rows").value),
        }

    def _consume_resume_position(self) -> tuple[int, dict]:
        """(start_epoch, {shard index -> batch offset}) for this fit(),
        consuming the data_state maybe_restore captured. Fresh runs,
        pre-v2 checkpoints, unreadable data_state, and COMPLETED
        checkpoints (continuation training) all start at (0, {}); an
        interrupted run's checkpoint resumes every shard's stream
        exactly where it stopped — whatever world size wrote it
        (checkpoint.normalize_data_state folds v1 records into the
        topology-independent form)."""
        ds_raw = self._resume_data_state
        self._resume_data_state = None
        from xflow_tpu.train.checkpoint import normalize_data_state

        if not isinstance(ds_raw, dict) or ds_raw.get("completed"):
            if isinstance(ds_raw, dict):
                # continuation training starts a fresh pass, but the
                # RECORD SET the completed checkpoint covered still
                # applies — a shrunk world keeps covering every shard
                try:
                    self._num_shards = max(
                        self._num_shards,
                        normalize_data_state(ds_raw)["num_shards"],
                    )
                except (TypeError, ValueError):
                    pass
            return 0, {}
        try:
            ds = normalize_data_state(ds_raw)
        except (TypeError, ValueError):
            print(
                "xflow: warning: checkpoint data_state is malformed; "
                "resuming with a fresh data stream",
                file=sys.stderr,
            )
            return 0, {}
        # GLOBAL example accounting survives any topology change: the
        # restored total becomes the base, and every rank's local
        # counter restarts at 0 for this generation
        self._examples_base = ds["examples"]
        self._examples_seen = 0
        self._num_shards = max(self._num_shards, ds["num_shards"])
        epoch, skips = ds["epoch"], ds["shard_batches"]
        world = jax.process_count()
        if epoch or any(skips.values()):
            from xflow_tpu.telemetry import resolve_restart_gen

            note = (
                f"; resharding {ds['num_shards']} shard(s) from "
                f"{ds['world_size']} rank(s) onto {world}"
                if ds["world_size"] != world
                else ""
            )
            print(
                f"resuming data stream at epoch {epoch}, shard offsets "
                f"{[skips.get(i, 0) for i in range(ds['num_shards'])]} "
                f"(restart generation {resolve_restart_gen()}){note}",
                file=sys.stderr,
            )
        return epoch, skips

    def _ckpt_span(self, name: str, t0_wall: float, t0: float,
                   step: int) -> None:
        """One kind="span" record per checkpoint save/restore
        (train.ckpt_spans): the checkpoint lifecycle joins the same
        span stream serving emits, so tools/request_trace.py --timeline
        can overlay saves/reloads against request-latency spikes."""
        if not self.cfg.train.ckpt_spans or not self.metrics.enabled:
            # enabled guards the tree walk + nbytes sum: with no
            # metrics sink the record would be built only to no-op
            return
        from xflow_tpu.tracing import emit_op_span

        emit_op_span(
            self.metrics, name, t0_wall, time.perf_counter() - t0,
            step=int(step),
            bytes=int(sum(
                x.nbytes
                for x in jax.tree.leaves(
                    (self.state.tables, self.state.opt_state)
                )
            )),
        )

    def _ckpt_async_on(self) -> bool:
        """train.ckpt_async, gated to single-process runs: _flatten's
        multihost gather is a collective no side thread may run. A
        multi-process run that asked for async falls back to synchronous
        saves with a one-time warning."""
        if not self.cfg.train.ckpt_async:
            return False
        if jax.process_count() > 1:
            if not getattr(self, "_ckpt_async_warned", False):
                self._ckpt_async_warned = True
                print(
                    "# checkpoint: train.ckpt_async is single-process "
                    "only (host-gather collectives cannot run on a side "
                    "thread); falling back to synchronous saves",
                    file=sys.stderr,
                )
            return False
        return True

    def _ensure_ckpt_writer(self):
        from xflow_tpu.train import checkpoint as ckpt

        if self._ckpt_writer is None:
            self._ckpt_writer = ckpt.AsyncCheckpointWriter(
                sink=self.metrics, ckpt_spans=self.cfg.train.ckpt_spans,
            )
        return self._ckpt_writer

    def save_checkpoint(self, publication: Optional[dict] = None,
                        wait: bool = False) -> bool:
        """Checkpoint the current state. Synchronous by default; with
        train.ckpt_async the fit loop only snapshots (device arrays are
        pinned + D2H transfers started, data_state captured HERE — its
        allgather is a collective) and the background writer owns the
        disk. Returns False only when an async submit was skipped
        because a save is still in flight; `wait=True` forces the save
        to be on disk when this returns (halt/signal/end-of-fit paths)."""
        from xflow_tpu.train import checkpoint as ckpt

        t0_wall, t0 = time.time(), time.perf_counter()
        data_state = self._data_state_record()
        if self._ckpt_async_on():
            w = self._ensure_ckpt_writer()
            if wait:
                # a final save must not be skippable: drain whatever is
                # in flight first, then the submit always lands. Re-stamp
                # the queue instant AFTER the drain — queued_ts is this
                # save's cadence instant, and the --check interval gate
                # (at most one save in flight) reads it against the
                # previous save's committed_ts
                w.drain()
                t0_wall = time.time()
            job = ckpt.SaveJob(
                snapshot=ckpt.SaveSnapshot(
                    self.state, self._logical_widths()
                ),
                ckpt_dir=self.cfg.train.checkpoint_dir,
                fmt=self.cfg.train.checkpoint_format,
                replica_dir=self.cfg.train.ckpt_replica_dir,
                keep=self.cfg.train.keep_checkpoints,
                keep_replica=self.cfg.train.keep_replica_checkpoints,
                data_state=data_state,
                publication=publication,
                queued_ts=t0_wall,
            )
            ok = w.submit(job)
            if wait:
                w.drain()
            return ok
        if self._ckpt_writer is not None:
            # a mode flip (or the final synchronous paths of an async
            # run) must not interleave with an in-flight async write
            self._ckpt_writer.drain()
        if self.cfg.train.checkpoint_format == "orbax":
            # orbax stores the device arrays in their NATIVE (possibly
            # packed) layout, shard-parallel; npz stores the LOGICAL
            # layout so export tools and differently-configured runs
            # read one format
            ckpt.save_orbax(
                self.cfg.train.checkpoint_dir, self.state,
                data_state=data_state, publication=publication,
            )
        else:
            ckpt.save(
                self.cfg.train.checkpoint_dir,
                self.state,
                self._logical_widths(),
                data_state=data_state,
                publication=publication,
            )
        self._ckpt_span("checkpoint_save", t0_wall, t0, int(self.state.step))
        # retention + stale-uncommitted sweep AFTER the commit: the save
        # that just landed proves no writer owns the swept debris
        ckpt.prune_checkpoints(
            self.cfg.train.checkpoint_dir,
            self.cfg.train.keep_checkpoints,
            fmt=self.cfg.train.checkpoint_format,
        )
        if self.cfg.train.ckpt_replica_dir and jax.process_index() == 0:
            # synchronous runs mirror inline (same commit contract, no
            # writer thread); a replica failure never harms the primary
            try:
                ckpt.mirror_step(
                    self.cfg.train.checkpoint_dir,
                    self.cfg.train.ckpt_replica_dir,
                    int(self.state.step),
                    fmt=self.cfg.train.checkpoint_format,
                )
                ckpt.prune_checkpoints(
                    self.cfg.train.ckpt_replica_dir,
                    self.cfg.train.keep_replica_checkpoints,
                    fmt=self.cfg.train.checkpoint_format,
                )
            except Exception as e:  # noqa: BLE001
                print(
                    f"# checkpoint: replica mirror of step "
                    f"{int(self.state.step)} failed "
                    f"({type(e).__name__}: {e}); the primary commit "
                    "stands",
                    file=sys.stderr,
                )
        return True

    def _logical_widths(self) -> dict:
        """{table: K} logical row widths, for unpacking packed storage."""
        return {
            name: trailing[0]
            for name, trailing in self.model.table_specs(self.cfg).items()
            if trailing
        }

    def export_sparse(self, out_path: str, table: str = "w") -> int:
        """Serving export of a table's nonzero rows, unpacking the live
        packed storage via the model's logical widths (checkpoint.export_sparse)."""
        from xflow_tpu.train import checkpoint as ckpt

        return ckpt.export_sparse(
            self.state, out_path, table, logical_widths=self._logical_widths()
        )

    def maybe_restore(self) -> bool:
        from xflow_tpu.train import checkpoint as ckpt

        if not (self.cfg.train.checkpoint_dir and self.cfg.train.resume):
            return False
        cdir = self.cfg.train.checkpoint_dir
        fmt = self.cfg.train.checkpoint_format
        # self-healing restore: the newest checkpoint failing to load
        # (truncated npz, corrupt orbax shard, a DIGEST mismatch against
        # the meta written at save — the silent-bit-flip case) walks
        # back to the previous committed step instead of killing the
        # resume (restore_any logs what it skipped and why). The
        # restore itself is topology-agnostic: each leaf lands on the
        # CURRENT state's sharding, whatever world size/engine wrote
        # the checkpoint. No checkpoint at all = fresh start; raises
        # only when checkpoints exist and NONE loads.
        t0_wall, t0 = time.time(), time.perf_counter()
        try:
            # the walk covers BOTH tiers: a primary step that is
            # missing or digest-poisoned restores from the replica
            # mirror (train.ckpt_replica_dir) before falling back to
            # an older step
            self.state, step, src = ckpt.restore_tiered(
                cdir, self.state, fmt=fmt,
                verify=self.cfg.train.checkpoint_verify,
                replica_dir=self.cfg.train.ckpt_replica_dir or None,
            )
        except FileNotFoundError:
            return False
        self._ckpt_span("checkpoint_restore", t0_wall, t0, int(step))
        # the data-stream position travels with the step that actually
        # restored (a walk-back must not pair step N-1's weights with
        # step N's stream offset) and from the TIER that restored it;
        # missing/unreadable data_state downgrades to a fresh stream
        # inside read_data_state
        self._resume_data_state = ckpt.read_data_state(src, step, fmt=fmt)
        return True


def _slot_any(mask2d, K: int):
    """Per-SLOT any over the row width K — packed storage
    ([S/pack, pack*K], ops/sorted_table.pack_table) groups pack slots
    per stored row, and an any over the full stored row would count
    8-slot groups, not slots. Grouped by a 0/1 matmul, NOT by a reshape
    to [S/pack, pack, K]: a minor dimension of K=11 is a relayout the
    TPU compiler spent 225 s on for that one op at 2^24 slots (PERF.md,
    PR 22), and 0/1 operands with sums <= K are exact at any matmul
    precision."""
    width = mask2d.shape[1]
    group = jnp.arange(width)[:, None] // K == jnp.arange(width // K)
    return (mask2d.astype(jnp.float32) @ group.astype(jnp.float32)) > 0

