"""Configuration tree for xflow-tpu.

The reference scatters its configuration across three primitive layers
(SURVEY.md §5 "Config / flag system"): positional argv
(`/root/reference/src/model/main.cc:16-45`), `DMLC_*` env vars for
topology, and hard-coded constants (FTRL hyperparams
`/root/reference/src/optimizer/ftrl.h:17-20`, SGD lr `sgd.h:16`, latent
dim `ftrl.h:16`, IO block size `lr_worker.h:68`). Here everything lives
in one dataclass tree with CLI/env overrides (see launch/cli.py).

Defaults reproduce the reference's hard-coded values so that a default
run is hyperparameter-equivalent to the reference's default run.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass(frozen=True)
class FTRLConfig:
    """FTRL-proximal hyperparameters.

    Defaults match `/root/reference/src/optimizer/ftrl.h:17-20`.
    """

    alpha: float = 5e-2
    beta: float = 1.0
    lambda1: float = 5e-5
    lambda2: float = 10.0


@dataclass(frozen=True)
class SGDConfig:
    """SGD hyperparameters. Default lr matches `/root/reference/src/optimizer/sgd.h:16`."""

    lr: float = 1e-3


@dataclass(frozen=True)
class OptimConfig:
    """Optimizer selection.

    The reference selects the optimizer by editing
    `/root/reference/src/model/server.h:24-29`; here it is config.
    `v_init_scale` / `v_init_sgd` reproduce the lazy v-table inits
    (`ftrl.h:117` ~N(0,1)*1e-2; `sgd.h:69` constant 1e-3).
    """

    name: str = "ftrl"  # "ftrl" | "sgd"
    ftrl: FTRLConfig = field(default_factory=FTRLConfig)
    sgd: SGDConfig = field(default_factory=SGDConfig)
    v_init_scale: float = 1e-2
    v_init_sgd: float = 1e-3
    # fused scatter+FTRL (ops/sorted_table.scatter_ftrl_sorted): the
    # single-device sorted FM step applies the optimizer INSIDE the
    # windowed scatter's block write (in-place state aliasing), so the
    # [S/8, 8K] table gradient never materializes in HBM. Measured
    # throughput-NEUTRAL vs the two-pass form (XLA already fuses that
    # chain; docs/PERF.md lever 5b) — the win is one table-sized
    # transient off peak HBM (738 MB at 2^24 FM). "auto" (default)
    # fuses the eligible FM config (ftrl + fused FM + flat sorted plan,
    # single device); "on" additionally covers the MVM product path —
    # measured ~3% slower there, so its memory win is an explicit
    # opt-in — and asserts eligibility loudly; "off" keeps the
    # two-pass form. Identical math either way (equality-tested; the
    # update runs on each window's COMPLETE gradient block; on-device
    # scatter_ftrl_* parity checks).
    fused_scatter: str = "auto"


@dataclass(frozen=True)
class ModelConfig:
    """Model selection and dims.

    `v_dim` default matches the reference latent dim
    (`/root/reference/src/optimizer/ftrl.h:16`, `fm_worker.h:92`).
    `num_fields` bounds the libffm field-group ids (bundled data uses 18,
    fields 0..17). `fm_standard` selects the textbook FM second-order
    term (per-latent-dim, with the 1/2 factor); the reference's FM
    couples latent dims through a shared accumulator
    (`/root/reference/src/model/fm/fm_worker.cc:178-196` sums v over all
    k into one scalar per row) — an accident SURVEY.md §7 says to fix,
    not replicate. Default is the standard form.
    """

    name: str = "lr"  # "lr" | "fm" | "mvm" | "ffm"
    v_dim: int = 10
    num_fields: int = 18
    # MVM exclusive-fields product path (models/mvm.py): when every
    # masked (row, field) has at most one occurrence — the natural
    # libffm shape — the field product collapses to a product over the
    # row's occurrences, computed through the same cache-resident
    # [B, ~24] row-sum kernel FM uses instead of the [B·nf, k+1]
    # segment aggregate (the measured MVM wall, docs/PERF.md 3a).
    # "auto": check each batch on the host and route duplicate-field
    # batches to the segment path. Single-process routes locally; the
    # multi-process fullshard engine coordinates the per-batch choice
    # through a rank-symmetric flag allgather
    # (train/engine.py `Engine.agree`) so every rank picks the
    # same mode; other multi-process engines raise on duplicates (no
    # coordination point — models/mvm.py resolve_mvm_product). "on":
    # require exclusive fields (raise on duplicates). "off": always
    # the general segment path.
    mvm_exclusive: str = "auto"
    # MVM factor form: False = plain view-sum product Π_f s (the
    # reference's live forward, mvm_worker.cc:202); True = Π_f (1 + s),
    # the bias-augmented form its OWN hand gradient assumes
    # (mvm_worker.cc:153-157 divides by 1 + v_sum; the `1+` forward is
    # commented out at :201). The plus-one form is what makes MVM
    # learnable from small inits: factors sit near 1 instead of near 0,
    # so the product — and every gradient, itself a product of the
    # row's OTHER factors — does not vanish multiplicatively with the
    # field count. Works on both the product and segment paths.
    mvm_plus_one: bool = False
    fm_standard: bool = True
    fm_half: bool = True
    # fused [S, 1+k] w+v table (one gather+scatter pass instead of two;
    # same math — docs/PERF.md lever 1). False = reference's two-table
    # layout (`fm_worker.cc:227-242`)
    fm_fused: bool = True


@dataclass(frozen=True)
class DataConfig:
    """Input pipeline configuration.

    `log2_slots` replaces the reference's unbounded 64-bit key space
    (hash of the feature-id string, `load_data_from_disk.cc:151`, stored
    sparsely in server hash maps) with a dense `2**log2_slots` table;
    collisions are accepted, as in the reference, and measurable via
    tools/collisions. `max_nnz` is the padded per-row feature capacity
    (bundled data has ~18). `block_bytes` mirrors the reference reader's
    block-buffered fread (`lr_worker.h:68` block_size=2 MiB).
    """

    train_path: str = ""
    test_path: str = ""
    batch_size: int = 1024
    max_nnz: int = 32
    log2_slots: int = 22
    hash_salt: int = 0
    block_bytes: int = 2 << 20
    drop_remainder: bool = False  # reference drops remainder rows (lr_worker.cc:190); we pad instead
    use_native_parser: bool = True  # C++ parser if built; falls back to Python
    # parser worker threads (reference: hardware_concurrency() pool,
    # thread_pool.h:70-86). 0 = auto (one per core, capped 16); 1 = the
    # sequential parser. Output is byte-identical either way (blocks are
    # reassembled in file order).
    parser_threads: int = 0
    # sorted-window table layout (ops/sorted_table.py): "auto" enables it
    # for single-device fused-FM and MVM training (where the windowed MXU
    # gather/scatter replaces latency-bound random HBM access); "on"/"off"
    # force it. Identical math either way (equality-tested).
    sorted_layout: str = "auto"
    # bf16 fast mode for the sorted-window Pallas kernels: table values
    # are read (and gradient rows written) through a single bf16 MXU
    # pass (8 mantissa bits) instead of the f32-accurate 3-term
    # decomposition — the standard bf16-training trade, +24% FM
    # throughput. Default off: table reads are then bit-exact and
    # gradients differ from the row-major path only in f32 summation
    # order (≤1 ulp per accumulated pair, as between any two reduction
    # schedules).
    sorted_bf16: bool = False
    # sub-batches per step for the sorted layout: the forward maps over
    # row-contiguous sub-batches so per-row aggregates stay cache-resident
    # (matters for MVM's [B·nf, k]); the optimizer still updates once per
    # batch, so the math is NS-invariant. 0 = auto (1 for FM; for MVM the
    # smallest power of two keeping B/NS·nf·(k+1)·4B under 16 MiB — the
    # measured sweet spot on v5e, docs/PERF.md).
    sorted_sub_batches: int = 0
    # host-side batch dedup for the ROW-MAJOR paths (reference analog:
    # per-minibatch unique-key Pull, lr_worker.cc:150-165): ship
    # (unique_slots, inverse) so the table gather moves U rows instead
    # of B*F (ops/sorted_table.dedup_slots). DEFAULT OFF, from
    # measurement: with packed tables the single-chip two-level gather
    # LOSES at every tested skew (hot-head U=168k: 303k vs 503k ex/s
    # direct — the [B, F] re-index gather costs as much as the direct
    # gather it replaces; docs/PERF.md lever 4). Turn "auto" on for
    # multi-chip GSPMD eval/fallback paths, where the win is CROSS-CHIP
    # gather volume over ICI (U rows instead of B*F through the
    # collectives), not local HBM traffic. "auto" applies to
    # single-process row-major batches only (multi-process cannot dedup
    # per batch: the unique count is data-dependent and the overflow
    # fallback would bake different collective programs on different
    # ranks); capacity = dedup_cap_frac * batch_size * max_nnz, the
    # first batch decides for the run.
    dedup: str = "off"
    dedup_cap_frac: float = 0.5
    # packed table storage (ops/sorted_table.py pack_table): vector
    # tables live as [S/8, 8K] instead of [S, K]. TPU HBM buffers are
    # (8, 128)-tiled, so a logical [S, 11] f32 table is STORED [S, 128]
    # — 11.6x its logical bytes (at 2^24 slots the FM FTRL state alone
    # is 3 x 8 GB and cannot fit one chip) and every elementwise
    # optimizer pass runs at 11/128 lane efficiency. Packed: 1.45x
    # padding and 88/128-lane FTRL. "auto" (default) packs whenever
    # num_slots % 8 == 0; "off" keeps logical [S, K] storage. Layout is
    # detected FROM THE SHAPE everywhere (pack_of), so hand-built
    # logical tables and old checkpoints keep working.
    packed_tables: str = "auto"
    # per-(source shard, owner block) occurrence buffer capacity, as a
    # multiple of the uniform-hash expectation Np/(D*T). Salted hashing
    # spreads slots near-uniformly, but a single hot feature's
    # occurrences all land in ONE owner block (the ps-lite analog has the
    # same imbalance: one server owns the hot key) — raise this for
    # heavily skewed data; overflow fails loudly at plan time.
    fullshard_slack: float = 2.0
    # packed shard cache (data/shardcache.py, docs/DATA.md): pre-hashed
    # binary sidecars (`<shard>.xfc`, built once by
    # `criteo_convert cache`) replace the per-epoch read/parse/hash/
    # batch/pad producer stages with np.memmap zero-copy slices —
    # batch assembly becomes an offset computation. "auto" (default)
    # uses a shard's cache whenever one exists, is fresh for this
    # config's hash parameters, and passes its crc32 digests (a stale
    # cache warns and falls back; a CORRUPT one is quarantined with a
    # logged text-path fallback — never a crash); "on" requires caches
    # to exist (missing/stale raise loudly; corruption still only
    # degrades); "off" never looks. Batches are bitwise-identical to
    # the text path's either way (pinned by tests/test_shardcache.py).
    cache: str = "auto"
    # where the .xfc files live: "" = sibling of each text shard; a
    # directory = `<cache_dir>/<shard basename>.xfc` (fast local disk
    # for caches of shards on slow shared storage)
    cache_dir: str = ""
    # bad-record budget (docs/ROBUSTNESS.md): a "bad" row is a labeled
    # line whose features ALL failed to parse (zero masked occurrences).
    # Both parsers keep such rows (a labeled line is an example), so an
    # entire epoch of garbage would train in silently — the reference
    # does exactly that (`load_data_from_disk.cc:150-153` skips
    # malformed tokens with no signal). Detection is batch-level
    # (row_mask on, feature mask all-zero), so the Python and native
    # parsing paths count identically. -1 = count + warn only; >= 0 =
    # raise BadRecordError once a file pass exceeds the budget.
    max_bad_rows: int = -1
    # "" = off; else bad rows are appended to this JSONL file
    # (source path, batch/row index, label) for offline triage
    quarantine_path: str = ""
    # ---- streaming source (docs/DATA.md "Streaming source") ----------
    # "off" (default): the exact batch pipeline above — every existing
    # stream stays byte-identical (no ingest records, no tail thread).
    # "tail": follow-the-tail mode — watch the train_path shard set for
    # new/growing libffm files, cut each poll's newly COMPLETED lines
    # into an immutable spool segment, convert it on arrival into a
    # packed .xfc cache (shardcache.write_shard_cache) so streamed data
    # rides the same device-rate path batch training does, and stamp
    # each segment with an ingest trace id (kind="ingest" record) the
    # freshness tooling follows across the train/serve boundary.
    stream: str = "off"
    # directory poll cadence while tailing (seconds)
    stream_poll_s: float = 0.25
    # end-of-stream idle timeout: no new complete rows for this long
    # ends the tail stream and the run (0 = follow forever). CI drills
    # set it so a tail run is bounded.
    stream_idle_s: float = 0.0
    # where spool segments and their .xfc caches land ("" = an
    # .xfstream dir next to the watched shards)
    stream_dir: str = ""


@dataclass(frozen=True)
class MeshConfig:
    """Device mesh: ('data', 'table').

    `data` is the analog of the reference's N worker processes
    (file-sharded async data parallelism), `table` the analog of its N
    key-range-sharded server processes (SURVEY.md §2 C13). -1 means
    "infer from available devices".
    """

    data: int = -1
    table: int = 1


@dataclass(frozen=True)
class TrainConfig:
    epochs: int = 60  # reference default (lr_worker.h:63)
    seed: int = 0
    eval_every: int = 0  # 0 = eval only at end, like the reference
    log_every: int = 100
    checkpoint_dir: str = ""
    checkpoint_every: int = 0  # steps; 0 = only at end if dir set
    checkpoint_format: str = "npz"  # "npz" (host-gathered) | "orbax" (sharded OCDBT)
    resume: bool = True
    pred_dump: bool = True  # write pred_<rank>_<block>.txt like lr_worker.cc:74-78
    # >0: streaming bucketed eval (local histograms + one collective; no
    # host ever holds the global pctr vector — the Criteo-1TB-scale path).
    # 0: exact rank-sum AUC with a host sort (reference parity,
    # base.h:84-110). -1 (default) = auto: exact when single-process,
    # 65536 buckets when multi-process — the exact path allgathers a
    # stacked [B, 3] array per eval batch, which dead-ends before
    # pod-scale eval (AUC error is bounded by bucket width, ~1/buckets).
    eval_buckets: int = -1
    metrics_path: str = ""  # JSONL per-step metrics stream ("" = stdout summary only)
    # size cap for the metrics JSONL (bytes; 0 = unbounded): past it
    # the file rolls to ONE <path>.1 sibling (jsonl.JsonlAppender), so
    # streaming/online trainers that never stop don't grow the stream
    # with uptime; read_jsonl folds the roll back in file order
    metrics_max_bytes: int = 0
    # checkpoint-lifecycle spans (docs/OBSERVABILITY.md "Request
    # tracing"): every checkpoint save/restore emits one kind="span"
    # record (start/end + bytes) into the metrics stream, so
    # tools/request_trace.py --timeline can overlay checkpoint and
    # hot-reload swaps against request-latency spikes. Off = the
    # pre-tracing record stream, byte-identical.
    ckpt_spans: bool = True
    profile_dir: str = ""  # jax.profiler trace output ("" = disabled)
    # programmatic trace window (telemetry.TraceWindow): with profile_dir
    # set and trace_start_step >= 1, the xprof trace starts just before
    # that step's dispatch — after compilation settles, so the window
    # shows the steady state instead of compile noise — and stops once
    # trace_num_steps steps have dispatched. 0 = legacy whole-run trace.
    trace_start_step: int = 0
    trace_num_steps: int = 20
    # preemption: on SIGTERM/SIGINT save a checkpoint at the next
    # coordination point and return early. Single-process coordinates
    # every step; multi-process runs agree on "stop at step N" through a
    # tiny flag allgather every `signal_sync_every` steps (a signal on
    # ANY rank stops ALL ranks at the same step, so the collective save
    # is rank-symmetric — round-2 weak #6). The reference loses all
    # weights on any termination (SURVEY.md §5 A3: server state is
    # in-memory only).
    ckpt_on_signal: bool = True
    # multi-process signal-coordination cadence, in steps (0 disables
    # the periodic allgather; preemption then degrades to the
    # checkpoint_every cadence). One [1]-int32 host allgather per
    # `signal_sync_every` steps is the entire cost.
    signal_sync_every: int = 100
    # non-finite guard (docs/ROBUSTNESS.md): every train step also
    # returns an `update_ok` flag — one jnp.isfinite reduction over the
    # loss and the GRADIENT as the optimizer is about to receive it,
    # decided before the write and computed INSIDE the SPMD program so
    # multi-process ranks agree for free (the flag is replicated; no
    # new host collectives). "skip" (default): a bad step hands the
    # optimizer a zero gradient — the identity for FTRL and SGD, so the
    # update is discarded on device with no second copy of the state,
    # no table-wide select and no table-wide sweep — is counted, and
    # training continues; "halt": abort on the first bad step, after
    # committing a checkpoint; "off": no check (a NaN batch silently
    # poisons the tables — the reference behavior).
    nonfinite_guard: str = "skip"
    # under "skip", this many CONSECUTIVE discarded steps abort anyway
    # (after a committed checkpoint): a stream of bad steps means the
    # data or the state is systematically poisoned, and skipping
    # forever would burn an epoch of compute learning nothing.
    # 0 = never abort.
    nonfinite_max_consecutive: int = 10
    # digest verification on restore (docs/ROBUSTNESS.md "Silent shard
    # corruption"): "auto" checks every stored array read against the
    # per-array digests meta.json recorded at save (checkpoint v3) —
    # a mismatch is a logged CheckpointDigestError and restore_any
    # walks back to the previous committed step; arrays without
    # digests (pre-v3 checkpoints, pod-scale multi-process orbax
    # saves) restore unverified. "off" skips the check entirely.
    checkpoint_verify: str = "auto"
    # checkpoint retention: keep the N newest COMMITTED checkpoints
    # and sweep stale uncommitted step dirs after each save (a crashed
    # save leaves a partial dir; readers already ignore it, this
    # reclaims the space). 0 = keep everything.
    keep_checkpoints: int = 0
    # asynchronous checkpointing (docs/ROBUSTNESS.md "Async tiered
    # checkpointing"): at checkpoint cadence the fit loop only SNAPSHOTS
    # — copy_to_host_async() on every table/optimizer leaf plus the
    # synchronously-captured data_state — and hands the snapshot to a
    # single background writer thread that serializes, digests, stages
    # the sidecars, and writes the COMMITTED marker last (the same
    # atomicity/walk-back contract as a synchronous save; a crash
    # mid-async-write is just the uncommitted-dir walk-back). At most
    # one save is in flight: a cadence hit while one is pending is a
    # logged, counted SKIP, never a queue; the halt/signal/end-of-fit
    # saves drain the writer so the run's last state is always durable.
    # Every async save emits one kind="ckpt" record per tier. Requires
    # a single process (the host-gather collectives cannot run on a
    # background thread; multi-process logs once and falls back to
    # synchronous saves). Default off = today's synchronous save path,
    # byte-identical (pinned by test).
    ckpt_async: bool = False
    # tier-2 checkpoint replica dir ("" = off): every committed step is
    # MIRRORED here — copy, digest re-verify of the replica's own bytes,
    # then the replica's own COMMITTED marker — so a lost/poisoned
    # primary volume costs no committed state. restore walks the UNION
    # of both tiers newest-step-first (primary preferred per step), and
    # under ckpt_async an ENOSPC/IO failure on the primary DEGRADES the
    # writer to replica-only saves instead of killing training
    # (docs/ROBUSTNESS.md failure matrix). The serve watcher reads the
    # same union, so a digest-poisoned primary hot-reloads from the
    # replica with zero dropped requests.
    ckpt_replica_dir: str = ""
    # replica-tier retention: keep_checkpoints semantics applied to
    # ckpt_replica_dir (0 = keep everything). Independent of the
    # primary's knob so the cheap tier can keep a deeper history.
    keep_replica_checkpoints: int = 0
    # in-run checkpoint publication cadence, in steps (0 = off): every
    # publish_every-th step commits a checkpoint through the atomic
    # staging contract WITH a publication.json sidecar stamped with the
    # newest ingest trace id whose data contributed to that step, and
    # emits one kind="publish" record plus one `publish` span carrying
    # that trace id — the train-side half of the freshness loop
    # (docs/SERVING.md "Freshness"). Requires checkpoint_dir.
    publish_every: int = 0
    # time-decayed sliding-window eval (streaming BucketAUC): each
    # eval_every pass multiplies the persistent bucket histograms by
    # this factor before folding the new pass in, so the logged
    # eval_auc tracks the recent window instead of restarting from
    # zero each pass. 0.0 (default) = per-pass-fresh histograms, the
    # exact pre-knob behavior.
    eval_window_decay: float = 0.0
    # model-health signals (docs/OBSERVABILITY.md "Health metrics"):
    # "norms" adds global grad-norm / update-norm / param-norm scalars to
    # every step's metrics output (fused into the jitted step — one
    # isfinite-style reduction per table, read back through the same
    # one-step-behind block the StepTimer uses, so no sync bubble) plus a
    # host-side loss EMA and live table-occupancy / collision-estimate
    # gauges; "full" additionally emits per-table norms. "off" (default)
    # leaves the step program untouched — zero overhead.
    health_metrics: str = "off"
    # loss-EMA decay for the health monitor (ema = d*ema + (1-d)*loss,
    # seeded by the first finite loss; McMahan et al. 2013 monitor
    # exactly this kind of smoothed online loss in production CTR)
    health_ema_decay: float = 0.99
    # liveness heartbeat JSONL ("" = off): one {step} record every
    # heartbeat_every steps plus start/final events, stamped
    # ts/rank/run_id/kind=heartbeat — the launcher watchdog and
    # metrics_report --health read these to flag dead ranks/stragglers
    heartbeat_path: str = ""
    heartbeat_every: int = 25
    # no-progress hang watchdog (0 = off): if no train step completes
    # for this many seconds, dump ALL thread stacks to stderr once per
    # stall (faulthandler), then re-arm when progress resumes. SIGUSR1
    # stack dumps are always installed during fit() (main thread only).
    hang_timeout_s: float = 0.0
    # input-pipeline stage profiler (docs/OBSERVABILITY.md
    # "Input-pipeline attribution"): attribute wall time per pipeline
    # stage — read/parse/hash/batch/pad/plan on the prefetch thread,
    # queue-wait/transfer/dispatch/device on the fit loop, plus the
    # prefetch queue's depth and producer-blocked gauges — into
    # kind="pipeline" window records in the metrics JSONL, read by
    # tools/pipeline_attrib.py (per-stage % table, bottleneck verdict,
    # host-gap bench record). Default off: the instrumented seams take
    # their exact pre-profiler code paths and the JSONL streams are
    # byte-identical to a build without the profiler (pinned by test).
    pipeline_metrics: bool = False
    # compile accounting (docs/OBSERVABILITY.md "Compile accounting"):
    # every step/predict compilation routes through a shared
    # telemetry.CompileRecorder — explicit .lower().compile() with the
    # compile timed and XLA's cost/memory analysis captured into
    # kind="compile" records in the metrics JSONL, plus the
    # {operation -> step phase} map (`op_scopes`; the one vocabulary,
    # telemetry.PHASE_LABELS) that tools/trace_attrib.py joins with a
    # device trace, and the recompile counter metrics_report --check gates
    # on ("each program compiles exactly once per run"). The compile
    # itself costs the same either way (jit would have built the same
    # executable lazily); off restores the implicit-jit path.
    compile_metrics: bool = True


@dataclass(frozen=True)
class ServeConfig:
    """Online-serving knobs (`xflow serve`, docs/SERVING.md).

    The model/data/train sections still apply at serve time: the model
    config must match the checkpoint (same contract as `xflow export`),
    `data.max_nnz`/`log2_slots`/`hash_salt` define the request hash
    path (a served feature must land in the slot it trained into), and
    `train.checkpoint_dir`/`checkpoint_format`/`checkpoint_verify`
    locate and gate what gets loaded.
    """

    host: str = "127.0.0.1"
    # TCP port (0 = pick a free one, reported in the ready line;
    # -1 = no TCP listener — unix_socket only)
    port: int = 8000
    # AF_UNIX socket path ("" = off): same HTTP protocol, for colocated
    # clients (the C API's native embedder) without the TCP stack
    unix_socket: str = ""
    # microbatching (serve/coalescer.py): requests queued inside this
    # window coalesce into ONE padded device batch — the window is the
    # idle-server latency floor and the busy-server throughput lever
    window_ms: float = 2.0
    # rows per device batch = the compiled batch shape (fixed, so the
    # predict program compiles once); also the per-request row cap
    max_batch: int = 256
    # backlog cap in rows; beyond it submits shed load with 503
    max_queue_rows: int = 8192
    # hot reload: poll the checkpoint dir for a newer COMMITTED step
    # this often (serve/runner.CheckpointWatcher); 0 < poll always on
    reload_poll_s: float = 2.0
    # kind="serve" telemetry JSONL ("" = off): QPS / batch-fill /
    # latency windows + reload events (docs/OBSERVABILITY.md)
    metrics_path: str = ""
    metrics_every_s: float = 5.0
    # size cap for the serve telemetry/span JSONL (bytes; 0 = unbounded):
    # past it the file rolls to a single <path>.1 sibling, so a
    # long-running fleet's streams are bounded at ~2x this
    # (jsonl.JsonlAppender; read_jsonl folds the roll transparently)
    metrics_max_bytes: int = 0
    # ---- request tracing (xflow_tpu/tracing.py, docs/OBSERVABILITY.md
    # "Request tracing") --------------------------------------------------
    # head-sampling rate for per-request span capture: each trace id
    # keeps/drops deterministically from its own hash, so the router
    # and every replica agree with no coordination. 0 (default) = off —
    # the serve JSONL output is byte-identical to a pre-tracing build.
    trace_sample_rate: float = 0.0
    # tail capture: any request slower than this (router budget or
    # replica-observed) — and any that errors, sheds, retries, or
    # hedges — is captured regardless of the sampling rate
    trace_slow_ms: float = 250.0
    # a request unanswered this long gets 503 (the device wedged)
    request_timeout_s: float = 30.0
    # ---- fleet (serve/fleet.py, `xflow serve-fleet`) -----------------
    # replica count for `serve-fleet` (each replica is one supervised
    # `xflow serve` process on its own port; docs/SERVING.md "Fleet")
    replicas: int = 2
    # per-replica hot-reload stagger: replica k delays acting on a newer
    # committed step by k * this many seconds, so the fleet never pauses
    # every replica for a checkpoint swap at once (0 = no stagger)
    reload_stagger_s: float = 1.0
    # ---- router (serve/router.py) ------------------------------------
    # replica health-check cadence (GET /healthz per replica); the same
    # loop runs circuit-breaker recovery (the half-open probe)
    health_poll_s: float = 0.5
    # consecutive failures (failed forwards or health checks) that eject
    # a replica into circuit-breaker OPEN state
    eject_failures: int = 3
    # how long an OPEN circuit waits before its half-open probe
    circuit_open_s: float = 2.0
    # per-request routing budget: retries/hedges must fit inside it;
    # exhausted = 503 deadline_exceeded back to the client
    route_deadline_ms: float = 2000.0
    # transparent retries on a DIFFERENT replica after a connect
    # failure / 503 (the "retry later" the coalescer's shed asks for)
    route_retries: int = 2
    # tail-latency hedging: a request outstanding this long fires a
    # duplicate at another healthy replica, first answer wins (0 = off)
    route_hedge_ms: float = 0.0
    # ---- brownout admission control (serve/coalescer.py) -------------
    # backlog above high_frac * max_queue_rows sustained for after_s
    # enters brownout: the coalescing window shrinks by window_factor
    # (drain faster) and low-priority requests (X-Request-Priority: low)
    # shed with 503 BEFORE the hard max_queue_rows cliff; backlog below
    # low_frac * max_queue_rows sustained for after_s exits it.
    brownout_high_frac: float = 0.5
    brownout_low_frac: float = 0.25
    brownout_after_s: float = 0.25
    brownout_window_factor: float = 0.25
    # ---- SLO autotuning (serve/autotune.py, docs/SERVING.md
    # "Autotuning") ----------------------------------------------------
    # closed-loop controller: each flushed telemetry window's queue-wait
    # vs device p99 decomposition steers window_ms (and the ladder rung)
    # toward slo_p99_ms. Off (default) leaves every knob exactly where
    # the config put it — the serve stream is byte-identical to a
    # pre-autotune build (pinned by test, like trace_sample_rate=0).
    autotune: bool = False
    # the total-latency p99 target the controller steers toward (ms)
    slo_p99_ms: float = 25.0
    # hysteresis band: no decision while total_p99 is within
    # slo * (1 ± band_frac) — the controller converges instead of
    # chasing window-to-window noise
    autotune_band_frac: float = 0.15
    # initial multiplicative step per decision; every direction
    # reversal halves the knob's step (damping), so an overshoot
    # cannot oscillate at constant amplitude
    autotune_step_frac: float = 0.5
    # window_ms floor: asked to shrink below it, the controller pins
    # there and emits ONE floor_pinned warning (unattainable SLO must
    # not flap the knob every window)
    autotune_min_window_ms: float = 0.25
    # precompiled batch-shape ladder ("16,64,256"; "" = max_batch
    # only): every rung AOT-compiles at startup and each batch flushes
    # at the smallest rung that fits, so small batches stop paying
    # full-max_batch padding. max_batch always joins as the top rung.
    ladder: str = ""


@dataclass(frozen=True)
class SyncConfig:
    """Cross-slice bounded-staleness table sync — the DCN tier of the
    two-tier topology (parallel/multislice.py, docs/DISTRIBUTED.md
    "Multi-slice bounded staleness"). Each slice trains synchronously
    inside its own mesh; between K-step blocks a host-level SliceSyncer
    exchanges additive table deltas with the other slices through a
    shared directory, with parameter-server failure semantics
    (timeout + retry/backoff on every wait, proceed-on-stale policy,
    dead slices dropped from the sync group)."""

    # off = no sync tier at all (byte-identical trainer behavior);
    # sync = wait for every live peer's current round (K is forced 0 —
    # lockstep across slices, today's fully-sync semantics);
    # bounded = wait only until every live peer is within staleness_k
    # rounds; async = never wait, apply whatever deltas have landed
    mode: str = "off"
    # the staleness bound K, in sync ROUNDS a live peer may trail
    # before the on_stale policy triggers (bounded mode only)
    staleness_k: int = 0
    # steps between sync rounds (the K-step scan block boundary)
    every_steps: int = 50
    # the shared sync directory (deltas + snapshots + membership);
    # launch-multislice wires it to <run_dir>/sync for every slice
    dir: str = ""
    # per-wait budget before a retry; every exchange is bounded — a
    # vanished peer costs timeout_s * (retries + 1), never a hang
    timeout_s: float = 30.0
    # staleness-wait retries, backoff_s * 2^attempt (jittered, the
    # supervise.backoff_delay curve) between them
    retries: int = 3
    backoff_s: float = 0.5
    # what a missed staleness bound does after the retry budget:
    # wait = keep training only after the bounded wait (counted);
    # proceed = check once and continue on stale state (counted)
    on_stale: str = "wait"
    # publish a full-state catch-up snapshot every this many rounds
    # (0 = never); a rejoining slice adopts the freshest one
    snapshot_every: int = 10


@dataclass(frozen=True)
class Config:
    model: ModelConfig = field(default_factory=ModelConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    data: DataConfig = field(default_factory=DataConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    sync: SyncConfig = field(default_factory=SyncConfig)

    @property
    def num_slots(self) -> int:
        return 1 << self.data.log2_slots


def _replace_nested(obj: Any, path: list[str], value: Any) -> Any:
    if len(path) == 1:
        fld = {f.name: f for f in dataclasses.fields(obj)}[path[0]]
        typ = fld.type
        cur = getattr(obj, path[0])
        if isinstance(cur, bool):
            if isinstance(value, str):
                value = value.lower() in ("1", "true", "yes", "on")
        elif isinstance(cur, int):
            value = int(value)
        elif isinstance(cur, float):
            value = float(value)
        return dataclasses.replace(obj, **{path[0]: value})
    child = getattr(obj, path[0])
    return dataclasses.replace(obj, **{path[0]: _replace_nested(child, path[1:], value)})


def override(cfg: Config, **dotted: Any) -> Config:
    """Apply dotted-path overrides: override(cfg, **{"optim.name": "sgd"})."""
    for key, value in dotted.items():
        cfg = _replace_nested(cfg, key.split("."), value)
    return cfg


def from_overrides(pairs: dict[str, Any], base: Optional[Config] = None) -> Config:
    return override(base or Config(), **pairs)
