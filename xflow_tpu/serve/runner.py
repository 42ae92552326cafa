"""The serve runner: checkpoint-backed online prediction with hot reload.

Loads any COMMITTED checkpoint into device-resident tables and answers
pCTR batches through the SAME jitted forward the trainer's evaluate
uses (models/predict.py — one function, so offline eval and online
serving cannot drift). Three properties carry the design:

- **Reshard-on-load** (PR 5): the restore paths place every leaf onto
  whatever devices serving has, so an N-rank training checkpoint loads
  on a 1-chip serving box or a serving mesh without conversion. The
  template the restore fills is built with `jax.eval_shape` — shapes
  and shardings only, no throwaway allocation — and for npz the
  optimizer state is skipped entirely (serving never reads n/z; the
  tables-only template makes the restore read 1/3 of the bytes).

- **Hot reload, double-buffered**: a background CheckpointWatcher polls
  the run dir for a NEWER committed step and loads it OFF the request
  path; the swap is one reference assignment (`self._gen = new`).
  In-flight requests captured the previous Generation object and
  finish on the old tables; new requests see the new one. No lock is
  held across a predict, nothing blocks, nothing drops. Every response
  carries the generation + checkpoint step that answered it.

- **Bad checkpoint ≠ outage**: a reload that fails (corrupt file,
  digest mismatch, torn copy) logs + emits a `reload_failed` event and
  KEEPS SERVING the current generation — restore_any's walk-back means
  a corrupt newest step quietly restores the previous committed one,
  and the runner refuses to "reload" backwards to the step it already
  serves (docs/SERVING.md failure matrix).
"""

from __future__ import annotations

import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from xflow_tpu.config import Config
from xflow_tpu.serve.coalescer import (
    PackedBatch,
    RowBlock,
    assemble_batch,
    assemble_packed,
    pack_arrays,
)


class BadRequest(ValueError):
    """A request the server answers with 400: malformed row, no
    parseable features. The serving analog of the data pipeline's
    bad-record quarantine (data/pipeline.py): reject and count the
    record, never crash the process."""


_NATIVE_READY: Optional[bool] = None  # the library's load, tried once a process


def native_parser_ready(dcfg) -> bool:
    """Whether requests are parsed by the native parser: the rule
    training's pipeline has — `data.use_native_parser` and the library
    builds and loads (else one warning, and the Python parser). The
    first call builds or loads it; `ServeRunner.load()` makes that
    call, so the cost lands in set-up and never in a request."""
    global _NATIVE_READY
    if not dcfg.use_native_parser:
        return False
    if _NATIVE_READY is None:
        try:
            from xflow_tpu.data.native import get_lib

            get_lib()
            _NATIVE_READY = True
        except (ImportError, OSError, RuntimeError, subprocess.SubprocessError) as e:
            from xflow_tpu.data.pipeline import _warn_python_parser

            _warn_python_parser(e)
            _NATIVE_READY = False
    return _NATIVE_READY


_ASSEMBLY_READY: Optional[bool] = None  # the GIL-keeping handle's load, tried once


def native_assembly_ready() -> bool:
    """Whether a solo runner packs its batches with the native
    `xf_assemble_rows` (serve/coalescer.py assemble_packed): the library
    builds and loads (else one warning, and `assemble_batch`'s dict).
    Not a config key: the library is the only condition."""
    global _ASSEMBLY_READY
    if _ASSEMBLY_READY is None:
        try:
            from xflow_tpu.data.native import get_gil_lib

            get_gil_lib()
            _ASSEMBLY_READY = True
        except (ImportError, OSError, RuntimeError, subprocess.SubprocessError) as e:
            from xflow_tpu.telemetry import warn_once

            warn_once(
                "native_assembly",
                f"native batch assembly unavailable ({type(e).__name__}: {e}); "
                "serve batches are assembled in numpy",
            )
            _ASSEMBLY_READY = False
    return _ASSEMBLY_READY


def unpack_batch(packed, max_nnz: int) -> dict:
    """In the program: a `PackedBatch` vector -> the {slots, fields,
    mask, row_mask} batch `models/predict.py predict_fn` reads. Static
    slices and reshapes; the mask's bits are the float32 mask's, exact."""
    import jax.numpy as jnp
    from jax import lax

    plane = (packed.shape[0] - 1) // 3
    shape = (plane // max_nnz, max_nnz)
    return {
        "slots": packed[:plane].reshape(shape),
        "fields": packed[plane:2 * plane].reshape(shape),
        "mask": lax.bitcast_convert_type(packed[2 * plane:3 * plane], jnp.float32).reshape(shape),
        "row_mask": (jnp.arange(shape[0]) < packed[3 * plane]).astype(jnp.float32),
    }


def make_packed_predict_fn(model, cfg: Config, recorder=None, name: str = "predict",
                           table_formats=None):
    """`make_predict_fn`'s program over a `PackedBatch` vector: unpack,
    then the unchanged forward. Its function keeps the name `step`, so
    the program keeps its name on a device trace (`jit_step`)."""
    from xflow_tpu.models.predict import jit_predict, predict_fn

    max_nnz = int(cfg.data.max_nnz)

    def step(tables, packed):
        return predict_fn(tables, unpack_batch(packed, max_nnz), model, cfg)

    return jit_predict(step, recorder=recorder, name=name, table_formats=table_formats)


def parse_request(rows: list, dcfg) -> RowBlock:
    """Parse request rows (libffm feature lists, optional leading label
    ignored) into one padded RowBlock through the SAME hash path
    training uses, so a served feature lands in the table slot it
    trained into: the native parser's `parse_row` (GIL released for
    the call) when `native_parser_ready`, else data/libffm.parse_line.
    Both give the same block, row for row.

    Raises BadRequest on a non-string row or a row with zero parseable
    features — the quarantine philosophy: a row whose features ALL
    failed to parse must not silently predict the bias."""
    if native_parser_ready(dcfg):
        block = _parse_native(rows, dcfg)
        if block is not None:
            return block
    return _parse_python(rows, dcfg)


def _bad_row(i: int, row) -> BadRequest:
    return BadRequest(f"row {i}: no parseable field:feature tokens in {row!r}")


def _not_a_string(i: int, row) -> BadRequest:
    return BadRequest(f"row {i}: expected a string, got {type(row).__name__}")


def _parse_native(rows: list, dcfg) -> Optional[RowBlock]:
    """The native read of `rows` (parser.cc xf_parse_rows), or None
    where a row holds a lone surrogate: it has no UTF-8 bytes to hash,
    and the Python parser answers it with a 400."""
    from xflow_tpu.data.native import native_parse_rows

    n = next((i for i, r in enumerate(rows) if not isinstance(r, str)), len(rows))
    try:
        encoded = [r.encode("utf-8") for r in rows[:n]]
    except UnicodeEncodeError:
        return None
    offsets = np.zeros((n + 1,), np.int64)
    np.cumsum(np.fromiter(map(len, encoded), np.int64, n), out=offsets[1:])
    rc, slots, fields, mask, nnz = native_parse_rows(b"".join(encoded), offsets, dcfg)
    if rc < n:
        raise _bad_row(rc, rows[rc])
    if n < len(rows):
        raise _not_a_string(n, rows[n])
    return RowBlock(slots, fields, mask, nnz, native=True)


def _parse_python(rows: list, dcfg) -> RowBlock:
    from xflow_tpu.data.libffm import parse_line

    fields_rows, slots_rows = [], []
    for i, row in enumerate(rows):
        if not isinstance(row, str):
            raise _not_a_string(i, row)
        # no tab = features-only (the serving shape); a tab means the
        # client sent a full libffm line and the label is ignored
        line = row if "\t" in row else "0\t" + row
        try:
            parsed = parse_line(line, dcfg.log2_slots, dcfg.hash_salt)
        except UnicodeEncodeError as e:
            raise BadRequest(f"row {i}: a feature id is not UTF-8 text ({e.reason})") from None
        if parsed is None or parsed[2].size == 0:
            raise _bad_row(i, row)
        _, f, s = parsed
        fields_rows.append(f)
        slots_rows.append(s)
    return RowBlock.from_rows(fields_rows, slots_rows, dcfg.max_nnz)


def parse_rows(rows: list, dcfg) -> tuple[list, list]:
    """`parse_request` as per-row (fields, slots) int32 arrays, each
    row its first data.max_nnz features."""
    b = parse_request(rows, dcfg)
    kept = np.minimum(b.nnz, b.slots.shape[1])
    return (
        [b.fields[i, :k] for i, k in enumerate(kept)],
        [b.slots[i, :k] for i, k in enumerate(kept)],
    )


def pinned_table_formats(tables, device) -> Optional[dict]:
    """The formats a one-chip runner on `device` places a generation's
    tables in at load, and compiles its predict rungs for: training's rule
    (train/engine.py `kernel_layout`). On a TPU a rank-2 table — packed
    [S/pack, pack·K] — is pinned row-major in (8, 128) tiles, the layout
    its row gather reads; in the client's default, transposed and
    unpadded, every predict call would first re-lay the whole table.
    Every other leaf (LR's 1-D table) keeps the device's default. None
    where nothing is pinned: off the TPU, or no rank-2 table."""
    from jax.experimental.layout import Format
    from jax.sharding import SingleDeviceSharding

    from xflow_tpu.train import engine

    sh = SingleDeviceSharding(device)
    layout = engine.kernel_layout(device)
    formats = {
        k: Format(layout, sh) if layout is not None and v.ndim == 2 else sh
        for k, v in tables.items()
    }
    return formats if any(isinstance(f, Format) for f in formats.values()) else None


@dataclass
class Generation:
    """One loaded model generation: the serving tables + provenance.

    `publication` is the checkpoint's publication.json sidecar when the
    trainer published it (train.publish_every, checkpoint
    .read_publication): the ingest trace id + timestamps that make the
    generation's DATA FRESHNESS measurable (docs/SERVING.md
    "Freshness"). None for unpublished checkpoints — every freshness
    surface (gauge, spans, /healthz field) simply stays absent, keeping
    the off-path byte-identical. `reload_span` is the span id of the
    swap that installed this generation (when a span sink is bound) —
    the parent the first-served-prediction span links under."""

    tables: dict
    step: int
    gen: int
    loaded_ts: float = field(default_factory=time.time)
    publication: Optional[dict] = None
    reload_span: str = ""

    def freshness_s(self, now: Optional[float] = None) -> Optional[float]:
        """Seconds between the served model's newest ingested row and
        `now` — the data_freshness_s gauge. None when this generation
        carries no publication (or a malformed one): absence means
        "not measurable", never a fake 0."""
        pub = self.publication
        ts = pub.get("ingest_ts") if isinstance(pub, dict) else None
        if not isinstance(ts, (int, float)) or not np.isfinite(ts):
            return None
        return max((time.time() if now is None else now) - float(ts), 0.0)


class ServeRunner:
    """Checkpoint-backed pCTR prediction (single process; the serving
    mesh is whatever local devices exist — pass `mesh` to pjit-shard
    the tables over them, None for single-device)."""

    def __init__(self, cfg: Config, mesh=None, recorder=None):
        from xflow_tpu.models import get_model
        from xflow_tpu.optim import get_optimizer

        self.cfg = cfg
        self.mesh = mesh
        self.model = get_model(cfg.model.name)
        self._optimizer = get_optimizer(cfg.optim.name)
        self._gen: Optional[Generation] = None
        self._gen_counter = 0
        self._reload_lock = threading.Lock()  # one loader at a time
        # hot-reload spans (docs/OBSERVABILITY.md "Request tracing"):
        # when serve_main binds a stamped appender here, every reload
        # swap emits one kind="span" record (start/end + bytes), so
        # request_trace --timeline can overlay swaps against latency
        # spikes. None (default) = no span, byte-identical streams.
        self.span_sink = None
        # compile accounting (train.compile_metrics): the predict
        # program routes through the same CompileRecorder seam the
        # trainer's engines use, so a serving run's stream carries its
        # kind="compile" record too (serve_main binds the sink)
        if recorder is None and cfg.train.compile_metrics:
            from xflow_tpu.telemetry import CompileRecorder

            recorder = CompileRecorder()
        self.compile_recorder = recorder
        if mesh is not None:
            from xflow_tpu.parallel.mesh import batch_sharding
            from xflow_tpu.parallel.train_step import make_sharded_eval_step

            self._predict_step = make_sharded_eval_step(
                self.model, cfg, mesh, recorder=recorder
            )
            bsh = batch_sharding(mesh)
            import jax

            self._put = lambda arrays: {
                k: jax.device_put(np.asarray(v), bsh[k]) for k, v in arrays.items()
            }
            # the batch-shape ladder is solo-only: a sharded eval step's
            # batch axis must divide the mesh, so the mesh path keeps
            # its single [max_batch] program (docs/SERVING.md)
            self.rungs = (int(cfg.serve.max_batch),)
            self._predict_steps = {self.rungs[0]: self._predict_step}
            self._table_formats = None
            self.packs_batches = False
        else:
            import jax

            from xflow_tpu.models.predict import make_predict_fn
            from xflow_tpu.serve.autotune import parse_ladder

            # the precompiled batch-shape ladder (serve/autotune.py):
            # one jitted program PER rung, each with its own program
            # name so compile accounting stays exactly-once per
            # (program, signature). An unconfigured ladder collapses to
            # the single "predict.serve" program — byte-identical
            # compile records to the pre-ladder build.
            self.rungs = parse_ladder(cfg.serve)
            # decided once: `load()` places every generation in these
            # formats, and each rung is compiled for them
            self._table_formats = pinned_table_formats(
                self._template().tables, jax.devices()[0]
            )
            if len(self.rungs) == 1:
                names = {self.rungs[0]: "predict.serve"}
            else:
                names = {r: f"predict.serve.b{r}" for r in self.rungs}
            # a batch's host buffer in one native call that keeps the
            # GIL, and one argument to its program (docs/SERVING.md
            # "Request path"); numpy's dict where the library is missing
            self.packs_batches = native_assembly_ready()
            make = make_packed_predict_fn if self.packs_batches else make_predict_fn
            self._predict_steps = {
                r: make(
                    self.model, cfg, recorder=recorder, name=names[r],
                    table_formats=self._table_formats,
                )
                for r in self.rungs
            }
            self._predict_step = self._predict_steps[self.rungs[-1]]
            self._put = jax.device_put

    # ------------------------------------------------------------- loading
    def _template(self):
        """An allocation-free restore template: the state's shapes (and
        shardings, on a mesh) as ShapeDtypeStructs. npz skips the
        optimizer state (restore() fills exactly what the template
        asks for); orbax restores the full tree (its tree-structure
        contract) and the opt arrays drop right after."""
        import jax

        from xflow_tpu.train.state import TrainState, init_state

        abstract = jax.eval_shape(
            lambda: init_state(self.model, self._optimizer, self.cfg)
        )
        if self.mesh is not None:
            from xflow_tpu.parallel.mesh import state_shardings

            sh = state_shardings(abstract, self.mesh)
            abstract = jax.tree.map(
                lambda s, shd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shd),
                abstract,
                sh,
            )
        if self.cfg.train.checkpoint_format != "orbax":
            abstract = TrainState(
                tables=abstract.tables, opt_state={}, step=abstract.step
            )
        return abstract

    @property
    def generation(self) -> Optional[Generation]:
        return self._gen

    @property
    def step(self) -> int:
        return self._gen.step if self._gen else -1

    def latest_committed_step(self) -> Optional[int]:
        """Newest committed step across BOTH checkpoint tiers — the
        replica (train.ckpt_replica_dir) counts, so a degraded trainer
        writing replica-only still advances the watcher."""
        from xflow_tpu.train import checkpoint as ckpt

        fmt = self.cfg.train.checkpoint_format
        dirs = [self.cfg.train.checkpoint_dir]
        rdir = self.cfg.train.ckpt_replica_dir
        if rdir and rdir not in dirs:
            dirs.append(rdir)
        steps = [s for d in dirs for s in ckpt.tier_steps(d, fmt)]
        return max(steps, default=None)

    def load(self) -> Generation:
        """Load the newest committed checkpoint (walk-back on corrupt
        newer steps, digest-verified per train.checkpoint_verify) and
        swap it in. Raises when no checkpoint loads at all — at
        STARTUP that is fatal; the watcher wraps reloads so a later
        failure never kills serving."""
        from xflow_tpu.train import checkpoint as ckpt

        native_parser_ready(self.cfg.data)  # the request parser, built in set-up
        with self._reload_lock:
            is_reload = self._gen is not None
            t0_wall, t0 = time.time(), time.perf_counter()
            # tiered walk: a digest-poisoned primary step restores from
            # the replica mirror before falling back to an older step —
            # serving never drops a request over one bad volume
            state, step, src = ckpt.restore_tiered(
                self.cfg.train.checkpoint_dir,
                self._template(),
                fmt=self.cfg.train.checkpoint_format,
                verify=self.cfg.train.checkpoint_verify,
                replica_dir=self.cfg.train.ckpt_replica_dir or None,
            )
            if self._gen is not None and step <= self._gen.step:
                # restore_any walked back to (or re-found) what we
                # already serve — swapping would REGRESS the generation
                raise RuntimeError(
                    f"newest loadable checkpoint is step {step}, already "
                    f"serving step {self._gen.step} — keeping the current "
                    "generation"
                )
            tables = state.tables
            del state  # orbax restores n and z too: gone before the relayout
            # placed before the swap: no request sees it unpinned
            tables, pinned, pinned_bytes = self._pin(tables)
            self._gen_counter += 1
            # publication sidecar (train.publish_every): best-effort —
            # read_publication returns None for unpublished steps and
            # logs-and-downgrades on a damaged sidecar; a publication
            # must never gate the swap itself
            # read the sidecar from the tier that actually restored
            pub = ckpt.read_publication(
                src, int(step),
                fmt=self.cfg.train.checkpoint_format,
            )
            gen = Generation(
                tables=tables, step=int(step), gen=self._gen_counter,
                publication=pub if isinstance(pub, dict) else None,
            )
            # the swap: one reference assignment — in-flight requests
            # hold the old Generation and finish on the old tables
            self._gen = gen
            if self.span_sink is not None:
                # the span covers restore-read through swap — exactly
                # the window a reload can lengthen request queues in
                import jax

                nbytes = int(sum(x.nbytes for x in jax.tree.leaves(tables)))
                trace = pub.get("trace") if isinstance(pub, dict) else None
                if isinstance(trace, str) and trace:
                    # a PUBLISHED step's swap CONTINUES the ingest trace
                    # (parented under the trainer's publish span) — the
                    # publish→swap edge of the freshness Δ
                    from xflow_tpu.tracing import emit_linked_span

                    rec = emit_linked_span(
                        self.span_sink,
                        "reload" if is_reload else "serve_load",
                        t0_wall,
                        time.perf_counter() - t0,
                        trace=trace,
                        parent=pub.get("span") or None,
                        step=gen.step,
                        generation=gen.gen,
                        bytes=nbytes,
                        pinned_leaves=pinned,
                        pinned_bytes=pinned_bytes,
                    )
                    gen.reload_span = rec["span"]
                else:
                    from xflow_tpu.tracing import emit_op_span

                    emit_op_span(
                        self.span_sink,
                        "reload" if is_reload else "serve_load",
                        t0_wall,
                        time.perf_counter() - t0,
                        step=gen.step,
                        generation=gen.gen,
                        bytes=nbytes,
                        pinned_leaves=pinned,
                        pinned_bytes=pinned_bytes,
                    )
            return gen

    def _pin(self, tables) -> tuple:
        """A restored generation's tables -> (the tables in the formats
        the predict rungs are compiled for, leaves pinned, their bytes as
        the device holds them, padding included). Leaf by leaf, as
        `Engine.place_state` does: each relayout is waited for and its
        source deleted, so no more than one leaf exists twice at any
        moment. Two generations, one serving and one being placed, must
        both fit the device (docs/SERVING.md "Resident layout")."""
        if self._table_formats is None:
            return tables, 0, 0
        import jax
        from jax.experimental.layout import Format

        from xflow_tpu.compile_cache import no_persistent_cache

        out, pinned, nbytes = dict(tables), 0, 0
        with no_persistent_cache():  # the relayout hands back a pinned leaf
            for k, fmt in self._table_formats.items():
                if not isinstance(fmt, Format):
                    continue
                old = out[k]
                out[k] = jax.block_until_ready(jax.device_put(old, fmt))
                if out[k] is not old:
                    old.delete()
                pinned += 1
                nbytes += out[k].on_device_size_in_bytes()
        return out, pinned, nbytes

    def maybe_reload(self) -> Optional[Generation]:
        """Reload iff a COMMITTED step newer than the serving one
        exists. Returns the new Generation, or None (nothing newer, or
        the reload failed — logged, old generation keeps serving)."""
        try:
            latest = self.latest_committed_step()
            if latest is None or (self._gen and latest <= self._gen.step):
                return None
            gen = self.load()
            print(
                f"serve: hot reload: now serving step {gen.step} "
                f"(generation {gen.gen})",
                file=sys.stderr,
            )
            return gen
        except Exception as e:  # noqa: BLE001 — ANY reload failure
            # (torn copy, digest mismatch, walk-back to the serving
            # step) keeps the current generation serving
            print(
                f"serve: reload failed ({type(e).__name__}: {e}); "
                f"keeping generation {self._gen.gen if self._gen else '?'} "
                f"(step {self.step})",
                file=sys.stderr,
            )
            return None

    # ----------------------------------------------------------- predicting
    def assemble(self, group: list, rung: int) -> tuple:
        """A group of requests as one padded batch of `rung` rows, in the
        form this runner's programs take: a `PackedBatch` where
        `packs_batches`, else `assemble_batch`'s dict. -> (batch, spans)."""
        make = assemble_packed if self.packs_batches else assemble_batch
        return make(group, rung, self.cfg.data.max_nnz)

    def predict(self, arrays) -> tuple[np.ndarray, Generation]:
        """One device batch: row-major {slots, fields, mask, row_mask}
        (a dict, or the `PackedBatch` `assemble` makes) -> (pctr [B]
        host array, the Generation that answered). The generation is
        captured ONCE before dispatch so a concurrent swap cannot split
        a batch across models."""
        gen = self._gen
        if gen is None:
            raise RuntimeError("no checkpoint loaded; call load() first")
        # ladder dispatch: the batch's leading dim picks its rung's
        # compiled program; an off-ladder shape (direct predict()
        # callers) falls back to jit's own shape specialization
        if self.packs_batches:
            batch = arrays if isinstance(arrays, PackedBatch) else pack_arrays(arrays)
            fn = self._predict_steps.get(batch.rung, self._predict_step)
            # the numpy vector straight into the call: its transfer and
            # the dispatch are one C++ call
            p = fn(gen.tables, batch.buf)
        else:
            fn = self._predict_steps.get(
                int(arrays["slots"].shape[0]), self._predict_step
            )
            p = fn(gen.tables, self._put(dict(arrays)))
        return np.asarray(p), gen

    def warmup(self) -> int:
        """AOT-compile every ladder rung before traffic arrives: one
        all-padding batch per rung through the real predict path, so
        the first real request at any rung never pays its compile.
        Returns the number of rungs warmed (serve_main logs it)."""
        if self._gen is None:
            raise RuntimeError("no checkpoint loaded; call load() first")
        for r in self.rungs:
            self.predict(self.assemble([], r)[0])
        return len(self.rungs)

    def predict_rows(self, rows: list) -> tuple[np.ndarray, Generation]:
        """Convenience (C API / tests): parse + pad + predict a list of
        libffm feature rows, chunking by serve.max_batch so the compiled
        batch shape stays fixed. Returns (pctr [len(rows)], generation)."""
        from xflow_tpu.serve.coalescer import PendingRequest

        block = parse_request(rows, self.cfg.data)
        B = self.cfg.serve.max_batch
        out = np.empty((len(rows),), np.float32)
        gen = None
        for lo in range(0, len(rows), B):
            req = PendingRequest(block=block.part(lo, lo + B))
            batch, _ = self.assemble([req], B)
            p, gen = self.predict(batch)
            out[lo : lo + req.num_rows] = p[: req.num_rows]
        return out, gen


class CheckpointWatcher(threading.Thread):
    """Polls the checkpoint dir every `poll_s` for a newer COMMITTED
    step and hot-reloads it off the request path. `on_reload(gen)` /
    `on_failed()` hooks feed the serve telemetry stream.

    `stagger_s` (the fleet's reload stagger, docs/SERVING.md "Fleet"):
    delay acting on a NEWLY noticed step by this long. A reload pauses
    the replica's request path for the restore's read time; if every
    replica of a fleet reloads the instant a step commits, the whole
    fleet pauses at once — the one synchronized hiccup the fleet
    exists to remove. `xflow serve-fleet` gives replica k a stagger of
    k * serve.reload_stagger_s (replica 0 reloads immediately), so at
    most one replica is swapping at any moment."""

    def __init__(
        self,
        runner: ServeRunner,
        poll_s: float = 2.0,
        on_reload=None,
        on_failed=None,
        stagger_s: float = 0.0,
    ):
        super().__init__(daemon=True, name="xflow-serve-watcher")
        self._runner = runner
        self._poll = max(float(poll_s), 0.05)
        self._stagger_s = max(float(stagger_s), 0.0)
        self._stop_evt = threading.Event()
        self._on_reload = on_reload
        self._on_failed = on_failed
        self._failed_step = None  # newest step that failed to load:
        # retry only when a DIFFERENT step commits — a permanently
        # corrupt checkpoint must not re-read the whole previous
        # checkpoint from disk (and spam reload_failed) every poll
        self.reloads = 0
        self.failures = 0

    def run(self) -> None:
        while not self._stop_evt.wait(self._poll):
            try:
                latest = self._runner.latest_committed_step()
            except Exception:
                continue
            if (
                latest is None
                or latest <= self._runner.step
                or latest == self._failed_step
            ):
                continue
            if self._stagger_s > 0 and self._stop_evt.wait(self._stagger_s):
                return  # shutdown mid-stagger: skip the reload
            gen = self._runner.maybe_reload()
            if gen is not None:
                self._failed_step = None
                self.reloads += 1
                if self._on_reload:
                    self._on_reload(gen)
            else:
                self._failed_step = latest
                self.failures += 1
                if self._on_failed:
                    self._on_failed()

    def close(self) -> None:
        self._stop_evt.set()
        if self.is_alive():
            self.join(timeout=10.0)
