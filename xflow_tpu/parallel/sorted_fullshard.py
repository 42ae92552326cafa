"""Fully-sharded sorted-window training: the pod-scale fast path.

The table AND its optimizer state shard over the WHOLE mesh —
``P(('data','table'), None)``, each device owning ``S/(D*T)`` slots =
``wpo`` whole windows — with NO replication anywhere (the 1B-feature /
12 GB-FTRL-state north-star regime only fits HBM this way; SURVEY.md §7
hard part d). This is the direct analog of ps-lite sharding the uint64
key space across *all* servers with no replication (SURVEY.md §2 C13).

Data flow per step, device (d, t), owner block o = d*T + t:

1. HOST: each data shard's occurrences are slot-sorted once
   (`plan_sorted_batch`, the same plan the single-chip engine uses) and
   then sliced at owner-block boundaries — a block's occurrences are one
   CONTIGUOUS span of the sorted stream — into fixed-capacity buffers
   ``[T, D_dst, cap]`` (`fullshard_buffers`). Pads carry the block's
   last local slot with mask 0, the same convention as plan pads.
2. ONE `all_to_all` over 'data' delivers to device (d, t) the D buffers
   (one per source shard) targeting ITS block — occurrence-scale
   traffic (~12 B/occurrence · slack), the synchronous analog of every
   worker Pulling from the server that owns each key
   (`lr_worker.cc:170`), batched into one collective.
3. The D received buffers — each slot-sorted over the SAME local
   ``[S/(D*T), K]`` shard — are merged ON THE DEVICE into one
   slot-sorted stream (`merge_received`: one `lax.sort` keyed on the
   slot, the global row id, field and mask riding folded into one int32
   payload word; the merged window offsets are the column sums of the
   buffers' offsets),
   and the single-stream Pallas sorted-window kernels the one-chip step
   uses run over it (`table_gather_sorted`: one span a table window;
   its VJP one ``[W, K]`` block write a window). Every consumer of the
   stream is per occurrence and the rows are summed by global row id,
   so the merged order is the same mathematics; what it saves is the
   kernels' cost per (window, buffer) span — at least one CHUNK-wide
   one-hot pass each, D times the windows for the same occurrences.
4. Per-row partial sums for ALL source shards are reduced to their row
   owners by ONE `psum_scatter` over 'data' + ONE `psum` over 'table'
   (~B·ch·4 B each) — aggregated rows cross the wire, never table rows.
5. Backward: the transpose all-gathers the small [R, ch] row cotangent
   over 'data'; the table gradient is a SHARD-LOCAL scatter — no
   table-scale collective exists in either direction.

Load imbalance, stated plainly: hashing spreads slots near-uniformly
across owner blocks, but a hot feature's occurrences all land in one
block (ps-lite has the identical imbalance: one server owns the hot
key). `data.fullshard_slack` sizes the buffers; overflow fails loudly
at plan time with the slack to raise. Host-side dedup shrinks exactly
this traffic on skewed data (docs/PERF.md lever 4).

Supports fused FM, MVM, and FFM (sorted-engine models; FFM rides the
MVM segment mode's machinery with its own channel contract —
models/ffm.py). LR stays on the GSPMD row-major path: its 1-D table
gather is already bandwidth-efficient (2.2× the per-chip target,
BENCH_r03) and needs no windowed engine.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xflow_tpu.compile_cache import past_cache
from xflow_tpu.config import Config
from xflow_tpu.metrics import binary_logloss_from_logits
from xflow_tpu.ops.sorted_table import (
    CHUNK,
    SortedPlan,
    chunk_chain_counts,
    map_host_parallel,
    plan_sorted_batch,
    row_sums_sorted,
    sorted_row_width,
    sorted_window,
    table_gather_sorted,
)
from xflow_tpu.parallel.mesh import DATA_AXIS, TABLE_AXIS
from xflow_tpu.train.state import TrainState
from xflow_tpu.train.step import guard_nonfinite, health_norms, metrics_keys

FS_KEYS = ("fs_slots", "fs_row", "fs_mask", "fs_off")


class FullshardOverflowError(ValueError):
    """An owner block's occurrences exceed the buffer capacity (data more
    skewed than data.fullshard_slack allows). Distinct from other config
    errors so the trainer can fall back to the GSPMD row-major step for
    the offending batch. Single-process falls back locally; multi-process
    coordinates the fallback rank-symmetrically — every rank contributes
    its overflow flag to one per-batch allgather and ALL ranks run the
    row-major step when any overflowed
    (train/engine.py `Engine.agree`), so the collective programs
    never desync."""


def _dims(cfg: Config, mesh: Mesh):
    d, t = mesh.shape[DATA_AXIS], mesh.shape[TABLE_AXIS]
    p = jax.process_count()
    return d, t, p


def validate_sorted_fullshard(cfg: Config, mesh: Mesh) -> None:
    """Reject configs the fully-sharded engine cannot run, with the
    specific reason."""
    d, t, p = _dims(cfg, mesh)
    S = cfg.num_slots
    if cfg.model.name == "fm":
        if not cfg.model.fm_fused:
            raise ValueError("fullshard FM needs model.fm_fused=true (one table)")
    elif cfg.model.name not in ("mvm", "ffm"):
        raise ValueError(
            "fullshard layout supports fused FM, MVM, and FFM (LR keeps the "
            f"GSPMD row-major path); got model={cfg.model.name}"
        )
    window = sorted_window(cfg)  # of a supported model's row: checked above
    if S % (d * t * window) != 0:
        raise ValueError(
            f"fullshard layout needs num_slots (2^{cfg.data.log2_slots}) "
            f"divisible by data*table*WINDOW = {d}*{t}*{window} (each device "
            "owns whole windows)"
        )
    if d % p != 0:
        raise ValueError(
            f"fullshard layout needs the data axis ({d}) divisible by the "
            f"process count ({p}): each process plans its rows into d/P shards"
        )
    if cfg.data.batch_size % (d // p) != 0:
        raise ValueError(
            f"per-process batch_size {cfg.data.batch_size} not divisible by "
            f"the local data-shard count {d // p}"
        )
    if cfg.data.sorted_sub_batches not in (0, d // p):
        raise ValueError(
            f"data.sorted_sub_batches={cfg.data.sorted_sub_batches} conflicts "
            f"with the fullshard plan count (= {d // p} per process); leave it 0"
        )
    segs = cfg.data.batch_size * p * (
        1 if cfg.model.name == "fm" else max(cfg.model.num_fields, 1)
    )
    if 2 * segs > 1 << 31:
        raise ValueError(
            f"fullshard layout folds (global row, field, mask) into one int32 "
            f"an occurrence: global batch {cfg.data.batch_size * p} x fields "
            f"needs {segs} ids, over 2^30"
        )
    if cfg.data.fullshard_slack < 1.0:
        raise ValueError(
            f"data.fullshard_slack={cfg.data.fullshard_slack} < 1 cannot hold "
            "even perfectly uniform occupancy"
        )


def fullshard_capacity(cfg: Config, mesh: Mesh) -> int:
    """Per-(source shard, owner block) buffer capacity: a CHUNK multiple
    covering `slack`× the uniform-hash expectation, plus one spare CHUNK
    for the plan pads that ride in the stream's last block."""
    d, t, p = _dims(cfg, mesh)
    rows = cfg.data.batch_size // (d // p)
    expect = rows * cfg.data.max_nnz / (d * t)  # real occurrences only:
    # plan pads are NOT copied into the buffers (fullshard_buffers clamps
    # spans to n_real; each buffer carries its own pads past its span)
    cap = int(np.ceil(cfg.data.fullshard_slack * expect / CHUNK)) * CHUNK
    return max(cap, CHUNK) + CHUNK


def fullshard_buffers(
    plan: SortedPlan,
    D: int,
    T: int,
    cap: int,
    s_local: int,
    slack: float,
    with_fields: bool = False,
    *,
    n_real: int,
) -> dict:
    """Slice ONE shard's flat sorted plan at owner-block boundaries into
    per-destination buffers.

    Returns ``fs_slots/fs_row/fs_mask`` ``[T, D, cap]`` (+ ``fs_fields``)
    and ``fs_off`` ``[T, D, wpo+1]``: buffer-local window offsets with
    the last entry extended to `cap`, so the block's last window owns the
    pads (pad slot = s_local-1, mask 0 — the plan-pad convention).
    """
    win_off = plan.win_off
    n_win = win_off.shape[0] - 1
    wpo = n_win // (D * T)
    # plan pads (slot num_slots-1, up to 2 CHUNKs of them) would all land
    # in the LAST owner block and can overflow its buffer; clamp every
    # span to `n_real` (the caller's real occurrence count — REQUIRED, so
    # no caller accidentally counts pads against capacity). Stable sorting
    # puts pads after the real occurrences of the last slot, so clamping
    # drops only pads; each buffer pads ITSELF past its span (mask 0,
    # slot s_local-1).
    slots = np.full((T, D, cap), s_local - 1, np.int32)
    row = np.zeros((T, D, cap), np.int32)
    mask = np.zeros((T, D, cap), np.float32)
    fields = np.zeros((T, D, cap), np.int32) if with_fields else None
    off = np.empty((T, D, wpo + 1), np.int32)
    for t in range(T):
        for d in range(D):
            o = d * T + t
            lo = min(int(win_off[o * wpo]), n_real)
            hi = min(int(win_off[(o + 1) * wpo]), n_real)
            L = hi - lo
            if L > cap:
                raise FullshardOverflowError(
                    f"owner block {o} holds {L} occurrences > buffer capacity "
                    f"{cap}: the hash distribution is more skewed than "
                    f"data.fullshard_slack={slack} allows — raise it (a hot "
                    "feature's occurrences all land in one block)"
                )
            slots[t, d, :L] = plan.sorted_slots[lo:hi] - o * s_local
            row[t, d, :L] = plan.sorted_row[lo:hi]
            mask[t, d, :L] = plan.sorted_mask[lo:hi]
            if with_fields:
                fields[t, d, :L] = plan.sorted_fields[lo:hi]
            off[t, d, :wpo] = (
                np.minimum(win_off[o * wpo : (o + 1) * wpo], n_real) - lo
            )
            off[t, d, wpo] = cap
    out = {"fs_slots": slots, "fs_row": row, "fs_mask": mask, "fs_off": off}
    if with_fields:
        out["fs_fields"] = fields
    return out


def plan_fullshard_batch(
    slots: np.ndarray,
    mask: np.ndarray,
    cfg: Config,
    mesh: Mesh,
    fields: Optional[np.ndarray] = None,
) -> dict:
    """This process's [B, F] batch -> stacked fullshard buffers
    [D_local, T, D, cap] (+ fs_off [D_local, T, D, wpo+1]).

    Each local data shard is planned (slot-sorted) and sliced
    independently; the C planner releases the GIL, so shards parallelize
    across host cores like plan_sorted_stacked's sub-batches.
    """
    from xflow_tpu.ops.sorted_table import _native_planner, _plan_pool

    d, t, p = _dims(cfg, mesh)
    d_local = d // p
    B = slots.shape[0]
    if B != cfg.data.batch_size or slots.shape[1] != cfg.data.max_nnz:
        # capacity is sized from the config; a mismatched batch would
        # validate against the wrong buffer budget
        raise ValueError(
            f"batch shape {slots.shape} != configured "
            f"(batch_size={cfg.data.batch_size}, max_nnz={cfg.data.max_nnz})"
        )
    rows = B // d_local
    cap = fullshard_capacity(cfg, mesh)
    s_local = cfg.num_slots // (d * t)
    with_fields = fields is not None
    window = sorted_window(cfg)

    def one(i):
        sl = slice(i * rows, (i + 1) * rows)
        plan = plan_sorted_batch(
            slots[sl], mask[sl], cfg.num_slots,
            fields=fields[sl] if with_fields else None, window=window,
        )
        return fullshard_buffers(
            plan, d, t, cap, s_local, cfg.data.fullshard_slack, with_fields,
            n_real=rows * slots.shape[1],
        )

    bufs = map_host_parallel(one, d_local)
    return {k: np.stack([b[k] for b in bufs]) for k in bufs[0]}


def fullshard_chunk_counts(fs_off) -> dict:
    """`chunk_chain_counts` of the streams the chips walk, a chip: a
    chip's merged stream has the sum of its sources' window offsets
    (`merge_received`), so `fs_off` [sources, T, D, wpo+1] summed over
    the sources is each destination's `win_off`; the mean over the
    destinations, rounded. One process that plans for every source (a
    single host's mesh) counts whole streams; of several, each the part
    its own sources send."""
    merged = np.asarray(fs_off, np.int64).sum(axis=0)
    chips = [chunk_chain_counts(off) for off in merged.reshape(-1, merged.shape[-1])]
    return {k: round(sum(c[k] for c in chips) / len(chips)) for k in chips[0]}


def fullshard_batch_sharding(mesh: Mesh, with_fields: bool = False) -> dict:
    """Subset of the canonical batch_sharding dict (parallel/mesh.py) so
    the placement and jit in_shardings contracts stay in lockstep."""
    from xflow_tpu.parallel.mesh import batch_sharding

    full = batch_sharding(mesh)
    keys = FS_KEYS + (("fs_fields",) if with_fields else ()) + (
        "labels", "row_mask",
    )
    return {k: full[k] for k in keys}


def merge_received(r_slots, r_off, word):
    """The D received buffers ``[D, cap]`` -> ONE slot-sorted stream
    ``[D*cap]`` with its window offsets ``[wpo+1]`` and the payload word
    in the same order: ``(slots, win_off, word)``.

    Every buffer is slot-sorted over the same local shard, so one
    `lax.sort` keyed on the slot merges them; what else is per
    occurrence (global row id, field, mask) rides folded into ONE int32
    `word` an occurrence, because every operand of the sort costs the
    chip's compiler seconds on a cold start (about twelve a payload
    operand at the four-chip cell's 1,050,624 positions) and the step
    most of a millisecond (PERF.md §6). The merged offsets need no search: window j of the merged stream starts
    after every buffer's occurrences of the windows before it, the
    column sum of the buffer-local offsets; each buffer's last entry is
    `cap`, so the last entry is the stream's length
    (`table_gather_sorted`'s contract). Pads (slot s_local-1, mask 0)
    sort to the tail of the last window, where each buffer carried its
    own. The order among equal slots is left to the sort
    (`is_stable=False`): nothing reads it, and a stable sort carries one
    more operand to break ties by position."""
    slots, word = jax.lax.sort(
        (r_slots.reshape(-1), word.reshape(-1)), num_keys=1, is_stable=False
    )
    return slots, r_off.sum(axis=0), word


def _local_logits(mode, tbl_local, fs_slots, fs_row, fs_mask, fs_off, fs_fields,
                  R, cfg, D, K, nf, bf16, plus):
    """Device (d, t) forward body, shared by the train and eval steps.

    tbl_local [S/(D*T)/pack, pack*K]; fs_* are MY source shard's buffers
    for column t, [D_dst, cap]; returns logits [R] for MY data
    coordinate's rows. Storage may be packed
    (ops/sorted_table.pack_table) — detected from the shard's shape,
    slot indices stay logical.

    Steps (the numbers refer to the module docstring's data flow):
    2. exchange: my buffer for dest d' -> device (d', t); receive every
       source's buffer for MY block — ONE all_to_all over 'data'.
    3. merge the D buffers into one slot-sorted stream, then the local
       windowed gather over it (+ shard-local scatter in the VJP).
    4. per-row aggregates return to their row owners: psum_scatter over
       'data' + psum over 'table' (owner_reduce).
    """
    from xflow_tpu.ops.sorted_table import pack_of, wire_rows

    def a2a(x):
        return jax.lax.all_to_all(x, DATA_AXIS, 0, 0, tiled=True)

    with_fields = mode in ("ffm", "mvm_segment")
    # the step's phases (telemetry.PHASE_LABELS): `exchange` is what this
    # chip does to receive its work — the all_to_all of the occurrences
    # and the merge's sort here, the row aggregates' return below;
    # `gather` is the windowed gather alone (its transpose, the two-pass
    # scatter, reads `scatter` through the `transpose(...)` autodiff
    # writes into the path); what is left of this body is the callers'
    # `rows`
    with jax.named_scope("exchange"):
        r_slots = a2a(fs_slots)  # [D_src, cap]
        r_off = a2a(fs_off)  # [D_src, wpo+1]
        # rows arrive shard-local [0, R); globalize by source index so one
        # segment space covers all D source shards' rows. The compacted wire
        # dtypes (compact_plan_wire) ride through the all_to_all — less ICI
        # traffic — and are folded here into the merge's one payload word,
        # [seg | mask bit]: seg = global row, times nf plus the field where
        # the mode has fields (validate_sorted_fullshard bounds it to 31 bits)
        seg = wire_rows(a2a(fs_row)) + jnp.arange(D, dtype=jnp.int32)[:, None] * R
        if with_fields:
            seg = seg * nf + wire_rows(a2a(fs_fields))
        word = seg * 2 + a2a(fs_mask).astype(jnp.int32)
        slots_flat, win_off, word = merge_received(r_slots, r_off, word)
    seg = word >> 1
    mask_flat = jax.lax.stop_gradient((word & 1).astype(jnp.float32))
    grow, fields_flat = (seg // nf, seg % nf) if with_fields else (seg, None)

    with jax.named_scope("gather"):
        occ_t = table_gather_sorted(
            tbl_local, slots_flat, win_off, bf16, pack_of(tbl_local, K)
        )
    occm_t = occ_t[:K] * mask_flat[None, :]

    def owner_reduce(partials):
        with jax.named_scope("exchange"):
            mine = jax.lax.psum_scatter(
                partials, DATA_AXIS, scatter_dimension=0, tiled=True
            )  # [1, R(*nf), ch]
            return jax.lax.psum(mine, TABLE_AXIS)[0]

    if mode == "ffm":
        from xflow_tpu.models.ffm import make_ffm_row_op
        from xflow_tpu.ops.sorted_table import segment_sum_channels

        k_lat = cfg.model.v_dim
        # FFM channel contract + exact-at-zeros hand VJP
        # (models/ffm.py make_ffm_row_op): one segment-sum into the
        # per-(row, field) space, owner_reduce row return like the
        # segment MVM mode; the bwd all-gathers the [R, nf·(K+1)]
        # row aggregates over 'data' — the same traffic class as
        # the plain path's d_sums transpose
        op = make_ffm_row_op(
            lambda data, seg: owner_reduce(
                segment_sum_channels(data, seg, D * R * nf).reshape(
                    D, R * nf, K + 1
                )
            ).reshape(R, nf, K + 1),
            lambda arr: jax.lax.all_gather(arr, DATA_AXIS, tiled=True),
            nf, k_lat,
            # the shard_map transpose hands each 'table' copy dl/T
            # (make_ffm_row_op docstring) — restore before use
            restore_dl=lambda dl: jax.lax.psum(dl, TABLE_AXIS),
        )
        return op(occ_t, mask_flat, fields_flat, grow)
    if mode == "mvm_segment":
        from xflow_tpu.ops.sorted_table import segment_sum_channels

        # mask rides as an extra channel: its segment-sum is the
        # per-(row, field) occurrence count => `present` (models/mvm.py)
        stacked = jnp.concatenate([occm_t, mask_flat[None, :]], axis=0)
        sums_t = segment_sum_channels(stacked, seg, D * R * nf)  # [D*R*nf, k+1]
        sums = owner_reduce(sums_t.reshape(D, R * nf, K + 1))
        sums = sums.reshape(R, nf, K + 1)
        s, present = sums[..., :K], sums[..., K] > 0
        factors = jnp.where(present[..., None], s + plus, 1.0)
        return jnp.prod(factors, axis=1).sum(axis=-1)
    if mode == "mvm_product":
        from xflow_tpu.models.mvm import make_row_products

        # log-space product channels are ADDITIVE over shards (sums
        # of ln|v| / negative and zero counts), so the cross-shard
        # reduction is the same rowsum + psum_scatter + psum as FM's;
        # the op's bwd all-gathers the small [R, 4k] row aggregates
        # over 'data' — the same traffic class as FM's backward
        op = make_row_products(
            lambda stacked, rows_: owner_reduce(
                row_sums_sorted(stacked, rows_, D * R).reshape(D, R, -1)
            ),
            lambda arr: jax.lax.all_gather(arr, DATA_AXIS, tiled=True),
            K,
            # the shard_map transpose hands each 'table' copy dP/T
            # (make_row_products docstring) — restore before use.
            # Without this the product path's updates diverged from
            # single-device at every T>1 (round-4 ADVICE finding,
            # measured in round 5)
            restore_dP=lambda dP: jax.lax.psum(dP, TABLE_AXIS),
        )
        return op(occ_t[:K] + plus, mask_flat, grow).sum(axis=1)
    from xflow_tpu.models.fm import fm_logits_from_sums, stack_channels

    stacked = stack_channels(occm_t, K)  # [ch, N]
    rs = row_sums_sorted(stacked, grow, D * R)  # [D*R, ch]
    sums = owner_reduce(rs.reshape(D, R, -1))
    return fm_logits_from_sums(sums, K, cfg)


def _mode_statics(cfg: Config, mesh: Mesh):
    """(D, tname, K, nf, bf16, plus) shared by the train and eval
    builders — the ONE place the logical row width lives:
    MVM [k], FM [1+k], FFM [1+nf·k]."""
    D, _, _ = _dims(cfg, mesh)
    return (
        D, "v" if cfg.model.name == "mvm" else "wv", sorted_row_width(cfg),
        cfg.model.num_fields, cfg.data.sorted_bf16,
        1.0 if cfg.model.mvm_plus_one else 0.0,
    )


def _batch_mode(cfg: Config, batch: dict) -> str:
    if cfg.model.name == "mvm":
        return "mvm_segment" if "fs_fields" in batch else "mvm_product"
    return "ffm" if cfg.model.name == "ffm" else "fm"


def make_fullshard_eval_step(cfg: Config, mesh: Mesh, recorder=None) -> Callable:
    """Forward-only fullshard step: eval consumes the SAME host plan the
    train step does (fs_* buffers, one all_to_all + owner_reduce)
    instead of shipping the dead row-major [B, F] arrays (~24 MB/batch
    at bench shapes — round-3 weak #5). Returns reference-clamped pctrs
    [B] sharded over 'data'."""
    from xflow_tpu.metrics import reference_pctr

    validate_sorted_fullshard(cfg, mesh)
    D, tname, K, nf, bf16, plus = _mode_statics(cfg, mesh)
    fs_spec = P(DATA_AXIS, TABLE_AXIS, None, None)
    jitted: dict = {}

    def build(mode: str):
        with_fields = mode in ("mvm_segment", "ffm")

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(
                P((DATA_AXIS, TABLE_AXIS), None),
                fs_spec, fs_spec, fs_spec, fs_spec, fs_spec,
                P(DATA_AXIS, None),  # labels (row count only)
            ),
            out_specs=P(DATA_AXIS, None),
            check_vma=False,
        )
        def sharded_pctr(tbl, fss, fsr, fsm, fso, fsf, labels):
            sq = lambda x: x[0, 0]
            with jax.named_scope("rows"):
                logits = _local_logits(
                    mode, tbl, sq(fss), sq(fsr), sq(fsm), sq(fso), sq(fsf),
                    labels.shape[1], cfg, D, K, nf, bf16, plus,
                )
                return reference_pctr(logits)[None, :]

        def eval_step(tables, batch: dict):
            fsf = batch["fs_fields"] if with_fields else batch["fs_slots"]
            return sharded_pctr(
                tables[tname],
                batch["fs_slots"], batch["fs_row"], batch["fs_mask"],
                batch["fs_off"], fsf,
                batch["labels"].reshape(D, -1),
            ).reshape(-1)

        keys = FS_KEYS + (("fs_fields",) if with_fields else ()) + ("labels",)
        return eval_step, keys

    def call(tables, batch: dict):
        mode = _batch_mode(cfg, batch)
        if mode not in jitted:
            step, keys = build(mode)
            fn = jax.jit(step)
            if recorder is not None:
                fn = recorder.wrap(f"predict.fullshard.{mode}", fn)
            jitted[mode] = (fn, keys)
        fn, keys = jitted[mode]
        return fn(tables, {k: batch[k] for k in keys})

    return call


def make_fullshard_train_step(
    optimizer, cfg: Config, mesh: Mesh, recorder=None, state_formats=None
) -> Callable:
    """FM/MVM train step with everything sharded over ('data','table').

    `state_formats` (state -> train/engine.py `state_formats`, or None)
    adds the on-device layout the kernels take w, n, z in to the state's
    shardings, on the way in and on the way out (`programs` below).

    MVM runs in one of two row-side modes, chosen PER BATCH by the
    planner (train/engine.py _mvm_wants_fields): "mvm_product" (no fs_fields —
    exclusive fields verified on the host; the row side is the same
    [R, ~24] row-sum + psum_scatter as FM, models/mvm.py) or
    "mvm_segment" (general multi-valued fields through the [R·nf]
    segment space). Each mode is its own jitted program; multi-process
    runs pin one mode for the whole run (resolve_mvm_product) so the
    ranks' collective sequences always agree.
    """
    validate_sorted_fullshard(cfg, mesh)
    D, tname, K, nf, bf16, plus = _mode_statics(cfg, mesh)
    # the compile record's `table_spans_per_step`: one span a local table
    # window in the gather and in its transpose, whatever the number of
    # source buffers (the merge, `merge_received`)
    wpo = cfg.num_slots // (D * mesh.shape[TABLE_AXIS]) // sorted_window(cfg)

    def local_logits(mode, tbl_local, fs_slots, fs_row, fs_mask, fs_off,
                     fs_fields, R):
        return _local_logits(
            mode, tbl_local, fs_slots, fs_row, fs_mask, fs_off, fs_fields,
            R, cfg, D, K, nf, bf16, plus,
        )

    def local_loss(mode, tbl_local, fs_slots, fs_row, fs_mask, fs_off, fs_fields,
                   labels, row_mask):
        """Device (d, t) body: the shared forward (`_local_logits`) plus
        the loss reduction."""
        # `rows` holds the row side and the loss; inside it `_local_logits`
        # opens `exchange` (all_to_all, merge, the aggregates' return) and
        # `gather` (the windowed gather; transposed, the scatter), and the
        # innermost label wins
        with jax.named_scope("rows"):
            logits = local_logits(
                mode, tbl_local, fs_slots, fs_row, fs_mask, fs_off, fs_fields,
                labels.shape[0],
            )
            per_row = binary_logloss_from_logits(logits, labels)
            loss_sum = jax.lax.psum((per_row * row_mask).sum(), DATA_AXIS)
            rows_n = jax.lax.psum(row_mask.sum(), DATA_AXIS)
            return loss_sum / jnp.maximum(rows_n, 1.0), rows_n

    fs_spec = P(DATA_AXIS, TABLE_AXIS, None, None)

    def build(mode: str):
        """One jitted step per row-side mode (its own collective program)."""
        with_fields = mode in ("mvm_segment", "ffm")

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(
                P((DATA_AXIS, TABLE_AXIS), None),  # table shard [S/(D*T), K]
                fs_spec, fs_spec, fs_spec, fs_spec, fs_spec,  # fs_* [1,1,D,cap]
                P(DATA_AXIS, None),  # labels [1, R]
                P(DATA_AXIS, None),  # row_mask
            ),
            out_specs=(P(), P()),
            check_vma=False,
        )
        def sharded_loss(tbl, fss, fsr, fsm, fso, fsf, labels, rm):
            sq = lambda x: x[0, 0]
            return local_loss(
                mode, tbl, sq(fss), sq(fsr), sq(fsm), sq(fso), sq(fsf),
                labels[0], rm[0],
            )

        def loss_for_grad(tbl, batch):
            # fs_fields only exists on the segment path; others pass
            # fs_slots as an unused same-shaped dummy
            fsf = batch["fs_fields"] if with_fields else batch["fs_slots"]
            return sharded_loss(
                tbl,
                batch["fs_slots"], batch["fs_row"], batch["fs_mask"],
                batch["fs_off"], fsf,
                batch["labels"].reshape(D, -1),
                batch["row_mask"].reshape(D, -1),
            )

        def grad_part(table, batch: dict):
            # forward and backward carry `local_loss`'s scopes; the
            # scatter (the gather's transpose, staying on the owning
            # device) is the `gather` scope under autodiff's `transpose(`
            return jax.value_and_grad(loss_for_grad, has_aux=True)(table, batch)

        def update_part(state: TrainState, grads, loss, rows):
            metrics = {"loss": loss, "rows": rows}
            with jax.named_scope("update"):
                # non-finite guard: update_ok computed from the replicated
                # loss + the sharded gradient (the isfinite reduction GSPMDs
                # to shard-local alls + one psum) — every rank/device sees
                # the same flag, so the zeroed gradient stays rank-symmetric
                safe_grads, metrics = guard_nonfinite(cfg, {tname: grads}, metrics)
                new_tables, new_opt = optimizer.apply(
                    {tname: state.tables[tname]}, state.opt_state, safe_grads, cfg
                )
                new_state = TrainState(new_tables, new_opt, state.step + 1)
            # health norms ride the same replicated-scalar contract as
            # the guard flag (shared helper, train/step.py): sharded
            # reductions + one psum, identical values on every rank
            with jax.named_scope("health"):
                metrics.update(
                    health_norms(
                        cfg, state.tables, new_tables, grads={tname: grads}
                    )
                )
            return new_state, metrics

        return grad_part, update_part, fullshard_batch_sharding(mesh, with_fields=with_fields)

    from xflow_tpu.parallel.mesh import state_shardings

    rep = NamedSharding(mesh, P())
    jitted: dict = {}

    def programs(mode: str, grad_part, update_part, bsh, like):
        """The step as its two programs, cut where the state is written.

        On a TPU the state is pinned in the kernels' layout
        (`state_formats`), and a program that hands back a pinned leaf
        is compiled past the persistent cache
        (`compile_cache.past_cache`). The whole step's compile is the
        exchange, the merge's sort and three kernels: seconds every
        process would pay. So the gradient program takes the table
        pinned, hands back only default layouts — the shard's gradient
        among them, re-laid once on each side of the cut — and is
        cached; the update, an elementwise sweep, is the one compiled in
        every process. Where nothing is pinned the cut buys nothing and
        stays: one structure, so what the tests and the IR lint read is
        what the chip runs."""
        plain = state_shardings(like, mesh)
        pinned = state_formats(like) if state_formats is not None else None
        ssh = pinned or plain
        grad_sh = plain.tables[tname]  # the gradient: sharded as the table, no layout
        grad = jax.jit(
            grad_part,
            in_shardings=(ssh.tables[tname], bsh),
            out_shardings=((rep, rep), grad_sh),
        )
        update = jax.jit(
            update_part,
            in_shardings=(ssh, grad_sh, rep, rep),
            out_shardings=(ssh, {k: rep for k in metrics_keys(cfg)}),
            donate_argnums=(0,),
        )
        if pinned is not None:
            update = past_cache(update)
        if recorder is not None:
            grad = recorder.wrap(
                f"train_step.fullshard.{mode}", grad, table_spans_per_step=wpo
            )
            update = recorder.wrap(f"update_step.fullshard.{mode}", update)

        def train_step(state: TrainState, batch: dict):
            (loss, rows), grads = grad(state.tables[tname], batch)
            return update(state, grads, loss, rows)

        return train_step

    def call(state: TrainState, batch: dict):
        mode = _batch_mode(cfg, batch)
        if mode not in jitted:
            *parts, bsh = build(mode)
            jitted[mode] = (programs(mode, *parts, bsh, state), bsh)
        fn, bsh = jitted[mode]
        return fn(state, {k: batch[k] for k in bsh})

    return call
