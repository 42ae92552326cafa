"""Sorted-window table engine: the TPU-native replacement for random
gather/scatter on giant embedding tables.

Why: the FM/MVM step is dominated by XLA's scatter-add of per-occurrence
gradient rows into the [S, 1+k] table — random HBM access at ~100 ns per
row (measured: 216 ms of a 280 ms step at 2M occurrences, and XLA does
not exploit sorted indices; docs/PERF.md). Sequential window streams +
MXU one-hot matmuls avoid table-scale random access entirely; the only
random access left is into [B, k]-sized (cache-resident) row aggregates.

Design (reference analog: the per-minibatch key sort + dedup the worker
does before Pull, `/root/reference/src/model/lr/lr_worker.cc:150-165` —
here the sort becomes the *device layout*):

- the HOST (parser / pipeline) emits each batch's occurrences in
  slot-sorted order: `sorted_slots [Np]`, `sorted_row [Np]`,
  `sorted_mask [Np]`, plus `win_off [S/W + 1]` — each W-slot table
  window's first occurrence position in the sorted order.
- `table_gather_sorted` (custom_vjp) returns per-occurrence table rows
  TRANSPOSED: `occ_t [K8, Np]` (K8 = K rounded up to the 8-sublane
  tile). The transposed layout is load-bearing twice over: elementwise
  work on [Np, 11] wastes ~11x lane bandwidth on TPU, and Mosaic
  rejects DMA slices whose minor dim is not 128-aligned — [K8, C]
  column slices of a [K8, Np] array satisfy both.
- its VJP consumes the cotangent in the same [K8, Np] layout and
  scatters with one [W, K] block write per window (MXU-accumulated).

Chunks are CHUNK-aligned (Mosaic requires aligned DMA offsets), so a
window's chunk range may include occurrences of neighboring windows;
the in-window test masks them in compute (scatter) or leaves their
columns of the chunk's staging buffer to the window that owns them
(gather) — no explicit tail masking needed. A window of a large table
holds a fraction of a chunk, so the single-stream kernels carry their
chunk chain from grid step to grid step: a chunk is loaded once a call
and the next ones are in flight while this one is computed on
(`_gather_span`).

Two implementations with identical semantics:
- Pallas TPU kernels (grid over windows; MXU does the heavy lifting);
- an XLA reference used on CPU (tests) and as the oracle.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

WINDOW = 2048  # table slots per grid step, for every row the rule below does not narrow it for
CHUNK = 512  # sorted occurrences per inner iteration (DMA granularity)


def _k8(k: int) -> int:
    return max(8, ((k + 7) // 8) * 8)


PACK = 8  # slots per packed table row (see pack_table)
PIPE_NB = 6  # chunk-chain pipeline depth of the kernels (buffers; see _gather_pallas)

# Mosaic's scoped VMEM on a v5e is 16 MiB, and the fused scatter+FTRL
# kernel is the one that fills it. `state_window_bytes` is what that
# kernel asks for, fitted to what the chip's compiler reports ("Scoped
# allocation with size ..."; AOT for a described v5e, 60 compiles over
# K = 11..385 and windows 256..8192, PR 36): the w, n, z blocks in and
# out, double buffered; the chunk pipeline's scratch; and the kernel's
# own values, which the compiler places — a window's transposed
# accumulator has pack*K rows, and a row costs the larger of its chunk
# side (the cotangent expanded and split in three bf16 terms, 4.7 KB)
# and its window side (accumulator, gradient and FTRL temporaries,
# 24 B a packed table row and 1.6 KB). Where the fit errs it errs high.
VMEM_SCOPED_BYTES = 16 << 20


def state_window_bytes(K: int, pack: int, window: int) -> int:
    """Scoped VMEM the fused scatter+FTRL kernel asks for at `window`
    slots a grid step (`_scatter_ftrl_pallas`)."""
    rows = window // pack
    lanes = -(-pack * K // 128) * 128
    blocks = 12 * rows * lanes * 4
    scratch = PIPE_NB * (_k8(K) + 8) * CHUNK * 4
    values = pack * K * max(4800, 1600 + 24 * rows)
    return blocks + scratch + values


def state_window(K: int, pack: int = PACK) -> int:
    """Table slots a grid step of the sorted kernels owns, from the row
    width: WINDOW halved until the fused kernel fits scoped VMEM
    (`state_window_bytes`). ONE rule for everything that must agree on
    it — the planner's `win_off`, the gather, both scatters, the
    engines' divisibility checks — so they read it here and nowhere
    keep a width of their own. FM's 11 floats (2.4 of 16 MiB) and every
    packed row up to 95 keep 2048; 96 to 160 floats get 1024 — FFM at 39
    fields x k=4 is 157: 15.2 MiB, where 2048 slots were refused at
    16.94; wider rows 512 and less. A smaller window also shortens the
    one-hot contraction a chunk (its MXU work is window/pack x CHUNK x
    3*pack*K), so wide rows lose nothing by it. Raises where even the
    smallest window (8 packed rows) does not fit: the kernel's values
    and the chunk scratch alone are then over."""
    window = WINDOW
    while window > 8 * pack and state_window_bytes(K, pack, window) > VMEM_SCOPED_BYTES:
        window //= 2
    need = state_window_bytes(K, pack, window)
    if need > VMEM_SCOPED_BYTES:
        raise ValueError(
            f"sorted engine: a table row of {K} floats (pack {pack}) needs "
            f"{need} B of VMEM at the smallest window of {window} slots, "
            f"over the kernels' {VMEM_SCOPED_BYTES}"
        )
    return window


def pack_table(t):
    """[S, K] logical table -> [S/PACK, PACK*K] packed storage (a pure
    reshape: slot s lives at [s // PACK, (s % PACK)*K : (s % PACK+1)*K]).

    WHY: TPU HBM buffers are (8, 128)-tiled, so a [S, 11] f32 array is
    stored [S, 128] — 11.6× its logical bytes (at 2^24 slots the FM FTRL
    state alone is 3 × 8 GB and cannot fit a v5e chip) — and every
    elementwise optimizer pass runs at 11/128 lane efficiency
    (docs/PERF.md microbench: the FTRL update was the dominant FM step
    cost for exactly this reason). Packed, the minor dim is PACK*K
    (88 for the fused FM table): 1.45× padding instead of 11.6×, and
    the FTRL update runs 88/128 of peak.

    Consumers detect the layout FROM THE SHAPE (`pack_of`), so
    hand-built logical tables keep working everywhere."""
    S, K = t.shape
    assert S % PACK == 0, (S, PACK)
    return t.reshape(S // PACK, PACK * K)


def unpack_table(t_packed, K: int):
    """Inverse of pack_table. On a TPU DEVICE this materializes the
    11.6×-padded logical buffer — call on host arrays (free reshape) or
    small tables only."""
    Sp, PK = t_packed.shape
    assert PK % K == 0, (PK, K)
    return t_packed.reshape(Sp * (PK // K), K)


def compact_plan_wire(arrays: dict, rows_bound: int, fields_bound: int = 0) -> dict:
    """Shrink the per-batch plan arrays' host->device wire format:
    row ids to uint16, fields to uint8, the 0/1 mask to uint8 —
    14.2 -> 8.2 MB per 64k x 18 batch (plus ~3.5 MB on the MVM segment
    path's fields), ~45% less host->device traffic per step. The
    jitted forwards upcast on device (`wire_rows` / `wire_mask`), where
    the cast fuses for free.

    Every decision here is made from CONFIG-DERIVED BOUNDS (`rows_bound`
    = rows per sub-batch/shard, `fields_bound` = model.num_fields), NOT
    from the data: in multi-process SPMD each rank compacts its own
    batch, the dtypes are baked into the jitted collective program, and
    a value-dependent choice could differ across ranks and desync the
    all_to_all sequences. The mask is guaranteed 0/1 by the data
    pipeline (parser/pad contract); a fractional mask from a custom
    caller is a bug and raises loudly rather than silently changing the
    wire format."""
    out = dict(arrays)
    if rows_bound <= (1 << 16):
        for key in ("sorted_row", "fs_row"):
            if key in out and np.asarray(out[key]).dtype == np.int32:
                out[key] = np.asarray(out[key]).astype(np.uint16)
    if 0 < fields_bound <= (1 << 8):
        for key in ("sorted_fields", "fs_fields"):
            if key in out and np.asarray(out[key]).dtype == np.int32:
                out[key] = np.asarray(out[key]).astype(np.uint8)
    for key in ("sorted_mask", "fs_mask"):
        if key in out:
            m = np.asarray(out[key])
            if m.dtype == np.float32:
                u8 = m.astype(np.uint8)
                if not (m == u8).all():
                    raise ValueError(
                        f"{key} carries non-0/1 values: the mask is a presence "
                        "mask by the batch-schema contract (data/schema.py); "
                        "fractional values here are a pipeline bug"
                    )
                out[key] = u8
    return out


def wire_rows(sorted_row):
    """Device-side upcast of a possibly-compacted row-id array."""
    return sorted_row.astype(jnp.int32)


def wire_mask(sorted_mask):
    """Device-side upcast of a possibly-compacted mask array."""
    return sorted_mask.astype(jnp.float32)


def dedup_slots(slots: np.ndarray, cap: int):
    """Host-side batch dedup for the ROW-MAJOR paths (reference analog:
    the per-minibatch unique-key Pull, `lr_worker.cc:150-165`):
    returns (unique_slots [cap] padded with the last unique, inverse
    [B, F] int32) or None when the batch has more than `cap` uniques
    (the caller ships row-major and the step's direct-gather variant
    runs — jit shapes must be static, so capacity is fixed).

    The win is on SKEWED data and on a sharded mesh: the table gather
    moves `cap` rows instead of B·F (cross-chip gather/scatter volume
    shrinks by U/(B·F)); uniform batches at bench shapes have U ≈ 0.76
    B·F and are not worth the host sort (docs/PERF.md lever 4)."""
    flat = np.asarray(slots, np.int32).ravel()
    u, inv = np.unique(flat, return_inverse=True)
    if u.size > cap or u.size == 0:
        return None
    pad = np.full(cap - u.size, u[-1], np.int32)
    return (
        np.concatenate([u.astype(np.int32), pad]),
        inv.astype(np.int32).reshape(np.asarray(slots).shape),
    )


def batch_rows(table, batch: dict, K: int):
    """Per-occurrence LOGICAL table rows for a row-major batch: the
    deduped two-level gather when the host attached (unique_slots,
    inverse), else the direct gather. Layout-blind (`table_rows`). The
    row-major steps' `gather` phase (telemetry.PHASE_LABELS); autodiff's
    transpose of it is their `scatter`."""
    with jax.named_scope("gather"):
        if "unique_slots" in batch:
            return table_rows(table, batch["unique_slots"], K)[batch["inverse"]]
        return table_rows(table, batch["slots"], K)


# packed-row gather intermediate cap (bytes). The packed gather
# materializes [chunk, pack*K] full packed rows before the sub-row
# select; at FFM's K=73 a 64k×18 batch would make that ~3 GB in one
# piece (the round-5 OOM at the 64k row-major shape). Chunking the
# occurrence axis caps it; 256 MB keeps the per-chunk gather large
# enough to stay on XLA's fast row-gather path. (A single 2-D
# lax.gather with a (row, sub-row·K) start index avoids the
# intermediate entirely but lowers to a ~2.5 µs/row scalar path on
# TPU — measured 140× slower.)
_PACKED_GATHER_CHUNK_BYTES = 256 * 1024 * 1024


def table_rows(table, slots, K: int):
    """Logical rows ``table[slots]`` from EITHER storage layout — the
    row-major paths' (GSPMD step, mesh eval, non-sorted forwards)
    layout-blind gather. Packed: a full-packed-row gather of
    [..., pack*K] plus an elementwise 0/1 sub-row select (never a
    matmul, so no MXU operand rounding — see `_sub_select`), chunked
    over the occurrence axis so the packed-row intermediate stays
    under _PACKED_GATHER_CHUNK_BYTES."""
    pack = pack_of(table, K)
    if pack == 1:
        return table[slots]
    flat = slots.reshape(-1)
    n = flat.shape[0]
    chunk_rows = max(1, _PACKED_GATHER_CHUNK_BYTES // (pack * K * 4))
    nch = -(-n // chunk_rows)
    if nch <= 1:
        rows = table[flat // pack]
        out = _sub_select(rows, flat % pack, pack, K)
    else:
        pad = nch * chunk_rows - n
        padded = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])

        def one(chunk):
            rows = table[chunk // pack]
            return _sub_select(rows, chunk % pack, pack, K)

        out = jax.lax.map(one, padded.reshape(nch, chunk_rows)).reshape(
            nch * chunk_rows, K
        )[:n]
    return out.reshape(*slots.shape, K)


def pack_of(table, K: int) -> int:
    """Storage layout of `table` given its LOGICAL row width K: 1 =
    logical [S, K], PACK = packed [S/PACK, PACK*K]. Raises on anything
    else (a shape mismatch here means a config/table disagreement)."""
    if table.ndim != 2 or table.shape[1] == K:
        return 1
    if table.shape[1] == PACK * K:
        return PACK
    raise ValueError(
        f"table shape {table.shape} is neither logical [S, {K}] nor "
        f"packed [S/{PACK}, {PACK * K}]"
    )


class SortedPlan(NamedTuple):
    """Host-computed sorted layout of one batch's feature occurrences.

    Arrays are padded to a CHUNK multiple plus one spare chunk so
    aligned [start, start+CHUNK) reads never leave bounds; pad slots are
    `num_slots - 1` (the LAST window) with mask/row 0, so every padded
    position is owned — and therefore written — by some window: the
    gather output has no uninitialized columns (a pad column holds row
    `num_slots-1`'s values; consumers must multiply by `sorted_mask`),
    and the scatter receives a zero cotangent there (mask zeroes it).
    """

    sorted_slots: np.ndarray  # int32 [Np]
    sorted_row: np.ndarray  # int32 [Np]
    sorted_mask: np.ndarray  # float32 [Np]
    win_off: np.ndarray  # int32 [S/window + 1], window = state_window(row width)
    sorted_fields: Optional[np.ndarray] = None  # int32 [Np] (MVM; pad 0)


def padded_len(n: int) -> int:
    return (n // CHUNK + 2) * CHUNK


_NATIVE_PLAN = None  # tri-state: None = untried, False = unavailable, else fn
_PLAN_POOL = None  # one fixed-size executor per process, created once
_PLAN_POOL_LOCK = __import__("threading").Lock()


def _plan_pool(workers: int):
    """Shared planning thread pool, sized once to the host's cores and
    NEVER shut down: a resize-by-replacement would race concurrent
    Trainers' in-flight map() futures against the old pool's shutdown
    (advisor r2). Oversubscription is impossible (cores is the useful
    ceiling regardless of any caller's num_sub); `workers` only matters
    the first call, as a floor for tiny-cpu_count() hosts."""
    global _PLAN_POOL
    with _PLAN_POOL_LOCK:
        if _PLAN_POOL is None:
            from concurrent.futures import ThreadPoolExecutor

            size = max(workers, min(os.cpu_count() or 1, 16))
            _PLAN_POOL = ThreadPoolExecutor(max_workers=size)
        return _PLAN_POOL


def _native_planner():
    """xf_plan_sorted via ctypes (native/parser.cc): a stable O(n) radix
    sort replacing np.argsort's ~150 ms/2M-occurrence comparison sort —
    the host would otherwise wall the sorted-engine step times. Falls
    back to numpy when the toolchain is missing, with one stderr
    warning that says why. XFLOW_NO_NATIVE_PLAN=1 forces the numpy path
    (used by the parity tests)."""
    global _NATIVE_PLAN
    if _NATIVE_PLAN is None:
        if os.environ.get("XFLOW_NO_NATIVE_PLAN"):
            _NATIVE_PLAN = False
        else:
            import subprocess

            try:
                from xflow_tpu.data import native

                # build + load NOW: the import alone compiles nothing, and
                # a missing toolchain must be decided here, once — not
                # raised from inside the first batch's plan
                native.get_lib()
                _NATIVE_PLAN = native.native_plan_sorted
            except (ImportError, OSError, subprocess.SubprocessError) as e:
                from xflow_tpu.telemetry import warn_once

                warn_once(
                    "native_planner",
                    f"native planner unavailable ({type(e).__name__}: {e}); "
                    "numpy's argsort builds the sorted plans instead (far "
                    "slower)",
                )
                _NATIVE_PLAN = False
    return _NATIVE_PLAN


def planner_name() -> str:
    """Which sort builds this process's sorted plans: "native" (the C
    radix sort) or "python" (numpy's argsort) — `xflow train` names it
    in its summary line."""
    return "native" if _native_planner() else "python"


def plan_sorted_batch(
    slots: np.ndarray,
    mask: np.ndarray,
    num_slots: int,
    fields: Optional[np.ndarray] = None,
    wire: bool = False,
    window: int = WINDOW,
) -> SortedPlan:
    """Sort a [B, F] batch's occurrences by table slot (host side).

    `window` is the table's `state_window` (the kernels take theirs from
    the same rule and refuse a plan made at another); the default is
    that of every packed row of up to 95 floats.

    Masked occurrences keep their (meaningless) slot — their mask rides
    along and zeroes both the forward contribution and the gradient.
    `fields` (MVM) rides through the same permutation when given.
    Uses the C radix-sort builder when built (bit-identical to the numpy
    path — both sorts are stable; parity-tested). `wire=True` asks the
    C builder to emit the compact wire dtypes (uint16 rows, uint8
    mask/fields — compact_plan_wire's format) DIRECTLY, skipping the
    int32/f32 intermediate and its astype passes; the caller must have
    checked the CONFIG bounds (rows per sub-batch ≤ 2^16, fields <
    2^8) — compact_plan_wire stays the single place those rules live,
    and it passes already-compact arrays through untouched. Without the
    native builder `wire` is ignored (the numpy path emits int32 and
    compaction happens downstream as before).
    """
    native = _native_planner()
    if native and num_slots % window == 0:
        # no try/except: the numpy fallback exists for a MISSING toolchain
        # (handled once at load in _native_planner); a runtime failure in a
        # successfully-built planner is a bug and must raise, not silently
        # re-run the 4x-slower argsort on every batch
        if wire:
            from xflow_tpu.data.native import native_plan_sorted_wire

            ss, row, m, f, off = native_plan_sorted_wire(
                np.ascontiguousarray(slots, np.int32),
                mask, fields, num_slots, window,
                padded_len(slots.size),
            )
            return SortedPlan(ss, row, m, off, f)
        ss, row, m, f, off = native(
            np.ascontiguousarray(slots, np.int32),
            mask, fields, num_slots, window,
            padded_len(slots.size),
        )
        return SortedPlan(ss, row, m, off, f)
    flat_slots = np.ascontiguousarray(slots, np.int32).ravel()
    flat_mask = np.ascontiguousarray(mask, np.float32).ravel()
    if flat_slots.size and (
        int(flat_slots.min()) < 0 or int(flat_slots.max()) >= num_slots
    ):
        # same loud-failure contract as the native planner: an out-of-range
        # slot would sort past the last window and be silently dropped
        raise ValueError(
            f"slot out of range [0, {num_slots}): "
            f"min={int(flat_slots.min())} max={int(flat_slots.max())}"
        )
    n = flat_slots.shape[0]
    np_len = padded_len(n)
    order = np.argsort(flat_slots, kind="stable").astype(np.int32)
    pad = np_len - n
    ss = np.concatenate([flat_slots[order], np.full(pad, num_slots - 1, np.int32)])
    # pads sort at (or past) the real occurrences of slot num_slots-1, so
    # the full padded array is sorted and the last window's range covers
    # every padded position — nothing is left unwritten by the kernels
    win_off = np.searchsorted(ss, np.arange(0, num_slots + 1, window)).astype(np.int32)
    sorted_fields = None
    if fields is not None:
        flat_fields = np.ascontiguousarray(fields, np.int32).ravel()
        sorted_fields = np.concatenate([flat_fields[order], np.zeros(pad, np.int32)])
    return SortedPlan(
        sorted_slots=ss,
        sorted_row=np.concatenate([(order // slots.shape[1]).astype(np.int32),
                                   np.zeros(pad, np.int32)]),
        sorted_mask=np.concatenate([flat_mask[order], np.zeros(pad, np.float32)]),
        win_off=win_off,
        sorted_fields=sorted_fields,
    )


def chunk_chain_counts(win_off) -> dict:
    """What one call of the sorted kernels does with a plan, from its
    offsets alone: `chunk_visits`, the (window, chunk) pairs a kernel
    computes on — every one a CHUNK-wide one-hot pass however few of the
    chunk's occurrences the window owns — and `chunk_loads`, the chunks
    it asks HBM for. A flat plan is one line and the single-stream
    kernels carry their chunk chain along it (`_gather_span`): a chunk
    is loaded once. Stacked plans ([NS, windows + 1]) go through the
    multi-buffer kernels, where every span is a chain of its own and
    loads what it visits. The step records' `host.chunk_visits` /
    `host.chunk_loads` (docs/OBSERVABILITY.md)."""
    off = np.asarray(win_off, np.int64)
    start, end = off[..., :-1], off[..., 1:]
    visits = int(np.where(end > start, (end - 1) // CHUNK - start // CHUNK + 1, 0).sum())
    if off.ndim > 1:
        return {"chunk_visits": visits, "chunk_loads": visits}
    lo, hi = int(off[0]), int(off[-1])
    loads = (hi - 1) // CHUNK - lo // CHUNK + 1 if hi > lo else 0
    return {"chunk_visits": visits, "chunk_loads": loads}


def map_host_parallel(fn, n: int) -> list:
    """Run fn(0..n-1) on the shared planning pool when the C planner is
    built (it releases the GIL during the sort, so plans parallelize
    across host cores); the numpy fallback holds the GIL through
    argsort, where threads would only add churn. Order-preserving."""
    workers = min(n, os.cpu_count() or 1)
    if workers > 1 and _native_planner():
        return list(_plan_pool(workers).map(fn, range(n)))
    return [fn(i) for i in range(n)]


def plan_sorted_stacked(
    slots: np.ndarray,
    mask: np.ndarray,
    num_slots: int,
    fields: Optional[np.ndarray] = None,
    num_sub: int = 1,
    wire: bool = False,
    window: int = WINDOW,
) -> SortedPlan:
    """Per-sub-batch sorted plans, stacked on a leading [NS] axis.

    Splits the [B, F] batch into `num_sub` row-contiguous sub-batches and
    plans each independently (row ids are LOCAL to the sub-batch). The
    device step maps over the NS axis, so per-row aggregates are sized
    [B/NS, ...] — small enough to stay cache-resident for models whose
    row-side state is large (MVM's [B·nf, k]); XLA accumulates the table
    gradient across sub-batches. `B % num_sub == 0` is required (the
    planner's callers pick a divisor). `num_sub=1` returns FLAT arrays.
    """
    B = slots.shape[0]
    if num_sub <= 1:
        return plan_sorted_batch(
            slots, mask, num_slots, fields=fields, wire=wire, window=window
        )
    if B % num_sub:
        raise ValueError(f"batch {B} not divisible by num_sub {num_sub}")
    bs = B // num_sub

    def one(i):
        return plan_sorted_batch(
            slots[i * bs : (i + 1) * bs],
            mask[i * bs : (i + 1) * bs],
            num_slots,
            fields=None if fields is None else fields[i * bs : (i + 1) * bs],
            wire=wire,
            window=window,
        )

    if num_slots % window == 0:
        plans = map_host_parallel(one, num_sub)
    else:
        plans = [one(i) for i in range(num_sub)]
    return SortedPlan(
        sorted_slots=np.stack([p.sorted_slots for p in plans]),
        sorted_row=np.stack([p.sorted_row for p in plans]),
        sorted_mask=np.stack([p.sorted_mask for p in plans]),
        win_off=np.stack([p.win_off for p in plans]),
        sorted_fields=(
            np.stack([p.sorted_fields for p in plans]) if fields is not None else None
        ),
    )


def sorted_gather_map(table, batch: dict, row_keys: tuple, batch_rows: int,
                      row_fn, K: int, bf16: bool):
    """Gather the table ONCE, then map the row side over sub-batches.

    `row_fn(occ_t [K8, Np], *row_arrays, rows)` computes logits for one
    sub-batch from its raw gathered rows. Flat plans use the
    single-stream gather; stacked plans ([NS, Np_sub],
    `plan_sorted_stacked`) run ONE `table_gather_sorted_multi` over the
    concatenated streams — window-major, so the table (and its gradient
    blocks in the VJP) crosses HBM exactly once per step instead of
    once per sub-batch. Before this, NS=4 sub-batching re-read the
    whole table 4× each direction — the dominant cost of the MVM
    segment path (docs/PERF.md 3a).
    """
    pack = pack_of(table, K)
    ss, wo = batch["sorted_slots"], batch["win_off"]
    arrs = tuple(batch[k] for k in row_keys)
    # the `gather` phase (telemetry.PHASE_LABELS) is opened where the
    # op is CALLED: its VJP, the scatter, inherits the caller's path
    # under autodiff's `transpose(`, which reads `scatter`
    if ss.ndim == 1:
        with jax.named_scope("gather"):
            occ_t = table_gather_sorted(table, ss, wo, bf16, pack)
        return row_fn(occ_t, *arrs, batch_rows)
    ns, np_sub = ss.shape
    rows = batch_rows // ns
    with jax.named_scope("gather"):
        occ_all = table_gather_sorted_multi(table, ss.reshape(-1), wo, bf16, pack)
    occ_ns = occ_all.reshape(occ_all.shape[0], ns, np_sub).transpose(1, 0, 2)
    logits = jax.lax.map(
        lambda a: row_fn(a[0], *a[1:], rows), (occ_ns, *arrs)
    )  # [NS, rows]
    return logits.reshape(batch_rows)


def auto_sub_batches(batch_size: int, row_state_bytes_per_row: int,
                     target_bytes: int = 1 << 24) -> int:
    """Smallest power-of-two NS (dividing batch_size) that keeps the
    per-sub-batch row-side state under `target_bytes`; capped so
    sub-batches keep >= 1024 rows. 16 MiB measured best on v5e for MVM
    at B=64k/nf=18/k=10 (NS=4 → 396k ex/s; NS=1 252k, NS=16 210k —
    smaller sub-batches pay window fragmentation in the table kernels,
    larger ones fall out of cache on the row side; docs/PERF.md)."""
    ns = 1
    while (
        batch_size % (ns * 2) == 0
        and batch_size // (ns * 2) >= 1024
        and (batch_size // ns) * row_state_bytes_per_row > target_bytes
    ):
        ns *= 2
    return ns


def resolve_sub_batches(cfg) -> int:
    """NS for the sorted layout (cfg.data.sorted_sub_batches; 0 = auto).

    Auto keeps MVM's *segment-path* per-sub-batch [B/NS·nf, k+1] row
    aggregate under 16 MiB (the measured v5e sweet spot — docs/PERF.md).
    FM's [B, 24] is already small, so NS=1 — and so is the MVM
    exclusive-fields product path's (models/mvm.py), which is the
    expected path whenever `model.mvm_exclusive` != off; a stray
    duplicate-field batch then runs the segment path at NS=1 (correct,
    just not cache-tuned — routing NS per batch would retrace the step).
    """
    ns = cfg.data.sorted_sub_batches
    B = cfg.data.batch_size
    if ns > 0:
        if B % ns:
            raise ValueError(
                f"data.sorted_sub_batches={ns} must divide batch_size={B}"
            )
        return ns
    if cfg.model.name == "mvm" and cfg.model.mvm_exclusive == "off":
        per_row = cfg.model.num_fields * (cfg.model.v_dim + 1) * 4
        return auto_sub_batches(B, per_row)
    if cfg.model.name == "ffm":
        # FFM's per-(row, field) aggregate is [B/NS·nf, nf·k+2]
        per_row = cfg.model.num_fields * (cfg.model.num_fields * cfg.model.v_dim + 2) * 4
        return auto_sub_batches(B, per_row)
    return 1


def sorted_row_width(cfg) -> int:
    """Floats in one row of the table the sorted engines stream — the
    one place the width lives: MVM [k], fused FM [1+k], FFM [1+nf·k]."""
    if cfg.model.name == "mvm":
        return cfg.model.v_dim
    if cfg.model.name == "ffm":
        return 1 + cfg.model.num_fields * cfg.model.v_dim
    return 1 + cfg.model.v_dim


def sorted_window(cfg) -> int:
    """`state_window` of the configured model's table as `init_tables`
    stores it (packed unless data.packed_tables=off)."""
    packed = cfg.data.packed_tables != "off" and cfg.num_slots % PACK == 0
    return state_window(sorted_row_width(cfg), PACK if packed else 1)


# ------------------------------------------------------------------ XLA path

def _sub_select(rows, sub, pack: int, K: int):
    """[..., pack*K] packed rows -> [..., K] logical rows selected by
    `sub` in [0, pack). Elementwise multiply-sum on 0/1 masks — NEVER a
    matmul, so no MXU operand rounding can touch the values."""
    sel = (sub[..., None] == jnp.arange(pack)).astype(rows.dtype)  # [..., pack]
    grouped = rows.reshape(*rows.shape[:-1], pack, K)
    return (grouped * sel[..., None]).sum(axis=-2)


def _gather_xla(table, sorted_slots, win_off, pack: int = 1):
    Sp, W = table.shape
    S, K = Sp * pack, W // pack
    safe = jnp.minimum(sorted_slots, S - 1)
    if pack == 1:
        occ = jnp.where((sorted_slots < S)[:, None], table[safe], 0.0)  # [Np, K]
    else:
        rows = jnp.where(
            (sorted_slots < S)[:, None], table[safe // pack], 0.0
        )  # [Np, pack*K]
        occ = _sub_select(rows, safe % pack, pack, K)
    out = jnp.zeros((_k8(K), sorted_slots.shape[0]), table.dtype)
    return jax.lax.dynamic_update_slice(out, occ.T, (0, 0))


def _scatter_xla(d_occ_t, sorted_slots, win_off, num_slots, k: int, pack: int = 1):
    safe = jnp.minimum(sorted_slots, num_slots - 1)
    d = jnp.where((sorted_slots < num_slots)[None, :], d_occ_t[:k], 0.0)
    if pack == 1:
        return jax.ops.segment_sum(d.T, safe, num_segments=num_slots)
    sub = safe % pack
    sel = (sub[:, None] == jnp.arange(pack)).astype(d.dtype)  # [Np, pack]
    d_exp = (d.T[:, None, :] * sel[:, :, None]).reshape(-1, pack * k)
    return jax.ops.segment_sum(d_exp, safe // pack, num_segments=num_slots // pack)


# --------------------------------------------------------------- Pallas path

def _dot_f32(a, onehot_f32, dims, bf16: bool):
    """MXU contraction of `a` against a 0/1 matrix, f32-accurate by default.

    `bf16=False` (default): splits `a` into three bf16 terms (hi/mid/lo,
    8 mantissa bits each — together the full f32 mantissa) and runs
    three DEFAULT-precision bf16 matmuls, since the other operand is
    EXACTLY representable in bf16 (one-hot 0/1). Where an output element
    selects a single column (the gather), (hi+mid)+lo reconstructs the
    f32 value BIT-exactly; where it sums several columns (the scatter,
    duplicate slots in a chunk), each column's contribution is exact and
    only the f32 summation ORDER differs from a direct accumulation —
    the same ≤1-ulp-per-add reorder class as any parallel reduction.
    Cost: 3 bf16 MXU passes — about half of Precision.HIGHEST (which
    decomposes BOTH operands), Mosaic's only other non-DEFAULT option.

    `bf16=True` (cfg.data.sorted_bf16): one rounded pass — values carry
    8 mantissa bits, the standard bf16-training trade. The flag is
    threaded as a static argument (never a global) so each jitted step
    keeps the precision of the config it was built with.

    The three exact terms run as ONE stacked MXU pass: hi/mid/lo
    concatenated along `a`'s free axis ([W, 3K] x [W, C] instead of
    three [W, K] x [W, C]), then the three output blocks summed in the
    same (hi+mid)+lo order — bit-identical results, and the skinny
    free dim (K=11 of 128 MXU rows) wastes 3x less of the systolic
    array per window (measured ~1.5x faster gather/scatter kernels than
    three separate passes)."""
    oh = onehot_f32.astype(jnp.bfloat16)
    if bf16:
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), oh, dims, preferred_element_type=jnp.float32
        )
    hi = a.astype(jnp.bfloat16)
    rem = a - hi.astype(jnp.float32)
    mid = rem.astype(jnp.bfloat16)
    lo = (rem - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    free = 1 - dims[0][0][0]  # a's non-contracted axis (2-D, one contract dim)
    a3 = jnp.concatenate([hi, mid, lo], axis=free)
    out = jax.lax.dot_general(a3, oh, dims, preferred_element_type=jnp.float32)
    # lhs free dims lead the result: blocks stack along result axis 0
    k = a.shape[free]
    return (out[:k] + out[k : 2 * k]) + out[2 * k :]

def _windowed_select(table_block, rel, pack: int, bf16: bool):
    """One window's per-occurrence table rows via the one-hot MXU
    contraction, in logical ([W, K] block, pack=1) or packed
    ([W/pack, pack*K] block) layout. Packed does the one-hot over
    PACKED rows (pack× narrower iota/compare and pack× shorter MXU
    contraction) and then selects the sub-row with `pack` STATIC
    slice-multiply-adds — 0/1 masks, elementwise, exact. Returns
    occ [K, C]."""
    if pack == 1:
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (table_block.shape[0], rel.shape[1]), 0)
            == rel
        ).astype(jnp.float32)
        return _dot_f32(table_block, onehot, (((0,), (0,)), ((), ())), bf16)
    Wp = table_block.shape[0]
    K = table_block.shape[1] // pack
    rel_p = rel // pack  # floor semantics also for out-of-window negatives
    onehot_p = (
        jax.lax.broadcasted_iota(jnp.int32, (Wp, rel.shape[1]), 0) == rel_p
    ).astype(jnp.float32)
    occ_p = _dot_f32(table_block, onehot_p, (((0,), (0,)), ((), ())), bf16)  # [pack*K, C]
    sub = rel - rel_p * pack  # [1, C]; out-of-window chunks have no onehot hit
    occ = occ_p[0:K, :] * (sub == 0)
    for p in range(1, pack):
        occ = occ + occ_p[p * K : (p + 1) * K, :] * (sub == p)
    return occ


def _open_chain(cur, first, last, when, start_in, nb):
    """Open a chunk chain over chunks first..last (where `when`): no
    chunk entered yet, the first nb-1 asked for."""
    from jax.experimental import pallas as pl

    @pl.when(when)
    def _():
        cur[0] = first - 1
        for i in range(nb - 1):
            @pl.when(first + i <= last)
            def _(i=i):
                start_in(first + i)


def _gather_span(slots_ref, out_ref, table_ref, slc, stage, cur, sem_s, sem_o, sem_d,
                 base, start, end, bf16, pack, stream=None):
    """Windowed gather of ONE occurrence span [start, end) against the
    table block at `base`, as one link of a CHUNK CHAIN that outlives
    the span. A chunk's ring buffer is chosen by its global index
    (`position // CHUNK % NB`, NB = the scratch buffer count, `PIPE_NB`)
    and `cur` (SMEM) holds the chunk the chain is on, so a span that
    begins on the chunk the span before it ended on — a window of the
    benchmark's cells holds a quarter of a chunk — neither loads nor
    waits: it computes on the buffer. Entering a chunk waits for its
    slots (asked for NB-1 chunks ago) and asks for chunk c+NB-1's,
    whatever span that one belongs to: a chunk asked for at the top of
    a grid step stands behind the step's own block transfers (1.3 us a
    window on FM's fused kernel, 6 us on FFM's; PERF.md §5), and a chain
    that restarted at every span had nothing to overlap that wait with.

    `stage[sel]` collects the columns of every span that overlaps the
    chunk (columns whose slot is outside this window are left as they
    are) and is written out ONCE, when a span reaches the chunk's end or
    the chain closes. `stream` = (stream start, stream end, first grid
    step?, last grid step?) is the single-stream kernels': their spans
    are one monotone line over the grid's steps, so the chain is the
    whole call's — opened in the first grid step, closed (a partial last
    chunk flushed, the out copies drained) in the last — and a chunk's
    staging starts from zeros. Without it (the multi-buffer kernels,
    which walk nbuf streams inside a grid step) the span is a chain of
    its own and starts each chunk from what the output holds, since a
    neighbouring span's columns of the same chunk go through HBM; the
    out copy of c-1 is then drained before chunk c+NB-1's read lands in
    the same buffer. Shared by both kernels — a fix here fixes both."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    own = stream is None
    lo, hi, opens, closes = (start, end, True, True) if own else stream
    first, last, live = lo // CHUNK, (hi - 1) // CHUNK, hi > lo
    NB = stage.shape[0]  # pipeline depth = scratch buffer count
    K = table_ref.shape[1] // pack
    window = table_ref.shape[0] * pack  # the block IS one window (state_window)
    c0 = start // CHUNK  # aligned down: a neighbour's columns self-mask
    n_chunks = jnp.where(end > start, (end - 1) // CHUNK - c0 + 1, 0)

    def in_copies(c):
        sel = c % NB
        cols = pl.ds(c * CHUNK, CHUNK)
        slots = pltpu.make_async_copy(slots_ref.at[:, cols], slc.at[sel], sem_s.at[sel])
        if not own:
            return (slots,)
        return slots, pltpu.make_async_copy(
            out_ref.at[:, cols], stage.at[sel], sem_d.at[sel]
        )

    def out_copy(c):
        sel = c % NB
        return pltpu.make_async_copy(
            stage.at[sel], out_ref.at[:, pl.ds(c * CHUNK, CHUNK)], sem_o.at[sel]
        )

    def start_in(c):
        for cp in in_copies(c):
            cp.start()

    _open_chain(cur, first, last, opens & live, start_in, NB)

    def chunk_step(i, carry):
        c = c0 + i
        sel = c % NB
        entering = c > cur[0]

        @pl.when(entering)
        def _():
            cur[0] = c
            for cp in in_copies(c):
                cp.wait()
            if not own:
                # stage[sel] was chunk c-NB's staging: its out copy has
                # had NB-1 chunks of time
                @pl.when(c - NB >= first)
                def _():
                    out_copy(c - NB).wait()

                stage[sel] = jnp.zeros(stage.shape[1:], jnp.float32)

        rel = slc[sel][0:1, :] - base  # [1, C]
        # f32-accurate selection via the stacked 3-term bf16 contraction
        # (_dot_f32): the MXU's default bf16 pass would round every
        # gathered table value to 8 mantissa bits (caught by an on-device
        # parity check vs the XLA gather, ~2^-8 rel error — CPU tests are
        # f32-exact and cannot see it)
        occ = _windowed_select(table_ref[:, :], rel, pack, bf16)  # [K, C]
        in_win = (rel >= 0) & (rel < window)  # [1, C]
        # No concat when K is already sublane-aligned: Mosaic rejects the
        # zero-row pad array (K=96/128/... would fail to compile)
        if stage.shape[1] > K:
            pad = jnp.zeros((stage.shape[1] - K, CHUNK), jnp.float32)
            occ = jnp.concatenate([occ, pad], axis=0)
        stage[sel] = jnp.where(in_win, occ, stage[sel])

        @pl.when(end >= (c + 1) * CHUNK)
        def _():
            out_copy(c).start()  # no later span of the chain owns a column

        @pl.when(entering & (c + NB - 1 <= last))
        def _():
            if own:
                @pl.when(c - 1 >= first)
                def _():
                    out_copy(c - 1).wait()

            start_in(c + NB - 1)  # chunk c-1's buffers: the chain has left it

        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_step, 0)

    @pl.when(closes & live)
    def _():
        @pl.when((last + 1) * CHUNK > hi)
        def _():
            out_copy(last).start()  # the chain ends inside its last chunk

        # entering c (on its own chain: asking for c+NB-1) drained the out
        # copies up to last-NB: one per buffer is still in flight, and an
        # unwaited DMA would leave its semaphore signaled for the next
        # chain
        for i in range(NB):
            @pl.when(last - i >= first)
            def _(i=i):
                out_copy(last - i).wait()


def _stream_of(off_ref):
    """The single-stream kernels' `stream` for the span functions: the
    whole call's occurrence line and where on the grid this step is."""
    from jax.experimental import pallas as pl

    t, n_win = pl.program_id(0), pl.num_programs(0)
    return off_ref[0], off_ref[n_win], t == 0, t == n_win - 1


def _gather_kernel(off_ref, slots_ref, table_ref, out_ref, slc, stage, cur, sem_s,
                   sem_o, *, bf16, n_tw, pack):
    """Single-stream windowed gather: grid step t owns logical window
    t % n_tw (identity when the stream covers the table once). The
    offsets are monotone over the whole grid (also where the stream is
    D buffers over one table and the grid wraps), so the steps' spans
    are one line and share one chunk chain (`_gather_span`): the grid
    runs in order (`dimension_semantics`), the scratch lives across its
    steps, and each chunk is loaded and written once a call."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    _gather_span(
        slots_ref, out_ref, table_ref, slc, stage, cur, sem_s, sem_o, None,
        (t % n_tw) * (table_ref.shape[0] * pack), off_ref[t], off_ref[t + 1], bf16, pack,
        stream=_stream_of(off_ref),
    )


def _gather_kernel_multi(off_ref, slots_ref, table_ref, out_ref, slc, stage, cur, sem_s,
                         sem_o, sem_d, *, bf16, nbuf, cap, pack):
    """Windowed gather over `nbuf` concatenated per-source buffers,
    WINDOW-MAJOR: grid step j owns table window j and walks every
    buffer's matching span, so each table block is DMA'd into VMEM
    exactly ONCE per call instead of once per buffer — the source-major
    order read the whole table nbuf times (nbuf = NS sub-batches on one
    device; measured 2×+ on the MVM segment path at NS=4). `off_ref` is [nbuf, wpo+1]
    buffer-local window offsets, the `_scatter_kernel_multi` contract.
    The spans of a grid step lie in nbuf streams, not on one line: each
    is a chunk chain of its own (`_gather_span` without `stream`)."""
    from jax.experimental import pallas as pl

    j = pl.program_id(0)

    def buf_step(i, carry):
        _gather_span(
            slots_ref, out_ref, table_ref, slc, stage, cur, sem_s, sem_o, sem_d,
            j * (table_ref.shape[0] * pack), i * cap + off_ref[i, j],
            i * cap + off_ref[i, j + 1], bf16, pack,
        )
        return carry

    jax.lax.fori_loop(0, nbuf, buf_step, 0)


# PIPE_NB (6, defined beside WINDOW) is the chunk-chain pipeline depth in
# buffers: deeper prefetch hides more of a chunk's wait (_gather_span) —
# 6 measured best vs 3 on v5e at bench shapes (the earlier rig, with a
# chain that restarted at every span); VMEM cost is NB × (K8+1) × CHUNK × 4 B: ~110 KB at
# K8=8, ~210 KB for the fused FM row (K8=16), 2 MB for FFM's K8=160 —
# `state_window` counts it


def _gather_pallas(table, sorted_slots, win_off, bf16=False, pack=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Sp, Kp = table.shape
    K = Kp // pack
    K8 = _k8(K)
    window = state_window(K, pack)
    n_tw = Sp * pack // window
    # grid = logical windows = len(win_off)-1; a multiple of n_tw when the
    # occurrence stream is D concatenated buffers over the same table
    n_win = win_off.shape[0] - 1
    assert n_win % n_tw == 0, (win_off.shape, Sp * pack, window)  # planned at another window
    n = sorted_slots.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_win,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # slots [1, Np]
            pl.BlockSpec((window // pack, Kp), lambda t, off: (t % n_tw, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),  # occ_t [K8, Np]
        scratch_shapes=[
            pltpu.VMEM((PIPE_NB, 1, CHUNK), jnp.int32),  # slc, the chain's ring
            pltpu.VMEM((PIPE_NB, K8, CHUNK), jnp.float32),  # out staging
            pltpu.SMEM((1,), jnp.int32),  # the chunk the chain is on
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
        ],
    )
    return pl.pallas_call(
        partial(_gather_kernel, bf16=bf16, n_tw=n_tw, pack=pack),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K8, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True
        ),
    )(win_off, sorted_slots.reshape(1, n), table)


def _gather_pallas_multi(table, sorted_slots, loc_off, cap, bf16=False, pack=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Sp, Kp = table.shape
    K = Kp // pack
    K8 = _k8(K)
    window = state_window(K, pack)
    n_win = Sp * pack // window
    nbuf, wpo1 = loc_off.shape
    n = sorted_slots.shape[0]
    assert wpo1 == n_win + 1, (loc_off.shape, n_win)
    assert cap % CHUNK == 0 and nbuf * cap == n, (nbuf, cap, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_win,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # slots [1, Np]
            pl.BlockSpec((window // pack, Kp), lambda t, off: (t, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),  # occ_t [K8, Np]
        scratch_shapes=[
            pltpu.VMEM((PIPE_NB, 1, CHUNK), jnp.int32),  # slc, pipelined
            pltpu.VMEM((PIPE_NB, K8, CHUNK), jnp.float32),  # old/staging
            pltpu.SMEM((1,), jnp.int32),  # the chunk a span's chain is on
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
        ],
    )
    return pl.pallas_call(
        partial(_gather_kernel_multi, bf16=bf16, nbuf=nbuf, cap=cap, pack=pack),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K8, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(loc_off, sorted_slots.reshape(1, n), table)


def _scatter_span(slots_ref, d_ref, slc, dch, cur, sem_s, sem_d, base, start, end,
                  acc_t, bf16, pack=1, k=None, stream=None):
    """Accumulate one occurrence span's contribution to the window at
    `base` into acc_t ([K8, W] logical, [pack*K, W/pack] packed) — the
    precision-critical DMA + one-hot + `_dot_f32` sequence shared by
    the single-stream and multi-buffer scatter kernels (a fix here
    fixes both). Packed expands the [K, C] cotangent chunk to
    [pack*K, C] with `pack` static 0/1-masked block copies (exact) and
    contracts against the PACKED one-hot — pack× fewer MXU MACs per
    chunk. The span is one link of the chunk chain `_gather_span`
    describes: a chunk's slots and cotangent sit in ring buffer
    `position // CHUNK % NB`, `cur` holds the chunk the chain is on, a
    span that begins on it computes without a load or a wait, and
    entering chunk c asks for chunk c+NB-1 whatever span that belongs
    to. With `stream` (the single-stream kernels) the chain is the whole
    call's, carried over the grid's steps: a chunk is loaded once a
    call. Without it (the multi-buffer kernel) each span is its own. A
    window adds its chunks in the order it always did, so the sums keep
    their bits."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lo, hi, opens = (start, end, True) if stream is None else stream[:3]
    first, last, live = lo // CHUNK, (hi - 1) // CHUNK, hi > lo
    c0 = start // CHUNK  # aligned down: a neighbour's columns get no one-hot lane
    n_chunks = jnp.where(end > start, (end - 1) // CHUNK - c0 + 1, 0)
    window = acc_t.shape[1] * pack  # the accumulator IS one window (state_window)

    NB = dch.shape[0]  # pipeline depth = scratch buffer count

    def in_copies(c):
        sel = c % NB
        cols = pl.ds(c * CHUNK, CHUNK)
        return (
            pltpu.make_async_copy(slots_ref.at[:, cols], slc.at[sel], sem_s.at[sel]),
            pltpu.make_async_copy(d_ref.at[:, cols], dch.at[sel], sem_d.at[sel]),
        )

    def start_in(c):
        for cp in in_copies(c):
            cp.start()

    _open_chain(cur, first, last, opens & live, start_in, NB)

    def chunk_step(i, acc):
        c = c0 + i
        sel = c % NB

        @pl.when(c > cur[0])
        def _():
            cur[0] = c
            for cp in in_copies(c):
                cp.wait()

            @pl.when(c + NB - 1 <= last)
            def _():
                start_in(c + NB - 1)  # chunk c-1's buffers: the chain has left it

        rel = slc[sel][0:1, :] - base  # [1, C]; out-of-window: no lane
        if pack == 1:
            onehot = (
                jax.lax.broadcasted_iota(jnp.int32, (window, CHUNK), 0) == rel
            ).astype(jnp.float32)  # [W, C]
            # [K8, C] x [W, C] contracting C -> [K8, W]
            # f32-accurate for the same reason as the gather; duplicate
            # slots in a chunk make this a SUM, so vs XLA's scatter only
            # the f32 accumulation order differs (<= 1 ulp/add, _dot_f32)
            return acc + _dot_f32(dch[sel], onehot, (((1,), (1,)), ((), ())), bf16)
        rel_p = rel // pack
        onehot_p = (
            jax.lax.broadcasted_iota(jnp.int32, (window // pack, CHUNK), 0) == rel_p
        ).astype(jnp.float32)  # [W/pack, C]
        sub = rel - rel_p * pack
        d_exp = jnp.concatenate(
            [dch[sel][0:k, :] * (sub == p) for p in range(pack)], axis=0
        )  # [pack*K, C]
        return acc + _dot_f32(d_exp, onehot_p, (((1,), (1,)), ((), ())), bf16)

    return jax.lax.fori_loop(0, n_chunks, chunk_step, acc_t)


def _scatter_kernel(off_ref, slots_ref, d_ref, out_ref, slc, dch, cur, sem_s, sem_d,
                    *, bf16, pack):
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    K8 = d_ref.shape[0]
    K = out_ref.shape[1] // pack
    window = out_ref.shape[0] * pack
    rows = pack * K if pack > 1 else K8
    acc_t = jnp.zeros((rows, window // pack), jnp.float32)
    acc_t = _scatter_span(
        slots_ref, d_ref, slc, dch, cur, sem_s, sem_d,
        t * window, off_ref[t], off_ref[t + 1], acc_t, bf16, pack, K,
        stream=_stream_of(off_ref),
    )
    out_ref[:, :] = (acc_t if pack > 1 else acc_t[0:K, :]).T  # [W/pack, pack*K]


def _scatter_pallas(d_occ_t, sorted_slots, win_off, num_slots, k: int, bf16=False,
                    pack=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K8, n = d_occ_t.shape
    window = state_window(k, pack)
    n_win = num_slots // window
    assert win_off.shape[0] == n_win + 1, (win_off.shape, num_slots, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_win,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # slots [1, Np]
            pl.BlockSpec(memory_space=pl.ANY),  # d [K8, Np]
        ],
        out_specs=pl.BlockSpec((window // pack, pack * k), lambda t, off: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((PIPE_NB, 1, CHUNK), jnp.int32),
            pltpu.VMEM((PIPE_NB, K8, CHUNK), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),  # the chunk the chain is on
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
        ],
    )
    return pl.pallas_call(
        partial(_scatter_kernel, bf16=bf16, pack=pack),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_slots // pack, pack * k), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(win_off, sorted_slots.reshape(1, n), d_occ_t)


def _scatter_kernel_multi(off_ref, slots_ref, d_ref, out_ref, slc, dch, cur, sem_s,
                          sem_d, *, bf16, nbuf, cap, pack):
    """Windowed scatter over `nbuf` concatenated per-source buffers.

    The cotangent stream is nbuf buffers of `cap` positions each (stacked
    sub-batch plans), all targeting the SAME table; grid step j
    owns table window j and accumulates the matching span of every
    buffer before one [W, K] block write — each output block is visited
    exactly once, so no cross-step revisit semantics are needed.
    `off_ref` is [nbuf, wpo+1] buffer-local window offsets with
    off_ref[i, wpo] extended to `cap` (pads ride in the last window)."""
    from jax.experimental import pallas as pl

    j = pl.program_id(0)
    K8 = d_ref.shape[0]
    K = out_ref.shape[1] // pack
    window = out_ref.shape[0] * pack

    def buf_step(i, acc_t):
        # aligned-down reads stay >= i*cap (cap % CHUNK == 0)
        return _scatter_span(
            slots_ref, d_ref, slc, dch, cur, sem_s, sem_d,
            j * window, i * cap + off_ref[i, j], i * cap + off_ref[i, j + 1],
            acc_t, bf16, pack, K,
        )

    rows = pack * K if pack > 1 else K8
    acc_t = jnp.zeros((rows, window // pack), jnp.float32)
    acc_t = jax.lax.fori_loop(0, nbuf, buf_step, acc_t)
    out_ref[:, :] = (acc_t if pack > 1 else acc_t[0:K, :]).T


def _scatter_pallas_multi(d_occ_t, sorted_slots, loc_off, num_slots, k, cap,
                          bf16=False, pack=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K8, n = d_occ_t.shape
    nbuf, wpo1 = loc_off.shape
    window = state_window(k, pack)
    n_win = num_slots // window
    assert wpo1 == n_win + 1, (loc_off.shape, n_win)
    assert cap % CHUNK == 0 and nbuf * cap == n, (nbuf, cap, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_win,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # slots [1, Np]
            pl.BlockSpec(memory_space=pl.ANY),  # d [K8, Np]
        ],
        out_specs=pl.BlockSpec((window // pack, pack * k), lambda t, off: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((PIPE_NB, 1, CHUNK), jnp.int32),
            pltpu.VMEM((PIPE_NB, K8, CHUNK), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),  # the chunk the chain is on
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
        ],
    )
    return pl.pallas_call(
        partial(_scatter_kernel_multi, bf16=bf16, nbuf=nbuf, cap=cap, pack=pack),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_slots // pack, pack * k), jnp.float32),
    )(loc_off, sorted_slots.reshape(1, n), d_occ_t)


def _scatter_ftrl_kernel(off_ref, slots_ref, d_ref, w_ref, n_ref, z_ref,
                         w_out, n_out, z_out, slc, dch, cur, sem_s, sem_d,
                         *, bf16, pack, alpha, beta, lambda1, lambda2):
    """Fused windowed scatter-add + FTRL-proximal window update: grid
    step t accumulates window t's complete gradient block (every chunk
    of its span — the block's gradient is FINAL at the write point, so
    applying the optimizer here is exact) and writes the UPDATED
    (w, n, z) blocks instead of the gradient. The gradient never
    exists in HBM, and the separate dense optimizer sweep — O(S) per
    step regardless of batch (docs/PERF.md lever 5b) — disappears into
    this already-streaming pass. FTRL math is optim/ftrl._update_one
    verbatim (incl. the lazy-init parity guard)."""
    from jax.experimental import pallas as pl

    from xflow_tpu.optim.ftrl import _update_one

    t = pl.program_id(0)
    K8 = d_ref.shape[0]
    K = w_out.shape[1] // pack
    window = w_out.shape[0] * pack
    rows = pack * K if pack > 1 else K8
    acc_t = jnp.zeros((rows, window // pack), jnp.float32)
    acc_t = _scatter_span(
        slots_ref, d_ref, slc, dch, cur, sem_s, sem_d,
        t * window, off_ref[t], off_ref[t + 1], acc_t, bf16, pack, K,
        stream=_stream_of(off_ref),
    )
    g = (acc_t if pack > 1 else acc_t[0:K, :]).T  # [W/pack, pack*K]
    w_new, n_new, z_new = _update_one(
        w_ref[:, :], n_ref[:, :], z_ref[:, :], g, alpha, beta, lambda1, lambda2
    )
    w_out[:, :] = w_new
    n_out[:, :] = n_new
    z_out[:, :] = z_new


def _scatter_ftrl_pallas(d_occ_t, sorted_slots, win_off, w, n, z, k, hp,
                         bf16=False, pack=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K8, n_occ = d_occ_t.shape
    num_slots = w.shape[0] * pack
    window = state_window(k, pack)
    n_win = num_slots // window
    assert win_off.shape[0] == n_win + 1, (win_off.shape, num_slots, window)
    state_block = pl.BlockSpec((window // pack, pack * k), lambda t, off: (t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_win,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # slots [1, Np]
            pl.BlockSpec(memory_space=pl.ANY),  # d [K8, Np]
            state_block, state_block, state_block,  # w, n, z windows
        ],
        out_specs=(state_block, state_block, state_block),
        scratch_shapes=[
            pltpu.VMEM((PIPE_NB, 1, CHUNK), jnp.int32),
            pltpu.VMEM((PIPE_NB, K8, CHUNK), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),  # the chunk the chain is on
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
            pltpu.SemaphoreType.DMA((PIPE_NB,)),
        ],
    )
    shape = jax.ShapeDtypeStruct((num_slots // pack, pack * k), jnp.float32)
    return pl.pallas_call(
        partial(
            _scatter_ftrl_kernel, bf16=bf16, pack=pack, alpha=hp.alpha,
            beta=hp.beta, lambda1=hp.lambda1, lambda2=hp.lambda2,
        ),
        grid_spec=grid_spec,
        out_shape=(shape, shape, shape),
        # update the state in place. Alias indices count ALL flattened
        # call operands INCLUDING the scalar-prefetch array: 0=win_off,
        # 1=slots, 2=d, 3=w, 4=n, 5=z -> outputs 0..2 (verified: a
        # {2: 0} mapping is rejected with d's shape in the error)
        input_output_aliases={3: 0, 4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(win_off, sorted_slots.reshape(1, n_occ), d_occ_t, w, n, z)


def scatter_ftrl_sorted(d_occ_t, sorted_slots, win_off, w, n, z, k: int, hp,
                        bf16=False, pack=1):
    """Windowed scatter-add of the occurrence cotangent + FTRL update in
    ONE table pass: returns (w', n', z'). `hp` carries
    (alpha, beta, lambda1, lambda2) — cfg.optim.ftrl. Semantically
    identical to `table_gather_sorted`'s VJP followed by
    optim/ftrl._update_one; the fusion removes the HBM-materialized
    gradient and the separate dense optimizer sweep (the CPU/XLA
    fallback composes exactly those pieces, so tests equate the two)."""
    if _on_tpu():
        return _scatter_ftrl_pallas(
            d_occ_t, sorted_slots, win_off, w, n, z, k, hp, bf16, pack
        )
    from xflow_tpu.optim.ftrl import _update_one

    num_slots = w.shape[0] * pack
    g = _scatter_xla(d_occ_t, sorted_slots, win_off, num_slots, k, pack)
    return _update_one(w, n, z, g, hp.alpha, hp.beta, hp.lambda1, hp.lambda2)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ------------------------------------------------- row-sum kernel (fwd)

def _rowsum_kernel_factory(num_rows, ch, chunk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(rows_ref, vals_ref, out_ref, vchunk, vt_ref, rchunk, sem_v, sem_r):
        n_chunks = vals_ref.shape[1] // chunk
        out_ref[:, :] = jnp.zeros((num_rows, ch), jnp.float32)

        def chunk_step(c, carry):
            o = c * chunk
            cp_r = pltpu.make_async_copy(rows_ref.at[:, pl.ds(o, chunk)], rchunk, sem_r)
            cp_r.start()
            cp_v = pltpu.make_async_copy(vals_ref.at[:, pl.ds(o, chunk)], vchunk, sem_v)
            cp_v.start()
            cp_r.wait()
            cp_v.wait()
            vt_ref[:, :] = vchunk[:, :].T  # [chunk, ch]: rows readable per i

            def inner(i, carry2):
                r = rchunk[0, i]
                out_ref[pl.ds(r, 1), :] += vt_ref[pl.ds(i, 1), :]
                return carry2

            jax.lax.fori_loop(0, chunk, inner, 0, unroll=chunk)
            return carry

        jax.lax.fori_loop(0, n_chunks, chunk_step, 0)

    return kernel


def _rowsum_pallas(vals_t, rows, num_rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ch, n = vals_t.shape
    assert n % CHUNK == 0, (n, CHUNK)
    assert ch % 8 == 0, ch
    return pl.pallas_call(
        _rowsum_kernel_factory(num_rows, ch, CHUNK),
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((num_rows, ch), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_rows, ch), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((ch, CHUNK), jnp.float32),
            pltpu.VMEM((CHUNK, ch), jnp.float32),
            pltpu.SMEM((1, CHUNK), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )(rows.reshape(1, n), vals_t)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def row_sums_sorted(vals_t, rows, num_rows):
    """Σ over occurrences into rows: out[r, c] = Σ_{j: rows[j]=r} vals_t[c, j].

    The occurrence→row reduction is the FM sorted-path wall (docs/PERF.md):
    XLA's scatter runs ~24 ns/occurrence at bench shapes. On TPU this op is
    a Pallas kernel holding the [num_rows, ch] accumulator VMEM-resident
    and doing one dynamic-sublane read-modify-write per occurrence on the
    scalar core (~15 ns measured, 1.6×) — viable only while
    num_rows × 128 lanes × 4 B fits VMEM (num_rows ≤ ~64k), which is why
    MVM's [B·nf] segment space keeps the XLA segment-sum instead.
    Constraints: ch % 8 == 0, len(rows) % CHUNK == 0 (pad rows with 0 and
    vals with 0 — pads accumulate zero into row 0). Differentiable in
    `vals_t`; the VJP is the row gather d_out.T[:, rows]."""
    # VMEM guard: the accumulator occupies num_rows × 128 lanes × 4 B
    # regardless of ch (lane padding); 64k rows = 33.5 MB is measured to
    # fit on v5e, 2× that failed to compile (tools/rowsum_probe.py) —
    # larger batches fall back to the XLA segment-sum rather than dying
    # in Mosaic. num_rows % 8: a non-sublane-aligned accumulator block
    # (e.g. batch_size=50) would also fail deep in Mosaic (advisor r2).
    if _on_tpu() and num_rows <= 65536 and num_rows % 8 == 0:
        return _rowsum_pallas(vals_t, rows, num_rows)
    return jax.ops.segment_sum(vals_t.T, rows, num_segments=num_rows)


def _rowsum_fwd(vals_t, rows, num_rows):
    return row_sums_sorted(vals_t, rows, num_rows), rows


def _rowsum_bwd(num_rows, rows, d_out):
    return jnp.take(d_out.T, rows, axis=1), None


row_sums_sorted.defvjp(_rowsum_fwd, _rowsum_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def segment_sum_channels(vals_t, seg, num_segments):
    """Σ over occurrences into segments: out[s, c] = Σ_{j: seg[j]=s} vals_t[c, j].

    The SEGMENT-space counterpart of `row_sums_sorted` for row sides too
    large for the VMEM accumulator (MVM's and FFM's [B·nf] per-(row,
    field) spaces). Forward is XLA's per-channel scatter-add; the win is
    the BACKWARD: the plain VJP gathers ch-wide rows from the [S, ch]
    cotangent, whose (8, 128)-tiled HBM layout serves ch/128 useful
    lanes per line. Here the bwd gathers PACK-row groups from the free
    [S/PACK, PACK·ch] reshape — full 512 B lines — and sub-selects
    elementwise (`_sub_select`, never a matmul: gradients stay exact).
    Bench-level effect: MVM dupfields 651k → ~705k ex/s (the remaining
    wall is the forward scatter-add itself — docs/PERF.md 3a). Falls
    back to the plain gather when S % PACK != 0."""
    sums = jax.vmap(
        lambda r: jax.ops.segment_sum(r, seg, num_segments=num_segments)
    )(vals_t)
    return sums.T  # [S, ch]


def _ssc_fwd(vals_t, seg, num_segments):
    return segment_sum_channels(vals_t, seg, num_segments), seg


def _ssc_bwd(num_segments, seg, d_out):
    ch = d_out.shape[1]
    if num_segments % PACK:
        return jnp.take(d_out, seg, axis=0).T, None
    grouped = d_out.reshape(num_segments // PACK, PACK * ch)
    rows = jnp.take(grouped, seg // PACK, axis=0)  # [Np, PACK*ch]
    return _sub_select(rows, seg % PACK, PACK, ch).T, None


segment_sum_channels.defvjp(_ssc_fwd, _ssc_bwd)


# ------------------------------------------------------------ public op

@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def table_gather_sorted(table, sorted_slots, win_off, bf16=False, pack=1):
    """Per-occurrence table rows, transposed: [K8, Np] for slot-sorted
    occurrences. Differentiable in `table`; the VJP is the windowed
    scatter-add. Rows K..K8 are zero. Padded columns (positions past the
    batch's real occurrences) hold row `S-1`'s values, not zeros —
    multiply by `sorted_mask` before use. `bf16` (static — thread
    cfg.data.sorted_bf16 here) trades the f32-accurate 3-pass MXU
    contraction for one rounded pass (see `_dot_f32`). `pack` (static;
    callers derive it with `pack_of`) says the table is stored
    [S/pack, pack*K] (see `pack_table`); slot indices stay LOGICAL, the
    output is identical either way, and the VJP writes the gradient in
    the table's own layout."""
    if _on_tpu():
        return _gather_pallas(table, sorted_slots, win_off, bf16, pack)
    return _gather_xla(table, sorted_slots, win_off, pack)


def _gather_fwd(table, sorted_slots, win_off, bf16=False, pack=1):
    return table_gather_sorted(table, sorted_slots, win_off, bf16, pack), (
        sorted_slots,
        win_off,
        table.shape,
    )


def _gather_bwd(bf16, pack, res, d_occ_t):
    sorted_slots, win_off, (rows, width) = res
    num_slots, k = rows * pack, width // pack
    if _on_tpu():
        d_table = _scatter_pallas(
            d_occ_t, sorted_slots, win_off, num_slots, k, bf16, pack
        )
    else:
        d_table = _scatter_xla(d_occ_t, sorted_slots, win_off, num_slots, k, pack)
    return d_table, None, None


table_gather_sorted.defvjp(_gather_fwd, _gather_bwd)


# --------------------------------- multi-buffer op (stacked sub-batches)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def table_gather_sorted_multi(table, sorted_slots, loc_off, bf16=False, pack=1):
    """`table_gather_sorted` over a concatenated multi-buffer stream: the
    per-call input is `nbuf` fixed-capacity buffers, each slot-sorted
    over the SAME table. Its one caller is `sorted_gather_map`: a single
    device's NS row-contiguous sub-batch plans (`plan_sorted_stacked`)
    over the whole table, whose row side needs the occurrences kept
    sub-batch-major. (The fullshard engine's per-source-shard buffers
    have the same shape but no such need: it merges them into one
    slot-sorted stream on the device and calls `table_gather_sorted` —
    parallel/sorted_fullshard.py `merge_received`.)
    Both directions are WINDOW-MAJOR — grid step j owns table window j
    and walks every buffer's matching span — so the table crosses
    HBM→VMEM exactly ONCE per call regardless of nbuf (the source-major
    order read it nbuf times; measured 2×+ on the MVM segment path at
    NS=4). What grows with nbuf is the SPANS: one per (window, buffer),
    each at least one CHUNK-wide one-hot pass however few occurrences
    it holds, and each a chunk chain of its own (PERF.md §5: a visit's
    compute as in the single-stream kernels plus the cold load their
    carried chain no longer pays, nbuf times the visits on sparse
    windows).
    The VJP accumulates every buffer's span into one [W, K] block write
    per window (`_scatter_kernel_multi`).

    `loc_off` [nbuf, wpo+1]: buffer-local window offsets, last entry
    extended to `cap`. Capacity = sorted_slots.size // nbuf, a CHUNK
    multiple (host contract: `plan_sorted_stacked` sub-batch plans via
    `sorted_gather_map`; pads at the table's last slot, mask 0).
    `pack` as in `table_gather_sorted` (the table stored
    [S/pack, pack*K])."""
    if _on_tpu():
        cap = sorted_slots.shape[0] // loc_off.shape[0]
        return _gather_pallas_multi(table, sorted_slots, loc_off, cap, bf16, pack)
    return _gather_xla(table, sorted_slots, None, pack)


def _gather_multi_fwd(table, sorted_slots, loc_off, bf16=False, pack=1):
    return table_gather_sorted_multi(table, sorted_slots, loc_off, bf16, pack), (
        sorted_slots,
        loc_off,
        table.shape,
    )


def _gather_multi_bwd(bf16, pack, res, d_occ_t):
    sorted_slots, loc_off, (rows, width) = res
    num_slots, k = rows * pack, width // pack
    if _on_tpu():
        cap = sorted_slots.shape[0] // loc_off.shape[0]
        d_table = _scatter_pallas_multi(
            d_occ_t, sorted_slots, loc_off, num_slots, k, cap, bf16, pack
        )
    else:
        d_table = _scatter_xla(d_occ_t, sorted_slots, None, num_slots, k, pack)
    return d_table, None, None


table_gather_sorted_multi.defvjp(_gather_multi_fwd, _gather_multi_bwd)
