"""Field-aware factorization machine (FFM).

BASELINE.json config 5 — "Field-aware FM (extend src/model) on Criteo"
— the one driver config the reference leaves unimplemented. The
semantic base is the reference's FM worker
(`/root/reference/src/model/fm/fm_worker.cc:80-86`), extended per Juan
et al.'s FFM: feature i carries one latent vector PER opposing field,
and the pair (i, j) interacts through its field-crossed vectors:

    ŷ = wx + Σ_{i<j} ⟨v_{i, f_j}, v_{j, f_i}⟩

Table layout: ONE fused ``wv [S, 1 + nf·k]`` row per feature — column 0
is w, then nf contiguous k-blocks, block c holding the feature's vector
against field c (the same fused-table argument as models/fm.py: the
step cost is table row traffic, and FFM's whole point is that a row is
wide, so never pay two gathers).

TPU shape — the field-sum formulation: with

    S[b, c1, c2, :] = Σ_{i : f_i = c1} v_{i, c2}      ([B, nf, nf, k])

the pairwise term is

    ½ ( Σ_{c1,c2} ⟨S[b,c1,c2,:], S[b,c2,c1,:]⟩ − Σ_i ‖v_{i, f_i}‖² )

S comes from a one-hot MXU contraction (row-major path) or a
per-(row, field) segment-sum over the slot-sorted occurrence stream
(sorted path — the same engine class as MVM's segment mode), and the
double-field contraction is one einsum. For one-feature-per-field rows
this reduces to the textbook FFM sum; for multi-valued fields it
generalizes it exactly — same-field feature pairs i, j ∈ c interact
through ⟨v_{i,c}, v_{j,c}⟩, which IS the textbook term since f_j = c.
(Proof: the c1↔c2 sum counts every unordered cross-field pair twice
and the diagonal counts same-field pairs twice plus the self terms;
halving and subtracting the selves leaves exactly Σ_{i<j}.)

Path choice: on ONE device `sorted_layout=auto` (and `on`) runs the
ALIGNED HYBRID sorted engine (`make_ffm_aligned_op` below): windowed
table gather + host placement permutation + the pair term over a
block transposition + fused scatter+FTRL. Measured on a v5e at
Criteo's shape (39 fields x k=4, rows of 157 floats, 2^21 slots; the
benchmark's `ffm-v4-f39-s21.text-zipf`, my chip runs of PR 37, PERF.md
sections 5 and 6): 298k examples/s at B = 32768, a 110 ms step.
Batches with duplicate (row, field) occurrences fall back per batch
to the row-major einsum path in `forward` (the general form; counted
in `final.ffm_rowmajor_batches`; not measured on this rig). The per-(row, field)
SEGMENT engine (`make_ffm_row_op`) is the fullshard MESH engine's row
side only, where the no-replication layout requires it — the round-4
single-device forced-sorted segment path (and the XLA compiler crash
it hit at nf·k = 128, B = 64k) no longer exists: `sorted_layout=on`
now means the hybrid, and rejects non-aligned batches with a clear
error (train/engine.py _ffm_aligned).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import Model, register_model


def _dims(cfg):
    return cfg.model.num_fields, cfg.model.v_dim


def _table_specs(cfg):
    nf, k = _dims(cfg)
    return {"wv": (1 + nf * k,)}


def ffm_logits_from_sums(sums, nf: int, k: int):
    """[rows, ch] per-(row·field) sums folded to [rows, nf, ch] →
    logits. Channel layout (ffm channel contract, shared by the
    single-device sorted path and the fullshard engine): 0 = w,
    1..nf·k = the v blocks, nf·k+1 = ‖v_self‖². `sums[r, c1, ...]` is
    the sum over the row's field-c1 occurrences."""
    K = 1 + nf * k
    wx = sums[:, :, 0].sum(axis=1)  # [rows]
    S = sums[:, :, 1:K].reshape(sums.shape[0], nf, nf, k)
    qsum = sums[:, :, K].sum(axis=1)  # [rows]
    full = jnp.einsum(
        "bcdk,bdck->b", S, S, precision=jax.lax.Precision.HIGHEST
    )
    return wx + 0.5 * (full - qsum)


def ffm_occurrence_channels(occ_t, mask, fields, nf: int, k: int):
    """[K8, Np] raw gathered rows + mask + per-occurrence field ids →
    [K+1, Np] channel stream for the per-(row, field) segment-sum:
    masked w, masked v blocks, then channel K = ‖v_{occ, f_occ}‖² (the
    self term — an own-field block select via a one-hot sum, never a
    gather; the mask is already folded into every channel)."""
    K = 1 + nf * k
    occm = occ_t[:K] * mask[None, :]
    v3 = occm[1:].reshape(nf, k, occm.shape[1])  # [nf, k, Np]
    onehot = (fields[None, :] == jnp.arange(nf)[:, None]).astype(occm.dtype)
    vself = (v3 * onehot[:, None, :]).sum(axis=0)  # [k, Np]
    q = (vself * vself).sum(axis=0)  # [Np]
    return jnp.concatenate([occm, q[None, :]], axis=0)  # [K+1, Np]


def make_ffm_row_op(reduce_segments, broadcast_rows, nf: int, k: int,
                    restore_dl=None):
    """Build the FFM row-side op:

        op(occ_t [K8, Np], mask [Np], fields [Np], rows [Np]) -> logits [R]

    computed through `reduce_segments(data [K+1, Np], seg [Np]) ->
    [R, nf, K+1]` (the occurrence→(row, field) reduction:
    `segment_sum_channels` on one device; segment-sum + owner_reduce in
    the fullshard engine) — with a HAND-WRITTEN VJP that is exact at
    structural zeros:

        d v_i[c,·] = dl_b · (S[b, c, f_i, ·] − [c == f_i]·v_i[c,·])
        d w_i      = dl_b

    The two terms live in ONE subtraction, so when S[b, c, f_i] is
    bitwise v_i (a single-occupant field — the diagonal self-pair that
    must contribute nothing) or exactly 0 (an absent opposing field),
    the gradient is EXACTLY zero. jax.grad through the
    full-minus-self formulation computes the same two terms along
    different graph paths, and backend fusion leaves ~1e-11 residues
    that flip FTRL's lazy-init guard (g==0 ∧ n==0 keeps the initial
    weight) — observed as engine divergence on the (1, 8) fullshard
    mesh; the same failure class MVM's product op solves the same way
    (models/mvm.py make_row_products). `broadcast_rows` is the bwd's
    row-aggregate transport (identity on one device; all_gather over
    'data' in the fullshard engine — the same traffic class as the
    plain path's d_sums transpose). `restore_dl` undoes any
    replication-split the engine's transpose applies to the incoming
    cotangent (fullshard: the shard_map transpose hands each 'table'
    copy dl/T — the plain autodiff path restores it through
    owner_reduce's psum transpose, which a custom bwd bypasses; the
    hook is a psum over 'table'). None = identity (single device)."""
    K = 1 + nf * k
    restore_dl = restore_dl or (lambda x: x)

    @jax.custom_vjp
    def op(occ_t, mask, fields, rows):
        return _fwd(occ_t, mask, fields, rows)[0]

    def _fwd(occ_t, mask, fields, rows):
        data = ffm_occurrence_channels(occ_t, mask, fields, nf, k)
        sums = reduce_segments(data, rows * nf + fields)  # [R, nf, K+1]
        return ffm_logits_from_sums(sums, nf, k), (occ_t, mask, fields, rows, sums)

    def _bwd(res, dl):
        occ_t, mask, fields, rows, sums = res
        R = sums.shape[0]
        dl = restore_dl(dl)
        # ship the small per-row aggregates; build the (row, f)-major
        # transpose locally after transport
        packed = broadcast_rows(
            jnp.concatenate([dl[:, None], sums.reshape(R, -1)], axis=1)
        )  # [R_all, 1 + nf*(K+1)]
        dl_all, sums_all = packed[:, 0], packed[:, 1:]
        R_all = sums_all.shape[0]
        A = sums_all.reshape(R_all, nf, K + 1)[:, :, 1:K].reshape(R_all, nf, nf, k)
        # Tmat[b*nf + f, c*k + kk] = S[b, c, f, kk]
        Tmat = A.transpose(0, 2, 1, 3).reshape(R_all * nf, nf * k)
        G = jnp.take(Tmat, rows * nf + fields, axis=0).T  # [nf*k, Np]
        occm_v = occ_t[1:K] * mask[None, :]
        blockmask = jnp.repeat(
            (fields[None, :] == jnp.arange(nf)[:, None]).astype(occ_t.dtype),
            k, axis=0,
        )  # [nf*k, Np]
        dl_occ = jnp.take(dl_all, rows) * mask  # [Np]
        d_v = (G - occm_v * blockmask) * dl_occ[None, :]
        d_w = dl_occ[None, :]
        pad = jnp.zeros((occ_t.shape[0] - K, occ_t.shape[1]), occ_t.dtype)
        return jnp.concatenate([d_w, d_v, pad], axis=0), None, None, None

    op.defvjp(lambda o, m, f, r: _fwd(o, m, f, r), _bwd)
    return op


def _row_side_sorted(occ_t, sorted_row, sorted_mask, sorted_fields, rows, cfg):
    """One sub-batch's row side from raw gathered rows: one segment-sum
    keyed on `row·nf + field` → [rows·nf, K+1] field sums → logits. The
    same engine class as MVM's segment mode (models/mvm.py), with FFM's
    wide channel set and the exact-at-zeros hand VJP (make_ffm_row_op)."""
    from xflow_tpu.ops.sorted_table import (
        segment_sum_channels,
        wire_mask,
        wire_rows,
    )

    nf, k = _dims(cfg)
    K = 1 + nf * k
    sorted_row, sorted_mask = wire_rows(sorted_row), wire_mask(sorted_mask)
    fields = wire_rows(sorted_fields)
    op = make_ffm_row_op(
        lambda data, seg: segment_sum_channels(data, seg, rows * nf).reshape(
            rows, nf, K + 1
        ),
        lambda arr: arr,
        nf, k,
    )
    return op(occ_t, sorted_mask, fields, sorted_row)


def _forward_sorted(tables, batch, cfg):
    from xflow_tpu.ops.sorted_table import sorted_gather_map

    wv = tables["wv"]
    nf, k = _dims(cfg)
    if "ffm_invperm" in batch:
        return _forward_sorted_aligned(wv, batch, cfg)
    return sorted_gather_map(
        wv, batch, ("sorted_row", "sorted_mask", "sorted_fields"),
        batch["labels"].shape[0],
        lambda occ, sr, sm, sf, rows: _row_side_sorted(occ, sr, sm, sf, rows, cfg),
        1 + nf * k, cfg.data.sorted_bf16,
    )


# ---------------------------------------------------------------------------
# Aligned hybrid path (the single-device FFM engine since round 5).
#
# On aligned batches — at most ONE masked occurrence per (row, field),
# libffm's natural shape and what the bundled/bench data always is —
# the per-(row, field) "segment sum" is a pure PLACEMENT, so the row
# side never needs the segment engine: the windowed sorted gather
# (table streamed once per step) hands occ_t [K8, Np] in slot order,
# one host-planned inverse permutation places it as A [B, nfp, K8]
# (nfp = nf rounded up to the 8-sublane multiple, so [B·nfp, K8] →
# [B, nfp, K8] is a free view — no lane-boundary reshape anywhere),
# and the pairwise term's field crossing X[b, d, c-block] = A[b, c,
# d-block] is the block transposition it is (`cross_fields`): data
# movement at HBM cost, linear in nf·K8 at every width, with a
# hand-written VJP that reuses the forward's X. No dot is left on the
# row side.
#
# Measured at B = 32768 x 39 fields, k = 4, 2^21 slots on a v5e (the
# traced run of `ffm-v4-f39-s21.text-zipf`, PR 37; PERF.md section 5;
# operations joined to scopes through the compile record's `op_scopes`):
# a 109.8 ms step — the placement 43.9 (two permutation gathers of 16.0
# and 15.6, three relayouts of 3.1-3.3 — the third lays A out with the
# batch in lanes, XLA's own choice for the crossing — and the absent
# pairs' mask 2.6), the fused scatter+FTRL 30.9, the windowed gather
# 17.1, the pair term 14.0 (the slice of the v blocks 2.5, the crossing
# 2.4, the forward sum 2.2, the backward 3.5, d_A back to rows 3.4: each
# a pass over 0.84-1.34 GB at 650-680 GB/s), the guard 3.5. XLA counts
# 4.0e9 FLOPs a step.
# ---------------------------------------------------------------------------


def nf_padded(nf: int) -> int:
    """nf rounded to the 8-sublane multiple (see the layout note) —
    the same rounding rule as the kernels' channel padding."""
    from xflow_tpu.ops.sorted_table import _k8

    return _k8(nf)


def ffm_invperm(sorted_row, sorted_fields, sorted_mask, rows: int, nf: int):
    """HOST-side placement permutation for an aligned plan: int32
    [rows·nfp] mapping destination (row, field) → its sorted position,
    absent pairs → Np-1 (always a pad position: plans carry one spare
    chunk, ops/sorted_table.padded_len). Raises on duplicate (row,
    field) pairs — callers route those batches elsewhere
    (resolve_ffm_aligned)."""
    import numpy as np

    nfp = nf_padded(nf)
    Np = sorted_row.shape[0]
    inv = np.full(rows * nfp, Np - 1, np.int32)
    real = np.asarray(sorted_mask) > 0
    dest = (
        np.asarray(sorted_row)[real].astype(np.int64) * nfp
        + np.asarray(sorted_fields)[real]
    )
    inv[dest] = np.nonzero(real)[0].astype(np.int32)
    # duplicate detection without a sort: duplicates overwrite one slot,
    # so fewer occupied destinations than real occurrences ⇔ collision
    # (real positions are never Np-1 — the plan's spare pad chunk)
    if int((inv != Np - 1).sum()) != dest.size:
        raise ValueError(
            "ffm_invperm: duplicate (row, field) occurrence in an "
            "aligned plan — route duplicate-field batches to the "
            "general path (resolve_ffm_aligned)"
        )
    return inv


def has_field_duplicates(fields, mask) -> bool:
    """True when any row carries two masked occurrences of one field
    (shared host check — same definition as models/mvm.py's)."""
    from xflow_tpu.models.mvm import has_field_duplicates as _h

    return _h(fields, mask)


def resolve_ffm_aligned(batch_fields, batch_mask) -> bool:
    """Route one FFM batch: aligned hybrid (True) or the row-major
    general path (False). Host-side per batch, like MVM's product
    routing: the hybrid requires ≤1 masked occurrence per (row, field).
    Duplicate-field batches run the layout-fixed row-major einsum path
    (the general form)."""
    return not has_field_duplicates(batch_fields, batch_mask)


def _pair_masks(nf: int, k: int, nfp: int, k8: int, dtype):
    """Static 0/1 masks of the aligned row side, built IN-GRAPH from
    iota/compares (never a captured constant):

      Q [nfp, k8]: own-block select (column block c of row c)
      W [nfp, k8]: the w channel (column 0, real fields only)
    """
    cq = jnp.arange(nfp)[:, None]
    eq = jnp.arange(k8)[None, :]
    kq = eq - 1 - cq * k
    Q = ((kq >= 0) & (kq < k) & (cq < nf)).astype(dtype)
    W = ((eq == 0) & (cq < nf)).astype(dtype)
    return Q, W


def cross_fields(A, nf: int, k: int):
    """The pair term's field crossing, A [B, nfp, k8] -> X [B, nfp, k8]:

        X[b, d, 1 + c·k + kk] = A[b, c, 1 + d·k + kk]     (c, d < nf)

    zero in every pad (column 0, columns >= 1 + nf·k, rows >= nf). A
    block transposition: every element of X is a COPY of one element of
    A, so X is exact and the op costs a pass over A, linear in nf·k8.
    (XLA's layout assignment puts the batch in lanes for it by itself:
    the crossing is then a major <-> sublane exchange of [k, B] blocks.)"""
    B, nfp, k8 = A.shape
    V = A[:, :nf, 1 : 1 + nf * k].reshape(B, nf, nf, k)
    Xv = V.transpose(0, 2, 1, 3).reshape(B, nf, nf * k)
    return jnp.pad(Xv, ((0, 0), (0, nfp - nf), (1, k8 - 1 - nf * k)))


def make_ffm_pair(nf: int, k: int):
    """Build the aligned row side's pair term, pair(A [B, nfp, k8]) ->
    logits [B], with a HAND-WRITTEN VJP:

        logits = wx + ½(Σ A·X − Σ A²·Q),   X = cross_fields(A)
        d_A    = dl·(X − A·Q + W)

    from the forward's own X — the crossing is an involution, so the
    backward needs no second one. Exactness at FTRL's zeros (the
    lazy-init parity class both sibling ops document): for a
    single-occupant field, X at the self position is a copy of A, so the
    subtraction is EXACTLY zero; absent fields have A = 0 ⇒ X = 0; X, Q
    and W are zero in every pad, so d_A's pads are. Tested against a
    float64 double loop over pairs, and through the step against the
    row-major oracle path."""

    @jax.custom_vjp
    def pair(A):
        return _fwd(A)[0]

    def _fwd(A):
        with jax.named_scope("ffm_pair"):
            Q, W = _pair_masks(nf, k, A.shape[1], A.shape[2], A.dtype)
            X = cross_fields(A, nf, k)
            full = (A * X).sum((-1, -2))
            qsum = (A * A * Q[None]).sum((-1, -2))
            wx = (A * W[None]).sum((-1, -2))
            return wx + 0.5 * (full - qsum), (A, X)

    def _bwd(res, dl):
        A, X = res
        with jax.named_scope("ffm_pair"):
            Q, W = _pair_masks(nf, k, A.shape[1], A.shape[2], A.dtype)
            return (dl[:, None, None] * (X - A * Q[None] + W[None]),)

    pair.defvjp(_fwd, _bwd)
    return pair


def make_ffm_aligned_op(nf: int, k: int, k8: int, rows: int):
    """Build the aligned row-side op:

        op(occ_t [K8, Np], invperm [rows·nfp], src [Np], smask [Np])
            -> logits [rows]

    occ_t is the slot-sorted windowed gather output; `invperm` places
    it (ffm_invperm) as A [rows, nfp, K8]; `src` = sorted_row·nfp +
    sorted_field is the reverse map; the pair term over A is
    `make_ffm_pair`'s. The placement carries a HAND-WRITTEN VJP too:
    the transpose of a (partial) permutation gather is the reverse
    gather — d_occ[:, p] = d_A[src[p]]·smask[p] — never an XLA scatter
    (which would pay ~35 ns/row random-write latency for what is a
    permutation)."""
    nfp = nf_padded(nf)
    pair = make_ffm_pair(nf, k)

    # labels of their own for the two halves of the row side, INSIDE
    # the step's `rows` phase and counted as it (telemetry.PHASE_LABELS):
    # `ffm_place` (the permutation gather and its reverse) and `ffm_pair`
    # (the crossing, the pair sum and its hand-written backward). The
    # step's compile record says which operations are whose (`op_scopes`;
    # the device trace names an XLA fusion after what it fuses) —
    # docs/OBSERVABILITY.md
    @jax.custom_vjp
    def place(occ_t, invperm, src, smask):
        with jax.named_scope("ffm_place"):
            dead = (invperm != occ_t.shape[1] - 1).astype(occ_t.dtype)
            return (occ_t.T[invperm] * dead[:, None]).reshape(rows, nfp, k8)

    def _fwd(occ_t, invperm, src, smask):
        return place(occ_t, invperm, src, smask), (src, smask)

    def _bwd(res, d_A):
        src, smask = res
        with jax.named_scope("ffm_place"):
            d_occ = (d_A.reshape(rows * nfp, k8)[src] * smask[:, None]).T
        return d_occ, None, None, None

    place.defvjp(_fwd, _bwd)

    def op(occ_t, invperm, src, smask):
        return pair(place(occ_t, invperm, src, smask))

    return op


def ffm_aligned_logits(occ_t, batch, cfg):
    """Row-side logits for an aligned-hybrid batch, from the gathered
    occ_t — shared by the fused train step (train/step.py), the plain
    autodiff forward below, and eval."""
    from xflow_tpu.ops.sorted_table import _k8, wire_mask, wire_rows

    nf, k = _dims(cfg)
    nfp = nf_padded(nf)
    rows = batch["labels"].shape[0]
    smask = wire_mask(batch["sorted_mask"])
    src = wire_rows(batch["sorted_row"]) * nfp + wire_rows(batch["sorted_fields"])
    op = make_ffm_aligned_op(nf, k, _k8(1 + nf * k), rows)
    return op(occ_t, batch["ffm_invperm"], src, smask)


def _forward_sorted_aligned(wv, batch, cfg):
    from xflow_tpu.ops.sorted_table import pack_of, table_gather_sorted

    nf, k = _dims(cfg)
    K = 1 + nf * k
    with jax.named_scope("gather"):
        occ_t = table_gather_sorted(
            wv, batch["sorted_slots"], batch["win_off"], cfg.data.sorted_bf16,
            pack_of(wv, K),
        )
    return ffm_aligned_logits(occ_t, batch, cfg)


def block_transpose_perm(nf: int, k: int):
    """Static involution on the flattened [nf·nf·k] S index:
    (c1, c2, kk) ↔ (c2, c1, kk). Applying it as a minor-dim gather is
    how the pairwise contraction avoids ever materializing S as a 4-D
    [B, nf, nf, k] tensor — see `forward`'s layout note."""
    import numpy as np

    c1, c2, kk = np.meshgrid(
        np.arange(nf), np.arange(nf), np.arange(k), indexing="ij"
    )
    return jnp.asarray(
        (c2 * nf * k + c1 * k + kk).reshape(-1).astype(np.int32)
    )


def forward(tables, batch, cfg):
    """Row-major FFM forward in LAYOUT-FRIENDLY 3-D shapes.

    TPU HBM buffers are (8, 128)-tiled, so any tensor whose minor dim
    is the latent width k (4 at the practical shape) is stored at
    128/k× its logical bytes. The original formulation materialized
    [B, F, nf, k] and [B, nf, nf, k] einsum operands — ~3.5 GB EACH at
    B = 16k once padded, which made fwd+bwd the measured step wall
    (round-5 probe: fwd 30 ms, bwd 46 ms of an 86 ms step) and OOM'd
    outright at B = 64k. This formulation keeps every operand's minor
    dim ≥ nf·k = 72:

      vm [B, F, nf·k]   masked v blocks (block c = the feature's vector
                        against field c)
      S  [B, nf, nf·k]  = einsum over occurrences with the field
                        one-hot — S[b, c1, c2·k+kk] = S4[b, c1, c2, kk]
      full              = Σ Sf · Sf[:, PERM] where PERM is the static
                        (c1,c2)-block-transpose involution
                        (block_transpose_perm) on the flattened minor
                        dim — the pairwise ⟨S[c1,c2], S[c2,c1]⟩ sum
                        with no 4-D transpose ever stored
      qsum              = Σ (vm²·own-block select), the self-norm term,
                        one fused elementwise pass

    Same math as the module docstring's field-sum proof; the einsums
    run f32-exact (HIGHEST)."""
    if "sorted_slots" in batch:
        return _forward_sorted(tables, batch, cfg)
    from xflow_tpu.ops.sorted_table import batch_rows

    nf, k = _dims(cfg)
    E = nf * k
    mask = batch["mask"]
    wvg = batch_rows(tables["wv"], batch, 1 + E)  # [B, F, 1+nf*k]
    wx = (wvg[..., 0] * mask).sum(axis=-1)
    B, F = mask.shape
    vm = wvg[..., 1:] * mask[..., None]  # [B, F, E]
    onehot = (batch["fields"][..., None] == jnp.arange(nf)).astype(vm.dtype)
    onehot = onehot * mask[..., None]  # [B, F, nf]
    S = jnp.einsum(
        "bfc,bfe->bce", onehot, vm, precision=jax.lax.Precision.HIGHEST
    )  # [B, nf, E]
    Sf = S.reshape(B, nf * E)
    full = (Sf * Sf[:, block_transpose_perm(nf, k)]).sum(axis=-1)
    # own-field block select per occurrence: blocksel[b,f,c·k+kk] =
    # onehot[b,f,c] (a static minor-dim gather that fuses); mask is 0/1
    # and already folded into both vm and onehot
    blocksel = jnp.repeat(onehot, k, axis=-1)  # [B, F, E]
    qsum = (vm * vm * blocksel).sum(axis=(-1, -2))
    return wx + 0.5 * (full - qsum)


MODEL = register_model(Model(name="ffm", table_specs=_table_specs, forward=forward))
