"""Multi-view machine.

Reference: `/root/reference/src/model/mvm/mvm_worker.cc`. Per latent
dim k it sums v over the features of each libffm field ("view"):
`v_sum[k][row][fgid] += v` (`mvm_worker.cc:182-196`), takes the product
over fields (`:198-205`), sums over k (`:207-212`), and applies σ.

Reference accidents not replicated (SURVEY.md §7):
- per-row field range is `[0, max_fgid)` sized by the *max* field id
  seen, so the max field's accumulation writes one past the vector end
  (`mvm_worker.cc:43` vs `:75` — out-of-bounds UB); we use the
  configured `num_fields` and multiply only over fields present in the
  row (absent fields contribute the multiplicative identity rather than
  a hard 0);
- its hand gradient divides by `1 + v_sum` while the forward's product
  has no `1 +` (`mvm_worker.cc:153-157` vs `:202` — the `1+` variant is
  commented out at `:201`), and zero-guards inconsistently; we use the
  exact gradient via `jax.grad`;
- predict iterates `v_multi.size()` = k rows instead of the batch
  (`mvm_worker.cc:96`), truncating evaluation to 10 rows per block.

The per-(row, field) segment-sum is expressed as a one-hot einsum —
a [F, num_fields] × [F, k] batched matmul that XLA maps onto the MXU —
rather than a scatter, keeping the hot path dense and fusible.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from xflow_tpu.models.base import Model, register_model

# Exclusive-fields product path constants (see mvm_product_channels):
# LOG_TINY guards ln(0) — EXACT zeros are tracked separately in the Z
# channel, and because every formula uses ln-sums DIFFERENCES (S, or the
# exclusive S - L_j), the clamped value cancels wherever it matters.
# The S clip bounds exp: products past e^60 are a diverged model (logits
# saturate the ±30 reference sigmoid clamp long before), and below e^-87
# f32 underflows to the 0 the true product rounds to anyway.
MVM_LOG_TINY = 1e-30
MVM_LOG_CLIP = (-87.0, 60.0)


def _table_specs(cfg):
    return {"v": (cfg.model.v_dim,)}


def has_field_duplicates(fields: np.ndarray, mask: np.ndarray) -> bool:
    """Host-side check: does any row carry two masked occurrences of the
    same field? The exclusive-fields product path requires it false (the
    per-(row, field) view sum then has at most one term, so the product
    over fields equals the product over the row's occurrences). Real
    libffm CTR data is one-feature-per-field by construction; multi-
    valued fields route to the segment-sum path instead.

    Bitmask popcount when field ids fit 64 bits (~3 vector passes), else
    a per-row sort."""
    f = np.asarray(fields)
    m = np.asarray(mask) > 0
    if f.size == 0 or f.shape[1] <= 1:
        return False
    if int(f.max(initial=0)) < 64 and hasattr(np, "bitwise_count"):
        # np.bitwise_count is NumPy >= 2.0; older NumPy (still JAX-
        # supported) takes the sort path below
        bits = np.where(m, np.uint64(1) << f.astype(np.uint64), np.uint64(0))
        distinct = np.bitwise_count(np.bitwise_or.reduce(bits, axis=1))
        return bool((distinct.astype(np.int64) < m.sum(axis=1)).any())
    # wide field spaces: masked-out entries get distinct negative keys so
    # they can never form an adjacent equal pair
    keyed = np.where(m, f.astype(np.int64), -1 - np.arange(f.shape[1])[None, :])
    s = np.sort(keyed, axis=1)
    return bool(((s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)).any())


def resolve_mvm_product(mvm_exclusive: str, has_dup: bool, num_processes: int) -> bool:
    """Route one batch: product path (True) or segment-sum path (False).

    Callers: single-process routing (any engine) and `mvm_exclusive=on`
    everywhere. The multi-process fullshard engine does NOT call this
    under `auto` — it plans with fields and coordinates the per-batch
    choice through a rank-symmetric flag allgather
    (train/engine.py `Engine.agree`), so a local data-dependent
    raise can never strand peer ranks in their collectives. Under `on`
    duplicates raise by contract (the user asserted exclusive fields).
    """
    if mvm_exclusive == "off":
        return False
    if mvm_exclusive not in ("auto", "on"):
        raise ValueError(
            f"model.mvm_exclusive={mvm_exclusive!r}: expected auto|on|off"
        )
    if has_dup:
        if mvm_exclusive == "on" or num_processes > 1:
            raise ValueError(
                "MVM exclusive-fields product path: a row carries two masked "
                "occurrences of the same field. Set model.mvm_exclusive=off "
                "to use the segment-sum path"
                + (
                    " (this multi-process configuration cannot fall back per "
                    "batch: the two paths' collective sequences differ across "
                    "ranks — only the fullshard engine's `auto` coordinates "
                    "the choice. Peer ranks that hit no duplicate may block "
                    "in their collectives until the launcher's fail-fast "
                    "teardown — set mvm_exclusive=off up front)"
                    if num_processes > 1
                    else ""
                )
            )
        return False
    return True


def mvm_product_channels(occ_t_k, sorted_mask, k: int):
    """[k, Np] RAW gathered v rows + [Np] mask -> [ch, Np] channels whose
    row sums carry the per-row factor products in log space.

    With exclusive fields, Π_f s[c,r,f] = Π_{occ∈r} v_c[occ] (absent
    fields are the multiplicative identity; masked pads contribute 0 to
    every channel). Channels per latent dim: ln|v| (zeros clamped to
    ln(LOG_TINY) — the Z channel is the truth about zeros, and every
    consumer uses ln-sum DIFFERENCES so the clamp cancels), negative
    count (sign parity), exact-zero count; zero-padded to a sublane
    multiple. The row state is a cache-resident [B, ~32] array — the
    same class as FM's, replacing the [B·nf, k+1] segment aggregate that
    was the MVM step's measured wall (docs/PERF.md 3a)."""
    from xflow_tpu.ops.sorted_table import _k8

    m = sorted_mask[None, :]
    L = m * jnp.log(jnp.maximum(jnp.abs(occ_t_k), MVM_LOG_TINY))
    N = m * (occ_t_k < 0.0)
    Z = m * (occ_t_k == 0.0)
    ch = _k8(3 * k)
    pad = jnp.zeros((ch - 3 * k, occ_t_k.shape[1]), occ_t_k.dtype)
    return jnp.concatenate([L, N, Z, pad], axis=0)


def _products_from_sums(S, NC, ZC):
    """(ln-sum, negative count, zero count) -> signed products. Counts
    are integer-valued floats ≤ max_nnz, exact in f32."""
    sign = 1.0 - 2.0 * jnp.mod(NC, 2.0)
    return jnp.where(ZC > 0, 0.0, sign * jnp.exp(jnp.clip(S, *MVM_LOG_CLIP)))


def make_row_products(reduce_rows, broadcast_rows, k: int, restore_dP=None):
    """Build the exclusive-fields product op:

        op(occ_t_k [k, Np], mask [Np], rows [Np]) -> P [R, k]

    with P[r, c] = Π over r's masked occurrences of v_c — computed in
    log space through `reduce_rows` (the occurrence→row reduction:
    `row_sums_sorted` on one device; rowsum + psum_scatter + psum in the
    fullshard engine) — and a HAND-WRITTEN VJP that is exact at FTRL's
    exact zeros in both directions:

      dP/dv_j = (exclusive product of the row's OTHER factors)
              = sign_ex · exp(S - L_j) · [ZC - Z_j == 0]

    A zero occurrence keeps its nonzero reactivation gradient (the
    clamped ln cancels in S - L_j), and the other occurrences of a
    zero-containing row get EXACTLY zero — matching the oracle bitwise
    in the zero pattern, which FTRL's lazy-init parity guard (g==0 ∧
    n==0 keeps the initial weight) depends on; an epsilon-perturbation
    scheme instead leaves ~1e-34 gradient residues that mark untouched
    slots as touched. `broadcast_rows` is the bwd's row-aggregate
    transport (identity on one device; all_gather over 'data' in the
    fullshard engine — the same small-row-cotangent traffic class as
    FM's backward). `restore_dP` undoes any replication-split the
    engine's transpose applies to the incoming cotangent — the SAME
    hook, for the same reason, as make_ffm_row_op's `restore_dl`: the
    fullshard shard_map transpose hands each 'table' copy dP/T (the
    plain autodiff path restores it through owner_reduce's psum
    transpose, which a custom bwd bypasses), so the engine passes a
    psum over 'table'. None = identity (single device). This was NOT a
    theoretical hole: without the hook the fullshard product path's
    updates diverged from single-device at every T>1 (measured at
    (4,2)/(2,4)/(1,8) after 3 steps: loss 0.693127/137/143 vs
    0.693108, table maxabs err up to 7e-4 and growing with T; exact at
    (8,1)) — covered by test_sorted_fullshard's product-mode
    parametrization.
    """
    restore_dP = restore_dP or (lambda x: x)

    @jax.custom_vjp
    def op(occ_t_k, mask, rows):
        P, _ = _fwd(occ_t_k, mask, rows)
        return P

    def _fwd(occ_t_k, mask, rows):
        sums = reduce_rows(mvm_product_channels(occ_t_k, mask, k), rows)
        S, NC, ZC = sums[:, :k], sums[:, k : 2 * k], sums[:, 2 * k : 3 * k]
        P = _products_from_sums(S, NC, ZC)
        return P, (occ_t_k, mask, rows, sums)

    def _bwd(res, dP):
        occ_t_k, mask, rows, sums = res
        dP = restore_dP(dP)
        per = jnp.take(
            broadcast_rows(jnp.concatenate([dP, sums[:, : 3 * k]], axis=1)),
            rows,
            axis=0,
        ).T  # [4k, Np]
        dPo, S, NC, ZC = (per[i * k : (i + 1) * k] for i in range(4))
        m = mask[None, :]
        L = jnp.log(jnp.maximum(jnp.abs(occ_t_k), MVM_LOG_TINY))
        S_ex = S - m * L
        NC_ex = NC - m * (occ_t_k < 0.0)
        ZC_ex = ZC - m * (occ_t_k == 0.0)
        sign_ex = 1.0 - 2.0 * jnp.mod(NC_ex, 2.0)
        P_ex = jnp.where(
            ZC_ex > 0, 0.0, sign_ex * jnp.exp(jnp.clip(S_ex, *MVM_LOG_CLIP))
        )
        return dPo * P_ex * m, None, None

    op.defvjp(lambda o, m_, r: _fwd(o, m_, r), _bwd)
    return op


def _segment_row_side(occ_t, sorted_row, sorted_mask, sorted_fields,
                      rows, nf, k, plus=0.0):
    """One sub-batch's row side from raw gathered rows: one segment-sum
    keyed on `row * nf + field` → logits [rows]."""
    from xflow_tpu.ops.sorted_table import (
        segment_sum_channels,
        wire_mask,
        wire_rows,
    )

    sorted_row, sorted_mask = wire_rows(sorted_row), wire_mask(sorted_mask)
    seg = sorted_row * nf + wire_rows(sorted_fields)  # [Np]
    occm_t = occ_t[:k] * sorted_mask[None, :]
    # stack the mask as one extra channel: its segment-sum is the
    # per-(row, field) occurrence count, giving `present` in the same op
    stacked = jnp.concatenate([occm_t, sorted_mask[None, :]], axis=0)  # [k+1, Np]
    sums = segment_sum_channels(stacked, seg, rows * nf)  # [rows*nf, k+1]
    s = sums[:, :k].reshape(rows, nf, k)
    present = (sums[:, k] > 0).reshape(rows, nf)
    factors = jnp.where(present[..., None], s + plus, 1.0)  # [rows, nf, k]
    return jnp.prod(factors, axis=1).sum(axis=-1)  # [rows]


def _product_row_side(occ_t, sorted_row, sorted_mask, rows, k, plus=0.0):
    """One sub-batch's row side on the exclusive-fields product path:
    the SAME [rows, ~32] row-sum kernel FM uses — no per-(row, field)
    segment space exists at all."""
    from xflow_tpu.ops.sorted_table import row_sums_sorted, wire_mask, wire_rows

    sorted_row, sorted_mask = wire_rows(sorted_row), wire_mask(sorted_mask)
    op = make_row_products(
        lambda stacked, rows_: row_sums_sorted(stacked, rows_, rows),
        lambda arr: arr,
        k,
    )
    # plus-one form: the per-occurrence factor is (plus + v) — with
    # exclusive fields this equals the per-field (plus + s), so the
    # same exclusive-product op covers both factor forms
    P = op(occ_t[:k] + plus, sorted_mask, sorted_row)  # [rows, k]
    return P.sum(axis=1)


def _forward_sorted(tables, batch, cfg):
    """Sorted-window path (ops/sorted_table.py), two row-side forms:

    - PRODUCT (no `sorted_fields` in the batch): the host verified every
      masked (row, field) has at most one occurrence (the natural libffm
      shape; `has_field_duplicates`), so each view sum is a single v and
      the field product collapses to a product over the row's
      occurrences — computed in log space through `row_sums_sorted`'s
      cache-resident [B, ~24] accumulator, exactly like FM.
    - SEGMENT (`sorted_fields` present): general multi-valued fields via
      one segment-sum keyed on `row * num_fields + field`. Its
      [B·nf, k+1] aggregate falls out of cache at B=64k (the backward
      gather was the measured MVM wall, docs/PERF.md 3a), so sorted
      arrays may arrive STACKED [NS, Np_sub] (`plan_sorted_stacked`) and
      the ROW side maps over row-contiguous sub-batches — the table
      side runs as ONE window-major multi-buffer gather/scatter
      (`sorted_gather_map`), so the table crosses HBM once per step,
      not once per sub-batch. NS-invariant math either way.
    """
    from xflow_tpu.ops.sorted_table import sorted_gather_map

    v = tables["v"]
    bf16 = cfg.data.sorted_bf16
    plus = 1.0 if cfg.model.mvm_plus_one else 0.0
    k = cfg.model.v_dim
    B = batch["labels"].shape[0]
    if "sorted_fields" not in batch:
        return sorted_gather_map(
            v, batch, ("sorted_row", "sorted_mask"), B,
            lambda occ, sr, sm, rows: _product_row_side(occ, sr, sm, rows, k, plus),
            k, bf16,
        )
    nf = cfg.model.num_fields
    return sorted_gather_map(
        v, batch, ("sorted_row", "sorted_mask", "sorted_fields"), B,
        lambda occ, sr, sm, sf, rows: _segment_row_side(
            occ, sr, sm, sf, rows, nf, k, plus
        ),
        k, bf16,
    )


def forward(tables, batch, cfg):
    if "sorted_slots" in batch:
        return _forward_sorted(tables, batch, cfg)
    from xflow_tpu.ops.sorted_table import batch_rows

    v = tables["v"]
    nf = cfg.model.num_fields
    mask = batch["mask"]
    vg = batch_rows(v, batch, cfg.model.v_dim) * mask[..., None]
    onehot = (batch["fields"][..., None] == jnp.arange(nf)) * mask[..., None]  # [B, F, nf]
    # full-precision einsum: the contraction is tiny (F × nf × k) and the
    # downstream product-of-fields amplifies any bf16 rounding
    s = jnp.einsum("bfn,bfk->bnk", onehot, vg, precision=jax.lax.Precision.HIGHEST)
    present = onehot.sum(axis=1) > 0  # [B, nf]
    plus = 1.0 if cfg.model.mvm_plus_one else 0.0
    factors = jnp.where(present[..., None], s + plus, 1.0)
    return jnp.prod(factors, axis=1).sum(axis=-1)  # [B]


MODEL = register_model(Model(name="mvm", table_specs=_table_specs, forward=forward))
