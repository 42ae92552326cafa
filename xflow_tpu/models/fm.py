"""Factorization machine.

Reference: `/root/reference/src/model/fm/fm_worker.cc`. Its forward
(`calculate_loss`, `fm_worker.cc:159-202`) computes
σ(wx + S² − Q) where S and Q accumulate v and v² over *both* the
feature and the latent axes (`fm_worker.cc:178-196`: `v_sum[sid]` is
indexed by row only, inside the k loop), i.e. latent dims are coupled
through one scalar — and its hand-written w-gradient is accumulated
once per latent dim (`fm_worker.cc:134-148`), scaling it by k. Both are
accidents relative to Rendle's FM (SURVEY.md §7: fix, not replicate).

Default here is the standard FM second-order term, per latent dim:
  ½ Σₖ [(Σᵢ v_{ik})² − Σᵢ v²_{ik}]
with `cfg.model.fm_half=False` dropping the ½ (the reference also omits
it) and `cfg.model.fm_standard=False` reproducing the reference's
coupled form exactly for parity experiments. Gradients are exact
(`jax.grad`), not the reference's approximation.

Table layout: ONE fused ``wv [S, 1+k]`` table (column 0 = w, columns
1..k = v) instead of the reference's two server tables
(`fm_worker.cc:227-242` pulls/pushes w and v separately). The step's
cost is dominated by latency-bound table row gathers/scatters (
docs/PERF.md), so fusing halves the number of gather+scatter passes —
a row of 1+k floats costs about the same as a scalar. FTRL/SGD are
elementwise, so optimizing the fused table is exactly equivalent to
optimizing the two tables separately. `cfg.model.fm_fused=False` (or
passing explicit {"w","v"} tables) keeps the two-table layout for
parity experiments; both layouts compute the same math.
"""

from __future__ import annotations

import jax.numpy as jnp

from xflow_tpu.models.base import Model, register_model


def _table_specs(cfg):
    if cfg.model.fm_fused:
        return {"wv": (1 + cfg.model.v_dim,)}
    return {"w": (), "v": (cfg.model.v_dim,)}


def _second_order(vg, cfg):
    """vg: [B, F, k] masked latent gathers -> [B] second-order term."""
    if cfg.model.fm_standard:
        s = vg.sum(axis=1)  # [B, k]
        q = (vg * vg).sum(axis=1)  # [B, k]
        second = (s * s - q).sum(axis=-1)
        if cfg.model.fm_half:
            second = 0.5 * second
    else:
        # reference-coupled form: one scalar accumulator across (i, k)
        s = vg.sum(axis=(1, 2))
        q = (vg * vg).sum(axis=(1, 2))
        second = s * s - q
    return second


def stack_channels(occm_t, K):
    """[K, Np] masked rows -> [ch, Np] (w, latents, squares, zero pad to a
    sublane multiple) — the channel layout `fm_logits_from_sums` expects."""
    from xflow_tpu.ops.sorted_table import _k8

    nch = 2 * K - 1  # w + k latents + k squares
    ch = _k8(nch)  # row_sums_sorted wants a sublane multiple
    return jnp.concatenate(
        [occm_t, occm_t[1:] ** 2,
         jnp.zeros((ch - nch, occm_t.shape[1]), occm_t.dtype)],
        axis=0,
    )


def fm_logits_from_sums(sums, K, cfg):
    """[rows, ch] per-row channel sums -> [rows] logits. Shared by the
    single-device sorted path and the fullshard engine
    (parallel/sorted_fullshard.py) so the second-order math cannot drift."""
    nch = 2 * K - 1
    wx = sums[:, 0]
    s, q = sums[:, 1:K], sums[:, K:nch]  # [rows, k] each
    if cfg.model.fm_standard:
        second = (s * s - q).sum(axis=-1)
        if cfg.model.fm_half:
            second = 0.5 * second
    else:
        s_all, q_all = s.sum(axis=-1), q.sum(axis=-1)
        second = s_all * s_all - q_all
    return wx + second


def _row_side_sorted(occ_t, sorted_row, sorted_mask, rows, cfg):
    from xflow_tpu.ops.sorted_table import row_sums_sorted, wire_mask, wire_rows

    K = 1 + cfg.model.v_dim  # logical row width (storage may be packed)
    sorted_row, sorted_mask = wire_rows(sorted_row), wire_mask(sorted_mask)
    # transposed throughout: [K8, Np] keeps the minor dim wide (full lanes)
    occm_t = occ_t[:K] * sorted_mask[None, :]
    stacked = stack_channels(occm_t, K)  # [ch, Np]
    sums = row_sums_sorted(stacked, sorted_row, rows)  # [rows, ch]
    return fm_logits_from_sums(sums, K, cfg)


def _forward_sorted(tables, batch, cfg):
    """Sorted-window path (ops/sorted_table.py): occurrences arrive
    slot-sorted from the host; the table gather/scatter streams W-slot
    windows with MXU one-hot matmuls (no random HBM access at table
    scale) and per-row sums cross through small [B, k] segment arrays.
    Sorted arrays may arrive stacked [NS, Np_sub] (plan_sorted_stacked):
    the row side maps over row-contiguous sub-batches while the table
    side runs once (sorted_gather_map; FM's row state is already
    cache-resident at NS=1, so auto keeps NS=1)."""
    from xflow_tpu.ops.sorted_table import sorted_gather_map

    wv = tables["wv"]
    return sorted_gather_map(
        wv, batch, ("sorted_row", "sorted_mask"), batch["labels"].shape[0],
        lambda occ, sr, sm, rows: _row_side_sorted(occ, sr, sm, rows, cfg),
        1 + cfg.model.v_dim, cfg.data.sorted_bf16,
    )


def forward(tables, batch, cfg):
    if "sorted_slots" in batch and "wv" in tables:
        return _forward_sorted(tables, batch, cfg)
    from xflow_tpu.ops.sorted_table import batch_rows

    mask = batch["mask"]
    if "wv" in tables:
        # fused: ONE row gather for w and v (and one scatter in backward);
        # batch_rows is layout-blind and honors host dedup (data.dedup)
        wvg = batch_rows(tables["wv"], batch, 1 + cfg.model.v_dim)
        wx = (wvg[..., 0] * mask).sum(axis=-1)
        vg = wvg[..., 1:] * mask[..., None]
    else:
        w, v = tables["w"], tables["v"]
        wg = batch_rows(w, batch, 1)  # [B, F]
        wx = (wg * mask).sum(axis=-1)
        vg = batch_rows(v, batch, cfg.model.v_dim) * mask[..., None]
    return wx + _second_order(vg, cfg)


MODEL = register_model(Model(name="fm", table_specs=_table_specs, forward=forward))
