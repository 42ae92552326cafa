"""On-device parity gate for the sorted-window Pallas kernels.

Round 2's silent-MXU-bf16 bug (docs/CHANGES_R2.md "Precision
integrity") is the class of regression CPU / interpret-mode tests are
structurally blind to: the kernels are only *lowered through Mosaic* on
a real chip, and the MXU's default operand rounding only exists there.
This module re-checks, on whatever backend is live:

- `table_gather_sorted` (single-stream, multi-buffer, and single-stream
  over the buffers merged on the device as the fullshard step merges
  them) is BIT-exact against the XLA gather oracle — the 3-term bf16 decomposition's
  selection property (`_dot_f32`), not a tolerance;
- the windowed scatter VJPs match `jax.ops.segment_sum` within the
  reduction-reorder class (≤ ~1 ulp per accumulated term);
- `row_sums_sorted`'s scalar-core RMW matches segment_sum likewise;
- the opt-in bf16 fast mode is *approximately* right (2^-7 rel) — it
  must stay a rounding trade, never a wrong-window bug.

Run by `bench.py` on the real chip (BENCH_r*.json carries a
`kernel_parity` field) and by `tests/test_kernel_parity_tpu.py`, which
auto-skips off-TPU (the pytest conftest pins CPU; set
`XFLOW_TEST_PLATFORM=tpu` on a TPU host to include it).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def _rel_err(a: np.ndarray, b: np.ndarray, floor: float = 1e-30) -> float:
    """Max ELEMENTWISE relative error: with the table's deliberately huge
    dynamic range, a global-max denominator would hide wrong values on
    small-magnitude entries entirely. `floor` is the absolute scale
    below which differences count as absolute, not relative — reduction
    checks need it because a slot whose unit-scale terms cancel to ~0
    has unbounded *relative* reorder noise while a wrong-routing bug
    still moves O(1) mass (err >= ~1 >> any tolerance here)."""
    return float(np.max(np.abs(a - b) / (np.abs(b) + floor)))


def check_kernel_parity(
    log2_slots: int = 15,
    n_occ: int = 1 << 17,
    k: int = 11,
    batch: int = 4096,
    seed: int = 0,
    window_stride: int = 1,
) -> dict:
    """Returns {"ok": bool, "checks": {name: max_rel_err}, "backend": str}.

    Gather checks require rel err == 0.0 (bit-exact); scatter/rowsum
    allow 1e-4 over a 1e-2 floor (f32 reduction reorder on unit-scale
    terms); bf16 mode allows 2^-7. The default shape puts 16 chunks in
    a window; `CASES` adds the benchmark cells' regime, a span shorter
    than a chunk (the kernels' chunk chain then runs across grid steps,
    ops/sorted_table.py `_gather_span`), and with `window_stride` > 1
    only every stride-th window holds occurrences: the chain crosses
    runs of empty windows, the first and (but for the pads) the last.
    """
    from xflow_tpu.ops.sorted_table import (
        _gather_xla,
        _k8,
        CHUNK,
        WINDOW,
        plan_sorted_batch,
        row_sums_sorted,
        table_gather_sorted,
        table_gather_sorted_multi,
    )

    rng = np.random.default_rng(seed)
    S = 1 << log2_slots
    nnz = n_occ // batch
    slots = rng.integers(0, S, (batch, nnz)).astype(np.int32)
    if window_stride > 1:
        live = np.arange(1 % window_stride, S // WINDOW, window_stride)
        slots = (rng.choice(live, slots.shape) * WINDOW + slots % WINDOW).astype(np.int32)
    mask = (rng.random((batch, nnz)) < 0.9).astype(np.float32)
    table = rng.standard_normal((S, k)).astype(np.float32)
    # exercise the full f32 mantissa: values whose hi/mid/lo bf16 terms
    # are all nonzero, plus denormal-adjacent magnitudes
    table *= np.exp(rng.uniform(-8, 8, (S, 1))).astype(np.float32)
    plan = plan_sorted_batch(slots, mask, S)
    Np = plan.sorted_slots.shape[0]
    checks: dict[str, float] = {}

    tbl = jnp.asarray(table)
    ss = jnp.asarray(plan.sorted_slots)
    wo = jnp.asarray(plan.win_off)

    # --- gather: bit-exact vs the XLA oracle on the same device
    got = np.asarray(jax.jit(lambda t, s, w: table_gather_sorted(t, s, w, False))(tbl, ss, wo))
    want = np.asarray(jax.jit(_gather_xla)(tbl, ss, wo))
    checks["gather_exact"] = _rel_err(got, want)

    # --- gather, bf16 opt-in: a rounding trade, not a routing bug
    got16 = np.asarray(jax.jit(lambda t, s, w: table_gather_sorted(t, s, w, True))(tbl, ss, wo))
    checks["gather_bf16"] = _rel_err(got16, want)

    # --- scatter (the gather VJP): reduction-reorder class vs segment_sum
    d_occ = rng.standard_normal((_k8(k), Np)).astype(np.float32)
    d_occ *= np.asarray(plan.sorted_mask)[None, :]

    def scat(t, s, w, d):
        _, vjp = jax.vjp(lambda tt: table_gather_sorted(tt, s, w, False), t)
        return vjp(d)[0]

    got_s = np.asarray(jax.jit(scat)(tbl, ss, wo, jnp.asarray(d_occ)))
    want_s = np.asarray(
        jax.jit(
            lambda d, s: jax.ops.segment_sum(d[:k].T, s, num_segments=S)
        )(jnp.asarray(d_occ), ss)
    )
    checks["scatter_exact"] = _rel_err(got_s, want_s, floor=1e-2)

    # --- multi-buffer gather/scatter (stacked sub-batch plans): split the
    # sorted stream in two, pad each buffer to a fixed capacity with
    # slot S-1 per the host contract (each half of a sorted stream is
    # itself sorted, so no re-sort is needed)
    cap = ((Np // 2) // CHUNK + 1) * CHUNK
    bufs, offs = [], []
    split = (Np // 2 // CHUNK) * CHUNK
    for part in (np.asarray(plan.sorted_slots)[:split],
                 np.asarray(plan.sorted_slots)[split:]):
        pad = np.full(cap - part.size, S - 1, np.int32)
        buf = np.concatenate([part.astype(np.int32), pad])
        off = np.searchsorted(buf, np.arange(0, S + 1, WINDOW)).astype(np.int32)
        off[-1] = cap  # pads ride in the last window
        bufs.append(buf)
        offs.append(off)
    mslots = jnp.asarray(np.concatenate(bufs))
    moff = jnp.asarray(np.stack(offs))
    got_m = np.asarray(
        jax.jit(lambda t, s, o: table_gather_sorted_multi(t, s, o, False))(tbl, mslots, moff)
    )
    want_m = np.asarray(jax.jit(_gather_xla)(tbl, mslots, jnp.zeros((1,), jnp.int32)))
    checks["gather_multi_exact"] = _rel_err(got_m, want_m)

    d_m = rng.standard_normal(got_m.shape).astype(np.float32)

    def scat_m(t, s, o, d):
        _, vjp = jax.vjp(lambda tt: table_gather_sorted_multi(tt, s, o, False), t)
        return vjp(d)[0]

    got_ms = np.asarray(jax.jit(scat_m)(tbl, mslots, moff, jnp.asarray(d_m)))
    want_ms = np.asarray(
        jax.jit(
            lambda d, s: jax.ops.segment_sum(d[:k].T, s, num_segments=S)
        )(jnp.asarray(d_m), mslots)
    )
    checks["scatter_multi_exact"] = _rel_err(got_ms, want_ms, floor=1e-2)

    # --- the fullshard step's stream: the same buffers merged into ONE
    # slot-sorted stream on the device (parallel/sorted_fullshard.py
    # merge_received: a sort keyed on the slot, the summed offsets) and
    # run through the single-stream kernels — bit-exact against the XLA
    # gather at the merged positions AND against the multi-buffer kernel
    # at the positions the merge took them from
    from xflow_tpu.parallel.sorted_fullshard import merge_received

    g_slots, g_off, g_perm = jax.jit(merge_received)(
        mslots.reshape(2, cap), moff, jnp.arange(2 * cap, dtype=jnp.int32).reshape(2, cap)
    )
    got_g = np.asarray(
        jax.jit(lambda t, s, w: table_gather_sorted(t, s, w, False))(tbl, g_slots, g_off)
    )
    want_g = np.asarray(jax.jit(_gather_xla)(tbl, g_slots, g_off))
    checks["gather_merged_exact"] = max(
        _rel_err(got_g, want_g), _rel_err(got_g, got_m[:, np.asarray(g_perm)])
    )
    d_g = jnp.asarray(d_m[:, np.asarray(g_perm)])
    got_gs = np.asarray(jax.jit(scat)(tbl, g_slots, g_off, d_g))
    checks["scatter_merged_exact"] = _rel_err(got_gs, want_ms, floor=1e-2)

    # --- packed storage ([S/8, 8K], pack_table): gather BIT-exact vs
    # the logical-layout kernel, scatter equal to the packed logical
    # gradient — the packed one-hot + static sub-row select must not
    # change a single bit of what the MXU produces
    from xflow_tpu.ops.sorted_table import pack_table, unpack_table

    tbl_p = jnp.asarray(pack_table(table))
    got_p = np.asarray(
        jax.jit(lambda t, s, w: table_gather_sorted(t, s, w, False, 8))(tbl_p, ss, wo)
    )
    checks["gather_packed"] = _rel_err(got_p, got)

    def scat_p(t, s, w, d):
        _, vjp = jax.vjp(lambda tt: table_gather_sorted(tt, s, w, False, 8), t)
        return vjp(d)[0]

    got_ps = np.asarray(jax.jit(scat_p)(tbl_p, ss, wo, jnp.asarray(d_occ)))
    checks["scatter_packed"] = _rel_err(
        unpack_table(got_ps, k), got_s, floor=1e-2
    )

    # --- sublane-ALIGNED row width (K8 == K): the kernels' pad-to-K8
    # blend has no pad rows here, a branch Mosaic only sees at aligned
    # widths (a zero-row pad array failed to compile for every
    # 8-multiple K until round 4 — FFM/MVM widths like 96 or 128 hit it)
    k_al = 16
    tbl_al = jnp.asarray(
        pack_table(rng.standard_normal((S, k_al)).astype(np.float32))
    )
    got_al = np.asarray(
        jax.jit(lambda t, s, w: table_gather_sorted(t, s, w, False, 8))(
            tbl_al, ss, wo
        )
    )
    want_al = np.asarray(jax.jit(lambda t, s: _gather_xla(t, s, None, 8))(tbl_al, ss))
    checks["gather_aligned_k"] = _rel_err(got_al, want_al)

    def scat_al(t, s, w, d):
        _, vjp = jax.vjp(lambda tt: table_gather_sorted(tt, s, w, False, 8), t)
        return vjp(d)[0]

    d_al = (rng.standard_normal(got_al.shape).astype(np.float32)
            * np.asarray(plan.sorted_mask)[None, :])
    got_als = np.asarray(jax.jit(scat_al)(tbl_al, ss, wo, jnp.asarray(d_al)))
    want_als = np.asarray(
        jax.jit(
            lambda d, s: jax.ops.segment_sum(d.T, s, num_segments=S)
        )(jnp.asarray(d_al[:k_al]), ss)
    )
    # compare in the packed layout the kernel writes
    checks["scatter_aligned_k"] = _rel_err(
        unpack_table(got_als, k_al), want_als, floor=1e-2
    )

    # --- fused scatter+FTRL (optim.fused_scatter): the Pallas window
    # pass that applies the optimizer at the gradient block's write
    # point must match the two-pass composition (XLA scatter + dense
    # _update_one) it replaces — w through the soft-threshold, n, z
    from xflow_tpu.config import FTRLConfig
    from xflow_tpu.ops.sorted_table import _scatter_xla, scatter_ftrl_sorted
    from xflow_tpu.optim.ftrl import _update_one

    hp = FTRLConfig()
    w0_l = rng.standard_normal((S, k)).astype(np.float32) * 0.01
    n0_l = np.abs(rng.standard_normal((S, k))).astype(np.float32) * 0.1
    z0_l = rng.standard_normal((S, k)).astype(np.float32) * 1e-4
    # exercise the lazy-init guard (g==0 ∧ n==0 keeps w) on device: the
    # upper half of the table gets NO gradient (its occurrences' d
    # columns zeroed — scatter of exact zeros) and zero n/z state, so
    # without the guard the closed form would zero those w's; the fused
    # kernel must keep the inits bitwise like the two-pass reference
    n0_l[S // 2:] = 0.0
    z0_l[S // 2:] = 0.0
    w0 = pack_table(w0_l)
    n0 = pack_table(n0_l)
    z0 = pack_table(z0_l)
    d_f = (rng.standard_normal((_k8(k), Np)).astype(np.float32)
           * np.asarray(plan.sorted_mask)[None, :]
           * (np.asarray(plan.sorted_slots) < S // 2)[None, :])
    # the DISPATCHING wrapper: Pallas on TPU, the two-pass composition
    # elsewhere — so this gate keeps running (trivially) off-TPU, per
    # the module contract
    fused = jax.jit(
        lambda d, s, w_, n_, z_: scatter_ftrl_sorted(
            d, s, wo, w_, n_, z_, k, hp, False, 8
        )
    )
    got_f = fused(jnp.asarray(d_f), ss, jnp.asarray(w0), jnp.asarray(n0), jnp.asarray(z0))
    g_ref = jax.jit(
        lambda d, s: _scatter_xla(d, s, None, S, k, 8)
    )(jnp.asarray(d_f), ss)
    want_f = jax.jit(
        lambda w_, n_, z_, g: _update_one(
            w_, n_, z_, g, hp.alpha, hp.beta, hp.lambda1, hp.lambda2
        )
    )(jnp.asarray(w0), jnp.asarray(n0), jnp.asarray(z0), g_ref)
    for i, name in ((0, "scatter_ftrl_w"), (1, "scatter_ftrl_n"), (2, "scatter_ftrl_z")):
        checks[name] = _rel_err(
            np.asarray(got_f[i]), np.asarray(want_f[i]), floor=1e-4
        )

    # --- a zero cotangent is the identity: the non-finite guard
    # (train/step.py guard_nonfinite) discards a bad step by handing this
    # kernel zeros, and keeps no copy of the old state. Applied to a
    # state the kernel itself wrote (so every stored w is f(z, n) or a
    # never-touched init), the window pass must return w, n, z unchanged
    # in value, exactly
    again = fused(jnp.zeros(d_f.shape, jnp.float32), ss, *got_f)
    checks["scatter_ftrl_zero_grad"] = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(again, got_f)
    )

    # --- row-sum kernel (the FM forward's occurrence->row reduction)
    ch = 24
    vals_t = (rng.standard_normal((ch, Np)).astype(np.float32)
              * np.asarray(plan.sorted_mask)[None, :])
    rows = jnp.asarray(plan.sorted_row)
    got_r = np.asarray(
        jax.jit(lambda v, r: row_sums_sorted(v, r, batch))(jnp.asarray(vals_t), rows)
    )
    want_r = np.asarray(
        jax.jit(lambda v, r: jax.ops.segment_sum(v.T, r, num_segments=batch))(
            jnp.asarray(vals_t), rows
        )
    )
    checks["rowsum"] = _rel_err(got_r, want_r, floor=1e-2)

    tol = {
        "gather_exact": 0.0,
        "gather_multi_exact": 0.0,
        "gather_merged_exact": 0.0,
        "gather_bf16": 2.0 ** -7,
        # scatters sum duplicate-slot terms in kernel order, segment_sum
        # in its own — absolute reorder noise is ~1e-6 on unit-scale
        # terms (measured on-device); with the 1e-2 floor that reads as
        # <=1e-4, while a routing bug moves O(1) mass (err >= ~1)
        "scatter_exact": 1e-4,
        "scatter_multi_exact": 1e-4,
        "scatter_merged_exact": 1e-4,
        "gather_packed": 0.0,
        "scatter_packed": 1e-4,
        "gather_aligned_k": 0.0,
        "scatter_aligned_k": 1e-4,
        # gradient reorder noise (scatter class) flows through FTRL's
        # sqrt/divide; same tolerance class as the plain scatters
        "scatter_ftrl_w": 1e-3,
        "scatter_ftrl_n": 1e-3,
        "scatter_ftrl_z": 1e-3,
        "scatter_ftrl_zero_grad": 0.0,
        "rowsum": 1e-4,
    }
    ok = all(checks[name] <= tol[name] for name in tol)
    return {"ok": ok, "checks": checks, "backend": jax.default_backend()}


# what `main` runs beside the default shape, as <suffix of the check's
# name>: <arguments>. 2^22 slots x 2^17 occurrences is 64 occurrences a
# window, eight windows a chunk
CASES = {
    "@s22": {"log2_slots": 22},
    "@s22_holes": {"log2_slots": 22, "window_stride": 4},
}


def main() -> int:
    import json
    import sys

    res = check_kernel_parity()
    for suffix, kwargs in CASES.items():
        more = check_kernel_parity(**kwargs)
        res["ok"] = res["ok"] and more["ok"]
        res["checks"].update({name + suffix: v for name, v in more["checks"].items()})
    if res["backend"] != "tpu":
        # every check would trivially compare the XLA path against
        # itself — "ok" here would be a false all-clear
        print(f"kernel_parity: backend is {res['backend']}, not tpu — "
              "the Pallas kernels were never executed", file=sys.stderr)
        print(json.dumps({**res, "ok": False, "error": "not on tpu"}))
        return 2
    print(json.dumps(res))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
