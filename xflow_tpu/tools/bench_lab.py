"""Sparse-primitive microbench lab: one harness for every hot-path probe.

The perf arc accumulated six one-off probe scripts — microbench_tpu
(raw gather/scatter/segment-sum latencies), layout_probe (carry-threaded
layout/bandwidth), mosaic_probe (Pallas DMA slice-shape compilability),
scatter_experiment (windowed-matmul scatter design), rowsum_probe
(scalar-core RMW row reduction), hostplane_bench (parse/plan host-plane
scaling) — each with its own timing harness and print-only output that
nothing consolidated or gated. This module unifies them:

- the SHARED measurement harness: `timeit_carry` (the carry-threaded
  scan pattern that defeats loop-invariant hoisting/DCE — docs/PERF.md
  "Measurement hygiene"), `timeit_scan` (the fold-into-carry scan the
  original microbench used), and `try_compile` (the Mosaic
  compilability probe), all synced by a host read of the result;
- the CORE SWEEP (`--suite core`): a deterministic matrix over
  gather / scatter-add / segment-sum x table size x nnz x dtype, each
  cell compiled through the telemetry.CompileRecorder so XLA's modeled
  flops/bytes (and the achieved bandwidth they imply) ride along, and
  emitted as ONE `BENCH_LAB.json` record that tools/perf_ledger.py
  consolidates and regression-gates — the measured baseline matrix the
  fused-Pallas-kernel milestone is judged against (ROADMAP [speed]),
  replacing docs/PERF.md's hand-derived ~11 ns/element figure with a
  cited cell;
- the six probes as SUITES (`--suite micro|layout|mosaic|scatter|
  rowsum|hostplane`): their bodies live here, and the original
  tools/*.py entry points remain as thin wrappers, so every published
  command line keeps working while the kernel arc has one entry point.

CPU-sized runs are first-class: the CI gate (tools/smoke_hotpath.sh)
sweeps small tables on the CPU backend — machine-local numbers, gated
only against their own metric names like every CPU smoke datapoint.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

CORE_OPS = ("gather", "scatter_add", "segment_sum")


# ----------------------------------------------------------- shared harness


def timeit_scan(fn, *args, iters=8, inner=4):
    """The original microbench pattern: `inner` applications inside one
    compiled lax.scan, the output folded into the carry so the loop
    cannot be elided, completion forced by a host scalar read. Beware
    the hoisting caveat (docs/PERF.md "Measurement hygiene"): fn's
    operands are loop-invariant here — prefer `timeit_carry` for ops
    XLA could hoist. Returns best seconds per application."""
    import jax

    @jax.jit
    def run(*a):
        def body(c, _):
            out = fn(*a)
            return c + out.ravel()[0].astype(np.float32), None

        c, _ = jax.lax.scan(body, np.float32(0.0), None, length=inner)
        return c

    r = run(*args)
    _ = float(r)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        _ = float(run(*args))
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def timeit_carry(step, init, iters=6, inner=4, recorder=None, name=""):
    """The hoisting-proof harness (layout_probe's): thread the state
    through the lax.scan CARRY so each iteration depends on the
    previous one — loop-invariant hoisting and DCE cannot fire — and
    force completion with a host scalar read. `step`: carry -> carry
    (same pytree structure). With a telemetry.CompileRecorder, the scan
    program compiles through it (timed compile + XLA cost analysis for
    the cell). Returns best seconds per iteration."""
    import jax

    @jax.jit
    def run(c):
        return jax.lax.scan(lambda c, _: (step(c), None), c, None, length=inner)[0]

    call = run
    if recorder is not None and name:
        compiled = recorder.record(name, run, init)
        if compiled is not None:
            call = compiled
    c = call(init)
    _ = float(jax.tree.leaves(c)[0].ravel()[0])
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        c = call(c)
        _ = float(jax.tree.leaves(c)[0].ravel()[0])
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def try_compile(name, fn, *args) -> bool:
    """Lower+compile `fn` for these args and report OK/FAIL — the
    Mosaic slice-shape compilability probe. Never raises."""
    import jax

    try:
        jax.jit(fn).lower(*args).compile()
        print(f"{name}: OK")
        return True
    except Exception as e:
        msg = str(e).split("\n")[0][:140]
        print(f"{name}: FAIL — {msg}")
        return False


# --------------------------------------------------------------- core sweep


def core_cell(op, table_log2, nnz_log2, dtype, row_width, iters, inner,
              recorder, seed=0):
    """One sweep cell: build the (seeded, deterministic) operands, time
    the op carry-threaded, and attach the CompileRecorder's cost stamps.
    The cell dict is the `cells[]` element of BENCH_LAB.json
    (docs/OBSERVABILITY.md "Sparse-primitive lab")."""
    import jax
    import jax.numpy as jnp

    if dtype not in ("f32", "bf16"):
        # a silent float32 fallback would mislabel gated baseline cells
        raise ValueError(f"dtype={dtype!r}: expected f32|bf16")
    S, N, K = 1 << table_log2, 1 << nnz_log2, int(row_width)
    jdtype = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    rng = np.random.default_rng(seed + (table_log2 << 16) + (nnz_log2 << 8))
    idx = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    tab = jnp.zeros((S, K), jdtype)
    vals = jnp.asarray(rng.normal(size=(N, K)).astype(np.float32)).astype(jdtype)
    name = f"lab_{op}_s{table_log2}_n{nnz_log2}_{dtype}"

    if op == "gather":
        # index perturbation depends on the carry scalar (always 0 in
        # practice, opaque to XLA) so the gather cannot be hoisted
        def step(c):
            t_, s = c
            i = idx + jnp.where(s > 1e30, 1, 0).astype(jnp.int32)
            return t_, s + t_[i].astype(jnp.float32).sum()

        t = timeit_carry(step, (tab, jnp.float32(0)), iters=iters,
                         inner=inner, recorder=recorder, name=name)
    elif op == "scatter_add":
        # the table IS the carry: a true sequential dependency
        t = timeit_carry(lambda t_: t_.at[idx].add(vals), tab, iters=iters,
                         inner=inner, recorder=recorder, name=name)
    elif op == "segment_sum":
        def step(c):
            bump = jnp.where(c > 1e30, 1.0, 0.0).astype(vals.dtype)
            out = jax.ops.segment_sum(vals + bump, idx, num_segments=S)
            return c + out.astype(jnp.float32).ravel()[0]

        t = timeit_carry(step, jnp.float32(0), iters=iters, inner=inner,
                         recorder=recorder, name=name)
    else:
        raise ValueError(f"op={op!r}: expected one of {CORE_OPS}")

    elements = N * K
    cell = {
        "op": op,
        "table_log2": int(table_log2),
        "nnz_log2": int(nnz_log2),
        "dtype": dtype,
        "row_width": K,
        "time_ms": round(t * 1e3, 4),
        "ns_per_element": round(t / elements * 1e9, 4),
    }
    rec = recorder.latest(name) if recorder is not None else None
    if rec:
        cell["compile_time_s"] = rec.get("compile_time_s")
        for key, per in (("flops", "flops"), ("bytes_accessed", "bytes_accessed")):
            v = rec.get(key)
            if isinstance(v, (int, float)):
                # the recorded program runs `inner` applications
                cell[per] = round(v / inner, 1)
        ba = cell.get("bytes_accessed")
        if isinstance(ba, (int, float)) and t > 0:
            cell["achieved_gbps"] = round(ba / t / 1e9, 4)
    return cell


def suite_core(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_lab --suite core",
        description="deterministic gather/scatter-add/segment-sum sweep "
        "matrix -> BENCH_LAB.json (the sparse-primitive baseline the "
        "kernel arc is measured against)",
    )
    ap.add_argument("--table-log2", default="22",
                    help="comma list of log2 table sizes (default 22)")
    ap.add_argument("--nnz-log2", default="21",
                    help="comma list of log2 occurrence counts (default 21)")
    ap.add_argument("--dtypes", default="f32",
                    help="comma list from {f32, bf16} (default f32)")
    ap.add_argument("--ops", default=",".join(CORE_OPS),
                    help=f"comma list from {CORE_OPS}")
    ap.add_argument("--row-width", type=int, default=11,
                    help="table row width K (default 11 = fused FM)")
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--inner", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--round", type=int, default=None,
                    help="trajectory round stamped into the record "
                         "(perf_ledger gates rounds)")
    ap.add_argument("--out", default="BENCH_LAB.json",
                    help="output path ('-' = stdout)")
    args = ap.parse_args(argv)

    import jax

    from xflow_tpu.telemetry import CompileRecorder, Registry

    recorder = CompileRecorder(registry=Registry())
    tables = [int(x) for x in args.table_log2.split(",") if x]
    nnzs = [int(x) for x in args.nnz_log2.split(",") if x]
    dtypes = [x.strip() for x in args.dtypes.split(",") if x.strip()]
    ops = [x.strip() for x in args.ops.split(",") if x.strip()]
    cells = []
    for op in ops:
        for tl in tables:
            for nl in nnzs:
                for dt in dtypes:
                    cell = core_cell(op, tl, nl, dt, args.row_width,
                                     args.iters, args.inner, recorder,
                                     seed=args.seed)
                    cells.append(cell)
                    print(
                        f"{op:12s} S=2^{tl:<2d} N=2^{nl:<2d} {dt:4s} "
                        f"{cell['time_ms']:10.3f} ms  "
                        f"{cell['ns_per_element']:8.3f} ns/elem"
                        + (f"  {cell['achieved_gbps']:7.2f} GB/s"
                           if "achieved_gbps" in cell else ""),
                        file=sys.stderr,
                    )
    # headline: the gather latency cell at the LARGEST swept shape —
    # the number the ledger's roofline extrapolation cites in place of
    # the hand-derived 11 ns/element (docs/PERF.md)
    heads = [c for c in cells if c["op"] == "gather" and c["dtype"] == "f32"]
    heads = heads or cells
    head = max(heads, key=lambda c: (c["table_log2"], c["nnz_log2"]))
    record = {
        "kind": "bench_lab",
        "device": str(jax.devices()[0]),
        "host_cores": os.cpu_count(),
        "metric": f"lab_{head['op']}_ns_per_element",
        "value": head["ns_per_element"],
        "unit": "ns/element",
        "headline_cell": f"lab_{head['op']}_s{head['table_log2']}"
                         f"_n{head['nnz_log2']}_{head['dtype']}",
        "row_width": args.row_width,
        "iters": args.iters,
        "inner": args.inner,
        "seed": args.seed,
        "cells": cells,
    }
    if args.round is not None:
        record["round"] = int(args.round)
    payload = json.dumps(record, indent=1)
    if args.out == "-":
        print(payload)
    else:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
        print(f"bench_lab: wrote {len(cells)} cell(s) to {args.out}",
              file=sys.stderr)
    return 0


# --------------------------------------------------- suite: micro (raw ops)


def suite_micro(argv) -> int:
    """TPU microbenchmarks for the sparse-table hot ops (docs/PERF.md
    "Round-2 microbench") — the former tools/microbench_tpu.py body."""
    import jax
    import jax.numpy as jnp

    S, N, K = 1 << 22, 1 << 21, 11  # table slots, occurrences, row width
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    idx_sorted = jnp.sort(idx)
    tab1 = jnp.zeros((S,), jnp.float32)
    tabk = jnp.zeros((S, K), jnp.float32)
    val1 = jnp.asarray(rng.normal(size=N).astype(np.float32))
    valk = jnp.asarray(rng.normal(size=(N, K)).astype(np.float32))

    res = {}
    res["gather_scalar_2M"] = timeit_scan(lambda t, i: t[i], tab1, idx)
    res["gather_rows_2M_x11"] = timeit_scan(lambda t, i: t[i], tabk, idx)
    res["scatter_add_scalar_2M"] = timeit_scan(
        lambda t, i, v: t.at[i].add(v), tab1, idx, val1
    )
    res["scatter_add_rows_2M_x11"] = timeit_scan(
        lambda t, i, v: t.at[i].add(v), tabk, idx, valk
    )
    res["scatter_add_rows_sorted"] = timeit_scan(
        lambda t, i, v: t.at[i].add(v), tabk, idx_sorted, valk
    )
    res["segment_sum_rows_to_table"] = timeit_scan(
        lambda v, i: jax.ops.segment_sum(v, i, num_segments=S), valk, idx
    )
    res["segment_sum_sorted_hint"] = timeit_scan(
        lambda v, i: jax.ops.segment_sum(v, i, num_segments=S,
                                         indices_are_sorted=True),
        valk,
        idx_sorted,
    )
    res["ftrl_elementwise_3xSxK"] = timeit_scan(lambda w, g: w + g * g, tabk, tabk)
    # dedup shape: U unique rows + re-gather occurrences from the small array
    for U_log in (17, 19):
        U = 1 << U_log
        uniq = jnp.asarray(rng.integers(0, S, U), jnp.int32)
        inv = jnp.asarray(rng.integers(0, U, N), jnp.int32)
        res[f"dedup_gather_U{U >> 10}k"] = timeit_scan(
            lambda t, u, i: t[u][i], tabk, uniq, inv
        )
        res[f"dedup_scatter_U{U >> 10}k"] = timeit_scan(
            lambda t, u, i, v: t.at[u].add(
                jax.ops.segment_sum(v, i, num_segments=U)
            ),
            tabk,
            uniq,
            inv,
            valk,
        )

    dev = jax.devices()[0]
    print(f"# device={dev}")
    for k, v in res.items():
        print(f"{k:32s} {v * 1e3:8.2f} ms")
    return 0


# ------------------------------------------------- suite: layout (carried)


def suite_layout(argv) -> int:
    """[S, k] vs flat layout/bandwidth probe, carry-threaded — the
    former tools/layout_probe.py body."""
    import jax
    import jax.numpy as jnp

    S, K, N = 1 << 22, 11, 1 << 21
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    valk = jnp.asarray(rng.normal(size=(N, K)).astype(np.float32))

    a2d = jnp.full((S, K), 1.0, jnp.float32)
    aflat = jnp.full((S * K,), 1.0, jnp.float32)
    apack = jnp.full((S * K // 128, 128), 1.0, jnp.float32)

    r = {}
    mul = lambda x: x * 1.000001 + 1e-9
    r["elementwise [4M,11]"] = timeit_carry(mul, a2d)
    r["elementwise flat 44M"] = timeit_carry(mul, aflat)
    r["elementwise [344k,128]"] = timeit_carry(mul, apack)

    # gather rows: force each iteration to depend on the previous via a
    # scalar folded into the indices (cannot be constant-folded)
    def gather_step(c):
        t, s = c
        i = idx + jnp.where(s > 1e30, 1, 0).astype(jnp.int32)
        g = t[i]
        return t, s + g.sum()

    r["gather rows [S,11]"] = timeit_carry(gather_step, (a2d, jnp.float32(0)))

    def gather_flat_step(c):
        t, s = c
        i = idx + jnp.where(s > 1e30, 1, 0).astype(jnp.int32)
        g = t.reshape(S, K)[i]
        return t, s + g.sum()

    r["gather via reshape"] = timeit_carry(gather_flat_step, (aflat, jnp.float32(0)))

    # scatter-add rows: table is the carry — true sequential dependency
    r["scatter rows [S,11]"] = timeit_carry(lambda t: t.at[idx].add(valk), a2d)
    r["scatter via reshape"] = timeit_carry(
        lambda t: t.reshape(S, K).at[idx].add(valk).reshape(S * K), aflat
    )

    # FTRL-ish update: w,n,z carried, g fixed
    def ftrl_step(c):
        w, n, z = c
        g = valk.sum() * 0 + 1e-4  # scalar, negligible
        n2 = n + g * g
        z2 = z + g - (jnp.sqrt(n2) - jnp.sqrt(n)) * 20.0 * w
        w2 = jnp.where(jnp.abs(z2) <= 5e-5, 0.0,
                       -z2 / ((1.0 + jnp.sqrt(n2)) * 20.0 + 10.0))
        return w2, n2, z2

    r["ftrl pass [4M,11]x3"] = timeit_carry(ftrl_step, (a2d, a2d * 0.5, a2d * 0.1))
    r["ftrl pass flat x3"] = timeit_carry(ftrl_step, (aflat, aflat * 0.5, aflat * 0.1))

    print(f"# device={jax.devices()[0]}  (s/iter, carry-threaded)")
    for k, v in r.items():
        print(f"{k:24s} {v * 1e3:8.2f} ms")
    return 0


# ------------------------------------------------------ suite: mosaic (DMA)


def suite_mosaic(argv) -> int:
    """Pallas/Mosaic DMA slice-shape compilability probe — the former
    tools/mosaic_probe.py body (decides the sorted-table kernel data
    layout, ops/sorted_table.py)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    W, C, K = 512, 512, 11
    S, N = 1 << 14, 1 << 13

    table = jnp.zeros((S, K), jnp.float32)
    d_t = jnp.zeros((K, N), jnp.float32)
    sl_row = jnp.zeros((1, N), jnp.int32)
    d_rows = jnp.zeros((N, K), jnp.float32)
    off = jnp.zeros((S // W + 1,), jnp.int32)

    # A: BlockSpec windowed table input
    def kern_a(off_ref, tab_ref, out_ref):
        out_ref[:, :] = tab_ref[:, :] * 2.0

    def fa(off, table):
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S // W,),
            in_specs=[pl.BlockSpec((W, K), lambda t, o: (t, 0))],
            out_specs=pl.BlockSpec((W, K), lambda t, o: (t, 0)),
        )
        return pl.pallas_call(kern_a, grid_spec=gs,
                              out_shape=jax.ShapeDtypeStruct((S, K), jnp.float32))(off, table)

    try_compile("A block (512,11) f32", fa, off, table)

    # B: DMA [K, C] col-slice of [K, N] f32 at dynamic 128-aligned offset
    def kern_b(off_ref, d_ref, out_ref, scr, sem):
        t = pl.program_id(0)
        start = (off_ref[t] // C) * C
        cp = pltpu.make_async_copy(d_ref.at[:, pl.ds(start, C)], scr, sem)
        cp.start()
        cp.wait()
        out_ref[0, 0] = scr[0, 0]

    def fb(off, d):
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(4,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            scratch_shapes=[pltpu.VMEM((K, C), jnp.float32), pltpu.SemaphoreType.DMA(())],
        )
        return pl.pallas_call(kern_b, grid_spec=gs,
                              out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32))(off, d)

    try_compile("B dma [11,512] of [11,N] f32", fb, off, d_t)

    # C: DMA [1, C] col-slice of [1, N] int32
    def kern_c(off_ref, s_ref, out_ref, scr, sem):
        t = pl.program_id(0)
        start = (off_ref[t] // C) * C
        cp = pltpu.make_async_copy(s_ref.at[:, pl.ds(start, C)], scr, sem)
        cp.start()
        cp.wait()
        out_ref[0, 0] = scr[0, 0]

    def fc(off, s):
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(4,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            scratch_shapes=[pltpu.VMEM((1, C), jnp.int32), pltpu.SemaphoreType.DMA(())],
        )
        return pl.pallas_call(kern_c, grid_spec=gs,
                              out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32))(off, s)

    try_compile("C dma [1,512] of [1,N] i32", fc, off, sl_row)

    # D: DMA [C, K] row-slice of [N, K] f32 at dynamic unaligned row offset
    def kern_d(off_ref, d_ref, out_ref, scr, sem):
        t = pl.program_id(0)
        start = off_ref[t]
        cp = pltpu.make_async_copy(d_ref.at[pl.ds(start, C), :], scr, sem)
        cp.start()
        cp.wait()
        out_ref[0, 0] = scr[0, 0]

    def fd(off, d):
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(4,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            scratch_shapes=[pltpu.VMEM((C, K), jnp.float32), pltpu.SemaphoreType.DMA(())],
        )
        return pl.pallas_call(kern_d, grid_spec=gs,
                              out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32))(off, d)

    try_compile("D dma [512,11] of [N,11] f32 dyn-row", fd, off, d_rows)

    # E: transpose cost [4M, 11] <-> [11, 4M]
    big = jnp.zeros((1 << 22, K), jnp.float32) + 1.0

    @jax.jit
    def tr(x, s):
        y = (x + s).T
        return y, y[0, 0]

    y, v = tr(big, 0.0)
    _ = float(v)
    best = 1e9
    for i in range(4):
        t0 = time.perf_counter()
        y, v = tr(big, float(i))
        _ = float(v)
        best = min(best, time.perf_counter() - t0)
    print(f"E transpose [4M,11]->[11,4M]: {best * 1e3:.1f} ms")
    return 0


# ------------------------------------------- suite: scatter (windowed plan)


def host_sort_plan(slots_flat: np.ndarray, S: int, C: int = 1024, W: int = 2048):
    """(perm [M], sorted_slots [M], bases [M//C]) — chunks grid-aligned.

    perm maps sorted position -> occurrence index (N = dummy zero row).
    The windowed-matmul scatter design probe's host planner (the former
    tools/scatter_experiment.py helper)."""
    N = slots_flat.shape[0]
    order = np.argsort(slots_flat, kind="stable")
    ss = slots_flat[order]
    win = ss // W
    # chunk boundaries: every C occurrences, or window change
    M_cap = N + (S // W + 1) * C
    perm = np.full(M_cap, N, np.int32)
    srt = np.zeros(M_cap, np.int32)
    bases = []
    pos = 0
    i = 0
    while i < N:
        w = win[i]
        j = min(N, i + C)
        # shrink to this window only
        j = i + int(np.searchsorted(win[i:j], w + 1))
        take = j - i
        perm[pos: pos + take] = order[i:j]
        srt[pos: pos + take] = ss[i:j]
        srt[pos + take: pos + C] = w * W  # dummies point in-window
        bases.append(w * W)
        pos += C
        i = j
    nchunks = len(bases)
    return (
        perm[: nchunks * C],
        srt[: nchunks * C],
        np.asarray(bases, np.int32),
    )


def suite_scatter(argv) -> int:
    """Sorted windowed-matmul scatter design probe — the former
    tools/scatter_experiment.py body (docs/PERF.md lever)."""
    import jax
    import jax.numpy as jnp

    C, W = 1024, 2048
    S, N, K = 1 << 22, 1 << 21, 11
    rng = np.random.default_rng(0)
    slots = rng.integers(0, S, N).astype(np.int32)
    d_occ = rng.normal(size=(N, K)).astype(np.float32)

    t0 = time.perf_counter()
    perm, srt, bases = host_sort_plan(slots, S, C, W)
    t_host = time.perf_counter() - t0
    nchunks = len(bases)
    print(f"host plan: {t_host * 1e3:.1f} ms, nchunks={nchunks} "
          f"(pad {nchunks * C / N:.3f}x)")

    jperm = jnp.asarray(perm)
    jsrt = jnp.asarray(srt.reshape(nchunks, C))
    jbases = jnp.asarray(bases)
    jd = jnp.asarray(d_occ)
    jslots = jnp.asarray(slots)

    def timeit(f, *a, iters=5):
        out = f(*a)
        _ = float(jax.tree.leaves(out)[0].ravel()[0])
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            out = f(*a)
            _ = float(jax.tree.leaves(out)[0].ravel()[0])
            best = min(best, time.perf_counter() - t0)
        return best

    # 1. permute gather: [M,K] from compact [N+1,K]
    @jax.jit
    def permute(d, p):
        dpad = jnp.concatenate([d, jnp.zeros((1, K), d.dtype)], 0)
        return dpad[p]

    t = timeit(permute, jd, jperm)
    print(f"permute gather [{len(perm)},{K}]: {t * 1e3:7.1f} ms")

    # 2. windowed matmul scatter via scan
    @jax.jit
    def windowed_scatter(d, p, srt2d, bases1d):
        dpad = jnp.concatenate([d, jnp.zeros((1, K), d.dtype)], 0)
        ds = dpad[p].reshape(nchunks, C, K)

        def body(tab, xs):
            dch, sch, base = xs
            onehot = (sch[:, None] == base + jax.lax.broadcasted_iota(
                jnp.int32, (C, W), 1)).astype(jnp.float32)
            upd = jax.lax.dot_general(
                onehot, dch, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [W, K]
            win = jax.lax.dynamic_slice(tab, (base, 0), (W, K))
            return jax.lax.dynamic_update_slice(tab, win + upd, (base, 0)), None

        tab = jnp.zeros((S, K), jnp.float32)
        tab, _ = jax.lax.scan(body, tab, (ds, srt2d, bases1d))
        return tab

    t = timeit(windowed_scatter, jd, jperm, jsrt, jbases)
    print(f"windowed scatter e2e   : {t * 1e3:7.1f} ms")

    # 3. XLA scatter baseline + equality
    @jax.jit
    def xla_scatter(d, s):
        return jnp.zeros((S, K), jnp.float32).at[s].add(d)

    t = timeit(xla_scatter, jd, jslots)
    print(f"xla scatter-add        : {t * 1e3:7.1f} ms")

    a = np.asarray(windowed_scatter(jd, jperm, jsrt, jbases))
    b = np.asarray(xla_scatter(jd, jslots))
    err = np.max(np.abs(a - b))
    print(f"max |windowed - xla|   : {err:.3e}")
    return 0


# --------------------------------------------- suite: rowsum (scalar RMW)


def suite_rowsum(argv) -> int:
    """Pallas scalar-core row-reduction probe — the former
    tools/rowsum_probe.py body (docs/PERF.md "row-reduction kernel")."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = 65536
    CH = 24  # padded channel count (21 used)
    C = 512  # chunk
    Np = 2098176  # padded_len(65536*32)
    K = 4  # batches in the scan

    rng = np.random.default_rng(0)
    rows = rng.integers(0, B, (K, Np)).astype(np.int32)
    vals = rng.normal(size=(K, CH, Np)).astype(np.float32)

    n_chunks = Np // C

    def kernel(rows_ref, vals_ref, out_ref, acc2, vchunk, vt_ref, rchunk,
               sem_v, sem_r):
        out_ref[:, :] = jnp.zeros((B, CH), jnp.float32)
        acc2[:, :] = jnp.zeros((B, CH), jnp.float32)

        def chunk_step(c, carry):
            o = c * C
            cp_r = pltpu.make_async_copy(rows_ref.at[:, pl.ds(o, C)], rchunk, sem_r)
            cp_r.start()
            cp_v = pltpu.make_async_copy(vals_ref.at[:, pl.ds(o, C)], vchunk, sem_v)
            cp_v.start()
            cp_r.wait()
            cp_v.wait()
            vt_ref[:, :] = vchunk[:, :].T  # [C, CH] staged for row reads

            def inner(i, carry2):
                r0 = rchunk[0, 2 * i]
                r1 = rchunk[0, 2 * i + 1]
                out_ref[pl.ds(r0, 1), :] += vt_ref[pl.ds(2 * i, 1), :]
                acc2[pl.ds(r1, 1), :] += vt_ref[pl.ds(2 * i + 1, 1), :]
                return carry2

            jax.lax.fori_loop(0, C // 2, inner, 0)
            return carry

        jax.lax.fori_loop(0, n_chunks, chunk_step, 0)
        out_ref[:, :] += acc2[:, :]

    def rowsum_pallas(rows1, vals1):
        return pl.pallas_call(
            kernel,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((B, CH), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, CH), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((B, CH), jnp.float32),
                pltpu.VMEM((CH, C), jnp.float32),
                pltpu.VMEM((C, CH), jnp.float32),
                pltpu.SMEM((1, C), jnp.int32),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ],
        )(rows1.reshape(1, Np), vals1)

    # correctness on a small case first (interpret on CPU would be slow;
    # run tiny on device)
    try:
        jit_rowsum = jax.jit(rowsum_pallas)
        small_out = jit_rowsum(jnp.asarray(rows[0]), jnp.asarray(vals[0]))
        got = np.asarray(small_out)
    except Exception as e:
        print(f"COMPILE/RUN FAIL: {str(e).splitlines()[0][:300]}")
        return 1
    want = np.zeros((B, CH), np.float32)
    np.add.at(want, rows[0], vals[0].T)
    err = np.abs(got - want).max()
    print(f"correctness: max abs err = {err:.2e}")

    @jax.jit
    def run_pallas(rows, vals):
        def body(c, b):
            out = rowsum_pallas(b[0], b[1])
            return c + out[::97, 0].sum() + out[::89, 5].sum(), None

        return jax.lax.scan(body, 0.0, (rows, vals))[0]

    @jax.jit
    def run_xla(rows, vals):
        def body(c, b):
            out = jax.ops.segment_sum(b[1].T, b[0], num_segments=B)
            return c + out[::97, 0].sum() + out[::89, 5].sum(), None

        return jax.lax.scan(body, 0.0, (rows, vals))[0]

    jrows, jvals = jnp.asarray(rows), jnp.asarray(vals)
    for name, fn in [("pallas scalar-RMW", run_pallas), ("xla segment_sum", run_xla)]:
        out = fn(jrows, jvals)
        _ = float(out)
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            out = fn(jrows, jvals)
            _ = float(out)
            best = min(best, (time.perf_counter() - t0) / K)
        print(f"{name}: {best * 1e3:.1f} ms ({best / Np * 1e9:.2f} ns/occurrence)")
    return 0


# ---------------------------------------------- suite: hostplane (CPU side)


def _hostplane_bench_parse(path: str, caps, cfg) -> dict:
    from xflow_tpu.config import override
    from xflow_tpu.data.pipeline import batch_iterator

    out = {}
    for cap in caps:
        c = override(cfg, **{"data.parser_threads": cap})
        # warm (page cache + pool spin-up)
        for _ in batch_iterator(path, c.data):
            pass
        t0 = time.perf_counter()
        n = 0
        for b in batch_iterator(path, c.data):
            n += b.num_rows
        dt = time.perf_counter() - t0
        out[f"parse_rows_per_sec_{cap}w"] = round(n / dt, 1)
    return out


def _hostplane_bench_plan(caps, batch: int, nnz: int, log2_slots: int,
                          num_sub: int) -> dict:
    from concurrent.futures import ThreadPoolExecutor

    from xflow_tpu.data.native import native_plan_sorted
    from xflow_tpu.ops.sorted_table import WINDOW, padded_len

    S = 1 << log2_slots
    rng = np.random.default_rng(0)
    bs = batch // num_sub
    subs = [
        np.ascontiguousarray(rng.integers(0, S, (bs, nnz)).astype(np.int32))
        for _ in range(num_sub)
    ]
    mask = np.ones((bs, nnz), np.float32)

    def one(i):
        return native_plan_sorted(subs[i], mask, None, S, WINDOW, padded_len(bs * nnz))

    out = {}
    for cap in caps:
        with ThreadPoolExecutor(max_workers=cap) as pool:
            list(pool.map(one, range(num_sub)))  # warm
            t0 = time.perf_counter()
            reps = 5
            for _ in range(reps):
                list(pool.map(one, range(num_sub)))
            dt = (time.perf_counter() - t0) / reps
        out[f"plan_rows_per_sec_{cap}w"] = round(batch / dt, 1)
    return out


def suite_hostplane(argv) -> int:
    """Host data-plane scaling harness — the former
    tools/hostplane_bench.py body (per-core parse/plan rates and the
    1/2/4-worker scaling curve; docs/PERF.md "Host data plane")."""
    import tempfile

    ap = argparse.ArgumentParser(prog="bench_lab --suite hostplane")
    ap.add_argument("--rows", type=int, default=500_000)
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--nnz", type=int, default=18)
    ap.add_argument("--log2-slots", type=int, default=22)
    ap.add_argument("--num-sub", type=int, default=8,
                    help="concurrent sub-batch plans (the trainer's "
                         "parallelism unit)")
    ap.add_argument("--caps", default="1,2,4")
    args = ap.parse_args(argv)

    from xflow_tpu.config import Config, override
    from xflow_tpu.data.synth import generate_shards_bulk

    caps = [int(c) for c in args.caps.split(",")]
    record = {"host_cores": os.cpu_count()}
    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "t")
        generate_shards_bulk(prefix, 1, args.rows, num_fields=args.nnz,
                             ids_per_field=200_000, seed=0)
        cfg = override(
            Config(),
            **{"data.batch_size": args.batch, "data.max_nnz": args.nnz,
               "data.log2_slots": args.log2_slots,
               "model.num_fields": args.nnz},
        )
        record.update(_hostplane_bench_parse(prefix + "-00000", caps, cfg))
    record.update(
        _hostplane_bench_plan(caps, args.batch, args.nnz, args.log2_slots,
                              args.num_sub)
    )
    print(json.dumps(record))
    return 0


# -------------------------------------------------------------------- main


SUITES = {
    "core": suite_core,
    "micro": suite_micro,
    "layout": suite_layout,
    "mosaic": suite_mosaic,
    "scatter": suite_scatter,
    "rowsum": suite_rowsum,
    "hostplane": suite_hostplane,
}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(
        description="sparse-primitive microbench lab: the unified probe "
        "harness (docs/PERF.md, docs/OBSERVABILITY.md \"Sparse-primitive "
        "lab\")"
    )
    ap.add_argument("--suite", default="core", choices=sorted(SUITES),
                    help="which probe suite to run (default: the core "
                         "sweep matrix -> BENCH_LAB.json)")
    args, rest = ap.parse_known_args(argv)
    return int(SUITES[args.suite](rest) or 0)


if __name__ == "__main__":
    sys.exit(main())
