"""Headline benchmark: sparse-LR train-step throughput (examples/sec).

BASELINE.md: the reference publishes no numbers; the north star is
Criteo-1TB LR on v5e-64 at ≥50M examples/sec/pod ⇒ ~781k ex/s/chip.
`vs_baseline` reports this chip's throughput against that per-chip
share (value 1.0 = on track for the pod target).

Measurement: K train steps run inside ONE compiled program
(`lax.scan` over K pre-staged device batches) and completion is forced
by a host value read, so the figure is the device's step and not the
host's per-dispatch overhead.

Prints ONE JSON line:
  {"metric": "lr_examples_per_sec", "value": N, "unit": "examples/sec",
   "vs_baseline": N}
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

PER_CHIP_TARGET = 50_000_000 / 64  # north-star pod target / chips


def stage_row_batches(rng, num_slots: int, num_fields: int, K: int, B: int,
                      F: int, with_slots: bool = True,
                      with_fields: bool = True) -> dict:
    """Host-staged [K, B, F] row-major batch arrays — the pre-staged
    device-bench harness shape, shared with tools/step_decompose.py so
    the two harnesses measure the same data distribution. The flags
    skip draws a caller replaces anyway (generating ~64 MB at the CLI
    shape only to throw it away): bench's main() takes slots
    per-distribution from `draw_slots` (zipf/uniform), and the MVM/FFM
    exclusive-fields shape uses one feature per field."""
    out = {}
    if with_slots:
        out["slots"] = rng.integers(0, num_slots, (K, B, F)).astype(np.int32)
    if with_fields:
        out["fields"] = rng.integers(0, num_fields, (K, B, F)).astype(np.int32)
    out.update({
        "mask": (rng.random((K, B, F)) < 0.6).astype(np.float32),
        "labels": (rng.random((K, B)) < 0.4).astype(np.float32),
        "row_mask": np.ones((K, B), np.float32),
    })
    return out


def measure_e2e(args, model: str, rows: int, use_cache: bool = False) -> float:
    """End-to-end trainer throughput: libffm file on disk → C++ parser →
    (sorted plan in the prefetch thread) → jitted device step. This is
    the number a user actually gets from `xflow train`, as opposed to
    the pre-staged device-only headline — the gap between them is the
    host data plane (docs/PERF.md "Host data plane"). Epoch 1 warms the
    compile caches; epoch 2 is timed. Returns examples/sec.

    `use_cache` packs the generated shard into the binary shard cache
    first (data/shardcache.py — hash at convert time, mmap zero-copy
    batches) and trains with data.cache=on: the parse/hash-free e2e
    figure, paired with the text number as the measured host gap."""
    import os
    import tempfile
    import time as _time

    from xflow_tpu.config import Config, override
    from xflow_tpu.data.synth import generate_shards_bulk
    from xflow_tpu.train.trainer import Trainer

    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "train")
        t0 = _time.perf_counter()
        generate_shards_bulk(prefix, 1, rows, num_fields=18,
                             ids_per_field=200_000, seed=0)
        gen_s = _time.perf_counter() - t0
        cfg = override(
            Config(),
            **{
                "model.name": model,
                "data.train_path": prefix,
                "data.log2_slots": args.log2_slots if not args.smoke else 16,
                # synth emits exactly one feature per field: size the padded
                # capacity to the data (a user would do the same) instead of
                # carrying 14 dead masked columns per row through the host
                # sort, the transfer, and the kernels
                "data.max_nnz": 18,
                "data.sorted_bf16": args.sorted_bf16,
                "data.batch_size": args.batch if not args.smoke else 2048,
                "data.sorted_sub_batches": args.sub_batches,
                "model.num_fields": 18,
                "train.epochs": 1,
                "train.pred_dump": False,
                "data.cache": "on" if use_cache else "off",
            },
        )
        if use_cache:
            from xflow_tpu.data.shardcache import build_cache

            t0 = _time.perf_counter()
            built = build_cache(prefix, cfg.data)
            print(
                f"# e2e[{model}]: cache build {built['rows']} rows "
                f"({built['bytes']} bytes) in "
                f"{_time.perf_counter() - t0:.1f}s",
                file=sys.stderr,
            )
        trainer = Trainer(cfg)
        res_warm = trainer.fit()  # epoch 1: compile + first pass
        t0 = _time.perf_counter()
        res = trainer.fit()  # timed epoch (fresh pass over the file)
        secs = _time.perf_counter() - t0
        rate = res.examples / secs
        print(
            f"# e2e[{model}]: rows={rows} gen={gen_s:.1f}s warm={res_warm.seconds:.1f}s "
            f"timed_epoch={secs:.2f}s steps={res.steps} engine={trainer.engine} "
            f"parser_threads=auto({os.cpu_count()} cores)",
            file=sys.stderr,
        )
        return rate


def bench_e2e(args) -> int:
    model = "fm" if args.model in ("all", "fm") else args.model
    rows = args.e2e_rows if not args.smoke else 20_000
    rate = measure_e2e(args, model, rows)
    rec = {
        "metric": f"e2e_{model}_examples_per_sec",
        "value": round(rate, 1),
        "unit": "examples/sec",
        "vs_baseline": round(rate / PER_CHIP_TARGET, 3),
        # wall clock for trajectory correlation only; every
        # duration above comes from time.perf_counter()
        "ts": round(time.time(), 3),
    }
    if args.e2e_cache:
        # the packed-shard-cache leg of the same workload: its
        # `_examples_per_sec` suffix makes it its own gated
        # perf_ledger group, and the speedup is the measured host gap
        cached = measure_e2e(args, model, rows, use_cache=True)
        rec[f"e2e_{model}_cached_examples_per_sec"] = round(cached, 1)
        rec["cache_speedup"] = round(cached / rate, 3) if rate > 0 else None
    print(json.dumps(rec))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--nnz", type=int, default=32)
    ap.add_argument("--log2-slots", type=int, default=22)
    ap.add_argument("--scan-steps", type=int, default=32, help="train steps per compiled program")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--model", default="all",
                    help="lr|fm|mvm|ffm|all (all = one JSON line, LR headline)")
    ap.add_argument("--smoke", action="store_true", help="tiny shapes for CI")
    ap.add_argument("--no-sorted", action="store_true",
                    help="disable the sorted-window layout (FM and MVM; ops/sorted_table.py)")
    ap.add_argument("--dedup", action="store_true",
                    help="host-dedup the row-major batches (unique_slots + "
                         "inverse; measures docs/PERF.md lever 4 on the "
                         "GSPMD-path step)")
    ap.add_argument("--sub-batches", type=int, default=0,
                    help="sorted-layout sub-batches per step (0 = auto)")
    ap.add_argument("--no-zipf", action="store_true",
                    help="skip the skewed-slot (Zipf) companion runs")
    ap.add_argument("--sorted-bf16", action="store_true",
                    help="bf16 fast mode for the sorted kernels (cfg.data.sorted_bf16)")
    ap.add_argument("--e2e", action="store_true",
                    help="end-to-end pipeline bench (file -> C++ parser -> "
                         "sorted plan -> device) instead of pre-staged batches")
    ap.add_argument("--e2e-rows", type=int, default=1_000_000)
    ap.add_argument("--e2e-cache", action="store_true",
                    help="with --e2e: also measure the packed-shard-cache "
                         "leg of the same workload (data/shardcache.py) — "
                         "the record gains e2e_<model>_cached_examples_per_sec "
                         "+ cache_speedup")
    args = ap.parse_args()
    if args.smoke:
        args.batch, args.log2_slots, args.scan_steps, args.repeats = 2048, 16, 4, 2

    import os

    if os.environ.get("JAX_PLATFORMS"):
        # ambient site config may pin another platform; env takes priority
        import jax

        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

    import jax
    import jax.numpy as jnp

    from xflow_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    from xflow_tpu.config import Config, override
    from xflow_tpu.models import get_model
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.train.state import init_state
    from xflow_tpu.train.step import make_train_step

    K, B, F = args.scan_steps, args.batch, args.nnz
    rng = np.random.default_rng(0)

    def draw_slots(num_slots: int, dist: str, shape=None) -> np.ndarray:
        """[K, B, F] slot ids. 'zipf' draws ranks from a bounded power law
        (alpha=1.05, Criteo-like head) and scrambles them with a
        multiplicative bijection mod 2^k so frequency skew survives but
        index locality (an artifact no hashed id stream has) does not."""
        shape = shape or (K, B, F)
        if dist == "uniform":
            return rng.integers(0, num_slots, shape).astype(np.int32)
        pmf = 1.0 / np.arange(1, num_slots + 1, dtype=np.float64) ** 1.05
        cdf = np.cumsum(pmf / pmf.sum())
        ranks = np.searchsorted(cdf, rng.random(shape))
        return ((ranks * 2654435761) % num_slots).astype(np.int32)

    if args.e2e:
        return bench_e2e(args)

    zipf_slots_cache = {}
    # compile accounting (telemetry.CompileRecorder): each model's
    # K-step program stamps its compile time and cost analysis; the
    # headline's lands in the JSON record so BENCH_r*/BENCH_SCALE
    # datapoints carry cost context, not just throughput. First bench
    # of a model wins (companion shapes — s24/bf16 — would overwrite
    # the CLI-shape cost the record describes).
    cost_by_model: dict = {}

    def bench_model(name: str, dists, dup_fields: bool = False,
                    log2_slots: int = 0, batch: int = 0, nnz: int = 0,
                    sorted_bf16: bool = None) -> dict:
        """Compile the model's K-step program ONCE, then time each slot
        distribution on it (shapes identical → no recompile).

        MVM benches its NATURAL data shape by default: one feature per
        field (fields 0..nnz-1 — what libffm FFM rows are), which the
        exclusive-fields product path (models/mvm.py) requires; per-row
        occurrence count matches the other models exactly.
        `dup_fields=True` instead draws random fields over num_fields=18
        (every row has duplicate fields), exercising the general
        segment-sum path — recorded as the `mvm_dupfields_*` companion.

        FFM benches at its practical shape — 18 one-feature-per-field
        fields, k=4 per opposing field (a [S, 73] fused row) — on the
        aligned-hybrid sorted engine at the full CLI batch (round 5;
        the round-4 16k cap was a segment-engine argument and the
        hybrid has no segment state).

        `log2_slots`/`batch`/`nnz` override the CLI shape (0 = CLI) —
        the 2^24 north-star companion runs use them.
        """
        log2_slots = log2_slots or args.log2_slots
        B_, F_ = batch or args.batch, nnz or args.nnz
        if sorted_bf16 is None:
            sorted_bf16 = args.sorted_bf16
        overrides = {
            "model.name": name,
            "data.log2_slots": log2_slots,
            "data.max_nnz": F_,
            "data.batch_size": B_,
            "data.sorted_sub_batches": args.sub_batches,
            "data.sorted_bf16": sorted_bf16,
        }
        if name == "mvm":
            if dup_fields:
                overrides["model.mvm_exclusive"] = "off"
            else:
                overrides["model.num_fields"] = F_
                overrides["model.mvm_exclusive"] = "on"
        if name == "ffm":
            overrides["model.num_fields"] = F_
            overrides["model.v_dim"] = 4
        cfg = override(Config(), **overrides)
        model, opt = get_model(name), get_optimizer("ftrl")
        step = make_train_step(model, opt, cfg, jit=False)
        # staging shared with tools/step_decompose.py (same harness,
        # same distribution); MVM/FFM's exclusive-fields shape uses one
        # feature per field instead of random fields, so that draw is
        # skipped too
        exclusive = name in ("mvm", "ffm") and not dup_fields
        staged = stage_row_batches(rng, cfg.num_slots, cfg.model.num_fields,
                                   K, B_, F_, with_slots=False,
                                   with_fields=not exclusive)
        mask_np = staged["mask"]
        if exclusive:
            fields_host = np.broadcast_to(
                np.arange(F_, dtype=np.int32), (K, B_, F_)
            ).copy()
        else:
            fields_host = staged["fields"]
        common = {
            "fields": jnp.asarray(fields_host),
            "mask": jnp.asarray(mask_np),
            "labels": jnp.asarray(staged["labels"]),
            "row_mask": jnp.asarray(staged["row_mask"]),
        }

        def make_batches(dist: str) -> dict:
            ck = (cfg.num_slots, B_, F_)
            if dist == "zipf" and ck not in zipf_slots_cache:
                zipf_slots_cache[ck] = draw_slots(cfg.num_slots, "zipf", (K, B_, F_))
            slots_np = (
                zipf_slots_cache[ck]
                if dist == "zipf"
                else draw_slots(cfg.num_slots, "uniform", (K, B_, F_))
            )
            batches = {**common, "slots": jnp.asarray(slots_np)}
            # only the row-major step consumes dedup arrays; attaching them
            # to a sorted-path run would measure dead transfers
            if args.dedup and (args.no_sorted or name == "lr"):
                # host dedup for the row-major step (data.dedup analog;
                # the skewed-data / cross-chip-volume lever): ships
                # (unique_slots, inverse) per scan step
                from xflow_tpu.ops.sorted_table import dedup_slots

                cap = int(B_ * F_ * 0.5)
                pairs = [dedup_slots(slots_np[i], cap) for i in range(K)]
                if all(p is not None for p in pairs):
                    batches["unique_slots"] = jnp.asarray(
                        np.stack([p[0] for p in pairs])
                    )
                    batches["inverse"] = jnp.asarray(np.stack([p[1] for p in pairs]))
                    print(f"# {name}: dedup on, cap={cap}", file=sys.stderr)
                else:
                    print(f"# {name}: dedup overflow (uniques > {cap}); direct",
                          file=sys.stderr)
            if name in ("fm", "mvm", "ffm") and not args.no_sorted:
                # sorted-window layout (ops/sorted_table.py): host-side
                # plan, sub-batched like the trainer (cache-resident rows).
                # FFM rides the ALIGNED HYBRID (round 5, models/ffm.py):
                # flat plan with fields + the host placement permutation
                from xflow_tpu.ops.sorted_table import (
                    plan_sorted_stacked,
                    resolve_sub_batches,
                )

                ns = 1 if name == "ffm" else resolve_sub_batches(cfg)
                # the MVM segment path and FFM consume per-occurrence
                # fields; the MVM product path routes on their absence
                use_fields = name == "ffm" or (name == "mvm" and dup_fields)
                plans = [
                    plan_sorted_stacked(
                        slots_np[i], mask_np[i], cfg.num_slots,
                        fields=fields_host[i] if use_fields else None,
                        num_sub=ns,
                    )
                    for i in range(K)
                ]
                path = (
                    f"sorted layout ({'segment' if use_fields else 'product'})"
                    if name == "mvm"
                    else "sorted layout (aligned hybrid)"
                    if name == "ffm"
                    else "sorted layout"
                )
                print(f"# {name}: {path}, sub_batches={ns}", file=sys.stderr)
                batches["sorted_slots"] = jnp.asarray(np.stack([p.sorted_slots for p in plans]))
                batches["sorted_row"] = jnp.asarray(np.stack([p.sorted_row for p in plans]))
                batches["sorted_mask"] = jnp.asarray(np.stack([p.sorted_mask for p in plans]))
                batches["win_off"] = jnp.asarray(np.stack([p.win_off for p in plans]))
                if use_fields:
                    batches["sorted_fields"] = jnp.asarray(
                        np.stack([p.sorted_fields for p in plans])
                    )
                if name == "ffm":
                    from xflow_tpu.models.ffm import ffm_invperm

                    batches["ffm_invperm"] = jnp.asarray(
                        np.stack(
                            [
                                ffm_invperm(
                                    p.sorted_row, p.sorted_fields,
                                    p.sorted_mask, B_, cfg.model.num_fields,
                                )
                                for p in plans
                            ]
                        )
                    )
            return batches

        from functools import partial

        # donate the state like every production engine does (train/
        # step.py and the three sharded builders): without it the K-step
        # scan keeps TWO copies of tables+optimizer state live in HBM
        # and benchmarks a memory profile the real step never has
        # (XF703, docs/STATIC_ANALYSIS.md)
        @partial(jax.jit, donate_argnums=(0,))
        def run_k_steps(state, batches):
            def body(st, batch):
                st, m = step(st, batch)
                return st, m["loss"]

            return jax.lax.scan(body, state, batches)

        from xflow_tpu.telemetry import CompileRecorder

        crec = CompileRecorder()
        run_k = crec.wrap(f"bench.{name}", run_k_steps)

        rates = {}
        for dist in dists:
            state = init_state(model, opt, cfg)
            batches = make_batches(dist)
            # warmup (compiles on the first dist; cache hit afterwards)
            state, losses = run_k(state, batches)
            _ = float(losses[-1])  # host read = hard sync
            times = []
            # companion runs (non-headline model or zipf) use fewer
            # repeats: the full 3-model x 2-dist sweep must fit a
            # single driver invocation comfortably
            reps = (
                args.repeats
                if (name == "lr" and dist == "uniform") or args.model != "all"
                else min(args.repeats, 3)
            )
            for _ in range(reps):
                t0 = time.perf_counter()
                state, losses = run_k(state, batches)
                _ = float(losses[-1])
                times.append(time.perf_counter() - t0)
            best = min(times)
            print(
                f"# {name}[{dist}]: device={jax.devices()[0]} scan_steps={K} batch={B_} "
                f"nnz={F_} slots=2^{log2_slots} best={best*1e3:.1f}ms/{K}steps "
                f"({best/K*1e6:.0f}µs/step) times_ms={[round(t*1e3,1) for t in times]}",
                file=sys.stderr,
            )
            rates[dist] = K * B_ / best
        info = crec.latest(f"bench.{name}")
        if info and info.get("flops"):
            cost_by_model.setdefault(name, {
                "compile_time_s": info["compile_time_s"],
                "flops": info["flops"],
                "bytes_accessed": info.get("bytes_accessed"),
                "examples_per_call": K * B_,  # one call = K steps x B_ rows
            })
        return rates

    kernel_parity = None
    if jax.default_backend() == "tpu" and not args.no_sorted:
        # on-device parity gate (VERDICT r2 item 6): the sorted-window
        # kernels are only lowered through Mosaic on a real chip, so the
        # silent-rounding class of bug is only visible here — fail the
        # bench loudly rather than record a fast-but-wrong number
        from xflow_tpu.tools.kernel_parity import check_kernel_parity

        par = check_kernel_parity()
        print(f"# kernel_parity: {par}", file=sys.stderr)
        if not par["ok"]:
            # fail loudly INSTEAD of recording a fast-but-wrong number:
            # no throughput line, nonzero exit
            print(json.dumps({"metric": "kernel_parity", "value": 0,
                              "unit": "bool", "vs_baseline": 0,
                              "error": f"kernel parity FAILED: {par['checks']}"}))
            return 1
        kernel_parity = "ok"

    models = ["lr", "fm", "mvm"] if args.model == "all" else [args.model]

    def model_shape(name: str) -> dict:
        # FFM benches at its practical shape — 18 one-feature-per-field
        # fields, k=4 — at 2x the CLI batch: wide-row models amortize
        # the per-step table-sized passes over more examples (measured
        # at 2^22: 64k -> 623k ex/s, 128k -> 742k, 192k OOM, 256k hits
        # the Mosaic compile-helper limit), and the aligned hybrid
        # carries no per-(row, field) segment state, so the round-4 16k
        # cap (a sorted-segment-engine argument) no longer applies.
        # 128k is also the recommended trainer batch for FFM (and the
        # cap here: a larger CLI batch would push the doubled FFM leg
        # into the measured OOM/compiler-limit territory).
        if name == "ffm":
            return {"batch": min(args.batch * 2, 131072), "nnz": 18}
        return {}
    # skewed-slot (Zipf alpha=1.05) runs ride along (round-1 verdict item
    # 9): real CTR id streams are heavy-tailed, and uniform slots are the
    # worst case for any dedup/caching lever — record both honestly
    dists = ("uniform",) if args.no_zipf else ("uniform", "zipf")
    rates = {name: bench_model(name, dists, **model_shape(name)) for name in models}
    headline = "lr" if "lr" in rates else models[0]
    record = {
        "metric": f"{headline}_examples_per_sec",
        "value": round(rates[headline]["uniform"], 1),
        "unit": "examples/sec",
        "vs_baseline": round(rates[headline]["uniform"] / PER_CHIP_TARGET, 3),
    }
    # secondary models ride along in the same single JSON line so FM/MVM
    # regressions are visible in BENCH_r*.json (round-1 verdict item 3)
    for name in models:
        if name != headline:
            record[f"{name}_examples_per_sec"] = round(rates[name]["uniform"], 1)
            record[f"{name}_vs_baseline"] = round(rates[name]["uniform"] / PER_CHIP_TARGET, 3)
    for name in models:
        if "zipf" in rates[name]:
            record[f"zipf_{name}_examples_per_sec"] = round(rates[name]["zipf"], 1)
    if "mvm" in models and not args.no_sorted:
        # general-path companion: random fields over 18 field groups =
        # every row has multi-valued fields, so the segment-sum path runs
        dup = bench_model("mvm", ("uniform",), dup_fields=True)
        record["mvm_dupfields_examples_per_sec"] = round(dup["uniform"], 1)
        record["mvm_dupfields_vs_baseline"] = round(
            dup["uniform"] / PER_CHIP_TARGET, 3
        )
        if args.log2_slots < 24 and not args.smoke and args.model == "all":
            # the segment path at the north-star table shape (round-4
            # verdict #3: recorded, not just the product path's s24)
            d24 = bench_model("mvm", ("uniform",), dup_fields=True,
                              log2_slots=24)
            record["mvm_dupfields_s24_examples_per_sec"] = round(
                d24["uniform"], 1
            )
            record["mvm_dupfields_s24_vs_baseline"] = round(
                d24["uniform"] / PER_CHIP_TARGET, 3
            )
    if args.model == "all":
        # FFM companion (BASELINE.json config 5) at its practical shape
        # (bench_model docstring): 18 one-feature-per-field fields, k=4
        # — a [S, 73] fused row on the aligned hybrid engine
        ffm = bench_model("ffm", ("uniform",), **model_shape("ffm"))
        record["ffm_examples_per_sec"] = round(ffm["uniform"], 1)
        record["ffm_vs_baseline"] = round(ffm["uniform"] / PER_CHIP_TARGET, 3)
        if args.log2_slots < 24 and not args.smoke:
            # north-star table shape (round-3 verdict #2): 2^24 slots/chip
            # = 1B features / 64 chips — the scale BASELINE.md's pod
            # target implies; recorded so BENCH_r*.json can't flatter by
            # benching only the smaller default shape
            for name in models:
                r24 = bench_model(name, ("uniform",), log2_slots=24)
                record[f"{name}_s24_examples_per_sec"] = round(r24["uniform"], 1)
                record[f"{name}_s24_vs_baseline"] = round(
                    r24["uniform"] / PER_CHIP_TARGET, 3
                )
            # FFM at 2^24 cannot run on one chip: the FTRL state is
            # 3 x [2^21, 584] f32 = 29.4 GB against ~15 GB of HBM (the
            # [S, 73] fused row is 6.6x FM's). At-scale FFM is the
            # fullshard mesh path (2^24 over 64 chips = 460 MB/chip);
            # recorded as a note so the absence is explicit, not silent
            record["ffm_s24_note"] = (
                "infeasible single-chip: FTRL state 3x9.8GB > 15GB HBM; "
                "at-scale FFM = fullshard mesh (dryrun leg covers it)"
            )
        if not args.smoke and not args.sorted_bf16:
            # bf16 fast-mode riders (cfg.data.sorted_bf16, docs/PERF.md
            # "Precision note"): the one-pass MXU read the exact default
            # deliberately forgoes — recorded so the trade stays visible
            b16 = bench_model("fm", ("uniform",), sorted_bf16=True)
            record["fm_bf16_examples_per_sec"] = round(b16["uniform"], 1)
            record["fm_bf16_vs_baseline"] = round(
                b16["uniform"] / PER_CHIP_TARGET, 3
            )
            f16 = bench_model("ffm", ("uniform",), sorted_bf16=True,
                              **model_shape("ffm"))
            record["ffm_bf16_examples_per_sec"] = round(f16["uniform"], 1)
            record["ffm_bf16_vs_baseline"] = round(
                f16["uniform"] / PER_CHIP_TARGET, 3
            )
        if not args.smoke:
            # end-to-end rider (round-3 verdict #5): disk → C++ parser →
            # plan → device, the number `xflow train` actually delivers;
            # the gap to the pre-staged headline is the host data plane
            e2e_rate = measure_e2e(args, "fm", min(args.e2e_rows, 1_000_000))
            record["e2e_fm_examples_per_sec"] = round(e2e_rate, 1)
            record["e2e_fm_vs_baseline"] = round(e2e_rate / PER_CHIP_TARGET, 3)
    if kernel_parity is not None:
        record["kernel_parity"] = kernel_parity
    # compile/cost context for the headline model (CompileRecorder):
    # per-example model FLOPs and bytes accessed are the roofline
    # numerators tools/perf_ledger.py converts the pod target with
    # (docs/PERF.md "Measured roofline")
    cost = cost_by_model.get(headline)
    if cost:
        ex = cost["examples_per_call"]
        record["compile_time_s"] = round(cost["compile_time_s"], 3)
        record["flops_per_example"] = round(cost["flops"] / ex, 2)
        if cost.get("bytes_accessed"):
            record["bytes_per_example"] = round(cost["bytes_accessed"] / ex, 2)
    # wall clock for trajectory correlation only; all durations above are
    # time.perf_counter() (monotonic — wall clock jumps under NTP slew)
    record["ts"] = round(time.time(), 3)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
