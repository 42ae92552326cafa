#!/usr/bin/env python3
"""The readings `correct`'s limits are set from, many seeds in one process:

    python3 benchmark/control.py --workload <name> --seeds 12 [--first-seed N] [--out FILE]

For each seed: the program's first steps against the reference (the
lower reading), the control (the reference in the precision below the
configuration's, put in the program's place), each fault planted in the
reference, and with --program-control the program's own lower-precision
path (`control_set` of the configuration file). A serve cell: one server
over the first seed's table, and for each seed a short window of that
seed's traffic (--seconds), every answer against the reference, then the
control and the three serving faults in the program's place. The
benchmark's own runs never call this; PERF.md section 2 holds what it
read on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

LOWER = {"float32": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--out", default="")
    ap.add_argument("--program-control", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--seconds", type=float, default=6.0, help="a serve cell's window for each seed")
    args = ap.parse_args(argv)
    import run as harness

    bench, cell, cfg, traffic = harness.load_cell(args.workload, args.rehearsal)
    chips = int(cell["chips"])
    jax = harness.start_jax(args.rehearsal, chips)
    if not args.rehearsal and jax.devices()[0].platform == "cpu":
        print("control.py: no accelerator", file=sys.stderr)
        return 3
    from lib import compare, drive, weights
    from lib.traffic import make_run_data, slots_of_ids
    from reference import core as refcore

    model = refcore.model_module(cfg["reference"])
    width, leaves = model.width(cfg), model.leaves(cfg)
    faults = ["half_batch"] + (["no_exchange"] if chips > 1 else [])
    workdir = os.path.join(ROOT, "bench_run", "control." + cell["name"])
    sink = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    if cfg.get("path", "train") == "serve":
        serve_pass(args, cell, cfg, traffic, width, workdir, emit)
        if sink:
            sink.close()
        return 0

    def program_pass(cfg_run, tag):
        trainer = None
        for k in range(args.seeds):
            seed = args.first_seed + 7919 * k
            data = make_run_data(os.path.join(workdir, "data"), seed, cfg_run, traffic, window=False)
            if trainer is None:
                trainer = drive.build_trainer(cfg_run, chips, data["train_prefix"])
            t = time.perf_counter()
            drive.install_weights(trainer, cfg_run, seed, width, weights.packed_table_fn)
            prog = drive.first_steps(trainer, cfg_run, seed, data, width, leaves, weights.packed_table_fn)
            batches = [(s["ids"], s["labels"]) for s in data["first"]]
            cfg_ref = dict(cfg, chips=chips)
            ref = refcore.run_steps(cfg_ref, seed, batches, slots_of_ids, weights.rows_numpy)
            rec = {"workload": cell["name"], "seed": seed, "what": tag,
                   "numbers": compare.readings(prog, ref), "program": prog, "reference": ref}
            emit(rec)
            if tag == "program":
                low = refcore.run_steps(cfg_ref, seed, batches, slots_of_ids, weights.rows_numpy,
                                        dtype=LOWER[cfg["dtype"]])
                emit({"workload": cell["name"], "seed": seed, "what": "control:" + LOWER[cfg["dtype"]],
                      "numbers": compare.readings(low, ref)})
                for fault in faults:
                    bad = refcore.run_steps(cfg_ref, seed, batches, slots_of_ids, weights.rows_numpy,
                                            fault=fault)
                    emit({"workload": cell["name"], "seed": seed, "what": "fault:" + fault,
                          "numbers": compare.readings(bad, ref)})
            print(f"control.py: seed {seed} {tag} in {time.perf_counter() - t:.1f}s", file=sys.stderr)
        del trainer

    program_pass(cfg, "program")
    if args.program_control and cfg.get("control_set"):
        import gc

        gc.collect()
        low_cfg = dict(cfg, program_set={**cfg.get("program_set", {}), **cfg["control_set"]})
        program_pass(low_cfg, "control:program " + json.dumps(cfg["control_set"]))
    import shutil

    shutil.rmtree(workdir, ignore_errors=True)
    if sink:
        sink.close()
    return 0


def serve_pass(args, cell, cfg, traffic, width, workdir, emit) -> None:
    """One server over the first seed's table; each seed's traffic through
    a short window; readings of the program, the control and the faults."""
    import shutil

    from lib import loadgen, serve_run, serve_stats, weights
    from lib.traffic import slots_of_ids
    from reference import predict as refpredict

    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    sock, sock_dir = serve_run.socket_path(workdir)
    table_seed = args.first_seed
    served, _ = serve_run.bring_up(cfg, table_seed, width, workdir, sock,
                                lambda m: print("control.py: " + m, file=sys.stderr))
    try:
        for k in range(args.seeds):
            seed = args.first_seed + 7919 * k
            t = time.perf_counter()
            gen = serve_run.Generator(HERE, seed, cfg, traffic, sock, workdir)
            try:
                gen.read()
                log = gen.run("window", args.seconds)
            finally:
                gen.close()
            window = serve_stats.window_stats(log)
            pool = loadgen.make_pool(seed, cfg, traffic)
            entries, row_of_answer, offsets = serve_stats.answered_rows(log, pool)
            ids = serve_stats.entry_ids(pool, entries)

            def answers(**kw):
                return refpredict.serve_pctr(cfg, table_seed, ids, slots_of_ids, weights.rows_numpy,
                                             offsets=offsets, **kw)[row_of_answer]

            ref = answers()
            base = {"workload": cell["name"], "seed": seed, "table_seed": table_seed, "rows": len(ref)}
            emit({**base, "what": "program", "numbers": serve_stats.pctr_gaps(log["pctr"], ref),
                  "window": {k2: window[k2] for k2 in ("requests", "failed", "shed", "generations_extra")}})
            emit({**base, "what": "control:" + LOWER[cfg["dtype"]],
                  "numbers": serve_stats.pctr_gaps(answers(dtype=LOWER[cfg["dtype"]]), ref)})
            for fault in refpredict.FAULTS:
                emit({**base, "what": "fault:" + fault, "numbers": serve_stats.pctr_gaps(answers(fault=fault), ref)})
            print(f"control.py: seed {seed} in {time.perf_counter() - t:.1f}s", file=sys.stderr)
    finally:
        served.close()
        if sock_dir:
            shutil.rmtree(sock_dir, ignore_errors=True)
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
