#!/usr/bin/env python3
"""The hand-run sweep of a serve cell (PERF.md section 7; not a cell):

    python3 benchmark/serve_sweep.py --workload <name> [--seconds 24] [--runs 3] [--out FILE]

One server over the seed's table, as `run.py` brings it up, and under it
the closed loop at each of --closed callers (the most requests/s any of
them sustains is the knee), then the open loop (Poisson arrivals,
latency from the due time) at each of --open-shares of the knee: --runs
windows of --seconds each, one JSON line a window. It finds the knee once; the
benchmark's cells offer load at a rate fixed in their traffic files and
never search for one.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

KEEP = ("requests", "failed", "shed", "rows_per_s", "requests_per_s", "p50_ms", "p95_ms", "p99_ms", "max_ms", "generator_late_p99_ms", "offered")


def shares(text: str) -> list:
    return [float(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_300_000_003)
    ap.add_argument("--seconds", type=float, default=24.0)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--closed", default="1,2,4,8,16")
    ap.add_argument("--open-shares", default="0.6,0.7,0.8,0.9")
    ap.add_argument("--open-connections", type=int, default=64)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    import run as harness

    _, cell, cfg, traffic = harness.load_cell(args.workload, args.rehearsal)
    jax = harness.start_jax(args.rehearsal, 1)
    if not args.rehearsal and jax.devices()[0].platform == "cpu":
        print("serve_sweep.py: no accelerator", file=sys.stderr)
        return 3
    from lib import serve_run, serve_stats
    from reference import core as refcore

    width = refcore.model_module(cfg["reference"]).width(cfg)
    workdir = os.path.join(ROOT, "bench_run", "sweep." + cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    sock, sock_dir = serve_run.socket_path(workdir)
    sink = open(args.out, "a") if args.out else None
    gen = serve_run.Generator(HERE, args.seed, cfg, traffic, sock, workdir)
    served = None
    try:
        served, _ = serve_run.bring_up(cfg, args.seed, width, workdir, sock,
                                       lambda m: print("serve_sweep.py: " + m, file=sys.stderr))
        gen.read()
        gen.run("warm", float(traffic["warm_seconds"]))

        def window(tag: str, k: int, seconds: float, over: dict) -> dict:
            st = serve_stats.window_stats(gen.run(f"{tag}.{k}", seconds, over))
            rec = {"workload": cell["name"], "tag": tag, "run": k, "seconds": seconds, **over,
                   **{key: st[key] for key in KEEP}}
            line = json.dumps(rec)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
            return st

        knee = 0.0
        for c in [int(x) for x in args.closed.split(",") if x]:
            rates = [window(f"closed-c{c}", k, args.seconds, {"loop": "closed", "connections": c})["requests_per_s"]
                     for k in range(args.runs)]
            knee = max(knee, statistics.median(rates))
        knee = knee or float(traffic.get("rate_rps") or 0.0) / 0.8  # no closed loop asked for: the cell's own rate
        for share in shares(args.open_shares):
            for k in range(args.runs):
                window(f"open-{share}", k, args.seconds,
                       {"loop": "open", "connections": args.open_connections, "rate_rps": share * knee})
    finally:
        if served is not None:
            served.close()
        gen.close()
        if sock_dir:
            shutil.rmtree(sock_dir, ignore_errors=True)
        shutil.rmtree(workdir, ignore_errors=True)
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
