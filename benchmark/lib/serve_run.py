"""One run of a serve cell (a configuration whose file says `"path":
"serve"`), in `run.py`'s own process, which owns the chip:

set-up: the generator's child started (it makes its request bodies
meanwhile), the seed's table written as a committed checkpoint by the
program's own writer, `ServeRunner.load()`, `warmup()`, the server up as
`xflow serve` brings it up, some seconds of the cell's own traffic
thrown away; the window: `--seconds` of requests from the child; after
it: server closed, runner freed, every answer compared with the plain
reference. Nothing here imports the program: `lib/serve_drive.py` does.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

TRACE_START_S = 4.0  # the traced part of a traced run's window: from here ...
TRACE_SECONDS = 6.0  # ... for so long (both cut to fit a shorter window)
SAMPLE_BATCHES = 64  # batches drawn from the pool to count a batch's distinct slots


class Generator:
    """The child that sends the traffic (`lib/loadgen.py`)."""

    def __init__(self, here: str, seed: int, cfg: dict, traffic: dict, sock: str, workdir: str):
        spec = {"seed": seed, "cfg": {"num_fields": cfg["num_fields"], "log2_slots": cfg["log2_slots"]},
                "traffic": traffic, "socket": sock, "out": workdir}
        path = os.path.join(workdir, "loadgen.json")
        with open(path, "w") as f:
            json.dump(spec, f)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(here, "lib", "loadgen.py"), "--spec", path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"the generator ended early (exit code {self.proc.poll()})")
        return json.loads(line)

    def run(self, tag: str, seconds: float, traffic: dict | None = None) -> dict:
        """One window; `traffic` overrides keys of the spec's traffic
        for this window alone (the hand-run sweep's loop and rate)."""
        cmd = {"cmd": "run", "tag": tag, "seconds": seconds, "traffic": traffic or {}}
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        done = self.read()
        with np.load(done["path"]) as z:
            return {k: z[k] for k in z.files}

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write('{"cmd": "quit"}\n')
                self.proc.stdin.flush()
                self.proc.wait(timeout=30)
            except Exception:  # noqa: BLE001 - it goes either way
                self.proc.kill()
        self.proc.wait()


def socket_path(workdir: str) -> tuple[str, str | None]:
    """A unix socket's path holds about a hundred characters: inside the
    run's directory where that fits, else in a directory under TMPDIR."""
    path = os.path.join(workdir, "s.sock")
    if len(path) <= 100:
        return path, None
    made = tempfile.mkdtemp(prefix="xfb")
    return os.path.join(made, "s.sock"), made


def bring_up(cfg: dict, seed: int, width: int, workdir: str, sock: str, log):
    """The seed's table through the program's checkpoint writer, then
    the server over it (`lib/serve_drive.py`), its serve stream (the
    `kind="serve"` windows and the request spans) written in every run:
    a traced run serves under the settings of a timed one."""
    from lib import serve_drive, weights

    t = time.perf_counter()
    ckpt_dir = os.path.join(workdir, "ckpt")
    nbytes = serve_drive.write_checkpoint(cfg, seed, width, ckpt_dir, weights.chunk_table_fn)
    log(f"checkpoint of {nbytes} table bytes written in {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    metrics_path = os.path.join(workdir, "serve.jsonl")
    served = serve_drive.Served(serve_drive.program_config(cfg, ckpt_dir, sock, metrics_path))
    log(f"loaded, {served.rungs} rung(s) warm and listening in {time.perf_counter() - t:.2f}s")
    return served, metrics_path


def batch_shape(pool: dict, cfg: dict, rows_per_batch: float, slots_of) -> dict:
    """Mean distinct slots and occurrences of a device batch of
    `rows_per_batch` rows: counted on runs of consecutive pool rows."""
    n = max(int(round(rows_per_batch)), 1)
    ids = pool["ids"]
    starts = np.linspace(0, max(len(ids) - n, 0), SAMPLE_BATCHES).astype(np.int64)
    distinct = [np.unique(slots_of(ids[s:s + n], int(cfg["log2_slots"]))).size for s in starts]
    return {"distinct_slots": float(np.mean(distinct)), "occurrences": float(n * ids.shape[1])}


def run(args, cell: dict, cfg: dict, traffic: dict, jax, devices, t_ready: float, here: str, root: str,
        log) -> dict:
    from lib import compare, loadgen, serve_drive, serve_stats, weights
    from lib.traffic import slots_of_ids
    from reference import core as refcore, predict as refpredict

    model = refcore.model_module(cfg["reference"])
    width = model.width(cfg)
    workdir = os.path.join(root, "bench_run", cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    sock, sock_dir = socket_path(workdir)
    gen = Generator(here, args.seed, cfg, traffic, sock, workdir)
    served = None
    try:
        # ---- set-up
        served, metrics_path = bring_up(cfg, args.seed, width, workdir, sock, lambda m: log("run.py: " + m))
        ready = gen.read()
        log(f"run.py: generator ready: {ready}")
        t = time.perf_counter()
        warm = serve_stats.window_stats(gen.run("warm", float(traffic["warm_seconds"])))
        log(f"run.py: warm traffic {warm['requests']} requests {warm['failed']} failed {warm['statuses']} in "
            f"{time.perf_counter() - t:.2f}s")
        served.flush_window()
        records_from = os.path.getsize(metrics_path) if os.path.exists(metrics_path) else 0
        compiles0 = served.compiles()
        setup_s = time.perf_counter() - t_ready

        # ---- the window
        profile_dir = os.path.join(workdir, "profile")
        traced = {"seconds": 0.0}
        tracer = None
        if args.trace and not args.rehearsal:
            start = min(TRACE_START_S, args.seconds / 4)
            length = min(TRACE_SECONDS, args.seconds / 2)

            def trace_part():
                time.sleep(start)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(profile_dir, profiler_options=opts)
                time.sleep(length)
                jax.profiler.stop_trace()
                traced["seconds"] = length

            tracer = threading.Thread(target=trace_part, daemon=True)
            tracer.start()
        window_log = gen.run("window", args.seconds)
        if tracer is not None:
            tracer.join()
        peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:1])
        compiles = served.compiles() - compiles0
        info = served.describe()
    finally:
        # ---- server closed, runner freed, generator ended
        if served is not None:
            served.close()
        gen.close()
        if sock_dir:
            shutil.rmtree(sock_dir, ignore_errors=True)
    del served
    gc.collect()
    window = serve_stats.window_stats(window_log)
    log(f"run.py: window {window['window_s']:.3f}s {window['requests']} requests {window['statuses']} "
        f"{window['rows_in_window']} rows inside it, p50 {window['p50_ms']:.2f} p95 {window['p95_ms']:.2f} "
        f"p99 {window['p99_ms']:.2f} ms, "
        f"last answer at {window['closed_s']:.3f}s, compiles {compiles}, peak {peak_bytes}")

    # ---- correct: every answer of the window against the plain reference
    t = time.perf_counter()
    pool = loadgen.make_pool(args.seed, cfg, traffic)
    entries, row_of_answer, _ = serve_stats.answered_rows(window_log, pool)
    ref = refpredict.serve_pctr(cfg, args.seed, serve_stats.entry_ids(pool, entries), slots_of_ids,
                                weights.rows_numpy)
    numbers = serve_stats.pctr_gaps(window_log["pctr"], ref[row_of_answer])
    numbers["window_failed_requests"] = window["failed"]
    numbers["window_shed_requests"] = window["shed"]
    numbers["window_compiles"] = compiles
    numbers["window_generations_extra"] = window["generations_extra"]
    correct, compared = compare.judge(numbers, compare.load_limits(here, cfg))
    log(f"run.py: reference over {len(ref)} rows of {len(entries)} requests in {time.perf_counter() - t:.2f}s")

    records = serve_drive.serve_records(metrics_path, records_from)
    shape_cache: dict = {}

    def shape() -> dict:
        batches = sum(w["batches"] for w in records["windows"])
        rows = sum(w["rows"] for w in records["windows"])
        if not shape_cache and batches:
            shape_cache.update(batch_shape(pool, cfg, rows / batches, slots_of_ids), batches=batches)
        return shape_cache

    spans = {}
    for name, recs in records["spans"].items():  # where a request's time goes, for reading by hand (PERF.md section 5)
        ms = sorted(r["dur_ms"] for r in recs if "dur_ms" in r)
        if ms:
            spans[str(name)] = {"n": len(ms), "p50_ms": serve_stats.percentile(ms, 50.0),
                                "p99_ms": serve_stats.percentile(ms, 99.0)}
    return {
        "spans": spans,
        "correct": correct, "compared": compared, "attempted": window["requests"], "failed": window["failed"],
        "setup_s": setup_s, "peak_bytes": peak_bytes, "info": info, "window": window,
        "end_to_end": {"serve_rows_per_s": window["rows_per_s"], "serve_p50_ms": window["p50_ms"],
                       "serve_p95_ms": window["p95_ms"], "serve_p99_ms": window["p99_ms"]},
        "profile_dir": profile_dir if traced["seconds"] > 0 else None,
        "run": {"serve": records, "window": window, "shape": shape, "width": width, "chips": 1,
                "traced_seconds": traced["seconds"]},
        "workdir": workdir,
    }
