#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in one process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

makes the data from the seed, builds the trainer as `xflow train` does,
takes the first steps, warms up, drives the window, compares with the
plain reference, and prints one JSON object as its last line. A
configuration whose file says `"path": "serve"` is served instead
(`lib/serve_run.py`): the seed's table loaded by `xflow serve`'s body,
requests from a child process over its unix socket, every answer
compared. The cell, its configuration, its traffic and its per-layer
metrics are found by name from BENCHMARK.json and the files beside this
one (README.md).
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python gives it (for `client_s`)

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)  # the program
sys.path.insert(0, HERE)  # lib/, reference/

TRACE_PASSES = 3  # passes of the window the profiler records


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_cell(workload: str, rehearsal: bool) -> tuple[dict, dict, dict, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    from lib.traffic import load_traffic

    traffic = load_traffic(HERE, cell["traffic"])
    if rehearsal:
        with open(os.path.join(HERE, "rehearsal.json")) as f:
            tiny = json.load(f)
        tiny = tiny.get("paths", {}).get(cfg.get("path", "train"), tiny)
        cfg.update(tiny["config"])
        traffic.update(tiny["traffic"])
    return bench, cell, cfg, traffic


def load_metric(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: dict, group: str) -> list:
    return [m for m in bench[group] if "workloads" not in m or cell["name"] in m["workloads"]]


def start_jax(rehearsal: bool, chips: int):
    """jax with the persistent compile cache on; held to the CPU (with
    as many virtual devices as the cell has chips) for a rehearsal."""
    import jax

    if rehearsal:
        try:
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_num_cpu_devices", max(chips, 1))
        except RuntimeError:
            pass  # a test's process: the backend is up already, on the CPU
    from xflow_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()  # <checkout>/.jax_cache unless the machine places it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"run.py: compile cache at {cache_dir}")
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes (rehearsal.json) on the CPU: every phase runs, "
                         "no device metric is printed")
    ap.add_argument("--keep", default="",
                    help="a directory to leave the traced run's outline in (planes, lines, "
                         "names by time) and a cut of the trace, for reading by hand")
    args = ap.parse_args(argv)
    bench, cell, cfg, traffic = load_cell(args.workload, args.rehearsal)
    chips = int(cell["chips"])

    jax = start_jax(args.rehearsal, chips)
    devices = jax.devices()
    t_ready = time.perf_counter()  # the runtime's own start-up ends here; set-up is timed from it
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    log(f"run.py: {cell['name']} seed={args.seed} device={device}")
    if not args.rehearsal and (device["platform"] == "cpu" or len(devices) < chips):
        log(f"run.py: the cell needs {chips} accelerator chip(s); found {device}")
        return 3
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    peak = peaks.get(device["kind"])
    if peak is None and not args.rehearsal:
        log(f"run.py: device kind {device['kind']!r} is not in peaks.json")
        return 3

    if cfg.get("path", "train") == "serve":
        return main_serve(args, bench, cell, cfg, traffic, jax, devices, device, peak, t_ready)

    from lib import compare, counts, drive, trace as tracelib, weights
    from lib.traffic import make_run_data, slots_of_ids
    from reference import core as refcore

    workdir = os.path.join(ROOT, "bench_run", cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    t = time.perf_counter()
    data = make_run_data(os.path.join(workdir, "data"), args.seed, cfg, traffic)
    log(f"run.py: data {data['text_bytes']} bytes of text in {time.perf_counter() - t:.2f}s")

    model = refcore.model_module(cfg["reference"])
    width, leaves = model.width(cfg), model.leaves(cfg)
    metrics_path = os.path.join(workdir, "metrics.jsonl") if args.trace else ""
    t = time.perf_counter()
    trainer = drive.build_trainer(cfg, chips, data["train_prefix"], metrics_path)
    drive.install_weights(trainer, cfg, args.seed, width, weights.packed_table_fn)
    log(f"run.py: trainer and weights in {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    prog = drive.first_steps(trainer, cfg, args.seed, data, width, leaves, weights.packed_table_fn)
    log(f"run.py: first steps in {time.perf_counter() - t:.2f}s {drive.describe(trainer)}")
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:warm_pass"):
        warm = trainer.fit()
    log(f"run.py: warm pass {warm.steps} steps in {time.perf_counter() - t:.2f}s")
    records_from = os.path.getsize(metrics_path) if args.trace and os.path.exists(metrics_path) else 0
    setup_s = time.perf_counter() - t_ready

    # ---- the window
    profile_dir = os.path.join(workdir, "profile")
    traced = {"on": False, "first": 1, "passes": 0}

    def on_pass(k: int) -> None:
        if not args.trace:
            return
        if k == traced["first"] and not traced["on"]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            traced["on"] = True
        elif traced["on"] and k == traced["first"] + TRACE_PASSES:
            jax.profiler.stop_trace()
            traced["on"], traced["passes"] = False, TRACE_PASSES

    window = drive.run_window(trainer, args.seconds, on_pass)
    if traced["on"]:
        jax.profiler.stop_trace()
        traced["on"], traced["passes"] = False, window["passes"] - traced["first"]
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:chips])
    log(f"run.py: window {window['window_s']:.3f}s {window['passes']} passes {window['steps']} steps "
        f"{window['examples']} rows, compiles {window['compiles']}, peak {peak_bytes}")
    records = drive.step_records(metrics_path, records_from)
    info = drive.describe(trainer)
    del trainer  # the program's state is freed before the reference runs
    gc.collect()

    # ---- correct: the first steps against the plain reference
    t = time.perf_counter()
    batches = [(s["ids"], s["labels"]) for s in data["first"]]
    ref = refcore.run_steps(cfg, args.seed, batches, slots_of_ids, weights.rows_numpy)
    numbers = compare.readings(prog, ref)
    numbers["window_bad_steps"] = window["bad_steps"]
    numbers["window_compiles"] = window["compiles"]
    numbers["window_short_rows"] = window["steps"] * int(cfg["batch_size"]) - window["examples"]
    correct, compared = compare.judge(numbers, compare.load_limits(HERE, cfg))
    log(f"run.py: reference in {time.perf_counter() - t:.2f}s")

    # ---- metrics
    out_metrics: dict = {}
    breakdown = None
    if not args.trace:
        if not args.rehearsal:
            out_metrics["train_examples_per_s"] = {
                "value": window["examples"] / window["window_s"], "unit": "examples/s"}
            out_metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        summary = None
        if not args.rehearsal and traced["passes"] > 0:
            loaded = tracelib.load(profile_dir)
            if args.keep:
                tracelib.keep(loaded, args.keep, cell["name"])
            summary = tracelib.summarize(loaded, tracelib.load_names(HERE), chips)
        shape_cache: dict = {}

        def shape() -> dict:
            if not shape_cache:
                slots = slots_of_ids(data["train_ids"], int(cfg["log2_slots"]))
                shape_cache.update(counts.batch_shape(slots, int(cfg["batch_size"])))
            return shape_cache

        run = {
            "records": records, "window": window, "trace": summary,
            "trace_steps": traced["passes"] * data["steps_per_pass"],
            "shape": shape, "width": width, "chips": chips, "peak": peak,
            "memory_peak_bytes": None if args.rehearsal else peak_bytes, "info": info,
        }
        for m in cell_metrics(bench, cell, "per_layer"):
            value = load_metric(m["name"]).read(run)
            if value is not None and not args.rehearsal:  # a CPU run prints no time or share
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary and summary.get("devices"):
            device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}
    device["memory_peak_bytes"] = peak_bytes
    shutil.rmtree(workdir, ignore_errors=True)

    extra = {"workload": cell["name"], "seed": args.seed, "rehearsal": args.rehearsal,
             "client_s": t_ready - _T0, "window_s": window["window_s"], "passes": window["passes"],
             "pass_end_s": [round(x, 4) for x in window["pass_s"]], "program": info}
    return print_result(correct, window["steps"], window["bad_steps"], out_metrics, device, breakdown,
                        extra, compared)


def print_result(correct, attempted, failed, out_metrics, device, breakdown, extra, compared) -> int:
    """The result's line, last on stdout; the numbers compared, each
    beside its limit, last on stderr and last in the line."""
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result.update(extra)
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name} = {c['value']:.6g}  limit {c['limit']:.6g}")
    log(f"correct = {correct}")
    print(json.dumps(result), flush=True)
    return 0


def main_serve(args, bench, cell, cfg, traffic, jax, devices, device, peak, t_ready) -> int:
    """A serve cell's run (`lib/serve_run.py`) and its result's line."""
    from lib import serve_run, trace as tracelib

    got = serve_run.run(args, cell, cfg, traffic, jax, devices, t_ready, HERE, ROOT, log)
    out_metrics: dict = {}
    breakdown = None
    if not args.trace:
        if not args.rehearsal:
            for m in cell_metrics(bench, cell, "end_to_end"):
                value = got["setup_s"] if m["name"] == "setup_s" else got["end_to_end"][m["name"]]
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        summary = None
        if got["profile_dir"]:
            loaded = tracelib.load(got["profile_dir"])
            if args.keep:
                tracelib.keep(loaded, args.keep, cell["name"])
            names = tracelib.load_names(HERE)
            summary = tracelib.summarize(loaded, names, 1)
            summary["module_runs"] = tracelib.module_runs(loaded, names)
        run = dict(got["run"], trace=summary, peak=peak,
                   memory_peak_bytes=None if args.rehearsal else got["peak_bytes"], info=got["info"])
        for m in cell_metrics(bench, cell, "per_layer"):
            value = load_metric(m["name"]).read(run)
            if value is not None and not args.rehearsal:  # a CPU run prints no time or share
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary and summary.get("devices"):
            device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}
    device["memory_peak_bytes"] = got["peak_bytes"]
    shutil.rmtree(got["workdir"], ignore_errors=True)
    w = got["window"]  # the tail and the median are printed in every run, beside the count of requests
    extra = {"workload": cell["name"], "seed": args.seed, "rehearsal": args.rehearsal,
             "client_s": t_ready - _T0, "window_s": w["window_s"],
             "window": {k: w[k] for k in ("requests", "answered", "shed", "rows_in_window", "requests_per_s",
                                          "rows_per_s", "p50_ms", "p95_ms", "p99_ms", "max_ms",
                                          "closed_s", "generator_late_p99_ms",
                                          "offered")},
             "program": got["info"]}
    if args.rehearsal:  # a CPU run prints no time
        extra["window"] = {k: w[k] for k in ("requests", "answered", "shed", "rows_in_window", "offered")}
    elif got["spans"]:
        extra["spans"] = got["spans"]
    return print_result(got["correct"], got["attempted"], got["failed"], out_metrics, device, breakdown,
                        extra, got["compared"])


if __name__ == "__main__":
    sys.exit(main())
