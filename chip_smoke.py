#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls:
`gen-data` -> `xflow train` (FM, sorted engine, Pallas kernels) -> the
same steps on the row-major XLA engine + the on-device kernel parity
gate -> `xflow serve` over the trained checkpoint -> a second start
that must find the persistent compile cache warm.

This process never imports JAX. Every phase that needs the device is a
`python -m xflow_tpu ...` child, started only after the one before it
has exited, so exactly one process holds the chip at any time.

stdout: one JSON object per phase, then — always last, always exactly
this shape — `{"ok": bool, "device": {"platform", "kind", "count"}}`
with the device as the training child reported it. `ok` is true only if
every phase passed on a TPU; the exit code is 0 only then. The sizes are
arguments so the same script rehearses on the CPU at a tiny size, where
it runs every phase and truthfully ends `"ok": false`.

`--chips 4` runs only the sharded path: the same config through `xflow
train` on the four-device mesh, compared step for step with `--no-mesh`
on device 0, and each chip's share of the table.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import signal
import statistics
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chip_smoke_run")  # data, checkpoints, metrics
LOGS = os.path.join(ROOT, "chiprun_out", "chip_smoke")  # children's stderr

NNZ = 32  # fields per row = data.max_nnz: the reference's padded row width
V_DIM = 10  # the reference latent dim (FM row = 1 + v_dim)
SERVE_MAX_BATCH = 256  # serve.max_batch default: the compiled predict shape

# Two engines (or one and four chips) sum the same f32 terms in another
# order: the reduction-reorder class tools/kernel_parity.py allows 1e-4
# per scattered element. A mean loss and a pCTR average that noise down;
# 1e-5 is ten times the six decimals the pred files carry and a
# hundredth of the spread of the predictions themselves (std ~1e-3
# after six steps), so a wrong-window or wrong-row bug cannot hide in it.
LOSS_RTOL = 1e-5
PCTR_ATOL = 1e-5
SERVE_ATOL = 1e-5


CHILDREN: list = []  # every process started, so that none outlives us


class PhaseFailed(Exception):
    """A phase could not produce its evidence (a child died, timed out
    or left no output, or we were told to stop). Distinct from a failed
    CHECK, which the phase line records as `"ok": false` while the run
    goes on."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def spawn(name: str, args: list, cwd: str):
    """Start `python -m <args>` with its stdout on a pipe and its stderr
    in a log file; returns (process, log path)."""
    os.makedirs(LOGS, exist_ok=True)
    log_path = os.path.join(LOGS, f"{name}.stderr.log")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, env.get("PYTHONPATH")) if p
    )
    with open(log_path, "w") as log:  # the child keeps its own descriptor
        proc = subprocess.Popen(
            [sys.executable, "-m", *args], cwd=cwd, env=env,
            stdout=subprocess.PIPE, stderr=log, text=True,
        )
    CHILDREN.append(proc)
    return proc, log_path


def run_child(name: str, args: list, cwd: str, timeout_s: float,
              ok_codes: tuple = (0,)) -> tuple:
    """Run a child to its end -> (exit code, stdout)."""
    proc, log_path = spawn(name, args, cwd)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise PhaseFailed(f"{name}: no exit within {timeout_s:.0f}s")
    if proc.returncode not in ok_codes:
        with open(log_path) as f:
            sys.stderr.write(f.read()[-4000:])
        raise PhaseFailed(f"{name}: exit code {proc.returncode} (see {log_path})")
    return proc.returncode, out


def last_json_line(text: str, what: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict):
            return obj
    raise PhaseFailed(f"{what}: no JSON object on stdout")


def read_pctrs(run_dir: str) -> list:
    """The trainer's eval dump (`pctr \\t 1-label \\t label` per test row)."""
    (path,) = glob.glob(os.path.join(run_dir, "pred_0_*.txt"))
    with open(path) as f:
        return [float(line.split("\t", 1)[0]) for line in f]


def median_or_none(xs: list):
    return statistics.median(xs) if xs else None


def max_err(got: list, want: list, relative: bool = False) -> float:
    if not got or len(got) != len(want):
        return float("inf")
    return max(
        abs(g - w) / (max(abs(w), 1e-12) if relative else 1.0)
        for g, w in zip(got, want)
    )


# ------------------------------------------------------------ the children


def model_flags(a) -> list:
    return [
        "--model", "fm", "--log2-slots", str(a.log2_slots),
        "--set", f"model.v_dim={V_DIM}", "--set", f"model.num_fields={NNZ}",
        "--set", f"data.max_nnz={NNZ}",
    ]


def train_run(a, name: str, extra: list, train_prefix: str = "train",
              test: bool = True) -> dict:
    """One `xflow train` child in its own directory -> its summary line,
    per-step losses and times, and compile records."""
    run_dir = os.path.join(WORK, name)
    os.makedirs(run_dir)
    # beside the stderr logs: what is left to read when a phase fails
    metrics = os.path.join(LOGS, f"{name}.metrics.jsonl")
    args = [
        "xflow_tpu", "train", "--train", os.path.join(WORK, train_prefix),
        *(["--test", os.path.join(WORK, "test")] if test else []),
        *model_flags(a), "--epochs", "1", "--batch-size", str(a.batch),
        "--set", f"train.metrics_path={metrics}", "--set", "train.log_every=1",
        *extra,
    ]
    t0 = time.perf_counter()
    _, out = run_child(name, args, run_dir, 900)
    with open(metrics) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    plain = [r for r in recs if "kind" not in r]
    return {
        "dir": run_dir,
        "wall_s": round(time.perf_counter() - t0, 1),
        "summary": last_json_line(out, name),
        "losses": [r["loss"] for r in plain if "step" in r and "loss" in r],
        # step timing lands one record behind: the first value is the
        # step that compiled, the rest are steady steps
        "step_ms": [
            r["step_time_p50_ms"] for r in plain if "step_time_p50_ms" in r
        ][1:],
        "compiles": {r["program"]: r for r in recs if r.get("kind") == "compile"},
        "spans_s": {
            r["name"]: round(r["dur_ms"] / 1e3, 1)
            for r in recs if r.get("kind") == "span"
        },
    }


def step_program(run: dict) -> dict:
    """The train step's compile record (`train_step`, or the mesh
    engine's `train_step.fullshard.fm`)."""
    return next(
        (r for p, r in sorted(run["compiles"].items()) if p.startswith("train_step")),
        {},
    )


def train_line(phase: str, a, run: dict, checks: dict, **more) -> dict:
    s = run["summary"]
    return {
        "phase": phase, "ok": all(checks.values()), "checks": checks,
        "device": s.get("device"), "engine": s.get("engine"),
        "parser": s.get("parser"), "planner": s.get("planner"),
        "model": "fm", "v_dim": V_DIM, "log2_slots": a.log2_slots,
        "batch": a.batch, "nnz": NNZ,
        "steps": s.get("steps"), "bad_steps": s.get("bad_steps"),
        "losses": run["losses"], "auc": s.get("auc"),
        "ms_per_step_median": median_or_none(run["step_ms"]),
        "pallas_calls": step_program(run).get("pallas_calls"),
        "compile_s": {p: r["compile_time_s"] for p, r in run["compiles"].items()},
        # the child's whole life, its fit loop, its checkpoint spans
        "wall_s": run["wall_s"], "fit_s": s.get("seconds"),
        "spans_s": run["spans_s"], **more,
    }


def main_path_checks(a, run: dict, engine: str) -> dict:
    s = run["summary"]
    return {
        "steps": s.get("steps") == a.steps and len(run["losses"]) == a.steps,
        "engine": s.get("engine") == engine,
        "native_parser": s.get("parser") == "native",
        "no_bad_steps": s.get("bad_steps") == 0,
        "losses_finite": all(
            isinstance(x, float) and math.isfinite(x) for x in run["losses"]
        ),
        "auc": isinstance(s.get("auc"), float) and s["auc"] > a.min_auc,
    }


def on_tpu(summary: dict) -> bool:
    return (summary.get("device") or {}).get("platform") == "tpu"


def compare_runs(ref: dict, other: dict) -> tuple:
    """(checks, errors) of `other` against `ref`: per-step loss and the
    test shard's predictions, reduction-reorder tolerance."""
    errors = {
        "loss_max_rel_err": max_err(other["losses"], ref["losses"], relative=True),
        "loss_rtol": LOSS_RTOL,
        "pctr_max_abs_err": max_err(read_pctrs(other["dir"]), read_pctrs(ref["dir"])),
        "pctr_atol": PCTR_ATOL,
    }
    checks = {
        "loss_parity": errors["loss_max_rel_err"] <= LOSS_RTOL,
        "pctr_parity": errors["pctr_max_abs_err"] <= PCTR_ATOL,
    }
    return checks, errors


# ------------------------------------------------------------------ phases


def phase_gen_data(a, ctx: dict) -> dict:
    t0 = time.perf_counter()
    paths = []
    for prefix, rows, seed in (
        ("train", a.steps * a.batch, a.seed), ("test", a.batch, a.seed + 1),
    ):
        _, out = run_child(
            f"gen_{prefix}",
            ["xflow_tpu", "gen-data", prefix, "--bulk", "--shards", "1",
             "--rows", str(rows), "--fields", str(NNZ),
             "--ids-per-field", str(a.ids_per_field),
             "--zipf-alpha", str(a.zipf_alpha),
             "--seed", str(seed), "--truth-seed", str(a.seed)],
            WORK, 600,
        )
        paths += out.split()
    return {
        "phase": "gen_data", "ok": len(paths) == 2,
        "train_rows": a.steps * a.batch, "test_rows": a.batch, "fields": NNZ,
        "bytes": sum(os.path.getsize(os.path.join(WORK, p)) for p in paths),
        "seconds": round(time.perf_counter() - t0, 1),
    }


def phase_train(a, ctx: dict) -> dict:
    run = ctx["train"] = train_run(
        a, "train_sorted", ["--no-mesh", "--checkpoint-dir", "ckpt"]
    )
    s = run["summary"]
    ctx["device"] = s.get("device")
    checks = main_path_checks(a, run, "sorted")
    checks.update(
        platform_tpu=on_tpu(s),
        native_planner=s.get("planner") == "native",
        # the compiled step holds the Mosaic custom calls (gather, row
        # sum, scatter+FTRL), not their XLA stand-ins
        pallas_kernels=(step_program(run).get("pallas_calls") or 0) >= 3,
        checkpoint=bool(
            glob.glob(os.path.join(run["dir"], "ckpt", "step_*", "COMMITTED"))
        ),
    )
    return train_line("train", a, run, checks)


def phase_xla_parity(a, ctx: dict) -> dict:
    run = train_run(
        a, "train_row_major", ["--no-mesh", "--set", "data.sorted_layout=off"]
    )
    checks = main_path_checks(a, run, "row_major")
    more, errors = compare_runs(ctx["train"], run)
    checks.update(more)
    # the per-kernel gate on the device: anywhere but on a TPU it would
    # compare the XLA path with itself, so it exits 2 with ok=false
    rc, out = run_child(
        "kernel_parity", ["xflow_tpu.tools.kernel_parity"], WORK, 600,
        ok_codes=(0, 1, 2),
    )
    parity = last_json_line(out, "kernel_parity")
    checks["kernel_parity"] = rc == 0 and parity.get("ok") is True
    return train_line(
        "xla_parity", a, run, checks, **errors,
        kernel_parity={
            "backend": parity.get("backend"), "checks": parity.get("checks"),
        },
    )


def http_json(url: str, payload=None) -> dict:
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(
        urllib.request.Request(url, data=data), timeout=120
    ) as resp:
        return json.loads(resp.read())


def phase_serve(a, ctx: dict) -> dict:
    want = read_pctrs(ctx["train"]["dir"])
    with open(os.path.join(WORK, "test-00000")) as f:
        rows = [line.rstrip("\n").split("\t", 1)[1] for line in f]
    t0 = time.perf_counter()
    proc, _ = spawn(
        "serve",
        ["xflow_tpu", "serve",
         "--checkpoint-dir", os.path.join(ctx["train"]["dir"], "ckpt"),
         *model_flags(a), "--port", "0", "--max-batch", str(SERVE_MAX_BATCH),
         "--no-mesh"],
        WORK,
    )
    lines: list = []

    def read_stdout() -> None:
        for line in proc.stdout:
            lines.append(line)

    reader = threading.Thread(target=read_stdout, daemon=True)
    reader.start()
    try:
        deadline = time.monotonic() + 600
        while not lines:
            if proc.poll() is not None or time.monotonic() > deadline:
                raise PhaseFailed("serve: no ready line")
            time.sleep(0.1)
        ready = json.loads(lines[0])
        ready_s = round(time.perf_counter() - t0, 1)
        base = f"http://{ready['host']}:{ready['port']}"
        health = http_json(base + "/healthz")
        # one row, a partial batch, a full serve.max_batch
        sizes = [1, min(37, a.batch), min(SERVE_MAX_BATCH, a.batch)]
        got, latencies_ms = [], []
        for n in sizes:
            t = time.perf_counter()
            answer = http_json(
                base + "/predict", {"rows": rows[len(got):len(got) + n]}
            )
            latencies_ms.append(round((time.perf_counter() - t) * 1e3, 3))
            if len(answer["pctr"]) != n:
                raise PhaseFailed(f"serve: {len(answer['pctr'])} pCTRs for {n} rows")
            got += answer["pctr"]
    finally:
        # drained shutdown: SIGTERM, then the server's own exit code
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            rc = proc.wait()
        reader.join(timeout=30)
    err = max_err(got, want[:len(got)])
    checks = {
        "ready": ready.get("serving") is True and ready.get("step") == a.steps,
        "same_device": ready.get("device") == ctx["device"],
        "healthz": health.get("ok") is True,
        "pctr_equals_trainer": err <= SERVE_ATOL,
        # ... and equal because the model answers, not because every row
        # still predicts the same constant
        "pctrs_differ": max(got) - min(got) > 100 * SERVE_ATOL,
        "drained_exit_0": rc == 0,
    }
    return {
        "phase": "serve", "ok": all(checks.values()), "checks": checks,
        "device": ready.get("device"), "step": ready.get("step"),
        "request_rows": sizes, "request_ms": latencies_ms,
        "pctr_max_abs_err": err, "pctr_atol": SERVE_ATOL,
        "pctr_range": [min(got), max(got)],
        "ready_s": ready_s, "exit_code": rc,
    }


def phase_warm_start(a, ctx: dict) -> dict:
    """A second start of the training process, one step (the one-batch
    test shard as its train data: same shapes, so the same program): its
    step must come out of the persistent compile cache, as the compile
    record's `cache_hit` says."""
    run = train_run(a, "train_warm", ["--no-mesh"], train_prefix="test", test=False)
    first, second = step_program(ctx["train"]), step_program(run)
    checks = {
        "one_step": run["summary"].get("steps") == 1,
        "cache_hit": second.get("cache_hit") is True,
    }
    return {
        "phase": "warm_start", "ok": all(checks.values()), "checks": checks,
        # "cold" is itself a hit on a machine whose cache outlives a run
        "cold_compile_s": first.get("compile_time_s"),
        "cold_cache_hit": first.get("cache_hit"),
        "warm_compile_s": second.get("compile_time_s"),
        "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(ROOT, ".jax_cache"),
        "wall_s": run["wall_s"],
    }


def phase_mesh(a, ctx: dict) -> dict:
    """--chips 4: `xflow train` on the ('data','table') mesh over every
    device against the one-device run, plus where the state lives."""
    one = train_run(a, "train_one_device", ["--no-mesh"])
    mesh = train_run(a, "train_mesh", [])
    s = mesh["summary"]
    ctx["device"] = s.get("device")
    checks = main_path_checks(a, mesh, "fullshard")
    more, errors = compare_runs(one, mesh)
    checks.update(more)
    held = s.get("state_bytes_per_device") or []
    held_one = one["summary"].get("state_bytes_per_device") or []
    total = sum(held_one)
    args_mesh = step_program(mesh).get("argument_bytes") or 0
    args_one = step_program(one).get("argument_bytes") or 0
    checks.update(
        platform_tpu=on_tpu(s),
        devices=(s.get("device") or {}).get("count") == a.chips,
        one_device_run_on_first=total > 0 and held_one[1:] == [0] * (a.chips - 1),
        # the arrays' own shards: every chip holds total/N, none holds all
        even_shards=len(held) == a.chips and all(b * a.chips == total for b in held),
        # and the compiled step's per-device arguments shrank with them
        program_args_sharded=0 < args_mesh <= 1.25 * args_one / a.chips,
        pallas_kernels=(step_program(mesh).get("pallas_calls") or 0) >= 3,
    )
    return train_line(
        "mesh", a, mesh, checks, **errors,
        state_bytes_per_device=held, one_device_state_bytes=held_one,
        argument_bytes_per_device=args_mesh, one_device_argument_bytes=args_one,
        one_device_ms_per_step_median=median_or_none(one["step_ms"]),
    )


# -------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the sharded path, on the four-chip mesh")
    ap.add_argument("--log2-slots", type=int, default=24)
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--ids-per-field", type=int, default=2000)
    ap.add_argument("--zipf-alpha", type=float, default=1.1,
                    help="feature skew (gen-data: ~1.1 is CTR-like); hot "
                         "features are what six FTRL steps can learn")
    ap.add_argument("--min-auc", type=float, default=0.6)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()

    phases = [phase_gen_data] + (
        [phase_mesh]
        if a.chips > 1
        else [phase_train, phase_xla_parity, phase_serve, phase_warm_start]
    )
    for stale in (WORK, LOGS):  # the trainer APPENDS to its metrics file
        shutil.rmtree(stale, ignore_errors=True)
    os.makedirs(WORK)
    ctx: dict = {"device": None}
    ok = True

    def on_sigterm(signum, frame):
        raise PhaseFailed("terminated by SIGTERM")

    signal.signal(signal.SIGTERM, on_sigterm)
    try:
        for phase in phases:
            try:
                line = phase(a, ctx)
            except Exception as e:  # noqa: BLE001 — reported, and the run FAILS
                emit({"phase": phase.__name__.removeprefix("phase_"), "ok": False,
                      "error": f"{type(e).__name__}: {e}"})
                ok = False
                break  # later phases need what this one did not leave behind
            emit(line)
            ok = ok and line["ok"] is True
    finally:
        for proc in CHILDREN:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        shutil.rmtree(WORK, ignore_errors=True)
    device = ctx["device"] or {}
    final = {
        "ok": ok and device.get("platform") == "tpu",
        "device": {
            "platform": device.get("platform"),
            "kind": device.get("kind"),
            "count": device.get("count", 0),
        },
    }
    # every child has exited and every thread is joined: nothing can
    # write to stdout after this line
    emit(final)
    return 0 if final["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
