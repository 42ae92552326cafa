"""Command-line interface.

Replaces the reference's entry point and launchers:

- `xflow train` ≙ the `xflow_lr` binary's train path
  (`/root/reference/src/model/main.cc:27-45`: argv = train-prefix,
  test-prefix, model-index, epochs) plus all the knobs the reference
  hard-codes;
- `xflow launch-local` ≙ `scripts/local.sh` (single-machine cluster
  emulation) — see launch/local.py;
- `xflow gen-data` — deterministic synthetic libffm shards;
- `xflow export` — sparse nonzero-weight export from a checkpoint.

Model indices 0/1/2 (LR/FM/MVM) are accepted for reference-CLI parity;
names are preferred. Arbitrary config overrides: `--set a.b.c=value`.
"""

from __future__ import annotations

import argparse
import json
import sys

MODEL_INDEX = {"0": "lr", "1": "fm", "2": "mvm"}


def _add_common(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="dotted config override, e.g. --set optim.name=sgd")


def _add_supervise_flags(ap: argparse.ArgumentParser) -> None:
    """Supervised auto-restart knobs shared by launch-local/launch-dist
    (launch/supervise.py). --max-restarts 0 (default) keeps the plain
    single-attempt behavior."""
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="relaunch the whole job (with train.resume=true) up "
                         "to this many times after a nonzero rank exit or a "
                         "watchdog dead-rank verdict (default 0 = no "
                         "supervision)")
    ap.add_argument("--restart-backoff", type=float, default=1.0,
                    help="base seconds between restarts; doubles per attempt "
                         "with jitter, capped at 60s (default 1.0)")
    ap.add_argument("--min-uptime-s", type=float, default=0.0,
                    help="an attempt dying faster than this is treated as a "
                         "config error and NOT restarted (default 0 = always "
                         "restart while the budget lasts)")
    ap.add_argument("--allow-shrink", action="store_true",
                    help="degraded-mode supervision: a watchdog dead-HOST "
                         "verdict (unreachable across the grace window, vs a "
                         "process that merely exits) relaunches on the "
                         "surviving host set with a recomputed world size; "
                         "the elastic restore reshards the checkpoint and "
                         "re-assigns the lost rank's data shards (default: "
                         "relaunch same-shape)")


def _add_watchdog_flags(ap: argparse.ArgumentParser) -> None:
    """Liveness-watchdog knobs shared by launch-local/launch-dist
    (active with --run-dir; launch/watchdog.py). 0 = module default."""
    ap.add_argument("--straggler-factor", type=float, default=0.0,
                    help="flag a rank whose heartbeat step trails the leader "
                         "by more than this factor (default 2.0)")
    ap.add_argument("--dead-after-s", type=float, default=0.0,
                    help="flag a rank with no heartbeat for this many "
                         "seconds as dead (default 60)")
    ap.add_argument("--watchdog-poll-s", type=float, default=0.0,
                    help="heartbeat poll interval in seconds (default 2)")


def _build_config(args) -> "Config":
    from xflow_tpu.config import Config, override

    cfg = Config()
    pairs = {}
    if getattr(args, "train", None):
        pairs["data.train_path"] = args.train
    if getattr(args, "test", None):
        pairs["data.test_path"] = args.test
    if getattr(args, "model", None):
        pairs["model.name"] = MODEL_INDEX.get(args.model, args.model)
    if getattr(args, "epochs", None) is not None:
        pairs["train.epochs"] = args.epochs
    if getattr(args, "batch_size", None) is not None:
        pairs["data.batch_size"] = args.batch_size
    if getattr(args, "optimizer", None):
        pairs["optim.name"] = args.optimizer
    if getattr(args, "log2_slots", None) is not None:
        pairs["data.log2_slots"] = args.log2_slots
    if getattr(args, "checkpoint_dir", None):
        pairs["train.checkpoint_dir"] = args.checkpoint_dir
    # serve-only flags (cmd_serve's parser uses serve_* dests so the
    # launchers' unrelated --port never collides here)
    for attr, key in (
        ("serve_port", "serve.port"),
        ("serve_host", "serve.host"),
        ("serve_unix_socket", "serve.unix_socket"),
        ("serve_window_ms", "serve.window_ms"),
        ("serve_max_batch", "serve.max_batch"),
        ("serve_poll_s", "serve.reload_poll_s"),
        ("serve_metrics_path", "serve.metrics_path"),
        # fleet/router flags (cmd_serve_fleet)
        ("serve_replicas", "serve.replicas"),
        ("serve_reload_stagger_s", "serve.reload_stagger_s"),
        ("serve_route_retries", "serve.route_retries"),
        ("serve_route_deadline_ms", "serve.route_deadline_ms"),
        ("serve_route_hedge_ms", "serve.route_hedge_ms"),
        ("serve_eject_failures", "serve.eject_failures"),
        ("serve_circuit_open_s", "serve.circuit_open_s"),
        ("serve_health_poll_s", "serve.health_poll_s"),
    ):
        v = getattr(args, attr, None)
        if v is not None:
            pairs[key] = v
    for item in args.set:
        k, _, v = item.partition("=")
        pairs[k] = v
    return override(cfg, **pairs)


def cmd_train(args) -> int:
    from xflow_tpu.parallel.distributed import maybe_initialize

    rank = maybe_initialize(args.coordinator, args.num_processes, args.process_id)
    cfg = _build_config(args)

    import jax

    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.telemetry import device_triple
    from xflow_tpu.train.trainer import Trainer

    mesh = None
    if not args.no_mesh and len(jax.devices()) > 1:
        mesh = make_mesh(cfg)
    trainer = Trainer(cfg, mesh=mesh, process_index=rank)
    resumed = trainer.maybe_restore()
    if resumed:
        print(f"resumed from step {int(trainer.state.step)}", file=sys.stderr)
    res = trainer.fit()
    summary = {
        "rank": rank,
        # where and through what the run went: a run that landed on the
        # CPU, on the row-major engine or on the Python stand-ins of the
        # native parser/planner must not look like any other
        "device": device_triple(),
        "state_bytes_per_device": _state_bytes_per_device(trainer.state),
        "engine": trainer.engine,
        "planner": trainer.planner,
        "steps": res.steps,
        "epochs": res.epochs,
        "examples": res.examples,
        "seconds": round(res.seconds, 3),
        "examples_per_sec": round(res.examples_per_sec, 1),
        "last_loss": res.last_loss,
        "occupancy": res.occupancy,
        "bad_steps": res.bad_steps,
    }

    def report() -> int:
        # read last: the eval pass opens shards too
        summary["parser"] = _parsers_that_ran()
        if rank == 0:
            print(json.dumps(summary))
        return 0

    if res.interrupted:
        # preempted: checkpoint was saved at the last step boundary; skip
        # the eval pass and report, so the grace period isn't spent there
        summary["interrupted"] = res.interrupted
        return report()
    # reference: only rank 0 runs predict (lr_worker.cc:211-215); here the
    # eval contains collectives, so every process participates and rank 0
    # reports/dumps
    if cfg.data.test_path:
        import jax as _jax

        if rank == 0 or _jax.process_count() > 1:
            auc, ll = trainer.evaluate()
            if rank == 0:
                summary["auc"], summary["logloss"] = auc, ll
                print(f"logloss: {ll}\tauc = {auc}", file=sys.stderr)
    return report()


def _state_bytes_per_device(state) -> list:
    """Bytes of tables + optimizer state on each local device, in
    device order: on a mesh every entry is its 1/N share, and a state
    that sits whole on the first device shows as such."""
    import jax

    held = {d.id: 0 for d in jax.local_devices()}
    for leaf in jax.tree.leaves((state.tables, state.opt_state)):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return [held[i] for i in sorted(held)]


def _parsers_that_ran() -> str:
    """What read this process's shards so far — "native", "python",
    "cache" (packed .xfc shards, no parser at all), "+"-joined when more
    than one did — from the pipeline's provenance counters."""
    from xflow_tpu.telemetry import default_registry

    counters = default_registry().snapshot()
    return "+".join(
        name
        for name, key in (
            ("native", "data.parser_native_shards"),
            ("python", "data.parser_python_shards"),
            ("cache", "data.cache_shards"),
        )
        if counters.get(key)
    )


def cmd_serve(args) -> int:
    """`xflow serve`: online pCTR inference over a committed checkpoint
    (docs/SERVING.md) — microbatched HTTP/unix-socket serving with hot
    reload when a newer checkpoint commits. The model/data config must
    match the checkpoint's (same contract as `xflow export`); pass the
    training run's --set overrides."""
    cfg = _build_config(args)
    if not cfg.train.checkpoint_dir:
        print("serve: --checkpoint-dir is required", file=sys.stderr)
        return 2
    import jax

    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.serve.server import serve_main

    mesh = None
    if not args.no_mesh and len(jax.devices()) > 1:
        mesh = make_mesh(cfg)
        if cfg.serve.max_batch % mesh.shape["data"] != 0:
            print(
                f"serve: serve.max_batch={cfg.serve.max_batch} must divide "
                f"by the mesh data axis ({mesh.shape['data']})",
                file=sys.stderr,
            )
            return 2
    try:
        return serve_main(cfg, mesh=mesh)
    except (FileNotFoundError, RuntimeError) as e:
        print(f"serve: cannot load a checkpoint: {e}", file=sys.stderr)
        return 1


def cmd_serve_fleet(args) -> int:
    """`xflow serve-fleet`: N supervised `xflow serve` replicas on
    distinct ports behind the health-checked failover router
    (serve/fleet.py, docs/SERVING.md "Fleet") — retries, circuit
    breaking, staggered hot reload, ordered drain. The serving analog
    of `launch-local --max-restarts`."""
    cfg = _build_config(args)
    if not cfg.train.checkpoint_dir:
        print("serve-fleet: --checkpoint-dir is required", file=sys.stderr)
        return 2

    from xflow_tpu.serve.fleet import fleet_main

    # the per-replica `xflow serve` argv: every serve-relevant flag the
    # operator passed, minus the fleet-owned ones (--port is per
    # replica, --metrics-path per replica under --run-dir)
    serve_args = ["--checkpoint-dir", args.checkpoint_dir]
    if args.serve_host:
        # the replicas must bind the same host the router dials
        serve_args += ["--host", args.serve_host]
    if args.model:
        serve_args += ["--model", args.model]
    if args.log2_slots is not None:
        serve_args += ["--log2-slots", str(args.log2_slots)]
    if args.serve_window_ms is not None:
        serve_args += ["--window-ms", str(args.serve_window_ms)]
    if args.serve_max_batch is not None:
        serve_args += ["--max-batch", str(args.serve_max_batch)]
    if args.serve_poll_s is not None:
        serve_args += ["--poll-s", str(args.serve_poll_s)]
    if args.no_mesh:
        serve_args += ["--no-mesh"]
    for item in args.set:
        serve_args += ["--set", item]
    return fleet_main(
        cfg, serve_args, run_dir=args.run_dir,
        max_restarts=args.max_restarts,
        restart_backoff=args.restart_backoff,
        min_uptime_s=args.min_uptime_s,
    )


def cmd_gen_data(args) -> int:
    from xflow_tpu.data.synth import generate_shards, generate_shards_bulk

    if args.bulk:
        if args.truth != "linear":
            print("--bulk supports only the linear truth (the vectorized "
                  "writer has no field-pair mode)", file=sys.stderr)
            return 2
        paths, _ = generate_shards_bulk(
            args.out_prefix, args.shards, args.rows,
            num_fields=args.fields, ids_per_field=args.ids_per_field,
            seed=args.seed, truth_seed=args.truth_seed,
            zipf_alpha=args.zipf_alpha,
        )
        print("\n".join(paths))
        return 0
    paths = generate_shards(
        args.out_prefix, args.shards, args.rows,
        num_fields=args.fields, ids_per_field=args.ids_per_field, seed=args.seed,
        truth_seed=args.truth_seed, zipf_alpha=args.zipf_alpha,
        truth=args.truth,
    )
    print("\n".join(paths))
    return 0


def cmd_export(args) -> int:
    import os

    import numpy as np

    from xflow_tpu.train.checkpoint import export_sparse_array, latest_step

    step = latest_step(args.checkpoint_dir)
    if step is None:
        print(f"no committed checkpoint in {args.checkpoint_dir}", file=sys.stderr)
        return 1
    data = np.load(os.path.join(args.checkpoint_dir, f"step_{step}", "state.npz"))
    key = f"tables/{args.table}"
    if key in data:
        arr = data[key]
    elif args.table in ("w", "v") and "tables/wv" in data:
        # fused FM layout: w is column 0, v the rest (models/fm.py)
        wv = data["tables/wv"]
        arr = wv[:, 0] if args.table == "w" else wv[:, 1:]
    else:
        have = sorted(k.split("/", 1)[1] for k in data.files if k.startswith("tables/"))
        print(f"no table {args.table!r} in checkpoint; have {have}", file=sys.stderr)
        return 1
    n = export_sparse_array(arr, args.out)
    print(json.dumps({"step": step, "table": args.table, "nonzero": n}))
    return 0


def cmd_collisions(args) -> int:
    from xflow_tpu.tools.collisions import measure

    print(json.dumps(measure(args.paths, args.log2_slots, args.salt)))
    return 0


def cmd_launch_local(args) -> int:
    from xflow_tpu.launch.local import launch_local

    return launch_local(
        args.num_processes, args.forward, port=args.port, run_dir=args.run_dir,
        straggler_factor=args.straggler_factor, dead_after_s=args.dead_after_s,
        watchdog_poll_s=args.watchdog_poll_s,
        max_restarts=args.max_restarts, restart_backoff=args.restart_backoff,
        min_uptime_s=args.min_uptime_s, allow_shrink=args.allow_shrink,
    )


def cmd_launch_multislice(args) -> int:
    from xflow_tpu.parallel.multislice import launch_multislice

    return launch_multislice(
        args.slices, args.forward, run_dir=args.run_dir,
        straggler_factor=args.straggler_factor, dead_after_s=args.dead_after_s,
        watchdog_poll_s=args.watchdog_poll_s,
        max_restarts=args.max_restarts, restart_backoff=args.restart_backoff,
        min_uptime_s=args.min_uptime_s,
    )


def cmd_launch_dist(args) -> int:
    from xflow_tpu.launch.dist import launch_dist, parse_hosts

    hosts = list(args.host or [])
    if args.hosts:
        hosts = parse_hosts(args.hosts) + hosts
    if len(hosts) < 2:
        print("launch-dist needs >= 2 hosts (--hosts FILE or repeated --host)",
              file=sys.stderr)
        return 2
    for kv in args.env or []:
        if "=" not in kv:
            print(f"--env expects K=V, got {kv!r}", file=sys.stderr)
            return 2
    env_extra = dict(kv.split("=", 1) for kv in (args.env or []))
    return launch_dist(
        hosts, args.forward, port=args.port, ssh_cmd=args.ssh_cmd,
        workdir=args.workdir, python=args.python, env_extra=env_extra,
        dry_run=args.dry_run, run_dir=args.run_dir,
        straggler_factor=args.straggler_factor, dead_after_s=args.dead_after_s,
        watchdog_poll_s=args.watchdog_poll_s,
        max_restarts=args.max_restarts, restart_backoff=args.restart_backoff,
        min_uptime_s=args.min_uptime_s, allow_shrink=args.allow_shrink,
    )


def _apply_platform_env() -> None:
    """Honor JAX_PLATFORMS / XFLOW_NUM_CPU_DEVICES through the config
    API too, which wins over any ambient site configuration. With
    neither set JAX picks its default: the TPU where there is one."""
    import os

    plat = os.environ.get("JAX_PLATFORMS")
    ncpu = os.environ.get("XFLOW_NUM_CPU_DEVICES")
    if plat or ncpu:
        import jax

        if plat:
            jax.config.update("jax_platforms", plat)
        if ncpu:
            jax.config.update("jax_num_cpu_devices", int(ncpu))


def main(argv=None) -> int:
    from xflow_tpu.compile_cache import enable_compile_cache

    _apply_platform_env()
    enable_compile_cache()
    ap = argparse.ArgumentParser(prog="xflow", description="TPU-native sparse CTR training")
    sub = ap.add_subparsers(dest="cmd", required=True)

    tr = sub.add_parser("train", help="train a model (LR/FM/FFM/MVM)")
    tr.add_argument("--train", required=True, help="train shard prefix (reads <prefix>-%%05d)")
    tr.add_argument("--test", default="", help="test shard prefix")
    tr.add_argument("--model", default="lr",
                    help="lr|fm|mvm|ffm or reference index 0|1|2")
    tr.add_argument("--epochs", type=int, default=None)
    tr.add_argument("--batch-size", type=int, default=None)
    tr.add_argument("--optimizer", default=None, help="ftrl|sgd")
    tr.add_argument("--log2-slots", type=int, default=None)
    tr.add_argument("--checkpoint-dir", default=None)
    tr.add_argument("--no-mesh", action="store_true", help="force single-device")
    tr.add_argument("--coordinator", default=None, help="host:port of rank 0 (multi-host)")
    tr.add_argument("--num-processes", type=int, default=None)
    tr.add_argument("--process-id", type=int, default=None)
    _add_common(tr)
    tr.set_defaults(fn=cmd_train)

    sv = sub.add_parser(
        "serve",
        help="online pCTR inference over a committed checkpoint "
             "(microbatching + hot reload; docs/SERVING.md)",
    )
    sv.add_argument("--checkpoint-dir", required=True,
                    help="run dir holding COMMITTED checkpoints; the newest "
                         "loads at startup and newer commits hot-reload")
    sv.add_argument("--model", default=None,
                    help="model of the checkpoint (lr|fm|mvm|ffm); must match")
    sv.add_argument("--log2-slots", type=int, default=None)
    sv.add_argument("--port", dest="serve_port", type=int, default=None,
                    help="TCP port (default 8000; 0 = pick free, reported in "
                         "the ready line; -1 = unix socket only)")
    sv.add_argument("--host", dest="serve_host", default=None)
    sv.add_argument("--unix-socket", dest="serve_unix_socket", default=None,
                    help="also (or only) serve HTTP over this AF_UNIX path")
    sv.add_argument("--window-ms", dest="serve_window_ms", type=float,
                    default=None,
                    help="microbatch coalescing window (default 2.0)")
    sv.add_argument("--max-batch", dest="serve_max_batch", type=int,
                    default=None,
                    help="rows per device batch = compiled batch shape "
                         "(default 256)")
    sv.add_argument("--poll-s", dest="serve_poll_s", type=float, default=None,
                    help="hot-reload checkpoint poll interval (default 2.0)")
    sv.add_argument("--metrics-path", dest="serve_metrics_path", default=None,
                    help="kind=serve telemetry JSONL (QPS/latency windows + "
                         "reload events; tools/metrics_report.py reads it)")
    sv.add_argument("--no-mesh", action="store_true", help="force single-device")
    _add_common(sv)
    sv.set_defaults(fn=cmd_serve)

    sf = sub.add_parser(
        "serve-fleet",
        help="N supervised serve replicas behind a health-checked "
             "failover router (retries, circuit breaking, staggered hot "
             "reload; docs/SERVING.md)",
    )
    sf.add_argument("--checkpoint-dir", required=True,
                    help="run dir holding COMMITTED checkpoints (every "
                         "replica loads + hot-reloads from it)")
    sf.add_argument("--model", default=None,
                    help="model of the checkpoint (lr|fm|mvm|ffm); must match")
    sf.add_argument("--log2-slots", type=int, default=None)
    sf.add_argument("--replicas", dest="serve_replicas", type=int, default=None,
                    help="replica count (default 2); each is one "
                         "supervised `xflow serve` on its own port")
    sf.add_argument("--port", dest="serve_port", type=int, default=None,
                    help="ROUTER port, the client-facing one (default "
                         "8000; 0 = pick free, reported in the ready "
                         "line); replicas always pick their own")
    sf.add_argument("--host", dest="serve_host", default=None)
    sf.add_argument("--window-ms", dest="serve_window_ms", type=float,
                    default=None,
                    help="per-replica microbatch coalescing window")
    sf.add_argument("--max-batch", dest="serve_max_batch", type=int,
                    default=None, help="per-replica rows per device batch")
    sf.add_argument("--poll-s", dest="serve_poll_s", type=float, default=None,
                    help="per-replica hot-reload poll interval")
    sf.add_argument("--reload-stagger-s", dest="serve_reload_stagger_s",
                    type=float, default=None,
                    help="replica k delays a noticed reload by k * this "
                         "(default 1.0) — never every replica swapping "
                         "at once")
    sf.add_argument("--retries", dest="serve_route_retries", type=int,
                    default=None,
                    help="router retries on another replica after a "
                         "connect failure / 503 (default 2)")
    sf.add_argument("--deadline-ms", dest="serve_route_deadline_ms",
                    type=float, default=None,
                    help="per-request routing budget (default 2000)")
    sf.add_argument("--hedge-ms", dest="serve_route_hedge_ms", type=float,
                    default=None,
                    help="tail-latency hedge delay (default 0 = off)")
    sf.add_argument("--eject-failures", dest="serve_eject_failures", type=int,
                    default=None,
                    help="consecutive failures ejecting a replica into "
                         "circuit OPEN (default 3)")
    sf.add_argument("--circuit-open-s", dest="serve_circuit_open_s",
                    type=float, default=None,
                    help="OPEN hold before the half-open probe (default 2)")
    sf.add_argument("--health-poll-s", dest="serve_health_poll_s", type=float,
                    default=None,
                    help="replica /healthz poll cadence (default 0.5)")
    sf.add_argument("--run-dir", default="",
                    help="collect fleet telemetry here: "
                         "<run-dir>/serve_replica<k>.jsonl + "
                         "serve_router.jsonl + replica<k>.log, one shared "
                         "run_id; summarize with tools/metrics_report.py")
    sf.add_argument("--max-restarts", type=int, default=0,
                    help="per-replica supervised restarts after a crash "
                         "(default 0 = a dead replica stays dead)")
    sf.add_argument("--restart-backoff", type=float, default=1.0,
                    help="base seconds between one replica's restarts "
                         "(exponential + jitter, capped 60s)")
    sf.add_argument("--min-uptime-s", type=float, default=0.0,
                    help="a replica dying faster than this stops its "
                         "supervision (crash loop = config error)")
    sf.add_argument("--no-mesh", action="store_true",
                    help="force single-device replicas")
    _add_common(sf)
    sf.set_defaults(fn=cmd_serve_fleet)

    gd = sub.add_parser("gen-data", help="generate synthetic libffm shards")
    gd.add_argument("out_prefix")
    gd.add_argument("--shards", type=int, default=3)
    gd.add_argument("--rows", type=int, default=1000)
    gd.add_argument("--fields", type=int, default=18)
    gd.add_argument("--ids-per-field", type=int, default=500)
    gd.add_argument("--seed", type=int, default=0)
    gd.add_argument("--truth-seed", type=int, default=None,
                    help="seed for the planted ground truth (default: --seed); use the "
                         "same value for train/test splits generated with different --seed")
    gd.add_argument("--zipf-alpha", type=float, default=0.0,
                    help="power-law feature skew (0 = uniform; ~1.1 ≈ CTR-like)")
    gd.add_argument("--truth", default="linear",
                    help="planted concept: linear | ffm (field-pair "
                         "interactions with non-separable signs — the "
                         "field-aware-model learnability gate)")
    gd.add_argument("--bulk", action="store_true",
                    help="chunked vectorized writer for realistic-scale datasets "
                         "(~30x faster; different RNG stream than the default)")
    gd.set_defaults(fn=cmd_gen_data)

    ex = sub.add_parser("export", help="export nonzero weights from a checkpoint")
    ex.add_argument("checkpoint_dir")
    ex.add_argument("--table", default="w")
    ex.add_argument("--out", required=True)
    ex.set_defaults(fn=cmd_export)

    co = sub.add_parser("collisions", help="measure feature-hash collision rate on libffm files")
    co.add_argument("paths", nargs="+")
    co.add_argument("--log2-slots", type=int, default=22)
    co.add_argument("--salt", type=int, default=0)
    co.set_defaults(fn=cmd_collisions)

    ll = sub.add_parser("launch-local", help="fork a local multi-process cluster (scripts/local.sh analog)")
    ll.add_argument("--num-processes", type=int, default=2)
    ll.add_argument("--port", type=int, default=0, help="coordinator port (0 = pick free)")
    ll.add_argument("--run-dir", default="",
                    help="collect per-rank telemetry here: each rank writes "
                         "<run-dir>/metrics_rank<k>.jsonl (overrides any "
                         "train.metrics_path in the forwarded args) and all "
                         "ranks share one run_id; summarize with "
                         "tools/metrics_report.py")
    _add_watchdog_flags(ll)
    _add_supervise_flags(ll)
    ll.add_argument("forward", nargs=argparse.REMAINDER,
                    help="-- followed by `xflow train` args to run in every process")
    ll.set_defaults(fn=cmd_launch_local)

    lm = sub.add_parser(
        "launch-multislice",
        help="emulate N slices with bounded-staleness table sync "
             "across them (sync.mode/staleness_k; each slice is an "
             "independent supervised `xflow train`; "
             "docs/DISTRIBUTED.md 'Multi-slice bounded staleness')",
    )
    lm.add_argument("--slices", type=int, default=2,
                    help="slice count (default 2); each slice is its own "
                         "single-process training world exchanging table "
                         "deltas via <run-dir>/sync")
    lm.add_argument("--run-dir", required=True,
                    help="REQUIRED shared run dir: the sync tier lives in "
                         "<run-dir>/sync (deltas, snapshots, "
                         "membership.json) and slice j writes "
                         "<run-dir>/metrics_rank<j>.jsonl + "
                         "heartbeat_rank<j>.jsonl; summarize with "
                         "tools/metrics_report.py")
    _add_watchdog_flags(lm)
    _add_supervise_flags(lm)
    lm.add_argument("forward", nargs=argparse.REMAINDER,
                    help="-- followed by `xflow train` args for every "
                         "slice; the literal {slice} substitutes to the "
                         "slice index (per-slice --train prefix / "
                         "--checkpoint-dir)")
    lm.set_defaults(fn=cmd_launch_multislice)

    ld = sub.add_parser(
        "launch-dist",
        help="start one rank per machine over ssh (run_ps_dist.sh analog; "
             "see docs/DISTRIBUTED.md)",
    )
    ld.add_argument("--hosts", help="hosts file: one host per line, first = rank 0 "
                                    "(scripts/hosts shape)")
    ld.add_argument("--host", action="append",
                    help="repeatable inline host (appended after --hosts entries)")
    ld.add_argument("--port", type=int, default=29431, help="coordinator port on host 0")
    ld.add_argument("--ssh-cmd", default="ssh",
                    help="remote runner prefix (default ssh; e.g. 'ssh -i key')")
    ld.add_argument("--workdir", default="",
                    help="remote working dir; {rank}/{host} placeholders supported")
    ld.add_argument("--python", default="", help="remote python (default python3)")
    ld.add_argument("--env", action="append", metavar="K=V",
                    help="extra env for every rank (repeatable)")
    ld.add_argument("--run-dir", default="",
                    help="REMOTE dir (shared filesystem recommended) for "
                         "per-rank telemetry: each rank writes "
                         "<run-dir>/metrics_rank<k>.jsonl and all ranks share "
                         "one run_id (XFLOW_RUN_ID); summarize with "
                         "tools/metrics_report.py")
    ld.add_argument("--dry-run", action="store_true",
                    help="print the per-host command lines instead of running")
    _add_watchdog_flags(ld)
    _add_supervise_flags(ld)
    ld.add_argument("forward", nargs=argparse.REMAINDER,
                    help="-- followed by `xflow train` args to run on every host")
    ld.set_defaults(fn=cmd_launch_dist)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
