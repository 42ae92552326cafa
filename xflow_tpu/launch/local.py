"""Local multi-process cluster emulation.

The reference's `scripts/local.sh:16-35` forks one scheduler + S servers
+ W workers of the same binary on 127.0.0.1 with `DMLC_*` role env vars.
The SPMD analog forks N identical `xflow train` processes pointed at a
local coordinator; rank k reads shard `<prefix>-%05d` % k (same
convention as `lr_worker.cc:210`). Each process sees only its own
devices (CPU here), so this exercises the true multi-process path:
rendezvous, global mesh, cross-process collectives.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def rank_metrics_args(run_dir: str, rank: int) -> list[str]:
    """Extra `xflow train` args pointing rank `rank`'s metrics AND
    heartbeat JSONL into the run dir — ONE file per rank per stream
    (two ranks appending to one file would interleave mid-line under
    concurrent flush). Shared by launch-local and launch-dist so the
    layout (`<run_dir>/metrics_rank<k>.jsonl` +
    `<run_dir>/heartbeat_rank<k>.jsonl`, what tools/metrics_report.py
    globs and the run watchdog polls) is defined once."""
    if not run_dir:
        return []
    path = os.path.join(run_dir, f"metrics_rank{rank}.jsonl")
    hb = os.path.join(run_dir, f"heartbeat_rank{rank}.jsonl")
    return [
        "--set", f"train.metrics_path={path}",
        "--set", f"train.heartbeat_path={hb}",
    ]


def resolve_launch_run_id() -> str:
    """The run id every rank of this launch stamps: honor an
    operator-exported XFLOW_RUN_ID, else mint one PER LAUNCH (two
    launches from one driver process must not share an id, so this is
    telemetry.new_run_id, not the process-cached resolve_run_id)."""
    from xflow_tpu.telemetry import new_run_id

    return new_run_id()


def _teardown(procs) -> None:
    """TERM-then-KILL every live rank (the shared escalation,
    launch/supervise.terminate_procs)."""
    from xflow_tpu.launch.supervise import terminate_procs

    terminate_procs(procs)


def _launch_local_once(
    num_processes: int,
    forward_args: list[str],
    port: int = 0,
    run_dir: str = "",
    straggler_factor: float = 0.0,
    dead_after_s: float = 0.0,
    watchdog_poll_s: float = 0.0,
    run_id: str = "",
    gen: int = 0,
    on_dead_row=None,
    orig_world: int = 0,
) -> int:
    """One attempt: fork the ranks, watch them, return the job's exit
    code. FAIL-FAST like launch-dist: SPMD peers of a dead rank block
    in collectives forever, so the first nonzero rank exit — or a
    watchdog dead/missing verdict (a WEDGED rank, which never exits on
    its own) — tears the whole job down; the supervision wrapper
    (`launch_local`) decides whether to relaunch."""
    port = port or _free_port()
    coordinator = f"127.0.0.1:{port}"
    watchdog = None
    dead_verdict = threading.Event()
    if run_dir:
        os.makedirs(run_dir, exist_ok=True)
        # liveness watchdog over the ranks' heartbeat streams: flags
        # dead ranks and stragglers while the run is still going
        # (launch/watchdog.py; <= 0 knobs take the module defaults).
        # The on_dead policy only SETS a flag (and hands the status row
        # to the supervisor's dead-host tracker, --allow-shrink) —
        # teardown happens on the launcher thread below, never on the
        # poller thread.
        from xflow_tpu.launch.watchdog import RunWatchdog

        def on_dead(row):
            if on_dead_row is not None:
                on_dead_row(row)
            dead_verdict.set()

        watchdog = RunWatchdog(
            run_dir,
            num_ranks=num_processes,
            straggler_factor=straggler_factor,
            dead_after_s=dead_after_s,
            poll_s=watchdog_poll_s,
            run_id=run_id,
            on_dead=on_dead,
            gen=gen,
        )
        watchdog.start()
    # Children MUST default to CPU: inheriting an ambient accelerator
    # platform would land every child on the same device, the world
    # would never form, and each child would silently train shard 0 as
    # its own rank 0. Real multi-host accelerator launches opt in via
    # XFLOW_LAUNCH_PLATFORM; parallel/distributed.py's process-count
    # assert catches any remaining mismatch.
    platform = os.environ.get("XFLOW_LAUNCH_PLATFORM", "cpu")
    print(
        f"launch-local: {num_processes} rank(s) on JAX_PLATFORMS={platform}",
        file=sys.stderr,
    )
    procs = []
    for rank in range(num_processes):
        env = dict(os.environ)
        env.update(
            XFLOW_COORDINATOR=coordinator,
            XFLOW_NUM_PROCESSES=str(num_processes),
            # the launch's ORIGINAL rank count: a shrunk relaunch that
            # has no committed data_state yet (death before the first
            # checkpoint) still learns the full shard set from this —
            # without it the survivors would silently train a subset
            XFLOW_ORIG_WORLD=str(orig_world or num_processes),
            XFLOW_PROCESS_ID=str(rank),
            XFLOW_RUN_ID=run_id,
            # restart generation: stamped into every JSONL record the
            # rank emits (jsonl.JsonlAppender) so metrics_report.py can
            # segment the multi-generation streams of a supervised run
            XFLOW_RESTART_GEN=str(gen),
            JAX_PLATFORMS=platform,
        )
        cmd = [
            sys.executable, "-m", "xflow_tpu", "train",
            *forward_args, *rank_metrics_args(run_dir, rank),
        ]
        procs.append(subprocess.Popen(cmd, env=env))
    from xflow_tpu.launch.supervise import wait_fail_fast

    try:
        return wait_fail_fast(
            procs, _teardown, dead_verdict=dead_verdict, label="launch-local"
        )
    except KeyboardInterrupt:
        _teardown(procs)
        raise
    finally:
        if watchdog is not None:
            watchdog.stop()


def launch_local(
    num_processes: int,
    forward_args: list[str],
    port: int = 0,
    run_dir: str = "",
    straggler_factor: float = 0.0,
    dead_after_s: float = 0.0,
    watchdog_poll_s: float = 0.0,
    max_restarts: int = 0,
    restart_backoff: float = 1.0,
    min_uptime_s: float = 0.0,
    allow_shrink: bool = False,
) -> int:
    """Run the local cluster under the supervision loop
    (launch/supervise.py): on a nonzero rank exit or a watchdog
    dead-rank verdict the whole job is torn down and — while the
    ``--max-restarts`` budget lasts — relaunched with
    ``train.resume=true`` under the SAME run dir and run id, the
    restart generation stamped into every record. With
    ``--allow-shrink``, a watchdog dead/missing verdict (the emulated
    host-loss: a WEDGED rank, vs a dead process that merely exits)
    relaunches with a SHRUNK world — the surviving rank count, ranks
    renumbered 0..M-1 — and the elastic restore reshards the
    checkpoint and re-assigns the data shards so the full record set
    stays covered (docs/ROBUSTNESS.md "Host lost"). max_restarts=0 is
    one plain un-supervised attempt."""
    from xflow_tpu.launch.supervise import (
        DeadHostTracker,
        resume_forward_args,
        supervise,
    )

    if forward_args and forward_args[0] == "--":
        forward_args = forward_args[1:]
    # one run id across all ranks AND all restart generations: their
    # metrics/quarantine/heartbeat JSONL streams join on it, and the
    # `gen` stamp keeps the generations apart within it
    run_id = resolve_launch_run_id()
    tracker = DeadHostTracker(allow_shrink)

    def attempt(gen: int) -> int:
        n = tracker.shrunk_world(num_processes)
        if n < num_processes:
            print(
                f"launch-local: relaunching generation {gen} DEGRADED at "
                f"{n}/{num_processes} rank(s) (--allow-shrink; "
                f"{len(tracker.lost)} emulated host(s) lost)",
                file=sys.stderr,
            )
        args = forward_args if gen == 0 else resume_forward_args(forward_args)
        return _launch_local_once(
            n,
            args,
            port=port,
            run_dir=run_dir,
            straggler_factor=straggler_factor,
            dead_after_s=dead_after_s,
            watchdog_poll_s=watchdog_poll_s,
            run_id=run_id,
            gen=gen,
            # one-loss-per-attempt policy (culprit ordering) lives on
            # the tracker; a local "host" is an emulated process slot
            on_dead_row=tracker.attempt_recorder(gen=gen),
            orig_world=num_processes,
        )

    return supervise(
        attempt,
        max_restarts=max_restarts,
        restart_backoff=restart_backoff,
        min_uptime_s=min_uptime_s,
        label="launch-local",
    )
