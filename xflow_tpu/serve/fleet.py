"""The serving fleet: N supervised replicas behind the failover router
(`xflow serve-fleet`, docs/SERVING.md "Fleet").

This is the serving analog of PR 4's supervised training launch: the
training tier's premise — no single process may take the job down —
applied to the tier that faces users. One fleet process owns:

- **N replica subprocesses**, each a plain `xflow serve` on its own
  (pre-picked, stable) port, each wrapped in its OWN supervision loop
  (launch/supervise.supervise: restart budget, exponential backoff,
  min-uptime crash-loop stop). A SIGKILLed replica relaunches with the
  NEXT restart generation stamped into every JSONL record it writes
  (XFLOW_RESTART_GEN — the PR 4 machinery verbatim), while its
  siblings keep serving: the client sees retries, not an outage.
- **Stable identity**: replica k exports XFLOW_REPLICA=k,
  XFLOW_REPLICA_PORT=<port>, XFLOW_PROCESS_ID=k under ONE shared
  XFLOW_RUN_ID, so the fleet's serve streams are distinct per replica
  and joinable per run (tools/metrics_report.py gates on it).
- **Staggered hot reload**: replica k exports XFLOW_RELOAD_STAGGER_S =
  k * serve.reload_stagger_s, so a newly committed checkpoint swaps
  through the fleet one replica at a time — never every replica paused
  on the same restore.
- **The router** (serve/router.py), in-process: health-checked
  round-robin, circuit breaking, retries, hedging — the client-facing
  port.
- **Ordered drain**: SIGTERM drains the ROUTER first (stop admitting,
  finish in-flight), and only then SIGTERMs the replicas (each drains
  its own backlog) — a deploy-style shutdown drops zero requests. The
  ordering lives in `drain_fleet` so tests pin it with fakes.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Optional

from xflow_tpu.config import Config


def _free_port(host: str = "127.0.0.1") -> int:
    s = socket.socket()
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


def replica_env(
    base: dict, idx: int, port: int, run_id: str, gen: int, stagger_s: float,
    world: int = 1,
) -> dict:
    """The env one replica attempt launches with — the fleet's whole
    identity/stagger contract in one testable place."""
    env = dict(base)
    env.update(
        XFLOW_RUN_ID=run_id,
        # rank stamp = replica index: serve streams key (run_id, rank)
        # apart without any report-tool change
        XFLOW_PROCESS_ID=str(idx),
        # the fleet's `world` = its replica count (rank < world holds in
        # metrics_report --check); serving never rendezvouses, so the
        # var only feeds the telemetry stamp here
        XFLOW_NUM_PROCESSES=str(max(int(world), 1)),
        XFLOW_RESTART_GEN=str(gen),
        XFLOW_REPLICA=str(idx),
        XFLOW_REPLICA_PORT=str(port),
        XFLOW_RELOAD_STAGGER_S=str(idx * max(stagger_s, 0.0)),
        JAX_PLATFORMS=_replica_platform(env),
    )
    return env


def _replica_platform(env) -> str:
    """Replicas default to CPU like launch-local's children: N serve
    processes inheriting one ambient accelerator would fight over it;
    real accelerator fleets opt in via XFLOW_LAUNCH_PLATFORM."""
    return env.get("XFLOW_LAUNCH_PLATFORM", env.get("JAX_PLATFORMS", "cpu"))


class ReplicaSupervisor:
    """One replica's supervision loop on its own thread.

    Each attempt: spawn `xflow serve --port <fixed>` with the fleet
    identity env, wait for the ready line (startup failure = nonzero
    attempt), then wait for exit. The port never changes across
    restarts, so the router's backend address stays valid through every
    relaunch — recovery is the health loop noticing /healthz answers
    again, no re-registration step."""

    def __init__(
        self,
        idx: int,
        port: int,
        serve_args: list,
        run_id: str,
        stagger_s: float,
        world: int = 1,
        max_restarts: int = 0,
        restart_backoff: float = 1.0,
        min_uptime_s: float = 0.0,
        log_path: str = "",
        on_ready=None,
    ):
        self.idx = int(idx)
        self.port = int(port)
        self._serve_args = list(serve_args)
        self._run_id = run_id
        self._stagger_s = stagger_s
        self._world = world
        self._max_restarts = max_restarts
        self._restart_backoff = restart_backoff
        self._min_uptime_s = min_uptime_s
        self._log_path = log_path
        self._on_ready = on_ready
        self._proc: Optional[subprocess.Popen] = None
        self._proc_lock = threading.Lock()
        self._stopping = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"xflow-fleet-replica{idx}"
        )
        self.rc: Optional[int] = None
        self.generations = 0  # attempts launched (restarts = gens - 1)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        self._thread.start()

    def _spawn(self, gen: int) -> subprocess.Popen:
        env = replica_env(
            os.environ, self.idx, self.port, self._run_id, gen,
            self._stagger_s, world=self._world,
        )
        cmd = [
            sys.executable, "-m", "xflow_tpu", "serve",
            *self._serve_args, "--port", str(self.port),
        ]
        log = (
            open(self._log_path, "a")
            if self._log_path
            else subprocess.DEVNULL
        )
        try:
            return subprocess.Popen(
                cmd, env=env, stdout=subprocess.PIPE, stderr=log, text=True
            )
        finally:
            if log is not subprocess.DEVNULL:
                log.close()  # the child holds its own fd now

    def _attempt(self, gen: int) -> int:
        if self._stopping.is_set():
            return 0  # woken out of a backoff by shutdown: no relaunch
        self.generations = gen + 1
        proc = self._spawn(gen)
        with self._proc_lock:
            self._proc = proc
        ready = None
        if proc.stdout is not None:
            # scan stdout for the ready JSON line, tolerating stray
            # non-JSON noise (a dependency warning must not read as a
            # failed startup)
            for line in proc.stdout:
                line = line.strip()
                if not line:
                    continue
                try:
                    parsed = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(parsed, dict):
                    ready = parsed
                    break
            # keep the pipe drained afterwards: a chatty child blocked
            # on a full pipe is indistinguishable from a wedged one
            threading.Thread(
                target=lambda f=proc.stdout: deque(f, maxlen=0),
                daemon=True,
                name=f"xflow-fleet-replica{self.idx}-stdout",
            ).start()
        if ready and self._on_ready:
            self._on_ready(self.idx, gen, ready)
        rc = proc.wait()
        with self._proc_lock:
            self._proc = None
        if self._stopping.is_set():
            # an exit during fleet shutdown is the shutdown, not a
            # fault — do NOT let the supervision loop relaunch it
            return 0
        return rc

    def _run(self) -> None:
        from xflow_tpu.launch.supervise import supervise

        self.rc = supervise(
            self._attempt,
            max_restarts=self._max_restarts,
            restart_backoff=self._restart_backoff,
            min_uptime_s=self._min_uptime_s,
            label=f"serve-fleet replica {self.idx}",
            # backoff sleeps must wake on shutdown or terminate() races
            # a pending relaunch
            sleep=lambda s: self._stopping.wait(s),
        )

    # ------------------------------------------------------------- shutdown
    def terminate(self, sig=signal.SIGTERM) -> None:
        """Stop supervising (no relaunch) and signal the live attempt."""
        self._stopping.set()
        with self._proc_lock:
            proc = self._proc
        if proc is not None and proc.poll() is None:
            try:
                proc.send_signal(sig)
            except OSError:
                pass

    def join(self, timeout_s: float = 30.0) -> None:
        self._thread.join(timeout=timeout_s)
        with self._proc_lock:
            proc = self._proc
        if proc is not None and proc.poll() is None:
            proc.kill()

    @property
    def alive(self) -> bool:
        with self._proc_lock:
            return self._proc is not None and self._proc.poll() is None


def drain_fleet(router, supervisors, drain_timeout_s: float = 30.0,
                out=None) -> bool:
    """THE deploy-shutdown ordering (pinned by tests): (1) router stops
    admitting and waits out every in-flight request; (2) only then the
    replicas get SIGTERM (each drains its own queued futures). A
    replica that died before its router-admitted request finished would
    turn a clean deploy into client-visible 503s — the ordering is the
    zero-drop guarantee. Returns router.drain()'s verdict."""
    err = out or sys.stderr
    print("serve-fleet: draining router (stop admitting, finish "
          "in-flight)", file=err)
    drained = router.drain(timeout_s=drain_timeout_s)
    if not drained:
        print("serve-fleet: drain timeout — in-flight requests remained",
              file=err)
    print("serve-fleet: stopping replicas", file=err)
    for sup in supervisors:
        sup.terminate()
    return drained


def fleet_main(cfg: Config, serve_args: list, run_dir: str = "",
               max_restarts: int = 0, restart_backoff: float = 1.0,
               min_uptime_s: float = 0.0, ready_out=None) -> int:
    """The `xflow serve-fleet` body: spawn N supervised replicas on
    pre-picked ports, start the router over them, print ONE ready line
    (router address + per-replica ports/pids), serve until SIGTERM/
    SIGINT, then drain router-first."""
    from xflow_tpu.jsonl import JsonlAppender
    from xflow_tpu.launch.local import resolve_launch_run_id
    from xflow_tpu.serve.router import Backend, CircuitBreaker, Router, \
        make_router_http_server

    scfg = cfg.serve
    n = int(scfg.replicas)
    if n < 1:
        print("serve-fleet: need >= 1 replica", file=sys.stderr)
        return 2
    run_id = resolve_launch_run_id()
    os.environ["XFLOW_RUN_ID"] = run_id
    os.environ["XFLOW_NUM_PROCESSES"] = str(n)
    if run_dir:
        os.makedirs(run_dir, exist_ok=True)
    print(
        f"serve-fleet: {n} replica(s) on JAX_PLATFORMS="
        f"{_replica_platform(os.environ)}",
        file=sys.stderr,
    )

    ports = [_free_port(scfg.host) for _ in range(n)]
    ready_info = {}
    ready_evt = threading.Event()

    def on_ready(idx: int, gen: int, ready: dict) -> None:
        if gen > 0:
            print(
                f"serve-fleet: replica {idx} rejoined (restart "
                f"generation {gen}, step {ready.get('step')})",
                file=sys.stderr,
            )
        # the FIRST ready per replica satisfies the startup gate,
        # whatever its generation — a replica that needed one
        # supervised restart to come up is a recovery, not a startup
        # failure
        if idx not in ready_info:
            ready_info[idx] = ready
            if len(ready_info) == n:
                ready_evt.set()

    supervisors = []
    for idx in range(n):
        args = list(serve_args)
        if run_dir:
            args += [
                "--metrics-path",
                os.path.join(run_dir, f"serve_replica{idx}.jsonl"),
            ]
        supervisors.append(
            ReplicaSupervisor(
                idx, ports[idx], args, run_id,
                stagger_s=scfg.reload_stagger_s,
                world=n,
                max_restarts=max_restarts,
                restart_backoff=restart_backoff,
                min_uptime_s=min_uptime_s,
                log_path=(
                    os.path.join(run_dir, f"replica{idx}.log") if run_dir else ""
                ),
                on_ready=on_ready,
            )
        )
    for sup in supervisors:
        sup.start()

    # startup gate: every replica's generation-0 ready line, or a
    # supervisor giving up (rc set) — partial fleets don't serve
    deadline = time.monotonic() + 600.0
    while not ready_evt.wait(0.2):
        if time.monotonic() > deadline or any(
            s.rc is not None and s.rc != 0 for s in supervisors
        ):
            print("serve-fleet: replicas failed to start", file=sys.stderr)
            for sup in supervisors:
                sup.terminate()
            for sup in supervisors:
                sup.join(10.0)
            return 1

    router_jsonl = (
        os.path.join(run_dir, "serve_router.jsonl") if run_dir else ""
    )
    # rank -1 = control-plane stream, the launcher-watchdog
    # convention (metrics_report exempts it from rank<world); capped
    # like the replica streams (serve.metrics_max_bytes). `world` is
    # passed like the watchdog passes it: a stamp left to resolve it
    # can end in jax.process_count(), which starts a backend — and this
    # process must never hold a device its replicas need
    router_app = JsonlAppender(
        router_jsonl, stamp={"rank": -1, "run_id": run_id, "world": n},
        max_bytes=scfg.metrics_max_bytes,
    )
    from xflow_tpu.tracing import Tracer

    router = Router(
        [
            Backend(
                idx, scfg.host, ports[idx],
                breaker=CircuitBreaker(
                    fail_threshold=scfg.eject_failures,
                    open_s=scfg.circuit_open_s,
                ),
            )
            for idx in range(n)
        ],
        deadline_ms=scfg.route_deadline_ms,
        retries=scfg.route_retries,
        hedge_ms=scfg.route_hedge_ms,
        health_poll_s=scfg.health_poll_s,
        appender=router_app,
        # request tracing: the router is where a fleet's trace ids are
        # born (docs/OBSERVABILITY.md "Request tracing"); rate 0 = off
        tracer=Tracer(
            router_app,
            sample_rate=scfg.trace_sample_rate,
            slow_ms=scfg.trace_slow_ms,
        ),
    )
    router.start()
    try:
        srv = make_router_http_server(router, scfg.host, max(scfg.port, 0))
    except Exception:
        # a router-tier failure (EADDRINUSE on the client-facing port)
        # must not orphan N replica subprocesses: their supervisor
        # threads are daemons and die with us, but the `xflow serve`
        # children are separate OS processes that would keep running
        # with nothing left to terminate them
        router.close()
        for sup in supervisors:
            sup.terminate()
        for sup in supervisors:
            sup.join(10.0)
        raise
    srv_thread = threading.Thread(target=srv.serve_forever, daemon=True)
    srv_thread.start()
    router._event("fleet_start", replicas=n,
                  ports=ports, router_port=srv.server_address[1])

    ready = {
        "serving": True,
        "fleet": True,
        "router_host": srv.server_address[0],
        "router_port": srv.server_address[1],
        "run_id": run_id,
        "pid": os.getpid(),
        "replicas": [
            {
                "replica": idx,
                "port": ports[idx],
                "step": ready_info.get(idx, {}).get("step"),
                "pid": ready_info.get(idx, {}).get("pid"),
            }
            for idx in range(n)
        ],
    }
    out = ready_out or sys.stdout
    print(json.dumps(ready), file=out, flush=True)

    stop = threading.Event()
    prev = {}

    def on_signal(signum, frame):
        stop.set()
        for s, h in prev.items():
            signal.signal(s, h)

    for s in (signal.SIGTERM, signal.SIGINT):
        prev[s] = signal.signal(s, on_signal)
    try:
        while not stop.wait(0.2):
            if all(s.rc is not None for s in supervisors):
                # every supervision loop gave up: nothing left to route
                print(
                    "serve-fleet: all replica supervisors exhausted; "
                    "shutting down",
                    file=sys.stderr,
                )
                return max(s.rc or 0 for s in supervisors) or 1
        return 0
    finally:
        drain_fleet(router, supervisors)
        srv.shutdown()
        for sup in supervisors:
            sup.join(30.0)
        router._event("fleet_final")
        router.close()
        srv.server_close()
