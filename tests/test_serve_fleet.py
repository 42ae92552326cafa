"""Serving-fleet resilience tests (serve/router.py, serve/fleet.py,
brownout admission control, docs/SERVING.md "Fleet").

Socket-free core first — the circuit-breaker state machine and the
brownout mode on injectable clocks — then the router against FAKE
replicas (tiny stdlib HTTP servers with scriptable behavior: no
checkpoint, no jax anywhere near the routing tests), the drain
ordering, the serve_bench client knobs, the metrics_report fleet
identity gates, and the CI chaos drill (tools/smoke_serve_fleet.sh:
3 replicas, SIGKILL one mid-bench, corrupt a checkpoint mid-reload,
zero failed client requests)."""

import json
import os
import socket
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from xflow_tpu.serve.coalescer import (
    BrownoutPolicy,
    MicroBatcher,
    RejectedRequest,
)
from xflow_tpu.serve.router import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    Backend,
    CircuitBreaker,
    ConnectError,
    Router,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ------------------------------------------------------- circuit breaker
def test_breaker_opens_after_k_consecutive_failures():
    clock = FakeClock()
    br = CircuitBreaker(fail_threshold=3, open_s=5.0, clock=clock)
    assert br.state == CLOSED and br.allow()
    assert br.record_failure() is False
    assert br.record_failure() is False
    # the tripping failure reports True exactly once (one event)
    assert br.record_failure() is True
    assert br.state == OPEN and not br.allow()
    assert br.opened_count == 1


def test_breaker_success_resets_consecutive_count():
    br = CircuitBreaker(fail_threshold=2, clock=FakeClock())
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == CLOSED  # 1+1 non-consecutive failures never trip


def test_breaker_half_open_probe_accounting():
    clock = FakeClock()
    br = CircuitBreaker(fail_threshold=1, open_s=5.0, clock=clock)
    br.record_failure()
    assert br.state == OPEN
    assert not br.allow_probe()  # OPEN holds: no probe before open_s
    clock.t = 5.1
    assert br.state == HALF_OPEN
    assert not br.allow()  # real traffic still fenced off
    assert br.allow_probe()  # exactly ONE probe permit...
    assert not br.allow_probe()  # ...while it is in flight
    br.record_success()
    assert br.state == CLOSED and br.allow()


def test_breaker_stale_success_cannot_close_an_open_circuit():
    # a forward launched BEFORE the trip completes after it: the
    # breaker opened on fresher evidence, so the straggler's 200 must
    # not skip the open_s hold — recovery goes through the probe
    clock = FakeClock()
    br = CircuitBreaker(fail_threshold=1, open_s=5.0, clock=clock)
    br.record_failure()
    assert br.state == OPEN
    assert br.record_success() is False  # stale: refused
    assert br.state == OPEN
    clock.t = 5.1
    assert br.allow_probe()
    assert br.record_success(probe=True) is True  # the probe closes it
    assert br.state == CLOSED


def test_breaker_stale_failure_cannot_reopen_a_half_open_circuit():
    # the mirror of the stale-success guard: a forward launched BEFORE
    # the trip that fails during the HALF_OPEN window is evidence about
    # the OLD process — it must not steal the probe permit or restart
    # the open_s timer (each straggler would delay rejoin by open_s)
    clock = FakeClock()
    br = CircuitBreaker(fail_threshold=1, open_s=5.0, clock=clock)
    br.record_failure()
    clock.t = 5.1
    assert br.state == HALF_OPEN
    assert br.allow_probe()  # the real probe is in flight
    assert br.record_failure() is False  # straggler fails now: ignored
    assert br.state == HALF_OPEN
    assert br.record_success(probe=True) is True  # probe still closes it
    assert br.state == CLOSED


def test_breaker_failed_probe_reopens_with_fresh_timer():
    clock = FakeClock()
    br = CircuitBreaker(fail_threshold=1, open_s=5.0, clock=clock)
    br.record_failure()
    clock.t = 5.1
    assert br.allow_probe()
    # re-open is not a new trip event
    assert br.record_failure(probe=True) is False
    assert br.state == OPEN
    clock.t = 10.0  # 4.9s after the re-open: timer restarted
    assert br.state == OPEN
    clock.t = 10.3
    assert br.state == HALF_OPEN and br.allow_probe()


# ------------------------------------------------------------- brownout
def _mb(clock, **kw):
    policy = BrownoutPolicy(
        high_rows=8, low_rows=2, after_s=1.0, window_factor=0.25
    )
    events = []
    mb = MicroBatcher(
        max_rows=4, window_s=8.0, max_queue_rows=100, clock=clock,
        brownout=policy,
        on_brownout=lambda active, q: events.append((active, q)),
        **kw,
    )
    return mb, events


def _rows(n, nnz=2):
    import numpy as np

    from xflow_tpu.serve.coalescer import RowBlock

    return RowBlock.from_rows(
        [np.arange(nnz, dtype=np.int32) for _ in range(n)],
        [np.full(nnz, 3, dtype=np.int32) for _ in range(n)],
        nnz,
    )


def test_brownout_enters_on_sustained_backlog_and_sheds_low_priority():
    clock = FakeClock()
    mb, events = _mb(clock)
    for _ in range(3):  # 9 rows queued >= high_rows=8
        mb.submit(_rows(3))
    assert not mb.brownout  # over the line but not SUSTAINED yet
    clock.t = 1.1
    mb.submit(_rows(1))  # the submit that observes the sustain window
    assert mb.brownout
    assert events == [(True, 10)]
    # low priority sheds with a retryable 503-class rejection...
    with pytest.raises(RejectedRequest, match="brownout") as ei:
        mb.submit(_rows(1), priority=-1)
    assert ei.value.shed and not ei.value.client_error
    # ...normal priority still queues (the hard cliff is far away)
    mb.submit(_rows(1), priority=0)
    assert mb.queued_rows == 11


def test_brownout_shrinks_the_coalescing_window():
    clock = FakeClock()
    mb, _ = _mb(clock)
    for _ in range(4):
        mb.submit(_rows(3))
    clock.t = 1.1
    mb.submit(_rows(3))  # sustained over high_rows: brownout enters
    assert mb.brownout
    # drain down to exactly the t=1.1 request (3 rows > low_rows=2, so
    # the exit timer never starts while we measure)
    while mb.queued_rows > 3:
        assert mb.take(timeout=0.0) is not None
    # its deadline flush: full window = 1.1 + 8s = 9.1; brownout window
    # = 1.1 + 8 * 0.25 = 3.1
    clock.t = 2.5
    assert mb.take(timeout=0.0) is None  # < 3.1: still coalescing
    clock.t = 3.2
    group = mb.take(timeout=0.0)
    assert group is not None  # the SHRUNK window flushed, not the 8s one
    assert mb.brownout  # still in brownout throughout the measurement


def test_brownout_exits_after_sustained_drain_with_hysteresis():
    clock = FakeClock()
    mb, events = _mb(clock)
    for _ in range(4):
        mb.submit(_rows(3))
    clock.t = 1.1
    mb.submit(_rows(1))  # 13 rows; brownout on
    assert mb.brownout
    while mb.take(timeout=0.0) is not None:
        pass
    assert mb.queued_rows == 0  # drained below low_rows=2...
    assert mb.brownout  # ...but not sustained yet (hysteresis)
    clock.t = 2.5
    assert mb.take(timeout=0.0) is None  # an idle take observes the exit
    assert not mb.brownout
    assert events == [(True, 13), (False, 0)]


def test_parse_priority_header():
    from xflow_tpu.serve.server import parse_priority

    assert parse_priority("low") == -1
    assert parse_priority(" LOW ") == -1
    assert parse_priority("normal") == 0
    assert parse_priority(None) == 0


# ------------------------------------------------------------ serve faults
def test_serve_faults_from_env(monkeypatch):
    from xflow_tpu.testing.faults import serve_faults_from_env

    assert serve_faults_from_env() == (0.0, 0)
    monkeypatch.setenv("XFLOW_FAULT_SERVE_DELAY_S", "0.25")
    monkeypatch.setenv("XFLOW_FAULT_SERVE_KILL_BATCHES", "7")
    assert serve_faults_from_env() == (0.25, 7)
    # replica-gated: wrong replica sees nothing
    monkeypatch.setenv("XFLOW_FAULT_SERVE_REPLICA", "1")
    assert serve_faults_from_env() == (0.0, 0)
    monkeypatch.setenv("XFLOW_REPLICA", "1")
    assert serve_faults_from_env() == (0.25, 7)
    # generation-gated kill: the supervised relaunch must survive
    monkeypatch.setenv("XFLOW_RESTART_GEN", "1")
    assert serve_faults_from_env() == (0.25, 0)


# ---------------------------------------------------------- fake replicas
class FakeReplica:
    """A scriptable stand-in for one `xflow serve` replica: answers the
    same /predict + /healthz wire protocol with a configurable mode —
    ok | shed (503) | slow (ok after delay_s) | broken (500, the
    device-error path: healthz still 200) — so routing policy is
    testable with no checkpoint or device anywhere."""

    def __init__(self, mode="ok", delay_s=0.0, step=20):
        self.mode = mode
        self.delay_s = delay_s
        self.step = step
        self.predicts = 0
        self.healthz = 0
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _reply(self, status, payload):
                data = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_POST(self):
                outer.predicts += 1
                n = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(n)) if n else {}
                rows = body.get("rows", [])
                if outer.mode == "shed":
                    self._reply(503, {"error": "queue full; retry later"})
                    return
                if outer.mode == "broken":
                    self._reply(500, {"error": "RuntimeError: device"})
                    return
                if outer.mode == "slow":
                    time.sleep(outer.delay_s)
                self._reply(200, {
                    "pctr": [0.5] * len(rows),
                    "generation": 1,
                    "step": outer.step,
                    "replica_mode": outer.mode,
                })

            def do_GET(self):
                outer.healthz += 1
                # a shedding replica is still ALIVE (healthz 200): the
                # router retries its 503s elsewhere but never ejects it
                self._reply(200, {"ok": True, "step": outer.step})

            def log_message(self, fmt, *args):
                pass

        class Server(ThreadingHTTPServer):
            daemon_threads = True

        self.srv = Server(("127.0.0.1", 0), Handler)
        self.port = self.srv.server_address[1]
        self.thread = threading.Thread(
            target=self.srv.serve_forever, daemon=True
        )
        self.thread.start()

    def close(self):
        self.srv.shutdown()
        self.srv.server_close()


def _dead_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port  # nothing listens here


def _router(replicas, tmp_path=None, **kw):
    backends = [
        Backend(i, "127.0.0.1", r if isinstance(r, int) else r.port,
                breaker=CircuitBreaker(
                    fail_threshold=kw.pop("fail_threshold", 3)
                    if "fail_threshold" in kw else 3,
                    open_s=kw.pop("open_s", 60.0) if "open_s" in kw else 60.0,
                ))
        for i, r in enumerate(replicas)
    ]
    from xflow_tpu.jsonl import JsonlAppender

    app = JsonlAppender(
        str(tmp_path / "router.jsonl") if tmp_path else "",
        stamp={"rank": -1, "run_id": "fleet-test"},
    )
    kw.setdefault("health_poll_s", 30.0)  # default: health loop inert
    return Router(backends, appender=app, **kw)


BODY = json.dumps({"rows": ["0:a 1:b"]}).encode()


def test_router_round_robins_across_healthy():
    reps = [FakeReplica(), FakeReplica()]
    r = _router(reps)
    try:
        for _ in range(6):
            status, data = r.handle_predict(BODY)
            assert status == 200
        assert reps[0].predicts == 3 and reps[1].predicts == 3
    finally:
        r.close()
        for rep in reps:
            rep.close()


def test_router_retries_503_on_a_different_replica():
    reps = [FakeReplica(mode="shed"), FakeReplica()]
    r = _router(reps, retries=2, deadline_ms=5000)
    try:
        for _ in range(4):
            status, data = r.handle_predict(BODY)
            assert status == 200, data
            assert json.loads(data)["replica_mode"] == "ok"
        assert r.stats["retries"] >= 1
    finally:
        r.close()
        for rep in reps:
            rep.close()


def test_router_never_ejects_a_shedding_replica():
    # a 503 is an ANSWER — the replica is alive, just shedding; feeding
    # it to the breaker would amplify a fleet-wide brownout into a
    # total "no healthy replica" outage for normal-priority traffic
    reps = [FakeReplica(mode="shed"), FakeReplica()]
    r = _router(reps, retries=2, deadline_ms=5000, fail_threshold=2)
    try:
        for _ in range(8):
            assert r.handle_predict(BODY)[0] == 200
        assert r.backends[0].breaker.state == CLOSED
        assert len(r.healthy()) == 2
    finally:
        r.close()
        for rep in reps:
            rep.close()


def test_router_retries_and_ejects_a_persistent_500_replica(tmp_path):
    # a non-503 5xx is the replica FAILING the request (device error,
    # broken tables) while its /healthz can still say 200 — the router
    # must retry it elsewhere AND feed the breaker, or 1/N of all
    # traffic round-robins into permanent 500s forever
    reps = [FakeReplica(mode="broken"), FakeReplica()]
    r = _router(reps, tmp_path=tmp_path, retries=2, deadline_ms=5000,
                fail_threshold=2)
    try:
        for _ in range(6):
            status, data = r.handle_predict(BODY)
            assert status == 200, data
            assert json.loads(data)["replica_mode"] == "ok"
        assert r.backends[0].breaker.state == OPEN
        assert [b.idx for b in r.healthy()] == [1]
        from xflow_tpu.jsonl import read_jsonl

        opens = [rec for rec in read_jsonl(str(tmp_path / "router.jsonl"))
                 if rec.get("event") == "circuit_open"]
        assert opens and opens[0]["reason"] == "http_500"
    finally:
        r.close()
        for rep in reps:
            rep.close()


def test_backend_flushes_keepalive_pool_on_connect_failure():
    # a SIGKILLed replica leaves dead keep-alive sockets in the pool;
    # each would burn one half-open probe and re-open the circuit,
    # stalling the restarted replica's rejoin by open_s per socket
    import http.client

    port = _dead_port()
    b = Backend(0, "127.0.0.1", port)
    try:
        for _ in range(3):  # the stale keep-alives the kill left behind
            b._put_conn(http.client.HTTPConnection("127.0.0.1", port))
        assert len(b._pool) == 3
        with pytest.raises(ConnectError):
            b.request("POST", "/predict", BODY, timeout=1.0)
        assert len(b._pool) == 0
    finally:
        b.close()


def test_router_failovers_counts_only_backend_switches():
    # one shedding replica is the only choice: retries re-land on it,
    # so retries climbs but failovers (actual backend SWITCHES) stays 0
    rep = FakeReplica(mode="shed")
    r = _router([rep], retries=2, deadline_ms=5000)
    try:
        status, _ = r.handle_predict(BODY)
        assert status == 503
        assert r.stats["retries"] == 2
        assert r.stats["failovers"] == 0
    finally:
        r.close()
        rep.close()
    # with somewhere else to go, the retry IS a failover (round-robin:
    # some first attempts land on the shedder and switch away)
    reps = [FakeReplica(mode="shed"), FakeReplica()]
    r = _router(reps, retries=2, deadline_ms=5000)
    try:
        for _ in range(4):
            assert r.handle_predict(BODY)[0] == 200
        assert r.stats["failovers"] >= 1
        assert r.stats["failovers"] == r.stats["retries"]
    finally:
        r.close()
        for rep in reps:
            rep.close()


def test_router_fails_over_a_dead_replica_and_ejects_it(tmp_path):
    reps = [_dead_port(), FakeReplica()]
    r = _router(reps, tmp_path=tmp_path, retries=2, deadline_ms=5000,
                fail_threshold=2)
    try:
        for _ in range(4):
            status, _ = r.handle_predict(BODY)
            assert status == 200
        # 2 consecutive connect failures ejected backend 0
        assert r.backends[0].breaker.state == OPEN
        assert [b.idx for b in r.healthy()] == [1]
        # post-ejection requests never touch the dead one (no retries)
        before = r.stats["retries"]
        for _ in range(3):
            assert r.handle_predict(BODY)[0] == 200
        assert r.stats["retries"] == before
        from xflow_tpu.jsonl import read_jsonl

        events = [rec["event"] for rec in read_jsonl(str(tmp_path / "router.jsonl"))]
        assert "circuit_open" in events
    finally:
        r.close()
        reps[1].close()


def test_router_circuit_recovers_via_half_open_probe(tmp_path):
    rep = FakeReplica()
    dead = _dead_port()
    r = _router([dead, rep], tmp_path=tmp_path, retries=2,
                fail_threshold=1, open_s=0.2, health_poll_s=0.1)
    r.start()
    try:
        # round-robin alternates; within two requests one lands on the
        # dead backend, trips it (fail_threshold=1), and fails over
        for _ in range(2):
            assert r.handle_predict(BODY)[0] == 200
        assert r.backends[0].breaker.state in (OPEN, HALF_OPEN)
        # resurrect "replica 0" at the same port — like a supervised
        # fleet restart rebinding its fixed port
        revived = None
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if revived is None:
                try:
                    # bind a real FakeReplica onto the SAME port
                    revived = _fake_on_port(dead)
                except OSError:
                    time.sleep(0.05)
                    continue
            if r.backends[0].breaker.state == CLOSED:
                break
            time.sleep(0.05)
        assert r.backends[0].breaker.state == CLOSED
        from xflow_tpu.jsonl import read_jsonl

        events = [rec["event"] for rec in read_jsonl(str(tmp_path / "router.jsonl"))]
        assert "circuit_close" in events
    finally:
        r.close()
        rep.close()
        if revived is not None:
            revived.close()


def _fake_on_port(port: int) -> FakeReplica:
    """A FakeReplica bound to a specific port (the revival drill)."""
    rep = FakeReplica.__new__(FakeReplica)
    rep.mode, rep.delay_s, rep.step = "ok", 0.0, 20
    rep.predicts = rep.healthz = 0
    outer = rep

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def _reply(self, status, payload):
            data = json.dumps(payload).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_POST(self):
            outer.predicts += 1
            self._reply(200, {"pctr": [0.5], "generation": 1, "step": 20})

        def do_GET(self):
            outer.healthz += 1
            self._reply(200, {"ok": True})

        def log_message(self, fmt, *args):
            pass

    class Server(ThreadingHTTPServer):
        daemon_threads = True
        allow_reuse_address = True

    rep.srv = Server(("127.0.0.1", port), Handler)
    rep.port = port
    rep.thread = threading.Thread(target=rep.srv.serve_forever, daemon=True)
    rep.thread.start()
    return rep


def test_router_hedges_a_slow_replica(tmp_path):
    slow = FakeReplica(mode="slow", delay_s=1.5)
    fast = FakeReplica()
    r = _router([slow, fast], tmp_path=tmp_path, retries=1,
                deadline_ms=10000, hedge_ms=100)
    try:
        # force the slow one primary: round-robin picks index (rr+1)%2
        wins = 0
        for _ in range(4):
            t0 = time.perf_counter()
            status, data = r.handle_predict(BODY)
            assert status == 200
            if time.perf_counter() - t0 < 1.0:
                wins += 1
        # at least the requests routed to the slow primary hedged fast
        assert r.stats["hedges"] >= 1
        assert r.stats["hedge_wins"] >= 1
        assert wins >= 1
    finally:
        r.close()
        slow.close()
        fast.close()


def test_router_retry_exhaustion_is_an_honest_503():
    # every retry burns on a fast fleet-wide shed with budget to spare:
    # counted retries_exhausted, NOT deadline_exceeded (the two signals
    # need opposite operator fixes — bigger budget vs more capacity)
    reps = [FakeReplica(mode="shed"), FakeReplica(mode="shed")]
    r = _router(reps, retries=5, deadline_ms=5000, fail_threshold=100)
    try:
        status, data = r.handle_predict(BODY)
        assert status == 503
        assert r.stats["retries"] > 0
        assert r.stats["retries_exhausted"] == 1
        assert r.stats["deadline_exceeded"] == 0
    finally:
        r.close()
        for rep in reps:
            rep.close()


def test_router_no_healthy_backend_is_503():
    r = _router([_dead_port()], retries=0, fail_threshold=1)
    try:
        assert r.handle_predict(BODY)[0] == 503  # connect fails, trips
        status, data = r.handle_predict(BODY)
        assert status == 503
        assert b"no healthy replica" in data
        assert r.stats["no_backend"] >= 1
    finally:
        r.close()


# ---------------------------------------------------------------- drain
def test_router_drain_finishes_inflight_then_rejects(tmp_path):
    slow = FakeReplica(mode="slow", delay_s=0.8)
    r = _router([slow], tmp_path=tmp_path, deadline_ms=10000)
    results = []
    try:
        t = threading.Thread(
            target=lambda: results.append(r.handle_predict(BODY))
        )
        t.start()
        time.sleep(0.2)  # request is in flight at the replica
        assert r.drain(timeout_s=10.0) is True  # waits it out
        t.join(timeout=10)
        assert results and results[0][0] == 200  # the admitted one FINISHED
        # post-drain arrivals are refused (retryable — the LB's cue)
        assert r.handle_predict(BODY)[0] == 503
        from xflow_tpu.jsonl import read_jsonl

        events = [rec["event"] for rec in read_jsonl(str(tmp_path / "router.jsonl"))]
        assert "drain" in events
    finally:
        r.close()
        slow.close()


def test_drain_fleet_orders_router_before_replicas():
    from xflow_tpu.serve.fleet import drain_fleet

    calls = []

    class FakeRouter:
        def drain(self, timeout_s=30.0):
            calls.append("router_drain")
            return True

    class FakeSup:
        def __init__(self, i):
            self.i = i

        def terminate(self, sig=None):
            calls.append(f"terminate_{self.i}")

    import io

    assert drain_fleet(FakeRouter(), [FakeSup(0), FakeSup(1)],
                       out=io.StringIO()) is True
    # THE ordering: no replica dies before the router finished draining
    assert calls == ["router_drain", "terminate_0", "terminate_1"]


def test_replica_env_contract():
    from xflow_tpu.serve.fleet import replica_env

    env = replica_env({"PATH": "/bin"}, idx=2, port=9003, run_id="r1",
                      gen=3, stagger_s=0.5, world=3)
    assert env["XFLOW_REPLICA"] == "2"
    assert env["XFLOW_REPLICA_PORT"] == "9003"
    assert env["XFLOW_PROCESS_ID"] == "2"
    assert env["XFLOW_NUM_PROCESSES"] == "3"  # fleet world = replica count
    assert env["XFLOW_RESTART_GEN"] == "3"
    assert env["XFLOW_RUN_ID"] == "r1"
    assert env["XFLOW_RELOAD_STAGGER_S"] == "1.0"  # idx * stagger
    assert env["JAX_PLATFORMS"] == "cpu"  # replicas default off-device
    assert env["PATH"] == "/bin"


def test_checkpoint_watcher_staggers_the_reload():
    """The staggered-reload contract: replica k's watcher delays acting
    on a NOTICED newer step by its stagger share, so a fleet never
    pauses every replica on one checkpoint swap at once."""
    from xflow_tpu.serve.runner import CheckpointWatcher

    class FakeRunner:
        def __init__(self):
            self.step = 4
            self.reloaded_at = None

        def latest_committed_step(self):
            return 8

        def maybe_reload(self):
            self.reloaded_at = time.monotonic()
            self.step = 8

            class G:
                step, gen = 8, 2

            return G()

    fast, slow = FakeRunner(), FakeRunner()
    t0 = time.monotonic()
    w0 = CheckpointWatcher(fast, poll_s=0.05, stagger_s=0.0)
    w2 = CheckpointWatcher(slow, poll_s=0.05, stagger_s=0.6)
    w0.start()
    w2.start()
    try:
        deadline = time.monotonic() + 10
        while (fast.reloaded_at is None or slow.reloaded_at is None) and \
                time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        w0.close()
        w2.close()
    assert fast.reloaded_at is not None and slow.reloaded_at is not None
    # replica 0 swaps promptly; replica 2 holds its stagger share
    assert slow.reloaded_at - t0 >= 0.6
    assert slow.reloaded_at - fast.reloaded_at >= 0.3


# ----------------------------------------------------- jsonl replica stamp
def test_jsonl_stamps_replica_identity(tmp_path, monkeypatch):
    from xflow_tpu.jsonl import JsonlAppender, read_jsonl

    monkeypatch.setenv("XFLOW_REPLICA", "2")
    monkeypatch.setenv("XFLOW_REPLICA_PORT", "9002")
    p = tmp_path / "a.jsonl"
    app = JsonlAppender(str(p), stamp={"rank": 2, "run_id": "r1"})
    app.append({"kind": "serve", "event": "start"})
    app.close()
    rec = read_jsonl(str(p))[0]
    assert rec["replica"] == 2 and rec["port"] == 9002
    # and without the fleet env the keys are ABSENT, not null
    monkeypatch.delenv("XFLOW_REPLICA")
    monkeypatch.delenv("XFLOW_REPLICA_PORT")
    p2 = tmp_path / "b.jsonl"
    app2 = JsonlAppender(str(p2), stamp={"rank": 0, "run_id": "r1"})
    app2.append({"kind": "serve", "event": "start"})
    app2.close()
    rec2 = read_jsonl(str(p2))[0]
    assert "replica" not in rec2 and "port" not in rec2


def test_jsonl_appender_is_thread_safe(tmp_path):
    # the router writes ONE appender from request-handler threads,
    # hedge legs, and the health loop at once; interleaved writes
    # would show up as damaged lines and flip metrics_report gates
    from xflow_tpu.jsonl import JsonlAppender, read_jsonl_counted

    p = tmp_path / "router.jsonl"
    app = JsonlAppender(str(p), stamp={"rank": -1, "run_id": "r1"})
    n_threads, n_each = 8, 100

    def writer(t):
        for i in range(n_each):
            app.append({"kind": "serve", "event": "x", "t": t, "i": i})

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    app.close()
    records, skipped = read_jsonl_counted(str(p), warn=False)
    assert skipped == 0
    assert len(records) == n_threads * n_each


def test_serve_window_never_stamps_behind_a_reload_event(tmp_path):
    """The watcher thread appends the reload event while the metrics
    thread holds a pre-swap (generation, step) snapshot for the window
    it is about to flush; the window record lands AFTER the event in
    file order, so stamping the snapshot would make the stream
    non-monotone (metrics_report --check: generation 2 -> 1). The sink
    folds both paths through one high-water mark under one lock."""
    from xflow_tpu.jsonl import read_jsonl
    from xflow_tpu.serve.metrics import ServeMetrics

    path = tmp_path / "serve.jsonl"
    m = ServeMetrics(str(path), every_s=60.0, batch_size=32)
    m.event("start", generation=1, step=20)
    m.observe_batch(2, 3, [0.001], 0.004, [0.005])
    # the reload event wins the race to the file...
    m.event("reload", generation=2, step=50)
    # ...then the flusher shows up with its stale snapshot
    rec = m.maybe_flush(1, 20, force=True)
    assert (rec["generation"], rec["step"]) == (2, 50)
    m.close(2, 50)
    recs = read_jsonl(str(path))
    pairs = [(r["generation"], r["step"]) for r in recs
             if "generation" in r]
    assert pairs == sorted(pairs), pairs
    mr = _metrics_report()
    assert mr.main([str(path), "--check"]) == 0


# --------------------------------------------------- report fleet gates
def _metrics_report():
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import metrics_report as mr

    return mr


def _serve_rec(run_id="r1", rank=0, gen=0, ts=1.0, **kw):
    base = {"ts": ts, "rank": rank, "run_id": run_id, "gen": gen,
            "kind": "serve", "event": "start"}
    base.update(kw)
    return base


def _write(tmp_path, name, recs):
    p = tmp_path / name
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(p)


def test_check_accepts_distinct_replicas(tmp_path):
    mr = _metrics_report()
    ok = _write(tmp_path, "ok.jsonl", [
        _serve_rec(rank=0, replica=0, port=9000),
        _serve_rec(rank=1, replica=1, port=9001),
        _serve_rec(rank=1, replica=1, port=9001, gen=1, ts=2.0),
    ])
    assert mr.main([ok, "--check"]) == 0


def test_check_rejects_replicas_colliding_on_rank(tmp_path):
    mr = _metrics_report()
    bad = _write(tmp_path, "bad.jsonl", [
        _serve_rec(rank=0, replica=0),
        _serve_rec(rank=0, replica=1, ts=2.0, gen=1),
    ])
    assert mr.main([bad, "--check"]) == 2


def test_check_rejects_mixed_replica_stamps_in_one_stream(tmp_path):
    mr = _metrics_report()
    bad = _write(tmp_path, "bad.jsonl", [
        _serve_rec(rank=0, replica=0),
        _serve_rec(rank=0, replica=1, ts=2.0),
    ])
    assert mr.main([bad, "--check"]) == 2


def test_check_rejects_replica_generation_regression(tmp_path):
    mr = _metrics_report()
    bad = _write(tmp_path, "bad.jsonl", [
        _serve_rec(rank=0, replica=0, gen=1, ts=1.0),
        _serve_rec(rank=0, replica=0, gen=0, ts=2.0),
    ])
    assert mr.main([bad, "--check"]) == 2
    # ACROSS replicas different gens are fine (replica 1 restarted,
    # replica 0 did not)
    ok = _write(tmp_path, "ok.jsonl", [
        _serve_rec(rank=0, replica=0, gen=0, ts=1.0),
        _serve_rec(rank=1, replica=1, gen=2, ts=0.5),
    ])
    assert mr.main([ok, "--check"]) == 0


# ------------------------------------------------------ serve_bench knobs
def test_serve_bench_retries_absorb_503(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import serve_bench

    rep = FakeReplica(mode="shed")

    # flip the replica healthy shortly into the bench: the 503s before
    # the flip are absorbed by --retries (with backoff), so exit stays 0
    def heal():
        time.sleep(0.3)
        rep.mode = "ok"

    threading.Thread(target=heal, daemon=True).start()
    out = tmp_path / "B.json"
    rc = serve_bench.main([
        "--url", f"http://127.0.0.1:{rep.port}", "--duration", "1.5",
        "--concurrency", "2", "--retries", "40", "--retry-backoff-ms", "50",
        "--deadline-ms", "10000", "--bench-json", str(out),
    ])
    rep.close()
    rec = json.load(open(out))
    assert rc == 0, rec
    assert rec["errors"] == 0
    assert rec["retried"] >= 1 and rec["retry_attempts"] >= rec["retried"]
    assert rec["deadline_exceeded"] == 0


def test_serve_bench_unabsorbed_errors_still_fail(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import serve_bench

    rep = FakeReplica(mode="shed")  # 503 forever: retries cannot absorb
    out = tmp_path / "B.json"
    rc = serve_bench.main([
        "--url", f"http://127.0.0.1:{rep.port}", "--duration", "0.8",
        "--concurrency", "1", "--retries", "1", "--bench-json", str(out),
    ])
    rep.close()
    rec = json.load(open(out))
    assert rc == 1
    assert rec["errors"] >= 1


def test_serve_bench_deadline_exceeded_counts_as_error(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import serve_bench

    rep = FakeReplica(mode="slow", delay_s=1.0)
    out = tmp_path / "B.json"
    rc = serve_bench.main([
        "--url", f"http://127.0.0.1:{rep.port}", "--duration", "0.9",
        "--concurrency", "1", "--deadline-ms", "200", "--retries", "3",
        "--bench-json", str(out),
    ])
    rep.close()
    rec = json.load(open(out))
    assert rc == 1
    assert rec["deadline_exceeded"] >= 1


def test_serve_bench_hedge_wins_on_slow_server(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import serve_bench

    # the server starts slow then heals: the early requests' hedge legs
    # fire (the client-side p99 amputation), later ones answer direct
    rep = FakeReplica(mode="slow", delay_s=0.6)

    def heal():
        time.sleep(0.5)
        rep.mode = "ok"

    threading.Thread(target=heal, daemon=True).start()
    out = tmp_path / "B.json"
    rc = serve_bench.main([
        "--url", f"http://127.0.0.1:{rep.port}", "--duration", "1.2",
        "--concurrency", "1", "--hedge-ms", "120", "--bench-json", str(out),
    ])
    rep.close()
    rec = json.load(open(out))
    assert rc == 0, rec
    assert rec["hedged"] >= 1


# ----------------------------------------------------------- CI chaos drill
def test_smoke_serve_fleet_script(tmp_path):
    """The fleet chaos gate end to end (tools/smoke_serve_fleet.sh):
    train -> 3-replica supervised fleet -> closed-loop bench through
    the router -> SIGKILL one replica mid-load (serve fault injector)
    AND commit a corrupt checkpoint mid-reload -> zero failed client
    requests, the killed replica restarts + rejoins, circuit events in
    the router JSONL, metrics_report --check green, BENCH datapoint."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "smoke_serve_fleet.sh"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=570, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "smoke_serve_fleet: OK" in r.stdout
    assert "chaos OK" in r.stdout
    assert "rejoin OK" in r.stdout
    bench = json.load(open(tmp_path / "BENCH_SERVE_FLEET.json"))
    assert bench["metric"] == "serve_qps" and bench["value"] > 0
    assert bench["errors"] == 0
