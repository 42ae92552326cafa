"""HTTP / unix-socket front end + the device worker loop.

Request path (docs/SERVING.md):

    HTTP handler thread: parse JSON -> parse rows (same hash path as
      training) -> MicroBatcher.submit -> block on the request Future
    device worker thread: MicroBatcher.take (coalescing window) ->
      assemble ONE padded batch -> ServeRunner.predict -> scatter pctr
      slices + generation provenance back to every request's Future

One device batch per coalescing window, whatever the concurrency — the
microbatching contract. The handler threads only parse and wait; the
single worker thread owns the device, so predict calls never interleave
and the jitted program compiles exactly once (fixed [max_batch,
max_nnz] shape).

Failure semantics: malformed body/rows -> 400 with the reason (the
quarantine philosophy — reject the record, never crash the server);
backlog full / shutdown -> 503 (load shedding is explicit); an
unexpected predict error fails ONLY the futures of that batch (500),
the worker keeps going. `GET /healthz` reports generation/step;
`GET /stats` snapshots the telemetry registry.
"""

from __future__ import annotations

import json
import os
import socket
import socketserver
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from xflow_tpu.config import Config
from xflow_tpu.serve.autotune import AutotuneController, pick_rung
from xflow_tpu.serve.coalescer import (
    BrownoutPolicy,
    MicroBatcher,
    RejectedRequest,
    assemble_batch,
)
from xflow_tpu.serve.metrics import ServeMetrics
from xflow_tpu.serve.runner import BadRequest, CheckpointWatcher, ServeRunner, parse_rows
from xflow_tpu.tracing import (
    FORCE_HEADER,
    PARENT_HEADER,
    TRACE_HEADER,
    Tracer,
    clean_id,
    emit_op_span,
    new_id,
)

# request-priority header (docs/SERVING.md "Brownout"): "low" marks a
# request sheddable under sustained backlog; anything else (or absence)
# is normal priority. Header-based so retrying proxies/the router can
# forward it untouched.
PRIORITY_HEADER = "X-Request-Priority"


def parse_priority(value: Optional[str]) -> int:
    """Header value -> internal priority: < 0 shed under brownout."""
    return -1 if value is not None and value.strip().lower() == "low" else 0


def _freshness(gen) -> Optional[float]:
    """Duck-typed: a generation without the freshness surface (test
    fakes, pre-publication runners) reads as not-measurable, never an
    error — absence of the gauge is the documented off state."""
    fn = getattr(gen, "freshness_s", None)
    return fn() if callable(fn) else None


class ServeApp:
    """Wires runner + batcher + metrics + the device worker thread.
    Socket-free by itself (tests drive `handle_predict` directly); the
    HTTP servers below call into it."""

    def __init__(self, cfg: Config, runner: ServeRunner, metrics: Optional[ServeMetrics] = None):
        self.cfg = cfg
        self.runner = runner
        scfg = cfg.serve
        self.metrics = metrics or ServeMetrics(
            scfg.metrics_path, every_s=scfg.metrics_every_s,
            batch_size=scfg.max_batch, max_bytes=scfg.metrics_max_bytes,
        )
        # request tracing (docs/OBSERVABILITY.md "Request tracing"):
        # spans ride the same stamped serve stream; rate 0 = off, and
        # the handler/worker paths skip every tracing branch
        self.tracer = Tracer(
            self.metrics.appender,
            sample_rate=scfg.trace_sample_rate,
            slow_ms=scfg.trace_slow_ms,
        )

        def on_brownout(active: bool, queued_rows: int) -> None:
            # the admission-control timeline rides the serve stream
            # (kind="serve" events, like reload/reload_failed)
            self.metrics.event(
                "brownout_enter" if active else "brownout_exit",
                queued_rows=queued_rows,
            )

        self.batcher = MicroBatcher(
            max_rows=scfg.max_batch,
            window_s=scfg.window_ms / 1e3,
            max_queue_rows=scfg.max_queue_rows,
            brownout=BrownoutPolicy.from_config(scfg),
            on_brownout=on_brownout,
        )
        # the batch-shape ladder + SLO controller (serve/autotune.py):
        # each flushed metrics window the worker loop feeds the
        # controller steers window_ms / the release rung toward
        # serve.slo_p99_ms. Off (default) = no controller object, no
        # autotune records/spans, rung == max_batch everywhere — the
        # stream stays byte-identical to a pre-autotune build.
        self._rungs = tuple(getattr(runner, "rungs", ())) or (
            int(scfg.max_batch),
        )
        self.autotuner = (
            AutotuneController(scfg, rungs=self._rungs)
            if scfg.autotune
            else None
        )
        self._timeout_s = scfg.request_timeout_s
        self._stop = threading.Event()
        self._worker = threading.Thread(
            target=self._worker_loop, daemon=True, name="xflow-serve-device"
        )
        # chaos-drill injectors (testing/faults.serve_faults_from_env):
        # resolved ONCE here — zero per-batch cost when unset
        from xflow_tpu.testing.faults import serve_faults_from_env

        self._fault_delay_s, self._fault_kill_batches = serve_faults_from_env()
        self._batches_served = 0
        # first-served-prediction marker (docs/SERVING.md "Freshness"):
        # the newest generation a batch has ANSWERED with — the worker
        # emits one serve_first span when it advances, closing the
        # ingest -> ... -> served-prediction trace
        self._first_served_gen = -1
        self.t_start = time.perf_counter()

    def start(self) -> None:
        self._worker.start()

    # ------------------------------------------------------- device worker
    def _worker_loop(self) -> None:
        cfg = self.cfg
        while True:
            group = self.batcher.take(timeout=0.1)
            if group is None:
                if self._stop.is_set():
                    return
                # idle tick: windows still flush on schedule (and a
                # window that flushes here still steers the controller)
                gen = self.runner.generation
                if gen is not None:
                    self._autotune(self.metrics.maybe_flush(
                        gen.gen, gen.step, freshness_s=_freshness(gen),
                    ))
                continue
            t_batch = time.perf_counter()
            if self._fault_delay_s > 0:
                # slow-replica injector: the device "runs slow" without
                # real overload — circuit/hedge drills use this
                time.sleep(self._fault_delay_s)
            # flush at the smallest precompiled rung that fits — small
            # batches stop paying full-max_batch padding (the single
            # unconfigured rung IS max_batch, the pre-ladder shape)
            rung = pick_rung(sum(r.num_rows for r in group), self._rungs)
            try:
                arrays, spans = assemble_batch(
                    group, rung, cfg.data.max_nnz
                )
                # predict's np.asarray readback IS the device sync: the
                # worker (not the handler threads) pays the batch's
                # device time, shared by all its requests
                p, gen = self.runner.predict(arrays)
            except Exception as e:  # noqa: BLE001 — fail THIS batch's
                # futures, keep the worker alive for the next window
                for req in group:
                    if not req.future.done():
                        req.future.set_exception(e)
                continue
            t_done = time.perf_counter()
            device_s = t_done - t_batch
            if gen.gen != self._first_served_gen:
                # first answered batch of a new generation: the
                # swap-to-first-serve edge of the freshness Δ
                self._first_served_gen = gen.gen
                self._first_serve_span(gen)
            self._trace_batch(group, spans, t_batch, t_done, gen, rung)
            queue_waits, totals = [], []
            n_rows = 0
            for req, lo, hi in spans:
                queue_waits.append(t_batch - req.t_submit)
                totals.append(t_done - req.t_submit)
                n_rows += hi - lo
                req.future.set_result(
                    {
                        "pctr": [float(x) for x in p[lo:hi]],
                        "generation": gen.gen,
                        "step": gen.step,
                        "queue_ms": round((t_batch - req.t_submit) * 1e3, 3),
                        "total_ms": round((t_done - req.t_submit) * 1e3, 3),
                    }
                )
            self.metrics.observe_batch(
                len(group), n_rows, queue_waits, device_s, totals,
                batch_size=rung,
            )
            self._autotune(self.metrics.maybe_flush(
                gen.gen, gen.step, freshness_s=_freshness(gen),
            ))
            self._batches_served += 1
            if (
                self._fault_kill_batches
                and self._batches_served >= self._fault_kill_batches
            ):
                # chaos drill: SIGKILL after the Nth answered batch — a
                # replica dying MID-LOAD with responses in flight (its
                # supervised relaunch inherits the env generation-gated,
                # so it survives; testing/faults.hard_kill)
                from xflow_tpu.testing.faults import hard_kill

                hard_kill()

    # ----------------------------------------------------------- autotune
    def _autotune(self, window: Optional[dict]) -> None:
        """Feed one flushed metrics window to the SLO controller and
        apply + publish its decisions. Every decision ships as a
        stamped kind="autotune" record (the audit trail metrics_report
        gates) plus an operational span carrying the same knob move, so
        `request_trace --timeline` overlays the controller's actions on
        the latency spans they caused. No-op when autotune is off or
        the window didn't flush."""
        if window is None or self.autotuner is None:
            return
        t0_wall, t0 = time.time(), time.perf_counter()
        for d in self.autotuner.observe(window):
            if d.knob == "window_ms" and d.new != d.old:
                self.batcher.set_window_s(d.new / 1e3)
            elif d.knob == "rung" and d.new != d.old:
                self.batcher.set_release_rows(int(d.new))
            self.metrics.appender.append({
                "kind": "autotune",
                "knob": d.knob,
                "old": round(d.old, 4),
                "new": round(d.new, 4),
                "reason": d.reason,
                "slo_p99_ms": self.autotuner.slo_ms,
                "total_p99_ms": window["total_p99_ms"],
                "queue_wait_p99_ms": window["queue_wait_p99_ms"],
                "device_p99_ms": window["device_p99_ms"],
                "batch_fill": window["batch_fill"],
            })
            emit_op_span(
                self.metrics.appender, "autotune", t0_wall,
                time.perf_counter() - t0,
                knob=d.knob, old=round(d.old, 4), new=round(d.new, 4),
                reason=d.reason,
            )

    # ------------------------------------------------------------- tracing
    def _first_serve_span(self, gen) -> None:
        """One `serve_first` span per model generation, emitted when its
        FIRST batch answers: carries the publication's ingest trace id
        (parented under the reload swap span), so
        tools/freshness_report.py can close the ingested-row ->
        served-prediction loop at the exact instant predictions from
        the new data became externally visible. Silent (byte-identical
        streams) without a span sink or a published checkpoint."""
        sink = self.runner.span_sink
        pub = getattr(gen, "publication", None)
        if sink is None or not isinstance(pub, dict):
            return
        trace = pub.get("trace")
        if not isinstance(trace, str) or not trace:
            return
        from xflow_tpu.tracing import emit_linked_span

        emit_linked_span(
            sink, "serve_first", time.time(), 0.0,
            trace=trace,
            parent=getattr(gen, "reload_span", None) or pub.get("span") or None,
            step=gen.step, generation=gen.gen,
        )

    def _trace_batch(self, group, spans, t_batch, t_done, gen, rung) -> None:
        """Emit the shared device_batch span + each traced member's
        queue/device spans (the batch-membership link: N request trees
        reference ONE batch span by id). Zero-cost when tracing is off
        or no member request carries a trace."""
        tr = self.tracer
        if not tr.enabled:
            return
        traced = [(req, lo, hi) for req, lo, hi in spans if req.trace]
        if not traced:
            return
        n_rows = sum(hi - lo for _, lo, hi in spans)
        # flush reason: the oldest member aging past the (possibly
        # brownout-shrunk) window means a deadline flush; otherwise the
        # backlog filled the batch (size flush / close drain)
        oldest_wait = t_batch - min(req.t_submit for req, _, _ in spans)
        flush = (
            "window"
            if oldest_wait >= 0.95 * self.batcher.effective_window_s
            else "size"
        )
        bid = new_id()
        batch_rec = {
            "kind": "span",
            "trace": traced[0][0].trace,
            "span": bid,
            "name": "device_batch",
            "t0": round(tr.wall(t_batch), 6),
            "dur_ms": round((t_done - t_batch) * 1e3, 3),
            "requests": len(spans),
            "rows": n_rows,
            # fill against the rung this batch actually shipped at (the
            # single unconfigured rung is max_batch — same value)
            "batch_fill": round(n_rows / max(rung, 1), 4),
            "flush": flush,
            "generation": gen.gen,
        }
        tr.add_shared(batch_rec, [req.trace for req, _, _ in traced])
        for req, lo, hi in traced:
            tr.add(req.trace, {
                "kind": "span", "trace": req.trace, "span": new_id(),
                "parent": req.span, "name": "queue",
                "t0": round(tr.wall(req.t_submit), 6),
                "dur_ms": round((t_batch - req.t_submit) * 1e3, 3),
                "rows": hi - lo,
            })
            tr.add(req.trace, {
                "kind": "span", "trace": req.trace, "span": new_id(),
                "parent": req.span, "name": "device",
                "t0": round(tr.wall(t_batch), 6),
                "dur_ms": round((t_done - t_batch) * 1e3, 3),
                "batch": bid,
            })

    # ----------------------------------------------------------- app logic
    def handle_predict(
        self,
        body: bytes,
        priority: int = 0,
        trace_id: str = "",
        parent_span: str = "",
        force_trace: bool = False,
    ) -> tuple[int, dict]:
        """(http_status, response dict) for one POST /predict body:
        {"rows": ["field:feat field:feat ...", ...]}. `priority` < 0
        (the X-Request-Priority: low header) marks the request
        sheddable under brownout. `trace_id`/`parent_span`/`force_trace`
        carry the X-Trace-Id / X-Parent-Span / X-Trace-Force headers:
        with tracing on, the request's server/parse/queue/device spans
        buffer under the trace and flush on its verdict (head-sampled,
        router-forced, or tail-captured here: error/shed/slow)."""
        tr = self.tracer if (self.tracer.enabled and trace_id) else None
        if tr is None:
            return self._predict_impl(body, priority)
        root = tr.span(trace_id, "server", parent=parent_span or None)
        status, payload = self._predict_impl(
            body, priority, tr=tr, trace_id=trace_id, root=root
        )
        rec = tr.end(root, status=status)
        # tail capture: errors, sheds, and slow requests are exemplars
        # whatever the sampling verdict (docs/OBSERVABILITY.md)
        tr.finish(
            trace_id,
            force=force_trace or status != 200
            or rec["dur_ms"] / 1e3 > tr.slow_s,
        )
        return status, payload

    def _predict_impl(
        self, body: bytes, priority: int = 0, tr=None, trace_id: str = "",
        root=None,
    ) -> tuple[int, dict]:
        try:
            payload = json.loads(body.decode("utf-8"))
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            self.metrics.observe_bad_request()
            return 400, {"error": f"body is not JSON: {e}"}
        rows = payload.get("rows") if isinstance(payload, dict) else None
        if not isinstance(rows, list) or not rows:
            self.metrics.observe_bad_request()
            return 400, {"error": 'expected {"rows": [<libffm feature row>, ...]}'}
        t_parse = time.perf_counter()
        try:
            fields_rows, slots_rows = parse_rows(rows, self.cfg.data)
        except BadRequest as e:
            self.metrics.observe_bad_request()
            return 400, {"error": str(e)}
        if tr is not None:
            tr.add(trace_id, {
                "kind": "span", "trace": trace_id, "span": new_id(),
                "parent": root["span"], "name": "parse",
                "t0": round(tr.wall(t_parse), 6),
                "dur_ms": round((time.perf_counter() - t_parse) * 1e3, 3),
                "rows": len(rows),
            })
        try:
            fut = self.batcher.submit(
                fields_rows, slots_rows, priority=priority,
                trace=trace_id if tr is not None else "",
                span=root["span"] if tr is not None else "",
            )
        except RejectedRequest as e:
            if e.shed:
                # brownout shed is ADMISSION telemetry, not a bad
                # request: its own counter, still a retryable 503
                self.metrics.observe_shed()
                return 503, {"error": str(e)}
            self.metrics.observe_bad_request()
            # oversized request is the CLIENT's error; backlog/shutdown
            # is load shedding (the exception carries the class)
            return (400 if e.client_error else 503), {"error": str(e)}
        try:
            return 200, fut.result(timeout=self._timeout_s)
        except FutureTimeout:
            return 503, {"error": f"timed out after {self._timeout_s}s"}
        except Exception as e:  # noqa: BLE001 — a failed batch reports
            # its reason to the client instead of a hung connection
            return 500, {"error": f"{type(e).__name__}: {e}"}

    def health(self) -> dict:
        gen = self.runner.generation
        out = {
            "ok": gen is not None,
            "generation": gen.gen if gen else 0,
            "step": gen.step if gen else -1,
            "queued_rows": self.batcher.queued_rows,
            "brownout": self.batcher.brownout,
            "uptime_s": round(time.perf_counter() - self.t_start, 3),
        }
        fresh = _freshness(gen)
        if fresh is not None:
            # present only for published checkpoints, so unpublished
            # fleets keep the pre-freshness /healthz shape (the router
            # probe and its fleet min/max read this field)
            out["data_freshness_s"] = round(fresh, 3)
        return out

    def stats(self) -> dict:
        from xflow_tpu.telemetry import default_registry

        out = {**self.health(), "registry": default_registry().snapshot()}
        if self.autotuner is not None:
            # live controller state (docs/SERVING.md "Autotuning"):
            # absent entirely when autotune is off, so off-mode /stats
            # responses stay shape-identical to a pre-autotune build
            out["autotune"] = self.autotuner.state()
        return out

    def close(self) -> None:
        """Graceful: stop intake, drain the backlog (every queued
        future resolves), stop the worker, flush metrics."""
        self.batcher.close()
        self._stop.set()
        if self._worker.is_alive():
            self._worker.join(timeout=30.0)
        gen = self.runner.generation
        self.metrics.close(
            gen.gen if gen else -1,
            gen.step if gen else -1,
            freshness_s=_freshness(gen),
        )


def _make_handler(app: ServeApp):
    class Handler(BaseHTTPRequestHandler):
        # serving answers many short requests; HTTP/1.1 keep-alive makes
        # the loadgen's closed loop connection-reuse instead of
        # connect-per-request
        protocol_version = "HTTP/1.1"
        # buffered wfile: headers + body leave in ONE segment (the
        # stdlib default wbufsize=0 writes them separately, and Nagle
        # holds the body until the headers are ACKed — with the peer's
        # delayed ACK that is a ~40 ms stall per response on loopback)
        wbufsize = -1

        def setup(self):
            super().setup()
            try:
                self.connection.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                )
            except OSError:
                pass  # AF_UNIX transport: no Nagle to disable

        def _reply(self, status: int, payload: dict, trace: str = "") -> None:
            data = json.dumps(payload).encode("utf-8")
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            if trace:
                # the trace-id echo: every response returns the id the
                # request carried (serve_bench asserts the round trip)
                self.send_header(TRACE_HEADER, trace)
            self.end_headers()
            self.wfile.write(data)

        def do_POST(self):  # noqa: N802 — BaseHTTPRequestHandler contract
            if self.path != "/predict":
                self._reply(404, {"error": f"no such endpoint {self.path!r}"})
                return
            try:
                n = int(self.headers.get("Content-Length", "0"))
            except ValueError:
                n = 0
            body = self.rfile.read(n) if n > 0 else b""
            # trace identity: a client-sent X-Trace-Id wins; with
            # tracing on, a direct (router-less) client gets one minted
            # here — the id is echoed either way, sampled only when
            # tracing is on
            tid = clean_id(self.headers.get(TRACE_HEADER))
            if not tid and app.tracer.enabled:
                tid = new_id()
            status, payload = app.handle_predict(
                body,
                priority=parse_priority(self.headers.get(PRIORITY_HEADER)),
                trace_id=tid,
                parent_span=clean_id(self.headers.get(PARENT_HEADER)),
                force_trace=self.headers.get(FORCE_HEADER) == "1",
            )
            self._reply(status, payload, trace=tid)

        def do_GET(self):  # noqa: N802
            if self.path == "/healthz":
                h = app.health()
                self._reply(200 if h["ok"] else 503, h)
            elif self.path == "/stats":
                self._reply(200, app.stats())
            else:
                self._reply(404, {"error": f"no such endpoint {self.path!r}"})

        def log_message(self, fmt, *args):  # quiet: telemetry JSONL is
            pass  # the record of traffic, not per-request stderr lines

        def address_string(self):
            # AF_UNIX client addresses are ''/b'' — BaseHTTPRequestHandler
            # indexes client_address[0], which only works for AF_INET
            try:
                return super().address_string()
            except (IndexError, TypeError):
                return "unix"

    return Handler


class _QuietDisconnects:
    """A client dropping its keep-alive connection mid-read is normal
    load-balancer/loadgen behavior, not a server error — suppress the
    default stderr traceback for exactly that; real errors still print."""

    def handle_error(self, request, client_address):
        import sys as _sys

        exc = _sys.exc_info()[1]
        if isinstance(exc, (ConnectionResetError, BrokenPipeError, TimeoutError)):
            return
        super().handle_error(request, client_address)


class _TCPHTTPServer(_QuietDisconnects, ThreadingHTTPServer):
    daemon_threads = True


def make_http_server(app: ServeApp, host: str, port: int) -> ThreadingHTTPServer:
    """TCP server (port 0 = pick free; read .server_address back)."""
    return _TCPHTTPServer((host, port), _make_handler(app))


class _UnixHTTPServer(
    _QuietDisconnects, socketserver.ThreadingMixIn, socketserver.TCPServer
):
    """HTTP over AF_UNIX: same handler, same wire protocol — the
    colocated-client path (the reference's C API embeds in a native
    ranking server; a unix socket skips the TCP stack for it)."""

    address_family = socket.AF_UNIX
    allow_reuse_address = True
    daemon_threads = True

    def server_bind(self):
        # a stale socket file from a dead server would EADDRINUSE
        if os.path.exists(self.server_address):
            os.unlink(self.server_address)
        super().server_bind()

    def get_request(self):
        request, _ = super().get_request()
        # BaseHTTPRequestHandler formats client_address[0]; give it a
        # stable shape for unix peers
        return request, ("unix", 0)


def make_unix_server(app: ServeApp, path: str) -> _UnixHTTPServer:
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    return _UnixHTTPServer(path, _make_handler(app))


def serve_main(cfg: Config, mesh=None, ready_out=None) -> int:
    """The `xflow serve` body: load -> watch -> serve until SIGTERM/
    SIGINT. `ready_out` (a file object; default stdout) gets ONE JSON
    line once the sockets are listening — scripts wait on it and read
    the bound port back (serve.port=0 picks a free one)."""
    import signal
    import sys

    runner = ServeRunner(cfg, mesh=mesh)
    gen = runner.load()  # startup: no checkpoint IS fatal
    app = ServeApp(cfg, runner)
    if runner.compile_recorder is not None:
        # compile records join the serve stream (the predict program
        # compiles lazily on the first batch, after this bind)
        runner.compile_recorder.bind(app.metrics.appender)
    if app.tracer.enabled:
        # hot-reload swaps emit kind="span" records into the same
        # stream (request_trace --timeline overlays them); off when
        # tracing is off so rate-0 streams stay byte-identical
        runner.span_sink = app.metrics.appender
    if cfg.serve.autotune or len(getattr(runner, "rungs", ())) > 1:
        # AOT-compile the whole ladder BEFORE the ready line: the
        # controller must be able to move the rung without the first
        # batch at a new shape paying its compile on the latency path.
        # Unladdered autotune-off servers keep the lazy first-batch
        # compile, byte-identical to the pre-ladder build.
        n = runner.warmup()
        print(f"serve: precompiled {n} ladder rung(s)", file=sys.stderr)
    app.metrics.event("start", generation=gen.gen, step=gen.step)
    try:
        # the fleet's staggered-reload offset (serve/fleet.py exports
        # replica k's share; solo servers have no stagger)
        stagger_s = float(os.environ.get("XFLOW_RELOAD_STAGGER_S", 0) or 0)
    except ValueError:
        stagger_s = 0.0
    watcher = CheckpointWatcher(
        runner,
        poll_s=cfg.serve.reload_poll_s,
        on_reload=lambda g: app.metrics.event(
            "reload", generation=g.gen, step=g.step
        ),
        on_failed=lambda: app.metrics.event("reload_failed"),
        stagger_s=stagger_s,
    )
    app.start()
    watcher.start()

    servers = []
    threads = []
    if cfg.serve.port >= 0:
        http = make_http_server(app, cfg.serve.host, cfg.serve.port)
        servers.append(http)
    if cfg.serve.unix_socket:
        servers.append(make_unix_server(app, cfg.serve.unix_socket))
    if not servers:
        print("serve: nothing to listen on (serve.port=-1 and no "
              "serve.unix_socket)", file=sys.stderr)
        return 2
    for srv in servers:
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        threads.append(t)

    from xflow_tpu.telemetry import device_triple

    ready = {
        "serving": True,
        "step": gen.step,
        "generation": gen.gen,
        "pid": os.getpid(),
        "device": device_triple(),
    }
    if cfg.serve.port >= 0:
        ready["host"], ready["port"] = servers[0].server_address[:2]
    if cfg.serve.unix_socket:
        ready["unix_socket"] = cfg.serve.unix_socket
    out = ready_out or sys.stdout
    print(json.dumps(ready), file=out, flush=True)

    stop = threading.Event()
    prev = {}

    def on_signal(signum, frame):
        stop.set()
        for s, h in prev.items():
            signal.signal(s, h)  # second signal kills normally

    for s in (signal.SIGTERM, signal.SIGINT):
        prev[s] = signal.signal(s, on_signal)
    try:
        while not stop.wait(0.2):
            pass
    finally:
        print("serve: shutting down (draining queued requests)", file=sys.stderr)
        for srv in servers:
            srv.shutdown()
        watcher.close()
        app.close()
        for srv in servers:
            srv.server_close()
        if cfg.serve.unix_socket and os.path.exists(cfg.serve.unix_socket):
            try:
                os.unlink(cfg.serve.unix_socket)
            except OSError:
                pass
    return 0
