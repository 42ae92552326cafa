"""Request-path distributed tracing tests (xflow_tpu/tracing.py,
tools/request_trace.py, docs/OBSERVABILITY.md "Request tracing").

Layered like the serving tests: the tracer core on fake appenders
first (deterministic head sampling, tail-force verdicts, the
shared-batch-span dedup, bounded buffers), then JSONL rotation, the
span emission of a real ServeApp + Router against fake replicas (no
checkpoint or device anywhere near them), cross-stream assembly from
fixture spans (a retried request spanning two replicas, a hedged
request whose losing leg is orphaned), the critical-path math against
a hand-built oracle, the Chrome export shape, the metrics_report span
gates, serve_bench's trace-id round trip, the trainer's checkpoint
spans, and the CI smoke drill (tools/smoke_trace.sh)."""

import json
import os
import subprocess
import sys
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.jsonl import JsonlAppender, read_jsonl
from xflow_tpu.serve.coalescer import assemble_batch
from xflow_tpu.tracing import (
    FORCE_HEADER,
    PARENT_HEADER,
    TRACE_HEADER,
    Tracer,
    clean_id,
    emit_op_span,
    new_id,
    sampled,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))

import request_trace  # noqa: E402  (tools/request_trace.py)


class ListSink:
    """An appender double: records land in a list."""

    def __init__(self):
        self.records = []

    def append(self, rec):
        self.records.append(rec)


# ------------------------------------------------------------ tracer core


def test_sampled_is_deterministic_and_bounded():
    assert sampled("anything", 1.0)
    assert not sampled("anything", 0.0)
    ids = [new_id() for _ in range(4000)]
    frac = sum(sampled(i, 0.25) for i in ids) / len(ids)
    assert 0.19 < frac < 0.31, frac
    # the same id always decides the same way — the zero-coordination
    # property the router/replica agreement depends on
    for i in ids[:100]:
        assert sampled(i, 0.3) == sampled(i, 0.3)


def test_clean_id_rejects_junk():
    assert clean_id("  abc-DEF_1.2  ") == "abc-DEF_1.2"
    assert clean_id(None) == ""
    assert clean_id("") == ""
    assert clean_id("x" * 65) == ""
    assert clean_id('evil" {injection}') == ""


def test_tracer_head_sampled_trace_emits():
    sink = ListSink()
    tr = Tracer(sink, sample_rate=1.0)
    s = tr.span("t1", "server")
    tr.end(s, status=200)
    assert sink.records == []  # buffered until the verdict
    assert tr.finish("t1")
    assert [r["name"] for r in sink.records] == ["server"]
    rec = sink.records[0]
    assert rec["kind"] == "span" and rec["trace"] == "t1"
    assert rec["status"] == 200 and rec["dur_ms"] >= 0 and rec["t0"] > 0


def test_tracer_unsampled_trace_drops_unless_forced():
    # find an id the head sampler rejects at a tiny rate
    tid = next(i for i in (new_id() for _ in range(100))
               if not sampled(i, 1e-9))
    sink = ListSink()
    tr = Tracer(sink, sample_rate=1e-9)
    tr.end(tr.span(tid, "server"))
    assert not tr.finish(tid)
    assert sink.records == []
    # the same shape again, but the tail verdict forces it
    tr.end(tr.span(tid + "b", "server"))
    assert tr.finish(tid + "b", force=True)
    assert len(sink.records) == 1


def test_tracer_shared_batch_span_emits_exactly_once():
    sink = ListSink()
    tr = Tracer(sink, sample_rate=1.0)
    batch = {"kind": "span", "trace": "a", "span": "B", "name": "device_batch",
             "t0": 1.0, "dur_ms": 2.0}
    tr.add_shared(batch, ["a", "b"])
    tr.end(tr.span("a", "server"))
    tr.end(tr.span("b", "server"))
    tr.finish("a")
    tr.finish("b")
    assert sum(1 for r in sink.records if r["name"] == "device_batch") == 1
    # the emitted copy dropped the internal dedup marker
    emitted = next(r for r in sink.records if r["name"] == "device_batch")
    assert "_shared" not in emitted


def test_tracer_late_span_follows_recorded_verdict():
    """A hedge leg losing the race lands its span AFTER the request's
    verdict — an emitted trace keeps it, a dropped one drops it."""
    sink = ListSink()
    tr = Tracer(sink, sample_rate=1.0)
    tr.end(tr.span("t", "request"))
    tr.finish("t")
    tr.add("t", {"kind": "span", "trace": "t", "span": "x", "name": "attempt",
                 "t0": 1.0, "dur_ms": 5.0})
    assert sum(1 for r in sink.records if r["name"] == "attempt") == 1
    tid = next(i for i in (new_id() for _ in range(100))
               if not sampled(i, 1e-9))
    tr2 = Tracer(sink, sample_rate=1e-9)
    tr2.end(tr2.span(tid, "request"))
    tr2.finish(tid)
    n = len(sink.records)
    tr2.add(tid, {"kind": "span", "trace": tid, "span": "y",
                  "name": "attempt", "t0": 1.0, "dur_ms": 5.0})
    assert len(sink.records) == n  # dropped trace stays dropped


def test_tracer_pending_buffer_is_bounded():
    """A trace whose finish never comes (a leaked id) must not grow
    the process: oldest pending traces evict."""
    sink = ListSink()
    tr = Tracer(sink, sample_rate=1.0, max_pending=8)
    for k in range(100):
        tr.end(tr.span(f"leak{k}", "server"))
    assert tr.pending_traces() <= 8


def test_emit_op_span_is_unconditional():
    sink = ListSink()
    rec = emit_op_span(sink, "checkpoint_save", 123.0, 0.5, step=10,
                       bytes=2048)
    assert sink.records == [rec]
    assert rec["name"] == "checkpoint_save" and rec["dur_ms"] == 500.0
    assert rec["step"] == 10 and rec["bytes"] == 2048
    assert rec["trace"] and rec["span"]


# ---------------------------------------------------------- JSONL rotation


def test_rotation_rolls_and_reader_folds_in_order(tmp_path):
    path = str(tmp_path / "rot.jsonl")
    app = JsonlAppender(path, stamp={"rank": 0, "run_id": "r"}, max_bytes=500)
    for k in range(12):
        app.append({"kind": "x", "k": k})
    app.close()
    assert os.path.exists(path + ".1")
    # both files individually under ~the cap, and the fold reads OLD
    # records first so file order (and every order-sensitive report
    # gate) survives the roll
    recs = read_jsonl(path)
    ks = [r["k"] for r in recs]
    assert ks == sorted(ks) and ks[-1] == 11
    assert len(read_jsonl(path + ".1", warn=False)) + len(
        read_jsonl(path, fold_rotated=False)
    ) == len(recs)


def test_rotation_keeps_locked_append_contract(tmp_path):
    """Concurrent appenders through one rolling sink: every line in
    the live + rolled files parses (no interleaved/torn lines)."""
    path = str(tmp_path / "conc.jsonl")
    app = JsonlAppender(path, stamp={"rank": 0, "run_id": "r"},
                        max_bytes=4096)
    def worker(tag):
        for k in range(50):
            app.append({"kind": "x", "tag": tag, "k": k})
    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    app.close()
    for f in (path + ".1", path):
        if os.path.exists(f):
            for line in open(f):
                json.loads(line)  # raises on a torn line


def test_unrotated_reads_are_untouched(tmp_path):
    path = str(tmp_path / "plain.jsonl")
    app = JsonlAppender(path, stamp={"rank": 0, "run_id": "r"})
    app.append({"kind": "x"})
    app.close()
    assert len(read_jsonl(path)) == 1


# -------------------------------------------------- server-side span wiring


class FakeGen:
    gen = 1
    step = 20


class FakeRunner:
    generation = FakeGen()
    compile_recorder = None
    span_sink = None

    def assemble(self, group, rung):
        return assemble_batch(group, rung, 8)

    def predict(self, arrays):
        n = arrays["row_mask"].shape[0]
        return np.full((n,), 0.5, np.float32), self.generation


def _app_cfg(tmp_path, **extra):
    base = {
        "data.log2_slots": 12, "data.max_nnz": 8, "model.num_fields": 5,
        "serve.window_ms": 1.0, "serve.max_batch": 8,
        "serve.metrics_path": str(tmp_path / "serve.jsonl"),
        "serve.metrics_every_s": 0.2,
        "serve.trace_sample_rate": 1.0,
    }
    base.update(extra)
    return override(Config(), **base)


BODY = json.dumps({"rows": ["0:a 1:b", "2:c"]}).encode()


def test_server_emits_linked_span_tree(tmp_path):
    from xflow_tpu.serve.server import ServeApp

    app = ServeApp(_app_cfg(tmp_path), FakeRunner())
    app.start()
    try:
        tid = new_id()
        status, _ = app.handle_predict(BODY, trace_id=tid)
        assert status == 200
    finally:
        app.close()
    spans = [r for r in read_jsonl(str(tmp_path / "serve.jsonl"))
             if r.get("kind") == "span"]
    names = sorted(s["name"] for s in spans)
    assert names == ["device", "device_batch", "parse", "queue", "server"]
    root = next(s for s in spans if s["name"] == "server")
    assert "parent" not in root and root["trace"] == tid
    by_name = {s["name"]: s for s in spans}
    # parse/queue/device all parent to the server span; device links
    # the shared batch span by id (the batch-membership join)
    for child in ("parse", "queue", "device"):
        assert by_name[child]["parent"] == root["span"]
    assert by_name["device"]["batch"] == by_name["device_batch"]["span"]
    assert by_name["device_batch"]["flush"] in ("window", "size")
    assert by_name["device_batch"]["rows"] == 2


def test_server_rate_zero_is_byte_identical(tmp_path):
    """The acceptance pin: trace_sample_rate=0 leaves the serve JSONL
    exactly as a pre-tracing build wrote it — no span records, no new
    keys — even when the client sends a trace id."""
    from xflow_tpu.serve.server import ServeApp

    app = ServeApp(
        _app_cfg(tmp_path, **{"serve.trace_sample_rate": 0.0}), FakeRunner()
    )
    app.start()
    try:
        status, _ = app.handle_predict(BODY, trace_id=new_id())
        assert status == 200
    finally:
        app.close()
    recs = read_jsonl(str(tmp_path / "serve.jsonl"))
    assert recs, "serve windows should still flush"
    assert not [r for r in recs if r.get("kind") == "span"]
    assert not [r for r in recs if "trace" in r]


def test_server_tail_captures_errors_despite_head_drop(tmp_path):
    """A 400 at a near-zero sample rate still lands on disk — the
    tail-capture contract."""
    from xflow_tpu.serve.server import ServeApp

    tid = next(i for i in (new_id() for _ in range(200))
               if not sampled(i, 1e-9))
    app = ServeApp(
        _app_cfg(tmp_path, **{"serve.trace_sample_rate": 1e-9}), FakeRunner()
    )
    app.start()
    try:
        status, _ = app.handle_predict(b"not json", trace_id=tid)
        assert status == 400
        # a 200 under the same rate drops (head sampling holds)
        ok_tid = next(i for i in (new_id() for _ in range(200))
                      if not sampled(i, 1e-9))
        status, _ = app.handle_predict(BODY, trace_id=ok_tid)
        assert status == 200
    finally:
        app.close()
    spans = [r for r in read_jsonl(str(tmp_path / "serve.jsonl"))
             if r.get("kind") == "span"]
    assert [s["trace"] for s in spans] == [tid]
    assert spans[0]["status"] == 400


# -------------------------------------------------- router-side span wiring


class EchoReplica:
    """A header-recording fake replica: answers /predict 200 (or a
    scripted failure budget) and records the tracing headers each
    forward carried."""

    def __init__(self, fail_first: int = 0):
        self.fail_first = fail_first
        self.seen_headers = []
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _reply(self, status, payload):
                data = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                tid = self.headers.get(TRACE_HEADER)
                if tid:
                    self.send_header(TRACE_HEADER, tid)
                self.end_headers()
                self.wfile.write(data)

            def do_POST(self):
                outer.seen_headers.append({
                    k: self.headers.get(k)
                    for k in (TRACE_HEADER, PARENT_HEADER, FORCE_HEADER)
                })
                n = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(n)) if n else {}
                if outer.fail_first > 0:
                    outer.fail_first -= 1
                    self._reply(503, {"error": "scripted shed"})
                    return
                self._reply(200, {
                    "pctr": [0.5] * len(body.get("rows", [])),
                    "generation": 1, "step": 20,
                })

            def do_GET(self):
                self._reply(200, {"ok": True})

            def log_message(self, fmt, *args):
                pass

        class Server(ThreadingHTTPServer):
            daemon_threads = True

        self.srv = Server(("127.0.0.1", 0), Handler)
        self.port = self.srv.server_address[1]
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()

    def close(self):
        self.srv.shutdown()
        self.srv.server_close()


def _traced_router(replicas, tmp_path, rate=1.0, **kw):
    from xflow_tpu.serve.router import Backend, Router

    app = JsonlAppender(str(tmp_path / "router.jsonl"),
                        stamp={"rank": -1, "run_id": "trace-test"})
    kw.setdefault("health_poll_s", 30.0)
    return Router(
        [Backend(i, "127.0.0.1", r.port) for i, r in enumerate(replicas)],
        appender=app,
        tracer=Tracer(app, sample_rate=rate, slow_ms=kw.pop("slow_ms", 250.0)),
        **kw,
    )


def test_router_retry_spans_and_force_propagation(tmp_path):
    """A retried request: root + one attempt per leg, the retry leg
    carrying X-Trace-Force to the replica (the replica cannot know the
    router's verdict), and the whole trace emitted even at a
    never-sample rate — retries are tail exemplars."""
    shedding, ok = EchoReplica(fail_first=10), EchoReplica()
    # backend order matters: pick() round-robins starting at index 1,
    # so the shedding replica sits there to take the primary leg
    router = _traced_router([ok, shedding], tmp_path, rate=1e-9,
                            deadline_ms=5000, retries=2)
    try:
        tid = next(i for i in (new_id() for _ in range(200))
                   if not sampled(i, 1e-9))
        status, _ = router.handle_predict(BODY, headers={TRACE_HEADER: tid})
        assert status == 200
    finally:
        router.close()
        shedding.close()
        ok.close()
    spans = [r for r in read_jsonl(str(tmp_path / "router.jsonl"), warn=False)
             if r.get("kind") == "span"]
    roots = [s for s in spans if s["name"] == "request"]
    attempts = sorted(
        (s for s in spans if s["name"] == "attempt"),
        key=lambda s: s["t0"],
    )
    assert len(roots) == 1 and roots[0]["trace"] == tid
    assert len(attempts) == 2
    assert attempts[0]["status"] == 503 and attempts[0]["leg"] == "primary"
    assert attempts[1]["status"] == 200 and attempts[1]["leg"] == "retry"
    assert all(a["parent"] == roots[0]["span"] for a in attempts)
    # header propagation: every forward carried the id + its attempt
    # span as parent; only the retry leg was forced
    seen = shedding.seen_headers + ok.seen_headers
    assert all(h[TRACE_HEADER] == tid for h in seen)
    parents = {a["span"] for a in attempts}
    assert {h[PARENT_HEADER] for h in seen} <= parents
    assert ok.seen_headers[-1][FORCE_HEADER] == "1"
    assert shedding.seen_headers[0][FORCE_HEADER] is None


def test_router_untraced_request_forwards_bare(tmp_path):
    """No X-Trace-Id in, tracing effectively off for the request: no
    spans, no tracing headers on the forward."""
    ok = EchoReplica()
    router = _traced_router([ok], tmp_path, rate=1.0, deadline_ms=2000)
    try:
        status, _ = router.handle_predict(BODY, headers={})
        assert status == 200
    finally:
        router.close()
        ok.close()
    # no spans at all: the lazy appender never even created the file
    assert not os.path.exists(tmp_path / "router.jsonl")
    assert ok.seen_headers[0][TRACE_HEADER] is None


def test_router_http_front_end_mints_and_echoes_id(tmp_path):
    """A client without an id gets one minted at the router and echoed
    in the response header — the fleet's id birthplace."""
    import http.client

    from xflow_tpu.serve.router import make_router_http_server

    ok = EchoReplica()
    router = _traced_router([ok], tmp_path, rate=1.0, deadline_ms=2000)
    srv = make_router_http_server(router, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.server_address[1],
                                       timeout=10)
        c.request("POST", "/predict", BODY,
                  {"Content-Type": "application/json"})
        resp = c.getresponse()
        minted = resp.getheader(TRACE_HEADER)
        resp.read()
        assert resp.status == 200 and minted
        # a client-sent id wins and echoes back verbatim
        sent = new_id()
        c.request("POST", "/predict", BODY,
                  {"Content-Type": "application/json", TRACE_HEADER: sent})
        resp = c.getresponse()
        assert resp.getheader(TRACE_HEADER) == sent
        resp.read()
        c.close()
    finally:
        srv.shutdown()
        srv.server_close()
        router.close()
        ok.close()
    spans = [r for r in read_jsonl(str(tmp_path / "router.jsonl"), warn=False)
             if r.get("kind") == "span"]
    assert {s["trace"] for s in spans if s["name"] == "request"} == {minted, sent}


# ------------------------------------------------- assembly + critical path


def _span(trace, span, name, t0, dur_ms, parent=None, **attrs):
    rec = {"kind": "span", "trace": trace, "span": span, "name": name,
           "t0": t0, "dur_ms": dur_ms, **attrs}
    if parent:
        rec["parent"] = parent
    return rec


def _oracle_trace(trace="t-oracle"):
    """A hand-built retried request spanning two replicas, with exact
    durations the critical-path math must reproduce."""
    return [
        _span(trace, "R", "request", 100.000, 100.0, status=200),
        _span(trace, "A1", "attempt", 100.001, 20.0, parent="R",
              status=503, leg="primary", backend=0),
        # the losing replica's side: a real server span on replica 0
        _span(trace, "S1", "server", 100.002, 18.0, parent="A1",
              status=503, replica=0, rank=0),
        _span(trace, "A2", "attempt", 100.030, 65.0, parent="R",
              status=200, leg="retry", backend=1),
        _span(trace, "S2", "server", 100.032, 60.0, parent="A2",
              status=200, replica=1, rank=1),
        _span(trace, "P", "parse", 100.033, 5.0, parent="S2", replica=1),
        _span(trace, "Q", "queue", 100.038, 20.0, parent="S2", replica=1),
        _span(trace, "D", "device", 100.058, 30.0, parent="S2",
              batch="B", replica=1),
    ], [
        _span(trace, "B", "device_batch", 100.058, 30.0, flush="size",
              requests=3, rows=6, batch_fill=0.75, replica=1),
    ]


def test_critical_path_matches_oracle():
    req, batch = _oracle_trace()
    trees = request_trace.assemble(req)
    rows = request_trace.decompose(trees, batch)
    assert len(rows) == 1
    r = rows[0]
    assert r["complete"] and r["status"] == 200 and r["replica"] == 1
    assert r["total_ms"] == pytest.approx(100.0)
    assert r["retry"] == pytest.approx(30.0, abs=1e-6)     # winner t0 - root t0
    assert r["network"] == pytest.approx(5.0)              # attempt - server
    assert r["parse"] == pytest.approx(5.0)
    assert r["queue"] == pytest.approx(20.0)               # size flush
    assert r["window"] == pytest.approx(0.0)
    assert r["device"] == pytest.approx(30.0)
    assert r["server_other"] == pytest.approx(5.0)         # 60 - 55
    assert r["router_other"] == pytest.approx(5.0)         # 100 - 30 - 65
    summary = request_trace.summarize(rows)
    assert summary["complete_frac"] == 1.0
    assert summary["per_replica"][1]["requests"] == 1


def test_window_flush_attributes_to_window_category():
    req, batch = _oracle_trace()
    batch[0]["flush"] = "window"
    rows = request_trace.decompose(request_trace.assemble(req), batch)
    assert rows[0]["window"] == pytest.approx(20.0)
    assert rows[0]["queue"] == pytest.approx(0.0)


def test_hedged_losing_leg_orphan_is_tolerated():
    """The losing hedge leg's replica-side spans whose router attempt
    never emitted: orphaned, counted, and the winner's path still
    assembles complete."""
    trace = "t-hedge"
    req = [
        _span(trace, "R", "request", 10.0, 50.0, status=200),
        _span(trace, "A1", "attempt", 10.001, 48.0, parent="R",
              status=200, leg="primary", backend=0),
        _span(trace, "S1", "server", 10.002, 40.0, parent="A1",
              status=200, replica=0),
        _span(trace, "P1", "parse", 10.003, 1.0, parent="S1", replica=0),
        _span(trace, "Q1", "queue", 10.004, 2.0, parent="S1", replica=0),
        _span(trace, "D1", "device", 10.006, 30.0, parent="S1",
              batch="B1", replica=0),
        # the losing leg: its parent attempt span was never emitted
        _span(trace, "S2", "server", 10.020, 35.0, parent="A-GONE",
              status=200, replica=1),
    ]
    batch = [_span(trace, "B1", "device_batch", 10.006, 30.0,
                   flush="window", replica=0)]
    trees = request_trace.assemble(req)
    tree = trees[trace]
    assert [s["span"] for s in tree.orphans] == ["S2"]
    assert len(tree.roots) == 1
    rows = request_trace.decompose(trees, batch)
    assert rows[0]["complete"]
    assert rows[0]["replica"] == 0  # the WINNING replica gets the blame row


def test_assembly_from_files_cross_stream(tmp_path):
    """The CLI path: spans scattered over router + two replica files
    (as a fleet writes them) assemble back into complete trees and the
    gate/--json/--chrome surfaces all work."""
    req, batch = _oracle_trace()
    by_file = {"serve_router.jsonl": [], "serve_replica0.jsonl": [],
               "serve_replica1.jsonl": []}
    for s in req + batch:
        rep = s.get("replica")
        f = ("serve_router.jsonl" if rep is None
             else f"serve_replica{rep}.jsonl")
        by_file[f].append(s)
    for f, recs in by_file.items():
        with open(tmp_path / f, "w") as fh:
            for r in recs:
                fh.write(json.dumps(
                    {"ts": r["t0"], "rank": r.get("rank", -1),
                     "run_id": "fix", **r}
                ) + "\n")
    out = tmp_path / "report.json"
    chrome = tmp_path / "chrome.json"
    rc = request_trace.main([
        str(tmp_path), "--json", str(out), "--chrome", str(chrome),
        "--min-complete", "0.99",
    ])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["complete"] == 1 and rep["complete_frac"] == 1.0
    assert rep["exemplars"]["p99"]["trace"] == "t-oracle"
    events = json.loads(chrome.read_text())["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == len(req) + len(batch)
    assert all(isinstance(e["pid"], int) and e["ts"] >= 0 for e in xs)
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"replica 0", "replica 1"} <= names


def test_timeline_overlays_ops_on_requests():
    req, batch = _oracle_trace()
    trees = request_trace.assemble(req)
    rows = request_trace.decompose(trees, batch)
    for r in rows:
        r["t0_wall"] = trees[r["trace"]].root["t0"]
    ops = [_span("op1", "O1", "reload", 100.050, 80.0, step=50,
                 generation=2, bytes=4096, replica=1)]
    text = request_trace.render_timeline(rows, ops)
    assert "reload" in text and "step=50" in text
    assert "worst" in text


# --------------------------------------------------- metrics_report gates


def _report(tmp_path, records, name="stream.jsonl"):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import metrics_report

    path = tmp_path / name
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    files = [str(path)]
    streams, _ = metrics_report.load_streams(files)
    return metrics_report.check_streams(streams, files)


def _stamped(rec, rank=0, replica=None):
    out = {"ts": rec.get("t0", 1.0), "rank": rank, "run_id": "r", **rec}
    if replica is not None:
        out["replica"] = replica
    return out


def test_check_passes_valid_span_streams(tmp_path):
    """A fleet-shaped layout — router spans in a rank=-1 stream, each
    replica's spans in its own replica-stamped stream — passes every
    span gate."""
    import metrics_report

    req, batch = _oracle_trace()
    files = []
    by_file: dict = {}
    for s in req + batch:
        rep = s.get("replica")
        rank = -1 if rep is None else rep
        rec = {"ts": s["t0"], "rank": rank, "run_id": "r", **s}
        by_file.setdefault(f"f{rank}.jsonl", []).append(rec)
    for fname, recs in by_file.items():
        path = tmp_path / fname
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        files.append(str(path))
    streams, _ = metrics_report.load_streams(files)
    problems = metrics_report.check_streams(streams, files)
    assert problems == [], problems


def test_check_flags_missing_span_keys(tmp_path):
    bad = _stamped({"kind": "span", "trace": "t", "name": "server"})
    problems = _report(tmp_path, [bad])
    assert any("span keys" in p for p in problems)


def test_check_flags_two_roots_in_one_trace(tmp_path):
    recs = [
        _stamped(_span("t", "R1", "request", 1.0, 5.0, status=200)),
        _stamped(_span("t", "S1", "server", 1.0, 4.0, status=200)),
    ]
    problems = _report(tmp_path, recs)
    assert any("parent to one root" in p for p in problems)


def test_check_flags_unreferenced_batch_span(tmp_path):
    recs = [
        _stamped(_span("t", "R", "server", 1.0, 5.0, status=200)),
        _stamped(_span("t", "B", "device_batch", 1.0, 2.0, flush="size")),
    ]
    problems = _report(tmp_path, recs)
    assert any("batch-membership" in p for p in problems)


def test_check_flags_span_stream_mixing_replicas(tmp_path):
    recs = [
        _stamped(_span("t1", "S1", "server", 1.0, 5.0, status=200),
                 replica=0),
        _stamped(_span("t2", "S2", "server", 2.0, 5.0, status=200),
                 replica=1),
    ]
    problems = _report(tmp_path, recs)
    assert any("mixes replica stamps" in p for p in problems)


def test_health_renders_queue_vs_device_split(tmp_path):
    import metrics_report

    window = {
        "ts": 1.0, "rank": 0, "run_id": "r", "kind": "serve",
        "requests": 10, "rows": 10, "qps": 5.0, "rows_per_s": 5.0,
        "batches": 2, "batch_fill": 0.5,
        "queue_wait_p50_ms": 1.0, "queue_wait_p99_ms": 9.0,
        "device_p50_ms": 1.0, "device_p99_ms": 2.0,
        "total_p50_ms": 2.0, "total_p99_ms": 11.0, "window_s": 2.0,
        "bad_requests": 0, "shed_requests": 0, "generation": 1, "step": 20,
        "replica": 1,
    }
    path = tmp_path / "serve.jsonl"
    path.write_text(json.dumps(window) + "\n")
    streams, _ = metrics_report.load_streams([str(path)])
    text = metrics_report.render_health(streams)
    assert "queue-wait vs device p99" in text
    assert "queue-wait-bound" in text  # 9.0 > 2.0


# -------------------------------------------------- serve_bench round trip


def test_serve_bench_trace_round_trip(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import serve_bench

    rep = EchoReplica()
    out = tmp_path / "bench.json"
    try:
        rc = serve_bench.main([
            "--url", f"http://127.0.0.1:{rep.port}", "--duration", "1.2",
            "--concurrency", "2", "--trace", "--trace-sample-rate", "0.01",
            "--bench-json", str(out),
        ])
    finally:
        rep.close()
    rec = json.loads(out.read_text())
    assert rc == 0, rec
    assert rec["traced"] is True
    assert rec["trace_sample_rate"] == 0.01
    assert rec["trace_echo_miss"] == 0
    assert rec["requests"] > 0 and rec["errors"] == 0
    # every forward carried an id (fresh per request)
    ids = [h[TRACE_HEADER] for h in rep.seen_headers]
    assert all(ids) and len(set(ids)) == len(ids)


def test_serve_bench_flags_missing_echo(tmp_path):
    """A server that answers 200 but drops the id: the round-trip
    gate fails the run."""

    class NoEcho(EchoReplica):
        pass

    rep = NoEcho()
    # strip the echo by monkey-patching the handler class's _reply
    handler_cls = rep.srv.RequestHandlerClass

    def _reply(self, status, payload):
        data = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    handler_cls._reply = _reply
    import serve_bench

    try:
        rc = serve_bench.main([
            "--url", f"http://127.0.0.1:{rep.port}", "--duration", "0.8",
            "--concurrency", "1", "--trace",
        ])
    finally:
        rep.close()
    assert rc == 1


# ---------------------------------------------------- trainer ckpt spans


def test_trainer_checkpoint_spans(tmp_path):
    from xflow_tpu.data.synth import generate_shards
    from xflow_tpu.train.trainer import Trainer

    generate_shards(str(tmp_path / "train"), 1, 64, num_fields=5,
                    ids_per_field=20, seed=0)
    cfg = override(Config(), **{
        "data.train_path": str(tmp_path / "train"),
        "data.batch_size": 32, "data.log2_slots": 10, "data.max_nnz": 8,
        "model.num_fields": 5, "train.pred_dump": False,
        "train.checkpoint_dir": str(tmp_path / "ck"),
        "train.metrics_path": str(tmp_path / "metrics.jsonl"),
    })
    t = Trainer(cfg)
    t.save_checkpoint()
    t2 = Trainer(cfg)
    assert t2.maybe_restore()
    t.metrics.close()
    t2.metrics.close()
    spans = [r for r in read_jsonl(str(tmp_path / "metrics.jsonl"))
             if r.get("kind") == "span"]
    names = [s["name"] for s in spans]
    assert "checkpoint_save" in names and "checkpoint_restore" in names
    for s in spans:
        assert s["bytes"] > 0 and s["dur_ms"] >= 0 and "step" in s

    # off = byte-identical metrics stream (no span records)
    cfg_off = override(cfg, **{
        "train.ckpt_spans": False,
        "train.metrics_path": str(tmp_path / "metrics_off.jsonl"),
        "train.checkpoint_dir": str(tmp_path / "ck_off"),
    })
    t3 = Trainer(cfg_off)
    t3.save_checkpoint()
    t3.metrics.close()
    recs = read_jsonl(str(tmp_path / "metrics_off.jsonl"), warn=False) \
        if os.path.exists(tmp_path / "metrics_off.jsonl") else []
    assert not [r for r in recs if r.get("kind") == "span"]


# ------------------------------------------------------------ CI smoke gate


def test_smoke_trace_script(tmp_path):
    """The tracing CI drill end to end (tools/smoke_trace.sh): train ->
    2-replica fleet with a fault-injected slow replica -> traced bench
    through the router -> request_trace reconstructs >=99% complete
    trees and blames the slow replica's hop -> metrics_report --check
    green -> BENCH_TRACE.json through perf_ledger."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "smoke_trace.sh"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=570, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "smoke_trace: OK" in r.stdout
    bench = json.load(open(tmp_path / "BENCH_TRACE.json"))
    assert bench["metric"] == "serve_qps" and bench["value"] > 0
    assert bench["traced"] is True and bench["trace_echo_miss"] == 0
    assert "qps_untraced" in bench and "trace_overhead_pct" in bench
