"""Serving subsystem tests (xflow_tpu/serve, docs/SERVING.md).

Socket-free core first — the coalescer's flush rules, padding, the
hot-reload swap under concurrent requests, malformed-request rejection
— then the HTTP layer on a real loopback socket, serve/eval prediction
parity (the no-drift pin for models/predict.py), the kind="serve"
telemetry schema through metrics_report, and the CI smoke gate
(tools/smoke_serve.sh: loadgen + hot reload mid-flight).
"""

import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.serve.coalescer import (
    MicroBatcher,
    PendingRequest,
    RejectedRequest,
    assemble_batch,
)
from xflow_tpu.serve.runner import BadRequest, ServeRunner, parse_rows

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- coalescer
class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _rows(n, nnz=3):
    fields = [np.arange(nnz, dtype=np.int32) for _ in range(n)]
    slots = [np.full(nnz, 7, dtype=np.int32) for _ in range(n)]
    return fields, slots


def test_coalescer_size_flush_before_window():
    clock = FakeClock()
    mb = MicroBatcher(max_rows=4, window_s=100.0, clock=clock)
    futs = [mb.submit(*_rows(2)) for _ in range(2)]
    # 4 rows queued = max_rows: take returns NOW despite the huge window
    group = mb.take(timeout=0.0)
    assert group is not None and sum(r.num_rows for r in group) == 4
    assert all(not f.done() for f in futs)  # resolution is the worker's job


def test_coalescer_deadline_flush():
    clock = FakeClock()
    mb = MicroBatcher(max_rows=100, window_s=5.0, clock=clock)
    mb.submit(*_rows(1))
    assert mb.take(timeout=0.0) is None  # window not expired, no flush
    clock.t = 5.1
    group = mb.take(timeout=0.0)
    assert group is not None and len(group) == 1


def test_coalescer_whole_request_boundary():
    clock = FakeClock()
    mb = MicroBatcher(max_rows=4, window_s=0.0, clock=clock)
    mb.submit(*_rows(3))
    mb.submit(*_rows(3))
    g1 = mb.take(timeout=0.0)
    # 3 + 3 > 4: the second request must NOT split across batches
    assert [r.num_rows for r in g1] == [3]
    g2 = mb.take(timeout=0.0)
    assert [r.num_rows for r in g2] == [3]


def test_coalescer_rejects_oversized_and_backlog():
    mb = MicroBatcher(max_rows=4, window_s=0.0, max_queue_rows=6)
    with pytest.raises(RejectedRequest, match="max_batch"):
        mb.submit(*_rows(5))
    with pytest.raises(RejectedRequest, match="no rows"):
        mb.submit([], [])
    mb.submit(*_rows(4))
    mb.submit(*_rows(2))
    with pytest.raises(RejectedRequest, match="queue full"):
        mb.submit(*_rows(1))


def test_coalescer_close_drains_then_none():
    clock = FakeClock()
    mb = MicroBatcher(max_rows=8, window_s=100.0, clock=clock)
    mb.submit(*_rows(2))
    mb.close()
    with pytest.raises(RejectedRequest):
        mb.submit(*_rows(1))
    assert len(mb.take(timeout=0.0)) == 1  # backlog drains on close
    assert mb.take(timeout=0.0) is None  # then the worker's exit signal


def test_assemble_batch_padding_and_truncation():
    r1 = PendingRequest(
        fields=[np.asarray([1, 2], np.int32)], slots=[np.asarray([10, 20], np.int32)]
    )
    long = np.arange(9, dtype=np.int32)
    r2 = PendingRequest(fields=[long], slots=[long + 100])
    arrays, spans = assemble_batch([r1, r2], batch_size=4, max_nnz=4)
    assert arrays["slots"].shape == (4, 4)
    np.testing.assert_array_equal(arrays["slots"][0], [10, 20, 0, 0])
    np.testing.assert_array_equal(arrays["mask"][0], [1, 1, 0, 0])
    # truncation: a 9-feature row keeps its deterministic 4-prefix
    np.testing.assert_array_equal(arrays["slots"][1], [100, 101, 102, 103])
    np.testing.assert_array_equal(arrays["row_mask"], [1, 1, 0, 0])
    assert arrays["mask"][2:].sum() == 0  # ragged tail fully masked
    assert [(lo, hi) for _, lo, hi in spans] == [(0, 1), (1, 2)]


# ------------------------------------------------------------ row parsing
def test_parse_rows_label_optional_and_hash_parity():
    from xflow_tpu.data.libffm import parse_line

    cfg = Config()
    fr, sr = parse_rows(["0:tok1 1:tok2", "1\t0:tok1 1:tok2"], cfg.data)
    # a features-only row and a labeled libffm line parse identically
    np.testing.assert_array_equal(sr[0], sr[1])
    # and land in the training parser's slots exactly
    _, _, train_slots = parse_line(
        "1\t0:tok1 1:tok2", cfg.data.log2_slots, cfg.data.hash_salt
    )
    np.testing.assert_array_equal(sr[0], train_slots)


def test_parse_rows_rejects_malformed():
    cfg = Config()
    with pytest.raises(BadRequest, match="no parseable"):
        parse_rows(["nothing here"], cfg.data)
    with pytest.raises(BadRequest, match="expected a string"):
        parse_rows([42], cfg.data)
    with pytest.raises(BadRequest):
        parse_rows([""], cfg.data)


# ------------------------------------------------------------- fixtures
def _serve_cfg(ckpt_dir, **extra):
    base = {
        "data.batch_size": 64,
        "data.log2_slots": 12,
        "data.max_nnz": 8,
        "model.num_fields": 5,
        "model.name": "lr",
        "train.pred_dump": False,
        "train.checkpoint_dir": str(ckpt_dir),
        "serve.window_ms": 1.0,
        "serve.max_batch": 32,
        "serve.metrics_every_s": 0.2,
    }
    base.update(extra)
    return override(Config(), **base)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One trained run with committed checkpoints at steps 4..16 and an
    eval pred dump from the final state — shared by the parity, reload,
    and HTTP tests (training it once keeps the module fast)."""
    from xflow_tpu.data.synth import generate_shards
    from xflow_tpu.train.trainer import Trainer

    work = tmp_path_factory.mktemp("serve_fixture")
    generate_shards(
        str(work / "train"), 1, 512, num_fields=5, ids_per_field=30, seed=0
    )
    cfg = _serve_cfg(
        work / "ck",
        **{"data.train_path": str(work / "train"), "train.epochs": 2,
           "train.checkpoint_every": 4},
    )
    t = Trainer(cfg)
    res = t.fit()
    assert res.steps == 16
    cwd = os.getcwd()
    os.chdir(work)
    try:
        t.evaluate(test_path=str(work / "train-00000"), dump=True, block=0)
    finally:
        os.chdir(cwd)
    rows = [
        line.split("\t", 1)[1].strip()
        for line in open(work / "train-00000").read().splitlines()[:96]
    ]
    preds = [
        float(line.split("\t")[0])
        for line in open(work / "pred_0_0.txt").read().splitlines()[:96]
    ]
    return {"work": work, "rows": rows, "preds": preds}


# ------------------------------------------------- parity (the drift pin)
def test_serve_matches_evaluate_probabilities(trained):
    """The satellite pin: online serve output == offline evaluate()
    probabilities on the same rows (models/predict.py is the ONE
    forward both compile)."""
    cfg = _serve_cfg(trained["work"] / "ck")
    r = ServeRunner(cfg)
    gen = r.load()
    assert gen.step == 16
    p, _ = r.predict_rows(trained["rows"])
    np.testing.assert_allclose(
        p, np.asarray(trained["preds"], np.float32), atol=1e-5
    )


def test_mesh_serving_reshards_and_matches(trained):
    """Reshard-on-load for serving: the 1-process training checkpoint
    loads onto a multi-device serving mesh (tables pjit-sharded over
    all devices) and predicts the same probabilities."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("single-device jax build")
    from xflow_tpu.parallel.mesh import make_mesh

    cfg = _serve_cfg(trained["work"] / "ck")
    mesh = make_mesh(cfg)
    r = ServeRunner(cfg, mesh=mesh)
    r.load()
    # the serving tables really are sharded over the whole mesh
    sh = r.generation.tables["w"].sharding
    assert not sh.is_fully_replicated
    p, _ = r.predict_rows(trained["rows"])
    np.testing.assert_allclose(
        p, np.asarray(trained["preds"], np.float32), atol=1e-5
    )


# --------------------------------------------------------------- reload
def _stage_ckpt(src_ck, dst_ck, step):
    """Copy one committed step dir into the serving dir ATOMICALLY
    (payload lands under a temp name, one rename publishes it) — the
    contract a checkpoint-shipping pipeline must follow."""
    os.makedirs(dst_ck, exist_ok=True)
    tmp = os.path.join(dst_ck, f".staging_step_{step}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    shutil.copytree(os.path.join(src_ck, f"step_{step}"), tmp)
    os.replace(tmp, os.path.join(dst_ck, f"step_{step}"))


def test_hot_reload_swaps_without_dropping_requests(trained, tmp_path):
    """The tentpole invariant: a reload mid-traffic drops and blocks
    NOTHING; responses carry a monotone generation that flips to the
    new checkpoint step."""
    from xflow_tpu.serve.server import ServeApp

    src = trained["work"] / "ck"
    dst = tmp_path / "serving_ck"
    _stage_ckpt(src, dst, 4)
    cfg = _serve_cfg(dst)
    runner = ServeRunner(cfg)
    assert runner.load().step == 4
    app = ServeApp(cfg, runner)
    app.start()
    results = []
    errors = []
    stop = threading.Event()

    def client(i):
        body = json.dumps({"rows": [trained["rows"][i % 64]]}).encode()
        while not stop.is_set():
            asked = time.perf_counter()
            status, payload = app.handle_predict(body)
            if status != 200:
                errors.append((status, payload))
                return
            results.append(
                (asked, time.perf_counter(), payload["generation"], payload["step"])
            )

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 10.0
        while not any(g == 2 for _, _, g, _ in results):
            if time.monotonic() > deadline:
                break
            if runner.step == 4:
                _stage_ckpt(src, dst, 16)
                runner.maybe_reload()
            time.sleep(0.05)
        time.sleep(0.2)  # traffic on BOTH sides of the swap
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        app.close()
    assert not errors, errors[:3]
    assert {g for _, _, g, _ in results} == {1, 2}
    # monotone: once a client HAS an answer at generation 2, no request
    # made after that is answered at 1 (a request's own two instants, so
    # a client thread that is slow to note its answer proves nothing)
    flipped = min(got for _, got, g, _ in results if g == 2)
    late = [r for r in results if r[2] == 1 and r[0] > flipped]
    assert not late, late[:3]
    steps = {g: s for _, _, g, s in results}
    assert steps == {1: 4, 2: 16}


def test_bad_checkpoint_mid_reload_keeps_serving_old_generation(trained, tmp_path):
    """Failure-matrix row: a corrupt checkpoint committed mid-reload
    must keep the old generation serving (restore_any walks back; the
    runner refuses to regress to the step it already serves)."""
    src = trained["work"] / "ck"
    dst = tmp_path / "serving_ck"
    _stage_ckpt(src, dst, 16)
    cfg = _serve_cfg(dst)
    r = ServeRunner(cfg)
    assert r.load().step == 16
    # a torn/corrupt NEWER checkpoint, committed: garbage npz + marker
    bad = dst / "step_99"
    bad.mkdir()
    (bad / "state.npz").write_bytes(b"this is not an npz file")
    (bad / "COMMITTED").write_text("ok\n")
    assert r.maybe_reload() is None  # walk-back lands on step 16 = serving
    assert r.step == 16 and r.generation.gen == 1
    p, gen = r.predict_rows(trained["rows"][:4])
    assert gen.gen == 1 and p.shape == (4,)


def test_watcher_does_not_retry_a_permanently_bad_step(trained, tmp_path):
    """A corrupt newest step must fail ONCE per committed step, not
    once per poll — no disk-thrash loop, no reload_failed spam."""
    from xflow_tpu.serve.runner import CheckpointWatcher

    src = trained["work"] / "ck"
    dst = tmp_path / "serving_ck"
    _stage_ckpt(src, dst, 8)
    cfg = _serve_cfg(dst)
    r = ServeRunner(cfg)
    r.load()
    bad = dst / "step_99"
    bad.mkdir()
    (bad / "state.npz").write_bytes(b"garbage")
    (bad / "COMMITTED").write_text("ok\n")
    w = CheckpointWatcher(r, poll_s=0.02)
    w.start()
    try:
        time.sleep(0.6)  # ~30 polls
    finally:
        w.close()
    assert w.failures == 1, w.failures
    assert r.step == 8 and r.generation.gen == 1  # still serving


def test_watcher_reloads_on_newer_commit(trained, tmp_path):
    from xflow_tpu.serve.runner import CheckpointWatcher

    src = trained["work"] / "ck"
    dst = tmp_path / "serving_ck"
    _stage_ckpt(src, dst, 8)
    cfg = _serve_cfg(dst)
    r = ServeRunner(cfg)
    r.load()
    seen = []
    w = CheckpointWatcher(r, poll_s=0.05, on_reload=lambda g: seen.append(g.step))
    w.start()
    try:
        _stage_ckpt(src, dst, 12)
        deadline = time.monotonic() + 10
        while r.step != 12 and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        w.close()
    assert r.step == 12 and seen == [12] and w.reloads == 1


# ----------------------------------------------------------- HTTP layer
@pytest.fixture()
def http_app(trained):
    from xflow_tpu.serve.server import ServeApp, make_http_server

    cfg = _serve_cfg(trained["work"] / "ck")
    runner = ServeRunner(cfg)
    runner.load()
    app = ServeApp(cfg, runner)
    app.start()
    srv = make_http_server(app, "127.0.0.1", 0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield app, srv.server_address[1]
    srv.shutdown()
    srv.server_close()
    app.close()


def _post(port, body, path="/predict"):
    import http.client

    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    c.request("POST", path, body, {"Content-Type": "application/json"})
    resp = c.getresponse()
    payload = json.loads(resp.read())
    c.close()
    return resp.status, payload


def test_http_malformed_requests_400_server_survives(trained, http_app):
    app, port = http_app
    # each malformed shape -> 400 with a reason, never a crash
    assert _post(port, b"not json")[0] == 400
    assert _post(port, json.dumps({"rows": []}))[0] == 400
    assert _post(port, json.dumps({"nope": 1}))[0] == 400
    assert _post(port, json.dumps({"rows": ["tokens without any colon"]}))[0] == 400
    assert _post(port, json.dumps({"rows": [123]}))[0] == 400
    # oversized request: client error, not load shedding
    too_big = json.dumps({"rows": ["0:a"] * 33})
    assert _post(port, too_big)[0] == 400
    # the server is still serving after all of that
    status, payload = _post(port, json.dumps({"rows": trained["rows"][:2]}))
    assert status == 200
    assert len(payload["pctr"]) == 2 and payload["generation"] == 1
    np.testing.assert_allclose(
        payload["pctr"], trained["preds"][:2], atol=1e-5
    )
    # and counted the rejects in the serve telemetry
    from xflow_tpu.telemetry import default_registry

    assert default_registry().counter("serve.bad_requests").value >= 6


def test_http_healthz_and_stats(http_app):
    import http.client

    _, port = http_app
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    c.request("GET", "/healthz")
    h = json.loads(c.getresponse().read())
    assert h["ok"] and h["step"] == 16 and h["generation"] == 1
    c.request("GET", "/stats")
    s = json.loads(c.getresponse().read())
    assert "registry" in s
    c.request("GET", "/nope")
    assert c.getresponse().status == 404
    c.close()


def test_concurrent_http_requests_coalesce(trained, http_app):
    """N concurrent 1-row requests answer from FEWER device batches
    than requests — the microbatching win, visible in batch_fill."""
    app, port = http_app
    from xflow_tpu.telemetry import default_registry

    reg = default_registry()
    req0 = reg.counter("serve.requests").value
    bat0 = reg.counter("serve.batches").value
    import concurrent.futures as cf

    body = json.dumps({"rows": trained["rows"][:1]})
    with cf.ThreadPoolExecutor(16) as ex:
        statuses = list(ex.map(lambda _: _post(port, body)[0], range(48)))
    assert statuses == [200] * 48
    requests = reg.counter("serve.requests").value - req0
    batches = reg.counter("serve.batches").value - bat0
    assert requests == 48
    assert batches < requests, (batches, requests)


# ------------------------------------------------------- serve telemetry
def test_serve_metrics_window_schema(tmp_path):
    from xflow_tpu.serve.metrics import SERVE_WINDOW_KEYS, ServeMetrics

    path = tmp_path / "serve.jsonl"
    m = ServeMetrics(str(path), every_s=60.0, batch_size=32)
    m.event("start", generation=1, step=4)
    m.observe_batch(2, 3, [0.001, 0.002], 0.004, [0.005, 0.006])
    m.observe_bad_request()
    rec = m.maybe_flush(1, 4, force=True)
    for k in SERVE_WINDOW_KEYS:
        assert k in rec, k
    assert rec["batch_fill"] == pytest.approx(3 / 32, abs=1e-4)
    m.event("reload", generation=2, step=8)
    m.close(2, 8)
    # the file passes the report tool's schema gate
    mr = _metrics_report()
    assert mr.main([str(path), "--check"]) == 0


def _metrics_report():
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import metrics_report as mr

    return mr


def _serve_rec(run_id="r1", rank=0, gen=0, ts=1.0, **kw):
    base = {"ts": ts, "rank": rank, "run_id": run_id, "gen": gen,
            "kind": "serve"}
    base.update(kw)
    return base


def _window(generation, step, ts=1.0, **kw):
    from xflow_tpu.serve.metrics import SERVE_WINDOW_KEYS

    rec = {k: 1 for k in SERVE_WINDOW_KEYS}
    rec.update(generation=generation, step=step)
    rec.update(kw)
    return _serve_rec(ts=ts, **rec)


def _write(tmp_path, name, recs):
    p = tmp_path / name
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(p)


def test_check_rejects_generation_regression(tmp_path):
    mr = _metrics_report()
    ok = _write(tmp_path, "ok.jsonl", [_window(1, 4, ts=1.0), _window(2, 8, ts=2.0)])
    assert mr.main([ok, "--check"]) == 0
    bad = _write(
        tmp_path, "bad.jsonl", [_window(2, 8, ts=1.0), _window(1, 8, ts=2.0)]
    )
    assert mr.main([bad, "--check"]) == 2


def test_check_rejects_partial_serve_window(tmp_path):
    mr = _metrics_report()
    rec = _window(1, 4)
    del rec["batch_fill"]
    assert mr.main([_write(tmp_path, "p.jsonl", [rec]), "--check"]) == 2
    # a record that is neither window nor event fails too
    stray = _serve_rec(other=1)
    assert mr.main([_write(tmp_path, "s.jsonl", [stray]), "--check"]) == 2


def test_serve_bench_record_and_table(tmp_path, capsys):
    mr = _metrics_report()
    path = _write(
        tmp_path,
        "serve.jsonl",
        [
            _serve_rec(event="start", generation=1, step=4),
            _window(1, 4, ts=1.0, requests=10, rows=20, qps=100.0,
                    window_s=0.1, total_p50_ms=2.0, total_p99_ms=9.0),
            _serve_rec(event="reload", generation=2, step=16, ts=1.5),
            _window(2, 16, ts=2.0, requests=30, rows=60, qps=300.0,
                    window_s=0.1, total_p50_ms=3.0, total_p99_ms=7.0),
        ],
    )
    assert mr.main([path]) == 0
    out = capsys.readouterr().out
    assert "serving (kind=serve):" in out
    streams, _ = mr.load_streams([path])
    rec = mr.serve_bench_record(streams)
    assert rec["metric"] == "serve_qps"
    assert rec["requests"] == 40 and rec["rows"] == 80
    # 40 requests over 0.2s of windows — computed from totals, not the
    # records' own qps fields
    assert rec["value"] == pytest.approx(200.0, rel=0.01)
    assert rec["reloads"] == 1 and rec["generations"] == [1, 2]
    assert rec["p99_ms"] == 9.0
    # --bench-json falls back to the serve record for serve-only dirs
    out_json = tmp_path / "B.json"
    assert mr.main([path, "--bench-json", str(out_json)]) == 0
    assert json.load(open(out_json))["metric"] == "serve_qps"


def test_serve_bench_record_time_weights_sequential_generations(tmp_path):
    """A restarted server's generations run SEQUENTIALLY: 100 qps in
    gen 0 then 100 qps in gen 1 is 100 qps, not 200 (concurrent RANKS
    still add)."""
    mr = _metrics_report()
    recs = [
        _window(1, 4, ts=1.0, gen=0, requests=10, window_s=0.1),
        _window(1, 4, ts=2.0, gen=1, requests=10, window_s=0.1),
        _window(1, 4, ts=1.0, gen=0, rank=1, requests=10, window_s=0.1),
    ]
    streams, _ = mr.load_streams([_write(tmp_path, "g.jsonl", recs)])
    rec = mr.serve_bench_record(streams)
    # rank 0: 20 reqs over 0.2s = 100 qps; rank 1 (concurrent): +100
    assert rec["value"] == pytest.approx(200.0, rel=0.01)
    assert rec["requests"] == 30


def test_summarize_serve_stream_aggregates():
    mr = _metrics_report()
    recs = [
        _window(1, 4, requests=10, rows=20, qps=100.0, window_s=0.1,
                batches=5, batch_fill=0.5, bad_requests=1),
        _serve_rec(event="reload_failed"),
        _window(1, 4, requests=10, rows=40, qps=100.0, window_s=0.1,
                batches=5, batch_fill=1.0, bad_requests=0),
    ]
    s = mr.summarize_serve_stream(recs)
    assert s["requests"] == 20 and s["rows"] == 60 and s["windows"] == 2
    assert s["qps"] == pytest.approx(100.0)
    assert s["batch_fill"] == pytest.approx(0.75)
    assert s["bad_requests"] == 1 and s["reload_failures"] == 1


# -------------------------------------------------------------------- CLI
def test_cli_serve_requires_and_validates_checkpoint(tmp_path):
    from xflow_tpu.launch.cli import main as cli_main

    # no checkpoints under the dir: clean failure, not a traceback
    rc = cli_main(["serve", "--checkpoint-dir", str(tmp_path / "empty")])
    assert rc == 1


# ----------------------------------------------------------- CI smoke gate
def test_smoke_serve_script(tmp_path):
    """The serving CI gate end to end (tools/smoke_serve.sh): train ->
    serve -> loadgen -> hot reload mid-load (generation flip, zero
    failed requests) -> serve/eval parity -> metrics_report --check ->
    BENCH_SERVE.json."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "smoke_serve.sh"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=570, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "smoke_serve: OK" in r.stdout
    assert "hot reload OK" in r.stdout
    assert "parity OK" in r.stdout
    bench = json.load(open(tmp_path / "BENCH_SERVE.json"))
    assert bench["metric"] == "serve_qps" and bench["value"] > 0
    assert bench["errors"] == 0 and bench["gen_flips"] >= 1
