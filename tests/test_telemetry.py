"""Telemetry subsystem tests (xflow_tpu/telemetry.py, jsonl.py,
tools/metrics_report.py, tools/smoke_telemetry.sh): registry semantics,
StepTimer decomposition, trace windows, record stamping, the
truncation-tolerant reader, and the report tool's summary/check paths.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.data.synth import generate_shards
from xflow_tpu.jsonl import JsonlAppender, read_jsonl, read_jsonl_counted
from xflow_tpu import telemetry
from xflow_tpu.telemetry import (
    HOST_CONSUMER_STAGES,
    HOST_SPANS,
    HOST_SPANS_FIT,
    HOST_SPANS_PREFETCH,
    HOST_STAGES,
    PIPELINE_PRODUCER_STAGES,
    PipelineProfiler,
    Registry,
    StepTimer,
    TraceWindow,
    default_registry,
    host_field,
    host_fields,
    resolve_run_id,
    span,
)
from xflow_tpu.train.trainer import Trainer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ registry


def test_counter_semantics():
    r = Registry()
    c = r.counter("n")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert r.counter("n") is c  # create-or-get
    with pytest.raises(ValueError):
        c.inc(-1)  # counters are monotone


def test_gauge_semantics():
    r = Registry()
    g = r.gauge("depth")
    g.set(3)
    g.set(1.5)
    assert g.value == 1.5


def test_timer_window_percentiles():
    r = Registry()
    t = r.timer("lat")
    for ms in (1, 2, 3, 4, 100):
        t.observe(ms / 1e3)
    assert t.count == 5
    assert t.total_s == pytest.approx(0.110)
    assert t.percentile(50) == pytest.approx(0.003)
    assert t.percentile(99) == pytest.approx(0.100, rel=0.05)
    window = t.window_reset()
    assert len(window) == 5
    # window cleared, totals survive
    assert np.isnan(t.percentile(50))
    assert t.count == 5
    with t.timing():
        time.sleep(0.01)
    assert t.count == 6 and t.percentile(50) >= 0.01


def test_registry_kind_clash_and_snapshot():
    r = Registry()
    r.counter("x").inc(2)
    r.gauge("y").set(7)
    r.timer("z").observe(0.5)
    with pytest.raises(TypeError):
        r.gauge("x")
    snap = r.snapshot()
    assert snap["x"] == 2 and snap["y"] == 7
    assert snap["z.count"] == 1 and snap["z.total_s"] == pytest.approx(0.5)
    r.reset()
    assert r.snapshot() == {}


def test_default_registry_is_shared():
    assert default_registry() is default_registry()


# ----------------------------------------------------------------- StepTimer


def test_step_timer_decomposition_synthetic():
    """30 synthetic steps with known host-side sleeps: every field
    present, steps counted, per-step sum components sane, and the
    step-time total telescopes to the elapsed wall time."""
    st = StepTimer(registry=Registry())

    def feed():
        for i in range(30):
            time.sleep(0.002)  # data wait, inside next()
            yield i

    t0 = time.perf_counter()
    for _ in st.batches(feed()):
        time.sleep(0.001)  # "dispatch"
        st.dispatched({"loss": np.float32(0.5)}, rows=64)
    st.flush()
    elapsed = time.perf_counter() - t0
    assert st.steps == 30
    assert st.rows == 30 * 64
    rec = st.window_record()
    for key in ("steps_per_s", "rows_per_s", "step_time_p50_ms",
                "step_time_p99_ms", "data_wait_ms", "dispatch_ms", "device_ms"):
        assert key in rec, key
    assert rec["data_wait_ms"] >= 2.0  # the sleep inside next()
    assert rec["dispatch_ms"] >= 1.0  # the sleep before dispatched()
    assert rec["step_time_p99_ms"] >= rec["step_time_p50_ms"] > 0
    # completion intervals telescope: their sum is the run's elapsed time
    assert st.steps / max(rec["steps_per_s"], 1e-9) == pytest.approx(
        elapsed, rel=0.25
    )
    # window consumed
    assert st.window_record() == {}


def test_step_timer_sum_matches_elapsed():
    st = StepTimer(registry=Registry())
    reg = st._reg
    t0 = time.perf_counter()
    for _ in st.batches(iter(range(10))):
        time.sleep(0.003)
        st.dispatched({"loss": 0.0}, rows=1)
    st.flush()
    elapsed = time.perf_counter() - t0
    assert reg.timer("step.time").count == 10
    assert reg.timer("step.time").total_s == pytest.approx(elapsed, rel=0.2)


def test_step_timer_closes_abandoned_iterator():
    closed = {}

    def feed():
        try:
            while True:
                yield 0
        finally:
            closed["yes"] = True

    st = StepTimer(registry=Registry())
    for i, _ in enumerate(st.batches(feed())):
        st.dispatched({}, rows=1)
        if i == 2:
            break
    import gc

    gc.collect()
    assert closed.get("yes"), "abandoned inner iterator was not closed"


# --------------------------------------------------------------- TraceWindow


class FakeProfiler:
    def __init__(self):
        self.events = []

    def start_trace(self, d):
        self.events.append(("start", d))

    def stop_trace(self):
        self.events.append(("stop", None))


def test_trace_window_respects_step_range():
    prof = FakeProfiler()
    tw = TraceWindow("dir", start_step=5, num_steps=3, profiler=prof)
    tw.maybe_start_run()
    assert prof.events == []  # window mode: nothing pre-loop
    for step in range(1, 13):
        tw.before_step(step)
        if step < 5:
            assert prof.events == [], f"started early at step {step}"
    tw.close()
    assert prof.events == [("start", "dir"), ("stop", None)]
    # stop fired when step 8 dispatched (5,6,7 traced), not at close
    tw2 = TraceWindow("dir", 5, 3, profiler=FakeProfiler())
    for step in range(1, 8):
        tw2.before_step(step)
    assert tw2._running  # step 8 never dispatched
    tw2.close()
    assert not tw2._running


def test_trace_window_whole_run_mode():
    prof = FakeProfiler()
    tw = TraceWindow("dir", start_step=0, profiler=prof)
    tw.maybe_start_run()
    for step in range(1, 5):
        tw.before_step(step)
    tw.close()
    assert prof.events == [("start", "dir"), ("stop", None)]


def test_trace_window_disabled_without_dir():
    tw = TraceWindow("", start_step=5, num_steps=3, profiler=FakeProfiler())
    tw.maybe_start_run()
    tw.before_step(5)
    tw.close()
    assert tw._prof.events == []


# --------------------------------------------------- trainer integration


def _train_cfg(tmp_path, **kw):
    base = {
        "data.train_path": str(tmp_path / "train"),
        "data.log2_slots": 12,
        "data.batch_size": 64,
        "data.max_nnz": 8,
        "model.num_fields": 6,
        "train.epochs": 1,
        "train.log_every": 10,
        "train.pred_dump": False,
    }
    base.update(kw)
    return override(Config(), **base)


@pytest.fixture
def train_data(tmp_path):
    generate_shards(
        str(tmp_path / "train"), 1, 1920, num_fields=6, ids_per_field=40, seed=0
    )
    return tmp_path


def test_trainer_emits_stamped_window_records(train_data, tmp_path, monkeypatch):
    """Acceptance gate: every record carries ts/rank/run_id; log-window
    records carry the full step decomposition; steps monotone; step-time
    totals ≈ the run's elapsed seconds."""
    monkeypatch.chdir(tmp_path)
    mpath = tmp_path / "run" / "metrics_rank0.jsonl"
    cfg = _train_cfg(train_data, **{"train.metrics_path": str(mpath)})
    # the default registry holds PROCESS totals — clear what earlier
    # tests in this pytest process accumulated so counts are exact
    default_registry().reset()
    res = Trainer(cfg).fit()
    assert res.steps == 30
    recs = read_jsonl(str(mpath))
    assert recs
    for r in recs:
        assert "ts" in r and "rank" in r and "run_id" in r
        assert r["rank"] == 0
    assert len({r["run_id"] for r in recs}) == 1
    windows = [r for r in recs if "rows_per_s" in r]
    assert windows, "no window records"
    for w in windows:
        for key in ("rows_per_s", "steps_per_s", "step_time_p50_ms",
                    "step_time_p99_ms", "data_wait_ms", "dispatch_ms",
                    "device_ms"):
            assert key in w, key
        assert w["rows_per_s"] > 0
        assert w["step_time_p99_ms"] >= w["step_time_p50_ms"] > 0
    steps = [r["step"] for r in recs if "step" in r]
    assert steps == sorted(steps)
    # pipeline counters rode along and the step-time totals telescope
    final = next(r for r in recs if r.get("final"))
    counters = final["counters"]
    assert counters["data.batches"] == 30
    assert counters["data.rows"] == 1920
    assert counters["step.time.count"] == 30
    assert counters["step.time.total_s"] == pytest.approx(res.seconds, rel=0.2)


def test_trainer_trace_window_mid_run(train_data, tmp_path, monkeypatch):
    """Programmatic window: profile dir non-empty, and the profiler was
    started/stopped exactly once at the configured steps."""
    monkeypatch.chdir(tmp_path)
    import glob

    import jax

    calls = []
    real_start, real_stop = jax.profiler.start_trace, jax.profiler.stop_trace
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d: (calls.append("start"), real_start(d))[1],
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: (calls.append("stop"), real_stop())[1]
    )
    cfg = _train_cfg(
        train_data,
        **{
            "train.profile_dir": str(tmp_path / "prof"),
            "train.trace_start_step": 5,
            "train.trace_num_steps": 5,
        },
    )
    Trainer(cfg).fit()
    assert calls == ["start", "stop"]
    traces = glob.glob(str(tmp_path / "prof" / "**" / "*"), recursive=True)
    assert traces, "trace window produced no profiler output"


# ------------------------------------------------------- the host timeline


class SpanLog:
    """Stands in for jax.profiler.TraceAnnotation (telemetry._annotation,
    the seam TraceWindow's `profiler` argument is for the trace): logs
    every annotation's begin and end with the thread that made it."""

    def __init__(self):
        self.events = []  # (thread name, "B" | "E", annotation name)

    def __call__(self, name):
        events = self.events

        class _Ann:
            def __enter__(self):
                events.append((threading.current_thread().name, "B", name))
                return self

            def __exit__(self, *exc):
                events.append((threading.current_thread().name, "E", name))
                return False

        return _Ann()

    def tree(self, thread):
        """[(depth, name)] of the thread's spans in opening order;
        raises where a span closes out of turn."""
        stack, out = [], []
        for th, kind, name in self.events:
            if th != thread:
                continue
            if kind == "B":
                out.append((len(stack), name))
                stack.append(name)
            else:
                assert stack and stack[-1] == name, f"{name} closed over {stack}"
                stack.pop()
        assert not stack, f"left open: {stack}"
        return out


@pytest.fixture
def span_log(monkeypatch):
    log = SpanLog()
    monkeypatch.setattr(telemetry, "_annotation", log)
    return log


def test_span_names_and_host_fields_come_from_one_tuple():
    """The annotation's name and the records' field are one string: the
    per-step stages the profiler accumulates are spans of their thread,
    and `host` holds exactly one field a stage."""
    assert set(HOST_CONSUMER_STAGES) - {"loop_other"} <= set(HOST_SPANS_FIT)
    # (xflow:read_ahead wraps the others over a pass's end: a span, no stage)
    # `ffm_place` runs inside `plan`: a part of that stage, not one of the tiling
    assert set(HOST_SPANS_PREFETCH) - {"read_ahead", "ffm_place"} <= set(PIPELINE_PRODUCER_STAGES)
    assert telemetry._SPAN_NAMES == {s: "xflow:" + s for s in HOST_SPANS}
    assert len(set(HOST_SPANS)) == len(HOST_SPANS)
    prof = PipelineProfiler(registry=Registry())
    prof.add("parse", 0.002)
    got = host_fields(prof.take_window())
    assert set(got) == {host_field(s) for s in HOST_STAGES} | {"batches"}
    assert got["parse_ms"] == 2.0
    # the train_step call's field steps aside for the StepTimer's
    # wider dispatch_ms, one level up in the same record
    assert host_field("dispatch") == "dispatch_call_ms"
    assert host_field("prev_ready") == "prev_ready_ms"
    with pytest.raises(KeyError):
        span("not_a_stage")


def test_span_annotates_always_and_accumulates_when_armed(span_log):
    prof = PipelineProfiler(registry=Registry())
    with span("plan") as unarmed:
        time.sleep(0.002)
    with span("plan", prof) as armed:
        time.sleep(0.002)
    with span("parse", prof):
        pass
    assert [e[1:] for e in span_log.events] == [
        ("B", "xflow:plan"), ("E", "xflow:plan"),
        ("B", "xflow:plan"), ("E", "xflow:plan"),
        ("B", "xflow:parse"), ("E", "xflow:parse"),
    ]
    assert unarmed.seconds >= 0.002 and armed.seconds >= 0.002
    totals, _ = prof.totals()
    assert totals["plan"] == pytest.approx(armed.seconds)  # the armed one alone
    assert totals["parse"] > 0


def test_fit_opens_the_vocabulary_nested_on_its_threads(
    train_data, tmp_path, monkeypatch, span_log
):
    """One fit() opens exactly the spans of the table in
    docs/OBSERVABILITY.md "The host timeline": the fit loop's under
    xflow:fit in loop order, the producer's on the prefetch thread —
    with nobody listening (no metrics, no profile: the spans are the
    loop's own, not the profiler's)."""
    monkeypatch.chdir(tmp_path)
    main = threading.current_thread().name
    trainer = Trainer(_train_cfg(train_data, **{"train.log_every": 0}))
    assert trainer.pipeline_prof is None
    assert span_log.tree(main) == [(0, "xflow:init_state")]
    del span_log.events[:]
    res = trainer.fit()
    n = res.steps
    assert n == 30
    tree = span_log.tree(main)
    assert tree[0] == (0, "xflow:fit")
    per_step = ["xflow:data_wait", "xflow:transfer", "xflow:dispatch",
                "xflow:prev_ready"]
    assert [name for depth, name in tree if depth == 1] == (
        ["xflow:fit_open"] + per_step * n
        + ["xflow:data_wait", "xflow:fit_flush", "xflow:occupancy",
           "xflow:fit_close"]
    )
    # deeper: the state is placed before the loop (inside fit_open), the
    # first dispatch compiles the step, and the pass's terminating
    # next() holds the prefetch teardown
    deeper = [name for depth, name in tree if depth >= 2]
    assert deeper[:3] == ["xflow:place_state", "xflow:lower", "xflow:compile"]
    assert deeper.count("xflow:iter_end") == 1
    last_wait = max(i for i, t in enumerate(tree) if t == (1, "xflow:data_wait"))
    assert tree[last_wait + 1] == (2, "xflow:iter_end")
    # the producer's, on its own thread, and nothing of the loop's: the
    # pass, then — under xflow:read_ahead — the head start on the next,
    # which ends where the queue is full (the worker waits outside it)
    deadline = time.time() + 10
    while trainer._read_ahead._head < 3 and time.time() < deadline:
        time.sleep(0.01)  # depth 2 ready + one in hand: the worker waits
    trainer._read_ahead.close()  # and its open span closes with it
    tree = span_log.tree("xflow-prefetch")
    ahead = tree.index((0, "xflow:read_ahead"))
    prod = [name for _, name in tree[:ahead]]
    assert prod.count("xflow:parse") == n + 1  # one a batch, and the EOF
    assert prod.count("xflow:plan") == n
    assert prod.count("xflow:producer_wait") == n + 1  # and the end-of-pass mark
    assert set(prod) == {"xflow:parse", "xflow:plan", "xflow:producer_wait"}
    head = tree[ahead + 1:]
    built = [name for depth, name in head if depth == 1]
    assert built.count("xflow:plan") == 3
    assert set(built) == {"xflow:parse", "xflow:plan", "xflow:producer_wait"}
    assert head[-1] == (0, "xflow:producer_wait")
    # everything opened is in the one tuple, and all of it turned up
    # but the cache's reader (text shards here) and FFM's placement (an
    # FM run; tests/test_ffm_reference.py opens that one)
    opened = {e[2] for e in span_log.events} | {"xflow:init_state"}
    assert opened == {"xflow:" + s for s in HOST_SPANS} - {"xflow:cache_read", "xflow:ffm_place"}


def test_unarmed_run_builds_no_profiler_and_its_stream_is_what_it_was(
    train_data, tmp_path, monkeypatch
):
    """metrics_path, profile_dir, pipeline_metrics all unset: no
    profiler object, no record, no pipeline gauge; each alone arms."""
    monkeypatch.chdir(tmp_path)
    default_registry().reset()
    trainer = Trainer(_train_cfg(train_data))
    assert trainer.pipeline_prof is None
    assert not trainer.metrics.enabled
    res = trainer.fit()
    assert res.steps == 30 and trainer._prev_fit is None
    assert not any(k.startswith("pipeline.") for k in default_registry().snapshot())
    for key, value in (
        ("train.metrics_path", str(tmp_path / "m.jsonl")),
        ("train.profile_dir", str(tmp_path / "prof")),
        ("train.pipeline_metrics", True),
    ):
        armed = Trainer(_train_cfg(train_data, **{key: value}))
        assert armed.pipeline_prof is not None, key
        assert armed.pipeline_prof.publish == (key == "train.pipeline_metrics")


def _two_fits(train_data, tmp_path, monkeypatch, **kw):
    """Two fit() calls on one Trainer, a record a step; returns the
    two fits' records and the perf_counter stamps an outsider can take:
    just before the first fit(), and each fit()'s last step ready (the
    return of StepTimer.flush)."""
    monkeypatch.chdir(tmp_path)
    ready = []

    class Stamped(StepTimer):
        def flush(self):
            super().flush()
            ready.append(time.perf_counter())

    import xflow_tpu.train.trainer as trainer_mod

    monkeypatch.setattr(trainer_mod, "StepTimer", Stamped)
    mpath = tmp_path / "run" / "metrics_rank0.jsonl"
    cfg = _train_cfg(train_data, **{
        "train.metrics_path": str(mpath), "train.log_every": 1, **kw})
    trainer = Trainer(cfg)
    entered = time.perf_counter()
    trainer.fit()
    n_first = len(read_jsonl(str(mpath)))
    time.sleep(0.03)  # the caller's own time between the fits
    trainer.fit()
    recs = read_jsonl(str(mpath))
    return recs[:n_first], recs[n_first:], entered, ready


def _steps(recs):
    return [r for r in recs if "step_time_p50_ms" in r and not r.get("kind")]


def test_boundary_tiles_the_wall_between_two_fits(train_data, tmp_path, monkeypatch):
    """Step intervals plus the out-of-step parts of `boundary` add up to
    the wall from the first `_fit` entry to the second's last step
    ready: nothing between two passes is outside the records."""
    first, second, entered, ready = _two_fits(train_data, tmp_path, monkeypatch)
    s1, s2 = _steps(first), _steps(second)
    assert len(s1) == 30 and len(s2) == 30  # a record a step
    b1, b2 = s1[0]["boundary"], s2[0]["boundary"]
    assert not any("boundary" in r for r in s1[1:] + s2[1:])
    for b in (b1, b2):
        assert all(v >= 0 for v in b.values()), b
    assert set(b2) == {"fit_tail_ms", "occupancy_ms", "close_ms", "between_fits_ms",
                       "fit_open_ms", "first_batch_ms", "first_dispatch_ms", "adopted"}
    assert b2["adopted"] is True and b1["adopted"] is False
    # the tail's named parts fit inside it, and the caller's sleep is
    # in between_fits_ms, not in the program's tail or open
    assert b2["occupancy_ms"] + b2["close_ms"] <= b2["fit_tail_ms"] + 1e-3
    assert b2["between_fits_ms"] >= 30.0
    # the first step's interval holds its own cold start
    assert s2[0]["step_time_p50_ms"] >= b2["first_batch_ms"] + b2["first_dispatch_ms"] - 1e-2
    intervals = sum(r["step_time_p50_ms"] for r in s1 + s2)
    outside = (b1["fit_open_ms"] + b2["fit_tail_ms"] + b2["between_fits_ms"]
               + b2["fit_open_ms"])
    wall_ms = (ready[1] - entered) * 1e3
    assert intervals + outside == pytest.approx(wall_ms, abs=5.0)


def test_first_fit_of_a_trainer_has_no_tail_behind_it(train_data, tmp_path, monkeypatch):
    first, _, _, _ = _two_fits(train_data, tmp_path, monkeypatch)
    assert set(_steps(first)[0]["boundary"]) == {
        "fit_open_ms", "first_batch_ms", "first_dispatch_ms", "adopted"}


def test_final_record_carries_its_own_fit_end(train_data, tmp_path, monkeypatch):
    """`final`: the terminating next(), the wait for the last step and
    the occupancy sweep of its own fit(); and with a log cadence longer
    than the pass, the pass's `host` window and `boundary` too."""
    first, second, _, _ = _two_fits(
        train_data, tmp_path, monkeypatch, **{"train.log_every": 100})
    for recs in (first, second):
        assert not [r for r in _steps(recs) if not r.get("final")]
        final = next(r for r in recs if r.get("final"))
        for key in ("iter_end_ms", "fit_flush_ms", "occupancy_ms"):
            assert final[key] >= 0, key
        assert final["host"]["batches"] == 30
        assert "fit_open_ms" in final["boundary"]
    assert "fit_tail_ms" in next(r for r in second if r.get("final"))["boundary"]
    # the second fit()'s boundary names the first's sweep
    f1 = next(r for r in first if r.get("final"))
    f2 = next(r for r in second if r.get("final"))
    assert f2["boundary"]["occupancy_ms"] == pytest.approx(f1["occupancy_ms"], abs=2e-3)


def test_cache_reader_opens_one_span_a_batch(tmp_path, span_log):
    from xflow_tpu.data.shardcache import (
        build_cache, cache_path_for, open_shard_cache,
    )

    generate_shards(str(tmp_path / "t"), 1, 200, num_fields=6, ids_per_field=40, seed=1)
    cfg = _train_cfg(tmp_path).data
    build_cache(str(tmp_path / "t"), cfg)
    cache = cache_path_for(str(tmp_path / "t-00000"), cfg.cache_dir)
    del span_log.events[:]  # the build parsed the text: its spans are not the reader's
    prof = PipelineProfiler(registry=Registry())
    batches = list(open_shard_cache(cache).iter_batches(64, profiler=prof))
    assert [b.num_rows for b in batches] == [64, 64, 64, 8]
    assert [e[2] for e in span_log.events if e[1] == "B"] == ["xflow:cache_read"] * 4
    assert prof.rows == 200 and prof.totals()[0]["cache_read"] > 0


def test_quarantine_records_are_stamped(tmp_path):
    """Quarantine and metrics streams must be joinable: both stamped
    with ts/rank/run_id by the shared appender."""
    from xflow_tpu.data.pipeline import batch_iterator
    from xflow_tpu.testing.faults import write_malformed_libffm

    shard = tmp_path / "junk-00000"
    info = write_malformed_libffm(str(shard), n_good=30, n_bad=4, seed=1)
    qpath = tmp_path / "quarantine.jsonl"
    cfg = override(
        Config(),
        **{
            "data.batch_size": 16,
            "data.max_bad_rows": 100,
            "data.quarantine_path": str(qpath),
            "data.log2_slots": 12,
            "data.max_nnz": 8,
        },
    ).data
    list(batch_iterator(str(shard), cfg))
    recs = read_jsonl(str(qpath))
    assert len(recs) == info["bad"]
    for r in recs:
        assert "ts" in r and "rank" in r and "run_id" in r
        assert r["source"] == str(shard)
    # same process → same run id as any other sink would stamp
    assert recs[0]["run_id"] == resolve_run_id()


# ------------------------------------------------------- tolerant reader


def test_read_jsonl_skips_truncated_tail(tmp_path, capsys):
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"step": 1}) + "\n")
        f.write(json.dumps({"step": 2}) + "\n")
        f.write('{"step": 3, "loss": 0.4')  # crash mid-append
    recs, skipped = read_jsonl_counted(str(p))
    assert [r["step"] for r in recs] == [1, 2]
    assert skipped == 1
    assert "skipped 1 unparseable" in capsys.readouterr().err


def test_read_jsonl_skips_mid_file_garbage(tmp_path):
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"step": 1}) + "\n")
        f.write("not json at all\n")
        f.write('[1, 2]\n')  # parseable but not a record
        f.write(json.dumps({"step": 2}) + "\n")
    recs, skipped = read_jsonl_counted(str(p), warn=False)
    assert [r["step"] for r in recs] == [1, 2]
    assert skipped == 2


def test_appender_stamps_and_reopens(tmp_path):
    p = tmp_path / "a.jsonl"
    a = JsonlAppender(str(p), stamp={"rank": 3, "run_id": "r1"})
    a.append({"x": 1})
    a.close()
    a.append({"x": 2})  # transparent reopen
    a.close()
    recs = read_jsonl(str(p))
    assert [r["x"] for r in recs] == [1, 2]
    assert all(r["rank"] == 3 and r["run_id"] == "r1" and "ts" in r for r in recs)


# -------------------------------------------------------- metrics_report


def _report(args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "metrics_report.py"),
         *args],
        capture_output=True,
        text=True,
        timeout=120,
    )


@pytest.fixture
def run_jsonl(train_data, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    mpath = tmp_path / "run" / "metrics_rank0.jsonl"
    cfg = _train_cfg(train_data, **{"train.metrics_path": str(mpath)})
    Trainer(cfg).fit()
    return mpath


def test_metrics_report_summary_and_check(run_jsonl, tmp_path):
    r = _report([str(run_jsonl.parent), "--check"])
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
    r = _report([str(run_jsonl.parent)])
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines[0].split() == [
        "run_id", "rank", "gen", "steps", "examples", "elapsed_s", "ex/s",
        "rows/s", "p50_ms", "p99_ms", "wait_ms", "loss", "bad_steps",
        "bad_rows", "auc",
    ]
    row = lines[2].split()
    assert row[1] == "0" and row[2] == "0"  # rank 0, generation 0
    assert row[3] == "30" and row[4] == "1920"


def test_metrics_report_tolerates_truncation(run_jsonl, tmp_path):
    data = run_jsonl.read_bytes()
    trunc = tmp_path / "trunc" / "metrics_rank0.jsonl"
    trunc.parent.mkdir()
    trunc.write_bytes(data[:-30])  # cut inside the final record
    r = _report([str(trunc)])
    assert r.returncode == 0, r.stderr
    assert "damaged line(s) skipped" in r.stdout
    assert "skipped 1 unparseable" in r.stderr
    r = _report([str(trunc), "--check"])
    assert r.returncode == 0, r.stderr  # damage is skipped, schema still OK


def test_quarantine_stream_tolerates_truncation(run_jsonl, tmp_path):
    """Regression (A3 satellite): a run dir holding BOTH a truncated
    metrics stream and a truncated quarantine stream must still
    summarize and --check clean — the SIGTERM/crash tail of either
    stream is a skipped line, never a dead report."""
    from xflow_tpu.data.pipeline import batch_iterator
    from xflow_tpu.testing.faults import truncate_file, write_malformed_libffm

    run = run_jsonl.parent
    shard = tmp_path / "junk-00000"
    write_malformed_libffm(str(shard), n_good=20, n_bad=3, seed=2)
    qpath = run / "quarantine.jsonl"
    cfg = override(
        Config(),
        **{
            "data.batch_size": 16,
            "data.max_bad_rows": 100,
            "data.quarantine_path": str(qpath),
            "data.log2_slots": 12,
            "data.max_nnz": 8,
        },
    ).data
    list(batch_iterator(str(shard), cfg))
    # tear the tails of BOTH streams (the crash-mid-append shape)
    truncate_file(str(qpath), keep_bytes=os.path.getsize(qpath) - 20)
    truncate_file(str(run_jsonl), keep_bytes=os.path.getsize(run_jsonl) - 25)
    recs, skipped = read_jsonl_counted(str(qpath))
    assert recs and skipped == 1
    assert all("ts" in r and "rank" in r and "run_id" in r for r in recs)
    r = _report([str(run), "--check"])
    assert r.returncode == 0, r.stderr
    assert "2 damaged line(s) skipped" in r.stdout
    r = _report([str(run)])
    assert r.returncode == 0, r.stderr


def test_metrics_report_check_accepts_heartbeat_stream(run_jsonl):
    """A heartbeat stream in the run dir is its own (kind-keyed) stream:
    its step sequence must not be merged into the metrics stream's
    monotonicity check, and its shape is validated."""
    hb = run_jsonl.parent / "heartbeat_rank0.jsonl"
    a = JsonlAppender(
        str(hb), stamp={"rank": 0, "run_id": "hbrun", "kind": "heartbeat"}
    )
    a.append({"event": "start", "step": 0})
    for s in (10, 20, 30):
        a.append({"step": s})
    a.append({"event": "final", "step": 30})
    a.close()
    r = _report([str(run_jsonl.parent), "--check"])
    assert r.returncode == 0, r.stderr
    # a heartbeat record that is neither a beat nor an event fails
    a.append({"nonsense": True})
    a.close()
    r = _report([str(run_jsonl.parent), "--check"])
    assert r.returncode != 0
    assert "neither a step heartbeat nor an event" in r.stderr


def test_metrics_report_bench_json(run_jsonl, tmp_path):
    out = tmp_path / "bench.json"
    r = _report([str(run_jsonl), "--bench-json", str(out)])
    assert r.returncode == 0, r.stderr
    rec = json.loads(out.read_text())
    assert rec["metric"] == "telemetry_examples_per_sec"
    assert rec["unit"] == "examples/sec"
    assert rec["value"] > 0
    assert rec["steps"] == 30 and rec["examples"] == 1920 and rec["ranks"] == 1


def test_metrics_report_check_flags_bad_schema(tmp_path):
    bad = tmp_path / "bad.jsonl"
    with open(bad, "w") as f:
        # unstamped record + backwards step
        f.write(json.dumps({"step": 5, "loss": 0.1}) + "\n")
        f.write(
            json.dumps(
                {"ts": 1.0, "rank": 0, "run_id": "r", "step": 3, "loss": 0.1}
            )
            + "\n"
        )
    r = _report([str(bad), "--check"])
    assert r.returncode != 0
    assert "FAIL" in r.stderr


def test_metrics_report_check_admits_and_gates_the_host_timeline(tmp_path):
    """--check's copy of the `host` / `boundary` / compile-split key
    sets is the writer's, and a drifted or negative field fails."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import metrics_report

    assert set(metrics_report.HOST_KEYS) == (
        {host_field(s) for s in HOST_STAGES} | {"batches"})
    assert metrics_report.HOST_OPTIONAL_KEYS == (
        {host_field(s) for s in telemetry.HOST_NESTED_STAGES} | set(telemetry.HOST_COUNTERS))
    stamp = {"ts": 1.0, "rank": 0, "run_id": "r", "gen": 0}
    window = {"steps_per_s": 1.0, "rows_per_s": 64.0, "step_time_p50_ms": 9.0,
              "step_time_p99_ms": 9.0, "data_wait_ms": 1.0, "dispatch_ms": 1.0,
              "device_ms": 7.0}
    host = {k: 0.5 for k in metrics_report.HOST_KEYS}
    opened = {k: 0.25 for k in metrics_report.BOUNDARY_OPEN_KEYS}
    tail = {k: 0.25 for k in metrics_report.BOUNDARY_TAIL_KEYS}
    comp = {"kind": "compile", "program": "train_step", "sig": "abc",
            "compile_time_s": 0.3, "flops": 1.0, "bytes_accessed": 2.0}

    def check(*recs):
        path = tmp_path / "m.jsonl"
        path.write_text("".join(json.dumps({**stamp, **r}) + "\n" for r in recs))
        return _report([str(path), "--check"])

    good = check(
        {"step": 1, **window, "host": host, "boundary": opened},
        {"step": 2, **window, "host": host, "boundary": {**opened, **tail}},
        # a sorted engine's plan: the kernels' chunk counts ride beside the stages
        {"step": 3, **window, "host": {**host, "chunk_visits": 20444, "chunk_loads": 4098}},
        {**comp, "lower_s": 0.2, "xla_compile_s": 0.1},
    )
    assert good.returncode == 0, good.stderr
    for bad, said in (
        ({"step": 1, **window, "host": {**host, "parse_ms": -1.0}}, "negative host"),
        ({"step": 1, **window, "host": {"parse_ms": 1.0}}, "host that is not"),
        ({"step": 1, **window, "boundary": {**opened, "fit_tail_ms": 1.0}},
         "boundary that is not"),
        ({**comp, "lower_s": 0.2, "xla_compile_s": 0.2}, "do not add up"),
        ({**comp, "lower_s": 0.3}, "do not add up"),
    ):
        r = check(bad)
        assert r.returncode == 2 and said in r.stderr, (bad, r.stderr)


def test_metrics_report_empty_dir(tmp_path):
    r = _report([str(tmp_path)])
    assert r.returncode != 0


# --------------------------------------------------------------- smoke gate


def test_smoke_telemetry_script(tmp_path):
    """tools/smoke_telemetry.sh: 50-step synthetic train + schema gate,
    runnable standalone and from CI."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "smoke_telemetry.sh"),
         str(tmp_path / "work")],
        capture_output=True,
        text=True,
        timeout=540,
        env=env,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "metrics_report: OK" in r.stdout
    assert "smoke_telemetry: OK" in r.stdout


# ------------------------------------------------------------ launch wiring


def test_launch_dist_run_dir_dry_run(tmp_path):
    """--run-dir threads per-rank metrics paths and a shared run id into
    every rank's command line (checked via --dry-run: no ssh runs)."""
    from xflow_tpu.launch.cli import main

    hosts = tmp_path / "hosts.txt"
    hosts.write_text("h0\nh1\n")
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(
            ["launch-dist", "--hosts", str(hosts), "--dry-run",
             "--run-dir", "/runs/exp1", "--",
             "--train", "/data/train", "--model", "lr"]
        )
    out = buf.getvalue()
    assert rc == 0
    assert "metrics_rank0.jsonl" in out and "metrics_rank1.jsonl" in out
    assert out.count("XFLOW_RUN_ID=") == 2
    # both ranks share the SAME id
    ids = {
        tok.split("=", 1)[1].strip("'\"")
        for line in out.splitlines()
        for tok in line.split()
        if tok.startswith("XFLOW_RUN_ID=")
    }
    assert len(ids) == 1


def test_launch_local_rank_metrics_args(tmp_path):
    from xflow_tpu.launch.local import rank_metrics_args

    assert rank_metrics_args("", 0) == []
    args = rank_metrics_args(str(tmp_path / "run"), 3)
    assert args[0] == "--set"
    assert args[1].endswith("metrics_rank3.jsonl")
