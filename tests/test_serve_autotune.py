"""SLO autotuning + batch-shape ladder tests (xflow_tpu/serve/autotune,
docs/SERVING.md "Autotuning").

Clock-injected controller units first — dominant-term steering,
hysteresis, reversal damping (no oscillation on a scripted load step),
the one-shot floor pin — then the ladder (parse/pick, exactly-once
compile per rung through the CompileRecorder, runner dispatch), the
coalescer's release-rung seam, the byte-identical-when-off pin, the
metrics_report kind="autotune" schema gate + fleet stamp separation,
the serve_bench SLO-attainment gate and the perf_ledger p99 leg.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.serve.autotune import (
    AUTOTUNE_KNOBS,
    AutotuneController,
    Decision,
    parse_ladder,
    pick_rung,
)
from xflow_tpu.serve.coalescer import MicroBatcher, RowBlock, assemble_batch

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _cfg(**extra):
    base = {
        "serve.autotune": True,
        "serve.slo_p99_ms": 20.0,
        "serve.window_ms": 10.0,
        "serve.max_batch": 64,
        "serve.autotune_band_frac": 0.15,
        "serve.autotune_step_frac": 0.5,
        "serve.autotune_min_window_ms": 0.25,
    }
    base.update(extra)
    return override(Config(), **base).serve


def _win(total, qw, dev, fill=0.5):
    return {
        "total_p99_ms": total,
        "queue_wait_p99_ms": qw,
        "device_p99_ms": dev,
        "batch_fill": fill,
    }


# ------------------------------------------------------------- controller
def test_queue_dominated_over_slo_shrinks_window():
    c = AutotuneController(_cfg(), clock=FakeClock())
    ds = c.observe(_win(30.0, 25.0, 5.0))
    assert [d.knob for d in ds] == ["window_ms"]
    assert ds[0].reason == "queue_dominated"
    assert ds[0].new < ds[0].old == 10.0
    assert c.window_ms == ds[0].new


def test_device_dominated_over_slo_steps_rung_down():
    c = AutotuneController(
        _cfg(**{"serve.ladder": "16,64"}), clock=FakeClock()
    )
    assert c.rungs == (16, 64) and c.rung == 64
    ds = c.observe(_win(30.0, 2.0, 28.0))
    assert [d.knob for d in ds] == ["rung"]
    assert ds[0].reason == "device_dominated"
    assert (ds[0].old, ds[0].new) == (64.0, 16.0) and c.rung == 16
    # at the bottom rung the window is the only remaining lever
    ds = c.observe(_win(30.0, 2.0, 28.0))
    assert [d.knob for d in ds] == ["window_ms"] and ds[0].new < 10.0


def test_hysteresis_band_holds_steady():
    c = AutotuneController(_cfg(), clock=FakeClock())
    # slo 20, band 0.15 -> [17, 23]: anything inside moves nothing
    assert c.observe(_win(20.0, 15.0, 5.0)) == []
    assert c.observe(_win(22.9, 1.0, 21.0)) == []
    assert c.observe(_win(17.1, 16.0, 1.0)) == []
    assert c.window_ms == 10.0 and c.decision_count == 0


def test_under_slo_restores_rung_then_grows_window():
    c = AutotuneController(
        _cfg(**{"serve.ladder": "16,64"}), clock=FakeClock()
    )
    c.observe(_win(30.0, 2.0, 28.0))  # rung down first
    assert c.rung == 16
    ds = c.observe(_win(5.0, 1.0, 4.0))
    assert [d.reason for d in ds] == ["rung_restore"]
    assert c.rung == 64
    ds = c.observe(_win(5.0, 1.0, 4.0))  # now device headroom grows
    assert [d.reason for d in ds] == ["device_headroom"]
    assert c.window_ms > 10.0
    # growth never passes the derived ceiling (= the SLO budget)
    for _ in range(50):
        c.observe(_win(5.0, 1.0, 4.0))
    assert c.window_ms <= c.max_window_ms == 20.0


def test_under_slo_queue_dominant_does_not_grow():
    c = AutotuneController(_cfg(), clock=FakeClock())
    # under SLO but queue-wait already dominates: growing the window
    # would hand the saved budget right back to coalescing delay
    assert c.observe(_win(10.0, 8.0, 2.0)) == []


def test_reversal_damping_converges_not_oscillates():
    c = AutotuneController(_cfg(), clock=FakeClock())
    # scripted flip-flop load: alternately over (queue) / under (device)
    # the band — an undamped multiplicative controller ping-pongs
    # forever; halving the step on each reversal must shrink the moves
    moves = []
    for i in range(20):
        w = _win(30.0, 25.0, 2.0) if i % 2 == 0 else _win(5.0, 1.0, 4.0)
        for d in c.observe(w):
            moves.append(abs(d.new - d.old))
    assert len(moves) >= 6
    # late moves are much smaller than the opening one: converging
    assert max(moves[-3:]) < 0.2 * moves[0]
    assert c.state()["step_frac"]["window_ms"] < 0.5


def test_floor_pin_warns_exactly_once_then_rearms_on_growth():
    c = AutotuneController(
        _cfg(**{"serve.window_ms": 0.25}), clock=FakeClock()
    )
    over = _win(40.0, 35.0, 5.0)
    ds = c.observe(over)
    assert [d.reason for d in ds] == ["floor_pinned"]
    assert ds[0].old == ds[0].new == 0.25  # the pin is the information
    # pinned: more over-SLO windows emit NOTHING (never flaps)
    for _ in range(5):
        assert c.observe(over) == []
    assert c.state()["floor_pinned"] is True
    # load eases -> window grows -> a NEW unattainable stretch warns again
    c.observe(_win(5.0, 1.0, 4.0))
    assert c.state()["floor_pinned"] is False
    # shrink back down to the floor, then the pin warns once more
    reasons = []
    for _ in range(20):
        reasons += [d.reason for d in c.observe(over)]
    assert reasons.count("floor_pinned") == 1


def test_observe_without_latency_evidence_steers_nothing():
    c = AutotuneController(_cfg(), clock=FakeClock())
    assert c.observe(_win(None, None, None)) == []
    assert c.observe({"batch_fill": 1.0}) == []
    assert c.windows_seen == 0


def test_controller_rejects_nonpositive_slo():
    with pytest.raises(ValueError, match="slo_p99_ms"):
        AutotuneController(_cfg(**{"serve.slo_p99_ms": 0.0}))


def test_state_snapshot_shape():
    clock = FakeClock()
    c = AutotuneController(_cfg(**{"serve.ladder": "16,64"}), clock=clock)
    c.observe(_win(30.0, 25.0, 5.0))
    clock.t = 2.0
    s = c.state()
    assert s["slo_p99_ms"] == 20.0 and s["rungs"] == [16, 64]
    assert s["windows_seen"] == 1 and s["decisions"] == 1
    assert s["since_last_decision_s"] == pytest.approx(2.0)
    assert set(s["step_frac"]) == set(AUTOTUNE_KNOBS)


# ----------------------------------------------------------------- ladder
def test_parse_ladder_shapes():
    assert parse_ladder(_cfg()) == (64,)  # "" = the pre-ladder shape
    assert parse_ladder(_cfg(**{"serve.ladder": "16,4,64"})) == (4, 16, 64)
    # rungs above max_batch clamp; max_batch always joins as the top
    assert parse_ladder(_cfg(**{"serve.ladder": "16,256"})) == (16, 64)
    with pytest.raises(ValueError, match="not an integer"):
        parse_ladder(_cfg(**{"serve.ladder": "16,big"}))
    with pytest.raises(ValueError, match=">= 1"):
        parse_ladder(_cfg(**{"serve.ladder": "0"}))


def test_pick_rung_smallest_fit():
    rungs = (16, 64, 256)
    assert pick_rung(1, rungs) == 16
    assert pick_rung(16, rungs) == 16
    assert pick_rung(17, rungs) == 64
    assert pick_rung(300, rungs) == 256  # beyond top: the top rung


# ------------------------------------------------- coalescer release rung
def _rows(n, nnz=3):
    fields = [np.arange(nnz, dtype=np.int32) for _ in range(n)]
    slots = [np.full(nnz, 7, dtype=np.int32) for _ in range(n)]
    return RowBlock.from_rows(fields, slots, nnz)


def test_release_rung_flushes_below_max_rows():
    clock = FakeClock()
    mb = MicroBatcher(max_rows=64, window_s=100.0, clock=clock)
    mb.set_release_rows(8)
    mb.submit(_rows(4))
    assert mb.take(timeout=0.0) is None  # 4 < release rung 8
    mb.submit(_rows(4))
    group = mb.take(timeout=0.0)  # 8 rows = the rung: size flush NOW
    assert group is not None and sum(r.num_rows for r in group) == 8


def test_release_rung_never_wedges_an_oversize_head():
    clock = FakeClock()
    mb = MicroBatcher(max_rows=64, window_s=100.0, clock=clock)
    mb.set_release_rows(8)
    # a 32-row request is legal (max_rows contract unchanged) and must
    # pop whole even though it exceeds the release rung
    mb.submit(_rows(32))
    group = mb.take(timeout=0.0)
    assert group is not None and [r.num_rows for r in group] == [32]


def test_set_window_takes_effect_on_queued_requests():
    clock = FakeClock()
    mb = MicroBatcher(max_rows=64, window_s=100.0, clock=clock)
    mb.submit(_rows(1))
    assert mb.take(timeout=0.0) is None
    mb.set_window_s(1.0)  # the controller shrinks the deadline
    clock.t = 1.5
    group = mb.take(timeout=0.0)
    assert group is not None and len(group) == 1


def test_release_rung_clamps_to_contract():
    mb = MicroBatcher(max_rows=64, window_s=1.0, clock=FakeClock())
    mb.set_release_rows(0)
    assert mb.release_rows == 1
    mb.set_release_rows(9999)
    assert mb.release_rows == 64


# ------------------------------------------------- runner ladder programs
@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One tiny trained run shared by the ladder-dispatch tests."""
    from xflow_tpu.data.synth import generate_shards
    from xflow_tpu.train.trainer import Trainer

    work = tmp_path_factory.mktemp("autotune_fixture")
    generate_shards(
        str(work / "train"), 1, 256, num_fields=5, ids_per_field=30, seed=0
    )
    cfg = _runner_cfg(
        work / "ck",
        **{"data.train_path": str(work / "train"), "train.epochs": 1,
           "train.checkpoint_every": 4},
    )
    t = Trainer(cfg)
    t.fit()
    return {"work": work}


def _runner_cfg(ckpt_dir, **extra):
    base = {
        "data.batch_size": 64,
        "data.log2_slots": 12,
        "data.max_nnz": 8,
        "model.num_fields": 5,
        "model.name": "lr",
        "train.pred_dump": False,
        "train.checkpoint_dir": str(ckpt_dir),
        "serve.max_batch": 16,
    }
    base.update(extra)
    return override(Config(), **base)


def test_ladder_compiles_each_rung_exactly_once(trained):
    from xflow_tpu.serve.runner import ServeRunner
    from xflow_tpu.telemetry import CompileRecorder

    sink: list = []
    cfg = _runner_cfg(trained["work"] / "ck", **{"serve.ladder": "4,16"})
    r = ServeRunner(cfg, recorder=CompileRecorder(sink=sink))
    r.load()
    assert r.rungs == (4, 16)
    assert r.warmup() == 2
    programs = sorted(rec["program"] for rec in sink)
    assert programs == ["predict.serve.b16", "predict.serve.b4"]
    # traffic at both rungs reuses the warmed executables: no recompile
    arrays, _ = assemble_batch([], 4, cfg.data.max_nnz)
    p, _ = r.predict(arrays)
    assert p.shape == (4,)
    arrays, _ = assemble_batch([], 16, cfg.data.max_nnz)
    p, _ = r.predict(arrays)
    assert p.shape == (16,)
    assert len(sink) == 2


def test_single_rung_keeps_pre_ladder_program_name(trained):
    """The byte-identical-off pin, compile-accounting half: no ladder
    -> ONE rung == max_batch under the ORIGINAL program name, so the
    compile stream cannot distinguish this build from a pre-ladder one."""
    from xflow_tpu.serve.runner import ServeRunner
    from xflow_tpu.telemetry import CompileRecorder

    sink: list = []
    cfg = _runner_cfg(trained["work"] / "ck")
    r = ServeRunner(cfg, recorder=CompileRecorder(sink=sink))
    r.load()
    assert r.rungs == (16,)
    assert r.warmup() == 1
    assert [rec["program"] for rec in sink] == ["predict.serve"]


def test_autotune_off_serve_stream_has_no_autotune_records(trained, tmp_path):
    """The byte-identical-off pin, telemetry half: with serve.autotune
    off (default) the app owns NO controller, and a served run's stream
    carries zero kind="autotune" records and zero autotune spans."""
    from xflow_tpu.serve.runner import ServeRunner
    from xflow_tpu.serve.server import ServeApp

    cfg = _runner_cfg(
        trained["work"] / "ck",
        **{"serve.window_ms": 1.0, "serve.metrics_every_s": 0.05,
           "serve.metrics_path": str(tmp_path / "serve.jsonl")},
    )
    runner = ServeRunner(cfg)
    runner.load()
    app = ServeApp(cfg, runner)
    assert app.autotuner is None
    assert "autotune" not in app.stats()
    app.start()
    try:
        body = json.dumps({"rows": ["0:1:1 1:2:1"]}).encode()
        for _ in range(3):
            status, _ = app.handle_predict(body)
            assert status == 200
    finally:
        app.close()
    recs = [json.loads(l) for l in open(tmp_path / "serve.jsonl")]
    assert not [r for r in recs if r.get("kind") == "autotune"]
    assert not [r for r in recs if r.get("name") == "autotune"]
    assert [r for r in recs if r.get("kind") == "serve"]


# ------------------------------------------- metrics_report autotune gate
def _metrics_report():
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import metrics_report as mr

    return mr


def _at_rec(ts=1.0, rank=0, run_id="r1", gen=0, **kw):
    base = {
        "ts": ts, "rank": rank, "run_id": run_id, "gen": gen,
        "kind": "autotune", "knob": "window_ms", "old": 10.0, "new": 5.0,
        "reason": "queue_dominated", "slo_p99_ms": 20.0,
        "total_p99_ms": 30.0, "queue_wait_p99_ms": 25.0,
        "device_p99_ms": 5.0, "batch_fill": 0.5,
    }
    base.update(kw)
    return base


def _write(tmp_path, name, recs):
    p = tmp_path / name
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(p)


def test_check_accepts_well_formed_autotune_trail(tmp_path):
    mr = _metrics_report()
    ok = _write(tmp_path, "ok.jsonl", [
        _at_rec(ts=1.0),
        _at_rec(ts=2.0, old=5.0, new=2.5),
        _at_rec(ts=3.0, knob="rung", old=64.0, new=16.0,
                reason="device_dominated"),
    ])
    assert mr.main([ok, "--check"]) == 0


def test_check_rejects_partial_autotune_record(tmp_path):
    mr = _metrics_report()
    rec = _at_rec()
    del rec["reason"]
    assert mr.main([_write(tmp_path, "p.jsonl", [rec]), "--check"]) == 2


def test_check_rejects_unknown_knob(tmp_path):
    mr = _metrics_report()
    bad = _write(tmp_path, "k.jsonl", [_at_rec(knob="prefetch_depth")])
    assert mr.main([bad, "--check"]) == 2


def test_check_rejects_time_travel_in_decision_trail(tmp_path):
    mr = _metrics_report()
    bad = _write(tmp_path, "t.jsonl",
                 [_at_rec(ts=5.0), _at_rec(ts=1.0, old=5.0, new=2.5)])
    assert mr.main([bad, "--check"]) == 2


def test_fleet_replicas_keep_separate_autotune_trails(tmp_path):
    """Two replicas' controllers each steer their own coalescer: trails
    in separate streams with distinct (rank, replica) stamps pass; one
    stream mixing replica stamps is two controllers on one file."""
    mr = _metrics_report()
    ok = [
        _at_rec(ts=1.0, rank=0, replica=0, port=8001),
        _at_rec(ts=2.0, rank=0, replica=0, port=8001, old=5.0, new=2.5),
    ]
    ok2 = [
        _at_rec(ts=1.0, rank=1, replica=1, port=8002, old=10.0, new=5.0),
    ]
    a = _write(tmp_path, "replica0.jsonl", ok)
    b = _write(tmp_path, "replica1.jsonl", ok2)
    assert mr.main([a, b, "--check"]) == 0
    mixed = _write(tmp_path, "mixed.jsonl", [
        _at_rec(ts=1.0, rank=0, replica=0),
        _at_rec(ts=2.0, rank=0, replica=1, old=5.0, new=2.5),
    ])
    assert mr.main([mixed, "--check"]) == 2


def test_health_renders_trajectory_and_verdicts(tmp_path, capsys):
    mr = _metrics_report()
    # a converging trail: monotone shrink, no reversal churn
    good = [
        _at_rec(ts=1.0, old=25.0, new=12.5),
        _at_rec(ts=2.0, old=12.5, new=6.2),
        _at_rec(ts=3.0, old=6.2, new=3.1),
    ]
    assert mr.main([_write(tmp_path, "g.jsonl", good), "--health"]) == 0
    out = capsys.readouterr().out
    assert "autotune trajectory" in out
    assert "window_ms 25 -> 3.1" in out
    assert "[converged]" in out
    # a flip-flopping trail earns the oscillating verdict
    osc, v = [], 10.0
    for i in range(8):
        nv = v * (0.5 if i % 2 == 0 else 2.0)
        osc.append(_at_rec(ts=float(i + 1), old=v, new=nv))
        v = nv
    assert mr.main([_write(tmp_path, "o.jsonl", osc), "--health"]) == 0
    assert "[oscillating]" in capsys.readouterr().out
    # a floor-pinned trail names the unattainable SLO
    pin = [
        _at_rec(ts=1.0, old=0.5, new=0.25),
        _at_rec(ts=2.0, old=0.25, new=0.25, reason="floor_pinned"),
    ]
    assert mr.main([_write(tmp_path, "f.jsonl", pin), "--health"]) == 0
    assert "pinned at floor" in capsys.readouterr().out


# -------------------------------------------------- serve_bench + ledger
def test_transport_is_single_segment_nodelay():
    """The Nagle contract (docs/SERVING.md "Telemetry + bench"): the
    handler answers headers+body in one buffered segment with
    TCP_NODELAY per connection, and the loadgen connects NODELAY. An
    unbuffered two-write response parks every request behind the
    peer's delayed ACK — a flat ~40 ms per round trip on loopback."""
    from xflow_tpu.serve.server import _make_handler

    handler = _make_handler(None)
    assert handler.wbufsize == -1  # buffered: one segment per response
    assert handler.protocol_version == "HTTP/1.1"
    assert "setup" in vars(handler)  # the guarded TCP_NODELAY hook

    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import serve_bench

    class _Args:
        unix = ""
        url = "http://127.0.0.1:1"
        timeout = 1.0

    conn = serve_bench._connect(_Args())
    assert isinstance(conn, serve_bench._NoDelayHTTPConnection)


def test_slo_attainment_pct():
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import serve_bench

    lats = [0.010, 0.020, 0.030, 0.040]  # seconds
    assert serve_bench.slo_attainment_pct(lats, 25.0) == 50.0
    assert serve_bench.slo_attainment_pct(lats, 40.0) == 100.0
    assert serve_bench.slo_attainment_pct(lats, 5.0) == 0.0
    assert serve_bench.slo_attainment_pct([], 25.0) == 0.0


def test_perf_ledger_gates_serve_p99_downward(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import perf_ledger as pl

    def entries(p99_new):
        old = pl.normalize_serve("BENCH_SERVE.json", {
            "metric": "serve_qps", "value": 320.0, "p99_ms": 27.0,
            "round": 9,
        })
        # round stamp fallback: the un-suffixed baseline file joins
        # the gate via its own "round" field
        assert old and all(e["round"] == 9 for e in old)
        new = pl.normalize_serve("BENCH_SERVE_r17.json", {
            "metric": "serve_qps", "value": 700.0, "p99_ms": p99_new,
        })
        assert new and all(e["round"] == 17 for e in new)
        out = old + new
        out.sort(key=lambda e: (e["series"], str(e["metric"]),
                                e["round"] if e["round"] is not None else -1))
        return out
    # QPS doubled AND the p99 leg improved: green
    assert pl.check_regressions(entries(20.0), tol=0.2) == []
    # QPS doubled but the tail blew out: the _ms leg gates DOWNWARD
    problems = pl.check_regressions(entries(40.0), tol=0.2)
    assert any("serve_qps_p99_ms" in p for p in problems)


def test_serve_bench_attainment_rides_the_record(tmp_path):
    """--slo-ms stamps slo_ms + slo_attainment_pct into the bench JSON
    (the perf_ledger normalizer folds them); --round stamps the round."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import perf_ledger as pl

    rec = {
        "metric": "serve_qps", "value": 650.0, "p99_ms": 20.0,
        "slo_ms": 27.741, "slo_attainment_pct": 99.5, "round": 17,
    }
    ent = pl.normalize_serve("BENCH_SERVE_r17.json", rec)
    head = ent[0]
    assert head["round"] == 17
    assert head["slo_attainment_pct"] == 99.5
    legs = {e["metric"] for e in ent}
    assert "serve_qps_p99_ms" in legs
    assert "serve_qps_slo_attainment_pct" in legs
