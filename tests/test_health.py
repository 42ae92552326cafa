"""Model-health observability tests (train.health_metrics,
telemetry.HealthMonitor/HangWatchdog, launch/watchdog.py,
metrics_report --health/--regress): norm/EMA math against NumPy
oracles, single-device vs GSPMD parity of the fused health scalars,
streaming-AUC-vs-exact-eval parity, occupancy/collision gauges,
heartbeat classification, and the launch-local straggler drill.
"""

import io
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.data.synth import generate_shards
from xflow_tpu.jsonl import JsonlAppender, read_jsonl
from xflow_tpu.telemetry import (
    HangWatchdog,
    HealthMonitor,
    Registry,
    default_registry,
    estimate_collision_rate,
)
from xflow_tpu.train.trainer import Trainer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- norm oracles


def _hcfg(mode="norms", model="lr", **kw):
    base = {
        "train.health_metrics": mode,
        "model.name": model,
        "data.log2_slots": 12,
        "model.num_fields": 6,
    }
    base.update(kw)
    return override(Config(), **base)


def test_health_norms_numpy_oracle():
    """health_norms == the NumPy norms of grads / (new-old) / new."""
    import jax.numpy as jnp

    from xflow_tpu.train.step import health_norms

    rng = np.random.default_rng(0)
    old = {"w": rng.normal(size=(32,)).astype(np.float32),
           "v": rng.normal(size=(16, 4)).astype(np.float32)}
    new = {k: v + rng.normal(size=v.shape).astype(np.float32) * 0.01
           for k, v in old.items()}
    grads = {k: rng.normal(size=v.shape).astype(np.float32) for k, v in old.items()}
    cfg = _hcfg("norms")
    out = health_norms(
        cfg,
        {k: jnp.asarray(v) for k, v in old.items()},
        {k: jnp.asarray(v) for k, v in new.items()},
        grads={k: jnp.asarray(v) for k, v in grads.items()},
    )
    g_exp = np.sqrt(sum(float((g.astype(np.float64) ** 2).sum()) for g in grads.values()))
    u_exp = np.sqrt(sum(float(((new[k] - old[k]).astype(np.float64) ** 2).sum()) for k in old))
    p_exp = np.sqrt(sum(float((new[k].astype(np.float64) ** 2).sum()) for k in old))
    assert float(out["grad_norm"]) == pytest.approx(g_exp, rel=1e-5)
    assert float(out["update_norm"]) == pytest.approx(u_exp, rel=1e-5)
    assert float(out["param_norm"]) == pytest.approx(p_exp, rel=1e-5)
    assert "grad_norm.w" not in out  # norms mode: global only


def test_health_norms_full_mode_per_table():
    import jax.numpy as jnp

    from xflow_tpu.train.step import health_norms

    old = {"w": np.zeros((8,), np.float32)}
    new = {"w": np.full((8,), 3.0, np.float32)}
    grads = {"w": np.full((8,), 2.0, np.float32)}
    cfg = _hcfg("full")
    out = health_norms(
        cfg, {"w": jnp.asarray(old["w"])}, {"w": jnp.asarray(new["w"])},
        grads={"w": jnp.asarray(grads["w"])},
    )
    assert float(out["grad_norm.w"]) == pytest.approx(2.0 * np.sqrt(8), rel=1e-6)
    assert float(out["update_norm.w"]) == pytest.approx(3.0 * np.sqrt(8), rel=1e-6)
    assert float(out["param_norm.w"]) == float(out["param_norm"])


def test_health_mode_validation():
    from xflow_tpu.train.step import health_mode, metrics_keys

    with pytest.raises(ValueError):
        health_mode(_hcfg("bogus"))
    assert "grad_norm" not in metrics_keys(_hcfg("off"))
    keys = metrics_keys(_hcfg("full", model="lr"))
    assert "grad_norm" in keys and "grad_norm.w" in keys and "update_ok" in keys


def test_sharded_step_health_matches_single_device():
    """The GSPMD step's fused health scalars equal the single-device
    step's (replicated-reduction contract)."""
    import jax
    import jax.numpy as jnp

    from xflow_tpu.models import get_model
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.parallel.mesh import batch_sharding, make_mesh
    from xflow_tpu.parallel.train_step import make_sharded_train_step, shard_state
    from xflow_tpu.train.state import init_state
    from xflow_tpu.train.step import make_train_step

    cfg = _hcfg(
        "norms", model="lr",
        **{"mesh.data": 4, "mesh.table": 2, "data.batch_size": 64},
    )
    model, opt = get_model("lr"), get_optimizer("ftrl")
    rng = np.random.default_rng(3)
    batch = {
        "slots": rng.integers(0, 1 << 12, (64, 10)).astype(np.int32),
        "fields": rng.integers(0, 6, (64, 10)).astype(np.int32),
        "mask": (rng.random((64, 10)) < 0.8).astype(np.float32),
        "labels": (rng.random(64) < 0.4).astype(np.float32),
        "row_mask": np.ones((64,), np.float32),
    }
    state1 = init_state(model, opt, cfg)
    _, m1 = make_train_step(model, opt, cfg)(
        state1, {k: jnp.asarray(v) for k, v in batch.items()}
    )
    mesh = make_mesh(cfg)
    state2 = shard_state(init_state(model, opt, cfg), mesh)
    bsh = batch_sharding(mesh)
    placed = {k: jax.device_put(jnp.asarray(v), bsh[k]) for k, v in batch.items()}
    _, m2 = make_sharded_train_step(model, opt, cfg, mesh)(state2, placed)
    for key in ("grad_norm", "update_norm", "param_norm"):
        assert float(m2[key]) == pytest.approx(float(m1[key]), rel=2e-4), key


def test_sorted_engines_emit_identical_health():
    """The fullshard engine fuses the SAME health scalars through its
    shard_map program as the single-device sorted step — norms agree
    across layouts, and the guard flag still rides."""
    import jax

    from xflow_tpu.data.schema import SparseBatch
    from xflow_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the conftest 8-device CPU mesh")
    base = override(Config(), **{
        "data.log2_slots": 14,
        "data.batch_size": 64,
        "data.max_nnz": 8,
        "model.name": "fm",
        "model.num_fields": 5,
        "model.v_dim": 4,
        "mesh.data": 4,
        "mesh.table": 2,
        "data.sorted_layout": "on",
        "train.health_metrics": "norms",
        # the two-pass form on one device too, as a mesh runs it: the
        # fused kernel's grad_norm is over occurrences, not slots
        "optim.fused_scatter": "off",
    })
    mesh = make_mesh(base)
    rng = np.random.default_rng(0)
    B, F = 64, 8
    batch = SparseBatch(
        slots=rng.integers(0, 1 << 14, (B, F)).astype(np.int32),
        fields=rng.integers(0, 5, (B, F)).astype(np.int32),
        mask=(rng.random((B, F)) < 0.8).astype(np.float32),
        labels=(rng.random(B) < 0.4).astype(np.float32),
        row_mask=np.ones((B,), np.float32),
    )
    got = {}
    for engine, on in (("fullshard", mesh), ("sorted", None)):
        t = Trainer(base, mesh=on)
        assert t.engine == engine
        _, arrays = t._with_arrays(batch)
        arrays = t._engine.shard_batch(arrays)
        t.state, m = t.train_step(t.state, arrays)
        assert "update_ok" in m  # guard flag still rides with health on
        got[engine] = {k: float(m[k]) for k in
                       ("grad_norm", "update_norm", "param_norm")}
        for v in got[engine].values():
            assert np.isfinite(v) and v > 0
    for key in got["fullshard"]:
        assert got["fullshard"][key] == pytest.approx(
            got["sorted"][key], rel=1e-4
        ), key


# --------------------------------------------------------------- EMA oracle


def test_health_monitor_ema_numpy_oracle():
    """staged/collect folds the EMA exactly like the NumPy recursion,
    one step behind, seeded by the first finite loss."""
    mon = HealthMonitor(mode="norms", ema_decay=0.9, registry=Registry())
    losses = [0.7, 0.6, float("nan"), 0.5, 0.4]
    ema = None
    for i, loss in enumerate(losses, 1):
        mon.staged({"loss": np.float32(loss), "grad_norm": np.float32(1.0),
                    "update_norm": np.float32(0.1), "param_norm": np.float32(2.0)})
        mon.collect()  # in the fit loop this collect belongs to step i+1
        if loss == loss:  # NaN (a guarded bad step) must not poison the EMA
            ema = loss if ema is None else 0.9 * ema + 0.1 * loss
        assert mon.loss_ema == pytest.approx(ema, rel=1e-6)
    rec = mon.window_record()
    assert rec["loss_ema"] == pytest.approx(ema, rel=1e-6)
    assert rec["grad_norm"] == pytest.approx(1.0)


def test_health_monitor_runs_one_behind():
    mon = HealthMonitor(mode="norms", registry=Registry())
    assert mon.window_record() == {}  # nothing collected yet
    mon.staged({"loss": np.float32(0.5)})
    assert mon.window_record() == {}  # step 1 staged but not collected
    mon.collect()
    assert mon.window_record()["loss_ema"] == pytest.approx(0.5)


def test_health_monitor_off_is_inert():
    mon = HealthMonitor(mode="off", registry=Registry(), num_slots=128)
    mon.staged({"loss": np.float32(0.5)})
    mon.collect()
    mon.observe_batch(np.zeros((2, 2), np.int32), np.ones((2, 2), np.float32))
    assert mon.window_record() == {}


# ----------------------------------------------------- occupancy / collisions


def test_estimate_collision_rate_bounds():
    assert estimate_collision_rate(0, 1 << 12) == 0.0
    assert estimate_collision_rate(1, 1 << 12) == pytest.approx(0.0, abs=1e-9)
    assert estimate_collision_rate(1 << 12, 1 << 12) == 1.0
    # sparse occupancy ⇒ near-zero estimate; heavy occupancy ⇒ substantial
    lo = estimate_collision_rate(10, 1 << 20)
    hi = estimate_collision_rate((1 << 12) - 10, 1 << 12)
    assert lo < 1e-4 < hi < 1.0
    # matches the closed form d = S(1-(1-1/S)^n) round-tripped
    S, n = 4096, 3000
    d = S * (1 - (1 - 1 / S) ** n)
    est = estimate_collision_rate(int(round(d)), S)
    assert est == pytest.approx(1 - d / n, abs=2e-3)


def test_occupancy_gauges():
    reg = Registry()
    mon = HealthMonitor(mode="norms", registry=reg, num_slots=256)
    slots = np.array([[1, 2], [3, 1]], np.int32)
    mask = np.array([[1, 1], [0, 1]], np.float32)  # slot 3 masked off
    mon.observe_batch(slots, mask)
    mon.staged({"loss": np.float32(0.5)})
    mon.collect()
    rec = mon.window_record()
    assert rec["slots_touched"] == 2  # {1, 2}
    assert rec["table_occupancy"] == pytest.approx(2 / 256, abs=1e-6)
    assert reg.gauge("health.table_occupancy").value == pytest.approx(2 / 256)


# ------------------------------------------------------------- trainer wiring


@pytest.fixture
def health_run(tmp_path, monkeypatch):
    """A small single-process run with health metrics, heartbeats, and a
    streaming eval all on; returns the run dir."""
    monkeypatch.chdir(tmp_path)
    generate_shards(str(tmp_path / "train"), 1, 640, num_fields=6,
                    ids_per_field=40, seed=0)
    generate_shards(str(tmp_path / "test"), 1, 256, num_fields=6,
                    ids_per_field=40, seed=1, truth_seed=0)
    run = tmp_path / "run"
    cfg = override(Config(), **{
        "data.train_path": str(tmp_path / "train"),
        "data.test_path": str(tmp_path / "test"),
        "data.log2_slots": 12,
        "data.batch_size": 64,
        "data.max_nnz": 8,
        "model.num_fields": 6,
        "train.epochs": 2,
        "train.log_every": 1,
        "train.eval_every": 1,
        "train.pred_dump": False,
        "train.health_metrics": "norms",
        "train.health_ema_decay": 0.9,
        "train.heartbeat_every": 5,
        "train.metrics_path": str(run / "metrics_rank0.jsonl"),
        "train.heartbeat_path": str(run / "heartbeat_rank0.jsonl"),
    })
    default_registry().reset()
    trainer = Trainer(cfg)
    res = trainer.fit()
    assert res.steps == 20
    return run, trainer


def test_trainer_health_fields_and_ema_oracle(health_run):
    """EVERY log record carries the full health key set, and the logged
    EMA replays exactly from the logged per-step losses, covering
    losses 1..i at the record for step i: since the XF110 fix the
    trainer stages each log-cadence record and writes it one step
    BEHIND (under the next step's device time), by which point the
    health collect for the record's own step has already run — so not
    even the first record is health-blind any more."""
    run, _ = health_run
    recs = read_jsonl(str(run / "metrics_rank0.jsonl"))
    steps = [r for r in recs if "step" in r and "loss" in r]
    health = [r for r in steps if "grad_norm" in r]
    assert len(health) == len(steps)  # one-behind write: all covered
    for r in health:
        for key in ("grad_norm", "update_norm", "param_norm", "loss_ema",
                    "grad_norm_max", "slots_touched", "table_occupancy",
                    "est_collision_rate"):
            assert key in r, key
        assert r["grad_norm"] > 0 and r["param_norm"] > 0
    losses = {r["step"]: r["loss"] for r in steps}
    ema = None
    for r in health:
        cur = losses[r["step"]]
        ema = cur if ema is None else 0.9 * ema + 0.1 * cur
        assert r["loss_ema"] == pytest.approx(ema, rel=1e-4), r["step"]
    # streaming evals landed mid-run, stamped with the step
    evals = [r for r in recs if "eval_auc" in r]
    assert len(evals) == 2
    assert all("eval_logloss" in r and "step" in r for r in evals)
    # occupancy only grows, and the touched count is honest (≤ slots)
    occs = [r["slots_touched"] for r in health]
    assert occs == sorted(occs) and occs[-1] <= 1 << 12
    # final record carries the tail health window too
    final = next(r for r in recs if r.get("final"))
    assert "grad_norm" in final and "loss_ema" in final


def test_trainer_health_full_per_table(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    generate_shards(str(tmp_path / "train"), 1, 256, num_fields=6,
                    ids_per_field=40, seed=0)
    mpath = tmp_path / "m.jsonl"
    cfg = override(Config(), **{
        "data.train_path": str(tmp_path / "train"),
        "data.log2_slots": 12,
        "data.batch_size": 64,
        "data.max_nnz": 8,
        "model.num_fields": 6,
        "model.name": "fm",
        "train.epochs": 1,
        "train.log_every": 2,
        "train.pred_dump": False,
        "train.health_metrics": "full",
        "train.metrics_path": str(mpath),
    })
    default_registry().reset()
    Trainer(cfg).fit()
    recs = [r for r in read_jsonl(str(mpath)) if "health_tables" in r]
    assert recs
    tables = recs[-1]["health_tables"]
    assert "wv" in tables  # fused FM single table
    assert set(tables["wv"]) == {"grad_norm", "update_norm", "param_norm"}


def test_sgd_update_norm_is_lr_times_grad_norm(tmp_path, monkeypatch):
    """NumPy-checkable invariant through the whole pipeline: under plain
    SGD the update is exactly −lr·grad, so update_norm == lr·grad_norm."""
    monkeypatch.chdir(tmp_path)
    generate_shards(str(tmp_path / "train"), 1, 128, num_fields=6,
                    ids_per_field=40, seed=0)
    mpath = tmp_path / "m.jsonl"
    cfg = override(Config(), **{
        "data.train_path": str(tmp_path / "train"),
        "data.log2_slots": 12,
        "data.batch_size": 64,
        "data.max_nnz": 8,
        "model.num_fields": 6,
        "optim.name": "sgd",
        "train.epochs": 1,
        "train.log_every": 1,
        "train.pred_dump": False,
        "train.health_metrics": "norms",
        "train.metrics_path": str(mpath),
    })
    default_registry().reset()
    Trainer(cfg).fit()
    recs = [r for r in read_jsonl(str(mpath)) if "grad_norm" in r and r.get("step")]
    assert recs
    for r in recs:
        # JSONL values are rounded to 6 decimals, hence the abs term
        assert r["update_norm"] == pytest.approx(
            cfg.optim.sgd.lr * r["grad_norm"], rel=1e-3, abs=2e-6
        )


def test_streaming_auc_matches_exact_eval(health_run):
    """The bucketed streaming eval the eval_every pass runs agrees with
    the exact rank-sum AUC to within bucket resolution, and the logloss
    exactly (same accumulation)."""
    _, trainer = health_run
    auc_exact, ll_exact = trainer.evaluate(dump=False)
    auc_stream, ll_stream = trainer.evaluate(dump=False, streaming=True)
    # bucketed error comes from same-bucket ties counted 1/2; with a
    # briefly-trained LR the scores cluster tightly, so allow a few
    # bucket-widths of slack rather than the ideal 1/buckets
    assert auc_stream == pytest.approx(auc_exact, abs=1e-3)
    assert ll_stream == pytest.approx(ll_exact, rel=1e-9)


def test_health_off_leaves_metrics_clean(tmp_path, monkeypatch):
    """Default (off): no health keys in the step metrics or the JSONL —
    the jitted step program is untouched."""
    monkeypatch.chdir(tmp_path)
    generate_shards(str(tmp_path / "train"), 1, 128, num_fields=6,
                    ids_per_field=40, seed=0)
    mpath = tmp_path / "m.jsonl"
    cfg = override(Config(), **{
        "data.train_path": str(tmp_path / "train"),
        "data.log2_slots": 12,
        "data.batch_size": 64,
        "data.max_nnz": 8,
        "model.num_fields": 6,
        "train.epochs": 1,
        "train.log_every": 1,
        "train.pred_dump": False,
        "train.metrics_path": str(mpath),
    })
    default_registry().reset()
    Trainer(cfg).fit()
    for r in read_jsonl(str(mpath)):
        assert "grad_norm" not in r and "loss_ema" not in r


# ------------------------------------------------------------ hang watchdog


def test_hang_watchdog_dumps_once_per_stall():
    out = io.StringIO()
    wd = HangWatchdog(0.15, out=out)
    try:
        time.sleep(0.6)  # stall: one dump, not one per poll
        assert wd.dumps == 1
        assert "hang watchdog" in out.getvalue()
        assert "Thread" in out.getvalue() or "thread" in out.getvalue()
        wd.tick()  # progress re-arms
        time.sleep(0.6)
        assert wd.dumps == 2
    finally:
        wd.close()


def test_hang_watchdog_disabled_at_zero():
    wd = HangWatchdog(0.0)
    assert wd._thread is None
    wd.close()


# ------------------------------------------------------- watchdog classifier


def test_watchdog_classify_statuses():
    from xflow_tpu.launch.watchdog import classify

    now = 1000.0
    beats = {
        0: {"step": 50, "ts": now - 1, "event": None},       # leader
        1: {"step": 10, "ts": now - 2, "event": None},       # straggler
        2: {"step": 48, "ts": now - 120, "event": None},     # dead
        3: {"step": 50, "ts": now - 300, "event": "final"},  # finished
    }
    beats[5] = {"step": 0, "ts": now - 500, "event": "start"}  # compiling
    rows = classify(beats, now, straggler_factor=2.0, dead_after_s=60.0,
                    expected_ranks=7)
    by_rank = {r["rank"]: r for r in rows}
    assert by_rank[0]["status"] == "ok"
    assert by_rank[1]["status"] == "straggler"
    assert by_rank[2]["status"] == "dead"
    assert by_rank[3]["status"] == "finished"
    # a rank still on its start beat is compiling, not dead/straggling —
    # TPU first-step compilation takes minutes
    assert by_rank[5]["status"] == "starting"
    assert by_rank[4]["status"] == "missing" and by_rank[6]["status"] == "missing"
    # culprit ordering: lowest step first (start-beat ranks excepted)
    assert rows[0]["rank"] in (1, 5)
    assert by_rank[1]["step"] == 10


def test_run_watchdog_flags_and_logs(tmp_path):
    from xflow_tpu.launch.watchdog import RunWatchdog

    run = tmp_path / "run"
    run.mkdir()
    now = time.time()
    for rank, step in ((0, 40), (1, 3)):
        a = JsonlAppender(str(run / f"heartbeat_rank{rank}.jsonl"),
                          stamp={"rank": rank, "run_id": "r1", "kind": "heartbeat"})
        a.append({"step": step})
        a.close()
    out = io.StringIO()
    wd = RunWatchdog(str(run), num_ranks=2, straggler_factor=2.0,
                     dead_after_s=600.0, run_id="r1", out=out)
    rows = wd.poll_once(now=now + 1)
    assert {r["rank"]: r["status"] for r in rows} == {0: "ok", 1: "straggler"}
    assert "rank 1 is a STRAGGLER" in out.getvalue()
    rows = wd.poll_once(now=now + 1)  # no re-report while unchanged
    assert out.getvalue().count("STRAGGLER") == 1
    wd.stop()
    events = read_jsonl(str(run / "watchdog.jsonl"))
    assert [e["event"] for e in events] == ["straggler"]
    # a reused run dir: the OLD run's beats must not leak into the new
    # run's live view (fold filters on the watchdog's run_id)
    from xflow_tpu.launch.watchdog import RunWatchdog as RW

    stale = JsonlAppender(str(run / "heartbeat_rank7.jsonl"),
                          stamp={"rank": 7, "run_id": "OLD", "kind": "heartbeat"})
    stale.append({"step": 999})
    stale.close()
    wd2 = RW(str(run), num_ranks=2, straggler_factor=2.0,
             dead_after_s=600.0, run_id="r1", out=io.StringIO())
    rows = wd2.poll_once(now=now + 1)
    assert 7 not in {r["rank"] for r in rows}
    assert max(r["max_step"] for r in rows) == 40  # old 999 ignored
    wd2.stop()
    assert events[0]["flagged_rank"] == 1 and events[0]["at_step"] == 3
    # stamped as the launcher's own stream, not any rank's
    assert events[0]["rank"] == -1 and events[0]["kind"] == "watchdog"


# ---------------------------------------------------- metrics_report wiring


def _report(args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "metrics_report.py"),
         *args],
        capture_output=True, text=True, timeout=120,
    )


def test_metrics_report_health_summary(health_run):
    run, _ = health_run
    r = _report([str(run), "--check"])
    assert r.returncode == 0, r.stderr
    r = _report([str(run), "--health"])
    assert r.returncode == 0, r.stderr
    assert "norms: grad" in r.stdout
    assert "auc trajectory (2 evals)" in r.stdout
    assert "occupancy" in r.stdout
    assert "[finished]" in r.stdout  # heartbeat table, clean finish


def test_metrics_report_check_flags_partial_health(tmp_path):
    bad = tmp_path / "bad.jsonl"
    with open(bad, "w") as f:
        f.write(json.dumps({"ts": 1.0, "rank": 0, "run_id": "r", "step": 1,
                            "loss": 0.5, "grad_norm": 1.0}) + "\n")
    r = _report([str(bad), "--check"])
    assert r.returncode != 0
    assert "health keys" in r.stderr


def test_metrics_report_check_flags_lone_eval_field(tmp_path):
    bad = tmp_path / "bad.jsonl"
    with open(bad, "w") as f:
        f.write(json.dumps({"ts": 1.0, "rank": 0, "run_id": "r",
                            "eval_auc": 0.7}) + "\n")
    r = _report([str(bad), "--check"])
    assert r.returncode != 0
    assert "eval_auc/eval_logloss" in r.stderr


def test_metrics_report_regress_gate(health_run, tmp_path):
    run, _ = health_run
    bench = tmp_path / "bench.json"
    r = _report([str(run), "--bench-json", str(bench)])
    assert r.returncode == 0, r.stderr
    rec = json.loads(bench.read_text())
    assert rec["value"] > 0 and "auc" in rec
    # self-comparison passes
    r = _report([str(run), "--regress", str(bench)])
    assert r.returncode == 0, r.stderr
    assert "no regression" in r.stdout
    # an inflated baseline fails on throughput
    fat = dict(rec, value=rec["value"] * 10)
    (tmp_path / "fat.json").write_text(json.dumps(fat))
    r = _report([str(run), "--regress", str(tmp_path / "fat.json")])
    assert r.returncode == 3
    assert "throughput regressed" in r.stderr
    # a better-AUC baseline fails on quality
    smart = dict(rec, auc=min(rec["auc"] + 0.05, 1.0))
    (tmp_path / "smart.json").write_text(json.dumps(smart))
    r = _report([str(run), "--regress", str(tmp_path / "smart.json")])
    assert r.returncode == 3
    assert "AUC regressed" in r.stderr


# -------------------------------------------------- launch-local drill


def test_launch_local_straggler_drill(tmp_path):
    """End-to-end watchdog drill: two launch-local ranks, rank 1 stalls
    mid-run (testing/faults.py env injector), the launcher watchdog
    flags it as a straggler while the run is live, and the run still
    completes cleanly once the stall ends."""
    from tests.test_launch_local import multiproc_cpu_supported, run_cli

    if not multiproc_cpu_supported():
        pytest.skip("this jax build cannot run multi-process CPU worlds")
    generate_shards(str(tmp_path / "train"), 2, 768, num_fields=6,
                    ids_per_field=40, seed=0)
    run = tmp_path / "run"
    r = run_cli(
        [
            "launch-local", "--num-processes", "2",
            "--run-dir", str(run),
            "--watchdog-poll-s", "0.2",
            "--straggler-factor", "1.01",
            "--dead-after-s", "300",
            "--",
            "--train", str(tmp_path / "train"), "--model", "lr",
            "--epochs", "1", "--batch-size", "32", "--log2-slots", "12",
            "--set", "model.num_fields=6",
            "--set", "data.max_nnz=8",
            "--set", "train.pred_dump=false",
            "--set", "train.heartbeat_every=1",
        ],
        cwd=str(tmp_path),
        extra_env={
            "XFLOW_FAULT_STALL_S": "6",
            "XFLOW_FAULT_STALL_STEP": "4",
            "XFLOW_FAULT_DELAY_RANK": "1",
        },
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "rank 1 is a STRAGGLER" in r.stderr, r.stderr
    events = read_jsonl(str(run / "watchdog.jsonl"), warn=False)
    assert any(
        e["event"] == "straggler" and e["flagged_rank"] == 1 for e in events
    )
    # every rank heartbeated and the post-mortem health view renders
    for rank in (0, 1):
        beats = read_jsonl(str(run / f"heartbeat_rank{rank}.jsonl"), warn=False)
        assert any(b.get("event") == "final" for b in beats)
    rep = _report([str(run), "--health"])
    assert rep.returncode == 0, rep.stderr
    assert "heartbeats" in rep.stdout
