"""Async tiered checkpointing suite (docs/ROBUSTNESS.md "Async tiered
checkpointing"): the background save pipeline (`train.ckpt_async`), the
tier-2 replica mirror (`train.ckpt_replica_dir`), the tiered restore
walk, the disk-fault injectors, the `kind="ckpt"` telemetry gates, and
the synchronous-mode artifact-identity pin.

The acceptance drills — kill mid-async-save resumes with exact example
accounting; a digest-poisoned primary restores from the replica tier in
the trainer AND the serve watcher — run here in-process/subprocess and
end-to-end via tools/smoke_durable.sh (test_smoke_durable_script)."""

import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.data.synth import generate_shards
from xflow_tpu.testing.faults import (
    ckpt_write_fault,
    corrupt_npz_checkpoint,
    corrupt_orbax_checkpoint,
)
from xflow_tpu.train import checkpoint as ckpt
from xflow_tpu.train.checkpoint import (
    committed_steps,
    mirror_step,
    read_data_state,
    tier_steps,
)
from xflow_tpu.train.trainer import Trainer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAULT_ENVS = (
    "XFLOW_FAULT_CKPT_ENOSPC_BYTES",
    "XFLOW_FAULT_CKPT_SLOW_S_PER_MB",
    "XFLOW_FAULT_CKPT_TIER",
)


@pytest.fixture(autouse=True)
def _clean_fault_env(monkeypatch):
    for name in FAULT_ENVS + ("XFLOW_FAULT_KILL_STEP",):
        monkeypatch.delenv(name, raising=False)


def make_cfg(root, **kw):
    base = {
        "data.train_path": str(root / "train"),
        "data.log2_slots": 12,
        "data.batch_size": 100,
        "data.max_nnz": 8,
        "model.num_fields": 5,
        "train.epochs": 2,
        "train.pred_dump": False,
    }
    base.update(kw)
    return override(Config(), **base)


@pytest.fixture
def dataset(tmp_path):
    generate_shards(
        str(tmp_path / "train"), 1, 600, num_fields=5, ids_per_field=30,
        seed=0,
    )
    return tmp_path


def read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ------------------------------------------------------ fault injector unit
def test_ckpt_write_fault_env_contract(monkeypatch, tmp_path):
    """ENOSPC budget + tier targeting, resolved fresh per save."""
    assert ckpt_write_fault("primary") is None  # nothing armed
    p = tmp_path / "blob"
    p.write_bytes(b"x" * 1000)
    monkeypatch.setenv("XFLOW_FAULT_CKPT_ENOSPC_BYTES", "1500")
    f = ckpt_write_fault("primary")
    f(str(p))  # 1000 staged bytes: under budget
    with pytest.raises(OSError) as ei:
        f(str(p))  # cumulative 2000 > 1500
    assert "ENOSPC" in str(ei.value)
    # a FRESH resolve gets a fresh budget (per save, not per run)
    ckpt_write_fault("primary")(str(p))
    # tier targeting: a replica-only fault leaves the primary unarmed
    monkeypatch.setenv("XFLOW_FAULT_CKPT_TIER", "replica")
    assert ckpt_write_fault("primary") is None
    assert ckpt_write_fault("replica") is not None


# -------------------------------------------------- replica walk-back matrix
FM_BASE = {
    # the fullshard engine's validated shape (test_topology idiom); the
    # fused fm "wv" table also exercises the packed/logical layout
    # bridge every engine restore must cross
    "model.name": "fm",
    "data.log2_slots": 14,
    "data.batch_size": 128,
}


@pytest.fixture(scope="module")
def tiered_runs(tmp_path_factory):
    """One fit per format with both tiers committed; the matrix cases
    below damage COPIES, so two fits serve all sixteen cases."""
    runs = {}
    for fmt in ("npz", "orbax"):
        if fmt == "orbax":
            pytest.importorskip("orbax.checkpoint")
        root = tmp_path_factory.mktemp(f"tiered_{fmt}")
        generate_shards(
            str(root / "train"), 1, 600, num_fields=5, ids_per_field=30,
            seed=0,
        )
        cfg = make_cfg(root, **FM_BASE, **{
            "train.checkpoint_dir": str(root / "ck"),
            "train.ckpt_replica_dir": str(root / "replica"),
            "train.checkpoint_every": 5,
            "train.checkpoint_format": fmt,
        })
        t = Trainer(cfg)
        t.fit()
        steps = tier_steps(str(root / "ck"), fmt)
        assert len(steps) >= 2  # cadence + final: a walk-back target
        assert tier_steps(str(root / "replica"), fmt) == steps
        runs[fmt] = {
            "root": root,
            "steps": steps,
            "wv": np.asarray(jax.device_get(t.state.tables["wv"])).copy(),
            "examples": read_data_state(
                str(root / "replica"), steps[0], fmt=fmt)["examples"],
        }
    return runs


def copy_tiers(src_root, tmp_path):
    primary = str(tmp_path / "ck")
    replica = str(tmp_path / "replica")
    shutil.copytree(str(src_root / "ck"), primary)
    shutil.copytree(str(src_root / "replica"), replica)
    return primary, replica


ENGINES = ("single", "gspmd", "fullshard")


def engine_trainer(cfg, engine):
    from xflow_tpu.parallel.mesh import make_mesh

    if engine == "single":
        return Trainer(cfg)
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 CPU devices")
    if engine == "gspmd":
        # sorted engines off -> the generic GSPMD mesh path
        cfg = override(cfg, **{"data.sorted_layout": "off"})
    mesh = make_mesh(cfg, np.array(jax.devices()[:2]))
    t = Trainer(cfg, mesh=mesh)
    assert t.engine == engine
    return t


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("fmt", ("npz", "orbax"))
@pytest.mark.parametrize("damage", ("missing", "bitflip"))
def test_replica_walkback_matrix(tiered_runs, tmp_path, engine, fmt, damage):
    """THE tier-2 acceptance matrix: with the newest primary step gone
    or digest-poisoned, every engine restores the SAME step from the
    replica mirror — same logical table bytes, same step, and the
    data-stream position travels from the tier that restored."""
    src = tiered_runs[fmt]
    newest = src["steps"][0]
    primary, replica = copy_tiers(src["root"], tmp_path)
    if damage == "missing":
        prefix = "orbax_step_" if fmt == "orbax" else "step_"
        shutil.rmtree(os.path.join(primary, f"{prefix}{newest}"))
    elif fmt == "orbax":
        corrupt_orbax_checkpoint(primary, step=newest, mode="bitflip",
                                 target="largest")
    else:
        corrupt_npz_checkpoint(primary, step=newest, mode="bitflip")

    cfg = make_cfg(src["root"], **FM_BASE, **{
        "train.checkpoint_dir": primary,
        "train.ckpt_replica_dir": replica,
        "train.checkpoint_format": fmt,
        "train.resume": True,
    })
    t = engine_trainer(cfg, engine)
    assert t.maybe_restore()
    assert int(t.state.step) == newest
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(t.state.tables["wv"])), src["wv"],
        err_msg=f"{engine}/{fmt}/{damage}: replica restore drifted",
    )
    assert t._resume_data_state is not None
    assert t._resume_data_state["examples"] == src["examples"]


def test_replica_divergence_walks_to_older_step(tiered_runs, tmp_path):
    """Both copies of the newest step bad (primary missing, replica
    poisoned — the replica-divergence row of the failure matrix): the
    walk continues to the previous committed step instead of restoring
    garbage or dying."""
    src = tiered_runs["npz"]
    newest, older = src["steps"][0], src["steps"][1]
    primary, replica = copy_tiers(src["root"], tmp_path)
    shutil.rmtree(os.path.join(primary, f"step_{newest}"))
    corrupt_npz_checkpoint(replica, step=newest, mode="bitflip")
    cfg = make_cfg(src["root"], **FM_BASE, **{
        "train.checkpoint_dir": primary,
        "train.ckpt_replica_dir": replica,
        "train.resume": True,
    })
    t = Trainer(cfg)
    assert t.maybe_restore()
    assert int(t.state.step) == older
    assert t._resume_data_state == read_data_state(primary, older)


def test_mirror_step_idempotent_and_committed_last(tiered_runs, tmp_path):
    """mirror_step re-run on an already-committed replica step is a
    no-op, and a fresh mirror lands digest-verified with its own
    COMMITTED marker."""
    src = tiered_runs["npz"]
    newest = src["steps"][0]
    primary = str(src["root"] / "ck")
    replica = str(tmp_path / "replica2")
    dst = mirror_step(primary, replica, newest)
    assert os.path.exists(os.path.join(dst, "COMMITTED"))
    assert committed_steps(replica) == [newest]
    before = sorted(os.listdir(dst))
    mtime = os.path.getmtime(os.path.join(dst, "state.npz"))
    assert mirror_step(primary, replica, newest) == dst  # idempotent
    assert sorted(os.listdir(dst)) == before
    assert os.path.getmtime(os.path.join(dst, "state.npz")) == mtime


# ------------------------------------------------------- skip-on-busy + off
def test_async_skip_on_busy_accounting(dataset, tmp_path, monkeypatch,
                                       capsys):
    """Cadence hit while a save is in flight = one logged, counted skip
    — never a queue. The slow-write fault pins the step-5 save in
    flight across the step-10 cadence; the end-of-fit wait=True save
    still commits step 12."""
    # ~48KB state * 60 s/MB ≈ 3s per staged file — far longer than the
    # fit needs to reach the step-10 cadence
    monkeypatch.setenv("XFLOW_FAULT_CKPT_SLOW_S_PER_MB", "60")
    ck = str(tmp_path / "ck")
    cfg = make_cfg(dataset, **{
        "train.checkpoint_dir": ck,
        "train.checkpoint_every": 5,
        "train.ckpt_async": True,
        "train.metrics_path": str(tmp_path / "metrics.jsonl"),
    })
    t = Trainer(cfg)
    res = t.fit()
    assert res.steps == 12
    assert t._ckpt_writer is None  # fit() closed the writer
    assert committed_steps(ck) == [12, 5]  # 10 skipped, final waited
    recs = [r for r in read_jsonl(str(tmp_path / "metrics.jsonl"))
            if r.get("kind") == "ckpt"]
    events = {(r["step"], r["event"]) for r in recs}
    assert (5, "committed") in events
    assert (10, "skipped") in events
    assert (12, "committed") in events
    assert max(r["skips"] for r in recs) == 1
    skipped = next(r for r in recs if r["event"] == "skipped")
    assert skipped["write_ms"] == 0.0 and skipped["tier"] == "primary"
    assert not any(r["degraded"] for r in recs)
    assert "previous save still in flight" in capsys.readouterr().err


def test_async_off_identical_artifact_no_records(dataset, tmp_path):
    """The ckpt_async=off pin: no writer thread and no kind="ckpt"
    records; and the async pipeline reorders work without changing the
    artifact — same step, same per-array digests, same data_state."""
    ck_sync = str(tmp_path / "ck_sync")
    cfg = make_cfg(dataset, **{
        "train.checkpoint_dir": ck_sync,
        "train.metrics_path": str(tmp_path / "m_sync.jsonl"),
    })
    t = Trainer(cfg)
    t.fit()
    assert t._ckpt_writer is None  # never started
    assert all(r.get("kind") != "ckpt"
               for r in read_jsonl(str(tmp_path / "m_sync.jsonl")))

    ck_async = str(tmp_path / "ck_async")
    Trainer(make_cfg(dataset, **{
        "train.checkpoint_dir": ck_async,
        "train.ckpt_async": True,
    })).fit()
    assert committed_steps(ck_sync) == committed_steps(ck_async) == [12]
    meta_s = ckpt.read_meta(ck_sync, 12)
    meta_a = ckpt.read_meta(ck_async, 12)
    assert meta_s["digests"] == meta_a["digests"]
    assert meta_s["layout"] == meta_a["layout"]
    assert read_data_state(ck_sync, 12) == read_data_state(ck_async, 12)


# --------------------------------------------------------- degraded mode
def test_enospc_degrades_to_replica_only(dataset, tmp_path, monkeypatch,
                                         capsys):
    """A primary-tier ENOSPC mid-save latches degraded mode: training
    finishes, every save lands as a FULL save on the replica tier, the
    kind="ckpt" trail says so, and the resume restores from the
    replica."""
    monkeypatch.setenv("XFLOW_FAULT_CKPT_ENOSPC_BYTES", "1")
    monkeypatch.setenv("XFLOW_FAULT_CKPT_TIER", "primary")
    ck = str(tmp_path / "ck")
    replica = str(tmp_path / "replica")
    cfg = make_cfg(dataset, **{
        "train.checkpoint_dir": ck,
        "train.ckpt_replica_dir": replica,
        "train.checkpoint_every": 5,
        "train.ckpt_async": True,
        "train.metrics_path": str(tmp_path / "metrics.jsonl"),
    })
    res = Trainer(cfg).fit()
    assert res.steps == 12  # training never stopped
    assert committed_steps(ck) == []  # the primary volume is "full"
    assert committed_steps(replica)[0] == 12
    recs = [r for r in read_jsonl(str(tmp_path / "metrics.jsonl"))
            if r.get("kind") == "ckpt"]
    assert any(r["tier"] == "primary" and r["event"] == "failed"
               for r in recs)
    assert any(r["tier"] == "replica" and r["event"] == "committed"
               and r["degraded"] for r in recs)
    assert "degrading to replica-only" in capsys.readouterr().err
    # the resume walks the union: replica-only steps restore fine
    for name in FAULT_ENVS:
        monkeypatch.delenv(name, raising=False)
    t2 = Trainer(override(cfg, **{"train.resume": True}))
    assert t2.maybe_restore() and int(t2.state.step) == 12


def test_sync_mirror_failure_never_harms_primary(dataset, tmp_path,
                                                 monkeypatch, capsys):
    """Synchronous mode with a replica-targeted fault: the primary
    commit stands, the mirror failure is a logged warning, training and
    the final save finish."""
    monkeypatch.setenv("XFLOW_FAULT_CKPT_ENOSPC_BYTES", "1")
    monkeypatch.setenv("XFLOW_FAULT_CKPT_TIER", "replica")
    ck = str(tmp_path / "ck")
    replica = str(tmp_path / "replica")
    cfg = make_cfg(dataset, **{
        "train.checkpoint_dir": ck,
        "train.ckpt_replica_dir": replica,
    })
    res = Trainer(cfg).fit()
    assert res.steps == 12
    assert committed_steps(ck) == [12]
    assert committed_steps(replica) == []
    assert "the primary commit stands" in capsys.readouterr().err


# ------------------------------------------------- kill mid-async-save
@pytest.mark.slow
def test_kill_mid_async_save_resume_parity(dataset, tmp_path):
    """The acceptance drill: SIGKILL lands while the background writer
    is mid-write (slow-write paced), the torn step is uncommitted
    debris, and the relaunch walks back, replays the exact lost
    examples, and converges to the uninterrupted run's state."""
    ref = Trainer(make_cfg(dataset))
    assert ref.fit().steps == 12

    ck = str(tmp_path / "ck")
    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = REPO_ROOT + os.pathsep + base_env.get(
        "PYTHONPATH", "")
    base_env["JAX_PLATFORMS"] = "cpu"

    def train_args(*extra_sets):
        args = [
            sys.executable, "-m", "xflow_tpu", "train",
            "--train", str(dataset / "train"), "--epochs", "2",
            "--batch-size", "100", "--log2-slots", "12", "--no-mesh",
            "--checkpoint-dir", ck,
            "--set", "model.num_fields=5", "--set", "data.max_nnz=8",
            "--set", "train.pred_dump=false",
            "--set", "train.checkpoint_every=5",
            "--set", "train.resume=true",
        ]
        for s in extra_sets:
            args += ["--set", s]
        return args

    # phase A: synchronous saves (deterministic commit), die after the
    # step-7 boundary — committed exactly [5]
    env = dict(base_env)
    env["XFLOW_FAULT_KILL_STEP"] = "7"
    r = subprocess.run(train_args(), capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode != 0  # SIGKILLed
    assert committed_steps(ck) == [5], r.stderr

    # phase B: resume from 5 with async on and the step-10 save paced
    # to ~30s; the kill at global step 11 (the injector counts THIS
    # process's steps: local 6) lands MID-WRITE — torn, uncommitted
    env = dict(base_env)
    env["XFLOW_FAULT_KILL_STEP"] = "6"
    env["XFLOW_FAULT_CKPT_SLOW_S_PER_MB"] = "600"
    env["XFLOW_FAULT_CKPT_TIER"] = "primary"
    r = subprocess.run(train_args("train.ckpt_async=true"),
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode != 0
    assert "resumed from step 5" in r.stderr
    assert committed_steps(ck) == [5], r.stderr
    assert os.path.isdir(os.path.join(ck, "step_10"))  # the torn save
    assert not os.path.exists(os.path.join(ck, "step_10", "COMMITTED"))

    # phase C: faults disarmed — the walk-back resume sweeps the
    # debris, retrains 6..12, and matches the uninterrupted run exactly
    r = subprocess.run(train_args("train.ckpt_async=true"),
                       capture_output=True, text=True, timeout=300,
                       env=base_env)
    assert r.returncode == 0, r.stderr
    assert "resumed from step 5" in r.stderr
    assert committed_steps(ck)[0] == 12
    t = Trainer(make_cfg(dataset, **{"train.checkpoint_dir": ck,
                                     "train.resume": True}))
    assert t.maybe_restore() and int(t.state.step) == 12
    np.testing.assert_allclose(
        np.asarray(t.state.tables["w"]), np.asarray(ref.state.tables["w"]),
        rtol=0, atol=1e-6,
        err_msg="kill-mid-async-save resume drifted from the "
                "uninterrupted stream",
    )
    ds = read_data_state(ck, 12)
    assert ds["completed"] and ds["examples"] == 1200


# --------------------------------------------------------- CLI + telemetry
def test_corrupt_ckpt_cli_tier_replica(tiered_runs, tmp_path):
    """The operator drill reaches the replica tier end to end: the CLI
    poisons the mirror, and the mirror then fails its digest check."""
    src = tiered_runs["npz"]
    newest = src["steps"][0]
    replica = str(tmp_path / "replica")
    shutil.copytree(str(src["root"] / "replica"), replica)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "corrupt_ckpt.py"),
         "--dir", "ignored", "--tier", "replica", "--replica-dir", replica,
         "--mode", "bitflip"],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    assert out["tier"] == "replica"
    assert out["corrupted"].startswith(replica)
    like = Trainer(make_cfg(src["root"], **FM_BASE)).state
    with pytest.raises(ckpt.CheckpointDigestError):
        ckpt.restore(replica, like, step=newest)


def _ck_rec(step, tier, event, q, c, skips=0, **kw):
    rec = {"ts": c, "rank": 0, "run_id": "r", "kind": "ckpt", "step": step,
           "tier": tier, "event": event, "queued_ts": q, "committed_ts": c,
           "queue_ms": 1.0, "write_ms": 2.0, "bytes": 100, "skips": skips,
           "degraded": False}
    rec.update(kw)
    return rec


def _check(dirpath, recs):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import metrics_report

    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(dirpath, "metrics_rank0.jsonl")
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    streams, _ = metrics_report.load_streams([path])
    return metrics_report.check_streams(streams, [path])


def test_metrics_report_ckpt_gate(tmp_path):
    """--check on kind="ckpt": all-or-none keys, tier/event vocabulary,
    commit-after-queue causality, non-overlapping intervals per tier,
    skip counter monotone — a good stream is clean, each violation is
    named."""
    good = [
        _ck_rec(5, "primary", "committed", 1.0, 2.0),
        _ck_rec(5, "replica", "committed", 1.0, 2.5),
        _ck_rec(10, "primary", "skipped", 3.0, 3.0, skips=1,
                write_ms=0.0),
        _ck_rec(12, "primary", "committed", 4.0, 5.0, skips=1),
        _ck_rec(12, "replica", "committed", 4.0, 5.5, skips=1),
    ]
    assert _check(tmp_path / "good", good) == []

    bad = [dict(good[0])]
    del bad[0]["queue_ms"]
    assert any("lacks ckpt keys" in p for p in _check(tmp_path / "m", bad))

    assert any("unknown ckpt tier" in p for p in _check(
        tmp_path / "t", [_ck_rec(5, "tertiary", "committed", 1.0, 2.0)]))

    assert any("unknown ckpt event" in p for p in _check(
        tmp_path / "e", [_ck_rec(5, "primary", "exploded", 1.0, 2.0)]))

    assert any("cannot commit" in p for p in _check(
        tmp_path / "c", [_ck_rec(5, "primary", "committed", 3.0, 2.0)]))

    # two saves in flight: the second commit's queued_ts predates the
    # first one's committed_ts on the same tier...
    assert any("two saves in flight" in p for p in _check(
        tmp_path / "o",
        [_ck_rec(5, "primary", "committed", 1.0, 4.0),
         _ck_rec(10, "primary", "committed", 3.0, 5.0)]))
    # ...but a replica interval sharing its job's queued_ts is FINE
    assert _check(tmp_path / "s",
                  [_ck_rec(5, "primary", "committed", 1.0, 2.0),
                   _ck_rec(5, "replica", "committed", 1.0, 2.5)]) == []

    assert any("skip counter went backwards" in p for p in _check(
        tmp_path / "k",
        [_ck_rec(5, "primary", "committed", 1.0, 2.0, skips=2),
         _ck_rec(12, "primary", "committed", 3.0, 4.0, skips=1)]))


def test_metrics_report_health_ckpt_section(dataset, tmp_path):
    """--health names the last committed step per tier; --check passes
    a real async run's stream."""
    mpath = str(tmp_path / "metrics.jsonl")
    cfg = make_cfg(dataset, **{
        "train.checkpoint_dir": str(tmp_path / "ck"),
        "train.ckpt_replica_dir": str(tmp_path / "replica"),
        "train.checkpoint_every": 5,
        "train.ckpt_async": True,
        "train.metrics_path": mpath,
    })
    Trainer(cfg).fit()
    tool = os.path.join(REPO_ROOT, "tools", "metrics_report.py")
    r = subprocess.run([sys.executable, tool, mpath, "--health"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "checkpoints (kind=ckpt" in r.stdout
    assert "primary: last committed step 12" in r.stdout
    assert "replica: last committed step 12" in r.stdout
    r2 = subprocess.run([sys.executable, tool, mpath, "--check"],
                        capture_output=True, text=True, timeout=300)
    assert r2.returncode == 0, r2.stdout + r2.stderr


# ------------------------------------------------------------- serve tier
def test_serve_watcher_follows_replica_tier(tiered_runs, tmp_path):
    """The hot-reload watcher's view spans both tiers: with the primary
    copy of the newest step digest-poisoned, latest_committed_step
    still reports it and load() swaps it in from the replica."""
    from xflow_tpu.serve.runner import ServeRunner

    src = tiered_runs["npz"]
    newest = src["steps"][0]
    primary, replica = copy_tiers(src["root"], tmp_path)
    corrupt_npz_checkpoint(primary, step=newest, mode="bitflip")
    cfg = make_cfg(src["root"], **FM_BASE, **{
        "train.checkpoint_dir": primary,
        "train.ckpt_replica_dir": replica,
    })
    runner = ServeRunner(cfg)
    assert runner.latest_committed_step() == newest
    gen = runner.load()
    assert gen.step == newest
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(gen.tables["wv"])), src["wv"],
        err_msg="serve-side replica restore drifted",
    )


# ---------------------------------------------------------------- CI gate
@pytest.mark.slow
def test_smoke_durable_script(tmp_path):
    """The durability CI gate end to end: async stall collapse through
    perf_ledger --regress, SIGKILL mid-async-save + exact accounting,
    poisoned primary + serve-side replica hot reload with zero dropped
    requests, metrics_report --check green (tools/smoke_durable.sh)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "smoke_durable.sh"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=570, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "smoke_durable: OK" in r.stdout
    bench = json.load(open(tmp_path / "BENCH_CKPT.json"))
    by_round = {b["round"]: b["value"] for b in bench}
    assert set(by_round) == {1, 2}
    assert by_round[2] < by_round[1]  # async stall < sync stall
