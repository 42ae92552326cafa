"""Multi-slice bounded-staleness sync suite (docs/DISTRIBUTED.md
"Multi-slice bounded staleness", docs/ROBUSTNESS.md "Slice lost
mid-sync"): the delta model's convergence algebra, the staleness
policies (wait vs proceed, both bounded), membership-driven wait
release, the rejoin catch-up paths (snapshot adoption + the
no-snapshot fast-forward), and the K=0 bitwise guarantee — sync.mode
off and sync must produce the identical model for a single slice.

The parity sweep over K in {1, 8, 64} (2 emulated slices under
`launch-multislice`) is the slow-marked launch matrix at the bottom.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.data.synth import generate_shards
from xflow_tpu.models import get_model
from xflow_tpu.optim import get_optimizer
from xflow_tpu.parallel.multislice import (
    SliceSyncer,
    read_membership,
    slice_forward_args,
    write_membership,
)
from xflow_tpu.testing.faults import sync_faults_from_env
from xflow_tpu.train import init_state
from xflow_tpu.train.trainer import Trainer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sync_cfg(tmp_path, **kw):
    base = {
        "sync.mode": "bounded",
        "sync.dir": str(tmp_path / "sync"),
        "sync.staleness_k": 1,
        "sync.on_stale": "proceed",
        "sync.timeout_s": 0.2,
        "sync.retries": 0,
        "sync.backoff_s": 0.0,
        "sync.snapshot_every": 1000,  # off unless a test asks
    }
    base.update(kw)
    return override(Config(), **base).sync


def tiny_state(seed=0):
    cfg = override(Config(), **{"data.log2_slots": 6})
    return cfg, init_state(get_model("lr"), get_optimizer("sgd"), cfg, seed=seed)


def bump(state, delta):
    """A fake local training block: every table leaf moves by `delta`."""
    return state._replace(
        tables={k: v + delta for k, v in state.tables.items()}
    )


# ------------------------------------------------------------- membership
def test_membership_defensive_read(tmp_path):
    # missing file: everyone is live (never fail-stop on bookkeeping)
    assert read_membership(str(tmp_path), 3) == {0, 1, 2}
    write_membership(str(tmp_path), {0, 2}, run_id="r", note="t")
    assert read_membership(str(tmp_path), 3) == {0, 2}
    # out-of-range ids are filtered, an empty result falls back to all
    write_membership(str(tmp_path), {7}, run_id="r", note="t")
    assert read_membership(str(tmp_path), 3) == {0, 1, 2}
    # corrupt json: everyone is live
    with open(os.path.join(str(tmp_path), "membership.json"), "w") as f:
        f.write("{nope")
    assert read_membership(str(tmp_path), 3) == {0, 1, 2}


def test_sync_fault_env_parsing(monkeypatch):
    for var in ("XFLOW_FAULT_SLICE_KILL_ROUND", "XFLOW_FAULT_SYNC_DELAY_S",
                "XFLOW_FAULT_SLICE", "XFLOW_FAULT_SLICE_KILL_GEN",
                "XFLOW_SLICE", "XFLOW_RESTART_GEN"):
        monkeypatch.delenv(var, raising=False)
    assert sync_faults_from_env() == (0, 0.0)
    monkeypatch.setenv("XFLOW_FAULT_SLICE_KILL_ROUND", "3")
    monkeypatch.setenv("XFLOW_FAULT_SYNC_DELAY_S", "0.25")
    assert sync_faults_from_env() == (3, 0.25)
    # targeted at another slice: both injectors disarm
    monkeypatch.setenv("XFLOW_FAULT_SLICE", "1")
    monkeypatch.setenv("XFLOW_SLICE", "0")
    assert sync_faults_from_env() == (0, 0.0)
    monkeypatch.setenv("XFLOW_SLICE", "1")
    assert sync_faults_from_env() == (3, 0.25)
    # the kill is generation-gated: the relaunch must rejoin, not re-die
    monkeypatch.setenv("XFLOW_RESTART_GEN", "1")
    kill, delay = sync_faults_from_env()
    assert kill == 0 and delay == 0.25


def test_slice_forward_args_substitution():
    out = slice_forward_args(
        ["--train", "/d/tr_s{slice}", "--epochs", "2"], 1
    )
    assert out == ["--train", "/d/tr_s1", "--epochs", "2"]


# ------------------------------------------------------- the delta algebra
def test_single_slice_passthrough_is_the_same_object(tmp_path):
    """No peers -> no merge -> the state OBJECT passes through: the
    strongest possible form of the K=0 bitwise guarantee (a float
    round-trip base + (local - base) would already break it)."""
    _, st = tiny_state()
    s = SliceSyncer(sync_cfg(tmp_path, **{"sync.mode": "sync"}), 0, 1)
    s.attach(st)
    st1 = bump(st, 1.0)
    st2, rec = s.sync(st1)
    assert st2 is st1
    assert rec["round"] == 1 and rec["k"] == 0 and rec["applied"] == 0
    st3, rec = s.sync(st2)
    assert st3 is st2 and rec["round"] == 2


def test_two_slices_converge_to_the_delta_sum(tmp_path):
    """Local-SGD algebra: both slices end at init + sum(all deltas),
    independent of apply order — exactly the large-batch semantics that
    make additive sync EXACT for sgd."""
    _, stA = tiny_state(seed=0)
    _, stB = tiny_state(seed=0)  # identical seeded init, the contract
    cfg = sync_cfg(tmp_path)
    sA, sB = SliceSyncer(cfg, 0, 2), SliceSyncer(cfg, 1, 2)
    sA.attach(stA)
    sB.attach(stB)
    stA1, recA = sA.sync(bump(stA, 1.0))   # publishes +1, sees nothing
    stB1, recB = sB.sync(bump(stB, 2.0))   # publishes +2, applies +1
    assert recA["applied"] == 0 and recB["applied"] == 1
    # A's round 2 adds nothing locally but folds in B's +2
    stA2, recA2 = sA.sync(stA1)
    assert recA2["applied"] == 1
    want = np.asarray(stA.tables["w"]) + 3.0
    np.testing.assert_allclose(np.asarray(stA2.tables["w"]), want, rtol=0)
    np.testing.assert_allclose(np.asarray(stB1.tables["w"]), want, rtol=0)


def test_sync_requires_attach(tmp_path):
    _, st = tiny_state()
    s = SliceSyncer(sync_cfg(tmp_path), 0, 1)
    with pytest.raises(RuntimeError):
        s.sync(st)


# ------------------------------------------------------ staleness policies
def test_proceed_on_stale_counts_and_continues(tmp_path):
    """k=0 bounded + proceed: a silent peer makes the round STALE
    (counted, lag reported) but never blocks."""
    _, st = tiny_state()
    s = SliceSyncer(
        sync_cfg(tmp_path, **{"sync.staleness_k": 0}), 0, 2
    )
    s.attach(st)
    _, rec = s.sync(bump(st, 1.0))
    assert rec["stale"] == 1 and rec["lags"] == {"1": 1}
    assert rec["timeouts"] == 0  # proceed never waits


def test_wait_on_stale_is_bounded_and_counted(tmp_path):
    _, st = tiny_state()
    s = SliceSyncer(
        sync_cfg(tmp_path, **{
            "sync.staleness_k": 0,
            "sync.on_stale": "wait",
            "sync.timeout_s": 0.05,
            "sync.retries": 1,
        }), 0, 2,
    )
    s.attach(st)
    _, rec = s.sync(bump(st, 1.0))  # returns despite the dead peer
    assert rec["timeouts"] >= 1 and rec["stale"] == 1


def test_membership_releases_the_wait(tmp_path):
    """A peer the launcher declared dead stops being waited on: the
    wait loop re-reads membership every poll. timeout_s is set long so
    a pass proves membership (not the timeout) released it."""
    _, st = tiny_state()
    cfg = sync_cfg(tmp_path, **{
        "sync.mode": "sync", "sync.timeout_s": 60.0, "sync.retries": 0,
    })
    s = SliceSyncer(cfg, 0, 2)
    s.attach(st)
    write_membership(cfg.dir, {0}, run_id="r", note="slice 1 dead")
    _, rec = s.sync(bump(st, 1.0))
    assert rec["live"] == [0] and rec["left"] == [1]
    assert rec["stale"] == 0  # staleness is judged against LIVE peers


def test_dead_peer_committed_deltas_still_apply(tmp_path):
    """Zero-lost-examples: rounds a slice PUBLISHED before dying are
    trained examples — survivors fold them in even after the member
    leaves the group."""
    _, stA = tiny_state(seed=0)
    _, stB = tiny_state(seed=0)
    cfg = sync_cfg(tmp_path)
    sB = SliceSyncer(cfg, 1, 2)
    sB.attach(stB)
    sB.sync(bump(stB, 2.0))  # B publishes round 1, then "dies"
    write_membership(cfg.dir, {0}, run_id="r", note="slice 1 dead")
    sA = SliceSyncer(cfg, 0, 2)
    sA.attach(stA)
    stA1, rec = sA.sync(bump(stA, 1.0))
    assert rec["applied"] == 1 and rec["live"] == [0]
    np.testing.assert_allclose(
        np.asarray(stA1.tables["w"]), np.asarray(stA.tables["w"]) + 3.0,
        rtol=0,
    )


# ------------------------------------------------------------ rejoin paths
def test_adopt_latest_snapshot(tmp_path):
    _, stA = tiny_state(seed=0)
    cfg = sync_cfg(tmp_path, **{"sync.snapshot_every": 1})
    sA = SliceSyncer(cfg, 0, 2)
    sA.attach(stA)
    stA1, _ = sA.sync(bump(stA, 1.0))  # publishes delta + snapshot r1
    _, stB = tiny_state(seed=0)
    sB = SliceSyncer(cfg, 1, 2)
    stB2, adopted = sB.adopt_latest_snapshot(stB)
    assert adopted == (1, 0)
    assert sB._applied[0] == 1 and sB.round == 1  # r1 must not re-apply
    np.testing.assert_allclose(
        np.asarray(stB2.tables["w"]), np.asarray(stA1.tables["w"]), rtol=0
    )
    # the adopted state keeps ITS OWN step counter (example accounting)
    assert int(stB2.step) == int(stB.step)


def test_attach_fast_forwards_without_snapshot(tmp_path, monkeypatch):
    """Death before the first snapshot: the restored checkpoint already
    folded in an unknown prefix of peer deltas, so a gen>0 attach with
    nothing to adopt skips everything already published rather than
    double-applying it."""
    _, stA = tiny_state(seed=0)
    cfg = sync_cfg(tmp_path)  # snapshots off
    sA = SliceSyncer(cfg, 0, 2)
    sA.attach(stA)
    st = bump(stA, 1.0)
    for _ in range(2):
        st, _ = sA.sync(st)
    monkeypatch.setenv("XFLOW_RESTART_GEN", "1")
    _, stB = tiny_state(seed=0)
    sB = SliceSyncer(cfg, 1, 2)
    stB2, adopted = sB.adopt_latest_snapshot(stB)
    assert adopted is None
    sB.attach(stB2)
    assert sB._applied[0] == 2
    stB3, rec = sB.sync(bump(stB2, 5.0))
    assert rec["applied"] == 0  # old rounds skipped, not double-counted


# -------------------------------------------------- K=0 bitwise, end to end
@pytest.fixture
def dataset(tmp_path):
    generate_shards(
        str(tmp_path / "train"), 1, 600, num_fields=5, ids_per_field=30,
        seed=0,
    )
    generate_shards(
        str(tmp_path / "test"), 1, 200, num_fields=5, ids_per_field=30,
        seed=1, truth_seed=0,
    )
    return tmp_path


def _fit_cfg(tmp_path, **kw):
    base = {
        "data.train_path": str(tmp_path / "train"),
        "data.log2_slots": 12,
        "data.batch_size": 100,
        "data.max_nnz": 8,
        "model.num_fields": 5,
        "model.name": "lr",
        "optim.name": "sgd",
        "train.epochs": 1,
        "train.pred_dump": False,
    }
    base.update(kw)
    return override(Config(), **base)


def test_mode_off_and_single_slice_sync_are_bitwise_identical(
    dataset, tmp_path
):
    """The pre-PR semantics gate: sync.mode=off and a single-slice
    sync.mode=sync run (rounds every 2 steps + the final round) produce
    byte-identical final tables — the sync boundary is a no-op when no
    peer delta applies."""
    t_off = Trainer(_fit_cfg(dataset))
    t_off.fit()
    t_sync = Trainer(_fit_cfg(dataset, **{
        "sync.mode": "sync",
        "sync.dir": str(tmp_path / "sync_solo"),
        "sync.every_steps": 2,
    }))
    t_sync.fit()
    for name in t_off.state.tables:
        a = np.asarray(t_off.state.tables[name])
        b = np.asarray(t_sync.state.tables[name])
        assert a.tobytes() == b.tobytes(), f"table {name} diverged"


# ------------------------------------------------- parity sweep (K matrix)
@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 8, 64])
def test_parity_k_sweep(tmp_path, k):
    """2-slice AUC at K in {1, 8, 64} (bounded, proceed-on-stale) lands
    within the parity tolerance of the K=0 lockstep run — staleness
    trades synchrony for throughput, not model quality
    (docs/DISTRIBUTED.md sweep table)."""
    for s, seed in (("0", 0), ("1", 1)):
        generate_shards(
            str(tmp_path / f"tr_s{s}"), 1, 3200, num_fields=5,
            ids_per_field=30, seed=seed, truth_seed=0,
        )
    generate_shards(
        str(tmp_path / "te"), 1, 800, num_fields=5, ids_per_field=30,
        seed=9, truth_seed=0,
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"

    def launch(tag, *sync_sets):
        r = subprocess.run(
            [sys.executable, "-m", "xflow_tpu", "launch-multislice",
             "--slices", "2", "--run-dir", str(tmp_path / f"run_{tag}"),
             "--",
             "--train", str(tmp_path / "tr_s{slice}"),
             "--test", str(tmp_path / "te"),
             "--model", "lr", "--optimizer", "sgd",
             "--epochs", "1", "--batch-size", "64", "--log2-slots", "12",
             "--set", "model.num_fields=5", "--set", "data.max_nnz=8",
             "--set", "train.pred_dump=false",
             "--set", "sync.every_steps=10",
             "--set", f"sync.dir={tmp_path / f'run_{tag}' / 'sync'}",
             *sync_sets],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert r.returncode == 0, f"{tag}: {r.stdout}\n{r.stderr}"
        aucs = [json.loads(l)["auc"] for l in r.stdout.splitlines()
                if l.strip().startswith("{") and "auc" in l]
        assert len(aucs) == 2, f"{tag}: missing slice summaries"
        return aucs

    base = launch("k0", "--set", "sync.mode=sync")
    assert base[0] == base[1], "K=0 slices must merge to one model"
    aucs = launch(
        f"k{k}", "--set", "sync.mode=bounded",
        "--set", f"sync.staleness_k={k}", "--set", "sync.on_stale=proceed",
    )
    for auc in aucs:
        assert abs(auc - base[0]) <= 0.01, (
            f"K={k} auc {auc} vs lockstep {base[0]}"
        )
