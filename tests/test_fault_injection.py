"""Fault-injection suite: every recovery path of the resilience
subsystem (docs/ROBUSTNESS.md) driven end-to-end through the shared
injector library (xflow_tpu/testing/faults.py).

Covers the failure matrix: NaN-poisoned batch (non-finite guard skip /
halt / consecutive-abort / off), truncated and bit-flipped npz + orbax
checkpoints (self-healing restore walk-back), malformed libffm shards
(bad-record quarantine + budget), a killed rank under launch-dist
(committed checkpoint survives and restores), plus the lifecycle
satellites (MetricsLogger close, prefetch worker exit, stale-dir
cleanup, retention).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.data.synth import generate_shards
from xflow_tpu.testing.faults import (
    bitflip_file,
    corrupt_npz_checkpoint,
    corrupt_orbax_checkpoint,
    poison_nan_batches,
    truncate_file,
    write_malformed_libffm,
)
from xflow_tpu.train.checkpoint import committed_steps, orbax_steps
from xflow_tpu.train.trainer import NonFiniteHalt, Trainer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_cfg(tmp_path, **kw):
    base = {
        "data.train_path": str(tmp_path / "train"),
        "data.log2_slots": 12,
        "data.batch_size": 100,
        "data.max_nnz": 8,
        "model.num_fields": 5,
        "train.epochs": 2,
        "train.log_every": 1,
        "train.pred_dump": False,
    }
    base.update(kw)
    return override(Config(), **base)


@pytest.fixture
def dataset(tmp_path):
    generate_shards(
        str(tmp_path / "train"), 1, 600, num_fields=5, ids_per_field=30, seed=0
    )
    return tmp_path


# ---------------------------------------------------------- non-finite guard
def test_nan_batch_skipped_run_completes(dataset, tmp_path):
    """Acceptance: a NaN-poisoned batch under nonfinite_guard=skip is
    discarded, counted in the metrics JSONL, and the run's final loss is
    finite."""
    mpath = tmp_path / "m" / "metrics.jsonl"
    cfg = make_cfg(dataset, **{"train.metrics_path": str(mpath)})
    t = Trainer(cfg)
    poison_nan_batches(t, steps=[4])
    res = t.fit()
    assert res.steps == 12 and res.bad_steps == 1
    assert np.isfinite(res.last_loss)
    # every table stayed finite — the poisoned update never landed
    for name, tab in t.state.tables.items():
        assert np.isfinite(np.asarray(tab)).all(), name
    recs = [json.loads(l) for l in open(mpath)]
    skipped = [r for r in recs if r.get("nonfinite_skipped")]
    assert len(skipped) == 1 and skipped[0]["step"] == 4
    # the logger parent dir was created lazily and the handle closed in
    # fit's finally (satellite: MetricsLogger lifecycle)
    assert t.metrics._f is None


def test_nan_batch_guard_off_poisons_state(dataset):
    """Negative control: with the guard off a single NaN batch poisons
    the tables — the reference behavior the guard exists to prevent."""
    cfg = make_cfg(dataset, **{"train.nonfinite_guard": "off"})
    t = Trainer(cfg)
    poison_nan_batches(t, steps=[4])
    res = t.fit()
    assert not np.isfinite(res.last_loss)


def test_nan_batch_halt_commits_then_raises(dataset, tmp_path):
    ck = tmp_path / "ck"
    cfg = make_cfg(
        dataset,
        **{"train.nonfinite_guard": "halt", "train.checkpoint_dir": str(ck)},
    )
    t = Trainer(cfg)
    poison_nan_batches(t, steps=[4])
    with pytest.raises(NonFiniteHalt, match="non-finite guard aborted"):
        t.fit()
    steps = committed_steps(str(ck))
    assert steps, "halt must commit a checkpoint before raising"
    # the committed state is the last GOOD one: finite everywhere
    t2 = Trainer(make_cfg(dataset, **{"train.checkpoint_dir": str(ck)}))
    assert t2.maybe_restore()
    for name, tab in t2.state.tables.items():
        assert np.isfinite(np.asarray(tab)).all(), name


def test_halt_on_final_step_still_writes_staged_log_record(dataset, tmp_path):
    """The XF110 one-behind log staging must not lose the halting
    step's record: a NaN on the run's LAST data step halts post-loop,
    and the staged metrics line (the run's most diagnostic one) is
    written before NonFiniteHalt raises."""
    mpath = tmp_path / "m" / "metrics.jsonl"
    cfg = make_cfg(
        dataset,
        **{"train.nonfinite_guard": "halt",
           "train.metrics_path": str(mpath)},
    )
    t = Trainer(cfg)
    poison_nan_batches(t, steps=[12])  # 600 rows / 100 x 2 epochs = final
    with pytest.raises(NonFiniteHalt):
        t.fit()
    recs = [json.loads(l) for l in open(mpath)]
    steps = [r for r in recs if "loss" in r and "step" in r]
    assert [r["step"] for r in steps][-1] == 12
    assert steps[-1]["loss"] is None  # discarded step: strict-JSON null
    assert any(r.get("nonfinite_halt") for r in recs)


def test_consecutive_bad_steps_abort_under_skip(dataset, tmp_path):
    ck = tmp_path / "ck"
    cfg = make_cfg(
        dataset,
        **{
            "train.nonfinite_max_consecutive": 3,
            "train.checkpoint_dir": str(ck),
            "train.epochs": 4,
        },
    )
    t = Trainer(cfg)
    poison_nan_batches(t, steps=range(5, 100))  # everything from step 5 on
    with pytest.raises(NonFiniteHalt, match="3 consecutive"):
        t.fit()
    assert committed_steps(str(ck))


def test_bad_guard_mode_rejected(dataset):
    with pytest.raises(ValueError, match="nonfinite_guard"):
        Trainer(make_cfg(dataset, **{"train.nonfinite_guard": "maybe"}))


def test_nan_batch_skipped_on_mesh(dataset):
    """The guard through the sharded engines: FM routes to the fullshard
    sorted engine on a 4x2 mesh (parallel/sorted_fullshard.py), LR to the
    GSPMD row-major step (parallel/train_step.py); the flag is replicated
    and the discard rank-symmetric."""
    from xflow_tpu.parallel.mesh import make_mesh

    for model in ("fm", "lr"):
        cfg = make_cfg(
            dataset,
            **{
                "model.name": model,
                "mesh.data": 4,
                "mesh.table": 2,
                # 2^14 slots: the fullshard engine needs num_slots
                # divisible by data*table*WINDOW = 8*2048
                "data.log2_slots": 14,
                "train.epochs": 1,
            },
        )
        mesh = make_mesh(cfg)
        t = Trainer(cfg, mesh=mesh)
        if model == "fm":
            assert t.engine == "fullshard"
        poison_nan_batches(t, steps=[2])
        res = t.fit()
        assert res.bad_steps == 1, model
        assert np.isfinite(res.last_loss), model
        for name, tab in t.state.tables.items():
            assert np.isfinite(np.asarray(tab)).all(), (model, name)


# ------------------------------------------------- checkpoint self-healing
def _fit_with_checkpoints(dataset, tmp_path, **extra):
    ck = tmp_path / "ck"
    cfg = make_cfg(
        dataset,
        **{"train.checkpoint_dir": str(ck), "train.checkpoint_every": 5, **extra},
    )
    t = Trainer(cfg)
    t.fit()
    return cfg, ck, t


def test_restore_walks_back_from_truncated_npz(dataset, tmp_path):
    """Acceptance: restore recovers from the previous committed step when
    the newest state.npz is truncated — driven through the operator CLI
    (tools/corrupt_ckpt.py) so the tool and the tests share one injector."""
    cfg, ck, t1 = _fit_with_checkpoints(dataset, tmp_path)
    steps = committed_steps(str(ck))
    assert steps == [12, 10, 5]
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "corrupt_ckpt.py"),
         "--dir", str(ck), "--mode", "truncate"],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["corrupted"].endswith("step_12/state.npz")
    t2 = Trainer(cfg)
    assert t2.maybe_restore()
    assert int(t2.state.step) == 10  # healed: newest skipped, previous loaded


def test_restore_walks_back_from_bitflipped_npz(dataset, tmp_path):
    cfg, ck, _ = _fit_with_checkpoints(dataset, tmp_path)
    corrupt_npz_checkpoint(str(ck), mode="bitflip", count=64, seed=3)
    t2 = Trainer(cfg)
    assert t2.maybe_restore()
    assert int(t2.state.step) in (5, 10)  # npz CRC catches the flip


def test_restore_all_corrupt_raises_with_reasons(dataset, tmp_path):
    cfg, ck, _ = _fit_with_checkpoints(dataset, tmp_path)
    for s in committed_steps(str(ck)):
        corrupt_npz_checkpoint(str(ck), step=s, mode="truncate", keep_frac=0.1)
    t2 = Trainer(cfg)
    with pytest.raises(RuntimeError, match="no loadable checkpoint"):
        t2.maybe_restore()


def test_orbax_restore_walks_back(dataset, tmp_path):
    pytest.importorskip("orbax.checkpoint")
    cfg, ck, _ = _fit_with_checkpoints(
        dataset, tmp_path, **{"train.checkpoint_format": "orbax"}
    )
    steps = orbax_steps(str(ck))
    assert steps[0] == 12 and len(steps) >= 2
    corrupt_orbax_checkpoint(str(ck), mode="truncate", keep_frac=0.05)
    t2 = Trainer(cfg)
    assert t2.maybe_restore()
    assert int(t2.state.step) < 12  # newest skipped


def test_save_cleans_stale_uncommitted_dir(dataset, tmp_path):
    """A crashed prior save leaves an uncommitted step_N dir; the next
    save of the same step must not mix generations of files in it."""
    ck = tmp_path / "ck"
    stale = ck / "step_12"
    os.makedirs(stale)
    with open(stale / "state.npz", "w") as f:
        f.write("debris from a crashed save")
    with open(stale / "leftover.tmp", "w") as f:
        f.write("junk")
    cfg = make_cfg(dataset, **{"train.checkpoint_dir": str(ck)})
    t = Trainer(cfg)
    t.fit()  # ends at step 12 — the same dir the stale debris occupies
    assert committed_steps(str(ck)) == [12]
    assert not (stale / "leftover.tmp").exists()
    t2 = Trainer(cfg)
    assert t2.maybe_restore() and int(t2.state.step) == 12


def test_keep_checkpoints_retention_and_sweep(dataset, tmp_path):
    ck = tmp_path / "ck"
    # plant stale uncommitted debris that the retention sweep must clear
    os.makedirs(ck / "step_3")
    with open(ck / "step_3" / "state.npz", "w") as f:
        f.write("partial")
    cfg = make_cfg(
        dataset,
        **{
            "train.checkpoint_dir": str(ck),
            "train.checkpoint_every": 5,
            "train.keep_checkpoints": 2,
        },
    )
    Trainer(cfg).fit()
    assert committed_steps(str(ck)) == [12, 10]  # step 5 pruned
    assert not (ck / "step_3").exists()  # stale dir swept
    assert not (ck / "step_5").exists()


# --------------------------------------------------- bad-record quarantine
def test_bad_rows_budget_raises(tmp_path):
    from xflow_tpu.data.pipeline import BadRecordError, batch_iterator

    shard = tmp_path / "junk-00000"
    info = write_malformed_libffm(str(shard), n_good=30, n_bad=6, seed=1)
    assert info["bad"] == 6
    cfg = make_cfg(tmp_path, **{"data.max_bad_rows": 3, "data.batch_size": 16}).data
    with pytest.raises(BadRecordError, match="max_bad_rows=3"):
        list(batch_iterator(str(shard), cfg))


def test_bad_rows_counted_and_quarantined(tmp_path):
    from xflow_tpu.data.pipeline import batch_iterator, count_batches

    shard = tmp_path / "junk-00000"
    info = write_malformed_libffm(
        str(shard), n_good=30, n_bad=6, seed=2, truncated_tail=True
    )
    qpath = tmp_path / "q" / "quarantine.jsonl"
    cfg = make_cfg(
        tmp_path,
        **{
            "data.max_bad_rows": 100,
            "data.quarantine_path": str(qpath),
            "data.batch_size": 16,
        },
    ).data
    batches = list(batch_iterator(str(shard), cfg))
    # bad rows are counted, NOT dropped: the batch count still matches
    # the row counters (the multi-process coordination contract)
    assert sum(int((np.asarray(b.row_mask) > 0).sum()) for b in batches) == info["rows"]
    assert len(batches) == count_batches(str(shard), cfg)
    recs = [json.loads(l) for l in open(qpath)]
    assert len(recs) == info["bad"]
    assert all(r["source"] == str(shard) for r in recs)


def test_trainer_survives_bad_rows_within_budget(tmp_path):
    """A shard with junk inside trains to completion when the budget
    allows — bad rows contribute a zero-feature example (logit 0), not a
    crash and not a poisoned table — and the quarantine file holds ONE
    record per bad row (first pass only), not one per epoch."""
    shard = tmp_path / "train-00000"
    info = write_malformed_libffm(str(shard), n_good=90, n_bad=5, seed=3)
    qpath = tmp_path / "quarantine.jsonl"
    cfg = make_cfg(
        tmp_path,
        **{
            "data.batch_size": 20,
            "data.max_bad_rows": 10,
            "data.quarantine_path": str(qpath),
            "train.epochs": 2,
            "data.log2_slots": 10,
            "model.num_fields": 6,
        },
    )
    res = Trainer(cfg).fit()
    assert res.steps > 0 and np.isfinite(res.last_loss)
    assert len(open(qpath).readlines()) == info["bad"]


def test_eval_never_enforces_bad_row_budget(tmp_path, monkeypatch):
    """The budget stops garbage from TRAINING in; a junk-heavy TEST
    shard must not kill the predict pass of a finished model."""
    monkeypatch.chdir(tmp_path)
    generate_shards(
        str(tmp_path / "train"), 1, 200, num_fields=5, ids_per_field=30, seed=0
    )
    write_malformed_libffm(
        str(tmp_path / "test-00000"), n_good=40, n_bad=8, seed=5
    )
    cfg = make_cfg(
        tmp_path,
        **{
            "data.test_path": str(tmp_path / "test"),
            "data.max_bad_rows": 3,  # below the test shard's 8 bad rows
            "train.epochs": 1,
        },
    )
    t = Trainer(cfg)
    t.fit()
    auc, ll = t.evaluate(dump=False)  # must complete, not BadRecordError
    assert np.isfinite(ll)


# ------------------------------------------------------ pipeline lifecycle
def _prefetch_threads():
    return {t for t in threading.enumerate()
            if t.name == "xflow-prefetch" and t.is_alive()}


def _stream(kind, make_items):
    """One pass over `make_items()` behind a producer thread: `plain` is
    `prefetch` (the thread ends with its pass), `carried` a pass of the
    `PassProducer` that fit() keeps between passes (the worker goes on
    to read ahead over the same items)."""
    from xflow_tpu.data.pipeline import PassProducer, PassSpec, prefetch

    if kind == "plain":
        return prefetch(iter(make_items()), depth=2)
    spec = PassSpec(shards=(), skips=())
    producer = PassProducer(lambda spec, defer: make_items(), depth=2)
    producer.start(spec, then=spec)
    return producer.batches()


@pytest.mark.parametrize("kind", ["plain", "carried"])
def test_prefetch_worker_exits_when_consumer_abandons(kind):
    started = threading.Event()
    before = _prefetch_threads()  # other tests' trainers may be uncollected yet

    def slow_infinite():
        i = 0
        while True:
            started.set()
            yield i
            i += 1

    it = _stream(kind, slow_infinite)
    assert next(it) == 0
    started.wait(timeout=10)
    it.close()  # the consumer walks away mid-epoch
    deadline = time.time() + 10
    while time.time() < deadline and _prefetch_threads() - before:
        time.sleep(0.05)
    assert not _prefetch_threads() - before, \
        "prefetch worker leaked after consumer close()"


@pytest.mark.parametrize("kind", ["plain", "carried"])
def test_prefetch_propagates_producer_error(kind):
    before = _prefetch_threads()

    def boom():
        yield 1
        raise OSError("disk on fire")

    it = _stream(kind, boom)
    assert next(it) == 1
    with pytest.raises(OSError, match="disk on fire"):
        next(it)
    # the failed pass took its worker with it, read-ahead or not
    assert not _prefetch_threads() - before


def test_metrics_logger_reopens_after_close(tmp_path):
    from xflow_tpu.train.trainer import MetricsLogger

    path = tmp_path / "sub" / "dir" / "m.jsonl"
    ml = MetricsLogger(str(path))
    ml.log({"a": 1})
    ml.close()
    ml.log({"b": 2})  # reopens in append mode
    ml.close()
    recs = [json.loads(l) for l in open(path)]
    assert len(recs) == 2 and recs[0]["a"] == 1 and recs[1]["b"] == 2
    # both appends carry the provenance stamp (PR 2: joinable streams)
    assert all("ts" in r and "rank" in r and "run_id" in r for r in recs)


# ------------------------------------------------------------- killed rank
def _rank_pids(marker: bytes, rank: int):
    """Pids whose environment carries `marker` AND XFLOW_PROCESS_ID=rank."""
    want = f"XFLOW_PROCESS_ID={rank}".encode() + b"\0"
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = f.read()
            if marker in env and want in env:
                out.append(int(pid))
        except OSError:
            continue
    return out


def test_killed_rank_committed_checkpoint_recovers(tmp_path):
    """SIGKILL one rank of a 2-'host' launch-dist run mid-training: the
    run dies, but the checkpoints committed before the kill survive (the
    commit-marker + atomic-write protocol) and restore into a fresh
    trainer — preemption-by-force-kill loses at most checkpoint_every
    steps, never the run (mirrors test_launch_dist.py's harness)."""
    from tests.test_launch_dist import _clean_env, _fake_ssh, _free_port
    from tests.test_launch_local import require_multiproc_cpu

    require_multiproc_cpu()
    generate_shards(str(tmp_path / "train"), 2, 4000, num_fields=4, ids_per_field=50)
    hosts = tmp_path / "hosts"
    hosts.write_text("127.0.0.1\n127.0.0.1\n")
    marker = f"XFLOW_FAULTKILL_{os.getpid()}"
    p = subprocess.Popen(
        [sys.executable, "-m", "xflow_tpu", "launch-dist",
         "--hosts", str(hosts), "--port", str(_free_port()),
         "--ssh-cmd", _fake_ssh(tmp_path),
         "--workdir", str(tmp_path / "rank{rank}"),
         "--python", sys.executable,
         "--env", "JAX_PLATFORMS=cpu",
         "--env", "PYTHONPATH=" + REPO_ROOT,
         "--env", marker + "=1",
         "--", "--train", str(tmp_path / "train"),
         "--batch-size", "20", "--model", "lr", "--epochs", "100000",
         "--log2-slots", "10", "--checkpoint-dir", "ckpt",
         "--set", "model.num_fields=4", "--set", "data.max_nnz=8",
         "--set", "train.pred_dump=false", "--set", "train.checkpoint_every=10"],
        env=_clean_env(), stdout=subprocess.DEVNULL,
        stderr=open(tmp_path / "launcher.err", "w"),
    )
    ck = tmp_path / "rank0" / "ckpt"
    try:
        deadline = time.time() + 300  # tight: typical commit lands in ~30 s
        committed = []
        while time.time() < deadline:
            committed = committed_steps(str(ck))
            if committed:
                break
            if p.poll() is not None:
                err = open(tmp_path / "launcher.err").read()
                if "Multiprocess computations aren't implemented" in err:
                    # this jax build cannot run multi-process CPU at all
                    # (every two-process test fails the same way); the
                    # killed-rank drill needs a capable runtime
                    pytest.skip("multi-process CPU unsupported by this jax build")
                assert False, f"launcher died before a checkpoint landed:\n{err[-2000:]}"
            time.sleep(0.3)
        assert committed, "no committed checkpoint within the deadline"
        victims = _rank_pids(marker.encode(), rank=1)
        assert victims, "rank 1 process not found"
        for pid in victims:
            os.kill(pid, signal.SIGKILL)  # the simulated hardware loss
        # no graceful teardown from here: kill the launcher too (its
        # die-with-connection watcher reaps the surviving rank)
        os.kill(p.pid, signal.SIGKILL)
        p.wait()
        # recovery: what was committed before the kill restores cleanly
        steps_after = committed_steps(str(ck))
        assert steps_after and steps_after[0] >= committed[0]
        cfg = override(Config(), **{
            "data.log2_slots": 10, "data.batch_size": 20, "data.max_nnz": 8,
            "model.num_fields": 4, "train.checkpoint_dir": str(ck),
        })
        t = Trainer(cfg)
        assert t.maybe_restore()
        assert int(t.state.step) == steps_after[0]
        for name, tab in t.state.tables.items():
            assert np.isfinite(np.asarray(tab)).all(), name
    finally:
        for pid in {p.pid, *_rank_pids(marker.encode(), 0), *_rank_pids(marker.encode(), 1)}:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
