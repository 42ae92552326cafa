"""Topology-elastic, integrity-verified checkpoints (docs/ROBUSTNESS.md
"Host lost" / "Silent shard corruption"; docs/DISTRIBUTED.md "Canonical
checkpoint layout").

Two properties are pinned here:

1. **Elastic restore**: a checkpoint written at one mesh/world shape
   restores into any other — the npz stores the canonical LOGICAL
   layout, every leaf lands on the live sharding, and the data_state's
   per-SHARD offsets re-assign the record set to the new world with
   exact coverage (no record trained twice, none dropped). The mesh
   matrix (1<->2<->4 devices, GSPMD / fullshard /
   single-device engines) runs in-process on the conftest's 8-CPU-device
   fake cluster; the true multi-PROCESS shrink drill is
   tools/smoke_topology.sh (probe-gated like every 2-proc drill).

2. **Integrity**: per-array digests written into meta.json at save are
   verified on restore; a digest mismatch is a logged walk-back to the
   previous committed step — drilled with the container-preserving
   payload bitflip (testing/faults.bitflip_npz_array) that every
   zip-level check survives, so ONLY the digest layer can catch it.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.data.pipeline import assign_shards, batch_iterator
from xflow_tpu.data.synth import generate_shards
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.testing.faults import bitflip_npz_array, corrupt_npz_checkpoint
from xflow_tpu.train.checkpoint import (
    CheckpointDigestError,
    array_digest,
    committed_steps,
    normalize_data_state,
    read_data_state,
    restore_any,
    verify_digest,
)
from xflow_tpu.train.trainer import Trainer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_cfg(tmp_path, **kw):
    base = {
        "data.train_path": str(tmp_path / "train"),
        "data.log2_slots": 12,
        "data.batch_size": 100,
        "data.max_nnz": 8,
        "model.num_fields": 5,
        "train.epochs": 1,
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


@pytest.fixture
def dataset2(tmp_path):
    """TWO shards — the record set of an (emulated) 2-rank run."""
    generate_shards(
        str(tmp_path / "train"), 2, 500, num_fields=5, ids_per_field=30, seed=0
    )
    return tmp_path


# ------------------------------------------------------- shard assignment
def test_assign_shards_legacy_and_elastic(tmp_path):
    p = str(tmp_path / "t")
    # fresh run (num_shards == world): rank k owns exactly shard k —
    # the legacy one-shard-per-rank contract, byte-identical paths
    assert assign_shards(p, 0, 1) == [(0, p + "-00000")]
    assert assign_shards(p, 1, 2) == [(1, p + "-00001")]
    # shrink 4 -> 1: the lone survivor covers the whole record set
    assert [i for i, _ in assign_shards(p, 0, 1, num_shards=4)] == [0, 1, 2, 3]
    # shrink 5 -> 2: round-robin, disjoint, complete
    r0 = [i for i, _ in assign_shards(p, 0, 2, num_shards=5)]
    r1 = [i for i, _ in assign_shards(p, 1, 2, num_shards=5)]
    assert r0 == [0, 2, 4] and r1 == [1, 3]
    # grow 2 -> 4: new ranks pick up their own (fresh) shard index
    assert assign_shards(p, 3, 4, num_shards=2) == [(3, p + "-00003")]


def test_normalize_data_state_versions():
    # v1 multi-process: per-rank examples fold to a global sum, the
    # coordinated offset fans out to every shard (lockstep invariant)
    v1 = {"version": 1, "epoch": 0, "batches": 7, "completed": False,
          "examples": 700, "examples_per_rank": [700, 650],
          "quarantined_rows": 0}
    got = normalize_data_state(v1)
    assert got["examples"] == 1350 and got["world_size"] == 2
    assert got["shard_batches"] == {0: 7, 1: 7} and got["num_shards"] == 2
    # v2 passes through with int-keyed offsets
    v2 = {"version": 2, "epoch": 1, "batches": 9, "completed": False,
          "examples": 2000, "shard_batches": {"0": 9, "2": 3},
          "num_shards": 3, "world_size": 3}
    got = normalize_data_state(v2)
    assert got["shard_batches"] == {0: 9, 2: 3} and got["num_shards"] == 3
    # malformed values raise (the caller downgrades to a fresh stream)
    with pytest.raises((TypeError, ValueError)):
        normalize_data_state({"epoch": "not-a-number"})


# ----------------------------------------------------------- integrity
def test_bitflip_npz_array_is_silent_to_the_container(tmp_path):
    """The drill primitive's contract: the rewritten npz passes every
    zip/numpy-level check (np.load succeeds, values differ) — only the
    digest layer can tell. A RAW flip on the same file trips the zip
    CRC instead (the loud mode restore_any always healed)."""
    p = str(tmp_path / "a.npz")
    a = np.arange(4096, dtype=np.float32)
    with open(p, "wb") as f:
        np.savez(f, x=a)
    before = array_digest(a)
    offs = bitflip_npz_array(p, count=8, seed=1)
    assert offs
    got = np.load(p)["x"]  # container-level read SUCCEEDS
    assert got.shape == a.shape and got.dtype == a.dtype
    assert array_digest(got) != before  # ... but the values changed
    with pytest.raises(CheckpointDigestError, match="digest mismatch"):
        verify_digest("x", got, {"x": before}, p)


def test_bitflipped_shard_walks_back_not_restores_garbage(dataset, tmp_path):
    """THE acceptance drill: a committed checkpoint bit-flipped through
    corrupt_ckpt's silent mode restores the PREVIOUS committed step
    with a logged digest mismatch — never the corrupted state."""
    ck = str(tmp_path / "ck")
    cfg = make_cfg(dataset, **{"train.epochs": 2,
                               "train.checkpoint_dir": ck,
                               "train.checkpoint_every": 5})
    t = Trainer(cfg)
    t.fit()
    good_w10 = None
    assert committed_steps(ck) == [12, 10, 5]
    good_w10 = np.load(os.path.join(ck, "step_10", "state.npz"))["tables/w"]
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "corrupt_ckpt.py"),
         "--dir", ck, "--mode", "bitflip", "--count", "16"],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["corrupted"].endswith("step_12/state.npz")
    t2 = Trainer(cfg)
    assert t2.maybe_restore()
    assert int(t2.state.step) == 10  # walked back past the flipped step
    np.testing.assert_array_equal(np.asarray(t2.state.tables["w"]), good_w10)
    # the stream position came from the step that ACTUALLY restored
    # (600 rows / 100 = 6 batches per epoch; step 10 = epoch 1, batch 4)
    assert t2._resume_data_state["batches"] == 4


def test_checkpoint_verify_off_disables_the_digest_gate(dataset, tmp_path):
    """Negative control: with train.checkpoint_verify=off the flipped
    newest step restores (values and all) — proving the digest layer,
    not some container check, is what catches the silent flip."""
    ck = str(tmp_path / "ck")
    cfg = make_cfg(dataset, **{"train.epochs": 2,
                               "train.checkpoint_dir": ck,
                               "train.checkpoint_every": 5})
    Trainer(cfg).fit()
    corrupt_npz_checkpoint(ck, mode="bitflip", count=16, seed=2)
    t2 = Trainer(override(cfg, **{"train.checkpoint_verify": "off"}))
    assert t2.maybe_restore()
    assert int(t2.state.step) == 12  # restored the corrupted newest step


def test_orbax_digest_verification_fires_end_to_end(dataset, tmp_path):
    """The orbax verify path: OCDBT's own b-tree CRC catches inline
    small-array flips (tested in test_fault_injection), but LARGE
    chunked payload reads are not checksum-verified — the meta
    sibling's digests are the net. Simulated here by recording a
    digest that does not match the (intact) stored bytes: restore must
    fail that step with CheckpointDigestError and walk back."""
    pytest.importorskip("orbax.checkpoint")
    ck = str(tmp_path / "ck")
    cfg = make_cfg(dataset, **{"train.epochs": 2,
                               "train.checkpoint_dir": ck,
                               "train.checkpoint_every": 5,
                               "train.checkpoint_format": "orbax"})
    Trainer(cfg).fit()
    meta_p = os.path.join(ck, "orbax_step_12.meta.json")
    meta = json.load(open(meta_p))
    assert meta["version"] == 3 and meta["digests"]
    meta["digests"]["tables/w"] = "crc32:deadbeef"
    json.dump(meta, open(meta_p, "w"))
    t2 = Trainer(cfg)
    assert t2.maybe_restore()
    assert int(t2.state.step) == 10


# ------------------------------------------------- mesh resharding matrix
def mesh_of(cfg, n):
    return make_mesh(cfg, np.array(jax.devices()[:n]))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 CPU devices")
def test_restore_reshards_gspmd_mesh_sizes(dataset, tmp_path):
    """LR on the GSPMD engine: save at a 2-device mesh, restore at 4
    devices and at a single device — identical logical tables."""
    cfg = make_cfg(dataset, **{"train.checkpoint_dir": str(tmp_path / "ck")})
    t = Trainer(cfg, mesh=mesh_of(cfg, 2))
    t.fit()
    w = np.asarray(jax.device_get(t.state.tables["w"]))
    for target in (4, 1, None):
        mesh = mesh_of(cfg, target) if target else None
        t2 = Trainer(cfg, mesh=mesh)
        assert t2.maybe_restore() and int(t2.state.step) == 6
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(t2.state.tables["w"])), w
        )
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(t2.state.opt_state["w"]["n"])),
            np.asarray(jax.device_get(t.state.opt_state["w"]["n"])),
        )


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 CPU devices")
def test_restore_reshards_across_sorted_engines(dataset, tmp_path):
    """Fused FM across the engines: a fullshard-engine checkpoint
    (2-device mesh) restores into the 4-device fullshard mesh, the
    2-device GSPMD row-major engine, and the single-device sorted step
    — the canonical logical npz layout makes the engine irrelevant."""
    base = {"train.checkpoint_dir": str(tmp_path / "ck"),
            "data.log2_slots": 14, "data.batch_size": 128,
            "model.name": "fm"}
    cfg = make_cfg(dataset, **base)
    t = Trainer(cfg, mesh=mesh_of(cfg, 2))
    assert t.engine == "fullshard"
    t.fit()
    wv = np.asarray(jax.device_get(t.state.tables["wv"]))
    step = int(t.state.step)

    # 4-device fullshard
    t4 = Trainer(cfg, mesh=mesh_of(cfg, 4))
    assert t4.engine == "fullshard"
    assert t4.maybe_restore() and int(t4.state.step) == step
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(t4.state.tables["wv"])), wv
    )
    # 2-device GSPMD row-major engine
    cfg_r = make_cfg(dataset, **{**base, "data.sorted_layout": "off"})
    tr = Trainer(cfg_r, mesh=mesh_of(cfg_r, 2))
    assert tr.engine == "gspmd"
    assert tr.maybe_restore() and int(tr.state.step) == step
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(tr.state.tables["wv"])), wv
    )
    # single-device sorted step
    t1 = Trainer(cfg)
    assert t1.maybe_restore() and int(t1.state.step) == step
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(t1.state.tables["wv"])), wv
    )


# --------------------------------------------- elastic data-stream resume
def record_consumed_labels(trainer, sink):
    """Wrap the trainer's batch stream to record every TRAINING batch's
    real (row-masked) labels — the record-set coverage probe."""
    orig = trainer._coordinated_batches

    def wrapped(path, *args, **kwargs):
        training = kwargs.get("enforce_bad_rows", True)
        for batch, arrays in orig(path, *args, **kwargs):
            if training:
                rm = np.asarray(batch.row_mask) > 0
                sink.append(np.asarray(batch.labels)[rm])
            yield batch, arrays

    trainer._coordinated_batches = wrapped


def test_shrunk_resume_covers_the_record_set_exactly(dataset2, tmp_path):
    """2 -> 1 data topology: a single rank resuming a 2-rank
    checkpoint's data_state (per-shard offsets {0: 2, 1: 2}) consumes
    EXACTLY each shard's untrained suffix — no record twice, none
    dropped — and the final checkpoint's global example accounting is
    exact: 400 restored + 600 consumed = 1000 = every row once."""
    ck = str(tmp_path / "ck")
    cfg = make_cfg(dataset2, **{"train.checkpoint_dir": ck})
    t = Trainer(cfg)
    # what a 2-rank gen-0 committed after 2 coordinated steps
    # (2 ranks x 2 batches x 100 rows = 400 examples)
    t._resume_data_state = {
        "version": 2, "epoch": 0, "batches": 2, "completed": False,
        "examples": 400, "examples_per_rank": [200, 200],
        "shard_batches": {"0": 2, "1": 2}, "num_shards": 2,
        "world_size": 2,
    }
    seen = []
    record_consumed_labels(t, seen)
    res = t.fit()
    # each 500-row shard holds 5 batches; offset 2 leaves 3 per shard
    assert res.steps == 6 and res.examples == 600
    expected = []
    for s in (0, 1):
        shard = str(dataset2 / "train") + "-%05d" % s
        for i, b in enumerate(batch_iterator(shard, cfg.data)):
            if i >= 2:
                rm = np.asarray(b.row_mask) > 0
                expected.append(np.asarray(b.labels)[rm])
    assert len(seen) == len(expected)
    for a, b in zip(seen, expected):
        np.testing.assert_array_equal(a, b)
    ds = read_data_state(ck, int(t.state.step))
    assert ds["completed"] and ds["examples"] == 1000
    assert ds["world_size"] == 1 and ds["num_shards"] == 2


def test_second_epoch_after_shrunk_resume_reads_all_shards(dataset2, tmp_path):
    """After the resumed epoch, later epochs read every owned shard
    from row 0 — the shrunk world keeps covering the whole record set,
    not just the resumed suffix."""
    cfg = make_cfg(dataset2, **{"train.epochs": 2})
    t = Trainer(cfg)
    t._resume_data_state = {
        "version": 2, "epoch": 0, "batches": 4, "completed": False,
        "examples": 800, "shard_batches": {"0": 4, "1": 4},
        "num_shards": 2, "world_size": 2,
    }
    res = t.fit()
    # epoch 0 remainder: (5-4)*2 shards = 2 steps; epoch 1: 10 steps
    assert res.steps == 12 and res.examples == 1200


# ------------------------------------------------ degraded-mode supervision
def test_dead_host_tracker_shrink_revive_floor():
    from xflow_tpu.launch.supervise import DeadHostTracker

    t = DeadHostTracker(allow_shrink=True)
    t.record("hostB")
    assert t.shrunk_world(3) == 2
    assert t.survivors(["a", "hostB", "c"]) == ["a", "c"]
    t.record("a")
    t.record("c")
    assert t.shrunk_world(3) == 1  # the last survivor keeps the run alive
    t.revive("a")  # the launch-dist probe found it reachable again
    assert t.survivors(["a", "hostB", "c"]) == ["a"]
    # off = same-shape supervision, untouched
    off = DeadHostTracker(allow_shrink=False)
    off.record("x")
    assert off.shrunk_world(3) == 3 and off.survivors(["x", "y"]) == ["x", "y"]


def test_launch_local_shrinks_after_dead_host_verdict(monkeypatch):
    """The wiring end to end (launcher level, fake attempts): gen 0's
    watchdog dead verdict shrinks gen 1 to the survivors — and only
    the FIRST verdict of the attempt counts (the culprit ordering puts
    the real loss first; its blocked SPMD peers are victims, not
    additional lost hosts)."""
    from xflow_tpu.launch import local as ll

    worlds = []

    def fake_once(n, args, on_dead_row=None, gen=0, **kw):
        worlds.append(n)
        if gen == 0:
            on_dead_row({"rank": 1, "status": "dead"})
            on_dead_row({"rank": 0, "status": "dead"})  # victim: ignored
            return 75  # EX_TEMPFAIL, the verdict-only failure code
        return 0

    monkeypatch.setattr(ll, "_launch_local_once", fake_once)
    rc = ll.launch_local(2, ["--train", "x"], max_restarts=2,
                         restart_backoff=0.0, allow_shrink=True)
    assert rc == 0 and worlds == [2, 1]
    # without --allow-shrink the relaunch stays same-shape
    worlds.clear()
    rc = ll.launch_local(2, ["--train", "x"], max_restarts=2,
                         restart_backoff=0.0)
    assert rc == 0 and worlds == [2, 2]


def test_orig_world_env_preserves_shard_coverage(dataset2, monkeypatch):
    """The shrink-before-first-checkpoint window: a relaunch that has
    no committed data_state cannot learn the shard set from a
    checkpoint — the supervisor's XFLOW_ORIG_WORLD export keeps the
    survivors covering every shard (here: a 1-rank world with original
    world 2 trains BOTH 500-row shards instead of silently dropping
    shard 1)."""
    monkeypatch.setenv("XFLOW_ORIG_WORLD", "2")
    res = Trainer(make_cfg(dataset2)).fit()
    assert res.steps == 10 and res.examples == 1000
    # control: without the env a fresh 1-rank run keeps the legacy
    # one-shard contract
    monkeypatch.delenv("XFLOW_ORIG_WORLD")
    res = Trainer(make_cfg(dataset2)).fit()
    assert res.steps == 5 and res.examples == 500


# ------------------------------------------------------------ world stamp
def test_world_stamp_in_every_jsonl_record(tmp_path, monkeypatch):
    from xflow_tpu.jsonl import JsonlAppender

    path = tmp_path / "m.jsonl"
    monkeypatch.setenv("XFLOW_NUM_PROCESSES", "3")
    ap = JsonlAppender(str(path), stamp={"rank": 0, "run_id": "r"})
    ap.append({"step": 1})
    ap.close()
    rec = json.loads(open(path).read())
    assert rec["world"] == 3


# --------------------------------------------------------- report tooling
def _rec(run_id, rank, gen, step, ts, world):
    return {"ts": ts, "rank": rank, "run_id": run_id, "gen": gen,
            "world": world, "step": step, "loss": 0.5,
            "examples": step * 10, "elapsed_s": float(step),
            "steps_per_s": 1.0, "rows_per_s": 10.0,
            "step_time_p50_ms": 1.0, "step_time_p99_ms": 2.0,
            "data_wait_ms": 0.1, "dispatch_ms": 0.1, "device_ms": 0.8}


def _load(tmp_path, name, recs):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import metrics_report

    path = tmp_path / name
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    streams, _ = metrics_report.load_streams([str(path)])
    return metrics_report, streams, [str(path)]


def test_check_accepts_world_shrink_across_generations(tmp_path):
    """A shrunk relaunch changes the rank set between generations of
    one run_id — that must pass --check; an INTRA-generation world
    disagreement (or a rank outside its world) must not."""
    recs = [_rec("r", 0, 0, 5, 1.0, 2), _rec("r", 1, 0, 5, 1.1, 2),
            _rec("r", 0, 1, 2, 2.0, 1)]  # gen 1: rank 1 shrunk away
    mr, streams, files = _load(tmp_path, "ok.jsonl", recs)
    assert mr.check_streams(streams, files) == []

    bad = [_rec("r", 0, 0, 5, 1.0, 2), _rec("r", 1, 0, 5, 1.1, 3)]
    mr, streams, files = _load(tmp_path, "bad.jsonl", bad)
    assert any("world stamp disagrees" in p for p in mr.check_streams(streams, files))

    oob = [_rec("r", 2, 0, 5, 1.0, 2)]  # rank 2 of a 2-world
    mr, streams, files = _load(tmp_path, "oob.jsonl", oob)
    assert any("world size" in p for p in mr.check_streams(streams, files))


def test_health_labels_shrunk_ranks_retired(tmp_path):
    """--health heartbeat table: a rank the supervisor shrank away
    (beats stop at gen 0, newest generation's world excludes it) reads
    ``retired@gen0``, not DEAD; a genuinely dead rank still reads
    dead."""
    def hb(rank, gen, step, ts, world, event=None):
        r = {"ts": ts, "rank": rank, "run_id": "r", "kind": "heartbeat",
             "gen": gen, "world": world, "step": step}
        if event:
            r["event"] = event
        return r

    recs = [
        hb(0, 0, 10, 100.0, 2), hb(1, 0, 10, 100.0, 2),
        hb(0, 1, 20, 500.0, 1), hb(0, 1, 20, 501.0, 1, event="final"),
    ]
    mr, streams, _ = _load(tmp_path, "heartbeat_rank0.jsonl", recs)
    rows = {r["rank"]: r["status"] for r in mr.heartbeat_rows(streams, "r")}
    assert rows[0] == "finished"
    assert rows[1] == "retired@gen0"
    # the full health render stays consumable and shows the label
    out = mr.render_health(streams)
    assert "retired@gen0" in out and "<-- RETIRED" not in out


# ----------------------------------------------------------- CI smoke gate
def test_smoke_topology_script(tmp_path):
    """The topology CI gate end to end (tools/smoke_topology.sh): the
    silent-corruption digest drill always runs; the 2-process
    kill-one-host shrink drill runs when this jax build supports
    multi-process CPU (the script probes, like every 2-proc drill)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "tools", "smoke_topology.sh"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=570, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "smoke_topology: OK" in r.stdout
    assert "digest drill OK" in r.stdout
    assert ("shrink drill OK" in r.stdout
            or "shrink drill skipped" in r.stdout)
    bench = json.load(open(tmp_path / "BENCH_r08.json"))
    assert bench["metric"] == "telemetry_examples_per_sec"
    assert bench["value"] > 0
