"""Multi-process cluster-emulation tests (the `scripts/local.sh` analog).

These run the REAL multi-process path — `xflow launch-local` forks N
`xflow train` processes that rendezvous through
`jax.distributed.initialize` on CPU, form a 2-process world, shard the
tables over the global mesh, and read per-rank input shards
(reference convention `lr_worker.cc:210`: rank k reads `<prefix>-%05d`).

Round-1 verdict: this path was silently broken (children inherited the
ambient accelerator platform, never formed a world, and each trained
shard 0 as its own rank 0) and had zero test coverage. These tests gate:
  - the world actually forms (the launcher now fails loudly otherwise),
  - exactly one rank-0 summary is printed,
  - final tables equal a single-process run on the batch-composed data,
  - ragged / missing shards are tolerated (reference parity: its async
    workers never synchronize, so ragged shards "just work" there).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from xflow_tpu.data.synth import generate_shards

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(args, cwd, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # children get ONE cpu device each (the conftest exports an 8-device
    # XLA_FLAGS for the in-process fake cluster; strip it here)
    env.pop("XFLOW_NUM_CPU_DEVICES", None)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-m", "xflow_tpu", *args],
        capture_output=True,
        text=True,
        cwd=cwd,
        env=env,
        timeout=600,
    )


_MULTIPROC_CPU = None

_PROBE = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(sys.argv[1], 2, int(sys.argv[2]))
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(np.array(jax.devices()), ("d",))
x = jax.device_put(np.zeros(4, np.float32), NamedSharding(mesh, P()))
jax.block_until_ready(x)
print("PROBE_OK")
"""


def _run_probe_once():
    """One 2-process probe run. Returns (ok, combined_output)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XFLOW_NUM_CPU_DEVICES", None)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _PROBE, f"127.0.0.1:{port}", str(r)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for r in range(2)
    ]
    ok, outs = True, []
    for pr in procs:
        try:
            out, _ = pr.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            pr.kill()
            out = pr.communicate()[0] or ""
        outs.append(out or "")
        ok = ok and pr.returncode == 0 and "PROBE_OK" in (out or "")
    return ok, "\n".join(outs)


def multiproc_cpu_supported() -> bool:
    """Can this jax build run a 2-process CPU world at all? Some jaxlib
    versions reject multi-process computations on the CPU backend
    ("Multiprocess computations aren't implemented..."), which dooms
    every two-process test here to a slow failure; one cached ~15 s
    probe (a cross-process replicated device_put, the exact op that
    trips first) converts them into immediate skips instead.

    Only the KNOWN incapability message caches False on the first try —
    a transient failure (port stolen between bind and rendezvous, CI
    load) gets one retry, so a capable build cannot be silently skipped
    wholesale by one flake."""
    global _MULTIPROC_CPU
    if _MULTIPROC_CPU is None:
        ok, out = _run_probe_once()
        if not ok and "aren't implemented" not in out:
            ok, out = _run_probe_once()  # transient-looking: retry once
        _MULTIPROC_CPU = ok
    return _MULTIPROC_CPU


def require_multiproc_cpu():
    if not multiproc_cpu_supported():
        pytest.skip("multi-process CPU computations unsupported by this jax build")


def _interleave_shards(paths, block_rows, out_path):
    """Compose the single-process analog of the 2-process global batch
    stream: step i's global batch is [rank0 rows | rank1 rows], so the
    combined file interleaves block_rows-row blocks from each shard."""
    shard_lines = [open(p).read().splitlines() for p in paths]
    n_blocks = max(len(ls) for ls in shard_lines) // block_rows
    out = []
    for b in range(n_blocks):
        for lines in shard_lines:
            out.extend(lines[b * block_rows : (b + 1) * block_rows])
    with open(out_path, "w") as f:
        f.write("\n".join(out) + "\n")


TRAIN_ARGS = [
    "--model", "lr", "--epochs", "2", "--log2-slots", "10",
    "--set", "model.num_fields=4", "--set", "data.max_nnz=8",
    "--set", "train.pred_dump=false",
]


def test_launch_local_two_process_matches_single_process(tmp_path):
    require_multiproc_cpu()
    B, rows = 32, 96  # 3 batches per rank per epoch, no remainder
    generate_shards(str(tmp_path / "train"), 2, rows, num_fields=4, ids_per_field=50)
    generate_shards(
        str(tmp_path / "test"), 2, B, num_fields=4, ids_per_field=50, seed=7, truth_seed=0
    )

    r2 = run_cli(
        ["launch-local", "--num-processes", "2",
         "--run-dir", str(tmp_path / "run2p"), "--",
         "--train", str(tmp_path / "train"), "--test", str(tmp_path / "test"),
         "--batch-size", str(B), "--checkpoint-dir", str(tmp_path / "ckpt2p"),
         # pin EXACT eval: this is the bit-match gate, and the multi-
         # process default (eval_buckets auto) is bucketed — its AUC
         # differs by bucket quantization, not a training divergence
         "--set", "train.eval_buckets=0",
         *TRAIN_ARGS],
        tmp_path,
    )
    assert r2.returncode == 0, r2.stderr
    # --run-dir collected one stamped telemetry stream per rank,
    # joinable on a single shared run_id
    telem = {}
    for rank in (0, 1):
        recs = [
            json.loads(l)
            for l in open(tmp_path / "run2p" / f"metrics_rank{rank}.jsonl")
        ]
        assert recs and all(r["rank"] == rank for r in recs)
        telem[rank] = recs
    assert {r["run_id"] for rs in telem.values() for r in rs} == {
        telem[0][0]["run_id"]
    }
    # exactly one summary line: rank 0's (the round-1 bug printed two)
    summaries = [json.loads(l) for l in r2.stdout.strip().splitlines() if l.startswith("{")]
    assert len(summaries) == 1, r2.stdout
    s2 = summaries[0]
    assert s2["rank"] == 0
    assert s2["steps"] == 2 * (rows // B)  # global steps, not per-rank sums
    assert s2["examples"] == 2 * rows  # rank 0's local rows over 2 epochs

    # single-process run on the batch-composed data
    _interleave_shards(
        [tmp_path / "train-00000", tmp_path / "train-00001"], B, tmp_path / "comb-00000"
    )
    _interleave_shards(
        [tmp_path / "test-00000", tmp_path / "test-00001"], B, tmp_path / "combtest-00000"
    )
    r1 = run_cli(
        ["train", "--train", str(tmp_path / "comb"), "--test", str(tmp_path / "combtest"),
         "--batch-size", str(2 * B), "--checkpoint-dir", str(tmp_path / "ckpt1p"),
         "--no-mesh", *TRAIN_ARGS],
        tmp_path,
    )
    assert r1.returncode == 0, r1.stderr
    s1 = json.loads(r1.stdout.strip().splitlines()[-1])

    d2 = np.load(tmp_path / "ckpt2p" / f"step_{s2['steps']}" / "state.npz")
    d1 = np.load(tmp_path / "ckpt1p" / f"step_{s1['steps']}" / "state.npz")
    assert s1["steps"] == s2["steps"]
    np.testing.assert_allclose(
        d2["tables/w"], d1["tables/w"], rtol=0, atol=1e-6,
        err_msg="2-process sharded tables != single-process tables on composed data",
    )
    np.testing.assert_allclose(d2["opt/w/n"], d1["opt/w/n"], rtol=0, atol=1e-6)
    assert abs(s2["auc"] - s1["auc"]) < 1e-5, (s2["auc"], s1["auc"])


def test_launch_local_ragged_and_missing_shards(tmp_path):
    require_multiproc_cpu()
    # rank 0 has 3 batches, rank 1 only 1: exhausted ranks pad with empty
    # batches until everyone is done (trainer._coordinated_batches)
    B = 32
    generate_shards(str(tmp_path / "train"), 1, 3 * B, num_fields=4, ids_per_field=50)
    generate_shards(str(tmp_path / "short"), 1, B, num_fields=4, ids_per_field=50, seed=3)
    os.rename(tmp_path / "short-00000", tmp_path / "train-00001")
    r = run_cli(
        ["launch-local", "--num-processes", "2", "--",
         "--train", str(tmp_path / "train"), "--batch-size", str(B),
         "--epochs", "1", "--model", "lr", "--log2-slots", "10",
         "--set", "model.num_fields=4", "--set", "data.max_nnz=8"],
        tmp_path,
    )
    assert r.returncode == 0, r.stderr
    s = json.loads(r.stdout.strip().splitlines()[-1])
    assert s["steps"] == 3  # rank 0's 3 batches drive the epoch

    # missing shard entirely: rank 1 finds no train-00001 → empty contribution
    os.remove(tmp_path / "train-00001")
    r = run_cli(
        ["launch-local", "--num-processes", "2", "--",
         "--train", str(tmp_path / "train"), "--batch-size", str(B),
         "--epochs", "1", "--model", "lr", "--log2-slots", "10",
         "--set", "model.num_fields=4", "--set", "data.max_nnz=8"],
        tmp_path,
    )
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout.strip().splitlines()[-1])["steps"] == 3


def test_launch_local_supervised_auto_restart(tmp_path):
    """Elastic-recovery drill (PR 4 acceptance): SIGKILL rank 1 mid-run
    (the env-gated kill injector, testing/faults.py) under
    --max-restarts — the launcher must tear the job down, auto-restart
    it WITHOUT operator action, restore the last committed checkpoint,
    resume the data stream at the stored offset, and finish with the
    exact total example count (the kill lands on a checkpoint boundary,
    so no step is retrained: every row trains exactly once across the
    two generations). metrics_report --check must accept the resulting
    multi-generation stream."""
    require_multiproc_cpu()
    B, rows = 32, 96  # 3 batches/rank/epoch x 2 epochs = 6 global steps
    generate_shards(str(tmp_path / "train"), 2, rows, num_fields=4, ids_per_field=50)
    run_dir = tmp_path / "run"
    r = run_cli(
        ["launch-local", "--num-processes", "2",
         "--max-restarts", "1", "--restart-backoff", "0.2",
         "--run-dir", str(run_dir), "--",
         "--train", str(tmp_path / "train"), "--batch-size", str(B),
         "--checkpoint-dir", str(tmp_path / "ckpt"),
         "--set", "train.checkpoint_every=2",
         "--set", "train.heartbeat_every=1",
         "--set", "train.log_every=1",
         *TRAIN_ARGS],
        tmp_path,
        # kill rank 1 the moment step 4 completes — right after its
        # checkpoint committed (generation-gated: the relaunch survives)
        extra_env={"XFLOW_FAULT_KILL_STEP": "4", "XFLOW_FAULT_KILL_RANK": "1"},
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "hard-killing rank 1 at step 4" in r.stderr
    assert "restarting generation 1" in r.stderr
    assert "resumed from step 4" in r.stderr
    assert "resuming data stream at epoch 1, shard offsets [1, 1]" in r.stderr
    assert "job succeeded after 1 restart(s)" in r.stderr

    # generation 1's rank-0 summary: exactly the un-trained suffix
    summaries = [json.loads(l) for l in r.stdout.strip().splitlines()
                 if l.startswith("{")]
    assert summaries and summaries[-1]["steps"] == 2  # steps 5, 6

    # the final checkpoint is the full run, and its data_state accounts
    # for every row exactly once on BOTH ranks (no replay, no loss)
    from xflow_tpu.train.checkpoint import latest_step, read_data_state

    ck = str(tmp_path / "ckpt")
    assert latest_step(ck) == 6
    ds = read_data_state(ck, 6)
    # GLOBAL accounting (v2 data_state): 2 shards x 96 rows x 2 epochs,
    # every row exactly once; per-rank counts are this GENERATION's
    # local consumption (steps 5-6 = 2 batches x 32 rows each)
    assert ds["completed"] and ds["examples"] == 4 * rows
    assert ds["examples_per_rank"] == [2 * B, 2 * B]
    assert ds["world_size"] == 2 and ds["num_shards"] == 2

    # both generations landed in the run dir under ONE run_id, and the
    # schema gate accepts the multi-generation stream
    recs = [json.loads(l) for l in open(run_dir / "metrics_rank0.jsonl")]
    assert {r_["gen"] for r_ in recs} == {0, 1}
    assert len({r_["run_id"] for r_ in recs}) == 1
    chk = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "metrics_report.py"),
         str(run_dir), "--check"],
        capture_output=True, text=True, timeout=300,
    )
    assert chk.returncode == 0, chk.stderr


def test_launch_local_two_process_sorted_engine(tmp_path):
    """The multi-process sorted engine: 2 processes × 1 device, mesh
    (data=2, table=1), fused FM with sorted_layout=on — final tables
    match a single-process sorted run on the batch-composed data
    (fullshard: table sharded over the whole mesh, the occurrence
    all_to_all crossing the process boundary)."""
    require_multiproc_cpu()
    B, rows = 32, 96
    fm_args = [
        "--model", "fm", "--epochs", "2", "--log2-slots", "13",
        "--set", "model.num_fields=4", "--set", "data.max_nnz=8",
        "--set", "train.pred_dump=false", "--set", "data.sorted_layout=on",
        # exact eval on both sides: this is an equality gate, and the
        # multi-process default (bucketed) differs by tie quantization
        # on a 64-row test set
        "--set", "train.eval_buckets=0",
    ]
    generate_shards(str(tmp_path / "train"), 2, rows, num_fields=4, ids_per_field=50)
    generate_shards(str(tmp_path / "test"), 2, B, num_fields=4, ids_per_field=50,
                    seed=7, truth_seed=0)
    r2 = run_cli(
        ["launch-local", "--num-processes", "2", "--",
         "--train", str(tmp_path / "train"), "--test", str(tmp_path / "test"),
         "--batch-size", str(B),
         "--checkpoint-dir", str(tmp_path / "ckpt2p"), *fm_args],
        tmp_path,
    )
    assert r2.returncode == 0, r2.stderr
    s2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert s2["steps"] == 2 * (rows // B)

    _interleave_shards(
        [tmp_path / "train-00000", tmp_path / "train-00001"], B, tmp_path / "comb-00000"
    )
    _interleave_shards(
        [tmp_path / "test-00000", tmp_path / "test-00001"], B, tmp_path / "combtest-00000"
    )
    r1 = run_cli(
        ["train", "--train", str(tmp_path / "comb"), "--test", str(tmp_path / "combtest"),
         "--batch-size", str(2 * B),
         "--checkpoint-dir", str(tmp_path / "ckpt1p"), "--no-mesh", *fm_args],
        tmp_path,
    )
    assert r1.returncode == 0, r1.stderr
    s1 = json.loads(r1.stdout.strip().splitlines()[-1])
    assert s1["steps"] == s2["steps"]
    # the fullshard engine's multi-process eval consumes the host plan
    # (sorted-plan eval, round-3 item 7) and must match the
    # single-process eval on the composed test set
    assert abs(s2["auc"] - s1["auc"]) < 1e-5, (s2["auc"], s1["auc"])

    d2 = np.load(tmp_path / "ckpt2p" / f"step_{s2['steps']}" / "state.npz")
    d1 = np.load(tmp_path / "ckpt1p" / f"step_{s1['steps']}" / "state.npz")
    np.testing.assert_allclose(
        d2["tables/wv"], d1["tables/wv"], rtol=1e-5, atol=1e-6,
        err_msg="2-process fullshard tables != single-process sorted tables",
    )
    np.testing.assert_allclose(d2["opt/wv/n"], d1["opt/wv/n"], rtol=1e-5, atol=1e-6)


def test_launch_local_two_process_fullshard_ffm(tmp_path):
    """Multi-process FFM on the fullshard engine (the widest-row model:
    the segment-mode a2a ships [1+nf*k]-channel buffers across the
    process boundary): final tables match a single-process run on the
    batch-composed data."""
    require_multiproc_cpu()
    B, rows = 32, 96
    ffm_args = [
        "--model", "ffm", "--epochs", "2", "--log2-slots", "13",
        "--set", "model.num_fields=4", "--set", "model.v_dim=3",
        "--set", "data.max_nnz=8",
        "--set", "train.pred_dump=false", "--set", "data.sorted_layout=on",
    ]
    generate_shards(str(tmp_path / "train"), 2, rows, num_fields=4, ids_per_field=50)
    r2 = run_cli(
        ["launch-local", "--num-processes", "2", "--",
         "--train", str(tmp_path / "train"), "--batch-size", str(B),
         "--checkpoint-dir", str(tmp_path / "ckpt2p"), *ffm_args],
        tmp_path,
    )
    assert r2.returncode == 0, (r2.stdout, r2.stderr)
    s2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert s2["steps"] == 2 * (rows // B)

    _interleave_shards(
        [tmp_path / "train-00000", tmp_path / "train-00001"], B, tmp_path / "comb-00000"
    )
    r1 = run_cli(
        ["train", "--train", str(tmp_path / "comb"), "--batch-size", str(2 * B),
         "--checkpoint-dir", str(tmp_path / "ckpt1p"), "--no-mesh", *ffm_args],
        tmp_path,
    )
    assert r1.returncode == 0, r1.stderr
    s1 = json.loads(r1.stdout.strip().splitlines()[-1])
    assert s1["steps"] == s2["steps"]
    d2 = np.load(tmp_path / "ckpt2p" / f"step_{s2['steps']}" / "state.npz")
    d1 = np.load(tmp_path / "ckpt1p" / f"step_{s1['steps']}" / "state.npz")
    np.testing.assert_allclose(
        d2["tables/wv"], d1["tables/wv"], rtol=1e-4, atol=1e-6,
        err_msg="2-process fullshard ffm != single-process on composed data",
    )


def test_launch_local_two_process_mvm_auto_dup_coordination(tmp_path):
    """ADVICE r3: multi-process MVM `mvm_exclusive=auto` must not raise
    (or desync) on duplicate fields. Only rank 0's FIRST batch has a
    row with a repeated field; the per-batch flag allgather must route
    that batch to the segment mode on BOTH ranks (rank 1's rows are
    clean) and the next batch back to the product mode — matching the
    single-process auto run on the batch-composed data, which sees the
    same duplicate pattern per global batch."""
    require_multiproc_cpu()
    B, rows = 32, 64
    rng = np.random.default_rng(9)

    def clean_row(label):
        feats = " ".join(f"{fg}:{rng.integers(0, 50)}:1.0" for fg in range(4))
        return f"{label}\t{feats}"

    with open(tmp_path / "train-00000", "w") as f:
        for i in range(rows):
            if i < B:  # first batch: field 2 repeated -> duplicate
                feats = " ".join(
                    [f"2:{rng.integers(0, 50)}:1.0", f"2:{rng.integers(0, 50)}:1.0"]
                    + [f"{fg}:{rng.integers(0, 50)}:1.0" for fg in (0, 1, 3)]
                )
                f.write(f"{i % 2}\t{feats}\n")
            else:
                f.write(clean_row(i % 2) + "\n")
    with open(tmp_path / "train-00001", "w") as f:
        for i in range(rows):
            f.write(clean_row((i + 1) % 2) + "\n")

    mvm_args = [
        "--model", "mvm", "--epochs", "1", "--log2-slots", "13",
        "--set", "model.num_fields=4", "--set", "data.max_nnz=8",
        "--set", "train.pred_dump=false", "--set", "data.sorted_layout=on",
        "--set", "model.mvm_exclusive=auto",
    ]
    r2 = run_cli(
        ["launch-local", "--num-processes", "2", "--",
         "--train", str(tmp_path / "train"), "--batch-size", str(B),
         "--checkpoint-dir", str(tmp_path / "ckpt2p"), *mvm_args],
        tmp_path,
    )
    assert r2.returncode == 0, (r2.stdout, r2.stderr)
    s2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert s2["steps"] == rows // B

    _interleave_shards(
        [tmp_path / "train-00000", tmp_path / "train-00001"], B, tmp_path / "comb-00000"
    )
    r1 = run_cli(
        ["train", "--train", str(tmp_path / "comb"), "--batch-size", str(2 * B),
         "--checkpoint-dir", str(tmp_path / "ckpt1p"), "--no-mesh", *mvm_args],
        tmp_path,
    )
    assert r1.returncode == 0, r1.stderr
    s1 = json.loads(r1.stdout.strip().splitlines()[-1])
    assert s1["steps"] == s2["steps"]
    d2 = np.load(tmp_path / "ckpt2p" / f"step_{s2['steps']}" / "state.npz")
    d1 = np.load(tmp_path / "ckpt1p" / f"step_{s1['steps']}" / "state.npz")
    np.testing.assert_allclose(
        d2["tables/v"], d1["tables/v"], rtol=1e-4, atol=1e-6,
        err_msg="2-process mvm auto dup-coordination != single-process",
    )


def test_launch_local_two_process_fullshard_hot_key_fallback(tmp_path):
    """Round-3 weak #1 gate: a hot feature skewed beyond the fullshard
    buffer capacity must NOT kill a multi-process run. Rank 0's shard
    carries a 100%-frequency feature (6 of 8 occurrences per row — its
    owner block gets ~75% of the shard's occurrences, far over slack
    1.25); rank 1's shard is uniform, so ONLY rank 0 overflows — the
    asymmetric case where rank 1 must drop its own (successful) plan via
    the per-batch flag allgather and join rank 0 on the GSPMD row-major
    step. Gate: trains through, warns, and bit-matches the
    single-process run on the batch-composed data. Reference behavior
    matched: ps-lite serves hot keys slowly but never dies
    (`/root/reference/src/optimizer/ftrl.h:54-79`)."""
    require_multiproc_cpu()
    B, rows = 1024, 2048
    rng = np.random.default_rng(5)
    hot = " ".join(["0:0:1.0"] * 6)
    with open(tmp_path / "train-00000", "w") as f:
        for i in range(rows):
            feats = " ".join(
                f"{fg}:{rng.integers(0, 50)}:1.0" for fg in (1, 2)
            )
            f.write(f"{i % 2}\t{hot} {feats}\n")
    with open(tmp_path / "train-00001", "w") as f:
        for i in range(rows):
            feats = " ".join(
                f"{fg}:{rng.integers(0, 50)}:1.0" for fg in range(1, 4) for _ in range(2)
            )
            f.write(f"{(i + 1) % 2}\t{feats}\n")
    fm_args = [
        "--model", "fm", "--epochs", "1", "--log2-slots", "13",
        "--set", "model.num_fields=4", "--set", "data.max_nnz=8",
        "--set", "train.pred_dump=false", "--set", "data.sorted_layout=on",
        "--set", "data.fullshard_slack=1.25",
    ]
    r2 = run_cli(
        ["launch-local", "--num-processes", "2", "--",
         "--train", str(tmp_path / "train"), "--batch-size", str(B),
         "--checkpoint-dir", str(tmp_path / "ckpt2p"), *fm_args],
        tmp_path,
    )
    assert r2.returncode == 0, r2.stderr
    assert "falling back to the GSPMD row-major step" in r2.stderr
    s2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert s2["steps"] == rows // B

    _interleave_shards(
        [tmp_path / "train-00000", tmp_path / "train-00001"], B, tmp_path / "comb-00000"
    )
    r1 = run_cli(
        ["train", "--train", str(tmp_path / "comb"), "--batch-size", str(2 * B),
         "--checkpoint-dir", str(tmp_path / "ckpt1p"), "--no-mesh", *fm_args],
        tmp_path,
    )
    assert r1.returncode == 0, r1.stderr
    s1 = json.loads(r1.stdout.strip().splitlines()[-1])
    assert s1["steps"] == s2["steps"]
    d2 = np.load(tmp_path / "ckpt2p" / f"step_{s2['steps']}" / "state.npz")
    d1 = np.load(tmp_path / "ckpt1p" / f"step_{s1['steps']}" / "state.npz")
    np.testing.assert_allclose(
        d2["tables/wv"], d1["tables/wv"], rtol=1e-4, atol=1e-6,
        err_msg="2-process hot-key fallback != single-process on composed data",
    )
    np.testing.assert_allclose(d2["opt/wv/n"], d1["opt/wv/n"], rtol=1e-4, atol=1e-6)


def test_launch_local_two_process_fullshard_mvm_product(tmp_path):
    """Multi-process MVM on the fullshard engine's exclusive-fields
    PRODUCT path (no fs_fields; synth data is one-feature-per-field, so
    multi-process auto routing takes the product mode on every rank):
    final tables match a single-process run on the batch-composed data."""
    require_multiproc_cpu()
    B, rows = 32, 96
    mvm_args = [
        "--model", "mvm", "--epochs", "2", "--log2-slots", "13",
        "--set", "model.num_fields=4", "--set", "data.max_nnz=8",
        "--set", "train.pred_dump=false", "--set", "data.sorted_layout=on",
    ]
    generate_shards(str(tmp_path / "train"), 2, rows, num_fields=4, ids_per_field=50)
    r2 = run_cli(
        ["launch-local", "--num-processes", "2", "--",
         "--train", str(tmp_path / "train"), "--batch-size", str(B),
         "--checkpoint-dir", str(tmp_path / "ckpt2p"), *mvm_args],
        tmp_path,
    )
    assert r2.returncode == 0, r2.stderr
    s2 = json.loads(r2.stdout.strip().splitlines()[-1])

    _interleave_shards(
        [tmp_path / "train-00000", tmp_path / "train-00001"], B, tmp_path / "comb-00000"
    )
    r1 = run_cli(
        ["train", "--train", str(tmp_path / "comb"), "--batch-size", str(2 * B),
         "--checkpoint-dir", str(tmp_path / "ckpt1p"), "--no-mesh", *mvm_args],
        tmp_path,
    )
    assert r1.returncode == 0, r1.stderr
    s1 = json.loads(r1.stdout.strip().splitlines()[-1])
    assert s1["steps"] == s2["steps"]
    d2 = np.load(tmp_path / "ckpt2p" / f"step_{s2['steps']}" / "state.npz")
    d1 = np.load(tmp_path / "ckpt1p" / f"step_{s1['steps']}" / "state.npz")
    np.testing.assert_allclose(
        d2["tables/v"], d1["tables/v"], rtol=1e-4, atol=1e-6,
        err_msg="2-process fullshard mvm-product != single-process",
    )
    np.testing.assert_allclose(d2["opt/v/n"], d1["opt/v/n"], rtol=1e-4, atol=1e-6)
