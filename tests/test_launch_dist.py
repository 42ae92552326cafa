"""Multi-machine launcher (`xflow launch-dist`, launch/dist.py — the
`run_ps_dist.sh` + `scripts/hosts` analog) and coordinated
multi-process preemption (train.signal_sync_every).

The two-"host" test drives the REAL launcher end to end with ssh
swapped for a local shim (`--ssh-cmd`), separate per-rank working
directories (`--workdir .../{rank}`), and the existing bit-match gate:
final tables equal a single-process run on the batch-composed data.
"""

import json
import os
import signal
import socket
import stat
import subprocess
import sys
import time

import numpy as np
import pytest

from xflow_tpu.data.synth import generate_shards

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _clean_env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XFLOW_NUM_CPU_DEVICES", None)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    if extra:
        env.update(extra)
    return env


def _fake_ssh(tmp_path) -> str:
    """An `ssh`-shaped shim: ignores the host argument and runs the
    remote command locally — two 'hosts' that are both this machine."""
    path = tmp_path / "fakessh"
    path.write_text('#!/bin/bash\nshift\nexec bash -c "$1"\n')
    path.chmod(path.stat().st_mode | stat.S_IEXEC)
    return str(path)


def test_dry_run_prints_env_contract(tmp_path):
    hosts = tmp_path / "hosts"
    hosts.write_text("# comment\nnode-a\nuser@node-b\n\n")
    r = subprocess.run(
        [sys.executable, "-m", "xflow_tpu", "launch-dist",
         "--hosts", str(hosts), "--port", "12345",
         "--workdir", "/w/{rank}", "--env", "FOO=bar r", "--dry-run",
         "--", "--train", "/data/t x", "--model", "fm"],
        capture_output=True, text=True, env=_clean_env(), timeout=120,
    )
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "# rank 0 on node-a:" in out and "# rank 1 on user@node-b:" in out
    # both ranks point at host 0 (user@ stripped from the address)
    assert out.count("XFLOW_COORDINATOR=node-a:12345") == 2
    assert "XFLOW_NUM_PROCESSES=2" in out
    assert "XFLOW_PROCESS_ID=0" in out and "XFLOW_PROCESS_ID=1" in out
    assert "/w/0" in out and "/w/1" in out
    # env values and forwarded args survive shell-quoted (the exact
    # escaping nests once more inside the ssh argument)
    assert "FOO=" in out and "bar r" in out
    assert "/data/t x" in out
    assert "ssh node-a" in out and "ssh user@node-b" in out


def test_launch_dist_two_hosts_bitmatch(tmp_path):
    """A 2-'host' run driven by launch-dist (separate workdirs, real
    rendezvous through the XFLOW_* contract) bit-matches a
    single-process run on the batch-composed data (round-2 verdict
    item 7's done criterion)."""
    from tests.test_launch_local import (
        TRAIN_ARGS, _interleave_shards, require_multiproc_cpu, run_cli,
    )

    require_multiproc_cpu()

    B, rows = 32, 96
    generate_shards(str(tmp_path / "train"), 2, rows, num_fields=4, ids_per_field=50)
    hosts = tmp_path / "hosts"
    hosts.write_text("127.0.0.1\n127.0.0.1\n")
    r2 = subprocess.run(
        [sys.executable, "-m", "xflow_tpu", "launch-dist",
         "--hosts", str(hosts), "--port", str(_free_port()),
         "--ssh-cmd", _fake_ssh(tmp_path),
         "--workdir", str(tmp_path / "rank{rank}"),
         "--python", sys.executable,
         "--env", "JAX_PLATFORMS=cpu",
         "--env", "PYTHONPATH=" + REPO_ROOT,
         "--", "--train", str(tmp_path / "train"),
         "--batch-size", str(B), "--checkpoint-dir", "ckpt",
         "--set", "train.eval_buckets=0",
         *TRAIN_ARGS],
        capture_output=True, text=True, env=_clean_env(), timeout=600,
    )
    assert r2.returncode == 0, (r2.stdout, r2.stderr)
    summaries = [json.loads(l) for l in r2.stdout.strip().splitlines()
                 if l.startswith("{")]
    assert len(summaries) == 1, r2.stdout  # rank 0 only
    s2 = summaries[0]
    assert s2["steps"] == 2 * (rows // B)
    # separate workdirs materialized; rank 0's checkpoint is the artifact
    assert (tmp_path / "rank0" / "ckpt").is_dir()
    assert (tmp_path / "rank1").is_dir()

    _interleave_shards(
        [tmp_path / "train-00000", tmp_path / "train-00001"], B,
        tmp_path / "comb-00000",
    )
    r1 = run_cli(
        ["train", "--train", str(tmp_path / "comb"), "--batch-size", str(2 * B),
         "--checkpoint-dir", str(tmp_path / "ckpt1p"), "--no-mesh", *TRAIN_ARGS],
        tmp_path,
    )
    assert r1.returncode == 0, r1.stderr
    s1 = json.loads(r1.stdout.strip().splitlines()[-1])
    d2 = np.load(tmp_path / "rank0" / "ckpt" / f"step_{s2['steps']}" / "state.npz")
    d1 = np.load(tmp_path / "ckpt1p" / f"step_{s1['steps']}" / "state.npz")
    assert s1["steps"] == s2["steps"]
    np.testing.assert_allclose(
        d2["tables/w"], d1["tables/w"], rtol=0, atol=1e-6,
        err_msg="launch-dist 2-host tables != single-process tables on composed data",
    )
    np.testing.assert_allclose(d2["opt/w/n"], d1["opt/w/n"], rtol=0, atol=1e-6)


def _pids_with_env(key: bytes) -> list:
    """All live pids whose environment contains `key` (via /proc)."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if key in f.read():
                    out.append(int(pid))
        except OSError:
            continue
    return out


def test_launch_dist_ranks_die_with_launcher(tmp_path):
    """The die-with-connection wrapper (rank_command): SIGKILL the
    launcher itself — no graceful teardown runs — and the rank
    processes must still exit, because the launcher's death closes the
    held-open ssh stdin pipes and the remote watcher TERMs each rank.
    Without the wrapper, ssh'd ranks blocked in collectives outlive the
    launcher and hold the coordinator port (ADVICE r3)."""
    from tests.test_launch_local import require_multiproc_cpu

    require_multiproc_cpu()
    generate_shards(str(tmp_path / "train"), 2, 4000, num_fields=4, ids_per_field=50)
    hosts = tmp_path / "hosts"
    hosts.write_text("127.0.0.1\n127.0.0.1\n")
    marker = f"XFLOW_DIEWITH_{os.getpid()}"
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
         "--log2-slots", "10", "--set", "model.num_fields=4",
         "--set", "data.max_nnz=8", "--set", "train.pred_dump=false"],
        env=_clean_env(), stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            ranks = _pids_with_env(marker.encode())
            if len(ranks) >= 2:
                break
            assert p.poll() is None, "launcher died before ranks started"
            time.sleep(0.3)
        assert len(ranks) >= 2, f"ranks never started: {ranks}"
        os.kill(p.pid, signal.SIGKILL)  # no teardown() runs
        p.wait()
        deadline = time.time() + 30  # watcher: TERM immediately, KILL +5s
        while time.time() < deadline:
            alive = [r for r in _pids_with_env(marker.encode()) if r != p.pid]
            if not alive:
                break
            time.sleep(0.5)
        assert not alive, f"rank pids outlived the launcher: {alive}"
    finally:
        for pid in _pids_with_env(marker.encode()):
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass


def _children_by_rank(parent_pid: int) -> dict:
    """rank -> pid of `xflow train` children, via /proc (Linux)."""
    out = {}
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().split()[3])
            if ppid != parent_pid:
                continue
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = dict(
                    kv.split(b"=", 1) for kv in f.read().split(b"\0") if b"=" in kv
                )
            rank = env.get(b"XFLOW_PROCESS_ID")
            if rank is not None:
                out[int(rank)] = int(pid)
        except (OSError, ValueError, IndexError):
            continue
    return out


def test_coordinated_preemption_two_process(tmp_path):
    """SIGTERM delivered to rank 1 ONLY: the flag allgather
    (train.signal_sync_every) stops BOTH ranks at the same step, both
    checkpoint collectively, and rank 0's summary reports the adopted
    signal (round-2 weak #6)."""
    from tests.test_launch_local import require_multiproc_cpu

    require_multiproc_cpu()
    generate_shards(str(tmp_path / "train"), 2, 2000, num_fields=4, ids_per_field=50)
    metrics = tmp_path / "metrics.jsonl"
    p = subprocess.Popen(
        [sys.executable, "-m", "xflow_tpu", "launch-local", "--num-processes", "2",
         "--", "--train", str(tmp_path / "train"), "--model", "lr",
         "--epochs", "100000", "--batch-size", "20", "--log2-slots", "10",
         "--checkpoint-dir", str(tmp_path / "ckpt"),
         "--set", "model.num_fields=4", "--set", "data.max_nnz=8",
         "--set", "train.pred_dump=false", "--set", "train.log_every=1",
         "--set", "train.signal_sync_every=2",
         "--set", f"train.metrics_path={metrics}"],
        cwd=tmp_path, env=_clean_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    def stepped():
        # a STEP record, not a non-empty file: the stream opens with the
        # kind="init_state" and kind="compile" records of the trainer's
        # construction, before fit() installs the signal handler — a
        # SIGTERM sent then is swallowed and the run never stops. A step
        # is collective, so once rank 0 logged one, rank 1 is inside fit().
        if not metrics.exists():
            return False
        with open(metrics) as f:
            return any('"loss"' in line and '"kind"' not in line for line in f)

    deadline = time.time() + 300
    while time.time() < deadline and not stepped():
        assert p.poll() is None, (p.stdout.read(), p.stderr.read())
        time.sleep(0.2)
    assert stepped(), "training never started"
    kids = _children_by_rank(p.pid)
    assert 1 in kids, f"children found: {kids}"
    os.kill(kids[1], signal.SIGTERM)  # NOT rank 0 — coordination must spread it
    out, err = p.communicate(timeout=300)
    assert p.returncode == 0, (out, err)
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["interrupted"] == int(signal.SIGTERM)  # adopted by rank 0
    assert summary["steps"] > 0
    steps = sorted((tmp_path / "ckpt").glob("step_*"))
    assert steps, "no coordinated checkpoint written"
