import json
import os
import subprocess
import sys

import pytest

from xflow_tpu.data.synth import generate_shards

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(args, cwd):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-m", "xflow_tpu", *args],
        capture_output=True,
        text=True,
        cwd=cwd,
        env=env,
        timeout=600,
    )


def test_gen_data_and_train_cli(tmp_path):
    r = run_cli(["gen-data", str(tmp_path / "train"), "--shards", "1", "--rows", "400",
                 "--fields", "5", "--ids-per-field", "30"], tmp_path)
    assert r.returncode == 0, r.stderr
    generate_shards(str(tmp_path / "test"), 1, 150, num_fields=5, ids_per_field=30, seed=9, truth_seed=0)
    r = run_cli(
        [
            "train",
            "--train", str(tmp_path / "train"),
            "--test", str(tmp_path / "test"),
            "--model", "lr",
            "--epochs", "4",
            "--batch-size", "64",
            "--log2-slots", "12",
            "--no-mesh",
            "--set", "model.num_fields=5",
        ],
        tmp_path,
    )
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["epochs"] == 4
    assert summary["auc"] > 0.75
    assert (tmp_path / "pred_0_0.txt").exists()


def test_reference_model_index_accepted(tmp_path):
    generate_shards(str(tmp_path / "train"), 1, 100, num_fields=4, ids_per_field=20)
    r = run_cli(
        ["train", "--train", str(tmp_path / "train"), "--model", "0", "--epochs", "1",
         "--batch-size", "32", "--log2-slots", "10", "--no-mesh",
         "--set", "model.num_fields=4"],
        tmp_path,
    )
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout.strip().splitlines()[-1])["steps"] == 4


def test_sigterm_checkpoints_and_resumes(tmp_path):
    """Preemption (SURVEY.md §5 A3): SIGTERM mid-train saves a checkpoint
    at the next step boundary, reports `interrupted`, and a rerun resumes
    from it. The reference loses all weights on any termination."""
    import signal
    import time

    generate_shards(str(tmp_path / "train"), 1, 2000, num_fields=5, ids_per_field=40, seed=3)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    args = [
        sys.executable, "-m", "xflow_tpu", "train",
        "--train", str(tmp_path / "train"),
        "--model", "lr",
        "--epochs", "100000",  # would run ~forever without the signal
        "--batch-size", "50",
        "--log2-slots", "12",
        "--no-mesh",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--set", "model.num_fields=5",
        "--set", "train.pred_dump=false",
    ]
    metrics = tmp_path / "metrics.jsonl"
    args += ["--set", f"train.metrics_path={metrics}", "--set", "train.log_every=1"]
    p = subprocess.Popen(args, cwd=tmp_path, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
    # wait until training has demonstrably taken steps (per-step metrics)
    def stepped():
        # a step record: the kind="init_state" / "compile" records of the
        # trainer's construction come before fit() installs the handler
        if not metrics.exists():
            return False
        with open(metrics) as f:
            return any('"loss"' in line and '"kind"' not in line for line in f)

    deadline = time.time() + 300
    while time.time() < deadline and not stepped():
        assert p.poll() is None, (p.stdout.read(), p.stderr.read())
        time.sleep(0.2)
    assert stepped(), "training never started"
    p.send_signal(signal.SIGTERM)
    out, err = p.communicate(timeout=120)
    assert p.returncode == 0, (out, err)
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["interrupted"] == int(signal.SIGTERM)
    assert summary["steps"] > 0
    assert "checkpointing at step" in err
    steps = sorted((tmp_path / "ckpt").glob("step_*"))
    assert steps, "no checkpoint written on signal"

    # rerun resumes from the signal checkpoint
    r = run_cli(
        ["train", "--train", str(tmp_path / "train"), "--model", "lr",
         "--epochs", "1", "--batch-size", "50", "--log2-slots", "12", "--no-mesh",
         "--checkpoint-dir", str(tmp_path / "ckpt"),
         "--set", "model.num_fields=5", "--set", "train.pred_dump=false"],
        tmp_path,
    )
    assert r.returncode == 0, r.stderr
    assert "resumed from step" in r.stderr
