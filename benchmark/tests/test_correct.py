"""`correct` at a size a test run can hold: sound runs come out
correct, the control and every fault a cell can have come out not.

The faults are planted under the harness (the program's step, or a
collective of jax), and the rest of a run is driven as `run.py` drives
it, with the look for a chip skipped (--rehearsal)."""

import json
import os

import pytest

import run as harness
from lib import compare, drive, weights
from lib.traffic import make_run_data, slots_of_ids
from reference import core as refcore

def _trains(cell: dict) -> bool:
    """Serve cells have faults and tests of their own (test_serve_cell.py)."""
    with open(os.path.join(harness.HERE, "configs", cell["config"] + ".json")) as f:
        return json.load(f).get("path", "train") == "train"


with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    _WORKLOADS = [w for w in json.load(_f)["workloads"] if _trains(w)]
CELLS = [w["name"] for w in _WORKLOADS]
FOUR_CHIP = [w["name"] for w in _WORKLOADS if w["chips"] == 4]


def _run(capsys, workload, seed=2**31 + 11):
    rc = harness.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.2",
                       "--trace", "0", "--rehearsal"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_prints_no_device_metric(capsys, workload):
    out = _run(capsys, workload)
    assert out["correct"] is True and out["metrics"] == {} and out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_fault_state_unchanged(capsys, monkeypatch, workload):
    build = drive.build_trainer

    # the step donates its state: keep a copy to hand back
    def broken_copying(*a, **k):
        import jax

        trainer = build(*a, **k)
        step = trainer.train_step

        def same(state, batch):
            kept = jax.tree.map(lambda x: x.copy(), state)
            return kept, step(state, batch)[1]

        trainer.train_step = same
        return trainer

    monkeypatch.setattr(drive, "build_trainer", broken_copying)
    out = _run(capsys, workload)
    assert out["correct"] is False
    assert out["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("workload", CELLS)
def test_fault_half_of_the_batch_left_out(capsys, monkeypatch, workload):
    build = drive.build_trainer

    def broken(*a, **k):
        trainer = build(*a, **k)
        step = trainer.train_step

        def half(state, batch):
            rm = batch["row_mask"]
            flat = rm.reshape(-1)
            flat = flat.at[flat.shape[0] // 2:].set(0.0)
            return step(state, {**batch, "row_mask": flat.reshape(rm.shape)})

        trainer.train_step = half
        return trainer

    monkeypatch.setattr(drive, "build_trainer", broken)
    out = _run(capsys, workload)
    assert out["correct"] is False
    assert out["compared"]["grad_norm_gap"]["value"] > 10 * out["compared"]["grad_norm_gap"]["limit"]


@pytest.mark.parametrize("workload", FOUR_CHIP)
def test_fault_exchange_between_chips_left_out(workload):
    """In a process of its own: the step's trace is cached by jax and by
    the program beyond what one test can clear."""
    import subprocess
    import sys

    script = (
        "import sys, jax\n"
        f"sys.path[:0] = [{harness.HERE!r}, {harness.ROOT!r}]\n"
        "jax.lax.all_to_all = lambda x, *a, **k: x\n"
        "import run\n"
        f"sys.exit(run.main(['--workload', {workload!r}, '--seed', '2147483659',"
        " '--seconds', '0.2', '--trace', '0', '--rehearsal']))\n"
    )
    done = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["correct"] is False
    assert out["compared"]["grad_norm_gap"]["value"] > 10 * out["compared"]["grad_norm_gap"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_lower_precision_is_not_correct(tmp_path, workload):
    """The reference in bfloat16, put in the program's place."""
    _, cell, cfg, traffic = harness.load_cell(workload, rehearsal=True)
    seed = 2**31 + 23
    data = make_run_data(str(tmp_path), seed, cfg, traffic, window=False)
    batches = [(s["ids"], s["labels"]) for s in data["first"]]
    ref = refcore.run_steps(cfg, seed, batches, slots_of_ids, weights.rows_numpy)
    low = refcore.run_steps(cfg, seed, batches, slots_of_ids, weights.rows_numpy, dtype="bfloat16")
    limits = compare.load_limits(harness.HERE, cfg)
    ok, table = compare.judge(compare.readings(low, ref), limits)
    assert not ok, table
    ok, _ = compare.judge(compare.readings(ref, ref), limits)
    assert ok


@pytest.mark.parametrize("workload", CELLS)
def test_faults_planted_in_the_reference_fail(workload):
    _, cell, cfg, traffic = harness.load_cell(workload, rehearsal=True)
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        data = make_run_data(td, 5, cfg, traffic, window=False)
    batches = [(s["ids"], s["labels"]) for s in data["first"]]
    ref = refcore.run_steps(dict(cfg, chips=4), 5, batches, slots_of_ids, weights.rows_numpy)
    limits = compare.load_limits(harness.HERE, cfg)
    for fault in ("half_batch",) + (("no_exchange",) if cell["chips"] == 4 else ()):
        bad = refcore.run_steps(dict(cfg, chips=4), 5, batches, slots_of_ids, weights.rows_numpy, fault=fault)
        ok, table = compare.judge(compare.readings(bad, ref), limits)
        assert not ok, (fault, table)
