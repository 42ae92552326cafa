"""`correct` for field-aware FM (`ffm-v4-f39-s21`, 39 fields x k=4) at a
size a test run can hold: a sound run passes every limit of
`limits/ffm-v4-f39-s21.json`; the control (the plain reference in
bfloat16, put in the program's place) and the half-batch fault, planted
under the harness and in the reference, do not, and each is caught by a
norm, not by every number."""

import json
import os

import pytest

import run as harness
from lib import compare, drive, weights
from lib.traffic import make_run_data, slots_of_ids
from reference import core as refcore

CELL = "ffm-v4-f39-s21.text-zipf"
NORMS = ("grad_norm_gap", "delta_norm_gap")


def _run(capsys, seed):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
                       "--trace", "0", "--rehearsal"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _first_batches(tmp_path, seed):
    _, _, cfg, traffic = harness.load_cell(CELL, rehearsal=True)
    data = make_run_data(str(tmp_path), seed, cfg, traffic, window=False)
    return cfg, [(s["ids"], s["labels"]) for s in data["first"]]


def test_limits_are_the_cells_own():
    _, _, cfg, _ = harness.load_cell(CELL, rehearsal=True)
    assert cfg["reference"] == "ffm" and cfg["num_fields"] == cfg["max_nnz"] == 39 and cfg["v_dim"] == 4
    assert cfg["reduced"] == [] and set(cfg["assumed"]) >= {"log2_slots", "batch_size", "optimizer"}
    with open(os.path.join(harness.HERE, "limits", cfg["name"] + ".json")) as f:
        own = json.load(f)
    assert compare.load_limits(harness.HERE, cfg) == own["limits"]
    assert own["readings"]  # the chip readings the limits were set from


@pytest.mark.parametrize("seed", [2**31 + 101, 7])
def test_sound_run_is_correct(capsys, seed):
    out = _run(capsys, seed)
    assert out["correct"] is True and out["program"]["engine"] == "sorted"
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())


def test_half_of_the_batch_left_out_under_the_harness_is_not_correct(capsys, monkeypatch):
    build = drive.build_trainer

    def broken(*a, **k):
        trainer = build(*a, **k)
        step = trainer.train_step

        def half(state, batch):
            rm = batch["row_mask"]
            return step(state, {**batch, "row_mask": rm.at[rm.shape[0] // 2:].set(0.0)})

        trainer.train_step = half
        return trainer

    monkeypatch.setattr(drive, "build_trainer", broken)
    out = _run(capsys, 2**31 + 103)
    assert out["correct"] is False
    assert any(out["compared"][k]["value"] > 10 * out["compared"][k]["limit"] for k in NORMS)


@pytest.mark.parametrize("seed", [2**31 + 107, 11, 3_600_000_001])
def test_control_and_fault_in_the_reference_are_not_correct(tmp_path, seed):
    cfg, batches = _first_batches(tmp_path, seed)
    limits = compare.load_limits(harness.HERE, cfg)
    ref = refcore.run_steps(cfg, seed, batches, slots_of_ids, weights.rows_numpy)
    ok, _ = compare.judge(compare.readings(ref, ref), limits)
    assert ok
    low = refcore.run_steps(cfg, seed, batches, slots_of_ids, weights.rows_numpy, dtype="bfloat16")
    half = refcore.run_steps(cfg, seed, batches, slots_of_ids, weights.rows_numpy, fault="half_batch")
    for name, bad in (("bfloat16", low), ("half_batch", half)):
        numbers = compare.readings(bad, ref)
        ok, table = compare.judge(numbers, limits)
        assert not ok, (name, table)
        assert any(numbers[k] > limits[k] for k in NORMS), (name, table)
