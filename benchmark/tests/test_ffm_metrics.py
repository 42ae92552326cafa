"""The field-aware cell's per-layer readers on runs written by hand:
the arithmetic of their needed bytes and operations, where each finds
its numbers, and that each reads nothing from a program that lacks what
it reads."""

import json
import os
import shutil

import pytest

import run as harness
from lib import counts

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
B, NF, K = 32768, 39, 4
WIDTH = 1 + NF * K


def _run(ops, records=(), steps=2):
    return {
        "records": list(records), "window": {"examples": 10 * B, "steps": 10, "window_s": 2.0},
        "trace": {"devices": 1, "ops": [ops], "busy_s": 1.0, "window_s": 1.0},
        "trace_steps": steps, "shape": lambda: {"distinct_slots": 250_000.0, "occurrences": float(B * NF)},
        "width": WIDTH, "chips": 1, "peak": PEAK, "memory_peak_bytes": 1, "info": {},
    }


@pytest.fixture
def metrics_file():
    """A traced run's metrics file where the harness leaves it."""
    d = os.path.join(harness.ROOT, "bench_run", "test-ffm-metrics")
    os.makedirs(d, exist_ok=True)
    yield os.path.join(d, "metrics.jsonl")
    shutil.rmtree(d, ignore_errors=True)


def test_kernel_rooflines_count_157_float_rows():
    ops = [["gather.7[pallas]", 0, 40e6], ["scatter_optimizer.1[pallas]", 50e6, 80e6], ["fusion.3", 0, 1e9]]
    run = _run(ops)
    g = harness.load_metric("ffm_gather_roofline").read(run)
    s = harness.load_metric("ffm_scatter_ftrl_roofline").read(run)
    need_g = (250_000 + B * NF) * WIDTH * 4 + B * NF * 4
    need_s = B * NF * WIDTH * 4 + B * NF * 4 + 250_000 * 6 * WIDTH * 4
    assert g == pytest.approx(100 * need_g / 819e9 / 0.020)
    assert s == pytest.approx(100 * need_s / 819e9 / 0.040)
    assert 0 < g < 100 and 0 < s < 100
    assert counts.gather_needs(1.0, 2.0, WIDTH)["bytes"] == 3 * WIDTH * 4 + 8
    none = _run([["fusion.3", 0, 1e9]])
    assert harness.load_metric("ffm_gather_roofline").read(none) is None
    assert harness.load_metric("ffm_scatter_ftrl_roofline").read(none) is None


def test_pair_roofline_joins_the_compile_record_and_the_trace(metrics_file):
    mod = harness.load_metric("ffm_pair_roofline")
    ops = [["fusion.3", 0, 100e6], ["multiply_reduce_fusion", 0, 90e6], ["fusion", 0, 30e6],
           ["copy.12", 0, 6e6], ["gather.7[pallas]", 0, 40e6], ["fusion.9", 0, 500e6]]
    run = _run(ops)
    assert mod.read(run) is None  # no compile record anywhere
    with open(metrics_file, "w") as f:
        f.write(json.dumps({"kind": "compile", "program": "train_step",
                            "op_scopes": {"fusion.9": "loss", "gather.7": "gather"}}) + "\n")
    assert mod.read(run) is None  # a program with no such scope
    with open(metrics_file, "a") as f:
        f.write("not json\n")
        f.write(json.dumps({"kind": "compile", "program": "predict", "op_scopes": {"fusion.9": "ffm_pair"}}) + "\n")
        f.write(json.dumps({"kind": "compile", "program": "train_step", "op_scopes": {
            "fusion.3": "ffm_pair", "multiply_reduce_fusion": "ffm_pair", "mul.7": "ffm_pair",
            "fusion": "ffm_place", "copy.12": "ffm_place", "fusion.9": "loss"}}) + "\n")
    seconds = (100e6 + 90e6 + 30e6 + 6e6) / 1e9 / 2  # a step
    by_bytes = B * NF * WIDTH * 4 * 2 / 819e9
    by_flops = B * 6.0 * NF * NF * K / 197e12
    assert by_bytes > by_flops
    assert mod.read(run) == pytest.approx(100 * by_bytes / seconds)
    assert mod.pair_needs(1.0, NF, NF, K, WIDTH) == {"flops": 6.0 * NF * NF * K, "bytes": NF * WIDTH * 8}
    assert mod.read({**run, "trace": None}) is None


def test_place_ms_is_the_median_batch():
    mod = harness.load_metric("ffm_place_ms")
    recs = [{"host": {"plan_ms": 60.0, "ffm_place_ms": v, "batches": 1}} for v in (30.0, 50.0, 40.0)]
    recs.append({"host": {"plan_ms": 130.0, "ffm_place_ms": 90.0, "batches": 2}})
    assert mod.read(_run([], recs)) == pytest.approx(42.5)
    assert mod.read(_run([], [{"host": {"plan_ms": 60.0, "batches": 1}}])) is None
    assert mod.read(_run([])) is None
