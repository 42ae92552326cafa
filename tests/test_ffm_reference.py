"""Field-aware FM on the normal path against the benchmark's plain
reference (benchmark/reference/ffm.py: the textbook sum over pairs,
sharing nothing with xflow_tpu/models/ffm.py): `Trainer.fit()` on the
`sorted` engine follows it from seeded weights; the reference itself
follows a Python double loop; and what the step and the producer say
about themselves on that path (the state window, `xflow:ffm_place`,
`final.ffm_rowmajor_batches`)."""

import json
import os
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bench_path(monkeypatch):
    """The benchmark's plain reference, text writer and driver: the same
    files `correct` runs on the chip."""
    bench = os.path.join(ROOT, "benchmark")
    monkeypatch.syspath_prepend(bench)
    yield bench
    for name in [m for m in sys.modules if m == "lib" or m.startswith(("lib.", "reference"))]:
        sys.modules.pop(name, None)


def _cell_cfg(bench_path, nf: int) -> dict:
    with open(os.path.join(bench_path, "configs", "ffm-v4-f39-s21.json")) as f:
        cfg = json.load(f)
    cfg.update(log2_slots=14, batch_size=256, num_fields=nf, max_nnz=nf)
    return cfg


@pytest.mark.parametrize("nf", [5, 39])
def test_sorted_trainer_follows_the_plain_reference(tmp_path, bench_path, nf):
    """Three `fit()` calls over three one-batch libffm text shards, k = 4,
    at 5 fields and at Criteo's 39 (rows of 157 floats: the state window
    is 1024 there). Float32 against float32 on one backend: the losses
    differ by the order of a 256-term sum, the norms by the order of the
    pair sum (the program sums A times its block transposition, the
    reference multiplies pair by pair)."""
    from lib import compare, drive, weights
    from lib.traffic import load_traffic, make_run_data, slots_of_ids
    from reference import core as refcore

    cfg = _cell_cfg(bench_path, nf)
    traffic = load_traffic(bench_path, "text-zipf")
    seed = 2**31 + 41 + nf
    data = make_run_data(str(tmp_path / "data"), seed, cfg, traffic, window=False)
    model = refcore.model_module(cfg["reference"])
    width, leaves = model.width(cfg), model.leaves(cfg)
    assert width == 1 + nf * 4 and leaves == {"w": slice(0, 1), "v": slice(1, width)}
    trainer = drive.build_trainer(cfg, 1, data["train_prefix"])
    assert trainer.engine == "sorted"
    drive.install_weights(trainer, cfg, seed, width, weights.packed_table_fn)
    prog = drive.first_steps(trainer, cfg, seed, data, width, leaves, weights.packed_table_fn)
    batches = [(s["ids"], s["labels"]) for s in data["first"]]
    ref = refcore.run_steps(cfg, seed, batches, slots_of_ids, weights.rows_numpy)
    got = compare.readings(prog, ref)
    for k in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert got[k] <= 5e-7, (k, got)
    assert got["grad_norm_gap"] <= 2e-6, got
    assert got["delta_norm_gap"] <= 2e-6, got
    assert all(v > 0 for v in ref["grad_norm"].values()) and all(v > 0 for v in ref["delta_norm"].values())
    # and the reference tells a wrong program from a right one at this size
    half = refcore.run_steps(cfg, seed, batches, slots_of_ids, weights.rows_numpy, fault="half_batch")
    assert compare.readings(half, ref)["grad_norm_gap"] > 1e-3


def test_reference_equals_a_double_loop_over_pairs(bench_path):
    from reference import ffm

    cfg = {"num_fields": 3, "v_dim": 2}
    rng = np.random.default_rng(7)
    rows = rng.standard_normal((5, 3, ffm.width(cfg))).astype(np.float32)
    want = np.zeros(5)
    for b in range(5):
        for i in range(3):
            want[b] += rows[b, i, 0]
            for j in range(i + 1, 3):
                v_ij = rows[b, i, 1 + 2 * j:3 + 2 * j]  # feature i against field j
                v_ji = rows[b, j, 1 + 2 * i:3 + 2 * i]  # feature j against field i
                want[b] += float(v_ij.astype(np.float64) @ v_ji.astype(np.float64))
    got = np.asarray(ffm.logits(rows, cfg))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    with pytest.raises(AssertionError):
        ffm.logits(rows[:, :2], cfg)  # a row that does not hold one feature a field


def _ffm_trainer(tmp_path, monkeypatch, rows, **extra):
    from xflow_tpu.config import Config, override
    from xflow_tpu.train.trainer import Trainer

    monkeypatch.chdir(tmp_path)
    with open(tmp_path / "train-00000", "w") as f:
        f.writelines(rows)
    return Trainer(override(Config(), **{
        "model.name": "ffm", "model.num_fields": 4, "model.v_dim": 4, "data.max_nnz": 4,
        "data.log2_slots": 12, "data.batch_size": 8, "data.train_path": str(tmp_path / "train"),
        "train.epochs": 1, "train.pred_dump": False, **extra,
    }))


def _aligned_rows(n):
    return [f"{i % 2}\t" + " ".join(f"{f}:{100 * f + (i * 7 + f) % 13}:1" for f in range(4)) + "\n"
            for i in range(n)]


def test_ffm_fit_records_the_placement_the_window_and_the_fallbacks(tmp_path, monkeypatch):
    from xflow_tpu.telemetry import PHASE_LABELS

    """An armed FFM run on the sorted engine: every step record's `host`
    carries `ffm_place_ms` inside its `plan_ms`, the step's compile
    record names the state window, and the final record counts the
    batches that took the row-major fallback — the one batch of the
    three here in which a row repeats a field."""
    rows = _aligned_rows(24)
    rows[11] = "1\t0:5:1 0:6:1 2:7:1 3:8:1\n"  # field 0 twice: no placement exists
    metrics = tmp_path / "m.jsonl"
    trainer = _ffm_trainer(tmp_path, monkeypatch, rows, **{
        "train.metrics_path": str(metrics), "train.log_every": 1,
    })
    assert trainer.engine == "sorted"
    res = trainer.fit()
    assert res.steps == 3 and res.ffm_rowmajor_batches == 1 and res.fullshard_overflow_batches == 0
    recs = [json.loads(line) for line in open(metrics)]
    final = [r for r in recs if r.get("final")][-1]
    assert final["ffm_rowmajor_batches"] == 1 and "fullshard_overflow_batches" not in final
    hosts = [r["host"] for r in recs if "host" in r and not r.get("final") and "kind" not in r]
    placed = [h for h in hosts if "ffm_place_ms" in h]
    assert len(placed) == 2  # the fallback batch builds no permutation
    assert all(0 < h["ffm_place_ms"] <= h["plan_ms"] for h in placed)
    compiles = [r for r in recs if r.get("kind") == "compile" and r.get("program") == "train_step"]
    assert compiles and all(r["state_window"] == 2048 for r in compiles)
    # the aligned step's record says which operations are the row side's
    # two labels (the device trace does not: benchmark/metrics/ffm_pair_roofline.py)
    labels = set(compiles[0]["op_scopes"].values())
    assert {"ffm_place", "ffm_pair"} <= labels and labels <= set(dict(PHASE_LABELS)) | {""}


def test_ffm_place_span_opens_inside_plan_on_the_producer(tmp_path, monkeypatch):
    from xflow_tpu import telemetry

    events = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append((threading.current_thread().name, "B", self.name))

        def __exit__(self, *exc):
            events.append((threading.current_thread().name, "E", self.name))

    monkeypatch.setattr(telemetry, "_annotation", Recorder)
    trainer = _ffm_trainer(tmp_path, monkeypatch, _aligned_rows(16))
    assert trainer.fit().steps == 2
    prod = [(kind, name) for thread, kind, name in events if thread == "xflow-prefetch"]
    starts = [i for i, e in enumerate(prod) if e == ("B", "xflow:ffm_place")]
    assert len(starts) >= 2
    for i in starts:
        assert prod[i + 1] == ("E", "xflow:ffm_place")
        opened = [n for k, n in prod[:i] if k == "B"].count("xflow:plan")
        closed = [n for k, n in prod[:i] if k == "E"].count("xflow:plan")
        assert opened == closed + 1  # inside an open xflow:plan
    assert not [e for t, _, e in events if e == "xflow:ffm_place" and t != "xflow-prefetch"]


@pytest.mark.parametrize("K,pack,want", [(11, 8, 2048), (95, 8, 2048), (128, 8, 1024), (129, 8, 1024),
                                          (157, 8, 1024), (257, 8, 256), (11, 1, 2048)])
def test_state_window_follows_the_row_width(K, pack, want):
    from xflow_tpu.ops import sorted_table as st

    assert st.state_window(K, pack) == want
    assert st.state_window_bytes(K, pack, want) <= st.VMEM_SCOPED_BYTES
    if want < st.WINDOW:
        assert st.state_window_bytes(K, pack, 2 * want) > st.VMEM_SCOPED_BYTES


def test_a_row_no_window_fits_is_refused_at_start_up_with_the_numbers(capsys):
    """k = 64 at 39 fields: 2,497 floats a row, 134 MB at the smallest
    window. `sorted_layout=on` refuses it when the engine is resolved;
    `auto` says so and runs the row-major engine; nothing is left for the
    compiler to die of inside fit()."""
    from xflow_tpu.config import Config, override
    from xflow_tpu.ops import sorted_table as st
    from xflow_tpu.train.engine import _choose

    with pytest.raises(ValueError, match=r"2497 floats .* needs \d+ B of VMEM at the smallest window of 64"):
        st.state_window(2497)
    cfg = override(Config(), **{"model.name": "ffm", "model.num_fields": 39, "model.v_dim": 64,
                                "data.log2_slots": 14, "data.sorted_layout": "on"})
    with pytest.raises(ValueError, match="2497 floats"):
        _choose(cfg, None)
    assert _choose(override(cfg, **{"data.sorted_layout": "auto"}), None) == "row_major"
    assert "2497 floats" in capsys.readouterr().err
    assert _choose(override(cfg, **{"model.v_dim": 4, "data.sorted_layout": "auto"}), None) == "sorted"


def test_plans_and_kernels_read_one_window(monkeypatch):
    """The planner's `win_off` at K = 157 has one entry a 1024-slot
    window, and a kernel wrapper refuses a plan made at another."""
    import jax
    import jax.numpy as jnp

    from xflow_tpu.config import Config, override
    from xflow_tpu.ops import sorted_table as st

    cfg = override(Config(), **{"model.name": "ffm", "model.num_fields": 39, "model.v_dim": 4,
                                "data.log2_slots": 13})
    assert st.sorted_row_width(cfg) == 157 and st.sorted_window(cfg) == 1024
    assert st.sorted_window(override(cfg, **{"model.name": "fm", "model.v_dim": 10})) == 2048
    S = cfg.num_slots
    slots = np.random.default_rng(0).integers(0, S, (64, 39)).astype(np.int32)
    mask = np.ones((64, 39), np.float32)
    for window in (1024, 2048):
        plan = st.plan_sorted_batch(slots, mask, S, window=window)
        assert plan.win_off.shape == (S // window + 1,)
        for t in range(S // window):
            seg = plan.sorted_slots[plan.win_off[t]:plan.win_off[t + 1]]
            assert np.all((seg >= t * window) & (seg < (t + 1) * window))
    table = jax.ShapeDtypeStruct((S // 8, 8 * 157), jnp.float32)
    stale = st.plan_sorted_batch(slots, mask, S)  # at the default 2048
    with pytest.raises(AssertionError):
        jax.eval_shape(
            lambda t: st._scatter_ftrl_pallas(
                jnp.zeros((160, stale.sorted_slots.size)), jnp.asarray(stale.sorted_slots),
                jnp.asarray(stale.win_off), t, t, t, 157, cfg.optim.ftrl, False, 8),
            table,
        )
