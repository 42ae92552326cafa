"""Fully-sharded sorted engine (parallel/sorted_fullshard.py): equality
vs the single-device step across mesh shapes for FM and MVM, the
no-replication memory contract, buffer-capacity overflow, and trainer
integration (auto engine selection, multi-step training equality)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.models import get_model
from xflow_tpu.optim import get_optimizer
from xflow_tpu.ops.sorted_table import WINDOW
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.sorted_fullshard import (
    fullshard_batch_sharding,
    fullshard_capacity,
    make_fullshard_train_step,
    plan_fullshard_batch,
    validate_sorted_fullshard,
)
from xflow_tpu.parallel.train_step import shard_state
from xflow_tpu.train.state import init_state
from xflow_tpu.train.step import make_train_step

B, F = 64, 10
LOG2_SLOTS = 14  # 16384 = 8 * WINDOW: divisible for every 8-device mesh
S = 1 << LOG2_SLOTS


def cfg_for(model_name, d, t, **extra):
    over = {
        "model.name": model_name,
        "model.num_fields": 5,
        "data.log2_slots": LOG2_SLOTS,
        "data.batch_size": B,
        "data.max_nnz": F,
        "mesh.data": d,
        "mesh.table": t,
        **extra,
    }
    return override(Config(), **over)


def rand_batch(rng, nf=5):
    return {
        "slots": rng.integers(0, S, (B, F)).astype(np.int32),
        "fields": rng.integers(0, nf, (B, F)).astype(np.int32),
        "mask": (rng.random((B, F)) < 0.8).astype(np.float32),
        "labels": (rng.random(B) < 0.4).astype(np.float32),
        "row_mask": np.ones((B,), np.float32),
    }


def _place_fullshard(batch, cfg, mesh, with_fields):
    arrays = plan_fullshard_batch(
        batch["slots"], batch["mask"], cfg, mesh,
        fields=batch["fields"] if with_fields else None,
    )
    arrays["labels"] = batch["labels"]
    arrays["row_mask"] = batch["row_mask"]
    bsh = fullshard_batch_sharding(mesh, with_fields=with_fields)
    return {k: jax.device_put(jnp.asarray(v), bsh[k]) for k, v in arrays.items()}


@pytest.mark.parametrize("model_name", ["fm", "mvm", "ffm", "mvm_product"])
@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_fullshard_step_matches_single_device(model_name, mesh_shape):
    d, t = mesh_shape
    # "mvm" plans WITH fields (the general segment mode); "mvm_product"
    # plans without them on exclusive-fields batches — the product-mode
    # custom VJP whose missing 'table'-axis cotangent restore diverged
    # at every T>1 (round-4 ADVICE; make_row_products restore_dP)
    product = model_name == "mvm_product"
    model_name = "mvm" if product else model_name
    # ffm: k=3 keeps the fused row width (1 + nf*k = 16) CI-sized
    extra = {"model.v_dim": 3} if model_name == "ffm" else {}
    cfg = cfg_for(model_name, d, t, **extra)
    model, opt = get_model(model_name), get_optimizer("ftrl")
    rng = np.random.default_rng(0)
    batches = [rand_batch(rng) for _ in range(3)]
    if product:
        for b in batches:
            # one occurrence per field: F=10 columns over nf=5 fields
            # would duplicate, so keep 5 columns live per row
            b["fields"] = np.broadcast_to(
                np.arange(F, dtype=np.int32) % 5, (B, F)
            ).copy()
            b["mask"] = b["mask"] * (np.arange(F) < 5)

    # single-device row-major reference
    state1 = init_state(model, opt, cfg)
    step1 = make_train_step(model, opt, cfg)
    losses1 = []
    for b in batches:
        state1, m = step1(state1, {k: jnp.asarray(v) for k, v in b.items()})
        losses1.append(float(m["loss"]))

    mesh = make_mesh(cfg, devices=jax.devices()[: d * t])
    state2 = shard_state(init_state(model, opt, cfg), mesh)
    step2 = make_fullshard_train_step(opt, cfg, mesh)
    losses2 = []
    for b in batches:
        state2, m = step2(
            state2,
            _place_fullshard(
                b, cfg, mesh, not product and model_name in ("mvm", "ffm")
            ),
        )
        losses2.append(float(m["loss"]))

    np.testing.assert_allclose(losses1, losses2, rtol=2e-5)
    for name in state1.tables:
        np.testing.assert_allclose(
            np.asarray(state1.tables[name]),
            np.asarray(state2.tables[name]),
            rtol=2e-4,
            atol=1e-6,
            err_msg=f"{model_name} table {name} diverged on mesh {mesh_shape}",
        )


def test_fullshard_no_replication():
    """The memory contract: every device holds EXACTLY S/(D*T) slots of
    each table and optimizer-state array — no data-axis replication
    (round-2 verdict missing #2)."""
    cfg = cfg_for("fm", 4, 2)
    mesh = make_mesh(cfg)
    model, opt = get_model("fm"), get_optimizer("ftrl")
    state = shard_state(init_state(model, opt, cfg), mesh)
    K = 1 + cfg.model.v_dim
    arrays = [state.tables["wv"], state.opt_state["wv"]["n"], state.opt_state["wv"]["z"]]
    for arr in arrays:
        shapes = {s.data.shape for s in arr.addressable_shards}
        # packed storage: each of the 8 devices owns S/8 slots = S/8/8
        # stored rows of 8*K (ops/sorted_table.pack_table)
        assert shapes == {(S // 8 // 8, 8 * K)}, shapes
        # 8 distinct shards — the whole array exists exactly once
        assert len(arr.addressable_shards) == 8
        starts = sorted(s.index[0].start or 0 for s in arr.addressable_shards)
        assert starts == [i * (S // 8 // 8) for i in range(8)]


def test_fullshard_capacity_overflow_raises():
    """More occurrences in one owner block than the buffer holds must
    fail loudly with the slack advice, not silently drop occurrences."""
    from xflow_tpu.ops.sorted_table import plan_sorted_batch
    from xflow_tpu.parallel.sorted_fullshard import fullshard_buffers

    slots = np.full((128, 10), 7, np.int32)  # 1280 occurrences, one block
    mask = np.ones((128, 10), np.float32)
    plan = plan_sorted_batch(slots, mask, S)
    with pytest.raises(ValueError, match="fullshard_slack"):
        fullshard_buffers(
            plan, D=4, T=2, cap=512, s_local=S // 8, slack=2.0, n_real=1280
        )


def test_fullshard_higher_slack_absorbs_skew():
    cfg = cfg_for("fm", 4, 2, **{"data.fullshard_slack": 16.0})
    mesh = make_mesh(cfg)
    rng = np.random.default_rng(3)
    b = rand_batch(rng)
    b["slots"][:] = 7
    arrays = plan_fullshard_batch(b["slots"], b["mask"], cfg, mesh)
    # all real occurrences are in (source-shard, block-0) buffers
    total = sum(
        float(arrays["fs_mask"][i].sum()) for i in range(arrays["fs_mask"].shape[0])
    )
    assert total == float(b["mask"].sum())


def test_fullshard_chunk_counts_are_the_merged_streams():
    """The counters a fullshard plan books are those of the stream each
    chip walks after `merge_received`: the sum of its sources' offsets,
    pads included, one chain a chip."""
    from xflow_tpu.ops.sorted_table import chunk_chain_counts
    from xflow_tpu.parallel.sorted_fullshard import fullshard_chunk_counts, merge_received

    cfg = cfg_for("fm", 4, 2)
    mesh = make_mesh(cfg)
    arrays = plan_fullshard_batch(*(rand_batch(np.random.default_rng(5))[k] for k in ("slots", "mask")), cfg, mesh)
    fs_off = arrays["fs_off"]  # [sources, T, D, wpo + 1]
    cap = arrays["fs_slots"].shape[-1]
    got = fullshard_chunk_counts(fs_off)
    chips = []
    for t in range(fs_off.shape[1]):
        for d in range(fs_off.shape[2]):
            _, off, _ = merge_received(
                jnp.asarray(arrays["fs_slots"][:, t, d]), jnp.asarray(fs_off[:, t, d]),
                jnp.zeros((fs_off.shape[0], cap), jnp.int32),
            )
            chips.append(chunk_chain_counts(np.asarray(off)))
    # every chip's stream is its four sources' buffers end to end
    assert all(c["chunk_loads"] == fs_off.shape[0] * cap // 512 for c in chips)
    assert got == {k: round(sum(c[k] for c in chips) / len(chips)) for k in chips[0]}
    assert got["chunk_visits"] >= got["chunk_loads"]


def test_fullshard_validation_messages():
    mesh = make_mesh(cfg_for("fm", 4, 2))
    with pytest.raises(ValueError, match="divisible by data\\*table\\*WINDOW"):
        validate_sorted_fullshard(cfg_for("fm", 4, 2, **{"data.log2_slots": 12}), mesh)
    with pytest.raises(ValueError, match="fused FM, MVM, and FFM"):
        validate_sorted_fullshard(cfg_for("lr", 4, 2), mesh)
    with pytest.raises(ValueError, match="fm_fused"):
        validate_sorted_fullshard(
            cfg_for("fm", 4, 2, **{"model.fm_fused": False}), mesh
        )
    # (global row, field, mask) must fold into the merge's one int32 word
    with pytest.raises(ValueError, match="one int32"):
        validate_sorted_fullshard(
            cfg_for("mvm", 4, 2, **{"data.batch_size": 1 << 24, "model.num_fields": 100}),
            mesh,
        )
    validate_sorted_fullshard(cfg_for("fm", 4, 2, **{"data.batch_size": 1 << 24}), mesh)
    cap = fullshard_capacity(cfg_for("fm", 4, 2), mesh)
    assert cap % 512 == 0 and cap >= 512


@pytest.mark.parametrize("model_name", ["fm", "mvm", "ffm"])
def test_trainer_fullshard_auto(model_name, tmp_path):
    """Trainer on a mesh auto-selects the fullshard engine for
    FM/MVM/FFM and trains to the same result as the single-device
    trainer."""
    from xflow_tpu.data.synth import generate_shards
    from xflow_tpu.train.trainer import Trainer

    generate_shards(str(tmp_path / "train"), 1, 128, num_fields=5,
                    ids_per_field=60, seed=0)
    over = {
        "data.train_path": str(tmp_path / "train"),
        "data.test_path": str(tmp_path / "train"),
        "train.epochs": 2,
        "train.pred_dump": False,
        "train.eval_buckets": 0,
    }
    if model_name == "ffm":
        over["model.v_dim"] = 3
    cfg = cfg_for(model_name, 4, 2, **over)
    mesh = make_mesh(cfg)
    t_mesh = Trainer(cfg, mesh=mesh)
    assert t_mesh.engine == "fullshard"
    res_mesh = t_mesh.fit()
    auc_mesh, ll_mesh = t_mesh.evaluate(dump=False)

    t_one = Trainer(cfg_for(model_name, 4, 2, **over, **{"data.sorted_layout": "off"}))
    res_one = t_one.fit()
    auc_one, ll_one = t_one.evaluate(dump=False)

    assert res_mesh.steps == res_one.steps
    np.testing.assert_allclose(res_mesh.last_loss, res_one.last_loss, rtol=2e-5)
    tname = "v" if model_name == "mvm" else "wv"
    np.testing.assert_allclose(
        np.asarray(t_mesh.state.tables[tname]),
        np.asarray(t_one.state.tables[tname]),
        rtol=2e-4, atol=1e-6,
    )
    assert abs(auc_mesh - auc_one) < 1e-6
    np.testing.assert_allclose(ll_mesh, ll_one, rtol=1e-5)


def test_trainer_auto_falls_back_to_gspmd_when_invalid(tmp_path):
    """log2_slots too small for the owner grid: auto keeps the GSPMD
    row-major path instead of failing."""
    from xflow_tpu.train.trainer import Trainer

    cfg = cfg_for("fm", 4, 2, **{"data.log2_slots": 12})
    mesh = make_mesh(cfg)
    t = Trainer(cfg, mesh=mesh)
    assert t.engine == "gspmd" and t.planner is None


def test_trainer_fullshard_overflow_falls_back_single_process(tmp_path, capsys):
    """A batch too skewed for the buffer capacity must NOT abort a
    single-process run: the trainer falls back to the GSPMD row-major
    step for that batch (state sharding is identical) and warns once."""
    from xflow_tpu.data.libffm import shard_path
    from xflow_tpu.train.trainer import Trainer

    # every row carries the SAME feature 4 of 8 times: half of all
    # occurrences land in one owner block, 4x the uniform expectation —
    # beyond slack 1.0, so the hot block's buffer overflows
    path = tmp_path / "train-00000"
    rng = np.random.default_rng(0)
    hot = " ".join(["0:0:1.0"] * 4)
    with open(path, "w") as f:
        for i in range(2048):
            feats = " ".join(
                f"{fg}:{rng.integers(0, 50)}:1.0" for fg in range(1, 5)
            )
            f.write(f"{i % 2}\t{hot} {feats}\n")
    cfg = cfg_for(
        "fm", 4, 2,
        **{
            "data.train_path": str(tmp_path / "train"),
            "data.batch_size": 2048,
            "data.max_nnz": 8,
            "train.epochs": 1,
            "train.pred_dump": False,
            "data.fullshard_slack": 1.0,
        },
    )
    mesh = make_mesh(cfg)
    t = Trainer(cfg, mesh=mesh)
    assert t.engine == "fullshard"
    res = t.fit()
    assert res.steps == 1
    assert "falling back to the GSPMD row-major step" in capsys.readouterr().err
    assert res.fullshard_overflow_batches == 1
    assert np.isfinite(res.last_loss)


@pytest.mark.parametrize("hot,want", [(4, [1, 1, 1]), (0, [0, 0, 0])])
def test_final_record_counts_fullshard_overflow_batches(tmp_path, hot, want):
    """How often a run fell back to the GSPMD step shows in every
    fit()'s `final` record: the count of THAT fit()'s train batches,
    not a once-a-run flag (two batches a pass here, one of them skewed
    when `hot` of a row's 8 occurrences are one feature)."""
    import json

    from xflow_tpu.train.trainer import Trainer

    rng = np.random.default_rng(0)
    with open(tmp_path / "train-00000", "w") as f:
        for i in range(4096):
            skew = hot if i < 2048 else 0
            feats = " ".join(
                ["0:0:1.0"] * skew
                + [f"{fg}:{rng.integers(0, 5000)}:1.0" for fg in range(1, 9 - skew)]
            )
            f.write(f"{i % 2}\t{feats}\n")
    mpath = tmp_path / "metrics.jsonl"
    cfg = cfg_for(
        "fm", 4, 2,
        **{
            "data.train_path": str(tmp_path / "train"),
            "data.batch_size": 2048,
            "data.max_nnz": 8,
            "model.num_fields": 9,
            "train.epochs": 1,
            "train.pred_dump": False,
            "data.fullshard_slack": 1.5,
            "train.metrics_path": str(mpath),
        },
    )
    t = Trainer(cfg, mesh=make_mesh(cfg))
    got = [t.fit().fullshard_overflow_batches for _ in range(3)]
    assert got == want
    finals = [r for r in map(json.loads, open(mpath)) if r.get("final")]
    assert [r["fullshard_overflow_batches"] for r in finals] == want
    assert all(r["steps"] == 2 for r in finals)


def _received_buffers(kind, with_fields, D=4, T=2, rows=32, nf=5, seed=0):
    """What device (d=1, t=1) holds after the exchange: every source
    shard's buffer for ITS owner block, as `fullshard_buffers` cuts them
    — (r_slots, r_row, r_mask, r_fields or None, r_off), each [D, ...]."""
    from xflow_tpu.ops.sorted_table import plan_sorted_batch
    from xflow_tpu.parallel.sorted_fullshard import fullshard_buffers

    rng = np.random.default_rng(seed)
    s_local = S // (D * T)
    d, t = 1, 1
    o = d * T + t
    cap = 1024
    out = {k: [] for k in ("fs_slots", "fs_row", "fs_mask", "fs_fields", "fs_off")}
    for src in range(D):
        if kind == "uniform":
            slots = rng.integers(0, S, (rows, F))
        elif kind == "skewed":
            # a power law inside every owner block: a few hot slots take
            # most occurrences, most windows of the block few or none
            hot = np.minimum(rng.zipf(1.3, (rows, F)) - 1, s_local - 1)
            slots = rng.integers(0, D * T, (rows, F)) * s_local + hot
        else:  # "one_empty": source 2 sends this block nothing but pads
            slots = rng.integers(0, S, (rows, F))
            if src == 2:
                slots = slots % s_local  # all in block 0
        slots = slots.astype(np.int32)
        mask = (rng.random((rows, F)) < 0.8).astype(np.float32)
        fields = rng.integers(0, nf, (rows, F)).astype(np.int32)
        plan = plan_sorted_batch(slots, mask, S, fields=fields if with_fields else None)
        bufs = fullshard_buffers(
            plan, D, T, cap, s_local, 8.0, with_fields, n_real=rows * F
        )
        for k in out:
            if k in bufs:
                out[k].append(bufs[k][t, d])
    got = {k: np.stack(v) for k, v in out.items() if v}
    if kind == "one_empty":
        assert got["fs_mask"][2].sum() == 0 and (got["fs_slots"][2] == s_local - 1).all()
    return got, s_local, cap, rows


@pytest.mark.parametrize("with_fields", [False, True], ids=["plain", "fields"])
@pytest.mark.parametrize("kind", ["uniform", "skewed", "one_empty"])
def test_merge_received_is_a_slot_sorted_permutation(kind, with_fields):
    """The on-device merge of the D received buffers: the same multiset
    of (slot, global row, mask[, field]) — the last three folded into
    the step's one payload word — in non-decreasing slot order,
    and the summed buffer offsets ARE the merged stream's window offsets
    (last entry D*cap: pads ride in the last window) — the single-stream
    kernels' contract, with no search on the device."""
    from xflow_tpu.parallel.sorted_fullshard import merge_received

    nf = 5
    bufs, s_local, cap, rows = _received_buffers(kind, with_fields, nf=nf)
    D = bufs["fs_slots"].shape[0]
    # the step's payload word: [global row (* nf + field) | mask bit]
    seg = bufs["fs_row"] + np.arange(D, dtype=np.int32)[:, None] * rows
    if with_fields:
        seg = seg * nf + bufs["fs_fields"]
    word = (seg * 2 + bufs["fs_mask"].astype(np.int32)).astype(np.int32)
    slots_m, win_off, word_m = map(np.asarray, jax.jit(merge_received)(
        jnp.asarray(bufs["fs_slots"]), jnp.asarray(bufs["fs_off"]), jnp.asarray(word)
    ))
    assert slots_m.shape == word_m.shape == (D * cap,) and word_m.dtype == np.int32
    assert np.all(np.diff(slots_m) >= 0)

    def occurrences(slots, word):
        seg, mask = word >> 1, word & 1
        cols = (seg // nf, seg % nf, mask) if with_fields else (seg, mask)
        return sorted(zip(slots.ravel().tolist(), *(c.ravel().tolist() for c in cols)))

    before = occurrences(bufs["fs_slots"], word)
    assert before == occurrences(slots_m, word_m)
    # ... and the word decodes to what the planner wrote
    raw = [bufs["fs_row"] + np.arange(D)[:, None] * rows]
    raw += [bufs["fs_fields"]] if with_fields else []
    raw += [bufs["fs_mask"].astype(np.int64)]
    assert before == sorted(zip(bufs["fs_slots"].ravel().tolist(),
                                *(c.ravel().tolist() for c in raw)))
    wpo = s_local // WINDOW
    want = np.searchsorted(slots_m, np.arange(wpo) * WINDOW, side="left")
    np.testing.assert_array_equal(win_off[:wpo], want)
    assert win_off[wpo] == D * cap and win_off.shape == (wpo + 1,)
    # every position lies in the window whose span owns it
    for j in range(wpo):
        seg = slots_m[win_off[j]:win_off[j + 1]]
        assert np.all((seg >= j * WINDOW) & (seg < (j + 1) * WINDOW))


def test_fullshard_compile_record_counts_table_spans():
    """The counter that says the merge engaged: the fullshard step's
    compile record carries `table_spans_per_step` = the local windows
    (one span each in the gather and in its transpose), not windows
    times source buffers."""
    from xflow_tpu.telemetry import CompileRecorder

    cfg = cfg_for("fm", 4, 2)
    mesh = make_mesh(cfg)
    model, opt = get_model("fm"), get_optimizer("ftrl")
    rec = CompileRecorder()
    step = make_fullshard_train_step(opt, cfg, mesh, recorder=rec)
    state = shard_state(init_state(model, opt, cfg), mesh)
    step(state, _place_fullshard(rand_batch(np.random.default_rng(0)), cfg, mesh, False))
    latest = rec.latest("train_step.fullshard.fm")
    assert latest["table_spans_per_step"] == S // 8 // WINDOW
    assert latest["pallas_calls"] == 0  # CPU: the kernels' XLA stand-ins
