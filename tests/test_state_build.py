"""A trainer's state is born in its shardings (train/state.py
build_state): the eager `init_state`'s values bit for bit, under every
layout a Trainer uses, with no device ever holding more than its share
— and a Trainer on four devices, driven by `fit()` over text shards,
follows the benchmark's plain reference."""

import json
import os
import sys

import jax
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.models import get_model
from xflow_tpu.optim import get_optimizer
from xflow_tpu.parallel.mesh import make_mesh, state_shardings
from xflow_tpu.train.state import build_state, init_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOG2_SLOTS = 12


def _cfg(model_name, log2_slots=LOG2_SLOTS, **extra):
    return override(Config(), **{
        "model.name": model_name, "data.log2_slots": log2_slots,
        "mesh.data": 2, "mesh.table": 2, **extra,
    })


def _mesh(cfg):
    return make_mesh(cfg, devices=jax.devices()[:4])


def _layouts(mesh):
    """name -> the `shardings` argument of build_state, as
    Trainer.__init__ passes it for that engine."""
    return {
        "fullshard": lambda s: state_shardings(s, mesh),
        "gspmd": lambda s: state_shardings(s, mesh),
        "one_device": None,
    }


def _bits(x):
    a = np.asarray(x)
    return a.view(np.uint32) if a.dtype == np.float32 else a


def _assert_same_bits(got, want):
    got_leaves, tree = jax.tree.flatten(got)
    want_leaves, want_tree = jax.tree.flatten(want)
    assert tree == want_tree
    for g, w in zip(got_leaves, want_leaves):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert np.array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("layout,model_name", [
    ("fullshard", "fm"), ("gspmd", "mvm"), ("gspmd", "lr"),
    ("fullshard", "ffm"),  # the widest packed row
    ("one_device", "fm"), ("one_device", "lr"),
])
def test_state_born_sharded_has_the_eager_init_bits(layout, model_name):
    """The same PRNGKey(train.seed), split order and packed shape:
    partitionable threefry gives the same bits sharded and unsharded,
    and the barrier in init_tables keeps XLA from folding the scale
    into the sampler's constants inside the jit (an ulp otherwise)."""
    cfg = _cfg(model_name)
    model, opt = get_model(model_name), get_optimizer("ftrl")
    mesh = _mesh(cfg)
    shardings = _layouts(mesh)[layout]
    state = build_state(model, opt, cfg, shardings)
    _assert_same_bits(state, init_state(model, opt, cfg))
    if shardings is None:
        assert all(len(x.sharding.device_set) == 1 for x in jax.tree.leaves(state))
    else:
        want = shardings(jax.eval_shape(lambda: init_state(model, opt, cfg)))
        for leaf, sh in zip(jax.tree.leaves(state), jax.tree.leaves(want)):
            assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)


def test_sharded_init_program_holds_a_share_per_device():
    """No leaf, and no temporary of a leaf's size, whole on one device:
    the compiled init program's output and temporaries a device are the
    unsharded program's over the number of shards (the sampler's words
    are partitioned with the leaf), within 4 KiB of slack for the step
    scalar and alignment."""
    cfg = _cfg("fm", log2_slots=16)
    model, opt = get_model("fm"), get_optimizer("ftrl")
    mesh = _mesh(cfg)

    def init():
        return init_state(model, opt, cfg)

    out = _layouts(mesh)["fullshard"](jax.eval_shape(init))
    whole = jax.jit(init).lower().compile().memory_analysis()
    part = jax.jit(init, out_shardings=out).lower().compile().memory_analysis()
    slack = 4096
    assert part.output_size_in_bytes <= whole.output_size_in_bytes / 4 + slack
    assert part.temp_size_in_bytes <= whole.temp_size_in_bytes / 4 + slack
    # the eager path held the whole leaf here
    assert part.temp_size_in_bytes < (1 << 16) * 11 * 4


def _trainer_cfg(engine, tmp_path, **extra):
    base = {
        "fullshard": ("fm", {}),
        "gspmd": ("lr", {}),
        "one_device": ("fm", {}),
    }[engine]
    # 2^14 slots: the sorted mesh engines want whole windows a shard
    return _cfg(base[0], log2_slots=14, **{**base[1], "train.pred_dump": False, **extra})


@pytest.mark.parametrize("engine", ["fullshard", "gspmd", "one_device"])
def test_trainer_builds_its_state_through_the_one_helper(engine, tmp_path):
    """Every engine of train/engine.py: the state is the eager
    init's, lives in the engine's layout, and the `xflow:init_state`
    span's kind="init_state" record carries its size, whole and on the
    fullest device."""
    from xflow_tpu.train.trainer import Trainer

    mpath = tmp_path / "metrics.jsonl"
    cfg = _trainer_cfg(engine, tmp_path, **{"train.metrics_path": str(mpath)})
    mesh = None if engine == "one_device" else _mesh(cfg)
    t = Trainer(cfg, mesh=mesh)
    assert t.engine == ("sorted" if engine == "one_device" else engine)
    _assert_same_bits(t.state, init_state(t.model, t.optimizer, cfg))
    t.metrics.close()
    recs = [json.loads(line) for line in open(mpath)]
    (rec,) = [r for r in recs if r.get("kind") == "init_state"]
    leaves = jax.tree.leaves(t.state)
    total = sum(x.nbytes for x in leaves)
    assert rec["state_bytes_total"] == total
    shards = 1 if engine == "one_device" else 4
    # every table leaf split `shards` ways, the int32 step on every device
    assert rec["state_bytes_per_device"] == (total - 4) // shards + 4
    fullest = max(
        sum(s.data.nbytes for x in leaves for s in x.addressable_shards if s.device == d)
        for d in leaves[0].sharding.device_set
    )
    assert rec["state_bytes_per_device"] == fullest
    assert rec["dur_ms"] >= 0


# ---------------------------------------------------------------- reference


@pytest.fixture
def bench_path(monkeypatch):
    """The benchmark's plain reference and its text writer (benchmark/):
    independent of the program, the same files `correct` runs on the chip."""
    bench = os.path.join(ROOT, "benchmark")
    monkeypatch.syspath_prepend(bench)
    yield bench
    # `lib` and `reference` are the benchmark's own top-level names: leave none behind
    for name in [m for m in sys.modules if m == "lib" or m.startswith(("lib.", "reference"))]:
        sys.modules.pop(name, None)


def test_four_device_trainer_follows_the_plain_reference(tmp_path, bench_path):
    """`Trainer` on four virtual devices (auto picks the fullshard
    engine), three `fit()` calls over three one-batch libffm text shards
    from seeded weights, against benchmark/reference (float32, touched
    slots only, FTRL from the published equations).

    Tolerances, float32 against float32 on the same backend: each loss
    is a mean of 512 softplus terms near ln 2, summed in another order
    by the sharded step (per-chip partial sums, then a psum): a few ulp
    of 0.69, 5e-7 relative. The gradient's and the table change's norms
    are sums of 16k squares gathered through the exchange and
    `psum_scatter`: order of summation only, 2e-6 relative by the
    worst leaf. Leaving the exchange out reads 0.7 on the gradient
    (benchmark/tests/test_correct.py), half a batch 0.03."""
    from lib import compare, drive, weights
    from lib.traffic import load_traffic, make_run_data, slots_of_ids
    from reference import core as refcore

    with open(os.path.join(bench_path, "configs", "fm-v10-s27-x4.json")) as f:
        cfg = json.load(f)
    cfg.update(log2_slots=14, batch_size=512)
    traffic = load_traffic(bench_path, "text-zipf")
    traffic.update(steps_per_pass=4)
    seed = 2**31 + 29
    data = make_run_data(str(tmp_path / "data"), seed, cfg, traffic, window=False)
    model = refcore.model_module(cfg["reference"])
    width, leaves = model.width(cfg), model.leaves(cfg)
    trainer = drive.build_trainer(cfg, 4, data["train_prefix"])
    assert trainer.engine == "fullshard"
    drive.install_weights(trainer, cfg, seed, width, weights.packed_table_fn)
    prog = drive.first_steps(trainer, cfg, seed, data, width, leaves, weights.packed_table_fn)
    batches = [(s["ids"], s["labels"]) for s in data["first"]]
    ref = refcore.run_steps(dict(cfg, chips=4), seed, batches, slots_of_ids, weights.rows_numpy)
    got = compare.readings(prog, ref)
    for k in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert got[k] <= 5e-7, (k, got)
    assert got["grad_norm_gap"] <= 2e-6, got
    assert got["delta_norm_gap"] <= 2e-6, got
    assert all(v > 0 for v in ref["grad_norm"].values()) and all(v > 0 for v in ref["delta_norm"].values())
