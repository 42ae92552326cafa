import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.data.pipeline import examples_to_batches
from xflow_tpu.data.synth import generate_shards
from xflow_tpu.data.libffm import iter_examples
from xflow_tpu.metrics import auc_logloss
from xflow_tpu.models import get_model
from xflow_tpu.optim import get_optimizer
from xflow_tpu.train import init_state, make_eval_step, make_train_step
from xflow_tpu.train.step import batch_to_arrays


def small_cfg(**kw):
    base = {
        "data.log2_slots": 14,
        "data.batch_size": 64,
        "data.max_nnz": 20,
        "model.num_fields": 6,
        "model.v_dim": 4,
    }
    base.update(kw)
    return override(Config(), **base)


def _device_batches(path, cfg):
    return [
        {k: jnp.asarray(v) for k, v in batch_to_arrays(b).items()}
        for b in examples_to_batches(
            iter_examples(path, cfg.data.log2_slots), cfg.data.batch_size, cfg.data.max_nnz
        )
    ]


def test_lr_gradient_is_scatter_of_residuals():
    # hand-check: grad wrt w[slot] == sum over occurrences (σ(wx)−y)/rows
    cfg = small_cfg()
    model = get_model("lr")
    from xflow_tpu.train.step import loss_fn

    w = jnp.zeros((cfg.num_slots,))
    batch = {
        "slots": jnp.asarray([[3, 5, 0], [3, 3, 0]], jnp.int32),
        "fields": jnp.zeros((2, 3), jnp.int32),
        "mask": jnp.asarray([[1, 1, 0], [1, 1, 0]], jnp.float32),
        "labels": jnp.asarray([1.0, 0.0]),
        "row_mask": jnp.ones((2,)),
    }
    g = jax.grad(loss_fn)(({"w": w}), batch, model, cfg)["w"]
    # logits 0 → σ=0.5; residuals: row0 = −0.5 on slots {3,5}, row1 = +0.5 twice on slot 3
    np.testing.assert_allclose(float(g[3]), (-0.5 + 0.5 + 0.5) / 2, rtol=1e-6)
    np.testing.assert_allclose(float(g[5]), -0.5 / 2, rtol=1e-6)
    assert float(g[0]) == 0.0  # masked padding contributes nothing


def test_training_learns_synthetic_lr(tmp_path):
    cfg = small_cfg()
    path = generate_shards(str(tmp_path / "s"), 1, 2000, num_fields=6, ids_per_field=50, seed=0, noise=0.3)[0]
    model, opt = get_model("lr"), get_optimizer("ftrl")
    state = init_state(model, opt, cfg)
    step = make_train_step(model, opt, cfg)
    eval_step = make_eval_step(model, cfg)
    batches = _device_batches(path, cfg)
    for epoch in range(8):
        for b in batches:
            state, m = step(state, b)
    pctrs, labels = [], []
    for b in batches:
        p = np.asarray(eval_step(state.tables, b))
        rm = np.asarray(b["row_mask"]) > 0
        pctrs.append(p[rm])
        labels.append(np.asarray(b["labels"])[rm])
    auc, ll = auc_logloss(np.concatenate(pctrs), np.concatenate(labels))
    assert auc > 0.85, f"LR failed to learn synthetic data: auc={auc}"


def test_training_learns_fm(tmp_path):
    path = generate_shards(str(tmp_path / "s"), 1, 1500, num_fields=6, ids_per_field=50, seed=1, noise=0.3)[0]
    cfg = override(small_cfg(), **{"model.name": "fm"})
    model, opt = get_model("fm"), get_optimizer("ftrl")
    state = init_state(model, opt, cfg)
    step = make_train_step(model, opt, cfg)
    eval_step = make_eval_step(model, cfg)
    batches = _device_batches(path, cfg)
    for epoch in range(10):
        for b in batches:
            state, m = step(state, b)
    pctrs, labels = [], []
    for b in batches:
        p = np.asarray(eval_step(state.tables, b))
        rm = np.asarray(b["row_mask"]) > 0
        pctrs.append(p[rm])
        labels.append(np.asarray(b["labels"])[rm])
    auc, _ = auc_logloss(np.concatenate(pctrs), np.concatenate(labels))
    assert auc > 0.8, f"fm failed to learn: auc={auc}"


def test_mvm_trains_loss_decreases(tmp_path):
    # MVM has no linear term: its logit is a product over field sums, so a
    # planted-LR task isn't representable near tiny init, and FTRL's soft
    # threshold zeroes the tiny latent weights outright (true of the
    # reference too). Assert steady SGD progress instead.
    path = generate_shards(str(tmp_path / "s"), 1, 512, num_fields=3, ids_per_field=20, seed=2, noise=0.3)[0]
    cfg = override(
        small_cfg(),
        **{
            "model.name": "mvm",
            "model.num_fields": 3,
            "optim.name": "sgd",
            "optim.sgd.lr": 1.0,
            "optim.v_init_sgd": 0.3,
        },
    )
    model, opt = get_model("mvm"), get_optimizer("sgd")
    state = init_state(model, opt, cfg)
    step = make_train_step(model, opt, cfg)
    batches = _device_batches(path, cfg)
    first = last = None
    for epoch in range(15):
        tot, n = 0.0, 0
        for b in batches:
            state, m = step(state, b)
            tot += float(m["loss"]); n += 1
        if first is None:
            first = tot / n
        last = tot / n
    assert last < first * 0.95, f"mvm loss did not decrease: {first} -> {last}"


def test_loss_decreases():
    cfg = small_cfg()
    rng = np.random.default_rng(0)
    model, opt = get_model("lr"), get_optimizer("sgd")
    cfg = override(cfg, **{"optim.name": "sgd", "optim.sgd.lr": 0.5})
    state = init_state(model, opt, cfg)
    step = make_train_step(model, opt, cfg)
    batch = {
        "slots": jnp.asarray(rng.integers(0, cfg.num_slots, (32, 8)), jnp.int32),
        "fields": jnp.zeros((32, 8), jnp.int32),
        "mask": jnp.ones((32, 8), jnp.float32),
        "labels": jnp.asarray((rng.random(32) < 0.5).astype(np.float32)),
        "row_mask": jnp.ones((32,)),
    }
    losses = []
    for _ in range(20):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8


def test_fused_scatter_ftrl_matches_two_pass():
    """optim.fused_scatter: the fused scatter+FTRL FM step (gradient
    applied inside the window write, ops/sorted_table.scatter_ftrl_sorted)
    must equal the value_and_grad + ftrl.apply two-pass form — same
    losses, same tables, same FTRL state, over several steps, packed
    and unpacked storage."""
    from xflow_tpu.ops.sorted_table import plan_sorted_batch

    for model_name, packed in (("fm", "auto"), ("fm", "off"), ("mvm", "auto")):
        # MVM fuses only under the explicit "on" (auto keeps it two-pass)
        base = {
            "model.name": model_name, "data.log2_slots": 13, "data.batch_size": 64,
            "data.max_nnz": 7, "model.num_fields": 5,
            "data.packed_tables": packed,
        }
        mode = "on" if model_name == "mvm" else "auto"
        cfg_f = override(Config(), **{**base, "optim.fused_scatter": mode})
        cfg_o = override(Config(), **{**base, "optim.fused_scatter": "off"})
        model, opt = get_model(model_name), get_optimizer("ftrl")
        tname = "v" if model_name == "mvm" else "wv"
        rng = np.random.default_rng(0)
        S = 1 << 13
        state_f = init_state(model, opt, cfg_f)
        state_o = init_state(model, opt, cfg_o)
        step_f = make_train_step(model, opt, cfg_f)
        step_o = make_train_step(model, opt, cfg_o)
        for i in range(3):
            slots = rng.integers(0, S, (64, 7)).astype(np.int32)
            mask = (rng.random((64, 7)) < 0.8).astype(np.float32)
            plan = plan_sorted_batch(slots, mask, S)
            batch = {
                "labels": jnp.asarray((rng.random(64) < 0.4).astype(np.float32)),
                "row_mask": jnp.ones(64, jnp.float32),
                "sorted_slots": jnp.asarray(plan.sorted_slots),
                "sorted_row": jnp.asarray(plan.sorted_row),
                "sorted_mask": jnp.asarray(plan.sorted_mask),
                "win_off": jnp.asarray(plan.win_off),
            }
            state_f, m_f = step_f(state_f, batch)
            state_o, m_o = step_o(state_o, batch)
            np.testing.assert_allclose(float(m_f["loss"]), float(m_o["loss"]), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(state_f.tables[tname]), np.asarray(state_o.tables[tname]),
            rtol=1e-6, atol=1e-8, err_msg=f"fused != two-pass ({model_name}, packed={packed})",
        )
        for key in ("n", "z"):
            np.testing.assert_allclose(
                np.asarray(state_f.opt_state[tname][key]),
                np.asarray(state_o.opt_state[tname][key]),
                rtol=1e-6, atol=1e-8,
            )


def test_fused_scatter_on_fails_loudly_when_ineligible():
    """optim.fused_scatter=on is a hard assertion, not a hint: config
    ineligibility (wrong optimizer/model, sharded builder) and
    non-flat-plan batches raise instead of silently running two-pass."""

    from xflow_tpu.train.step import _fused_scatter_eligible

    on = override(Config(), **{"optim.fused_scatter": "on"})
    assert _fused_scatter_eligible(override(on, **{"model.name": "fm"}), True)
    with pytest.raises(ValueError, match="fused_scatter=on"):
        _fused_scatter_eligible(override(on, **{"model.name": "lr"}), True)
    with pytest.raises(ValueError, match="single_device"):
        _fused_scatter_eligible(override(on, **{"model.name": "fm"}), False)
    with pytest.raises(ValueError, match="optim.name=ftrl"):
        _fused_scatter_eligible(override(on, **{"optim.name": "sgd"}), True)

    # a row-major batch under 'on' raises at trace time
    cfg = override(Config(), **{"optim.fused_scatter": "on", "model.name": "fm",
                                "data.log2_slots": 12, "data.batch_size": 16,
                                "data.max_nnz": 4, "model.num_fields": 3})
    model, opt = get_model("fm"), get_optimizer("ftrl")
    state = init_state(model, opt, cfg)
    step = make_train_step(model, opt, cfg)
    rng = np.random.default_rng(0)
    batch = {
        "slots": jnp.asarray(rng.integers(0, 1 << 12, (16, 4)).astype(np.int32)),
        "fields": jnp.zeros((16, 4), jnp.int32),
        "mask": jnp.ones((16, 4), jnp.float32),
        "labels": jnp.zeros(16, jnp.float32),
        "row_mask": jnp.ones(16, jnp.float32),
    }
    with pytest.raises(ValueError, match="no flat fields-free sorted plan"):
        step(state, batch)


def test_kernel_parity_runs_off_tpu():
    """The parity gate's contract: runnable on whatever backend is live
    (the fused scatter+FTRL check dispatches to the two-pass fallback
    off-TPU and passes trivially)."""
    from xflow_tpu.tools.kernel_parity import check_kernel_parity

    par = check_kernel_parity(log2_slots=13, n_occ=1 << 12, batch=256)
    assert par["ok"], par["checks"]
    # every stream the steps hand the kernels is in the gate: one plan,
    # the multi-buffer form, and the buffers merged on the device
    for form in ("exact", "multi_exact", "merged_exact"):
        assert f"gather_{form}" in par["checks"] and f"scatter_{form}" in par["checks"]


def test_fused_scatter_on_rejected_on_mesh_at_startup():
    """optim.fused_scatter=on on a mesh must fail at Trainer
    construction (the mesh engines run two-pass; a lazily-built
    overflow-fallback step raising mid-run would be far worse)."""

    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.train.trainer import Trainer

    cfg = override(Config(), **{
        "model.name": "fm", "data.log2_slots": 14, "mesh.data": 4,
        "mesh.table": 2, "optim.fused_scatter": "on",
    })
    with pytest.raises(ValueError, match="single-device"):
        Trainer(cfg, mesh=make_mesh(cfg))


# ---------------------------------------------------- non-finite guard
#
# The guard decides BEFORE the write (train/step.py guard_nonfinite): a
# bad step hands the optimizer a zero gradient, and a zero gradient is
# the identity (optim/base.py's contract). These tests hold every step
# builder and every optimizer to that.

GUARD_B, GUARD_F, GUARD_LOG2 = 64, 8, 14  # 16384 slots = 8 windows
GUARD_BUILDERS = ("single_lr", "sorted_fm", "gspmd", "fullshard")


def _guard_rig(builder, optim, guard):
    """(step, state, place) for one step builder at a tiny size: `place`
    turns a row-major numpy batch into that builder's step input."""
    from xflow_tpu.ops.sorted_table import plan_sorted_batch
    from xflow_tpu.parallel.mesh import batch_sharding, make_mesh
    from xflow_tpu.parallel.sorted_fullshard import (
        fullshard_batch_sharding, make_fullshard_train_step, plan_fullshard_batch,
    )
    from xflow_tpu.parallel.train_step import make_sharded_train_step, shard_state

    d, t = 4, 2
    cfg = override(Config(), **{
        "model.name": "lr" if builder in ("single_lr", "gspmd") else "fm",
        "model.num_fields": 5, "data.log2_slots": GUARD_LOG2,
        "data.batch_size": GUARD_B, "data.max_nnz": GUARD_F,
        "optim.name": optim, "train.nonfinite_guard": guard,
        # SGD's published step (1e-3) moves a float32 table by under an
        # ulp here; a visible step keeps "discarded" distinct from "tiny"
        "optim.sgd.lr": 0.5,
        **({} if builder in ("single_lr", "sorted_fm") else {"mesh.data": d, "mesh.table": t}),
    })
    model, opt = get_model(cfg.model.name), get_optimizer(optim)
    state = init_state(model, opt, cfg)
    S = cfg.num_slots
    rows = lambda b: {"labels": b["labels"], "row_mask": b["row_mask"]}

    def sorted_arrays(plan, b):
        return {**rows(b), "sorted_slots": plan.sorted_slots, "sorted_row": plan.sorted_row,
                "sorted_mask": plan.sorted_mask, "win_off": plan.win_off}

    if builder == "single_lr":
        step = make_train_step(model, opt, cfg)
        place = lambda b: {k: jnp.asarray(v) for k, v in b.items()}
    elif builder == "sorted_fm":
        # FTRL takes the fused scatter+FTRL form, SGD the two-pass sorted one
        step = make_train_step(model, opt, cfg)
        place = lambda b: {
            k: jnp.asarray(v)
            for k, v in sorted_arrays(plan_sorted_batch(b["slots"], b["mask"], S), b).items()
        }
    else:
        mesh = make_mesh(cfg, devices=jax.devices()[: d * t])
        if builder == "gspmd":
            step = make_sharded_train_step(model, opt, cfg, mesh)
            state = shard_state(state, mesh)
            bsh = batch_sharding(mesh)
            place = lambda b: {k: jax.device_put(jnp.asarray(v), bsh[k]) for k, v in b.items()}
        else:
            step = make_fullshard_train_step(opt, cfg, mesh)
            state = shard_state(state, mesh)
            bsh = fullshard_batch_sharding(mesh, with_fields=False)
            place = lambda b: {
                k: jax.device_put(jnp.asarray(v), bsh[k])
                for k, v in {**plan_fullshard_batch(b["slots"], b["mask"], cfg, mesh), **rows(b)}.items()
            }
    return step, state, place


def _guard_batches(n, seed=7):
    rng = np.random.default_rng(seed)
    return [
        {
            "slots": rng.integers(0, 1 << GUARD_LOG2, (GUARD_B, GUARD_F)).astype(np.int32),
            "fields": rng.integers(0, 5, (GUARD_B, GUARD_F)).astype(np.int32),
            "mask": (rng.random((GUARD_B, GUARD_F)) < 0.8).astype(np.float32),
            "labels": (rng.random(GUARD_B) < 0.4).astype(np.float32),
            "row_mask": np.ones((GUARD_B,), np.float32),
        }
        for _ in range(n)
    ]


def _host_leaves(state):
    # copies: the step donates its state
    return [np.array(x) for x in jax.tree.leaves((state.tables, state.opt_state))]


@pytest.mark.parametrize("builder,optim", [
    (b, o) for b in GUARD_BUILDERS for o in ("ftrl", "sgd")
])
def test_nonfinite_guard_discards_exactly_and_costs_good_steps_nothing(builder, optim):
    good = _guard_batches(4)
    runs = {}
    for guard in ("off", "skip"):  # the guarded rig stays in hand below
        step, state, place = _guard_rig(builder, optim, guard)
        losses = []
        for b in good[:3]:
            state, m = step(state, place(b))
            assert guard == "off" or bool(m["update_ok"])
            losses.append(np.asarray(m["loss"]).tobytes())
        runs[guard] = (losses, _host_leaves(state))
    # on good batches the guarded step is bit-identical to the unguarded
    assert runs["skip"][0] == runs["off"][0]
    assert [x.tobytes() for x in runs["skip"][1]] == [x.tobytes() for x in runs["off"][1]]

    # a NaN-label batch after the good steps: flagged, step counted,
    # every table and optimizer leaf VALUE-equal to the state before it
    # (-0.0 + 0.0 may flip a zero's sign, so values, not bytes)
    before = runs["skip"][1]
    assert any(np.any(x != 0) for x in before)
    state, m = step(state, place({**good[3], "labels": np.full((GUARD_B,), np.nan, np.float32)}))
    assert not bool(m["update_ok"])
    assert int(state.step) == 4
    after = _host_leaves(state)
    assert len(after) == len(before)
    for x, y in zip(before, after):
        np.testing.assert_array_equal(x, y)
    # ... and the discarded step poisons nothing: the next good step lands
    state, m = step(state, place(good[3]))
    assert bool(m["update_ok"]) and np.isfinite(float(m["loss"]))
    assert any(np.any(x != y) for x, y in zip(after, _host_leaves(state)))


def test_optimizer_zero_gradient_is_the_identity():
    """The contract the guard's discard rests on (optim/base.py): for
    every registered optimizer, apply(tables, state, zeros) after real
    updates returns tables and state unchanged."""
    from xflow_tpu.optim.base import _REGISTRY

    assert {"ftrl", "sgd"} <= set(_REGISTRY)
    cfg = small_cfg()
    rng = np.random.default_rng(3)
    shapes = {"w": (4096,), "v": (4096, 4)}
    for name, opt in sorted(_REGISTRY.items()):
        tables = {k: jnp.asarray(rng.normal(0, 0.01, s).astype(np.float32)) for k, s in shapes.items()}
        state = opt.init_state(tables)
        apply = jax.jit(lambda t, s, g, opt=opt: opt.apply(t, s, g, cfg))
        for _ in range(3):
            # half of the slots never see a gradient (FTRL's lazy-init rule)
            grads = {
                k: jnp.asarray((rng.normal(0, 1, s) * (rng.random(s) < 0.5)).astype(np.float32))
                for k, s in shapes.items()
            }
            tables, state = apply(tables, state, grads)
        zeros = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
        new_tables, new_state = apply(tables, state, zeros)
        for x, y in zip(jax.tree.leaves((tables, state)), jax.tree.leaves((new_tables, new_state))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=name)


def _table_shaped_counts(builder_row):
    """(select_n, is_finite) equations of one step program whose operand
    has the shape of a table or optimizer-state leaf."""
    from xflow_tpu.analysis.ir import _build_program, _iter_eqns

    _, fn, args, _ = _build_program(*builder_row)
    # the whole state, or (the fullshard step's gradient program) its table alone
    packed = (args[0].tables, args[0].opt_state) if hasattr(args[0], "tables") else args[0]
    leaf_shapes = {tuple(x.shape) for x in jax.tree.leaves(packed)}
    counts = {"select_n": 0, "is_finite": 0}
    for eqn in _iter_eqns(fn.trace(*args).jaxpr.jaxpr):
        if eqn.primitive.name in counts and any(
            tuple(getattr(v.aval, "shape", ())) in leaf_shapes for v in eqn.invars
        ):
            counts[eqn.primitive.name] += 1
    return counts["select_n"], counts["is_finite"]


def _train_program_keys():
    from xflow_tpu.analysis.ir import PROGRAMS

    return [p[0] for p in PROGRAMS if p[2].endswith(("_train", "_update"))]


@pytest.mark.parametrize("key", _train_program_keys())
def test_guarded_step_has_no_table_wide_select_or_sweep(key):
    """Structure, from the jaxpr (nothing runs), for every train program
    of the analyzer's matrix (all step builders): the guard adds no
    select between old and new state and no isfinite over a state leaf.
    What is table-shaped with the guard off — the optimizer's own
    selects, FTRL's shrink and lazy-init rules — is all a guarded step
    holds besides one select and one isfinite per GRADIENT leaf, where
    the parent held three of each per table (w, n, z). The fused sorted
    step's gradient is the batch-sized cotangent, so there the guard
    adds nothing table-shaped at all."""
    from xflow_tpu.analysis.ir import PROGRAMS

    row = next(p for p in PROGRAMS if p[0] == key)
    with_guard = lambda g: (row[0], row[1], row[2], {**row[3], "train.nonfinite_guard": g}, row[4])
    sel_on, fin_on = _table_shaped_counts(with_guard("skip"))
    sel_off, fin_off = _table_shaped_counts(with_guard("off"))
    assert fin_off == 0
    # every program of the matrix has ONE table ("w" or "wv"), so one
    # gradient leaf — none on the fused path, and none in the fullshard
    # step's gradient program: its guard sits in the update program
    added = 0 if key in ("train_step[fm.sorted]", "train_step.fullshard.fm[fm]") else 1
    assert (sel_on - sel_off, fin_on) == (added, added)
