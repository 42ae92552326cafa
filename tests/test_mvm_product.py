"""MVM exclusive-fields product path (models/mvm.py).

When no row repeats a field (the natural libffm shape), the
per-(row, field) view sums are single v values and the field product
collapses to a log-space product over the row's occurrences — the same
cache-resident [B, ~24] row-sum shape as FM, replacing the [B·nf, k+1]
segment aggregate that was the measured MVM wall (docs/PERF.md 3a).

Covers: duplicate detection, routing (auto/on/off × process count),
logit equality vs the row-major oracle, the FTRL-critical exact-zero
reactivation gradient, multi-step training equality vs the segment
path, trainer plan routing, and fullshard-engine equality.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.models import get_model
from xflow_tpu.models.mvm import (
    has_field_duplicates,
    resolve_mvm_product,
)
from xflow_tpu.ops.sorted_table import plan_sorted_batch
from xflow_tpu.optim import get_optimizer
from xflow_tpu.train.state import init_state
from xflow_tpu.train.step import make_train_step

LOG2_SLOTS = 14
S = 1 << LOG2_SLOTS
B, F = 64, 8


def _cfg(**extra):
    return override(
        Config(),
        **{
            "model.name": "mvm",
            "model.num_fields": F,
            "data.log2_slots": LOG2_SLOTS,
            "data.batch_size": B,
            "data.max_nnz": F,
            **extra,
        },
    )


def _exclusive_batch(rng, b=B, f=F):
    """One feature per field per row (fields 0..f-1), random mask."""
    return {
        "slots": rng.integers(0, S, (b, f)).astype(np.int32),
        "fields": np.broadcast_to(np.arange(f, dtype=np.int32), (b, f)).copy(),
        "mask": (rng.random((b, f)) < 0.8).astype(np.float32),
        "labels": (rng.random(b) < 0.4).astype(np.float32),
        "row_mask": np.ones((b,), np.float32),
    }


def _sorted_arrays(batch, with_fields):
    plan = plan_sorted_batch(
        batch["slots"], batch["mask"], S,
        fields=batch["fields"] if with_fields else None,
    )
    out = {
        "sorted_slots": jnp.asarray(plan.sorted_slots),
        "sorted_row": jnp.asarray(plan.sorted_row),
        "sorted_mask": jnp.asarray(plan.sorted_mask),
        "win_off": jnp.asarray(plan.win_off),
        "labels": jnp.asarray(batch["labels"]),
        "row_mask": jnp.asarray(batch["row_mask"]),
    }
    if with_fields:
        out["sorted_fields"] = jnp.asarray(plan.sorted_fields)
    return out


# ------------------------------------------------------------- detection

def test_has_field_duplicates_bitmask_path():
    fields = np.array([[0, 1, 2], [3, 3, 4]], np.int32)
    mask = np.ones((2, 3), np.float32)
    assert has_field_duplicates(fields, mask)
    # the duplicate pair masked out -> no duplicates among MASKED occs
    mask[1, 0] = 0.0
    assert not has_field_duplicates(fields, mask)


def test_has_field_duplicates_wide_field_space():
    # field ids >= 64 exercise the sort-based path
    fields = np.array([[100, 200, 100], [1, 2, 3]], np.int64)
    mask = np.ones((2, 3), np.float32)
    assert has_field_duplicates(fields, mask)
    mask[0, 2] = 0.0
    assert not has_field_duplicates(fields, mask)


def test_has_field_duplicates_empty_and_single():
    assert not has_field_duplicates(np.zeros((0, 3), np.int32), np.zeros((0, 3)))
    assert not has_field_duplicates(np.zeros((4, 1), np.int32), np.ones((4, 1)))


# --------------------------------------------------------------- routing

def test_resolve_mvm_product_routing():
    assert resolve_mvm_product("auto", False, 1)
    assert resolve_mvm_product("auto", False, 4)
    assert not resolve_mvm_product("auto", True, 1)  # per-batch fallback
    assert not resolve_mvm_product("off", False, 1)
    assert resolve_mvm_product("on", False, 1)
    with pytest.raises(ValueError, match="mvm_exclusive=off"):
        resolve_mvm_product("on", True, 1)
    with pytest.raises(ValueError, match="collective"):
        resolve_mvm_product("auto", True, 2)  # multi-process cannot reroute
    with pytest.raises(ValueError, match="auto|on|off"):
        resolve_mvm_product("maybe", False, 1)


# ------------------------------------------------------- forward parity

def test_product_logits_match_rowmajor_oracle():
    cfg = _cfg()
    model = get_model("mvm")
    rng = np.random.default_rng(0)
    batch = _exclusive_batch(rng)
    # O(1)-scale v so products neither vanish nor explode
    v = jnp.asarray(rng.standard_normal((S, cfg.model.v_dim)).astype(np.float32))
    ref = np.asarray(
        model.forward({"v": v}, {k: jnp.asarray(a) for k, a in batch.items()}, cfg)
    )
    got = np.asarray(model.forward({"v": v}, _sorted_arrays(batch, False), cfg))
    # ln/exp round-trip noise ~ |sum of logs| * eps, plus sign-cancelled
    # sums across latent dims: compare with a scale-aware atol
    np.testing.assert_allclose(
        got, ref, rtol=1e-4, atol=np.abs(ref).max() * 1e-5 + 1e-10
    )


def test_product_matches_segment_path_on_exclusive_data():
    cfg = _cfg()
    model = get_model("mvm")
    rng = np.random.default_rng(1)
    batch = _exclusive_batch(rng)
    v = jnp.asarray(rng.standard_normal((S, cfg.model.v_dim)).astype(np.float32))
    seg = np.asarray(model.forward({"v": v}, _sorted_arrays(batch, True), cfg))
    prod = np.asarray(model.forward({"v": v}, _sorted_arrays(batch, False), cfg))
    np.testing.assert_allclose(
        prod, seg, rtol=1e-4, atol=np.abs(seg).max() * 1e-5 + 1e-10
    )


def test_zero_value_reactivation_gradient():
    """FTRL-proximal zeroes v entries as its sparsity mechanism; the
    product path must keep the oracle's NONZERO gradient at exact-zero
    v (dP/dv = product of the row's other factors), or sparsified
    weights would freeze forever. The Z channel + the exclusive-product
    custom VJP in make_row_products (models/mvm.py) provide this — the
    clamped ln cancels in S - L_j, so no epsilon perturbation exists
    anywhere."""
    cfg = _cfg()
    model = get_model("mvm")
    rng = np.random.default_rng(2)
    batch = _exclusive_batch(rng)
    v_np = rng.standard_normal((S, cfg.model.v_dim)).astype(np.float32)
    # zero latent dim 0 for each row's FIELD-0 occurrence only, so the
    # product of the row's OTHER factors (the reactivation gradient)
    # stays nonzero
    v_np[batch["slots"][:, 0], 0] = 0.0
    v = jnp.asarray(v_np)
    rowmajor = {k: jnp.asarray(a) for k, a in batch.items()}
    sorted_b = _sorted_arrays(batch, False)

    def loss(tbl, b):
        return model.forward(tbl, b, cfg).sum()

    g_ref = np.asarray(jax.grad(loss)({"v": v}, rowmajor)["v"])
    g_got = np.asarray(jax.grad(loss)({"v": v}, sorted_b)["v"])
    touched = np.zeros(S, bool)
    touched[batch["slots"].ravel()] = True
    # dim-0 gradients at the zeroed entries are the nonzero reactivation
    # gradients; they must match the oracle, not be zero
    assert np.abs(g_ref[touched, 0]).max() > 0
    np.testing.assert_allclose(
        g_got[touched], g_ref[touched],
        rtol=1e-3, atol=np.abs(g_ref).max() * 2e-5 + 1e-10,
    )


def test_training_equality_product_vs_segment():
    """A few FTRL steps through each path end at the same tables."""
    cfg = _cfg()
    model, opt = get_model("mvm"), get_optimizer("ftrl")
    rng = np.random.default_rng(3)
    batches = [_exclusive_batch(rng) for _ in range(3)]
    step = make_train_step(model, opt, cfg)

    states = {}
    for with_fields in (False, True):
        st = init_state(model, opt, cfg)
        for b in batches:
            st, _ = step(st, _sorted_arrays(b, with_fields))
        states[with_fields] = st
    np.testing.assert_allclose(
        np.asarray(states[False].tables["v"]),
        np.asarray(states[True].tables["v"]),
        rtol=2e-4, atol=1e-6,
    )


# ------------------------------------------------------ trainer routing

def test_trainer_routes_exclusive_to_product_path():
    from xflow_tpu.data.schema import SparseBatch
    from xflow_tpu.train.trainer import Trainer

    cfg = _cfg()
    rng = np.random.default_rng(4)
    b = _exclusive_batch(rng)
    sb = SparseBatch(
        slots=b["slots"], fields=b["fields"], mask=b["mask"],
        labels=b["labels"], row_mask=b["row_mask"],
    )
    tr = Trainer(cfg)
    assert tr.engine == "sorted"
    arrays = tr._engine.batch_arrays(sb)
    assert "sorted_fields" not in arrays  # product path
    # duplicate fields in one row -> auto falls back to the segment path
    dup = SparseBatch(
        slots=b["slots"], fields=np.zeros_like(b["fields"]), mask=b["mask"],
        labels=b["labels"], row_mask=b["row_mask"],
    )
    arrays = tr._engine.batch_arrays(dup)
    assert "sorted_fields" in arrays
    # forcing exclusivity raises on the same batch
    tr_on = Trainer(_cfg(**{"model.mvm_exclusive": "on"}))
    with pytest.raises(ValueError, match="mvm_exclusive=off"):
        tr_on._engine.batch_arrays(dup)


# ------------------------------------------------------ fullshard engine

@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2)])
def test_fullshard_product_matches_single_device(mesh_shape):
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.sorted_fullshard import (
        fullshard_batch_sharding,
        make_fullshard_train_step,
        plan_fullshard_batch,
    )
    from xflow_tpu.parallel.train_step import shard_state

    d, t = mesh_shape
    cfg = _cfg(**{"mesh.data": d, "mesh.table": t})
    model, opt = get_model("mvm"), get_optimizer("ftrl")
    rng = np.random.default_rng(5)
    batches = [_exclusive_batch(rng) for _ in range(3)]

    state1 = init_state(model, opt, cfg)
    step1 = make_train_step(model, opt, cfg)
    losses1 = []
    for b in batches:
        state1, m = step1(state1, {k: jnp.asarray(v) for k, v in b.items()})
        losses1.append(float(m["loss"]))

    mesh = make_mesh(cfg, devices=jax.devices()[: d * t])
    state2 = shard_state(init_state(model, opt, cfg), mesh)
    step2 = make_fullshard_train_step(opt, cfg, mesh)
    bsh = fullshard_batch_sharding(mesh, with_fields=False)
    losses2 = []
    for b in batches:
        arrays = plan_fullshard_batch(b["slots"], b["mask"], cfg, mesh)
        arrays["labels"] = b["labels"]
        arrays["row_mask"] = b["row_mask"]
        placed = {k: jax.device_put(jnp.asarray(v), bsh[k]) for k, v in arrays.items()}
        assert "fs_fields" not in placed  # product mode
        state2, m = step2(state2, placed)
        losses2.append(float(m["loss"]))

    np.testing.assert_allclose(losses1, losses2, rtol=2e-4)
    np.testing.assert_allclose(
        np.asarray(state1.tables["v"]),
        np.asarray(state2.tables["v"]),
        rtol=2e-4, atol=1e-6,
    )


def test_plus_one_form_all_paths_agree():
    """model.mvm_plus_one (the reference gradient's bias-augmented
    factor form, mvm_worker.cc:153-157): row-major, segment, and
    product paths compute the same logits."""
    cfg = _cfg(**{"model.mvm_plus_one": True})
    model = get_model("mvm")
    rng = np.random.default_rng(7)
    batch = _exclusive_batch(rng)
    v = jnp.asarray(
        (rng.standard_normal((S, cfg.model.v_dim)) * 0.1).astype(np.float32)
    )
    ref = np.asarray(
        model.forward({"v": v}, {k: jnp.asarray(a) for k, a in batch.items()}, cfg)
    )
    seg = np.asarray(model.forward({"v": v}, _sorted_arrays(batch, True), cfg))
    prod = np.asarray(model.forward({"v": v}, _sorted_arrays(batch, False), cfg))
    scale = np.abs(ref).max() * 1e-5 + 1e-10
    np.testing.assert_allclose(seg, ref, rtol=1e-4, atol=scale)
    np.testing.assert_allclose(prod, ref, rtol=1e-4, atol=scale)


def test_plus_one_learns_where_plain_product_cannot():
    """With 8+ fields and the reference's 1e-2 v init, the plain product
    model's gradients vanish multiplicatively (each is a product of the
    row's OTHER ~1e-2 factors); the plus-one form keeps factors near 1
    and learns. This is why mvm_plus_one exists."""
    from xflow_tpu.train.step import loss_fn

    model, opt = get_model("mvm"), get_optimizer("ftrl")
    rng = np.random.default_rng(8)
    batch = _exclusive_batch(rng)
    batch["mask"][:] = 1.0  # all 8 fields present: Π_others ~ (1e-2)^7
    last = {}
    for plus in (False, True):
        cfg = _cfg(**{"model.mvm_plus_one": plus})
        st = init_state(model, opt, cfg)
        g = jax.grad(loss_fn)(st.tables, _sorted_arrays(batch, False), model, cfg)
        last[plus] = float(np.abs(np.asarray(g["v"])).max())
    assert last[False] < 1e-9  # multiplicatively vanished
    assert last[True] > 1e-4  # alive
