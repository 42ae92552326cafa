"""Field-aware FM (models/ffm.py — BASELINE.json config 5, the model
the reference does not implement; semantic base
`/root/reference/src/model/fm/fm_worker.cc:80-86` extended per-field):
forward math vs a brute-force pair oracle (incl. duplicate fields and
masks), sorted-path == row-major equality across packed/unpacked
storage, full train-step equality, and the learnability gate — FFM
beats a plain FM on field-pair-interaction truth (`truth="ffm"`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.models import get_model
from xflow_tpu.optim import get_optimizer
from xflow_tpu.train.state import init_state
from xflow_tpu.train.step import make_train_step

NF, K_LAT, LOG2 = 5, 3, 12
S = 1 << LOG2


def ffm_cfg(**kw):
    cfg = override(
        Config(),
        **{
            "model.name": "ffm",
            "model.v_dim": K_LAT,
            "model.num_fields": NF,
            "data.log2_slots": LOG2,
        },
    )
    return override(cfg, **kw) if kw else cfg


def rand_batch(rng, B=32, F=7):
    return {
        "slots": rng.integers(0, S, (B, F)).astype(np.int32),
        "fields": rng.integers(0, NF, (B, F)).astype(np.int32),  # dups happen
        "mask": (rng.random((B, F)) < 0.8).astype(np.float32),
        "labels": (rng.random(B) < 0.4).astype(np.float32),
        "row_mask": np.ones((B,), np.float32),
    }


def oracle_logits(wv, batch):
    """Brute-force Σ_{i<j} ⟨v_{i,f_j}, v_{j,f_i}⟩ + wx over masked
    occurrences — the textbook FFM sum, pairs enumerated explicitly."""
    slots, fields, mask = batch["slots"], batch["fields"], batch["mask"]
    B = slots.shape[0]
    out = np.zeros(B)
    for b in range(B):
        idx = [i for i in range(slots.shape[1]) if mask[b, i] > 0]
        wx = sum(wv[slots[b, i], 0] for i in idx)
        t = 0.0
        for a in range(len(idx)):
            for c in range(a + 1, len(idx)):
                i, j = idx[a], idx[c]
                vi = wv[slots[b, i], 1 + fields[b, j] * K_LAT: 1 + (fields[b, j] + 1) * K_LAT]
                vj = wv[slots[b, j], 1 + fields[b, i] * K_LAT: 1 + (fields[b, i] + 1) * K_LAT]
                t += float(vi @ vj)
        out[b] = wx + t
    return out


def test_forward_matches_pair_oracle():
    rng = np.random.default_rng(0)
    batch = rand_batch(rng)
    wv = rng.normal(0, 1, (S, 1 + NF * K_LAT)).astype(np.float32)
    cfg = ffm_cfg()
    got = np.asarray(
        get_model("ffm").forward(
            {"wv": jnp.asarray(wv)},
            {k: jnp.asarray(v) for k, v in batch.items()},
            cfg,
        )
    )
    np.testing.assert_allclose(got, oracle_logits(wv, batch), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("packed", ["off", "auto"])
def test_sorted_step_matches_row_major(packed):
    """Full train-step equality between the sorted segment path and the
    row-major einsum path, across storage layouts."""
    cfg_s = ffm_cfg(**{"data.sorted_layout": "on", "data.packed_tables": packed,
                       "data.batch_size": 64, "data.max_nnz": 7})
    cfg_r = ffm_cfg(**{"data.sorted_layout": "off", "data.packed_tables": packed,
                       "data.batch_size": 64, "data.max_nnz": 7})
    model, opt = get_model("ffm"), get_optimizer("ftrl")
    rng = np.random.default_rng(1)
    batches = [rand_batch(rng, B=64) for _ in range(3)]

    from xflow_tpu.ops.sorted_table import plan_sorted_batch

    state_s = init_state(model, opt, cfg_s)
    state_r = init_state(model, opt, cfg_r)
    step_s = make_train_step(model, opt, cfg_s)
    step_r = make_train_step(model, opt, cfg_r)
    for b in batches:
        plan = plan_sorted_batch(b["slots"], b["mask"], S, fields=b["fields"])
        sorted_arrays = {
            "labels": jnp.asarray(b["labels"]),
            "row_mask": jnp.asarray(b["row_mask"]),
            "sorted_slots": jnp.asarray(plan.sorted_slots),
            "sorted_row": jnp.asarray(plan.sorted_row),
            "sorted_mask": jnp.asarray(plan.sorted_mask),
            "sorted_fields": jnp.asarray(plan.sorted_fields),
            "win_off": jnp.asarray(plan.win_off),
        }
        state_s, m_s = step_s(state_s, sorted_arrays)
        state_r, m_r = step_r(state_r, {k: jnp.asarray(v) for k, v in b.items()})
        np.testing.assert_allclose(
            float(m_s["loss"]), float(m_r["loss"]), rtol=2e-5
        )
    np.testing.assert_allclose(
        np.asarray(state_s.tables["wv"]).reshape(-1),
        np.asarray(state_r.tables["wv"]).reshape(-1),
        rtol=2e-4, atol=1e-6,
    )


def test_ffm_beats_fm_on_field_interaction_truth(tmp_path, monkeypatch):
    """BASELINE.json config 5's learnability gate: on field-PAIR
    interaction truth (non-separable sign structure — data/synth.py
    `_planted_ffm_truth`), FFM with k=4 beats a plain FM given MORE
    latent budget (k=16). SGD with a real init/lr: under the
    reference-default FTRL, v collapses toward 0 on first touch and
    interaction gradients (∝ v) cannot bootstrap for EITHER model."""
    from xflow_tpu.data.synth import generate_shards
    from xflow_tpu.train.trainer import Trainer

    monkeypatch.chdir(tmp_path)
    nf = 4
    generate_shards(str(tmp_path / "train"), 1, 12000, num_fields=nf,
                    ids_per_field=15, seed=0, noise=0.05, truth="ffm")
    generate_shards(str(tmp_path / "test"), 1, 3000, num_fields=nf,
                    ids_per_field=15, seed=99, noise=0.05, truth="ffm",
                    truth_seed=0)
    base = {
        "data.train_path": str(tmp_path / "train"),
        "data.test_path": str(tmp_path / "test"),
        "data.log2_slots": 13, "data.batch_size": 256, "data.max_nnz": 6,
        "model.num_fields": nf, "train.epochs": 30, "train.pred_dump": False,
        "optim.name": "sgd", "optim.sgd.lr": 0.5, "optim.v_init_sgd": 0.1,
    }
    aucs = {}
    for name, vd in (("ffm", 4), ("fm", 16)):
        cfg = override(Config(), **{**base, "model.name": name, "model.v_dim": vd})
        t = Trainer(cfg)
        t.fit()
        aucs[name], _ = t.evaluate(dump=False)
    assert aucs["ffm"] > 0.8, aucs
    assert aucs["ffm"] > aucs["fm"] + 0.02, aucs


def test_ffm_table_specs_and_init():
    """Fused [S, 1+nf·k] table; w column zero-init even in packed storage."""
    from xflow_tpu.models.base import init_tables
    from xflow_tpu.ops.sorted_table import unpack_table

    cfg = ffm_cfg()
    model = get_model("ffm")
    assert model.table_specs(cfg) == {"wv": (1 + NF * K_LAT,)}
    tables = init_tables(model, cfg, jax.random.PRNGKey(0))
    K = 1 + NF * K_LAT
    logical = np.asarray(unpack_table(tables["wv"], K))
    assert logical.shape == (S, K)
    assert np.all(logical[:, 0] == 0.0)  # w column
    assert np.std(logical[:, 1:]) > 0  # v blocks random


def aligned_batch(rng, B=64, nf=NF):
    """One occurrence per field (columns == fields), random subset
    masked — libffm's natural shape, what the aligned hybrid requires."""
    return {
        "slots": rng.integers(0, S, (B, nf)).astype(np.int32),
        "fields": np.broadcast_to(np.arange(nf, dtype=np.int32), (B, nf)).copy(),
        "mask": (rng.random((B, nf)) < 0.7).astype(np.float32),
        "labels": (rng.random(B) < 0.4).astype(np.float32),
        "row_mask": np.ones((B,), np.float32),
    }


def hybrid_arrays(b, nf=NF):
    from xflow_tpu.models.ffm import ffm_invperm
    from xflow_tpu.ops.sorted_table import plan_sorted_batch

    plan = plan_sorted_batch(b["slots"], b["mask"], S, fields=b["fields"])
    return {
        "labels": jnp.asarray(b["labels"]),
        "row_mask": jnp.asarray(b["row_mask"]),
        "sorted_slots": jnp.asarray(plan.sorted_slots),
        "sorted_row": jnp.asarray(plan.sorted_row),
        "sorted_mask": jnp.asarray(plan.sorted_mask),
        "sorted_fields": jnp.asarray(plan.sorted_fields),
        "win_off": jnp.asarray(plan.win_off),
        "ffm_invperm": jnp.asarray(
            ffm_invperm(plan.sorted_row, plan.sorted_fields,
                        plan.sorted_mask, b["labels"].shape[0], nf)
        ),
    }


@pytest.mark.parametrize("packed", ["off", "auto"])
@pytest.mark.parametrize("fused", ["auto", "off"])
def test_aligned_hybrid_step_matches_row_major(packed, fused):
    """Full train-step equality: the round-5 aligned hybrid (windowed
    gather + placement permutation + block-transposition row side, fused
    scatter+FTRL under `auto`) vs the row-major autodiff oracle path,
    across storage layouts and with/without the fused optimizer."""
    over = {"data.packed_tables": packed, "optim.fused_scatter": fused,
            "data.batch_size": 64, "data.max_nnz": NF}
    cfg_h = ffm_cfg(**{"data.sorted_layout": "on", **over})
    cfg_r = ffm_cfg(**{"data.sorted_layout": "off", **over})
    model, opt = get_model("ffm"), get_optimizer("ftrl")
    rng = np.random.default_rng(7)
    batches = [aligned_batch(rng) for _ in range(3)]
    state_h, state_r = init_state(model, opt, cfg_h), init_state(model, opt, cfg_r)
    step_h, step_r = make_train_step(model, opt, cfg_h), make_train_step(model, opt, cfg_r)
    for b in batches:
        state_h, m_h = step_h(state_h, hybrid_arrays(b))
        state_r, m_r = step_r(state_r, {k: jnp.asarray(v) for k, v in b.items()})
        np.testing.assert_allclose(float(m_h["loss"]), float(m_r["loss"]), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(state_h.tables["wv"]).reshape(-1),
        np.asarray(state_r.tables["wv"]).reshape(-1),
        rtol=2e-4, atol=1e-6,
    )
    for part in ("n", "z"):
        np.testing.assert_allclose(
            np.asarray(state_h.opt_state["wv"][part]).reshape(-1),
            np.asarray(state_r.opt_state["wv"][part]).reshape(-1),
            rtol=2e-4, atol=1e-6,
        )


def test_aligned_hybrid_untouched_slots_bitwise_initial():
    """FTRL lazy-init parity through the hybrid: slots no batch touches
    must keep their initial weights BITWISE (the pair term's hand-written
    VJP is exact at structural zeros — make_ffm_pair docstring)."""
    from xflow_tpu.ops.sorted_table import pack_of, unpack_table

    cfg = ffm_cfg(**{"data.sorted_layout": "on", "data.batch_size": 32,
                     "data.max_nnz": NF})
    model, opt = get_model("ffm"), get_optimizer("ftrl")
    rng = np.random.default_rng(11)
    b = aligned_batch(rng, B=32)
    state0 = init_state(model, opt, cfg)
    K = 1 + NF * K_LAT
    w0 = np.asarray(unpack_table(state0.tables["wv"], K))
    state, _ = make_train_step(model, opt, cfg)(state0, hybrid_arrays(b))
    w1 = np.asarray(unpack_table(state.tables["wv"], K))
    touched = np.zeros(S, bool)
    touched[np.unique(b["slots"][b["mask"] > 0])] = True
    assert (w1[~touched] == w0[~touched]).all(), "untouched slots moved"
    assert not np.array_equal(w1[touched], w0[touched])


def test_trainer_routes_ffm_sorted_and_falls_back_on_dup(tmp_path):
    """Trainer auto: FFM now takes the sorted hybrid; a duplicate-field
    batch runs the row-major fallback in the same run; sorted_layout=on
    rejects duplicate-field batches with the clear error."""
    from xflow_tpu.data.schema import SparseBatch
    from xflow_tpu.train.trainer import Trainer

    cfg = ffm_cfg(**{"data.batch_size": 16, "data.max_nnz": NF,
                     "train.metrics_path": str(tmp_path / "m.jsonl")})
    t = Trainer(cfg)
    assert t.engine == "sorted", "FFM auto should select the sorted hybrid now"
    rng = np.random.default_rng(3)
    b = aligned_batch(rng, B=16)
    sb = SparseBatch(slots=b["slots"], fields=b["fields"], mask=b["mask"],
                     labels=b["labels"], row_mask=b["row_mask"])
    arrays = t._engine.batch_arrays(sb)
    assert "ffm_invperm" in arrays and "sorted_slots" in arrays
    dup = dict(b)
    dup["fields"] = dup["fields"].copy()
    dup["fields"][:, 1] = 0  # field 0 twice
    dup["mask"] = np.ones_like(dup["mask"])
    sbd = SparseBatch(slots=dup["slots"], fields=dup["fields"], mask=dup["mask"],
                      labels=dup["labels"], row_mask=dup["row_mask"])
    arrays_dup = t._engine.batch_arrays(sbd)
    assert "sorted_slots" not in arrays_dup and "slots" in arrays_dup

    t_on = Trainer(override(cfg, **{"data.sorted_layout": "on"}))
    with pytest.raises(ValueError, match="aligned"):
        t_on._engine.batch_arrays(sbd)


def _placed_rows(rng, B, nf, k):
    """A [B, nfp, k8] as the placement hands it over: w in column 0, nf
    k-blocks, zero in every pad; a fifth of the (row, field) pairs
    absent, row 0 with one occupant only and the last row empty (a pad
    row of a short batch)."""
    from xflow_tpu.models.ffm import nf_padded
    from xflow_tpu.ops.sorted_table import _k8

    nfp, K = nf_padded(nf), 1 + nf * k
    present = rng.random((B, nf)) < 0.8
    present[0] = False
    present[0, nf // 2] = True
    present[-1] = False
    A = np.zeros((B, nfp, _k8(K)), np.float32)
    A[:, :nf, :K] = rng.normal(0, 0.5, (B, nf, K)).astype(np.float32) * present[..., None]
    return A, present


def _pair_loop(A64, nf, k):
    """wx + sum over field pairs c < d of <v_c against d, v_d against c>
    — the textbook sum over the pairs a double loop lists (taken in one
    indexed read: 741 pairs of slices at 39 fields take XLA half a
    minute to compile); an absent field's rows are 0."""
    pairs = np.array([(c, d) for c in range(nf) for d in range(c + 1, nf)], np.int32).reshape(-1, 2)
    V = A64[:, :nf, 1: 1 + nf * k].reshape(A64.shape[0], nf, nf, k)
    c, d = pairs[:, 0], pairs[:, 1]
    return A64[:, :nf, 0].sum(axis=1) + (V[:, c, d] * V[:, d, c]).sum(axis=(1, 2))


@pytest.mark.parametrize("nf,k", [(39, 4), (8, 4), (5, 3), (1, 2)])
def test_aligned_pair_is_a_block_transposition(nf, k):
    """The aligned op's pair term at each width: the crossing X is
    bitwise the copied elements of A and zero in every pad; logits and
    the hand-written VJP match a float64 double loop over pairs and
    jax.grad of it; a single-occupant field's own block gets a gradient
    of bitwise 0 (FTRL's lazy-init guard); d_A is 0 in every pad apart
    from the w channel."""
    from xflow_tpu.models.ffm import cross_fields, make_ffm_pair

    rng = np.random.default_rng(nf * 10 + k)
    B = 6
    A, present = _placed_rows(rng, B, nf, k)
    K = 1 + nf * k
    X = np.asarray(cross_fields(jnp.asarray(A), nf, k))
    want = np.zeros_like(A)
    for c in range(nf):
        for d in range(nf):
            want[:, d, 1 + c * k: 1 + (c + 1) * k] = A[:, c, 1 + d * k: 1 + (d + 1) * k]
    assert X.shape == A.shape and X.tobytes() == want.tobytes()

    pair = make_ffm_pair(nf, k)
    dl = rng.normal(0, 1, B).astype(np.float32)
    logits, vjp = jax.vjp(pair, jnp.asarray(A))
    (d_A,) = vjp(jnp.asarray(dl))
    d_A = np.asarray(d_A)
    with jax.enable_x64(True):
        A64, dl64 = jnp.asarray(A, jnp.float64), jnp.asarray(dl, jnp.float64)
        ref = np.asarray(_pair_loop(A64, nf, k))
        ref_grad = np.asarray(jax.grad(lambda a: (_pair_loop(a, nf, k) * dl64).sum())(A64))
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=1e-5, atol=1e-5)
    # the oracle differentiates w of absent fields too (their A is 0,
    # not their w channel): compare where the op defines the gradient
    real = np.zeros_like(A, bool)
    real[:, :nf, 1:K] = True
    np.testing.assert_allclose(d_A[real], ref_grad[real], rtol=1e-5, atol=1e-6)
    assert (d_A[:, :nf, 0] == dl[:, None]).all()
    c = nf // 2  # row 0's only occupant: nothing to pair with, so its
    # own block (X - A·Q, a copy minus itself) and every other are 0
    assert present[0].sum() == 1
    assert (d_A[0, c, 1:] == 0).all() and np.asarray(logits)[0] == A[0, c, 0]
    assert (d_A[:, nf:] == 0).all() and (d_A[:, :, K:] == 0).all()


def test_aligned_pair_costs_no_selector_product():
    """At Criteo's width the compiled value-and-gradient of the aligned
    op is data movement and 3 elementwise passes: XLA counts under 1e8
    FLOPs at 256 rows where a [6400 x 6400] selector product and its
    transpose count 4.2e10 — the quadratic form cannot come back
    unseen, and no dot is left at all."""
    from xflow_tpu.models.ffm import ffm_invperm, make_ffm_aligned_op, nf_padded
    from xflow_tpu.ops.sorted_table import _k8, padded_len

    nf, k, rows = 39, 4, 256
    nfp, k8 = nf_padded(nf), _k8(1 + nf * k)
    Np = padded_len(rows * nf)
    rng = np.random.default_rng(0)
    order = rng.permutation(rows * nf)
    sorted_row = np.zeros(Np, np.int32)
    sorted_fields = np.zeros(Np, np.int32)
    smask = np.zeros(Np, np.float32)
    sorted_row[: rows * nf], sorted_fields[: rows * nf] = order // nf, order % nf
    smask[: rows * nf] = 1
    inv = ffm_invperm(sorted_row, sorted_fields, smask, rows, nf)
    op = make_ffm_aligned_op(nf, k, k8, rows)
    fn = jax.jit(jax.value_and_grad(
        lambda occ_t, inv, src, sm: op(occ_t, inv, src, sm).sum()
    ))
    compiled = fn.lower(
        jnp.zeros((k8, Np), jnp.float32), jnp.asarray(inv),
        jnp.asarray(sorted_row * nfp + sorted_fields), jnp.asarray(smask),
    ).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["flops"] < 1e8, cost["flops"]
    text = compiled.as_text()
    assert " dot(" not in text and " convolution(" not in text
