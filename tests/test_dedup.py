"""Host-side batch dedup for the row-major paths (data.dedup,
ops/sorted_table.dedup_slots — the reference's per-minibatch unique-key
Pull, lr_worker.cc:150-165, as a two-level device gather)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.models import get_model
from xflow_tpu.ops.sorted_table import dedup_slots
from xflow_tpu.optim import get_optimizer
from xflow_tpu.train.state import init_state
from xflow_tpu.train.step import make_train_step

LOG2 = 12
S = 1 << LOG2
B, F = 64, 8


def _zipf_batch(rng, hot=32):
    """Heavily skewed slots: most occurrences hit `hot` ids."""
    slots = np.where(
        rng.random((B, F)) < 0.9,
        rng.integers(0, hot, (B, F)),
        rng.integers(0, S, (B, F)),
    ).astype(np.int32)
    return {
        "slots": slots,
        "fields": np.broadcast_to(np.arange(F, dtype=np.int32), (B, F)).copy(),
        "mask": (rng.random((B, F)) < 0.9).astype(np.float32),
        "labels": (rng.random(B) < 0.4).astype(np.float32),
        "row_mask": np.ones((B,), np.float32),
    }


def test_dedup_slots_roundtrip_and_overflow():
    rng = np.random.default_rng(0)
    slots = rng.integers(0, 50, (B, F)).astype(np.int32)
    got = dedup_slots(slots, cap=64)
    assert got is not None
    u, inv = got
    assert u.shape == (64,)
    np.testing.assert_array_equal(u[inv], slots)  # exact reconstruction
    assert dedup_slots(slots, cap=16) is None  # overflow -> caller falls back


@pytest.mark.parametrize("model_name", ["lr", "fm", "mvm"])
def test_dedup_training_equality(model_name):
    """A few FTRL steps with and without the deduped gather end at
    identical tables (the two-level gather is the same math)."""
    cfg = override(
        Config(),
        **{
            "model.name": model_name,
            "model.num_fields": F,
            "model.v_dim": 3,
            "data.log2_slots": LOG2,
            "data.batch_size": B,
            "data.max_nnz": F,
            "data.sorted_layout": "off",  # force the row-major path
        },
    )
    model, opt = get_model(model_name), get_optimizer("ftrl")
    rng = np.random.default_rng(1)
    batches = [_zipf_batch(rng) for _ in range(3)]
    step = make_train_step(model, opt, cfg)

    states = {}
    for dedup in (False, True):
        st = init_state(model, opt, cfg)
        for b in batches:
            arrays = {k: jnp.asarray(v) for k, v in b.items()}
            if dedup:
                u, inv = dedup_slots(b["slots"], cap=B * F // 2)
                arrays["unique_slots"] = jnp.asarray(u)
                arrays["inverse"] = jnp.asarray(inv)
            st, _ = step(st, arrays)
        states[dedup] = st
    for n in states[False].tables:
        np.testing.assert_allclose(
            np.asarray(states[True].tables[n]),
            np.asarray(states[False].tables[n]),
            rtol=1e-6, atol=1e-7,
            err_msg=f"{model_name} table {n} diverged under dedup",
        )


def test_trainer_first_batch_decides(tmp_path):
    from xflow_tpu.data.schema import SparseBatch
    from xflow_tpu.train.trainer import Trainer

    cfg = override(
        Config(),
        **{
            "model.name": "lr",
            "model.num_fields": F,
            "data.log2_slots": LOG2,
            "data.batch_size": B,
            "data.max_nnz": F,
        },
    )
    rng = np.random.default_rng(2)

    def sb(slots):
        return SparseBatch(
            slots=slots,
            fields=np.zeros((B, F), np.int32),
            mask=np.ones((B, F), np.float32),
            labels=np.zeros((B,), np.float32),
            row_mask=np.ones((B,), np.float32),
        )

    # dedup default is OFF (measured single-chip loss; docs/PERF.md)
    skewed = sb(np.zeros((B, F), np.int32))
    assert "unique_slots" not in Trainer(cfg)._engine.batch_arrays(skewed)
    cfg = override(cfg, **{"data.dedup": "auto"})
    # skewed first batch -> dedup on and attached
    tr = Trainer(cfg)
    arrays = tr._engine.batch_arrays(skewed)
    assert "unique_slots" in arrays and "slots" not in arrays
    # near-uniform FIRST batch -> decided off for the run: later batches
    # skip the host sort entirely (even skewed ones)
    tr2 = Trainer(cfg)
    distinct = np.arange(B * F, dtype=np.int32).reshape(B, F)
    arrays = tr2._engine.batch_arrays(sb(distinct))
    assert "unique_slots" not in arrays
    arrays = tr2._engine.batch_arrays(skewed)
    assert "unique_slots" not in arrays
    # explicit off disables entirely
    tr3 = Trainer(override(cfg, **{"data.dedup": "off"}))
    assert "unique_slots" not in tr3._engine.batch_arrays(skewed)
