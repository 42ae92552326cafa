"""The plain reference's served answer: pCTR of request rows from the
seed's weights, touched slots only.

    pctr = sigmoid(logit), with the upstream's clamps (pandadady/xflow
    src/base/base.h:54-63): logit < -30 gives 1e-6, logit > 30 gives 1.

Imports nothing of the program and takes nothing it has made: ids come
from the generator, slots from the benchmark's own copy of the hash
rule, weights from `lib/weights.py` evaluated at the touched slots, the
logit from the model's `reference/<model>.py logits` in float32
`jax.numpy`, the sigmoid on the host in float64. `dtype` other than
float32 turns it into the control: rows, sums, logit and sigmoid in
that type. `fault` plants one of the serving faults in it.
"""

from __future__ import annotations

import functools
import json

import numpy as np

from .core import model_module

BLOCK_ROWS = 32768  # rows a block: one compiled shape, [BLOCK_ROWS, F, width] float32 at a time
FAULTS = ("other_table", "field_left_out", "rows_rotated")


def pctr_of_logits(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    with np.errstate(over="ignore"):
        p = 1.0 / (1.0 + np.exp(-x))
    return np.where(x < -30.0, 1e-6, np.where(x > 30.0, 1.0, p))


@functools.lru_cache(maxsize=None)
def _block_fn(cfg_json: str, dtype: str):
    """rows [BLOCK_ROWS, F, width], occurrence weights [F] -> float32
    [BLOCK_ROWS]: the logit (float32) or, in the control's type, its
    sigmoid. One compiled program for every call with this model."""
    import jax
    import jax.numpy as jnp

    cfg = json.loads(cfg_json)
    model, dt = model_module(cfg["reference"]), jnp.dtype(dtype)

    def block(rows, occ_w):
        x = model.logits(rows.astype(dt) * occ_w.astype(dt)[None, :, None], cfg)
        if dt != jnp.float32:  # the control's sigmoid is in its own type too
            x = jax.nn.sigmoid(x)
        return x.astype(jnp.float32)

    return jax.jit(block)


def serve_pctr(cfg: dict, seed: int, ids: np.ndarray, slots_of, init_rows,
               dtype: str = "float32", fault: str | None = None,
               offsets: np.ndarray | None = None) -> np.ndarray:
    """float32 [rows]: the pCTR of each row of `ids` [rows, F] (one
    feature a field, value 1).

    Faults: "other_table" (the weights of seed + 1), "field_left_out"
    (the last field's feature dropped from every row), "rows_rotated"
    (with `offsets` [requests + 1] into the rows: each request's answers
    rotated by one row, what a wrong slice in the scatter-back does)."""
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault={fault!r}")
    width = model_module(cfg["reference"]).width(cfg)
    block = _block_fn(json.dumps({k: cfg[k] for k in ("reference", "v_dim", "num_fields")}, sort_keys=True), dtype)
    scale = float(cfg.get("v_init_scale", 0.0))
    table_seed = seed + 1 if fault == "other_table" else seed
    occ = np.ones(ids.shape[1], np.float32)
    if fault == "field_left_out":
        occ[-1] = 0.0

    out = np.empty(len(ids), np.float32)
    for lo in range(0, len(ids), BLOCK_ROWS):
        part = ids[lo:lo + BLOCK_ROWS]
        slots = slots_of(part, int(cfg["log2_slots"]))
        touched, idx = np.unique(slots.reshape(-1), return_inverse=True)
        rows = init_rows(table_seed, touched, width, scale)[idx.reshape(slots.shape)]
        if len(part) < BLOCK_ROWS:
            rows = np.concatenate([rows, np.zeros((BLOCK_ROWS - len(part),) + rows.shape[1:], np.float32)])
        x = np.asarray(block(jnp.asarray(rows), jnp.asarray(occ)))[:len(part)]
        out[lo:lo + len(part)] = pctr_of_logits(x) if dtype == "float32" else x
    if fault == "rows_rotated":
        for a, b in zip(offsets[:-1], offsets[1:]):
            out[a:b] = np.roll(out[a:b], 1)
    return out
