"""The plain reference's training loop: touched slots only, float32
`jax.numpy`, FTRL-proximal from the published equations.

Imports nothing of the program and takes nothing it has made: rows come
from the generator, slots from the benchmark's own copy of the hash
rule, initial weights from `lib/weights.py` evaluated at the touched
slots. `dtype` other than float32 turns it into the control: the same
arithmetic with every table, gather, sum and update in that type.

FTRL-proximal (McMahan et al. 2013; pandadady/xflow src/optimizer/ftrl.h),
per element with gradient g:
    n' = n + g*g
    z' = z + g - (sqrt(n') - sqrt(n)) / alpha * w
    w' = 0 if |z'| <= lambda1 else -(z' - sign(z') lambda1) / ((beta + sqrt(n')) / alpha + lambda2)
and an element that has never had a gradient keeps its initial weight
(the original creates an entry only when a key is first pushed).
"""

from __future__ import annotations

import importlib

import numpy as np


def model_module(name: str):
    return importlib.import_module(f"{__package__}.{name}")


def _ftrl(w, n, z, g, hp, jnp):
    dt = w.dtype
    alpha, beta, l1, l2 = (jnp.asarray(hp[k], dt) for k in ("alpha", "beta", "lambda1", "lambda2"))
    n2 = n + g * g
    z2 = z + g - (jnp.sqrt(n2) - jnp.sqrt(n)) / alpha * w
    w2 = jnp.where(
        jnp.abs(z2) <= l1, jnp.zeros((), dt),
        -(z2 - jnp.sign(z2) * l1) / ((beta + jnp.sqrt(n2)) / alpha + l2),
    )
    w2 = jnp.where((g == 0) & (n == 0), w, w2)
    return w2, n2, z2


def make_step(model, cfg: dict, dt):
    """One jitted training step over the touched slots' local table:
    (table, n, z), idx [B, F], labels, row weights, occurrence weights
    -> (new state, loss, gradient)."""
    import jax
    import jax.numpy as jnp

    hp = cfg["ftrl"]

    def step(state, idx, labels, row_w, occ_w):
        table, n, z = state

        def loss_fn(t):
            x = model.logits(t[idx] * occ_w[..., None], cfg).astype(jnp.float32)
            per = jax.nn.softplus(x) - labels * x
            return (per * row_w).sum() / row_w.sum()

        loss, g = jax.value_and_grad(loss_fn)(table)
        w2, n2, z2 = _ftrl(table, n, z, g.astype(dt), hp, jnp)
        return (w2, n2, z2), loss, g

    return jax.jit(step)


def run_steps(cfg: dict, seed: int, batches: list, slots_of, init_rows,
              dtype: str = "float32", fault: str | None = None) -> dict:
    """Follow `batches` [(ids [B, F], labels [B]), ...] from the seed's
    initial weights. Returns each step's loss, the first gradient's norm
    and the parameters' change after the last step, by leaf.

    `fault` plants one of the harness's faults in the reference itself:
    "half_batch" (the second half of every batch left out, the mean
    taken over the rest) or "no_exchange" (with the rows split over
    `chips` data shards and the slots over as many owners, an occurrence
    counts only where its row's shard owns its slot)."""
    import jax
    import jax.numpy as jnp

    model = model_module(cfg["reference"])
    width = model.width(cfg)
    dt = jnp.dtype(dtype)
    log2 = int(cfg["log2_slots"])
    slot_lists = [slots_of(ids, log2) for ids, _ in batches]
    touched = np.unique(np.concatenate([s.reshape(-1) for s in slot_lists]))
    w0 = jnp.asarray(init_rows(seed, touched, width, float(cfg.get("v_init_scale", 0.0))))
    step = make_step(model, cfg, dt)
    state = (w0.astype(dt), jnp.zeros_like(w0, dt), jnp.zeros_like(w0, dt))
    losses, grad0 = [], None
    for (ids, labels), slots in zip(batches, slot_lists):
        B = ids.shape[0]
        row_w = np.ones(B, np.float32)
        occ_w = np.ones(ids.shape, np.float32)
        if fault == "half_batch":
            row_w[B // 2:] = 0.0
        elif fault == "no_exchange":
            chips = int(cfg.get("chips", 4))
            shard = (np.arange(B) * chips // B)[:, None]
            owner = slots >> (log2 - int(np.log2(chips)))
            occ_w = (owner == shard).astype(np.float32)
        elif fault is not None:
            raise ValueError(f"fault={fault!r}")
        idx = np.searchsorted(touched, slots).astype(np.int32)
        state, loss, g = step(state, jnp.asarray(idx), jnp.asarray(labels, jnp.float32),
                              jnp.asarray(row_w), jnp.asarray(occ_w, dt))
        losses.append(float(loss))
        if grad0 is None:
            grad0 = np.asarray(g.astype(jnp.float32), np.float64)
    delta = np.asarray(state[0].astype(jnp.float32), np.float64) - np.asarray(w0, np.float64)
    leaves = model.leaves(cfg)
    return {
        "loss": losses,
        "grad_norm": {k: float(np.sqrt((grad0[:, c] ** 2).sum())) for k, c in leaves.items()},
        "delta_norm": {k: float(np.sqrt((delta[:, c] ** 2).sum())) for k, c in leaves.items()},
        "touched_slots": int(touched.size),
    }
