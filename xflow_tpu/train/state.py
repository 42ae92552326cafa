"""Training state: parameter tables + optimizer state + step counter.

This is the TPU-resident analog of the reference's *server* state —
the per-key FTRL entries in `std::unordered_map<ps::Key, Entry>`
(`/root/reference/src/optimizer/ftrl.h:84,151`) — as a pytree of dense
sharded arrays. Unlike the reference (which never serializes it,
SURVEY.md §5 "Checkpoint / resume: absent"), this state is a plain
pytree and checkpoints via train/checkpoint.py.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from xflow_tpu.config import Config
from xflow_tpu.models.base import Model, init_tables
from xflow_tpu.optim.base import Optimizer


class TrainState(NamedTuple):
    tables: Dict[str, jax.Array]
    opt_state: Dict[str, Any]
    step: jax.Array  # int32 scalar


def init_state(model: Model, optimizer: Optimizer, cfg: Config, seed: int | None = None) -> TrainState:
    key = jax.random.PRNGKey(cfg.train.seed if seed is None else seed)
    tables = init_tables(model, cfg, key)
    return TrainState(
        tables=tables,
        opt_state=optimizer.init_state(tables),
        step=jnp.zeros((), dtype=jnp.int32),
    )


def build_state(
    model: Model,
    optimizer: Optimizer,
    cfg: Config,
    shardings: Optional[Callable[[TrainState], Any]] = None,
) -> TrainState:
    """`init_state`'s values, every leaf born in its sharding.

    One jitted call with `out_shardings`: the compiler partitions the
    random bits and the zero fills, so each device makes only its own
    shard and no leaf (nor a temporary of a leaf's size) ever exists
    whole on one device — a mesh trains a state no single chip holds.
    `shardings` maps the abstract state (`jax.eval_shape`, nothing
    allocated) to a matching pytree of shardings; None leaves the state
    on the default device. The values are `init_state`'s bit for bit
    (partitionable threefry: the same key gives the same bits sharded
    and unsharded; tests/test_state_build.py)."""

    def init() -> TrainState:
        return init_state(model, optimizer, cfg)

    out = None if shardings is None else shardings(jax.eval_shape(init))
    return jax.jit(init, out_shardings=out)()
