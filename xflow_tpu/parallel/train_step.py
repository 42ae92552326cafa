"""Sharded train/eval steps.

The single-device step (train/step.py) IS the multi-device step: the
program is written once over logical arrays, shardings are attached to
the inputs, and GSPMD partitions the computation — the table gather
(Pull) and its scatter-add transpose (Push) lower to cross-chip
collectives over ICI/DCN, and the loss/metric reductions to psums.
This is the design center of the rebuild (SURVEY.md §2 C13): where the
reference hand-routes sparse KV RPC over ZeroMQ, here the compiler
emits the communication from sharding annotations.

Explicit in/out shardings are passed to `jax.jit` so the step never
silently falls back to replicated tables, and the donated input state
buffer is reused for the output (in-place HBM update, like the server's
in-place hash-map mutation — but functional).
"""

from __future__ import annotations

from typing import Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xflow_tpu.compile_cache import past_cache
from xflow_tpu.config import Config
from xflow_tpu.models.base import Model
from xflow_tpu.optim.base import Optimizer
from xflow_tpu.parallel.mesh import batch_sharding, replicated, state_shardings
from xflow_tpu.train.state import TrainState
from xflow_tpu.train.step import make_train_step, make_eval_step, metrics_keys


def shard_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place an (unsharded) TrainState onto the mesh's table sharding."""
    shardings = state_shardings(state, mesh)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), state, shardings)


def make_sharded_train_step(
    model: Model, optimizer: Optimizer, cfg: Config, mesh: Mesh, recorder=None,
    state_formats=None,
) -> Callable:
    """The GSPMD step. `state_formats` (state -> train/engine.py
    `state_formats`, or None): the fullshard engine's fallback takes and
    returns the state in that engine's layout."""
    step = make_train_step(model, optimizer, cfg, jit=False, allow_fused=False)
    # state shardings depend only on pytree structure; build from a spec of
    # the real state at first call via jit's lazy specialization
    bsh = batch_sharding(mesh)

    def sharded(state: TrainState, batch: dict):
        # the step's phases (gather / rows / scatter / update / health)
        # are make_train_step's scopes; the collectives the compiler puts
        # in come with the path of what they serve, and the compile
        # record's phase map books every collective to `exchange`
        # (telemetry.op_phases)
        return step(state, batch)

    # the non-finite guard's update_ok flag rides in the metrics dict
    # (train/step.py metrics_keys), replicated like loss/rows
    out_metrics_sh = {k: replicated(mesh) for k in metrics_keys(cfg)}

    def wrap(state: TrainState, batch: dict, formats):
        # the same shardings, the packed leaves' layout pinned
        ssh = formats or state_shardings(state, mesh)
        return jax.jit(
            sharded,
            # subset to the batch's actual keys: jit in_shardings must
            # match the pytree exactly, and batch_sharding carries entries
            # for optional arrays (sorted plans) too
            in_shardings=(ssh, {k: bsh[k] for k in batch}),
            out_shardings=(ssh, out_metrics_sh),
            donate_argnums=(0,),
        )

    # cache the jitted fn per batch-key set (state structure is fixed);
    # the compile recorder (one shared program name — signatures tell
    # the key sets apart) gives each set its kind="compile" record
    cache = {}

    def call(state: TrainState, batch: dict):
        key = frozenset(batch)
        if key not in cache:
            formats = state_formats(state) if state_formats is not None else None
            jitted = wrap(state, batch, formats)
            if formats is not None:  # it hands back pinned leaves
                jitted = past_cache(jitted)
            cache[key] = (
                recorder.wrap("train_step.gspmd", jitted)
                if recorder is not None
                else jitted
            )
        return cache[key](state, batch)

    return call


def make_sharded_eval_step(
    model: Model, cfg: Config, mesh: Mesh, recorder=None
) -> Callable:
    ev = make_eval_step(model, cfg, jit=False)
    bsh = batch_sharding(mesh)
    cache = {}

    def call(tables, batch):
        # accept the tables AS SHARDED (jit with explicit in_shardings
        # rejects mismatches instead of resharding): the GSPMD eval
        # forward partitions fine under either the default
        # P(('data','table')) layout or the sorted engine's
        # P('table', None). The live shardings are part of the cache key:
        # a restore/device_put that reshards the tables mid-lifetime gets
        # a fresh jit instead of an in_shardings mismatch error (advisor r2).
        tsh = jax.tree.map(
            lambda x: x.sharding if hasattr(x, "sharding") else replicated(mesh),
            tables,
        )
        key = (frozenset(batch), tuple(jax.tree.leaves(tsh)))
        if key not in cache:
            jitted = jax.jit(
                ev,
                in_shardings=(tsh, {k: bsh[k] for k in batch}),
                out_shardings=NamedSharding(mesh, P("data")),
            )
            cache[key] = (
                recorder.wrap("predict.gspmd", jitted)
                if recorder is not None
                else jitted
            )
        return cache[key](tables, batch)

    return call
