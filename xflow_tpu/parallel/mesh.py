"""Device mesh and sharding specs.

The reference's process topology — N async workers × M key-range-sharded
servers (ps-lite; SURVEY.md §1 "Parallelism topology") — maps onto a
2-D ``('data', 'table')`` mesh:

- the ``data`` axis is the worker tier: the batch is split across it
  (synchronous data parallelism instead of hogwild async);
- the ``table`` axis is the server tier: parameter/optimizer tables are
  sharded on the feature-slot axis.

Tables are sharded over *both* axes (``P(('data','table'))``) so every
chip holds 1/(D·T) of each table — the 1B-feature FTRL state of the
north-star config only fits HBM fully sharded (SURVEY.md §7 hard part
d). GSPMD then lowers the step's gather/scatter into the ICI
collectives that replace ps-lite's ZMQ Push/Pull RPC.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xflow_tpu.config import Config

DATA_AXIS = "data"
TABLE_AXIS = "table"


def make_mesh(cfg: Config, devices=None) -> Mesh:
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = devices.size
    d, t = cfg.mesh.data, cfg.mesh.table
    if d == -1 and t == -1:
        d, t = n, 1
    elif d == -1:
        d = n // t
    elif t == -1:
        t = n // d
    if d * t != n:
        raise ValueError(f"mesh {d}x{t} != {n} devices")
    return Mesh(devices.reshape(d, t), (DATA_AXIS, TABLE_AXIS))


def table_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Slot axis fully sharded over the whole mesh; trailing dims replicated."""
    spec = ((DATA_AXIS, TABLE_AXIS),) + (None,) * (ndim - 1)
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> dict:
    """Batch arrays split on the leading (row) axis over the data axis."""
    row2d = NamedSharding(mesh, P(DATA_AXIS, None))
    row1d = NamedSharding(mesh, P(DATA_AXIS))
    # fullshard buffers [D_src, T, D_dst, cap]: source shard on 'data',
    # destination column on 'table' (parallel/sorted_fullshard.py)
    fs4 = NamedSharding(mesh, P(DATA_AXIS, TABLE_AXIS, None, None))
    return {
        "slots": row2d,
        "fields": row2d,
        "mask": row2d,
        "labels": row1d,
        "row_mask": row1d,
        "fs_slots": fs4,
        "fs_row": fs4,
        "fs_mask": fs4,
        "fs_off": fs4,
        "fs_fields": fs4,
        # host-dedup arrays (data.dedup): the unique set is global to the
        # batch (replicated); the inverse indexes per row
        "unique_slots": NamedSharding(mesh, P()),
        "inverse": row2d,
    }


def state_shardings(state, mesh: Mesh):
    """A pytree of NamedShardings matching a TrainState."""

    def spec(leaf):
        if getattr(leaf, "ndim", 0) >= 1:
            n = getattr(leaf, "shape", (0,))[0]
            if n % mesh.size != 0:
                # fail here with a framework message instead of deep
                # inside XLA partitioning
                raise ValueError(
                    f"table slot count {n} is not divisible by the mesh size "
                    f"{mesh.size} ({dict(mesh.shape)}); pick data.log2_slots "
                    "so 2^log2_slots is a multiple of data*table"
                )
            return table_sharding(mesh, leaf.ndim)
        return replicated(mesh)

    return jax.tree.map(spec, state)
