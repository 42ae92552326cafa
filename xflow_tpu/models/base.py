"""Model interface.

A model is a set of named parameter tables plus a pure forward function
from (tables, batch) to logits. Tables are dense ``[num_slots]`` or
``[num_slots, v_dim]`` arrays sharded on the slot axis (the TPU analog
of ps-lite's key-range-sharded server tables, SURVEY.md §2 C2/C13).
Gradients come from `jax.grad` through the table gathers — the gather
is the reference's Pull, its transpose (scatter-add) is the Push.

The reference's model zoo and table usage:
- LR: table w (dim 1)            (`/root/reference/src/model/lr/`)
- FM: tables w (dim 1) + v (dim k) (`/root/reference/src/model/fm/`)
- MVM: table v (dim k) only        (`/root/reference/src/model/mvm/`,
  pushes only v: `mvm_worker.cc:270`)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from xflow_tpu.config import Config


@dataclass(frozen=True)
class Model:
    name: str
    # table name -> trailing dims ( () for scalar table, (v_dim,) for latent )
    table_specs: Callable[[Config], Dict[str, tuple]]
    # (tables, batch_arrays, cfg) -> logits [B]
    forward: Callable


_REGISTRY: Dict[str, Model] = {}


def register_model(model: Model) -> Model:
    _REGISTRY[model.name] = model
    return model


def get_model(name: str) -> Model:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def init_tables(model: Model, cfg: Config, key: jax.Array) -> Dict[str, jax.Array]:
    """Build dense parameter tables.

    w-tables init to 0 (reference: default-constructed FTRL entries,
    `ftrl.h:27-36`). v-tables init ~N(0,1)*v_init_scale for FTRL
    (`ftrl.h:117`) or constant v_init_sgd for SGD (`sgd.h:69`) — the
    reference does this lazily per touched key; dense pre-init is
    equivalent because the FTRL update preserves never-touched slots
    (g=0 ∧ n=0 keeps w, see `optim/ftrl.py:_update_one`) and SGD with
    g=0 is a no-op.
    """
    from xflow_tpu.ops.sorted_table import PACK

    # packed [S/8, 8K] storage for vector tables (pack_table docstring:
    # the (8,128) HBM tiling makes logical [S, 11] storage 11.6x its
    # bytes). Created DIRECTLY in packed shape — building [S, K] first
    # and reshaping would materialize the padded buffer this exists to
    # avoid. The init distribution is elementwise iid, so the packed
    # init is distribution-identical (not bitwise: the RNG->element map
    # differs between layouts).
    mode = cfg.data.packed_tables
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"data.packed_tables={mode!r}: expected auto|on|off")
    if mode == "on" and cfg.num_slots % PACK != 0:
        raise ValueError(
            f"data.packed_tables=on needs num_slots divisible by {PACK}; "
            f"got 2^{cfg.data.log2_slots}"
        )
    pack = PACK if mode != "off" and cfg.num_slots % PACK == 0 else 1
    tables = {}
    specs = model.table_specs(cfg)
    for tname, trailing in sorted(specs.items()):
        if trailing == ():
            tables[tname] = jnp.zeros((cfg.num_slots,), dtype=jnp.float32)
            continue
        K = trailing[0]
        shape = (cfg.num_slots // pack, pack * K)
        key, sub = jax.random.split(key)
        if cfg.optim.name == "sgd":
            t = jnp.full(shape, cfg.optim.v_init_sgd, dtype=jnp.float32)
        else:
            # the barrier keeps the scale a multiply of its own: inside a
            # jit (train/state.py build_state) XLA would fold it into the
            # sampler's constants and move values by an ulp, and a state
            # built sharded must carry the eager init's bits
            t = jax.lax.optimization_barrier(
                jax.random.normal(sub, shape, dtype=jnp.float32)
            ) * cfg.optim.v_init_scale
        if tname == "wv":
            # fused FM layout: logical column 0 is the linear w (zero-init
            # like a scalar w-table) — every pack*K-row position j with
            # j % K == 0 in packed storage
            t = t.at[:, ::K].set(0.0) if pack > 1 else t.at[:, 0].set(0.0)
        tables[tname] = t
    return tables
