"""Optimizer interface.

In the reference the optimizer runs *on the server* as a ps-lite
request handler mutating per-key entries in a hash map
(`/root/reference/src/model/server.h:23-29` installs the handles from
`src/optimizer/ftrl.h` / `sgd.h`); workers only push raw gradients.
Here the optimizer is a pure elementwise function over dense state
arrays, compiled into the train step. Because FTRL's closed-form w is a
deterministic function of (z, n) and a zero gradient leaves (z, n)
unchanged, applying the update to every slot is a no-op for untouched
slots — so no touched-mask is needed and XLA fuses the whole update
with the gradient scatter.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict

from xflow_tpu.config import Config


@dataclass(frozen=True)
class Optimizer:
    """A registered optimizer.

    Contract: a zero gradient is the identity —
    `apply(tables, state, zeros, cfg)` returns `tables` and `state`
    unchanged in value. The dense sweep leans on it for every slot a
    batch does not touch, and the non-finite guard
    (`train/step.py guard_nonfinite`) leans on it to discard a bad step:
    it hands `apply` a zero gradient and keeps no copy of the old state.
    An optimizer for which that does not hold (momentum, weight decay)
    must not be registered without giving the guard another way to
    discard; `tests/test_train_step.py` holds every registered optimizer
    to it.
    """

    name: str
    # tables -> opt_state pytree (dict per table)
    init_state: Callable
    # (tables, opt_state, grads, cfg) -> (new_tables, new_opt_state)
    apply: Callable


_REGISTRY: Dict[str, Optimizer] = {}


def register_optimizer(opt: Optimizer) -> Optimizer:
    _REGISTRY[opt.name] = opt
    return opt


def get_optimizer(name: str) -> Optimizer:
    if name not in _REGISTRY:
        raise KeyError(f"unknown optimizer {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]
