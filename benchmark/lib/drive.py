"""The system under test: `xflow train`'s body, built and driven the way
`xflow_tpu/launch/cli.py cmd_train` builds and drives it.

The only file of the benchmark that imports the program. What it takes
from it: `Config`/`override` (the CLI's own override path), `make_mesh`,
`Trainer`, `Trainer.fit()`, the state's table and FTRL leaves, the
compile recorder's count, the metrics JSONL.
"""

from __future__ import annotations

import json
import os
import time

# a configuration file's plain keys -> the program's dotted config keys
KEYMAP = {
    "model": "model.name",
    "v_dim": "model.v_dim",
    "num_fields": "model.num_fields",
    "max_nnz": "data.max_nnz",
    "log2_slots": "data.log2_slots",
    "batch_size": "data.batch_size",
    "optimizer": "optim.name",
    "v_init_scale": "optim.v_init_scale",
}
FTRL_KEYS = ("alpha", "beta", "lambda1", "lambda2")


def program_overrides(cfg: dict, train_prefix: str, metrics_path: str = "") -> dict:
    pairs = {dst: cfg[src] for src, dst in KEYMAP.items() if src in cfg}
    for k in FTRL_KEYS:
        pairs[f"optim.ftrl.{k}"] = cfg["ftrl"][k]
    pairs.update({
        "data.train_path": train_prefix,
        "data.cache": "off",  # text shards: parser and hash stay in the window
        "train.epochs": 1,  # one fit() is one pass over the shard
        "train.pred_dump": False,
    })
    if metrics_path:
        # the traced run's step records, one a step
        pairs.update({"train.metrics_path": metrics_path, "train.log_every": 1})
    pairs.update(cfg.get("program_set", {}))
    return pairs


def build_trainer(cfg: dict, chips: int, train_prefix: str, metrics_path: str = ""):
    import jax

    from xflow_tpu.config import Config, override
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.train.trainer import Trainer

    pcfg = override(Config(), **program_overrides(cfg, train_prefix, metrics_path))
    mesh = None
    if chips > 1:
        mesh = make_mesh(pcfg, devices=jax.devices()[:chips])
    return Trainer(pcfg, mesh=mesh)


def describe(trainer) -> dict:
    rec = trainer.compile_recorder
    steps = [r for r in rec.records if "train" in str(r.get("program"))] if rec else []
    return {
        "engine": trainer.engine,
        "planner": trainer.planner,
        "pallas_calls": steps[-1].get("pallas_calls") if steps else None,
        "train_step_cache_hit": steps[-1].get("cache_hit") if steps else None,
        "train_step_temp_bytes": steps[-1].get("temp_bytes") if steps else None,
    }


def compiles(trainer) -> int:
    rec = trainer.compile_recorder
    return len(rec.records) if rec else 0


# ---------------------------------------------------------------- state


def _leaf_masks(ncols: int, width: int, leaves: dict):
    import jax.numpy as jnp

    col = jnp.arange(ncols, dtype=jnp.int32) % width
    return {k: (col >= s.start) & (col < s.stop) for k, s in leaves.items()}


def install_weights(trainer, cfg: dict, seed: int, width: int, table_fn) -> None:
    """Put the seed's weights into the program's table (zero FTRL state,
    step 0): one jitted call on the device, laid out and sharded as the
    program's own table is."""
    import jax
    import jax.numpy as jnp

    name = cfg["program_table"]
    st = trainer.state
    old = st.tables[name]
    zeros = jax.jit(lambda: jnp.zeros(old.shape, old.dtype), out_shardings=old.sharding)
    if old.ndim == 1:
        new = zeros()
    else:
        pack = old.shape[1] // width
        make = table_fn(seed, old.shape[0] * pack, width, pack, float(cfg.get("v_init_scale", 0.0)))
        new = jax.jit(make, out_shardings=old.sharding)()
    tables = dict(st.tables)
    tables[name] = new
    opt = dict(st.opt_state)
    opt[name] = {"n": zeros(), "z": zeros()}
    trainer.state = type(st)(tables=tables, opt_state=opt, step=jnp.zeros_like(st.step))


def grad_sq_by_leaf(trainer, cfg: dict, width: int, leaves: dict) -> dict:
    """Squared norm of the first gradient as the optimizer got it, by
    leaf: FTRL's n after one step from zero is g*g."""
    import jax
    import jax.numpy as jnp

    n = trainer.state.opt_state[cfg["program_table"]]["n"]

    def f(n):
        n2 = n if n.ndim == 2 else n[:, None]
        masks = _leaf_masks(n2.shape[1], width, leaves)
        return {k: jnp.sum(jnp.where(m[None, :], n2, 0.0)) for k, m in masks.items()}

    return {k: float(v) for k, v in jax.jit(f)(n).items()}


def delta_sq_by_leaf(trainer, cfg: dict, seed: int, width: int, leaves: dict, table_fn) -> dict:
    """Squared norm of (table now - the seed's table), by leaf; the
    seed's table is recomputed inside the same program, never held."""
    import jax
    import jax.numpy as jnp

    t = trainer.state.tables[cfg["program_table"]]
    if t.ndim == 2:
        pack = t.shape[1] // width
        make = table_fn(seed, t.shape[0] * pack, width, pack, float(cfg.get("v_init_scale", 0.0)))

    def f(t):
        d = (t - make()) if t.ndim == 2 else t[:, None]
        masks = _leaf_masks(d.shape[1], width, leaves)
        return {k: jnp.sum(jnp.where(m[None, :], d * d, 0.0)) for k, m in masks.items()}

    return {k: float(v) for k, v in jax.jit(f)(t).items()}


# ---------------------------------------------------------------- drive


def first_steps(trainer, cfg: dict, seed: int, data: dict, width: int, leaves: dict,
                table_fn) -> dict:
    """The first steps through the window's own call and feed: one
    `fit()` over each one-batch shard. Returns what `correct` compares."""
    losses, grad_sq = [], None
    for k, shard in enumerate(data["first"]):
        res = trainer.fit(train_path=shard["path"])
        if res.steps != 1 or res.bad_steps:
            raise RuntimeError(f"first step {k + 1}: steps={res.steps} bad_steps={res.bad_steps}")
        losses.append(float(res.last_loss))
        if k == 0:
            grad_sq = grad_sq_by_leaf(trainer, cfg, width, leaves)
    delta_sq = delta_sq_by_leaf(trainer, cfg, seed, width, leaves, table_fn)
    return {
        "loss": losses,
        "grad_norm": {k: v ** 0.5 for k, v in grad_sq.items()},
        "delta_norm": {k: v ** 0.5 for k, v in delta_sq.items()},
    }


def run_window(trainer, seconds: float, on_pass=None) -> dict:
    """`fit()` passes over the window's shard until `seconds` have gone;
    the window closes when the last pass's state is ready. What
    `on_pass` takes between passes (the profiler's start and stop in a
    traced run) is the harness's own time and is left out."""
    import jax

    out = {"passes": 0, "steps": 0, "examples": 0, "bad_steps": 0, "pass_s": []}
    compiles0 = compiles(trainer)
    t0 = time.perf_counter()
    while True:
        if on_pass is not None:
            t = time.perf_counter()
            on_pass(out["passes"])
            t0 += time.perf_counter() - t
        with jax.profiler.TraceAnnotation("bench:fit_pass"):
            res = trainer.fit()
        out["passes"] += 1
        out["steps"] += res.steps
        out["examples"] += res.examples
        out["bad_steps"] += res.bad_steps
        now = time.perf_counter() - t0
        out["pass_s"].append(now)
        if now >= seconds:
            break
    jax.block_until_ready(trainer.state)
    out["window_s"] = time.perf_counter() - t0
    out["compiles"] = compiles(trainer) - compiles0
    out["last_loss"] = float(res.last_loss)
    return out


def step_records(path: str, offset: int = 0) -> list:
    """The metrics JSONL's per-step records written after `offset`."""
    out = []
    if not path or not os.path.exists(path):
        return out
    with open(path) as f:
        f.seek(offset)
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if "step_time_p50_ms" in rec and "kind" not in rec and not rec.get("final"):
                out.append(rec)
    return out
