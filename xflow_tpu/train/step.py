"""The jitted train/eval steps.

One reference worker-thread iteration (`lr_worker.cc:145-177`: gather
unique keys → Pull → forward → residual → per-key mean gradient → Push;
server applies FTRL per key) becomes ONE pure function:

    grads = ∇ mean-BCE(tables; batch)      # gather fwd, scatter-add bwd
    tables, opt_state = optimizer(tables, opt_state, grads)

`jax.grad` through the table gather produces exactly the reference's
Push payload (summed residuals per key / batch rows); the optimizer is
the reference's server-side handler as an elementwise array op. Under
jit XLA fuses forward, backward, and update; under a sharded mesh GSPMD
inserts the gather/scatter collectives that replace ps-lite RPC
(SURVEY.md §2 C13).

Masked padded rows contribute zero gradient; the loss mean divides by
the number of *real* rows (reference divides by its sub-batch line
count, `lr_worker.cc:116-118`).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from xflow_tpu.compile_cache import past_cache
from xflow_tpu.config import Config
from xflow_tpu.metrics import binary_logloss_from_logits
from xflow_tpu.models.base import Model
from xflow_tpu.optim.base import Optimizer
from xflow_tpu.train.state import TrainState


def batch_to_arrays(batch) -> dict:
    """SparseBatch (host numpy) → the dict of arrays the step consumes."""
    return {
        "slots": batch.slots,
        "fields": batch.fields,
        "mask": batch.mask,
        "labels": batch.labels,
        "row_mask": batch.row_mask,
    }


def masked_mean_logloss(logits, labels, row_mask):
    """Mean BCE over REAL rows (the reference divides by its sub-batch
    line count, `lr_worker.cc:116-118`) — the one loss reduction, shared
    by the autodiff and fused step forms so they cannot drift."""
    per_row = binary_logloss_from_logits(logits, labels)
    return (per_row * row_mask).sum() / jnp.maximum(row_mask.sum(), 1.0)


def loss_fn(tables, batch, model: Model, cfg: Config):
    # the step's phases (telemetry.PHASE_LABELS, docs/OBSERVABILITY.md
    # "Step phases"): `rows` holds the model's forward and the loss
    # reduction, and with them their backward; the table lookups inside
    # the forward open `gather` scopes of their own (ops/sorted_table.py
    # `batch_rows`, `sorted_gather_map`) and the innermost label wins.
    # Autodiff transposes a `gather` into the scatter-add and writes
    # `transpose(...)` into its path: that is the `scatter` phase
    with jax.named_scope("rows"):
        logits = model.forward(tables, batch, cfg)
        return masked_mean_logloss(logits, batch["labels"], batch["row_mask"])


def nonfinite_guard_on(cfg: Config) -> bool:
    """Validate train.nonfinite_guard and return whether the guard runs."""
    g = cfg.train.nonfinite_guard
    if g not in ("off", "skip", "halt"):
        raise ValueError(
            f"train.nonfinite_guard={g!r}: expected off|skip|halt"
        )
    return g != "off"


def guard_nonfinite(cfg: Config, grads, metrics: dict):
    """Decide the non-finite guard BEFORE the write: returns the
    gradient the optimizer is to receive, and the step's metrics.

    `update_ok` = the loss AND every gradient leaf, as the optimizer is
    about to receive it, are finite (one isfinite reduction per gradient
    leaf). On a bad step every gradient is replaced by zeros — a
    scalar-predicate select on the gradient only, which fuses into its
    consumer — and the optimizer then runs unconditionally, in place, on
    the donated state. A zero gradient IS the discard: it is the
    optimizer contract (`optim/base.py`) that `apply(tables, state, 0)`
    leaves tables and state unchanged, the identity every step already
    leans on for the slots its batch does not touch. So nothing reads
    the pre-step state after the update: no second copy of w, n, z, no
    table-wide select, no table-wide isfinite sweep. The step counter
    still advances, so checkpoint names stay monotonic.

    What the flag promises: a step whose loss or gradient is non-finite
    never lands; given a finite state it leaves a finite state — except
    through float32 overflow inside the optimizer itself (g² with
    |g| > 1.8e19, or an accumulator within one step of 3.4e38), which
    the flag does not see (docs/ROBUSTNESS.md). A state poisoned that
    way is caught by the next step that gathers the slot.

    Shared by all four step builders (single-device, GSPMD, fullshard,
    replicated sorted) so their guard semantics cannot drift. Under a
    mesh the reduction over a sharded gradient is shard-local plus one
    psum, so the flag is replicated and every multi-process rank sees
    the same bit with no host collective — the trainer's skip/halt
    bookkeeping stays rank-symmetric for free.
    """
    if not nonfinite_guard_on(cfg):
        return grads, metrics
    ok = jnp.isfinite(metrics["loss"])
    for g in jax.tree.leaves(grads):
        ok = ok & jnp.isfinite(g).all()
    grads = jax.tree.map(lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads)
    return grads, dict(metrics, update_ok=ok)


def health_mode(cfg: Config) -> str:
    """Validate train.health_metrics and return the mode."""
    m = cfg.train.health_metrics
    if m not in ("off", "norms", "full"):
        raise ValueError(f"train.health_metrics={m!r}: expected off|norms|full")
    return m


def health_metric_keys(cfg: Config) -> tuple:
    """The health-scalar keys every step's metrics dict carries under
    this config: global grad/update/param norms ("norms"), plus
    per-table norms ("full"). Derived from the model's table specs so
    the four step builders and the sharded out_shardings pytrees agree
    by construction."""
    mode = health_mode(cfg)
    if mode == "off":
        return ()
    keys = ["grad_norm", "update_norm", "param_norm"]
    if mode == "full":
        from xflow_tpu.models import get_model

        for t in sorted(get_model(cfg.model.name).table_specs(cfg)):
            keys += [f"grad_norm.{t}", f"update_norm.{t}", f"param_norm.{t}"]
    return tuple(keys)


def health_norms(cfg: Config, old_tables, new_tables, grads=None, grad_sq=None) -> dict:
    """Health scalars for one step, fused into the jitted program.

    Per table: squared grad norm (from `grads` arrays, or engine-supplied
    `grad_sq` scalars where the table gradient never materializes — the
    fused scatter+FTRL path passes the occurrence-space cotangent's
    norm), squared update norm ||new − old||², squared param norm
    ||new||². Emitted as sqrt'd scalars keyed by `health_metric_keys`.
    Reductions are plain sums, so under GSPMD/shard_map-produced sharded
    leaves they lower to shard-local reductions + one psum and every
    rank sees identical replicated values — no host collective, same
    cost model as the non-finite guard's isfinite reduction. Callers
    pass the gradient as it was BEFORE the guard zeroed it: a discarded
    step's exploding grad norm is exactly the diagnostic the health
    stream exists to show. `update_norm` of a discarded step reads 0 —
    nothing was proposed to the table."""
    mode = health_mode(cfg)
    if mode == "off":
        return {}
    names = sorted(new_tables)
    sqsum = lambda x: (x.astype(jnp.float32) ** 2).sum()
    sq = {}
    for name in names:
        if grad_sq is not None and name in grad_sq:
            sq[name] = jnp.asarray(grad_sq[name], jnp.float32)
        elif grads is not None and name in grads:
            sq[name] = sqsum(grads[name])
        else:
            sq[name] = jnp.float32(0.0)
    upd = {n: sqsum(new_tables[n] - old_tables[n]) for n in names}
    par = {n: sqsum(new_tables[n]) for n in names}
    total = lambda d: jnp.sqrt(sum(d.values()))
    out = {
        "grad_norm": total(sq),
        "update_norm": total(upd),
        "param_norm": total(par),
    }
    if mode == "full":
        for n in names:
            out[f"grad_norm.{n}"] = jnp.sqrt(sq[n])
            out[f"update_norm.{n}"] = jnp.sqrt(upd[n])
            out[f"param_norm.{n}"] = jnp.sqrt(par[n])
    return out


def metrics_keys(cfg: Config) -> tuple:
    """The step-metrics dict keys under this config — the sharded step
    builders derive their out_shardings pytrees from this so neither the
    guard's extra flag nor the health scalars ever desync a jit
    contract."""
    base = ("loss", "rows") + health_metric_keys(cfg)
    return base + (("update_ok",) if nonfinite_guard_on(cfg) else ())


def _fused_scatter_eligible(cfg: Config, allow_fused: bool) -> bool:
    """Fused scatter+FTRL (cfg.optim.fused_scatter, ops/sorted_table
    .scatter_ftrl_sorted) applies to the single-device sorted fused-FM
    step with FTRL — the one-table case where the step's whole table
    gradient comes from a single windowed scatter. `allow_fused` is the
    caller's single-device assertion: the sharded builders pass False
    (an in-place window kernel over a sharded table is not this op's
    contract), and `on` there is a config error, not a silent downgrade.
    """
    if cfg.optim.fused_scatter == "off":
        return False
    if cfg.optim.fused_scatter not in ("auto", "on"):
        raise ValueError(
            f"optim.fused_scatter={cfg.optim.fused_scatter!r}: expected auto|on|off"
        )
    fm_ok = cfg.model.name == "fm" and cfg.model.fm_fused
    mvm_ok = cfg.model.name == "mvm"
    ffm_ok = cfg.model.name == "ffm"
    base_ok = allow_fused and cfg.optim.name == "ftrl"
    if cfg.optim.fused_scatter == "on":
        if not (base_ok and (fm_ok or mvm_ok or ffm_ok)):
            raise ValueError(
                "optim.fused_scatter=on requires the single-device step "
                "with optim.name=ftrl and model.name=fm (fm_fused=true), "
                f"mvm, or ffm; got optim={cfg.optim.name} "
                f"model={cfg.model.name} fm_fused={cfg.model.fm_fused} "
                f"single_device={allow_fused}"
            )
        return True
    # auto: FM (measured throughput-NEUTRAL; kept for the memory win)
    # and FFM's aligned hybrid, where the fusion removes a [S/8, 1256]
    # dense gradient (1.3 GB at 2^21 slots) and an optimizer sweep over
    # 4 GB of state: the fused kernel takes 30.9 ms of the 110 ms step at
    # 39 fields x k=4, B = 32768 (v5e; my chip run of PR 37, PERF.md
    # section 5; the two-pass form compiles but was not timed). The MVM
    # product path measured ~3% slower fused on an earlier rig (41.3 vs
    # 40.0 ms), so its memory win stays an explicit opt-in ("on").
    return base_ok and (fm_ok or ffm_ok)


def _fused_sorted_step(state: TrainState, batch: dict, cfg: Config):
    """Sorted train step with the optimizer applied inside the scatter's
    window write: gather → row-side vjp → ONE scatter_ftrl_sorted pass.
    Covers fused FM (table "wv") and the MVM product path (table "v").
    Bit-equal to value_and_grad + ftrl.apply (same kernels, same
    elementwise math on each window's complete gradient block); the
    difference is that the [S, K] gradient never exists in HBM and the
    dense optimizer sweep is gone. The non-finite guard decides on the
    occurrence cotangent before the kernel runs (`guard_nonfinite`), so
    the kernel's aliased w, n, z are the only copy: with
    train.health_metrics=off nothing reads the pre-step table after it."""
    from xflow_tpu.ops.sorted_table import (
        pack_of, scatter_ftrl_sorted, sorted_row_width, table_gather_sorted,
    )

    mvm = cfg.model.name == "mvm"
    ffm = cfg.model.name == "ffm"
    tname = "v" if mvm else "wv"
    K = sorted_row_width(cfg)
    table = state.tables[tname]
    pack = pack_of(table, K)
    with jax.named_scope("gather"):
        occ_t = table_gather_sorted(
            table, batch["sorted_slots"], batch["win_off"], cfg.data.sorted_bf16, pack
        )

    def row_loss(occ):
        # the row side and the loss reduction are the SAME functions the
        # two-pass form uses (fm._row_side_sorted / mvm._product_row_side
        # via sorted_gather_map; masked_mean_logloss via loss_fn) — only
        # the gather/scatter seam is split here so the table cotangent
        # feeds the fused kernel
        rows = batch["labels"].shape[0]
        if ffm:
            from xflow_tpu.models.ffm import ffm_aligned_logits

            logits = ffm_aligned_logits(occ, batch, cfg)
        elif mvm:
            from xflow_tpu.models.mvm import _product_row_side

            plus = 1.0 if cfg.model.mvm_plus_one else 0.0
            logits = _product_row_side(
                occ, batch["sorted_row"], batch["sorted_mask"], rows,
                cfg.model.v_dim, plus,
            )
        else:
            from xflow_tpu.models.fm import _row_side_sorted

            logits = _row_side_sorted(
                occ, batch["sorted_row"], batch["sorted_mask"], rows, cfg
            )
        return masked_mean_logloss(logits, batch["labels"], batch["row_mask"])

    # `rows`: the gathered occurrences -> the occurrence cotangent (row
    # sums, row math, loss, their backward); no `scatter` phase here:
    # the gather's transpose is the fused kernel's first half
    with jax.named_scope("rows"):
        loss, vjp = jax.vjp(row_loss, occ_t)
        (d_occ,) = vjp(jnp.ones_like(loss))
        metrics = {"loss": loss, "rows": batch["row_mask"].sum()}
    st = state.opt_state[tname]
    with jax.named_scope("update"):
        # the guard decides on the cotangent the kernel is about to
        # receive ([K8, Np], batch-sized): zeroed on a bad step, the
        # window write leaves every slot as it leaves an untouched one
        g_occ, metrics = guard_nonfinite(cfg, d_occ, metrics)
        # the fused kernel IS scatter + optimizer in one window write; it
        # keeps a label of its own inside `update`, the name the trace
        # and the kernel's roofline readers know it by
        with jax.named_scope("scatter_optimizer"):
            w_new, n_new, z_new = scatter_ftrl_sorted(
                g_occ, batch["sorted_slots"], batch["win_off"], table, st["n"], st["z"],
                K, cfg.optim.ftrl, cfg.data.sorted_bf16, pack,
            )
        new_state = TrainState(
            {tname: w_new}, {tname: {"n": n_new, "z": z_new}}, state.step + 1
        )
    # the table gradient never materializes on this path (that is the
    # point of the fusion) — the occurrence-space cotangent's norm, taken
    # before the guard zeroes it, stands in for the grad norm (equal when
    # the batch's occurrences hit distinct slots; a divergence signal
    # either way). update/param norms keep the pre-step table live.
    with jax.named_scope("health"):
        metrics.update(
            health_norms(
                cfg, state.tables, new_state.tables,
                grad_sq={tname: (d_occ.astype(jnp.float32) ** 2).sum()},
            )
        )
    return new_state, metrics


def make_train_step(model: Model, optimizer: Optimizer, cfg: Config, jit: bool = True,
                    allow_fused: bool = True, recorder=None, state_formats=None,
                    record_fields=None) -> Callable:
    """Returns train_step(state, batch_arrays) -> (state, metrics).

    `state_formats` (train/engine.py `state_formats`; None = the
    devices' defaults) pins the state's on-device layout on the way in
    AND on the way out: the state comes back as the next call takes it,
    and the donation stays an alias. Such a program is compiled past
    the persistent cache (`compile_cache.past_cache`).

    `allow_fused=False` (the sharded builders) disables the fused
    scatter+FTRL path regardless of config — the fusion's contract is
    the single-device step (`_fused_scatter_eligible`).

    `recorder` (telemetry.CompileRecorder) routes the jit through the
    compile-accounting seam: explicit timed .lower().compile() with
    cost/memory analysis into a kind="compile" record, program name
    "train_step"; `record_fields` are the builder's own fields of that
    record (the sorted engine's `state_window`)."""
    fuse = _fused_scatter_eligible(cfg, allow_fused)

    def train_step(state: TrainState, batch: dict):
        # fused path: only for FLAT sorted plans without per-occurrence
        # fields (MVM's segment path keeps two-pass) — except FFM's
        # aligned hybrid, whose plan carries fields for the placement's
        # reverse map plus ffm_invperm. Batch structure is static under
        # jit, so this resolves at trace time
        fusable = (
            "sorted_slots" in batch
            and batch["sorted_slots"].ndim == 1
            and (
                "ffm_invperm" in batch
                if cfg.model.name == "ffm"
                else "sorted_fields" not in batch
            )
        )
        if fuse and fusable:
            return _fused_sorted_step(state, batch, cfg)
        if fuse and cfg.optim.fused_scatter == "on":
            raise ValueError(
                "optim.fused_scatter=on but this batch has no flat "
                "fields-free sorted plan (sorted_layout off/row-major "
                "fallback, stacked sub-batch plans, MVM's segment "
                "path, or a non-aligned FFM batch) — the fused path "
                "cannot run; use auto to allow the two-pass form on "
                "such batches"
            )
        # forward and backward carry `loss_fn`'s scopes: `gather`,
        # `rows`, and the gather's transpose (`scatter`)
        loss, grads = jax.value_and_grad(loss_fn)(state.tables, batch, model, cfg)
        with jax.named_scope("rows"):
            metrics = {"loss": loss, "rows": batch["row_mask"].sum()}
        # `update`: the gradient -> the new state, guard included (XLA
        # fuses its select into the optimizer's sweep)
        with jax.named_scope("update"):
            safe_grads, metrics = guard_nonfinite(cfg, grads, metrics)
            new_tables, new_opt = optimizer.apply(
                state.tables, state.opt_state, safe_grads, cfg
            )
            new_state = TrainState(new_tables, new_opt, state.step + 1)
        with jax.named_scope("health"):
            metrics.update(health_norms(cfg, state.tables, new_tables, grads=grads))
        return new_state, metrics

    if jit:
        # donate the state: tables and optimizer state update in place in HBM
        pinned = {} if state_formats is None else {
            "in_shardings": (state_formats, None),
            "out_shardings": (state_formats, None),
        }
        train_step = jax.jit(train_step, donate_argnums=(0,), **pinned)
        if pinned:
            train_step = past_cache(train_step)
        if recorder is not None:
            return recorder.wrap("train_step", train_step, **(record_fields or {}))
    return train_step


def make_eval_step(model: Model, cfg: Config, jit: bool = True, recorder=None) -> Callable:
    """Returns eval_step(tables, batch_arrays) -> pctr [B].

    Delegates to the ONE shared pctr forward (models/predict.py
    make_predict_fn) — the same function the serve runner compiles, so
    offline eval and online serving cannot drift."""
    from xflow_tpu.models.predict import make_predict_fn

    return make_predict_fn(model, cfg, jit=jit, recorder=recorder)
