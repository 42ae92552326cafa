"""Sharded sorted-window FM training: the pod-scale path for the Pallas
table engine (ops/sorted_table.py).

Layout (vs the GSPMD row-major path, parallel/train_step.py, which
shards tables over BOTH mesh axes and lets the compiler route the
gather/scatter collectives):

- the fused FM table (and its FTRL state) is sharded on the slot axis
  over the **'table' axis only** — `P('table', None)` — and replicated
  across 'data'. Each device owns `S/T` slots = `n_win/T` whole windows.
- each 'data' shard plans ITS rows' occurrences over the FULL table
  (host side, `plan_sorted_stacked` with `num_sub = D`), so a device's
  occurrences for its windows are one contiguous span of the
  slot-sorted stream: the Pallas kernels run *unmodified* on the local
  table shard with a sliced `win_off` and rebased slots.
- forward cross-device traffic is ONE `psum` of the per-row partial
  sums `[B/D, ch]` over the 'table' axis (~tens of KB at k=10) — the
  analog of the reference workers pulling from every server
  (`lr_worker.cc:170`), but aggregated rows cross the wire instead of
  per-key values.
- backward needs NO extra collective on the 'table' axis (each shard
  scatters only its own windows); shard_map's transpose inserts the
  gradient `psum` over 'data' (the table is replicated there) — the
  classic data-parallel allreduce, ~(S/T)·(1+k)·4 B per step.

Trade-off, stated plainly: replicating the table across the 'data' axis
costs D× table memory. For the 1B-feature / 12 GB-state regime, use the
fully-sharded GSPMD path; this path is the throughput engine for tables
that fit per-host HBM (e.g. 2^26 slots × 11 × 3 arrays ≈ 8.8 GB split
over T=4 ⇒ 2.2 GB/device).

Reference analog: N workers × M servers (SURVEY.md §1) with D data
shards × T table shards; `Wait(Pull)`/`Wait(Push)` become the one psum
and the transpose-inserted gradient allreduce.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xflow_tpu.config import Config
from xflow_tpu.metrics import binary_logloss_from_logits
from xflow_tpu.ops.sorted_table import (
    WINDOW,
    row_sums_sorted,
    table_gather_sorted,
)
from xflow_tpu.parallel.mesh import DATA_AXIS, TABLE_AXIS
from xflow_tpu.train.state import TrainState
from xflow_tpu.train.step import guard_nonfinite, health_norms, metrics_keys


def validate_sorted_sharded(cfg: Config, mesh: Mesh) -> None:
    """Reject configs the sharded sorted engine cannot run, with the
    specific reason. Multi-process: each of P processes plans its OWN
    (per-process) batch into d/P sub-plans, so the divisibility
    requirements are per-process."""
    d, t = mesh.shape[DATA_AXIS], mesh.shape[TABLE_AXIS]
    p = jax.process_count()
    S = cfg.num_slots
    if S % (t * WINDOW) != 0:
        raise ValueError(
            f"sorted sharded layout needs num_slots (2^{cfg.data.log2_slots}) "
            f"divisible by table_axis*WINDOW = {t}*{WINDOW}"
        )
    if d % p != 0:
        raise ValueError(
            f"sorted sharded layout needs the data axis ({d}) divisible by "
            f"the process count ({p}): each process plans its rows into d/P "
            "sub-plans"
        )
    if cfg.data.batch_size % (d // p) != 0:
        raise ValueError(
            f"per-process batch_size {cfg.data.batch_size} not divisible by "
            f"the local data-shard count {d // p} (data axis {d} / {p} "
            "process(es))"
        )
    if not (cfg.model.name == "fm" and cfg.model.fm_fused):
        raise ValueError("sorted sharded layout supports fused FM only")
    if cfg.data.sorted_sub_batches not in (0, d // p):
        # the per-process plan count IS d/P here; silently overriding a
        # user's explicit single-device tuning value would benchmark a
        # different configuration than they asked for
        raise ValueError(
            f"data.sorted_sub_batches={cfg.data.sorted_sub_batches} conflicts "
            f"with the mesh sorted path (per-process plan count = {d // p}); "
            "leave it 0"
        )


def sorted_batch_sharding(mesh: Mesh) -> dict:
    """Shardings for the stacked per-data-shard plan arrays [D, Np_l] —
    subset of the canonical dict so the two stay in lockstep."""
    from xflow_tpu.parallel.mesh import batch_sharding

    full = batch_sharding(mesh)
    return {k: full[k] for k in ("sorted_slots", "sorted_row", "sorted_mask", "win_off")}


def make_sorted_sharded_train_step(
    optimizer, cfg: Config, mesh: Mesh, recorder=None
) -> Callable:
    """FM train step over ('data','table'): Pallas sorted kernels on the
    local table shard, one row-sum psum, shard_map-transposed grad psum.
    `recorder` routes the jit through the compile-accounting seam
    (telemetry.CompileRecorder, program "train_step.replicated").
    """
    validate_sorted_sharded(cfg, mesh)
    S = cfg.num_slots
    T = mesh.shape[TABLE_AXIS]
    S_local = S // T
    wpt = (S // WINDOW) // T  # windows per table shard

    def local_loss(wv_local, sorted_slots, sorted_row, sorted_mask, win_off,
                   labels, row_mask):
        """Per-device body. wv_local [S/T/pack, pack*K]; occurrence
        arrays are this data shard's full plan [Np_l]; labels/row_mask
        [B/D]. Storage may be packed (pack_table) — detected from the
        shard shape; slot indices stay logical."""
        from xflow_tpu.ops.sorted_table import pack_of, wire_mask, wire_rows

        sorted_row = wire_rows(sorted_row)
        sorted_mask = wire_mask(sorted_mask)
        K = 1 + cfg.model.v_dim
        t_idx = jax.lax.axis_index(TABLE_AXIS)
        # this shard's windows: global win_off sliced to [t*wpt, (t+1)*wpt]
        off_local = jax.lax.dynamic_slice(win_off, (t_idx * wpt,), (wpt + 1,))
        # rebase global slots to the local shard's window space; positions
        # outside this shard's span get out-of-range values the kernels
        # never touch (their chunk ranges come from off_local) and the
        # in-span mask removes from compute
        slots_local = sorted_slots - t_idx * S_local
        with jax.named_scope("gather"):
            occ_t = table_gather_sorted(
                wv_local, slots_local, off_local, cfg.data.sorted_bf16,
                pack_of(wv_local, K),
            )  # [K8, Np_l]
        pos = jnp.arange(sorted_slots.shape[0], dtype=jnp.int32)
        in_span = (pos >= off_local[0]) & (pos < off_local[-1])
        # where() (not multiply) so untouched positions — which may hold
        # uninitialized/garbage values — cannot poison the sums as NaN*0
        occm_t = jnp.where(in_span[None, :], occ_t[:K], 0.0) * sorted_mask[None, :]
        from xflow_tpu.models.fm import stack_channels

        with jax.named_scope("loss"):
            stacked = stack_channels(occm_t, K)
            partial_sums = row_sums_sorted(stacked, sorted_row, labels.shape[0])
            sums = jax.lax.psum(partial_sums, TABLE_AXIS)  # the ONE fwd collective
            from xflow_tpu.models.fm import fm_logits_from_sums

            logits = fm_logits_from_sums(sums, K, cfg)
            per_row = binary_logloss_from_logits(logits, labels)
            loss_sum = jax.lax.psum((per_row * row_mask).sum(), DATA_AXIS)
            rows = jax.lax.psum(row_mask.sum(), DATA_AXIS)
            return loss_sum / jnp.maximum(rows, 1.0), rows

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(TABLE_AXIS, None),  # wv shard
            P(DATA_AXIS, None),  # sorted_slots [D, Np_l]
            P(DATA_AXIS, None),  # sorted_row
            P(DATA_AXIS, None),  # sorted_mask
            P(DATA_AXIS, None),  # win_off [D, n_win+1]
            P(DATA_AXIS, None),  # labels [D, B/D]
            P(DATA_AXIS, None),  # row_mask
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def sharded_loss(wv, ss, sr, sm, wo, labels, rm):
        return local_loss(wv, ss[0], sr[0], sm[0], wo[0], labels[0], rm[0])

    def loss_for_grad(wv, batch):
        loss, rows = sharded_loss(
            wv,
            batch["sorted_slots"],
            batch["sorted_row"],
            batch["sorted_mask"],
            batch["win_off"],
            batch["labels"].reshape(mesh.shape[DATA_AXIS], -1),
            batch["row_mask"].reshape(mesh.shape[DATA_AXIS], -1),
        )
        return loss, rows

    def train_step(state: TrainState, batch: dict):
        # "grad" covers forward+backward: the windowed scatter (the
        # gather's transpose) and the 'data'-axis gradient psum land here
        with jax.named_scope("grad"):
            (loss, rows), grads = jax.value_and_grad(loss_for_grad, has_aux=True)(
                state.tables["wv"], batch
            )
        metrics = {"loss": loss, "rows": rows}
        # non-finite guard + health norms: the shared helpers every
        # engine uses (train/step.py) — reductions over the sharded
        # leaves lower to shard-local sums + one psum, outputs replicated
        safe_grads, metrics = guard_nonfinite(cfg, {"wv": grads}, metrics)
        with jax.named_scope("optimizer"):
            new_tables, new_opt = optimizer.apply(
                {"wv": state.tables["wv"]}, state.opt_state, safe_grads, cfg
            )
        metrics.update(
            health_norms(cfg, state.tables, new_tables, grads={"wv": grads})
        )
        return TrainState(new_tables, new_opt, state.step + 1), metrics

    table_sh = NamedSharding(mesh, P(TABLE_AXIS, None))
    opt_sh = {"wv": {"n": table_sh, "z": table_sh}}
    state_sh = TrainState(
        {"wv": table_sh}, opt_sh, NamedSharding(mesh, P())
    )
    bsh = {
        **sorted_batch_sharding(mesh),
        "labels": NamedSharding(mesh, P(DATA_AXIS)),
        "row_mask": NamedSharding(mesh, P(DATA_AXIS)),
    }
    rep = NamedSharding(mesh, P())
    jitted = jax.jit(
        train_step,
        in_shardings=(state_sh, bsh),
        out_shardings=(state_sh, {k: rep for k in metrics_keys(cfg)}),
        donate_argnums=(0,),
    )
    if recorder is not None:
        jitted = recorder.wrap("train_step.replicated", jitted)

    def call(state: TrainState, batch: dict):
        # tolerate a batch dict carrying extra keys (slots/fields/mask for
        # the eval path): jit in_shardings must match the pytree exactly
        return jitted(state, {k: batch[k] for k in bsh})

    return call


def sorted_state_shardings(state: TrainState, mesh: Mesh):
    """A pytree of NamedShardings matching a TrainState: the
    table-axis-only layout this path uses."""
    table_sh = NamedSharding(mesh, P(TABLE_AXIS, None))
    scalar_sh = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: table_sh if getattr(x, "ndim", 0) >= 1 else scalar_sh, state
    )


def shard_sorted_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place a finished state (a restore's or a test's host data) onto
    this path's sharding; a Trainer's own state is born there
    (train/state.py build_state)."""
    return jax.tree.map(jax.device_put, state, sorted_state_shardings(state, mesh))
