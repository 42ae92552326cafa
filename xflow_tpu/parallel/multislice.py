"""Emulated multi-slice runtime: bounded-staleness table sync across
slice subprocesses — the DCN tier of the two-tier topology the ROADMAP
names (synchronous SPMD inside a slice over ICI, asynchronous
parameter-server semantics ACROSS slices over DCN).

The reference system's defining robustness property was asynchrony:
ps-lite workers push/pull the shared tables and never block on each
other (PAPER.md: KVWorker ``Wait(Push/Pull)``), so a slow or dead
worker degrades throughput instead of halting the job. Our GSPMD
engine is the opposite — fully synchronous — and this module restores
the asynchronous tier WITHOUT touching the jit programs: each slice is
one independent ``xflow train`` subprocess (own mesh, own data shards,
own checkpoints — the launch-local pattern minus the coordinator), and
a host-level `SliceSyncer` exchanges ADDITIVE table deltas through a
shared directory between K-step scan blocks. Engine-agnostic by
construction: the syncer sees only the host-side TrainState pytree.

Delta model (local-SGD style): every slice keeps ``base`` — its state
at the last sync. At a sync boundary it publishes
``delta_i = local - base``, applies every peer delta it has not yet
applied (in (round, slice) order, each exactly once), and rebases.
Since every slice starts from the same seeded init, all slices
converge to ``init + sum(all deltas)`` once caught up — regardless of
HOW stale each exchange ran. The one structural guarantee: when no
peer delta applies (single slice, or async with nothing landed), the
live state passes through UNTOUCHED — no base + (local - base) float
round-trip — so K=0 single-slice runs are bitwise-identical to a plain
run (tests/test_multislice.py).

Failure semantics (parameter-server, throughout):
- every staleness wait is bounded by ``sync.timeout_s`` with
  ``sync.retries`` backoff-spaced re-checks (supervise.backoff_delay —
  the rendezvous-hardening curve); a vanished peer costs a bounded
  wait, never a hang;
- a slice that misses its bound triggers the ``sync.on_stale`` policy
  (wait vs. proceed-on-stale), counted in the ``kind="sync"`` record;
- a slice that DIES (watchdog dead verdict or process exit) is dropped
  from ``membership.json`` by the launcher, and survivors stop waiting
  on it — degraded continue;
- a relaunched slice resumes its OWN checkpoint (exact data_state
  accounting — zero lost examples) and catches up by adopting the
  freshest published full-state snapshot at syncer attach (the
  reshard-on-load restore idiom: host arrays placed onto the live
  sharding).

Every sync emits a stamped ``kind="sync"`` JSONL record plus a
``kind="span"`` timing span (tracing.emit_op_span), so
``metrics_report --check`` gates the schema and ``--health`` can name
the most-stale slice (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import threading
import time
from typing import TYPE_CHECKING, Optional

import numpy as np

if TYPE_CHECKING:  # config type only — no runtime import cost
    from xflow_tpu.config import SyncConfig

MEMBERSHIP_FILE = "membership.json"
_DELTA_RE = re.compile(r"^delta_s(\d+)_r(\d+)\.ok$")
_SNAP_RE = re.compile(r"^snap_s(\d+)_r(\d+)\.ok$")
# staleness-wait poll cadence: the deltas land via os.replace, so a
# tight poll costs one readdir — cheap against a K-step train block
_POLL_S = 0.05


# ----------------------------------------------------------- membership
def write_membership(sync_dir: str, live, run_id: str = "",
                     note: str = "") -> None:
    """Atomically publish the live slice set (launcher-owned: the
    watchdog dead verdict and the per-slice supervision loop are the
    only writers; every SliceSyncer re-reads it on each wait poll so a
    dead slice stops being waited on mid-exchange)."""
    from xflow_tpu.train.checkpoint import _write_atomic

    payload = {
        "live": sorted(int(s) for s in live),
        "run_id": run_id,
        "note": note,
        "ts": round(time.time(), 6),
    }

    def write_json(p):
        with open(p, "w") as f:
            json.dump(payload, f)

    _write_atomic(os.path.join(sync_dir, MEMBERSHIP_FILE), write_json)


def read_membership(sync_dir: str, num_slices: int) -> set:
    """The live slice set, defensively: a missing/corrupt membership
    file (first sync racing the launcher's initial write) means
    everyone is live — the syncer's timeouts bound the cost of a wrong
    optimistic answer, while a wrong 'dead' answer would silently drop
    a healthy slice's deltas."""
    path = os.path.join(sync_dir, MEMBERSHIP_FILE)
    try:
        with open(path) as f:
            data = json.load(f)
        live = {int(s) for s in data["live"]}
    except (OSError, ValueError, TypeError, KeyError):
        return set(range(num_slices))
    return {s for s in live if 0 <= s < num_slices} or set(range(num_slices))


# ------------------------------------------------------------ the syncer
class SliceSyncer:
    """The per-slice half of the sync tier: publish my delta, gather my
    peers' (subject to the staleness bound), apply, rebase.

    Pure against I/O other than the sync dir: the caller (the trainer's
    fit-loop hook) owns record emission and spans; `sync` returns the
    new state plus the ready-to-append ``kind="sync"`` record body.
    Rounds are 1-based; ``_applied[p]`` is the last round of peer ``p``
    folded into my state (0 = none yet)."""

    def __init__(self, sync_cfg: "SyncConfig", slice_id: int,
                 num_slices: int, clock=time.monotonic, sleep=time.sleep):
        mode = str(sync_cfg.mode)
        if mode not in ("sync", "bounded", "async"):
            raise ValueError(
                f"sync.mode={mode!r}: expected sync|bounded|async "
                "(off never constructs a syncer)"
            )
        if not sync_cfg.dir:
            raise ValueError(
                "sync.dir is empty: the sync tier needs a shared "
                "directory (launch-multislice wires <run_dir>/sync)"
            )
        self.cfg = sync_cfg
        self.mode = mode
        # mode=sync is the K=0 lockstep; bounded honors staleness_k
        self.k = 0 if mode == "sync" else max(int(sync_cfg.staleness_k), 0)
        self.slice_id = int(slice_id)
        self.num_slices = max(int(num_slices), 1)
        self.dir = sync_cfg.dir
        self.round = 0
        self._base: Optional[dict] = None
        self._applied = {
            p: 0 for p in range(self.num_slices) if p != self.slice_id
        }
        self._last_live = set(range(self.num_slices))
        self._adopted = False
        self._clock = clock
        self._sleep = sleep
        # chaos injectors, resolved once (testing/faults.py)
        from xflow_tpu.testing.faults import sync_faults_from_env

        self._kill_round, self._delay_s = sync_faults_from_env()
        os.makedirs(self.dir, exist_ok=True)

    # ------------------------------------------------- state <-> host
    def _flatten(self, state) -> dict:
        """Host-side flat view of the SYNCABLE leaves — tables plus
        optimizer state (FTRL z/n are additive accumulators, so the
        delta model covers them), NEVER the step counter: each slice's
        step/data position is its own (exact example accounting)."""
        from xflow_tpu.train.checkpoint import _flatten

        flat = _flatten(state)
        flat.pop("step", None)
        return flat

    def _rebuild(self, state, flat: dict):
        """Place the merged host arrays back onto the live state's
        shardings (the reshard-on-load idiom, train/checkpoint.restore:
        device_put against each leaf's own sharding handles any
        in-slice mesh layout)."""
        import jax

        tables = {}
        for name, t in state.tables.items():
            arr = np.asarray(flat[f"tables/{name}"], dtype=t.dtype)
            tables[name] = jax.device_put(arr, t.sharding)
        opt_state = {}
        for name, st in state.opt_state.items():
            opt_state[name] = {}
            for k, v in st.items():
                arr = np.asarray(flat[f"opt/{name}/{k}"], dtype=v.dtype)
                opt_state[name][k] = jax.device_put(arr, v.sharding)
        return state._replace(tables=tables, opt_state=opt_state)

    def attach(self, state):
        """Fix the delta base = the state entering the fit loop. MUST
        run before the first `sync` (the trainer calls it at fit start,
        after any checkpoint restore and snapshot adoption)."""
        self._base = self._flatten(state)
        latest = self._scan(_DELTA_RE)
        # a relaunched slice must continue its round numbering past its
        # previous generation's published files (peers' _applied
        # bookkeeping survives in their processes; re-publishing an old
        # round would collide with a committed file)
        self.round = max(self.round, latest.get(self.slice_id, 0))
        from xflow_tpu.telemetry import resolve_restart_gen

        if resolve_restart_gen() > 0 and not self._adopted:
            # rejoin WITHOUT a snapshot to adopt (death before the
            # first snapshot round): the restored checkpoint already
            # folded in some unknown prefix of every peer's deltas, so
            # re-applying from round 1 would double-count. Fast-forward
            # the bookkeeping past everything already published —
            # peer work from the dead window is skipped, never applied
            # twice (monotone, bounded-staleness-honest; the snapshot
            # path is the lossless catch-up).
            for p in self._applied:
                self._applied[p] = max(self._applied[p], latest.get(p, 0))

    # ------------------------------------------------------ dir scans
    def _scan(self, rx: re.Pattern) -> dict:
        """{slice: newest committed round} for one marker family —
        commit markers only (the .npz lands first via temp+rename, the
        .ok marker witnesses the ordering, same protocol as COMMITTED)."""
        latest: dict = {}
        try:
            names = os.listdir(self.dir)
        except OSError:
            return latest
        for name in names:
            m = rx.match(name)
            if m:
                s, r = int(m.group(1)), int(m.group(2))
                if r > latest.get(s, 0):
                    latest[s] = r
        return latest

    def _live(self) -> set:
        return read_membership(self.dir, self.num_slices)

    def _delta_path(self, s: int, r: int) -> str:
        return os.path.join(self.dir, f"delta_s{s}_r{r}.npz")

    def _snap_path(self, s: int, r: int) -> str:
        return os.path.join(self.dir, f"snap_s{s}_r{r}.npz")

    def _publish(self, kind: str, path: str, marker: str, arrays: dict,
                 extra: Optional[dict] = None) -> int:
        """Atomic npz + JSON commit marker; returns the payload bytes."""
        from xflow_tpu.train.checkpoint import _write_atomic

        def write_npz(p):
            with open(p, "wb") as f:
                np.savez(f, **arrays)

        _write_atomic(path, write_npz)
        size = os.path.getsize(path)
        meta = {
            "kind": kind,
            "slice": self.slice_id,
            "bytes": size,
            "ts": round(time.time(), 6),
            **(extra or {}),
        }

        def write_marker(p):
            with open(p, "w") as f:
                json.dump(meta, f)

        _write_atomic(marker, write_marker)
        return size

    # ------------------------------------------------ snapshot catch-up
    def adopt_latest_snapshot(self, state):
        """Rejoin catch-up: overwrite the syncable leaves with the
        freshest published snapshot (highest round; ties to the lowest
        slice), KEEPING my own step counter and data position — the
        checkpoint restore already placed those, and they are what the
        zero-lost-examples accounting audits. Returns
        (state, (round, source_slice) | None). Peer bookkeeping jumps
        to the snapshot round: deltas the snapshot already folded in
        must not double-apply (older rounds are skipped; missing files
        in the gap are tolerated — at-least-once, bounded-staleness
        semantics, not exact replay)."""
        snaps = self._scan(_SNAP_RE)
        if not snaps:
            return state, None
        r = max(snaps.values())
        src = min(s for s, rr in snaps.items() if rr == r)
        try:
            with np.load(self._snap_path(src, r)) as z:
                flat = {k: z[k] for k in z.files if k != "step"}
        except (OSError, ValueError) as e:
            print(
                f"# multislice: snapshot s{src} r{r} unreadable "
                f"({type(e).__name__}: {e}); rejoining without catch-up",
                file=sys.stderr,
            )
            return state, None
        state = self._rebuild(state, flat)
        self._base = flat
        for p in self._applied:
            self._applied[p] = max(self._applied[p], r)
        self.round = max(self.round, r)
        self._adopted = True
        return state, (r, src)

    # ------------------------------------------------------- the round
    def _wait_for_bound(self, want: int, peers_of) -> tuple:
        """Block until every live peer has published round >= want, the
        membership has shrunk past the laggard, or the timeout+retry
        budget is spent. Returns (satisfied, timeouts, live_set).
        Every path is bounded: worst case timeout_s * (retries + 1)
        plus the backoff sleeps."""
        from xflow_tpu.launch.supervise import backoff_delay

        timeouts = 0
        retries = max(int(self.cfg.retries), 0)
        timeout_s = max(float(self.cfg.timeout_s), 0.0)
        for attempt in range(retries + 1):
            deadline = self._clock() + timeout_s
            while True:
                live = self._live()
                latest = self._scan(_DELTA_RE)
                if all(latest.get(p, 0) >= want for p in peers_of(live)):
                    return True, timeouts, live
                if self._clock() >= deadline:
                    break
                self._sleep(_POLL_S)
            timeouts += 1
            if attempt < retries:
                self._sleep(
                    backoff_delay(attempt, float(self.cfg.backoff_s))
                )
        return False, timeouts, self._live()

    def sync(self, state) -> tuple:
        """One sync round: publish my delta, gather peers under the
        staleness policy, apply in (round, slice) order, rebase.
        Returns (new_state, record) — `record` is the ``kind="sync"``
        body the trainer appends (docs/OBSERVABILITY.md schema)."""
        t0 = time.perf_counter()
        self.round += 1
        r = self.round
        if self._kill_round and r == self._kill_round:
            # the slice-loss drill: die ENTERING the round, before the
            # delta publishes — peers must time out, drop us via the
            # launcher's membership update, and continue degraded
            from xflow_tpu.testing.faults import hard_kill

            hard_kill()
        if self._delay_s:
            self._sleep(self._delay_s)  # the straggler drill
        if self._base is None:
            raise RuntimeError("SliceSyncer.sync before attach()")
        local = self._flatten(state)
        delta = {k: local[k] - self._base[k] for k in local}
        bytes_out = self._publish(
            "delta",
            self._delta_path(self.slice_id, r),
            os.path.join(self.dir, f"delta_s{self.slice_id}_r{r}.ok"),
            delta,
            extra={"round": r},
        )
        del delta

        def peers_of(live):
            return [
                p for p in sorted(live)
                if p != self.slice_id and p in self._applied
            ]

        timeouts = 0
        if self.mode != "async":
            want = r - self.k
            latest = self._scan(_DELTA_RE)
            satisfied = all(
                latest.get(p, 0) >= want for p in peers_of(self._live())
            )
            if not satisfied and want > 0 and not (
                self.mode == "bounded" and str(self.cfg.on_stale) == "proceed"
            ):
                # on_stale=proceed checks once and continues on stale
                # state (counted below); everyone else runs the bounded
                # wait
                _, timeouts, _ = self._wait_for_bound(want, peers_of)
        # apply every not-yet-applied peer round up to MY round (peer
        # rounds from my future wait until I get there: deterministic
        # at K=0, and exactly the staleness window otherwise). ALL
        # peers, live or not: a dead slice's committed deltas are
        # trained examples — dropping them would lose its work, and the
        # zero-lost-examples accounting audits exactly that.
        latest = self._scan(_DELTA_RE)
        merged: Optional[dict] = None
        bytes_in = 0
        applied = 0
        for p in sorted(self._applied):
            top = min(latest.get(p, 0), r)
            for rr in range(self._applied[p] + 1, top + 1):
                path = self._delta_path(p, rr)
                marker = os.path.join(self.dir, f"delta_s{p}_r{rr}.ok")
                if not os.path.exists(marker):
                    continue  # gap from a crashed generation: tolerated
                try:
                    with np.load(path) as z:
                        if merged is None:
                            merged = {k: local[k].copy() for k in local}
                        for k in merged:
                            merged[k] += z[k]
                except (OSError, ValueError, KeyError) as e:
                    print(
                        f"# multislice: delta s{p} r{rr} unreadable "
                        f"({type(e).__name__}: {e}); skipped",
                        file=sys.stderr,
                    )
                    continue
                bytes_in += os.path.getsize(path)
                applied += 1
            self._applied[p] = max(self._applied[p], top)
        if merged is not None:
            state = self._rebuild(state, merged)
            self._base = merged
        else:
            # structural passthrough: the bitwise-K=0 guarantee
            self._base = local
        # staleness accounting against the LIVE set only (a dead slice
        # is the launcher's problem, not a lag statistic)
        live = self._live()
        lags = {
            str(p): r - self._applied[p] for p in peers_of(live)
        }
        lag_max = max(lags.values(), default=0)
        stale = sum(1 for v in lags.values() if v > self.k)
        joined = sorted(live - self._last_live)
        left = sorted(self._last_live - live)
        self._last_live = live
        if self.cfg.snapshot_every > 0 and r % int(self.cfg.snapshot_every) == 0:
            snap = dict(self._base)
            snap["step"] = np.asarray(state.step)
            self._publish(
                "snapshot",
                self._snap_path(self.slice_id, r),
                os.path.join(self.dir, f"snap_s{self.slice_id}_r{r}.ok"),
                snap,
                extra={"round": r, "step": int(state.step)},
            )
        record = {
            "kind": "sync",
            "round": r,
            "k": self.k,
            "mode": self.mode,
            "live": sorted(live),
            "joined": joined,
            "left": left,
            "bytes_out": int(bytes_out),
            "bytes_in": int(bytes_in),
            "applied": int(applied),
            "stale": int(stale),
            "timeouts": int(timeouts),
            "lag_max": int(lag_max),
            "lags": lags,
            "dur_ms": round((time.perf_counter() - t0) * 1e3, 3),
        }
        return state, record


# ----------------------------------------------------------- the launcher
def slice_forward_args(forward_args: list, j: int) -> list:
    """Per-slice argv: the literal ``{slice}`` placeholder substitutes
    to the slice index, so one command line gives every slice its own
    data shards and checkpoint dir (e.g.
    ``--train data/s{slice} --checkpoint-dir run/ckpt_slice{slice}``)."""
    return [a.replace("{slice}", str(j)) for a in forward_args]


def _slice_platform() -> str:
    """CPU devices by default, same reasoning as launch-local: every
    slice landing on one ambient accelerator would serialize them."""
    return os.environ.get("XFLOW_LAUNCH_PLATFORM", "cpu")


def _spawn_slice(j: int, num_slices: int, forward_args: list, run_dir: str,
                 sync_dir: str, run_id: str, gen: int) -> subprocess.Popen:
    """One slice subprocess: an independent single-process
    ``xflow train`` (no coordinator — each slice is its own world; the
    DCN tier is the filesystem, not collectives). XFLOW_PROCESS_ID
    doubles as the rank stamp so the shared watchdog and
    metrics_report see slice j as rank j."""
    from xflow_tpu.launch.local import rank_metrics_args

    env = dict(os.environ)
    env.pop("XFLOW_COORDINATOR", None)
    env.pop("XFLOW_NUM_PROCESSES", None)
    env.update(
        XFLOW_SLICE=str(j),
        XFLOW_NUM_SLICES=str(num_slices),
        XFLOW_PROCESS_ID=str(j),
        XFLOW_RUN_ID=run_id,
        XFLOW_RESTART_GEN=str(gen),
        JAX_PLATFORMS=_slice_platform(),
    )
    cmd = [
        sys.executable, "-m", "xflow_tpu", "train",
        *slice_forward_args(forward_args, j),
        *rank_metrics_args(run_dir, j),
        "--set", f"sync.dir={sync_dir}",
    ]
    return subprocess.Popen(cmd, env=env)


def launch_multislice(
    num_slices: int,
    forward_args: list,
    run_dir: str,
    straggler_factor: float = 0.0,
    dead_after_s: float = 0.0,
    watchdog_poll_s: float = 0.0,
    max_restarts: int = 0,
    restart_backoff: float = 1.0,
    min_uptime_s: float = 0.0,
) -> int:
    """Run N slices under PER-SLICE supervision. The structural
    difference from launch-local: slices share no collectives, so a
    dead slice must NOT tear the job down (no fail-fast) — its
    supervision loop relaunches it alone (with ``train.resume=true``,
    restoring its own checkpoint for exact data accounting) while the
    survivors keep training degraded. The launcher owns
    ``membership.json``: a slice leaves the live set on process exit or
    a watchdog dead verdict (PR 5's DeadHostTracker bookkeeping — a
    wedged slice that never exits is killed so its supervisor can act)
    and rejoins when its relaunch spawns. Returns 0 iff every slice's
    supervision ended clean."""
    from xflow_tpu.launch.local import resolve_launch_run_id
    from xflow_tpu.launch.supervise import (
        DeadHostTracker,
        resume_forward_args,
        supervise,
        terminate_procs,
    )
    from xflow_tpu.launch.watchdog import RunWatchdog

    if forward_args and forward_args[0] == "--":
        forward_args = forward_args[1:]
    if num_slices < 1:
        print("launch-multislice: --slices must be >= 1", file=sys.stderr)
        return 2
    if not run_dir:
        print(
            "launch-multislice: --run-dir is required (the sync tier "
            "lives in <run-dir>/sync)",
            file=sys.stderr,
        )
        return 2
    os.makedirs(run_dir, exist_ok=True)
    sync_dir = os.path.join(run_dir, "sync")
    os.makedirs(sync_dir, exist_ok=True)
    print(
        f"launch-multislice: {num_slices} slice(s) on JAX_PLATFORMS="
        f"{_slice_platform()}",
        file=sys.stderr,
    )
    run_id = resolve_launch_run_id()
    live = set(range(num_slices))
    lock = threading.Lock()
    write_membership(sync_dir, live, run_id=run_id, note="launch")
    procs: dict = {}
    # slices are always shrinkable (no collectives to wedge peers), so
    # the tracker runs in allow-shrink mode unconditionally
    tracker = DeadHostTracker(allow_shrink=True)

    def set_live(j: int, alive: bool, note: str) -> None:
        with lock:
            changed = (j in live) != alive
            if alive:
                live.add(j)
            else:
                live.discard(j)
            if changed:
                write_membership(sync_dir, live, run_id=run_id, note=note)
        if changed:
            print(
                f"launch-multislice: slice {j} "
                f"{'rejoined' if alive else 'left'} the sync group "
                f"({note}); live = {sorted(live)}",
                file=sys.stderr,
            )

    def on_dead(row: dict) -> None:
        # the wedged-slice path: a dead/missing verdict drops the slice
        # from the sync group and KILLS its process, so the per-slice
        # supervisor (below) observes the exit and relaunches it —
        # verdict-to-recovery without any cross-slice teardown
        j = row.get("rank")
        if not isinstance(j, int) or not 0 <= j < num_slices:
            return
        tracker.record(("slice", j))
        set_live(j, False, "watchdog-dead")
        p = procs.get(j)
        if p is not None and p.poll() is None:
            p.kill()

    watchdog = RunWatchdog(
        run_dir,
        num_ranks=num_slices,
        straggler_factor=straggler_factor,
        dead_after_s=dead_after_s,
        poll_s=watchdog_poll_s,
        run_id=run_id,
        on_dead=on_dead,
        gen=0,
    )
    watchdog.start()
    results: dict = {}

    def slice_main(j: int) -> None:
        def attempt(gen: int) -> int:
            args = (
                forward_args if gen == 0 else resume_forward_args(forward_args)
            )
            if gen > 0:
                set_live(j, True, f"relaunch gen {gen}")
            p = _spawn_slice(
                j, num_slices, args, run_dir, sync_dir, run_id, gen
            )
            procs[j] = p
            rc = p.wait()
            if rc != 0:
                tracker.record(("slice", j))
                set_live(j, False, f"exit rc={rc}")
            else:
                # a finished slice publishes no further rounds — leave
                # the group so still-training peers stop waiting on it
                # (their staleness waits re-read membership each poll)
                set_live(j, False, "finished")
            return rc

        results[j] = supervise(
            attempt,
            max_restarts=max_restarts,
            restart_backoff=restart_backoff,
            min_uptime_s=min_uptime_s,
            label=f"launch-multislice[slice{j}]",
        )

    threads = [
        threading.Thread(target=slice_main, args=(j,), name=f"xflow-slice{j}")
        for j in range(num_slices)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    except KeyboardInterrupt:
        terminate_procs([p for p in procs.values() if p is not None])
        raise
    finally:
        watchdog.stop()
    lost = len(tracker.lost)
    if lost:
        print(
            f"launch-multislice: {lost} slice-loss event(s) recorded "
            f"this run (see {os.path.join(run_dir, 'watchdog.jsonl')} "
            "and the kind=sync membership trail)",
            file=sys.stderr,
        )
    return next((rc for rc in results.values() if rc), 0)
