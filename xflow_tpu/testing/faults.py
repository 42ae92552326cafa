"""Fault injectors for the resilience subsystem (docs/ROBUSTNESS.md).

One shared library drives every recovery path end-to-end — the tier-1
fault-injection tests (tests/test_fault_injection.py) and the operator
CLI (tools/corrupt_ckpt.py) call the SAME functions, so what the tests
prove recoverable is exactly what an operator can rehearse against a
real checkpoint dir:

- `poison_nan_batches`: wrap a Trainer so chosen steps' labels become
  NaN — the non-finite guard's trigger (`train.nonfinite_guard`).
- `truncate_file` / `bitflip_file`: byte-level corruption primitives.
- `corrupt_npz_checkpoint` / `corrupt_orbax_checkpoint`: apply them to
  the newest (or a chosen) checkpoint — the self-healing restore's
  trigger (`checkpoint.restore_any`).
- `write_malformed_libffm`: shards mixing good rows with junk labels,
  feature-less lines, separators-only lines, and a truncated final
  line — the bad-record quarantine's trigger (`data.max_bad_rows`) and
  the counter/parser parity tests' input.
- `kill_step_from_env` / `hard_kill`: env-gated SIGKILL at step K
  (generation-gated so a supervised relaunch survives) and
  `abort_after_step`: the in-process crash analog — the elastic
  recovery layer's triggers (supervised auto-restart + exact data
  resume, docs/ROBUSTNESS.md "Elastic recovery").
- `serve_faults_from_env`: the serving-fleet chaos injectors — a
  per-batch delay (slow replica: circuit-breaker/hedging drills) and a
  kill-after-N-batches SIGKILL (replica dying mid-load; generation-
  gated so the supervised relaunch rejoins) — tools/smoke_serve_fleet.sh
  drives both through `xflow serve-fleet` (docs/SERVING.md).

The reference has no analog: it neither checkpoints nor validates input
(SURVEY.md §5 A3), so every one of these faults is either fatal or
silent there.
"""

from __future__ import annotations

import errno
import os
import random
import time
from typing import Iterable, Optional

import numpy as np


# ------------------------------------------------------------- byte faults
def truncate_file(path: str, keep_frac: float = 0.5,
                  keep_bytes: Optional[int] = None) -> int:
    """Truncate `path` to `keep_bytes` (or keep_frac of its size).
    Returns the new size. Emulates a crashed/partial write."""
    size = os.path.getsize(path)
    keep = keep_bytes if keep_bytes is not None else int(size * keep_frac)
    keep = max(0, min(size, keep))
    with open(path, "rb+") as f:
        f.truncate(keep)
    return keep


def bitflip_file(path: str, offset: Optional[int] = None, count: int = 8,
                 seed: int = 0) -> list[int]:
    """Flip one bit in each of `count` bytes (random offsets from `seed`
    unless `offset` pins the first). Returns the offsets touched.
    Emulates silent media/transfer corruption."""
    size = os.path.getsize(path)
    if size == 0:
        return []
    rng = random.Random(seed)
    offsets = sorted(
        {offset if offset is not None and i == 0 else rng.randrange(size)
         for i in range(count)}
    )
    with open(path, "rb+") as f:
        for off in offsets:
            f.seek(off)
            b = f.read(1)
            f.seek(off)
            f.write(bytes([b[0] ^ (1 << rng.randrange(8))]))
    return offsets


def bitflip_npz_array(path: str, member: Optional[str] = None, count: int = 8,
                      seed: int = 0, offset: Optional[int] = None) -> list[int]:
    """Flip bits inside ONE array member's payload of an .npz and
    REWRITE the container with fresh zip CRCs — SILENT corruption by
    construction: a raw `bitflip_file` on an npz trips the zip layer's
    own CRC32 on read (the loud failure mode `restore_any` already
    heals), while this flip survives every container-level check and is
    caught only by the per-array digests meta.json records at save
    (checkpoint v3, `verify_digest`). The .npy header is skipped too —
    a damaged header fails loudly at parse, which is not the drill.

    `member` defaults to the largest array (the table payload).
    `offset` pins the first flipped byte, RELATIVE to the array payload
    (offset 0 = the first data byte after the header); out-of-payload
    offsets raise ValueError rather than silently invalidating the
    drill. Returns the flipped offsets within the member's bytes."""
    import struct
    import zipfile

    with zipfile.ZipFile(path) as z:
        names = z.namelist()
        target = member or max(names, key=lambda n: z.getinfo(n).file_size)
        blobs = {n: z.read(n) for n in names}
    data = bytearray(blobs[target])
    # .npy layout: \x93NUMPY, major, minor, header-len (2 bytes v1.x /
    # 4 bytes v2.x), header, then raw array bytes — flip only past the
    # header so dtype/shape parse fine and the VALUES are what changed
    if len(data) < 12 or data[:6] != b"\x93NUMPY":
        raise ValueError(f"{target!r} in {path!r} is not an .npy member")
    if data[6] >= 2:
        start = 12 + struct.unpack("<I", data[8:12])[0]
    else:
        start = 10 + struct.unpack("<H", data[8:10])[0]
    if start >= len(data):
        raise ValueError(f"{target!r} has no array payload to corrupt")
    first = None
    if offset is not None:
        first = start + int(offset)
        if not start <= first < len(data):
            raise ValueError(
                f"offset {offset} is outside {target!r}'s array payload "
                f"(0..{len(data) - start - 1})"
            )
    rng = random.Random(seed)
    offsets = sorted(
        {first if first is not None and i == 0 else rng.randrange(start, len(data))
         for i in range(count)}
    )
    for off in offsets:
        data[off] ^= 1 << rng.randrange(8)
    blobs[target] = bytes(data)
    # rewrite uncompressed (np.savez's own layout): the zip member CRCs
    # are recomputed over the CORRUPTED bytes, so the container stays
    # self-consistent and only the digest layer can tell
    with zipfile.ZipFile(path, "w", zipfile.ZIP_STORED) as z:
        for n in names:
            z.writestr(n, blobs[n])
    return offsets


# ------------------------------------------------------ checkpoint corruption
def _apply(path: str, mode: str, **kw) -> str:
    if mode == "truncate":
        truncate_file(path, **{k: v for k, v in kw.items()
                               if k in ("keep_frac", "keep_bytes")})
    elif mode == "bitflip":
        bitflip_file(path, **{k: v for k, v in kw.items()
                              if k in ("offset", "count", "seed")})
    else:
        raise ValueError(f"mode={mode!r}: expected truncate|bitflip")
    return path


def corrupt_npz_checkpoint(ckpt_dir: str, step: Optional[int] = None,
                           mode: str = "truncate", target: str = "state",
                           **kw) -> str:
    """Corrupt a file of the newest (or given) COMMITTED checkpoint.
    The commit marker is left intact — the point is a checkpoint that
    LOOKS valid and fails only when read.

    target="state" (default): `state.npz` — the case restore_any heals
    by walking back to the previous committed step. mode="bitflip"
    there flips bytes INSIDE an array payload and rewrites the
    container (`bitflip_npz_array`): the zip CRCs stay self-consistent,
    so only the v3 per-array digests catch it — the silent-corruption
    drill. (mode="truncate", and raw flips via the CLI's --file, stay
    the loud container-level failure modes.)
    target="data_state": `data_state.json` (elastic recovery) — the
    case read_data_state DOWNGRADES: the model still restores, the run
    resumes with a fresh stream, and the downgrade is logged. Operators
    drill both through tools/corrupt_ckpt.py."""
    from xflow_tpu.train.checkpoint import committed_steps, data_state_path

    if step is None:
        steps = committed_steps(ckpt_dir)
        if not steps:
            raise FileNotFoundError(f"no committed checkpoint under {ckpt_dir!r}")
        step = steps[0]
    if target == "data_state":
        victim = data_state_path(ckpt_dir, step, fmt="npz")
        if not os.path.exists(victim):
            raise FileNotFoundError(
                f"checkpoint step {step} has no data_state (pre-v2 "
                f"checkpoint?) under {ckpt_dir!r}"
            )
    elif target == "state":
        victim = os.path.join(ckpt_dir, f"step_{step}", "state.npz")
        if mode == "bitflip":
            bitflip_npz_array(
                victim,
                **{k: v for k, v in kw.items()
                   if k in ("member", "offset", "count", "seed")},
            )
            return victim
    else:
        raise ValueError(f"target={target!r}: expected state|data_state")
    return _apply(victim, mode, **kw)


def corrupt_orbax_checkpoint(ckpt_dir: str, step: Optional[int] = None,
                             mode: str = "truncate",
                             target: str = "manifest", **kw) -> str:
    """Corrupt a file inside the newest (or given) orbax checkpoint dir.

    target="manifest" (default): the top-level OCDBT manifest — the torn
    partial-upload scenario; its loss makes restore fail LOUDLY
    (DATA_LOSS), which is what restore_any's walk-back heals.
    target="largest": the biggest data file (the table shards). CAVEAT,
    measured on this tensorstore: byte corruption THERE restores without
    error and yields wrong values — OCDBT data reads are not
    checksum-verified, unlike npz (zip CRC32 catches every flip). Use
    npz where end-to-end integrity matters (docs/ROBUSTNESS.md)."""
    from xflow_tpu.train.checkpoint import orbax_steps

    if step is None:
        steps = orbax_steps(ckpt_dir)
        if not steps:
            raise FileNotFoundError(f"no orbax checkpoint under {ckpt_dir!r}")
        step = steps[0]
    root = os.path.join(ckpt_dir, f"orbax_step_{step}")
    if target == "data_state":
        from xflow_tpu.train.checkpoint import data_state_path

        victim = data_state_path(ckpt_dir, step, fmt="orbax")
        if not os.path.exists(victim):
            raise FileNotFoundError(
                f"orbax step {step} has no data_state sibling under "
                f"{ckpt_dir!r}"
            )
        return _apply(victim, mode, **kw)
    if target == "manifest":
        victim = os.path.join(root, "manifest.ocdbt")
        if not os.path.exists(victim):
            raise FileNotFoundError(f"no OCDBT manifest under {root!r}")
    elif target == "largest":
        victim, largest_size = None, -1
        for dirpath, _, files in os.walk(root):
            for name in files:
                p = os.path.join(dirpath, name)
                s = os.path.getsize(p)
                if s > largest_size:
                    victim, largest_size = p, s
        if victim is None:
            raise FileNotFoundError(f"no files under {root!r}")
    else:
        raise ValueError(
            f"target={target!r}: expected manifest|largest|data_state"
        )
    return _apply(victim, mode, **kw)


# --------------------------------------------------------------- disk faults
def ckpt_write_fault(tier: str):
    """Disk-fault injector for checkpoint WRITES — the async-tiered
    durability drills' trigger (docs/ROBUSTNESS.md "Async tiered
    checkpointing"). Returns a callback `fault(tmp_path)` the writer
    invokes on each staged temp file just before its commit rename, or
    None when no fault is armed — resolved ONCE per save per tier, so
    the ENOSPC byte budget is per-save, not cumulative across a run.

    Env contract (tools/smoke_durable.sh and tests/test_durable_ckpt.py
    export these):
    - XFLOW_FAULT_CKPT_ENOSPC_BYTES: once the save's cumulative staged
      bytes pass this budget, raise OSError(ENOSPC) — a volume filling
      up mid-write. The trainer's async writer latches degraded mode
      and falls back to replica-only saves.
    - XFLOW_FAULT_CKPT_SLOW_S_PER_MB: sleep size/1e6 * this per staged
      file — a slow disk. Widens the in-flight window so the
      kill-mid-async-save and skip-on-busy drills land deterministically.
    - XFLOW_FAULT_CKPT_TIER: restrict to "primary" or "replica"
      (default: both tiers).

    Injection rides the npz temp+replace path and the replica mirror's
    per-file copy; the orbax main step dir writes through orbax's own
    machinery and is NOT injected (its sidecars are).
    """
    target = os.environ.get("XFLOW_FAULT_CKPT_TIER")
    if target is not None and target != tier:
        return None

    def _num(name: str, cast, default):
        try:
            return cast(os.environ.get(name, default) or default)
        except ValueError:
            return cast(default)

    enospc = _num("XFLOW_FAULT_CKPT_ENOSPC_BYTES", int, 0)
    slow = _num("XFLOW_FAULT_CKPT_SLOW_S_PER_MB", float, 0.0)
    if enospc <= 0 and slow <= 0:
        return None
    written = {"bytes": 0}

    def fault(tmp_path: str) -> None:
        size = os.path.getsize(tmp_path)
        if slow > 0:
            time.sleep(size / 1e6 * slow)
        written["bytes"] += size
        if 0 < enospc < written["bytes"]:
            raise OSError(
                errno.ENOSPC,
                "injected ENOSPC (XFLOW_FAULT_CKPT_ENOSPC_BYTES)",
                tmp_path,
            )

    return fault


# -------------------------------------------------------------- kill faults
def kill_step_from_env(rank: int) -> int:
    """1-based step at which this rank hard-kills itself (0 = off) — the
    elastic-recovery drill injector, resolved ONCE at fit() start like
    the pacing faults (zero per-step cost when unset).

    Env contract (the launch-local auto-restart drill exports these):
    - XFLOW_FAULT_KILL_STEP: SIGKILL this process the moment that
      1-based step completes (after its heartbeat/checkpoint cadence
      ran, so a kill on a checkpoint boundary leaves that step
      committed) — a preemption without grace.
    - XFLOW_FAULT_KILL_RANK: restrict the kill to one rank (default:
      all ranks).
    - XFLOW_FAULT_KILL_GEN (default 0): only kill in this restart
      generation — the supervised relaunch (which inherits the env)
      must survive, not die at step K forever.
    """
    try:
        step = int(os.environ.get("XFLOW_FAULT_KILL_STEP", 0) or 0)
    except ValueError:
        return 0
    if step <= 0:
        return 0
    r = os.environ.get("XFLOW_FAULT_KILL_RANK")
    if r is not None:
        try:
            if int(r) != rank:
                return 0
        except ValueError:
            return 0
    from xflow_tpu.telemetry import resolve_restart_gen

    try:
        want_gen = int(os.environ.get("XFLOW_FAULT_KILL_GEN", 0) or 0)
    except ValueError:
        want_gen = 0
    return step if resolve_restart_gen() == want_gen else 0


def hard_kill() -> None:
    """SIGKILL this process — no atexit, no finally blocks, no flushes
    beyond what already hit the disk: the closest userspace emulation of
    a preempted/OOM-killed host."""
    import signal

    try:
        os.kill(os.getpid(), signal.SIGKILL)
    except (OSError, AttributeError):
        pass
    os._exit(137)  # unreachable on POSIX; belt for exotic platforms


def abort_after_step(trainer, step: int) -> None:
    """Make the trainer's TRAINING stream raise RuntimeError right after
    the 1-based global step `step`'s batch is consumed — the in-process
    analog of a mid-run crash (the subprocess drills use
    kill_step_from_env instead). Checkpoints committed up to the abort
    survive, so a resume exercises the exact-stream data_state path;
    eval streams pass through untouched (same seam and counting rule as
    poison_nan_batches)."""
    orig = trainer._coordinated_batches
    counter = [0]

    def wrapped(path, *args, **kwargs):
        training = kwargs.get("enforce_bad_rows", True)
        for batch, arrays in orig(path, *args, **kwargs):
            yield batch, arrays
            if training:
                counter[0] += 1
                if counter[0] >= step:
                    raise RuntimeError(
                        f"injected abort after step {counter[0]} "
                        "(testing/faults.abort_after_step)"
                    )

    trainer._coordinated_batches = wrapped


# ------------------------------------------------------------- serve faults
def serve_faults_from_env() -> tuple[float, int]:
    """(per_batch_delay_s, kill_after_batches) for THIS serve process —
    the serving-fleet chaos injectors, resolved ONCE at ServeApp
    construction like the fit-loop faults (zero per-batch cost unset).

    Env contract (tools/smoke_serve_fleet.sh exports these):
    - XFLOW_FAULT_SERVE_DELAY_S: sleep this long before EVERY device
      batch — a persistently slow replica (the router's circuit breaker
      and hedging drills, docs/SERVING.md failure matrix).
    - XFLOW_FAULT_SERVE_KILL_BATCHES: SIGKILL the process right after
      the Nth answered batch (responses already in flight) — a replica
      dying MID-LOAD, deterministic where a timed external kill races
      the bench.
    - XFLOW_FAULT_SERVE_REPLICA: restrict either fault to one fleet
      replica index (default: all; matched against XFLOW_REPLICA via
      telemetry.resolve_replica).
    - XFLOW_FAULT_SERVE_KILL_GEN (default 0): only kill in this restart
      generation — the supervised relaunch (which inherits the env)
      must survive and REJOIN, not re-die forever (same contract as
      XFLOW_FAULT_KILL_GEN).
    """
    from xflow_tpu.telemetry import resolve_replica, resolve_restart_gen

    def _num(name: str, cast, default):
        try:
            return cast(os.environ.get(name, default) or default)
        except ValueError:
            return cast(default)

    target = os.environ.get("XFLOW_FAULT_SERVE_REPLICA")
    if target is not None:
        try:
            if int(target) != resolve_replica():
                return 0.0, 0
        except (ValueError, TypeError):
            return 0.0, 0
    delay = _num("XFLOW_FAULT_SERVE_DELAY_S", float, 0.0)
    kill = _num("XFLOW_FAULT_SERVE_KILL_BATCHES", int, 0)
    if kill > 0 and resolve_restart_gen() != _num(
        "XFLOW_FAULT_SERVE_KILL_GEN", int, 0
    ):
        kill = 0
    return max(delay, 0.0), max(kill, 0)


# -------------------------------------------------------------- sync faults
def sync_faults_from_env() -> tuple[int, float]:
    """(kill_round, sync_delay_s) for THIS slice's sync tier — the
    multi-slice chaos injectors (parallel/multislice.SliceSyncer
    resolves them ONCE at construction, zero per-round cost unset).

    Env contract (tests/test_multislice.py::test_sync_fault_env_parsing):
    - XFLOW_FAULT_SLICE_KILL_ROUND: SIGKILL this slice the moment it
      ENTERS that 1-based sync round, before publishing its delta — the
      slice-loss drill: survivors must drop it from the sync group and
      continue degraded, and its supervised relaunch must catch up from
      the freshest published snapshot.
    - XFLOW_FAULT_SYNC_DELAY_S: sleep this long inside EVERY sync round
      — a persistently straggling slice (the staleness-bound /
      proceed-on-stale drill; peers see its lag grow past K).
    - XFLOW_FAULT_SLICE: restrict either fault to one slice index
      (default: all; matched against XFLOW_SLICE via
      telemetry.resolve_slice). XFLOW_FAULT_SLICE_KILL_SLICE /
      XFLOW_FAULT_SYNC_DELAY_SLICE override it per injector — the
      smoke drill kills slice 1 while pacing slice 0 as a straggler so
      the survivor's sync trail deterministically records the
      leave/degraded/rejoin sequence.
    - XFLOW_FAULT_SLICE_KILL_GEN (default 0): only kill in this restart
      generation — the relaunched slice (which inherits the env) must
      survive and REJOIN, not re-die at round R forever (same contract
      as XFLOW_FAULT_KILL_GEN).
    """
    from xflow_tpu.telemetry import resolve_restart_gen, resolve_slice

    def _num(name: str, cast, default):
        try:
            return cast(os.environ.get(name, default) or default)
        except ValueError:
            return cast(default)

    def _targeted(var: str) -> bool:
        """True when the injector guarded by `var` aims at THIS slice
        (unset target = every slice; unparseable = no slice)."""
        target = os.environ.get(var, os.environ.get("XFLOW_FAULT_SLICE"))
        if target is None:
            return True
        try:
            return int(target) == resolve_slice()
        except (ValueError, TypeError):
            return False

    kill = (
        _num("XFLOW_FAULT_SLICE_KILL_ROUND", int, 0)
        if _targeted("XFLOW_FAULT_SLICE_KILL_SLICE") else 0
    )
    # the straggler can aim at a DIFFERENT slice than the kill (the
    # smoke drill paces the survivor while killing its peer)
    delay = (
        _num("XFLOW_FAULT_SYNC_DELAY_S", float, 0.0)
        if _targeted("XFLOW_FAULT_SYNC_DELAY_SLICE") else 0.0
    )
    if kill > 0 and resolve_restart_gen() != _num(
        "XFLOW_FAULT_SLICE_KILL_GEN", int, 0
    ):
        kill = 0
    return max(kill, 0), max(delay, 0.0)


# ------------------------------------------------------------ pacing faults
def fit_delays_from_env(rank: int) -> tuple[float, int, float]:
    """(per_step_sleep_s, stall_step, stall_s) for this rank — the
    straggler/hang drill injector the fit loop resolves ONCE at start
    (zero per-step cost when unset).

    Env contract (the launch-local watchdog drill exports these):
    - XFLOW_FAULT_STEP_DELAY_S: sleep this long before EVERY step — a
      persistently slow host.
    - XFLOW_FAULT_STALL_S (+ XFLOW_FAULT_STALL_STEP, default 1): sleep
      once, at that 1-based step — a rank that stops progressing while
      its peers run ahead, the heartbeat watchdog's straggler signature.
    - XFLOW_FAULT_DELAY_RANK: restrict either fault to one rank
      (default: all ranks).
    """
    r = os.environ.get("XFLOW_FAULT_DELAY_RANK")
    if r is not None:
        try:
            if int(r) != rank:
                return 0.0, 0, 0.0
        except ValueError:
            return 0.0, 0, 0.0
    delay = float(os.environ.get("XFLOW_FAULT_STEP_DELAY_S", 0) or 0)
    stall = float(os.environ.get("XFLOW_FAULT_STALL_S", 0) or 0)
    stall_step = int(os.environ.get("XFLOW_FAULT_STALL_STEP", 1) or 1)
    return delay, stall_step, stall


# ------------------------------------------------------------- data faults
def poison_nan_batches(trainer, steps: Iterable[int],
                       value: float = float("nan")) -> None:
    """Make the trainer's batch stream deliver `value` as every label of
    the 1-based global step indices in `steps` (counted across epochs).

    Injection happens at the (batch, arrays) seam the fit loop consumes
    — after parsing, before device transfer — because libffm labels
    cannot be non-finite by construction (label = 1 iff strtod(tok) >
    1e-7), so a NaN batch models an upstream feature-store bug, exactly
    the failure the non-finite guard exists for."""
    bad = set(int(s) for s in steps)
    orig = trainer._coordinated_batches
    counter = [0]

    def wrapped(path, *args, **kwargs):
        # only TRAINING streams advance the step counter: eval/predict
        # passes announce themselves with enforce_bad_rows=False, and
        # counting their batches would drift the poisoned indices off
        # the fit loop's steps whenever train.eval_every interleaves
        # eval passes between epochs
        training = kwargs.get("enforce_bad_rows", True)
        for batch, arrays in orig(path, *args, **kwargs):
            if training:
                counter[0] += 1
                if counter[0] in bad:
                    arrays = dict(arrays)
                    arrays["labels"] = np.full_like(
                        np.asarray(arrays["labels"]), value
                    )
            yield batch, arrays

    trainer._coordinated_batches = wrapped


def write_malformed_libffm(path: str, n_good: int = 40, n_bad: int = 6,
                           n_junk_label: int = 4, n_nonrows: int = 5,
                           seed: int = 0, truncated_tail: bool = False) -> dict:
    """Write a libffm shard mixing good rows with malformed content.

    Composition (shuffled, seeded):
    - `n_good` well-formed rows (`label\\tf:id:1 ...`);
    - `n_bad` BAD rows: labeled lines whose every feature token is
      malformed (no ':'), so they parse to zero features — counted
      rows, quarantine fodder;
    - `n_junk_label` rows with junk labels but valid features (strtod
      yields 0.0 → label 0; the row itself is fine);
    - `n_nonrows` lines that are NOT rows for either parser: empty,
      whitespace-only, and label-only lines without a separator;
    - `truncated_tail`: ends the file mid-token without a newline (a
      torn write); the partial line still contains a separator, so both
      the counters and the parsers must agree on treating it as a row.

    Returns {"rows": ..., "bad": ..., "lines": ...} where `rows` is the
    count BOTH `count_rows` and `native_count_rows` must report and both
    parsers must yield, and `bad` the zero-feature subset.
    """
    rng = random.Random(seed)
    lines = []
    for i in range(n_good):
        toks = " ".join(
            f"{f}:{rng.randrange(1000)}:1" for f in range(rng.randrange(1, 5))
        )
        lines.append((f"{rng.randrange(2)}\t{toks}", "good"))
    for i in range(n_bad):
        junk = " ".join(rng.choice(["garbage", "??", "novalue", "a_b"])
                        for _ in range(rng.randrange(1, 3)))
        lines.append((f"{rng.randrange(2)}\t{junk}", "bad"))
    for i in range(n_junk_label):
        lines.append((f"abc{i}\t0:{rng.randrange(1000)}:1", "junk_label"))
    # non-rows for BOTH parsers: empty, whitespace-only (incl. a lone
    # tab, which strips to empty), and label-only lines with no separator
    nonrows = ["", "   ", "\t", "1", "justalabel"][:n_nonrows]
    lines.extend((l, "nonrow") for l in nonrows)
    rng.shuffle(lines)
    tail = None
    if truncated_tail:
        # a separator is present, the final token is torn mid-way
        tail = "1\t3:12345"
    rows = sum(1 for _, kind in lines if kind != "nonrow")
    bad = sum(1 for _, kind in lines if kind == "bad")
    with open(path, "w") as f:
        for text, _ in lines:
            f.write(text + "\n")
        if tail is not None:
            f.write(tail)  # no trailing newline
    if tail is not None:
        rows += 1
    return {"rows": rows, "bad": bad, "lines": len(lines) + (1 if tail else 0)}
