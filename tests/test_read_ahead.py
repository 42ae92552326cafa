"""The producer reads ahead over the end of a pass (`PassProducer`,
`Trainer._carried_pass`): the next pass — the next epoch of this fit(),
or the first of the next fit() — takes the running producer over if and
only if it would have opened the same stream, and a read-ahead that is
not taken over leaves nothing behind.
"""

import gc
import os
import signal
import threading
import time
import weakref

import jax
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.data.pipeline import PassProducer
from xflow_tpu.data.synth import generate_shards
from xflow_tpu.jsonl import read_jsonl
from xflow_tpu.telemetry import default_registry
from xflow_tpu.train.trainer import Trainer

B = 64
ROWS = 6 * B  # six batches a pass: the head start (2 ready + 1 in hand) is half of it
HEAD = 3


@pytest.fixture(autouse=True)
def _time_limit():
    """Every test here waits on a thread somewhere: none may hang the run."""

    def on_alarm(signum, frame):
        raise TimeoutError("test_read_ahead: a test ran over its time limit")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, 180)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, old)


def make_data(tmp_path, name="train", rows=ROWS, seed=0, shards=1):
    prefix = str(tmp_path / name)
    generate_shards(prefix, shards, rows, num_fields=6, ids_per_field=50, seed=seed)
    return prefix


def make_cfg(prefix, **kw):
    return override(Config(), **{
        "model.name": "lr",
        "model.num_fields": 6,
        "data.train_path": prefix,
        "data.log2_slots": 12,
        "data.max_nnz": 8,
        "data.batch_size": B,
        "train.epochs": 1,
        "train.pred_dump": False,
        **kw,
    })


def prefetch_threads():
    return {t for t in threading.enumerate()
            if t.name == "xflow-prefetch" and t.is_alive()}


def wait_until(cond, what, seconds=20.0):
    deadline = time.time() + seconds
    while not cond():
        assert time.time() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


def settle(trainer, head=HEAD):
    """The read-ahead has built all it may: `head` batches, the worker waits."""
    wait_until(lambda: trainer._read_ahead._head >= head, "the head start")


def record_stream(trainer, sink):
    """Every training pair the fit loop consumes, copied array for array."""
    orig = trainer._coordinated_batches

    def wrapped(path, *args, **kwargs):
        for batch, arrays in orig(path, *args, **kwargs):
            if kwargs.get("enforce_bad_rows", True):
                sink.append((
                    {f: np.array(getattr(batch, f))
                     for f in ("slots", "fields", "mask", "labels", "row_mask")},
                    {k: np.array(v) for k, v in arrays.items()},
                ))
            yield batch, arrays

    trainer._coordinated_batches = wrapped


def no_read_ahead(trainer):
    """The stream as it was before any pass was carried: a prefetch
    thread a pass, gone with the pass."""
    orig = trainer._coordinated_batches

    def wrapped(path, *args, **kwargs):
        kwargs.pop("then", None)
        return orig(path, *args, **kwargs)

    trainer._coordinated_batches = wrapped


def assert_same_stream(got, want):
    assert len(got) == len(want)
    for (gb, ga), (wb, wa) in zip(got, want):
        assert set(ga) == set(wa)
        for k in wb:
            np.testing.assert_array_equal(gb[k], wb[k], err_msg=k)
        for k in wa:
            np.testing.assert_array_equal(ga[k], wa[k], err_msg=k)


def assert_same_state(a, b):
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------- (a) hits change nothing
@pytest.mark.parametrize("shape", ["three_epochs", "three_fits"])
def test_adopted_stream_and_state_equal_the_cold_ones(tmp_path, monkeypatch, shape):
    prefix = make_data(tmp_path)
    epochs, fits = (3, 1) if shape == "three_epochs" else (1, 3)

    def run():
        trainer = Trainer(make_cfg(prefix, **{"train.epochs": epochs}))
        stream, results = [], []
        record_stream(trainer, stream)
        for _ in range(fits):
            results.append(trainer.fit())
            if fits > 1:
                settle(trainer)
        return trainer, stream, results

    carried, got, res = run()
    # every pass but the trainer's first opened on the running producer
    assert sum(r.read_ahead_passes for r in res) == 2
    assert sum(r.read_ahead_discarded for r in res) == 0
    if fits > 1:
        assert [r.read_ahead_batches for r in res] == [0, HEAD, HEAD]
    monkeypatch.setattr(PassProducer, "adopt", lambda self, spec, then: None)
    cold, want, res = run()
    assert sum(r.read_ahead_passes for r in res) == 0
    assert len(want) == 3 * ROWS // B
    assert_same_stream(got, want)
    assert_same_state(carried, cold)


# --------------------------------------------- (b) another stream is never adopted
def _append(prefix):
    extra = prefix + "-extra"
    generate_shards(extra, 1, 2 * B, num_fields=6, ids_per_field=50, seed=7)
    with open(prefix + "-00000", "a") as f, open(extra + "-00000") as more:
        f.write(more.read())


def _truncate(prefix):
    with open(prefix + "-00000") as f:
        lines = f.readlines()
    with open(prefix + "-00000", "w") as f:
        f.writelines(lines[: 4 * B + 5])


def _replace(prefix):
    # the same bytes under a new inode: what an atomic re-upload leaves
    with open(prefix + "-00000", "rb") as f:
        data = f.read()
    with open(prefix + ".new", "wb") as f:
        f.write(data)
    os.replace(prefix + ".new", prefix + "-00000")


def _second_shard(prefix):
    generate_shards(prefix + "-late", 1, 2 * B, num_fields=6, ids_per_field=50, seed=9)
    os.replace(prefix + "-late-00000", prefix + "-00001")


MISSES = {
    # name: (what happens between the two fit() calls, the second call's path)
    "other_path": (lambda prefix: None, "other"),
    "resume_skips": (lambda prefix: None, None),
    "appended": (_append, None),
    "truncated": (_truncate, None),
    "replaced": (_replace, None),
    "appears": (_second_shard, None),
}


@pytest.mark.parametrize("case", sorted(MISSES))
def test_a_pass_over_another_stream_builds_its_own(tmp_path, case):
    prefix = make_data(tmp_path)
    other = make_data(tmp_path, "other", rows=4 * B, seed=3) + "-00000"
    change, path = MISSES[case]
    path = other if path else None

    def second_fit(trainer):
        if case == "resume_skips":
            trainer._resume_data_state = {
                "version": 2, "epoch": 0, "batches": 2, "completed": False,
                "examples": 2 * B, "shard_batches": {"0": 2}, "num_shards": 1,
            }
        stream = []
        record_stream(trainer, stream)
        return trainer.fit(train_path=path), stream

    def build():
        trainer = Trainer(make_cfg(prefix))
        if case == "appears":
            trainer._num_shards = 2  # this rank owns shard 1 too, once it exists
        return trainer

    carried = build()
    carried.fit()
    settle(carried)  # the read-ahead holds three batches of the old stream
    stale = carried._read_ahead
    change(prefix)
    res, got = second_fit(carried)
    assert res.read_ahead_passes == 0 and res.read_ahead_batches == 0
    assert res.read_ahead_discarded == HEAD
    assert carried._read_ahead is not stale
    wait_until(lambda: not stale._thread.is_alive(), "the discarded producer's exit")
    # what a trainer that never read ahead consumes from the same call
    fresh = build()
    no_read_ahead(fresh)
    fres, want = second_fit(fresh)
    assert fres.steps == res.steps and res.steps == len(got)
    assert_same_stream(got, want)
    if case == "resume_skips":
        assert res.steps == ROWS // B - 2
    # and the pass after a miss is carried again
    settle(carried, head=min(HEAD, res.steps))
    again = carried.fit(train_path=path)
    assert again.read_ahead_passes == 1 and again.read_ahead_discarded == 0


# ------------------------------------ (c) a discarded read-ahead leaves no trace
def test_discarded_read_ahead_over_bad_rows_leaves_no_trace(tmp_path):
    from xflow_tpu.testing.faults import write_malformed_libffm

    bad = str(tmp_path / "bad-00000")
    write_malformed_libffm(bad, n_good=150, n_bad=12, n_junk_label=4, seed=1)
    good = make_data(tmp_path, "good", rows=3 * B) + "-00000"

    def run(name, read_ahead):
        qpath = str(tmp_path / name / "quarantine.jsonl")
        mpath = str(tmp_path / name / "metrics.jsonl")
        default_registry().reset()
        trainer = Trainer(make_cfg(str(tmp_path / "bad"), **{
            "data.quarantine_path": qpath, "data.max_bad_rows": 1000,
            "train.metrics_path": mpath, "train.log_every": 1,
            "train.health_metrics": "norms",
        }))
        if not read_ahead:
            no_read_ahead(trainer)
        trainer.fit(train_path=bad)
        if read_ahead:
            # the whole of `bad` is read ahead again, as a new fit()'s
            # first pass: monitored, to be quarantined
            settle(trainer)
        res = trainer.fit(train_path=good)
        assert res.read_ahead_discarded == (HEAD if read_ahead else 0)
        records = [{k: v for k, v in r.items() if k != "ts" and k != "run_id"}
                   for r in read_jsonl(qpath)]
        counters = {k: v for k, v in default_registry().snapshot().items()
                    if k.startswith("data.")}
        # the profiler's run totals and the step records' `host` windows
        prof = trainer.pipeline_prof
        host = sum(r["host"]["batches"] for r in read_jsonl(mpath) if "host" in r)
        return (records, counters, (prof.batches, prof.rows, host),
                int(np.count_nonzero(trainer._health._seen)))

    with_ra = run("carried", True)
    without = run("cold", False)
    assert len(without[0]) == 12  # one record a bad row, from the one pass over `bad`
    assert without[1]["data.bad_rows"] == 12 and without[1]["data.quarantined_rows"] == 12
    assert without[2][0] == without[2][2] == 166 // B + 1 + 3  # batches consumed
    assert with_ra == without


# ----------------------------------------------- (d) no thread outlives its trainer
@pytest.mark.parametrize("how", ["returned", "raised"])
def test_the_producer_goes_with_its_trainer(tmp_path, how):
    from xflow_tpu.testing.faults import abort_after_step

    prefix = make_data(tmp_path)
    before = prefetch_threads()
    trainer = Trainer(make_cfg(prefix))
    trainer.fit()
    if how == "raised":
        abort_after_step(trainer, 2)
        with pytest.raises(RuntimeError, match="injected abort"):
            trainer.fit()
        # fit()'s own exit stopped the pass's producer: nothing waits for
        # the traceback to let go of the loop's frame
        assert not prefetch_threads() - before
    else:
        settle(trainer)
        assert len(prefetch_threads() - before) == 1
    ref = weakref.ref(trainer)
    del trainer
    gc.collect()
    assert ref() is None, "the carried producer keeps its trainer alive"
    assert not prefetch_threads() - before


def test_ten_fits_leave_one_thread(tmp_path):
    prefix = make_data(tmp_path)
    other = make_data(tmp_path, "other", rows=4 * B, seed=3) + "-00000"
    before = prefetch_threads()
    trainer = Trainer(make_cfg(prefix))
    for k in range(10):
        # hits and misses mixed: every third call goes to another path
        trainer.fit(train_path=other if k % 3 == 2 else None)
    wait_until(lambda: len(prefetch_threads() - before) <= 1, "discarded producers' exit")
    assert len(prefetch_threads() - before) == 1
    del trainer
    gc.collect()
    assert not prefetch_threads() - before


# ------------------------------------------------- (e) the records, and the scope
def test_final_record_and_boundary_say_what_was_adopted(tmp_path):
    prefix = make_data(tmp_path)
    other = make_data(tmp_path, "other", rows=4 * B, seed=3) + "-00000"
    mpath = str(tmp_path / "run" / "metrics.jsonl")
    trainer = Trainer(make_cfg(prefix, **{
        "train.metrics_path": mpath, "train.log_every": 1}))
    for path in (None, None, other):
        trainer.fit(train_path=path)
        settle(trainer)
    recs = read_jsonl(mpath)
    finals = [r for r in recs if r.get("final")]
    firsts = [r["boundary"] for r in recs if "boundary" in r]
    got = [(f["read_ahead_passes"], f["read_ahead_batches"], f["read_ahead_discarded"])
           for f in finals]
    # cold; a hit, bounded by depth + 1; a miss
    assert got == [(0, 0, 0), (1, HEAD, 0), (0, 0, HEAD)]
    assert [b["adopted"] for b in firsts] == [False, True, False]
    # the adopted pass's open is a take from a full queue
    assert firsts[1]["first_batch_ms"] < firsts[0]["first_batch_ms"]


def test_evaluate_and_tail_streaming_never_read_ahead(tmp_path):
    prefix = make_data(tmp_path)
    before = prefetch_threads()
    trainer = Trainer(make_cfg(prefix, **{"data.test_path": prefix}))
    trainer.evaluate(dump=False)
    assert trainer._read_ahead is None and not prefetch_threads() - before
    # a fit() carries; the eval pass after it neither adopts nor disturbs
    trainer.fit()
    settle(trainer)
    held = trainer._read_ahead
    trainer.evaluate(dump=False)
    assert trainer._read_ahead is held and held._head == HEAD
    assert trainer.fit().read_ahead_batches == HEAD
    del trainer, held
    gc.collect()
    tail = Trainer(make_cfg(prefix, **{
        "data.stream": "tail", "data.stream_idle_s": 0.2, "data.stream_poll_s": 0.05,
        "data.stream_dir": str(tmp_path / "spool")}))
    res = tail.fit()
    assert res.steps == ROWS // B
    assert tail._read_ahead is None and not prefetch_threads() - before


# ------------------------------------------ the producer alone, threads switching fast
def test_producer_keeps_order_and_effects_under_fast_thread_switches():
    """Hundreds of passes, adopted as fast as they end, with the
    interpreter switching threads every 10 us: every pass is the same
    items in order, what they deferred ran exactly once and only for
    items taken, and a discarded head start ran nothing."""
    import sys

    from xflow_tpu.data.pipeline import PassSpec

    N = 7
    spec = PassSpec(shards=(), skips=())
    other = PassSpec(shards=(), skips=(), quarantine=False)
    ran = []

    def open_pass(spec, defer):
        for i in range(N):
            defer(ran.append, i)
            yield i

    before = prefetch_threads()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for trial in range(10):
            del ran[:]
            producer = PassProducer(open_pass, depth=2)
            producer.start(spec, then=spec)
            for k in range(1, 41):
                assert list(producer.batches()) == list(range(N))
                assert ran == list(range(N)) * k  # nothing of the read-ahead yet
                if k < 40:
                    head = producer.adopt(spec, spec)
                    assert head is not None and 0 <= head <= HEAD
            assert producer.adopt(other, other) is None  # another stream: a miss
            assert 0 <= producer.stop() <= HEAD
            producer._thread.join(timeout=10)
            assert not producer._thread.is_alive()
            assert ran == list(range(N)) * 40
    finally:
        sys.setswitchinterval(old)
    assert not prefetch_threads() - before
