"""The readers of the host timeline's records (`host`, `boundary`):
their arithmetic on records written by hand, nothing to read on records
of a program that has no such fields, and one rehearsal run whose
records carry both."""

import json

import pytest

import run as harness

NEW = ("pass_boundary_pct", "fit_tail_ms", "fit_open_ms", "producer_busy_pct")
HOST_ZERO = {k: 0.0 for k in (
    "read_ms", "parse_ms", "hash_ms", "batch_ms", "pad_ms", "cache_read_ms", "plan_ms",
    "producer_wait_ms", "data_wait_ms", "transfer_ms", "dispatch_call_ms", "prev_ready_ms",
    "loop_other_ms")}


def _read(name, records):
    return harness.load_metric(name).read({"records": records})


def _step(ms=100.0, host=None, boundary=None):
    rec = {"step_time_p50_ms": ms, "data_wait_ms": 1.0, "dispatch_ms": 2.0}
    if host is not None:
        rec["host"] = {**HOST_ZERO, "batches": 1, **host}
    if boundary is not None:
        rec["boundary"] = boundary
    return rec


def _pass(steps, boundary, **host):
    return [_step(boundary=boundary, host=host)] + [_step(host=host) for _ in range(steps - 1)]


FIRST = {"fit_open_ms": 4.0, "first_batch_ms": 30.0, "first_dispatch_ms": 6.0}
LATER = {"fit_tail_ms": 50.0, "occupancy_ms": 20.0, "close_ms": 5.0, "between_fits_ms": 1000.0,
         "fit_open_ms": 10.0, "first_batch_ms": 25.0, "first_dispatch_ms": 5.0}


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_on_records_without_the_fields(name):
    """The parent's records: step timings alone. No value, no raise."""
    assert _read(name, [_step(), _step()]) is None
    assert _read(name, []) is None


def test_pass_boundary_pct_arithmetic():
    recs = _pass(4, FIRST) + _pass(4, LATER) + _pass(4, LATER)
    # the program's own boundary: 40 (first pass: no tail behind it) + 2 x 90;
    # the wall the records cover: 12 x 100 of step intervals + what lies outside them, 4 + 2 x 60
    assert _read("pass_boundary_pct", recs) == pytest.approx(100.0 * 220.0 / 1324.0)


def test_between_fits_is_the_callers_and_is_ignored():
    recs = _pass(4, FIRST) + _pass(4, LATER)
    far = _pass(4, FIRST) + _pass(4, {**LATER, "between_fits_ms": 9e6})
    for name in NEW:
        assert _read(name, recs) == _read(name, far), name


def test_fit_tail_and_open_are_medians_over_passes():
    tails = (50.0, 70.0, 300.0)
    recs = _pass(2, FIRST)
    for t in tails:
        recs += _pass(2, {**LATER, "fit_tail_ms": t, "first_batch_ms": t / 2})
    assert _read("fit_tail_ms", recs) == 70.0  # the first pass has none: three tails
    # opens: 40 (first), then 10 + t/2 + 5 = 40, 50, 165
    assert _read("fit_open_ms", recs) == pytest.approx(45.0)


def test_a_window_of_one_pass():
    """One pass in the window, the first of its trainer: an open, no tail."""
    recs = _pass(3, FIRST, parse_ms=20.0)
    assert _read("fit_tail_ms", recs) is None
    assert _read("fit_open_ms", recs) == 40.0
    assert _read("pass_boundary_pct", recs) == pytest.approx(100.0 * 40.0 / 304.0)
    assert _read("producer_busy_pct", recs) == pytest.approx(20.0)


def test_producer_busy_counts_work_and_not_the_wait_in_put():
    recs = _pass(4, FIRST, parse_ms=30.0, plan_ms=15.0, cache_read_ms=1.0, read_ms=2.0,
                 producer_wait_ms=50.0, transfer_ms=9.0, prev_ready_ms=80.0)
    assert _read("producer_busy_pct", recs) == pytest.approx(48.0)
    # a record the window kept without `host` (none here in practice) adds its step time alone
    assert _read("producer_busy_pct", recs + [_step()]) == pytest.approx(100.0 * 192.0 / 500.0)


def test_rehearsal_records_carry_host_and_boundary(capsys, monkeypatch):
    """`--rehearsal --trace 1` on the CPU: the traced run's step records
    have `host` and `boundary`, and the four readers find them (a
    rehearsal prints no time, so the values are caught on their way)."""
    seen, runs = {}, []
    load = harness.load_metric

    def spying(name):
        mod = load(name)

        class Spy:
            META = mod.META

            @staticmethod
            def read(run):
                runs.append(run)
                seen[name] = mod.read(run)
                return seen[name]

        return Spy

    monkeypatch.setattr(harness, "load_metric", spying)
    rc = harness.main(["--workload", "lr-s29.text-zipf", "--seed", str(2**31 + 29), "--seconds", "0.2",
                       "--trace", "1", "--rehearsal"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["metrics"] == {}
    recs = runs[0]["records"]
    assert recs and all("host" in r for r in recs)
    marks = [r["boundary"] for r in recs if "boundary" in r]
    assert len(marks) == out["passes"]  # one a fit(), on its first step's record
    assert all("fit_tail_ms" in b and "between_fits_ms" in b for b in marks)  # the warm pass is behind them
    assert all(seen[name] is not None and seen[name] >= 0 for name in NEW), seen
    assert 0 < seen["pass_boundary_pct"] < 100
