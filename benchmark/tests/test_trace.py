"""The reduction from a trace to numbers, on traces written by hand and
on one cut from a real trace of the chip."""

import gzip
import json
import os

import pytest

from lib import trace

NAMES = trace.load_names(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _trace(dev_events, host_events=()):
    planes = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": [["jit_step", 0, 1000]]},
        {"name": "XLA Ops", "events": ev}]} for i, ev in enumerate(dev_events)]
    planes.append({"name": "/host:CPU", "lines": [{"name": "python", "events": list(host_events)}]})
    return {"planes": planes}


def test_busy_union_and_idle_share():
    # ops at [0,100) [50,150) [300,400): union 250 of a 400 window
    tr = _trace([[["a", 0, 100], ["b", 50, 100], ["c", 300, 100]]])
    s = trace.summarize(tr, NAMES, chips=1)
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(400e-9)
    assert s["busy_s"] == pytest.approx(250e-9)


def test_busy_is_averaged_over_devices():
    tr = _trace([[["a", 0, 100]], [["a", 0, 50], ["b", 150, 50]]])
    s = trace.summarize(tr, NAMES, chips=2)
    assert s["busy_s"] == pytest.approx(100e-9)
    assert s["window_s"] == pytest.approx(200e-9)


def test_wrappers_that_cover_their_children_are_dropped():
    tr = _trace([[["while.3", 0, 1000], ["fusion.1", 0, 100]]])
    assert trace.summarize(tr, NAMES, 1)["busy_s"] == pytest.approx(100e-9)


def test_kernel_time_sums_matching_ops():
    ops = trace.device_ops(_trace([[["gather_sorted.1", 0, 10], ["x", 10, 5], ["gather_sorted.2", 20, 30]]]), NAMES)
    assert trace.op_seconds(ops, "gather_sorted") == pytest.approx(40e-9)
    assert trace.op_seconds(ops, "absent") == 0.0


def test_exposed_collective_is_what_no_compute_hides():
    # all-to-all [0,100); compute [40,70) hides 30 of it; all-reduce [200,250) under compute [190,300)
    ops = trace.device_ops(_trace([[["all-to-all.1", 0, 100], ["fusion", 40, 30],
                                    ["fusion.2", 190, 110], ["all-reduce", 200, 50]]]), NAMES)
    assert trace.exposed_seconds(ops, "all-to-all|all-reduce") == pytest.approx(70e-9)


def test_idle_gaps_go_to_the_shortest_covering_host_span():
    tr = _trace([[["a", 0, 100], ["b", 300, 100], ["c", 1000, 10]]],
                [["bench:fit_pass", 0, 2000], ["PjitFunction(step)", 150, 100]])
    gaps = dict(trace.summarize(tr, NAMES, 1)["idle_gaps"])
    assert gaps == {"PjitFunction(step)": pytest.approx(200e-9), "bench:fit_pass": pytest.approx(600e-9)}


def test_top_ops_fold_numbered_suffixes():
    tr = _trace([[["fusion.1", 0, 10], ["fusion.22", 10, 30], ["copy", 50, 5]]])
    assert trace.summarize(tr, NAMES, 1)["device_ops"] == [["fusion", pytest.approx(40e-9)], ["copy", pytest.approx(5e-9)]]


def test_a_trace_with_no_device_plane_reads_nothing():
    assert trace.summarize({"planes": [{"name": "/host:CPU", "lines": []}]}, NAMES, 1) == {"devices": 0}


def test_recorded_trace_of_the_chip():
    """A cut of a real trace (fm-v10-s25.text-zipf on a v5e, PR 26): the
    names in trace_names.json and in the kernel metrics find their
    events, and busy never exceeds the window."""
    path = os.path.join(os.path.dirname(__file__), "data", "v5e_fm_trace_cut.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this tree")
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    s = trace.summarize(tr, NAMES, 1)
    assert s["devices"] == 1 and 0 < s["busy_s"] <= s["window_s"]
    import run as harness

    for name in ("gather_roofline", "scatter_ftrl_roofline"):
        assert trace.op_seconds(s["ops"], harness.load_metric(name).KERNEL) > 0
