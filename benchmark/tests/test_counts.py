"""The byte and operation counts on a batch worked by hand."""

import numpy as np

from lib import counts

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_step_needs_by_hand():
    # 2 rows x 3 features, slots {5, 5, 9 | 5, 2, 2}: 6 occurrences, 3 distinct slots, FM width 11
    slots = np.array([[5, 5, 9], [5, 2, 2]])
    shape = counts.batch_shape(slots, batch_size=2)
    assert shape == {"distinct_slots": 3.0, "occurrences": 6.0}
    needs = counts.step_needs(3, 6, 11)
    assert needs["bytes"] == 3 * 6 * 11 * 4 + 6 * 12 == 864
    assert needs["flops"] == 6 * 7 * 11 + 3 * 11 * 12 == 858


def test_batch_shape_is_a_mean_over_batches():
    slots = np.array([[1, 2], [3, 4], [1, 1], [1, 1]])
    assert counts.batch_shape(slots, 2) == {"distinct_slots": 2.5, "occurrences": 4.0}


def test_kernel_needs_by_hand():
    assert counts.gather_needs(3, 6, 11)["bytes"] == (3 + 6) * 44 + 24
    assert counts.scatter_ftrl_needs(3, 6, 11)["bytes"] == 6 * 44 + 24 + 3 * 6 * 44


def test_least_seconds_names_its_bound():
    t, bound = counts.least_seconds({"bytes": 819e9, "flops": 1.0}, PEAK)
    assert (t, bound) == (1.0, "bytes")
    t, bound = counts.least_seconds({"bytes": 1.0, "flops": 197e12 * 4}, PEAK, chips=4)
    assert (round(t, 9), bound) == (1.0, "flops")


def test_needed_bytes_never_exceed_what_any_step_moves():
    # the count per distinct slot is what ONE touch of w, n, z costs: any
    # implementation that updates the slot moves at least that
    for u, n, w in ((1, 1, 1), (583_000, 2_097_152, 11)):
        assert counts.step_needs(u, n, w)["bytes"] <= n * (6 * w * 4 + 12)


def test_kernel_roofline_share_by_hand():
    # one chip, 2 traced steps, the kernel ran 2 x 1 ms; needed bytes of the
    # gather for 3 distinct slots and 6 occurrences of width 11: 420 B
    ops = [[["gather.5[pallas]", 0, 1e6], ["x", 2e6, 5], ["gather.5[pallas]", 3e6, 1e6]]]
    run = {"trace": {"devices": 1, "ops": ops}, "peak": PEAK, "trace_steps": 2, "chips": 1, "width": 11,
           "shape": lambda: {"distinct_slots": 3.0, "occurrences": 6.0}}
    got = counts.kernel_roofline_pct(run, r"^gather[.\d]*\[pallas\]$", counts.gather_needs)
    assert abs(got - 100.0 * (420 / 819e9) / 1e-3) < 1e-12
    assert counts.kernel_roofline_pct(run, "absent", counts.gather_needs) is None
    assert counts.kernel_roofline_pct({**run, "trace": None}, "gather", counts.gather_needs) is None
