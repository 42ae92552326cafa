"""The sharded step's table-kernel share worked by hand, and its
silence where there is nothing to read."""

import run as harness

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run(chips, ops):
    return {"trace": {"devices": chips, "ops": ops}, "peak": PEAK, "trace_steps": 2, "chips": chips,
            "width": 11, "shape": lambda: {"distinct_slots": 12.0, "occurrences": 24.0}}


def test_share_by_hand_on_four_chips():
    # four chips, 2 traced steps; on every chip the forward gather, the row
    # sums and the transpose each ran 1 ms a step under the one name. A
    # chip's share is 3 distinct slots and 6 occurrences of width 11: the
    # gather needs (3 + 6) * 44 + 24 = 420 B, its transpose the same again.
    dev = [[f"gather.{k}[pallas]", (3 * s + j) * 2e6, 1e6] for s in range(2) for j, k in enumerate((10, 11, 12))]
    mod = harness.load_metric("shard_gather_roofline")
    got = mod.read(_run(4, [dev + [["copy.1", 20e6, 7.0]]] * 4))
    assert abs(got - 100.0 * (840 / 819e9) / 3e-3) < 1e-12
    assert mod.gather_and_transpose_needs(3, 6, 11) == {"bytes": 840, "flops": 66}


def test_nothing_to_read_on_one_chip_or_without_a_trace():
    mod = harness.load_metric("shard_gather_roofline")
    one = [[["gather.5[pallas]", 0, 1e6]]]
    assert mod.read(_run(1, one)) is None
    assert mod.read({**_run(4, one * 4), "trace": None}) is None
    assert mod.read(_run(4, [[["fusion", 0, 1e6]]] * 4)) is None
