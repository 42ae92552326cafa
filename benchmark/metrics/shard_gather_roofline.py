"""The sharded step's table kernels against their roofline (the
fullshard engine: every chip gathers, from its own shard of the table,
the rows that all the chips' batch shards ask of it, and scatters their
gradients back into a shard-sized buffer).

What the trace calls `gather[pallas]` there is two kernels, not one: a
transpose keeps its scope's name, so the windowed gather and the
gather's transpose (the two-pass scatter) both carry it; the row sums,
which carried it too until PR 38, are `rows[pallas]` now (PERF.md
section 5 has each one's time). The time is theirs together, a step and
chip; the needed bytes are a chip's share of the gather's (distinct rows
read, one row written an occurrence) and of the scatter's (one row read
an occurrence, distinct rows written): the same count in both
directions, a lower bound, so the share cannot pass 100%.
Nothing to read on one chip: `gather_roofline` reads the single-device
kernel, which has the name to itself."""

META = {"layer": "kernels", "unit": "%", "source": "device_trace", "better": "higher"}
KERNEL = r"^gather[.\d]*\[pallas\]$"


def gather_and_transpose_needs(distinct_slots: float, occurrences: float, width: int) -> dict:
    from lib import counts

    one_way = counts.gather_needs(distinct_slots, occurrences, width)
    return {"bytes": 2 * one_way["bytes"], "flops": occurrences * width}


def read(run: dict):
    from lib import counts

    if run.get("chips", 1) < 2:
        return None
    return counts.kernel_roofline_pct(run, KERNEL, gather_and_transpose_needs)
