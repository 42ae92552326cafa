"""Median host time a step, from the batch in hand to the step program
dispatched (plan resolve, host-to-device transfer, async dispatch)."""

import statistics

META = {"layer": "input pipeline", "unit": "ms", "source": "program_span", "better": "lower"}


def read(run: dict):
    recs = run["records"]
    return statistics.median(r["dispatch_ms"] for r in recs) if recs else None
