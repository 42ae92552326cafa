"""Median host time a batch the prefetch thread spends building field-
aware FM's placement permutation (the program's `xflow:ffm_place` span,
inside `xflow:plan`): the step records' `host.ffm_place_ms`, one batch a
record in a traced run. A part of `plan_ms`, so `producer_busy_pct`
holds it already. Nothing to read where the program has no such span."""

import statistics

META = {"layer": "input pipeline", "unit": "ms", "source": "program_span", "better": "lower"}


def read(run: dict):
    hosts = [r["host"] for r in run["records"] if "ffm_place_ms" in r.get("host", {})]
    if not hosts:
        return None
    return statistics.median(h["ffm_place_ms"] / max(h.get("batches", 1), 1) for h in hosts)
