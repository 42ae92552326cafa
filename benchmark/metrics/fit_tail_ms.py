"""What ending a `fit()` costs after its last step is done (the guard's
and the loss's reads, the last record, the occupancy sweep, the final
record, the closes): median over the window's passes of the next
`fit()`'s `boundary.fit_tail_ms`."""

import statistics

META = {"layer": "fit loop", "unit": "ms", "source": "program_span", "better": "lower"}


def read(run: dict):
    tails = [r["boundary"]["fit_tail_ms"] for r in run["records"]
             if "fit_tail_ms" in r.get("boundary", {})]
    return statistics.median(tails) if tails else None
