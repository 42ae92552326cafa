"""The pipeline's cold start: from `_fit` entered to the first step
dispatched (`fit_open_ms` + `first_batch_ms` + `first_dispatch_ms` of
the first step record's `boundary`), median over the window's passes."""

import statistics

META = {"layer": "fit loop", "unit": "ms", "source": "program_span", "better": "lower"}

PARTS = ("fit_open_ms", "first_batch_ms", "first_dispatch_ms")


def read(run: dict):
    opens = [sum(r["boundary"][k] for k in PARTS) for r in run["records"]
             if all(k in r.get("boundary", {}) for k in PARTS)]
    return statistics.median(opens) if opens else None
