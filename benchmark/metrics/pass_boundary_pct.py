"""Share of the wall the window's step records cover that the program
spends between passes: per `fit()`, the end after its last step is done
(`fit_tail_ms`) and the start up to its first step dispatched
(`fit_open_ms`, `first_batch_ms`, `first_dispatch_ms`), from the first
step record's `boundary`. The caller's time between two `fit()` calls
(`between_fits_ms`: the harness starts its profiler there) is in
neither sum."""

META = {"layer": "fit loop", "unit": "%", "source": "program_span", "better": "lower"}

OUTSIDE = ("fit_tail_ms", "fit_open_ms")  # outside every step interval
INSIDE = ("first_batch_ms", "first_dispatch_ms")  # inside the first step's


def read(run: dict):
    recs = run["records"]
    marks = [r["boundary"] for r in recs if "boundary" in r]
    if not marks:
        return None
    outside = sum(b.get(k, 0.0) for b in marks for k in OUTSIDE)
    inside = sum(b.get(k, 0.0) for b in marks for k in INSIDE)
    wall = sum(r["step_time_p50_ms"] for r in recs) + outside
    return 100.0 * (outside + inside) / wall if wall > 0 else None
