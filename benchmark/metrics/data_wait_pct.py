"""Share of the window's step time the fit loop spent waiting for the
next batch: the step records' `data_wait_ms` over their step intervals."""

META = {"layer": "input pipeline", "unit": "%", "source": "program_span", "better": "lower"}


def read(run: dict):
    recs = run["records"]
    total = sum(r["step_time_p50_ms"] for r in recs)
    if not recs or total <= 0:
        return None
    return 100.0 * sum(r["data_wait_ms"] for r in recs) / total
