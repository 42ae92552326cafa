"""90th percentile of the completion-to-completion step intervals over
all steps of the window; needs a hundred steps, so ten lie beyond it."""

import statistics

META = {"layer": "step program", "unit": "ms", "source": "program_span", "better": "lower"}


def read(run: dict):
    steps = [r["step_time_p50_ms"] for r in run["records"]]
    if len(steps) < 100:
        return None
    return statistics.quantiles(steps, n=10)[8]
