"""The predict program's share of its roofline: the least time the chip
could take for the bytes a served batch needs (lib/counts.py
predict_needs: distinct rows read once, one row and 12 B an occurrence,
at the mean rows a batch of the window) over the program's device time
an execution in the trace."""

import re

META = {"layer": "predict program", "unit": "%", "source": "device_trace", "better": "higher"}
MODULE = r"^jit_step"  # the predict program's name on the trace's line of whole executions


def read(run: dict):
    from lib import counts

    tr = run.get("trace")
    if not tr or not tr.get("devices") or not run.get("peak"):
        return None
    runs = [v for k, v in tr.get("module_runs", {}).items() if re.search(MODULE, k)]
    n, seconds = sum(v[0] for v in runs), sum(v[1] for v in runs)
    shape = run["shape"]()
    if not n or seconds <= 0 or not shape:
        return None
    needs = counts.predict_needs(shape["distinct_slots"], shape["occurrences"], run["width"])
    return 100.0 * counts.least_seconds(needs, run["peak"])[0] / (seconds / n)
