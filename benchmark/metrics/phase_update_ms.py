"""Device time a step and chip of the step's `update` phase: the gradient
-> the new state: the guard's read and select, the FTRL sweep or the
fused scatter+FTRL kernel (`scatter_optimizer` counts as it), the
relayout on the update program's side of the cut. Which operation is
whose is said by the program's compile records, joined with the trace by
module (`lib/phases.py`)."""

META = {"layer": "step program", "unit": "ms", "source": "device_trace", "better": "lower"}


def read(run: dict):
    from lib import phases

    return phases.phase_ms(run, "update")
