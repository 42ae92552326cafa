"""Device time a step and chip of the step's `scatter` phase: the gather's
transpose where it is an operation of its own: XLA's scatter-add into a
zeroed gradient (row-major), the two-pass scatter kernel and the
gradient's relayout on its side of the cut (fullshard). Which operation
is whose is said by the program's compile records, joined with the trace
by module (`lib/phases.py`)."""

META = {"layer": "kernels", "unit": "ms", "source": "device_trace", "better": "lower"}


def read(run: dict):
    from lib import phases

    return phases.phase_ms(run, "scatter")
