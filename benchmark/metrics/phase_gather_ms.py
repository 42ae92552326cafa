"""Device time a step and chip of the step's `gather` phase: table rows ->
occurrence space, forward only: the windowed Pallas gather of the sorted
engines, XLA's gather on the row-major step. Which operation is whose is
said by the program's compile records, joined with the trace by module
(`lib/phases.py`)."""

META = {"layer": "kernels", "unit": "ms", "source": "device_trace", "better": "lower"}


def read(run: dict):
    from lib import phases

    return phases.phase_ms(run, "gather")
