"""Device time a step and chip of the step's `rows` phase: everything
between the gathered occurrences and their cotangent: row sums, row
math, the loss, their backward (field-aware FM's `ffm_place` and
`ffm_pair` count as it). Which operation is whose is said by the
program's compile records, joined with the trace by module
(`lib/phases.py`)."""

META = {"layer": "step program", "unit": "ms", "source": "device_trace", "better": "lower"}


def read(run: dict):
    from lib import phases

    return phases.phase_ms(run, "rows")
