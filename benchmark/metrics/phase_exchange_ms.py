"""Device time a step and chip of the step's `exchange` phase: what a chip
does to receive its work: every collective operation, the occurrences'
all_to_all, the merge's sort, the row aggregates' return. Which
operation is whose is said by the program's compile records, joined with
the trace by module (`lib/phases.py`)."""

META = {"layer": "collectives", "unit": "ms", "source": "device_trace", "better": "lower"}


def read(run: dict):
    from lib import phases

    return phases.phase_ms(run, "exchange")
