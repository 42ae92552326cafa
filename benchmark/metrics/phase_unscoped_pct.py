"""Share of the device's busy time that the program gives no phase:
operations its compile records map to "", operations of a module with no
record (the harness's own small programs between passes), operations no
record holds, and the `health` phase (`lib/phases.py`). What is not here
is in one of the `phase_*_ms`: they and this remainder sum to the
trace's busy time."""

META = {"layer": "device", "unit": "%", "source": "device_trace", "better": "lower"}


def read(run: dict):
    from lib import phases

    return phases.unscoped_pct(run)
