"""Share of the traced part of a serve window in which no operation ran
on the device."""

META = {"layer": "device", "unit": "%", "source": "device_trace", "better": "lower"}


def read(run: dict):
    tr = run.get("trace")
    if not tr or not tr.get("devices") or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
