"""Share of the traced window in which a collective runs on a device
and no other operation does (all_to_all, psum and reduce-scatter of the
fully sharded engine); nothing to read on one chip."""

META = {"layer": "collectives", "unit": "%", "source": "device_trace", "better": "lower"}
COLLECTIVE = r"all-to-all|all-reduce|reduce-scatter|all-gather|collective-permute"


def read(run: dict):
    from lib import trace

    tr = run.get("trace")
    if not tr or tr.get("devices", 0) < 2:
        return None
    return 100.0 * trace.exposed_seconds(tr["ops"], COLLECTIVE) / tr["window_s"]
