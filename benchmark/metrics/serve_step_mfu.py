"""The whole serving path's share of the chip's peak: the least time the
chip could take for what all the window's device batches need
(lib/counts.py predict_needs a batch, times the batches the serve stream
counted) over the seconds from the window's start to its last answer."""

META = {"layer": "predict program", "unit": "%", "source": "host_clock", "better": "higher"}


def read(run: dict):
    from lib import counts

    shape = run["shape"]()
    if not run.get("peak") or not shape or run["window"]["closed_s"] <= 0:
        return None
    needs = counts.predict_needs(shape["distinct_slots"], shape["occurrences"], run["width"])
    least = counts.least_seconds(needs, run["peak"])[0] * shape["batches"]
    return 100.0 * least / run["window"]["closed_s"]
