"""The whole step's share of the chip's peak: the least time the cell's
chips could take for one step's needed work (lib/counts.py: the larger
of operations over peak FLOP/s and bytes over peak bytes/s; bytes hold
here) over the measured time a step, window seconds over window steps."""

META = {"layer": "step program", "unit": "%", "source": "host_clock", "better": "higher"}


def read(run: dict):
    from lib import counts

    win = run["window"]
    if not run.get("peak") or not win["steps"]:
        return None
    shape = run["shape"]()
    needs = counts.step_needs(shape["distinct_slots"], shape["occurrences"], run["width"])
    least, _ = counts.least_seconds(needs, run["peak"], run["chips"])
    return 100.0 * least / (win["window_s"] / win["steps"])
