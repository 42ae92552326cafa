"""The sorted gather kernel's share of its roofline on field-aware FM's
157-float rows: its needed bytes (distinct rows read, one row written an
occurrence, at the configuration's row width) over the HBM peak, against
its device time a step in the trace. The FFM step has one kernel of this
name: its row side is XLA's (`ffm_pair_roofline`), not a row-sum kernel."""

META = {"layer": "kernels", "unit": "%", "source": "device_trace", "better": "higher"}
KERNEL = r"^gather[.\d]*\[pallas\]$"


def read(run: dict):
    from lib import counts

    return counts.kernel_roofline_pct(run, KERNEL, counts.gather_needs)
