"""The sorted gather kernel's share of its roofline: its needed bytes
(distinct rows read, one row written an occurrence) over the HBM peak,
against its device time a step in the trace."""

META = {"layer": "kernels", "unit": "%", "source": "device_trace", "better": "higher"}
KERNEL = r"^gather[.\d]*\[pallas\]$"


def read(run: dict):
    from lib import counts

    return counts.kernel_roofline_pct(run, KERNEL, counts.gather_needs)
