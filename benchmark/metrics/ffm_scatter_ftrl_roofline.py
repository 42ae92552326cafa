"""The fused scatter+FTRL kernel's share of its roofline on field-aware
FM's 157-float rows: its needed bytes (one gradient row read an
occurrence; w, n, z read and written a distinct slot, at the
configuration's row width) over the HBM peak, against its device time a
step in the trace."""

META = {"layer": "kernels", "unit": "%", "source": "device_trace", "better": "higher"}
KERNEL = r"^scatter_optimizer[.\d]*\[pallas\]$"


def read(run: dict):
    from lib import counts

    return counts.kernel_roofline_pct(run, KERNEL, counts.scatter_ftrl_needs)
