"""On-TPU kernel parity gate (VERDICT r2 item 6).

Skips off-TPU: the pytest conftest pins an 8-device CPU platform, so in
CI this file is a no-op; on a TPU host run

    XFLOW_TEST_PLATFORM=tpu python -m pytest tests/test_kernel_parity_tpu.py

`chip_smoke.py` runs the same check on the chip in every smoke run, and
`bench.py` on every benchmark invocation — the silent-MXU-rounding class
of bug (docs/CHANGES_R2.md "Precision integrity") cannot regress unseen.
"""

import pytest


def test_kernel_parity_on_device():
    # asked here, not at import: every xdist worker imports this file, and
    # a module must not touch a backend to decide which tests it has
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("requires a real TPU chip")
    from xflow_tpu.tools.kernel_parity import check_kernel_parity

    res = check_kernel_parity()
    assert res["ok"], res
    # the fullshard step's merged stream is checked beside the multi case
    assert {"gather_merged_exact", "scatter_merged_exact"} <= set(res["checks"])
