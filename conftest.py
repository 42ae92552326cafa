"""Root conftest: force an 8-virtual-device CPU platform BEFORE any test
touches jax.

This is the framework's "fake cluster" (SURVEY.md §4): the analog of the
reference's single-machine multi-process emulation (`scripts/local.sh`)
is a single-process 8-device CPU mesh. The chip itself is exercised by
chip_smoke.py, outside pytest; tests/test_tpu_compile.py compiles the
kernels for a described chip without running them.
"""

import os

# belt: env for subprocesses spawned by tests
os.environ["JAX_PLATFORMS"] = os.environ.get("XFLOW_TEST_PLATFORM", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# suspenders: an ambient site config can override the JAX_PLATFORMS
# variable, so pin the jax config directly too
import jax

jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
jax.config.update("jax_num_cpu_devices", 8)

import pytest


@pytest.fixture(autouse=True)
def _quarantine_counter_starts_at_zero():
    """`data.quarantined_rows` lives in the process-wide registry and
    every checkpoint's data_state records its run total: a test that
    quarantined rows would otherwise show up in the data_state of
    whichever test the worker runs next (tests/test_elastic.py and
    tests/test_topology.py compare that dict whole)."""
    from xflow_tpu.telemetry import default_registry

    default_registry().discard("data.quarantined_rows")
