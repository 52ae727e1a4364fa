"""TPU-vs-CPU consistency tier (reference pattern:
tests/python/gpu/test_operator_gpu.py running check_consistency across
[cpu, gpu] ctx lists, test_utils.py:1203).

This suite needs BOTH backends in one process, so it lives outside
tests/ (whose conftest forces JAX_PLATFORMS=cpu).  Run it on the chip:

    python -m pytest tests_tpu/ -q -rA

The device is checked in this process (a child that grabbed the chip to
ask would take it from us): the tier skips only where the environment
says there is no chip on purpose (JAX_PLATFORMS=cpu), and otherwise
fails unless device 0 is a TPU.  MXT_CONSISTENCY_SELFTEST=1 validates
the harness cpu-vs-cpu (tests/test_consistency_harness.py).
"""
import os

import pytest


def pytest_collection_modifyitems(config, items):
    if os.environ.get("MXT_CONSISTENCY_SELFTEST"):
        return
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        skip = pytest.mark.skip(reason="JAX_PLATFORMS=cpu: this tier "
                                       "compares the TPU against the CPU")
        for item in items:
            item.add_marker(skip)
        return
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise pytest.UsageError(
            f"tests_tpu needs a TPU as device 0, found {dev.platform!r} "
            f"({dev.device_kind!r}); it does not fall back to the CPU")


@pytest.fixture(autouse=True)
def _seed():
    import numpy as np
    np.random.seed(0)
    yield
