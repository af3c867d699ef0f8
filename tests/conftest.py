"""Test environment: force an 8-device virtual CPU mesh before JAX import.

The idiomatic JAX answer to "test distributed without a cluster"
(SURVEY.md §4): XLA's host platform is told to expose 8 devices, and every
sharding test runs over a real Mesh on them. JAX_PLATFORMS=cpu is what
makes the kernels run under the Pallas interpreter
(kme_tpu/_jaxsetup.py); the chip path is exercised by chip_smoke.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture(scope="session", autouse=True)
def _lockcheck_gate():
    """When tier-1 runs under KME_LOCKCHECK=1 (kme_tpu/__init__ patched
    the lock factories), fail the session if any lock-order inversion
    was observed across the whole run."""
    yield
    from kme_tpu.analysis import lockcheck

    if lockcheck.enabled():
        lockcheck.assert_clean()
