"""Shared test configuration.

Tests run on the CPU backend (``JAX_PLATFORMS=cpu``).  The persistent
XLA compile cache is JAX's own: exporting ``JAX_COMPILATION_CACHE_DIR``
(as CI does, restored across runs by actions/cache) serves every engine
compile in the suite from disk; nothing here sets another directory.

Multi-device tests: the ``@pytest.mark.multidevice`` tier (the lane-
sharding golden suite) needs more than one JAX device.  CPU-only hosts
get them by *forcing* host devices BEFORE jax initializes::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 pytest tests/test_lane_sharding.py

(the forced-multi-device CI job does exactly this).  When only one
device is visible and forcing is off, marked tests auto-skip; the
``n_devices`` fixture reports the session's device count either way.
"""
import pytest


def _device_count() -> int:
    import jax
    return len(jax.devices())


def pytest_collection_modifyitems(config, items):
    if not any("multidevice" in item.keywords for item in items):
        return  # don't initialize jax for suites that never need it
    if _device_count() > 1:
        return
    skip = pytest.mark.skip(
        reason="needs >1 JAX device — run under "
               "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    for item in items:
        if "multidevice" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def n_devices() -> int:
    """Number of JAX devices this session can shard lanes over
    (includes forced host devices)."""
    return _device_count()
