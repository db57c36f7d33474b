"""Test configuration: virtual 8-device CPU mesh.

Multi-chip behavior (sharding, collectives, pipeline) is validated on a
virtual CPU mesh (XLA host devices); the same code paths run unmodified on
a real TPU slice. The suite is a CPU suite wherever it runs: the
platform is forced here through jax.config (which wins over the
environment), so a machine that holds a chip does not hand it to the
tests. The chip run is chip_smoke.py."""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


# The suite compiles thousands of XLA executables in ONE process; past
# ~250 tests the accumulated jit cache segfaults jaxlib's CPU compiler
# (r05: three suite runs died at three different late-suite points, all
# inside backend_compile, after the serving tests pushed the count up).
# Dropping the caches at module boundaries bounds the accumulation; the
# next module recompiles what it needs.
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` wall-clock budget; "
        "still run by the packaged make targets (e.g. paged-check), which "
        "invoke their test files unfiltered.")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    yield
    jax.clear_caches()
