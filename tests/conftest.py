"""Test configuration: force an 8-virtual-device CPU platform.

Multi-chip sharding is validated on a virtual CPU mesh (the TPU test double),
so every sharding/collective path compiles and runs in CI without TPU
hardware.  Must run before the first ``import jax`` anywhere in the test
process.
"""

import os
import sys

# The package is imported from the source tree (not installed); make the
# suite cwd-independent.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Tests run on the CPU backend whatever the environment says (the driver
# also sets JAX_PLATFORMS=cpu).  CFK_TPU_TESTS=1 leaves the platform alone so
# the on-chip kernel tests can reach a TPU:
#   CFK_TPU_TESTS=1 python -m pytest tests/test_pallas_tpu.py -q
import jax

if os.environ.get("CFK_TPU_TESTS") != "1":
    jax.config.update("jax_platforms", "cpu")
# Trainers and engines turn the persistent compile cache on at entry
# (config.enable_compile_cache).  A test process has nothing to reuse, would
# write thousands of tiny CPU programs into the checkout, and — in
# tests/test_chip_compile.py — must not read back entries written for a
# described device.  So the cache stays off here; the tests of the cache
# itself switch it on around themselves.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


TINY = "/root/reference/data/data_sample_tiny.txt"
SMALL = "/root/reference/data/data_sample_small.txt"
MEDIUM = "/root/reference/data/data_sample_medium.txt"

# The reference repo's sample data is an OPTIONAL fixture set: present
# where /root/reference is mounted, absent in bare containers.  Tests that
# need it skip cleanly (ISSUE 8 satellite: the tier-1 failure set must be
# EMPTY without it, not "identical to seed") — via the session fixtures
# below, or via @pytest.mark.reference_data for tests that reach the
# files through the CLI/examples rather than a fixture.
HAS_REFERENCE_DATA = os.path.exists(TINY)
_REFERENCE_SKIP_REASON = (
    "/root/reference sample data not present in this container"
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "reference_data: needs the /root/reference sample data files",
    )


def pytest_collection_modifyitems(config, items):
    if HAS_REFERENCE_DATA:
        return
    skip = pytest.mark.skip(reason=_REFERENCE_SKIP_REASON)
    for item in items:
        if item.get_closest_marker("reference_data"):
            item.add_marker(skip)


@pytest.fixture(scope="session")
def tiny_coo():
    if not HAS_REFERENCE_DATA:
        pytest.skip(_REFERENCE_SKIP_REASON)
    from cfk_tpu.data.netflix import parse_netflix_python

    return parse_netflix_python(TINY)


@pytest.fixture(scope="session")
def tiny_dataset(tiny_coo):
    from cfk_tpu.data.blocks import Dataset

    return Dataset.from_coo(tiny_coo)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
