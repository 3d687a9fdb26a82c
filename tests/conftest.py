"""Test config: force an 8-device virtual CPU mesh before JAX imports.

Tests validate multi-chip sharding logic without TPU hardware (the driver
separately dry-runs the multichip path via __graft_entry__.dryrun_multichip).
"""

import os
import sys

# The suite validates consensus + sharding logic on an 8-device virtual
# CPU mesh, never on real hardware: pinned here, before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from bitcoinconsensus_tpu.utils import compile_cache  # noqa: E402

# Persistent compile cache, placed by the same rule as the backend
# (utils/compile_cache.py). NOTE on a hard-won stability story: jaxlib
# intermittently SEGFAULTS on its LARGEST compiles late in a long-lived
# pytest process — observed inside backend_compile_and_load AND in the
# persistent-cache read/write paths, with this cache on and off, with the
# native core on and off; the identical compiles in a clean process always
# pass. The suite therefore runs its two big-compile families
# (interpret-mode pallas equality, the 8-device shard_map mesh programs)
# in fresh subprocesses (tests/pallas_equality_check.py,
# tests/mesh_checks.py); the compiles that remain in-process are small.
# Set BITCOINCONSENSUS_TPU_TEST_CACHE=0 to disable the cache when
# debugging a suspected cache-layer crash.
if os.environ.get("BITCOINCONSENSUS_TPU_TEST_CACHE", "") in ("0", "off"):
    jax.config.update("jax_enable_compilation_cache", False)
else:
    compile_cache.configure()

# Suite budget split: `-m consensus` runs the host-side consensus core in
# ~3 minutes; everything else (`-m kernel`) is the device-kernel families
# whose compiles dominate suite wall time.
_KERNEL_MODULES = {
    "test_ops_limbs",
    "test_ops_curve",
    "test_ops_sha256",
    "test_pallas_kernel",
    "test_parallel",
    "test_exhaustive_group",
}


def pytest_collection_modifyitems(config, items):
    import pytest

    for item in items:
        name = item.module.__name__ if item.module else ""
        if name in _KERNEL_MODULES:
            item.add_marker(pytest.mark.kernel)
        else:
            item.add_marker(pytest.mark.consensus)

import pytest  # noqa: E402

REFERENCE_ROOT = os.environ.get("BITCOIN_REFERENCE_ROOT", "/root/reference")
TEST_DATA_DIR = os.path.join(REFERENCE_ROOT, "depend", "bitcoin", "src", "test", "data")


def require_test_data():
    if not os.path.isdir(TEST_DATA_DIR):
        pytest.skip(f"consensus test vectors not found at {TEST_DATA_DIR}")
    return TEST_DATA_DIR
