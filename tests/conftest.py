"""Test configuration: force an 8-device virtual CPU mesh.

The reference has no way to test multi-node paths without a cluster
(SURVEY.md §4); here every collective/sharding test runs the *real* SPMD
program on 8 virtual CPU devices.

Env vars must be set before the first `import jax` anywhere, which pytest
guarantees by importing conftest first.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the ambient env may point at a TPU
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


@pytest.fixture
def lock_witness():
    """Arm the runtime lock-order witness (distributed_llama_tpu/lockcheck)
    for one test: locks CONSTRUCTED inside the test get witness wrappers
    checking the pyproject [tool.dllama.analysis.locks] hierarchy, and the
    violation ledger is clean on entry and restored on exit. Chaos tests
    opt in with this fixture (or export DLT_LOCK_CHECK=1, as CI does)."""
    from distributed_llama_tpu import lockcheck

    lockcheck.configure(mode="raise")
    lockcheck.reset()
    try:
        yield lockcheck
    finally:
        lockcheck.configure()
        lockcheck.reset()
