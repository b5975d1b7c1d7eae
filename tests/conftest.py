"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective paths are
validated on a virtual 8-device CPU platform (the reference's analog is
MiniCluster: multi-node semantics in one process, ``MiniCluster.java``).
Must run before jax initializes its backends, hence top of conftest.
"""

import os

# Force, don't setdefault: a machine with a chip pre-sets JAX_PLATFORMS to the
# TPU, a chip belongs to one process at a time, and unit tests (and the
# worker processes they spawn, which inherit this environment) must never
# take it.  JAX reads the environment itself, when it is first imported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# Every operator instance re-traces its jitted steps (they are static on
# ``self``), so one run of the suite compiles the same HLO hundreds of times.
# The persistent compile cache turns the repeats into loads — at the fixed
# in-checkout directory the entry points use, so the workers that tests
# spawn through ``python -m flink_tpu`` share it.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
