"""Test harness: N virtual devices on one host as the default distributed mode.

Mirrors the reference's test strategy of running distributed code paths on
local[*] with one partition per "node" (reference:
core/test/base/TestBase.scala:74-160, SparkSessionFactory.scala:36-53):
here every test sees an 8-device CPU mesh via
``xla_force_host_platform_device_count``, so shard_map/psum paths are exercised
without TPU hardware. Must run before anything imports jax.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# persistent compile cache, placed from outside like any deployment would
# (utils/compile_cache sets no directory when this is set): XLA compiles
# dominate test wall-time, so cache them across runs — and keep tier-1 from
# filling <checkout>/.jax_cache, which the chip tool copies with the tree.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_subprocess_env():
    """Environment for subprocess tests (RSS measurement, multi-process):
    CPU jax on the 8-device virtual mesh. One definition — the subprocess
    env must not diverge across test files."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    return env


@pytest.fixture(scope="session")
def mesh8():
    from mmlspark_tpu.parallel.mesh import make_mesh

    return make_mesh()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)

