"""Test fixture: 8 fake CPU devices (SURVEY.md §4.3).

The distributed-without-a-cluster pattern: XLA's host platform is forced
to expose 8 devices so the *real* shard_map/psum round engine runs over
a clients=8 mesh with no TPU pod. The suite is CPU-only by contract:
the platform is pinned through jax.config before any backend
initialization, so it holds even where JAX_PLATFORMS is not exported.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")
# in-process `cli.main` calls place the persistent compilation cache
# (utils/compile_cache.py); the suite must neither write into the
# checkout's .jax_cache nor have a warm cache from an earlier run decide
# what a test compiles
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_devices():
    assert len(jax.devices()) == 8, "conftest failed to get 8 fake CPU devices"
    yield
