"""Test fixture: 8 fake CPU devices (SURVEY.md §4.3).

The distributed-without-a-cluster pattern: XLA's host platform is forced
to expose 8 devices so the *real* shard_map/psum round engine runs over
a clients=8 mesh with no TPU pod. The suite is CPU-only by contract:
the platform is pinned through jax.config before any backend
initialization, so it holds even where JAX_PLATFORMS is not exported.
"""

import contextlib
import faulthandler
import hashlib
import os
import shutil
import sys
import tempfile

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# The longest one test may run. A hang inside an XLA execution (PERF.md
# section 7: the CPU runtime waits for ever where lanes take different
# numbers of kernel calls) never returns to Python, so no exception can
# end it: past the limit the process dumps every thread's traceback and
# exits, which costs one test and one worker restart, not the run's
# whole clock. Inner limits of tests that wait on children
# (test_multihost.py) stay under it.
TEST_LIMIT_S = 300


_stderr_fd = pytest.StashKey[int]()
_died_before = pytest.StashKey[bool]()


def pytest_configure(config):
    # capture is suspended while pytest configures: this is the process's
    # own stderr, not the file a test's output is captured into, which
    # would die with the process and take the traceback with it
    config.stash[_stderr_fd] = os.dup(sys.__stderr__.fileno())
    # One directory a run, made fresh by the process that starts the run
    # (the xdist controller, configured before it spawns its workers, or
    # the one process of a plain run) and found by the workers, and by
    # every child a test starts, through the variable jax itself reads.
    # It holds the run's persistent compilation cache: a program that two
    # tests, two workers or two fresh processes build is compiled once. A
    # warm cache from an earlier run still never decides what a test
    # compiles, and nothing is written into the checkout: with the
    # variable set, utils/compile_cache.py (in-process `cli.main` calls)
    # places no directory of its own. The thresholds are zero so that the
    # hundreds of sub-second helper programs of flax's eager init and of
    # Experiment.__init__ are kept too.
    if not hasattr(config, "workerinput"):
        run_dir = tempfile.mkdtemp(prefix="colearn-tests-")
        config.add_cleanup(lambda: shutil.rmtree(run_dir, ignore_errors=True))
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(run_dir, "jax-cache")
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # jax read its environment when this file imported it, before the
    # variables were there
    jax.config.update(
        "jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def pytest_collection_modifyitems(items):
    # xdist's loadfile scheduler hands files out in collection order and
    # the run ends when the last file does: with the large files first (a
    # file's size stands for its weight, unmeasured and unmaintained) the
    # small ones fill the end. The sort is stable: a file's own order stays.
    items.sort(key=lambda item: -os.path.getsize(item.path))


@contextlib.contextmanager
def hang_limit(seconds, file=sys.__stderr__):
    faulthandler.dump_traceback_later(seconds, exit=True, file=file)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item):
    """The limit spans a test's whole protocol and not a fixture's scope:
    what a module-scoped fixture compiles is set up inside the first
    test that asks for it. The mark outlives a process that the limit
    (or a crash) ended: xdist's loadfile scheduler gives the whole file
    of a worker that died to the next one, the test that killed it
    included, until the restarts run out, so a test whose mark is still
    there is failed in pytest_runtest_setup and not run again."""
    mark = os.path.join(
        os.path.dirname(os.environ["JAX_COMPILATION_CACHE_DIR"]),
        "started-" + hashlib.sha1(item.nodeid.encode()).hexdigest())
    item.stash[_died_before] = os.path.exists(mark)
    open(mark, "w").close()
    with hang_limit(TEST_LIMIT_S, item.config.stash[_stderr_fd]):
        result = yield
    os.remove(mark)
    return result


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    if item.stash[_died_before]:
        pytest.fail(
            "the process that ran this test before did not survive it "
            f"(a hang past {TEST_LIMIT_S} s, or a crash): not run again",
            pytrace=False,
        )


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_devices():
    assert len(jax.devices()) == 8, "conftest failed to get 8 fake CPU devices"
    yield


@pytest.fixture
def shallow_zoo(monkeypatch):
    """The zoo's two deep convolutional families at two stages each, for
    a test that drives a named config through the engine and asserts
    nothing about depth: ResNet-18 keeps its stem, an identity block and
    a strided block with its projection (windowed kernels still 95 % of
    it), MobileNetV2 its unexpanded block, a strided and a residual
    inverted block and its head. Same modules, same kwargs, same names
    in the registry; what jax has to trace and compile is a quarter."""
    import jax.numpy as jnp

    from colearn_federated_learning_tpu.models import model_registry
    from colearn_federated_learning_tpu.models.mobilenet import MobileNetV2
    from colearn_federated_learning_tpu.models.resnet import ResNet18

    def resnet18(num_classes=10, small_inputs=True, width=64,
                 compute_dtype=jnp.float32, param_dtype=jnp.float32, **_):
        return ResNet18(num_classes=num_classes, stage_sizes=(1, 1),
                        small_inputs=small_inputs, width=width,
                        compute_dtype=compute_dtype, param_dtype=param_dtype)

    def mobilenetv2(num_classes=62, width_mult=1.0, small_inputs=True,
                    compute_dtype=jnp.float32, param_dtype=jnp.float32, **_):
        return MobileNetV2(num_classes=num_classes, width_mult=width_mult,
                           small_inputs=small_inputs,
                           blocks=((1, 16, 1, 1), (6, 24, 2, 2)),
                           compute_dtype=compute_dtype, param_dtype=param_dtype)

    monkeypatch.setitem(model_registry._entries, "resnet18", resnet18)
    monkeypatch.setitem(model_registry._entries, "mobilenetv2", mobilenetv2)
