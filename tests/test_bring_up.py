"""Bring-up contracts (ISSUE 21): where the compile cache lives, that
importing an entry point never takes the chip, that ``chip_smoke.py``
refuses to run off-chip, and that the executable registry fails loudly.

Tier-1 is wall-clock limited and this file runs early, so nothing here
compiles: the three child interpreters start together in one fixture
and only import/inspect.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from colearn_federated_learning_tpu.obs.executables import ExecutableRegistry
from colearn_federated_learning_tpu.utils import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# import every entry point, report what jax holds afterwards, then ask
# the helper where the cache is
_IMPORT_PROBE = """
import json, sys
sys.path.insert(0, {root!r})
import chip_smoke, __graft_entry__
import colearn_federated_learning_tpu.cli
import colearn_federated_learning_tpu.server.round_driver
from jax._src import xla_bridge
backends = sorted(xla_bridge._backends)
from colearn_federated_learning_tpu.utils.compile_cache import (
    configure_compile_cache,
)
first = configure_compile_cache()
print(json.dumps({{"backends": backends, "dirs": [first,
                                                  configure_compile_cache()]}}))
"""


def _child_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


@pytest.fixture(scope="module")
def children(tmp_path_factory):
    placed = str(tmp_path_factory.mktemp("placed_cache"))
    probe = [sys.executable, "-c", _IMPORT_PROBE.format(root=_ROOT)]
    procs = {
        "unset": subprocess.Popen(
            probe, env=_child_env(), cwd=placed,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True),
        "set": subprocess.Popen(
            probe, env=_child_env(JAX_COMPILATION_CACHE_DIR=placed),
            cwd=placed,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True),
        "smoke": subprocess.Popen(
            [sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
            env=_child_env(JAX_LOG_COMPILES="1"), cwd=placed,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True),
    }
    out = {"placed": placed}
    try:
        for name, p in procs.items():
            stdout, stderr = p.communicate(timeout=120)
            out[name] = (p.returncode, stdout, stderr)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
    return out


def test_cache_dir_defaults_to_checkout_and_is_stable(children):
    want = os.path.join(_ROOT, ".jax_cache")
    assert compile_cache.DEFAULT_CACHE_DIR == want
    rc, stdout, stderr = children["unset"]
    assert rc == 0, stderr[-2000:]
    # two calls in the child (whose cwd is elsewhere) agree with this
    # process: nothing in the path depends on pid, time or cwd
    assert json.loads(stdout.splitlines()[-1])["dirs"] == [want, want]


def test_cache_dir_from_environment_is_left_alone(children, monkeypatch):
    rc, stdout, stderr = children["set"]
    assert rc == 0, stderr[-2000:]
    placed = children["placed"]
    assert json.loads(stdout.splitlines()[-1])["dirs"] == [placed, placed]
    # and in-process: with the variable set the helper sets no directory
    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    try:
        compile_cache.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)


def test_importing_entry_points_initialises_no_backend(children):
    """__graft_entry__'s dry-run parent spawns a child that needs the
    device: the import alone must not take it."""
    rc, stdout, stderr = children["unset"]
    assert rc == 0, stderr[-2000:]
    assert json.loads(stdout.splitlines()[-1])["backends"] == []


def test_chip_smoke_refuses_cpu_before_compiling(children):
    rc, stdout, stderr = children["smoke"]
    assert rc != 0
    assert "not 'tpu'" in stderr
    assert '"ok"' not in stdout  # no result line
    assert "Compiling" not in stderr  # JAX_LOG_COMPILES=1 saw none


@pytest.mark.parametrize("kind,known", [
    ("TPU v5 lite", True), ("TPU v5 lite (described)", False),
], ids=["known", "unknown"])
def test_chip_smoke_takes_device_kinds_from_the_benchmarks_peaks(kind, known):
    """One table of peaks in the repo: the benchmark's, which refuses a
    device it has no row for; the smoke refuses the same devices."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    peaks_file = os.path.join(_ROOT, "benchmark", "harness", "peaks.json")
    assert chip_smoke.PEAKS_FILE == peaks_file
    with open(peaks_file) as f:
        assert (kind in json.load(f)) == known
    if known:
        chip_smoke.require_known_device_kind(kind)
    else:
        with pytest.raises(RuntimeError, match="benchmark/harness/peaks.json"):
            chip_smoke.require_known_device_kind(kind)
        with pytest.raises(RuntimeError, match="peaks.json"):
            chip_smoke.require_known_device_kind("_source")  # a note, no row


class _Lowered:
    def __init__(self, call_error=None):
        self._call_error = call_error

    def compile(self):
        def compiled(*args, **kwargs):
            if self._call_error is not None:
                raise self._call_error
            return "aot result"
        return compiled


class _FakeJit:
    """Quacks like a jitted function; ``lower`` fails on demand."""

    def __init__(self, lower_error=None, call_error=None):
        self._lower_error = lower_error
        self._call_error = call_error

    def lower(self, *args, **kwargs):
        if self._lower_error is not None:
            raise self._lower_error
        return _Lowered(self._call_error)

    def __call__(self, *args, **kwargs):
        return "jit result"


def test_registry_failures_raise_and_keep_the_record():
    """A program that cannot be lowered, or an AOT executable that
    rejects its call, used to be re-dispatched through plain jit and
    the fit finished as if nothing had happened."""
    # one shape per program: the registry's cache is keyed on the avals
    a, b, c = (np.ones((n,), np.float32) for n in (1, 2, 3))
    reg = ExecutableRegistry(backend="cpu")
    assert reg.call("round.ok", _FakeJit(), (a,), {}) == "aot result"
    with pytest.raises(RuntimeError, match="mosaic says no"):
        reg.call("round.lower", _FakeJit(
            lower_error=RuntimeError("mosaic says no")), (b,), {})
    with pytest.raises(ValueError, match="sharding mismatch"):
        reg.call("round.call", _FakeJit(
            call_error=ValueError("sharding mismatch")), (c,), {})
    records = reg.drain_records()
    warnings = {r["warning"]: r for r in records
                if r["event"] == "warning"}
    assert set(warnings) == {"executable_lower_failed",
                             "executable_call_failed"}
    assert "mosaic says no" in warnings["executable_lower_failed"]["detail"]
    failed = [r for r in records if r["event"] == "executable_compiled"
              and r["name"] == "round.lower"]
    assert failed and failed[0]["flops"] is None
