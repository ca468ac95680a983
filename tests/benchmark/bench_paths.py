"""Where the benchmark lives, for the tests beside this file."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")

if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)


def run_benchmark(args, tmp_path, cwd=ROOT, script=None, devices=0):
    """``benchmark/run.py`` as a process on the CPU, with a compile cache
    of its own; ``devices`` > 1 gives it that many virtual CPU devices."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # conftest's 8 fake devices are not ours
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    script = script or os.path.join(BENCH_DIR, "run.py")
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def bench_line(stdout, tag):
    """The payload of the ``[bench] <tag>: {...}`` line of a run's output."""
    import json

    head = f"[bench] {tag}: "
    return json.loads(next(l for l in stdout.splitlines()
                           if l.startswith(head))[len(head):])
