"""Persistent XLA compilation cache placement (process start-up).

One rule, applied by every entry point that compiles (``cli.main``,
``benchmark/run.py``, ``chip_smoke.py``) before its first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads the directory
  from the environment — the program sets no directory in code, so
  whoever launched the process decides where the cache lives.
- unset: ``<checkout>/.jax_cache``, derived from this package's
  location. The path is stable across processes and runs on purpose:
  the cache only hits when a later process looks in the same place, so
  it is never a temp dir and never keyed on a pid or the time.
"""

from __future__ import annotations

import os

import jax

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Apply the rule above and return the directory in effect."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # cache every program, not just the slow compiles: a warm headline
    # start still ran ~140 sub-second compiles (init, placement, eval
    # helpers) that together outweighed the cached round program
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
