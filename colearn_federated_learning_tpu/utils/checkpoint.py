"""Checkpoint / resume (SURVEY.md §2 C15, §5) on orbax.

Persisted state: ``{params, server_opt_state, round, rng_key}`` where
``server_opt_state`` is the ``{"round": int32, "opt": <optax state>}``
wrapper (aggregation.py); SCAFFOLD runs additionally persist
``c_global`` (params-shaped f32 tree) and ``c_clients`` (``[N, ...]``
stacked f32 tree of every client's control variate). The cohort sampler
is stateless (pure function of seed+round), so resume at round r
replays the exact schedule — determinism test §4.5 covers this across a
save/restore boundary.

Importing this module does not import orbax: the first
:class:`CheckpointStore` built does (12.9 s on the chip's host, PERF.md
PR 34), so a process that never checkpoints never loads it.
``export_params`` / ``load_params`` are flax msgpack and need none of it.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import numpy as np


class CheckpointStore:
    def __init__(self, directory: str):
        self.directory = os.path.abspath(os.path.expanduser(directory))
        os.makedirs(self.directory, exist_ok=True)
        # here and not at module level (module docstring)
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self._mngr = ocp.CheckpointManager(self.directory)

    def save(self, step: int, state: Dict[str, Any], force: bool = False,
             block: bool = False):
        """Persist ``state`` at ``step``.

        ASYNC by default (SURVEY.md §5: "async checkpointing so the round
        loop never blocks"): orbax's blocking portion only snapshots
        device arrays to host, then the serialize+write runs on a
        background thread while the round loop keeps dispatching. Host
        numpy leaves (scaffold's c_clients, fedbuff's queue arrays) are
        mutated in place between rounds, so they are copied here to keep
        the in-flight snapshot consistent. ``block=True`` restores the
        synchronous behavior for final/retry-critical saves."""
        # rng keys aren't directly serializable; store raw key data
        state = dict(state)
        if "rng_key" in state:
            state["rng_key"] = np.asarray(jax.random.key_data(state["rng_key"]))
        if not block:
            state = jax.tree.map(
                lambda a: np.array(a, copy=True)
                if isinstance(a, np.ndarray) else a,
                state,
            )
        self._mngr.save(step, args=self._ocp.args.StandardSave(state), force=force)
        if block:
            self._mngr.wait_until_finished()

    def wait(self):
        """Join any in-flight async save."""
        self._mngr.wait_until_finished()

    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    def steps(self):
        """All persisted steps, ascending — `colearn replay` picks the
        nearest one at or before its target window's start."""
        return sorted(int(s) for s in self._mngr.all_steps())

    def restore(self, step: Optional[int] = None, template: Optional[Dict[str, Any]] = None):
        # an in-flight async save must land before it can be restored
        self._mngr.wait_until_finished()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        if template is not None:
            template = dict(template)
            if "rng_key" in template:
                template["rng_key"] = np.asarray(
                    jax.random.key_data(template["rng_key"])
                )
            restored = self._mngr.restore(
                step, args=self._ocp.args.StandardRestore(template))
        else:
            restored = self._mngr.restore(step)
        restored = dict(restored)
        if "rng_key" in restored:
            restored["rng_key"] = jax.random.wrap_key_data(
                np.asarray(restored["rng_key"]).astype(np.uint32)
            )
        return restored, step

    def close(self):
        # joins in-flight async saves before releasing the manager
        self._mngr.wait_until_finished()
        self._mngr.close()


def export_params(params, path: str) -> str:
    """Serialize a params pytree to a single self-contained flax
    msgpack file — the deployment artifact (the torch-world equivalent
    of exporting a ``state_dict``): no orbax directory structure, no
    optimizer/round state, loadable anywhere flax is installed via
    :func:`load_params` (or ``flax.serialization.msgpack_restore``).
    """
    from flax import serialization

    path = os.path.abspath(os.path.expanduser(path))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "wb") as f:
        f.write(serialization.to_bytes(jax.device_get(params)))
    return path


def load_params(path: str, template=None):
    """Load an :func:`export_params` artifact. With ``template`` the
    result keeps the template's exact pytree/dtype structure; without
    it, the raw msgpack dict-of-arrays is returned."""
    from flax import serialization

    with open(os.path.expanduser(path), "rb") as f:
        data = f.read()
    if template is not None:
        return serialization.from_bytes(template, data)
    return serialization.msgpack_restore(data)
