"""Multi-host distributed bring-up (SURVEY.md §3.5, §5 "distributed
communication backend").

The reference's NCCL process group becomes JAX's multi-controller
runtime: every host runs the same program, ``jax.distributed.initialize``
wires them into one cluster over gRPC, and after that ``jax.devices()``
is the *global* device list — the client mesh (parallel/mesh.py) spans
hosts transparently and the round engine's psums ride ICI within a slice
and DCN across slices. There is no server/rank asymmetry to port: the
"server" is the replicated psum result on every host.

Bring-up paths:

- **TPU pods**: ``jax.distributed.initialize()`` with no arguments —
  coordinator/process count/ids come from the TPU runtime metadata.
- **Explicit / loopback** (CI, CPU clusters): set
  ``COLEARN_COORDINATOR=host:port``, ``COLEARN_NUM_PROCESSES``,
  ``COLEARN_PROCESS_ID`` (or call :func:`initialize` yourself). The
  ``multihost``-marked loopback test drives a real 2-process × 4-device
  cluster this way on one machine.

Host-local input rule: the driver feeds per-round index tensors via
:func:`host_local_array` so each process materializes only its
addressable shards; replicated arrays (params, dataset bytes) use plain
``device_put`` which every process executes identically.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join (or form) the multi-controller cluster.

    No-args on a TPU pod; explicit coordinator/count/id elsewhere.
    Idempotent: repeated calls after a successful bring-up are no-ops.
    """
    # Must not touch the backend (jax.process_count() would initialize
    # it); ask the distributed client state directly.
    if jax.distributed.is_initialized():
        return
    kwargs = {}
    if coordinator is not None:
        kwargs = dict(
            coordinator_address=coordinator,
            num_processes=int(num_processes),
            process_id=int(process_id),
        )
    jax.distributed.initialize(**kwargs)


def maybe_initialize_from_env(env=None) -> bool:
    """CLI hook: bring up the cluster when COLEARN_COORDINATOR is set.

    Returns True iff distributed initialization ran. Must be called
    before anything touches the JAX backend.
    """
    env = os.environ if env is None else env
    coordinator = env.get("COLEARN_COORDINATOR")
    if not coordinator:
        return False
    missing = [k for k in ("COLEARN_NUM_PROCESSES", "COLEARN_PROCESS_ID")
               if k not in env]
    if missing:
        raise RuntimeError(
            f"COLEARN_COORDINATOR is set but {', '.join(missing)} "
            f"is missing; explicit bring-up needs all three variables"
        )
    initialize(
        coordinator,
        env["COLEARN_NUM_PROCESSES"],
        env["COLEARN_PROCESS_ID"],
    )
    return True


def host_local_array(tree, sharding):
    """Assemble global jax.Arrays from host-replicated NumPy data
    (works on a single array or a whole pytree).

    Every process holds the same data (index tensors are deterministic
    functions of (seed, round), so all hosts compute identical copies)
    and uploads exactly its addressable shards — no cross-host data
    movement.
    """

    def one(a):
        a = np.asarray(a)
        return jax.make_array_from_callback(a.shape, sharding, lambda idx: a[idx])

    return jax.tree.map(one, tree)
