"""Per-round communication + device-memory counters.

The byte accounting is an **analytic wire model**, not a measurement:
the simulator never serializes client messages, so the honest number is
the bytes the configured protocol WOULD move — a pure function of the
config, the model size, and the round's realized participation. That
purity is what makes the sharded and sequential engines agree bit-for-
bit on the counters (pinned by ``tests/test_obs.py``), and what lets
``summarize`` report a run's total traffic without replaying it.

Model, per participating client:

- uplink raw: one params-sized delta at the server param dtype.
- uplink wire: ``secure_aggregation`` ships dense int32 (4 B/coord —
  masking IS the wire format); ``topk`` ships k (value, index) pairs at
  8 B each; ``qsgd`` ships ~(1 sign + ⌈log2 levels⌉) bits/coord (the
  per-tensor norm scalars are noise at model scale and ignored);
  otherwise the raw delta.
- downlink raw: one params-sized broadcast per client that STARTED the
  round (dropouts downloaded before failing; stragglers too).
- downlink wire: ``downlink_compression='qsgd'`` quantizes the
  broadcast the same way; otherwise raw.

Gossip has no server: per mixing sweep each client exchanges its
boundary replica rows with two ring neighbours (or everything under
``full``), so the modeled traffic is symmetric — reported as equal
upload/download halves of the sweep volume.

The round records carry :func:`round_host_input_bytes` and
:func:`round_shape_stats`'s ``padded_step_fraction`` gauge;
:func:`block_step_counts` says how many of those padded steps the
megabatch block trainer does not execute.
"""

from __future__ import annotations

import math
from typing import Dict


def _qsgd_bits(levels: int) -> int:
    # sign bit + level index; levels=1 degenerates to sign-only
    return 1 + max(1, math.ceil(math.log2(max(levels, 2))))


def round_comm_bytes(server, n_participants: int, n_downloads: int,
                     n_coords: int, param_bytes: int) -> Dict[str, int]:
    """Wire/raw upload+download bytes for one centralized round.

    ``server`` is a :class:`~colearn_federated_learning_tpu.config.
    ServerConfig`; ``n_participants`` is the number of clients whose
    update actually aggregates (dropouts excluded), ``n_downloads`` the
    number that received the broadcast (the real — non-pad — cohort).
    """
    if server.secure_aggregation:
        up_wire = n_coords * 4  # dense int32 masked fixed-point
    elif server.compression == "topk":
        k = max(1, int(round(server.compression_topk_ratio * n_coords)))
        up_wire = k * 8  # 4 B value + 4 B index per kept coordinate
    elif server.compression == "qsgd":
        up_wire = math.ceil(
            n_coords * _qsgd_bits(server.compression_qsgd_levels) / 8
        )
    else:
        up_wire = param_bytes
    if server.downlink_compression == "qsgd":
        down_wire = math.ceil(
            n_coords * _qsgd_bits(server.downlink_qsgd_levels) / 8
        )
    else:
        down_wire = param_bytes
    return {
        "upload_bytes": int(n_participants) * up_wire,
        "upload_bytes_raw": int(n_participants) * param_bytes,
        "download_bytes": int(n_downloads) * down_wire,
        "download_bytes_raw": int(n_downloads) * param_bytes,
    }


def gossip_round_bytes(num_clients: int, mixing_steps: int, topology: str,
                       param_bytes: int) -> Dict[str, int]:
    """Symmetric neighbour-exchange traffic for one gossip round: under
    ``ring`` each client sends its replica to 2 neighbours per sweep;
    under ``full`` every sweep is an all-to-all average (modeled as one
    replica broadcast per client per sweep — the allreduce-equivalent
    volume, not N² point-to-point)."""
    fan_out = 2 if topology == "ring" else 1
    vol = int(num_clients) * fan_out * int(mixing_steps) * param_bytes
    return {
        "upload_bytes": vol,
        "upload_bytes_raw": vol,
        "download_bytes": vol,
        "download_bytes_raw": vol,
    }


def round_host_input_bytes(k: int, steps: int, batch: int,
                           on_device_mask: bool) -> int:
    """Analytic host→device wire bytes for one round's index inputs:
    the ``[K, steps, batch]`` int32 gather indices, the validity-mask
    input — the full ``[K, steps, batch]`` float32 slab on the legacy
    path, the ``[K, 2]`` int32 spec when the engine rebuilds the mask
    on device — and the ``[K]`` float32 FedAvg weights. Same
    pure-function honesty contract as :func:`round_comm_bytes`: this is
    what the configured input format WOULD move, so removing the mask
    slab shows up as exactly its byte count."""
    idx_b = int(k) * int(steps) * int(batch) * 4
    mask_b = int(k) * 2 * 4 if on_device_mask else idx_b
    return idx_b + mask_b + int(k) * 4


def round_shape_stats(spec, steps: int, batch: int,
                      local_epochs: int) -> Dict[str, float]:
    """Padded-step / wasted-FLOP gauges for one round's ``[K, 2]`` mask
    spec on a ``steps × batch`` grid.

    - ``padded_step_fraction``: fraction of the cohort's scan steps
      that are complete no-ops (no real example) — each costs a full
      training step of device FLOPs on the padded grid.
    - ``padded_example_fraction``: fraction of grid POSITIONS that are
      padding (counts partially-filled tail batches too — the
      mask-weighted FLOP waste, the complement of effective MFU).
    """
    import numpy as np

    spec = np.asarray(spec)
    k = len(spec)
    if k == 0 or steps == 0:
        return {"padded_step_fraction": 0.0, "padded_example_fraction": 0.0}
    spe = max(1, steps // max(1, local_epochs))
    n = spec[:, 0].astype(np.int64)
    vsteps = spec[:, 1].astype(np.int64)
    real_steps = np.zeros(k, np.int64)
    real_examples = np.zeros(k, np.int64)
    for e in range(local_epochs):
        avail = np.clip(vsteps - e * spe, 0, spe)
        real_steps += np.minimum(-(-n // batch), avail)
        real_examples += np.minimum(n, avail * batch)
    total_steps = k * steps
    total_examples = total_steps * batch
    return {
        "padded_step_fraction": round(
            1.0 - float(real_steps.sum()) / total_steps, 4
        ),
        "padded_example_fraction": round(
            1.0 - float(real_examples.sum()) / total_examples, 4
        ),
    }


def block_step_counts(mask, steps: int, batch: int, local_epochs: int,
                      width: int, group: int,
                      shared_first: bool) -> Dict[str, int]:
    """What the megabatch block trainer does with one round's grid, by
    its own grouping rule (``client/trainer.py``: ``_block_steps``,
    ``block_group``), from the ``[K, 2]`` mask spec (or the full ``[K,
    steps, batch]`` mask): consecutive blocks of ``width`` clients, a
    block's consecutive ``group`` clients a conditional.

    - ``client_steps``: the grid, ``K x steps``.
    - ``dead_steps``: client-steps without a real row (what
      :func:`round_shape_stats` calls ``padded_step_fraction``).
    - ``skipped_steps``: client-steps the program does not execute:
      those of a group whose every client is dead at that step. With
      ``shared_first`` the first step runs for the whole block whatever
      its masks (the shared-weight phase).

    ``tests/test_trainer.py`` lays it beside the predicates the
    program's conditional evaluates."""
    import numpy as np

    mask = np.asarray(mask)
    if mask.ndim == 2:  # the spec: data/loader.expand_mask_spec's rule
        s = np.arange(steps)
        spe = max(1, steps // max(1, local_epochs))
        live = (((s % spe) * batch)[None] < mask[:, :1]) & (s[None] < mask[:, 1:])
    else:
        live = mask.sum(-1) > 0
    ran = live.reshape(-1, width // group, group, steps).any(2)
    if shared_first:
        ran[:, :, 0] = True
    return {
        "client_steps": int(live.size),
        "dead_steps": int(live.size - live.sum()),
        "skipped_steps": int((ran.size - ran.sum()) * group),
    }


def device_memory_stats() -> Dict[str, int]:
    """Current device-memory gauges from ``jax`` memory stats, or ``{}``
    when the backend reports none (CPU, older runtimes)."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        return {}
    out = {}
    for src, dst in (
        ("bytes_in_use", "hbm_in_use_bytes"),
        ("peak_bytes_in_use", "hbm_peak_bytes"),
        ("bytes_limit", "hbm_limit_bytes"),
    ):
        if src in stats:
            out[dst] = int(stats[src])
    return out
