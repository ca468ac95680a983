"""How a cell's ``traffic_seed`` was chosen (PR 43): not for speed.

    python3 tests/benchmark/traffic_seed_candidates.py r18_c16_k8 9 48 43001 43002 ...

For each candidate: build the cell's ``Experiment`` with ``run.seed`` =
the candidate (no device program is traced or compiled:
``Experiment._host_inputs(round)`` is pure in seed and round), and for
every round of the window (1-based ``first``..``last``: the warm-up's
rounds come before) count with ``obs/counters.block_step_counts`` the
client-steps the block trainer executes in each lane; a round takes as
long as its slowest lane. Prints one JSON line a candidate and the
candidate whose mean is the median of them all (the upper of the two
middle ones), which is the one the cell's file takes.

A four-chip cell needs four devices: off the chip run it with
``XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import json
import statistics
import sys

import bench_paths  # noqa: F401  (puts the harness on sys.path)
from harness import catalog

sys.path.insert(0, bench_paths.ROOT)


def window_steps(cell_name: str, traffic_seed: int, first: int, last: int):
    """Per round of ``first..last`` (1-based): the executed client-steps
    of the slowest lane, and over the window the dead and skipped shares."""
    from colearn_federated_learning_tpu.config import resolve_config
    from colearn_federated_learning_tpu.obs import block_step_counts
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cell = dict(catalog.load_workload(cell_name), traffic_seed=traffic_seed)
    config = catalog.load_config(cell["config"])
    cfg = resolve_config(cell["named_config"],
                         catalog.experiment_overrides(cell, config, 0))
    exp = Experiment(cfg, echo=False)
    try:
        width, group, shared_first = exp._block
        slowest, totals = [], {"client_steps": 0, "dead_steps": 0,
                               "skipped_steps": 0}
        for r in range(first - 1, last):
            # the [K, 2] spec or the whole mask: the counter reads either
            _, _, mask, _, _ = exp._host_inputs(r)
            shape = exp._round_shape(r)
            args = (shape.steps, shape.batch_size, shape.local_epochs,
                    width, group, shared_first)
            for key, n in block_step_counts(mask, *args).items():
                totals[key] += n
            lanes = [block_step_counts(mask[lo:lo + width], *args)
                     for lo in range(0, len(mask), width)]
            slowest.append(max(c["client_steps"] - c["skipped_steps"]
                               for c in lanes))
    finally:
        exp._stop_prefetch()
    return {
        "traffic_seed": traffic_seed, "rounds": [first, last],
        "slowest_lane_steps_mean": statistics.fmean(slowest),
        "slowest_lane_steps_min_max": [min(slowest), max(slowest)],
        "dead_steps_pct": 100 * totals["dead_steps"] / totals["client_steps"],
        "skipped_steps_pct": (100 * totals["skipped_steps"]
                              / totals["client_steps"]),
    }


def main(argv) -> int:
    cell, first, last = argv[0], int(argv[1]), int(argv[2])
    rows = [window_steps(cell, int(c), first, last) for c in argv[3:]]
    for row in rows:
        print(json.dumps(row), flush=True)
    ranked = sorted(rows, key=lambda r: r["slowest_lane_steps_mean"])
    print(json.dumps({
        "cell": cell,
        "ranked": [r["traffic_seed"] for r in ranked],
        "median_candidate": ranked[len(ranked) // 2]["traffic_seed"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
