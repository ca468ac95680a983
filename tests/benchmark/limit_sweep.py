"""The readings a cell's limits are set from, many seeds in one process.

    python3 tests/benchmark/limit_sweep.py --workload vit_silo_plain \
        --seeds 2045012331,2147484001,1,2,... [--rounds 12] [--dry]

Only for a cell whose file fixes its ``traffic_seed``: there a seed draws
the weights and the run's key alone, so the ``Experiment`` (data,
partition, engine, compiled round program) is built once and every seed
is ``init_state(seed)`` -> the rounds up to the cell's loss-check round
-> the plain reference's rounds, through ``benchmark/run.py``'s own
``check_loss`` / ``check_reference`` / ``compared``. One JSON line a
seed (``[bench] sweep: {...}``): the first rounds' losses, the check
round's loss, the reference's errors, each beside its limit as a run
would print it, and ``correct`` as a run would decide it from them. A
last line (``[bench] sweep_summary``) gives, per compared number, the
extreme readings, their mean and sample standard deviation. No window is
measured: nothing here is a metric.
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time

import bench_paths  # noqa: F401  (puts the harness on sys.path)
from startup_probe import load_run


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; the failures on record first")
    ap.add_argument("--rounds", type=int, default=0,
                    help="run at least this many rounds a seed "
                         "(default: the cell's loss-check round)")
    ap.add_argument("--dry", action="store_true",
                    help="any backend (the dry_* presets, on the CPU)")
    return ap.parse_args(argv)


def sweep(args):
    bench = load_run()  # puts the checkout's root on sys.path
    from harness import catalog, window

    cell = catalog.load_workload(args.workload)
    if "traffic_seed" not in cell:
        sys.exit(f"{args.workload!r} fixes no traffic_seed: a seed there "
                 f"builds another Experiment; run it a process a seed")
    config = catalog.load_config(cell["config"])
    with open(f"{bench.BENCH_DIR}/harness/peaks.json") as f:
        peaks = json.load(f)

    from colearn_federated_learning_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    bench.require_chip(cell, peaks, args.dry)

    from colearn_federated_learning_tpu.config import resolve_config
    from colearn_federated_learning_tpu.obs import executables as exec_mod
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    seeds = [int(s) for s in args.seeds.split(",") if s]
    exp = Experiment(resolve_config(
        cell["named_config"],
        catalog.experiment_overrides(cell, config, seeds[0]),
    ), echo=False)
    if exp._exec_reg is not None:
        exec_mod.install(exp._exec_reg)
    rows = []
    try:
        for seed in seeds:
            t0 = time.perf_counter()
            run = window.Run(exp, seed)
            run.start()
            run.first_dispatch()
            last = max(int(cell["loss_check"]["round"]), args.rounds)
            run.warm_up(math.ceil(last / run.fuse) - 1)
            run.state = None  # the reference runs with the state freed
            loss = bench.check_loss(cell, run)
            ref = bench.check_reference(cell, config, run)
            # no window here: its three counts are as a clean run's
            checks = bench.compared(
                0, {"failed": 0, "completed": len(run.rounds),
                    "attempted": len(run.rounds)}, loss, ref, cell)
            row = {
                "seed": seed, "run_seed": exp.cfg.run.seed,
                "correct": all(bench.inside(c) for c in checks.values()),
                "checks": checks, "first_rounds": loss["first_rounds"],
                "losses_reference": ref["losses_reference"],
                "reference_s": ref["seconds"],
                "seconds": time.perf_counter() - t0,
            }
            rows.append(row)
            bench.say("sweep", row)
    finally:
        exp._stop_prefetch()
        if exp._exec_reg is not None:
            exec_mod.uninstall()
    summary = {}
    for name in rows[0]["checks"]:
        values = [r["checks"][name][0] for r in rows]
        if any(v is None for v in values):
            continue
        summary[name] = {
            "min": min(values), "max": max(values),
            "mean": statistics.fmean(values),
            "sd": statistics.stdev(values) if len(values) > 1 else 0.0,
            "limit": rows[0]["checks"][name][1:],
        }
    bench.say("sweep_summary", {
        "workload": args.workload, "seeds": len(rows),
        "not_correct": [r["seed"] for r in rows if not r["correct"]],
        "numbers": summary})


if __name__ == "__main__":
    sweep(parse_args())
