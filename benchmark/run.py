"""One cell of the benchmark, one process, one final JSON line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its per-layer metrics are data files
found by name (``harness/catalog.py``; ``benchmark/README.md`` says how
to add one). Without a TPU, or with fewer chips than the cell asks for,
the program exits non-zero before compiling and prints no result.
``--dry`` is the rehearsal off the chip: it runs a cell (meant for the
tiny ``dry_*`` presets) on whatever backend is there and prints counts
only, never a metric of the benchmark.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import catalog, window  # noqa: E402

SCOPES = ("round_local_train", "round_aggregate", "round_server_apply",
          "round_fused_reduce_apply", "round_control_plane",
          "round_attack_transform", "round_client_ledger")
ROUND_PROGRAM = "jit_round_fn"


def say(tag: str, payload) -> None:
    """An earlier line: for the reader of a log, never for the driver."""
    print(f"[bench] {tag}: {json.dumps(payload, sort_keys=True)}", flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window "
                         "(default: BENCHMARK.json run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry", action="store_true",
                    help="rehearsal: any backend, counts only")
    ap.add_argument("--keep-trace", default="",
                    help="for tests/benchmark/fixtures/make_fixture.py: copy "
                         "the traced run's .xplane.pb and the round program's "
                         "op names here (a path inside the checkout)")
    return ap.parse_args(argv)


def require_chip(cell, peaks, dry: bool):
    """This device's row of the peaks table; exits the process without a
    result where the contract says so."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if dry:
        if len(devices) < cell["chips"]:
            sys.exit(f"--dry: {len(devices)} devices, cell wants "
                     f"{cell['chips']}")
        return None
    if first.platform != "tpu":
        sys.exit(f"no accelerator: jax reports platform {first.platform!r}; "
                 f"the benchmark measures on a TPU only")
    if first.device_kind not in peaks:
        sys.exit(f"device_kind {first.device_kind!r} is not in "
                 f"harness/peaks.json; add its published peaks first")
    if len(devices) < cell["chips"]:
        sys.exit(f"cell {cell['name']!r} needs {cell['chips']} chips, "
                 f"jax sees {len(devices)}")
    return peaks[first.device_kind]


def check_loss(cell, run) -> dict:
    """The train loss at the cell's fixed check round against its band."""
    spec = cell["loss_check"]
    got = run.rounds.get(int(spec["round"]))
    out = {"round": spec["round"], "band": spec["band"],
           "train_loss": None if got is None else got["train_loss"],
           # what a new cell's band is set from (benchmark/README.md)
           "first_rounds": {r: v["train_loss"]
                            for r, v in sorted(run.rounds.items())[:12]}}
    out["ok"] = bool(
        got is not None and spec["band"] is not None
        and spec["band"][0] <= got["train_loss"] <= spec["band"][1]
    )
    return out


def check_reference(cell, config, run, bench_dir=BENCH_DIR) -> dict:
    """The system's first dispatch against the plain reference round(s)
    of the cell's ``reference.impl`` (``references/<impl>.py``), both
    from the seeded initial state."""
    from harness import reference

    t0 = time.perf_counter()
    n_rounds = int(cell["reference"]["rounds"])
    if n_rounds != run.fuse:
        raise catalog.CatalogError(
            f"cell {cell['name']!r}: reference.rounds={n_rounds} but one "
            f"dispatch runs {run.fuse} rounds"
        )
    impl = catalog.load_reference(cell["reference"]["impl"], bench_dir)
    initial, ref_params, ref_losses = impl.run_rounds(
        run.exp, config, run.seed, n_rounds
    )
    out = reference.compare(
        run.first_dispatch_params,
        [run.rounds[r + 1]["train_loss"] for r in range(n_rounds)],
        initial, ref_params, ref_losses, cell["reference"],
    )
    out["seconds"] = time.perf_counter() - t0
    return out


def compared(compiles_in_window, result, loss, ref, cell) -> dict:
    """Every number that decides ``correct``, beside its limit:
    ``[number, upper limit]``, or ``[number, lower, upper]`` for a band.
    A number that is missing or not finite, or a limit that is missing,
    is ``null`` (the line stays JSON) and fails."""
    def number(v):
        return v if v is not None and math.isfinite(v) else None

    tols = cell["reference"]
    loss_tols = tols.get("loss_rel_tols") or []
    out = {
        "compiles_in_window": [compiles_in_window, 0],
        "rounds_failed": [result["failed"], 0],
        "rounds_completed": [result["completed"], 1, result["attempted"]],
        f"loss_round_{loss['round']}": [number(loss["train_loss"]),
                                        *(loss["band"] or [None, None])],
    }
    for k, err in enumerate(ref["loss_rel_errs"]):
        out[f"ref_loss_rel_err_round_{k + 1}"] = [
            number(err), loss_tols[k] if k < len(loss_tols) else None]
    out["ref_delta_rel_l2_err"] = [number(ref["delta_rel_l2_err"]),
                                   tols.get("state_rel_l2_tol")]
    return out


def inside(check) -> bool:
    """One entry of ``compared``: nothing of it is ``null`` and the
    number is inside its limit(s)."""
    if any(v is None for v in check):
        return False
    if len(check) == 3:
        return check[1] <= check[0] <= check[2]
    return check[0] <= check[1]


def round_programs(exp) -> list:
    """The compiled round program(s) this run executed, from the
    program's executable registry."""
    if exp._exec_reg is None:
        return []
    return [entry["compiled"] for entry in exp._exec_reg._cache.values()
            if entry["name"].startswith("round.")]


def round_program_op_names(exp) -> dict:
    """{HLO instruction: op_name} of the compiled round program(s), from
    the program's executable registry: the trace's events carry no
    named-scope path themselves (harness/trace_reduce.py)."""
    from harness import trace_reduce

    names = {}
    for compiled in round_programs(exp):
        names.update(trace_reduce.scopes_from_hlo(compiled.as_text()))
    return names


def layer_metrics(entries, ctx, bench_dir) -> dict:
    """Each per-layer metric through its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for entry in entries:
        spec = catalog.load_layer_metric(entry["name"], bench_dir)
        read = catalog.load_reader(spec["reader"], bench_dir)
        value = read(ctx, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    marks = window.Marks(_T0, window.process_age_s() or 0.0)
    benchmark = catalog.load_benchmark()
    cell = catalog.load_workload(args.workload)
    config = catalog.load_config(cell["config"])
    listed = catalog.benchmark_cell(benchmark, args.workload)
    if listed is None and not args.dry:
        sys.exit(f"{args.workload!r} is not a cell of BENCHMARK.json "
                 f"(rehearse an unlisted preset with --dry)")
    seconds = float(args.seconds if args.seconds is not None
                    else benchmark["run_seconds"])
    with open(os.path.join(BENCH_DIR, "harness", "peaks.json")) as f:
        peaks_table = json.load(f)

    import jax

    from colearn_federated_learning_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    clock = window.CompileClock().install()
    peaks = require_chip(cell, peaks_table, args.dry)
    marks.mark("runtime_up")

    from colearn_federated_learning_tpu.config import resolve_config
    from colearn_federated_learning_tpu.obs import executables as exec_mod
    from colearn_federated_learning_tpu.server.round_driver import Experiment

    cfg = resolve_config(
        cell["named_config"],
        catalog.experiment_overrides(cell, config, args.seed),
    )
    exp = Experiment(cfg, echo=False)
    used = list(exp.mesh.devices.flat)
    if len(used) != cell["chips"]:
        sys.exit(f"the experiment's mesh holds {len(used)} devices, the "
                 f"cell asks for {cell['chips']}")
    marks.mark("experiment_built")
    run = window.Run(exp, args.seed)
    if exp._exec_reg is not None:
        exec_mod.install(exp._exec_reg)
    trace_dir = ""
    try:
        run.start()
        marks.mark("state_placed")
        helpers_before_s = clock.seconds
        run.first_dispatch()
        marks.mark("first_dispatch_done")
        helpers_skip_s = clock.seconds - helpers_before_s
        run.warm_up(int(cell["warmup_dispatches"]) - 1)
        exp.tracer.drain()  # the window's spans start here
        compiles_at_start = clock.count
        helpers_s = clock.seconds - helpers_skip_s
        setup_s = marks.mark("window_start")
        if args.trace:
            trace_dir = os.path.join(BENCH_DIR, "out", "trace",
                                     f"{args.workload}.{args.seed}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir)
        result = run.measure(
            seconds, trace_dir or None,
            trace_dispatches=int(cell.get("trace_dispatches", 3)),
            trace_max_s=float(cell.get("trace_max_s", 5.0)),
            trace_after_round=int(cell["loss_check"]["round"]),
        )
        marks.mark("window_end")
        compiles_in_window = clock.count - compiles_at_start
        spans = exp.tracer.drain()
        memory = (window.peak_memory_bytes(used) if not args.dry
                  else {"memory_peak_bytes": 0})
        memory_peak = memory["memory_peak_bytes"]
    finally:
        exp._stop_prefetch()
        if exp._exec_reg is not None:
            exec_mod.uninstall()

    # the round program: the registry's wall time for trace + lower +
    # compile-or-load; every other program: its backend_compile event.
    # Events inside the first dispatch are the round program's own (and
    # a handful of helpers'), already inside the registry's wall time.
    round_program_s = (exp._exec_reg.total_compile_ms / 1e3
                       if exp._exec_reg is not None else helpers_skip_s)
    compile_s = round_program_s + helpers_s
    warmup_exec_s = max(0.0, marks.at["window_start"] - marks.at["state_placed"]
                        - round_program_s - (helpers_s - helpers_before_s))
    setup = {
        "setup_s": setup_s,
        "import_and_runtime_s": marks.at["runtime_up"],
        "data_partition_engine_s": (marks.at["experiment_built"]
                                    - marks.at["runtime_up"]),
        "init_and_placement_s": (marks.at["state_placed"]
                                 - marks.at["experiment_built"]),
        "first_dispatch_s": (marks.at["first_dispatch_done"]
                             - marks.at["state_placed"]),
        "further_warmup_s": (marks.at["window_start"]
                             - marks.at["first_dispatch_done"]),
        "compile_s": compile_s,
        "round_program_compile_s": round_program_s,
        "helper_programs_compile_s": helpers_s,
        "programs_compiled_or_loaded": compiles_at_start,
        "warmup_exec_s": warmup_exec_s,
        "host_prep_s": max(0.0, setup_s - compile_s - warmup_exec_s),
        "compile_cache_dir": cache_dir,
        "host_pipeline": "native" if exp._native else "numpy",
    }
    say("setup", setup)
    say("window", {**result, "seconds_asked": seconds,
                   "client_updates_per_s_per_chip": (
                       result["rounds_per_s"] * cfg.server.cohort_size
                       / cell["chips"]),
                   "compiles_in_window": compiles_in_window,
                   "memory": memory,
                   "spans_ms": {k: v["total_ms"] for k, v in spans.items()}})

    loss = check_loss(cell, run)
    say("loss_check", loss)
    ref = check_reference(cell, config, run)
    say("reference", ref)
    checks = compared(compiles_in_window, result, loss, ref, cell)
    correct = bool(result["error"] is None
                   and all(inside(c) for c in checks.values()))

    first = used[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": cell["chips"], "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": {}, "device": device}
    xplane, op_names = "", {}
    if trace_dir:
        from harness import trace_reduce

        xplane = trace_reduce.find_xplane(trace_dir)
        op_names = round_program_op_names(exp)
        if args.keep_trace:
            os.makedirs(os.path.dirname(os.path.abspath(args.keep_trace)),
                        exist_ok=True)
            shutil.copy(xplane, args.keep_trace)
            with open(args.keep_trace + ".op_names.json", "w") as f:
                json.dump(op_names, f)
    if args.dry:
        # counts only: nothing here carries the name of a metric
        out["metrics"] = {
            "rounds_completed": {"value": result["completed"],
                                 "unit": "rounds"},
            "compiles_in_window": {"value": compiles_in_window,
                                   "unit": "compiles"},
        }
    elif not args.trace:
        values = {"rounds_per_s": result["rounds_per_s"],
                  "peak_hbm_gb": memory_peak / 1e9, "setup_s": setup_s}
        for entry in catalog.cell_metrics(benchmark, args.workload,
                                          "end_to_end"):
            out["metrics"][entry["name"]] = {
                "value": values[entry["name"]], "unit": entry["unit"]}
    else:
        from harness import flops

        trace = trace_reduce.load(xplane, op_names)
        windows = trace_reduce.steady_windows(trace, ROUND_PROGRAM)
        if not windows:
            sys.exit("the trace holds fewer than two executions of the "
                     "round program on some device: no steady window")
        ctx = {
            "cell": cell, "config": config, "bench_dir": BENCH_DIR,
            "peaks": peaks, "setup": setup,
            "spans": spans, "window": result, "trace": trace,
            "windows": windows,
            "fuse": run.fuse, "scopes": SCOPES,
            "round_program": ROUND_PROGRAM, "flops": flops,
            "reduce": trace_reduce,
            "counters": {"examples_per_round": result["examples_per_round"],
                         "cohort_size": cfg.server.cohort_size,
                         "server_momentum": cfg.server.optimizer == "fedavgm"},
        }
        out["metrics"] = layer_metrics(
            catalog.cell_metrics(benchmark, args.workload, "per_layer"),
            ctx, BENCH_DIR,
        )
        busy = [trace_reduce.measure(trace_reduce.busy_intervals(d, lo, hi))
                for d, lo, hi, _ in windows]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (sum(hi - lo for _, lo, hi, _ in windows)
                              / len(windows) / 1e9)
        # the busiest chip's view: the ops that took most time and the
        # longest gaps, by what the host was doing
        dev, lo, hi, _ = windows[max(range(len(busy)), key=busy.__getitem__)]
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(dev, lo, hi, SCOPES, 10),
            "idle_gaps": trace_reduce.idle_gaps(dev, trace.host, lo, hi, 5),
        }
    if trace_dir:  # a check's tree stays small enough to copy
        shutil.rmtree(trace_dir, ignore_errors=True)
    # what decided `correct`, each number beside its limit: last on the
    # error stream, and last in the result's line
    out["checks"] = checks
    for name, check in checks.items():
        print(f"[check] {name}: {json.dumps(check)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
