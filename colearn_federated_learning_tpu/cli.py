"""`colearn` CLI (SURVEY.md §2 C1, layer L6).

Entry points with capability parity to the reference's
``colearn fit`` / ``colearn evaluate`` (BASELINE.json:5)::

    colearn fit --config cifar10_fedavg_100 --set server.num_rounds=50
    colearn evaluate --config cifar10_fedavg_100
    colearn export --config <c> --output m.msgpack  # a checkpoint's
                               # global model as one flax msgpack file
    colearn configs            # list the named BASELINE configs
    colearn store build|info   # on-disk mmap client store
                               # (data/store.py): write or describe one
    colearn summarize <run>    # per-phase timing table from a run's JSONL
    colearn watch <run>        # live tail of a run (mid-fit or done):
                               # rounds/sec, loss, health, coverage,
                               # pager hit rate, phase sparklines
    colearn population <run>   # post-hoc federation health report
                               # (population_health JSONL records)
    colearn clients <run>      # per-client forensic ledger report
                               # (anomalies + attack precision/recall)
    colearn check              # static invariant analyzer: capability
                               # matrix golden pin, seed-purity
                               # lint, JSONL schema cross-check
                               # (exit 1 naming each violation)
    colearn diff <a> <b>       # determinism bisection: align two runs'
                               # digest chains and localize the first
                               # divergent round + component
                               # (exit 1 on divergence)
    colearn replay --config <c> --round r  # re-execute one logged digest
                               # round from the nearest checkpoint and
                               # verify the recomputed digest
    colearn preflight --config <c>  # compile every round program
                               # abstractly and report predicted peak
                               # HBM against the budget

``--config`` accepts a registry name or a YAML path; ``--set a.b=v``
overrides any field. ``fit --resume`` continues from the latest
checkpoint; ``--profile N`` traces round N with jax.profiler;
``--sanitize`` enables NaN debugging + finite-params assertions.
"""

from __future__ import annotations

import argparse
import json
import sys


def _parse_overrides(pairs):
    out = {}
    for pair in pairs or []:
        if "=" not in pair:
            raise SystemExit(f"--set expects key=value, got {pair!r}")
        k, v = pair.split("=", 1)
        lowered = v.lower()
        if lowered in ("true", "false"):
            out[k] = lowered == "true"
        else:
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
    return out


def _add_common(p):
    p.add_argument("--config", required=True,
                   help="named config (see `colearn configs`) or YAML path")
    p.add_argument("--set", action="append", metavar="KEY=VALUE", dest="overrides",
                   help="dotted config override, e.g. server.num_rounds=5")
    p.add_argument("--out-dir", default=None, help="override run.out_dir")


def build_parser():
    p = argparse.ArgumentParser(prog="colearn",
                                description="TPU-native federated learning simulation")
    sub = p.add_subparsers(dest="cmd", required=True)

    fit = sub.add_parser("fit", help="run federated training")
    _add_common(fit)
    fit.add_argument("--resume", action="store_true", help="resume from latest checkpoint")
    fit.add_argument("--profile", type=int, default=None, metavar="ROUND",
                     help="jax.profiler trace of round ROUND")
    fit.add_argument("--sanitize", action="store_true",
                     help="NaN debugging + finite-params checks")
    fit.add_argument("--engine", choices=["sharded", "sequential"], default=None)
    fit.add_argument("--strict-digest", action="store_true",
                     help="abort when resume-time digest-chain "
                          "verification fails (run.obs.digest) instead "
                          "of logging a digest_resume warning")

    ev = sub.add_parser("evaluate", help="evaluate latest (or --step) checkpoint")
    _add_common(ev)
    ev.add_argument("--step", type=int, default=None, help="checkpoint round to load")
    ev.add_argument("--federated", action="store_true",
                    help="also report the per-client accuracy distribution "
                         "of the global model (fairness view: mean/median/"
                         "p10/worst across clients)")
    ev.add_argument("--federated-clients", type=int, default=64,
                    help="max clients in the federated evaluation")
    ev.add_argument("--personalize", action="store_true",
                    help="also report per-client fine-tune-then-eval accuracy")
    ev.add_argument("--personalize-epochs", type=int, default=1,
                    help="local fine-tune epochs per client")
    ev.add_argument("--personalize-clients", type=int, default=32,
                    help="max clients evaluated (sampled deterministically)")
    ev.add_argument("--holdout-frac", type=float, default=0.2,
                    help="per-client held-out fraction for the local eval")

    ex = sub.add_parser(
        "export",
        help="export a checkpoint's global model params to one flax "
             "msgpack file (the deployment artifact)",
    )
    _add_common(ex)
    ex.add_argument("--step", type=int, default=None, help="checkpoint round to load")
    ex.add_argument("--output", required=True, metavar="PATH",
                    help="output .msgpack path")

    sub.add_parser("configs", help="list named configs")

    st = sub.add_parser(
        "store",
        help="on-disk mmap client store (data/store.py): build one from "
             "a config's data (or stream a synthetic federation at any "
             "client count), or inspect an existing store",
    )
    st_sub = st.add_subparsers(dest="store_cmd", required=True)
    sb = st_sub.add_parser(
        "build",
        help="write fixed-record binary shards + per-client index; "
             "point data.store.dir at the result to run store-backed",
    )
    sb.add_argument("--out", required=True, metavar="DIR",
                    help="store directory to create")
    sb.add_argument("--config", default=None,
                    help="convert this config's data (synthetic/LEAF/"
                         "real + partition, exactly what the in-memory "
                         "run would see — store-backed runs are then "
                         "bitwise-equal to it)")
    sb.add_argument("--set", action="append", metavar="KEY=VALUE",
                    dest="overrides", help="dotted config override")
    sb.add_argument("--synthetic-clients", type=int, default=None,
                    metavar="N",
                    help="instead of --config: stream a deterministic "
                         "synthetic federation of N clients straight to "
                         "shards (never materializes the corpus — the "
                         "million-client path)")
    sb.add_argument("--leaf-femnist", default=None, metavar="DATA_DIR",
                    help="instead of --config: stream DATA_DIR/femnist "
                         "LEAF json files to shards, one writer per "
                         "client, one file resident at a time")
    sb.add_argument("--leaf", default=None, metavar="LEAF_DIR",
                    help="instead of --config: stream ANY LEAF-format "
                         "json directory (the all_data/*.json layout — "
                         "femnist, sent140, shakespeare-style flat "
                         "features) to shards; record shape inferred "
                         "from the first user")
    sb.add_argument("--cifar10", default=None, metavar="DATA_DIR",
                    help="instead of --config: convert the real CIFAR-10 "
                         "python pickles under DATA_DIR/"
                         "cifar-10-batches-py into a partitioned record "
                         "store (two-pass staging, labels-only in RAM) — "
                         "the cifar10_krum_byzantine store-backed path")
    sb.add_argument("--clients", type=int, default=100, metavar="N",
                    help="--cifar10 only: number of clients to "
                         "partition into (default 100)")
    sb.add_argument("--partition", default="dirichlet",
                    help="--cifar10 only: partition kind (dirichlet/"
                         "iid/shard, as data.partition; default "
                         "dirichlet)")
    sb.add_argument("--alpha", type=float, default=0.5,
                    help="--cifar10 only: dirichlet concentration "
                         "(default 0.5)")
    sb.add_argument("--examples-per-client", type=int, default=2)
    sb.add_argument("--shape", default="12,12,1",
                    help="synthetic example shape, comma-separated "
                         "(default 12,12,1)")
    sb.add_argument("--classes", type=int, default=10)
    sb.add_argument("--seed", type=int, default=0)
    sb.add_argument("--test-examples", type=int, default=64)
    sb.add_argument("--shard-mb", type=int, default=64,
                    help="approximate shard file size; shards only "
                         "split between clients")
    si = st_sub.add_parser(
        "info",
        help="describe an existing store: schema, size facts, and the "
             "per-shard breakdown (examples / whole clients / bytes)",
    )
    si.add_argument("dir", metavar="DIR")
    si.add_argument("--json", action="store_true",
                    help="emit the description as one JSON object "
                         "instead of the table")

    sm = sub.add_parser(
        "summarize",
        help="aggregate a run's metrics JSONL into a per-phase "
             "timing/throughput table (no backend needed)",
    )
    sm.add_argument("run", metavar="RUN",
                    help="run name (looked up under --out-dir), a run "
                         "directory, or a .metrics.jsonl path")
    sm.add_argument("--out-dir", default="runs",
                    help="where <RUN>.metrics.jsonl lives (default: runs)")
    sm.add_argument("--json", action="store_true",
                    help="emit the aggregated summary as one JSON object "
                         "instead of the table")

    cl = sub.add_parser(
        "clients",
        help="per-client forensic ledger report: top-k anomalous "
             "clients, participation histogram, and attack-detection "
             "precision/recall (requires run.obs.client_ledger; no "
             "backend needed)",
    )
    cl.add_argument("run", metavar="RUN",
                    help="run name (looked up under --out-dir), a run "
                         "directory, or a .metrics.jsonl path")
    cl.add_argument("--out-dir", default="runs",
                    help="where <RUN>.metrics.jsonl lives (default: runs)")
    cl.add_argument("--top", type=int, default=10,
                    help="how many anomalous clients to list")
    cl.add_argument("--min-flag-rate", type=float, default=0.5,
                    help="fraction of a client's participations that "
                         "must be flagged to count as detected")
    cl.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object instead of "
                         "the table")
    cl.add_argument("--threshold-sweep", action="store_true",
                    help="also print detection precision/recall at "
                         "several min-flag-rate cutoffs (requires an "
                         "attack run), so the detection threshold can "
                         "be picked without re-running")

    wa = sub.add_parser(
        "watch",
        help="live view of a run from its metrics JSONL (pure host — "
             "no backend init, works mid-fit and on completed runs): "
             "rounds/sec, loss, health/divergence state, pager hit "
             "rate, coverage %%, phase-ms sparklines, and — for "
             "fedbuff/churn runs — the async panel (arrival rate, "
             "staleness distribution + sparkline, clamp/backpressure "
             "counts) and realized churn counts; refreshes until the "
             "run completes",
    )
    wa.add_argument("run", metavar="RUN",
                    help="run name (looked up under --out-dir), a run "
                         "directory, or a .metrics.jsonl path")
    wa.add_argument("--out-dir", default="runs",
                    help="where <RUN>.metrics.jsonl lives (default: runs)")
    wa.add_argument("--interval", type=float, default=2.0,
                    help="seconds between refreshes (default: 2)")
    wa.add_argument("--json", action="store_true",
                    help="one-shot mode for scripting: emit the current "
                         "snapshot as one JSON object and exit")
    wa.add_argument("--once", action="store_true",
                    help="render one frame and exit (no follow loop)")

    po = sub.add_parser(
        "population",
        help="post-hoc federation health report from a run's "
             "population_health JSONL records (run.obs.population): "
             "coverage, draw split, staleness, ledger-pager and store "
             "I/O health, participation fairness (no backend needed)",
    )
    po.add_argument("run", metavar="RUN",
                    help="run name (looked up under --out-dir), a run "
                         "directory, or a .metrics.jsonl path")
    po.add_argument("--out-dir", default="runs",
                    help="where <RUN>.metrics.jsonl lives (default: runs)")
    po.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object instead of "
                         "the table")

    ck = sub.add_parser(
        "check",
        help="static invariant analyzer (analysis/): capability-matrix "
             "extraction from validate() against the checked-in golden, "
             "seed-purity AST lint against the checked-in allowlist, "
             "and the JSONL record-schema emit/consume cross-check — "
             "exits 1 naming each violation (pure host, no backend "
             "init)",
    )
    ck.add_argument("--root", default=None,
                    help="repo root to analyze (default: the directory "
                         "holding the installed package)")
    ck.add_argument("--update-matrix", action="store_true",
                    help="regenerate capability_matrix.json from the "
                         "code before checking (review the diff!)")
    ck.add_argument("--json", action="store_true",
                    help="emit the full report as one JSON object "
                         "instead of the table")

    df = sub.add_parser(
        "diff",
        help="determinism bisection (run.obs.digest, obs/digest.py): "
             "align two runs' round_digest chains, verify each chain's "
             "hash links, and localize the FIRST divergent round + "
             "component (params leaf / opt / ledger / schedule / wire "
             "/ rng) with a per-leaf drill-down — exit 1 on divergence "
             "or a broken/tampered chain (pure host, no backend init)",
    )
    df.add_argument("run_a", metavar="RUN_A",
                    help="run name (looked up under --out-dir), a run "
                         "directory, or a .metrics.jsonl path")
    df.add_argument("run_b", metavar="RUN_B",
                    help="the run to compare against (same forms)")
    df.add_argument("--out-dir", default="runs",
                    help="where <RUN>.metrics.jsonl lives (default: runs)")
    df.add_argument("--json", action="store_true",
                    help="emit the diff report as one JSON object "
                         "instead of the table")

    rp = sub.add_parser(
        "replay",
        help="single-round determinism replay (run.obs.digest): "
             "re-execute exactly one logged digest round from the "
             "nearest checkpoint at or before its window start and "
             "verify the recomputed digest against the round_digest "
             "record, component by component — exit 1 on mismatch",
    )
    _add_common(rp)
    rp.add_argument("--round", type=int, required=True, metavar="R",
                    dest="replay_round",
                    help="digest round to replay (a round carrying a "
                         "round_digest record)")

    pf = sub.add_parser(
        "preflight",
        help="OOM preflight (run.obs.executables, obs/executables.py): "
             "lower + compile every round program abstractly — no real "
             "buffers bound, nothing executed — and report each "
             "program's predicted peak HBM (arguments + outputs + XLA "
             "temp high-water) against run.obs.hbm_budget_mb and the "
             "device capacity, naming the dominant buffers — exit 1 "
             "when over budget, 2 when the config cannot be "
             "preflighted (sequential engine)",
    )
    _add_common(pf)
    pf.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object instead "
                         "of the table")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)

    # deferred imports keep `colearn configs --help` fast
    from colearn_federated_learning_tpu.config import list_named_configs, resolve_config

    if args.cmd == "configs":
        for name in list_named_configs():
            print(name)
        return 0

    if args.cmd == "store":
        from colearn_federated_learning_tpu.data import store as store_mod

        if args.store_cmd == "info":
            try:
                info = store_mod.open_store(args.dir).describe()
            except (FileNotFoundError, ValueError) as e:
                print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
                return 2
            if args.json:
                print(json.dumps(info))
            else:
                print(store_mod.format_store_info(info))
            return 0
        # build: exactly one source
        sources = [args.config, args.synthetic_clients, args.leaf_femnist,
                   args.leaf, args.cifar10]
        if sum(s is not None for s in sources) != 1:
            print("error: store build needs exactly one of --config, "
                  "--synthetic-clients, --leaf-femnist, --leaf, or "
                  "--cifar10",
                  file=sys.stderr)
            return 2
        try:
            if args.leaf_femnist is not None:
                out = store_mod.write_femnist_store(
                    args.leaf_femnist, args.out, seed=args.seed,
                    shard_mb=args.shard_mb,
                )
            elif args.leaf is not None:
                out = store_mod.write_leaf_store(
                    args.leaf, args.out, seed=args.seed,
                    shard_mb=args.shard_mb,
                )
            elif args.cifar10 is not None:
                out = store_mod.write_cifar10_store(
                    args.cifar10, args.out, num_clients=args.clients,
                    partition=args.partition, alpha=args.alpha,
                    seed=args.seed, shard_mb=args.shard_mb,
                )
            elif args.config is not None:
                cfg = resolve_config(
                    args.config, _parse_overrides(args.overrides)
                )
                if cfg.data.store.dir:
                    raise ValueError(
                        "the source config already points at a store "
                        "(data.store.dir) — converting a store into a "
                        "store is a no-op; use the original config"
                    )
                from colearn_federated_learning_tpu.data import (
                    build_federated_data,
                )

                fed = build_federated_data(
                    cfg.data, seed=cfg.run.seed, **cfg.model.kwargs
                )
                out = store_mod.write_store(
                    args.out, fed, shard_mb=args.shard_mb
                )
            else:
                out = store_mod.build_synthetic_store(
                    args.out,
                    num_clients=args.synthetic_clients,
                    examples_per_client=args.examples_per_client,
                    shape=[int(s) for s in args.shape.split(",")],
                    num_classes=args.classes,
                    seed=args.seed,
                    test_examples=args.test_examples,
                    shard_mb=args.shard_mb,
                )
        except (KeyError, ValueError, FileNotFoundError) as e:
            print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
            return 2
        print(json.dumps(store_mod.open_store(out).describe()))
        return 0

    if args.cmd == "check":
        # static analysis over the repo itself: validate() is called
        # as a plain function — no backend init, no engine
        # construction
        from colearn_federated_learning_tpu.analysis import check as _check

        try:
            report = _check.run_check(args.root,
                                      update_matrix=args.update_matrix)
        except (ValueError, OSError) as e:
            print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
            return 2
        if args.json:
            print(json.dumps(report))
        else:
            print(_check.format_report(report))
        return 0 if report["clean"] else 1

    if args.cmd == "diff":
        # pure-host digest-chain bisection — two logs in, the first
        # divergent round + component out (obs/digest.py)
        from colearn_federated_learning_tpu.obs import digest as obs_digest
        from colearn_federated_learning_tpu.obs import summary as obs_summary

        sides = []
        for run in (args.run_a, args.run_b):
            try:
                path = obs_summary.resolve_metrics_path(run, args.out_dir)
            except FileNotFoundError as e:
                print(f"error: {e.args[0] if e.args else e}",
                      file=sys.stderr)
                return 2
            records = obs_summary.load_records(path)
            if not any(r.get("event") == "round_digest" for r in records):
                print(f"error: no round_digest records in {path} "
                      f"(was the run recorded with "
                      f"run.obs.digest.enabled=true?)", file=sys.stderr)
                return 2
            sides.append((path, records))
        report = obs_digest.diff_streams(sides[0][1], sides[1][1])
        if args.json:
            print(json.dumps(dict(
                report, path_a=sides[0][0], path_b=sides[1][0],
            )))
        else:
            print(obs_digest.format_diff(report, args.run_a, args.run_b))
        if report["status"] == "no_overlap":
            return 2
        return 0 if report["status"] == "match" else 1

    if args.cmd in ("summarize", "clients", "watch", "population"):
        # pure-host JSONL aggregation — runs before (and without) any
        # jax backend initialization
        from colearn_federated_learning_tpu.obs import summary as obs_summary

        try:
            path = obs_summary.resolve_metrics_path(args.run, args.out_dir)
        except FileNotFoundError as e:
            print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
            return 2
        records = obs_summary.load_records(path)
        if not records:
            # an empty (or torn-to-nothing) log gets a clean error, not
            # a zero-row table or a traceback — watch included (the
            # live tailer shares summarize's empty/missing contract)
            print(f"error: no metrics records in {path}", file=sys.stderr)
            return 2
        if args.cmd == "watch":
            from colearn_federated_learning_tpu.obs import (
                population as obs_population,
            )

            if args.json or args.once:
                snap = obs_population.watch_snapshot(records)
                if args.json:
                    print(json.dumps(dict(snap, path=path)))
                else:
                    print(obs_population.format_watch(snap, path))
                return 0
            return obs_population.watch_follow(path, interval=args.interval)
        if args.cmd == "population":
            from colearn_federated_learning_tpu.obs import (
                population as obs_population,
            )

            try:
                report = obs_population.population_report(records)
            except ValueError as e:
                print(f"error: {e.args[0] if e.args else e}",
                      file=sys.stderr)
                return 2
            if args.json:
                print(json.dumps(dict(report, path=path)))
            else:
                print(obs_population.format_population_report(report, path))
            return 0
        if args.cmd == "clients":
            from colearn_federated_learning_tpu.obs import ledger as obs_ledger

            try:
                report = obs_ledger.clients_report(
                    records, top_k=args.top,
                    min_flag_rate=args.min_flag_rate,
                )
                sweep = None
                if args.threshold_sweep:
                    sweep = obs_ledger.threshold_sweep(records)
            except ValueError as e:
                print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
                return 2
            if args.json:
                if sweep is not None:
                    report = dict(report, threshold_sweep=sweep)
                print(json.dumps(dict(report, path=path)))
            else:
                print(obs_ledger.format_clients_report(report, path))
                if sweep is not None:
                    print()
                    print("detection threshold sweep:")
                    print(obs_ledger.format_threshold_sweep(sweep))
            return 0
        agg = obs_summary.summarize_records(records)
        if args.json:
            print(json.dumps(dict(agg, path=path)))
        else:
            print(obs_summary.format_summary(agg, path))
        return 0

    # every command below compiles: place the persistent compilation
    # cache first (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
    from colearn_federated_learning_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()

    # multi-host bring-up must precede any backend touch (SURVEY.md §3.5);
    # no-op unless COLEARN_COORDINATOR is set (TPU pods auto-detect inside)
    from colearn_federated_learning_tpu.parallel.distributed import (
        maybe_initialize_from_env,
    )

    maybe_initialize_from_env()

    overrides = _parse_overrides(args.overrides)
    if args.out_dir is not None:
        overrides["run.out_dir"] = args.out_dir
    if args.cmd == "fit":
        if args.resume:
            overrides["run.resume"] = True
        if args.profile is not None:
            overrides["run.profile_round"] = args.profile
        if args.sanitize:
            overrides["run.sanitize"] = True
        if args.engine:
            overrides["run.engine"] = args.engine
        if args.strict_digest:
            overrides["run.obs.digest.strict"] = True
    if args.cmd == "replay":
        # append-mode logger: the replay reads the run's own JSONL and
        # must never truncate it; digest-on is purely observational so
        # forcing it on matches any recorded run's digests
        overrides["run.resume"] = True
        overrides["run.obs.digest.enabled"] = True
    if args.cmd == "preflight":
        # the preflight IS the executable registry — force it on even
        # when the config under test disables observability
        overrides["run.obs.executables"] = True
    try:
        cfg = resolve_config(args.config, overrides)
    except (KeyError, ValueError, FileNotFoundError) as e:
        msg = e.args[0] if e.args else str(e)
        print(f"error: {msg}", file=sys.stderr)
        return 2

    from colearn_federated_learning_tpu.server.round_driver import Experiment

    try:
        exp = Experiment(cfg)
    except (ValueError, KeyError, FileNotFoundError) as e:
        # configuration-shaped failures get a clean one-liner; genuine
        # runtime errors below still surface with full tracebacks
        print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
        return 2
    if args.cmd == "preflight":
        from colearn_federated_learning_tpu.obs.executables import (
            HbmBudgetError,
            format_preflight_report,
        )

        try:
            report = exp.preflight()
        except HbmBudgetError as e:
            # names the offending program + its dominant buffers
            print(f"preflight: {e}", file=sys.stderr)
            return 1
        except ValueError as e:
            print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
            return 2
        if args.json:
            print(json.dumps(report))
        else:
            print(format_preflight_report(report))
        budget = report["hbm_budget_bytes"]
        return 1 if budget and report["predicted_peak_bytes"] > budget else 0
    if args.cmd == "replay":
        try:
            report = exp.replay_round(args.replay_round)
        except (ValueError, FileNotFoundError) as e:
            print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
            return 2
        print(json.dumps(report))
        return 0 if report["match"] else 1
    if args.cmd == "fit":
        from colearn_federated_learning_tpu.obs import HealthAbortError
        from colearn_federated_learning_tpu.obs.digest import (
            DigestResumeError,
        )

        try:
            state = exp.fit()
        except HealthAbortError as e:
            # the run's health monitor aborted it (run.obs.on_unhealthy);
            # the JSONL holds the structured health events — point there
            print(f"error: run aborted unhealthy: {e}", file=sys.stderr)
            return 3
        except DigestResumeError as e:
            # --strict-digest: the checkpoint's chain head did not
            # verify against the log — refuse to continue a run whose
            # history cannot be trusted
            print(f"error: {e}", file=sys.stderr)
            return 3
        final = {"event": "done", "rounds": int(state["round"]),
                 "wall_time_sec": round(state.get("wall_time", 0.0), 2)}
        final.update(exp.evaluate(state["params"]))
        print(json.dumps(final))
        return 0
    if args.cmd == "evaluate":
        kwargs = {}
        if args.federated:
            kwargs["federated"] = True
            kwargs["federated_clients"] = args.federated_clients
        if args.personalize:
            kwargs.update({
                "personalize": True,
                "epochs": args.personalize_epochs,
                "max_clients": args.personalize_clients,
                "holdout_frac": args.holdout_frac,
            })
        try:
            out = exp.evaluate_checkpoint(step=args.step, **kwargs)
        except (ValueError, FileNotFoundError) as e:
            print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
            return 2
        print(json.dumps(out))
        return 0
    if args.cmd == "export":
        try:
            out = exp.export_checkpoint(args.output, step=args.step)
        except (ValueError, FileNotFoundError) as e:
            print(f"error: {e.args[0] if e.args else e}", file=sys.stderr)
            return 2
        print(json.dumps(out))
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
