"""Run-summary aggregation: metrics JSONL → per-phase timing table.

``colearn summarize <run>`` makes a finished (or in-flight) run
inspectable without TensorBoard or a trace viewer: it folds every
``spans`` record into one per-phase table (count / total / mean / max /
share of the round loop), totals the communication counters, and
surfaces health/retry/profile events. Pure stdlib — importable (and
fast) without touching a jax backend, so the CLI wires it up before
device initialization.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict, List


def resolve_metrics_path(run: str, out_dir: str = "runs") -> str:
    """Resolve a ``summarize`` argument to a metrics JSONL path: a file
    path as-is, a directory's newest ``*.metrics.jsonl``, else
    ``<out_dir>/<run>.metrics.jsonl`` (the logger's layout)."""
    if os.path.isfile(run):
        return run
    if os.path.isdir(run):
        hits = sorted(
            glob.glob(os.path.join(run, "*.metrics.jsonl")),
            key=os.path.getmtime,
        )
        if not hits:
            raise FileNotFoundError(f"no *.metrics.jsonl under {run!r}")
        return hits[-1]
    cand = os.path.join(out_dir, f"{run}.metrics.jsonl")
    if os.path.isfile(cand):
        return cand
    raise FileNotFoundError(
        f"cannot resolve run {run!r}: not a file, not a directory, and "
        f"{cand!r} does not exist"
    )


def load_records(path: str) -> List[Dict[str, Any]]:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # a torn tail line from a crashed run is expected
    return records


_COUNTER_KEYS = (
    "upload_bytes", "upload_bytes_raw", "download_bytes",
    "download_bytes_raw",
)


def summarize_records(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold a run's records into one summary dict (see format_summary).

    Totals come from the end-of-fit ``run_summary`` record when one
    exists (the driver logs it on every exit path — aborts included —
    exactly so consumers don't have to re-aggregate the whole JSONL);
    per-round counter summation runs only as the fallback for logs
    that predate it. ``summary["source"]`` records which path was
    taken, and the rendered table prints it."""
    # the authoritative totals record lives at the tail of the log —
    # scan from the end so the fast path stays fast on long logs
    run_sum = next(
        (r for r in reversed(records) if r.get("event") == "run_summary"),
        None,
    )
    phases: Dict[str, Dict[str, float]] = {}
    counters: Dict[str, int] = {}
    health: Dict[str, int] = {}
    events: Dict[str, int] = {}
    rounds = 0
    rps: List[float] = []
    last_eval: Dict[str, float] = {}
    precision: Dict[str, Any] = {}
    executables: Dict[str, Dict[str, Any]] = {}
    retraces: List[Dict[str, Any]] = []
    hbm_peak_bytes = 0
    hbm_peak_program = None
    dropped = stragglers = byzantine = 0
    for rec in records:
        ev = rec.get("event")
        if ev:
            events[ev] = events.get(ev, 0) + 1
        if ev == "executable_compiled":
            # the registry's per-program compile ledger (PR 20); a
            # preflight rehearsal's compiles are not this run's
            if rec.get("preflight"):
                continue
            cur = executables.setdefault(str(rec.get("name", "?")), {
                "compiles": 0, "compile_ms": 0.0, "flops": None,
                "peak_bytes": None,
            })
            cur["compiles"] += 1
            cur["compile_ms"] += float(rec.get("compile_ms") or 0.0)
            if rec.get("flops") is not None:
                cur["flops"] = float(rec["flops"])
            if rec.get("peak_bytes") is not None:
                cur["peak_bytes"] = int(rec["peak_bytes"])
            continue
        if ev == "retrace":
            retraces.append({
                "round": rec.get("round"),
                "name": rec.get("name"),
                "changed": rec.get("changed") or [],
            })
            continue
        if ev == "hbm_watermark":
            wb = int(rec.get("watermark_bytes") or 0)
            if wb > hbm_peak_bytes:
                hbm_peak_bytes = wb
                hbm_peak_program = rec.get("program")
            continue
        if ev == "precision":
            # dtype/fusion provenance logged at fit start — surfaced so
            # a throughput read-off carries its compute_dtype column
            precision = {
                k: rec.get(k) for k in (
                    "param_dtype", "compute_dtype", "local_param_dtype",
                    "fused_apply", "double_buffer",
                ) if k in rec
            }
            continue
        if ev == "spans":
            for name, agg in (rec.get("phases") or {}).items():
                cur = phases.setdefault(
                    name, {"count": 0, "total_ms": 0.0, "max_ms": 0.0,
                           "self_ms": 0.0}
                )
                cur["count"] += int(agg.get("count", 0))
                cur["total_ms"] += float(agg.get("total_ms", 0.0))
                cur["max_ms"] = max(cur["max_ms"], float(agg.get("max_ms", 0.0)))
                # logs from before the tracer kept a span stack have no
                # self time: all of a span then counts as its own
                cur["self_ms"] += float(
                    agg.get("self_ms", agg.get("total_ms", 0.0))
                )
            continue
        if ev == "health":
            kind = rec.get("kind", "?")
            health[kind] = health.get(kind, 0) + 1
            continue
        if ev is None and "round" in rec:
            rounds = max(rounds, int(rec["round"]))
            if "rounds_per_sec" in rec:
                rps.append(float(rec["rounds_per_sec"]))
            if run_sum is None:
                # fallback only: pre-run_summary logs re-aggregate the
                # per-round counters; newer logs take the totals from
                # the authoritative record below
                for k in _COUNTER_KEYS:
                    if k in rec:
                        counters[k] = counters.get(k, 0) + int(rec[k])
            dropped += int(rec.get("dropped_clients", 0))
            stragglers += int(rec.get("straggler_clients", 0))
            byzantine += int(rec.get("byzantine_count", 0))
            for k in ("eval_loss", "eval_acc"):
                if k in rec:
                    last_eval[k] = float(rec[k])
    out: Dict[str, Any] = {
        "rounds": rounds,
        "phases": phases,
        "events": events,
        "source": "run_summary" if run_sum is not None else "reaggregated",
    }
    if rps:
        out["rounds_per_sec_mean"] = sum(rps) / len(rps)
    if executables:
        out["executables"] = executables
    if retraces:
        out["retraces"] = retraces
    if run_sum is not None:
        out["rounds"] = max(rounds, int(run_sum.get("rounds", 0)))
        if "wall_time_sec" in run_sum:
            out["wall_time_sec"] = float(run_sum["wall_time_sec"])
        if "compiles" in run_sum:
            out["compiles"] = int(run_sum["compiles"])
        # the run_summary HBM peak (driver-tracked across the whole
        # run) is authoritative over the per-flush watermarks
        if run_sum.get("hbm_peak_bytes") is not None:
            hbm_peak_bytes = int(run_sum["hbm_peak_bytes"])
            hbm_peak_program = run_sum.get("hbm_peak_program")
        counters = {
            k: int(run_sum[k]) for k in _COUNTER_KEYS if k in run_sum
        }
        # paged-ledger accounting (PR 9 recorded these; now rendered):
        # evictions are cold spills, page_syncs the blocking hot-set
        # fetches they forced
        paging = {
            k: int(run_sum[k])
            for k in ("ledger_evictions", "ledger_page_syncs")
            if k in run_sum
        }
        if paging:
            out["ledger_paging"] = paging
        # population totals (run.obs.population): lifetime coverage /
        # participation, overall pager hit rate, store gather bytes
        population = {
            k: run_sum[k]
            for k in ("population_unique_clients",
                      "population_coverage_pct",
                      "population_participations", "pager_hit_rate",
                      "store_gather_bytes")
            if k in run_sum
        }
        if population:
            out["population"] = population
        # async-plane totals (fedbuff runs): absorbed-update throughput
        # and the REALIZED staleness quantiles next to the configured
        # bound — the numbers a staleness-bound claim is checked against
        async_stats = {
            k: run_sum[k]
            for k in ("async_updates_absorbed", "async_updates_per_sec",
                      "async_staleness_bound", "async_staleness_p50",
                      "async_staleness_p90", "async_staleness_max")
            if k in run_sum
        }
        if async_stats:
            out["async"] = async_stats
        # multi-version absorption split (server.async_versions > 1):
        # which model line each absorbed update landed on
        if isinstance(run_sum.get("async_per_version"), dict):
            out["async_per_version"] = {
                str(k): int(v)
                for k, v in run_sum["async_per_version"].items()
            }
        # two-tier wire accounting: core-link upload bytes ride the
        # wire-totals line so hierarchy runs read both tiers at once
        if "hier_core_upload_bytes" in run_sum:
            out["hier_core_upload_bytes"] = int(
                run_sum["hier_core_upload_bytes"]
            )
    if hbm_peak_bytes:
        out["hbm_peak"] = {"bytes": hbm_peak_bytes,
                           "program": hbm_peak_program}
    if counters:
        out["comm"] = counters
    if dropped or stragglers or byzantine:
        out["failures"] = {
            "dropped_clients": dropped,
            "straggler_clients": stragglers,
            "byzantine_sampled": byzantine,
        }
    if health:
        out["health"] = health
    if last_eval:
        out["final_eval"] = last_eval
    if precision:
        out["precision"] = precision
    return out


def _desc_short(d) -> str:
    """Compact render of a registry leaf descriptor (("a", shape,
    dtype, weak, sharding) after a JSON round-trip) for the retrace
    table; anything unrecognized prints truncated, never raises."""
    if isinstance(d, (list, tuple)) and len(d) >= 3 and d[0] == "a":
        try:
            return f"{tuple(d[1])}:{d[2]}"
        except TypeError:
            pass
    return "absent" if d is None else str(d)[:48]


def _fmt_bytes(n: int) -> str:
    v = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if v < 1024.0 or unit == "TiB":
            return f"{v:.1f} {unit}" if unit != "B" else f"{int(v)} B"
        v /= 1024.0


def format_summary(summary: Dict[str, Any], path: str = "") -> str:
    """Render the summary as an aligned text table."""
    lines = []
    head = f"run: {path}" if path else "run summary"
    head += f"  rounds: {summary['rounds']}"
    if "rounds_per_sec_mean" in summary:
        head += f"  rounds/sec (window mean): {summary['rounds_per_sec_mean']:.3f}"
    lines.append(head)
    # which totals path produced the numbers below — the run_summary
    # record when the log carries one, per-round re-aggregation only
    # for logs that predate it
    src = summary.get("source", "reaggregated")
    src_line = (
        "totals: run_summary record" if src == "run_summary"
        else "totals: re-aggregated per-round (log predates run_summary)"
    )
    if "wall_time_sec" in summary:
        src_line += f"  wall: {summary['wall_time_sec']:.1f}s"
    lines.append(src_line)
    prec = summary.get("precision")
    if prec:
        bits = [
            f"compute={prec.get('compute_dtype', '?')}",
            f"params={prec.get('param_dtype', '?')}",
            f"local={prec.get('local_param_dtype', '?')}",
        ]
        if prec.get("fused_apply"):
            bits.append("fused_apply")
        if prec.get("double_buffer"):
            bits.append("double_buffer")
        lines.append("precision: " + "  ".join(bits))
    phases = summary.get("phases") or {}
    if phases:
        # share is relative to the "round" parent span when present,
        # else to the largest phase — nested children overlap, so the
        # column reads "fraction of the round loop", not "sums to 100%"
        base = phases.get("round", {}).get("total_ms") or max(
            (p["total_ms"] for p in phases.values()), default=0.0
        )
        lines.append("")
        lines.append(
            f"{'phase':<24}{'count':>8}{'total s':>11}{'self s':>11}"
            f"{'mean ms':>10}{'max ms':>10}{'share':>8}"
        )
        for name in sorted(phases, key=lambda n: -phases[n]["total_ms"]):
            p = phases[name]
            mean = p["total_ms"] / p["count"] if p["count"] else 0.0
            share = p["total_ms"] / base if base else 0.0
            lines.append(
                f"{name:<24}{p['count']:>8}{p['total_ms'] / 1000.0:>11.3f}"
                f"{p.get('self_ms', p['total_ms']) / 1000.0:>11.3f}"
                f"{mean:>10.2f}{p['max_ms']:>10.2f}{share:>7.0%} "
            )
    else:
        lines.append("no span records (run.obs.spans was off, or pre-obs run)")
    execs = summary.get("executables")
    if execs:
        # the per-executable compile ledger (registry records): what
        # compiled, how often, how long, and the HLO-derived flops —
        # this table supersedes the bare compile-count line below
        lines.append("")
        lines.append(
            f"{'executable':<24}{'compiles':>9}{'wall ms':>10}"
            f"{'flops':>16}{'peak MiB':>10}"
        )
        for name in sorted(execs, key=lambda n: -execs[n]["compile_ms"]):
            e = execs[name]
            flops = ("n/a" if e["flops"] is None
                     else format(int(e["flops"]), ","))
            peak = ("n/a" if e["peak_bytes"] is None
                    else f"{e['peak_bytes'] / 2**20:.1f}")
            lines.append(
                f"{name:<24}{e['compiles']:>9}{e['compile_ms']:>10.1f}"
                f"{flops:>16}{peak:>10}"
            )
        hbm = summary.get("hbm_peak")
        if hbm:
            lines.append(
                f"hbm peak: {hbm['bytes'] / 2**20:.1f} MiB "
                f"({hbm.get('program') or 'n/a'})"
            )
    elif "compiles" in summary:
        # pre-PR-20 log: the run_summary compile count is all there is
        lines.append(
            f"compiles: {summary['compiles']} (per-executable table "
            "n/a — log predates the executable registry)"
        )
    rets = summary.get("retraces")
    if rets:
        lines.append("")
        lines.append("retraces (recompiles of a seen program — each "
                     "names the argument that changed):")
        lines.append(f"{'round':>6}  {'executable':<22}changed")
        for r in rets[:20]:
            changed = "; ".join(
                f"{c.get('arg', '?')}: {_desc_short(c.get('before'))}"
                f" -> {_desc_short(c.get('after'))}"
                for c in (r.get("changed") or [])
            ) or "n/a"
            lines.append(
                f"{r.get('round') or 0:>6}  {str(r.get('name', '?')):<22}"
                f"{changed}"
            )
        if len(rets) > 20:
            lines.append(f"  ... {len(rets) - 20} more retraces")
    comm = summary.get("comm")
    if comm:
        lines.append("")
        comm_line = (
            "comm: upload "
            f"{_fmt_bytes(comm.get('upload_bytes', 0))} wire / "
            f"{_fmt_bytes(comm.get('upload_bytes_raw', 0))} raw, download "
            f"{_fmt_bytes(comm.get('download_bytes', 0))} wire / "
            f"{_fmt_bytes(comm.get('download_bytes_raw', 0))} raw"
        )
        if "hier_core_upload_bytes" in summary:
            comm_line += (
                ", hier core upload "
                f"{_fmt_bytes(summary['hier_core_upload_bytes'])}"
            )
        lines.append(comm_line)
    paging = summary.get("ledger_paging")
    if paging:
        lines.append(
            f"ledger paging: {paging.get('ledger_evictions', 0)} "
            f"evictions, {paging.get('ledger_page_syncs', 0)} page syncs"
        )
    pop = summary.get("population")
    if pop:
        bits = []
        if "population_unique_clients" in pop:
            bits.append(
                f"{pop['population_unique_clients']} unique clients"
                + (f" ({pop['population_coverage_pct']:.1f}% coverage)"
                   if "population_coverage_pct" in pop else "")
            )
        if "population_participations" in pop:
            bits.append(
                f"{pop['population_participations']} participations"
            )
        if "pager_hit_rate" in pop:
            bits.append(
                f"pager hit rate {100.0 * pop['pager_hit_rate']:.1f}%"
            )
        if "store_gather_bytes" in pop:
            bits.append(
                f"store gathered {_fmt_bytes(pop['store_gather_bytes'])}"
            )
        lines.append("population: " + "  ".join(bits))
    a = summary.get("async")
    if a:
        bits = []
        if "async_updates_absorbed" in a:
            bits.append(f"{a['async_updates_absorbed']} updates absorbed")
        if "async_updates_per_sec" in a:
            bits.append(f"{a['async_updates_per_sec']:.1f}/s")
        if "async_staleness_p50" in a:
            bits.append(
                "staleness p50/p90/max "
                f"{a.get('async_staleness_p50')}/"
                f"{a.get('async_staleness_p90')}/"
                f"{a.get('async_staleness_max')}"
                + (f" (bound {a['async_staleness_bound']})"
                   if "async_staleness_bound" in a else "")
            )
        lines.append("async: " + "  ".join(bits))
    apv = summary.get("async_per_version")
    if apv:
        split = "  ".join(
            f"v{k}: {v}" for k, v in sorted(apv.items(), key=lambda i: i[0])
        )
        lines.append(f"async per-version absorption: {split}")
    fails = summary.get("failures")
    if fails:
        lines.append(
            f"failures: {fails['dropped_clients']} dropped, "
            f"{fails['straggler_clients']} stragglers, "
            f"{fails['byzantine_sampled']} byzantine-sampled"
        )
    health = summary.get("health")
    if health:
        kinds = ", ".join(f"{k}×{v}" for k, v in sorted(health.items()))
        lines.append(f"health events: {kinds}")
    ev = summary.get("final_eval")
    if ev:
        parts = ", ".join(f"{k}={v:.4f}" for k, v in sorted(ev.items()))
        lines.append(f"final eval: {parts}")
    return "\n".join(lines)


def summarize_path(path: str) -> str:
    return format_summary(summarize_records(load_records(path)), path)
