"""JSONL record-schema registry + emit/consume cross-check
(`colearn check` analyzer c).

Four pure-host CLIs (summarize / watch / population / clients)
consume the metrics JSONL that the driver and obs modules emit — three
hand-maintained shapes with no machine check that they agree. This
module is the single registry of every record type plus two static
extractors that cross-check it against the code:

- **emit sites**: AST-walk the emitting modules for ``logger.log({...})``
  calls (dict literals AND locally-assigned dicts with their
  ``rec["k"] = ...`` / ``rec.update({...})`` augmentations) and for
  record-constructor dict literals carrying an ``"event"`` key
  (obs/health.py, obs/population.py return records the driver logs).
  Unregistered record types, emitted-but-unregistered fields, and
  statically-missing required fields all fail with file:line.
- **consumers**: AST-walk the report modules for record-variable field
  accesses (``rec.get("x")`` / ``rec["x"]`` / ``"x" in rec``), where
  record variables are inferred from iteration over the records list,
  ``next(...)`` over filtered generators, filtered-list subscripts, and
  propagation through local assignment + record-returning helpers.
  Consumed-but-never-registered types and fields fail with file:line.

``validate_records`` is the runtime twin: the tier-1 suite runs it over
a live tiny-fit's JSONL so dynamically-keyed records (comm stats, the
ledger columns, ``run_summary`` spreads) are held to the registry too.
"""

from __future__ import annotations

import ast
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from colearn_federated_learning_tpu.obs.ledger import LEDGER_COLS

# fields MetricsLogger.log stamps onto every record
UNIVERSAL_FIELDS = frozenset({"time", "schema"})


class RecordSpec:
    """One record type's contract: required + optional field names, and
    whether runtime extras beyond them are legal (``open_fields`` —
    used only for records whose keys are inherently dynamic, e.g. the
    backend-defined ``device_memory`` gauges)."""

    def __init__(self, required: Iterable[str],
                 optional: Iterable[str] = (),
                 open_fields: bool = False, doc: str = ""):
        self.required = frozenset(required)
        self.optional = frozenset(optional)
        self.open_fields = open_fields
        self.doc = doc

    @property
    def fields(self) -> frozenset:
        return self.required | self.optional


_COMM_FIELDS = (
    "upload_bytes", "upload_bytes_raw", "download_bytes",
    "download_bytes_raw", "upload_bytes_full", "wire_reduction_vs_full",
    "host_input_bytes",
)

# The registry. "round" is the event-less per-round metrics record; all
# others are keyed by their "event" value.
REGISTRY: Dict[str, RecordSpec] = {
    "round": RecordSpec(
        required=("round", "train_loss", "examples"),
        optional=_COMM_FIELDS + (
            "padded_step_fraction", "padded_example_fraction",
            "shape_bucket_steps", "dropped_clients", "straggler_clients",
            "dp_epsilon", "dp_client_epsilon", "mean_staleness",
            "max_staleness", "staleness_p50", "staleness_p90",
            "staleness_clamped", "backpressure_dropped",
            "backpressure_rejected", "churn_unavailable", "churn_dropped",
            "churn_crashed",
            # multi-version / hierarchy planes (ISSUE 16)
            "async_version", "version_readmitted", "hier_edge_crashed",
            "hier_edge_excluded", "hier_core_upload_bytes",
            "byzantine_count", "consensus_dist", "rounds_per_sec",
            "client_updates_per_sec_per_chip", "eval_loss", "eval_acc",
            # RoundMetrics.aux: the decoders' counters (models/keye.py,
            # models/axk1.py, models/mellum2.py)
            "indexer_loss", "held_assignment_share",
            "expert_load_max_over_mean", "selected_key_share",
            "held_group_hit_share", "expert_tile_fill", "band_pair_share",
        ),
        doc="per-round metrics (driver flush windows)",
    ),
    "spans": RecordSpec(
        required=("round", "phases", "process_index"),
        doc="per-phase timing aggregates at each metrics flush: "
            "phases = {name: {count, total_ms, max_ms, self_ms}}",
    ),
    "device_memory": RecordSpec(
        required=("round",), open_fields=True,
        doc="jax device memory gauges (backend-defined keys)",
    ),
    "health": RecordSpec(
        required=("kind", "round"),
        optional=("loss", "best_loss", "factor"),
        doc="NaN/divergence monitor events (obs/health.py)",
    ),
    "retry": RecordSpec(
        required=("attempt", "round", "error"),
        doc="failure-recovery attempts (run.max_retries)",
    ),
    "run_summary": RecordSpec(
        required=("rounds", "wall_time_sec", "compiles", "compile_ms"),
        optional=_COMM_FIELDS + (
            "host_prefetched", "placed_prefetched", "prefetch_dropped",
            "slab_prefetched",
            "ledger_evictions", "ledger_page_syncs",
            "population_unique_clients", "population_coverage_pct",
            "population_participations", "pager_hit_rate",
            # store data plane (PR 19): wall throughput + pool width
            "store_gather_bytes", "store_gather_mbps",
            "store_gather_workers",
            # production-traffic totals (run.churn / fedbuff promotion)
            "staleness_clamped", "backpressure_dropped",
            "backpressure_rejected", "churn_unavailable", "churn_dropped",
            "churn_crashed", "async_updates_absorbed",
            "async_updates_per_sec", "async_staleness_bound",
            # multi-version / hierarchy planes (ISSUE 16)
            "async_staleness_p50", "async_staleness_p90",
            "async_staleness_max", "async_per_version",
            "version_readmitted", "hier_edges", "hier_edge_absorbed",
            "hier_edge_crashed", "hier_edge_excluded",
            "hier_core_upload_bytes",
            # compiled-program observatory (PR 20): run HBM peak
            "hbm_peak_bytes", "hbm_peak_program", "executables_compiled",
        ),
        doc="end-of-fit totals (every exit path, aborts included)",
    ),
    "trace": RecordSpec(
        required=("path",), optional=("merged_fragments",),
        doc="Chrome-trace export provenance",
    ),
    "churn": RecordSpec(
        required=("diurnal_period", "diurnal_amplitude",
                  "base_availability", "min_availability",
                  "dropout_hazard", "crash_rate"),
        optional=("trace", "trace_rounds", "trace_rows"),
        doc="churn hazard-model provenance at fit start (run.churn)",
    ),
    "hierarchy": RecordSpec(
        required=("num_edges", "core_aggregator", "edge_aggregator",
                  "edge_dropout_rate", "core_trust_decay"),
        doc="two-tier topology provenance at fit start "
            "(server.hierarchy)",
    ),
    "async_versions": RecordSpec(
        required=("versions", "retire_rounds", "retire_updates",
                  "readmit_decay", "strict_versions"),
        doc="multi-version scheduler provenance at fit start "
            "(server.async_versions > 1)",
    ),
    "resumed": RecordSpec(
        required=("round", "host_pipeline"),
        doc="checkpoint-resume provenance",
    ),
    "precision": RecordSpec(
        required=("param_dtype", "compute_dtype", "local_param_dtype",
                  "fused_apply", "double_buffer", "control_plane"),
        doc="dtype/fusion/control-plane provenance at fit start",
    ),
    "poisson_sampling": RecordSpec(
        required=("q", "cap", "dp_delta_abort"),
        doc="poisson-sampling provenance (cap + abort probability)",
    ),
    "shape_buckets": RecordSpec(
        required=("ladder", "full_steps_per_epoch",
                  "max_compiles_per_engine"),
        doc="bucket-ladder provenance at fit start",
    ),
    "shape_bucket": RecordSpec(
        required=("round", "bucket_steps", "ladder_steps", "compiles"),
        optional=("compile_ms",),
        doc="per-dispatch bucket-rung attribution",
    ),
    "attack": RecordSpec(
        required=("kind", "fraction", "scale", "eps", "n_compromised",
                  "compromised"),
        doc="adversary provenance (ground truth for `colearn clients`)",
    ),
    "warning": RecordSpec(
        required=("warning", "detail"), optional=("round",),
        doc="structured run-log warnings",
    ),
    "partition_repair": RecordSpec(
        required=("moved",),
        doc="extreme-alpha Dirichlet partition repair provenance",
    ),
    "profile": RecordSpec(
        required=("round", "dir"),
        doc="jax.profiler trace provenance (run.profile_round)",
    ),
    "client_ledger": RecordSpec(
        required=("round", "num_clients", "ema", "zmax", "ids")
        + LEDGER_COLS[:2],
        optional=LEDGER_COLS[2:],
        doc="columnar forensic-ledger snapshot (obs/ledger.py)",
    ),
    "population_health": RecordSpec(
        required=("round", "window_rounds", "participants", "coverage",
                  "fairness", "staleness"),
        optional=("draws", "sketch", "pager", "store", "async", "churn"),
        doc="per-window federation health record (obs/population.py)",
    ),
    "round_digest": RecordSpec(
        required=("round", "prev_round", "prev", "self", "params",
                  "params_leaves", "opt", "ledger", "schedule", "wire",
                  "rng"),
        doc="determinism flight-recorder chain link (obs/digest.py): "
            "per-component state digests + the hash-chain self/prev",
    ),
    "digest_resume": RecordSpec(
        required=("round", "ok", "head_round", "head", "detail"),
        doc="checkpoint digest-head vs log chain verification at resume "
            "(run.obs.digest.verify_resume)",
    ),
    "executable_compiled": RecordSpec(
        required=("round", "name", "fingerprint", "compile_ms"),
        optional=("flops", "bytes_accessed", "argument_bytes",
                  "output_bytes", "temp_bytes", "generated_code_bytes",
                  "peak_bytes", "donated_args", "rounds_per_call",
                  "backend", "preflight"),
        doc="per-compiled-program XLA cost/memory truth "
            "(obs/executables.py; run.obs.executables)",
    ),
    "retrace": RecordSpec(
        required=("round", "name", "fingerprint", "prev_fingerprint",
                  "n_changed", "changed"),
        doc="recompile forensics: which argument of an already-seen "
            "program changed shape/dtype/sharding",
    ),
    "hbm_watermark": RecordSpec(
        required=("round", "watermark_bytes"),
        optional=("program", "resident_bytes", "temp_bytes", "programs",
                  "peak_bytes"),
        doc="per-flush predicted HBM high-water mark over the window's "
            "dispatched programs (+ running run peak)",
    ),
}

# modules whose logger.log(...) calls are emit sites (repo-root relative)
EMIT_LOG_MODULES = (
    "colearn_federated_learning_tpu/server/round_driver.py",
)
# modules whose "event"-keyed dict literals are record constructors the
# driver logs (returned, not logged in place)
EVENT_DICT_MODULES = (
    "colearn_federated_learning_tpu/obs/health.py",
    "colearn_federated_learning_tpu/obs/population.py",
    "colearn_federated_learning_tpu/obs/executables.py",
)
# the pure-host report modules `colearn summarize/watch/population/
# clients` run
CONSUMER_MODULES = (
    "colearn_federated_learning_tpu/obs/summary.py",
    "colearn_federated_learning_tpu/obs/population.py",
    "colearn_federated_learning_tpu/obs/ledger.py",
    "colearn_federated_learning_tpu/obs/digest.py",
)


def all_registered_fields() -> Set[str]:
    out: Set[str] = set(UNIVERSAL_FIELDS) | {"event", "round"}
    for spec in REGISTRY.values():
        out |= spec.fields
    return out


# ---------------------------------------------------------------------------
# emit-site extraction
# ---------------------------------------------------------------------------


class _DictInfo:
    """Statically-known shape of one emitted dict: literal keys, the
    constant "event" value (if any), and whether dynamic writes (** /
    .update(expr) / var[expr] = ...) make it open-ended."""

    def __init__(self, line: int):
        self.line = line
        self.keys: Set[str] = set()
        self.event: Optional[str] = None
        self.open = False

    def absorb_literal(self, node: ast.Dict):
        for k, v in zip(node.keys, node.values):
            if k is None:  # ** spread
                self.open = True
            elif isinstance(k, ast.Constant) and isinstance(k.value, str):
                self.keys.add(k.value)
                if k.value == "event":
                    if isinstance(v, ast.Constant) and isinstance(v.value, str):
                        self.event = v.value
                    else:
                        self.open = True  # event not statically known
            else:
                self.open = True


def _collect_fn_dicts(fn: ast.AST) -> Dict[str, _DictInfo]:
    """var name → dict shape, from ``v = {...}`` assignments plus
    ``v["k"] = ...`` / ``v.update(...)`` augmentations in one function
    (nested defs included — the driver's flush closures)."""
    infos: Dict[str, _DictInfo] = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(
                node.targets[0], ast.Subscript):
            sub = node.targets[0]
            if isinstance(sub.value, ast.Name) and sub.value.id in infos:
                idx = sub.slice
                if isinstance(idx, ast.Constant) and isinstance(idx.value, str):
                    infos[sub.value.id].keys.add(idx.value)
                else:
                    infos[sub.value.id].open = True
        elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    info = infos.setdefault(tgt.id, _DictInfo(node.lineno))
                    info.absorb_literal(node.value)
        elif (isinstance(node, ast.AnnAssign)
                and isinstance(node.value, ast.Dict)
                and isinstance(node.target, ast.Name)):
            # `rec: Dict[str, Any] = {...}` — the driver's preferred style
            info = infos.setdefault(node.target.id, _DictInfo(node.lineno))
            info.absorb_literal(node.value)
        elif (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "update"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in infos):
            info = infos[node.func.value.id]
            if node.args and isinstance(node.args[0], ast.Dict):
                info.absorb_literal(node.args[0])
            else:
                info.open = True
    return infos


def _iter_functions(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def extract_emit_sites(root: str,
                       log_modules: Sequence[str] = EMIT_LOG_MODULES,
                       dict_modules: Sequence[str] = EVENT_DICT_MODULES,
                       ) -> List[Dict[str, Any]]:
    """Every statically-resolvable emit site:
    ``{file, line, type, keys, open, resolved}`` — ``type`` is None for
    ``.log(expr)`` calls whose dict could not be resolved (dynamic
    sites; the runtime validator covers them)."""
    sites: List[Dict[str, Any]] = []

    def _site(rel, info: _DictInfo, line=None):
        rtype = info.event
        if rtype is None and "round" in info.keys:
            rtype = "round"
        sites.append({
            "file": rel, "line": line or info.line, "type": rtype,
            "keys": sorted(info.keys), "open": info.open, "resolved": True,
        })

    for rel in log_modules:
        path = os.path.join(root, rel)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for fn in _iter_functions(tree):
            infos = None
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "log"
                        and "logger" in _attr_base_names(node.func)):
                    continue
                arg = node.args[0] if node.args else None
                if isinstance(arg, ast.Dict):
                    info = _DictInfo(node.lineno)
                    info.absorb_literal(arg)
                    _site(rel, info, node.lineno)
                elif isinstance(arg, ast.Name):
                    if infos is None:
                        infos = _collect_fn_dicts(fn)
                    if arg.id in infos:
                        _site(rel, infos[arg.id], node.lineno)
                    else:
                        sites.append({
                            "file": rel, "line": node.lineno, "type": None,
                            "keys": [], "open": True, "resolved": False,
                        })
                else:
                    sites.append({
                        "file": rel, "line": node.lineno, "type": None,
                        "keys": [], "open": True, "resolved": False,
                    })
    for rel in dict_modules:
        path = os.path.join(root, rel)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        assigned_dicts: Set[int] = set()
        for fn in _iter_functions(tree):
            infos = _collect_fn_dicts(fn)
            for node in ast.walk(fn):
                if isinstance(node, (ast.Assign, ast.AnnAssign)) \
                        and isinstance(node.value, ast.Dict):
                    assigned_dicts.add(id(node.value))
            for name, info in infos.items():
                if info.event is not None:
                    _site(rel, info)
        # record-constructor dicts used inline (e.g. `return {...}`)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Dict) and id(node) not in assigned_dicts
                    and any(isinstance(k, ast.Constant) and k.value == "event"
                            for k in node.keys if k is not None)):
                info = _DictInfo(node.lineno)
                info.absorb_literal(node)
                if info.event is not None:
                    _site(rel, info)
    # module walks visit nested defs through their parents too — one
    # site per (file, line), first wins
    seen: Set[Tuple[str, int]] = set()
    unique: List[Dict[str, Any]] = []
    for site in sites:
        key = (site["file"], site["line"])
        if key not in seen:
            seen.add(key)
            unique.append(site)
    return unique


def _attr_base_names(node: ast.Attribute) -> Set[str]:
    names: Set[str] = set()
    cur = node.value
    while isinstance(cur, ast.Attribute):
        names.add(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        names.add(cur.id)
    return names


def check_emit_sites(root: str,
                     log_modules: Sequence[str] = EMIT_LOG_MODULES,
                     dict_modules: Sequence[str] = EVENT_DICT_MODULES,
                     ) -> Tuple[List[Dict[str, Any]],
                                List[Dict[str, Any]]]:
    """Cross-check emit sites against the registry. Returns
    (violations, sites). Module lists are injectable so seeded-violation
    fixtures can be scanned."""
    sites = extract_emit_sites(root, log_modules, dict_modules)
    violations: List[Dict[str, Any]] = []
    for site in sites:
        where = f"{site['file']}:{site['line']}"
        rtype = site["type"]
        if not site["resolved"]:
            continue  # dynamic site — the runtime validator owns it
        if rtype is None:
            violations.append({
                "kind": "emit_untyped_record", "where": where,
                "message": "emitted record has neither a constant "
                           "'event' nor a 'round' key",
            })
            continue
        spec = REGISTRY.get(rtype)
        if spec is None:
            violations.append({
                "kind": "emit_unregistered_type", "where": where,
                "message": f"record type {rtype!r} is emitted here but "
                           f"not registered in analysis/schema.py",
            })
            continue
        legal = spec.fields | UNIVERSAL_FIELDS | {"event", "round"}
        for key in site["keys"]:
            if key not in legal and not spec.open_fields:
                violations.append({
                    "kind": "emit_unregistered_field", "where": where,
                    "message": f"record type {rtype!r} emits field "
                               f"{key!r} not registered in its schema",
                })
        if not site["open"]:
            missing = spec.required - set(site["keys"]) - {"event"}
            for key in sorted(missing):
                violations.append({
                    "kind": "emit_missing_required", "where": where,
                    "message": f"record type {rtype!r} emit site lacks "
                               f"required field {key!r}",
                })
    return violations, sites


# ---------------------------------------------------------------------------
# consumer extraction
# ---------------------------------------------------------------------------

_RECORD_LIST_PARAMS = {"records", "recs"}


class _ConsumerScan:
    """Per-function record-variable inference (see module docstring)."""

    def __init__(self, record_returning: Set[str]):
        self.record_returning = record_returning
        self.types: List[Tuple[str, int]] = []     # (type literal, line)
        self.fields: List[Tuple[str, int]] = []    # (field literal, line)

    def scan(self, fn: ast.AST):
        record_vars: Set[str] = set()
        list_vars: Set[str] = set(
            a.arg for a in getattr(fn, "args", ast.arguments(
                args=[], posonlyargs=[], kwonlyargs=[], kw_defaults=[],
                defaults=[])).args
            if a.arg in _RECORD_LIST_PARAMS
        )
        event_vars: Set[str] = set()

        def is_list_expr(node) -> bool:
            if isinstance(node, ast.Name):
                return node.id in list_vars
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                if node.func.id in ("reversed", "sorted", "list") and node.args:
                    return is_list_expr(node.args[0])
            return False

        def is_record_expr(node) -> bool:
            if isinstance(node, ast.Name):
                return node.id in record_vars
            if isinstance(node, ast.Subscript) and is_list_expr(node.value):
                return True
            if isinstance(node, ast.Call):
                if (isinstance(node.func, ast.Name)
                        and node.func.id == "next" and node.args
                        and isinstance(node.args[0], ast.GeneratorExp)
                        and is_list_expr(node.args[0].generators[0].iter)):
                    return True
                if (isinstance(node.func, ast.Name)
                        and node.func.id in self.record_returning):
                    return True
            return False

        # two fixpoint passes: comprehension targets + assignments can
        # chain (recs = [r for r in records ...]; led = recs[-1])
        for _ in range(3):
            for node in ast.walk(fn):
                if isinstance(node, (ast.For,)):
                    if is_list_expr(node.iter) and isinstance(
                            node.target, ast.Name):
                        record_vars.add(node.target.id)
                elif isinstance(node, (ast.ListComp, ast.GeneratorExp,
                                       ast.SetComp)):
                    for gen in node.generators:
                        if is_list_expr(gen.iter) and isinstance(
                                gen.target, ast.Name):
                            record_vars.add(gen.target.id)
                elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    tgt = node.targets[0].id
                    val = node.value
                    if is_record_expr(val):
                        record_vars.add(tgt)
                    elif isinstance(val, (ast.ListComp,)) and is_list_expr(
                            val.generators[0].iter):
                        list_vars.add(tgt)
                    elif (isinstance(val, ast.Call)
                            and isinstance(val.func, ast.Attribute)
                            and val.func.attr == "get"
                            and isinstance(val.func.value, ast.Name)
                            and val.func.value.id in record_vars
                            and val.args
                            and isinstance(val.args[0], ast.Constant)
                            and val.args[0].value == "event"):
                        event_vars.add(tgt)
                elif isinstance(node, ast.BoolOp):
                    # `cov = r.get("coverage") or {}` — handled above via
                    # Assign; BoolOp values don't create record vars
                    pass

        def is_event_expr(node) -> bool:
            if isinstance(node, ast.Name) and node.id in event_vars:
                return True
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "get"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in record_vars
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and node.args[0].value == "event"):
                return True
            if (isinstance(node, ast.Subscript)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in record_vars
                    and isinstance(node.slice, ast.Constant)
                    and node.slice.value == "event"):
                return True
            return False

        for node in ast.walk(fn):
            # consumed record types: `<event-expr> == "lit"` (+ tuples)
            if isinstance(node, ast.Compare) and is_event_expr(node.left):
                for comp in node.comparators:
                    if isinstance(comp, ast.Constant) and isinstance(
                            comp.value, str):
                        self.types.append((comp.value, node.lineno))
                    elif isinstance(comp, ast.Tuple):
                        for el in comp.elts:
                            if isinstance(el, ast.Constant) and isinstance(
                                    el.value, str):
                                self.types.append((el.value, node.lineno))
            # consumed fields
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "get"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in record_vars
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                self.fields.append((node.args[0].value, node.lineno))
            elif (isinstance(node, ast.Subscript)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in record_vars
                    and isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)):
                self.fields.append((node.slice.value, node.lineno))
            elif (isinstance(node, ast.Compare)
                    and isinstance(node.left, ast.Constant)
                    and isinstance(node.left.value, str)
                    and len(node.ops) == 1
                    and isinstance(node.ops[0], (ast.In, ast.NotIn))
                    and isinstance(node.comparators[0], ast.Name)
                    and node.comparators[0].id in record_vars):
                self.fields.append((node.left.value, node.lineno))


def _record_returning_functions(tree: ast.Module) -> Set[str]:
    """Module functions whose return value is a record (``recs[-1]``
    style) — their callers' assignment targets become record vars."""
    out: Set[str] = set()
    for fn in _iter_functions(tree):
        scan = _ConsumerScan(set())
        # reuse the record-var inference by checking returns manually
        record_vars: Set[str] = set()
        list_vars: Set[str] = {
            a.arg for a in fn.args.args if a.arg in _RECORD_LIST_PARAMS
        }
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.ListComp) \
                    and isinstance(node.value.generators[0].iter, ast.Name) \
                    and node.value.generators[0].iter.id in list_vars:
                list_vars.add(node.targets[0].id)
        for node in ast.walk(fn):
            if isinstance(node, ast.Return) and node.value is not None:
                v = node.value
                if (isinstance(v, ast.Subscript)
                        and isinstance(v.value, ast.Name)
                        and v.value.id in list_vars):
                    out.add(fn.name)
        del scan, record_vars
    return out


def extract_consumed(root: str,
                     modules: Sequence[str] = CONSUMER_MODULES,
                     ) -> Tuple[List[Tuple[str, str, int]],
                                List[Tuple[str, str, int]]]:
    """Returns (types, fields) as lists of (literal, file, line)."""
    types: List[Tuple[str, str, int]] = []
    fields: List[Tuple[str, str, int]] = []
    for rel in modules:
        path = os.path.join(root, rel)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        returning = _record_returning_functions(tree)
        for fn in _iter_functions(tree):
            scan = _ConsumerScan(returning)
            scan.scan(fn)
            types.extend((t, rel, ln) for t, ln in scan.types)
            fields.extend((fld, rel, ln) for fld, ln in scan.fields)
    return types, fields


def check_consumers(root: str,
                    modules: Sequence[str] = CONSUMER_MODULES,
                    ) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """Cross-check consumer modules against the registry."""
    types, fields = extract_consumed(root, modules)
    legal_fields = all_registered_fields()
    violations: List[Dict[str, Any]] = []
    for t, rel, ln in types:
        if t not in REGISTRY:
            violations.append({
                "kind": "consume_unregistered_type",
                "where": f"{rel}:{ln}",
                "message": f"consumer filters on record type {t!r}, "
                           f"which no emit site registers",
            })
    for fld, rel, ln in fields:
        if fld not in legal_fields:
            violations.append({
                "kind": "consume_unregistered_field",
                "where": f"{rel}:{ln}",
                "message": f"consumer reads field {fld!r}, which no "
                           f"registered record type emits",
            })
    summary = {
        "consumed_types": sorted({t for t, _, _ in types}),
        "consumed_fields": sorted({f for f, _, _ in fields}),
    }
    return violations, summary


# ---------------------------------------------------------------------------
# runtime validation (live JSONL → registry)
# ---------------------------------------------------------------------------


def validate_records(records: Iterable[Dict[str, Any]],
                     ) -> List[Dict[str, Any]]:
    """Hold a live run's JSONL to the registry: every record must carry
    a registered type, its required fields, and (unless the spec is
    open) only registered fields. The tier-1 suite runs this over a
    tiny-fit log so dynamically-keyed emits can't drift unregistered."""
    violations: List[Dict[str, Any]] = []
    for i, rec in enumerate(records):
        rtype = rec.get("event")
        if rtype is None:
            rtype = "round" if "round" in rec else None
        if rtype is None:
            violations.append({
                "kind": "record_untyped", "where": f"record[{i}]",
                "message": f"record carries neither 'event' nor 'round': "
                           f"{sorted(rec)[:8]}",
            })
            continue
        spec = REGISTRY.get(rtype)
        if spec is None:
            violations.append({
                "kind": "record_unregistered_type", "where": f"record[{i}]",
                "message": f"record type {rtype!r} is not registered",
            })
            continue
        keys = set(rec) - UNIVERSAL_FIELDS - {"event"}
        missing = spec.required - keys
        for key in sorted(missing):
            violations.append({
                "kind": "record_missing_required", "where": f"record[{i}]",
                "message": f"{rtype!r} record lacks required field {key!r}",
            })
        if not spec.open_fields:
            extras = keys - spec.fields - {"round"}
            for key in sorted(extras):
                violations.append({
                    "kind": "record_unregistered_field",
                    "where": f"record[{i}]",
                    "message": f"{rtype!r} record carries unregistered "
                               f"field {key!r}",
                })
    return violations


def check_schema(root: str) -> Dict[str, Any]:
    """The `colearn check` entry: both static cross-checks."""
    emit_violations, sites = check_emit_sites(root)
    consume_violations, consumed = check_consumers(root)
    return {
        "registered_types": sorted(REGISTRY),
        "emit_sites": len(sites),
        "emit_sites_resolved": sum(1 for s in sites if s["resolved"]),
        "violations": emit_violations + consume_violations,
        **consumed,
    }
