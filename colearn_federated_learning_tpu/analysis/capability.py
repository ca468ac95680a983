"""Capability matrix: which features may be combined, extracted from
the one place that decides it (`colearn check` analyzer a).

``config.ExperimentConfig.validate()`` is the only refusal site: the
engine factories take a validated config's values and refuse no pairing
themselves (``parallel/round_engine.make_sharded_round_fn``). This
module enumerates a curated FEATURE catalog (each feature = the
canonical-valid override set that turns one subsystem on), asks
``validate()`` about every feature singleton and every pairing, and
compares the answer with the checked-in ``capability_matrix.json`` —
the golden set of rejected pairings and their reasons. A pairing that
flips either way, or a reason that changes by a letter, fails
`colearn check` (and tests/test_static_analysis.py, feature by
feature) and names itself; `colearn check --update-matrix` regenerates
the artifact for review. That golden set is the oracle for turning
``validate()``'s pairing rules into a table (ROADMAP.md D1).

The artifact holds the catalog (overrides + note per feature), the
counts, and ``rejected``: ``{"a+b": reason}``. A pairing that is not
listed there validates; a pairing whose two override sets set one knob
to different values is ill-posed and skipped. Rejections without a
reason string fail outright.
"""

from __future__ import annotations

import itertools
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from colearn_federated_learning_tpu.config import ExperimentConfig

MATRIX_FILENAME = "capability_matrix.json"
MATRIX_VERSION = 2


class Feature:
    """One subsystem in its canonical-valid form: the dotted overrides
    that enable it."""

    def __init__(self, overrides: Dict[str, Any], note: str = ""):
        self.overrides = overrides
        self.note = note


# The feature catalog. Every singleton MUST validate clean (checked at
# extraction — a failing singleton means the catalog itself is broken).
FEATURES: Dict[str, Feature] = {
    "sequential_engine": Feature({"run.engine": "sequential"},
                                 "the bit-parity oracle engine"),
    "scaffold": Feature({"algorithm": "scaffold", "client.momentum": 0.0},
                        "client control variates"),
    "feddyn": Feature({"algorithm": "feddyn"},
                      "dynamic regularization"),
    "fedbuff": Feature({"algorithm": "fedbuff"},
                       "async buffered aggregation (own engine)"),
    "gossip": Feature({"algorithm": "gossip"},
                      "decentralized DFedAvg (own engine)"),
    "example_dp": Feature({"dp.enabled": True},
                          "example-level local DP-SGD"),
    "client_dp": Feature({"server.dp_client_noise_multiplier": 1.0,
                          "server.clip_delta_norm": 1.0},
                         "central client-level DP (DP-FedAvg)"),
    "secagg": Feature({"server.secure_aggregation": True,
                       "server.clip_delta_norm": 1.0},
                      "ring-mask secure aggregation"),
    "secagg_pairwise": Feature({"server.secure_aggregation": True,
                                "server.clip_delta_norm": 1.0,
                                "server.secagg_mode": "pairwise"},
                               "Bonawitz pairwise-mask protocol shape"),
    "attack_sign_flip": Feature({"attack.kind": "sign_flip"},
                                "boosted sign-flip upload attack"),
    "attack_alie": Feature({"attack.kind": "alie"},
                           "colluding a-little-is-enough attack"),
    "attack_label_flip": Feature({"attack.kind": "label_flip"},
                                 "host-side data poisoning (never "
                                 "reaches the engine)"),
    "robust_median": Feature({"server.aggregator": "median"},
                             "coordinate-wise median"),
    "robust_trimmed_mean": Feature({"server.aggregator": "trimmed_mean"},
                                   "coordinate-wise trimmed mean"),
    "robust_krum": Feature({"server.aggregator": "krum",
                            "server.krum_byzantine": 1},
                           "whole-update krum selection"),
    "compression_topk": Feature({"server.compression": "topk"},
                                "sparse top-k uplink compression"),
    "compression_qsgd": Feature({"server.compression": "qsgd"},
                                "dense unbiased quantization"),
    "error_feedback": Feature({"server.compression": "qsgd",
                               "server.error_feedback": True},
                              "EF-SGD residual memory (needs a "
                              "compressor; qsgd is the canonical pick)"),
    "downlink_qsgd": Feature({"server.downlink_compression": "qsgd"},
                             "broadcast quantization"),
    "client_ledger": Feature({"run.obs.client_ledger.enabled": True},
                             "per-client forensic ledger"),
    "paged_ledger": Feature({"run.obs.client_ledger.enabled": True,
                             "run.obs.client_ledger.hot_capacity": 8},
                            "hot/cold paged ledger store "
                            "(paging is driver-level, not engine-level)"),
    "reputation": Feature({"run.obs.client_ledger.enabled": True,
                           "server.reputation.enabled": True},
                          "ledger-driven trust weighting"),
    "sampling_weighted": Feature({"server.sampling": "weighted"},
                                 "size-proportional cohort draw"),
    "sampling_poisson": Feature({"server.sampling": "poisson"},
                                "Poisson subsampling (exact DP q)"),
    "sampling_adaptive": Feature({"server.sampling": "adaptive",
                                  "run.obs.client_ledger.enabled": True,
                                  "run.obs.client_ledger.log_every": 1},
                                 "Oort-style utility-aware draw "
                                 "(needs periodic ledger snapshots)"),
    "sampling_streaming_ledger": Feature(
        {"server.sampling": "streaming",
         "run.obs.client_ledger.enabled": True,
         "run.obs.client_ledger.log_every": 1},
        "million-client streaming draw with ledger-fed sketch"),
    "fuse_rounds": Feature({"run.fuse_rounds": 2},
                           "multi-round fused scan"),
    "shape_buckets": Feature({"run.shape_buckets.enabled": True},
                             "cohort-shaped step ladder"),
    "megabatch": Feature({"run.cohort_layout": "megabatch"},
                         "cohort axis collapsed into the GEMM batch"),
    "fused_apply": Feature({"server.fused_apply": True},
                           "pallas fused server-apply kernel"),
    "stragglers": Feature({"server.straggler_rate": 0.5},
                          "partial-work straggler simulation"),
    "churn": Feature({"run.churn.enabled": True,
                      "run.churn.dropout_hazard": 0.1,
                      "run.churn.crash_rate": 0.1},
                     "seed-pure diurnal availability / dropout hazard / "
                     "crash-mid-round model (driver + sampler level; "
                     "never reaches the engine)"),
    "batch_shards": Feature({"run.batch_shards": 2},
                            "intra-client batch mesh axis"),
    "stream_placement": Feature({"data.placement": "stream"},
                                "O(cohort) host-RAM slab path"),
    "client_store": Feature({"data.store.dir": "<store>"},
                            "on-disk mmap client store (dir is a "
                            "validate-level sentinel; existence is "
                            "checked at construction)"),
    "store_gather_pool": Feature({"data.store.dir": "<store>",
                                  "data.store.gather_workers": 4},
                                 "sharded parallel gather pool: rows "
                                 "split by owning shard, per-shard "
                                 "copies on a shared worker pool — "
                                 "bitwise row order at every worker "
                                 "count (data level; the engine never "
                                 "sees it)"),
    "native_pipeline": Feature({"run.host_pipeline": "native"},
                               "C++ threaded host pipeline"),
    "lora": Feature({"model.name": "bert_tiny", "model.num_classes": 0,
                     "model.kwargs": {"vocab_size": 32, "seq_len": 8},
                     "model.lora.enabled": True, "model.lora.rank": 2},
                    "adapter-plane uploads (params ARE the "
                    "adapters; engine-transparent by construction)"),
    "hierarchy": Feature({"server.hierarchy.num_edges": 2},
                         "two-tier edge/core federation (the engine "
                         "reused recursively, one tier down)"),
    "multi_version": Feature({"algorithm": "fedbuff",
                              "server.async_versions": 2},
                             "concurrent model versions, one async "
                             "buffer each (fedbuff scheduler level)"),
    "churn_trace": Feature({"run.churn.enabled": True,
                            "run.churn.trace": "<trace>"},
                           "trace-replay availability (recorded on/off "
                           "bitmap; dir is a validate-level sentinel, "
                           "existence checked at model construction)"),
    "digest": Feature({"run.obs.digest.enabled": True},
                      "determinism flight recorder (driver-level digest "
                      "of fetched state; never reaches the engine)"),
    "control_plane_device": Feature(
        {"run.control_plane": "device"},
        "device-resident control plane (server/device_plane.py): "
        "cohort/churn/slab derivation lowered into the round program; "
        "driver-level — the engines run unchanged under the wrapper"),
    "executables": Feature(
        {"run.obs.executables": True},
        "compiled-program observatory (obs/executables.py): AOT "
        "lower/compile registry harvesting XLA cost/memory analysis, "
        "HBM watermarks and retrace forensics; observational like "
        "digest — the lowering is the one jit would produce, params "
        "are bitwise identical with it off"),
}


def base_config() -> ExperimentConfig:
    """The probe base every feature overlays: a small valid federation
    sized so every catalog feature can turn on (krum's Blanchard bound,
    paged-ledger capacity, fuse divisibility...)."""
    cfg = ExperimentConfig()
    cfg.name = "capability_probe"
    cfg.data.num_clients = 16
    cfg.data.synthetic_train_size = 256
    cfg.data.synthetic_test_size = 64
    cfg.server.cohort_size = 8
    cfg.server.num_rounds = 8
    cfg.server.eval_every = 2
    return cfg


def _merge(a: Dict[str, Any], b: Dict[str, Any]
           ) -> Optional[Dict[str, Any]]:
    """Union of two override sets; None when they set the same knob to
    different values (the pairing is ill-posed, not rejected)."""
    out = dict(a)
    for k, v in b.items():
        if k in out and out[k] != v:
            return None
        out[k] = v
    return out


def _validate_verdict(overrides: Dict[str, Any]) -> Optional[str]:
    """None when ``validate()`` accepts the base config under
    ``overrides``, else the reason it gives."""
    cfg = base_config()
    cfg.apply_overrides(dict(overrides))
    try:
        cfg.validate()
        return None
    except ValueError as e:
        return str(e.args[0]) if e.args else ""


def feature_verdicts(name: str) -> Tuple[Optional[str], Dict[str, str]]:
    """What ``validate()`` says of one feature: its verdict alone (None
    = accepted, as every catalog feature must be), and ``{partner:
    reason}`` for every well-posed pairing it refuses."""
    mine = FEATURES[name].overrides
    refused: Dict[str, str] = {}
    for other in sorted(FEATURES):
        if other == name:
            continue
        merged = _merge(mine, FEATURES[other].overrides)
        if merged is None:
            continue
        reason = _validate_verdict(merged)
        if reason is not None:
            refused[other] = reason
    return _validate_verdict(mine), refused


def extract_matrix() -> Dict[str, Any]:
    """Build the matrix: every singleton + every non-conflicting
    pairing through ``validate()``; the rejected ones with reasons."""
    names = sorted(FEATURES)
    rejected: Dict[str, str] = {}
    for a in names:
        alone, refused = feature_verdicts(a)
        if alone is not None:
            raise ValueError(
                f"capability catalog is broken: singleton {a!r} does "
                f"not validate: {alone}"
            )
        rejected.update(
            {f"{a}+{b}": reason for b, reason in refused.items() if a < b}
        )
    pairs = list(itertools.combinations(names, 2))
    n_pairs = sum(
        _merge(FEATURES[a].overrides, FEATURES[b].overrides) is not None
        for a, b in pairs
    )
    skipped = len(pairs) - n_pairs
    return {
        "version": MATRIX_VERSION,
        "base": "16 clients / cohort 8 / 8 rounds / eval_every 2 "
                "(capability.base_config)",
        "features": {
            n: {"overrides": FEATURES[n].overrides,
                "note": FEATURES[n].note}
            for n in names
        },
        "counts": {
            "features": len(names),
            "pairs": n_pairs,
            "supported": n_pairs - len(rejected),
            "rejected": len(rejected),
            "skipped_conflicts": skipped,
        },
        "rejected": rejected,
    }


def matrix_path(root: str) -> str:
    return os.path.join(root, MATRIX_FILENAME)


def load_matrix(root: str) -> Dict[str, Any]:
    with open(matrix_path(root)) as f:
        return json.load(f)


def write_matrix(root: str, matrix: Optional[Dict[str, Any]] = None) -> str:
    matrix = matrix or extract_matrix()
    path = matrix_path(root)
    with open(path, "w") as f:
        json.dump(matrix, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def check_capability(root: str) -> Dict[str, Any]:
    """The `colearn check` entry: extract, refuse reason-less
    rejections, and diff against the checked-in artifact."""
    matrix = extract_matrix()
    violations: List[Dict[str, Any]] = []
    for pair, reason in matrix["rejected"].items():
        if not reason.strip():
            violations.append({
                "kind": "rejection_without_reason", "where": pair,
                "message": f"pairing {pair} is rejected with an "
                           f"empty reason string",
            })
    if not os.path.isfile(matrix_path(root)):
        violations.append({
            "kind": "matrix_missing", "where": MATRIX_FILENAME,
            "message": f"checked-in {MATRIX_FILENAME} is missing — run "
                       f"`colearn check --update-matrix`",
        })
    else:
        committed = load_matrix(root)
        if committed != matrix:
            changed = _diff_pairs(committed, matrix)
            violations.append({
                "kind": "matrix_drift", "where": MATRIX_FILENAME,
                "message": (
                    f"checked-in {MATRIX_FILENAME} disagrees with "
                    f"validate() ({len(changed)} pairing(s) changed: "
                    f"{', '.join(changed[:5])}"
                    f"{'...' if len(changed) > 5 else ''}) — run "
                    f"`colearn check --update-matrix` and review the diff"
                ),
            })
    return {
        "matrix": matrix,
        "counts": matrix["counts"],
        "violations": violations,
    }


def _diff_pairs(old: Dict[str, Any], new: Dict[str, Any]) -> List[str]:
    """Names of what differs: pairings whose verdict or reason changed,
    then features whose catalog entry changed."""
    changed = []
    for key in ("rejected", "features"):
        o, n = old.get(key, {}), new.get(key, {})
        changed += sorted(k for k in set(o) | set(n) if o.get(k) != n.get(k))
    return changed or ["<metadata>"]
