"""Per-phase FLOP/HBM-byte cost model, MFU waterfall, and the bench
regression observatory.

The headline bench emits ONE number (``mfu_pct``) and ROADMAP item 2
asks where the other ~59% goes. This module turns that scalar into an
attributable breakdown using the same discipline as the wire-byte
counters (obs/counters.py): an **analytic cost model** — a pure
function of the config and the round's realized shapes, never a
measurement — joined with measured span timings. Purity is what makes
the sharded and sequential engines agree bit-for-bit on every
``phase_cost`` record (pinned by ``tests/test_roofline.py``), and what
lets ``colearn mfu`` decompose a finished run from its JSONL alone.

Three layers, all pure stdlib (the CLI imports this before any jax
backend initialization):

1. **Cost model** — :func:`round_phase_costs`: analytic FLOPs and
   HBM bytes moved per round-program stage (local train fwd/bwd,
   attack transform, aggregation, server apply incl. the Pallas fused
   path, ledger stats). The local-train FLOP count reuses the bench's
   ``model_tflops_per_round`` machinery: either XLA's cost analysis of
   one scan-free train step (``run.obs.phase_cost_flops="xla"``) or
   the dense 6·P·B approximation (default — no extra compile).
2. **Waterfall** — :func:`waterfall`: headline MFU decomposed into
   effective compute, padding loss (``padded_step_fraction`` dead
   steps: executed at full cost in the spatial layout; the megabatch
   block trainer skips those of a group whose every client is dead,
   ``obs/counters.block_step_counts``, which this term does not read
   yet), non-matmul compute (the cost model's non-train phases at
   roofline speed), host-exposed time (spans not hidden under
   ``round.dispatch``), and residual kernel inefficiency. The
   components sum to 100% of wall time within
   :data:`WATERFALL_TOL_PCT` — the waterfall identity — and
   ``effective + padding == headline`` by the same tolerance.
3. **Observatory** — :func:`load_bench_history` /
   :func:`bench_report`: the ``BENCH_r*.json`` trajectory with
   per-phase deltas vs best-so-far and budget gates from a checked-in
   baseline file (``BENCH_BUDGETS.json``), generalizing bench.py's
   scalar device-ms ``_gate`` to per-phase budgets so the next plateau
   is localized to a phase the moment it appears. Historical entries
   that predate a field render ``n/a`` — never a KeyError.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
from typing import Any, Dict, List, Optional, Sequence

# ---------------------------------------------------------------------------
# peaks (single source of truth — bench.py imports these)
# ---------------------------------------------------------------------------

# The chip the constants below describe, as jax names it
# (``jax.local_devices()[0].device_kind``). Every record that carries a
# peak also carries the ``device_kind`` that actually ran, so a number
# divided by these peaks on any other device is visible as such;
# ``chip_smoke.py`` fails when the two differ. (The table keyed by
# device_kind is ROADMAP S1(c).)
PEAK_DEVICE_KIND = "TPU v5 lite"
# Dense bf16 peak of one TPU v5e (v5 lite) chip; MFU = achieved / peak.
PEAK_BF16_FLOPS = 197e12
# The MXU retires f32 products at no better than half the bf16 rate, so
# bf16/2 is the conventional (and still optimistic) stand-in for the
# unpublished v5e f32 peak. `mfu_basis` records which denominator
# produced every number — a bf16 measurement silently compared against
# an f32 peak is the exact hygiene failure the basis exists to stop.
PEAK_F32_FLOPS = PEAK_BF16_FLOPS / 2
# HBM bandwidth of one v5e chip — the roof the memory-bound phases hit.
PEAK_HBM_BYTES_PER_SEC = 819e9

# Waterfall identity tolerance, in MFU percentage points: the
# components are computed from three independent record streams
# (analytic phase costs, measured spans, measured rounds/sec), so the
# identity holds only up to their rounding (record fields are rounded
# to 3-4 decimals at log time).
WATERFALL_TOL_PCT = 0.5

# The cost-model phase taxonomy, in round-program order. Matches the
# engines' jax.named_scope annotations (round_local_train,
# round_attack_transform, round_aggregate, round_server_apply /
# round_fused_reduce_apply, round_client_ledger) so device profiles
# join with the analytic model by name.
PHASES = (
    "local_train",
    "attack_transform",
    "aggregation",
    "server_apply",
    "ledger_stats",
)

# Span phases that do NOT count as host-exposed time: `round` is the
# parent bracket, `round.dispatch` is where async device execution is
# buried, and `compile` fires INSIDE the dispatch call that triggered
# it (counting it again would double-book that wall). The executable
# registry's own spans (`obs.executables` AOT lower+compile and
# `obs.preflight`) bracket compile work the `compile` listener already
# books — counting them would charge each compilation twice.
# `round.run` is the bracket of one dispatch call (its children are
# counted by name), `round.prefetch` the worker's bracket, which runs
# beside the main thread, and `round.device_wait` is the host waiting
# for a device that is busy: none of it is exposed. Every other span
# (host_inputs, placement, fetch, eval, checkpoint, stream_slab, ...)
# is host time the device sits idle through.
_NON_HOST_EXPOSED_SPANS = ("round", "round.run", "round.prefetch",
                           "round.dispatch", "round.device_wait",
                           "compile", "obs.executables", "obs.preflight")

# Attribution sub-spans nested INSIDE an already-counted host span: the
# parent's bracket (`round.host_inputs`) contains their wall time, so
# summing both would double-book the host wall. They exist so `colearn
# mfu` can split the host-exposed line into named control-plane
# sub-lines (sampler / churn / slot-assign / slab-build), not to add
# to the total.
_SUBSPAN_PREFIXES = ("round.host_inputs.",)

# Set-up (`setup.*`, `init.*`: Experiment.__init__, init_state,
# _place_state, before the first round's wall time begins) and the
# registry's `compile.lower` / `compile.backend` inside
# `obs.executables`: the first `spans` record of a fit carries them,
# and none of it is a round's host time.
_SETUP_PREFIXES = ("setup.", "init.", "compile.")


def _is_host_exposed(name: str) -> bool:
    return (name not in _NON_HOST_EXPOSED_SPANS
            and not name.startswith(_SUBSPAN_PREFIXES + _SETUP_PREFIXES))


# Byte-model pass counts (documented constants, not magic numbers):
# local train touches the params 4× per step (fwd read, bwd read, grad
# write, local-SGD update write) — activation traffic is workload-
# dependent and excluded, so local-train bytes are a floor (harmless:
# the phase is compute-bound by orders of magnitude anyway).
LOCAL_TRAIN_PARAM_PASSES = 4
# Unfused server apply is a chain of separate XLA ops (trust/weight
# scale → reduction output materialized → delta apply → optimizer),
# each re-reading its operands from HBM: read delta, read params, read
# momentum, write momentum, write params, plus the materialized
# intermediate — 6 params-sized passes.
SERVER_APPLY_PASSES_UNFUSED = 6
# The Pallas fused path (ops/pallas_apply.py) runs the same chain as
# ONE VMEM-resident pass: read params + momentum, write params +
# momentum — 4 passes, and the mean-delta intermediate (1 write + 1
# re-read in `aggregation`) never touches HBM at all.
SERVER_APPLY_PASSES_FUSED = 4


def mfu_basis(compute_dtype: str, local_param_dtype: Optional[str],
              param_dtype: str) -> tuple:
    """(basis name, peak FLOP/s) from the effective compute precision:
    the matmuls run bf16 when either the model compute dtype or the
    effective local-param dtype is bfloat16. Pure so bench.py and the
    driver's ``phase_cost_model`` record derive the identical basis."""
    eff_local = local_param_dtype or param_dtype
    if "bfloat16" in (compute_dtype, eff_local):
        return "bf16_peak", PEAK_BF16_FLOPS
    return "f32_peak", PEAK_F32_FLOPS


def peak_for_basis(basis: str) -> float:
    return PEAK_BF16_FLOPS if basis == "bf16_peak" else PEAK_F32_FLOPS


def analytic_step_flops(n_coords: int, batch_units: int) -> int:
    """Dense fwd+bwd FLOPs of one train step: 2·P per unit forward,
    2× that backward — the standard 6·P·B approximation. ``batch_units``
    is examples × tokens-per-example for sequence models. Under-counts
    convolutional re-use (a conv layer applies its kernel per spatial
    position); the XLA-counted alternative (``phase_cost_flops="xla"``)
    is exact but costs one extra compile per run."""
    return 6 * int(n_coords) * int(batch_units)


def analytic_lora_step_flops(full_coords: int, adapter_coords: int,
                             batch_units: int) -> int:
    """Adapter-step FLOPs under a frozen LoRA base (``model.lora``):
    the forward and the backward's activation-gradient chain still
    traverse the FULL merged model (2·P_full·B each — gradients must
    propagate through frozen layers to reach earlier adapters), but
    weight-gradient contractions exist only for the trainable factors
    (2·P_adapter·B). Total ``4·P_full·B + 2·P_adapter·B`` — vs full
    training's ``6·P_full·B`` and vs the naive adapter-only count
    ``6·P_adapter·B``, which understates a LoRA step by ~P_full/P_adapter.
    Modeling either endpoint would mis-attribute the MFU waterfall for
    every adapter config; this is the honest middle the frozen-base
    structure actually executes."""
    return (4 * int(full_coords) + 2 * int(adapter_coords)) * int(batch_units)


# ---------------------------------------------------------------------------
# cohort-layout GEMM geometry (run.cohort_layout)
# ---------------------------------------------------------------------------

# The MXU retires 128×128 tiles; a GEMM whose row count (the activation/
# batch dim, M) is not a tile multiple pads the last tile with dead rows.
MXU_TILE_ROWS = 128

COHORT_LAYOUTS = ("spatial", "megabatch")


def layout_gemm_rows(cohort_layout: str, clients_per_lane: int,
                     batch: int, lora_all_steps: bool = False) -> int:
    """The M rows a shared-weight train-step GEMM feeds the MXU under a
    cohort layout. ``spatial`` trains clients as separate (or batched)
    per-client GEMMs — batched dot dimensions do NOT merge into M, so
    every GEMM's rows are ONE client's batch regardless of
    ``client_vmap_width``; that cap is exactly why the layout, not the
    width, is the structural lever. ``megabatch`` flattens the lane's
    whole client chunk into the row axis: M = K_local·batch.

    ``lora_all_steps``: megabatch × frozen-base LoRA via the decomposed
    apply (models/lora.py ``apply_decomposed``). The row count is the
    same M = K_local·batch, but its COVERAGE changes: without the flag
    the un-batched-weight GEMMs exist only in the shared-weight step-0
    phase (params diverge from step 1 and every base GEMM re-batches);
    with it the frozen base contracts the flattened megabatch in EVERY
    local step — only the rank-r adapter factors batch. Spatial has no
    decomposed path, so the pairing is rejected rather than silently
    annotated."""
    if cohort_layout not in COHORT_LAYOUTS:
        raise ValueError(
            f"unknown cohort_layout {cohort_layout!r}; "
            f"allowed: {', '.join(COHORT_LAYOUTS)}"
        )
    if lora_all_steps and cohort_layout != "megabatch":
        raise ValueError(
            "lora_all_steps GEMM geometry exists only under "
            "cohort_layout='megabatch' (the decomposed LoRA apply is a "
            "megabatch-layout optimization)"
        )
    if cohort_layout == "megabatch":
        return int(clients_per_lane) * int(batch)
    return int(batch)


def mxu_tile_pad_fraction(gemm_rows: int, tile: int = MXU_TILE_ROWS) -> float:
    """Fraction of the MXU's row-tile slots wasted on padding when a
    GEMM with ``gemm_rows`` rows is tiled: ``1 − rows/(⌈rows/tile⌉·tile)``.
    Batch 32 under the spatial layout wastes 0.75 of every row tile;
    a 16-client megabatch at the same batch (512 rows) wastes 0.0 —
    the tile-level attribution of the layout's MFU win (`colearn mfu`
    prints it next to the waterfall)."""
    rows = int(gemm_rows)
    if rows <= 0:
        raise ValueError(f"gemm_rows must be > 0, got {gemm_rows}")
    tiles = -(-rows // int(tile))
    return 1.0 - rows / float(tiles * int(tile))


# ---------------------------------------------------------------------------
# the analytic per-phase cost model
# ---------------------------------------------------------------------------


def round_phase_costs(*, k: int, steps: int, batch: int, n_coords: int,
                      compute_bytes: int, step_flops: int,
                      aggregator: str = "weighted_mean",
                      attack: bool = False, ledger: bool = False,
                      reputation: bool = False,
                      fused_apply: bool = False,
                      host_input_bytes: int = 0) -> Dict[str, Dict[str, int]]:
    """Analytic FLOPs + HBM bytes per round-program stage for one
    centralized round on the **padded** ``steps × batch`` grid (the
    same grid headline MFU counts — padding waste is attributed by the
    waterfall, not hidden here).

    Same honesty contract as :func:`~colearn_federated_learning_tpu.
    obs.counters.round_comm_bytes`: these are the FLOPs/bytes the
    configured round program WOULD execute/move — a pure function of
    the config and the realized grid, identical across the sharded,
    sequential, and fused engines by construction.

    Only phases the config actually runs appear in the result. Wire
    stacks and aggregation intermediates are f32 (4 B); server params/
    momentum are f32 master; local-train compute traffic moves at
    ``compute_bytes`` (2 under bf16 compute).
    """
    k, steps, batch = int(k), int(steps), int(batch)
    n, cb = int(n_coords), int(compute_bytes)
    out: Dict[str, Dict[str, int]] = {}

    # local train: the matmul phase. step_flops is fwd+bwd of ONE batch.
    out["local_train"] = {
        "flops": int(step_flops) * steps * k,
        "bytes": (steps * k * LOCAL_TRAIN_PARAM_PASSES * n * cb
                  + int(host_input_bytes)),
    }

    if attack:
        # elementwise transform over the [K, n] wire stack (sign flip /
        # scale / noise add): 2 flops/coord, read + write at f32
        out["attack_transform"] = {
            "flops": 2 * k * n,
            "bytes": 2 * k * n * 4,
        }

    if aggregator == "krum":
        # pairwise squared distances over the stack: K(K-1)/2 ordered
        # pairs × (sub, mul, add)/coord; each pair reads two vectors
        pairs = k * (k - 1) // 2
        agg_flops = 3 * pairs * n
        agg_bytes = 2 * pairs * n * 4
        # + the winner's delta materialized (one-hot weighted reduce)
        agg_flops += 2 * k * n
        agg_bytes += k * n * 4
    elif aggregator in ("median", "trimmed_mean"):
        # coordinate-wise sort network over K values: ~K·ceil(log2 K)
        # compare-exchanges per coordinate, stack read + sorted write
        agg_flops = k * max(1, math.ceil(math.log2(max(k, 2)))) * n
        agg_bytes = 2 * k * n * 4
    else:  # weighted_mean
        # multiply-accumulate over the stack (or the psum-equivalent)
        agg_flops = 2 * k * n
        agg_bytes = k * n * 4
    if reputation:
        # trust enters as one extra multiply per stack coordinate
        agg_flops += k * n
    if not (fused_apply and aggregator in ("weighted_mean", "krum")):
        # the mean delta materializes to HBM and server_apply re-reads
        # it; under the fused Pallas path the reduction output stays in
        # VMEM, so these two passes are exactly the fused saving
        agg_bytes += 2 * n * 4
    out["aggregation"] = {"flops": agg_flops, "bytes": agg_bytes}

    # server apply: delta scale + momentum update + param apply —
    # elementwise over the f32 master params
    fused = fused_apply and aggregator in ("weighted_mean", "krum")
    passes = (SERVER_APPLY_PASSES_FUSED if fused
              else SERVER_APPLY_PASSES_UNFUSED)
    out["server_apply"] = {
        "flops": 4 * n,
        "bytes": passes * n * 4,
    }

    if ledger:
        # per-client stats over the wire stack (obs/ledger.py): L2 norm
        # (2·n), dot with the mean delta (2·n), residual norm (2·n) per
        # client; the stack is re-read once and the mean delta K times
        # in principle but streams — counted once per client
        out["ledger_stats"] = {
            "flops": 6 * k * n,
            "bytes": 2 * k * n * 4,
        }
    return out


def phase_time_s(cost: Dict[str, int], peak_flops: float,
                 peak_bw: float = PEAK_HBM_BYTES_PER_SEC) -> float:
    """Roofline execution-time floor of one phase: whichever roof —
    compute or memory — binds."""
    return max(cost["flops"] / peak_flops, cost["bytes"] / peak_bw)


def classify_phase(cost: Dict[str, int], peak_flops: float,
                   peak_bw: float = PEAK_HBM_BYTES_PER_SEC) -> str:
    """``compute`` vs ``memory`` bound: arithmetic intensity
    (flops/byte) against the ridge point of the configured roofline."""
    if cost["bytes"] <= 0:
        return "compute"
    ridge = peak_flops / peak_bw
    return "compute" if cost["flops"] / cost["bytes"] >= ridge else "memory"


# ---------------------------------------------------------------------------
# the MFU waterfall
# ---------------------------------------------------------------------------

WATERFALL_COMPONENTS = (
    "effective_compute",
    "padding",
    "non_matmul",
    "host_exposed",
    "residual",
)


def waterfall(phase_costs: Dict[str, Dict[str, int]],
              rounds_per_sec: float, peak_flops: float, n_chips: int = 1,
              padded_step_fraction: float = 0.0,
              host_exposed_ms_per_round: float = 0.0,
              peak_bw: float = PEAK_HBM_BYTES_PER_SEC) -> Dict[str, Any]:
    """Decompose headline MFU into the waterfall components, each in
    percent of wall time (so they sum to 100).

    - ``headline_mfu_pct`` — the bench's number: padded-grid local-
      train FLOPs × rounds/sec ÷ peak.
    - ``effective_compute`` + ``padding`` — the headline split by
      ``padded_step_fraction``: the grid's dead scan steps, each at a
      full step's FLOPs. That is what the spatial layout executes; the
      megabatch block trainer runs a group's step under a conditional
      and skips it where the whole group is dead
      (``client/trainer.py`` ``_block_steps``), so there the term
      overstates what was burnt by ``skipped_steps`` of
      ``obs/counters.block_step_counts``.
    - ``non_matmul`` — the cost model's non-train phases at roofline
      speed (each phase's max(compute, memory) floor).
    - ``host_exposed`` — measured span time NOT hidden under
      ``round.dispatch`` (host inputs, placement, fetch, eval,
      checkpoint, compile), per round.
    - ``residual`` — whatever wall time remains: kernel inefficiency,
      pipeline bubbles, and every un-modeled stall. Negative residual
      beyond :data:`WATERFALL_TOL_PCT` means the model over-accounts
      the measured wall and is surfaced, never clamped away.
    """
    if rounds_per_sec <= 0:
        raise ValueError("rounds_per_sec must be > 0 for a waterfall")
    wall_s = 1.0 / rounds_per_sec
    chips = max(1, int(n_chips))
    train_flops = phase_costs.get("local_train", {}).get("flops", 0)
    headline = 100.0 * train_flops / (wall_s * peak_flops * chips)
    padding = headline * float(padded_step_fraction)
    effective = headline - padding
    non_matmul_s = sum(
        phase_time_s(c, peak_flops, peak_bw) / chips
        for name, c in phase_costs.items() if name != "local_train"
    )
    non_matmul = 100.0 * non_matmul_s / wall_s
    host = 100.0 * (host_exposed_ms_per_round / 1000.0) / wall_s
    residual = 100.0 - headline - non_matmul - host
    return {
        "headline_mfu_pct": headline,
        "components": {
            "effective_compute": effective,
            "padding": padding,
            "non_matmul": non_matmul,
            "host_exposed": host,
            "residual": residual,
        },
        "wall_ms_per_round": wall_s * 1000.0,
    }


def check_waterfall_identity(wf: Dict[str, Any],
                             tol: float = WATERFALL_TOL_PCT) -> List[str]:
    """The documented identity, as violations (empty = holds):
    components sum to 100% of wall, effective + padding reconstructs
    the headline, and no component over-accounts (residual may be
    negative only within tolerance)."""
    comp = wf["components"]
    problems = []
    total = sum(comp[k] for k in WATERFALL_COMPONENTS)
    if abs(total - 100.0) > tol:
        problems.append(f"components sum to {total:.3f}%, not 100%")
    if abs(comp["effective_compute"] + comp["padding"]
           - wf["headline_mfu_pct"]) > tol:
        problems.append("effective + padding != headline MFU")
    if comp["residual"] < -tol:
        problems.append(
            f"residual {comp['residual']:.3f}% < 0: the analytic model "
            f"over-accounts the measured wall time"
        )
    return problems


# ---------------------------------------------------------------------------
# `colearn mfu <run>` — the report over a run's JSONL records
# ---------------------------------------------------------------------------


def mfu_report(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Join a run's ``phase_cost_model`` / ``phase_cost`` / ``spans`` /
    round records into the waterfall + roofline report. Raises
    ValueError with an actionable message when the run predates the
    observatory (no phase_cost records)."""
    model = None
    costs_sum: Dict[str, Dict[str, float]] = {}
    costs_n = 0
    span_ms: Dict[str, float] = {}
    rps: List[float] = []
    padded: List[float] = []
    rounds = 0
    exec_recs: Dict[str, Dict[str, Any]] = {}
    for rec in records:
        ev = rec.get("event")
        if ev == "phase_cost_model":
            model = rec
        elif ev == "executable_compiled":
            # the registry's HLO-derived truth (latest compile per
            # program wins — retraces refresh the measured flops);
            # preflight compiles are abstract rehearsals, not the run
            if not rec.get("preflight"):
                exec_recs[str(rec.get("name"))] = rec
        elif ev == "phase_cost":
            costs_n += 1
            for name, c in (rec.get("phases") or {}).items():
                cur = costs_sum.setdefault(name, {"flops": 0.0, "bytes": 0.0})
                cur["flops"] += float(c.get("flops", 0))
                cur["bytes"] += float(c.get("bytes", 0))
        elif ev == "spans":
            for name, agg in (rec.get("phases") or {}).items():
                span_ms[name] = span_ms.get(name, 0.0) + float(
                    agg.get("total_ms", 0.0)
                )
        elif ev is None and "round" in rec:
            rounds = max(rounds, int(rec["round"]))
            if "rounds_per_sec" in rec:
                rps.append(float(rec["rounds_per_sec"]))
            if "padded_step_fraction" in rec:
                padded.append(float(rec["padded_step_fraction"]))
    if model is None or not costs_n:
        raise ValueError(
            "no phase_cost records in this log (run.obs.phase_cost was "
            "off, or the run predates the performance observatory)"
        )
    if not rps:
        raise ValueError(
            "no rounds_per_sec in this log (no completed flush window) "
            "— cannot anchor the waterfall to wall time"
        )
    # mean analytic cost per round (varies only with bucket rungs /
    # realized participation)
    costs = {
        name: {"flops": int(c["flops"] / costs_n),
               "bytes": int(c["bytes"] / costs_n)}
        for name, c in costs_sum.items()
    }
    peak = float(model.get("peak_flops") or
                 peak_for_basis(model.get("mfu_basis", "bf16_peak")))
    peak_bw = float(model.get("peak_hbm_bytes_per_sec")
                    or PEAK_HBM_BYTES_PER_SEC)
    n_chips = int(model.get("n_chips", 1))
    host_ms = sum(
        ms for name, ms in span_ms.items()
        if _is_host_exposed(name)
    ) / max(1, rounds)
    # control-plane attribution: the named children of the host-input
    # span (sampler / churn / slot-assign / slab-build), per round —
    # excluded from the host_exposed SUM above (their parent bracket
    # already holds their wall), surfaced here as waterfall sub-lines
    host_sub_ms = {}
    for name in sorted(span_ms):
        for pref in _SUBSPAN_PREFIXES:
            if name.startswith(pref):
                host_sub_ms[name[len(pref):]] = (
                    span_ms[name] / max(1, rounds)
                )
    # measured-vs-analytic drift: the XLA cost_analysis flops of the
    # dominant round program (per round — fused programs carry
    # rounds_per_call) against the analytic model's per-round total.
    # A pre-PR-20 log has no executable_compiled records: the section
    # is None and every consumer renders n/a, never a KeyError.
    analytic_round = sum(c["flops"] for c in costs.values())
    round_progs: Dict[str, float] = {}
    for name, rec in exec_recs.items():
        fl = rec.get("flops")
        if fl is None or not name.startswith("round."):
            continue
        per_call = max(1, int(rec.get("rounds_per_call") or 1))
        round_progs[name] = float(fl) / per_call
    measured = None
    if round_progs:
        prog = max(round_progs, key=lambda n: round_progs[n])
        m_flops = round_progs[prog]
        measured = {
            "programs": {n: round_progs[n] for n in sorted(round_progs)},
            "round_program": prog,
            "round_flops_measured": m_flops,
            "round_flops_analytic": float(analytic_round),
            "flop_model_drift_pct": (
                100.0 * (m_flops - analytic_round) / analytic_round
                if analytic_round else None
            ),
        }
    rps_mean = sum(rps) / len(rps)
    wf = waterfall(
        costs, rps_mean, peak, n_chips=n_chips,
        padded_step_fraction=(sum(padded) / len(padded)) if padded else 0.0,
        host_exposed_ms_per_round=host_ms, peak_bw=peak_bw,
    )
    roofline = {
        name: {
            **costs[name],
            # None (not inf) when the phase moves no modeled bytes, so
            # the --json output stays strict JSON
            "intensity": (costs[name]["flops"] / costs[name]["bytes"]
                          if costs[name]["bytes"] else None),
            "bound": classify_phase(costs[name], peak, peak_bw),
            "time_us_at_peak": phase_time_s(costs[name], peak, peak_bw)
            / max(1, n_chips) * 1e6,
        }
        for name in PHASES if name in costs
    }
    return {
        "rounds": rounds,
        "rounds_per_sec": rps_mean,
        "mfu_basis": model.get("mfu_basis", "n/a"),
        "flop_source": model.get("flop_source", "n/a"),
        "peak_tflops": peak / 1e12,
        "peak_hbm_gbs": peak_bw / 1e9,
        "n_chips": n_chips,
        "waterfall": wf,
        "identity_violations": check_waterfall_identity(wf),
        "roofline": roofline,
        "host_exposed_ms_per_round": host_ms,
        "host_exposed_sub_ms_per_round": host_sub_ms,
        "measured": measured,
        # cohort-layout attribution (runs predating the layout fields
        # render n/a — never a KeyError)
        "layout": {
            "cohort_layout": model.get("cohort_layout"),
            "clients_per_lane": model.get("clients_per_lane"),
            "gemm_rows": model.get("gemm_rows"),
            "lora_all_steps": model.get("lora_all_steps"),
            "mxu_tile_pad_fraction": model.get("mxu_tile_pad_fraction"),
        },
    }


_WF_LABELS = {
    "effective_compute": "effective compute",
    "padding": "padding (dead steps)",
    "non_matmul": "non-matmul compute",
    "host_exposed": "host-exposed time",
    "residual": "residual kernel inefficiency",
}


def format_mfu_report(report: Dict[str, Any], path: str = "") -> str:
    wf = report["waterfall"]
    lines = []
    head = f"run: {path}" if path else "mfu report"
    lines.append(
        f"{head}  rounds: {report['rounds']}  "
        f"wall/round: {wf['wall_ms_per_round']:.1f} ms  "
        f"basis: {report['mfu_basis']} "
        f"({report['peak_tflops']:.1f} TF/s, "
        f"{report['peak_hbm_gbs']:.0f} GB/s HBM, "
        f"{report['n_chips']} chip(s), {report['flop_source']} flops)"
    )
    lines.append(f"headline MFU: {wf['headline_mfu_pct']:.2f}%")
    lay = report.get("layout") or {}
    if lay.get("cohort_layout"):
        pad = lay.get("mxu_tile_pad_fraction")
        rows_note = (
            " all steps (lora decomposed)" if lay.get("lora_all_steps")
            else ""
        )
        lines.append(
            f"cohort layout: {lay['cohort_layout']}  "
            f"(K_local {_na(lay.get('clients_per_lane'))}, "
            f"gemm rows {_na(lay.get('gemm_rows'))}{rows_note}, "
            f"mxu row-tile padding "
            f"{_na(None if pad is None else 100.0 * pad, '{:.1f}%')})"
        )
    lines.append("")
    lines.append(f"waterfall (% of wall time, sums to 100 "
                 f"± {WATERFALL_TOL_PCT}):")
    subs = report.get("host_exposed_sub_ms_per_round") or {}
    wall_ms = wf["wall_ms_per_round"]
    for name in WATERFALL_COMPONENTS:
        lines.append(
            f"  {_WF_LABELS[name]:<30}{wf['components'][name]:>8.2f}%"
        )
        if name == "host_exposed" and subs:
            # control-plane split of the line above (span children of
            # round.host_inputs — attribution, not additional time)
            for sub in sorted(subs):
                pct = (100.0 * (subs[sub] / wall_ms)) if wall_ms else 0.0
                lines.append(
                    f"    · {sub:<26}{pct:>8.2f}%"
                    f"  ({subs[sub]:.3f} ms/round)"
                )
    for v in report["identity_violations"]:
        lines.append(f"  WARNING: {v}")
    roof = report.get("roofline") or {}
    meas = report.get("measured") or {}
    if roof:
        lines.append("")
        lines.append(
            f"{'phase':<18}{'flops/round':>14}{'bytes/round':>14}"
            f"{'flops/byte':>12}{'bound':>9}{'us@peak':>10}{'measured':>13}"
        )
        for name in PHASES:
            if name not in roof:
                continue
            r = roof[name]
            inten = ("inf" if r["intensity"] is None
                     else f"{r['intensity']:.1f}")
            # measured flops exist at PROGRAM granularity (XLA fuses
            # the whole round into one executable), so phase rows carry
            # the analytic model and the join lands on the total row
            lines.append(
                f"{name:<18}{r['flops']:>14.3g}{r['bytes']:>14.3g}"
                f"{inten:>12}{r['bound']:>9}{r['time_us_at_peak']:>10.1f}"
                f"{'n/a':>13}"
            )
        if meas:
            drift = meas.get("flop_model_drift_pct")
            lines.append(
                f"{'round total':<18}"
                f"{meas['round_flops_analytic']:>14.3g}"
                f"{'':>14}{'':>12}{'':>9}{'':>10}"
                f"{meas['round_flops_measured']:>13.3g}"
            )
            lines.append(
                f"measured vs analytic flops/round "
                f"({meas['round_program']}, XLA cost_analysis): "
                f"drift {_na(drift, '{:+.2f}%')}"
            )
        else:
            lines.append(
                "measured flops: n/a (no executable_compiled records — "
                "run predates the executable registry or "
                "run.obs.executables was off)"
            )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# bench regression observatory (`colearn bench-report`)
# ---------------------------------------------------------------------------


def _na(v, fmt="{}"):
    return "n/a" if v is None else fmt.format(v)


def load_bench_history(bench_dir: str) -> List[Dict[str, Any]]:
    """Parse the ``BENCH_r*.json`` trajectory in ``bench_dir`` into
    normalized entries, tolerant of every historical shape: entries
    missing ``parsed`` (a failed bench run), and extras that predate
    ``mfu_basis`` / ``compute_dtype`` / ``phase_ms`` / ``timed_rounds``
    get ``None`` fields (rendered ``n/a``), never a KeyError."""
    paths = sorted(
        glob.glob(os.path.join(bench_dir, "BENCH_r*.json")),
        key=lambda p: (
            int(m.group(1)) if (m := re.search(r"_r(\d+)", p)) else 0, p
        ),
    )
    entries = []
    for p in paths:
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            entries.append({"file": os.path.basename(p), "n": None,
                            "value": None, "error": "unreadable"})
            continue
        parsed = doc.get("parsed") or {}
        extra = parsed.get("extra") or {}
        timed = extra.get("timed_rounds")
        phase_ms = extra.get("phase_ms")
        phase_ms_per_round = None
        if isinstance(phase_ms, dict) and timed:
            phase_ms_per_round = {
                k: float(v) / float(timed) for k, v in phase_ms.items()
            }
        entries.append({
            "file": os.path.basename(p),
            "n": doc.get("n"),
            "value": parsed.get("value"),
            "vs_baseline": parsed.get("vs_baseline"),
            "mfu_pct": extra.get("mfu_pct"),
            "effective_mfu_pct": extra.get("effective_mfu_pct"),
            "mfu_basis": extra.get("mfu_basis"),
            "compute_dtype": extra.get("compute_dtype"),
            "device_ms_per_round": extra.get("device_ms_per_round"),
            "timed_rounds": timed,
            "phase_ms_per_round": phase_ms_per_round,
            "padded_step_fraction": extra.get("padded_step_fraction"),
            # the n_chips axis (weak-scaling bench): historical entries
            # that predate it render n/a like every other field
            "n_chips": extra.get("n_chips"),
            "updates_per_sec_per_chip": extra.get(
                "client_updates_per_sec_per_chip"
            ),
            "cohort_layout": extra.get("cohort_layout"),
            # control-plane mode (run.control_plane, ISSUE 18): entries
            # predating the knob (r01–r05) render n/a
            "control_plane": extra.get("control_plane"),
            # measured-vs-analytic flop drift (executable registry,
            # ISSUE 20): r01–r19 entries predate the extra → n/a
            "flop_model_drift_pct": extra.get("flop_model_drift_pct"),
            "weak_scale": _tail_weak_scale_records(doc, parsed),
            "async_throughput": _tail_async_records(doc, parsed),
            "store_gather": _tail_store_records(doc, parsed),
        })
    return entries


def _tail_store_records(doc, parsed) -> List[Dict[str, Any]]:
    """Store-backed bench records carrying the ``store_gather_mbps``
    extra in one BENCH_r*.json — the file's own parsed entry or extra
    ``--matrix`` tail lines, like the async/weak-scale scans. These
    feed the ``store_gather_mbps_min`` gate; entries predating the
    data-plane extras (r01–r18) are simply absent, never an error."""
    candidates: List[Dict[str, Any]] = []
    for line in str(doc.get("tail") or "").splitlines():
        line = line.strip()
        if not (line.startswith("{") and "store_gather_mbps" in line):
            continue
        try:
            candidates.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    if (parsed.get("extra") or {}).get("store_gather_mbps") is not None:
        candidates.append(parsed)
    records: List[Dict[str, Any]] = []
    seen = set()
    for rec in candidates:
        extra = rec.get("extra") or {}
        mbps = extra.get("store_gather_mbps")
        if mbps is None:
            continue
        name = str(rec.get("config") or rec.get("metric") or "store")
        if name in seen:
            continue
        seen.add(name)
        records.append({
            "name": name,
            "store_gather_mbps": float(mbps),
            "gather_workers": extra.get("gather_workers"),
        })
    return records


def _tail_async_records(doc, parsed) -> List[Dict[str, Any]]:
    """``async_throughput_*`` bench records carried by one
    BENCH_r*.json — the file's own parsed entry or extra ``--matrix``
    tail lines, exactly like the weak-scale scan. Normalized to the
    fields the async-throughput gate reads; anything unparsable or
    missing them is skipped (the r01+ history predates async entries
    and must keep loading clean)."""
    candidates: List[Dict[str, Any]] = []
    for line in str(doc.get("tail") or "").splitlines():
        line = line.strip()
        if not (line.startswith("{") and (
            "async_throughput" in line or "hier_async" in line
        )):
            continue
        try:
            candidates.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    if (
        str(parsed.get("config") or "").startswith(
            ("async_throughput", "hier_async")
        )
        or (parsed.get("extra") or {}).get("staleness_bound") is not None
    ):
        candidates.append(parsed)
    records = []
    seen = set()
    for rec in candidates:
        extra = rec.get("extra") or {}
        ups = rec.get("value")
        bound = extra.get("staleness_bound")
        if ups is None or bound is None:
            continue
        name = str(rec.get("config") or rec.get("metric") or "async")
        if name in seen:
            continue
        seen.add(name)
        records.append({
            "name": name,
            "updates_per_sec": float(ups),
            "staleness_bound": int(bound),
            "max_realized_staleness": extra.get("max_realized_staleness"),
            "staleness_clamped": extra.get("staleness_clamped"),
            "backpressure_shed": extra.get("backpressure_shed"),
            # hierarchical multi-version entries (hier_async_*) carry
            # the per-tier breakdown the staleness-bound gate prints
            "hier_edges": extra.get("hier_edges"),
            "async_versions": extra.get("async_versions"),
            "per_version_absorbed": extra.get("per_version_absorbed"),
            "per_edge_absorbed": extra.get("per_edge_absorbed"),
        })
    return records


def _tail_weak_scale_records(doc, parsed) -> List[Dict[str, Any]]:
    """weak_scale_* bench records carried by one BENCH_r*.json — either
    the file's own parsed entry (a dedicated weak-scale run) or extra
    JSON lines in its raw ``tail`` (a ``--matrix`` run prints one line
    per config; ``parsed`` keeps only the last). Normalized to the few
    fields the weak-scaling report needs; anything unparsable or
    missing fields is skipped, never a KeyError — the r01+ history
    predates weak scaling entirely and must keep loading clean."""
    records = []
    candidates: List[Dict[str, Any]] = []
    for line in str(doc.get("tail") or "").splitlines():
        line = line.strip()
        if not (line.startswith("{") and "weak_scale" in line):
            continue
        try:
            candidates.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    if (
        "weak_scale" in str(parsed.get("metric") or "")
        or str(parsed.get("config") or "").startswith("weak_scale")
        # a direct `bench.py --config weak_scale_*` record carries no
        # `config` key and its metric reads "weak scaling: ..." — the
        # per-chip-cohort extra is the reliable marker
        or (parsed.get("extra") or {}).get("weak_scale_per_chip_cohort")
        is not None
    ):
        candidates.append(parsed)
    seen = set()
    for rec in candidates:
        extra = rec.get("extra") or {}
        per_chip = extra.get("weak_scale_per_chip_cohort")
        name = rec.get("config") or extra.get("weak_scale_name") or (
            f"weak_scale_{per_chip}" if per_chip is not None
            else rec.get("metric")
        )
        ups = extra.get("client_updates_per_sec_per_chip")
        chips = extra.get("n_chips")
        if name is None or ups is None or chips is None:
            continue
        key = (str(name), int(chips))
        if key in seen:
            continue
        seen.add(key)
        records.append({
            "name": str(name),
            "n_chips": int(chips),
            "per_chip_cohort": per_chip,
            "cohort_size": extra.get("cohort_size"),
            "updates_per_sec_per_chip": float(ups),
            "cohort_layout": extra.get("cohort_layout"),
        })
    return records


DEFAULT_PHASE_REGRESSION_FACTOR = 1.25


def bench_report(entries: Sequence[Dict[str, Any]],
                 budgets: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Trajectory deltas + budget gates over a bench history.

    ``budgets`` is the checked-in baseline (``BENCH_BUDGETS.json``):

    - ``rounds_per_sec_min`` / ``mfu_pct_min`` — floors on the LATEST
      entry (the scalar gates, generalized from bench.py's ``_gate``).
    - ``phase_budget_ms`` — explicit per-phase ms/round ceilings.
    - ``phase_regression_factor`` — for phases with no explicit budget,
      the ceiling is best-so-far (earlier entries) × factor.

    Gates never fire on ``n/a`` (a missing field is a provenance gap,
    not a regression); they fire the moment the field exists and
    exceeds its budget, naming the offending phase.
    """
    budgets = budgets or {}
    factor = float(budgets.get("phase_regression_factor",
                               DEFAULT_PHASE_REGRESSION_FACTOR))
    explicit = budgets.get("phase_budget_ms") or {}
    # best-so-far per phase over all but the latest measurable entry
    measurable = [e for e in entries if e.get("value") is not None]
    latest = measurable[-1] if measurable else None
    best_phase: Dict[str, float] = {}
    best_value = None
    for e in measurable[:-1]:
        if e.get("value") is not None:
            best_value = max(best_value or 0.0, e["value"])
        for ph, ms in (e.get("phase_ms_per_round") or {}).items():
            if ph not in best_phase or ms < best_phase[ph]:
                best_phase[ph] = ms
    violations: List[str] = []
    if latest is not None:
        rps_min = budgets.get("rounds_per_sec_min")
        if rps_min is not None and latest["value"] < float(rps_min):
            violations.append(
                f"rounds_per_sec {latest['value']:.3f} < budget floor "
                f"{float(rps_min):.3f} ({latest['file']})"
            )
        mfu_min = budgets.get("mfu_pct_min")
        if (mfu_min is not None and latest.get("mfu_pct") is not None
                and latest["mfu_pct"] < float(mfu_min)):
            violations.append(
                f"mfu_pct {latest['mfu_pct']:.2f} < budget floor "
                f"{float(mfu_min):.2f} ({latest['file']})"
            )
        # measured-vs-analytic flop drift ceiling: the cost-model truth
        # gate — |drift| over budget means the analytic phase model and
        # the XLA cost_analysis of the compiled round program no longer
        # agree. Fires only when the entry carries the extra (r01–r19
        # histories render n/a, never a gate)
        drift_max = budgets.get("flop_drift_pct_max")
        if (drift_max is not None
                and latest.get("flop_model_drift_pct") is not None
                and abs(latest["flop_model_drift_pct"]) > float(drift_max)):
            violations.append(
                f"flop_model_drift_pct "
                f"{latest['flop_model_drift_pct']:+.2f} exceeds "
                f"± budget ceiling {float(drift_max):.2f} "
                f"({latest['file']})"
            )
        for ph, ms in (latest.get("phase_ms_per_round") or {}).items():
            if ph in explicit:
                budget = float(explicit[ph])
                src = "explicit budget"
            elif ph in best_phase:
                budget = best_phase[ph] * factor
                src = f"best-so-far {best_phase[ph]:.2f} ms × {factor}"
            else:
                continue  # first appearance of the phase: becomes the pin
            if ms > budget:
                violations.append(
                    f"phase {ph}: {ms:.2f} ms/round exceeds "
                    f"{budget:.2f} ms ({src})"
                )
    # async-throughput floor (the promoted FedBuff plane): gate the
    # NEWEST history entry that carries an async_throughput record —
    # histories that predate the entry never fire (n/a, not a gate)
    ups_min = budgets.get("async_updates_per_sec_min")
    if ups_min is not None:
        with_async = [e for e in entries if e.get("async_throughput")]
        if with_async:
            for rec in with_async[-1]["async_throughput"]:
                if rec["updates_per_sec"] < float(ups_min):
                    violations.append(
                        f"async updates/sec {rec['updates_per_sec']:.1f} "
                        f"< budget floor {float(ups_min):.1f} "
                        f"({rec['name']}, {with_async[-1]['file']})"
                    )
    # hierarchical-async staleness ceiling: the hier_async_* entries
    # gate on BOTH axes — the shared throughput floor above AND the
    # realized-staleness bound here, so trading staleness for
    # throughput cannot pass the report
    # store-gather throughput floor (the store data plane): gate the
    # NEWEST entry carrying store_gather records — histories that
    # predate the extras never fire (n/a is a provenance gap, not a
    # regression)
    mbps_min = budgets.get("store_gather_mbps_min")
    if mbps_min is not None:
        with_store = [e for e in entries if e.get("store_gather")]
        if with_store:
            for rec in with_store[-1]["store_gather"]:
                if rec["store_gather_mbps"] < float(mbps_min):
                    violations.append(
                        f"store gather {rec['store_gather_mbps']:.1f} "
                        f"MiB/s < budget floor {float(mbps_min):.1f} "
                        f"({rec['name']}, {with_store[-1]['file']})"
                    )
    stale_max = budgets.get("hier_async_staleness_bound")
    if stale_max is not None:
        with_async = [e for e in entries if e.get("async_throughput")]
        if with_async:
            for rec in with_async[-1]["async_throughput"]:
                if "hier_async" not in rec["name"]:
                    continue
                ms = rec.get("max_realized_staleness")
                if ms is not None and int(ms) > int(stale_max):
                    violations.append(
                        f"hier async realized staleness {int(ms)} "
                        f"> budget bound {int(stale_max)} "
                        f"({rec['name']}, {with_async[-1]['file']})"
                    )
    return {
        "entries": list(entries),
        "latest": latest,
        "best_phase_ms": best_phase,
        "violations": violations,
        "weak_scaling": weak_scaling_report(entries),
    }


def weak_scaling_report(entries: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Weak-scaling efficiency over the history's ``weak_scale_*``
    records: updates/sec/chip at each chip count relative to the same
    per-chip-cohort workload's 1-chip pin (ideal weak scaling holds
    efficiency at 1.0 as chips × cohort grow together). Groups by
    per-chip cohort; when no 1-chip measurement exists yet the
    smallest-chip-count record becomes the pin (recorded as
    ``pin_n_chips`` so the readout stays honest). Empty list when the
    history carries no weak_scale entries — the r01+ era — which
    formats as ``n/a``, never an error."""
    groups: Dict[Any, List[Dict[str, Any]]] = {}
    for e in entries:
        for r in e.get("weak_scale") or []:
            key = r.get("per_chip_cohort")
            if key is None:
                key = r.get("name")
            groups.setdefault(key, []).append(dict(r, file=e.get("file")))
    out: List[Dict[str, Any]] = []
    for key in sorted(groups, key=str):
        recs = groups[key]
        pins = [r for r in recs if r.get("n_chips") == 1]
        pin = pins[-1] if pins else min(recs, key=lambda r: r["n_chips"])
        pin_ups = pin["updates_per_sec_per_chip"]
        for r in sorted(recs, key=lambda r: (r["n_chips"], str(r.get("file")))):
            out.append({
                "group": key,
                "name": r.get("name"),
                "file": r.get("file"),
                "n_chips": r["n_chips"],
                "cohort_size": r.get("cohort_size"),
                "updates_per_sec_per_chip": r["updates_per_sec_per_chip"],
                "cohort_layout": r.get("cohort_layout"),
                "pin_n_chips": pin["n_chips"],
                "efficiency": (
                    r["updates_per_sec_per_chip"] / pin_ups
                    if pin_ups else None
                ),
            })
    return out


def format_bench_report(report: Dict[str, Any], bench_dir: str = "") -> str:
    entries = report["entries"]
    lines = [
        f"bench trajectory"
        + (f" ({bench_dir})" if bench_dir else "")
        + f": {len(entries)} entries"
    ]
    lines.append(
        f"{'entry':<18}{'r/s':>8}{'vs_base':>9}{'mfu%':>8}"
        f"{'basis':>11}{'dtype':>10}{'dev ms':>8}"
        f"{'chips':>7}{'upd/s/chip':>12}{'mode':>8}"
    )
    for e in entries:
        lines.append(
            f"{e['file']:<18}"
            f"{_na(e.get('value'), '{:.3f}'):>8}"
            f"{_na(e.get('vs_baseline'), '{:.3f}'):>9}"
            f"{_na(e.get('mfu_pct'), '{:.2f}'):>8}"
            f"{_na(e.get('mfu_basis')):>11}"
            f"{_na(e.get('compute_dtype')):>10}"
            f"{_na(e.get('device_ms_per_round'), '{:.1f}'):>8}"
            f"{_na(e.get('n_chips')):>7}"
            f"{_na(e.get('updates_per_sec_per_chip'), '{:.1f}'):>12}"
            f"{_na(e.get('control_plane')):>8}"
        )
    latest = report.get("latest")
    phases = (latest or {}).get("phase_ms_per_round")
    if phases:
        best = report.get("best_phase_ms") or {}
        lines.append("")
        lines.append(f"{'phase (latest)':<24}{'ms/round':>10}"
                     f"{'best':>10}{'Δ vs best':>11}")
        for ph in sorted(phases, key=lambda p: -phases[p]):
            b = best.get(ph)
            delta = ("n/a" if b is None or b == 0
                     else f"{100.0 * (phases[ph] - b) / b:+.0f}%")
            lines.append(
                f"{ph:<24}{phases[ph]:>10.2f}"
                f"{_na(b, '{:.2f}'):>10}{delta:>11}"
            )
    elif latest is not None:
        lines.append("")
        lines.append("per-phase ms: n/a (history predates phase_ms extras)")
    ws = report.get("weak_scaling") or []
    lines.append("")
    if ws:
        lines.append("weak scaling (updates/sec/chip vs the pin):")
        for r in ws:
            eff = _na(r.get("efficiency"), "{:.2f}")
            note = (
                "" if r.get("pin_n_chips") == 1
                else f"  [pin: {r['pin_n_chips']}-chip]"
            )
            lines.append(
                f"  {str(r.get('name')):<22}{r['n_chips']:>3} chip(s)"
                f"{r['updates_per_sec_per_chip']:>12.1f} upd/s/chip"
                f"   eff {eff}{note}"
            )
    else:
        lines.append(
            "weak scaling: n/a (no weak_scale_* entries in this history)"
        )
    lines.append("")
    if report["violations"]:
        lines.append("GATE FAILURES:")
        lines.extend(f"  {v}" for v in report["violations"])
    else:
        lines.append("gates: PASS")
    return "\n".join(lines)
