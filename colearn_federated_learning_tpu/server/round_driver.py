"""Server round driver (SURVEY.md §2 C3, call stack §3.1; layer L4).

Owns the outer round loop the reference drives from its server process:
sample cohort → (broadcast) → local training → aggregate → eval / log /
checkpoint. In the sharded engine the broadcast+train+aggregate middle
is one XLA program (parallel/round_engine.py); this driver's per-round
host work is just index-tensor construction and a scalar metrics fetch.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

# first of the imports that cost anything, on purpose: it reads the
# clock before flax, optax and the models' Pallas kernels load (orbax
# is not among them: the first checkpoint store built imports it)
from colearn_federated_learning_tpu.server import import_clock  # isort: skip

import jax
import jax.numpy as jnp
import numpy as np

from colearn_federated_learning_tpu.client.trainer import (
    RoundData,
    block_group,
    make_eval_fn,
    make_local_train_fn,
    make_loss_fn,
    shared_weight_phase,
)
from colearn_federated_learning_tpu.config import DPConfig, ExperimentConfig
from colearn_federated_learning_tpu.data import build_federated_data
from colearn_federated_learning_tpu.data.loader import (
    RoundShape,
    bucket_ladder,
    compute_round_shape,
    eval_batches,
    iter_client_slabs,
    make_round_indices,
    make_round_spec,
    pick_bucket,
    spec_examples,
)
from colearn_federated_learning_tpu.models import build_model
from colearn_federated_learning_tpu.obs import (
    HealthAbortError,
    HealthMonitor,
    Tracer,
    block_step_counts,
    device_memory_stats,
    gossip_round_bytes,
    round_comm_bytes,
    round_host_input_bytes,
    round_shape_stats,
)
from colearn_federated_learning_tpu.obs import digest as digest_mod
from colearn_federated_learning_tpu.obs import executables as exec_mod
from colearn_federated_learning_tpu.obs.executables import (
    ExecutableRegistry,
    HbmBudgetError,
)
from colearn_federated_learning_tpu.parallel import mesh as mesh_lib
from colearn_federated_learning_tpu.parallel.round_engine import (
    apply_store_shard_ownership,
    make_async_round_fn,
    make_sequential_round_fn,
    make_sharded_round_fn,
)
from colearn_federated_learning_tpu.server.aggregation import make_server_update_fn
from colearn_federated_learning_tpu.server.attacks import (
    UPLOAD_ATTACKS,
    flip_labels,
    select_compromised,
)
from colearn_federated_learning_tpu.server.sampler import CohortSampler
from colearn_federated_learning_tpu.utils.checkpoint import CheckpointStore
from colearn_federated_learning_tpu.utils.metrics import MetricsLogger

# (start, end) of this module's imports on the perf_counter clock: seconds
# of every process's set-up, which the first Experiment puts into its
# tracer's start-up record as ``setup.import`` (None once claimed)
_IMPORT_SPAN: Optional[tuple] = (import_clock.STARTED, time.perf_counter())

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}

# warn-once latch for bf16-on-a-backend-without-native-bf16-matmuls:
# the run is CORRECT there (XLA emulates), just silently slow — e.g. a
# TPU config's bf16 settings smoke-tested on a CPU box
_BF16_BACKEND_WARNED = False


def _warn_bf16_backend(cfg) -> None:
    global _BF16_BACKEND_WARNED
    if _BF16_BACKEND_WARNED:
        return
    eff_local = cfg.run.local_param_dtype or cfg.run.param_dtype
    if "bfloat16" not in (cfg.run.compute_dtype, eff_local):
        return
    backend = jax.default_backend()
    if backend in ("tpu", "gpu"):
        return
    _BF16_BACKEND_WARNED = True
    import logging

    logging.getLogger(__name__).warning(
        "bfloat16 compute requested (run.compute_dtype=%s, effective "
        "local dtype %s) on backend %r, which has no native bf16 "
        "matmul units — results are correct but matmuls run emulated "
        "and SLOWER than float32; this is expected only when "
        "smoke-testing a TPU config off-TPU",
        cfg.run.compute_dtype, eff_local, backend,
    )


class Experiment:
    """Everything needed to run ``fit`` / ``evaluate`` for one config."""

    def __init__(self, cfg: ExperimentConfig, echo: bool = True):
        cfg.validate()
        self.cfg = cfg
        # Round-lifecycle telemetry (run.obs, obs/): the tracer times
        # host phases (and attributes retraces via compile hooks). It
        # comes first so that its ``setup.*`` spans bracket everything
        # a process pays before its first dispatch (obs/spans.py keeps
        # them past drain()). Under multi-process EVERY process traces
        # into its own lane (pid = process_index): non-primaries export
        # per-host `trace.p<i>.json` fragments and the primary merges
        # them into the final trace.json — the merged timeline replaces
        # the old process-0-only export. The JSONL stays single-writer.
        obs = cfg.run.obs
        self._process_index = jax.process_index()
        self.tracer = Tracer(
            enabled=obs.spans, trace=obs.trace,
            max_events=obs.trace_max_events,
            process_index=self._process_index,
        )
        global _IMPORT_SPAN
        if _IMPORT_SPAN is not None:
            self.tracer.note_past("setup.import", *_IMPORT_SPAN)
            _IMPORT_SPAN = None
        with self.tracer.span("setup.experiment"):
            self._build(cfg, echo)

    def _build(self, cfg: ExperimentConfig, echo: bool) -> None:
        """Everything ``__init__`` constructs, inside its
        ``setup.experiment`` span."""
        span = self.tracer.span
        if cfg.run.sanitize:
            jax.config.update("jax_debug_nans", True)
        compute_dtype = _DTYPES[cfg.run.compute_dtype]
        # LoRA adapter plane (model.lora, models/lora.py): wrap the
        # transformer so the params pytree every downstream subsystem
        # sees IS the adapter set — the [K,·] wire stack carries adapter
        # deltas, and aggregation/compression/attacks/ledger/reputation
        # all run in adapter space with zero engine involvement. The
        # frozen base is data (``frozen_base``, drawn and placed once by
        # init_state, an argument of every program that applies the
        # model), stored in the dtype local training holds weights in
        # (what _cast_params would make of it every round, done once):
        # the base module is built with that param_dtype, the adapters
        # keep run.param_dtype. lora-off constructs no wrapper at all
        # (the bitwise-identity contract).
        self._lora = cfg.model.lora.enabled
        param_dtype = _DTYPES[cfg.run.param_dtype]
        self._full_param_stats_cache = None
        self._wire_reduction_cache = None
        self.frozen_base = None
        self._frozen_base_seed = None
        with span("setup.model"):
            self.model = build_model(
                cfg.model.name, cfg.model.num_classes,
                compute_dtype=compute_dtype,
                param_dtype=(self._local_dtype() or param_dtype)
                if self._lora else param_dtype,
                **cfg.model.kwargs,
            )
            if self._lora:
                from colearn_federated_learning_tpu.models.lora import (
                    build_lora_model,
                )

                self.model = build_lora_model(
                    self.model, cfg.model.name,
                    rank=cfg.model.lora.rank, alpha=cfg.model.lora.alpha,
                    target=cfg.model.lora.target, adapter_dtype=param_dtype,
                )
        self.fed = build_federated_data(
            cfg.data, seed=cfg.run.seed, span=span, **cfg.model.kwargs)
        self.task = self.fed.task
        self.shape = compute_round_shape(self.fed, cfg.client, cfg.data)
        # On-device masks (r7): the synchronous cohort paths ship the
        # compact [K, 2] (examples_per_epoch, valid_steps) spec instead
        # of the [K, steps, batch] float32 mask slab — the engines
        # rebuild the identical mask in-program (round_engine
        # `on_device_mask`), roughly halving round-input wire bytes.
        # gossip and fedbuff keep the legacy full-mask inputs (their
        # engines consume it directly).
        self._spec_inputs = cfg.algorithm not in ("gossip", "fedbuff")
        # Device-resident control plane (run.control_plane="device",
        # server/device_plane.py): cohort ids, churn gates, the index
        # slab, and ledger slot ids derive INSIDE the round program —
        # the host ships static plan tables once and a round index per
        # dispatch; realized schedules surface at flush boundaries.
        # validate() restricted the pairing surface (uniform sampling,
        # hbm placement, sharded/sequential engines, dense ledger).
        self._cp_device = cfg.run.control_plane == "device"
        # Ledger-driven adaptive selection (server.sampling="adaptive"):
        # the sampler scores clients Oort-style from periodic host-side
        # ledger snapshots — COLUMN-SLIMMED to the three columns it
        # scores (sampler.SNAPSHOT_COLS: count, flagged, ema_loss). The
        # snapshot refreshes at client_ledger.log_every round boundaries
        # (one blocking fetch each — see run_round) and rides the
        # checkpoint (state["ledger_snapshot"], [num_clients, 3]), so
        # the schedule is a pure function of (seed, round, snapshot) and
        # resume replays it exactly. server.sampling="streaming" is the
        # million-client sibling: O(cohort·log) draws from a fixed-size
        # score SKETCH (state["ledger_sketch_*"]) instead of any dense
        # [num_clients] structure; with the ledger off it degrades to a
        # uniform streaming draw with no snapshot machinery at all.
        self._adaptive = cfg.server.sampling == "adaptive"
        self._streaming = cfg.server.sampling == "streaming"
        lcfg = cfg.run.obs.client_ledger
        self._ledger_on = lcfg.enabled
        self._ledger_cfg = lcfg
        self._snapshot_refresh = self._adaptive or (
            self._streaming and lcfg.enabled and lcfg.log_every >= 1
        )
        self._sampler_snapshot: Optional[np.ndarray] = None
        self._sampler_snapshot_round = 0
        self._sketch_ids = np.full(
            cfg.server.adaptive.sketch_size, -1, np.int32
        )
        self._sketch_stats = np.zeros(
            (cfg.server.adaptive.sketch_size, 3), np.float32
        )
        # Seed-pure availability/churn model (run.churn, server/
        # churn.py): every realized churn event is a pure function of
        # (run.seed, round, client_id), so schedules stay resume-
        # replayable and engine-invariant with zero checkpoint state.
        # The samplers reject offline candidates; dispatched cohort
        # members realize dropout/crash through _apply_failures; the
        # fedbuff scheduler defers offline completions. churn-off
        # constructs no model anywhere (bitwise-identity contract).
        from colearn_federated_learning_tpu.server.churn import (
            build_churn_model,
        )

        self._churn = build_churn_model(cfg)
        # Multi-version async lines (server.async_versions): round r
        # drives line r mod V at line-local version r div V — line 0
        # keeps the legacy state keys (the V=1 bitwise-identity
        # contract), lines l >= 1 ride `*_l{l}` keys. Retirement /
        # re-admission generation accounting lives in state["line_*"].
        self._versions = cfg.server.async_versions
        self._staleness_hist: Dict[int, int] = {}
        self._per_version_absorbed = np.zeros(
            max(1, cfg.server.async_versions), np.int64
        )
        self._version_readmitted = 0
        self._readmit_warned = False
        # Two-tier hierarchy (server.hierarchy): E edge aggregators
        # over deterministic contiguous sub-population blocks. Sync
        # rounds re-run the ONE compiled engine per edge
        # (_run_hier_round) and robust-combine edge deltas at the core;
        # fedbuff groups each popped completion by its edge host-side
        # (crashed edges' members are excluded, never NaN-poisoning
        # the core). hierarchy-off constructs nothing (the bitwise-
        # identity contract).
        self._hier = cfg.server.hierarchy.num_edges > 0
        self._hier_stats: Dict[int, Dict[str, int]] = {}
        self._edge_absorbed = np.zeros(
            max(1, cfg.server.hierarchy.num_edges), np.int64
        )
        self.sampler = CohortSampler(
            self.fed.num_clients, cfg.server.cohort_size, seed=cfg.run.seed,
            weights=(
                self.fed.client_sizes() if cfg.server.sampling == "weighted" else None
            ),
            mode=(
                "poisson" if cfg.server.sampling == "poisson"
                else "adaptive" if self._adaptive
                else "streaming" if self._streaming else "fixed"
            ),
            explore=cfg.server.adaptive.explore,
            staleness_gain=cfg.server.adaptive.staleness_gain,
            flag_suppress=cfg.server.adaptive.flag_suppress,
            sketch_size=cfg.server.adaptive.sketch_size,
            availability_fn=(
                self._churn.available if self._churn is not None else None
            ),
        )
        # Hierarchy edge samplers (sync path): one fixed-mode sampler
        # per edge over its contiguous block [e·N/E, (e+1)·N/E),
        # id_base-offset so draws come back as GLOBAL client ids (the
        # churn availability_fn and the engine index the flat
        # population). Stateless pure-(seed, round) draws — resumes
        # replay them for free. fedbuff pops its queue instead.
        self._edge_samplers = []
        if self._hier and cfg.algorithm not in ("fedbuff", "gossip"):
            _n = self.fed.num_clients
            _e_cnt = cfg.server.hierarchy.num_edges
            for _e in range(_e_cnt):
                _lo = (_e * _n) // _e_cnt
                _hi = ((_e + 1) * _n) // _e_cnt
                self._edge_samplers.append(CohortSampler(
                    _hi - _lo, cfg.server.cohort_size,
                    seed=cfg.run.seed + (_e + 1) * 1_000_003,
                    mode="fixed", id_base=_lo,
                    availability_fn=(
                        self._churn.available
                        if self._churn is not None else None
                    ),
                ))
        # Poisson sampling: the realized Binomial(N, q) cohort is padded
        # to a STATIC cap of K + 5σ (so XLA never retraces); overflow
        # raises — an OBSERVABLE abort whose exact binomial-tail
        # probability is logged as dp_delta_abort and belongs to the DP
        # δ (the (ε, δ + δ_abort) composition for aborting mechanisms).
        self._poisson = cfg.server.sampling == "poisson"
        self._poisson_cap = 0
        if self._poisson:
            import math as _math

            _k, _n = cfg.server.cohort_size, self.fed.num_clients
            _q = _k / _n
            self._poisson_cap = min(
                _n, _k + _math.ceil(5.0 * _math.sqrt(_k * (1.0 - _q))) + 1
            )
        # Heterogeneity-aware round shapes (run.shape_buckets, r7): the
        # federation-max steps_per_epoch is quantized onto a geometric
        # ladder; each round (chunk, under fusion) dispatches on the
        # smallest rung covering the SAMPLED cohort's max capped shard.
        # The bucket for a round is a pure function of (seed, round) —
        # resume and the stream-prefetch worker recompute it for free —
        # and jit caches one executable per realized [K, steps, batch]
        # shape, so the compile budget is bounded by the ladder size
        # (per-bucket attribution: _bucket_compile_span).
        self._sizes_capped = np.minimum(
            self.fed.client_sizes(), self.shape.cap
        ).astype(np.int64)
        sb = cfg.run.shape_buckets
        self._bucket_ladder = (
            bucket_ladder(self.shape.steps_per_epoch, sb.base, sb.count)
            if sb.enabled else None
        )
        self._bucket_cache: Dict[int, int] = {}
        self._bucket_shapes: Dict[int, RoundShape] = {
            self.shape.steps_per_epoch: self.shape
        }
        self._seen_buckets: set = set()
        self.server_opt_init, server_update = make_server_update_fn(cfg.server)
        # SCAFFOLD (cfg.algorithm): per-client control variates live as
        # one stacked [N_pad, ...] tree per leaf. Under the sharded
        # engine the store is DEVICE-RESIDENT, mesh-sharded over the
        # clients axis, and the cohort gather/scatter happens inside the
        # round program (round_engine.py) — zero per-round host sync,
        # multi-host capable. The sequential engine keeps the
        # host-numpy store (it is the debugging oracle).
        self.scaffold = cfg.algorithm == "scaffold"
        # FedDyn shares scaffold's state plumbing: c_global carries h,
        # c_clients carries the per-client gᵢ corrections
        self.feddyn = cfg.algorithm == "feddyn"
        self.stateful = self.scaffold or self.feddyn
        # Error-feedback compression (ServerConfig.error_feedback) rides
        # the SAME device-resident store (c_clients carries the eᵢ
        # residuals) but has no global state — store_state gates the
        # store plumbing, stateful the c_global/dc machinery
        self.ef = cfg.server.error_feedback
        self.store_state = self.stateful or self.ef
        # FedBuff (cfg.algorithm="fedbuff"): the server steps an
        # asynchronous in-flight queue instead of sampling synchronous
        # cohorts — client completions are consumed K at a time, each
        # trained against the stale params version it started from
        # (kept in an on-device history ring), staleness-decayed.
        self.fedbuff = cfg.algorithm == "fedbuff"
        # Decentralized gossip (cfg.algorithm="gossip", parallel/gossip.py):
        # no server — every client keeps its own replica in a [N, ...]
        # mesh-sharded tree; rounds are local-train + ring halo-exchange
        # mixing. state["params"] tracks the consensus mean (what eval/
        # checkpoint-export consume); state["replicas"] is the stack.
        self.gossip = cfg.algorithm == "gossip"
        # partial-participation gossip (r5): K < N ⇒ the sampled cohort
        # trains (in-program gather/scatter over the replica stack),
        # everyone mixes; 0 = classic full participation
        self._gossip_partial = (
            cfg.server.cohort_size
            if self.gossip and cfg.server.cohort_size < cfg.data.num_clients
            else 0
        )
        # secure aggregation (ServerConfig.secure_aggregation): masks
        # ride a STATIC full-cohort ring; the fixed-point range checks
        # run after the aggregation-weight mode is resolved below
        self.secagg = cfg.server.secure_aggregation
        if self.fedbuff:
            # per-client base durations for the async workload model:
            # capped work (= the examples the client actually trains on)
            # quantile-ranked into 1..S — see _client_durations
            work = np.minimum(self.fed.client_sizes(), self.shape.cap)
            ranks = np.argsort(np.argsort(work, kind="stable"))
            s = cfg.server.async_max_staleness
            self._duration_base = (
                1 + (ranks * s) // max(len(work), 1)
            ).astype(np.int32)
        # per-round async scheduler stats (mean/max staleness, clamp +
        # backpressure counts), drained into round records at flush;
        # _traffic_totals accumulates the summable ones for run_summary
        self._async_stats: Dict[int, Dict[str, Any]] = {}
        self._traffic_totals: Dict[str, int] = {}
        self._async_absorbed = 0
        self._staleness_warned = False
        # observability (run.obs, obs/): per-round comm-byte and
        # failure-count stats keyed by round (host-side, popped at
        # flush); the health monitor is built after the logger
        # below. _param_stats_cache backs both the HBM
        # pre-flight and the comm-byte model.
        self._param_stats_cache = None
        self._param_shapes_cache = None
        # the megabatch block trainer's (width, group, shared first
        # step), for _count_block_steps; None: another layout
        self._block = None
        self._dp_param_counts_cache = None
        self._comm_stats: Dict[int, Dict[str, int]] = {}
        self._fail_stats: Dict[int, Dict[str, int]] = {}
        # unfused engine twin for non-chunk-aligned resumes under
        # run.fuse_rounds > 1 (set below for the sharded sync path)
        self._make_engine = None
        self._unfused_cache = None
        # Byzantine adversary simulation (AttackConfig, server/attacks.py):
        # the compromised id set is a deterministic pure function of
        # (run.seed, num_clients, fraction) — fixed for the whole run,
        # identical across engines and resumes. Upload attacks ride the
        # engines' [K] byzantine-mask input; label_flip poisons the
        # compromised clients' training labels host-side below, before
        # the corpus is placed (so hbm, stream, and both engines all see
        # the same poisoned shards).
        self.attack_kind = cfg.attack.kind
        self._attack_upload = self.attack_kind in UPLOAD_ATTACKS
        self.compromised = np.zeros(0, np.int64)
        self._attack_stats: Dict[int, int] = {}
        # Per-client forensic ledger (run.obs.client_ledger, obs/
        # ledger.py): each round program emits a [K] per-client stats
        # block (upload L2 / cosine-vs-aggregate / clip-EF residual /
        # loss / robust-z flag) and scatters it into a device-resident
        # [num_clients, LEDGER_WIDTH] store carried across rounds —
        # periodic `client_ledger` JSONL records + the `colearn
        # clients` report read it. validate() already rejected the
        # unsound pairings (secagg, client-DP, gossip/fedbuff,
        # stateful algorithms).
        # (lcfg/_ledger_on/_ledger_cfg were hoisted above the sampler —
        # the snapshot-refresh machinery needs them)
        self._ledger_ref = None
        self._ledger_logged_round = -1
        if self.attack_kind:
            self.compromised = select_compromised(
                self.fed.num_clients, cfg.attack.fraction, cfg.run.seed
            )
            if self.attack_kind == "label_flip":
                if self.fed.task != "classify":
                    raise ValueError(
                        "attack.kind='label_flip' requires a "
                        "classification task"
                    )
                self.fed.train_y = flip_labels(
                    self.fed.train_y, self.fed.client_indices,
                    self.compromised, self.fed.num_classes,
                )
        # Size-proportional sampling pairs with UNIFORM aggregation
        # weights: example-weighting on top of p∝size sampling would count
        # shard size twice (contribution ∝ size²). Uniform sampling keeps
        # classic example-weighted FedAvg. (The pairing is the standard FL
        # importance-sampling heuristic — exactly unbiased only in the
        # with-replacement limit; without-replacement cohorts cap a huge
        # client's inclusion probability at 1, mildly under-weighting it.)
        agg = "uniform" if cfg.server.sampling == "weighted" else "examples"
        if self.feddyn:
            agg = "uniform"  # the paper's plain mean over the cohort
        if cfg.server.dp_client_noise_multiplier > 0.0:
            # client-level DP needs w_i ∈ {0,1} and a fixed public
            # denominator — example weights are private data and would
            # invalidate the sensitivity analysis (ServerConfig docs)
            agg = "uniform"
        self._agg_mode = agg
        # (the secagg fixed-point bound check runs AFTER engine
        # construction so the poisson cap is already lane-rounded —
        # the bound must cover the padded worst case)

        with span("setup.engine"):
            if cfg.run.engine == "sharded":
                batch_shards = max(1, cfg.run.batch_shards)
                if cfg.client.batch_size % batch_shards:
                    raise ValueError(
                        f"run.batch_shards={batch_shards} must divide "
                        f"client.batch_size={cfg.client.batch_size}"
                    )
                avail = len(jax.devices()) // batch_shards
                if avail < 1:
                    raise ValueError(
                        f"run.batch_shards={batch_shards} > visible devices "
                        f"{len(jax.devices())}"
                    )
                if cfg.run.num_lanes:
                    lanes = cfg.run.num_lanes
                    if not self._poisson and cfg.server.cohort_size % lanes != 0:
                        raise ValueError(
                            f"run.num_lanes={lanes} must divide cohort_size="
                            f"{cfg.server.cohort_size} (set num_lanes=0 to auto-pick)"
                        )
                else:
                    lanes = mesh_lib.largest_lane_count(cfg.server.cohort_size, avail)
                if self._poisson:
                    # static rows must divide the lanes; pad rows are free
                    self._poisson_cap = -(-self._poisson_cap // lanes) * lanes
                self.mesh = mesh_lib.build_client_mesh(lanes, batch_shards=batch_shards)
                if self.gossip:
                    from colearn_federated_learning_tpu.parallel.gossip import (
                        make_gossip_round_fn,
                    )

                    self.round_fn = make_gossip_round_fn(
                        self.model, cfg.client, cfg.dp, self.task, self.mesh,
                        num_clients=self.fed.num_clients,
                        gamma=cfg.server.gossip_gamma,
                        mixing_steps=cfg.server.gossip_mixing_steps,
                        topology=cfg.server.gossip_topology,
                        local_dtype=self._local_dtype(),
                        scan_unroll=cfg.run.scan_unroll,
                        cohort_size=cfg.server.cohort_size,
                        attack=self.attack_kind if self._attack_upload else "",
                        attack_scale=cfg.attack.scale,
                        attack_eps=cfg.attack.eps,
                    )
                elif self.fedbuff:
                    self.round_fn = make_async_round_fn(
                        self.model, cfg.client, cfg.dp, self.task, self.mesh,
                        server_update, buffer_size=cfg.server.cohort_size,
                        window=2 * cfg.server.async_max_staleness + 1,
                        client_vmap_width=cfg.run.client_vmap_width,
                        local_dtype=self._local_dtype(),
                        clip_delta_norm=cfg.server.clip_delta_norm,
                        scan_unroll=cfg.run.scan_unroll,
                        client_ledger=self._ledger_on,
                        ledger_ema=lcfg.ema,
                        ledger_zmax=lcfg.zmax,
                        reputation=cfg.server.reputation.enabled,
                        rep_floor=cfg.server.reputation.floor,
                        rep_strength=cfg.server.reputation.strength,
                        rep_z_gain=cfg.server.reputation.z_gain,
                    )
                else:
                    def _make_engine(fuse, donate=True):
                        return make_sharded_round_fn(
                            self.model, cfg.client, cfg.dp, self.task, self.mesh,
                            server_update,
                            self._poisson_cap or cfg.server.cohort_size,
                            dp_fixed_denom=cfg.server.cohort_size,
                            client_vmap_width=cfg.run.client_vmap_width,
                            cohort_layout=cfg.run.cohort_layout,
                            local_dtype=self._local_dtype(), agg=agg,
                            scaffold=self.scaffold,
                            num_clients=self.fed.num_clients,
                            aggregator=cfg.server.aggregator,
                            trim_ratio=cfg.server.trim_ratio,
                            compression=cfg.server.compression,
                            topk_ratio=cfg.server.compression_topk_ratio,
                            qsgd_levels=cfg.server.compression_qsgd_levels,
                            topk_exact=cfg.server.compression_topk_exact,
                            clip_delta_norm=cfg.server.clip_delta_norm,
                            feddyn_alpha=(
                                cfg.server.feddyn_alpha if self.feddyn else 0.0
                            ),
                            byzantine_f=cfg.server.krum_byzantine,
                            scan_unroll=cfg.run.scan_unroll,
                            secagg=self.secagg,
                            secagg_quant_step=cfg.server.secagg_quant_step,
                            secagg_mode=cfg.server.secagg_mode,
                            client_dp_noise=cfg.server.dp_client_noise_multiplier,
                            downlink=cfg.server.downlink_compression,
                            downlink_levels=cfg.server.downlink_qsgd_levels,
                            error_feedback=self.ef,
                            fuse_rounds=fuse,
                            attack=(
                                self.attack_kind if self._attack_upload else ""
                            ),
                            attack_scale=cfg.attack.scale,
                            attack_eps=cfg.attack.eps,
                            on_device_mask=self._spec_inputs,
                            client_ledger=self._ledger_on,
                            ledger_ema=lcfg.ema,
                            ledger_zmax=lcfg.zmax,
                            reputation=cfg.server.reputation.enabled,
                            rep_floor=cfg.server.reputation.floor,
                            rep_strength=cfg.server.reputation.strength,
                            rep_z_gain=cfg.server.reputation.z_gain,
                            fused_apply=cfg.server.fused_apply,
                            # hierarchy re-dispatches the SAME params/opt
                            # buffers once per edge — donation would delete
                            # them after the first edge's call; the device
                            # control plane moves donation to its outer
                            # wrapper jit (donate=False here)
                            donate=donate and not self._hier,
                        )

                    self.round_fn = _make_engine(cfg.run.fuse_rounds)
                    # an unfused twin is built lazily (one extra compile)
                    # only when a resume lands off a chunk boundary — see
                    # _unfused_round_fn / the _fit_body catch-up loop; the
                    # device control plane keeps the factory for its
                    # donate-free inner engines
                    if cfg.run.fuse_rounds > 1 or self._cp_device:
                        self._make_engine = _make_engine
                self._data_sharding = mesh_lib.replicated(self.mesh)
                self._cohort_sharding = mesh_lib.cohort_sharded(self.mesh)
                self._client_sharding = mesh_lib.client_sharded(self.mesh)
                self.n_chips = lanes * batch_shards
                if cfg.run.cohort_layout == "megabatch":
                    width = (self._poisson_cap
                             or cfg.server.cohort_size) // lanes
                    shapes = self._param_shapes()
                    self._block = (width, block_group(shapes, width),
                                   shared_weight_phase(shapes))
                # per-client state store rows: N padded up to a lane multiple
                # (pad rows are never sampled into a cohort, so they stay 0)
                self._state_rows = -(-self.fed.num_clients // lanes) * lanes
            else:
                self.mesh = None
                self.round_fn = make_sequential_round_fn(
                    self.model, cfg.client, cfg.dp, self.task, server_update,
                    dp_fixed_denom=cfg.server.cohort_size,
                    local_dtype=self._local_dtype(), agg=agg,
                    scaffold=self.scaffold, num_clients=self.fed.num_clients,
                    aggregator=cfg.server.aggregator,
                    trim_ratio=cfg.server.trim_ratio,
                    compression=cfg.server.compression,
                    topk_ratio=cfg.server.compression_topk_ratio,
                    qsgd_levels=cfg.server.compression_qsgd_levels,
                    topk_exact=cfg.server.compression_topk_exact,
                    clip_delta_norm=cfg.server.clip_delta_norm,
                    feddyn_alpha=(
                        cfg.server.feddyn_alpha if self.feddyn else 0.0
                    ),
                    byzantine_f=cfg.server.krum_byzantine,
                    secagg=self.secagg,
                    secagg_quant_step=cfg.server.secagg_quant_step,
                    secagg_mode=cfg.server.secagg_mode,
                    scan_unroll=cfg.run.scan_unroll,
                    client_dp_noise=cfg.server.dp_client_noise_multiplier,
                    downlink=cfg.server.downlink_compression,
                    downlink_levels=cfg.server.downlink_qsgd_levels,
                    error_feedback=self.ef,
                    attack=self.attack_kind if self._attack_upload else "",
                    attack_scale=cfg.attack.scale,
                    attack_eps=cfg.attack.eps,
                    on_device_mask=self._spec_inputs,
                    client_ledger=self._ledger_on,
                    ledger_ema=lcfg.ema,
                    ledger_zmax=lcfg.zmax,
                    reputation=cfg.server.reputation.enabled,
                    rep_floor=cfg.server.reputation.floor,
                    rep_strength=cfg.server.reputation.strength,
                    rep_z_gain=cfg.server.reputation.z_gain,
                    fused_apply=cfg.server.fused_apply,
                )
                self._data_sharding = None
                self._cohort_sharding = None
                self._client_sharding = None
                self.n_chips = 1
                self._state_rows = self.fed.num_clients

        if self.secagg:
            # after engine construction: the poisson cap (if any) is now
            # lane-rounded, so the worst-case aggregate bound is final
            self._check_secagg_bounds()

        # Paged ledger (run.obs.client_ledger.hot_capacity, obs/ledger
        # LedgerPager): the device store shrinks to a [hot_capacity,
        # LEDGER_WIDTH] hot set scattered by SLOT; the driver remaps
        # cohort ids → slots host-side (the round program is unchanged)
        # and spills cold rows to an anonymous host mmap. hot_capacity
        # >= num_clients (or 0) keeps the classic dense store. The
        # capacity floor uses the LANE-ROUNDED poisson cap and the full
        # fused-chunk cohort union — the worst case one dispatch can
        # touch — so "cohort fits the hot set" is a construction-time
        # guarantee, not a runtime surprise.
        self._pager = None
        self._ledger_rows = self.fed.num_clients
        hot = lcfg.hot_capacity
        if self._ledger_on and 0 < hot < self.fed.num_clients:
            need = (self._poisson_cap or cfg.server.cohort_size) * max(
                1, cfg.run.fuse_rounds
            )
            if hot < need:
                raise ValueError(
                    f"run.obs.client_ledger.hot_capacity={hot} is smaller "
                    f"than the worst-case dispatch cohort "
                    f"({self._poisson_cap or cfg.server.cohort_size} "
                    f"clients × fuse_rounds={max(1, cfg.run.fuse_rounds)} "
                    f"= {need}) — every dispatched cohort must fit the "
                    f"hot set; raise hot_capacity or shrink the cohort"
                )
            from colearn_federated_learning_tpu.obs.ledger import LedgerPager

            self._pager = LedgerPager(self.fed.num_clients, hot)
            self._ledger_rows = hot

        # Training-corpus placement (SURVEY.md §2 C10 at scale):
        #   hbm    — dataset bytes go to HBM exactly once (replicated over
        #            lanes); rounds gather on device. Default.
        #   stream — corpus stays in host RAM; each round uploads only a
        #            fixed-size slab of the cohort's examples with the
        #            index tensors remapped into it (max slab rows =
        #            cohort × cap + 1). Unlocks corpora larger than HBM;
        #            the per-round working set still must fit.
        # Multi-host runs assemble global arrays from the host-replicated
        # copies instead of device_put-ing across processes.
        put = self._put_data
        self._stream = cfg.data.placement == "stream"
        with span("setup.model"):  # the parameter statistics (eval_shape)
            self._check_memory_budget()
        # Fused-chunk placement (run.fuse_rounds > 1): the stacked
        # [F, K, ...] host slabs go through the same _put path as the
        # per-round tensors, with the fuse dim replicated — under
        # multi-process each host uploads only its addressable shards
        # (host_local_array), so fusion composes with multi-host meshes.
        if self.mesh is not None:
            self._fused_cohort_sharding = mesh_lib.fused_cohort_sharded(
                self.mesh
            )
            self._fused_client_sharding = mesh_lib.fused_client_sharded(
                self.mesh
            )
        else:
            self._fused_cohort_sharding = None
            self._fused_client_sharding = None
        self._prefetch: Dict[int, Any] = {}
        self._host_executor = None
        # Double-buffered rounds (run.double_buffer, ROADMAP item 2
        # lever c): a host worker builds AND places round N+1's inputs
        # while round N's dispatched compute runs — see _maybe_prefetch
        # for the drain rules (fuse chunks, bucket rungs, adaptive
        # snapshot boundaries). fedbuff's scheduler pops its queue
        # in-order and is not buffered.
        self._double_buffer = (
            bool(cfg.run.double_buffer) and not self.fedbuff
            and not self._hier
            # device control plane: there are no host slabs to build
            # ahead — the worker would race the in-program derivation
            # for nothing, so double-buffering is structurally off
            and not self._cp_device
        )
        self._db_stats = {
            "host_prefetched": 0, "placed_prefetched": 0,
            "prefetch_dropped": 0, "slab_prefetched": 0,
        }
        # fused chunk-union slab prefetch (stream × fuse): one future
        # per upcoming chunk, keyed by chunk start round — submitted
        # right before the current chunk's dispatch so the next
        # chunk's store gather runs while this dispatch executes
        self._chunk_prefetch: Dict[int, Any] = {}
        _warn_bf16_backend(cfg)
        if self._stream:
            rows_per_round = (
                (self._poisson_cap or cfg.server.cohort_size)
                * self.shape.cap + 1
            )
            self._slab_rows = min(rows_per_round, len(self.fed.train_x))
            # fused chunks gather ONE union slab over the chunk's
            # cohorts (static shape: fuse rounds' worth of rows) and
            # remap the stacked index tensors into it — the engine
            # still sees a single corpus input per dispatch
            self._fused_slab_rows = min(
                cfg.run.fuse_rounds * (rows_per_round - 1) + 1,
                len(self.fed.train_x),
            )
            self.train_x = None
            self.train_y = None
            # multi-host shard ownership (store-backed pods): each
            # process marks the store shards whose clients land on its
            # contiguous client block, so steady-state gathers fault
            # only local pages; off-block touches fall back to read
            # replicas (counted in gather_stats) — see round_engine
            self._store_ownership = apply_store_shard_ownership(self.fed)
        else:
            self._store_ownership = None
            with span("setup.data.place"):
                self.train_x = put(jnp.asarray(self.fed.train_x))
                self.train_y = put(jnp.asarray(self.fed.train_y))
        # Device control plane: build the static plan (cohort table via
        # the UNMODIFIED host sampler — device cohorts are bitwise-equal
        # to host mode by construction — churn thresholds, shard table),
        # ship it to HBM once, and wrap the donate-free engine twins.
        self._device_plan = None
        self._device_sched: Dict[int, Any] = {}
        self._device_draw_stats: Dict[int, Optional[Dict[str, int]]] = {}
        if self._cp_device:
            self._init_device_plane()
        eval_fn = make_eval_fn(self.model, self.task)
        self._eval_fn = exec_mod.instrument("eval.task", jax.jit(eval_fn))

        # Federated (per-client) eval as ONE dispatch: nested lax.scan —
        # outer over clients, inner over each client's padded batch stack
        # — instead of one jitted call per client per batch (up to
        # clients × batches dispatches; same fix as _eval_all).
        def _fed_eval_all(params, xs, ys, ms, frozen=None):
            def per_client(_, client_b):
                def body(acc, b):
                    _, c, n = eval_fn(params, *b, frozen)
                    return (acc[0] + c, acc[1] + n), None

                sums, _ = jax.lax.scan(
                    body, (jnp.zeros(()), jnp.zeros(())), client_b
                )
                return None, sums

            _, (c, n) = jax.lax.scan(per_client, None, (xs, ys, ms))
            return c, n  # per-client correct/example counts, [n_clients]

        self._fed_eval_all = exec_mod.instrument(
            "eval.fed_all", jax.jit(_fed_eval_all)
        )

        # Full-test-set eval as ONE dispatch: lax.scan over the stacked
        # eval batches instead of one jitted call per batch — at ImageNet
        # scale (50k test / batch 64 ≈ 780 batches) the per-batch loop is
        # host-dispatch-bound. Parity with the per-batch
        # loop is pinned by tests/test_e2e_mnist.py::test_eval_scan_parity.
        def _eval_all(params, xb, yb, mb, frozen=None):
            def body(acc, b):
                l, c, n = eval_fn(params, *b, frozen)
                return (acc[0] + l, acc[1] + c, acc[2] + n), None

            acc, _ = jax.lax.scan(
                body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())),
                (xb, yb, mb),
            )
            return acc

        self._eval_all = exec_mod.instrument("eval.all", jax.jit(_eval_all))
        # eval batches are fixed for the run: build + upload exactly once
        with span("setup.eval_batches"):
            xb, yb, mb = eval_batches(
                self.fed.test_x, self.fed.test_y, cfg.client.batch_size
            )
            self._eval_data = (put(jnp.asarray(xb)), put(jnp.asarray(yb)),
                               put(jnp.asarray(mb)))
        # Multi-host: every process runs the identical fit loop (SPMD over
        # the global mesh), but artifacts are SINGLE-WRITER — only process
        # 0 writes/echoes metrics. Checkpointing stays collective (orbax
        # coordinates its own primary-writer protocol internally).
        self._primary = jax.process_index() == 0
        if (self.store_state and jax.process_count() > 1
                and cfg.run.engine != "sharded"):
            # only the sequential oracle still host-scatters per-client
            # state (device_get of non-addressable shards is impossible
            # in a multi-controller run); the sharded engine keeps the
            # store device-resident and is fully multi-host capable
            raise NotImplementedError(
                "scaffold/feddyn/error_feedback under multi-host requires "
                "run.engine=sharded (the sequential oracle's host-"
                "resident state scatter cannot cross processes)"
            )
        self.logger = MetricsLogger(
            (cfg.run.out_dir or None) if self._primary else None,
            cfg.name, echo=echo and self._primary,
            append=cfg.run.resume,
            tensorboard=cfg.run.tensorboard,
        )
        # The health monitor watches the fetched losses at flush
        # boundaries (the tracer beside it in run.obs was built first).
        obs = cfg.run.obs
        self.health = (
            HealthMonitor(obs.divergence_factor) if obs.health else None
        )
        # Compiled-program observatory (run.obs.executables): the
        # per-fit AOT registry — installed around fit() so the engines'
        # instrumented jit sites route through it; drained into
        # `executable_compiled`/`retrace`/`hbm_watermark` records at
        # flush boundaries. The same lowering jit would produce —
        # registry-on is bitwise-identical to registry-off
        # (test-pinned).
        self._exec_reg: Optional[ExecutableRegistry] = None
        if obs.executables:
            self._exec_reg = ExecutableRegistry(
                hbm_budget_bytes=obs.hbm_budget_mb * 2**20,
                device_capacity_bytes=exec_mod.device_hbm_capacity(),
                tracer=self.tracer,
            )
        self._counters_on = obs.counters
        # Federation health observatory (run.obs.population, obs/
        # population.py): population/data-plane telemetry — coverage,
        # draw split, staleness, pager/store health, fairness — folded
        # into one `population_health` record per flush window. Purely
        # observational host-side accounting: no device work, no rng
        # consumption, and every count-based column is a pure function
        # of the cohort schedule, so records are engine-parity pinned
        # (the `*_ms` wall-clock fields are the one exception).
        self._population = None
        if obs.population.enabled:
            from colearn_federated_learning_tpu.obs.population import (
                PopulationTracker,
            )

            self._population = PopulationTracker(
                self.fed.num_clients,
                top_k=obs.population.top_k,
                hll_bits=obs.population.hll_bits,
                recency_capacity=obs.population.recency_capacity,
            )

        # Determinism flight recorder (run.obs.digest, obs/digest.py):
        # per-boundary canonical state digests chained prev → self in
        # the JSONL, chain head riding every checkpoint. Read-only over
        # fetched state — digest-on runs are bitwise-identical to
        # digest-off (test-pinned); the O(P) fetch+hash is amortized by
        # `every` and the window fold keeps the schedule/wire
        # components invariant to flush cadence and fuse_rounds.
        self._digest_on = bool(obs.digest.enabled)
        self._digest_every = max(1, int(obs.digest.every))
        self._digest_cohorts: Dict[int, np.ndarray] = {}
        self._digest_window = (
            digest_mod.RoundWindow() if self._digest_on else None
        )
        self._digest_prev = digest_mod.GENESIS
        self._digest_prev_round = 0

        # Host-side round-input construction: the C++ threaded pipeline
        # (native/round_pipeline.cpp) builds + prefetches index tensors off
        # the round loop's critical path; NumPy path otherwise.
        self._native = None
        if self._poisson and cfg.run.host_pipeline == "native":
            raise ValueError(
                "run.host_pipeline=native does not support "
                "server.sampling=poisson (variable cohorts are padded "
                "host-side); use host_pipeline=numpy"
            )
        if (cfg.run.host_pipeline in ("auto", "native")
                and not self._poisson
                # the device control plane derives round inputs
                # in-program — there is no host slab to prefetch
                # (validate() rejects explicit 'native'; 'auto' skips)
                and not self._cp_device
                # bucketed grids vary per round; the C++ pipeline builds
                # ONE fixed shape (validate() rejects the explicit
                # 'native' pairing; 'auto' degrades to NumPy here).
                # snapshot-fed sampling (adaptive, or streaming with a
                # ledger sketch): the pipeline prefetches FUTURE cohorts
                # and treats resubmission as a no-op, so a snapshot
                # refresh between prefetch and dispatch would silently
                # serve a stale cohort's tensors (validate() rejects
                # explicit 'native'; 'auto' degrades). Store-backed
                # federations skip it too: the pipeline materializes the
                # full per-client index lists the store exists to avoid.
                and self._bucket_ladder is None
                and not self._snapshot_refresh
                and not cfg.data.store.dir):
            from colearn_federated_learning_tpu import native

            with span("setup.engine"):  # g++ on first use, then the load
                if native.available():
                    self._native = native.NativeRoundPipeline(
                        self.fed.client_indices,
                        self.shape.local_epochs, self.shape.steps_per_epoch,
                        self.shape.batch_size, self.shape.cap,
                        seed=cfg.run.seed,
                        # spec-input engines rebuild the mask on device —
                        # the pipeline skips the float mask slab entirely
                        build_mask=not self._spec_inputs,
                    )
                elif cfg.run.host_pipeline == "native":
                    raise RuntimeError(
                        f"run.host_pipeline=native but the C++ pipeline "
                        f"cannot be built: {native.build_error()}"
                    )

    # ------------------------------------------------------------------

    def _check_secagg_bounds(self) -> None:
        """Worst-case fixed-point range checks for secure aggregation
        (see ServerConfig). The max FedAvg weight comes from the
        RESOLVED aggregation mode (``uniform`` ⇒ 1.0), not the sampling
        mode — e.g. client-DP-forced uniform weights must not inflate
        the bound by the example cap.

        - Per-client: ``max_w·clip/quant_step`` must stay < 2^24 for
          the f32 rounding in ``_secagg_upload`` to remain integer-
          exact. Warn only — realized deltas usually sit orders of
          magnitude below the clip bound.
        - Aggregate: the cohort-summed bound must stay < 2^31 or the
          int32 accumulator can WRAP, silently corrupting the round —
          refuse to run unless the config explicitly opts in via
          ``server.secagg_allow_wrap_risk=true``.
        """
        import logging

        log = logging.getLogger(__name__)
        s = self.cfg.server
        max_w = 1.0 if self._agg_mode == "uniform" else float(self.shape.cap)
        per_client = max_w * s.clip_delta_norm / s.secagg_quant_step
        if per_client >= 2**24:
            log.warning(
                "secure_aggregation per-client fixed-point bound "
                "max_weight*clip/quant_step = %.3g >= 2^24: f32 rounding "
                "in the quantizer can lose integer exactness for clients "
                "that approach the clip bound — consider a larger "
                "secagg_quant_step",
                per_client,
            )
        # poisson: worst case is the static cap (more than K clients can
        # realize); fixed: the cohort size
        bound = (self._poisson_cap or s.cohort_size) * per_client
        if bound >= 2**31:
            if s.secagg_allow_wrap_risk:
                log.warning(
                    "secure_aggregation worst-case aggregate bound "
                    "cohort*max_weight*clip/quant_step = %.3g >= 2^31 "
                    "(secagg_allow_wrap_risk=true): aggregates WILL wrap "
                    "if clients actually reach the clip bound",
                    bound,
                )
            else:
                min_step = (
                    s.cohort_size * max_w * s.clip_delta_norm / (2**31 - 1)
                )
                raise ValueError(
                    f"secure_aggregation worst-case aggregate bound "
                    f"cohort*max_weight*clip/quant_step = {bound:.3g} >= "
                    f"2^31 — an int32 wrap would silently corrupt the "
                    f"aggregate. Raise server.secagg_quant_step to at "
                    f"least {min_step:.3g}, or set "
                    f"server.secagg_allow_wrap_risk=true to accept the "
                    f"risk explicitly"
                )

    def _param_shapes(self):
        """The params tree's shapes (eval_shape: no compute, no device
        memory). Cached: the model is traced once for it."""
        if self._param_shapes_cache is None:
            from colearn_federated_learning_tpu.client.trainer import (
                normalize_input,
            )

            dummy = jax.ShapeDtypeStruct(
                (1,) + self.fed.train_x.shape[1:],
                self.fed.train_x.dtype,  # LM corpora are int tokens — an
                # f32 dummy would crash nn.Embed's integer check
            )
            self._param_shapes_cache = jax.eval_shape(
                lambda d: self.model.init(
                    jax.random.PRNGKey(0), normalize_input(d), train=False
                )["params"],
                dummy,
            )
        return self._param_shapes_cache

    def _count_block_steps(self, mask, shape) -> None:
        """Under the megabatch layout: the round's client-steps, the
        dead ones and those the block trainer skips
        (``obs/counters.block_step_counts``), on the span that built
        the inputs."""
        if self._block and self.tracer.enabled:
            self.tracer.count(
                "round.host_inputs.slab_build",
                **block_step_counts(
                    mask, shape.steps, shape.batch_size, shape.local_epochs,
                    *self._block,
                ))

    def _count_dp_params(self) -> None:
        """Under example-level DP-SGD: the trained parameters and those
        of them whose per-example gradients the trainer never forms
        (``privacy/dp.ghost_param_counts``: the trainer's own predicate
        on the model's shapes), once a round on the span that built the
        inputs."""
        if not (self.cfg.dp.enabled and self.tracer.enabled):
            return
        if self._dp_param_counts_cache is None:
            from colearn_federated_learning_tpu.privacy.dp import (
                ghost_param_counts,
            )

            frozen = ({} if self.frozen_base is None
                      else {"frozen": self.frozen_base})
            self._dp_param_counts_cache = ghost_param_counts(
                make_loss_fn(self.model, self.task), self.cfg.dp,
                self._param_shapes(),
                *(jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                  for a in (self.fed.train_x, self.fed.train_y)), **frozen)
        self.tracer.count("round.host_inputs.slab_build",
                          **self._dp_param_counts_cache)

    def _param_stats(self) -> tuple:
        """(n_coords, bytes) of one params tree at run.param_dtype, via
        eval_shape (no compute, no device memory — shapes only). Cached:
        the HBM pre-flight and the per-round comm-byte model share it."""
        if self._param_stats_cache is None:
            leaves = jax.tree.leaves(self._param_shapes())
            coords = sum(int(np.prod(l.shape)) for l in leaves)
            nbytes = sum(
                int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves
            )
            self._param_stats_cache = (coords, nbytes)
        return self._param_stats_cache

    def _param_bytes(self) -> int:
        return self._param_stats()[1]

    def _full_param_stats(self) -> tuple:
        """(n_coords, bytes) of the FULL model — the trained tree's twin
        with LoRA off. Equals :meth:`_param_stats` for non-LoRA runs;
        under the adapter plane it is the frozen base model's size, the
        denominator of ``wire_reduction_vs_full``."""
        if not self._lora:
            return self._param_stats()
        if self._full_param_stats_cache is None:
            from colearn_federated_learning_tpu.client.trainer import (
                normalize_input,
            )

            dummy = jax.ShapeDtypeStruct(
                (1,) + self.fed.train_x.shape[1:], self.fed.train_x.dtype
            )
            shapes = jax.eval_shape(
                lambda d: self.model.base.init(
                    jax.random.PRNGKey(0), normalize_input(d), train=False
                )["params"],
                dummy,
            )
            leaves = jax.tree.leaves(shapes)
            coords = sum(int(np.prod(l.shape)) for l in leaves)
            # the full-delta twin ships run.param_dtype; the base itself
            # is stored in the local dtype (frozen_base_bytes)
            self._full_param_stats_cache = (
                coords,
                coords * jnp.dtype(
                    _DTYPES[self.cfg.run.param_dtype]).itemsize,
            )
            self._frozen_base_bytes_cache = sum(
                int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)
        return self._full_param_stats_cache

    def frozen_base_bytes(self) -> int:
        """Bytes of a LoRA model's frozen base as every chip holds it
        (0 without one): what the HBM pre-flight counts."""
        if not self._lora:
            return 0
        self._full_param_stats()
        return self._frozen_base_bytes_cache

    def wire_reduction_vs_full(self) -> float:
        """Analytic per-client upload-byte ratio full-delta ÷ trained
        delta on THIS config's wire format (compression applies to both
        twins, so it cancels) — the logged LoRA communication win,
        exactly 1.0 for non-LoRA runs. Pure function of the config, so
        every engine logs the identical number."""
        if self._wire_reduction_cache is None:
            coords, p_bytes = self._param_stats()
            f_coords, f_bytes = self._full_param_stats()
            up = round_comm_bytes(
                self.cfg.server, 1, 1, coords, p_bytes
            )["upload_bytes"]
            full = round_comm_bytes(
                self.cfg.server, 1, 1, f_coords, f_bytes
            )["upload_bytes"]
            self._wire_reduction_cache = full / max(up, 1)
        return self._wire_reduction_cache

    def _check_memory_budget(self) -> None:
        """Construction-time HBM pre-flight (VERDICT r4 missing-#4):
        estimate the PERSISTENT per-device footprint and fail fast with
        an actionable breakdown when it exceeds the budget. At the
        north-star scales the N·|params| stacks dominate: gossip
        N=1000 × ResNet-18 is ~44 GB f32 on one lane — impossible on a
        16 GB chip, and without this check the failure is an opaque
        RESOURCE_EXHAUSTED minutes into compilation. Transients
        (activations, collective buffers) are NOT modeled; the check is
        a lower bound on usage, so exceeding it is definitely fatal."""
        budget_gb = self.cfg.run.hbm_gb
        if budget_gb < 0:
            return
        if budget_gb == 0:
            # local_devices: under multi-process, jax.devices()[0] can
            # belong to ANOTHER process and memory_stats then raises
            dev = jax.local_devices()[0]
            if dev.platform == "cpu":
                return  # host RAM; no meaningful fixed budget
            limit = (dev.memory_stats() or {}).get("bytes_limit")
            if not limit:
                # no guessed capacity: a budget the device did not
                # report would make the pre-flight pass or fail on a
                # number nobody measured
                raise ValueError(
                    f"device {dev.device_kind!r} ({dev.platform}) reports "
                    f"no memory_stats()['bytes_limit']; set run.hbm_gb to "
                    f"its HBM capacity in GiB (or -1 to skip the "
                    f"pre-flight)"
                )
            budget_gb = limit / 2**30
        gib = float(2**30)
        p_bytes = self._param_bytes()
        lanes = self.mesh.shape[mesh_lib.CLIENT_AXIS] if self.mesh else 1
        parts: Dict[str, float] = {}
        if not self._stream:
            parts["corpus (replicated)"] = (
                self.fed.train_x.nbytes + self.fed.train_y.nbytes
            ) / gib
        opt_factor = {"mean": 0, "fedavgm": 1, "fedadam": 2, "fedyogi": 2}[
            self.cfg.server.optimizer
        ]
        parts["params + server opt"] = p_bytes * (1 + opt_factor) / gib
        if self._lora:
            parts["frozen base (replicated)"] = (
                self.frozen_base_bytes() / gib)
        state_itemsize = (
            2 if self.cfg.server.client_state_dtype == "bfloat16" else 4
        )
        if self.store_state:
            rows = self._state_rows / lanes
            n_trees = 1 + (1 if self.stateful else 0)  # store (+ c_global)
            parts["per-client state store / lane"] = (
                rows * p_bytes * state_itemsize / 4 * n_trees / gib
            )
        if self.gossip:
            parts["gossip replica stack / lane"] = (
                (self.fed.num_clients / lanes) * p_bytes / gib
            )
        if self.fedbuff:
            window = 2 * self.cfg.server.async_max_staleness + 1
            parts["fedbuff history ring"] = window * p_bytes / gib
        total = sum(parts.values())
        if total > 0.9 * budget_gb:
            breakdown = "; ".join(f"{k}: {v:.2f} GiB" for k, v in parts.items())
            raise ValueError(
                f"persistent HBM footprint ≈ {total:.2f} GiB exceeds 90% "
                f"of the {budget_gb:.1f} GiB device budget ({breakdown}). "
                f"Remedies: data.placement=stream (drops the replicated "
                f"corpus), server.client_state_dtype=bfloat16 (halves the "
                f"state store), more mesh lanes (stacks shard over "
                f"lanes), fewer clients, or a smaller model. Set "
                f"run.hbm_gb to adjust the budget or -1 to disable this "
                f"check."
            )

    def preflight(self) -> Dict[str, Any]:
        """OOM preflight (``colearn preflight``): walk ONE round of the
        real dispatch path with a preflight-mode executable registry —
        every instrumented jit site lowers and compiles (XLA memory
        analysis = the predicted peak) but returns abstract
        ``ShapeDtypeStruct`` outputs instead of executing, so output
        and temp buffers are never allocated. Host-side inputs (params,
        cohort slabs) ARE staged — they must fit anyway for the run to
        start; the unknown the preflight answers is the program's
        working set. Returns the registry's report (predicted peak
        bytes + per-program dominant buffers); raises
        :class:`HbmBudgetError` when ``run.obs.hbm_budget_mb`` is set
        and exceeded.

        Requires a fully-jitted round program: the sequential oracle's
        eager python loop cannot run on abstract values."""
        if self.cfg.run.engine != "sharded":
            raise ValueError(
                "preflight requires run.engine=sharded: the sequential "
                "oracle's eager per-client loop cannot run on abstract "
                "outputs"
            )
        obs = self.cfg.run.obs
        reg = ExecutableRegistry(
            preflight=True,
            hbm_budget_bytes=obs.hbm_budget_mb * 2**20,
            device_capacity_bytes=exec_mod.device_hbm_capacity(),
            tracer=self.tracer,
        )
        prev = exec_mod.current()
        exec_mod.install(reg)
        try:
            state = self._place_state(self.init_state())
            try:
                self.run_round(state, 0)
            except HbmBudgetError:
                raise
            except Exception:
                # post-dispatch host unwinding on abstract outputs
                # (metric slicing, store scatter) is expected to fail —
                # the programs were already captured at that point. An
                # empty registry means the dispatch itself never
                # lowered: that IS the preflight failure.
                if not reg.preflight_report()["programs"]:
                    raise
        finally:
            if prev is not None:
                exec_mod.install(prev)
            else:
                exec_mod.uninstall()
        return reg.preflight_report()

    def _local_dtype(self):
        d = self.cfg.run.local_param_dtype
        return _DTYPES[d] if d else None

    def _put(self, arr, sharding):
        if sharding is None:
            return jax.device_put(arr)
        if jax.process_count() > 1:
            from colearn_federated_learning_tpu.parallel.distributed import (
                host_local_array,
            )

            return host_local_array(arr, sharding)
        return jax.device_put(arr, sharding)

    def _put_data(self, arr):
        return self._put(arr, self._data_sharding)

    def _init_frozen_base(self, init_rng, dummy, seed: int) -> None:
        """Draw a LoRA model's frozen base for ``seed`` and place it,
        replicated over the lanes: one jitted program whose outputs are
        born on the device in their stored dtype, leaf by leaf (a base
        of gigabytes never exists on the host, nor whole in float32).
        A pure function of the seed: never checkpointed, re-derived on
        resume. Drawn once per seed; the round programs, eval and
        export read ``frozen_base``."""
        with self.tracer.span("init.frozen_base"):
            self.frozen_base = None  # the old seed's, before the new
            draw = jax.jit(self.model.init_frozen,
                           out_shardings=self._data_sharding)
            self.frozen_base = jax.block_until_ready(draw(init_rng, dummy))
            self._frozen_base_seed = seed

    def _round_data(self, train_x):
        """The corpus argument of a round program: the examples, with
        the frozen base beside them where the model has one
        (client/trainer.RoundData)."""
        if not self._lora:
            return train_x
        if self.frozen_base is None:
            raise RuntimeError(
                "model.lora: the frozen base is drawn by init_state(); "
                "call it before running a round")
        return RoundData(train_x, self.frozen_base)

    def init_state(self, seed: Optional[int] = None) -> Dict[str, Any]:
        with self.tracer.span("setup.init_state"):
            return self._init_state(seed)

    def _init_state(self, seed: Optional[int]) -> Dict[str, Any]:
        seed = self.cfg.run.seed if seed is None else seed
        rng = jax.random.PRNGKey(seed)
        init_rng, run_rng = jax.random.split(rng)
        from colearn_federated_learning_tpu.client.trainer import normalize_input

        dummy = normalize_input(jnp.asarray(self.fed.train_x[:1]))
        with self.tracer.span("setup.init.model"):  # flax's eager init
            variables = self.model.init(init_rng, dummy, train=False)
        params = variables["params"]
        if self._lora and self._frozen_base_seed != seed:
            self._init_frozen_base(init_rng, dummy, seed)
        with self.tracer.span("setup.init.server_opt"):
            server_opt_state = self.server_opt_init(params)
        state = {
            "params": params,
            "server_opt_state": server_opt_state,
            "round": 0,
            "rng_key": run_rng,
        }
        if self.store_state:
            # scaffold: c (replicated) + all-clients cᵢ; feddyn: h + gᵢ
            # — same shapes; error feedback: per-client eᵢ residuals
            # only (no global). The template is host numpy (cheap: zeros
            # are lazily allocated); _place_state moves it to the device
            # store (sharded engine) or keeps it writable numpy
            # (sequential oracle). Rows are lane-padded under the
            # sharded engine; pad rows are never addressed.
            if self.stateful:
                state["c_global"] = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
            state["c_clients"] = jax.tree.map(
                lambda p: np.zeros((self._state_rows,) + p.shape, np.float32),
                params,
            )
        if self._ledger_on:
            # per-client forensic ledger rows (count, flagged, EMAs);
            # dense: row index == client id; paged: row index == HOT
            # SLOT (the driver remaps ids — see LedgerPager), with the
            # cold spill + slot bookkeeping riding alongside. No lane
            # padding either way (the store is replicated — a few KB).
            # Pads/non-residents scatter out of bounds and drop.
            from colearn_federated_learning_tpu.obs.ledger import (
                LEDGER_WIDTH,
            )

            state["ledger"] = np.zeros(
                (self._ledger_rows, LEDGER_WIDTH), np.float32
            )
            if self._pager is not None:
                state["ledger_cold"] = np.zeros(
                    (self.fed.num_clients, LEDGER_WIDTH), np.float32
                )
                state["ledger_slots"] = np.full(
                    self._ledger_rows, -1, np.int64
                )
                state["ledger_slot_used"] = np.full(
                    self._ledger_rows, -1, np.int64
                )
        if self._adaptive:
            # the adaptive sampler's ACTIVE ledger snapshot (host-side,
            # refreshed at log_every round boundaries) rides the
            # checkpoint so a resumed run scores rounds between
            # snapshot boundaries exactly like the straight run did.
            # Column-slimmed (PR 9): only the three scored columns
            # (sampler.SNAPSHOT_COLS) are fetched and persisted.
            from colearn_federated_learning_tpu.server.sampler import (
                SNAPSHOT_COLS,
            )

            state["ledger_snapshot"] = np.zeros(
                (self.fed.num_clients, len(SNAPSHOT_COLS)), np.float32
            )
            state["ledger_snapshot_round"] = 0
        if self._streaming and self._snapshot_refresh:
            # the streaming sampler's fixed-size score sketch: columnar
            # (ids, scored stats) arrays bounded by sketch_size — the
            # O(1)-in-num_clients replacement for the dense snapshot
            state["ledger_sketch_ids"] = np.full(
                len(self._sketch_ids), -1, np.int32
            )
            state["ledger_sketch_stats"] = np.zeros(
                self._sketch_stats.shape, np.float32
            )
            state["ledger_snapshot_round"] = 0
        if self.gossip:
            # every client starts at the same point (the standard
            # consensus init); the stack is host numpy until
            # _place_state shards it over the mesh
            state["replicas"] = jax.tree.map(
                lambda p: np.broadcast_to(
                    np.asarray(p)[None], (self.fed.num_clients,) + p.shape
                ).copy(),
                params,
            )
        if self.fedbuff:
            s_max = self.cfg.server.async_max_staleness
            window = 2 * s_max + 1
            k = self.cfg.server.cohort_size
            m = k * s_max  # in-flight concurrency
            state["history"] = jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (window,) + p.shape), params
            )
            qrng = np.random.default_rng((seed, 8191))
            state["queue_clients"] = qrng.choice(
                self.fed.num_clients, size=m,
                replace=m > self.fed.num_clients,
            ).astype(np.int32)
            state["queue_versions"] = np.zeros(m, np.int32)
            state["queue_finish"] = self._client_durations(
                state["queue_clients"], qrng
            )
            state["queue_seq"] = np.arange(m, dtype=np.int32)
            state["queue_next_seq"] = m
            if self._versions > 1:
                # multi-version lines: line 0 keeps the legacy keys
                # above; each extra line is an independent FedBuff
                # instance (own params/opt/history ring/queue) seeded
                # from its own qrng stream. line_* carries the
                # retirement generation bookkeeping per line.
                V = self._versions
                state["queue_gen"] = np.zeros(m, np.int32)
                for li in range(1, V):
                    qrng_l = np.random.default_rng((seed, 8191, li))
                    state[f"params_l{li}"] = params
                    state[f"server_opt_state_l{li}"] = (
                        self.server_opt_init(params)
                    )
                    state[f"history_l{li}"] = jax.tree.map(
                        lambda p: jnp.broadcast_to(
                            p[None], (window,) + p.shape
                        ), params,
                    )
                    state[f"queue_clients_l{li}"] = qrng_l.choice(
                        self.fed.num_clients, size=m,
                        replace=m > self.fed.num_clients,
                    ).astype(np.int32)
                    state[f"queue_versions_l{li}"] = np.zeros(m, np.int32)
                    state[f"queue_finish_l{li}"] = self._client_durations(
                        state[f"queue_clients_l{li}"], qrng_l
                    )
                    state[f"queue_seq_l{li}"] = np.arange(m, dtype=np.int32)
                    state[f"queue_next_seq_l{li}"] = m
                    state[f"queue_gen_l{li}"] = np.zeros(m, np.int32)
                state["line_gen"] = np.zeros(V, np.int32)
                state["line_birth"] = np.zeros(V, np.int32)
                state["line_absorbed"] = np.zeros(V, np.int64)
        if self._hier:
            # per-edge reputation trust for the core tier (EMA over
            # edge liveness; consumed when core_aggregator="reputation",
            # always maintained as a health signal). Checkpointed.
            state["edge_trust"] = np.ones(
                self.cfg.server.hierarchy.num_edges, np.float32
            )
        # digest-chain head (run.obs.digest): uint32 [hash_lo, hash_hi,
        # round], all-zero = genesis. ALWAYS in the template — orbax
        # restore requires template/checkpoint key agreement, and a
        # digest-off run must be able to restore a digest-on run's
        # checkpoint (and vice versa). Popped from live state at fit
        # start (_fit_body) and re-injected at every save site.
        state["digest_head"] = np.zeros(3, np.uint32)
        return state

    def _client_durations(self, clients: np.ndarray, rng) -> np.ndarray:
        """Simulated train durations (server steps, 1..S) for the given
        clients: SIZE-CORRELATED (VERDICT r2 weak-#4) — a client's local
        work is its capped example count, so the per-client base duration
        is its work rank quantile-mapped into 1..S, plus ±1 stochastic
        jitter. Big-data clients therefore finish later and accumulate
        more staleness, which couples the staleness distribution to the
        data heterogeneity — the regime async FL is designed for.
        Durations stay ≤ S, so the pop-K-earliest 2S staleness bound
        (and the 2S+1 ring sizing) is unchanged."""
        s_max = self.cfg.server.async_max_staleness
        base = self._duration_base[clients]
        jitter = rng.integers(-1, 2, size=len(clients))
        return np.clip(base + jitter, 1, s_max).astype(np.int32)

    def _place_state(self, state: Dict[str, Any]) -> Dict[str, Any]:
        """Replicate params/opt state over the mesh (fresh init or restore)."""
        with self.tracer.span("setup.place_state"):
            return self._place_state_on_mesh(state)

    def _place_state_on_mesh(self, state: Dict[str, Any]) -> Dict[str, Any]:
        if self._data_sharding is not None:
            state["params"] = self._put_data(state["params"])
            state["server_opt_state"] = self._put_data(state["server_opt_state"])
            if self.stateful:
                state["c_global"] = self._put_data(state["c_global"])
        if self.store_state:
            if self._data_sharding is not None:
                # device-resident store: client-sharded over the mesh at
                # the configured storage dtype; HBM budget is
                # state_rows·|params| at that dtype ÷ lanes per chip.
                # Cast on HOST (ml_dtypes numpy bf16) and hand numpy to
                # device_put so only each chip's shard is uploaded — a
                # jnp cast would transiently materialize the FULL store
                # on one device, an L× spike over the per-chip budget.
                if self.cfg.server.client_state_dtype == "bfloat16":
                    import ml_dtypes

                    np_dt = ml_dtypes.bfloat16
                else:
                    np_dt = np.float32

                def _place_store(a):
                    if isinstance(a, jax.Array) and a.dtype == np_dt:
                        # warm-start state from a previous fit() on this
                        # Experiment: already device-resident + sharded
                        # (fetching it would break under multi-host)
                        return a
                    return self._put(
                        np.asarray(a).astype(np_dt, copy=False),
                        self._client_sharding,
                    )

                state["c_clients"] = jax.tree.map(
                    _place_store, state["c_clients"]
                )
            else:
                # sequential oracle: restored checkpoints arrive as jax
                # arrays; the host scatter path needs writable numpy
                # (fresh init already is)
                state["c_clients"] = jax.tree.map(
                    lambda a: a
                    if isinstance(a, np.ndarray) and a.flags.writeable
                    else np.array(a, dtype=np.float32, copy=True),
                    state["c_clients"],
                )
        if self._ledger_on:
            # ledger (dense, or the paged HOT set): replicated device
            # array (tiny); a warm-start or restored ledger arrives as
            # jax/numpy — both place fine
            state["ledger"] = self._put(
                jnp.asarray(np.asarray(state["ledger"], np.float32)),
                self._data_sharding,
            )
            if self._pager is not None:
                # cold spill + slot bookkeeping stay HOST-side: load
                # them into the pager's mmap/maps and re-point the
                # state at the live structures (so later checkpoints
                # capture the current paging state without copies)
                self._pager.load_state(
                    state["ledger_slots"], state["ledger_slot_used"],
                    state["ledger_cold"],
                )
                state["ledger_cold"] = self._pager.cold
                state["ledger_slots"] = self._pager.slot_clients
                state["ledger_slot_used"] = self._pager.slot_used
        if self._adaptive:
            # the sampler snapshot stays HOST-side (the sampler is host
            # code); a restored checkpoint hands back jax arrays
            state["ledger_snapshot"] = np.asarray(
                state["ledger_snapshot"], np.float32
            )
            state["ledger_snapshot_round"] = int(
                np.asarray(state["ledger_snapshot_round"])
            )
        if self._streaming and self._snapshot_refresh:
            state["ledger_sketch_ids"] = np.asarray(
                state["ledger_sketch_ids"], np.int32
            )
            state["ledger_sketch_stats"] = np.asarray(
                state["ledger_sketch_stats"], np.float32
            )
            state["ledger_snapshot_round"] = int(
                np.asarray(state["ledger_snapshot_round"])
            )
        if self.gossip:
            # warm-start replicas from a previous fit() on this
            # Experiment are already device-resident + client-sharded;
            # fresh init / orbax restore arrive as host numpy and only
            # each chip's shard is uploaded (same rationale as the
            # scaffold store placement above)
            state["replicas"] = jax.tree.map(
                lambda a: a if isinstance(a, jax.Array)
                else self._put(np.asarray(a), self._client_sharding),
                state["replicas"],
            )
        if self.fedbuff:
            V = self._versions
            qkeys = ["queue_clients", "queue_versions", "queue_finish",
                     "queue_seq"] + (["queue_gen"] if V > 1 else [])
            for li in range(V):
                sfx = "" if li == 0 else f"_l{li}"
                if self._data_sharding is not None:
                    state["history" + sfx] = self._put_data(
                        state["history" + sfx]
                    )
                    if sfx:
                        # extra lines' trees place like line 0's (which
                        # went through the generic params placement at
                        # the top of this method)
                        state["params" + sfx] = self._put_data(
                            state["params" + sfx]
                        )
                        state["server_opt_state" + sfx] = self._put_data(
                            state["server_opt_state" + sfx]
                        )
                for key in qkeys:
                    a = state[key + sfx]
                    if not (isinstance(a, np.ndarray) and a.flags.writeable):
                        state[key + sfx] = np.array(
                            a, dtype=np.int32, copy=True
                        )
                state["queue_next_seq" + sfx] = int(
                    state["queue_next_seq" + sfx]
                )
            if V > 1:
                for key, dt in (("line_gen", np.int32),
                                ("line_birth", np.int32),
                                ("line_absorbed", np.int64)):
                    a = state[key]
                    if not (isinstance(a, np.ndarray) and a.flags.writeable
                            and a.dtype == dt):
                        state[key] = np.array(a, dtype=dt, copy=True)
        if self._hier:
            a = state["edge_trust"]
            if not (isinstance(a, np.ndarray) and a.flags.writeable
                    and a.dtype == np.float32):
                state["edge_trust"] = np.array(
                    a, dtype=np.float32, copy=True
                )
        return state

    # ---- heterogeneity-aware round shapes (run.shape_buckets) --------

    def _round_bucket_spe(self, round_idx: int) -> int:
        """The ladder rung (steps_per_epoch) for one round: smallest
        rung whose grid holds the SAMPLED cohort's max capped shard.
        Pure in (seed, round) — the sampler is stateless, so the
        prefetch worker, a resume, and the fused chunk-max computation
        all agree without coordination."""
        spe = self._bucket_cache.get(round_idx)
        if spe is None:
            cohort = np.asarray(self.sampler.sample(round_idx))
            max_need = (
                int(self._sizes_capped[cohort].max()) if len(cohort) else 1
            )
            need = max(1, -(-max_need // self.shape.batch_size))
            spe = pick_bucket(need, self._bucket_ladder)
            self._bucket_cache[round_idx] = spe
        return spe

    def _bucket_shape(self, spe: int) -> RoundShape:
        import dataclasses as _dc

        shp = self._bucket_shapes.get(spe)
        if shp is None:
            shp = _dc.replace(self.shape, steps_per_epoch=spe)
            self._bucket_shapes[spe] = shp
        return shp

    def _round_shape(self, round_idx: int) -> RoundShape:
        """The round's grid shape: a ladder rung under shape buckets,
        the federation-max legacy shape otherwise."""
        if self._bucket_ladder is None:
            return self.shape
        return self._bucket_shape(self._round_bucket_spe(round_idx))

    def _bucket_compile_span(self, round_idx: int, steps: int):
        """Context manager wrapping the FIRST dispatch on a new ladder
        rung: brackets the tracer's backend_compile counters and logs a
        `shape_bucket` event attributing the rung's retrace cost — the
        per-bucket compile accounting the ≤-ladder-size budget is
        asserted against (tests/test_shape_buckets.py)."""
        from contextlib import contextmanager

        @contextmanager
        def span():
            if self._exec_reg is not None:
                # every dispatch site enters this span — the registry's
                # records carry the round they were compiled on
                self._exec_reg.round = round_idx + 1
            if self._bucket_ladder is None or steps in self._seen_buckets:
                yield
                return
            self._seen_buckets.add(steps)
            c0, s0 = self.tracer.compile_stats()
            yield
            c1, s1 = self.tracer.compile_stats()
            self.logger.log({
                "event": "shape_bucket",
                "round": round_idx + 1,
                "bucket_steps": int(steps),
                "ladder_steps": [
                    r * self.cfg.client.local_epochs
                    for r in self._bucket_ladder
                ],
                "compiles": int(c1 - c0),
                "compile_ms": round((s1 - s0) * 1000.0, 3),
            })

        return span()

    def _host_inputs(self, round_idx: int, shape: Optional[RoundShape] = None,
                     build_slab: bool = True):
        """All host-side work for one round: sampling, index construction,
        dropout weights, and (stream mode) the slab gather. Pure in
        (seed, round) — safe to run ahead on a worker thread.
        ``shape`` overrides the round's grid (the fused chunk-max path);
        default is the round's own bucket rung (or the legacy full
        shape). Under ``_spec_inputs`` the third return slot carries the
        [K, 2] mask SPEC instead of the full float32 mask.
        ``build_slab=False`` skips the per-round stream slab — the fused
        chunk path gathers ONE union slab over the whole chunk instead."""
        # named control-plane sub-spans (children of round.host_inputs):
        # exactly the work the device control plane removes,
        # attributable line by line
        with self.tracer.span("round.host_inputs.sampler"):
            if self.gossip and self._gossip_partial == 0:
                # full participation: row i of the round tensors IS
                # client i (the ring order is the client-id order,
                # every round)
                cohort = np.arange(self.fed.num_clients, dtype=np.int64)
            else:
                # centralized cohorts, or partial-participation
                # gossip's per-round active subset (uniform without
                # replacement)
                cohort = self.sampler.sample(round_idx)
        if shape is None:
            shape = self._round_shape(round_idx)
        host_rng = np.random.default_rng((self.cfg.run.seed, 7919, round_idx))
        with self.tracer.span("round.host_inputs.slab_build"):
            if self._native is not None:
                self._native.submit(round_idx, cohort)  # no-op if prefetched
                # overlap: the NEXT dispatch's tensors build on C++
                # worker threads while the device executes this one.
                # Under run.fuse_rounds > 1 a dispatch consumes a whole
                # chunk, so the look-ahead window is `fuse` rounds of
                # index slabs per submit (duplicate submits are no-ops
                # in the pipeline).
                ahead = max(1, self.cfg.run.fuse_rounds)
                for j in range(1, ahead + 1):
                    nxt = round_idx + j
                    if nxt < self.cfg.server.num_rounds:
                        self._native.submit(nxt, self.sampler.sample(nxt))
                idx, mask, n_ex = self._native.fetch(round_idx, len(cohort))
                if self._spec_inputs:
                    # the pipeline skipped the mask slab
                    # (build_mask=False); the spec is analytic — native
                    # packs each epoch's min(|shard|, cap) real indices
                    # contiguously
                    take = self._sizes_capped[np.asarray(cohort)]
                    mask = np.stack(
                        [take, np.full(len(cohort), shape.steps, np.int64)],
                        1,
                    ).astype(np.int32)
            elif self._spec_inputs:
                idx, mask, n_ex = make_round_spec(
                    self.fed, cohort, shape, host_rng
                )
            else:
                idx, mask, n_ex = make_round_indices(
                    self.fed, cohort, shape, host_rng
                )
        with self.tracer.span("round.host_inputs.churn"):
            mask, n_ex = self._apply_failures(
                mask, n_ex, len(cohort), host_rng, round_idx=round_idx,
                shape=shape, cohort=cohort,
            )
        if self._poisson:
            cap, b = self._poisson_cap, len(cohort)
            if b > cap:
                raise RuntimeError(
                    f"poisson cohort {b} exceeded the static cap {cap} "
                    f"(a ~5-sigma event; its exact probability is logged "
                    f"as dp_delta_abort and is part of the DP delta). "
                    f"Aborting rather than silently truncating — rerun "
                    f"with a different seed or a larger cohort_size."
                )
            pad = cap - b
            if pad:
                # pad id == num_clients: OUT OF RANGE by construction, so
                # state-store scatters drop it and no real client's row
                # can be touched by a pad slot; pad rows carry zero mask
                # and zero weight (exact no-ops, the dropout machinery)
                cohort = np.concatenate(
                    [cohort, np.full(pad, self.fed.num_clients, cohort.dtype)]
                )
                idx = np.concatenate(
                    [idx, np.zeros((pad,) + idx.shape[1:], idx.dtype)]
                )
                mask = np.concatenate(
                    [mask, np.zeros((pad,) + mask.shape[1:], mask.dtype)]
                )
                n_ex = np.concatenate([n_ex, np.zeros(pad, n_ex.dtype)])
        self._count_block_steps(mask, shape)
        self._count_dp_params()
        slab = (
            self._stream_slab(idx) if self._stream and build_slab else None
        )
        return cohort, idx, mask, n_ex, slab

    def _apply_failures(self, mask, n_ex, k, host_rng, round_idx=None,
                        shape=None, cohort=None):
        """Straggler truncation + dropout zeroing — shared by the sync
        cohort path and the async (fedbuff) scheduler. Realized counts
        are recorded per round for the telemetry counters (this runs on
        the prefetch worker thread too; dict stores are atomic).
        ``mask`` is either the full [K, steps, batch] float mask or the
        [K, 2] spec (``_spec_inputs``) — straggler truncation writes the
        spec's valid-steps column and recomputes the weights through the
        closed form ``spec_examples`` (exactly ``mask.sum((1, 2))`` of
        the expanded mask), so both representations realize identical
        failures from identical host draws.

        With ``run.churn`` on, ``cohort`` (the round's client ids)
        additionally realizes the seed-pure churn draws through the
        SAME machinery: a crash-mid-round truncates the client's mask
        at its hash-drawn work fraction (the straggler path — partial
        work still aggregates), and offline/hazard-dropped members
        zero their weight (the dropout path). Every churn draw is a
        pure function of (seed, round, id) — no host_rng consumption —
        so churn-on failures are identical across engines, resumes,
        and the prefetch worker, and churn-off leaves host_rng's
        stream untouched (the bitwise-identity contract). An all-
        dropped round is legitimate (a diurnal trough): the engines'
        degenerate-denominator path handles it, exactly like an empty
        poisson round."""
        if k == 0:
            return mask, n_ex  # empty poisson round: nothing to fail
        shape = shape or self.shape
        spec_mode = mask.ndim == 2  # [K, 2] spec vs [K, steps, batch]
        n_strag = n_drop = 0
        if self.cfg.server.straggler_rate > 0:
            # simulated stragglers (SURVEY.md §5, FedProx's motivating
            # scenario): a fraction of the cohort completes only
            # straggler_work of its local steps — their mask tail is
            # truncated, so the engine's padded-step machinery makes the
            # unfinished steps exact no-ops and the FedAvg weight (and
            # SCAFFOLD's Kᵢ) shrinks to the work actually done
            strag = host_rng.random(k) < self.cfg.server.straggler_rate
            if strag.any():
                done = max(1, int(round(
                    self.cfg.server.straggler_work * shape.steps
                )))
                mask = mask.copy()
                if spec_mode:
                    mask[strag, 1] = np.minimum(mask[strag, 1], done)
                    n_ex = spec_examples(mask, shape)
                else:
                    mask[strag, done:, :] = 0.0
                    n_ex = mask.sum((1, 2))
                n_strag = int(strag.sum())
        if self.cfg.server.dropout_rate > 0:
            # simulated client dropout (SURVEY.md §5): zero the FedAvg weight
            participate = (
                host_rng.random(k) >= self.cfg.server.dropout_rate
            )
            if not participate.any():
                participate[host_rng.integers(k)] = True
            n_ex = n_ex * participate.astype(np.float32)
            if self.gossip:
                # gossip has no aggregation weight for n_ex to zero —
                # the local phase is gated by the step mask, so a
                # dropped client must have its mask zeroed too (it then
                # trains zero valid steps and only RELAYS its replica,
                # the decentralized dropout semantics)
                mask = mask.copy()
                mask[~participate] = 0.0
            n_drop = int(k - participate.sum())
        n_unavail = n_hazard = n_crash = 0
        if (self._churn is not None and cohort is not None
                and round_idx is not None):
            ids = np.asarray(cohort, np.int64)
            real = ids < self.fed.num_clients  # poisson pads never churn
            crashed, frac = self._churn.crashed(round_idx, ids)
            crashed &= real
            if crashed.any():
                # crash-mid-round: truncate at the hash-drawn fraction
                # of the FULL step grid (≥ 1 step — a crash during
                # step 1 still uploads that step's work)
                done = np.maximum(
                    1, np.floor(frac * shape.steps).astype(np.int64)
                )
                mask = mask.copy()
                if spec_mode:
                    mask[crashed, 1] = np.minimum(
                        mask[crashed, 1], done[crashed]
                    )
                    n_ex = spec_examples(mask, shape)
                else:
                    cut = (
                        np.arange(shape.steps)[None, :] < done[crashed, None]
                    )
                    mask[crashed] = mask[crashed] * cut[:, :, None].astype(
                        mask.dtype
                    )
                    n_ex = mask.sum((1, 2))
                n_crash = int(crashed.sum())
            offline = ~self._churn.available(round_idx, ids) & real
            hazard = self._churn.dropped(round_idx, ids) & real
            churn_drop = offline | hazard
            if churn_drop.any():
                n_ex = n_ex * (~churn_drop).astype(np.float32)
                n_unavail = int(offline.sum())
                n_hazard = int((hazard & ~offline).sum())
        if (round_idx is not None and self._counters_on
                and (n_strag or n_drop or n_unavail or n_hazard or n_crash)):
            stats = {}
            if n_strag or n_drop:
                stats["straggler_clients"] = n_strag
                stats["dropped_clients"] = n_drop
            if n_unavail:
                stats["churn_unavailable"] = n_unavail
            if n_hazard:
                stats["churn_dropped"] = n_hazard
            if n_crash:
                stats["churn_crashed"] = n_crash
            self._fail_stats[round_idx] = stats
        return mask, n_ex

    def _prefetch_spe(self, round_idx: int) -> Optional[int]:
        """The ladder rung the steady-state dispatch will request for
        this round (None without buckets): the chunk-max rung under
        fusion, the round's own rung otherwise. Pure in (seed, round),
        so the prefetch worker and the consumer agree — an unaligned-
        resume catch-up round (dispatched fuse=1 on its OWN rung) is
        the one deliberate mismatch, and the consumer drains it."""
        if self._bucket_ladder is None:
            return None
        fuse = self.cfg.run.fuse_rounds
        if fuse > 1:
            start = round_idx - round_idx % fuse
            end = min(start + fuse, self.cfg.server.num_rounds)
            return max(self._round_bucket_spe(j) for j in range(start, end))
        return self._round_bucket_spe(round_idx)

    def _place_round_inputs(self, idx, mask, n_ex, slab):
        """Device placement of one round's host tensors — shared by the
        critical path and the double-buffer prefetch worker (device_put
        is async, so a worker-thread placement overlaps the dispatched
        compute of the PREVIOUS round)."""
        if slab is not None:
            idx, slab_x, slab_y = slab
            train_x = self._round_data(
                self._put_data(jnp.asarray(slab_x)))
            train_y = self._put_data(jnp.asarray(slab_y))
        else:
            train_x, train_y = self._round_data(self.train_x), self.train_y
        if self._cohort_sharding is not None:
            idx = self._put(idx, self._cohort_sharding)
            # the [K, 2] spec has no batch dim — cohort-sharded only
            mask = self._put(
                mask,
                self._client_sharding if self._spec_inputs
                else self._cohort_sharding,
            )
            n_ex = self._put(n_ex, self._client_sharding)
        return idx, mask, n_ex, train_x, train_y

    def _on_worker(self, first_round: int, build, *args):
        """The prefetch worker's bracket around one build: span
        ``round.prefetch``, whose ``round`` is the first round (1-based)
        of the steady-state dispatch that will consume what is built —
        the identifier the main thread's ``round.run`` of that dispatch
        carries too, and the worker's sampler / slab / churn spans
        inherit."""
        with self.tracer.span("round.prefetch", round=first_round + 1):
            return build(*args)

    def _build_prefetch_entry(self, round_idx: int, spe: Optional[int],
                              place: bool) -> Dict[str, Any]:
        """Worker-thread body: build (and, double-buffered, place) one
        round's inputs. The entry records the rung it was built for so
        the consumer can detect (and drain) a grid mismatch."""
        shape = self._bucket_shape(spe) if spe is not None else None
        cohort, idx, mask, n_ex, slab = self._host_inputs(
            round_idx, shape=shape,
            # fused chunks consume host tensors only (the union slab is
            # gathered at chunk-stack time); per-round slabs would be
            # wasted work the consumer drops
            build_slab=self.cfg.run.fuse_rounds == 1,
        )
        placed = (
            self._place_round_inputs(idx, mask, n_ex, slab) if place
            else None
        )
        return {"spe": spe, "host": (cohort, idx, mask, n_ex, slab),
                "placed": placed}

    def _build_chunk_slab_entry(self, start: int, fuse: int,
                                spe: Optional[int]) -> Optional[Dict[str, Any]]:
        """Worker-thread body for the fused chunk-union slab (stream ×
        fuse overlap): stack the chunk's index grids — reusing the
        per-round prefetch entries, which the one-worker executor's
        FIFO order guarantees already completed; _host_inputs is pure
        in (seed, round), so rebuilding any missing one is bitwise
        harmless — dedup into the union row set, and run the store
        gather (the expensive mmap I/O) off the critical path. The
        consumer verifies the row set against its own stack and drains
        on any mismatch, so a wrong-shape build can never smuggle
        wrong bytes into a dispatch."""
        shape = self._bucket_shape(spe) if spe is not None else None
        idxs = []
        for t in range(start, start + fuse):
            entry = None
            fut = self._prefetch.get(t)
            if fut is not None:
                entry = fut.result()
            if entry is not None and entry["spe"] == spe:
                idxs.append(entry["host"][1])
            else:
                _c, idx, _m, _n, _s = self._host_inputs(
                    t, shape=shape, build_slab=False
                )
                idxs.append(idx)
        uniq = np.unique(np.stack(idxs))
        rows = self._fused_slab_rows
        if len(uniq) > rows:
            # overflow is the consumer's error to raise (same message,
            # its own stack); an over-full prefetched slab is just drained
            return None
        slab_x = np.empty((rows,) + self.fed.train_x.shape[1:],
                          self.fed.train_x.dtype)
        slab_y = np.empty((rows,) + self.fed.train_y.shape[1:],
                          self.fed.train_y.dtype)
        slab_x[: len(uniq)] = self.fed.train_x[uniq]
        slab_y[: len(uniq)] = self.fed.train_y[uniq]
        return {"spe": spe, "fuse": fuse, "uniq": uniq,
                "slab_x": slab_x, "slab_y": slab_y}

    def _submit_chunk_slab_prefetch(self, round_idx: int, fuse: int) -> None:
        """Queue the NEXT chunk's union-slab store gather on the host
        worker — called right before this chunk's dispatch, so the
        gather I/O runs while the device executes and the next
        ``round.stream_slab`` span collapses to a verify+remap. The
        next chunk's per-round host builds are already queued ahead of
        it (FIFO), so the slab builder reuses their index grids. The
        ledger-snapshot refresh boundary rule from _maybe_prefetch
        applies chunk-wholesale: a chunk past the boundary is a
        function of a snapshot that does not exist yet."""
        if (not self._stream or not self._double_buffer
                or self._native is not None):
            return
        start = round_idx + fuse
        if (start >= self.cfg.server.num_rounds
                or start in self._chunk_prefetch):
            return
        if self._snapshot_refresh:
            le = self._ledger_cfg.log_every
            if le and (start + fuse - 1) // le != round_idx // le:
                return
        ex = self._ensure_executor()
        if ex is None:
            return
        self._chunk_prefetch[start] = ex.submit(
            self._on_worker, start, self._build_chunk_slab_entry, start,
            fuse, self._prefetch_spe(start),
        )

    def _ensure_executor(self):
        if self._host_executor is None and (
            self._double_buffer or self._stream
        ):
            from concurrent.futures import ThreadPoolExecutor

            # ONE worker: all builds serialize, so the native pipeline
            # and the samplers never see two concurrent builders
            self._host_executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="colearn-prefetch"
            )
        return self._host_executor

    def _maybe_prefetch(self, round_idx: int) -> None:
        """Submit the upcoming rounds' input builds to the host worker
        (run.double_buffer): the next round's build AND placement run
        while round_idx's dispatched compute executes — the second
        in-flight placed-slab buffer. Under fuse_rounds the whole next
        chunk's host slabs build ahead (placement stays with the chunk
        stacker, and the chunk-union STORE GATHER runs ahead through
        _build_chunk_slab_entry); double-buffered stream mode builds
        AND places the next round's slab ahead too — the second
        in-flight slab is the overlap buffer, the deliberate +1-slab
        cost of hiding the store gather under dispatch (legacy
        non-double-buffered stream keeps the build-only single
        look-ahead). The adaptive sampler never prefetches across a
        ledger-snapshot refresh boundary — the cohort there is a
        function of a snapshot that does not exist yet."""
        ex = self._ensure_executor()
        if ex is None:
            return
        fuse = self.cfg.run.fuse_rounds
        if not self._double_buffer:
            depth = 1  # legacy stream-mode behavior
        elif fuse > 1:
            depth = fuse
        else:
            depth = 2
        for t in range(round_idx + 1, round_idx + 1 + depth):
            if t >= self.cfg.server.num_rounds or t in self._prefetch:
                continue
            if self._snapshot_refresh:
                # never prefetch across a snapshot/sketch refresh
                # boundary — the cohort there is a function of a
                # snapshot that does not exist yet (adaptive AND
                # sketch-fed streaming sampling)
                le = self._ledger_cfg.log_every
                if le and t // le != round_idx // le:
                    continue
            place = self._double_buffer and fuse == 1
            self._prefetch[t] = ex.submit(
                self._on_worker, t - t % fuse, self._build_prefetch_entry,
                t, self._prefetch_spe(t), place,
            )

    def _round_inputs(self, round_idx: int, place: bool = True,
                      shape: Optional[RoundShape] = None):
        """``place=False`` returns the idx/mask/n_ex tensors as HOST
        arrays (the fused-chunk path stacks `fuse` rounds of them and
        places the [F, ...] slabs once through the fused shardings —
        stacking already-placed global arrays would be an eager op on
        non-addressable shards under multi-process). ``shape`` is the
        fused chunk-max grid override; prefetch entries are keyed by
        round with the bucket baked in (the bucket is a pure function
        of the round, so worker and consumer agree)."""
        if shape is not None:
            want_spe = shape.steps_per_epoch
        elif self._bucket_ladder is not None:
            want_spe = self._round_bucket_spe(round_idx)
        else:
            want_spe = None
        fut = self._prefetch.pop(round_idx, None)
        entry = None
        # the span measures the CRITICAL-PATH host-input cost: ~0 when
        # the prefetch worker ran ahead, the full build otherwise
        with self.tracer.span("round.host_inputs"):
            if fut is not None:
                entry = fut.result()
                if entry["spe"] != want_spe or (
                    place and self._stream and entry["host"][4] is None
                ):
                    # overlap drain: the prefetched grid was built for a
                    # different ladder rung (unaligned-resume catch-up
                    # dispatches on the round's own rung, not the
                    # steady-state chunk max), or — stream × fuse — it
                    # was built slab-less for a fused consumer but an
                    # unfused catch-up round needs the per-round slab.
                    # Rebuild on the right shape.
                    self._db_stats["prefetch_dropped"] += 1
                    entry = None
                else:
                    self._db_stats["host_prefetched"] += 1
            if entry is not None:
                cohort, idx, mask, n_ex, slab = entry["host"]
            else:
                cohort, idx, mask, n_ex, slab = self._host_inputs(
                    round_idx, shape=shape, build_slab=place,
                )
        if self._population is not None and slab is not None:
            # stream-slab dedup shape, observed at CONSUMPTION (not in
            # _stream_slab, which may also run for prefetch entries the
            # consumer drops): the remapped index tensor's max + 1 IS
            # the unique-row count the gather copied
            sl_idx = slab[0]
            self._population.observe_slab(
                int(sl_idx.size),
                int(sl_idx.max()) + 1 if sl_idx.size else 0,
            )
        self._maybe_prefetch(round_idx)
        if self._digest_on:
            # schedule-component capture (consumed at flush): the
            # realized cohort ids, poisson pads included — the pad
            # pattern is part of the deterministic schedule
            self._digest_cohorts[round_idx] = np.asarray(
                cohort, np.int64
            ).copy()
        n_host = np.asarray(n_ex)  # pairwise secagg reads dropout host-side
        if self._counters_on:
            stats = self._round_comm(cohort, n_host)
            # padded-shape accounting (r7): grid provenance, analytic
            # host→device index-input bytes (the mask slab's removal is
            # visible here), and the padded-step / wasted-FLOP gauges
            rows, steps_g, batch_g = (
                int(idx.shape[0]), int(idx.shape[1]), int(idx.shape[2])
            )
            stats["host_input_bytes"] = round_host_input_bytes(
                rows, steps_g, batch_g, self._spec_inputs
            )
            if self._spec_inputs:
                stats.update(round_shape_stats(
                    mask, steps_g, batch_g, self.shape.local_epochs
                ))
                if self._bucket_ladder is not None:
                    stats["shape_bucket_steps"] = steps_g
            self._comm_stats[round_idx] = stats
        if not place:
            # fuse>1 requires hbm placement (validate), so slab is None
            return (cohort, idx, mask, n_ex,
                    self._round_data(self.train_x), self.train_y, n_host)
        with self.tracer.span("round.placement"):
            if entry is not None and entry["placed"] is not None:
                # double-buffered: the worker already placed this
                # round's tensors while the previous dispatch ran —
                # the placement span records only this hand-off
                idx, mask, n_ex, train_x, train_y = entry["placed"]
                self._db_stats["placed_prefetched"] += 1
            else:
                idx, mask, n_ex, train_x, train_y = self._place_round_inputs(
                    idx, mask, n_ex, slab
                )
        return cohort, idx, mask, n_ex, train_x, train_y, n_host

    def _round_comm(self, cohort, n_host) -> Dict[str, int]:
        """Analytic wire bytes for one round (obs/counters.py): the
        realized participant count (dropouts excluded) uploads, the
        real — non-poisson-pad — cohort downloads."""
        coords, p_bytes = self._param_stats()
        _, f_bytes = self._full_param_stats()
        if self.gossip:
            stats = gossip_round_bytes(
                self.fed.num_clients, self.cfg.server.gossip_mixing_steps,
                self.cfg.server.gossip_topology, p_bytes,
            )
            full_up = gossip_round_bytes(
                self.fed.num_clients, self.cfg.server.gossip_mixing_steps,
                self.cfg.server.gossip_topology, f_bytes,
            )["upload_bytes"]
        else:
            n_up = int((n_host > 0).sum())
            n_down = int(
                (np.asarray(cohort) < self.fed.num_clients).sum()
            )
            stats = round_comm_bytes(
                self.cfg.server, n_participants=n_up, n_downloads=n_down,
                n_coords=coords, param_bytes=p_bytes,
            )
            f_coords, _ = self._full_param_stats()
            full_up = round_comm_bytes(
                self.cfg.server, n_participants=n_up, n_downloads=n_down,
                n_coords=f_coords, param_bytes=f_bytes,
            )["upload_bytes"]
        # LoRA wire accounting (ROADMAP item 3's headline number): what
        # the FULL-delta twin would have uploaded this round, and the
        # per-client reduction ratio — 1.0 exactly for non-LoRA runs
        stats["upload_bytes_full"] = full_up
        stats["wire_reduction_vs_full"] = round(
            self.wire_reduction_vs_full(), 2
        )
        return stats

    def _stream_slab(self, idx: np.ndarray):
        """Gather this round's unique example rows into a fixed-shape slab
        (static shape ⇒ one XLA trace for the whole run) and remap the
        index tensor into it. Tail rows past ``len(uniq)`` are left
        uninitialized — every remapped index points below ``len(uniq)``,
        so they are never gathered."""
        with self.tracer.span("round.stream_slab"):
            uniq, inv = np.unique(idx, return_inverse=True)
            if len(uniq) > self._slab_rows:
                raise RuntimeError(
                    f"stream slab overflow: round gathered {len(uniq)} "
                    f"unique example rows but the static slab holds "
                    f"{self._slab_rows} — the construction-time sizing "
                    f"(cohort x cap + 1) should have prevented this"
                )
            slab_x = np.empty((self._slab_rows,) + self.fed.train_x.shape[1:],
                              self.fed.train_x.dtype)
            slab_y = np.empty((self._slab_rows,) + self.fed.train_y.shape[1:],
                              self.fed.train_y.dtype)
            slab_x[: len(uniq)] = self.fed.train_x[uniq]
            slab_y[: len(uniq)] = self.fed.train_y[uniq]
            new_idx = inv.reshape(idx.shape).astype(np.int32)
            return new_idx, slab_x, slab_y

    def _run_async_round(self, state: Dict[str, Any], round_idx: int) -> Dict[str, Any]:
        """One FedBuff server step: pop the K earliest-finishing in-flight
        clients, train each against its stale start version (history
        ring gather inside the program), aggregate with staleness-decayed
        weights, start K replacement clients at the new version.

        The pop-K-earliest discipline with durations ≤ S and concurrency
        K·S bounds realized staleness by 2S (a finished client waits at
        most concurrency/K = S further steps), which sizes the 2S+1-slot
        ring. Without churn the bound is an invariant (violations
        raise); under ``run.churn`` offline clients DEFER completions
        and the bound becomes a BUDGET — the admission gate clamps an
        over-bound update's start version to the oldest retained ring
        slot, decays its weight at the TRUE staleness (strictly
        stronger), counts it (``staleness_clamped``), and warns once.
        ``run.strict_staleness=true`` restores the raise.

        Million-client plane (the churn PR): ``data.placement=stream``
        gathers only the popped buffer's example rows into the
        fixed-shape slab (mmap store composes — the gather IS the
        store read path), ``server.sampling=streaming`` draws arrivals
        through the O(cohort·log) sketch sampler (availability-gated,
        Oort-scored once per-insert ledger stats feed the sketch), and
        ``run.obs.client_ledger`` rides the round program per insert.
        ``server.async_backlog_cap`` sheds completed backlog beyond
        the cap per ``async_overload_policy`` (drop-oldest vs
        reject-newest; shed clients re-enter as fresh arrivals at the
        current version, their in-flight work discarded and counted)."""
        cfg = self.cfg
        s_max = cfg.server.async_max_staleness
        window = 2 * s_max + 1
        k = cfg.server.cohort_size
        # multi-version lines (server.async_versions): round r drives
        # line r mod V at LINE-LOCAL version r div V — each line is an
        # independent FedBuff instance (own params/history/queue) whose
        # queue arithmetic runs in line-local steps. V=1 degenerates to
        # line 0 at version == round_idx, bitwise the single-version
        # plane (sfx == "" selects the legacy state keys).
        V = self._versions
        line = round_idx % V
        version = round_idx // V
        sfx = "" if line == 0 else f"_l{line}"
        q_clients = state["queue_clients" + sfx]
        q_versions = state["queue_versions" + sfx]
        q_finish = state["queue_finish" + sfx]
        q_seq = state["queue_seq" + sfx]
        host_rng = np.random.default_rng((cfg.run.seed, 6073, round_idx))
        # version retirement (server.async_retire_*): at the line's
        # turn, a generation that aged past async_retire_rounds or
        # absorbed async_retire_updates RETIRES — the line's params
        # continue as the successor generation, and in-flight work
        # against the retired generation re-admits below at the oldest
        # live version with decayed weight (strict_versions rejects).
        gen = 0
        q_gen = None
        if V > 1:
            q_gen = state["queue_gen" + sfx]
            gen = int(state["line_gen"][line])
            age = version - int(state["line_birth"][line])
            rr = cfg.server.async_retire_rounds
            ru = cfg.server.async_retire_updates
            if ((rr > 0 and age >= rr) or
                    (ru > 0 and int(state["line_absorbed"][line]) >= ru)):
                gen += 1
                state["line_gen"][line] = gen
                state["line_birth"][line] = version
                state["line_absorbed"][line] = 0
        if (self._snapshot_refresh and round_idx > 0
                and round_idx % self._ledger_cfg.log_every == 0):
            # streaming-sketch refresh from the per-insert ledger, at
            # the same log_every boundaries as the sync loop — arrival
            # draws for rounds [r, r + log_every) are a pure function
            # of (seed, round, sketch@r)
            self._refresh_adaptive_snapshot(round_idx)

        n_bp_drop = n_bp_rej = 0
        with self.tracer.span("round.async_schedule"):
            if self._churn is not None:
                # availability-aware pop: an offline client's
                # completion cannot be absorbed — it WAITS (sorted
                # behind every online entry), so its staleness
                # accumulates while the device is dark, exactly the
                # production regime the admission gate below absorbs.
                # Stateless by construction (the availability bit is
                # the pure churn hash — nothing mutates, so resume
                # replays the same pops). When fewer than K online
                # completions exist, offline entries fill the static-
                # shape pop and realize as churn dropouts (weight 0)
                # in _apply_failures, their slots re-queued fresh.
                offline = (
                    ~self._churn.available(round_idx, q_clients)
                ).astype(np.int32)
                order = np.lexsort((q_seq, q_finish, offline))
            else:
                order = np.lexsort((q_seq, q_finish))
            pick = order[:k]
            cap = cfg.server.async_backlog_cap
            if cap > 0:
                # overload backpressure: completed entries beyond the
                # K this step absorbs form the backlog; anything past
                # the cap is shed per policy — the client re-enters as
                # a fresh arrival at the current version, its
                # in-flight work discarded (counted)
                done = np.flatnonzero(q_finish <= version)
                waiting = np.setdiff1d(done, pick, assume_unique=False)
                excess = len(waiting) - cap
                if excess > 0:
                    if cfg.server.async_overload_policy == "drop_oldest":
                        # shed the stalest waiters (oldest start
                        # version first; ties by arrival order)
                        shed_order = np.lexsort((
                            q_seq[waiting], q_versions[waiting],
                        ))
                        shed = waiting[shed_order[:excess]]
                        n_bp_drop = excess
                    else:  # reject_newest: FIFO admission
                        shed_order = np.lexsort((
                            -q_seq[waiting], -q_versions[waiting],
                        ))
                        shed = waiting[shed_order[:excess]]
                        n_bp_rej = excess
                    q_versions[shed] = version + 1
                    q_finish[shed] = (
                        version + 1 + self._client_durations(
                            q_clients[shed], host_rng
                        )
                    ).astype(np.int32)
                    nxt_shed = state["queue_next_seq" + sfx]
                    q_seq[shed] = np.arange(
                        nxt_shed, nxt_shed + excess, dtype=np.int32
                    )
                    state["queue_next_seq" + sfx] = nxt_shed + excess
                    if q_gen is not None:
                        # shed clients re-enter as fresh arrivals of
                        # the CURRENT generation
                        q_gen[shed] = gen
            cohort = q_clients[pick].copy()
            staleness = version - q_versions[pick]
            late = np.zeros(k, dtype=bool)
            if q_gen is not None:
                late = q_gen[pick] < gen
            n_readmit = int(late.sum())
        if not (staleness >= 0).all():
            # a negative staleness is a scheduler bug, never a churn
            # outcome — must survive python -O
            raise RuntimeError(
                f"fedbuff staleness bound violated: {staleness} outside "
                f"[0, {2 * s_max}] — history ring sizing is wrong"
            )
        over = staleness > 2 * s_max
        n_clamped = int(over.sum())
        if n_clamped and cfg.run.strict_staleness:
            # the pre-churn contract, preserved behind the escape
            # hatch: the ring bound is an invariant
            raise RuntimeError(
                f"fedbuff staleness bound violated: {staleness} outside "
                f"[0, {2 * s_max}] — history ring sizing is wrong"
            )
        # graceful admission: an update whose start version aged out of
        # the ring trains against the OLDEST RETAINED version (slot
        # arithmetic on the clamped version — the true start was
        # overwritten), while its weight decays at the TRUE staleness
        eff_versions = np.maximum(
            q_versions[pick], version - 2 * s_max
        )
        slots = (eff_versions % window).astype(np.int32)
        if n_readmit:
            # late completions against a retired generation: hard
            # reject under run.strict_versions, otherwise re-admit at
            # the oldest live version (the slot clamp above already
            # covers an aged-out start) with decayed weight below
            if cfg.run.strict_versions:
                raise RuntimeError(
                    f"fedbuff line {line}: {n_readmit} completion(s) "
                    f"arrived against a retired generation "
                    f"(queue gen < line gen {gen}) and "
                    f"run.strict_versions=true rejects re-admission"
                )
            if not self._readmit_warned:
                self._readmit_warned = True
                self.logger.log({
                    "event": "warning",
                    "warning": "version_readmitted",
                    "round": int(round_idx),
                    "detail": (
                        f"fedbuff line {line}: completion(s) against a "
                        f"retired generation re-admitted at the oldest "
                        f"live version with weight decayed by "
                        f"async_readmit_decay="
                        f"{cfg.server.async_readmit_decay} per retired "
                        f"generation; counted as version_readmitted "
                        f"(warn-once; set run.strict_versions=true to "
                        f"make this an error)"
                    ),
                })
        if n_clamped and not self._staleness_warned:
            self._staleness_warned = True
            self.logger.log({
                "event": "warning",
                "warning": "staleness_clamped",
                "round": int(round_idx),
                "detail": (
                    f"fedbuff update(s) exceeded the 2S={2 * s_max} "
                    f"staleness bound (max realized "
                    f"{int(staleness.max())}): start version clamped "
                    f"to the oldest retained ring slot, weight decayed "
                    f"at the true staleness; counted as "
                    f"staleness_clamped (warn-once; set "
                    f"run.strict_staleness=true to make this an error)"
                ),
            })
        stale_f = staleness.astype(np.float64)
        self._async_stats[round_idx] = {
            "mean": float(staleness.mean()),
            "max": int(staleness.max()),
            "p50": float(np.percentile(stale_f, 50)),
            "p90": float(np.percentile(stale_f, 90)),
            "clamped": n_clamped,
            "bp_dropped": n_bp_drop,
            "bp_rejected": n_bp_rej,
        }
        if V > 1:
            self._async_stats[round_idx]["version"] = line
            self._async_stats[round_idx]["readmitted"] = n_readmit
        # pooled run-level staleness distribution (run_summary / bench
        # extras): a bounded value→count histogram, never per-update
        for v_, c_ in zip(*np.unique(staleness, return_counts=True)):
            self._staleness_hist[int(v_)] = (
                self._staleness_hist.get(int(v_), 0) + int(c_)
            )
        self._version_readmitted += n_readmit

        with self.tracer.span("round.host_inputs"):
            idx, mask, n_ex = make_round_indices(
                self.fed, cohort, self.shape, host_rng
            )
            mask, n_ex = self._apply_failures(mask, n_ex, k, host_rng,
                                              round_idx=round_idx,
                                              shape=self.shape,
                                              cohort=cohort)
        if self._digest_on:
            # schedule-component capture: the popped completion set IS
            # the async scheduler's realized schedule for this step
            self._digest_cohorts[round_idx] = np.asarray(
                cohort, np.int64
            ).copy()
        if self._counters_on:
            self._comm_stats[round_idx] = self._round_comm(cohort, n_ex)
        base_w = (
            n_ex if self._agg_mode == "examples"
            else (n_ex > 0).astype(np.float32)
        )
        agg_w = (
            base_w * (1.0 + staleness.astype(np.float32))
            ** -cfg.server.async_staleness_exponent
        )
        if n_readmit:
            # re-admission decay: once per retired generation gap, on
            # top of the true-staleness decay above
            agg_w = agg_w * np.where(
                late,
                np.float32(cfg.server.async_readmit_decay)
                ** (gen - q_gen[pick]).astype(np.float32),
                np.float32(1.0),
            ).astype(np.float32)
        absorbed_mask = n_ex > 0
        n_edges_crashed = n_edge_excluded = 0
        if self._hier:
            # async two-tier grouping: each popped completion belongs
            # to the edge covering its contiguous id block. A crashed
            # edge's completions are EXCLUDED (weight 0, counted) — a
            # dead tier degrades the step, never NaN-poisons the core.
            # core_aggregator="reputation" folds the edge-liveness
            # trust EMA into its members' admission weights.
            from colearn_federated_learning_tpu.server.churn import (
                edge_crashed,
            )

            E = cfg.server.hierarchy.num_edges
            edge_ids = (
                np.asarray(cohort, np.int64) * E // self.fed.num_clients
            )
            e_crashed = edge_crashed(
                cfg.run.seed, round_idx, E,
                cfg.server.hierarchy.edge_dropout_rate,
            )
            n_edges_crashed = int(e_crashed.sum())
            excl = e_crashed[edge_ids]
            n_edge_excluded = int((excl & absorbed_mask).sum())
            agg_w = agg_w * (~excl).astype(np.float32)
            absorbed_mask = absorbed_mask & ~excl
            trust = state["edge_trust"]
            if cfg.server.hierarchy.core_aggregator == "reputation":
                agg_w = agg_w * trust[edge_ids].astype(np.float32)
            d = cfg.server.hierarchy.core_trust_decay
            trust *= np.float32(1.0 - d)
            trust += np.float32(d) * (~e_crashed).astype(np.float32)
            np.add.at(self._edge_absorbed, edge_ids[absorbed_mask], 1)
            if n_edges_crashed:
                self._async_stats[round_idx]["edge_crashed"] = (
                    n_edges_crashed
                )
                self._async_stats[round_idx]["edge_excluded"] = (
                    n_edge_excluded
                )
        n_absorbed = int(absorbed_mask.sum())
        self._async_absorbed += n_absorbed
        self._per_version_absorbed[line] += n_absorbed
        if V > 1:
            state["line_absorbed"][line] += n_absorbed
        if self._population is not None:
            self._population.observe_async(
                round_idx, staleness, absorbed=n_absorbed,
                clamped=n_clamped, bp_dropped=n_bp_drop,
                bp_rejected=n_bp_rej, readmitted=n_readmit,
                edge_crashed=n_edges_crashed,
                version=line if V > 1 else None,
            )

        if self._stream:
            # store-backed / larger-than-HBM corpora: gather only this
            # step's example rows into the fixed-shape slab (the mmap
            # store's gather path) and remap the index tensor into it
            idx, slab_x, slab_y = self._stream_slab(idx)
            if self._population is not None:
                self._population.observe_slab(
                    int(idx.size), int(len(np.unique(idx)))
                )
            train_x = self._round_data(
                self._put_data(jnp.asarray(slab_x)))
            train_y = self._put_data(jnp.asarray(slab_y))
        else:
            train_x, train_y = self._round_data(self.train_x), self.train_y

        put_c = lambda a: self._put(jnp.asarray(a), self._client_sharding)  # noqa: E731
        rng = jax.random.fold_in(state["rng_key"], round_idx)
        common = (
            state["history" + sfx], state["server_opt_state" + sfx],
            train_x, train_y,
            put_c(idx), put_c(mask), put_c(agg_w.astype(np.float32)),
            put_c(n_ex), put_c(slots),
        )
        ring = (
            jnp.int32(version % window), jnp.int32((version + 1) % window),
        )
        ledger = None
        with self.tracer.span("round.dispatch"):
            if self._ledger_on:
                # per-insert forensic stats + (optionally) the
                # staleness-aware reputation-weighted merge: cohort ids
                # and the carried ledger ride the program; the updated
                # ledger comes back before the metrics
                cohort_dev = self._put(
                    jnp.asarray(np.asarray(cohort, np.int32)),
                    self._data_sharding,
                )
                history, params, opt_state, ledger, metrics = self.round_fn(
                    *common, cohort_dev, state["ledger"], *ring, rng,
                )
            else:
                history, params, opt_state, metrics = self.round_fn(
                    *common, *ring, rng,
                )

        # replace the popped clients: fresh arrivals starting at the
        # NEW version, finishing 1..S steps from the next step. The
        # draw is uniform (churn-gated to online clients), or the
        # streaming sketch sampler's O(cohort·log) draw — availability-
        # gated and Oort-scored once ledger evidence feeds the sketch.
        if self._streaming:
            # the streaming sampler's draw is availability-gated and
            # (with ledger evidence) Oort-scored; its deterministic
            # backstop guarantees exactly K ids
            arrivals = self.sampler.sample(round_idx).astype(np.int32)
            arrival_draws = self.sampler.take_draw_stats(round_idx)
        else:
            if self._churn is not None:
                all_ids = np.arange(self.fed.num_clients)
                online = all_ids[self._churn.available(round_idx, all_ids)]
                pool = online if len(online) else all_ids
                arrivals = host_rng.choice(
                    pool, size=k, replace=k > len(pool),
                ).astype(np.int32)
            else:
                # churn-off keeps the exact pre-churn draw (int form —
                # the bitwise-identity contract covers the rng stream)
                arrivals = host_rng.choice(
                    self.fed.num_clients, size=k,
                    replace=k > self.fed.num_clients,
                ).astype(np.int32)
            arrival_draws = None
        if self._population is not None:
            # coverage/fairness track the REALIZED server step (pads
            # and zero-weight failures excluded); the draw split — when
            # present — describes this step's ARRIVALS (fedbuff pops
            # its queue; the sampler only feeds it)
            self._population.observe_cohort(
                round_idx, cohort, n_ex, arrival_draws,
            )
        q_clients[pick] = arrivals
        q_versions[pick] = version + 1
        q_finish[pick] = (
            version + 1
            + self._client_durations(q_clients[pick], host_rng)
        ).astype(np.int32)
        nxt = state["queue_next_seq" + sfx]
        q_seq[pick] = np.arange(nxt, nxt + k, dtype=np.int32)
        if q_gen is not None:
            q_gen[pick] = gen

        # pass-through: every other line's state (and any host-side
        # sampler/ledger keys) rides unchanged; only this line's tree,
        # ring, and queue-counter keys are replaced. V=1 produces
        # exactly the legacy key set (the bitwise-identity contract).
        new_state = dict(state)
        new_state.pop("_metrics", None)
        new_state.update({
            "history" + sfx: history,
            "params" + sfx: params,
            "server_opt_state" + sfx: opt_state,
            "round": round_idx + 1,
            "queue_next_seq" + sfx: nxt + k,
            "_metrics": metrics,
        })
        if self._ledger_on:
            new_state["ledger"] = ledger
        return new_state

    def _pairwise_seeds(self, round_idx: int, n_host: np.ndarray):
        """One round of the Bonawitz key protocol, host-side
        (privacy/secagg_keys.py): fresh per-round DH secrets + Shamir
        shares, pairwise seed matrix for the cohort, and — when clients
        dropped (weight 0 at collection) — the server's REAL recovery
        path: reconstruct each dropped secret from exactly t survivor
        shares and recompute its seed row from the publics alone.
        Raises ThresholdError below t survivors (the protocol's defined
        abort; nothing can be aggregated that round)."""
        from colearn_federated_learning_tpu.privacy import secagg_keys as sk

        k = self.cfg.server.cohort_size
        t = self.cfg.server.secagg_threshold or (k // 2 + 1)
        rng = np.random.default_rng((self.cfg.run.seed, round_idx, 0x5ECA))
        keys = sk.setup_cohort(rng, k, t)
        seeds = sk.build_seed_matrix(keys)
        dropped = np.flatnonzero(n_host == 0)
        if dropped.size:
            survivors = np.flatnonzero(n_host > 0)
            rows = sk.recover_dropped_rows(keys, dropped.tolist(),
                                           survivors.tolist())
            for d, row in rows.items():
                # DH symmetry guarantees the recovered row equals the
                # client's own; check it explicitly (cheap, and it IS
                # the protocol correctness property — an explicit raise,
                # not an assert, so the gate survives `python -O`)
                if not np.array_equal(row, seeds[d]):
                    raise RuntimeError(
                        f"pairwise secagg: Shamir-recovered seeds for "
                        f"dropped client {d} diverge from DH agreement "
                        f"— seed recovery is corrupt; aborting the round"
                    )
                seeds[d] = row
        arr = jnp.asarray(seeds)
        if self._data_sharding is not None:
            arr = self._put(arr, self._data_sharding)
        return arr

    def _unfused_round_fn(self):
        """The fuse_rounds=1 engine twin, built lazily (one extra
        compile) the first time a non-chunk-aligned resume needs
        unfused catch-up rounds."""
        if self._unfused_cache is None:
            if self._make_engine is None:
                raise RuntimeError(
                    "no unfused engine twin for this configuration"
                )
            self._unfused_cache = self._make_engine(1)
        return self._unfused_cache

    # ---- device-resident control plane (run.control_plane="device") --

    def _init_device_plane(self) -> None:
        """Build the device control plane (server/device_plane.py): the
        cohort table runs the UNCHANGED host sampler over every round
        (so device cohorts are bitwise-equal to host mode by
        construction), churn thresholds precompute the diurnal curve as
        integer gates, and the shard table makes the index slab a pure
        in-program gather. Draw-provenance tallies are captured here
        per round (the sampler bounds its unconsumed backlog) and
        consumed by the flush drain's population feed."""
        from colearn_federated_learning_tpu.server.device_plane import (
            build_device_plan,
            make_schedule_fn,
            plan_arrays,
        )

        cfg = self.cfg

        def _sample(r):
            out = self.sampler.sample(r)
            self._device_draw_stats[r] = self.sampler.take_draw_stats(r)
            return out

        self._device_plan = build_device_plan(
            self.fed, self.shape, _sample, self._churn,
            cfg.run.seed, cfg.server.num_rounds,
        )
        arrs = plan_arrays(self._device_plan)
        if self._data_sharding is not None:
            self._device_arrays = {
                k: self._put(jnp.asarray(v), self._data_sharding)
                for k, v in arrs.items()
            }
        else:
            self._device_arrays = {
                k: jnp.asarray(v) for k, v in arrs.items()
            }
        self._schedule_fn = make_schedule_fn(self._device_plan)
        self._device_unfused_cache = None
        if self.mesh is not None:
            self._device_round_fn = self._build_device_round_fn(
                cfg.run.fuse_rounds
            )
        else:
            # sequential oracle: the jitted schedule derivation runs on
            # device and its fetched outputs feed the python-loop
            # engine — the oracle pins schedule/params parity, not
            # wall-clock
            self._device_schedule_jit = jax.jit(self._schedule_fn)

    def _build_device_round_fn(self, fuse: int):
        from colearn_federated_learning_tpu.parallel.round_engine import (
            make_device_round_fn,
        )

        return make_device_round_fn(
            self._make_engine(fuse, donate=False), self._schedule_fn,
            fuse, client_ledger=self._ledger_on,
            data_sharding=self._data_sharding,
            cohort_sharding=self._cohort_sharding,
            client_sharding=self._client_sharding,
            fused_cohort_sharding=self._fused_cohort_sharding,
            fused_client_sharding=self._fused_client_sharding,
        )

    def _device_unfused_round_fn(self):
        """The fuse=1 device-wrapper twin, built lazily for unaligned-
        resume catch-up rounds (mirrors _unfused_round_fn)."""
        if self._device_unfused_cache is None:
            self._device_unfused_cache = self._build_device_round_fn(1)
        return self._device_unfused_cache

    def _note_device_sched(self, round_idx: int, fuse: int,
                           sched: Dict[str, Any]) -> None:
        """Keep device handles of the realized schedule (WITHOUT the
        index slab — cohort/spec/weights/churn scalars only) for the
        flush-boundary drain. Under fuse the [F]-stacked outputs are
        held as per-sub-round device slices, like pending metrics."""
        sched = {k: v for k, v in sched.items() if k != "idx"}
        if fuse > 1:
            for j in range(fuse):
                self._device_sched[round_idx + j] = jax.tree.map(
                    lambda a, j=j: a[j], sched
                )
        else:
            self._device_sched[round_idx] = sched

    def _run_device_round(self, state: Dict[str, Any], round_idx: int,
                          fuse: int) -> Dict[str, Any]:
        """One device-control-plane dispatch: the round program derives
        its own cohort, churn gates, and index slab from (seed, round)
        — the host passes a round index. Under fuse>1 the scan body
        derives each sub-round's schedule itself, so host I/O collapses
        to flush boundaries."""
        if self.mesh is None:
            return self._run_device_round_seq(state, round_idx)
        if fuse == self.cfg.run.fuse_rounds:
            round_fn = self._device_round_fn
        else:
            round_fn = self._device_unfused_round_fn()
        args = (state["params"], state["server_opt_state"],
                self._round_data(self.train_x), self.train_y,
                self._device_arrays, jnp.int32(round_idx), state["rng_key"])
        with self.tracer.span("round.dispatch"):
            if self._ledger_on:
                params, opt_state, ledger, metrics, sched = round_fn(
                    *args, state["ledger"]
                )
            else:
                params, opt_state, metrics, sched = round_fn(*args)
        self._note_device_sched(round_idx, fuse, sched)
        new_state = {
            "params": params,
            "server_opt_state": opt_state,
            "round": round_idx + fuse,
            "rng_key": state["rng_key"],
            "_metrics": metrics,
        }
        if self._ledger_on:
            new_state["ledger"] = ledger
        return new_state

    def _run_device_round_seq(self, state: Dict[str, Any],
                              round_idx: int) -> Dict[str, Any]:
        """Sequential-engine device mode: the schedule still derives
        on device (the jitted schedule program — host_inputs is one
        fetch, no sampler/churn/slab python), then feeds the unchanged
        per-client oracle loop."""
        with self.tracer.span("round.host_inputs"):
            sched = self._device_schedule_jit(
                self._device_arrays, jnp.int32(round_idx)
            )
        with self.tracer.span("round.device_wait", what="schedule"):
            sched = jax.device_get(sched)
        self._note_device_sched(round_idx, 1, sched)
        rng = jax.random.fold_in(state["rng_key"], round_idx)
        kw = {}
        if self._ledger_on:
            kw = dict(
                ledger=state["ledger"],
                ledger_ids=jnp.asarray(
                    np.asarray(sched["cohort"], np.int32)
                ),
            )
        with self.tracer.span("round.dispatch"):
            out = self.round_fn(
                state["params"], state["server_opt_state"],
                self._round_data(self.train_x), self.train_y,
                sched["idx"], sched["spec"], sched["n_ex"], rng, **kw,
            )
        if self._ledger_on:
            params, opt_state, ledger, metrics = out
        else:
            params, opt_state, metrics = out
        new_state = {
            "params": params,
            "server_opt_state": opt_state,
            "round": round_idx + 1,
            "rng_key": state["rng_key"],
            "_metrics": metrics,
        }
        if self._ledger_on:
            new_state["ledger"] = ledger
        return new_state

    def _drain_device_sched(self) -> None:
        """Flush-boundary drain of the device-derived schedules: ONE
        device fetch of every pending round's realized (cohort, spec,
        weights, churn stats), then the same per-round bookkeeping the
        host control plane does inline — digest cohorts, wire counters
        (host_input_bytes=0: no index slab crossed the wire), padded-
        shape gauges, churn fail counters, phase costs, and the
        population observatory's cohort/draw feed. Runs FIRST in
        flush(), so the record loop's pops find everything in place."""
        if not self._device_sched:
            return
        pend = sorted(self._device_sched)
        with self.tracer.span("round.sched_fetch"):
            fetched = jax.device_get(
                [self._device_sched[r] for r in pend]
            )
        self._device_sched.clear()
        for ridx, s in zip(pend, fetched):
            cohort = np.asarray(s["cohort"], np.int64)
            spec = np.asarray(s["spec"])
            n_ex = np.asarray(s["n_ex"])
            if self._digest_on:
                self._digest_cohorts[ridx] = cohort.copy()
            if self._counters_on:
                stats = self._round_comm(cohort, n_ex)
                stats["host_input_bytes"] = 0
                stats.update(round_shape_stats(
                    spec, self.shape.steps, self.shape.batch_size,
                    self.shape.local_epochs,
                ))
                self._comm_stats[ridx] = stats
                fail = {
                    key: int(s[src]) for key, src in (
                        ("churn_unavailable", "unavailable"),
                        ("churn_dropped", "dropped"),
                        ("churn_crashed", "crashed"),
                    ) if int(s[src])
                }
                if fail:
                    self._fail_stats[ridx] = fail
            if self._population is not None:
                self._population.observe_cohort(
                    ridx, cohort, n_ex,
                    self._device_draw_stats.pop(ridx, None),
                )

    def _run_hier_round(self, state: Dict[str, Any],
                        round_idx: int) -> Dict[str, Any]:
        """One two-tier synchronous round (``server.hierarchy``): E
        edge aggregators each run the EXISTING compiled round program
        over a cohort sampled from their contiguous sub-population
        block (device → edge tier, with ``server.aggregator`` as the
        edge-tier defense, e.g. krum), then the core combines the E
        edge DELTAS per ``hierarchy.core_aggregator`` — example-
        weighted mean, reputation-weighted mean over the edge-liveness
        trust EMA, or a robust reduce (median/trimmed_mean/krum with
        the core knobs). Edge-dropout fault injection
        (``edge_dropout_rate``, seed-pure per (round, edge)) skips the
        crashed edge's dispatch entirely: its delta is EXCLUDED from
        the core combine and counted — a dead tier degrades the round,
        it never NaN-poisons the aggregate (an all-crashed round is an
        exact no-op). The engine is reused recursively: ONE compile
        serves all E invocations, and validate() already restricted
        the pairing surface to what that reuse keeps sound."""
        from colearn_federated_learning_tpu.parallel.round_engine import (
            RoundMetrics,
        )
        from colearn_federated_learning_tpu.server.aggregation import (
            robust_reduce,
        )
        from colearn_federated_learning_tpu.server.churn import edge_crashed

        cfg = self.cfg
        hier = cfg.server.hierarchy
        E = hier.num_edges
        crashed = edge_crashed(
            cfg.run.seed, round_idx, E, hier.edge_dropout_rate
        )
        n_crashed = int(crashed.sum())
        params0 = state["params"]
        base_rng = jax.random.fold_in(state["rng_key"], round_idx)
        zero_delta = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params0
        )
        deltas = []
        participation = np.zeros(E, np.float32)
        edge_examples = np.zeros(E, np.float64)
        edge_metrics = []
        opt_state_new = None
        fail_acc: Dict[str, int] = {}
        byz_total = 0
        all_cohorts, all_nex = [], []
        for e in range(E):
            cohort = np.asarray(self._edge_samplers[e].sample(round_idx))
            with self.tracer.span("round.host_inputs"):
                host_rng = np.random.default_rng(
                    (cfg.run.seed, 7919, round_idx, e)
                )
                if self._spec_inputs:
                    idx, mask, n_ex = make_round_spec(
                        self.fed, cohort, self.shape, host_rng
                    )
                else:
                    idx, mask, n_ex = make_round_indices(
                        self.fed, cohort, self.shape, host_rng
                    )
                mask, n_ex = self._apply_failures(
                    mask, n_ex, len(cohort), host_rng,
                    round_idx=round_idx, shape=self.shape, cohort=cohort,
                )
                # _apply_failures stores per-ROUND counts; merge the
                # per-edge dicts so the round record sums all tiers
                for key_, v_ in self._fail_stats.pop(round_idx, {}).items():
                    fail_acc[key_] = fail_acc.get(key_, 0) + int(v_)
            all_cohorts.append(cohort)
            all_nex.append(np.asarray(n_ex))
            if crashed[e]:
                # edge crashed mid-round: no dispatch, delta excluded
                deltas.append(zero_delta)
                continue
            akw = {}
            if self.attack_kind:
                byz_h = np.isin(cohort, self.compromised)
                byz_total += int(byz_h.sum())
                if self._attack_upload:
                    byz = jnp.asarray(byz_h.astype(np.float32))
                    if self._client_sharding is not None:
                        byz = self._put(byz, self._client_sharding)
                    akw["byz"] = byz
            idx_p, mask_p, n_ex_p, train_x, train_y = (
                self._place_round_inputs(idx, mask, n_ex, None)
            )
            rng_e = jax.random.fold_in(base_rng, e)
            with self.tracer.span("round.dispatch"):
                params_e, opt_e, metrics_e = self.round_fn(
                    params0, state["server_opt_state"], train_x, train_y,
                    idx_p, mask_p, n_ex_p, rng_e, **akw,
                )
            deltas.append(jax.tree.map(
                lambda a, b: (a - b).astype(jnp.float32),
                params_e, params0,
            ))
            participation[e] = 1.0
            edge_examples[e] = float(np.asarray(n_ex).sum())
            edge_metrics.append(metrics_e)
            if opt_state_new is None:
                # optimizer="mean" (validate-enforced): every edge's
                # returned opt state is identical — take the first
                opt_state_new = opt_e
        if fail_acc:
            self._fail_stats[round_idx] = fail_acc
        if self.attack_kind:
            self._attack_stats[round_idx] = byz_total
        n_alive = int(participation.sum())
        self._edge_absorbed += participation.astype(np.int64)
        if n_alive == 0:
            # every edge crashed: the round is an exact no-op (params
            # and opt state carry; the zero-example metrics record it)
            new_params = params0
            opt_state_new = state["server_opt_state"]
            metrics = RoundMetrics(jnp.float32(0.0), jnp.float32(0.0))
        else:
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *deltas)
            if hier.core_aggregator in ("median", "trimmed_mean", "krum"):
                mean_delta = robust_reduce(
                    stacked, jnp.asarray(participation),
                    hier.core_aggregator,
                    trim_ratio=hier.core_trim_ratio,
                    byzantine_f=hier.core_krum_byzantine,
                )
            else:
                w = edge_examples * participation.astype(np.float64)
                if hier.core_aggregator == "reputation":
                    w = w * state["edge_trust"].astype(np.float64)
                ws = w.sum()
                w = (w / (ws if ws > 0 else 1.0)).astype(np.float32)
                wj = jnp.asarray(w)
                mean_delta = jax.tree.map(
                    lambda s: jnp.tensordot(wj, s, axes=(0, 0)), stacked
                )
            new_params = jax.tree.map(
                lambda p, d: (p + d.astype(p.dtype)).astype(p.dtype),
                params0, mean_delta,
            )
            losses = jnp.stack([m.train_loss for m in edge_metrics])
            exs = jnp.stack(
                [jnp.asarray(m.examples, jnp.float32) for m in edge_metrics]
            )
            tot = exs.sum()
            metrics = RoundMetrics(
                (losses * exs).sum() / jnp.maximum(tot, 1.0), tot
            )
        # edge-liveness trust EMA (consumed by core "reputation",
        # always maintained as the tier-health signal)
        trust = state["edge_trust"]
        d = hier.core_trust_decay
        trust *= np.float32(1.0 - d)
        trust += np.float32(d) * (~crashed).astype(np.float32)
        union_cohort = np.concatenate(all_cohorts)
        union_nex = np.concatenate(all_nex)
        if self._digest_on:
            # schedule-component capture: the per-edge cohorts' union,
            # in edge order — the two-tier round's realized schedule
            self._digest_cohorts[round_idx] = np.asarray(
                union_cohort, np.int64
            ).copy()
        if self._counters_on:
            stats = self._round_comm(union_cohort, union_nex)
            # per-tier wire accounting: the edge→core tier moves one
            # full delta per LIVE edge on top of the device→edge tier
            # the cohort numbers above describe
            _, p_bytes = self._param_stats()
            stats["hier_core_upload_bytes"] = n_alive * p_bytes
            self._comm_stats[round_idx] = stats
        if n_crashed:
            self._hier_stats[round_idx] = {"edge_crashed": n_crashed}
        if self._population is not None:
            self._population.observe_cohort(
                round_idx, union_cohort, union_nex, None,
            )
        return {
            "params": new_params,
            "server_opt_state": opt_state_new,
            "round": round_idx + 1,
            "rng_key": state["rng_key"],
            "edge_trust": trust,
            "_metrics": metrics,
        }

    def run_round(self, state: Dict[str, Any], round_idx: int,
                  fuse_override: Optional[int] = None) -> Dict[str, Any]:
        """One dispatch: a round, or a fused chunk of rounds.
        ``fuse_override=1`` forces a single unfused round through the
        lazily-built fuse=1 engine twin — the catch-up path for resumes
        that land off a chunk boundary (see _fit_body).

        Span ``round.run`` brackets the whole call and names the
        dispatch's first round (1-based, as the records count): the
        spans below inherit that ``round``, and what runs under none of
        them — this loop's own Python, the observers' bookkeeping — is
        ``round.run``'s self time. The body stays inline under the
        ``with``: one more Python frame beneath the trace of a ViT-sized
        round program cost 12 s of set-up on the chip's host (PERF.md
        section 6, PR 23)."""
        with self.tracer.span("round.run", round=round_idx + 1):
            if self.fedbuff:
                return self._run_async_round(state, round_idx)
            if self._hier:
                return self._run_hier_round(state, round_idx)
            if self._cp_device:
                # device control plane: the program derives its own
                # schedule — none of the host input machinery below runs
                return self._run_device_round(
                    state, round_idx,
                    self.cfg.run.fuse_rounds if fuse_override is None
                    else fuse_override,
                )
            if (self._snapshot_refresh and round_idx > 0
                    and round_idx % self._ledger_cfg.log_every == 0):
                # snapshot/sketch refresh BEFORE this round samples: the
                # cohort for rounds [r, r + log_every) is a pure function of
                # (seed, round, ledger@r) — round 0 keeps the all-unseen
                # uniform prior (the zero snapshot/sketch init_state seeds)
                self._refresh_adaptive_snapshot(round_idx)
            fuse = (
                self.cfg.run.fuse_rounds if fuse_override is None
                else fuse_override
            )
            if fuse > 1:
                return self._run_fused_chunk(state, round_idx, fuse)
            round_fn = self.round_fn
            if self.cfg.run.fuse_rounds > 1:
                round_fn = self._unfused_round_fn()
            (cohort, idx, mask, n_ex, train_x, train_y,
             n_host) = self._round_inputs(round_idx)
            if self._population is not None:
                self._population.observe_cohort(
                    round_idx, cohort, n_host,
                    self.sampler.take_draw_stats(round_idx),
                )
            rng = jax.random.fold_in(state["rng_key"], round_idx)
            # Byzantine mask for this round's cohort: which sampled slots
            # the adversary owns. An ARRAY input alongside n_ex (no
            # retrace); poisson pad slots (id == num_clients) can never be
            # compromised. byzantine_count is recorded for every attack
            # kind (label_flip included — its slots attack through data).
            akw = {}
            if self.attack_kind:
                byz_h = np.isin(np.asarray(cohort), self.compromised)
                self._attack_stats[round_idx] = int(byz_h.sum())
                if self._attack_upload:
                    byz = jnp.asarray(byz_h.astype(np.float32))
                    if self._client_sharding is not None:
                        byz = self._put(byz, self._client_sharding)
                    akw["byz"] = byz
            if self.gossip:
                extra = ()
                if self._gossip_partial:
                    extra = (self._put(
                        jnp.asarray(np.asarray(cohort, np.int32)),
                        self._data_sharding,
                    ),)
                with self.tracer.span("round.dispatch"):
                    replicas, mean_params, metrics = round_fn(
                        state["replicas"], train_x, train_y, idx, mask, n_ex,
                        rng, *extra, **akw,
                    )
                return {
                    "params": mean_params,
                    "server_opt_state": state["server_opt_state"],
                    "round": round_idx + 1,
                    "rng_key": state["rng_key"],
                    "replicas": replicas,
                    "_metrics": metrics,
                }
            if self.store_state:
                # scaffold/feddyn carry c_global on top of the per-client
                # store; error feedback is store-only. One branch covers
                # both — the round fn's extra leading state arg (c_global)
                # and return slot exist exactly when self.stateful.
                common = (state["params"], state["server_opt_state"],
                          train_x, train_y, idx, mask, n_ex, rng)
                glob = (state["c_global"],) if self.stateful else ()
                ledger = None
                if self._data_sharding is not None:
                    # device-resident store: the cohort gather/scatter runs
                    # INSIDE the round program (donated, so the store is
                    # updated in place) — no host sync, multi-host capable
                    cohort_dev = self._put(
                        jnp.asarray(np.asarray(cohort, np.int32)),
                        self._data_sharding,
                    )
                    ltail = (state["ledger"],) if self._ledger_on else ()
                    with self._bucket_compile_span(round_idx, int(idx.shape[1])), \
                            self.tracer.span("round.dispatch"):
                        out = round_fn(
                            *common, *glob, state["c_clients"], cohort_dev,
                            *ltail,
                        )
                    if self._ledger_on:
                        *head, c_clients, ledger, metrics = out
                    else:
                        *head, c_clients, metrics = out
                else:
                    # sequential oracle: host-resident numpy store with an
                    # explicit per-round gather/scatter. Poisson pad slots
                    # carry id == num_clients (OOB by construction): gather
                    # reads row 0 in their place (harmless — pad rows are
                    # fully masked) and the scatter SKIPS them, mirroring
                    # the sharded engine's take-fill/scatter-drop semantics.
                    rows = np.asarray(cohort)
                    real = rows < self.fed.num_clients
                    safe = np.where(real, rows, 0)
                    c_cohort = jax.tree.map(
                        lambda a: jnp.asarray(a[safe]), state["c_clients"]
                    )
                    lkw = {}
                    if self._ledger_on:
                        lkw = dict(
                            ledger=state["ledger"],
                            ledger_ids=jnp.asarray(
                                np.asarray(cohort, np.int32)
                            ),
                        )
                    with self._bucket_compile_span(round_idx, int(idx.shape[1])), \
                            self.tracer.span("round.dispatch"):
                        out = round_fn(
                            *common, *(glob or (None,)), c_cohort, **lkw,
                        )
                    if self._ledger_on:
                        *head, new_c_cohort, ledger, metrics = out
                    else:
                        *head, new_c_cohort, metrics = out
                    with self.tracer.span("round.device_wait",
                                          what="client_state"):
                        fetched = jax.device_get(new_c_cohort)
                    jax.tree.map(
                        lambda store, f: store.__setitem__(
                            rows[real], f[real]
                        ),
                        state["c_clients"], fetched,
                    )
                    c_clients = state["c_clients"]
                new_state = {
                    "params": head[0],
                    "server_opt_state": head[1],
                    "round": round_idx + 1,
                    "rng_key": state["rng_key"],
                    "c_clients": c_clients,
                    "_metrics": metrics,
                }
                if self._ledger_on:
                    new_state["ledger"] = ledger
                if self.stateful:
                    new_state["c_global"] = head[2]
                return new_state
            kw = dict(akw)
            if self.secagg and self.cfg.server.secagg_mode == "pairwise":
                with self.tracer.span("round.secagg_keys"):
                    kw["pair_seeds"] = self._pairwise_seeds(round_idx, n_host)
            if self._ledger_on:
                with self.tracer.span("round.host_inputs.slot_assign"):
                    cohort_ids = jnp.asarray(
                        self._ledger_slot_ids(cohort, round_idx, state)
                    )
                if self._data_sharding is not None:
                    # sharded: positional trailing (byz, ledger, cohort) so
                    # the ledger input stays donatable
                    with self._bucket_compile_span(round_idx, int(idx.shape[1])), \
                            self.tracer.span("round.dispatch"):
                        params, opt_state, ledger, metrics = round_fn(
                            state["params"], state["server_opt_state"],
                            train_x, train_y, idx, mask, n_ex, rng,
                            kw.get("byz"), state["ledger"],
                            self._put(cohort_ids, self._data_sharding),
                        )
                else:
                    with self._bucket_compile_span(round_idx, int(idx.shape[1])), \
                            self.tracer.span("round.dispatch"):
                        params, opt_state, ledger, metrics = round_fn(
                            state["params"], state["server_opt_state"],
                            train_x, train_y, idx, mask, n_ex, rng,
                            ledger=state["ledger"], ledger_ids=cohort_ids,
                            **kw,
                        )
                return {
                    "params": params,
                    "server_opt_state": opt_state,
                    "round": round_idx + 1,
                    "rng_key": state["rng_key"],
                    "ledger": ledger,
                    "_metrics": metrics,
                }
            with self._bucket_compile_span(round_idx, int(idx.shape[1])), \
                    self.tracer.span("round.dispatch"):
                params, opt_state, metrics = round_fn(
                    state["params"], state["server_opt_state"],
                    train_x, train_y, idx, mask, n_ex, rng, **kw,
                )
            return {
                "params": params,
                "server_opt_state": opt_state,
                "round": round_idx + 1,
                "rng_key": state["rng_key"],
                "_metrics": metrics,
            }

    def _run_fused_chunk(self, state: Dict[str, Any], round_idx: int,
                         fuse: int) -> Dict[str, Any]:
        """Dispatch one fused chunk: `fuse` rounds as ONE XLA program.

        The chunk's host inputs are built per sub-round (exactly the
        unfused loop's tensors, prefetch included), stacked host-side
        into [F, ...] slabs, and placed ONCE through the fused
        shardings — the multi-process-capable path (each host uploads
        only its addressable shards). Per-round rngs are the unfused
        loop's exact derivations, so fused ≡ unfused bitwise. Upload
        attacks ride a stacked [F, K] byzantine-mask input; error
        feedback's store enters as the donated scan carry and comes
        back updated in place."""
        # shape buckets compose with fusion at CHUNK granularity: the
        # stacked [F, K, steps, batch] slab must be rectangular, so the
        # chunk dispatches on the max of its sub-rounds' ladder rungs
        # (monotone ladder pick ⇒ identical to picking for the chunk-max
        # requirement). Padded steps are no-ops, so a sub-round riding a
        # larger-than-its-own rung is still bitwise the same round.
        chunk_shape = None
        if self._bucket_ladder is not None:
            chunk_shape = self._bucket_shape(max(
                self._round_bucket_spe(round_idx + j) for j in range(fuse)
            ))
        idxs, masks, n_exs, rngs, cohorts, byz_rows = [], [], [], [], [], []
        train_x = train_y = None
        for j in range(fuse):
            (c_j, i_j, m_j, n_j, train_x, train_y,
             _) = self._round_inputs(round_idx + j, place=False,
                                     shape=chunk_shape)
            if self._population is not None:
                self._population.observe_cohort(
                    round_idx + j, c_j, n_j,
                    self.sampler.take_draw_stats(round_idx + j),
                )
            idxs.append(i_j)
            masks.append(m_j)
            n_exs.append(n_j)
            cohorts.append(np.asarray(c_j, np.int32))
            rngs.append(jax.random.fold_in(state["rng_key"], round_idx + j))
            if self.attack_kind:
                # byzantine_count per fused sub-round, for every attack
                # kind (label_flip attacks through data and composes
                # with fusion with no engine involvement)
                byz_h = np.isin(np.asarray(c_j), self.compromised)
                self._attack_stats[round_idx + j] = int(byz_h.sum())
                if self._attack_upload:
                    byz_rows.append(byz_h.astype(np.float32))
        with self.tracer.span("round.placement"):
            idx_stack = np.stack(idxs)
            if self._stream:
                # stream × fuse: ONE union slab over the chunk's cohorts
                # (static [rows, ...] shape — one trace for the run),
                # stacked indices remapped into it. The engine still
                # sees a single corpus input; only the chunk's unique
                # example records are gathered/uploaded.
                with self.tracer.span("round.stream_slab"):
                    uniq, inv = np.unique(idx_stack, return_inverse=True)
                    rows = self._fused_slab_rows
                    if len(uniq) > rows:
                        raise RuntimeError(
                            f"fused union-slab overflow: chunk gathered "
                            f"{len(uniq)} unique example rows but the "
                            f"static slab holds {rows} — the "
                            f"construction-time sizing (fuse x cohort x "
                            f"cap + 1) should have prevented this"
                        )
                    if self._population is not None:
                        # union-slab dedup under fuse: the whole chunk's
                        # grid slots vs the one slab actually gathered
                        self._population.observe_slab(
                            int(idx_stack.size), int(len(uniq))
                        )
                    # overlapped chunk gather: the PREVIOUS chunk queued
                    # this chunk's union-slab build before its dispatch,
                    # so the mmap I/O ran under device compute. Adopt it
                    # only if the row set matches bitwise what we just
                    # stacked (a cheap np.array_equal vs the expensive
                    # gather) — any mismatch (rung drift, resume seam)
                    # drains to the synchronous build below.
                    for stale in [k for k in self._chunk_prefetch
                                  if k < round_idx]:
                        self._chunk_prefetch.pop(stale).cancel()
                    pre = self._chunk_prefetch.pop(round_idx, None)
                    entry = pre.result() if pre is not None else None
                    if (entry is not None
                            and entry["spe"] == self._prefetch_spe(round_idx)
                            and entry["fuse"] == fuse
                            and np.array_equal(entry["uniq"], uniq)):
                        slab_x = entry["slab_x"]
                        slab_y = entry["slab_y"]
                        self._db_stats["slab_prefetched"] += 1
                    else:
                        if pre is not None:
                            self._db_stats["prefetch_dropped"] += 1
                        slab_x = np.empty(
                            (rows,) + self.fed.train_x.shape[1:],
                            self.fed.train_x.dtype,
                        )
                        slab_y = np.empty(
                            (rows,) + self.fed.train_y.shape[1:],
                            self.fed.train_y.dtype,
                        )
                        slab_x[: len(uniq)] = self.fed.train_x[uniq]
                        slab_y[: len(uniq)] = self.fed.train_y[uniq]
                    idx_stack = inv.reshape(idx_stack.shape).astype(np.int32)
                train_x = self._round_data(
                    self._put_data(jnp.asarray(slab_x)))
                train_y = self._put_data(jnp.asarray(slab_y))
            idx_f = self._put(idx_stack, self._fused_cohort_sharding)
            # mask SPECS [F, K, 2] have no batch dim: fuse replicated,
            # cohort over lanes — the per-client fused sharding
            mask_f = self._put(
                np.stack(masks),
                self._fused_client_sharding if self._spec_inputs
                else self._fused_cohort_sharding,
            )
            n_ex_f = self._put(np.stack(n_exs), self._fused_client_sharding)
        # rng keys are tiny device scalars derived identically on every
        # process; stack on host (normalizing typed PRNG keys — a
        # restored checkpoint's rng_key comes back typed — to their raw
        # uint32 data, which fold_in/split accept with identical bits),
        # replicate like other per-round inputs
        def _key_data(k):
            if jax.dtypes.issubdtype(k.dtype, jax.dtypes.prng_key):
                k = jax.random.key_data(k)
            return np.asarray(k)

        # the keys were derived ON the device, behind the dispatch in
        # flight: reading them back holds the host until that dispatch
        # has finished. That is waiting, not placement, and has a span
        # of its own.
        with self.tracer.span("round.device_wait", what="rng_keys"):
            keys_h = [_key_data(r) for r in rngs]
        with self.tracer.span("round.placement"):
            rngs_f = self._put(np.stack(keys_h), self._data_sharding)
            tail = ()
            if byz_rows:
                tail = (self._put(
                    np.stack(byz_rows), self._fused_client_sharding
                ),)
            if self.ef or self._ledger_on:
                with self.tracer.span("round.host_inputs.slot_assign"):
                    if self._pager is not None:
                        # paged ledger: assign hot slots for the
                        # CHUNK'S cohort union up front (one assignment
                        # protects every sub-round's residents from
                        # mid-chunk eviction), seed paged-in slots,
                        # then ship slot ids; the engine's
                        # gather/scatter is unchanged
                        union = np.unique(np.concatenate(cohorts))
                        self._ledger_slot_ids(union, round_idx, state)
                        cohort_rows = np.stack(
                            [self._pager.lookup(c) for c in cohorts]
                        )
                    else:
                        cohort_rows = np.stack(cohorts)
                cohorts_f = self._put(cohort_rows, self._data_sharding)
        common = (state["params"], state["server_opt_state"], train_x,
                  train_y, idx_f, mask_f, n_ex_f, rngs_f)
        # queue the NEXT chunk's union-slab store gather before this
        # chunk's dispatch — the I/O overlaps device compute (tentpole
        # of the store data plane: slab_build collapses under dispatch)
        self._submit_chunk_slab_prefetch(round_idx, fuse)
        ledger = None
        with self._bucket_compile_span(round_idx, int(idx_f.shape[2])), \
                self.tracer.span("round.dispatch", fuse=fuse):
            if self.ef:
                if self._ledger_on:
                    (params, opt_state, c_clients, ledger,
                     metrics) = self.round_fn(
                        *common, state["c_clients"], cohorts_f,
                        state["ledger"],
                    )
                else:
                    params, opt_state, c_clients, metrics = self.round_fn(
                        *common, state["c_clients"], cohorts_f,
                    )
            elif self._ledger_on:
                # the ledger rides the fused scan carry; per-sub-round
                # cohort ids are a stacked [fuse, K] scan input
                params, opt_state, ledger, metrics = self.round_fn(
                    *common, tail[0] if tail else None, state["ledger"],
                    cohorts_f,
                )
            else:
                params, opt_state, metrics = self.round_fn(*common, *tail)
        new_state = {
            "params": params,
            "server_opt_state": opt_state,
            "round": round_idx + fuse,
            "rng_key": state["rng_key"],
            "_metrics": metrics,
        }
        if self._ledger_on:
            new_state["ledger"] = ledger
        if self.ef:
            new_state["c_clients"] = c_clients
        return new_state

    # ------------------------------------------------------------------

    def _run_dir(self) -> str:
        """Base directory for this run's artifacts; out_dir="" → cwd."""
        return os.path.join(self.cfg.run.out_dir or ".", self.cfg.name)

    def _stop_prefetch(self) -> None:
        """Shut down the host prefetch worker (no-op when none ran).

        Outstanding futures are CANCELLED before their keys are
        dropped: with a second in-flight placed buffer, clearing the
        dict alone would orphan a still-running future whose
        device_put lands AFTER an abort/KeyboardInterrupt — masking
        the ledger's final flush and racing the shutdown. A future
        already executing cannot be cancelled; ``shutdown(wait=True)``
        then blocks until it drains, so nothing runs past this call."""
        ex, self._host_executor = self._host_executor, None
        for fut in self._prefetch.values():
            fut.cancel()
        self._prefetch.clear()
        for fut in self._chunk_prefetch.values():
            fut.cancel()
        self._chunk_prefetch.clear()
        if ex is not None:
            ex.shutdown(wait=True, cancel_futures=True)

    def _ckpt_store(self, required: bool = False) -> Optional[CheckpointStore]:
        """The store under ``_run_dir()``; without ``run.out_dir`` None,
        unless ``required`` (evaluate and export read a checkpoint back
        from the cwd's run directory then). The one place the driver
        builds a store, and so the place a process imports orbax
        (utils/checkpoint.py): the span ``setup.checkpoint_store`` holds
        those seconds in the start-up record of exactly the runs that
        pay them. ``_fit`` builds its store before the round loop, so a
        checkpointing run pays them at the start of ``fit()`` (or at a
        resume, retry, replay, evaluate or export) and never inside a
        round."""
        if not (self.cfg.run.out_dir or required):
            return None
        with self.tracer.span("setup.checkpoint_store"):
            return CheckpointStore(os.path.join(self._run_dir(), "ckpt"))

    # EF residuals and scaffold/feddyn control variates share the
    # checkpoint key "c_clients" (same [N_pad, ...] shapes); a resume
    # across those settings would silently reinterpret one as the
    # other (ADVICE r4 #3). A sidecar records the store's SEMANTICS —
    # not the raw algorithm string: stateless pairs (fedavg ↔ fedprox)
    # have no c_clients rows and may resume each other freely, while
    # structurally-different states (gossip replicas, fedbuff queue)
    # already fail orbax's template restore on their own.
    def _state_kind(self) -> Dict[str, Any]:
        if self.scaffold:
            kind = "scaffold"
        elif self.feddyn:
            kind = "feddyn"
        elif self.ef:
            kind = "ef"
        else:
            kind = "none"
        return {"client_state": kind}

    def _state_kind_path(self) -> str:
        return os.path.join(self._run_dir(), "ckpt", "STATE_KIND.json")

    def _write_state_kind(self) -> None:
        if not self._primary or not self.cfg.run.out_dir:
            return
        path = self._state_kind_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # atomic: a crash mid-write must not leave a truncated sidecar
        # that would later read as corrupt
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._state_kind(), f)
        os.replace(tmp, path)

    def _check_state_kind(self) -> None:
        """Reject a run whose existing checkpoint store was written under
        different state semantics. Absent sidecar (pre-r5 run dirs) is
        accepted for backward compatibility; a corrupt sidecar is an
        error (silently skipping the check would defeat it)."""
        try:
            with open(self._state_kind_path()) as f:
                saved = json.load(f)
        except FileNotFoundError:
            return
        except json.JSONDecodeError as e:
            raise ValueError(
                f"corrupt state-kind sidecar {self._state_kind_path()}: {e}; "
                f"delete it (accepting the pre-r5 no-provenance behavior) "
                f"or use a fresh run.out_dir"
            ) from e
        want = self._state_kind()
        if saved != want:
            raise ValueError(
                f"checkpoint store at {self._state_kind_path()} was written "
                f"with state semantics {saved}, but this run is configured "
                f"as {want}; 'c_clients' rows would be silently "
                f"reinterpreted — use a fresh run.out_dir or match the "
                f"original algorithm/error_feedback settings"
            )

    def _ledger_slot_ids(self, cohort, round_idx: int,
                         state: Dict[str, Any]) -> np.ndarray:
        """Ledger row ids for a cohort: the client ids verbatim on the
        dense store; hot-set SLOT ids under paging (obs/ledger.py
        LedgerPager). Paging cold members in seeds their slots from the
        cold mmap via one tiny async device scatter — ``state["ledger"]``
        is rebound to the seeded array, so the subsequent round dispatch
        reads client rows identical to the dense run's (the paging-is-
        invisible contract). Pads (id == num_clients) and anything not
        resident map out of bounds and drop, exactly like dense pads."""
        ids = np.asarray(cohort, np.int64)
        if self._pager is None:
            return ids.astype(np.int32)
        def fetch_hot():
            with self.tracer.span("round.device_wait", what="ledger_hot"):
                return np.asarray(jax.device_get(state["ledger"]))

        slots, new_slots, seed_rows = self._pager.assign(
            ids, round_idx, fetch_hot=fetch_hot,
        )
        if len(new_slots):
            upd = self._put(jnp.asarray(seed_rows), self._data_sharding)
            at = self._put(jnp.asarray(new_slots), self._data_sharding)
            state["ledger"] = state["ledger"].at[at].set(upd)
            self._ledger_ref = state["ledger"]
        return slots

    def _log_ledger(self, round_idx: int) -> Optional[np.ndarray]:
        """Emit one columnar `client_ledger` JSONL record from the
        device-resident ledger (rows with at least one participation).
        Called at periodic flush boundaries, at the adaptive sampler's
        snapshot refreshes (which consume the returned array — the
        JSONL flush IS the sampler's feed), and — via fit()'s finally —
        on EVERY exit path, so aborted runs (HealthAbortError,
        KeyboardInterrupt, crashes) still land their partial ledger,
        mirroring the trace-on-abort guarantee. Returns the fetched
        ``[num_clients, LEDGER_WIDTH]`` array (None when no ledger)."""
        if self._ledger_ref is None:
            return None
        from colearn_federated_learning_tpu.obs.ledger import LEDGER_COLS

        ids, rows = self._fetch_ledger_rows()
        rec: Dict[str, Any] = {
            "event": "client_ledger",
            "round": int(round_idx),
            "num_clients": int(self.fed.num_clients),
            "ema": self._ledger_cfg.ema,
            "zmax": self._ledger_cfg.zmax,
            "ids": [int(i) for i in ids],
            "count": [int(v) for v in rows[:, 0]],
            "flagged": [int(v) for v in rows[:, 1]],
        }
        for j, col in enumerate(LEDGER_COLS[2:], start=2):
            rec[col] = [round(float(v), 6) for v in rows[:, j]]
        self.logger.log(rec)
        self._ledger_logged_round = int(round_idx)
        return ids, rows

    def _fetch_ledger_rows(self):
        """ONE blocking device fetch of the ledger, reduced to the
        columnar active view ``(client ids, [A, LEDGER_WIDTH] rows)`` —
        ids ascending, one row per client with ≥1 participation. Dense:
        a flatnonzero over the fetched store. Paged: the hot set is
        written back into the cold mmap and the merged view scanned —
        client ids throughout, never slots, so records/reports/snapshots
        are layout-independent (paged ≡ dense, test-pinned)."""
        with self.tracer.span("round.device_wait", what="ledger"):
            hot = np.asarray(jax.device_get(self._ledger_ref))
        if self._pager is not None:
            return self._pager.active_rows(hot)
        active = np.flatnonzero(hot[:, 0] > 0)
        return active, hot[active]

    def _refresh_adaptive_snapshot(self, round_idx: int) -> None:
        """Refresh the sampler's ledger view at a ``log_every`` round
        boundary: ONE blocking device fetch of the ledger (the same
        fetch emits the periodic ``client_ledger`` JSONL record — the
        flush is the sampler's feed). The refresh rounds are pure round
        arithmetic (multiples of log_every — chunk boundaries under
        fuse_rounds, enforced by validate()), so a resumed run
        refreshes at exactly the rounds the straight run did; between
        refreshes the checkpointed snapshot/sketch covers it.

        Only the three scored columns flow to the sampler
        (sampler.SNAPSHOT_COLS — count, flagged, ema_loss):
        ``adaptive`` scatters them into its dense [num_clients, 3]
        snapshot; ``streaming`` keeps the fixed-size columnar sketch
        (top participation, ties by id) and never builds anything
        O(num_clients)."""
        if self._ledger_ref is None:
            return
        if self._ledger_logged_round == round_idx:
            # a flush boundary already logged (and fetched) this exact
            # round — fetch without emitting a duplicate JSONL record
            ids, rows = self._fetch_ledger_rows()
        else:
            ids, rows = self._log_ledger(round_idx)
        # LEDGER_COLS → SNAPSHOT_COLS: count, flagged, ema_loss
        cols = rows[:, [0, 1, 5]].astype(np.float32)
        self._sampler_snapshot_round = int(round_idx)
        if self._adaptive:
            dense = np.zeros((self.fed.num_clients, 3), np.float32)
            dense[ids] = cols
            self._sampler_snapshot = dense
            self.sampler.observe_snapshot(dense, round_idx)
            return
        m = len(self._sketch_ids)
        total_flagged = float(cols[:, 1].sum())
        if len(ids) > m:
            keep = np.sort(np.lexsort((ids, -cols[:, 0]))[:m])
            ids, cols = ids[keep], cols[keep]
        if self._population is not None:
            # sketch-vs-universe flag coverage: how much of the
            # ledger's flagged (attacker-evidence) mass the retained
            # sketch rows carry — the number that says whether the
            # streaming sampler can SEE the attacker population
            self._population.observe_sketch_refresh(
                total_flagged, float(cols[:, 1].sum())
            )
        self._sketch_ids = np.full(m, -1, np.int32)
        self._sketch_ids[: len(ids)] = ids
        self._sketch_stats = np.zeros((m, 3), np.float32)
        self._sketch_stats[: len(ids)] = cols
        self.sampler.observe_snapshot(
            {
                "ids": ids,
                "count": cols[:, 0],
                "flagged": cols[:, 1],
                "ema_loss": cols[:, 2],
            } if len(ids) else None,
            round_idx,
        )

    def _log_population(self, last_round: int) -> None:
        """Fold the population tracker's window into one
        ``population_health`` JSONL record (no-op when tracking is off
        or the window saw no rounds — tail flushes stay silent)."""
        if self._population is None:
            return
        store_arrays = [
            a for a in (self.fed.train_x, self.fed.train_y)
            if hasattr(a, "gather_stats")
        ]
        sketch_ids = refresh_age = None
        if self._streaming and self._snapshot_refresh:
            sketch_ids = self._sketch_ids
            refresh_age = max(
                0, int(last_round) - int(self._sampler_snapshot_round)
            )
        rec = self._population.window_record(
            last_round, pager=self._pager, store_arrays=store_arrays,
            sketch_ids=sketch_ids, refresh_age=refresh_age,
        )
        if rec is not None:
            self.logger.log(rec)

    def _seed_sampler_from_state(self, state: Dict[str, Any]) -> None:
        """Feed the sampler the checkpoint's ACTIVE snapshot (adaptive)
        or score sketch (streaming) so a resumed run scores mid-window
        rounds exactly like the straight run did (zeros / empty sketch
        on a fresh run → the uniform all-unseen prior)."""
        self._sampler_snapshot_round = int(state["ledger_snapshot_round"])
        if self._adaptive:
            self._sampler_snapshot = state["ledger_snapshot"]
            self.sampler.observe_snapshot(
                self._sampler_snapshot, self._sampler_snapshot_round
            )
            return
        self._sketch_ids = np.asarray(state["ledger_sketch_ids"], np.int32)
        self._sketch_stats = np.asarray(
            state["ledger_sketch_stats"], np.float32
        )
        live = self._sketch_ids >= 0
        self.sampler.observe_snapshot(
            {
                "ids": self._sketch_ids[live],
                "count": self._sketch_stats[live, 0],
                "flagged": self._sketch_stats[live, 1],
                "ema_loss": self._sketch_stats[live, 2],
            } if live.any() else None,
            self._sampler_snapshot_round,
        )

    def _carry_host_ledger_state(self, state: Dict[str, Any]) -> None:
        """run_round returns a fresh state dict holding only the round
        program's outputs — re-attach the host-side sampler snapshot /
        sketch and the pager's cold-spill bookkeeping so they ride
        every checkpoint."""
        if self._snapshot_refresh:
            state["ledger_snapshot_round"] = self._sampler_snapshot_round
            if self._adaptive:
                state["ledger_snapshot"] = self._sampler_snapshot
            else:
                state["ledger_sketch_ids"] = self._sketch_ids
                state["ledger_sketch_stats"] = self._sketch_stats
        if self._pager is not None:
            state["ledger_cold"] = self._pager.cold
            state["ledger_slots"] = self._pager.slot_clients
            state["ledger_slot_used"] = self._pager.slot_used

    def _staleness_percentiles(self) -> tuple:
        """(p50, p90, max) over the pooled per-update staleness
        histogram accumulated across every async round this fit —
        exact weighted percentiles (the histogram is value → count, so
        no sample is ever dropped), (0.0, 0.0, 0) before any absorb."""
        if not self._staleness_hist:
            return (0.0, 0.0, 0)
        vals = np.array(sorted(self._staleness_hist), np.int64)
        cnts = np.array(
            [self._staleness_hist[int(v)] for v in vals], np.int64
        )
        cum = np.cumsum(cnts)
        total = int(cum[-1])
        p50 = float(vals[np.searchsorted(cum, 0.5 * total)])
        p90 = float(vals[np.searchsorted(cum, 0.9 * total)])
        return (p50, p90, int(vals[-1]))

    def fit(self, state: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        caller_state = state is not None
        # per-fit accumulators for the end-of-fit `run_summary` record
        # (cumulative wire bytes, rounds, wall time, compile count) and
        # the ledger's final flush
        self._fit_t0 = time.perf_counter()
        self._rounds_done = 0
        self._run_totals = {
            k: 0 for k in ("upload_bytes", "upload_bytes_raw",
                           "download_bytes", "download_bytes_raw",
                           "upload_bytes_full")
            + (("hier_core_upload_bytes",) if self._hier else ())
        }
        self._total_compiles = 0
        self._total_compile_ms = 0.0
        self._ledger_logged_round = -1
        self._traffic_totals = {}
        self._async_absorbed = 0
        self._staleness_warned = False
        self._staleness_hist = {}
        self._per_version_absorbed[:] = 0
        self._version_readmitted = 0
        self._readmit_warned = False
        self._edge_absorbed[:] = 0
        self._db_stats = {k: 0 for k in self._db_stats}
        # Checkpoint provenance baseline: only checkpoints written BY THIS
        # fit() call may be restored on retry — restoring a stale
        # checkpoint left in the same out_dir by an earlier run would
        # silently return the old run's params as "recovered".
        baseline_step = None
        if self.cfg.run.max_retries > 0:
            store = self._ckpt_store()
            if store is not None:
                baseline_step = store.latest_step()
                store.close()
        retries = 0
        if self._exec_reg is not None:
            # fit-scoped: sequential fits on other Experiment instances
            # must not route through this registry's cache
            exec_mod.install(self._exec_reg)
        try:
            while True:
                try:
                    return self._fit(state)
                except KeyboardInterrupt:
                    raise
                except HealthAbortError:
                    # the monitor's configured abort is a VERDICT, not a
                    # transient failure — a NaN/diverged run restored
                    # from its own checkpoint re-diverges; retrying
                    # would spend the retry budget hiding the signal
                    raise
                except digest_mod.DigestResumeError:
                    # strict digest verification failed: the retry path
                    # skips verification (its own log tail is expected
                    # to disagree), so retrying would silently bypass
                    # the --strict-digest contract
                    raise
                except HbmBudgetError:
                    # the over-budget verdict is a property of the
                    # compiled program, not a transient failure —
                    # recompiling predicts the same peak
                    raise
                except Exception as e:  # noqa: BLE001 — failure recovery (§5)
                    if retries >= self.cfg.run.max_retries:
                        raise
                    restored = None
                    store = self._ckpt_store()
                    if store is not None:
                        latest = store.latest_step()
                        if latest is not None and (
                            baseline_step is None or latest > baseline_step
                        ):
                            restored, _ = store.restore(
                                template=self.init_state()
                            )
                        store.close()
                    if restored is None and caller_state:
                        # the caller's warm-start state may have been
                        # donated to the failed attempt's round dispatch;
                        # with no checkpoint of our own there is nothing
                        # safe to resume from
                        raise
                    retries += 1
                    self.logger.log({
                        "event": "retry",
                        "attempt": retries,
                        "round": None if restored is None else int(restored["round"]),
                        "error": repr(e)[:200],
                    })
                    # drop any in-flight prefetch state from the failed
                    # attempt; state=None restarts fresh (or re-resumes,
                    # if this run was itself a --resume run)
                    self._stop_prefetch()
                    state = restored
        finally:
            self._stop_prefetch()
            if self._exec_reg is not None:
                exec_mod.uninstall()
                # abort paths can leave queued registry records behind
                # the last flush boundary — the JSONL gets them anyway
                try:
                    for _rec in self._exec_reg.drain_records():
                        self.logger.log(_rec)
                except Exception as e:
                    print(f"executable record flush failed: {e}",
                          flush=True)
            if self._ledger_on and self._ledger_ref is not None:
                # final (or abort-path partial) ledger flush — same
                # every-exit-path guarantee as the trace export below
                try:
                    if self._ledger_logged_round != self._rounds_done:
                        self._log_ledger(self._rounds_done)
                except Exception as e:
                    print(f"client_ledger flush failed: {e}", flush=True)
            try:
                # end-of-fit run_summary: totals that otherwise require
                # re-aggregating the whole JSONL (aborts included)
                self.logger.log({
                    "event": "run_summary",
                    "rounds": int(self._rounds_done),
                    "wall_time_sec": round(
                        time.perf_counter() - self._fit_t0, 3
                    ),
                    "compiles": int(self._total_compiles),
                    "compile_ms": round(self._total_compile_ms, 3),
                    # double-buffer accounting: rounds whose host build
                    # / device placement were served from the prefetch
                    # buffers (i.e. hidden under the previous round's
                    # dispatch), and drains where purity forced a
                    # rebuild
                    **{k: int(v) for k, v in self._db_stats.items()},
                    **{k: int(v) for k, v in self._run_totals.items()},
                    # adapter-plane wire accounting: the full-delta ÷
                    # adapter-delta upload ratio (1.0 when lora is off)
                    "wire_reduction_vs_full": round(
                        self.wire_reduction_vs_full(), 2
                    ),
                    # ledger paging accounting: evictions are the cold
                    # spills, page_syncs the blocking hot-set fetches
                    # they forced (0 when the working set fit)
                    **({
                        "ledger_evictions": int(self._pager.evictions),
                        "ledger_page_syncs": int(self._pager.page_syncs),
                    } if self._pager is not None else {}),
                    # production-traffic totals (run.churn / fedbuff):
                    # staleness clamps, backpressure sheds, realized
                    # churn counts — present only on runs that saw them
                    **{k: int(v) for k, v in sorted(
                        self._traffic_totals.items()
                    )},
                    # the async throughput headline: updates absorbed
                    # (weight > 0 at admission) per wall-clock second,
                    # at the configured staleness bound — the number
                    # the async_throughput bench entry reads
                    **({
                        "async_updates_absorbed": int(self._async_absorbed),
                        "async_updates_per_sec": round(
                            self._async_absorbed
                            / max(time.perf_counter() - self._fit_t0, 1e-9),
                            3,
                        ),
                        "async_staleness_bound": int(
                            2 * self.cfg.server.async_max_staleness
                        ),
                        # pooled staleness distribution over every
                        # absorbed update this fit (satellite of the
                        # hier_async bench: the bound above is the
                        # ceiling, these are the realized quantiles)
                        "async_staleness_p50": self._staleness_percentiles()[0],
                        "async_staleness_p90": self._staleness_percentiles()[1],
                        "async_staleness_max": self._staleness_percentiles()[2],
                    } if self.fedbuff else {}),
                    # multi-version plane (server.async_versions > 1):
                    # per-version absorbed counts + late re-admissions
                    **({
                        "async_per_version": {
                            str(v): int(n) for v, n in enumerate(
                                self._per_version_absorbed[:self._versions]
                            )
                        },
                    } if self.fedbuff and self._versions > 1 else {}),
                    # hierarchy plane (server.hierarchy): per-edge
                    # absorbed updates and the final edge-trust vector
                    **({
                        "hier_edges": int(
                            self.cfg.server.hierarchy.num_edges
                        ),
                        "hier_edge_absorbed": {
                            str(e): int(n)
                            for e, n in enumerate(self._edge_absorbed)
                        },
                    } if self._hier else {}),
                    # population totals (run.obs.population): lifetime
                    # coverage / participation / pager hit rate / store
                    # bytes — `colearn summarize` renders these
                    **(self._population.summary_totals(
                        self._pager,
                        (self.fed.train_x, self.fed.train_y),
                    ) if self._population is not None else {}),
                    # compiled-program observatory: the run's predicted
                    # HBM high-water mark and which program set it
                    **({
                        "hbm_peak_bytes": int(self._exec_reg.peak_bytes),
                        "hbm_peak_program": self._exec_reg.peak_program,
                        "executables_compiled": int(
                            self._exec_reg.total_compiles
                        ),
                    } if self._exec_reg is not None
                        and self._exec_reg.peak_program else {}),
                })
            except Exception as e:
                print(f"run_summary log failed: {e}", flush=True)
            if self.tracer.trace and self.cfg.run.out_dir:
                # end-of-fit Chrome-trace dump (aborted/failed runs
                # included — the trace is the post-mortem artifact).
                # Multi-process: non-primaries write per-host
                # `trace.p<i>.json` fragments; the primary merges every
                # fragment present into the final trace.json so the
                # timeline carries one lane group per host (fragments
                # from hosts that finish later stay loadable on their
                # own — the merge is best-effort by design).
                try:
                    if self._primary:
                        import glob as _glob

                        frags = sorted(_glob.glob(
                            os.path.join(self._run_dir(), "trace.p*.json")
                        ))
                        path = self.tracer.export(
                            os.path.join(self._run_dir(), "trace.json"),
                            fragments=frags,
                        )
                        if path:
                            self.logger.log({
                                "event": "trace", "path": path,
                                "merged_fragments": len(frags),
                            })
                    else:
                        self.tracer.export(os.path.join(
                            self._run_dir(),
                            f"trace.p{self._process_index}.json",
                        ))
                except Exception as e:
                    print(f"trace export failed: {e}", flush=True)
            # flush + join the TensorBoard writer thread (no-op without TB)
            self.logger.close()

    def _fit(self, state: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        cfg = self.cfg
        store = self._ckpt_store()
        try:
            return self._fit_body(state, store)
        finally:
            # close on BOTH paths — a crashed attempt under run.max_retries
            # must not leak an open orbax manager per retry
            if store is not None:
                store.close()

    def _fit_body(self, state, store):
        cfg = self.cfg
        if store and store.latest_step() is not None:
            # checked for NON-resume runs too: a fresh run over a
            # mismatched store would overwrite the sidecar while orbax
            # retains the old run's higher-numbered checkpoints — a later
            # resume would then load them under the new (wrong) semantics
            self._check_state_kind()
        resumed = False
        if state is None:
            if cfg.run.resume and store and store.latest_step() is not None:
                template = self.init_state()
                state, step = store.restore(template=template)
                resumed = True
                self.logger.log({
                    "event": "resumed", "round": int(state["round"]),
                    # the two host pipelines use different (both
                    # deterministic) permutation RNGs; exact schedule
                    # replay requires resuming on the same kind
                    "host_pipeline": "native" if self._native else "numpy",
                })
            else:
                state = self.init_state()
        # The digest-chain head is host bookkeeping, not live round
        # state (run_round returns fresh dicts that would drop it):
        # pop it before placement and re-anchor the recorder. A retried
        # attempt (run.max_retries) re-enters here with the restored
        # head and NO verification — its own log tail past the restore
        # point is expected, and the re-run boundaries overwrite it
        # (last-wins in obs/digest.py's stream view).
        head = state.pop("digest_head", None)
        self._digest_prev, self._digest_prev_round = (
            digest_mod.head_unpack(head) if head is not None
            else (digest_mod.GENESIS, 0)
        )
        self._digest_cohorts.clear()
        if self._digest_on:
            self._digest_window = digest_mod.RoundWindow()
        if self._digest_on and resumed and cfg.run.obs.digest.verify_resume:
            self._verify_digest_resume(int(state["round"]))
        state = self._place_state(state)
        if self._ledger_on:
            self._ledger_ref = state.get("ledger")
        if self._snapshot_refresh:
            # seed the sampler with the checkpoint's ACTIVE snapshot /
            # sketch (zeros/empty on a fresh run → the uniform all-
            # unseen prior); refreshes at later log_every boundaries
            # override it at exactly the rounds the straight run did
            self._seed_sampler_from_state(state)
        start_round = int(state["round"])
        self._rounds_done = max(self._rounds_done, start_round)
        if start_round == 0:
            # precision/fusion provenance: every throughput or MFU
            # number read off this log is meaningless without the
            # dtype policy it ran under (`colearn summarize` surfaces
            # this record as its precision line)
            self.logger.log({
                "event": "precision",
                "param_dtype": cfg.run.param_dtype,
                "compute_dtype": cfg.run.compute_dtype,
                "local_param_dtype": (
                    cfg.run.local_param_dtype or cfg.run.param_dtype
                ),
                "fused_apply": bool(cfg.server.fused_apply),
                "double_buffer": bool(self._double_buffer),
                "control_plane": cfg.run.control_plane,
                **({"frozen_base_bytes": self.frozen_base_bytes(),
                    "frozen_base_dtype": (cfg.run.local_param_dtype
                                          or cfg.run.param_dtype)}
                   if self._lora else {}),
            })
        if start_round == 0 and self._poisson:
            self.logger.log({
                "event": "poisson_sampling",
                "q": round(self.sampler.q, 6),
                "cap": int(self._poisson_cap),
                # exact total abort probability over the run — the
                # δ_abort term of the (ε, δ + δ_abort) guarantee for the
                # aborting mechanism (see dp_client_epsilon)
                "dp_delta_abort": float(self.dp_delta_abort()),
            })
        if start_round == 0 and self._churn is not None:
            # churn provenance: the full hazard model, so any staleness
            # / dropout / convergence number in this log can be
            # attributed to the traffic shape it ran under
            cch = cfg.run.churn
            self.logger.log({
                "event": "churn",
                "diurnal_period": int(cch.diurnal_period),
                "diurnal_amplitude": float(cch.diurnal_amplitude),
                "base_availability": float(cch.base_availability),
                "min_availability": float(cch.min_availability),
                "dropout_hazard": float(cch.dropout_hazard),
                "crash_rate": float(cch.crash_rate),
                # trace replay (run.churn.trace): the availability
                # schedule came from a recorded on/off bitmap, not the
                # analytic diurnal model — record its shape so a
                # resume/replay can be checked against the same file
                **({
                    "trace": str(cch.trace),
                    "trace_rounds": int(self._churn.trace_rounds),
                    "trace_rows": int(self._churn.trace_rows),
                } if cch.trace else {}),
            })
        if start_round == 0 and self._hier:
            # hierarchy provenance: the two-tier topology and the core
            # defense every per-tier number in this log ran under
            hch = cfg.server.hierarchy
            self.logger.log({
                "event": "hierarchy",
                "num_edges": int(hch.num_edges),
                "core_aggregator": str(hch.core_aggregator),
                "edge_aggregator": str(cfg.server.aggregator),
                "edge_dropout_rate": float(hch.edge_dropout_rate),
                "core_trust_decay": float(hch.core_trust_decay),
            })
        if start_round == 0 and self.fedbuff and self._versions > 1:
            # multi-version provenance: concurrent model lines and the
            # retirement policy their generations age under
            self.logger.log({
                "event": "async_versions",
                "versions": int(self._versions),
                "retire_rounds": int(cfg.server.async_retire_rounds),
                "retire_updates": int(cfg.server.async_retire_updates),
                "readmit_decay": float(cfg.server.async_readmit_decay),
                "strict_versions": bool(cfg.run.strict_versions),
            })
        if start_round == 0 and self._bucket_ladder is not None:
            # shape-bucket provenance: the ladder every round's grid is
            # drawn from (rungs in steps_per_epoch), plus the bound the
            # compile budget is asserted against
            self.logger.log({
                "event": "shape_buckets",
                "ladder": [int(r) for r in self._bucket_ladder],
                "full_steps_per_epoch": int(self.shape.steps_per_epoch),
                "max_compiles_per_engine": len(self._bucket_ladder),
            })
        if start_round == 0 and self.attack_kind:
            # attack provenance: everything needed to attribute a run's
            # metrics to its adversary (kind, knobs, the compromised set)
            self.logger.log({
                "event": "attack",
                "kind": self.attack_kind,
                "fraction": cfg.attack.fraction,
                "scale": cfg.attack.scale,
                "eps": cfg.attack.eps,
                "n_compromised": int(len(self.compromised)),
                # the FULL set (one event per run): the `colearn
                # clients` report scores the anomaly flag against it
                "compromised": [int(c) for c in self.compromised],
            })
        if start_round == 0 and cfg.dp.enabled and cfg.dp.clipping == "two_pass":
            # ADVICE r5 #1: two_pass clipping is exact only up to
            # floating-point reassociation between the pass-1 norms and
            # the pass-2 released gradients; the accountant does not
            # model that slack, so make the assumption visible in the
            # run log next to the epsilons it qualifies
            self.logger.log({
                "event": "warning",
                "warning": "dp_two_pass_clipping",
                "detail": (
                    "dp.clipping='two_pass' with DP accounting enabled: "
                    "the reported dp_epsilon assumes exact per-example "
                    "clipping; two_pass clipping is exact only up to "
                    "floating-point reassociation between the norm pass "
                    "and the release pass"
                ),
            })
        if start_round == 0 and self.fed.meta.get("repair_used"):
            # the Dirichlet extreme-α repair changed the realized label
            # skew — record it in the run log so experiments at extreme α
            # know their partition was patched (data/partition.py)
            self.logger.log({
                "event": "partition_repair",
                "moved": int(self.fed.meta.get("repair_moved", 0)),
            })
        t_start = time.perf_counter()

        # Rounds are DISPATCHED asynchronously; per-round metric scalars
        # stay on device in `pending` and are drained in one device_get at
        # flush boundaries. Host↔device round-trips (each one a host
        # sync that stalls the dispatch queue) happen once per flush, not
        # once per round.
        # Throughput is measured per flush window (dispatch timestamps are
        # meaningless under async execution); the first window includes
        # compile time.
        flush_every = max(1, cfg.run.metrics_flush_every)
        if cfg.run.sanitize:
            flush_every = 1  # sanitize wants per-round finiteness checks
        if self._stream:
            # every dispatched-but-unexecuted round holds a full slab in
            # HBM; cap the async backlog so stream mode's bounded-memory
            # promise survives (≤2 dispatched + 1 prefetching)
            flush_every = min(flush_every, 2)
        pending = []  # (round_idx, RoundMetrics-on-device)
        flush_t0 = time.perf_counter()

        obs_cfg = cfg.run.obs

        def flush_obs(last_round):
            """Drain the tracer (+ device-memory gauges) into the JSONL
            — one `spans` record per flush window, not per span."""
            phases = self.tracer.drain()
            if phases:
                comp = phases.get("compile")
                if comp:
                    # run_summary accounting: lifetime compile totals
                    self._total_compiles += comp["count"]
                    self._total_compile_ms += comp["total_ms"]
                self.logger.log({
                    "event": "spans", "round": last_round, "phases": phases,
                    "process_index": int(self._process_index),
                })
            if obs_cfg.device_memory:
                mem = device_memory_stats()
                if mem:
                    self.logger.log(
                        {"event": "device_memory", "round": last_round, **mem}
                    )
            if self._exec_reg is not None:
                # registry-built records (executable_compiled / retrace
                # / warning) + this window's HBM high-water mark
                for rec in self._exec_reg.drain_records():
                    self.logger.log(rec)
                wm = self._exec_reg.watermark(last_round)
                if wm is not None:
                    self.logger.log(wm)
            self._log_population(last_round)

        def unhealthy(events, current_state):
            """Apply the configured on_unhealthy policy to this window's
            health events (already logged)."""
            if not events or obs_cfg.on_unhealthy == "warn":
                return
            if obs_cfg.on_unhealthy == "checkpoint_abort" and store is not None:
                with self.tracer.span("round.checkpoint"):
                    self._write_state_kind()
                    store.save(
                        int(current_state["round"]),
                        self._state_for_save(current_state),
                        force=True, block=True,
                    )
            flush_obs(int(current_state["round"]))
            kinds = ", ".join(
                f"{e['kind']}@round {e['round']}" for e in events
            )
            raise HealthAbortError(
                f"run.obs.on_unhealthy={obs_cfg.on_unhealthy!r}: {kinds}"
            )

        def flush(current_state):
            nonlocal flush_t0
            if not pending:
                return
            if self._cp_device:
                # drain the device-derived schedules FIRST: the record
                # loop below pops the per-round stats this populates
                self._drain_device_sched()
            with self.tracer.span("round.fetch"):
                fetched = jax.device_get([m for _, m in pending])
            dt = time.perf_counter() - flush_t0
            rounds_per_sec = len(pending) / dt if dt > 0 else 0.0
            updates_per_sec = (
                rounds_per_sec * cfg.server.cohort_size / self.n_chips
            )
            health_events = []
            for (ridx, _), m in zip(pending, fetched):
                record = {
                    "round": ridx + 1,
                    "train_loss": float(m.train_loss),
                    "examples": float(m.examples),
                }
                # a model's own counters (RoundMetrics.aux; none but
                # for models/keye.py)
                record.update({k: float(v) for k, v
                               in dict(getattr(m, "aux", ())).items()})
                comm = self._comm_stats.pop(ridx, None)
                fail = self._fail_stats.pop(ridx, None)
                if comm:
                    record.update(comm)
                if fail:
                    record.update(fail)
                if self._digest_window is not None:
                    # fold this round into the digest window (flush
                    # drains pending in round order, so the fold is
                    # invariant to flush cadence and fuse_rounds)
                    self._digest_window.observe(
                        ridx + 1, self._digest_cohorts.pop(ridx, None),
                        comm, fail,
                    )
                if self.health is not None:
                    ev = self.health.observe_loss(ridx + 1, record["train_loss"])
                    if ev is not None:
                        health_events.append(ev)
                if cfg.dp.enabled:
                    record["dp_epsilon"] = round(self.dp_epsilon(ridx + 1), 4)
                if cfg.server.dp_client_noise_multiplier > 0.0:
                    record["dp_client_epsilon"] = round(
                        self.dp_client_epsilon(ridx + 1), 4
                    )
                if ridx in self._async_stats:
                    astat = self._async_stats.pop(ridx)
                    record["mean_staleness"] = round(astat["mean"], 3)
                    record["max_staleness"] = int(astat["max"])
                    record["staleness_p50"] = round(astat["p50"], 3)
                    record["staleness_p90"] = round(astat["p90"], 3)
                    if "version" in astat:
                        # multi-version plane: which model line this
                        # round drove, and any late completions folded
                        # back in from a retired generation
                        record["async_version"] = int(astat["version"])
                    if astat.get("readmitted"):
                        record["version_readmitted"] = int(
                            astat["readmitted"]
                        )
                    if astat.get("clamped"):
                        record["staleness_clamped"] = int(astat["clamped"])
                    if astat.get("bp_dropped"):
                        record["backpressure_dropped"] = int(
                            astat["bp_dropped"]
                        )
                    if astat.get("bp_rejected"):
                        record["backpressure_rejected"] = int(
                            astat["bp_rejected"]
                        )
                    if astat.get("edge_crashed"):
                        record["hier_edge_crashed"] = int(
                            astat["edge_crashed"]
                        )
                    if astat.get("edge_excluded"):
                        record["hier_edge_excluded"] = int(
                            astat["edge_excluded"]
                        )
                if ridx in self._hier_stats:
                    hstat = self._hier_stats.pop(ridx)
                    if hstat.get("edge_crashed"):
                        record["hier_edge_crashed"] = int(
                            hstat["edge_crashed"]
                        )
                for key in ("staleness_clamped", "backpressure_dropped",
                            "backpressure_rejected", "churn_unavailable",
                            "churn_dropped", "churn_crashed",
                            "version_readmitted", "hier_edge_crashed",
                            "hier_edge_excluded"):
                    if key in record:
                        self._traffic_totals[key] = (
                            self._traffic_totals.get(key, 0)
                            + int(record[key])
                        )
                if self._population is not None and any(
                    key in record for key in
                    ("churn_unavailable", "churn_dropped", "churn_crashed")
                ):
                    self._population.observe_churn(
                        record.get("churn_unavailable", 0),
                        record.get("churn_dropped", 0),
                        record.get("churn_crashed", 0),
                    )
                if ridx in self._attack_stats:
                    # compromised clients sampled into this round's
                    # cohort (attack provenance: the "attack" event at
                    # fit start records kind/knobs/the full set)
                    record["byzantine_count"] = self._attack_stats.pop(ridx)
                if hasattr(m, "consensus_dist"):
                    # decentralized health: Σ‖xᵢ−x̄‖²/N after mixing
                    record["consensus_dist"] = float(m.consensus_dist)
                if ridx == pending[-1][0]:
                    record["rounds_per_sec"] = round(rounds_per_sec, 4)
                    record["client_updates_per_sec_per_chip"] = round(updates_per_sec, 4)
                    if cfg.server.eval_every and (ridx + 1) % cfg.server.eval_every == 0:
                        record.update(self.evaluate(current_state["params"]))
                for k in self._run_totals:
                    if k in record:
                        self._run_totals[k] += int(record[k])
                self.logger.log(record)
            last_round = pending[-1][0] + 1
            self._rounds_done = max(self._rounds_done, last_round)
            pending.clear()
            if (self._digest_on and last_round % self._digest_every == 0
                    and last_round > self._digest_prev_round):
                # digest boundary: current_state is exactly the state
                # after last_round (pending held rounds ..last_round-1)
                self._emit_round_digest(last_round, current_state)
            if (self._ledger_on and self._ledger_cfg.log_every
                    and self._ledger_ref is not None
                    and last_round - self._ledger_logged_round
                    >= self._ledger_cfg.log_every):
                # periodic device-resident-ledger snapshot: one fetch
                # per log_every rounds, at a flush boundary (the fetch
                # is a few KB — never per round)
                self._log_ledger(last_round)
            if self.health is not None and obs_cfg.params_check:
                finite = all(
                    bool(jnp.isfinite(x).all())
                    for x in jax.tree.leaves(current_state["params"])
                )
                ev = self.health.observe_params_finite(last_round, finite)
                if ev is not None:
                    health_events.append(ev)
            for ev in health_events:
                self.logger.log(ev)
            flush_obs(last_round)
            unhealthy(health_events, current_state)
            flush_t0 = time.perf_counter()

        fuse = cfg.run.fuse_rounds if not (
            self.fedbuff or self.gossip or self.stateful
        ) else 1
        if fuse > 1 and start_round % fuse:
            # A warm-start/checkpoint at an unaligned round would shift
            # every chunk boundary: evals/saves (validated as fuse
            # multiples) would never fire and the last chunk would run
            # past num_rounds. Instead of refusing, run UNFUSED rounds
            # (through the lazily-built fuse=1 engine twin) up to the
            # next chunk boundary, then re-enter the fused loop on the
            # re-aligned schedule.
            aligned = min(-(-start_round // fuse) * fuse,
                          cfg.server.num_rounds)
            self.logger.log({
                "event": "warning",
                "warning": "fuse_unaligned_resume",
                "round": start_round,
                "detail": (
                    f"resume/warm-start round {start_round} is not a "
                    f"fuse_rounds={fuse} chunk boundary; running "
                    f"{aligned - start_round} unfused catch-up round(s) "
                    f"to round {aligned}, then re-entering the fused loop"
                ),
            })
            for r in range(start_round, aligned):
                with self.tracer.span("round"):
                    state = self.run_round(state, r, fuse_override=1)
                if self._ledger_on:
                    self._ledger_ref = state.get("ledger")
                self._carry_host_ledger_state(state)
                pending.append((r, state.pop("_metrics")))
                if self._digest_on and (r + 1) % self._digest_every == 0:
                    # a digest needs the state AT its boundary — flush
                    # per catch-up round when one is due
                    flush(state)
            flush(state)
            start_round = aligned
        for r in range(start_round, cfg.server.num_rounds, fuse):
            profiling = r == cfg.run.profile_round
            if profiling:
                flush(state)
                profile_dir = os.path.join(self._run_dir(), "profile")
                jax.profiler.start_trace(profile_dir)
            try:
                with self.tracer.span("round"):
                    state = self.run_round(state, r)
                if self._ledger_on:
                    self._ledger_ref = state.get("ledger")
                # the ACTIVE snapshot/sketch + pager bookkeeping ride
                # every checkpoint so a resume scores mid-window rounds
                # (and replays slot assignment) exactly like the
                # straight run (run_round returns a fresh dict)
                self._carry_host_ledger_state(state)
                ms = state.pop("_metrics")
                if fuse == 1:
                    pending.append((r, ms))
                else:
                    # [F]-stacked fields from the fused scan: tiny device
                    # slices, drained at the same flush boundaries
                    pending.extend(
                        (r + j, jax.tree.map(lambda a, j=j: a[j], ms))
                        for j in range(fuse)
                    )
                if profiling:
                    # Wait for the round before the trace stops: it must
                    # contain the round's device compute (a scalar fetch
                    # syncs exactly like block_until_ready on the chip).
                    jax.device_get(pending[-1][1].train_loss)
            finally:
                if profiling:
                    # stop on the error path too — a raise mid-profiled-
                    # round must not leak an open trace session
                    jax.profiler.stop_trace()
                    self.logger.log({
                        "event": "profile", "round": r + 1,
                        "dir": profile_dir,
                    })
            r_end = r + fuse  # validate() pins eval/ckpt to chunk ends
            at_eval = cfg.server.eval_every and r_end % cfg.server.eval_every == 0
            at_ckpt = store and cfg.server.checkpoint_every and r_end % cfg.server.checkpoint_every == 0
            # digest boundaries force a flush (the digest reads the
            # state AT the boundary); ordered before at_ckpt's save so
            # a checkpoint's head always covers its own round
            at_digest = self._digest_on and r_end % self._digest_every == 0
            if len(pending) >= flush_every or at_eval or at_ckpt or at_digest or r_end == cfg.server.num_rounds:
                flush(state)
            if cfg.run.sanitize:
                finite = all(
                    bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(state["params"])
                )
                if not finite:
                    if self.health is not None:
                        # the structured twin of the raise below, so
                        # post-mortems find it in the JSONL
                        self.logger.log({
                            "event": "health",
                            "kind": "non_finite_params",
                            "round": r_end,
                        })
                    raise FloatingPointError(f"non-finite params after round {r_end}")
            if at_ckpt:
                with self.tracer.span("round.checkpoint"):
                    self._write_state_kind()
                    store.save(r_end, self._state_for_save(state))
                flush_t0 = time.perf_counter()  # keep save time out of the next window
        flush(state)
        state["wall_time"] = time.perf_counter() - t_start
        if store:
            store.wait()  # land in-flight async saves before deciding
            if store.latest_step() != int(state["round"]):
                with self.tracer.span("round.checkpoint"):
                    self._write_state_kind()
                    store.save(int(state["round"]),
                               self._state_for_save(state),
                               force=True, block=True)
        flush_obs(int(state["round"]))  # tail spans (final save, eval)
        return state

    # ---- determinism flight recorder (run.obs.digest) ----------------

    def _state_for_save(self, state: Dict[str, Any]) -> Dict[str, Any]:
        """Checkpoint view of the live state: the wall-time scalar out,
        the digest-chain head in (template parity with init_state —
        digest-off runs save the genesis zeros)."""
        out = {k: v for k, v in state.items() if k != "wall_time"}
        out["digest_head"] = digest_mod.head_pack(
            self._digest_prev, self._digest_prev_round
        )
        return out

    def _compute_digest(self, last_round: int,
                        state: Dict[str, Any]) -> Dict[str, Any]:
        """The six digest components over the state after
        ``last_round`` + the window since the previous boundary. ONE
        host fetch (params/opt/ledger together), read-only — the
        digest-on ≡ digest-off bitwise contract lives here."""
        ledger_items = {
            k: state[k]
            for k in digest_mod.LEDGER_STATE_KEYS if k in state
        }
        fetched = jax.device_get({
            "params": state["params"],
            "opt": state["server_opt_state"],
            "ledger": ledger_items,
        })
        sched_hex, wire_hex = self._digest_window.drain(last_round)
        return digest_mod.state_components(
            fetched["params"], fetched["opt"], fetched["ledger"],
            sched_hex, wire_hex,
            {
                "seed": int(self.cfg.run.seed),
                "round": int(last_round),
                "snapshot_round": int(
                    np.asarray(state.get("ledger_snapshot_round", 0))
                ),
            },
        )

    def _emit_round_digest(self, last_round: int,
                           state: Dict[str, Any]) -> None:
        with self.tracer.span("round.digest"):
            comp = self._compute_digest(last_round, state)
            self_hex = digest_mod.chain_digest(
                self._digest_prev, last_round, comp
            )
            self.logger.log({
                "event": "round_digest",
                "round": int(last_round),
                "prev_round": int(self._digest_prev_round),
                "prev": self._digest_prev,
                "self": self_hex,
                "params": comp["params"],
                "params_leaves": comp["params_leaves"],
                "opt": comp["opt"],
                "ledger": comp["ledger"],
                "schedule": comp["schedule"],
                "wire": comp["wire"],
                "rng": comp["rng"],
            })
            self._digest_prev = self_hex
            self._digest_prev_round = int(last_round)

    def _load_own_records(self):
        """This run's already-written JSONL records (resume verify /
        replay read their own log before training continues)."""
        path = self.logger.path
        records = []
        if path and os.path.exists(path):
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        records.append(json.loads(line))
                    except json.JSONDecodeError:
                        continue  # torn tail line from a crashed writer
        return records

    def _verify_digest_resume(self, start_round: int) -> None:
        """Resume-time chain verification: the checkpoint's head must
        match a chain-valid ``round_digest`` record in the log
        (truncated/tampered logs fail). Logged as a ``digest_resume``
        event; ``run.obs.digest.strict`` escalates a failure to
        DigestResumeError before any training happens."""
        ok, detail = digest_mod.resume_head_status(
            self._load_own_records(),
            self._digest_prev, self._digest_prev_round,
        )
        self.logger.log({
            "event": "digest_resume",
            "round": int(start_round),
            "ok": bool(ok),
            "head_round": int(self._digest_prev_round),
            "head": self._digest_prev,
            "detail": detail,
        })
        if not ok and self.cfg.run.obs.digest.strict:
            raise digest_mod.DigestResumeError(
                f"digest chain verification failed on resume at round "
                f"{start_round}: {detail}"
            )

    def replay_round(self, target_round: int) -> Dict[str, Any]:
        """Re-execute exactly one logged digest round — the "reproduce
        round 4 317 on my desk" workflow behind ``colearn replay``.

        Restores the nearest checkpoint at or before the target
        record's window start (round 0's deterministic init is the
        virtual step-0 checkpoint), re-runs the intervening rounds
        UNFUSED (the catch-up twin — digest streams are fuse-invariant
        by construction), recomputes the target boundary's digest from
        the re-realized schedule/wire/state, and compares it component
        by component against the logged record. Sync rounds replay
        exactly; snapshot-fed sampling (adaptive/streaming) replays
        exactly only when the window does not cross a sampler-refresh
        boundary (the refresh rides metrics flushes the replay loop
        does not perform) — the schedule component catches the
        difference rather than hiding it."""
        if not self._digest_on:
            raise ValueError(
                "replay requires run.obs.digest.enabled=true (the "
                "digest config must match the recorded run)"
            )
        target_round = int(target_round)
        records = self._load_own_records()
        stream = digest_mod.digest_records(records)
        by_round = {int(r["round"]): r for r in stream}
        rec = by_round.get(target_round)
        if rec is None:
            have = ", ".join(str(r) for r in sorted(by_round)[:12])
            raise ValueError(
                f"no round_digest record at round {target_round} in "
                f"{self.logger.path} (digest rounds: {have or 'none'})"
            )
        window_start = int(rec["prev_round"])
        store = self._ckpt_store()
        steps = [
            s for s in (store.steps() if store else [])
            if s <= window_start
        ]
        if steps:
            state, step = store.restore(template=self.init_state(),
                                        step=steps[-1])
        else:
            # round 0: init_state is seed-deterministic — the virtual
            # step-0 checkpoint every run starts from
            state, step = self.init_state(), 0
        if store is not None:
            store.close()
        state.pop("digest_head", None)
        state = self._place_state(state)
        if self._ledger_on:
            self._ledger_ref = state.get("ledger")
        if self._snapshot_refresh:
            self._seed_sampler_from_state(state)
        self._digest_cohorts.clear()
        self._digest_window = digest_mod.RoundWindow()
        for r in range(step, target_round):
            state = self.run_round(state, r, fuse_override=1)
            if self._ledger_on:
                self._ledger_ref = state.get("ledger")
            self._carry_host_ledger_state(state)
            state.pop("_metrics", None)
            comm = self._comm_stats.pop(r, None)
            fail = self._fail_stats.pop(r, None)
            cohort = self._digest_cohorts.pop(r, None)
            for scratch in (self._async_stats, self._hier_stats,
                            self._attack_stats):
                scratch.pop(r, None)
            if r + 1 > window_start:
                # rounds at or before the window start were digested
                # by an EARLIER boundary in the original run
                self._digest_window.observe(r + 1, cohort, comm, fail)
        comp = self._compute_digest(target_round, state)
        replayed_self = digest_mod.chain_digest(
            rec.get("prev", digest_mod.GENESIS), target_round, comp
        )
        logged = digest_mod.components_from_record(rec)
        components = {
            name: comp[name] == logged.get(name)
            for name in digest_mod.COMPONENT_ORDER
        }
        leaves = sorted(
            set(comp["params_leaves"]) | set(logged["params_leaves"])
        )
        return {
            "round": target_round,
            "checkpoint_step": int(step),
            "replayed_rounds": target_round - int(step),
            "match": replayed_self == rec.get("self"),
            "logged": rec.get("self"),
            "replayed": replayed_self,
            "components": components,
            "params_leaves_diverged": [
                k for k in leaves
                if comp["params_leaves"].get(k)
                != logged["params_leaves"].get(k)
            ],
        }

    # ------------------------------------------------------------------

    def dp_epsilon(self, rounds_done: int) -> float:
        """(ε, δ) spent so far: example-level DP-SGD accounting composed
        over every local step executed across rounds.

        The sampling rate uses the **minimum** client shard size (the
        worst case over participants), so the reported ε upper-bounds
        every client's spend. See privacy/dp.py for the Poisson-vs-
        shuffle accounting caveat.
        """
        from colearn_federated_learning_tpu.privacy.dp import rdp_epsilon

        min_shard = float(min(self.shape.cap, int(self.fed.client_sizes().min())))
        q = min(1.0, self.cfg.client.batch_size / max(min_shard, 1.0))
        total_steps = rounds_done * self.shape.steps
        return rdp_epsilon(
            self.cfg.dp.noise_multiplier, q, total_steps, self.cfg.dp.delta
        )

    def dp_client_epsilon(self, rounds_done: int) -> float:
        """Client-level (ε, δ) spent by central DP-FedAvg noise: the
        sampled-Gaussian RDP accountant (same closed form as the
        example-level accountant) composed over rounds with client
        sampling rate q = cohort/num_clients; δ from cfg.dp.delta.
        config.validate() REJECTS weighted sampling under client DP
        (size-proportional sampling would push a big client's per-round
        inclusion probability above q).

        Exactness depends on ``server.sampling``:

        - ``"poisson"`` — every client independently participates with
          probability q each round, which is PRECISELY the mechanism
          the Poisson subsampled-Gaussian RDP bound is derived for: the
          reported ε is a sound upper bound at δ + δ_abort, where
          δ_abort (:meth:`dp_delta_abort`, logged at fit start) is the
          exact probability that some round's realized cohort overflows
          the static cap and the run ABORTS (observable, never silent).
        - ``"uniform"`` — cohorts are fixed-size samples without
          replacement, while the bound is derived for Poisson
          subsampling at rate q — the standard approximation in the
          DP-FedAvg literature (McMahan et al. 2018 §3.1 make the same
          substitution), not a strict upper bound for WOR sampling.
        """
        from colearn_federated_learning_tpu.privacy.dp import rdp_epsilon

        q = min(1.0, self.cfg.server.cohort_size / self.fed.num_clients)
        return rdp_epsilon(
            self.cfg.server.dp_client_noise_multiplier, q, rounds_done,
            self.cfg.dp.delta,
        )

    def dp_delta_abort(self, rounds: Optional[int] = None) -> float:
        """Exact probability that ANY of the run's poisson rounds
        realizes a cohort above the static cap (union bound over rounds
        on the exact Binomial(N, q) upper tail, computed in log space).
        This is the δ_abort of the aborting mechanism's
        (ε, δ + δ_abort)-DP guarantee; with the 5σ default cap it is
        ~1e-8 per run. 0.0 when not poisson or cap == N."""
        if not self._poisson:
            return 0.0
        n, cap = self.fed.num_clients, self._poisson_cap
        if cap >= n:
            return 0.0
        q = self.sampler.q
        from math import exp, lgamma, log

        lq, l1q = log(q), log(1.0 - q)
        tail = 0.0
        for b in range(cap + 1, n + 1):
            tail += exp(
                lgamma(n + 1) - lgamma(b + 1) - lgamma(n - b + 1)
                + b * lq + (n - b) * l1q
            )
        t = self.cfg.server.num_rounds if rounds is None else rounds
        return min(1.0, t * tail)

    def evaluate(self, params) -> Dict[str, float]:
        with self.tracer.span("round.eval"):
            xb, yb, mb = self._eval_data
            loss, acc, n = jax.device_get(
                self._eval_all(params, xb, yb, mb, self.frozen_base))
            return {"eval_loss": float(loss / n), "eval_acc": float(acc / n)}

    def evaluate_federated(self, params, max_clients: int = 64,
                           seed: Optional[int] = None) -> Dict[str, float]:
        """Federated (per-client) evaluation of the GLOBAL model: run the
        model on each client's OWN shard and report the accuracy
        distribution across clients — the fairness view centralized eval
        averages away (a model can hold 90% central accuracy while its
        worst-decile clients sit near chance under label skew).

        Simulation caveat, stated rather than hidden: clients have no
        separate local test split (the reference's datasets don't ship
        one), so this evaluates on each client's local data — the
        standard simulator proxy for federated evaluation; it measures
        the global model's FIT to each client's distribution, not
        held-out generalization (``evaluate`` does that centrally,
        ``evaluate_personalized`` does per-client holdouts).

        Deterministic in ``seed`` (client subsample when
        num_clients > max_clients). Reports mean/std/median, the 10th
        percentile, and the worst client. Runs as ONE device dispatch:
        every client's batches are padded to a common count (zero-mask
        pad batches contribute nothing) and stacked ``[clients, batches,
        batch, ...]``, then a nested ``lax.scan`` computes all per-client
        sums — not clients × batches jitted calls (the dispatch-bound
        pattern ``_eval_all`` exists to avoid)."""
        if max_clients < 1:
            raise ValueError(f"max_clients must be >= 1, got {max_clients}")
        seed = self.cfg.run.seed if seed is None else seed
        rng = np.random.default_rng((seed, 60013))
        eligible = [
            cid for cid in range(self.fed.num_clients)
            if len(self.fed.client_indices[cid]) >= 1
        ]
        if len(eligible) > max_clients:
            eligible = sorted(
                rng.choice(eligible, size=max_clients, replace=False)
            )
        batch = self.cfg.client.batch_size
        # every client pads to the same batch count (one trace; pad
        # batches are zero-mask, contributing nothing)
        nb = max(
            -(-len(self.fed.client_indices[cid]) // batch) for cid in eligible
        )

        def pad(a):
            if a.shape[0] == nb:
                return a
            fill = np.zeros((nb - a.shape[0],) + a.shape[1:], a.dtype)
            return np.concatenate([a, fill])

        # chunk clients so the stacked [chunk, nb, batch, ...] buffer
        # stays bounded in BOTH host RAM and HBM (real federated-
        # ImageNet shards would otherwise stack to many GB); batches
        # are built per chunk, so peak host memory is one chunk, and
        # it is still one dispatch per CHUNK, never per batch
        bytes_per_client = nb * batch * (
            int(np.prod(self.fed.train_x.shape[1:])) * self.fed.train_x.itemsize
            + int(np.prod(self.fed.train_y.shape[1:]) or 1) * self.fed.train_y.itemsize
            + 4  # mask f32
        )
        chunk = max(1, min(len(eligible), (512 << 20) // max(bytes_per_client, 1)))
        # per-client rows stream through iter_client_slabs: under a
        # store backend consecutive client ids coalesce into bounded
        # contiguous-range gathers (eval_buffer_mb) instead of one
        # transient arange materialization per client — bitwise the
        # same bytes as the in-memory fancy-index (test-pinned in
        # tests/test_store_data_plane.py)
        eval_buf = self.cfg.data.store.eval_buffer_mb << 20
        cs, ns = [], []
        for lo in range(0, len(eligible), chunk):
            part = [
                eval_batches(cx, cy, batch)
                for _cid, cx, cy in iter_client_slabs(
                    self.fed.train_x, self.fed.train_y,
                    self.fed.client_indices, eligible[lo:lo + chunk],
                    eval_buf,
                )
            ]
            xs, ys, ms = (
                np.stack([pad(t[i]) for t in part]) for i in range(3)
            )
            c, n = jax.device_get(self._fed_eval_all(
                params, jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(ms),
                self.frozen_base,
            ))
            cs.append(np.asarray(c))
            ns.append(np.asarray(n))
        a = np.concatenate(cs) / np.maximum(np.concatenate(ns), 1.0)
        return {
            "federated_acc_mean": float(a.mean()),
            "federated_acc_std": float(a.std()),
            "federated_acc_median": float(np.median(a)),
            "federated_acc_p10": float(np.percentile(a, 10)),
            "federated_acc_worst": float(a.min()),
            "federated_clients": len(a),
        }

    def evaluate_personalized(self, params, epochs: int = 1,
                              holdout_frac: float = 0.2,
                              max_clients: int = 32,
                              seed: Optional[int] = None,
                              round_idx: int = 0) -> Dict[str, float]:
        """Per-client personalization metric (pFL evaluation protocol):
        fine-tune the GLOBAL model ``epochs`` epochs on each client's
        train split, then evaluate on that client's held-out split;
        ``baseline_*`` is the un-tuned global model on the SAME holdouts,
        so the personalization gain is read directly off the pair.

        Deterministic in ``seed`` (splits, batch order, sampled client
        subset). Clients with fewer than 2 examples are skipped. Uses a
        per-client slab gather (host → device) so it works under both
        ``data.placement`` modes; cost is one local-training call per
        evaluated client — cap via ``max_clients``.

        ``round_idx``: the round the evaluated params came from — the
        fine-tune runs at the same decayed lr (``lr·decay^round``) the
        run's clients would use, not the hot initial lr."""
        if epochs < 1:
            raise ValueError(f"personalize epochs must be >= 1, got {epochs}")
        if not 0.0 < holdout_frac < 1.0:
            raise ValueError(
                f"holdout_frac must be in (0, 1), got {holdout_frac}"
            )
        if max_clients < 1:
            raise ValueError(f"max_clients must be >= 1, got {max_clients}")
        seed = self.cfg.run.seed if seed is None else seed
        rng = np.random.default_rng((seed, 104729))
        eligible = [
            cid for cid in range(self.fed.num_clients)
            if len(self.fed.client_indices[cid]) >= 2
        ]
        if len(eligible) > max_clients:
            eligible = sorted(
                rng.choice(eligible, size=max_clients, replace=False)
            )
        batch = self.cfg.client.batch_size
        cap = self.shape.cap
        steps = epochs * self.shape.steps_per_epoch
        if getattr(self, "_personal_train", None) is None:
            # built once — jax.jit retraces per input shape on its own;
            # local_dtype matches the run so the personalization metric
            # is measured under the precision clients actually train with
            self._personal_train = exec_mod.instrument(
                "personal.local_train",
                jax.jit(make_local_train_fn(
                    self.model, self.cfg.client, DPConfig(), self.task,
                    local_dtype=self._local_dtype(),
                )),
            )

        pers, base = [], []
        # clients stream through iter_client_slabs (store-coalesced
        # contiguous gathers, bounded by eval_buffer_mb); the
        # holdout/train split permutes LOCAL positions into each
        # client's natural-order slab — rng.permutation(n) consumes the
        # generator identically to the former rng.permutation(ids)
        # (Fisher–Yates swaps are index-based), and cx[perm] is the
        # same bytes, so splits/batch order/metrics stay bitwise
        for cid, cx, cy in iter_client_slabs(
            self.fed.train_x, self.fed.train_y, self.fed.client_indices,
            eligible, self.cfg.data.store.eval_buffer_mb << 20,
        ):
            perm = rng.permutation(len(cx))
            n_hold = min(max(1, int(round(holdout_frac * len(perm)))),
                         len(perm) - 1)
            hold, train = perm[:n_hold], perm[n_hold:]
            if len(train) > cap:
                train = train[:cap]
            n = len(train)
            # slab-local finetune grid, same layout as make_round_indices
            idx = np.zeros((steps * batch,), np.int32)
            mask = np.zeros((steps * batch,), np.float32)
            per_epoch = self.shape.steps_per_epoch * batch
            for e in range(epochs):
                off = e * per_epoch
                idx[off : off + n] = rng.permutation(n).astype(np.int32)
                mask[off : off + n] = 1.0
            pad = cap - n
            slab_x = cx[train]
            slab_y = cy[train]
            if pad:
                slab_x = np.concatenate(
                    [slab_x, np.repeat(slab_x[:1], pad, axis=0)]
                )
                slab_y = np.concatenate(
                    [slab_y, np.repeat(slab_y[:1], pad, axis=0)]
                )
            extra = ()
            if self.cfg.client.lr_decay != 1.0:
                extra = (jnp.float32(self.cfg.client.lr_decay ** round_idx),)
            p_i, _ = self._personal_train(
                params, self._round_data(jnp.asarray(slab_x)),
                jnp.asarray(slab_y),
                jnp.asarray(idx.reshape(steps, batch)),
                jnp.asarray(mask.reshape(steps, batch)),
                jax.random.fold_in(jax.random.PRNGKey(seed), cid),
                *extra,
            )
            xb, yb, mb = eval_batches(cx[hold], cy[hold], batch)
            accs = {}
            for tag, p in (("personalized", p_i), ("baseline", params)):
                c_sum = n_sum = 0.0
                for b in range(xb.shape[0]):
                    _, c, m = self._eval_fn(
                        p, jnp.asarray(xb[b]), jnp.asarray(yb[b]),
                        jnp.asarray(mb[b]), self.frozen_base,
                    )
                    c_sum += float(c)
                    n_sum += float(m)
                accs[tag] = c_sum / max(n_sum, 1.0)
            pers.append(accs["personalized"])
            base.append(accs["baseline"])
        if not pers:
            # nothing eligible (all shards < 2 examples): report the
            # count honestly instead of NaN means (which break JSON)
            return {"personalized_clients": 0, "personalize_epochs": epochs}
        pers_a, base_a = np.asarray(pers), np.asarray(base)
        return {
            "personalized_acc_mean": float(pers_a.mean()),
            "personalized_acc_std": float(pers_a.std()),
            "baseline_acc_mean": float(base_a.mean()),
            "baseline_acc_std": float(base_a.std()),
            "personalized_clients": len(pers),
            "personalize_epochs": epochs,
        }

    def export_checkpoint(self, path: str, step: Optional[int] = None) -> Dict[str, Any]:
        """Export a checkpoint's GLOBAL MODEL PARAMS to a single flax
        msgpack file (`colearn export`) — the deployment artifact; see
        utils/checkpoint.export_params / load_params for the consumer
        side."""
        from colearn_federated_learning_tpu.utils.checkpoint import export_params

        store = self._ckpt_store(required=True)
        state, step = store.restore(step=step, template=self.init_state())
        store.close()
        params = state["params"]
        if self._lora:
            # the deployment artifact is the MERGED model (W +
            # (alpha/r)·A·B over the seed-derived frozen base) — a
            # consumer of the export never needs the adapter structure
            params = self.model.merged_params(params, self.frozen_base)
        out_path = export_params(params, path)
        n_params = sum(
            int(np.prod(p.shape)) for p in jax.tree.leaves(params)
        )
        return {"event": "exported", "path": out_path, "round": int(state["round"]),
                "num_params": n_params}

    def evaluate_checkpoint(self, step: Optional[int] = None,
                            personalize: bool = False,
                            federated: bool = False,
                            federated_clients: int = 64,
                            **personalize_kwargs) -> Dict[str, float]:
        store = self._ckpt_store(required=True)
        template = self.init_state()
        state, step = store.restore(step=step, template=template)
        store.close()
        state = self._place_state(state)
        out = self.evaluate(state["params"])
        if federated:
            out.update(
                self.evaluate_federated(
                    state["params"], max_clients=federated_clients,
                )
            )
        if personalize:
            out.update(
                self.evaluate_personalized(
                    state["params"], round_idx=int(state["round"]),
                    **personalize_kwargs,
                )
            )
        out["round"] = int(state["round"])
        return out
