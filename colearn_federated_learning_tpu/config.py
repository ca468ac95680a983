"""Experiment configuration system (SURVEY.md §2 C2, layer L5).

Typed dataclass configs + YAML files + the five named BASELINE configs
(BASELINE.json:7-11). ``colearn fit --config <name-or-path>`` resolves a
name through :func:`get_named_config` or loads a YAML file; dotted CLI
overrides (``--set server.num_rounds=5``) mutate fields after load.

Everything that affects traced XLA shapes (cohort size, local steps,
batch size, pad length) is pinned here so a config change — not runtime
data — is the only thing that can trigger recompilation.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import yaml


@dataclass
class LoRAConfig:
    """LoRA adapter plane (``model.lora``, models/lora.py — ROADMAP
    item 3): freeze the transformer base and train/ship/aggregate ONLY
    rank-r adapter pairs. Every targeted dense kernel ``W [d_in,
    d_out]`` gains ``A [d_in, r]`` / ``B [r, d_out]`` and the
    effective weight is ``W + (alpha/r)·A·B`` (``B`` starts at zero,
    so the merged model initially equals the base). The params pytree
    the whole round stack sees (engines, aggregation — weighted_mean
    AND krum/median over flattened factors — compression, upload
    attacks, the forensic ledger's norm/cosine stats, reputation,
    checkpoints, wire counters) IS the adapter set, so every subsystem
    operates in adapter space by construction and the per-client
    upload drops ~d/(2r) per target (the realized ratio is logged as
    ``wire_reduction_vs_full`` in the round counters and
    ``run_summary``). Eval and export run against the merged model.
    The frozen base params are a pure function of ``run.seed`` (the
    init rng) — re-derived on resume, never checkpointed or shipped
    (the one-time base broadcast is out of the per-round wire model,
    like any deployed-base LoRA system). Supported model families:
    ``bert_tiny``, ``vit_b16`` (the transformer-block injection map),
    ``axk1_decoder`` (the projections of its latent attention, stacked
    over the layers); other zoo members are rejected with a clear
    error. The base lives on the device as data, in
    ``run.local_param_dtype`` (else ``run.param_dtype``), an argument
    of every round program; ``run.hbm_gb``'s pre-flight counts it. With
    ``enabled=false`` no wrapper is constructed anywhere and runs are
    bitwise-identical to pre-LoRA builds (test-pinned)."""

    enabled: bool = False
    # adapter rank r (must be < min(d_in, d_out) of every target kernel
    # — checked at model construction with the offending kernel named)
    rank: int = 4
    # merge scale numerator: the effective weight is W + (alpha/r)·A·B
    # (Hu et al.'s parameterization — tune lr and alpha together)
    alpha: float = 8.0
    # which dense kernels inside each transformer block get adapters:
    #   attention — the fused qkv projection + the attention output
    #   mlp       — the MLP in/out projections
    #   all       — both sets
    target: str = "attention"


@dataclass
class ModelConfig:
    name: str = "lenet5"
    num_classes: int = 10
    # model-family extras (e.g. vocab_size / seq_len for LMs, image_size)
    kwargs: Dict[str, Any] = field(default_factory=dict)
    # LoRA adapter plane — see LoRAConfig.
    lora: LoRAConfig = field(default_factory=LoRAConfig)


@dataclass
class StoreConfig:
    """On-disk memory-mapped client store (``data.store``, data/store.py
    — ROADMAP item 1, the million-client data path). With ``dir`` set,
    the training corpus comes from fixed-record binary shards plus a
    small per-client offset/length index built by ``colearn store
    build``: example bytes stay on disk behind ``np.memmap`` views, the
    per-client partition IS the store's index (``data.partition`` /
    synthetic knobs are ignored — they were baked in at build time),
    and the host pipeline gathers only the sampled cohort's records
    into each round's slab. Pair with ``data.placement="stream"`` for
    the O(cohort) host-RAM path (``"hbm"`` still works — the whole
    store is materialized to device once, for small stores / big
    chips). Store-backed runs are BITWISE-equal to the in-memory run
    the store was converted from, on the same seed (test-pinned across
    engines and fuse_rounds). ``data.num_clients`` must match the
    store's client count (checked with a clear error). Rejected
    pairings: ``attack.kind="label_flip"`` (poisons labels host-side;
    the store is a read-only mmap) and ``run.host_pipeline="native"``
    (the C++ pipeline materializes the per-client index lists;
    ``"auto"`` degrades to NumPy)."""

    # store directory ("" = off, classic in-memory data path)
    dir: str = ""
    # load the whole store into plain host arrays and run the classic
    # in-memory path — the "in-memory twin" for store↔in-memory parity
    # checks; only sensible for stores that fit in RAM
    materialize: bool = False
    # parallel shard-gather pool width (data/store.py): a slab's row
    # set is split by owning shard and the per-shard mmap copies run
    # concurrently on a shared worker pool. 0 = auto (min(4, cores)),
    # 1 = serial, N = exactly N threads. Deterministic at EVERY
    # setting — workers write disjoint output rows, so the gathered
    # bytes never depend on the worker count (test-pinned).
    gather_workers: int = 0
    # bounded reassembly buffer (MB) for store-backed federated /
    # personalized eval: eval batches stream through the contiguous
    # client-index ranges in bounded multi-client slabs instead of
    # materializing a transient per-client arange gather each —
    # bitwise-identical metrics, O(buffer) host residency.
    eval_buffer_mb: int = 256


@dataclass
class DataConfig:
    name: str = "mnist"
    num_clients: int = 2
    partition: str = "iid"  # iid | dirichlet | natural | silo
    dirichlet_alpha: float = 0.5
    data_dir: str = "~/.cache/colearn_data"
    # When real dataset files are absent (this sandbox has zero egress),
    # fall back to a deterministic synthetic dataset with the same
    # shapes/cardinality so every config stays runnable end-to-end.
    synthetic_fallback: bool = True
    synthetic_train_size: int = 2048
    synthetic_test_size: int = 512
    # Synthetic image SNR: x = w·class_template + (1−w)·noise. 0.7 is an
    # easy task (saturates at acc 1.0 — right for smoke tests); the
    # convergence regression lowers it so the plateau sits strictly
    # below 1.0 and a mid-curve band can catch subtle aggregation drift.
    synthetic_template_weight: float = 0.7
    # Synthetic task family (VERDICT r4 weak-#4 — one family can't
    # catch structure-sensitive regressions):
    #   template    — x = w·T_class + (1−w)·noise; linearly separable
    #                 (class means recover it), the fast smoke default.
    #   template_pair — x superposes TWO templates, label = (a+b) mod
    #                 C: spatially structured (convnet-learnable) but a
    #                 linear model's additive scores cap far below the
    #                 ceiling; pair with synthetic_label_noise for a
    #                 strict ceiling below 1.
    synthetic_task: str = "template"  # template | template_pair
    # template_pair only: fraction of labels flipped uniformly at random
    synthetic_label_noise: float = 0.0
    # Cap on examples a client contributes per round (static-shape pad target;
    # 0 = derive from the largest client shard).
    max_examples_per_client: int = 0
    # Where the training corpus lives during the round loop:
    #   hbm    — whole corpus uploaded once, rounds gather on device
    #            (fastest; requires the corpus to fit in device memory)
    #   stream — corpus stays in host RAM; each round only the cohort's
    #            examples are gathered into a slab and uploaded, with the
    #            index tensors remapped into it. Unlocks corpora larger
    #            than HBM (e.g. real ImageNet at 224px) at the cost of a
    #            per-round host→device transfer.
    placement: str = "hbm"  # hbm | stream
    # On-disk mmap client store — see StoreConfig.
    store: StoreConfig = field(default_factory=StoreConfig)


@dataclass
class ClientConfig:
    local_epochs: int = 1
    batch_size: int = 32
    optimizer: str = "sgd"  # sgd | adamw
    lr: float = 0.1
    # per-round multiplicative LR decay: round r trains at lr·decay^r
    # (1.0 = constant). Computed inside the compiled round program from
    # the server state's round counter — no retracing.
    lr_decay: float = 1.0
    momentum: float = 0.9
    weight_decay: float = 0.0
    # FedProx proximal coefficient μ (0.0 == plain FedAvg local training)
    prox_mu: float = 0.0


@dataclass
class ReputationConfig:
    """Reputation-weighted aggregation (``server.reputation``,
    server/aggregation.py ``reputation_weights``): the closed control
    loop over the per-client forensic ledger. Each round program
    converts every cohort member's ledger row — cumulative flag rate
    ``flagged/count`` and the norm/cosine robust-z EMA — into a
    multiplicative TRUST weight in ``[floor, 1]``:

        score = flag_rate + z_gain * max(ema_z/zmax - 1, 0)
        trust = floor + (1 - floor) * exp(-strength * score)

    (unseen clients — ``count == 0`` — get trust exactly 1, so
    reputation never suppresses a client before the ledger has
    evidence). The trust is computed INSIDE the round program from the
    device-resident ``[num_clients, LEDGER_WIDTH]`` ledger carried from
    the PREVIOUS rounds (this round's stats update lands after
    aggregation), so the single-psum weighted-mean path stays host-free
    and under ``run.fuse_rounds`` the trust derives from the fused scan
    carry. Where it applies:

    - ``aggregator="weighted_mean"``: the FedAvg weight becomes
      ``w_i · trust_i`` (numerator AND denominator — a true reweighted
      mean; the reported ``train_loss`` is the same trust-weighted
      mean). This is the soft complement to krum's hard rejection:
      near ``f ≈ K/2`` krum's selection guarantee is void (the
      Blanchard bound 2f+2 < n cannot be satisfied) while the
      reputation-weighted mean degrades the attackers' mass gradually
      as ledger evidence accumulates.
    - robust aggregators (median/trimmed_mean/krum): order statistics
      are unweighted by design, so trust instead SCALES each client's
      delta (``trust_i · Δ_i``) before the reduction — a suppressed
      client's upload shrinks toward the zero update rather than being
      ejected, so false flags cost a fraction of one update instead of
      a cohort slot.

    Requires ``run.obs.client_ledger.enabled`` (trust is a function of
    the ledger); the ledger's pairing exclusions (secure aggregation,
    client-level DP, gossip/fedbuff, scaffold/feddyn) therefore apply
    verbatim — see ClientLedgerConfig for the reasons. With
    ``enabled=false`` (default) no trust input exists anywhere and runs
    are bitwise-identical to pre-reputation builds."""

    enabled: bool = False
    # minimum trust weight: a fully-flagged client keeps this fraction
    # of its voice (soft weighting — never a hard zero, so a falsely
    # accused client can still earn its reputation back)
    floor: float = 0.05
    # exponential decay rate of trust in the anomaly score; flag_rate=1
    # drives trust to ~floor + (1-floor)*exp(-strength)
    strength: float = 6.0
    # weight of the z-history term: only the part of the EMA'd robust z
    # ABOVE the flag threshold (ema_z/zmax - 1) contributes, so honest
    # clients' sub-threshold z noise never erodes their trust
    z_gain: float = 1.0


@dataclass
class AdaptiveSamplerConfig:
    """Knobs for ``server.sampling="adaptive"`` (server/sampler.py):
    Oort-style utility-aware cohort selection (Lai et al., OSDI'21)
    scored from the client ledger's periodic host-side snapshots. Per
    client the score is

        util      = ema_loss (unseen clients: the max seen utility —
                    optimistic initialization, explore-eagerly)
        staleness = 1 + staleness_gain * max(expected - count, 0)
                    / max(expected, 1),  expected = round * K / N
        score     = (util + eps) * staleness * exp(-flag_suppress
                    * flag_rate)

    and the draw probabilities are ``(1 - explore) * score/Σscore +
    explore/N`` — the exploration floor keeps every client drawable
    forever. The snapshot refreshes from the device-resident ledger at
    ``run.obs.client_ledger.log_every`` round boundaries (one host
    fetch per refresh, logged as the same ``client_ledger`` JSONL
    record), so the cohort for round ``r`` is a pure function of
    ``(seed, r, ledger_snapshot)`` and a resumed run replays the exact
    straight-run schedule — the active snapshot rides the checkpoint.
    See DataConfig/RunConfig pairing rejections in ``validate()``."""

    # fraction of each draw's probability mass spread uniformly over
    # ALL clients (the exploration floor; must be in (0, 1])
    explore: float = 0.1
    # boost for under-sampled clients (participation deficit vs the
    # uniform expectation) — Oort's staleness/fairness term
    staleness_gain: float = 1.0
    # exponential suppression of high-flag-rate clients in the draw
    # probabilities (the selection-side twin of reputation weighting)
    flag_suppress: float = 4.0
    # sampling="streaming" only: max rows in the compact adaptive-score
    # sketch (the columnar {id, count, flagged, ema_loss} table the
    # streaming draw scores from). When more clients than this have
    # ledger evidence, the highest-participation rows are kept; clients
    # outside the sketch draw from the closed-form optimistic unseen
    # pool. Bounds the sampler's host memory and checkpoint footprint
    # regardless of num_clients.
    sketch_size: int = 4096


@dataclass
class HierarchyConfig:
    """Two-tier (device → edge → core) federation (``server.hierarchy``,
    server/round_driver.py). ``num_edges = E > 0`` splits the client
    universe into E deterministic contiguous blocks (client ``i``
    belongs to edge ``i·E // num_clients``); each edge aggregator runs
    the EXISTING round program over a cohort drawn from its own block
    (per-edge deterministic samplers), and the core round aggregates the
    E edge deltas — the engine reused recursively, one tier down.

    Per-tier robust aggregation composes: ``server.aggregator`` is the
    EDGE tier's defense (e.g. krum over each edge's cohort) and
    ``core_aggregator`` the core tier's (e.g. the reputation-weighted
    mean over edge deltas) — a compromised edge is degraded at the core
    even when its in-edge defense was overwhelmed. Edge-dropout fault
    injection (``edge_dropout_rate``) crashes whole edges with a
    seed-pure per-(round, edge) hash draw: a crashed edge's delta is
    EXCLUDED from the core aggregate and counted
    (``hier_edge_crashed``), never NaN-poisoning the core.

    Under ``algorithm="fedbuff"`` the hierarchy rides the async
    scheduler instead: each popped completion is grouped by its
    client's edge, a crashed edge's completions are excluded for that
    server step, and per-edge trust (``core_aggregator="reputation"``)
    multiplies the staleness-decayed weights — per-tier absorbed/
    staleness accounting lands in round records and run_summary.

    Sync-path pairing restrictions live in ``validate()`` with reasons
    (stateful algorithms, secure aggregation, DP accounting, the client
    ledger, stream placement, fused rounds — each assumes exactly one
    cohort dispatch per round). ``num_edges = 0`` constructs nothing
    and is bitwise-identical to the flat plane (test-pinned)."""

    # number of edge aggregators; 0 = hierarchy off (the flat plane)
    num_edges: int = 0
    # core-tier aggregation over the [E] stacked edge deltas:
    #   mean        — participation-weighted mean (crashed edges excluded)
    #   median | trimmed_mean | krum — the robust_reduce order
    #                 statistics, one tier up (sync path only)
    #   reputation  — trust-weighted mean; per-edge trust is an EMA of
    #                 the edge's crash/alive history (edge_trust rides
    #                 the checkpoint, so resume replays core weights)
    core_aggregator: str = "mean"
    # trimmed_mean core only: fraction trimmed from each side
    core_trim_ratio: float = 0.1
    # krum core only: assumed Byzantine edge count f
    core_krum_byzantine: int = 0
    # core_aggregator="reputation" only: EMA rate of the per-edge trust
    # update trust ← (1-decay)·trust + decay·alive
    core_trust_decay: float = 0.25
    # per-(round, edge) probability that an edge aggregator crashes for
    # that round (seed-pure hash draw; its delta is excluded + counted)
    edge_dropout_rate: float = 0.0


@dataclass
class ServerConfig:
    num_rounds: int = 10
    cohort_size: int = 2
    eval_every: int = 1
    checkpoint_every: int = 0  # 0 = only at end
    # Server-side optimizer applied to the aggregated delta:
    #   mean (plain FedAvg) | fedavgm (server momentum) | fedadam | fedyogi
    optimizer: str = "mean"
    server_lr: float = 1.0
    server_momentum: float = 0.9
    # Cohort delta aggregation:
    #   weighted_mean — FedAvg's example-weighted mean (single psum)
    #   median | trimmed_mean — coordinate-wise Byzantine-robust
    #   statistics over per-client deltas (unweighted by design; costs
    #   K× the aggregation memory of the psum path)
    #   krum — whole-update selection (Blanchard et al. 2017): keep the
    #   one delta closest to its m−f−2 nearest neighbours
    aggregator: str = "weighted_mean"
    # fraction trimmed from EACH side per coordinate (trimmed_mean only)
    trim_ratio: float = 0.1
    # krum only: assumed number of Byzantine clients f (neighbour count
    # = participants − f − 2, clamped ≥ 1)
    krum_byzantine: int = 0
    # Client-update (uplink) compression applied to each client's delta
    # BEFORE aggregation — simulates communication-constrained FL:
    #   "" (off) | topk (keep top fraction by magnitude per tensor)
    #   | qsgd (unbiased stochastic quantization, Alistarh et al. 2017)
    compression: str = ""
    compression_topk_ratio: float = 0.01
    compression_qsgd_levels: int = 256
    # topk thresholds leaves ≥ 2×65536 coords from a strided sampled
    # quantile (selected count within ±10% of k; see ops/compression.py).
    # True restores the exact full-sort threshold — 10× the training
    # step's device time on ResNet-18-sized models (BASELINE.md r4/r5).
    compression_topk_exact: bool = False
    # Error-feedback compression memory (EF-SGD family — Seide et al.
    # 2014, Stich et al. 2018): each client keeps a persistent
    # params-shaped residual eᵢ in the device-resident per-client state
    # store (same [N, ...] mesh-sharded plumbing as scaffold); per round
    # the upload is C(Δᵢ + eᵢ) and eᵢ⁺ = Δᵢ + eᵢ − C(Δᵢ + eᵢ), which
    # de-biases sparse compressors (every coordinate top-k drops is
    # retried until it ships). Requires `compression`; incompatible with
    # stateful algorithms (one store per run), robust aggregators
    # (history-dependent uploads have no order-statistic semantics),
    # secure_aggregation and client-level DP (the memory breaks the
    # per-round upload norm bound their analyses need). HBM budget =
    # N·|params| at client_state_dtype, sharded over lanes.
    error_feedback: bool = False
    # Clip each client's delta to this L2 norm (whole-tree) before
    # aggregation — the standard heterogeneity stabilizer (and DP-SGD's
    # clipping step without the noise). 0 = off.
    clip_delta_norm: float = 0.0
    # algorithm=fedbuff only: client train durations are 1..S server
    # steps (S = async_max_staleness); the pop-K-earliest-finish queue
    # discipline bounds realized staleness by 2S, which sizes the
    # on-device params-history ring (2S+1 versions). In-flight
    # concurrency = cohort_size × S.
    async_max_staleness: int = 4
    # staleness decay exponent α: aggregation weight × (1+s)^-α
    async_staleness_exponent: float = 0.5
    # fedbuff overload backpressure: cap on the COMPLETED-but-unpopped
    # backlog beyond the K updates each server step absorbs. Under
    # churn, offline clients defer completions and the backlog can
    # spike when a diurnal wave brings a cohort back online; entries
    # beyond the cap are shed per async_overload_policy, re-queued as
    # fresh arrivals at the current version (their in-flight work is
    # discarded — counted in round records and run_summary). 0 = no
    # cap (every completion waits its turn, staleness absorbs the
    # backlog instead).
    async_backlog_cap: int = 0
    # which completions are shed at the cap:
    #   drop_oldest  — shed the STALEST waiting completions (bound the
    #                  staleness tail; the freshest work survives)
    #   reject_newest — shed the most recent completions (FIFO
    #                  admission; the oldest waiters keep their slot)
    async_overload_policy: str = "drop_oldest"  # drop_oldest | reject_newest
    # algorithm=fedbuff only: number of CONCURRENT model versions
    # ("lines"), each with its own in-flight buffer, params trajectory,
    # and 2S+1 history ring. Server steps round-robin over the lines
    # (round r drives line r mod V at line-local version r div V); the
    # availability-aware pop routes each completion to the line it was
    # admitted by, and staleness is accounted per line in line-local
    # steps. 1 (default) = the single-version plane, bitwise-identical
    # to pre-multi-version builds (test-pinned). Line 0 is the primary
    # version: eval, run_summary final loss, and `colearn export` read
    # state["params"].
    async_versions: int = 1
    # Version retirement (async_versions >= 2 only; 0 = never retire).
    # When a line reaches this AGE (line-local server steps since its
    # generation was born) at its turn, the generation retires: the
    # line's params continue as the successor generation, but every
    # completion still in flight against the retired generation is a
    # LATE completion — popped later, it is re-admitted at the oldest
    # live version (staleness clamped to 2S) with its weight decayed by
    # async_readmit_decay, counted (`version_readmitted`) and warned
    # once, rather than dropped. run.strict_versions=true restores a
    # hard reject (RuntimeError) for late completions.
    async_retire_rounds: int = 0
    # retire a line's generation once it has ABSORBED this many updates
    # (whichever of age/updates trips first; 0 = no update threshold)
    async_retire_updates: int = 0
    # weight multiplier applied to a late completion re-admitted after
    # its generation retired (composes with the staleness decay)
    async_readmit_decay: float = 0.5
    # Two-tier edge/core aggregation — see HierarchyConfig.
    hierarchy: HierarchyConfig = field(default_factory=HierarchyConfig)
    # algorithm=feddyn only: the dynamic-regularization coefficient α
    # (both the client proximal pull and the server h-correction scale)
    feddyn_alpha: float = 0.1
    # algorithm=gossip only (decentralized DFedAvg, parallel/gossip.py):
    # every client keeps its OWN replica ([N, ...] mesh-sharded tree);
    # per round all N clients train locally then mix with their ring
    # neighbours — xᵢ ← (1−2γ)xᵢ + γ(xᵢ₋₁+xᵢ₊₁), a halo exchange whose
    # cross-chip traffic is 2·|params| per lane per step regardless of
    # N (vs the centralized psum). γ ∈ (0, 0.5]; 1/3 is the Metropolis
    # ring weight. topology "full" = complete averaging each step
    # (equals centralized uniform FedAvg from a consensus start — the
    # tested oracle). Eval/checkpoint export use the consensus mean.
    gossip_gamma: float = 1.0 / 3.0
    gossip_mixing_steps: int = 1
    gossip_topology: str = "ring"  # ring | full
    # scaffold/feddyn/error_feedback: storage dtype of the device-
    # resident per-client state store (the [N, ...] stacked cᵢ/gᵢ/eᵢ
    # tree, sharded
    # over the mesh's clients axis under run.engine=sharded). The HBM
    # budget is N·|params| at this dtype, divided across lanes.
    # "bfloat16" halves it but rounds the PERSISTENT state at each
    # scatter-back (in-round state math always runs f32); keep
    # "float32" unless the store dominates HBM.
    client_state_dtype: str = "float32"  # float32 | bfloat16
    # Cohort sampling:
    #   uniform  — fixed-size cohort, without replacement (classic).
    #   weighted — fixed-size, p ∝ client shard size (big-data clients
    #              drawn more often; pairs with uniform aggregation
    #              weights — the standard importance-sampling heuristic
    #              for example-weighted FedAvg, exact in the
    #              with-replacement limit).
    #   poisson  — every client independently participates with
    #              q = cohort_size/num_clients; the realized cohort is
    #              VARIABLE, padded to a static cap (≈K + 5σ, lane-
    #              rounded; overflow raises — observable abort whose
    #              exact binomial-tail probability is logged as
    #              dp_delta_abort). This is the sampling under which
    #              the client-level DP accountant's Poisson
    #              subsampled-Gaussian bound is EXACT (VERDICT r4
    #              missing-#3); under uniform/weighted it is an
    #              approximation (see dp_client_epsilon).
    #   adaptive — fixed-size, Oort-style utility-aware draw scored
    #              from the client ledger's periodic snapshots (loss-
    #              utility EMA × participation staleness, exploration
    #              floor, flag-rate suppression — see
    #              AdaptiveSamplerConfig / `server.adaptive`). Requires
    #              run.obs.client_ledger.enabled with log_every >= 1.
    #   streaming — the million-client mode: draws a fixed-size cohort
    #              in O(cohort·log) without ever enumerating the client
    #              universe (no dense [num_clients] probability vector,
    #              no O(N) permutation). Uniform rejection draw until
    #              ledger evidence arrives; with the client ledger on
    #              (log_every >= 1) it scores the SAME Oort-style
    #              formula as "adaptive" over a compact fixed-size
    #              sketch of observed clients plus a closed-form
    #              optimistic unseen pool (server.adaptive.sketch_size
    #              caps the sketch). Schedules are deterministic in
    #              (seed, round, sketch) and resume-replayable, but are
    #              a DIFFERENT deterministic sequence than "uniform"/
    #              "adaptive" produce (different draw algorithm).
    sampling: str = "uniform"  # uniform | weighted | poisson | adaptive | streaming
    # Simulated client dropout: fraction of the sampled cohort whose
    # update is zeroed inside the round function (total failure).
    dropout_rate: float = 0.0
    # Simulated stragglers (partial work, FedProx's motivating case):
    # each round, straggler_rate of the cohort completes only
    # straggler_work of its local steps (mask-truncated; the FedAvg
    # weight shrinks to the work actually done). Unlike dropout_rate,
    # stragglers' partial updates still aggregate.
    straggler_rate: float = 0.0
    straggler_work: float = 0.5
    # Secure aggregation — the masking core of Bonawitz et al. 2017,
    # simulated faithfully at the arithmetic level: each participant's
    # weighted delta is quantized to fixed-point int32 and additively
    # masked with UNIFORM int32 ring masks m(slot) − m(slot+1 mod K)
    # that cancel EXACTLY (mod 2^32) in the aggregate psum, so the
    # server-visible per-client contribution is information-
    # theoretically hidden while the aggregate is exact up to the
    # quantization step. The mask ring is the STATIC full cohort,
    # committed BEFORE training: dropout is discovered only after
    # uploads are collected, and the server then reconstructs each
    # dropped client's mask term m(slot) − m(slot+1) from the recovered
    # mask seed and adds it so the ring still telescopes to zero — the
    # real protocol's post-upload seed-share recovery, with the shared
    # mask key standing in for Shamir reconstruction. The dropped
    # client's data never enters the aggregate. Scope: the
    # key-agreement/secret-sharing layers of the real protocol are out
    # of simulation scope, and the loss/example-count metrics still
    # aggregate in plaintext (as published deployments also do for
    # counts). Requires clip_delta_norm > 0 so |quantized values| are
    # bounded: cohort · max_weight · clip / quant_step must stay < 2^31
    # (enforced at Experiment construction — see secagg_allow_wrap_risk)
    # and per-client values < 2^24 for exact f32 rounding (warned).
    secure_aggregation: bool = False
    # fixed-point quantization step for secure aggregation
    secagg_quant_step: float = 1e-4
    # Mask construction (privacy/secagg_keys.py):
    #   "ring"     — O(K) mask streams from one key; dropout recovery
    #                uses the shared key (arithmetic-exact simulation,
    #                the fast default).
    #   "pairwise" — the Bonawitz et al. 2017 §4-5 protocol shape:
    #                per-pair DH-agreed seeds, t-of-n Shamir recovery of
    #                dropped clients' seeds, round ABORTS below the
    #                threshold. O(K²) mask streams — opt-in; overhead
    #                measured in BASELINE.md r5.
    secagg_mode: str = "ring"
    # Shamir threshold t for pairwise mode: ≥t survivor shares
    # reconstruct a dropped client's seeds, t−1 reveal nothing.
    # 0 = auto (⌊K/2⌋+1, the honest-but-curious majority setting).
    secagg_threshold: int = 0
    # An int32 WRAP in the masked aggregate silently corrupts the round,
    # so a config whose worst-case bound cohort·max_weight·clip/
    # quant_step reaches 2^31 is REJECTED at Experiment construction
    # unless this explicit opt-in is set (the run then only warns).
    # Realized deltas usually sit far below the clip bound — but that is
    # a statistical observation, not a guarantee, hence opt-in.
    secagg_allow_wrap_risk: bool = False
    # Central CLIENT-level DP (DP-FedAvg, McMahan et al. 2018 "Learning
    # Differentially Private Recurrent Language Models"): Gaussian noise
    # with std z·S/K is added ONCE to the aggregated mean delta, where
    # z is this multiplier, S = clip_delta_norm is the per-client L2
    # sensitivity, and K = cohort_size is a FIXED PUBLIC denominator —
    # enabling client DP forces UNIFORM aggregation weights and the
    # fixed denominator, because a data-dependent denominator (realized
    # example counts) is itself private and would invalidate the
    # sensitivity analysis. Protects whole clients rather than single
    # examples (dp.* is example-level local DP-SGD; both can be
    # enabled). Requires clip_delta_norm > 0; composes with
    # secure_aggregation (noise is server-side, post-unmask — the
    # standard deployed stack). ε accounting: the sampled-Gaussian RDP
    # accountant with q = cohort/num_clients per round, reported as
    # dp_client_epsilon in the run log.
    dp_client_noise_multiplier: float = 0.0
    # Simulated downlink (server→client broadcast) compression: QSGD-
    # style unbiased stochastic quantization of the global params each
    # round — clients train FROM the quantized broadcast, deltas are
    # taken against it, the aggregate applies to the server's exact
    # params (ops/compression.py downlink_quantize). Pairs with the
    # uplink `compression` knob for the full comm-constrained story.
    downlink_compression: str = ""  # "" | qsgd
    downlink_qsgd_levels: int = 256
    # Fused server-apply chain (ops/pallas_apply.py): run the round
    # tail — trust/weight scaling → weighted reduction (stacked paths)
    # → server delta apply → optimizer update — as ONE VMEM-resident
    # pallas kernel pass over the flat param vector instead of a chain
    # of full-params XLA ops (each a |params| HBM round trip; the
    # stacked robust/attack paths additionally materialize weighted
    # [K, |params|] intermediates the kernel never writes). On the
    # weighted_mean psum path the in-lane reduction is untouched and
    # the kernel fuses apply+optimizer only; median/trimmed_mean keep
    # their coordinate-wise sorts (not a weighted reduction) and also
    # take the apply-only fusion. Interpret mode (exact, slow) runs the
    # same kernel on non-TPU backends, so CPU CI pins it against the
    # unfused reference per aggregator × reputation × error feedback.
    # Requires optimizer "mean" or "fedavgm" (the kernel's FMA chain);
    # fused ≡ unfused at f32-reassociation tolerance, not bitwise.
    fused_apply: bool = False
    # Reputation-weighted aggregation off the client ledger — see
    # ReputationConfig.
    reputation: ReputationConfig = field(default_factory=ReputationConfig)
    # sampling="adaptive" scoring knobs — see AdaptiveSamplerConfig.
    adaptive: AdaptiveSamplerConfig = field(
        default_factory=AdaptiveSamplerConfig
    )


@dataclass
class DPConfig:
    enabled: bool = False
    l2_clip: float = 1.0
    noise_multiplier: float = 1.0
    delta: float = 1e-5
    # examples per step of the trainer's scan over the batch: a
    # microbatch's per-example quantities are held at once
    microbatch_size: int = 16
    # Clipping strategy (privacy/dp.py — both exact, same mechanism):
    #   microbatch — lax.scan over microbatches of one vmapped forward
    #                and backward. The kernel of an nn.Dense, or of an
    #                nn.Conv that is a patch embedding, never has its
    #                per-example gradient formed: its norm comes from
    #                two Gram products of the product's input rows and
    #                output cotangents, its clipped sum from one
    #                weighted product. Every other leaf's per-example
    #                gradient is materialised [microbatch, ...].
    #   two_pass   — pass 1 computes per-example grad NORMS only (grads
    #                discarded), pass 2 is ONE fully batched weighted
    #                backward whose gradient IS the clipped sum (grad of
    #                the scale-masked mean × Σscale). Two backwards; the
    #                norm and the sum come from different backward
    #                passes (dp_grads_two_pass on what that costs).
    clipping: str = "microbatch"  # microbatch | two_pass


@dataclass
class AttackConfig:
    """Byzantine adversary simulation (server/attacks.py): a
    deterministic ``(run.seed)``-pure set of compromised clients
    attacks during ``fit``, so the robust aggregation stack can be
    MEASURED against a live adversary instead of hand-crafted tensors.

    Threat model — where each attack acts:

    - upload attacks (``sign_flip``/``gauss``/``scale``/``alie``): the
      compromised client controls its wire message; the transform
      applies to its delta after clipping/compression (the honest
      client's update rule) and before aggregation, inside the round
      program (a ``[K]`` byzantine-mask input — no retrace, exact
      sharded↔sequential parity). Under ``algorithm=gossip`` the
      "upload" is the poisoned replica the attacker gossips to its
      ring neighbours (``alie`` is rejected there: it sizes itself
      from cohort statistics a decentralized attacker cannot observe).
    - ``label_flip``: data poisoning — the compromised clients'
      training labels are flipped ``y → (C−1)−y`` host-side before
      corpus placement; the upload is an honest gradient of poisoned
      data. Composes with any engine path (no engine involvement).

    Expected defense behavior (the headline e2e test pins it): plain
    ``weighted_mean`` collapses under ``sign_flip`` at f=2/8 while
    krum / median / trimmed_mean hold their benign accuracy band.

    Pairings rejected by validate() (with reasons): secure_aggregation,
    client-level DP, example-level DP, scaffold/feddyn, fedbuff,
    error_feedback. Upload attacks compose with run.fuse_rounds>1: the
    per-round byzantine masks become a stacked [fuse, K] scan input and
    the attacked delta stack stays private to the fused scan body.
    """

    # "" (off) | sign_flip | gauss | scale | alie | label_flip
    kind: str = ""
    # fraction of the FEDERATION compromised; the id set is
    # round(fraction·num_clients) clients (≥1), drawn deterministically
    # from run.seed — identical across engines, resumes, and reruns
    fraction: float = 0.25
    # sign_flip/scale boost factor: sign_flip uploads −scale·Δ, scale
    # uploads +scale·Δ (model-replacement boosting). 1.0 = pure flip /
    # honest magnitude.
    scale: float = 10.0
    # gauss: per-coordinate noise std (the upload is eps·N(0,I));
    # alie: the z of μ − z·σ (how many honest stds the colluders shift)
    eps: float = 1.0


@dataclass
class ClientLedgerConfig:
    """Per-client forensic ledger (``run.obs.client_ledger``,
    obs/ledger.py): each round program additionally emits a small
    ``[K]``-shaped per-client stats block (upload L2 norm, cosine
    similarity to the aggregated delta, clip/EF residual magnitude,
    post-local-train loss, and a robust median/MAD z-score anomaly
    flag) and scatters it IN-PROGRAM into a device-resident
    ``[num_clients]`` ledger carried across rounds (participation
    count, EMA of each stat, cumulative flagged rounds) — zero extra
    host round-trips, riding the fused scan carry under
    ``run.fuse_rounds`` exactly like the EF residual store. The ledger
    flows out as periodic ``client_ledger`` JSONL records (plus a
    final one on every exit path, aborts included) and powers the
    ``colearn clients <run>`` report: top-k anomalous clients,
    participation histogram, and — when ``attack.kind`` is set —
    detection precision/recall against the ground-truth compromised
    set. Aggregation itself is untouched: a ledger-on run's params
    trajectory is bitwise identical to the same run with the ledger
    off (the stats block reads the upload stack; it never feeds back).

    Rejected pairings (validate(), with reasons): secure_aggregation
    (per-client uploads are exactly what masking hides), client-level
    DP (a per-client statistics channel voids the client-DP release),
    gossip (no server-visible upload stack), and scaffold/feddyn
    (their store plumbing owns the per-client state path; robust/
    attack forensics is rejected there anyway). ``algorithm="fedbuff"``
    is SUPPORTED since the churn PR via per-INSERT stats — each async
    server step computes the stats block over its popped buffer's
    uploads and scatters by true client id (dense ledger only; the
    paged hot set's slot remap stays a synchronous-dispatch feature)."""

    enabled: bool = False
    # EMA coefficient for the per-stat running means: ema_x moves by
    # ema*(x - ema_x) per observed round; a client's first observation
    # seeds the EMA with the value itself
    ema: float = 0.2
    # robust z-score threshold: a participant whose max(z_l2, z_cos)
    # exceeds this is flagged for the round (3.5 is the classic
    # median/MAD outlier cutoff)
    zmax: float = 3.5
    # rounds between periodic client_ledger JSONL snapshots (emitted at
    # metrics-flush boundaries); 0 = only the end-of-fit/abort record
    log_every: int = 0
    # Paged ledger (obs/ledger.py LedgerPager — the million-client
    # mode): 0 keeps the classic dense [num_clients, LEDGER_WIDTH]
    # device store; > 0 keeps only a [hot_capacity, LEDGER_WIDTH]
    # LRU-style HOT set device-resident, scattered by SLOT (the driver
    # remaps cohort ids to slots host-side; the round program is
    # unchanged), with cold rows spilled to an anonymous host mmap.
    # Page-ins ride a tiny async device scatter; an eviction needs one
    # blocking hot-set fetch (counted as ledger_page_syncs in
    # run_summary). Reputation/adaptive selection read exactly the same
    # rows they would from the dense ledger, so paging is invisible to
    # the round program for any cohort that fits the hot set — the
    # merged (hot ∪ cold) ledger is bitwise-equal to the dense run's
    # (test-pinned), and flush/resume behave exactly like today. Must
    # be >= cohort_size × fuse_rounds (checked at construction);
    # values >= num_clients degrade to the dense store. Incompatible
    # with server.error_feedback (the EF store is indexed by true
    # client ids on the same cohort input the pager remaps).
    hot_capacity: int = 0


@dataclass
class PopulationConfig:
    """Federation health observatory (``run.obs.population``,
    obs/population.py): per-metrics-flush-window ``population_health``
    JSONL records covering the data plane the million-client structures
    run on — sampler health (cumulative unique-client coverage via an
    O(1)-memory HLL-style probabilistic counter, exploration/
    exploitation draw split, streaming-sketch occupancy/refresh-age/
    flag-rate coverage, cohort staleness distribution), ledger-pager
    health (per-window hit/miss/page-in/eviction counts + page-sync
    stall ms — the PR 9 run_summary totals as a time series), store I/O
    (bytes gathered, gather wall ms, per-shard touch counts, union-slab
    dedup ratio), and participation fairness (Gini/max-share over a
    bounded top-k participation sketch — never a dense [num_clients]
    histogram). Every tracked structure is O(cohort) per round or
    fixed-size, and every count-based column is a pure function of the
    host-side cohort schedule, so records are engine-parity pinned
    (sharded ≡ sequential ≡ fused) on everything but the ``*_ms``
    wall-clock fields. Purely observational: no device work, no rng
    consumption, params bitwise-unchanged. ``colearn watch <run>``
    renders the live view; ``colearn population <run>`` is the post-hoc
    report; ``colearn summarize`` surfaces the run_summary totals."""

    enabled: bool = False
    # capacity of the bounded top-k participation sketch the fairness
    # stats (gini, max-share, top clients) are computed over
    top_k: int = 64
    # HLL register count = 2**hll_bits (12 → 4096 one-byte registers,
    # ~1.6% relative error on the coverage estimate)
    hll_bits: int = 12
    # bounded last-participation map behind the staleness distribution;
    # cohort members evicted from it count as staleness-unknown
    recency_capacity: int = 8192


@dataclass
class DigestConfig:
    """Determinism flight recorder (``run.obs.digest``, obs/digest.py):
    at each digest boundary the driver computes a canonical,
    dtype/shape-tagged 64-bit digest over the fetched state — params
    (per-top-level-leaf + rolled up), server opt state, the
    ledger/pager hot set, the realized cohort schedule + failure
    counts and wire-byte counters since the previous boundary, and the
    RNG inputs — and emits one ``round_digest`` JSONL record chaining
    ``prev`` → ``self`` (a hash chain: truncated/tampered logs are
    self-evident). The chain head rides every checkpoint and resume
    verifies it against the log before training continues. Purely
    observational: digests are a pure function of fetched state
    (engine-invariant wherever the engines are bitwise) and digest-on
    runs are bitwise-identical to digest-off runs on the same seed
    (test-pinned). ``colearn diff`` bisects two streams to the first
    divergent round + component; ``colearn replay`` re-executes one
    logged round and verifies its digest. Off by default (the
    benchmark's cells never pay the O(P) host fetch)."""

    enabled: bool = False
    # rounds between digest boundaries; the O(params) host-side fetch
    # + hash is amortized by this cadence. Under run.fuse_rounds > 1
    # must be a chunk multiple (boundaries land on chunk ends).
    every: int = 1
    # verify the checkpoint's chain head against the log on resume
    # (warn on mismatch; run.obs.digest.strict aborts instead)
    verify_resume: bool = True
    # escalate a failed resume verification from a logged warning to
    # DigestResumeError (`colearn fit --strict-digest`)
    strict: bool = False


@dataclass
class ObsConfig:
    """Round-lifecycle telemetry (``obs/``): phase spans, comm/device
    counters, and run-health monitoring — the observability layer every
    perf PR measures against. All host-side; the engines are unchanged
    apart from trace annotations."""

    # Time the round lifecycle (host inputs → placement → dispatch →
    # fetch → eval → checkpoint) and log a per-phase `spans` record at
    # every metrics-flush boundary. Off = spans are shared no-ops.
    spans: bool = True
    # Also accumulate Chrome-trace events and write
    # <out_dir>/<name>/trace.json at the end of fit (open in
    # ui.perfetto.dev or chrome://tracing). Requires spans.
    trace: bool = False
    # Cap on accumulated Chrome-trace events: long runs otherwise
    # silently produce multi-GB trace.json files. When the cap is hit
    # the tracer warns ONCE and drops further events (per-phase span
    # aggregates are unaffected); the export also warns once when the
    # written file exceeds the size threshold. 0 = unbounded.
    trace_max_events: int = 1_000_000
    # Per-round communication byte counters (analytic wire model:
    # upload/download, pre/post compression — obs/counters.py) merged
    # into each round's JSONL record.
    counters: bool = True
    # Poll jax device memory stats at flush boundaries and log a
    # `device_memory` record (in-use / peak / limit bytes). Off by
    # default: the gauges are per-process globals, noisy under tests.
    device_memory: bool = False
    # NaN/Inf (+ optional divergence) monitoring over the per-round
    # training loss — free, the loss is fetched anyway at flush.
    health: bool = True
    # Also probe the PARAMS for finiteness at flush boundaries (one
    # device fetch per flush window; run.sanitize does it per round).
    params_check: bool = False
    # 0 = off; otherwise flag `divergence` when a round's loss exceeds
    # factor × the best loss seen so far. Must be > 1 when set.
    divergence_factor: float = 0.0
    # What to do on an unhealthy round:
    #   warn             — log the health event, keep training
    #   abort            — raise HealthAbortError (NOT retried by
    #                      run.max_retries: a NaN run re-NaNs)
    #   checkpoint_abort — save a post-mortem checkpoint first
    on_unhealthy: str = "warn"  # warn | abort | checkpoint_abort
    # Compiled-program observatory (obs/executables.py): route every
    # engine/eval jit through an AOT executable registry and record,
    # per compiled program, XLA's own cost_analysis FLOPs,
    # memory_analysis argument/output/temp bytes, the donation map, a
    # stable fingerprint, and compile wall-ms (`executable_compiled`
    # records), plus per-flush `hbm_watermark` records and `retrace`
    # forensics naming the argument whose shape/dtype/sharding
    # changed. Execution is the SAME lowering jit would produce —
    # bitwise-identical results, test-pinned. Off = jit dispatch
    # untouched, records absent.
    executables: bool = True
    # 0 = off; otherwise any newly compiled program whose predicted
    # peak HBM (argument + output + temp + generated-code bytes,
    # donation-aliased buffers counted once) exceeds this many MiB
    # aborts the fit with HbmBudgetError BEFORE the program executes
    # (deliberately not retried — recompiling predicts the same peak).
    # `colearn preflight` applies the same ceiling without executing
    # anything. Requires executables.
    hbm_budget_mb: int = 0
    # Per-client forensic ledger — see ClientLedgerConfig.
    client_ledger: ClientLedgerConfig = field(
        default_factory=ClientLedgerConfig
    )
    # Federation health observatory — see PopulationConfig.
    population: PopulationConfig = field(default_factory=PopulationConfig)
    # Determinism flight recorder — see DigestConfig.
    digest: DigestConfig = field(default_factory=DigestConfig)


@dataclass
class ShapeBucketsConfig:
    """Heterogeneity-aware round shapes (``run.shape_buckets``): the
    round grid's step count becomes a function of the SAMPLED COHORT,
    not the federation. The federation-max ``steps_per_epoch`` is
    quantized onto a small geometric ladder (top rung = the legacy full
    shape); each round the driver picks the smallest rung covering the
    realized cohort's max capped shard (per CHUNK under
    ``run.fuse_rounds`` > 1, so fused slabs stay rectangular) and
    dispatches through one lazily-compiled executable per realized rung.
    Padded steps are exact algebraic no-ops, so a bucketed run is
    BITWISE-EQUAL to the buckets-off run on the same seed (test-pinned,
    sharded↔sequential and fused↔unfused) — only the mask-zeroed scan
    iterations (real TPU FLOPs under power-law client sizes) disappear.
    Compile budget: ≤ ladder-size retraces per engine, attributed via
    the obs compile listener (``shape_bucket`` events).

    Rejected pairings (validate(), each with its reason): example-level
    DP (per-step noise keys are positional in the padded grid — a
    trimmed grid would shift every noise stream), stragglers (their
    truncation is parameterized on the full-shape step grid),
    fedbuff/gossip (their schedulers own the round shape), and
    ``run.host_pipeline='native'`` (the C++ pipeline builds for one
    fixed shape; ``auto`` falls back to NumPy while buckets are on)."""

    # off = exact-legacy behavior: every round pads to the federation max
    enabled: bool = False
    # geometric ladder ratio between adjacent rungs (> 1)
    base: float = 2.0
    # number of rungs below (and including) the full shape; the realized
    # ladder is deduplicated, so count only bounds it
    count: int = 4


@dataclass
class ChurnConfig:
    """Seed-pure availability/churn model (``run.churn``,
    server/churn.py — the production-traffic plane): per-client diurnal
    availability waves, mid-round dropout hazard, and crash-mid-round
    injection, every draw a pure function of ``(run.seed, round,
    client_id)`` via counter-mode hashing — so schedules are
    resume-replayable with zero checkpoint state and engine-invariant
    (sharded ≡ sequential ≡ prefetch worker, bitwise).

    Where it acts: the uniform and streaming cohort samplers reject
    offline candidates (an unavailable client is simply not drawn);
    any cohort member that still dispatches while offline, draws the
    dropout hazard, or crashes mid-round realizes its failure through
    the existing straggler/dropout machinery (``n_ex`` zeroing and
    mask/spec truncation — partial work still aggregates, weighted by
    the steps actually done). Under ``algorithm="fedbuff"`` offline
    clients additionally DEFER their completions, growing realized
    staleness — the regime the bounded-staleness admission gate
    (``run.strict_staleness``) and the overload backpressure policy
    (``server.async_backlog_cap``) exist for.

    Rejected pairings (validate(), with reasons): gossip (all clients
    train every round — there is no availability-gated cohort draw),
    ``run.shape_buckets`` (crash truncation is parameterized on the
    full-shape step grid, same reason as stragglers), and the
    weighted/poisson/adaptive samplers (static size-weights and the
    Poisson DP-exact ``q`` assume unconditional draws; the dense
    adaptive scores would need availability renormalization — the
    uniform and streaming samplers are the gated pair). ``enabled=
    False`` constructs no model anywhere and is bitwise-identical to
    pre-churn builds (test-pinned with stray knob values)."""

    enabled: bool = False
    # rounds per simulated day: each client's availability follows
    # base + amplitude*sin(2π(round/period + phase_i)) with a fixed
    # hash-derived per-client phase (its "timezone")
    diurnal_period: int = 24
    # peak-to-mean swing of the diurnal wave (0 = flat availability)
    diurnal_amplitude: float = 0.5
    # mean availability probability (the wave's midline)
    base_availability: float = 0.75
    # clip floor for the per-round availability probability: no client
    # is ever permanently unreachable (the exploration-floor principle)
    min_availability: float = 0.05
    # probability a dispatched participant fails mid-round entirely
    # (total failure — weight zeroed, same path as server.dropout_rate)
    dropout_hazard: float = 0.0
    # probability a dispatched participant crashes mid-round at a
    # hash-drawn fraction of its local steps (partial work aggregates,
    # mask-truncated — the straggler path)
    crash_rate: float = 0.0
    # Trace-replay availability: path to a FedScale-style per-client
    # on/off trace (a .npy uint8 bitmap [trace_rounds, trace_rows];
    # `server.churn.build_synthetic_trace` writes one). When set, the
    # diurnal wave is REPLACED by trace playback: client i maps to a
    # stable hash-derived trace row, round r reads row bit
    # [r mod trace_rounds], and the availability probability is the bit
    # clipped to [min_availability, 1] — an off-bit client keeps the
    # exploration-floor probability, with the same seed-pure hash
    # tie-breaking as the analytic wave (schedules stay O(cohort),
    # engine-invariant, and resume-replayable; the trace file is
    # mmap-read, never materialized). diurnal_* knobs are ignored.
    # File existence is checked at Experiment construction.
    trace: str = ""


@dataclass
class RunConfig:
    seed: int = 0
    # sharded: the shard_map/psum round engine (one XLA program per round)
    # sequential: python loop over cohort clients (reference semantics; used
    #             for bit-parity tests and single-device debugging)
    engine: str = "sharded"
    # number of mesh lanes on the "clients" axis; 0 = all visible devices
    num_lanes: int = 0
    # second mesh axis for intra-client batch DP on big silo models; 1 = off
    batch_shards: int = 1
    # clients trained as one vmap block per lane (effective batch =
    # width × batch_size keeps the MXU fed for small models); 1 = pure
    # sequential scan (min memory), 0 = whole lane in one vmap.
    # Ignored under cohort_layout="megabatch" (the layout owns the
    # in-lane batching; an explicit width >= 2 is rejected).
    client_vmap_width: int = 1
    # Cohort layout (parallel/round_engine.py, client/trainer.py):
    #   spatial   — the classic placement: the cohort shards over lanes
    #               and each lane trains its clients in client_vmap_width
    #               blocks. With width 1 every per-chip GEMM is capped at
    #               ONE client's batch — the MXU starves on small models.
    #   megabatch — collapse the cohort axis into the GEMM batch: a lane
    #               owns K_local = cohort/lanes clients and their local
    #               training runs as ONE fused block. The first local
    #               step (all clients still hold the round's identical
    #               broadcast weights) runs as a true megabatch — the
    #               forward and activation-gradient GEMMs contract
    #               [K_local·batch, ...] activations against ONE weight
    #               — and the remaining steps run as a lane-local vmap
    #               over the diverged per-client params (one batched
    #               GEMM per layer instead of K_local sequential
    #               launches). A model of windowed convolutions (3x3
    #               and larger kernels over half its elements) skips
    #               the shared first step — its GEMM rows are
    #               batch·H·W already — and runs every step diverged
    #               (client/trainer.py shared_weight_phase). A pure
    #               performance knob: the wire shapes
    #               ([K] weights, [K,2] mask specs, the [K,·] upload
    #               stack, psum/robust-reduce aggregation, ledger stats)
    #               are unchanged and megabatch ≡ spatial is parity-
    #               pinned (tests/test_round_engine.py). Rejected
    #               pairings in validate(): stateful algorithms
    #               (scaffold/feddyn own per-client correction trees in
    #               the scan layout), gossip/fedbuff (their engines own
    #               the round shape), and run.batch_shards > 1 (the
    #               flattened [K_local·batch] megabatch rows are exactly
    #               the axis the batch mesh splits). The sequential
    #               engine is layout-free (it IS the oracle).
    cohort_layout: str = "spatial"  # spatial | megabatch
    # Unroll factor for the client's local-step lax.scan (jax's native
    # `unroll=`): >1 trades compile time / code size for fewer loop
    # iterations and cross-step fusion opportunities; lax.scan handles
    # non-dividing step counts itself. 1 = no unrolling.
    scan_unroll: int = 1
    # Multi-round fusion: F rounds compiled as ONE XLA program (a
    # lax.scan over the round body with stacked index tensors and the
    # same per-round rngs — fused ≡ unfused bitwise). Amortizes
    # per-round dispatch, the dominant cost of tiny-model configs
    # (BASELINE.md r5, an earlier installation). Covers the fedavg/fedprox family
    # including robust aggregators (median/trimmed_mean/krum — the
    # per-client delta stack stays private to the scan body), upload
    # attacks (byzantine masks ride a stacked [fuse, K] scan input),
    # error feedback (the residual store is a donated scan carry), and
    # multi-process meshes (stacked host slabs place through the
    # sharded path). Excluded: scaffold/feddyn/fedbuff/gossip (their
    # state recursions / schedulers cannot ride the carry), secagg
    # (per-round key-protocol host I/O), and data.placement=stream
    # (slabs are built per round). Must divide num_rounds, eval_every
    # and checkpoint_every so evals and saves land on fused-chunk
    # boundaries; a resume at a non-chunk-aligned round runs unfused
    # catch-up rounds to the next boundary (logged) and then re-enters
    # the fused loop. 1 = off.
    fuse_rounds: int = 1
    # Failure recovery (SURVEY.md §5): on an unexpected error inside the
    # round loop, reload the latest checkpoint and continue, up to this
    # many times per fit() call. 0 = fail fast. Requires out_dir +
    # checkpoint_every for mid-run restarts (otherwise the retry starts
    # from round 0). KeyboardInterrupt is never retried.
    max_retries: int = 0
    # Device HBM budget in GiB for the construction-time memory
    # pre-flight (PERSISTENT per-device arrays: replicated corpus +
    # params + server-opt state + the N-row client-state / replica
    # stacks divided over lanes + the fedbuff history ring). A config
    # whose persistent footprint exceeds the budget fails FAST with a
    # per-component breakdown and remedies, instead of an opaque
    # RESOURCE_EXHAUSTED minutes into compilation (VERDICT r4
    # missing-#4). 0 = auto (the device's memory_stats()["bytes_limit"];
    # skipped on CPU; an accelerator that reports none is an error
    # asking for this option — no capacity is guessed); -1 = disable.
    hbm_gb: float = 0.0
    # Double-buffered host↔device rounds (server/round_driver.py): a
    # host worker thread builds round N+1's inputs AND places them on
    # device (a second in-flight placed-slab buffer keyed like the
    # prefetch futures) while the device executes round N's dispatched
    # compute, so the round.host_inputs / round.placement phases hide
    # under round.dispatch. Inputs are pure in (seed, round[, ledger
    # snapshot]), so buffered ≡ unbuffered BITWISE (test-pinned); the
    # overlap drains itself wherever purity would break — fused-chunk
    # grids built for a different ladder rung are dropped and rebuilt,
    # and the adaptive sampler never prefetches across a ledger-
    # snapshot refresh boundary. stream placement keeps its legacy
    # build-only one-ahead prefetch (a placed slab would double the
    # bounded-memory promise); fedbuff's scheduler is not buffered.
    double_buffer: bool = True
    # Host-side round-input construction (idx/mask/n_ex tensors):
    #   auto   — the C++ threaded pipeline (native/) when the toolchain
    #            builds it, else the NumPy path; prefetches round r+1
    #            while the device executes round r
    #   native — require the C++ pipeline (error if unavailable)
    #   numpy  — single-threaded NumPy construction (data/loader.py)
    # Both are deterministic in (seed, round) but use different
    # permutation RNGs; a resumed run only replays the original batch
    # schedule on the same pipeline kind — pin "native" or "numpy"
    # explicitly if a run may migrate across machines mid-flight.
    host_pipeline: str = "auto"
    # Round control plane (ISSUE 18):
    #   host   — the legacy path: sampler draws, churn realization, and
    #            index-slab construction run in host Python between
    #            dispatches (bitwise-identical to pre-knob builds).
    #   device — the control plane lowers into the round program
    #            (server/device_plane.py): cohort ids come from a tiny
    #            precomputed per-round table, churn gates are evaluated
    #            in-program by a uint32-pair SplitMix64 bitwise-equal
    #            to server/churn.py's host draws, and the index slab is
    #            derived from a device-resident shard table — the host
    #            ships nothing per round, and under fuse_rounds > 1 the
    #            fused scan derives every sub-round's schedule itself,
    #            so host I/O collapses to flush boundaries. Cohort ids
    #            and churn fail stats stay bitwise-equal to host mode;
    #            per-batch example ORDER is the device plane's own
    #            seed-pure rotation discipline (documented in
    #            DESIGN.md). The realized schedule is emitted as a
    #            fetched-at-flush program output. Requires the
    #            fixed/uniform sampler, hbm placement, and the
    #            sharded/sequential engines; samplers that need host
    #            state (adaptive/streaming), fedbuff/gossip/hierarchy,
    #            attacks, secagg, and per-round host protocols are
    #            rejected with reasons (capability matrix
    #            `control_plane_device`).
    control_plane: str = "host"
    # rounds between metric fetches. Dispatch is async; a host fetch
    # waits for the device and drains the dispatch queue, so the driver
    # buffers per-round metric scalars on device and drains them every
    # N rounds. 1 = fetch every round (debug).
    metrics_flush_every: int = 10
    out_dir: str = "runs"
    # also mirror per-round metrics as TensorBoard scalars under
    # <out_dir>/<name>/tb (JSONL is always written)
    tensorboard: bool = False
    resume: bool = False
    profile_round: int = -1  # round index to wrap in jax.profiler.trace; -1 = off
    sanitize: bool = False  # jax_debug_nans + finite-params assertions
    param_dtype: str = "float32"
    compute_dtype: str = "float32"  # bfloat16 on real TPU configs
    # Mixed-precision local training: cast global params to this dtype
    # ONCE per client at local-training entry ("" = keep param_dtype).
    # With f32 params + bf16 compute, "bfloat16" removes the per-step
    # f32→bf16 parameter conversions (~17% of round time on v5e, see
    # BASELINE.md profile) while server aggregation and the cross-round
    # trajectory stay f32.
    local_param_dtype: str = ""
    # Cohort-shaped step buckets — see ShapeBucketsConfig.
    shape_buckets: ShapeBucketsConfig = field(
        default_factory=ShapeBucketsConfig
    )
    # Seed-pure availability/churn model — see ChurnConfig.
    churn: ChurnConfig = field(default_factory=ChurnConfig)
    # algorithm=fedbuff only: what a staleness-bound violation does.
    # False (default) = the GRACEFUL path: an update whose start
    # version aged out of the 2S+1 history ring trains against the
    # OLDEST RETAINED version instead, its aggregation weight decays at
    # the TRUE staleness (strictly stronger down-weighting), and the
    # event is counted (`staleness_clamped` in round records and
    # run_summary) with a warn-once log — the production behavior
    # under churn, where offline clients legitimately exceed the
    # bound. True = the pre-churn contract: any staleness > 2S raises
    # (ring sizing is then an invariant, not a budget).
    strict_staleness: bool = False
    # algorithm=fedbuff with server.async_versions >= 2 only: what a
    # late completion against a RETIRED version generation does. False
    # (default) = the graceful drain: the completion is re-admitted at
    # the oldest live version with its weight decayed by
    # server.async_readmit_decay, counted (`version_readmitted`) and
    # warned once. True = hard reject: a late completion raises
    # (retirement then asserts the buffer drained before the threshold).
    strict_versions: bool = False
    # Observability block (spans / counters / health) — see ObsConfig.
    obs: ObsConfig = field(default_factory=ObsConfig)


# the federated algorithms the driver implements (validate() + docs)
ALGORITHMS = ("fedavg", "fedprox", "scaffold", "feddyn", "fedbuff", "gossip")


@dataclass
class ExperimentConfig:
    name: str = "mnist_fedavg_2"
    # fedavg | fedprox (prox_mu>0 implied) | scaffold (client control
    # variates, Karimireddy et al. 2020 — needs plain client SGD) |
    # fedbuff (asynchronous buffered aggregation, Nguyen et al. 2022 —
    # clients train on stale versions, staleness-decayed weights)
    algorithm: str = "fedavg"
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    client: ClientConfig = field(default_factory=ClientConfig)
    server: ServerConfig = field(default_factory=ServerConfig)
    dp: DPConfig = field(default_factory=DPConfig)
    attack: AttackConfig = field(default_factory=AttackConfig)
    run: RunConfig = field(default_factory=RunConfig)

    def _effective_local_dtype(self) -> str:
        """The dtype local training actually runs in: local_param_dtype,
        or — when empty — the server param dtype itself."""
        return self.run.local_param_dtype or self.run.param_dtype

    def _stateful_dtype_ok(self) -> bool:
        """Stateful algorithms (scaffold/feddyn) need the WHOLE parameter
        trajectory in f32: local training (w_K feeds the persistent
        state) AND server params/delta accumulators (params must move by
        exactly the deltas the f32 state tracks)."""
        return (
            self._effective_local_dtype() == "float32"
            and self.run.param_dtype == "float32"
        )

    def validate(self) -> "ExperimentConfig":
        if self.server.cohort_size > self.data.num_clients:
            raise ValueError(
                f"cohort_size {self.server.cohort_size} > num_clients {self.data.num_clients}"
            )
        if self.algorithm == "fedprox" and self.client.prox_mu <= 0:
            raise ValueError("fedprox requires client.prox_mu > 0")
        if self.algorithm not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {self.algorithm!r}")
        if self.algorithm == "feddyn":
            if self.client.prox_mu > 0.0:
                # the α/2‖w−w₀‖² term IS feddyn's regularizer; the engine
                # injects prox_mu=feddyn_alpha itself
                raise ValueError("feddyn injects prox_mu=alpha; set prox_mu=0")
            if self.server.feddyn_alpha <= 0.0:
                raise ValueError("feddyn requires server.feddyn_alpha > 0")
            if self.dp.enabled:
                raise ValueError("feddyn is incompatible with dp.enabled")
            if not self._stateful_dtype_ok():
                raise ValueError(
                    "feddyn requires an f32 parameter trajectory "
                    "(run.param_dtype=float32 and f32 local training) — "
                    "the persistent gᵢ/h state tracks exact deltas"
                )
            if self.server.aggregator != "weighted_mean":
                raise ValueError(
                    "feddyn is incompatible with robust server.aggregator "
                    "(the h recursion tracks raw deltas)"
                )
            if self.server.compression or self.server.clip_delta_norm > 0.0:
                raise ValueError(
                    "feddyn is incompatible with compression/clip_delta_norm "
                    "(params would move by modified deltas while gᵢ/h track "
                    "the raw trajectory)"
                )
            if self.server.optimizer != "mean" or self.server.server_lr != 1.0:
                # the engine applies the paper's exact step and bypasses
                # the optax server optimizer — a configured server_lr
                # would be silently ignored, so reject it
                raise ValueError(
                    "feddyn defines its own server update; set "
                    "server.optimizer=mean and server_lr=1.0"
                )
        if self.algorithm == "gossip":
            if self.run.engine != "sharded":
                raise ValueError("gossip requires run.engine=sharded")
            # cohort_size == num_clients: every client trains every
            # round (classic DFedAvg). cohort_size < num_clients (r5):
            # PARTIAL participation — only the sampled cohort trains
            # (in-program gather/train/scatter over the replica stack,
            # O(K) local compute), everyone mixes. The replica stack is
            # O(N·|params|/lanes) either way — run.hbm_gb pre-flights
            # it. Measured N=128 on the real chip: BASELINE.md r5.
            if self.server.optimizer != "mean" or self.server.server_lr != 1.0:
                # there is no server update at all — a configured server
                # optimizer would be silently ignored, so reject it
                raise ValueError(
                    "gossip has no server optimizer; set "
                    "server.optimizer=mean and server_lr=1.0"
                )
            if self.server.sampling != "uniform":
                raise ValueError(
                    "gossip schedules all clients every round; "
                    f"server.sampling={self.server.sampling} is not supported"
                )
            if (self.server.aggregator != "weighted_mean"
                    or self.server.compression
                    or self.server.downlink_compression
                    or self.server.secure_aggregation
                    or self.server.error_feedback
                    or self.server.dp_client_noise_multiplier > 0.0
                    or self.server.clip_delta_norm > 0.0):
                # all of these are server-aggregation concepts; gossip
                # has no server and no uplink — neighbour messages are
                # the full replicas
                raise ValueError(
                    "gossip is incompatible with server-side aggregation "
                    "options (aggregator/compression/downlink_compression/"
                    "secagg/error_feedback/client-DP/clip_delta_norm)"
                )
            if not 0.0 < self.server.gossip_gamma <= 0.5:
                raise ValueError(
                    f"server.gossip_gamma must be in (0, 0.5], "
                    f"got {self.server.gossip_gamma}"
                )
            if self.server.gossip_mixing_steps < 1:
                raise ValueError("server.gossip_mixing_steps must be >= 1")
            if self.server.gossip_topology not in ("ring", "full"):
                raise ValueError(
                    f"unknown server.gossip_topology "
                    f"{self.server.gossip_topology!r}"
                )
            if self.run.batch_shards > 1:
                raise ValueError("gossip is incompatible with run.batch_shards")
            if self.data.placement != "hbm":
                raise ValueError("gossip requires data.placement=hbm")
            if self.client.lr_decay != 1.0:
                # the gossip engine has no server round counter to
                # derive the decay schedule from — a configured decay
                # would be silently ignored, so reject it (same
                # principle as the server-optimizer rejection above)
                raise ValueError(
                    "gossip does not support client.lr_decay"
                )
        if self.algorithm == "fedbuff":
            if self.run.engine != "sharded":
                raise ValueError("fedbuff requires run.engine=sharded")
            if self.server.aggregator != "weighted_mean":
                raise ValueError(
                    "fedbuff is incompatible with robust server.aggregator"
                )
            if self.server.compression:
                raise ValueError("fedbuff is incompatible with server.compression")
            if self.run.batch_shards > 1:
                raise ValueError("fedbuff is incompatible with run.batch_shards")
            if self.server.sampling not in ("uniform", "streaming"):
                # arrivals are drawn per server step: uniform draws, or
                # the O(cohort·log) streaming sketch draw (optionally
                # scored from the per-insert ledger stats — the
                # million-client arrival path). weighted/poisson/
                # adaptive parameterize a synchronous cohort draw the
                # queue scheduler does not make.
                raise ValueError(
                    "fedbuff draws queue arrivals via uniform or "
                    "streaming sampling only; "
                    f"server.sampling={self.server.sampling} is not supported"
                )
            if self.server.async_max_staleness < 1:
                raise ValueError("async_max_staleness must be >= 1")
            if self.server.async_staleness_exponent < 0.0:
                raise ValueError("async_staleness_exponent must be >= 0")
            if self.server.async_backlog_cap < 0:
                raise ValueError("async_backlog_cap must be >= 0")
            if self.server.async_overload_policy not in (
                "drop_oldest", "reject_newest",
            ):
                raise ValueError(
                    f"unknown server.async_overload_policy "
                    f"{self.server.async_overload_policy!r}; expected "
                    f"'drop_oldest' or 'reject_newest'"
                )
            if self.server.async_versions < 1:
                raise ValueError("server.async_versions must be >= 1")
            if self.server.async_versions == 1:
                if (self.server.async_retire_rounds
                        or self.server.async_retire_updates):
                    raise ValueError(
                        "server.async_retire_rounds/async_retire_updates "
                        "require server.async_versions >= 2 (retirement "
                        "rotates version generations; the single-version "
                        "plane has nothing to retire into)"
                    )
                if self.run.strict_versions:
                    raise ValueError(
                        "run.strict_versions requires server."
                        "async_versions >= 2 (there are no version "
                        "generations to enforce on the single-version "
                        "plane)"
                    )
            if self.server.async_retire_rounds < 0:
                raise ValueError("async_retire_rounds must be >= 0")
            if self.server.async_retire_updates < 0:
                raise ValueError("async_retire_updates must be >= 0")
            if not 0.0 < self.server.async_readmit_decay <= 1.0:
                raise ValueError(
                    f"server.async_readmit_decay must be in (0, 1], "
                    f"got {self.server.async_readmit_decay}"
                )
            if self.server.async_versions > 1 and self.run.fuse_rounds > 1:
                raise ValueError(
                    "server.async_versions >= 2 is incompatible with "
                    "run.fuse_rounds (the line scheduler interleaves "
                    "versions across server steps; a fused chunk would "
                    "span lines)"
                )
        else:
            if self.server.async_versions != 1:
                raise ValueError(
                    "server.async_versions requires algorithm='fedbuff' "
                    "(concurrent model versions are an async-buffer "
                    "concept; the synchronous round has exactly one)"
                )
            if self.run.strict_versions:
                raise ValueError(
                    "run.strict_versions requires algorithm='fedbuff' "
                    "with server.async_versions >= 2"
                )
        hier = self.server.hierarchy
        if hier.num_edges < 0:
            raise ValueError("server.hierarchy.num_edges must be >= 0")
        if hier.core_aggregator not in (
            "mean", "median", "trimmed_mean", "krum", "reputation",
        ):
            raise ValueError(
                f"unknown server.hierarchy.core_aggregator "
                f"{hier.core_aggregator!r}"
            )
        if not 0.0 <= hier.core_trim_ratio < 0.5:
            raise ValueError(
                f"server.hierarchy.core_trim_ratio must be in [0, 0.5), "
                f"got {hier.core_trim_ratio}"
            )
        if not 0.0 <= hier.edge_dropout_rate <= 1.0:
            raise ValueError(
                f"server.hierarchy.edge_dropout_rate must be in [0, 1], "
                f"got {hier.edge_dropout_rate}"
            )
        if not 0.0 < hier.core_trust_decay <= 1.0:
            raise ValueError(
                f"server.hierarchy.core_trust_decay must be in (0, 1], "
                f"got {hier.core_trust_decay}"
            )
        if hier.num_edges > 0:
            if self.algorithm == "gossip":
                raise ValueError(
                    "server.hierarchy is incompatible with "
                    "algorithm='gossip' (the decentralized engine has no "
                    "edge/core tiers — its topology IS the aggregation "
                    "structure)"
                )
            if self.algorithm == "fedbuff":
                # the async path: edges group the popped buffer — robust
                # order statistics at the core need the synchronized [E]
                # delta stack the async scheduler never forms
                if hier.core_aggregator not in ("mean", "reputation"):
                    raise ValueError(
                        f"server.hierarchy.core_aggregator="
                        f"{hier.core_aggregator!r} requires the "
                        f"synchronous round program; under "
                        f"algorithm='fedbuff' the async scheduler never "
                        f"forms the synchronized per-edge delta stack "
                        f"order statistics need — use 'mean' or "
                        f"'reputation'"
                    )
            else:
                # the sync path: E invocations of the existing round
                # program per round — everything that assumes exactly
                # one cohort dispatch per round is rejected with its
                # reason
                if self.data.num_clients // hier.num_edges \
                        < self.server.cohort_size:
                    raise ValueError(
                        f"server.hierarchy.num_edges={hier.num_edges}: "
                        f"each edge block holds ~"
                        f"{self.data.num_clients // hier.num_edges} "
                        f"clients but must cover a full cohort of "
                        f"{self.server.cohort_size}"
                    )
                if self.algorithm in ("scaffold", "feddyn"):
                    raise ValueError(
                        "server.hierarchy is incompatible with stateful "
                        "algorithms (scaffold/feddyn scatter per-client "
                        "state once per round; E edge invocations would "
                        "apply E conflicting server-side corrections)"
                    )
                if self.server.error_feedback:
                    raise ValueError(
                        "server.hierarchy is incompatible with "
                        "server.error_feedback (the EF residual store "
                        "rides the single-cohort round program)"
                    )
                if self.server.secure_aggregation:
                    raise ValueError(
                        "server.hierarchy is incompatible with "
                        "server.secure_aggregation (the mask ring is "
                        "committed over ONE round cohort; per-edge "
                        "cohorts would need per-edge key ceremonies)"
                    )
                if self.server.dp_client_noise_multiplier > 0.0:
                    raise ValueError(
                        "server.hierarchy is incompatible with client-"
                        "level DP (noise calibrated for one aggregate "
                        "per round would be added once per edge — E "
                        "times the analyzed mechanism)"
                    )
                if self.dp.enabled:
                    raise ValueError(
                        "server.hierarchy is incompatible with "
                        "dp.enabled (the DP-SGD accountant composes one "
                        "cohort draw per round; E edge cohorts change "
                        "the sampling probability the bound assumes)"
                    )
                if self.run.obs.client_ledger.enabled:
                    raise ValueError(
                        "server.hierarchy is incompatible with "
                        "run.obs.client_ledger (the device-resident "
                        "ledger carry and its paging assume a single "
                        "cohort scatter per round)"
                    )
                if self.server.optimizer != "mean":
                    raise ValueError(
                        "server.hierarchy requires server.optimizer="
                        "'mean' (stateful/adaptive server optimizers "
                        "are not tier-decomposable: each edge would "
                        "evolve its own moment estimates and the core "
                        "delta-space aggregate could not recombine "
                        "them)"
                    )
                if self.server.sampling != "uniform":
                    raise ValueError(
                        f"server.hierarchy draws per-edge cohorts via "
                        f"uniform sampling only; server.sampling="
                        f"{self.server.sampling} is not supported "
                        f"(size weights, Poisson q, adaptive scores, "
                        f"and streaming sketches are parameterized on "
                        f"the GLOBAL population, not per-edge blocks)"
                    )
                if self.data.placement != "hbm":
                    raise ValueError(
                        "server.hierarchy requires data.placement=hbm "
                        "(the stream slab prefetch builds one cohort "
                        "slab per round; per-edge cohorts would race "
                        "it)"
                    )
                if self.run.fuse_rounds > 1:
                    raise ValueError(
                        "server.hierarchy is incompatible with "
                        "run.fuse_rounds (the fused scan compiles one "
                        "cohort per round body; the edge fan-out is a "
                        "host-side loop)"
                    )
                if self.run.shape_buckets.enabled:
                    raise ValueError(
                        "server.hierarchy is incompatible with "
                        "run.shape_buckets (the bucket rung is sized by "
                        "THE round's single sampled cohort; E per-edge "
                        "cohorts would need E rungs per round)"
                    )
                if self.run.host_pipeline == "native":
                    raise ValueError(
                        "server.hierarchy is incompatible with "
                        "run.host_pipeline='native' (the C++ pipeline "
                        "prefetches one cohort per round; use 'auto' or "
                        "'numpy')"
                    )
        if self.algorithm == "scaffold":
            # the option-II control-variate identity cᵢ⁺ = (w₀−w_K)/(K·lr)
            # assumes plain SGD local steps (Karimireddy et al. 2020 §3);
            # momentum breaks it, and DP noise would leak into cᵢ state
            if self.client.optimizer != "sgd" or self.client.momentum != 0.0:
                raise ValueError(
                    "scaffold requires client.optimizer=sgd with momentum=0"
                )
            if self.client.prox_mu > 0.0:
                # the proximal pull μ(w−w₀) is anchored to the ROUND's w₀,
                # so (w₀−w_K)/(K·lr) would bake a round-local term into the
                # persistent cᵢ. (weight_decay is fine: identical across
                # clients, it enters every cᵢ equally and cancels in c−cᵢ.)
                raise ValueError("scaffold is incompatible with client.prox_mu > 0")
            if self.dp.enabled:
                raise ValueError("scaffold is incompatible with dp.enabled")
            if not self._stateful_dtype_ok():
                # cᵢ⁺ divides (w₀−w_K) by K·lr; low-precision anywhere in
                # the trajectory (local w_K OR the server params/delta
                # accumulator) bakes rounding error into the PERSISTENT
                # control variates, which re-enter every local gradient
                raise ValueError(
                    "scaffold requires an f32 parameter trajectory "
                    "(run.param_dtype=float32 and f32 local training)"
                )
            if self.server.aggregator != "weighted_mean":
                # the c update (c += Σδc/N) has no robust equivalent: a
                # poisoned client clipped out of the PARAM update would
                # still poison c_global, which feeds every later round's
                # gradients — the robust aggregator would be a bypassable
                # promise. Forbid rather than mislead.
                raise ValueError(
                    "scaffold is incompatible with robust server.aggregator "
                    "(the control-variate update is a plain mean)"
                )
            if self.server.compression:
                # compressed deltas would move params while cᵢ tracks the
                # UNcompressed trajectory (w₀−w_K)/(K·lr), permanently
                # biasing the corrections
                raise ValueError(
                    "scaffold is incompatible with server.compression"
                )
            if self.server.clip_delta_norm > 0.0:
                # same trajectory-mismatch failure as compression: params
                # move by the CLIPPED delta while cᵢ tracks the raw one
                raise ValueError(
                    "scaffold is incompatible with server.clip_delta_norm"
                )
        if self.run.engine not in ("sharded", "sequential"):
            raise ValueError(f"unknown engine {self.run.engine!r}")
        if self.server.sampling not in (
            "uniform", "weighted", "poisson", "adaptive", "streaming"
        ):
            raise ValueError(f"unknown server.sampling {self.server.sampling!r}")
        if (self.server.sampling == "poisson"
                and self.server.secure_aggregation
                and self.server.secagg_mode == "pairwise"):
            # pairwise secagg's key agreement + Shamir threshold assume a
            # KNOWN cohort that commits keys; Poisson's pad slots are
            # nonexistent clients, which would both skew the threshold
            # semantics (t vs a cap-sized ring) and force per-round
            # recovery work for every unfilled slot. Ring-mode secagg
            # composes fine (pad slots behave as committed-then-dropped).
            raise ValueError(
                "sampling=poisson is incompatible with "
                "secagg_mode='pairwise' (unknown-cohort key agreement); "
                "use secagg_mode='ring'"
            )
        if self.server.aggregator not in (
            "weighted_mean", "median", "trimmed_mean", "krum"
        ):
            raise ValueError(f"unknown server.aggregator {self.server.aggregator!r}")
        if self.server.krum_byzantine < 0:
            raise ValueError(
                f"server.krum_byzantine must be >= 0, "
                f"got {self.server.krum_byzantine}"
            )
        if (self.server.aggregator == "krum"
                and 2 * self.server.krum_byzantine + 2 >= self.server.cohort_size):
            # Blanchard et al. 2017's resilience condition 2f + 2 < n —
            # beyond it Krum provably cannot tolerate f colluders, so a
            # config claiming that defense must not validate
            raise ValueError(
                "krum requires 2*krum_byzantine + 2 < cohort_size "
                "(Blanchard et al. resilience bound)"
            )
        if not 0.0 <= self.server.trim_ratio < 0.5:
            raise ValueError(
                f"server.trim_ratio must be in [0, 0.5), got {self.server.trim_ratio}"
            )
        if self.server.compression not in ("", "topk", "qsgd"):
            raise ValueError(
                f"unknown server.compression {self.server.compression!r}"
            )
        if self.server.error_feedback:
            if not self.server.compression:
                # the memory accumulates what the compressor dropped;
                # with no compressor it is identically zero
                raise ValueError(
                    "server.error_feedback requires server.compression"
                )
            if self.algorithm in ("scaffold", "feddyn", "fedbuff"):
                # scaffold/feddyn own the per-client store (and reject
                # compression outright); fedbuff's async engine has no
                # cohort-synchronous store to scatter into
                raise ValueError(
                    f"server.error_feedback is incompatible with "
                    f"algorithm={self.algorithm!r}"
                )
            if self.server.aggregator != "weighted_mean":
                # EF uploads carry past rounds' residuals — messages of
                # mixed effective timescales with unbounded per-client
                # hidden state; coordinate-wise order statistics over
                # them have no robustness interpretation
                raise ValueError(
                    "server.error_feedback is incompatible with robust "
                    "server.aggregator"
                )
            if self.server.secure_aggregation:
                # secagg's int32 fixed-point range analysis needs the
                # per-round clip bound; C(Δ+e) is not norm-bounded
                raise ValueError(
                    "server.error_feedback is incompatible with "
                    "server.secure_aggregation"
                )
            if self.server.dp_client_noise_multiplier > 0.0:
                # same bound: the DP sensitivity is the clipped delta
                # norm, which the memory term escapes
                raise ValueError(
                    "server.error_feedback is incompatible with "
                    "client-level DP"
                )
        if not 0.0 < self.server.compression_topk_ratio <= 1.0:
            raise ValueError(
                f"server.compression_topk_ratio must be in (0, 1], "
                f"got {self.server.compression_topk_ratio}"
            )
        if self.server.compression_qsgd_levels < 1:
            raise ValueError(
                f"server.compression_qsgd_levels must be >= 1, "
                f"got {self.server.compression_qsgd_levels}"
            )
        if (self.server.compression == "topk"
                and self.server.aggregator != "weighted_mean"):
            # top-k zeroes ~(1-ratio) of each client's coordinates; any
            # coordinate kept by fewer than half the cohort then has a
            # majority of exact zeros in the sorted column, so the
            # coordinate-wise median (and most of the trim window) is 0 —
            # training silently stalls. qsgd (dense, unbiased) composes
            # fine with robust aggregation.
            raise ValueError(
                "server.compression='topk' (sparse) breaks robust "
                "order-statistic aggregators; use qsgd or weighted_mean"
            )
        if self.server.clip_delta_norm < 0.0:
            raise ValueError(
                f"server.clip_delta_norm must be >= 0, "
                f"got {self.server.clip_delta_norm}"
            )
        if self.server.downlink_compression not in ("", "qsgd"):
            raise ValueError(
                f"unknown server.downlink_compression "
                f"{self.server.downlink_compression!r}"
            )
        if self.server.downlink_compression:
            if self.server.downlink_qsgd_levels < 1:
                raise ValueError(
                    f"server.downlink_qsgd_levels must be >= 1, "
                    f"got {self.server.downlink_qsgd_levels}"
                )
            if self.algorithm not in ("fedavg", "fedprox"):
                # scaffold/feddyn's state recursions assume clients
                # received the exact params their c/h corrections track;
                # fedbuff's ring would need per-version quantization
                raise ValueError(
                    "downlink_compression supports fedavg/fedprox only"
                )
        if self.server.dp_client_noise_multiplier < 0.0:
            raise ValueError(
                f"server.dp_client_noise_multiplier must be >= 0, "
                f"got {self.server.dp_client_noise_multiplier}"
            )
        if self.server.dp_client_noise_multiplier > 0.0:
            if self.server.clip_delta_norm <= 0.0:
                # the clip IS the sensitivity bound the noise is
                # calibrated to — without it the guarantee is vacuous
                raise ValueError(
                    "client-level DP requires clip_delta_norm > 0"
                )
            if self.server.aggregator != "weighted_mean":
                # the sensitivity analysis is for the weighted mean;
                # order statistics change the mechanism entirely
                raise ValueError(
                    "client-level DP requires aggregator=weighted_mean"
                )
            if self.server.compression:
                # qsgd's unbiased quantization can inflate a clipped
                # delta's norm past the clip, breaking the sensitivity
                # bound; keep the mechanism sound
                raise ValueError(
                    "client-level DP is incompatible with compression"
                )
            if self.algorithm not in ("fedavg", "fedprox"):
                # stateful trajectories (scaffold/feddyn) would consume
                # noisy aggregates in their c/h recursions; fedbuff's
                # staleness breaks the per-round sampling analysis
                raise ValueError(
                    "client-level DP supports fedavg/fedprox only"
                )
            if self.server.sampling == "weighted":
                # size-proportional sampling raises a big client's
                # per-round inclusion probability above cohort/N, so the
                # accountant's q would understate that client's true
                # RDP spend — the logged ε must be an upper bound for
                # EVERY client (privacy/dp.py contract)
                raise ValueError(
                    "client-level DP requires server.sampling='uniform' "
                    "(weighted sampling breaks the q = cohort/N bound)"
                )
        if self.server.secure_aggregation:
            if self.server.aggregator != "weighted_mean":
                # order statistics need raw per-client deltas — exactly
                # what secure aggregation exists to hide
                raise ValueError(
                    "secure_aggregation is incompatible with robust "
                    "aggregators (they need unmasked per-client deltas)"
                )
            if self.server.compression:
                # masking produces dense uniform int32 — it IS the wire
                # format; sparsity/quantization underneath is meaningless
                raise ValueError(
                    "secure_aggregation is incompatible with "
                    "server.compression"
                )
            if self.algorithm not in ("fedavg", "fedprox"):
                # scaffold/feddyn aggregate per-client state deltas in
                # plaintext (would leak around the masking); fedbuff's
                # buffer membership breaks the per-round participant ring
                raise ValueError(
                    "secure_aggregation supports fedavg/fedprox only"
                )
            if self.server.clip_delta_norm <= 0.0:
                raise ValueError(
                    "secure_aggregation requires clip_delta_norm > 0 "
                    "(bounds the fixed-point range; see ServerConfig)"
                )
            if self.server.secagg_quant_step <= 0.0:
                raise ValueError(
                    f"secagg_quant_step must be > 0, "
                    f"got {self.server.secagg_quant_step}"
                )
            if self.server.secagg_mode not in ("ring", "pairwise"):
                raise ValueError(
                    f"server.secagg_mode must be 'ring' or 'pairwise', "
                    f"got {self.server.secagg_mode!r}"
                )
            t = self.server.secagg_threshold
            if t != 0 and self.server.secagg_mode != "pairwise":
                raise ValueError(
                    "server.secagg_threshold only applies to "
                    "secagg_mode='pairwise'"
                )
            if t != 0 and not 2 <= t <= self.server.cohort_size:
                raise ValueError(
                    f"server.secagg_threshold must be in [2, cohort_size="
                    f"{self.server.cohort_size}], got {t}"
                )
        if not 0.0 <= self.server.straggler_rate <= 1.0:
            raise ValueError(
                f"server.straggler_rate must be in [0, 1], "
                f"got {self.server.straggler_rate}"
            )
        if not 0.0 < self.server.straggler_work <= 1.0:
            raise ValueError(
                f"server.straggler_work must be in (0, 1], "
                f"got {self.server.straggler_work}"
            )
        if self.server.client_state_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown server.client_state_dtype "
                f"{self.server.client_state_dtype!r}"
            )
        if self.run.host_pipeline not in ("auto", "native", "numpy"):
            raise ValueError(f"unknown run.host_pipeline {self.run.host_pipeline!r}")
        if self.run.control_plane not in ("host", "device"):
            raise ValueError(
                f"unknown run.control_plane {self.run.control_plane!r}; "
                f"allowed: host | device"
            )
        if self.run.control_plane == "device":
            # the device plane derives the whole schedule in-program
            # from (seed, round) — anything that injects per-round HOST
            # state into the schedule (adaptive scores, fedbuff queues,
            # secagg key protocols, host_rng failure draws) cannot
            # lower and is rejected with its reason (capability matrix
            # feature `control_plane_device`)
            if self.server.sampling != "uniform":
                raise ValueError(
                    f"run.control_plane='device' requires server."
                    f"sampling='uniform' (got {self.server.sampling!r}: "
                    f"weighted/poisson draw host-RNG shapes and "
                    f"adaptive/streaming need per-round host score "
                    f"state — they stay host-fed)"
                )
            if self.algorithm not in ("fedavg", "fedprox"):
                raise ValueError(
                    f"run.control_plane='device' supports fedavg/"
                    f"fedprox only (got {self.algorithm!r}: scaffold/"
                    f"feddyn thread host-gathered per-client state and "
                    f"the fedbuff/gossip schedulers are host-resident)"
                )
            if self.run.engine not in ("sharded", "sequential"):
                raise ValueError(
                    f"run.control_plane='device' requires run.engine="
                    f"sharded or sequential, got {self.run.engine!r}"
                )
            if self.data.placement != "hbm":
                raise ValueError(
                    "run.control_plane='device' requires data.placement="
                    "'hbm' (stream slabs are built per round on host)"
                )
            if self.server.hierarchy.num_edges > 0:
                raise ValueError(
                    "run.control_plane='device' is incompatible with "
                    "server.hierarchy (edge partitioning is a host "
                    "scheduler)"
                )
            if self.server.secure_aggregation:
                raise ValueError(
                    "run.control_plane='device' is incompatible with "
                    "secure_aggregation (per-round key protocol is "
                    "host I/O)"
                )
            if self.attack.kind:
                raise ValueError(
                    "run.control_plane='device' is incompatible with "
                    "attack simulation (byzantine masks are host-drawn "
                    "per round)"
                )
            if self.server.error_feedback:
                raise ValueError(
                    "run.control_plane='device' is incompatible with "
                    "server.error_feedback (the EF store gathers by "
                    "host-assigned rows)"
                )
            if self.server.straggler_rate > 0 or self.server.dropout_rate > 0:
                raise ValueError(
                    "run.control_plane='device' is incompatible with "
                    "server.straggler_rate/dropout_rate (host-RNG "
                    "failure draws; use run.churn's seed-pure planes "
                    "instead — they lower)"
                )
            if self.run.shape_buckets.enabled:
                raise ValueError(
                    "run.control_plane='device' is incompatible with "
                    "run.shape_buckets (per-round grid re-shaping is a "
                    "host decision; the device program has ONE shape)"
                )
            if self.run.host_pipeline == "native":
                raise ValueError(
                    "run.control_plane='device' is incompatible with "
                    "run.host_pipeline='native' (there is no host slab "
                    "pipeline to accelerate)"
                )
            if self.run.churn.enabled and self.run.churn.trace:
                raise ValueError(
                    "run.control_plane='device' is incompatible with "
                    "run.churn.trace (trace playback reads a host "
                    "memmap; the analytic diurnal planes lower)"
                )
            cl_dev = self.run.obs.client_ledger
            if (cl_dev.enabled and 0 < cl_dev.hot_capacity
                    < self.data.num_clients):
                raise ValueError(
                    "run.control_plane='device' requires the DENSE "
                    "client ledger (hot_capacity=0 or >= num_clients): "
                    "paged slot assignment is a host-stateful remap"
                )
            if self.run.churn.enabled:
                cells = self.server.num_rounds * self.data.num_clients
                if cells > 4_194_304:
                    raise ValueError(
                        f"run.control_plane='device' with churn "
                        f"precomputes a [num_rounds, num_clients] "
                        f"availability-threshold table; {cells} cells "
                        f"exceeds the 4194304 bound — shorten the run, "
                        f"shrink the federation, or use "
                        f"control_plane='host'"
                    )
        if self.run.cohort_layout not in ("spatial", "megabatch"):
            raise ValueError(
                f"unknown run.cohort_layout {self.run.cohort_layout!r}; "
                f"allowed: spatial | megabatch"
            )
        if self.run.cohort_layout == "megabatch":
            if self.algorithm in ("scaffold", "feddyn"):
                # the stateful algorithms thread per-client correction
                # trees (c − cᵢ / −gᵢ) through the per-block vmap; the
                # megabatch block trains the whole lane from ONE shared
                # weight replica at step 0, which has no per-client
                # correction slot — and their f32-trajectory constraints
                # make the layout's bf16 megabatch target moot anyway
                raise ValueError(
                    f"run.cohort_layout='megabatch' is incompatible with "
                    f"algorithm={self.algorithm!r} (stateful per-client "
                    f"correction trees are threaded through the spatial "
                    f"per-block scan)"
                )
            if self.algorithm in ("gossip", "fedbuff"):
                # their engines own the round shape (replica stack /
                # staleness ring) — there is no lane-owned cohort block
                # to megabatch
                raise ValueError(
                    f"run.cohort_layout='megabatch' is incompatible with "
                    f"algorithm={self.algorithm!r} (no lane-owned cohort "
                    f"block; the gossip/fedbuff engines own the round "
                    f"shape)"
                )
            if self.run.batch_shards > 1:
                # the megabatch flattens [K_local, batch] into the GEMM
                # row axis — exactly the axis a batch-sharded mesh
                # splits across chips; the two layouts are rivals for
                # the same dimension
                raise ValueError(
                    "run.cohort_layout='megabatch' is incompatible with "
                    "run.batch_shards > 1 (the megabatch rows are the "
                    "axis the batch mesh shards)"
                )
            if self.run.client_vmap_width >= 2:
                # the layout owns the in-lane batching (whole lane as
                # one block); a narrower explicit width would silently
                # contradict it — reject rather than reinterpret
                raise ValueError(
                    f"run.cohort_layout='megabatch' owns the in-lane "
                    f"batching (the whole lane trains as one block); "
                    f"leave run.client_vmap_width at 1 or 0, got "
                    f"{self.run.client_vmap_width}"
                )
        if self.run.scan_unroll < 1:
            raise ValueError(
                f"run.scan_unroll must be >= 1, got {self.run.scan_unroll}"
            )
        if not 0.0 < self.data.synthetic_template_weight <= 1.0:
            raise ValueError(
                f"data.synthetic_template_weight must be in (0, 1], "
                f"got {self.data.synthetic_template_weight}"
            )
        f = self.run.fuse_rounds
        if f < 1:
            raise ValueError(f"run.fuse_rounds must be >= 1, got {f}")
        if f > 1:
            if self.run.engine != "sharded":
                raise ValueError("fuse_rounds > 1 requires run.engine=sharded")
            if self.algorithm not in ("fedavg", "fedprox"):
                raise ValueError(
                    "fuse_rounds > 1 supports fedavg/fedprox only "
                    "(the scaffold/feddyn c_global recursion and the "
                    "fedbuff/gossip schedulers cannot ride the fused "
                    "scan carry)"
                )
            if self.server.secure_aggregation:
                # the pairwise seed matrix is a per-round host PROTOCOL
                # output (DH agreement + Shamir recovery of the realized
                # dropout set, discovered only after uploads) — it
                # cannot be precomputed into a stacked scan input.
                # Robust aggregators, upload attacks, and error
                # feedback all fuse (the delta stack stays private to
                # the scan body; the EF store rides the scan carry).
                raise ValueError(
                    "fuse_rounds > 1 is incompatible with "
                    "secure_aggregation (per-round key-protocol host "
                    "I/O cannot ride the fused scan)"
                )
            # data.placement="stream" composes since the client-store PR:
            # the fused chunk gathers ONE union slab over its sub-rounds'
            # cohorts (static rows = fuse × slab rows) and remaps the
            # stacked index tensors into it — the engine still sees a
            # single corpus input per dispatch.
            if self.server.num_rounds % f:
                raise ValueError(
                    f"fuse_rounds={f} must divide num_rounds="
                    f"{self.server.num_rounds}"
                )
            for name in ("eval_every", "checkpoint_every"):
                v = getattr(self.server, name)
                if v and v % f:
                    raise ValueError(
                        f"fuse_rounds={f} must divide server.{name}={v} "
                        f"(evals/saves land on chunk boundaries)"
                    )
            if self.run.profile_round >= 0 and self.run.profile_round % f:
                raise ValueError(
                    f"run.profile_round={self.run.profile_round} must be "
                    f"a fuse_rounds={f} chunk boundary (the fit loop "
                    f"steps by chunks; an unaligned value would silently "
                    f"never trigger)"
                )
        sb = self.run.shape_buckets
        if sb.base <= 1.0:
            raise ValueError(
                f"run.shape_buckets.base must be > 1, got {sb.base}"
            )
        if sb.count < 1:
            raise ValueError(
                f"run.shape_buckets.count must be >= 1, got {sb.count}"
            )
        if sb.enabled:
            if self.algorithm in ("fedbuff", "gossip"):
                # fedbuff's in-flight queue and gossip's all-clients
                # round own their own shapes — there is no sampled
                # cohort for the ladder to size against
                raise ValueError(
                    f"run.shape_buckets is incompatible with "
                    f"algorithm={self.algorithm!r} (no sampled cohort "
                    f"to size the step ladder against)"
                )
            if self.dp.enabled:
                # local DP-SGD derives per-step noise keys by POSITION
                # in the padded step grid (split(rng, steps)); trimming
                # padded steps would shift the noise stream of every
                # epoch after the first, breaking the bucketed==full
                # bitwise contract
                raise ValueError(
                    "run.shape_buckets is incompatible with dp.enabled "
                    "(per-step DP noise keys are positional in the "
                    "padded step grid — trimming it shifts the streams)"
                )
            if self.server.straggler_rate > 0.0:
                # straggler truncation cuts at a fraction of the FULL
                # grid's steps; on a trimmed grid the same fraction cuts
                # different examples, so bucketed != full
                raise ValueError(
                    "run.shape_buckets is incompatible with "
                    "server.straggler_rate > 0 (straggler truncation is "
                    "parameterized on the full-shape step grid)"
                )
            if self.run.host_pipeline == "native":
                # the C++ pipeline is constructed for ONE fixed
                # [steps, batch] grid and its own RNG streams; a
                # bucketed run would silently change schedules vs the
                # buckets-off run. 'auto' degrades to the NumPy path.
                raise ValueError(
                    "run.shape_buckets is incompatible with "
                    "run.host_pipeline='native' (the C++ pipeline "
                    "builds one fixed grid); use 'auto' or 'numpy'"
                )
        if self.dp.clipping not in ("microbatch", "two_pass"):
            raise ValueError(
                f"unknown dp.clipping {self.dp.clipping!r}"
            )
        from colearn_federated_learning_tpu.models import returns_aux_loss
        from colearn_federated_learning_tpu.models.lora import LORA_SUPPORTED

        if returns_aux_loss(self.model.name):
            # the model returns (logits, aux): its auxiliary loss and its
            # counters travel through the plain per-client step only
            # (client/trainer.make_loss_fn); name the pairing here
            # instead of failing inside flax. Adapters are the model's
            # own matter: one with an injection map (models/lora.py)
            # takes them.
            unsupported = [
                what for what, on in (
                    ("model.lora.enabled", self.model.lora.enabled
                     and self.model.name not in LORA_SUPPORTED),
                    ("run.cohort_layout='megabatch'",
                     self.run.cohort_layout == "megabatch"),
                    ("dp.enabled", self.dp.enabled),
                    ("run.batch_shards > 1", self.run.batch_shards > 1),
                ) if on
            ]
            if unsupported:
                raise ValueError(
                    f"model {self.model.name!r} (it returns an auxiliary "
                    f"loss) does not support: {', '.join(unsupported)}"
                )
        lora = self.model.lora
        if lora.enabled:
            from colearn_federated_learning_tpu.models.lora import (
                LORA_TARGETS,
            )

            if self.model.name not in LORA_SUPPORTED:
                raise ValueError(
                    f"model.lora is not supported for model "
                    f"{self.model.name!r}: no transformer-block "
                    f"injection map; supported: "
                    f"{', '.join(LORA_SUPPORTED)}"
                )
            if lora.rank < 1:
                raise ValueError(
                    f"model.lora.rank must be >= 1, got {lora.rank}"
                )
            if lora.alpha <= 0.0:
                raise ValueError(
                    f"model.lora.alpha must be > 0, got {lora.alpha}"
                )
            if lora.target not in LORA_TARGETS:
                raise ValueError(
                    f"unknown model.lora.target {lora.target!r}; "
                    f"allowed: {', '.join(LORA_TARGETS)}"
                )
        atk = self.attack
        if atk.kind:
            from colearn_federated_learning_tpu.server.attacks import (
                ATTACK_KINDS,
                UPLOAD_ATTACKS,
            )

            if atk.kind not in ATTACK_KINDS:
                raise ValueError(
                    f"unknown attack.kind {atk.kind!r}; "
                    f"known: {sorted(ATTACK_KINDS)}"
                )
            if not 0.0 < atk.fraction < 1.0:
                raise ValueError(
                    f"attack.fraction must be in (0, 1), got {atk.fraction}"
                )
            if atk.scale <= 0.0:
                raise ValueError(
                    f"attack.scale must be > 0, got {atk.scale}"
                )
            if atk.eps < 0.0:
                raise ValueError(
                    f"attack.eps must be >= 0, got {atk.eps}"
                )
            # pairing rejections (each combination is unsound, not
            # merely unimplemented):
            if self.server.secure_aggregation:
                raise ValueError(
                    "attack simulation is incompatible with "
                    "secure_aggregation: masking hides exactly the "
                    "per-client uploads the attack transform acts on, "
                    "and a Byzantine upload breaks the honest-clipping "
                    "int32 range analysis"
                )
            if self.server.dp_client_noise_multiplier > 0.0:
                raise ValueError(
                    "attack simulation is incompatible with client-level "
                    "DP: the sensitivity analysis assumes every upload "
                    "honors the clip bound — a Byzantine upload voids "
                    "the reported dp_client_epsilon"
                )
            if self.dp.enabled:
                raise ValueError(
                    "attack simulation is incompatible with dp.enabled: "
                    "the example-level accountant assumes every client "
                    "runs the DP-SGD mechanism, which a Byzantine client "
                    "does not — the reported dp_epsilon would be "
                    "misleading"
                )
            if self.algorithm in ("scaffold", "feddyn"):
                raise ValueError(
                    f"attack simulation is incompatible with "
                    f"algorithm={self.algorithm!r}: poisoned uploads "
                    f"enter the persistent c/h state through a plain "
                    f"mean the robust stack cannot defend (same "
                    f"reasoning as the robust-aggregator rejection)"
                )
            if self.algorithm == "fedbuff":
                raise ValueError(
                    "attack simulation is incompatible with "
                    "algorithm='fedbuff': the async buffer has no "
                    "per-cohort upload stack to transform, and "
                    "staleness-decayed weights have no Byzantine "
                    "semantics"
                )
            if self.server.error_feedback:
                raise ValueError(
                    "attack simulation is incompatible with "
                    "error_feedback: a Byzantine client's residual "
                    "memory is unbounded hidden state carried across "
                    "rounds"
                )
            if atk.kind == "label_flip" and self.model.num_classes < 2:
                raise ValueError(
                    "attack.kind='label_flip' requires a classification "
                    "label space (model.num_classes >= 2)"
                )
            if atk.kind in UPLOAD_ATTACKS:
                if self.algorithm == "gossip" and atk.kind == "alie":
                    raise ValueError(
                        "attack.kind='alie' is incompatible with "
                        "algorithm='gossip': alie sizes its perturbation "
                        "from cohort-wide statistics a decentralized "
                        "attacker cannot observe"
                    )
        if self.data.synthetic_task not in ("template", "template_pair"):
            raise ValueError(
                f"unknown data.synthetic_task {self.data.synthetic_task!r}"
            )
        if not 0.0 <= self.data.synthetic_label_noise < 1.0:
            raise ValueError(
                f"data.synthetic_label_noise must be in [0, 1), "
                f"got {self.data.synthetic_label_noise}"
            )
        if self.data.placement not in ("hbm", "stream"):
            raise ValueError(f"unknown data.placement {self.data.placement!r}")
        # dtype strings are resolved through a fixed table deep in the
        # driver — without this check a typo ("bf16") surfaces as an
        # opaque KeyError/jnp.dtype error far from the config
        _DTYPE_NAMES = ("float32", "bfloat16", "float16")
        for f in ("param_dtype", "compute_dtype"):
            if getattr(self.run, f) not in _DTYPE_NAMES:
                raise ValueError(
                    f"unknown run.{f} {getattr(self.run, f)!r}; "
                    f"allowed: {', '.join(_DTYPE_NAMES)}"
                )
        if self.run.local_param_dtype not in ("",) + _DTYPE_NAMES:
            raise ValueError(
                f"unknown run.local_param_dtype "
                f"{self.run.local_param_dtype!r}; allowed: '' (inherit "
                f"run.param_dtype), {', '.join(_DTYPE_NAMES)}"
            )
        if self.server.fused_apply:
            if self.server.optimizer not in ("mean", "fedavgm"):
                # the kernel's one-pass FMA chain is exactly
                # sgd(+momentum); fedadam/fedyogi second-moment state
                # has no single-pass expression
                raise ValueError(
                    "server.fused_apply supports server.optimizer="
                    "'mean' or 'fedavgm' only (the pallas kernel "
                    "implements the sgd(+momentum) update); got "
                    f"{self.server.optimizer!r}"
                )
            if self.algorithm in ("scaffold", "feddyn", "gossip"):
                # scaffold/feddyn interleave their c/h state recursions
                # with the apply (feddyn bypasses the server optimizer
                # entirely); gossip has no server apply at all
                raise ValueError(
                    f"server.fused_apply is incompatible with "
                    f"algorithm={self.algorithm!r} (stateful algorithms "
                    f"own the server step; gossip has no server apply)"
                )
        obs = self.run.obs
        if obs.on_unhealthy not in ("warn", "abort", "checkpoint_abort"):
            raise ValueError(
                f"unknown run.obs.on_unhealthy {obs.on_unhealthy!r}; "
                f"expected warn | abort | checkpoint_abort"
            )
        if obs.divergence_factor != 0.0 and obs.divergence_factor <= 1.0:
            # a factor in (0, 1] would flag every round at or above the
            # best loss — i.e. immediately and forever
            raise ValueError(
                f"run.obs.divergence_factor must be 0 (off) or > 1, "
                f"got {obs.divergence_factor}"
            )
        if obs.trace and not obs.spans:
            raise ValueError(
                "run.obs.trace=true requires run.obs.spans=true (the "
                "trace is built from the spans)"
            )
        if obs.trace_max_events < 0:
            raise ValueError(
                f"run.obs.trace_max_events must be >= 0, "
                f"got {obs.trace_max_events}"
            )
        if obs.hbm_budget_mb < 0:
            raise ValueError(
                f"run.obs.hbm_budget_mb must be >= 0, "
                f"got {obs.hbm_budget_mb}"
            )
        if obs.hbm_budget_mb > 0 and not obs.executables:
            raise ValueError(
                "run.obs.hbm_budget_mb requires run.obs.executables "
                "(the budget check reads the registry's predicted peaks)"
            )
        dg = obs.digest
        if dg.every < 1:
            raise ValueError(
                f"run.obs.digest.every must be >= 1, got {dg.every}"
            )
        if (dg.enabled and self.run.fuse_rounds > 1
                and dg.every % self.run.fuse_rounds):
            # digest boundaries force a metrics flush; the fit loop
            # steps by chunks, so an unaligned cadence would silently
            # never fire (same contract as eval_every/checkpoint_every)
            raise ValueError(
                f"run.obs.digest.every ({dg.every}) must be a "
                f"fuse_rounds={self.run.fuse_rounds} multiple (digest "
                f"boundaries land on chunk ends)"
            )
        pop = obs.population
        if not 4 <= pop.hll_bits <= 18:
            raise ValueError(
                f"run.obs.population.hll_bits must be in [4, 18], "
                f"got {pop.hll_bits}"
            )
        if pop.top_k < 1:
            raise ValueError(
                f"run.obs.population.top_k must be >= 1, got {pop.top_k}"
            )
        if pop.recency_capacity < 1:
            raise ValueError(
                f"run.obs.population.recency_capacity must be >= 1, "
                f"got {pop.recency_capacity}"
            )
        cl = obs.client_ledger
        if not 0.0 < cl.ema <= 1.0:
            raise ValueError(
                f"run.obs.client_ledger.ema must be in (0, 1], got {cl.ema}"
            )
        if cl.zmax <= 0.0:
            raise ValueError(
                f"run.obs.client_ledger.zmax must be > 0, got {cl.zmax}"
            )
        if cl.log_every < 0:
            raise ValueError(
                f"run.obs.client_ledger.log_every must be >= 0, "
                f"got {cl.log_every}"
            )
        if cl.hot_capacity < 0:
            raise ValueError(
                f"run.obs.client_ledger.hot_capacity must be >= 0, "
                f"got {cl.hot_capacity}"
            )
        if cl.enabled and cl.hot_capacity > 0 and self.server.error_feedback:
            # the EF residual store is indexed by TRUE client ids and
            # shares the engines' cohort-id input with the ledger — the
            # pager's slot remap would scatter residuals to wrong rows
            raise ValueError(
                "run.obs.client_ledger.hot_capacity > 0 is incompatible "
                "with server.error_feedback (the EF store is indexed by "
                "true client ids on the same cohort-id input the paged "
                "ledger remaps to hot-set slots)"
            )
        if cl.enabled:
            if self.server.secure_aggregation:
                # the ledger computes per-client upload statistics —
                # exactly the information secure aggregation exists to
                # hide from the server
                raise ValueError(
                    "run.obs.client_ledger is incompatible with "
                    "secure_aggregation (per-client upload statistics "
                    "are what masking hides)"
                )
            if self.server.dp_client_noise_multiplier > 0.0:
                # client-level DP releases only the noised aggregate;
                # a per-client statistics side channel voids it
                raise ValueError(
                    "run.obs.client_ledger is incompatible with "
                    "client-level DP (per-client statistics are a "
                    "disclosure channel the DP analysis does not cover)"
                )
            if self.algorithm == "gossip":
                raise ValueError(
                    "run.obs.client_ledger is incompatible with "
                    "algorithm='gossip' (no server-visible upload "
                    "stack to compute stats over — neighbour messages "
                    "are whole replicas)"
                )
            if self.algorithm == "fedbuff" and cl.hot_capacity > 0:
                # per-INSERT stats over each server step's popped
                # buffer feed the dense ledger fine (fedbuff × ledger
                # is supported since the churn PR); the pager's
                # id→hot-slot remap is wired into the synchronous
                # dispatch paths only
                raise ValueError(
                    "run.obs.client_ledger.hot_capacity > 0 (paged "
                    "ledger) is not supported with algorithm='fedbuff' "
                    "— the async scheduler ships true client ids; use "
                    "the dense ledger (hot_capacity=0)"
                )
            if self.algorithm in ("scaffold", "feddyn"):
                raise ValueError(
                    f"run.obs.client_ledger is incompatible with "
                    f"algorithm={self.algorithm!r} (the stateful "
                    f"engines own the per-client state path; the "
                    f"attack/robust stacks the ledger audits are "
                    f"rejected there anyway)"
                )
        rep = self.server.reputation
        if not 0.0 < rep.floor < 1.0:
            raise ValueError(
                f"server.reputation.floor must be in (0, 1), got {rep.floor}"
            )
        if rep.strength <= 0.0:
            raise ValueError(
                f"server.reputation.strength must be > 0, "
                f"got {rep.strength}"
            )
        if rep.z_gain < 0.0:
            raise ValueError(
                f"server.reputation.z_gain must be >= 0, got {rep.z_gain}"
            )
        if rep.enabled and not cl.enabled:
            # trust weights are a pure function of the ledger rows; the
            # ledger's own pairing rejections above (secure aggregation,
            # client-level DP, gossip/fedbuff, scaffold/feddyn) therefore
            # exclude exactly the combinations that would be unsound for
            # reputation too — its stats channel IS the ledger's
            raise ValueError(
                "server.reputation requires run.obs.client_ledger."
                "enabled (trust weights are computed from the "
                "device-resident ledger rows; enabling the ledger also "
                "applies its pairing exclusions — secagg, client-level "
                "DP, gossip, stateful algorithms)"
            )
        if self.server.sampling in ("adaptive", "streaming"):
            ad = self.server.adaptive
            if not 0.0 < ad.explore <= 1.0:
                raise ValueError(
                    f"server.adaptive.explore must be in (0, 1], "
                    f"got {ad.explore}"
                )
            if ad.staleness_gain < 0.0:
                raise ValueError(
                    f"server.adaptive.staleness_gain must be >= 0, "
                    f"got {ad.staleness_gain}"
                )
            if ad.flag_suppress < 0.0:
                raise ValueError(
                    f"server.adaptive.flag_suppress must be >= 0, "
                    f"got {ad.flag_suppress}"
                )
            if ad.sketch_size < 1:
                raise ValueError(
                    f"server.adaptive.sketch_size must be >= 1, "
                    f"got {ad.sketch_size}"
                )
        if self.server.sampling == "streaming" and cl.enabled and cl.log_every >= 1:
            # ledger evidence flows into the streaming sketch at the
            # same snapshot-refresh boundaries as "adaptive" — the same
            # schedule-purity constraints apply (the cohort must be a
            # pure function of (seed, round, sketch) so prefetch/resume
            # replay it; the prefetch worker drains itself at refresh
            # boundaries, which is why placement=stream IS allowed here)
            if self.run.fuse_rounds > 1 and cl.log_every % self.run.fuse_rounds:
                raise ValueError(
                    f"server.sampling='streaming' with run.fuse_rounds="
                    f"{self.run.fuse_rounds} requires client_ledger."
                    f"log_every ({cl.log_every}) to be a fuse_rounds "
                    f"multiple (sketch refreshes must land on fused-"
                    f"chunk boundaries)"
                )
            if self.run.shape_buckets.enabled:
                raise ValueError(
                    "server.sampling='streaming' with ledger-fed "
                    "sketches is incompatible with run.shape_buckets "
                    "(the bucket rung must be a pure function of "
                    "(seed, round); sketch-scored cohorts depend on "
                    "the ledger snapshot)"
                )
            if self.run.host_pipeline == "native":
                raise ValueError(
                    "server.sampling='streaming' with ledger-fed "
                    "sketches is incompatible with run.host_pipeline="
                    "'native' (the C++ pipeline prefetches future "
                    "cohorts ahead of sketch refreshes); use 'auto' or "
                    "'numpy'"
                )
        ch = self.run.churn
        if ch.diurnal_period < 1:
            raise ValueError(
                f"run.churn.diurnal_period must be >= 1, "
                f"got {ch.diurnal_period}"
            )
        if not 0.0 <= ch.diurnal_amplitude <= 1.0:
            raise ValueError(
                f"run.churn.diurnal_amplitude must be in [0, 1], "
                f"got {ch.diurnal_amplitude}"
            )
        if not 0.0 < ch.base_availability <= 1.0:
            raise ValueError(
                f"run.churn.base_availability must be in (0, 1], "
                f"got {ch.base_availability}"
            )
        if not 0.0 < ch.min_availability <= 1.0:
            raise ValueError(
                f"run.churn.min_availability must be in (0, 1], "
                f"got {ch.min_availability}"
            )
        if not 0.0 <= ch.dropout_hazard < 1.0:
            raise ValueError(
                f"run.churn.dropout_hazard must be in [0, 1), "
                f"got {ch.dropout_hazard}"
            )
        if not 0.0 <= ch.crash_rate < 1.0:
            raise ValueError(
                f"run.churn.crash_rate must be in [0, 1), "
                f"got {ch.crash_rate}"
            )
        if ch.trace and not ch.enabled:
            raise ValueError(
                "run.churn.trace requires run.churn.enabled (trace "
                "replay is an availability model; enabled=false must "
                "construct nothing)"
            )
        if ch.enabled:
            if self.algorithm == "gossip":
                raise ValueError(
                    "run.churn is incompatible with algorithm='gossip' "
                    "(every client trains every round — there is no "
                    "availability-gated cohort draw; gossip's own "
                    "dropout_rate models link failure)"
                )
            if self.run.shape_buckets.enabled:
                # same reason as the straggler rejection: crash
                # truncation cuts at a fraction of the FULL grid's
                # steps; a trimmed grid would cut different examples
                raise ValueError(
                    "run.churn is incompatible with run.shape_buckets "
                    "(crash-mid-round truncation is parameterized on "
                    "the full-shape step grid, like stragglers)"
                )
            if self.server.sampling in ("weighted", "poisson", "adaptive"):
                raise ValueError(
                    f"run.churn gates the uniform and streaming cohort "
                    f"samplers only; server.sampling="
                    f"{self.server.sampling} is not supported (static "
                    f"size weights and the Poisson DP-exact q assume "
                    f"unconditional draws; dense adaptive scores would "
                    f"need availability renormalization)"
                )
        st = self.data.store
        if st.gather_workers < 0:
            raise ValueError(
                f"data.store.gather_workers must be >= 0 (0 = auto), "
                f"got {st.gather_workers}"
            )
        if st.eval_buffer_mb < 1:
            raise ValueError(
                f"data.store.eval_buffer_mb must be >= 1, "
                f"got {st.eval_buffer_mb}"
            )
        if st.dir:
            if self.attack.kind == "label_flip":
                raise ValueError(
                    "attack.kind='label_flip' is incompatible with "
                    "data.store (label poisoning mutates training labels "
                    "host-side; the store's records are a read-only mmap)"
                )
            if self.run.host_pipeline == "native":
                raise ValueError(
                    "data.store is incompatible with run.host_pipeline="
                    "'native' (the C++ pipeline materializes the full "
                    "per-client index lists the store exists to avoid); "
                    "use 'auto' or 'numpy'"
                )
        if self.server.sampling == "adaptive":
            if not cl.enabled or cl.log_every < 1:
                # the sampler's scores refresh from the periodic ledger
                # snapshots; without a cadence they would stay frozen at
                # the all-unseen prior forever
                raise ValueError(
                    "server.sampling='adaptive' requires "
                    "run.obs.client_ledger.enabled with log_every >= 1 "
                    "(utility scores refresh from the periodic ledger "
                    "snapshots; the ledger's pairing exclusions apply)"
                )
            if self.run.fuse_rounds > 1 and cl.log_every % self.run.fuse_rounds:
                # the ledger only materializes at chunk boundaries under
                # fusion; a mid-chunk refresh round would have nothing
                # deterministic to fetch
                raise ValueError(
                    f"server.sampling='adaptive' with run.fuse_rounds="
                    f"{self.run.fuse_rounds} requires client_ledger."
                    f"log_every ({cl.log_every}) to be a fuse_rounds "
                    f"multiple (snapshot refreshes must land on fused-"
                    f"chunk boundaries)"
                )
            if self.data.placement != "hbm":
                # the stream-mode prefetch worker builds round r+1's
                # inputs while round r runs; a snapshot refresh between
                # build and consumption would sample a cohort a resumed
                # run could not replay
                raise ValueError(
                    "server.sampling='adaptive' requires "
                    "data.placement=hbm (the stream prefetch worker "
                    "races the ledger-snapshot refresh, breaking the "
                    "(seed, round, snapshot)-pure schedule)"
                )
            if self.run.shape_buckets.enabled:
                # the bucket ladder's contract is that the cohort (and
                # hence the rung) is a pure function of (seed, round) —
                # adaptive cohorts additionally depend on the ledger
                raise ValueError(
                    "server.sampling='adaptive' is incompatible with "
                    "run.shape_buckets (the bucket rung must be a pure "
                    "function of (seed, round); adaptive cohorts depend "
                    "on the ledger snapshot)"
                )
            if self.run.host_pipeline == "native":
                # the C++ pipeline prefetches FUTURE rounds' cohorts and
                # treats resubmission as a no-op — a snapshot refresh
                # between prefetch and dispatch would silently serve
                # tensors for a stale cohort ('auto' degrades to NumPy)
                raise ValueError(
                    "server.sampling='adaptive' is incompatible with "
                    "run.host_pipeline='native' (the C++ pipeline "
                    "prefetches future cohorts ahead of snapshot "
                    "refreshes); use 'auto' or 'numpy'"
                )
        return self

    # ---- serialization ------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ExperimentConfig":
        def build(dc_cls, sub):
            fields = {f.name: f for f in dataclasses.fields(dc_cls)}
            kwargs = {}
            for k, v in sub.items():
                if k not in fields:
                    raise KeyError(f"unknown config key {k!r} for {dc_cls.__name__}")
                f = fields[k]
                if dataclasses.is_dataclass(f.type) or f.name in _NESTED:
                    kwargs[k] = build(_NESTED[f.name], v)
                else:
                    kwargs[k] = v
            return dc_cls(**kwargs)

        _NESTED = {
            "model": ModelConfig,
            "data": DataConfig,
            "client": ClientConfig,
            "server": ServerConfig,
            "dp": DPConfig,
            "attack": AttackConfig,
            "run": RunConfig,
            "obs": ObsConfig,  # nested under run
            "shape_buckets": ShapeBucketsConfig,  # nested under run
            "churn": ChurnConfig,  # nested under run
            "client_ledger": ClientLedgerConfig,  # nested under run.obs
            "population": PopulationConfig,  # nested under run.obs
            "reputation": ReputationConfig,  # nested under server
            "adaptive": AdaptiveSamplerConfig,  # nested under server
            "hierarchy": HierarchyConfig,  # nested under server
            "store": StoreConfig,  # nested under data
            "lora": LoRAConfig,  # nested under model
        }
        return build(cls, d)

    @classmethod
    def from_yaml(cls, path: str) -> "ExperimentConfig":
        with open(path) as f:
            return cls.from_dict(yaml.safe_load(f))

    def to_yaml(self, path: str) -> None:
        with open(path, "w") as f:
            yaml.safe_dump(self.to_dict(), f, sort_keys=False)

    def apply_overrides(self, overrides: Dict[str, Any]) -> "ExperimentConfig":
        """Apply dotted-path overrides like {'server.num_rounds': 5}.

        Paths may descend into dict-typed fields (``model.kwargs.seq_len``).
        """
        for dotted, value in overrides.items():
            obj = self
            *head, last = dotted.split(".")
            for part in head:
                if isinstance(obj, dict):
                    obj = obj[part]
                elif hasattr(obj, part):
                    obj = getattr(obj, part)
                else:
                    # unknown section must fail the same clean way as an
                    # unknown leaf (CLI turns KeyError into exit 2)
                    raise KeyError(f"unknown config path {dotted!r}")
            if isinstance(obj, dict):
                obj[last] = value
                continue
            if not hasattr(obj, last):
                raise KeyError(f"unknown config path {dotted!r}")
            current = getattr(obj, last)
            if current is not None and not isinstance(current, dict):
                value = type(current)(value) if not isinstance(value, type(current)) else value
            setattr(obj, last, value)
        return self


# ---------------------------------------------------------------------------
# The five named BASELINE configs (BASELINE.json:7-11)
# ---------------------------------------------------------------------------


def _mnist_fedavg_2() -> ExperimentConfig:
    """BASELINE config #1: FedAvg, 2 clients, LeNet-5 on MNIST (CPU smoke)."""
    return ExperimentConfig(
        name="mnist_fedavg_2",
        algorithm="fedavg",
        model=ModelConfig(name="lenet5", num_classes=10),
        data=DataConfig(name="mnist", num_clients=2, partition="iid"),
        client=ClientConfig(local_epochs=1, batch_size=32, lr=0.1),
        server=ServerConfig(num_rounds=20, cohort_size=2),
    )


def _cifar10_fedavg_100() -> ExperimentConfig:
    """BASELINE config #2: FedAvg, 100 clients, ResNet-18 on CIFAR-10 Dirichlet.

    The headline-metric config (BASELINE.json:2): FL rounds/sec and
    client-updates/sec/chip are measured here.
    """
    return ExperimentConfig(
        name="cifar10_fedavg_100",
        algorithm="fedavg",
        model=ModelConfig(name="resnet18", num_classes=10),
        data=DataConfig(
            name="cifar10",
            num_clients=100,
            partition="dirichlet",
            dirichlet_alpha=0.5,
            max_examples_per_client=512,
        ),
        client=ClientConfig(local_epochs=1, batch_size=64, lr=0.05),
        server=ServerConfig(num_rounds=500, cohort_size=16, eval_every=10),
        # megabatch cohort layout (r12): on one chip the whole cohort-16
        # block trains as one fused step — the shared-weight first step
        # feeds the MXU [16·64 = 1024]-row GEMMs where the spatial scan
        # capped every matmul at one client's 64 — the structural answer
        # to the 41.4% MFU plateau of the spatial layout
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16",
                      cohort_layout="megabatch"),
    )


def _cifar10_fedavg_1000() -> ExperimentConfig:
    """The NORTH-STAR scale config (BASELINE.json:5): FedAvg, 1000 clients,
    ResNet-18 on CIFAR-10 Dirichlet non-IID, cohort 64.

    Same per-client workload as the headline ``cifar10_fedavg_100`` so
    the two are directly comparable; only the federation size (1000
    shards over the full 50k-example corpus — real CIFAR-10's
    cardinality, mirrored by the synthetic fallback) and the cohort
    (64) change. At ~50 examples/client the Dirichlet shards are small
    and skewed; ``max_examples_per_client=128`` bounds the static pad
    without truncating any but the largest shards."""
    return ExperimentConfig(
        name="cifar10_fedavg_1000",
        algorithm="fedavg",
        model=ModelConfig(name="resnet18", num_classes=10),
        data=DataConfig(
            name="cifar10",
            num_clients=1000,
            partition="dirichlet",
            dirichlet_alpha=0.5,
            synthetic_train_size=50_000,
            synthetic_test_size=2_000,
            max_examples_per_client=128,
        ),
        client=ClientConfig(local_epochs=1, batch_size=64, lr=0.05),
        server=ServerConfig(num_rounds=1000, cohort_size=64, eval_every=20),
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16",
                      cohort_layout="megabatch"),
    )


def _femnist_fedprox_500() -> ExperimentConfig:
    """BASELINE config #3: FedProx, 500 clients, MobileNetV2 on FEMNIST (LEAF)."""
    return ExperimentConfig(
        name="femnist_fedprox_500",
        algorithm="fedprox",
        model=ModelConfig(name="mobilenetv2", num_classes=62, kwargs={"width_mult": 1.0}),
        data=DataConfig(
            name="femnist",
            num_clients=500,
            partition="natural",
            max_examples_per_client=256,
        ),
        client=ClientConfig(local_epochs=1, batch_size=32, lr=0.03, prox_mu=0.01),
        # cohort 32 adopted from the r5 sweep: 281→337→396→448
        # updates/s/chip at cohorts 8/16/32/64 — MobileNetV2@28 is
        # memory-bound so gains are shallow; 32 takes the +17% without
        # an extreme participation ratio (BASELINE.md r5)
        server=ServerConfig(num_rounds=500, cohort_size=32, eval_every=10),
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16",
                      cohort_layout="megabatch"),
    )


def _shakespeare_fedavg() -> ExperimentConfig:
    """BASELINE config #4: FedAvg, BERT-tiny next-token LM on Shakespeare (LEAF)."""
    return ExperimentConfig(
        name="shakespeare_fedavg",
        algorithm="fedavg",
        model=ModelConfig(
            name="bert_tiny",
            num_classes=0,
            kwargs={"vocab_size": 90, "seq_len": 80},
        ),
        data=DataConfig(
            name="shakespeare",
            num_clients=128,
            partition="natural",
            max_examples_per_client=256,
        ),
        client=ClientConfig(local_epochs=1, batch_size=16, lr=0.5),
        # cohort 32 + fuse 10 adopted from the r5 sweep (VERDICT r4
        # weak-#2): 381→560→722→793 updates/s/chip at cohorts 8/16/32/
        # 64, and multi-round fusion stacks another ~11% on the
        # dispatch-dominated wall clock — 32+fuse measured 801
        # updates/s/chip vs the old config's 381, a 2.1× improvement at
        # a sane 25% participation ratio (BASELINE.md r5). fuse=10
        # divides num_rounds and eval_every (chunk-boundary cadence).
        server=ServerConfig(num_rounds=200, cohort_size=32, eval_every=10),
        # megabatch layout (r12) supersedes the r4 client_vmap_width=0
        # adoption: the whole-lane vmap was worth 7.0 → 6.24 ms/round
        # (BASELINE.md r4); the layout keeps that batched-GEMM shape for
        # the diverged steps AND runs the shared-weight first step as a
        # true [K_local·16]-row megabatch against unbatched weights.
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16",
                      cohort_layout="megabatch", fuse_rounds=10),
    )


def _imagenet_silo_dp() -> ExperimentConfig:
    """BASELINE config #5: cross-silo FedAvg + DP-SGD, ViT-B/16, 32 silos."""
    return ExperimentConfig(
        name="imagenet_silo_dp",
        algorithm="fedavg",
        model=ModelConfig(
            name="vit_b16", num_classes=1000, kwargs={"image_size": 224}
        ),
        data=DataConfig(
            name="imagenet_federated",
            num_clients=32,
            partition="silo",
            max_examples_per_client=1024,
        ),
        client=ClientConfig(local_epochs=1, batch_size=64, lr=0.003, optimizer="adamw"),
        server=ServerConfig(num_rounds=100, cohort_size=32, eval_every=5),
        # microbatch 16: measured ~5% faster than 8 on v5e at 224px; 32 is
        # marginally faster still but near the compile/memory ceiling
        dp=DPConfig(enabled=True, l2_clip=1.0, noise_multiplier=0.8, microbatch_size=16),
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16"),
    )


def _cifar10_gossip_16() -> ExperimentConfig:
    """Beyond-reference: decentralized DFedAvg (algorithm=gossip) at the
    headline workload — 16 clients, ResNet-18 on CIFAR-10 Dirichlet,
    ring topology. Every client trains every round from its OWN replica
    and mixes with its two ring neighbours (a halo exchange on the
    mesh); eval runs on the consensus mean and the consensus distance
    is logged per round. Same per-client workload as
    ``cifar10_fedavg_100`` so the serverless round cost is directly
    comparable to the centralized one."""
    return ExperimentConfig(
        name="cifar10_gossip_16",
        algorithm="gossip",
        model=ModelConfig(name="resnet18", num_classes=10),
        data=DataConfig(
            name="cifar10",
            num_clients=16,
            partition="dirichlet",
            dirichlet_alpha=0.5,
            max_examples_per_client=512,
        ),
        client=ClientConfig(local_epochs=1, batch_size=64, lr=0.05),
        server=ServerConfig(num_rounds=500, cohort_size=16, eval_every=10),
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16"),
    )


def _cifar10_krum_byzantine() -> ExperimentConfig:
    """Beyond-reference: the adversarial workload — the headline
    CIFAR-10 federation under a live sign-flipping adversary (attack.*,
    server/attacks.py) defended by Krum. 2/16 cohort slots are expected
    Byzantine in steady state (fraction 0.125 of 100 clients ≈ 12
    compromised, cohort 16 uniform), matching the krum_byzantine=2
    defense assumption within the Blanchard 2f+2 < n resilience bound.
    The per-round ``byzantine_count`` metric logs the realized count."""
    return ExperimentConfig(
        name="cifar10_krum_byzantine",
        algorithm="fedavg",
        model=ModelConfig(name="resnet18", num_classes=10),
        data=DataConfig(
            name="cifar10",
            num_clients=100,
            partition="dirichlet",
            dirichlet_alpha=0.5,
            max_examples_per_client=512,
        ),
        client=ClientConfig(local_epochs=1, batch_size=64, lr=0.05),
        server=ServerConfig(
            num_rounds=500, cohort_size=16, eval_every=10,
            aggregator="krum", krum_byzantine=2,
        ),
        attack=AttackConfig(kind="sign_flip", fraction=0.125, scale=10.0),
        # megabatch composes with the attacked krum path (the wire stack
        # and robust selection see identical [K, ·] shapes either way —
        # parity-pinned in tests/test_round_engine.py)
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16",
                      cohort_layout="megabatch"),
    )


def _bert_lora_federated() -> ExperimentConfig:
    """Beyond-reference (ROADMAP item 3): million-user-shaped
    transformer federation on adapter uploads — BERT-tiny on the LEAF
    Shakespeare task, 1024 natural-partition clients drawn by the
    O(cohort·log) streaming sampler, with rank-2 attention LoRA so the
    per-client wire message is the adapter factors only (~136× fewer
    upload bytes than the full-delta twin at this geometry; the
    analytic counters log the exact ``wire_reduction_vs_full``). The
    base transformer stays frozen at its seed-derived init; clients
    train only the qkv/attention-output adapters at a hot adapter
    learning rate (adapter-space steps move a ~3k-coordinate subspace,
    so the stable lr sits well above the full-model config's 0.5).
    Scale this up with `colearn store build` + ``data.store.dir`` +
    ``data.placement=stream``."""
    return ExperimentConfig(
        name="bert_lora_federated",
        algorithm="fedavg",
        model=ModelConfig(
            name="bert_tiny",
            num_classes=0,
            kwargs={"vocab_size": 90, "seq_len": 80},
            lora=LoRAConfig(enabled=True, rank=2, alpha=8.0,
                            target="attention"),
        ),
        data=DataConfig(
            name="shakespeare",
            num_clients=1024,
            partition="natural",
            max_examples_per_client=128,
        ),
        client=ClientConfig(local_epochs=1, batch_size=16, lr=2.0),
        server=ServerConfig(
            num_rounds=200, cohort_size=32, eval_every=10,
            sampling="streaming",
        ),
        # megabatch (r12) supersedes client_vmap_width=0: under LoRA the
        # adapters ARE the params, so the shared-weight first step
        # megabatches the whole frozen-base forward at [K_local·16] rows
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16",
                      cohort_layout="megabatch"),
    )


def _vit_lora_dp() -> ExperimentConfig:
    """Beyond-reference (ROADMAP item 3 follow-up): the cross-silo ViT
    workload on ADAPTER uploads with example-level DP — ``vit_b16``'s
    LoRA injection map (models/lora.py ``LORA_SUPPORTED``) finally
    exercised by a named config. Each of 32 silos trains rank-4
    attention adapters over the frozen ViT-B/16 base under DP-SGD
    (per-example clipping + noise act on the ADAPTER gradients — the
    released coordinates are the ~590k-coordinate adapter subspace
    instead of the 86M-param full model, which both shrinks the wire
    message and concentrates the privacy budget on what actually
    ships). Two-pass clipping keeps the per-example backward
    MXU-batched at 224px. Layout stays spatial: DP's per-example
    gradients multiply activation memory by the microbatch, so a
    cohort-wide megabatch block would trade the MXU win for an HBM
    cliff on this model."""
    return ExperimentConfig(
        name="vit_lora_dp",
        algorithm="fedavg",
        model=ModelConfig(
            name="vit_b16", num_classes=1000, kwargs={"image_size": 224},
            lora=LoRAConfig(enabled=True, rank=4, alpha=8.0,
                            target="attention"),
        ),
        data=DataConfig(
            name="imagenet_federated",
            num_clients=32,
            partition="silo",
            max_examples_per_client=1024,
        ),
        # adamw on the factor pair (the Hu et al. recipe); adapter-space
        # steps move a small subspace, so the lr sits above the
        # full-model silo config's 0.003
        client=ClientConfig(local_epochs=1, batch_size=64, lr=0.01,
                            optimizer="adamw"),
        server=ServerConfig(num_rounds=100, cohort_size=32, eval_every=5),
        dp=DPConfig(enabled=True, l2_clip=1.0, noise_multiplier=0.8,
                    microbatch_size=16, clipping="two_pass"),
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16"),
    )


def _keye_silo_lm() -> ExperimentConfig:
    """Cross-silo FedAvg on the language decoder of Keye-VL-2.0-30B-A3B
    as one chip of an 8-way expert-parallel deployment holds it
    (models/keye.py: 16 of 128 experts, an eighth of the vocabulary, 4 of
    48 layers; every width as published): 8 silos continue training on
    long private documents, 2 local AdamW steps of one 8,192-token
    sequence per round. Spatial layout, no DP, no LoRA (validate() names
    what this model does not support)."""
    return ExperimentConfig(
        name="keye_silo_lm",
        algorithm="fedavg",
        model=ModelConfig(
            name="keye_decoder",
            num_classes=0,
            kwargs={"vocab_size": 18992, "seq_len": 8192, "layers": 4,
                    "experts_held": 16},
        ),
        data=DataConfig(
            name="synthetic_text",
            num_clients=8,
            partition="silo",
            max_examples_per_client=2,
        ),
        client=ClientConfig(local_epochs=1, batch_size=1, lr=1e-4,
                            optimizer="adamw", weight_decay=0.01),
        server=ServerConfig(num_rounds=100, cohort_size=8, eval_every=0),
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16"),
    )


def _axk1_silo_lora() -> ExperimentConfig:
    """Cross-silo FedAvg of rank-16 adapters on A.X-K1 as one chip of a
    16-way expert-parallel deployment holds it (models/axk1.py: latent
    attention, the leading dense layer and 4 of the 60 expert layers, 12
    of 192 routed experts beside the shared one, an eighth of the
    vocabulary; every width as published): 8 silos adapt the frozen
    bfloat16 base (3.49 B parameters, 7 GB, an argument of the round
    program) to private text and exchange only the adapters of the five
    latent-attention projections (5.0 M parameters), 2 local AdamW steps
    of one 4,096-token sequence per round. Spatial layout, no DP
    (validate() names what this model does not support)."""
    return ExperimentConfig(
        name="axk1_silo_lora",
        algorithm="fedavg",
        model=ModelConfig(
            name="axk1_decoder",
            num_classes=0,
            kwargs={"vocab_size": 20480, "seq_len": 4096, "layers": 5,
                    "experts_held": 12},
            lora=LoRAConfig(enabled=True, rank=16, alpha=32.0,
                            target="attention"),
        ),
        data=DataConfig(
            name="synthetic_text",
            num_clients=8,
            partition="silo",
            max_examples_per_client=2,
        ),
        client=ClientConfig(local_epochs=1, batch_size=1, lr=1e-4,
                            optimizer="adamw", weight_decay=0.01),
        server=ServerConfig(num_rounds=100, cohort_size=8, eval_every=0),
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16"),
    )


def _mellum2_silo_lm() -> ExperimentConfig:
    """Cross-silo FedAvg on the decoder of Mellum2-12B-A2.5B as one chip
    of an 8-way expert-parallel deployment holds it (models/mellum2.py:
    one whole period of its layers, three sliding-window layers and one
    full layer with YaRN's rope, 8 of 64 experts, an eighth of the
    vocabulary; every width as published), trained in full: 8 silos
    continue training on private repositories, 2 local AdamW steps of
    one 16,384-token sequence per round. Spatial layout, no DP, no LoRA
    (validate() names what this model does not support)."""
    return ExperimentConfig(
        name="mellum2_silo_lm",
        algorithm="fedavg",
        model=ModelConfig(
            name="mellum2_decoder",
            num_classes=0,
            kwargs={"vocab_size": 12288, "seq_len": 16384, "layers": 4,
                    "experts_held": 8},
        ),
        data=DataConfig(
            name="synthetic_text",
            num_clients=8,
            partition="silo",
            max_examples_per_client=2,
        ),
        client=ClientConfig(local_epochs=1, batch_size=1, lr=1e-4,
                            optimizer="adamw", weight_decay=0.01),
        server=ServerConfig(num_rounds=100, cohort_size=8, eval_every=0),
        run=RunConfig(compute_dtype="bfloat16", local_param_dtype="bfloat16"),
    )


_NAMED = {
    "mnist_fedavg_2": _mnist_fedavg_2,
    "cifar10_fedavg_100": _cifar10_fedavg_100,
    "cifar10_fedavg_1000": _cifar10_fedavg_1000,
    "femnist_fedprox_500": _femnist_fedprox_500,
    "shakespeare_fedavg": _shakespeare_fedavg,
    "imagenet_silo_dp": _imagenet_silo_dp,
    "cifar10_gossip_16": _cifar10_gossip_16,
    "cifar10_krum_byzantine": _cifar10_krum_byzantine,
    "bert_lora_federated": _bert_lora_federated,
    "vit_lora_dp": _vit_lora_dp,
    "keye_silo_lm": _keye_silo_lm,
    "axk1_silo_lora": _axk1_silo_lora,
    "mellum2_silo_lm": _mellum2_silo_lm,
}


def get_named_config(name: str) -> ExperimentConfig:
    try:
        return _NAMED[name]().validate()
    except KeyError:
        raise KeyError(f"unknown named config {name!r}; known: {sorted(_NAMED)}") from None


def list_named_configs():
    return sorted(_NAMED)


def resolve_config(name_or_path: str, overrides: Optional[Dict[str, Any]] = None) -> ExperimentConfig:
    """Resolve a config by registry name or YAML path, then apply overrides."""
    if name_or_path in _NAMED:
        cfg = get_named_config(name_or_path)
    elif name_or_path.endswith((".yaml", ".yml")) or "/" in name_or_path:
        cfg = ExperimentConfig.from_yaml(name_or_path)
    else:
        raise KeyError(
            f"unknown config {name_or_path!r}; known named configs: "
            f"{sorted(_NAMED)} (or pass a .yaml path)"
        )
    if overrides:
        cfg.apply_overrides(overrides)
    return cfg.validate()
