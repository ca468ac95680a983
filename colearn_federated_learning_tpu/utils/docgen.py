"""Generate the config reference (docs/CONFIG.md) from the dataclasses.

The field/default tables are derived from the live dataclasses, so the
committed doc cannot drift silently: ``tests/test_docs.py`` regenerates
and compares. Field SEMANTICS live as comments in config.py (the single
source of truth) — the doc links each section there.
"""

from __future__ import annotations

import dataclasses

from colearn_federated_learning_tpu import config as config_mod

_SECTIONS = [
    ("model", config_mod.ModelConfig, "Model selection (zoo name + per-family kwargs)."),
    ("model.lora", config_mod.LoRAConfig,
     "LoRA adapter plane (models/lora.py): freeze the transformer base "
     "and train/ship/aggregate ONLY rank-r adapter pairs — every "
     "targeted dense kernel W gains A [d_in, r] / B [r, d_out] and the "
     "effective weight is W + (alpha/r)*A*B (B starts at zero, so the "
     "merged model initially equals the base). The params pytree the "
     "whole round stack operates on IS the adapter set, so engines, "
     "aggregation (weighted_mean AND krum/median over flattened "
     "factors), compression, upload attacks, the forensic ledger, "
     "reputation, and the wire counters all run in adapter space by "
     "construction; eval and `colearn export` use the merged model. "
     "Cuts per-client upload bytes ~d/(2r) per target (the shipped "
     "bert_lora_federated geometry logs wire_reduction_vs_full = "
     "136x); supported families: bert_tiny, vit_b16, axk1_decoder (the "
     "five projections of its latent attention, stacked over the "
     "layers, applied as side products). The frozen base is DATA: "
     "drawn on the device by init_state (span init.frozen_base), leaf "
     "by leaf in run.local_param_dtype (else run.param_dtype), "
     "replicated over the lanes, handed to every round program as an "
     "argument beside the corpus (client/trainer.RoundData) and to "
     "eval and export as Experiment.frozen_base; never donated, "
     "aggregated, compressed, attacked, ledgered, checkpointed or "
     "shipped (a pure function of run.seed, re-derived on resume); "
     "run.hbm_gb's pre-flight counts its bytes. The adapters keep "
     "run.param_dtype. lora off builds the exact pre-LoRA program "
     "(bitwise, test-pinned). See docs/DESIGN.md \"LoRA adapter "
     "plane\"."),
    ("data", config_mod.DataConfig, "Dataset, federation partition, placement."),
    ("data.store", config_mod.StoreConfig,
     "On-disk memory-mapped client store (data/store.py) — the "
     "million-client data path. `colearn store build` converts a "
     "config's data (or streams a synthetic federation at any client "
     "count) into fixed-record binary shards + a small per-client "
     "offset/length index; with `dir` set the corpus stays on disk "
     "behind np.memmap views and the host pipeline gathers only the "
     "sampled cohort's records into each round's slab — every "
     "host-side structure the round loop touches is O(cohort). "
     "Store-backed runs are BITWISE-equal to the in-memory run the "
     "store was converted from on the same seed and host pipeline "
     "(pin run.host_pipeline explicitly when comparing — 'auto' may "
     "pick the native pipeline for the in-memory run while the store "
     "path always uses NumPy). Pair with data.placement=\"stream\" + "
     "server.sampling=\"streaming\" (+ client_ledger.hot_capacity for "
     "the paged ledger) for the full O(cohort) story. See "
     "docs/DESIGN.md \"Client store & million-client scaling\"."),
    ("client", config_mod.ClientConfig, "Per-client local training."),
    ("server", config_mod.ServerConfig,
     "Round schedule, aggregation, algorithms' server-side knobs."),
    ("server.reputation", config_mod.ReputationConfig,
     "Reputation-weighted aggregation off the per-client forensic "
     "ledger: each round converts every cohort member's ledger row "
     "(cumulative flag rate, above-threshold robust-z EMA) into a "
     "multiplicative trust weight in [floor, 1], computed IN-PROGRAM "
     "from the device-resident ledger carried from previous rounds — "
     "the single-psum weighted-mean path stays host-free and the "
     "trust rides the fused scan carry under run.fuse_rounds. Under "
     "aggregator=weighted_mean the FedAvg weight becomes w*trust "
     "(numerator and denominator); under robust aggregators trust "
     "scales each delta before the order statistics (soft suppression "
     "— a false flag costs a fraction of one update, not a cohort "
     "slot). Unseen clients carry trust exactly 1. This is the soft "
     "complement to krum's hard rejection: near f = K/2 the Blanchard "
     "resilience bound is void, while the reputation-weighted mean "
     "degrades attackers gradually as ledger evidence accumulates "
     "(test-pinned: sign_flip at f = K/2 - 1 on cohort 8 breaks both "
     "plain weighted_mean and krum; the reputation-weighted mean "
     "stays in the benign band). Requires run.obs.client_ledger."
     "enabled (and inherits its pairing exclusions). See "
     "docs/DESIGN.md \"Adaptive selection & reputation\"."),
    ("server.hierarchy", config_mod.HierarchyConfig,
     "Two-tier (device -> edge -> core) federation "
     "(server/round_driver.py): num_edges = E > 0 splits the client "
     "universe into E deterministic contiguous blocks (client i "
     "belongs to edge i*E // num_clients); each edge aggregator runs "
     "the EXISTING compiled round program over a cohort drawn from "
     "its own block (per-edge pure-(seed, round) samplers) with "
     "server.aggregator as the edge-tier defense (e.g. krum), and "
     "the core combines the E edge DELTAS per core_aggregator — "
     "example-weighted mean, reputation (trust-weighted mean over a "
     "per-edge liveness EMA, decay core_trust_decay), or "
     "median/trimmed_mean/krum one tier up (robust_reduce over the "
     "[E] stack; sync path only). edge_dropout_rate injects seed-pure "
     "per-(round, edge) crashes: a crashed edge's delta is EXCLUDED "
     "and counted (hier_edge_crashed), never NaN-poisoning the core "
     "— an all-crashed round is an exact no-op. Under "
     "algorithm=fedbuff the hierarchy rides the async scheduler "
     "instead: popped completions group by their client's edge, "
     "crashed edges' completions are excluded that server step, and "
     "edge trust multiplies the staleness-decayed weights. Per-tier "
     "wire accounting (hier_core_upload_bytes) and per-edge absorbed "
     "counts land in round records and run_summary. num_edges=0 "
     "constructs nothing and is bitwise-identical to the flat plane "
     "(test-pinned). See docs/DESIGN.md \"Hierarchical & "
     "multi-version federation\"."),
    ("server.adaptive", config_mod.AdaptiveSamplerConfig,
     "Scoring knobs for server.sampling=\"adaptive\": Oort-style "
     "utility-aware cohort selection from the ledger's periodic "
     "host-side snapshots — loss-utility EMA x participation-"
     "staleness boost x exponential flag-rate suppression, mixed with "
     "a uniform exploration floor so every client stays drawable. The "
     "snapshot refreshes at client_ledger.log_every round boundaries "
     "and rides the checkpoint, so the schedule is a pure function of "
     "(seed, round, snapshot) and resume replays it exactly. Requires "
     "run.obs.client_ledger.enabled with log_every >= 1; rejected "
     "with data.placement=stream, run.shape_buckets, and "
     "run.host_pipeline='native' (each would race or stale the "
     "snapshot — see config.py for the reasons)."),
    ("dp", config_mod.DPConfig, "DP-SGD (per-example clip + noise, RDP accounting)."),
    ("attack", config_mod.AttackConfig,
     "Byzantine adversary simulation (in-loop attack injection)."),
    ("run", config_mod.RunConfig,
     "Engine/mesh/dtype/ops switches (profiling, retries, host pipeline)."),
    ("run.shape_buckets", config_mod.ShapeBucketsConfig,
     "Heterogeneity-aware round shapes: quantize each round's step grid "
     "onto a geometric ladder sized by the SAMPLED cohort (chunk-max "
     "under run.fuse_rounds) instead of the federation max. Padded "
     "steps are exact no-ops, so bucketed runs are bitwise-equal to "
     "buckets-off runs on the same seed and host pipeline, with <= "
     "ladder-size extra compiles per engine (attributed per rung via "
     "the obs compile listener's `shape_bucket` events). See "
     "docs/DESIGN.md \"Shape buckets & retrace policy\"."),
    ("run.churn", config_mod.ChurnConfig,
     "Seed-pure availability/churn model (server/churn.py) — the "
     "production-traffic plane: per-client diurnal availability waves "
     "(hash-derived phase per client), a mid-round dropout hazard, and "
     "crash-mid-round injection at a hash-drawn work fraction. Every "
     "draw is a pure function of (run.seed, round, client_id) by "
     "counter-mode hashing, so schedules are resume-replayable with "
     "zero checkpoint state and engine-invariant. Gates the uniform "
     "and streaming samplers (offline candidates rejected); dispatched "
     "members realize failures through the existing straggler/dropout "
     "machinery (crash -> mask truncation, offline/hazard -> weight "
     "zeroing); under algorithm=fedbuff offline clients defer "
     "completions, growing realized staleness toward the bounded-"
     "staleness admission gate (run.strict_staleness) and the "
     "server.async_backlog_cap backpressure policy. churn off "
     "constructs nothing and is bitwise-identical to pre-churn builds. "
     "See docs/DESIGN.md \"Churn & async production traffic\"."),
    ("run.obs", config_mod.ObsConfig,
     "Observability: round-lifecycle phase spans (+ optional Chrome-trace "
     "export), communication/device counters, and NaN/divergence health "
     "monitoring with configurable abort. `colearn summarize <run>` "
     "aggregates the resulting JSONL into a per-phase timing table."),
    ("run.obs.client_ledger", config_mod.ClientLedgerConfig,
     "Per-client forensic ledger: each round program emits a [K] "
     "per-client stats block (upload L2 norm, cosine vs the aggregated "
     "delta, clip/EF residual magnitude, post-local-train loss, robust "
     "median/MAD z-score anomaly flag) and scatters it in-program into "
     "a device-resident [num_clients] store carried across rounds "
     "(participation count, per-stat EMAs, cumulative flagged rounds) "
     "— riding the fused scan carry under run.fuse_rounds like the EF "
     "residual store, with zero extra host round-trips and an "
     "unchanged params trajectory. Periodic `client_ledger` JSONL "
     "records (final flush on EVERY exit path, aborts included) feed "
     "`colearn clients <run>`: top-k anomalous clients, participation "
     "histogram, and detection precision/recall against the attack "
     "provenance event's ground-truth compromised set. Rejected "
     "pairings with reasons: secure_aggregation (masking hides exactly "
     "these statistics), client-level DP (a per-client disclosure "
     "channel), gossip (no server-visible upload stack), scaffold/"
     "feddyn (stateful store plumbing). algorithm=fedbuff is SUPPORTED "
     "via per-insert stats over each async server step's popped buffer "
     "(dense ledger only — hot_capacity paging stays synchronous). See "
     "docs/DESIGN.md \"Client ledger & attack attribution\"."),
    ("run.obs.population", config_mod.PopulationConfig,
     "Federation health observatory (obs/population.py): per-flush-"
     "window `population_health` JSONL records covering the data "
     "plane the million-client structures run on — sampler health "
     "(cumulative unique-client coverage via a seed-pure O(1)-memory "
     "HLL-style counter, exploration/exploitation draw split, "
     "streaming-sketch occupancy / refresh age / flag-rate coverage, "
     "cohort staleness distribution over a bounded recency map), "
     "ledger-pager health (per-window hit/miss/page-in/eviction "
     "counts + page-sync stall ms — the run_summary totals as a time "
     "series), store I/O (bytes gathered, gather wall ms, per-shard "
     "touch counts, union-slab dedup ratio), and participation "
     "fairness (Gini/max-share over a bounded top-k sketch, never a "
     "dense [num_clients] histogram). Every structure is O(cohort) or "
     "fixed-size and every count-based column is engine-parity pinned "
     "(sharded = sequential = fused; only `*_ms` wall-clock fields "
     "may differ). Purely observational — params bitwise-unchanged. "
     "`colearn watch <run>` renders the live view (pure host, works "
     "mid-fit), `colearn population <run>` the post-hoc report; "
     "`colearn summarize` surfaces the run_summary totals. See "
     "docs/DESIGN.md \"Federation health observatory\"."),
    ("run.obs.digest", config_mod.DigestConfig,
     "Determinism flight recorder (obs/digest.py): at each digest "
     "boundary (`every` rounds; must land on fused-chunk ends under "
     "run.fuse_rounds) the driver hashes the fetched round state — "
     "params (per-top-level-leaf AND rolled up), optimizer state, the "
     "ledger/pager hot set, the realized cohort schedule + failure "
     "stats, the per-round wire-byte counters, and the RNG inputs — "
     "into one `round_digest` JSONL record whose `self` hash chains "
     "over `prev`, so a truncated or tampered log is self-evident. "
     "The chain head rides every checkpoint and is re-verified "
     "against the log on resume (`verify_resume`; warn, or abort "
     "with `strict` / `colearn fit --strict-digest`). Digests are "
     "pure functions of fetched state: identical across engines "
     "where engines are bitwise, invariant to fuse_rounds and flush "
     "cadence, and digest-on leaves the params trajectory bitwise "
     "unchanged. `colearn diff <a> <b>` aligns two runs' chains and "
     "names the first divergent round + component (params leaf / opt "
     "/ ledger / schedule / wire / rng); `colearn replay <run> "
     "--round r` re-executes one round from the nearest checkpoint "
     "and verifies the recomputed digest. Off by default (and in "
     "benches — the digest fetch is host-exposed time). See "
     "docs/DESIGN.md \"Determinism flight recorder\"."),
]

# appended under the `attack` section table (kept here so the generated
# doc and the committed doc cannot drift apart)
_THREAT_MODEL = """\
### Threat model

Where each attack acts, and which defenses are expected to hold:

| attack | acts on | mechanism |
|---|---|---|
| `sign_flip` | upload | compromised delta becomes `-scale*delta` (gradient reversal, boosted) |
| `gauss` | upload | compromised delta replaced by `eps*N(0, I)` (pure noise) |
| `scale` | upload | compromised delta becomes `scale*delta` (model-replacement boosting) |
| `alie` | upload | all colluders send `mean - eps*std` of the honest cohort's per-coordinate statistics ("a little is enough", Baruch et al. 2019) |
| `label_flip` | data | compromised clients' training labels flipped `y -> (C-1)-y` host-side; the upload is an honest gradient of poisoned data |

Upload attacks apply inside the round program, after clipping/compression
(the honest client's update rule) and before aggregation — the point a
real attacker controls. The compromised id set is a deterministic pure
function of `run.seed`; a `[K]` byzantine-mask input rides alongside
`n_ex`, so the attacked set changes per round with no retrace and the
sharded and sequential engines agree on attacked rounds. Under
`algorithm=gossip` the poisoned artifact is the replica gossiped to ring
neighbours (`alie` is rejected there — no cohort statistics are
observable to a decentralized attacker).

Expected defense behavior (pinned by `tests/test_attack.py`): plain
`server.aggregator="weighted_mean"` collapses toward chance accuracy
under `sign_flip` at f=2 of cohort 8, while `krum`, `median`, and
`trimmed_mean` under the identical attack stay within their benign
accuracy band. Defenses act per round on the upload stack, so they do
NOT defend `label_flip` (an honest-looking gradient of poisoned data) —
that is the attack's point. Unsound pairings (secure aggregation,
client-level or example-level DP, scaffold/feddyn, fedbuff,
error feedback) are rejected by `validate()` with reasons. Upload
attacks compose with `run.fuse_rounds > 1`: the per-round byzantine
masks become a stacked `[fuse, K]` scan input and the attacked delta
stack stays private to the fused scan body.
"""


def _fmt(v) -> str:
    if isinstance(v, str):
        return f'`"{v}"`' if v else '`""`'
    if isinstance(v, dict) and not v:
        return "`{}`"
    if dataclasses.is_dataclass(v):
        # nested config block: its own section carries the fields
        return "(nested section below)"
    return f"`{v}`"


def config_reference_markdown() -> str:
    section_names = {s for s, _, _ in _SECTIONS}
    top = [
        f"`{f.name}` ({_fmt(f.default)})"
        for f in dataclasses.fields(config_mod.ExperimentConfig)
        if f.name not in section_names
    ]
    algos = " | ".join(config_mod.ALGORITHMS)
    lines = [
        "# Config reference",
        "",
        "Generated from the dataclasses in "
        "`colearn_federated_learning_tpu/config.py` — semantics are "
        "documented as comments there; this file lists every field and "
        "its default. Regenerated + diffed by `tests/test_docs.py`.",
        "",
        f"Top-level `ExperimentConfig` fields: {', '.join(top)}; "
        f"`algorithm` is one of {algos}. The sections below follow. Any "
        "field is settable from the CLI with `--set section.field=value`.",
        "",
    ]
    for section, cls, blurb in _SECTIONS:
        lines += [f"## `{section}` — {cls.__name__}", "", blurb, "",
                  "| field | default |", "|---|---|"]
        for f in dataclasses.fields(cls):
            if f.default is not dataclasses.MISSING:
                default = f.default
            else:
                default = f.default_factory()
            lines.append(f"| `{f.name}` | {_fmt(default)} |")
        lines.append("")
        if section == "attack":
            lines += [_THREAT_MODEL]
    lines += _model_kwargs_section("keye_decoder", _KEYE_KWARGS_BLURB)
    lines += _model_kwargs_section("axk1_decoder", _AXK1_KWARGS_BLURB)
    lines += _model_kwargs_section("mellum2_decoder", _MELLUM2_KWARGS_BLURB)
    names = config_mod.list_named_configs()
    named = ", ".join(f"`{n}`" for n in names)
    lines += [
        "## Named configs",
        "",
        f"{named} — the {len(names)} shipped capability configs "
        "(`colearn configs` lists them; `colearn fit --config <name>` "
        "runs one).",
        "",
    ]
    appendix = capability_matrix_appendix()
    if appendix:
        lines += [appendix]
    return "\n".join(lines)


_KEYE_KWARGS_BLURB = (
    "The language decoder of Keye-VL-2.0-30B-A3B as one chip of an "
    "expert-parallel deployment holds it (models/keye.py; named config "
    "`keye_silo_lm`). The defaults are the published widths; `layers`, "
    "`experts_held` (with `expert_offset`, the first global id held) and "
    "`vocab_size` are the chip's share. `index_topk` keys are selected "
    "per query by the indexer, which learns from a loss of its own; "
    "`q_chunk` (queries per attention chunk) and `moe_tile` (rows per "
    "expert tile) are tilings that change no value. Where "
    "`experts_held` is less than `num_experts` the gates are constants "
    "of the backward pass (the router is not trained: ops/moe.route). "
    "The model does not support "
    "`model.lora.enabled`, `run.cohort_layout=megabatch`, `dp.enabled` "
    "or `run.batch_shards > 1` (validate() names them)."
)


_AXK1_KWARGS_BLURB = (
    "The decoder of A.X-K1 as one chip of an expert-parallel deployment "
    "holds it (models/axk1.py; named config `axk1_silo_lora`, which "
    "trains rank-16 adapters on its latent attention over a frozen "
    "bfloat16 base). The defaults are the published widths; `layers` "
    "(the leading dense layer and `layers - 1` expert layers), "
    "`experts_held` (with `expert_offset`) and `vocab_size` are the "
    "chip's share. Latent attention: `q_rank` / `kv_rank` latents, "
    "`heads` of `qk_nope + qk_rope` query-key dims and `v_dim` value "
    "dims, one rope key per position; the `rope_*` kwargs are YaRN's. "
    "Routing: sigmoid scores, the `topk_group` best of `n_group` groups, "
    "`experts_per_token` among them, gates scaled by `gate_scale`; a "
    "shared expert beside the held ones. `q_chunk` and `moe_tile` are "
    "tilings that change no value. The model reports counters and "
    "does not support `run.cohort_layout=megabatch`, `dp.enabled` or "
    "`run.batch_shards > 1` (validate() names them); with "
    "`model.lora.enabled` everything but the adapters is frozen, "
    "without it the whole decoder trains."
)


_MELLUM2_KWARGS_BLURB = (
    "The decoder of Mellum2-12B-A2.5B as one chip of an expert-parallel "
    "deployment holds it (models/mellum2.py; named config "
    "`mellum2_silo_lm`, trained in full). The defaults are the published "
    "widths; `layers` (whole periods of `period`), `experts_held` (with "
    "`expert_offset`) and `vocab_size` are the chip's share. `period` "
    "lists the kinds of a period's layers: a `sliding` layer's query "
    "reads itself and the `sliding_window - 1` positions before it and "
    "turns by plain RoPE, a `full` layer's reads the whole causal "
    "triangle and turns by YaRN's frequencies (`rope_factor`, "
    "`rope_original`, `rope_beta_fast`, `rope_beta_slow`) with cosine "
    "and sine times `rope_attention_factor`; both kinds run "
    "ops/band_attention.py, which visits the band's tiles only. "
    "`q_chunk` (the attention kernels' tile, both kinds) and `moe_tile` "
    "are tilings that change no value. Where "
    "`experts_held` is less than `num_experts` the gates are constants "
    "of the backward pass (ops/moe.route). The model reports counters "
    "and does not support `model.lora.enabled`, "
    "`run.cohort_layout=megabatch`, `dp.enabled` or "
    "`run.batch_shards > 1` (validate() names them)."
)


def _model_kwargs_section(name: str, blurb: str):
    """`model.kwargs` of one zoo family, from its factory's signature."""
    import inspect

    from colearn_federated_learning_tpu.models import model_registry

    factory = model_registry.get(name)
    counters = ", ".join(f"`{c}`" for c in factory.aux_counters)
    lines = [f"## `model.kwargs` of `{name}`", "", blurb, "",
             f"Counters in the round's metrics, per round: {counters}.", "",
             "| kwarg | default |", "|---|---|"]
    for p in inspect.signature(factory).parameters.values():
        if p.kind is p.VAR_KEYWORD or p.name in (
                "num_classes", "compute_dtype", "param_dtype"):
            continue
        lines.append(f"| `{p.name}` | {_fmt(p.default)} |")
    return lines + [""]


def capability_matrix_appendix() -> str:
    """Auto-generated pairing-matrix appendix, sourced from the
    checked-in ``capability_matrix.json`` (`colearn check` extracts it
    from validate(); analysis/capability.py). The artifact lists the
    rejected pairings; every other pairing validates. Empty string
    when the artifact is absent (fresh checkouts before the first
    `colearn check --update-matrix`)."""
    import os

    from colearn_federated_learning_tpu.analysis import capability
    from colearn_federated_learning_tpu.analysis.check import detect_root

    root = detect_root()
    if not os.path.isfile(capability.matrix_path(root)):
        return ""
    matrix = capability.load_matrix(root)
    c = matrix["counts"]
    lines = [
        "## Appendix: capability pairing matrix",
        "",
        f"Sourced from `capability_matrix.json` (version "
        f"{matrix['version']}; regenerate with `colearn check "
        f"--update-matrix`): {c['features']} features x {c['pairs']} "
        f"pairings — {c['supported']} supported, {c['rejected']} "
        f"rejected by `validate()` with reasons. The rejected pairings:",
        "",
        "| pairing | reason |",
        "|---|---|",
    ]
    for pair, reason in matrix["rejected"].items():
        reason = " ".join(reason.replace("|", "\\|").split())
        if len(reason) > 140:
            reason = reason[:137] + "..."
        lines.append(f"| `{pair}` | {reason} |")
    lines.append("")
    return "\n".join(lines)
