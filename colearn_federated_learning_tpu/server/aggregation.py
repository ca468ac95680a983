"""Aggregation + server optimizer (SURVEY.md §2 C6; call stack §3.4).

The math: ``Δ̄ = Σᵢ nᵢ·Δᵢ / Σᵢ nᵢ`` over the cohort (the reference
realizes the same weighted-sum as an NCCL allreduce, BASELINE.json:5;
the shard_map engine realizes it as ``jax.lax.psum`` — see
parallel/round_engine.py — and this module is the shared host-side /
server-update half).

We aggregate **deltas** (wᵢ − w_global) rather than raw params so a
server-side optimizer (FedAvgM / FedAdam, Reddi et al. 2021) can treat
−Δ̄ as a pseudo-gradient. With the default ``mean`` optimizer and
server_lr=1 this is exactly classic FedAvg.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import optax

from colearn_federated_learning_tpu.config import ServerConfig
from colearn_federated_learning_tpu.utils import trees


def weighted_delta_mean(deltas, weights):
    """Host-side reference weighted mean over a list of delta pytrees."""
    return trees.tree_weighted_mean(deltas, weights)


def reputation_weights(ledger, cohort_ids, floor: float, strength: float,
                       z_gain: float, zmax: float):
    """``[K]`` multiplicative trust weights for one round's cohort from
    the device-resident ``[num_clients, LEDGER_WIDTH]`` forensic ledger
    (obs/ledger.py; ``server.reputation``). Per cohort member::

        flag_rate = flagged / max(count, 1)
        excess_z  = max(ema_z / zmax - 1, 0)      # above-threshold only
        score     = flag_rate + z_gain * excess_z
        trust     = floor + (1 - floor) * exp(-strength * score)

    Unseen clients (``count == 0``) — and poisson pad slots, whose
    out-of-range id makes ``take`` fill a zero row — get trust exactly
    1.0: reputation only ever acts on ledger EVIDENCE, so round 1 is a
    plain weighted mean and a fresh client enters at full voice. The
    trust derives from the ledger AS CARRIED INTO the round (the
    round's own stats scatter lands after aggregation), all in f32 with
    one shared implementation for the sharded program, the sequential
    oracle, and the fused scan body — cross-engine parity by
    construction, exactly like ``client_round_stats``. Runs as plain
    jnp under the round jit: zero extra host round-trips."""
    rows = ledger.shape[0]
    ids = jnp.where(
        (cohort_ids >= 0) & (cohort_ids < rows),
        cohort_ids.astype(jnp.int32), jnp.int32(rows),
    )
    row = jnp.take(ledger, ids, axis=0, mode="fill", fill_value=0.0)
    count = row[:, 0]
    flag_rate = row[:, 1] / jnp.maximum(count, 1.0)
    excess_z = jnp.maximum(row[:, 6] / jnp.float32(zmax) - 1.0, 0.0)
    score = flag_rate + jnp.float32(z_gain) * excess_z
    trust = jnp.float32(floor) + jnp.float32(1.0 - floor) * jnp.exp(
        -jnp.float32(strength) * score
    )
    return jnp.where(count > 0, trust, 1.0).astype(jnp.float32)


def scale_deltas_by_trust(deltas, trust):
    """Scale a ``[K, ...]`` stacked delta tree by per-client trust — the
    reputation hook for the ROBUST aggregators, whose order statistics
    are unweighted by design (a weighted median would re-open the
    attack surface weights provide): a suppressed client's upload
    shrinks toward the zero update instead of being hard-ejected, so a
    false flag costs a fraction of one update rather than a cohort
    slot. Shared by both engines."""
    return jax.tree.map(
        lambda d: d * trust.reshape(
            (trust.shape[0],) + (1,) * (d.ndim - 1)
        ).astype(d.dtype),
        deltas,
    )


def robust_reduce(deltas, participation, mode: str, trim_ratio: float = 0.1,
                  byzantine_f: int = 0):
    """Byzantine-robust aggregate of stacked client deltas.

    ``deltas``: ``[K, ...]`` tree (the cohort's updates); ``participation``:
    ``[K]`` 0/1 — non-participants (dropout, empty shards) are excluded
    EXACTLY, via an input-independent trick that keeps shapes static: their
    rows are set to +inf before a per-coordinate sort, so they land past
    every participant, and the order statistics index only the first
    ``m = Σ participation`` rows (dynamic scalar, static shapes — XLA
    sorts are oblivious to m). Modes:

    - ``"median"``    — coordinate-wise median over participants (Yin et
      al. 2018); tolerates < m/2 corrupted clients per coordinate.
    - ``"trimmed_mean"`` — drop ``⌊trim_ratio·m⌋`` smallest and largest
      values per coordinate, average the rest (0 ≤ ratio < 0.5).
    - ``"krum"``      — selection (Blanchard et al. 2017): return the ONE
      participant delta whose summed squared distance to its
      ``m − byzantine_f − 2`` nearest participant neighbours is
      smallest (clamped ≥ 1 neighbour). Whole-update selection — a
      poisoned update is discarded entirely rather than per-coordinate.

    Robust statistics are unweighted by design (a weighted median would
    re-open the attack surface weights provide). Math in f32. The result
    feeds the server optimizer exactly like the weighted mean."""
    if mode == "krum":
        return _krum(deltas, participation, byzantine_f)
    part = participation.astype(jnp.float32)
    m = part.sum().astype(jnp.int32)
    k = part.shape[0]
    iota = jnp.arange(k)

    def leaf(d):
        pb = part.reshape((k,) + (1,) * (d.ndim - 1))
        s = jnp.sort(
            jnp.where(pb > 0, d.astype(jnp.float32), jnp.inf), axis=0
        )
        if mode == "median":
            lo = jnp.clip((m - 1) // 2, 0, k - 1)
            hi = jnp.clip(m // 2, 0, k - 1)
            med = 0.5 * (jnp.take(s, lo, axis=0) + jnp.take(s, hi, axis=0))
            return jnp.where(m > 0, med, 0.0)
        if mode != "trimmed_mean":
            raise ValueError(f"unknown robust aggregator {mode!r}")
        t = jnp.floor(trim_ratio * m.astype(jnp.float32)).astype(jnp.int32)
        keep = ((iota >= t) & (iota < m - t)).astype(jnp.float32)
        keep = keep.reshape((k,) + (1,) * (d.ndim - 1))
        cnt = jnp.maximum((m - 2 * t).astype(jnp.float32), 1.0)
        # zero dropped rows BEFORE multiplying: 0·inf would be NaN
        return (jnp.where(keep > 0, s, 0.0)).sum(0) / cnt

    return jax.tree.map(leaf, deltas)


def krum_select(deltas, participation, byzantine_f: int):
    """The selection half of krum: ``(winner index, participant count)``
    over a [K, ...] delta stack. Split out of :func:`_krum` so the
    fused server-apply path (``server.fused_apply``) can turn the
    winner into a one-hot reduction row for the pallas kernel while
    ``_krum`` itself keeps the identical score/argmin ops (bitwise-
    preserving refactor — the unfused path's float sequence is
    unchanged)."""
    part = participation.astype(jnp.float32)
    k = part.shape[0]
    m = part.sum()
    # pairwise squared distances summed over the whole tree, one [K, K]
    # Gram accumulation per leaf (K is a cohort — tiny)
    d2 = jnp.zeros((k, k), jnp.float32)
    for leaf in jax.tree.leaves(deltas):
        x = leaf.astype(jnp.float32).reshape(k, -1)
        sq = (x * x).sum(-1)
        d2 = d2 + jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * (x @ x.T), 0.0)
    inf = jnp.float32(jnp.inf)
    alive = part > 0
    pair_ok = alive[:, None] & alive[None, :]
    d2 = jnp.where(pair_ok, d2, inf)
    d2 = d2.at[jnp.arange(k), jnp.arange(k)].set(inf)  # exclude self
    s = jnp.sort(d2, axis=1)  # each row: finite neighbours first
    n_nb = jnp.maximum(m - byzantine_f - 2, 1.0)  # dynamic neighbour count
    keep = (jnp.arange(k)[None, :] < n_nb).astype(jnp.float32)
    scores = (jnp.where(keep > 0, s, 0.0)).sum(1)
    # m == 1: the lone participant has no neighbours (score inf) — give
    # it score 0 so argmin still selects a participant
    scores = jnp.where(alive & (m > 1), scores, jnp.where(alive, 0.0, inf))
    return jnp.argmin(scores), m


def _krum(deltas, participation, byzantine_f: int):
    """Krum selection over a [K, ...] delta stack (see robust_reduce)."""
    winner, m = krum_select(deltas, participation, byzantine_f)
    # m == 0 (full dropout): every score is inf and argmin would pick an
    # arbitrary NON-participant — return the zero update instead, like
    # the median/trimmed_mean paths do
    return jax.tree.map(
        lambda d: jnp.where(
            m > 0, jnp.take(d.astype(jnp.float32), winner, axis=0), 0.0
        ),
        deltas,
    )


def make_server_optimizer(cfg: ServerConfig) -> optax.GradientTransformation:
    if cfg.optimizer == "mean":
        return optax.sgd(cfg.server_lr)
    if cfg.optimizer == "fedavgm":
        return optax.sgd(cfg.server_lr, momentum=cfg.server_momentum)
    if cfg.optimizer == "fedadam":
        return optax.adam(cfg.server_lr, eps=1e-3)
    if cfg.optimizer == "fedyogi":
        # Reddi et al. 2021 (Adaptive Federated Optimization) — yogi's
        # additive second-moment update resists the per-round pseudo-
        # gradient variance that makes fedadam's v_t collapse early.
        return optax.yogi(cfg.server_lr, eps=1e-3)
    raise ValueError(f"unknown server optimizer {cfg.optimizer!r}")


def make_server_update_fn(cfg: ServerConfig):
    """(params, opt_state, mean_delta) → (new_params, new_opt_state).

    Feeds ``−Δ̄`` to optax as the gradient, so every optax transform is a
    valid server optimizer. The state carries a monotone round counter
    (``"round"``) alongside the optax state — the round engine reads it
    to compute round-indexed schedules (client LR decay) *inside* the
    compiled program, so schedules need no extra traced inputs.

    Format note: the ``{"round", "opt"}`` wrapper was introduced in
    round 2 of this build — checkpoints written by earlier builds (raw
    optax state) are not restorable against the current template. No
    migration shim is shipped: there are no deployed checkpoints of the
    old format (run artifacts were never part of the repo).

    ``cfg.fused_apply`` swaps the optax chain for the pallas fused
    server-apply kernel (ops/pallas_apply.py): the delta apply and the
    optimizer update run as one VMEM-resident pass over the flat param
    vector instead of a chain of full-params XLA ops. The optax STATE
    STRUCTURE is kept bit-for-bit (``(TraceState, EmptyState)`` /
    ``(EmptyState, EmptyState)``), so fused and unfused runs checkpoint-
    interoperate; only ``mean`` / ``fedavgm`` are expressible as the
    kernel's single FMA chain — fedadam/fedyogi carry second-moment
    state the one-pass kernel does not model (validate() refuses
    them). The returned ``update`` additionally carries
    a ``fused_reduce(params, opt_state, wire_stack, weights)`` attribute
    — the stacked-path entry the engines use to fuse trust/weight
    scaling → weighted reduction → apply → optimizer into the same
    kernel (weights pre-folded with the 1/denominator or krum's one-hot
    winner row). Fused ≡ unfused at f32-reassociation tolerance
    (tests/test_fused_apply.py), never bitwise — the fused FMA order
    differs.
    """
    opt = make_server_optimizer(cfg)
    fused = getattr(cfg, "fused_apply", False)

    def init(params) -> Any:
        return {"round": jnp.zeros((), jnp.int32), "opt": opt.init(params)}

    if not fused:
        def update(params, opt_state, mean_delta) -> Tuple[Any, Any]:
            pseudo_grad = jax.tree.map(jnp.negative, mean_delta)
            updates, new_opt = opt.update(pseudo_grad, opt_state["opt"], params)
            new_state = {"round": opt_state["round"] + 1, "opt": new_opt}
            return optax.apply_updates(params, updates), new_state

        return init, update

    from colearn_federated_learning_tpu.ops.pallas_apply import (
        fused_delta_apply,
        fused_reduce_apply,
    )

    has_mom = cfg.optimizer == "fedavgm"
    beta = cfg.server_momentum if has_mom else 0.0

    def _momentum(opt_state):
        # optax.sgd state: (TraceState(trace), EmptyState()) with
        # momentum, (EmptyState(), EmptyState()) without
        return opt_state["opt"][0].trace if has_mom else None

    def _repack(opt_state, new_mom) -> Any:
        new_opt = opt_state["opt"]
        if has_mom:
            new_opt = (new_opt[0]._replace(trace=new_mom),) + new_opt[1:]
        return {"round": opt_state["round"] + 1, "opt": new_opt}

    def update(params, opt_state, mean_delta) -> Tuple[Any, Any]:
        new_params, new_mom = fused_delta_apply(
            params, _momentum(opt_state), mean_delta,
            cfg.server_lr, beta,
        )
        return new_params, _repack(opt_state, new_mom)

    def fused_reduce(params, opt_state, wire_stack, weights):
        """(params′, opt_state′, mean_delta) from the wire stack in one
        kernel pass; ``weights`` pre-folded (see ops/pallas_apply)."""
        new_params, new_mom, mean_delta = fused_reduce_apply(
            wire_stack, weights, params, _momentum(opt_state),
            cfg.server_lr, beta,
        )
        return new_params, _repack(opt_state, new_mom), mean_delta

    update.fused_reduce = fused_reduce
    return init, update
