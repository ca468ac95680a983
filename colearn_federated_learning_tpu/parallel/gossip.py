"""Decentralized (serverless) federated learning — gossip averaging.

DFedAvg / consensus-SGD (Lian et al. 2017 "Can Decentralized Algorithms
Outperform Centralized?"; Koloskova et al. 2020): there is NO server.
Every client keeps its OWN model replica; each round every client trains
locally from its own replica, then mixes with its graph neighbours
through a doubly-stochastic gossip matrix W:

    xᵢ ← Σⱼ Wᵢⱼ · xⱼ(after local training)

The TPU-native mapping (spec frame: SURVEY.md §2 C6/C8 — the
aggregation/communication rows; the reference mount is empty so the
citation points at the spec): replicas live as ONE ``[N, ...]`` stacked
tree, mesh-sharded over the ``clients`` axis — each lane owns a
contiguous arc of the ring. Ring mixing is then a **halo exchange**:
only each lane's two boundary rows cross the ICI (two ``ppermute``s of
one params-sized message each, independent of N), while the interior
rows mix with an in-lane shift. Per mixing step the cross-chip traffic
is 2·|params| per lane — compare centralized FedAvg's full psum tree —
which is exactly why gossip methods exist: O(degree) neighbour traffic
instead of all-reduce.

Topologies:

- ``ring``: W = Metropolis ring weights ``xᵢ ← (1−2γ)xᵢ + γ(xᵢ₋₁ +
  xᵢ₊₁)`` (doubly stochastic for any γ; contraction for 0 < γ ≤ 1/2;
  γ = 1/3 is the Metropolis choice). Consensus error contracts by the
  spectral gap 1 − λ₂(W), λ₂ = 1 − 2γ(1 − cos 2π/N).
- ``full``: W = (1/N)·11ᵀ — complete averaging each mixing step. One
  mixing step from a consensus start is EXACTLY centralized FedAvg
  with uniform weights (the parity oracle the tests pin).

Mixing preserves the replica mean exactly (W doubly stochastic), so
the consensus mean ``x̄`` — which the round fn also returns, for
evaluation/checkpoint export — follows the averaged-SGD trajectory.

Participation: a client whose ``n_ex`` is 0 (dropout upstream zeroing)
trains zero valid steps — its local phase is an exact no-op — but still
gossips, which is how an idle node in a real decentralized system
behaves (it keeps relaying its current model).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from colearn_federated_learning_tpu.client.trainer import make_local_train_fn
from colearn_federated_learning_tpu.obs.executables import instrument
from colearn_federated_learning_tpu.parallel.mesh import CLIENT_AXIS, has_batch_axis
from jax.sharding import PartitionSpec as P


class GossipMetrics(NamedTuple):
    train_loss: jnp.ndarray
    examples: jnp.ndarray
    # mean over clients of ‖xᵢ − x̄‖² (post-mixing), summed over leaves —
    # THE health metric of a decentralized run (should contract toward
    # the noise floor set by data heterogeneity × lr)
    consensus_dist: jnp.ndarray


def make_gossip_round_fn(model, client_cfg, dp_cfg, task, mesh,
                         num_clients: int, gamma: float = 1.0 / 3.0,
                         mixing_steps: int = 1, topology: str = "ring",
                         donate: bool = True, local_dtype=None,
                         scan_unroll: int = 1, cohort_size: int = 0,
                         attack: str = "", attack_scale: float = 10.0,
                         attack_eps: float = 1.0):
    """Build the jitted one-program gossip round.

    Signature of the returned fn (full participation,
    ``cohort_size`` 0 or == N)::

        (replicas [N, ...] client-sharded, train_x, train_y,
         idx [N,steps,batch], mask [N,steps,batch], n_ex [N], rng)
        → (new_replicas, mean_params, GossipMetrics)

    **Partial participation** (``cohort_size`` = K < N, r5 — what makes
    gossip schedulable beyond toy N): only the K sampled clients train;
    everyone still mixes. The local phase costs O(K·steps) compute
    instead of O(N·steps): the cohort's replica rows are GATHERED from
    the client-sharded stack in-program (take-with-fill + one psum —
    each row owned by exactly one lane, the state-store pattern from
    round_engine.py), trained cohort-sharded, and scattered back
    (all_gather + windowed in-shard write, OOB drops). Signature gains
    trailing ``cohort_ids [K]`` (replicated) and idx/mask/n_ex/keys
    become ``[K, ...]`` cohort-sharded::

        (replicas, train_x, train_y, idx [K,steps,batch],
         mask [K,steps,batch], n_ex [K], rng, cohort_ids [K])
        → (new_replicas, mean_params, GossipMetrics)

    Replica-stack memory stays O(N·|params|/lanes) — partial
    participation cuts compute, not storage; the driver's HBM
    pre-flight guards the stack itself.

    ``num_clients`` must divide evenly over the mesh's client lanes
    (there are no pad rows to hide); so must ``cohort_size``.

    ``attack`` (server/attacks.py): the decentralized threat model — a
    compromised client gossips a POISONED replica to its neighbours.
    The round fn gains a trailing ``byz`` mask input (``[N]`` under
    full participation, ``[K]`` under partial — aligned with ``n_ex``);
    after local training and before mixing, each compromised client's
    local update ``x_trained − x_pre`` is transformed by the shared
    per-client attack operator (``sign_flip``/``gauss``/``scale``;
    ``config.validate`` refuses ``alie`` — it sizes itself from cohort
    statistics a decentralized attacker cannot observe) and its replica
    rewritten to ``x_pre + Δ_attacked``. Honest neighbours then mix the
    poison in.

    No ``lr_scale`` is plumbed into ``local_train`` here:
    ``config.validate`` refuses ``client.lr_decay`` with gossip, as it
    refuses every other pairing (a factory takes a validated config's
    values — ``round_engine.make_sharded_round_fn``).
    """
    if topology not in ("ring", "full"):
        raise ValueError(f"unknown gossip topology {topology!r}")
    if attack:
        from colearn_federated_learning_tpu.server.attacks import (
            UPLOAD_ATTACKS,
        )

        if attack not in UPLOAD_ATTACKS:
            raise ValueError(f"unknown upload attack {attack!r}")
    if not 0.0 < gamma <= 0.5:
        # γ > 1/2 makes the ring weights non-contractive (negative
        # self-weight); γ ≤ 0 is no mixing at all
        raise ValueError(f"gossip gamma must be in (0, 0.5], got {gamma}")
    if mixing_steps < 1:
        raise ValueError(f"mixing_steps must be >= 1, got {mixing_steps}")
    if has_batch_axis(mesh):
        raise ValueError("gossip does not support a batch axis (yet)")
    n_lanes = mesh.shape[CLIENT_AXIS]
    if num_clients % n_lanes != 0:
        raise ValueError(
            f"num_clients {num_clients} not divisible by {n_lanes} lanes "
            f"(every client trains every round — no pad rows)"
        )
    rows = num_clients // n_lanes
    if cohort_size in (0, num_clients):
        cohort_size = 0  # full participation: the classic path
    elif not 0 < cohort_size < num_clients:
        raise ValueError(
            f"gossip cohort_size {cohort_size} must be in (0, "
            f"num_clients={num_clients}]"
        )
    elif cohort_size % n_lanes != 0:
        raise ValueError(
            f"gossip cohort_size {cohort_size} not divisible by "
            f"{n_lanes} lanes"
        )
    k_rows = cohort_size // n_lanes if cohort_size else 0
    local_train = make_local_train_fn(
        model, client_cfg, dp_cfg, task, local_dtype=local_dtype,
        scan_unroll=scan_unroll,
    )
    # the ring is the global client order: lane l owns rows
    # [l·rows, (l+1)·rows); forward neighbour of the lane's last row is
    # the NEXT lane's first row
    fwd = [(i, (i + 1) % n_lanes) for i in range(n_lanes)]
    bwd = [(i, (i - 1) % n_lanes) for i in range(n_lanes)]

    if attack:
        from colearn_federated_learning_tpu.server.attacks import (
            apply_upload_attack,
        )

    def _poison(trained_t, pre_t, byz_b, keys_b):
        """Rewrite the compromised rows' replicas to ``x_pre +
        attack(Δ)`` where ``Δ = x_trained − x_pre`` — the shared
        per-client upload transform applied at the decentralized
        "upload": the replica about to be gossiped. f32 math, cast back
        to the replica storage dtype."""
        delta = jax.tree.map(
            lambda t, p: t.astype(jnp.float32) - p.astype(jnp.float32),
            trained_t, pre_t,
        )
        delta = apply_upload_attack(
            delta, byz_b, keys_b, attack, attack_scale, attack_eps
        )
        return jax.tree.map(
            lambda p, d: (p.astype(jnp.float32) + d).astype(p.dtype),
            pre_t, delta,
        )

    def lane_fn(replicas, train_x, train_y, idx, mask, n_ex, keys, *rest):
        rest = list(rest)
        cohort_ids = rest.pop(0) if cohort_size else None
        byz = rest.pop(0) if attack else None
        # --- local phase ----------------------------------------------
        def per_row(_, inp):
            r_params, r_idx, r_mask, r_key = inp
            w, m = local_train(r_params, train_x, train_y, r_idx, r_mask, r_key)
            # replicas stay at the storage dtype across rounds even when
            # local training runs bf16
            w = jax.tree.map(
                lambda a, p: a.astype(p.dtype), w, r_params
            )
            return 0.0, (w, m.loss)

        if cohort_size:
            # partial participation: gather the cohort's replica rows
            # (each owned by exactly one lane ⇒ the psum superposition
            # is exact), train only those, scatter back
            lane = jax.lax.axis_index(CLIENT_AXIS)
            pos = cohort_ids - lane * rows  # [K]; OOB = not owned
            pos = jnp.where(pos >= 0, pos, rows)
            gathered = jax.tree.map(
                lambda a: jax.lax.psum(
                    jnp.take(a, pos, axis=0, mode="fill", fill_value=0)
                    .astype(jnp.float32),
                    CLIENT_AXIS,
                ),
                replicas,
            )
            chunk = jax.tree.map(
                lambda a, r: jax.lax.dynamic_slice_in_dim(
                    a, lane * k_rows, k_rows, 0
                ).astype(r.dtype),
                gathered, replicas,
            )
            with jax.named_scope("gossip_local_train"):
                _, (trained_chunk, losses) = jax.lax.scan(
                    per_row, 0.0, (chunk, idx, mask, keys)
                )
            if attack:
                # poison the cohort's uploads before the scatter — the
                # byz mask is cohort-aligned ([K], sharded like n_ex)
                trained_chunk = _poison(trained_chunk, chunk, byz, keys)
            trained_full = jax.tree.map(
                lambda t: jax.lax.all_gather(
                    t, CLIENT_AXIS, axis=0, tiled=True
                ),
                trained_chunk,
            )
            trained = jax.tree.map(
                lambda a, nn: a.at[pos].set(nn.astype(a.dtype), mode="drop"),
                replicas, trained_full,
            )
        else:
            # full participation: every row trains from its own params
            with jax.named_scope("gossip_local_train"):
                _, (trained, losses) = jax.lax.scan(
                    per_row, 0.0, (replicas, idx, mask, keys)
                )
            if attack:
                # byz mask is [N], sharded — this lane poisons its rows
                trained = _poison(trained, replicas, byz, keys)

        # --- gossip phase: mixing_steps sweeps of W -------------------
        def sweep_ring(tree):
            # Halo exchange for the whole tree as TWO collectives: the
            # lane's boundary rows (every leaf's first/last row) pack
            # into one flat f32 buffer each, so a sweep is exactly two
            # params-sized ppermute messages — not two per LEAF, which
            # would pay collective-launch latency on dozens of
            # sliver-sized bias/norm leaves.
            leaves, treedef = jax.tree.flatten(tree)
            firsts = jnp.concatenate(
                [l[0].astype(jnp.float32).reshape(-1) for l in leaves]
            )
            lasts = jnp.concatenate(
                [l[-1].astype(jnp.float32).reshape(-1) for l in leaves]
            )
            prev_last = jax.lax.ppermute(lasts, CLIENT_AXIS, fwd)
            next_first = jax.lax.ppermute(firsts, CLIENT_AXIS, bwd)
            out, off = [], 0
            for l in leaves:
                n = 1
                for d in l.shape[1:]:
                    n *= d
                pl = prev_last[off:off + n].reshape(l.shape[1:]).astype(l.dtype)
                nf = next_first[off:off + n].reshape(l.shape[1:]).astype(l.dtype)
                off += n
                up = jnp.concatenate([pl[None], l[:-1]], axis=0)    # xᵢ₋₁
                down = jnp.concatenate([l[1:], nf[None]], axis=0)   # xᵢ₊₁
                out.append(
                    ((1.0 - 2.0 * gamma) * l + gamma * (up + down)).astype(l.dtype)
                )
            return jax.tree.unflatten(treedef, out)

        def sweep_full(tree):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(
                    (jax.lax.psum(a.sum(0), CLIENT_AXIS)
                     / float(num_clients))[None],
                    a.shape,
                ).astype(a.dtype),
                tree,
            )

        sweep = sweep_ring if topology == "ring" else sweep_full
        # named scopes put the gossip sub-phases (local train vs mixing
        # vs consensus) on the device profile — the round is one XLA
        # program, so in-trace attribution is the only attribution
        with jax.named_scope("gossip_mix"):
            mixed = trained
            for _ in range(mixing_steps):
                mixed = sweep(mixed)

        # --- consensus diagnostics + the mean for eval ----------------
        with jax.named_scope("gossip_consensus"):
            mean_params = jax.tree.map(
                lambda a: jax.lax.psum(a.sum(0), CLIENT_AXIS) / float(num_clients),
                mixed,
            )
            dist = sum(
                jax.lax.psum(
                    jnp.sum((a.astype(jnp.float32)
                             - m[None].astype(jnp.float32)) ** 2),
                    CLIENT_AXIS,
                )
                for a, m in zip(jax.tree.leaves(mixed), jax.tree.leaves(mean_params))
            ) / float(num_clients)
        w = n_ex.astype(jnp.float32)
        w_sum = jax.lax.psum(w.sum(), CLIENT_AXIS)
        l_sum = jax.lax.psum((w * losses).sum(), CLIENT_AXIS)
        denom = jnp.where(w_sum > 0, w_sum, 1.0)
        return mixed, mean_params, {
            "loss": l_sum / denom,
            "n": w_sum,
            "consensus": dist,
        }

    in_specs = (P(CLIENT_AXIS), P(), P(), P(CLIENT_AXIS), P(CLIENT_AXIS),
                P(CLIENT_AXIS), P(CLIENT_AXIS))
    if cohort_size:
        in_specs += (P(),)  # cohort ids, replicated
    if attack:
        in_specs += (P(CLIENT_AXIS),)  # byz mask, aligned with n_ex
    sharded_lane = jax.shard_map(
        lane_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(CLIENT_AXIS), P(), {"loss": P(), "n": P(),
                                         "consensus": P()}),
    )

    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def round_fn(replicas, train_x, train_y, idx, mask, n_ex, rng,
                 cohort_ids=None, byz=None):
        for leaf in jax.tree.leaves(replicas):
            if leaf.shape[0] != num_clients:
                raise ValueError(
                    f"replicas leading dim {leaf.shape[0]} != num_clients "
                    f"{num_clients}"
                )
            break
        keys = jax.random.split(rng, idx.shape[0])
        extra = ()
        if cohort_size:
            if cohort_ids is None:
                raise TypeError("partial gossip requires cohort_ids")
            extra = (cohort_ids,)
        if attack:
            if byz is None:
                raise TypeError(f"attack={attack!r} requires the byz mask input")
            extra += (byz,)
        mixed, mean_params, out = sharded_lane(
            replicas, train_x, train_y, idx, mask, n_ex, keys, *extra
        )
        return mixed, mean_params, GossipMetrics(
            out["loss"], out["n"], out["consensus"]
        )

    return instrument("round.gossip", round_fn)
