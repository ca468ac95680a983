"""The TPU-native FL round engine (SURVEY.md §2 C8, §3.1; the north star).

One federated round == ONE compiled XLA program::

    jit(
      shard_map over Mesh(("clients",)):
        lane: lax.scan over its cohort chunk:
                 client local training (lax.scan over steps)
              → Σ nᵢ·Δᵢ, Σ nᵢ, Σ nᵢ·lossᵢ   (per-lane partial sums)
        psum over "clients"                  (the NCCL-allreduce analogue)
      → server optimizer applies Δ̄
    )

What the reference does with a process group + NCCL allreduce
(BASELINE.json:5) is here a single ``jax.lax.psum`` riding the ICI; the
params broadcast disappears entirely because the psum result is already
replicated. Host involvement per round: feeding the int32 index/mask
tensors and one ``device_get`` of scalar metrics.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from colearn_federated_learning_tpu.client.trainer import make_local_train_fn
from colearn_federated_learning_tpu.obs.executables import instrument
from colearn_federated_learning_tpu.parallel.mesh import (
    BATCH_AXIS,
    CLIENT_AXIS,
    has_batch_axis,
)
from colearn_federated_learning_tpu.utils import trees


def _pcast_varying(tree):
    def cast(x):
        if CLIENT_AXIS in getattr(jax.typeof(x), "vma", frozenset()):
            return x  # already device-varying
        return jax.lax.pcast(x, (CLIENT_AXIS,), to="varying")

    return jax.tree.map(cast, tree)


def _on_every_device(server_update, mesh):
    """The fused server update (``server.fused_apply``) as a manual
    region over the whole mesh with every operand replicated: each
    device runs the Pallas kernel on its own replica, exactly as GSPMD
    replicates the optax chain. A Mosaic kernel is a custom call the
    partitioner cannot split — left in the auto-partitioned region of a
    mesh wider than one chip, lowering fails with "Mosaic kernels
    cannot be automatically partitioned". The optax update passes
    through untouched."""
    if not hasattr(server_update, "fused_reduce"):
        return server_update
    replicated = partial(jax.shard_map, mesh=mesh, in_specs=P(),
                         out_specs=P())
    update = replicated(server_update)
    update.fused_reduce = replicated(server_update.fused_reduce)
    return update


class RoundMetrics(NamedTuple):
    train_loss: jnp.ndarray  # cohort example-weighted mean local loss
    examples: jnp.ndarray  # total real examples processed
    # {name: cohort mean, weighted like train_loss} of a model's own
    # counters (client/trainer.LocalMetrics.aux); empty for a model
    # that has none. The plain synchronous path fills it.
    aux: Any = ()


def apply_store_shard_ownership(fed, replica_fallback: bool = True):
    """Multi-host shard ownership for store-backed federations (the
    weak-scaling page-cache rule): mark on the mmap record arrays the
    store shards whose clients land on this process's lanes, so each
    host's gathers fault only its own shards' pages in steady state.

    The lane→client rule mirrors the engines' host-input contract:
    cohort rows shard over the mesh's client axis in contiguous lane
    blocks, and processes own contiguous client-id blocks
    ``[floor(p·C/P), floor((p+1)·C/P))`` — with the store's
    client-contiguous global ids, the owned shard set is then a pure
    function of the shard start offsets (``owned_shard_range``), no
    index scan. Off-block touches (a sampled cohort is never perfectly
    lane-aligned) fall back to READ REPLICAS — correct everywhere,
    counted in ``gather_stats()['replica_rows']``.

    No-op (returns None) on single-process runs and non-store
    federations."""
    if jax.process_count() <= 1:
        return None
    starts = getattr(fed.client_indices, "starts", None)
    if starts is None or not hasattr(fed.train_x, "set_shard_ownership"):
        return None
    p, n = jax.process_index(), jax.process_count()
    c = fed.num_clients
    lo, hi = (p * c) // n, ((p + 1) * c) // n
    ex_lo, ex_hi = int(starts[lo]), int(starts[hi])
    owned = fed.train_x.owned_shard_range(ex_lo, ex_hi)
    for arr in (fed.train_x, fed.train_y):
        arr.set_shard_ownership(owned, replica_fallback=replica_fallback)
    return {
        "process_index": int(p),
        "process_count": int(n),
        "clients": [int(lo), int(hi)],
        "owned_shards": [owned.start, owned.stop],
    }


def _mask_from_spec(spec, steps: int, batch_local: int, local_epochs: int,
                    batch_total: int, batch_offset):
    """Rebuild the ``[C, steps, batch]`` float32 validity mask from the
    ``[C, 2]`` int32 ``(examples_per_epoch, valid_steps)`` spec.

    Padding is contiguous per epoch (data/loader.py packs each epoch's
    real indices first), so a position is valid iff its flat offset
    within its epoch block sits below the client's per-epoch example
    count and its step below the valid-step bound (straggler
    truncation). Produces EXACTLY the 0.0/1.0 float32 values the host
    used to ship — the engines' bitwise contracts are unchanged; only
    the host→device bytes are (a [K, 2] spec instead of the
    [K, steps, batch] slab). Under a batch-sharded mesh each shard
    rebuilds its own columns: ``batch_offset`` is the shard's global
    column origin, so the flat offsets agree with the unsharded mask.
    """
    if steps % local_epochs:
        raise ValueError(
            f"steps={steps} not a multiple of local_epochs={local_epochs}"
        )
    spe = steps // local_epochs
    s = jax.lax.broadcasted_iota(jnp.int32, (steps, batch_local), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (steps, batch_local), 1)
    pos = (s % spe) * batch_total + b + batch_offset
    n_ep = spec[:, 0][:, None, None]
    vsteps = spec[:, 1][:, None, None]
    return ((pos[None] < n_ep) & (s[None] < vsteps)).astype(jnp.float32)


def _decay_scale(decay: float, server_opt_state):
    """lr multiplier decay^round from the server state's round counter."""
    r = server_opt_state["round"].astype(jnp.float32)
    return jnp.power(jnp.float32(decay), r)


def _clip_block(delta_b, clip: float):
    """Clip each client's whole-tree delta to L2 norm ≤ clip.

    ``delta_b`` leaves are ``[width, ...]``; the norm is per CLIENT over
    all leaves jointly (the DP-SGD clipping geometry), shared by both
    engines. Applied BEFORE compression — a real client clips as part of
    its update rule, then compresses the wire format."""
    sq = sum(
        (d.reshape(d.shape[0], -1) ** 2).sum(-1) for d in jax.tree.leaves(delta_b)
    )
    scale = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq), 1e-30))  # [width]
    return jax.tree.map(
        lambda d: d * scale.reshape((d.shape[0],) + (1,) * (d.ndim - 1)), delta_b
    )


def _scaffold_c_update(b_c, c_global, params, w_b, k_valid, lr_i, part):
    """SCAFFOLD option-II control-variate update over a client block.

    ``cᵢ⁺ = cᵢ + (w₀ − w_K)/(Kᵢ·lr) − c`` for participants, ``cᵢ`` for
    non-participants — the participation gate ``part`` folds into the
    per-client scales so the non-participant case is exact. All leaves
    ``[width, ...]``; ``k_valid``/``part`` are ``[width]`` vectors;
    SHARED by the sharded lane and the sequential oracle so the two
    engines stay definitionally identical. Math in f32 regardless of
    the local-training dtype."""
    inv = part / (jnp.maximum(k_valid, 1.0) * lr_i)

    def leaf(ci, cg, w0, wk):
        bshape = (ci.shape[0],) + (1,) * (ci.ndim - 1)
        return (
            ci
            + (w0[None].astype(jnp.float32) - wk.astype(jnp.float32))
            * inv.reshape(bshape)
            - part.reshape(bshape) * cg
        )

    return jax.tree.map(leaf, b_c, c_global, params, w_b)


def _fused_stack_inputs(stacked, n_ex, trust, aggregator: str, agg: str,
                        byzantine_f: int, cohort_size: int):
    """(stack, combined ``[K]`` weights) feeding the fused reduce-apply
    kernel (``server.fused_apply``, ops/pallas_apply.py) — ONE shared
    implementation for the sharded program and the sequential oracle,
    so the fused path's cross-engine parity holds by construction:

    - ``weighted_mean``: the FedAvg weight (examples or participation)
      × reputation trust, divided by the guarded weight sum — exactly
      ``stack_weighted_mean``'s arithmetic, pre-folded so the kernel's
      contraction is the finished mean.
    - ``krum``: trust scales the stack first (the same soft suppression
      as the unfused path), then the winner's one-hot row IS the
      reduction — selection as a degenerate weighted sum. ``m == 0``
      (full dropout) zeroes the row, preserving robust_reduce's
      zero-update semantics.
    """
    if aggregator == "krum":
        from colearn_federated_learning_tpu.server.aggregation import (
            krum_select,
            scale_deltas_by_trust,
        )

        if trust is not None:
            stacked = scale_deltas_by_trust(stacked, trust)
        winner, m = krum_select(stacked, n_ex > 0, byzantine_f)
        w = jax.nn.one_hot(winner, cohort_size, dtype=jnp.float32)
        return stacked, w * (m > 0)
    w = (
        n_ex.astype(jnp.float32) if agg == "examples"
        else (n_ex > 0).astype(jnp.float32)
    )
    if trust is not None:
        w = w * trust.astype(jnp.float32)
    w_sum = w.sum()
    denom = jnp.where(w_sum > 0, w_sum, 1.0)
    return stacked, w / denom


# fold constant deriving the secure-aggregation mask key from the round
# rng — MUST be identical in both engines (mask parity is the parity)
_SECAGG_FOLD = 0x5ECA66
# fold constant for the central client-level DP noise key (DP-FedAvg);
# identical in both engines so parity tests cover the noisy path too
_CLIENT_DP_FOLD = 0xD9FEDA
# fold constant for the downlink broadcast-quantization dither
_DOWNLINK_FOLD = 0xD0147


def _client_dp_noise(dp_key, template, std):
    """Central DP-FedAvg noise tree (McMahan et al. 2018): one Gaussian
    per coordinate with traced std ``z·S/denom``, one threefry stream
    per leaf, cast to the leaf dtype. Added ONCE to the aggregated mean
    delta — never per client. Shared by both engines."""
    leaves, treedef = jax.tree.flatten(template)
    out = []
    for i, leaf in enumerate(leaves):
        n = jax.random.normal(
            jax.random.fold_in(dp_key, i), leaf.shape, jnp.float32
        )
        out.append(leaf + (n * std).astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


def _secagg_quantize(delta_b, b_w, b_part, quant_step: float):
    """Weighted fixed-point quantization of a delta block, shared by
    both mask modes AND pinned behind optimization barriers: the
    weighting multiply and the round(c/step) must lower to the SAME
    instructions whether this runs eagerly (sequential oracle), inside
    a jitted helper, or fused into the sharded round program — an FMA/
    reassociation difference of one ulp at a .5 boundary flips a
    quantization unit and breaks the engines' bitwise-parity contract
    (observed: ring-eager vs pairwise-jit diverged by exactly 1 unit on
    2 of 60k coordinates before the barriers)."""
    part = b_part.astype(jnp.float32)
    contrib = jax.tree.map(
        lambda dd: dd * (part * b_w.astype(jnp.float32)).reshape(
            (dd.shape[0],) + (1,) * (dd.ndim - 1)
        ),
        delta_b,
    )
    contrib = jax.lax.optimization_barrier(contrib)
    # multiply by the PRECOMPUTED f32 reciprocal instead of dividing:
    # XLA canonicalizes division-by-constant to reciprocal multiplication
    # under jit but NOT in eager dispatch, and the two round differently
    # at .5 boundaries (observed: c/1e-4 = 2.5000002 vs c*1e4 = 2.5) —
    # doing the multiply ourselves makes every context emit the same op
    inv_step = jnp.float32(1.0 / quant_step)
    q = jax.tree.map(
        lambda c: jnp.round(c * inv_step).astype(jnp.int32), contrib
    )
    return jax.lax.optimization_barrier(q)


def _secagg_masks(mask_key, slot, template):
    """Uniform int32 mask tree for one client ``slot`` (SecAgg core,
    Bonawitz et al. 2017 §4 arithmetic): one threefry stream per
    (slot, leaf), bitcast so all 32 bits survive (astype would clamp).
    A client's wire mask is ``_secagg_masks(slot) − _secagg_masks(next)``
    over int32 wraparound; summed over the FULL cohort ring every
    stream appears once with + and once with −, so the aggregate
    cancellation is EXACT mod 2^32 — not float-approximate. Shared by
    both engines."""
    leaves, treedef = jax.tree.flatten(template)
    ks = jax.random.fold_in(mask_key, slot)
    out = []
    for i, leaf in enumerate(leaves):
        bits = jax.random.bits(
            jax.random.fold_in(ks, i), leaf.shape, jnp.uint32
        )
        out.append(jax.lax.bitcast_convert_type(bits, jnp.int32))
    return jax.tree.unflatten(treedef, out)


def _secagg_upload(delta_b, b_w, b_slot, b_part, mask_key, params,
                   quant_step: float, cohort_size: int):
    """One block's secure-aggregation contributions, as the sum of the
    protocol's two message kinds (Bonawitz et al. 2017 §5 round shape):

    - **client upload** (survivors, ``part = 1``): the WEIGHTED delta
      quantized to fixed-point int32 (exact for |q| < 2^24) plus the
      ring mask ``m(slot) − m(slot+1 mod K)``. Masks are committed to
      the STATIC full-cohort ring BEFORE training — no participant
      knowledge enters mask construction.
    - **server reconstruction** (dropped, ``part = 0``): the dropped
      client's upload never arrives; the server, learning the dropout
      set only AFTER collecting uploads, reconstructs that client's
      mask term ``m(slot) − m(slot+1)`` from the recovered seed (here:
      the shared mask key — the simulation stand-in for Shamir
      seed-share reconstruction) and adds it so the full ring still
      telescopes to zero. The dropped client's DATA (``q``) never
      enters the aggregate.

    Both terms ride the same int32 accumulator, so cancellation stays
    exact mod 2^32. Shared by both engines."""
    q = _secagg_quantize(delta_b, b_w, b_part, quant_step)
    b_next = (b_slot + 1) % cohort_size
    m_own = jax.vmap(lambda s: _secagg_masks(mask_key, s, params))(b_slot)
    m_nxt = jax.vmap(lambda s: _secagg_masks(mask_key, s, params))(b_next)
    parti = b_part.astype(jnp.int32)

    def merge(qq, a, b):
        pshape = (parti.shape[0],) + (1,) * (a.ndim - 1)
        p = parti.reshape(pshape)
        upload = p * (qq + a - b)  # what a survivor sends
        reconstruction = (1 - p) * (a - b)  # what the server rebuilds
        return upload + reconstruction

    return jax.tree.map(merge, q, m_own, m_nxt)


# base key for expanding a 32-bit pairwise seed into a params-shaped
# mask stream; distinct from every other stream family in the program
_SECAGG_PAIR_FOLD = 0x5ECA67


def _pairwise_prg(seed_u32, template):
    """Expand one pairwise seed into a params-shaped int32 mask tree:
    one threefry stream per (seed, leaf), bitcast so all 32 bits
    survive. BOTH endpoints of a pair (and the server's reconstruction)
    expand the identical stream from the identical seed — that identity
    is the whole cancellation argument."""
    leaves, treedef = jax.tree.flatten(template)
    ks = jax.random.fold_in(
        jax.random.PRNGKey(_SECAGG_PAIR_FOLD), seed_u32
    )
    out = []
    for i, leaf in enumerate(leaves):
        bits = jax.random.bits(
            jax.random.fold_in(ks, i), leaf.shape, jnp.uint32
        )
        out.append(jax.lax.bitcast_convert_type(bits, jnp.int32))
    return jax.tree.unflatten(treedef, out)


def _secagg_pairwise_upload(delta_b, b_w, b_slot, b_part, part_full,
                            seeds, params, quant_step: float,
                            cohort_size: int):
    """One block's pairwise-masked contributions (Bonawitz §4–5 shape;
    ``server.secagg_mode="pairwise"``). Per client i the protocol's two
    message kinds are:

    - **survivor upload**: q_i + Σ_{j>i} PRG(s_ij) − Σ_{j<i} PRG(s_ij)
      — every pair's stream appears once with + and once with −, so the
      full-cohort sum telescopes to zero exactly (mod 2^32).
    - **server reconstruction** (i dropped): the survivors' uploads
      contain the now-uncancelled terms sgn(i−s)·PRG(s_si); the server,
      holding i's Shamir-reconstructed seeds (privacy/secagg_keys.py —
      the driver performs that recovery for real and aborts below
      threshold), adds −Σ_{s surviving} sgn(i−s)·PRG(s_si).

    Both reduce to one signed coefficient per ordered pair —
    ``coeff_ij = sgn(j−i)·[part_i·1(j≠i) + (1−part_i)·part_j]``
    (for i surviving the mask sign; for i dropped, −sgn(i−j)·part_j =
    sgn(j−i)·part_j, the reconstruction sign) — so each pair stream is
    expanded ONCE per client row. Cost: K·(K−1) PRG expansions of
    |params| per round (the real protocol's client-side cost, all paid
    on one chip here) vs the ring mode's 2K; opt-in accordingly.
    """
    q = _secagg_quantize(delta_b, b_w, b_part, quant_step)
    parti_full = part_full.astype(jnp.int32)  # [K]

    def one_client(slot, p_i, q_i):
        row = seeds[slot]  # [K] this client's pairwise seeds
        j_ids = jnp.arange(cohort_size, dtype=jnp.int32)
        sgn = jnp.sign(j_ids - slot).astype(jnp.int32)
        coeff = sgn * (
            p_i * (j_ids != slot).astype(jnp.int32)
            + (1 - p_i) * parti_full
        )  # [K] ∈ {-1, 0, +1}

        def add_pair(acc, sj):
            s_ij, c_ij = sj
            m = _pairwise_prg(s_ij, params)
            return jax.tree.map(lambda a, mm: a + c_ij * mm, acc, m), None

        acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.int32), params)
        if any(
            CLIENT_AXIS in getattr(jax.typeof(x), "vma", frozenset())
            for x in jax.tree.leaves(q_i)
        ):
            # under shard_map the carry becomes device-varying after the
            # first += (coeff depends on this lane's slot); the initial
            # zeros must match (scan-vma typing). No-op for the eager
            # sequential oracle, which has no mesh context.
            acc0 = _pcast_varying(acc0)
        masked, _ = jax.lax.scan(add_pair, acc0, (row, coeff))
        # survivors ship q + mask; dropped ship only the reconstruction
        return jax.tree.map(lambda qq, mm: p_i * qq + mm, q_i, masked)

    parti = b_part.astype(jnp.int32)
    return jax.vmap(one_client)(b_slot, parti, q)


def _feddyn_prepare(client_cfg, scaffold, feddyn_alpha):
    """FedDyn's prox_mu=α injection, SHARED by both engine factories so
    the injected objective can't drift between the engine and its
    parity oracle. The one check reads the factory's own arguments:
    ``scaffold`` and ``feddyn_alpha`` are separate keywords here, where
    a config has one ``algorithm``."""
    feddyn = feddyn_alpha > 0.0
    if not feddyn:
        return False, client_cfg
    if scaffold:
        raise ValueError("scaffold and feddyn are mutually exclusive")
    import dataclasses as _dc

    return True, _dc.replace(client_cfg, prox_mu=feddyn_alpha)


def _feddyn_g_update(b_c, params, w_b, part, alpha: float):
    """FedDyn ``gᵢ⁺ = gᵢ − α·(w_K − w₀)`` over a ``[width, ...]`` block,
    participants only; f32 math. Shared by both engines."""
    return jax.tree.map(
        lambda gi, w0, wk: gi
        - alpha * part.reshape((gi.shape[0],) + (1,) * (gi.ndim - 1))
        * (wk.astype(jnp.float32) - w0[None].astype(jnp.float32)),
        b_c, params, w_b,
    )


def _feddyn_server_step(params, mean_delta, h_new, alpha: float):
    """FedDyn server update ``w ← w₀ + Δ̄ − h⁺/α``; f32 math with the
    final cast back to the params dtype. Shared by both engines."""
    return jax.tree.map(
        lambda p, d, h: (
            p.astype(jnp.float32) + d.astype(jnp.float32) - h / alpha
        ).astype(p.dtype),
        params, mean_delta, h_new,
    )


def make_sharded_round_fn(model, client_cfg, dp_cfg, task, mesh, server_update,
                          cohort_size: int, donate: bool = True,
                          client_vmap_width: int = 1, local_dtype=None,
                          agg: str = "examples", scaffold: bool = False,
                          num_clients: int = 0,
                          aggregator: str = "weighted_mean",
                          trim_ratio: float = 0.1,
                          compression: str = "", topk_ratio: float = 0.01,
                          qsgd_levels: int = 256, topk_exact: bool = False,
                          clip_delta_norm: float = 0.0,
                          feddyn_alpha: float = 0.0,
                          byzantine_f: int = 0,
                          scan_unroll: int = 1,
                          secagg: bool = False,
                          secagg_quant_step: float = 1e-4,
                          secagg_mode: str = "ring",
                          client_dp_noise: float = 0.0,
                          dp_fixed_denom: float = 0.0,
                          downlink: str = "",
                          downlink_levels: int = 256,
                          error_feedback: bool = False,
                          fuse_rounds: int = 1,
                          attack: str = "",
                          attack_scale: float = 10.0,
                          attack_eps: float = 1.0,
                          on_device_mask: bool = False,
                          client_ledger: bool = False,
                          ledger_ema: float = 0.2,
                          ledger_zmax: float = 3.5,
                          reputation: bool = False,
                          rep_floor: float = 0.05,
                          rep_strength: float = 6.0,
                          rep_z_gain: float = 1.0,
                          fused_apply: bool = False,
                          cohort_layout: str = "spatial"):
    """Build the jitted one-program round function.

    A factory takes a validated config's values; what may be combined
    is ``ExperimentConfig.validate()``'s to say. ``Experiment`` calls
    it before it builds an engine, so the factories of this module
    (and ``parallel/gossip.py``, ``client/trainer.py``,
    ``server/aggregation.py``) refuse no pairing of features
    themselves. What they do check is what ``validate()`` cannot see:
    the mesh's shape against the sizes they were given, an object
    handed in (``server_update``), and the range or consistency of
    their own keyword arguments.

    ``cohort_layout`` (``run.cohort_layout``): ``"spatial"`` is the
    classic placement — each lane trains its K/L clients in
    ``client_vmap_width`` blocks, so with width 1 every per-chip GEMM
    is capped at one client's batch. ``"megabatch"`` collapses the
    cohort axis into the GEMM batch: the lane's whole client chunk
    trains as ONE block (``client_vmap_width`` is owned by the layout),
    with the first local step run from the REPLICATED round weights so
    its forward/activation-gradient GEMMs contract the flattened
    ``[K_local·batch, ...]`` megabatch against one un-batched weight,
    and the remaining (diverged-weights) steps scanned as a lane-local
    vmap — one batched GEMM per layer instead of K_local sequential
    launches (client/trainer.py ``megabatch``; a model of windowed
    convolutions runs its first step that way too). Purely a performance
    layout: every wire shape — the ``[K]`` weights/participation, the
    ``[K, 2]`` on-device mask spec, the ``[K, ·]`` upload stack, the
    psum/robust-reduce aggregation contract, ledger stats — is
    unchanged, and megabatch ≡ spatial is parity-pinned across
    aggregators × attacks × EF × fuse_rounds
    (tests/test_round_engine.py). Incompatible with stateful
    algorithms (``config.validate``: their per-client correction trees
    ride the spatial per-block scan) and batch-sharded meshes (the
    flattened rows are the axis the batch mesh splits).

    Signature of the returned fn::

        (params, server_opt_state, train_x, train_y,
         idx [K,steps,batch], mask [K,steps,batch], n_ex [K], rng)
        → (new_params, new_server_opt_state, RoundMetrics)

    ``on_device_mask``: the ``mask`` input is the compact ``[K, 2]``
    int32 ``(examples_per_epoch, valid_steps)`` spec instead of the
    full ``[K, steps, batch]`` float32 slab; each lane rebuilds its
    mask shard in-program via ``broadcasted_iota < n``
    (:func:`_mask_from_spec`) — bit-identical to the shipped mask, at
    ~half the round's host→device wire bytes. The grid's step count is
    read off ``idx``, so one engine serves every ``run.shape_buckets``
    rung (jit caches one executable per realized [K, steps, batch]
    shape — the ladder bounds the retrace budget).

    ``n_ex`` are the per-client example counts; simulated client dropout
    (SURVEY.md §5) is upstream zeroing of entries — exact math, no
    control-flow divergence.

    ``agg`` selects the FedAvg weights: ``"examples"`` (wᵢ = nᵢ, the
    classic example-weighted mean, correct under UNIFORM cohort
    sampling) or ``"uniform"`` (wᵢ = 1 for participants — the unbiased
    pairing for size-proportional ``server.sampling="weighted"``, where
    example-weighting would count shard size twice). Dropped clients
    (nᵢ = 0) carry zero weight in both modes; the ``examples`` metric
    always reports Σnᵢ.

    ``client_vmap_width``: how many of a lane's clients train as one
    ``vmap`` block (effective conv/matmul batch = width × batch_size —
    what keeps the MXU fed when per-client batches are small). 1 = pure
    sequential ``lax.scan`` (minimum memory); 0 = the whole lane in one
    vmap; any other value must exactly divide the lane's client count
    (raises otherwise — never silently rewritten). Peak memory scales
    with width (one activation set per vmapped client), so big-model
    configs keep it low.

    ``scaffold``: SCAFFOLD control variates (Karimireddy et al. 2020,
    option II). The round fn takes three extra trailing inputs —
    ``c_global`` (replicated params-shaped tree), ``c_clients`` (the
    FULL per-client state store: a ``[N_pad, ...]`` stacked tree,
    mesh-sharded over the ``clients`` axis on its leading dim — N_pad
    must be a lane-count multiple; pad rows are never addressed), and
    ``cohort`` (``[K]`` int32 of this round's client ids, replicated) —
    and returns ``(params, opt_state, new_c_global, new_c_clients,
    metrics)``. The cohort rows are gathered INSIDE the round program
    (each lane contributes the rows its state shard owns; one psum
    replicates the cohort's state) and scattered back after the update
    (all_gather of the cohort's new rows + a windowed in-shard write) —
    per-client state is device-resident across rounds with ZERO host
    involvement, and the collectives ride the ICI like the aggregation
    psum. Per-round state traffic: 2·K·|params| (one psum + one
    all_gather), vs the host round-trip of the same bytes over PCIe the
    host-resident design would cost. HBM budget: N_pad·|params| at
    ``state_dtype`` SHARDED over lanes (per-chip share: N_pad/L rows);
    ``state_dtype=bfloat16`` halves it at the cost of rounding the
    PERSISTENT control variates each round (the in-round c math stays
    f32 — upcast at gather, downcast at scatter; the c_global running
    sum tracks the unrounded f32 increments, so c == mean(cᵢ) holds to
    bf16 rounding only). Per step the client gradient gets
    ``+ (c − cᵢ)``; afterwards ``cᵢ⁺ = cᵢ − c + (w₀ − w_K)/(K·lr)``
    (the option-II identity: exactly the client's average applied local
    gradient), and ``c ← c + Σᵢ Δcᵢ / num_clients``. Requires plain
    client SGD (momentum breaks the identity — config.validate enforces
    it); non-participating clients (dropout / empty shards) keep cᵢ and
    contribute zero Δc. All in-round c math is f32 regardless of local
    dtype.

    ``aggregator``: ``"weighted_mean"`` (default — the single-psum
    FedAvg path) or a Byzantine-robust statistic (``"median"`` /
    ``"trimmed_mean"``, server/aggregation.py ``robust_reduce``). Robust
    modes emit the cohort's per-client deltas client-sharded from the
    lane and reduce them with plain jnp ops OUTSIDE the shard_map but
    inside the same jit — GSPMD inserts the cross-lane collectives for
    the coordinate-wise sort, so one XLA program per round still holds.
    Costs K× the aggregation memory/traffic of the psum path (inherent:
    order statistics need all K values).

    ``error_feedback`` activates EF compression memory (the EF-SGD /
    EF21 family, Seide et al. 2014; Stich et al. 2018; Richtárik et al.
    2021) on the SAME device-resident per-client store as scaffold:
    each client keeps a params-shaped residual ``eᵢ``; per round the
    participant uploads ``C(Δᵢ + eᵢ)`` and keeps ``eᵢ⁺ = Δᵢ + eᵢ −
    C(Δᵢ + eᵢ)`` (non-participants keep ``eᵢ``), which turns the BIASED
    top-k operator into an asymptotically-unbiased one — every dropped
    coordinate is retried until it ships. The round fn takes two extra
    trailing inputs (``e_clients`` — the ``[N_pad, ...]`` store,
    mesh-sharded over ``clients`` — and ``cohort``) and returns
    ``(params, opt_state, new_e_clients, metrics)``; gather/scatter
    run in-program exactly like scaffold's (zero host sync,
    multi-host capable). Requires ``compression``; incompatible with
    stateful algorithms (store conflict), robust aggregation, secagg,
    and client-level DP (``config.validate`` refuses each).

    ``feddyn_alpha`` > 0 activates FedDyn (Acar et al. 2021) on the
    SAME stateful plumbing as scaffold (mutually exclusive): the
    per-client state gᵢ enters as the gradient correction ``−gᵢ``, the
    proximal pull ``α(w−w₀)`` is injected via prox_mu, afterwards
    ``gᵢ⁺ = gᵢ − α·(w_K − w₀)`` (participants only), and the server
    applies ``h ← h + ΣΔgᵢ/N;  w ← w₀ + Δ̄ − h/α`` (c_global carries h;
    the server optimizer is bypassed — FedDyn defines its own update —
    but the round counter still advances for LR decay).

    ``attack`` (server/attacks.py): Byzantine adversary simulation. The
    round fn gains an optional trailing ``byz`` input — a ``[K]`` 0/1
    mask of compromised cohort slots, an ARRAY input alongside ``n_ex``
    so the attacked-set can change per round with no retrace. On
    attacked rounds the lane emits the per-client delta stack (the
    robust aggregators' path — order statistics need it anyway, and
    ``alie`` needs cohort statistics), the attack transform applies to
    the stack after clipping/compression and before aggregation —
    exactly where a real attacker controls the upload — and the
    aggregate is the weighted mean over the (poisoned) stack or
    ``robust_reduce`` under a robust ``aggregator``. The transform and
    the stack aggregation are one shared implementation with the
    sequential oracle, so attacked-round parity holds by construction.

    ``client_ledger`` (obs/ledger.py): the round fn takes two extra
    trailing inputs — the ``[num_clients, LEDGER_WIDTH]`` float32
    ledger store (replicated) and the ``[K]`` int32 cohort ids — and
    returns the updated ledger just before the metrics. The per-client
    stats block (upload L2, cosine vs the aggregated delta, clip/EF
    residual, loss, robust-z flag) is computed in-program from the
    cohort's WIRE uploads (post clip/compression/attack) and scattered
    into the ledger with the EF store's OOB-drop discipline; the
    params trajectory is untouched — the weighted-mean path still
    aggregates through its psum, the upload stack only feeds the
    stats. Under ``fuse_rounds > 1`` the ledger rides the scan carry
    and the cohort ids a stacked ``[fuse, K]`` input.

    ``reputation`` (server/aggregation.py ``reputation_weights``;
    requires ``client_ledger``): each round converts the cohort's
    ledger rows — flag-rate, above-threshold z-EMA — into ``[K]``
    multiplicative trust weights IN-PROGRAM, from the ledger as carried
    into the round (this round's stats land after aggregation). On the
    psum path the trust rides a ``[K]`` lane input multiplied into the
    FedAvg weight (numerator and denominator — the loss metric becomes
    the same trust-weighted mean); on the stack paths it reweights
    ``stack_weighted_mean`` or scales the deltas fed to
    ``robust_reduce`` (soft suppression — order statistics stay
    unweighted). Unseen clients carry trust exactly 1, so fresh runs
    start as plain FedAvg. Composes with ``fuse_rounds`` (trust derives
    from the carried ledger per sub-round) and with the attack stack —
    that composition is the point: soft degradation where krum's hard
    rejection breaks near f ≈ K/2.

    ``fused_apply`` (``server.fused_apply``, ops/pallas_apply.py):
    requires a ``server_update`` built by ``make_server_update_fn``
    with the same flag (which already fuses the psum path's delta
    apply + optimizer into one pallas pass). Here it additionally
    routes the STACKED paths — attacked weighted_mean and krum — into
    ``server_update.fused_reduce``: trust/weight scaling, the weighted
    reduction (krum's winner as a one-hot row via
    ``_fused_stack_inputs``), the delta apply, and the optimizer run
    as one VMEM-resident kernel pass, with the delta emitted for the
    client ledger's cosine stat. median/trimmed_mean keep their
    per-coordinate sorts and take the apply-only fusion. Fused ≡
    unfused at f32-reassociation tolerance (tests/test_fused_apply.py).
    """
    if fused_apply and not hasattr(server_update, "fused_reduce"):
        # the stacked-path kernel entry lives on the fused server
        # update (make_server_update_fn with cfg.fused_apply) — a
        # mismatched pairing would silently run the unfused tail
        raise ValueError(
            "fused_apply=True requires a server_update built by "
            "make_server_update_fn with fused_apply enabled"
        )
    server_update = _on_every_device(server_update, mesh)
    if client_dp_noise > 0.0 and agg != "uniform":
        # the fixed-denominator sensitivity analysis needs w_i ∈ {0,1}
        raise ValueError(
            "client-level DP requires uniform aggregation weights "
            "(the driver selects them automatically)"
        )
    feddyn, client_cfg = _feddyn_prepare(client_cfg, scaffold, feddyn_alpha)
    batch_sharded = has_batch_axis(mesh)
    if batch_sharded and client_cfg.batch_size % mesh.shape[BATCH_AXIS]:
        raise ValueError(
            f"batch_size {client_cfg.batch_size} not divisible by "
            f"{mesh.shape[BATCH_AXIS]} batch shards"
        )
    megabatch = cohort_layout == "megabatch"
    if megabatch and batch_sharded:
        # read off the mesh: the flattened [K_local·batch] rows ARE the
        # axis the batch mesh shards
        raise ValueError(
            "cohort_layout='megabatch' is incompatible with a "
            "batch-sharded mesh (run.batch_shards > 1)"
        )
    local_train = make_local_train_fn(
        model, client_cfg, dp_cfg, task,
        batch_axis=BATCH_AXIS if batch_sharded else None,
        local_dtype=local_dtype, scan_unroll=scan_unroll,
        megabatch=megabatch,
    )
    n_lanes = mesh.shape[CLIENT_AXIS]
    if cohort_size % n_lanes != 0:
        raise ValueError(f"cohort {cohort_size} not divisible by lanes {n_lanes}")
    clients_per_lane = cohort_size // n_lanes
    if megabatch:
        # the layout owns the in-lane batching: the whole lane is one
        # block (config.validate rejects an explicit width >= 2)
        width = clients_per_lane
    else:
        width = client_vmap_width if client_vmap_width > 0 else clients_per_lane
        if width > clients_per_lane or clients_per_lane % width != 0:
            raise ValueError(
                f"client_vmap_width {width} must divide the {clients_per_lane} "
                f"clients per lane (cohort {cohort_size} / {n_lanes} lanes); "
                f"use 0 for the full lane"
            )

    if agg not in ("examples", "uniform"):
        raise ValueError(f"unknown aggregation mode {agg!r}")
    stateful = scaffold or feddyn
    # use_store: anything that rides the device-resident [N_pad, ...]
    # per-client store (stateful algorithms carry c_global + the dc psum
    # on top of it; error feedback only the store itself)
    use_store = stateful or error_feedback
    # fuse_rounds > 1 arrives without stateful algorithms and without
    # secure aggregation (config.validate): scaffold/feddyn's c_global
    # recursion has no fused form, and secagg's pairwise seed matrices
    # are per-round host PROTOCOL outputs (DH agreement + Shamir
    # recovery of the realized dropout set) that cannot be precomputed
    # into a stacked scan input. Robust aggregators, upload attacks,
    # and error feedback all fuse: the per-client delta stack stays
    # private to the scan body, byzantine masks become [fuse, K] scan
    # inputs, and the EF store rides the scan carry.
    if use_store and num_clients <= 0:
        raise ValueError("per-client state requires num_clients")
    if aggregator not in ("weighted_mean", "median", "trimmed_mean", "krum"):
        raise ValueError(f"unknown aggregator {aggregator!r}")
    robust = aggregator != "weighted_mean"
    # attacked rounds need the per-client delta stack (the transform —
    # and alie's cohort statistics — act on individual uploads), so the
    # lane emits it exactly as the robust aggregators do
    emit_stack = robust or bool(attack)
    use_decay = client_cfg.lr_decay != 1.0
    from colearn_federated_learning_tpu.ops.compression import (
        downlink_quantize,
        make_compressor,
    )

    compress = make_compressor(compression, topk_ratio, qsgd_levels,
                               topk_exact=topk_exact)

    def _bcast(params, rng):
        """The weights clients actually receive this round."""
        if not downlink:
            return params
        return downlink_quantize(
            params, jax.random.fold_in(rng, _DOWNLINK_FOLD), downlink_levels
        )

    def lane_fn(params, train_x, train_y, idx, mask, n_ex, keys, *rest):
        # idx/mask: [C, steps, batch] — this lane's chunk of the cohort
        # Mark params as device-varying so scan carries (which mix in
        # per-lane data) type-check under shard_map's vma system.
        if on_device_mask:
            # mask arrived as the [C, 2] spec; rebuild this lane's (and,
            # under a batch axis, this shard's) mask columns in-program
            off = (
                jax.lax.axis_index(BATCH_AXIS) * idx.shape[2]
                if batch_sharded else 0
            )
            mask = _mask_from_spec(
                mask, idx.shape[1], idx.shape[2], client_cfg.local_epochs,
                client_cfg.batch_size, off,
            )
        rest = list(rest)
        lr_scale = rest.pop(0) if use_decay else None
        # reputation trust weights: [C] per-lane chunk, computed outside
        # the shard_map from the replicated ledger (same jit program)
        trust_l = rest.pop(0) if reputation else None
        c_global, c_cohort, c_all, state_pos = None, None, None, None
        if use_store:
            # Device-resident per-client state (VERDICT r3 missing-#1):
            # c_all is this lane's shard of the FULL [N_pad, ...] state
            # store (scaffold/feddyn control variates, or the EF
            # compression residuals). Gather the cohort's rows
            # in-program: each lane `take`s the rows its shard owns (OOB
            # positions fill 0), and ONE psum superposes the lanes —
            # every row is owned by exactly one lane, so the sum is
            # exact even in bf16. The lane then slices its own K/L chunk
            # of the replicated cohort state and upcasts to f32 for the
            # state math.
            if stateful:
                c_global = rest.pop(0)
            c_all, cohort_ids = rest.pop(0), rest.pop(0)
            lane = jax.lax.axis_index(CLIENT_AXIS)
            rows = jax.tree.leaves(c_all)[0].shape[0]  # N_pad / lanes
            state_pos = cohort_ids - lane * rows  # [K]; OOB = not owned
            # negative indices WRAP in take/scatter (numpy semantics) —
            # remap rows owned by earlier lanes to an explicit OOB value
            # so fill/drop treat them as not-owned
            state_pos = jnp.where(state_pos >= 0, state_pos, rows)
            gathered = jax.tree.map(
                lambda a: jnp.take(
                    a, state_pos, axis=0, mode="fill", fill_value=0
                ).astype(jnp.float32),
                c_all,
            )
            cohort_rep = jax.tree.map(
                lambda g: jax.lax.psum(g, CLIENT_AXIS), gathered
            )
            c_cohort = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(
                    a, lane * clients_per_lane, clients_per_lane, 0
                ),
                cohort_rep,
            )
        if secagg:
            if secagg_mode == "pairwise":
                # [K, K] replicated pairwise-seed matrix (host-built by
                # privacy/secagg_keys.py: DH agreement; dropped rows are
                # the server's Shamir reconstruction). Masks still
                # commit to the static full cohort before training.
                pair_seeds = rest.pop(0)
                part_full = jax.lax.all_gather(
                    n_ex > 0, CLIENT_AXIS, tiled=True
                )
            else:
                mask_key = rest.pop(0)
            # the mask ring is STATIC over the full cohort (committed
            # before training / before dropouts are known): this lane's
            # global slots are its position in the cohort layout
            lane = jax.lax.axis_index(CLIENT_AXIS)
            slots_l = (
                lane * clients_per_lane
                + jnp.arange(clients_per_lane, dtype=jnp.int32)
            )
        dp_key = rest.pop(0) if client_dp_noise > 0.0 else None
        params = _pcast_varying(params)
        if stateful:
            c_global = _pcast_varying(c_global)

        def _train_block(p, b_idx, b_mask, b_keys, extra):
            """One client block through local training. The megabatch
            layout hands the whole block to the fused block trainer
            (shared-weight step 0 at [C·batch] rows + lane-local vmap);
            the spatial layout vmaps the per-client fn over the block —
            the same per-client step body either way."""
            if megabatch:
                return local_train(
                    p, train_x, train_y, b_idx, b_mask, b_keys, *extra
                )
            return jax.vmap(
                local_train,
                in_axes=(None, None, None, 0, 0, 0) + (None,) * len(extra),
            )(p, train_x, train_y, b_idx, b_mask, b_keys, *extra)

        def per_block(acc, inp):
            b_tr = None
            if reputation:
                # trust rides scan slot 4 (after keys); strip it here so
                # the per-path unpacking below stays untouched
                inp = list(inp)
                b_tr = inp.pop(4)
                inp = tuple(inp)
            b_c = None
            if error_feedback:
                # EF residual rows ride the store slot; training itself
                # is plain (the memory only touches the upload)
                b_idx, b_mask, b_n, b_keys, b_c = inp
                extra = () if lr_scale is None else (lr_scale,)
                w_b, m_b = _train_block(params, b_idx, b_mask, b_keys, extra)
            elif stateful:
                b_idx, b_mask, b_n, b_keys, b_c = inp
                if scaffold:
                    # SCAFFOLD correction (c − cᵢ), constant over the
                    # local phase; f32 leaf broadcast [..] − [width, ..]
                    corr = jax.tree.map(lambda cg, ci: cg - ci, c_global, b_c)
                else:
                    # FedDyn linear term: −gᵢ (the global h only enters
                    # server-side)
                    corr = jax.tree.map(jnp.negative, b_c)
                w_b, m_b = jax.vmap(
                    local_train, in_axes=(None, None, None, 0, 0, 0, None, 0),
                )(params, train_x, train_y, b_idx, b_mask, b_keys, lr_scale, corr)
            else:
                if secagg:  # leading axis: width
                    b_idx, b_mask, b_n, b_keys, b_slot = inp
                else:
                    b_idx, b_mask, b_n, b_keys = inp
                extra = () if lr_scale is None else (lr_scale,)
                w_b, m_b = _train_block(params, b_idx, b_mask, b_keys, extra)
            # FedAvg weight per client: example count, or participation
            # (n>0) under "uniform" — dropout zeroing propagates either way
            b_w = b_n if agg == "examples" else (b_n > 0).astype(b_n.dtype)
            if reputation:
                # reputation folds multiplicatively into the FedAvg
                # weight — numerator AND denominator (a true reweighted
                # mean), and the loss metric weights identically
                b_w = b_w * b_tr.astype(b_w.dtype)
            d_acc, w_acc, n_acc, l_acc, dc_acc, a_acc = acc
            ys = {}
            # per-client deltas in f32 (bf16 local weights upcast here, so
            # client-side mixed precision never degrades the aggregation);
            # the uplink-compression operator applies per client BEFORE any
            # aggregation — exactly where a real client would compress
            delta_b = jax.tree.map(
                lambda w, p: w.astype(jnp.float32) - p[None].astype(jnp.float32),
                w_b, params,
            )
            # client_ledger: the residual stat compares what the client
            # computed against what it ships — raw delta on the plain
            # path, the EF accumulator (delta + memory) under EF
            pre_b = delta_b if client_ledger else None
            if clip_delta_norm > 0.0:
                delta_b = _clip_block(delta_b, clip_delta_norm)
            if error_feedback:
                # EF memory: the wire message is C(Δᵢ + eᵢ); the
                # residual of that SAME quantity becomes the new eᵢ.
                # Non-participants (dropout: Δᵢ = 0, weight 0) keep eᵢ
                # bit-identical — their C(eᵢ) never ships (zero weight
                # in the aggregation contraction below).
                part_b = (b_n > 0).astype(jnp.float32)

                def _bshape(p, d):
                    return p.reshape((d.shape[0],) + (1,) * (d.ndim - 1))

                acc_b = jax.tree.map(
                    lambda d, e: d + e.astype(jnp.float32), delta_b, b_c
                )
                if client_ledger:
                    pre_b = acc_b  # ledger resid = ||e_i^+|| under EF
                comp_b = compress(acc_b, b_keys)
                ys["c"] = jax.tree.map(
                    lambda a, cp, e: jnp.where(
                        _bshape(part_b, a) > 0, a - cp, e.astype(jnp.float32)
                    ),
                    acc_b, comp_b, b_c,
                )
                delta_b = comp_b
            elif compress is not None:
                delta_b = compress(delta_b, b_keys)
            if emit_stack or client_ledger:
                # robust/attacked modes need every client's delta
                # individually — emit the block's deltas instead of
                # accumulating; the ledger emits them ALONGSIDE the
                # psum accumulation (stats only — aggregation unchanged)
                ys["delta"] = delta_b
            if client_ledger:
                from colearn_federated_learning_tpu.obs.ledger import (
                    upload_residual,
                )

                ys["pc_loss"] = m_b.loss
                ys["pc_resid"] = upload_residual(pre_b, delta_b)
            if emit_stack:
                pass  # the stack IS the aggregation input downstream
            elif secagg:
                # survivor uploads + server mask reconstruction for
                # dropped clients (n = 0); the int32 accumulator's
                # wraparound is the protocol's mod-2^32 arithmetic
                if secagg_mode == "pairwise":
                    upload_b = _secagg_pairwise_upload(
                        delta_b, b_w, b_slot, b_n > 0, part_full,
                        pair_seeds, params, secagg_quant_step, cohort_size,
                    )
                else:
                    upload_b = _secagg_upload(
                        delta_b, b_w, b_slot, b_n > 0, mask_key, params,
                        secagg_quant_step, cohort_size,
                    )
                d_acc = jax.tree.map(
                    lambda a, u: a + u.sum(0), d_acc, upload_b
                )
            else:
                # Σ over the block of w_i·(Δ_i), fused as one contraction
                d_acc = jax.tree.map(
                    lambda a, dd: a + jnp.einsum(
                        "c,c...->...", b_w.astype(jnp.float32), dd
                    ).astype(a.dtype),
                    d_acc, delta_b,
                )
            if stateful:
                # Kᵢ = # non-padded steps, counted on the GLOBAL mask so
                # batch shards agree on validity (same rule as the
                # trainer's _global_count — a step whose valid examples
                # all sit on another batch shard is still a real step)
                step_counts = b_mask.sum(-1)  # [width, steps] (this shard)
                if batch_sharded:
                    step_counts = jax.lax.psum(step_counts, BATCH_AXIS)
                k_valid = (step_counts > 0).sum(-1).astype(jnp.float32)
                part = ((b_n > 0) & (k_valid > 0)).astype(jnp.float32)
                if scaffold:
                    lr_i = jnp.float32(client_cfg.lr)
                    if lr_scale is not None:
                        lr_i = lr_i * lr_scale.astype(jnp.float32)
                    new_c_block = _scaffold_c_update(
                        b_c, c_global, params, w_b, k_valid, lr_i, part
                    )
                else:
                    new_c_block = _feddyn_g_update(
                        b_c, params, w_b, part, feddyn_alpha
                    )
                dc_acc = jax.tree.map(
                    lambda a, nc, ci: a + (nc - ci).sum(0), dc_acc, new_c_block, b_c
                )
                ys["c"] = new_c_block
            # a model's own counters, weighted like the loss ({} for a
            # model without any: no leaf, the program is unchanged)
            a_acc = {k: a + (b_w * m_b.aux[k]).sum()
                     for k, a in a_acc.items()}
            return (d_acc, w_acc + b_w.sum(), n_acc + b_n.sum(),
                    l_acc + (b_w * m_b.loss).sum(), dc_acc, a_acc), ys

        n_blocks = idx.shape[0] // width
        scan_in = (idx, mask, n_ex, keys)
        if reputation:
            scan_in += (trust_l,)
        scan_in += (c_cohort,) if use_store else ()
        if secagg:
            scan_in += (slots_l,)
        blocked = jax.tree.map(
            lambda a: a.reshape((n_blocks, width) + a.shape[1:]), scan_in
        )
        # dc accumulates f32 c-variate deltas regardless of params dtype
        # (the "all c math is f32" invariant — and the scan carry must
        # match the f32 per-block increment)
        dc0 = (
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if stateful else jnp.zeros(())
        )
        # robust/attacked modes emit per-client deltas as scan ys instead
        # of the weighted-sum accumulator — collapse that carry slot to a
        # scalar; secagg accumulates the masked fixed-point uploads in int32
        if emit_stack:
            d0 = jnp.zeros(())
        elif secagg:
            d0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.int32), params)
        else:
            d0 = trees.tree_zeros_like(params)
        a0 = {k: jnp.zeros(()) for k in getattr(local_train, "aux_names", ())}
        acc0 = _pcast_varying(
            (d0, jnp.zeros(()), jnp.zeros(()), jnp.zeros(()), dc0, a0),
        )
        (d_sum, w_sum, n_sum, l_sum, dc_sum, a_sum), ys = jax.lax.scan(
            per_block, acc0, blocked
        )
        # The aggregation collective — the reference's NCCL allreduce
        # (BASELINE.json:5) as a single XLA psum over the ICI.
        w_sum = jax.lax.psum(w_sum, CLIENT_AXIS)
        n_sum = jax.lax.psum(n_sum, CLIENT_AXIS)
        l_sum = jax.lax.psum(l_sum, CLIENT_AXIS)
        # weights here are integer example counts or 0/1 participation
        # flags, so w_sum ∈ (0,1) is impossible — the where-form is
        # exactly the max-with-1 floor, written to match the async engine
        denom = jnp.where(w_sum > 0, w_sum, 1.0)
        unblock = lambda t: jax.tree.map(  # noqa: E731  [n_blocks,width,...]→[C,...]
            lambda a: a.reshape((idx.shape[0],) + a.shape[2:]), t
        )
        out = {"n": n_sum, "loss": l_sum / denom}
        if a_sum:
            out["aux"] = {k: jax.lax.psum(a, CLIENT_AXIS) / denom
                          for k, a in a_sum.items()}
        # Under client-level DP the mean's denominator is the FIXED
        # public cohort size, never the realized weight sum — a
        # data-dependent denominator is itself private and would break
        # the sensitivity analysis (dropout then attenuates the
        # estimator instead of leaking through the divisor).
        # under poisson sampling the engine's static row count is the
        # PADDED cap; the DP estimator's fixed public denominator stays
        # the nominal qN = configured cohort_size (dp_fixed_denom)
        agg_denom = (
            jnp.float32(dp_fixed_denom or cohort_size)
            if client_dp_noise > 0.0 else denom
        )
        if emit_stack or client_ledger:
            out["deltas"] = unblock(ys["delta"])  # client-sharded stack
        if client_ledger:
            # per-client loss / residual-magnitude columns of the
            # ledger stats block ([K], client-sharded like the stack)
            out["pc_loss"] = unblock(ys["pc_loss"])
            out["pc_resid"] = unblock(ys["pc_resid"])
        if not emit_stack:
            d_sum = jax.lax.psum(d_sum, CLIENT_AXIS)
            if secagg:
                # the cross-lane psum completed the mod-2^32 ring — masks
                # are gone EXACTLY; dequantize back to the params dtype
                out["mean_delta"] = jax.tree.map(
                    lambda d, p: (
                        d.astype(jnp.float32) * secagg_quant_step / agg_denom
                    ).astype(p.dtype),
                    d_sum, params,
                )
            else:
                out["mean_delta"] = trees.tree_scale(d_sum, 1.0 / agg_denom)
            if dp_key is not None:
                # central DP-FedAvg noise: std = z·S/K with per-client
                # sensitivity S = clip (uniform weights enforced) and
                # fixed K; every lane derives the identical streams, so
                # the replicated aggregate stays replicated
                std = (
                    jnp.float32(client_dp_noise * clip_delta_norm)
                    / agg_denom
                )
                out["mean_delta"] = _client_dp_noise(
                    dp_key, out["mean_delta"], std
                )
        if stateful:
            out["dc_sum"] = jax.lax.psum(dc_sum, CLIENT_AXIS)
        if use_store:
            # scatter the cohort's updated rows back into the sharded
            # state store, in-program: all lanes see the full [K, ...]
            # new state (all_gather in cohort order), then each lane
            # writes the rows its shard owns (OOB positions drop).
            # state_pos is unique per owned row (cohorts sample without
            # replacement), so the windowed write is well-defined.
            new_c_full = jax.tree.map(
                lambda t: jax.lax.all_gather(
                    t, CLIENT_AXIS, axis=0, tiled=True
                ),
                unblock(ys["c"]),
            )
            out["c_all"] = jax.tree.map(
                lambda a, nn: a.at[state_pos].set(
                    nn.astype(a.dtype), mode="drop"
                ),
                c_all, new_c_full,
            )
        return out

    # [K, steps, batch] index/mask tensors additionally shard the batch
    # dim over the batch axis when present; n_ex/keys stay per-client.
    # The compact mask SPEC has no batch dim — cohort over lanes only.
    cohort_spec = (
        P(CLIENT_AXIS, None, BATCH_AXIS) if batch_sharded else P(CLIENT_AXIS)
    )
    mask_in_spec = P(CLIENT_AXIS) if on_device_mask else cohort_spec
    in_specs = (P(), P(), P(), cohort_spec, mask_in_spec, P(CLIENT_AXIS), P(CLIENT_AXIS))
    if use_decay:
        in_specs += (P(),)  # lr_scale scalar, replicated
    if reputation:
        in_specs += (P(CLIENT_AXIS),)  # [K] trust weights, per-client
    if stateful:
        # c_global (replicated), c_clients (state store, sharded on its
        # leading N_pad dim), cohort ids (replicated)
        in_specs += (P(), P(CLIENT_AXIS), P())
    elif error_feedback:
        # e_clients store (sharded) + cohort ids; no global state
        in_specs += (P(CLIENT_AXIS), P())
    if secagg:
        in_specs += (P(),)  # replicated mask key; the ring is static
    if client_dp_noise > 0.0:
        in_specs += (P(),)  # central DP noise key, replicated
    out_specs = {"n": P(), "loss": P()}
    if getattr(local_train, "aux_names", ()):
        out_specs["aux"] = P()
    if emit_stack or client_ledger:
        out_specs["deltas"] = P(CLIENT_AXIS)
    if client_ledger:
        out_specs["pc_loss"] = P(CLIENT_AXIS)
        out_specs["pc_resid"] = P(CLIENT_AXIS)
    if not emit_stack:
        out_specs["mean_delta"] = P()
    if stateful:
        out_specs["dc_sum"] = P()
    if use_store:
        out_specs["c_all"] = P(CLIENT_AXIS)
    sharded_lane = jax.shard_map(
        lane_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )

    def _wire_stack(out, n_ex, byz, keys):
        """The cohort's [K, ...] WIRE uploads: the lane-emitted stack
        with the attack transform applied (plain jnp under the same
        jit — GSPMD handles the client-sharded axis), after clipping/
        compression and before aggregation: the upload boundary. Feeds
        the robust/attacked aggregation AND the client-ledger stats."""
        deltas = out["deltas"]
        if attack:
            from colearn_federated_learning_tpu.server.attacks import (
                apply_upload_attack,
            )

            # the scope name is what a device profile is read by: the
            # benchmark's trace reduction (benchmark/run.py SCOPES)
            # finds the attack's device time under it
            with jax.named_scope("round_attack_transform"):
                deltas = apply_upload_attack(
                    deltas, byz, keys, attack, attack_scale, attack_eps,
                    participation=n_ex > 0,
                )
        return deltas

    def _mean_delta(out, n_ex, params=None, wire=None, trust=None):
        if emit_stack:
            if robust:
                from colearn_federated_learning_tpu.server.aggregation import (
                    robust_reduce,
                    scale_deltas_by_trust,
                )

                if trust is not None:
                    # reputation under a robust aggregator: scale each
                    # upload by its trust (soft suppression) — order
                    # statistics themselves stay unweighted by design
                    wire = scale_deltas_by_trust(wire, trust)
                # the coordinate-wise sort runs as plain jnp under jit —
                # GSPMD handles the lanes
                return robust_reduce(wire, n_ex > 0, aggregator,
                                     trim_ratio, byzantine_f)
            from colearn_federated_learning_tpu.server.attacks import (
                stack_weighted_mean,
            )

            # weighted_mean over the (attacked) stack — the stacked-path
            # twin of the in-lane psum accumulation, shared with the
            # sequential oracle; trust reweights it multiplicatively
            return stack_weighted_mean(wire, n_ex, agg, params, trust)
        return out["mean_delta"]

    def _trust_weights(ledger, cohort):
        """[K] reputation trust from the ledger AS CARRIED INTO the
        round (the round's own stats scatter lands after aggregation).
        Plain jnp under the round jit — host-free, fuses into the scan
        body under fuse_rounds."""
        from colearn_federated_learning_tpu.server.aggregation import (
            reputation_weights,
        )

        return reputation_weights(
            ledger, cohort.astype(jnp.int32), rep_floor, rep_strength,
            rep_z_gain, ledger_zmax,
        )

    def _ledger_update(out, wire, mean_delta, n_ex, ledger, cohort):
        """In-program ledger step: the shared stats block over the wire
        uploads, scattered into the device-resident store (obs/ledger).
        Runs under the round jit — zero extra host round-trips."""
        from colearn_federated_learning_tpu.obs.ledger import (
            client_round_stats,
            update_ledger,
        )

        with jax.named_scope("round_client_ledger"):
            stats = client_round_stats(
                wire, mean_delta, out["pc_loss"], out["pc_resid"], n_ex,
                ledger_zmax,
            )
            return update_ledger(
                ledger, cohort.astype(jnp.int32), n_ex, stats, ledger_ema
            )

    if stateful:

        @partial(jax.jit, donate_argnums=(0, 1, 8, 9) if donate else ())
        def round_fn(params, server_opt_state, train_x, train_y, idx, mask,
                     n_ex, rng, c_global, c_clients, cohort):
            n_lanes_ = mesh.shape[CLIENT_AXIS]
            for leaf in jax.tree.leaves(c_clients):
                if leaf.shape[0] % n_lanes_:
                    raise ValueError(
                        f"c_clients leading dim {leaf.shape[0]} must be a "
                        f"multiple of {n_lanes_} lanes (pad the state "
                        f"store; pad rows are never addressed)"
                    )
                break
            keys = jax.random.split(rng, idx.shape[0])
            extra = ()
            if use_decay:
                extra = (_decay_scale(client_cfg.lr_decay, server_opt_state),)
            with jax.named_scope("round_local_train"):
                out = sharded_lane(
                    params, train_x, train_y, idx, mask, n_ex, keys,
                    *extra, c_global, c_clients, cohort.astype(jnp.int32),
                )
            # both algorithms accumulate their global state the same way:
            # scaffold  c ← c + ΣΔcᵢ/N   (paper's |S|/N · mean over S)
            # feddyn    h ← h + ΣΔgᵢ/N   (= h − α·(1/N)Σ(wᵢ−w₀))
            with jax.named_scope("round_aggregate"):
                new_c_global = jax.tree.map(
                    lambda c, dc: c + dc / float(num_clients), c_global, out["dc_sum"]
                )
                mean_delta = _mean_delta(out, n_ex)
            with jax.named_scope("round_server_apply"):
                if feddyn:
                    # FedDyn server step; the configured server optimizer
                    # is bypassed (the paper defines the update), only
                    # the round counter advances
                    new_params = _feddyn_server_step(
                        params, mean_delta, new_c_global, feddyn_alpha
                    )
                    new_opt_state = dict(
                        server_opt_state, round=server_opt_state["round"] + 1
                    )
                else:
                    new_params, new_opt_state = server_update(
                        params, server_opt_state, mean_delta
                    )
            return (new_params, new_opt_state, new_c_global, out["c_all"],
                    RoundMetrics(out["loss"], out["n"]))

        return instrument("round.stateful", round_fn)

    if error_feedback:

        def _ef_check(e_clients):
            n_lanes_ = mesh.shape[CLIENT_AXIS]
            for leaf in jax.tree.leaves(e_clients):
                if leaf.shape[0] % n_lanes_:
                    raise ValueError(
                        f"e_clients leading dim {leaf.shape[0]} must be a "
                        f"multiple of {n_lanes_} lanes (pad the state "
                        f"store; pad rows are never addressed)"
                    )
                break

        def _ef_one_round(params, server_opt_state, train_x, train_y, idx,
                          mask, n_ex, rng, e_clients, cohort, ledger=None):
            if client_ledger and ledger is None:
                raise TypeError("client_ledger requires the ledger input")
            keys = jax.random.split(rng, idx.shape[0])
            extra = ()
            if use_decay:
                extra = (_decay_scale(client_cfg.lr_decay, server_opt_state),)
            if reputation:
                # EF aggregates through the psum path — trust enters as
                # the [K] lane input multiplied into the FedAvg weight
                extra = extra + (_trust_weights(ledger, cohort),)
            with jax.named_scope("round_local_train"):
                out = sharded_lane(
                    _bcast(params, rng), train_x, train_y, idx, mask, n_ex,
                    keys, *extra, e_clients, cohort.astype(jnp.int32),
                )
            new_ledger = None
            if client_ledger:
                # EF aggregates through the psum path; the stats block
                # reads the emitted C(delta+e) upload stack
                new_ledger = _ledger_update(
                    out, out["deltas"], out["mean_delta"], n_ex, ledger,
                    cohort,
                )
            with jax.named_scope("round_server_apply"):
                new_params, new_opt_state = server_update(
                    params, server_opt_state, out["mean_delta"]
                )
            metrics = RoundMetrics(out["loss"], out["n"])
            if client_ledger:
                return (new_params, new_opt_state, out["c_all"],
                        new_ledger, metrics)
            return new_params, new_opt_state, out["c_all"], metrics

        if fuse_rounds > 1:
            # fused EF: the device-resident [N_pad, ...] residual store
            # is a DONATED scan carry — the in-program scatter updates
            # it each fused sub-round with zero host involvement, and
            # the store buffer is reused across the whole chunk. The
            # client ledger (when on) rides the same carry.
            _ef_donate = (0, 1, 8) + ((10,) if client_ledger else ())

            @partial(jax.jit, donate_argnums=_ef_donate if donate else ())
            def round_fn(params, server_opt_state, train_x, train_y, idx_f,
                         mask_f, n_ex_f, rngs, e_clients, cohorts,
                         ledger=None):
                _ef_check(e_clients)
                if client_ledger and ledger is None:
                    raise TypeError("client_ledger requires the ledger input")

                def body(carry, inp):
                    p, o, e, led = carry
                    i, m, n, r, coh = inp
                    res = _ef_one_round(
                        p, o, train_x, train_y, i, m, n, r, e, coh, led
                    )
                    if client_ledger:
                        p, o, e, led, met = res
                    else:
                        p, o, e, met = res
                    return (p, o, e, led), met

                (p, o, e, led), ms = jax.lax.scan(
                    body, (params, server_opt_state, e_clients, ledger),
                    (idx_f, mask_f, n_ex_f, rngs, cohorts),
                )
                if client_ledger:
                    return p, o, e, led, ms
                return p, o, e, ms  # RoundMetrics with [F]-stacked fields

            return instrument("round.ef_fused", round_fn,
                              rounds_per_call=fuse_rounds)

        _ef_donate1 = (0, 1, 8) + ((10,) if client_ledger else ())

        @partial(jax.jit, donate_argnums=_ef_donate1 if donate else ())
        def round_fn(params, server_opt_state, train_x, train_y, idx, mask,
                     n_ex, rng, e_clients, cohort, ledger=None):
            _ef_check(e_clients)
            return _ef_one_round(params, server_opt_state, train_x, train_y,
                                 idx, mask, n_ex, rng, e_clients, cohort,
                                 ledger)

        return instrument("round.ef", round_fn)

    if secagg:

        @partial(jax.jit, donate_argnums=(0, 1) if donate else ())
        def round_fn(params, server_opt_state, train_x, train_y, idx, mask,
                     n_ex, rng, pair_seeds=None):
            keys = jax.random.split(rng, idx.shape[0])
            if secagg_mode == "pairwise":
                # pairwise mode: the seed matrix is a host-built INPUT
                # (key agreement + Shamir recovery are host protocol
                # steps), not derivable from the round rng
                if pair_seeds is None:
                    raise TypeError("secagg_mode='pairwise' requires pair_seeds")
                secagg_in = pair_seeds
            else:
                # ring mode: the mask key is a pure function of the
                # round rng — every lane (and the sequential oracle)
                # derives the same streams
                secagg_in = jax.random.fold_in(rng, _SECAGG_FOLD)
            extra = ()
            if use_decay:
                extra = (_decay_scale(client_cfg.lr_decay, server_opt_state),)
            tail = (
                (jax.random.fold_in(rng, _CLIENT_DP_FOLD),)
                if client_dp_noise > 0.0 else ()
            )
            with jax.named_scope("round_local_train"):
                out = sharded_lane(
                    _bcast(params, rng), train_x, train_y, idx, mask, n_ex,
                    keys, *extra, secagg_in, *tail,
                )
            with jax.named_scope("round_server_apply"):
                new_params, new_opt_state = server_update(
                    params, server_opt_state, out["mean_delta"]
                )
            return new_params, new_opt_state, RoundMetrics(out["loss"], out["n"])

        return instrument("round.secagg", round_fn)

    def _one_round(params, server_opt_state, train_x, train_y, idx, mask,
                   n_ex, rng, byz=None, ledger=None, cohort=None):
        if attack and byz is None:
            raise TypeError(f"attack={attack!r} requires the byz mask input")
        if client_ledger and (ledger is None or cohort is None):
            raise TypeError(
                "client_ledger requires the ledger and cohort inputs"
            )
        keys = jax.random.split(rng, idx.shape[0])
        extra = ()
        if use_decay:
            # round-indexed client LR decay, derived inside the program
            # from the server state's round counter (aggregation.py)
            extra = (_decay_scale(client_cfg.lr_decay, server_opt_state),)
        trust = None
        if reputation:
            trust = _trust_weights(ledger, cohort)
            extra = extra + (trust,)
        tail = (
            (jax.random.fold_in(rng, _CLIENT_DP_FOLD),)
            if client_dp_noise > 0.0 else ()
        )
        # named scopes carry the round's in-program phases into device
        # profiles (jax.profiler / bench traces) — the only attribution
        # possible for phases fused inside ONE XLA program
        with jax.named_scope("round_local_train"):
            out = sharded_lane(
                _bcast(params, rng), train_x, train_y, idx, mask, n_ex, keys,
                *extra, *tail,
            )
        wire = None
        if emit_stack or client_ledger:
            wire = _wire_stack(out, n_ex, byz, keys)
        if fused_apply and emit_stack and aggregator in (
            "weighted_mean", "krum",
        ):
            # the fused server chain (server.fused_apply): trust/weight
            # scaling → weighted reduction → delta apply → optimizer as
            # ONE pallas pass over the flat param vector. The stack
            # enters the kernel's manual region replicated
            # (_on_every_device); the robust/attacked paths materialize
            # the full stack for their cross-lane statistics anyway.
            with jax.named_scope("round_fused_reduce_apply"):
                stack_in, w_in = _fused_stack_inputs(
                    wire, n_ex, trust, aggregator, agg, byzantine_f,
                    cohort_size,
                )
                new_params, new_opt_state, delta = server_update.fused_reduce(
                    params, server_opt_state, stack_in, w_in
                )
        else:
            with jax.named_scope("round_aggregate"):
                delta = _mean_delta(out, n_ex, params, wire, trust)
            with jax.named_scope("round_server_apply"):
                new_params, new_opt_state = server_update(
                    params, server_opt_state, delta
                )
        new_ledger = None
        if client_ledger:
            new_ledger = _ledger_update(out, wire, delta, n_ex, ledger,
                                        cohort)
        metrics = RoundMetrics(out["loss"], out["n"], out.get("aux", ()))
        if client_ledger:
            return new_params, new_opt_state, new_ledger, metrics
        return new_params, new_opt_state, metrics

    if fuse_rounds > 1:
        # Multi-round fusion (r5, VERDICT r4 weak-#2; generalized r6):
        # F rounds as ONE XLA program — a lax.scan over the per-round
        # body with stacked [F, ...] index tensors and the SAME
        # per-round rngs the unfused loop derives, so fused ≡ unfused
        # bitwise (test-pinned) while the per-round dispatch cost (the
        # dominant cost of the tiny-model configs) is paid once per F.
        # Robust aggregators and upload attacks fuse
        # too: _one_round's per-client delta stack (and the attack
        # transform / coordinate-wise sort over it) stays PRIVATE to
        # the scan body — only the [F]-stacked scalar metrics leave the
        # program — and the per-round byzantine masks ride a stacked
        # [F, K] scan input alongside n_ex_f.

        _fuse_donate = (0, 1) + ((9,) if client_ledger else ())

        @partial(jax.jit, donate_argnums=_fuse_donate if donate else ())
        def round_fn(params, server_opt_state, train_x, train_y, idx_f,
                     mask_f, n_ex_f, rngs, byz_f=None, ledger=None,
                     cohorts_f=None):
            if attack and byz_f is None:
                raise TypeError(
                    f"attack={attack!r} requires the stacked [fuse, K] "
                    f"byz mask input"
                )
            if client_ledger and (ledger is None or cohorts_f is None):
                raise TypeError(
                    "client_ledger requires the ledger input and the "
                    "stacked [fuse, K] cohort ids"
                )

            def body(carry, inp):
                p, o, led = carry
                rest = list(inp)
                i, m, n, r = rest[:4]
                rest = rest[4:]
                bz = rest.pop(0) if attack else None
                coh = rest.pop(0) if client_ledger else None
                res = _one_round(p, o, train_x, train_y, i, m, n, r,
                                 bz, led, coh)
                if client_ledger:
                    p, o, led, met = res
                else:
                    p, o, met = res
                return (p, o, led), met

            xs = (idx_f, mask_f, n_ex_f, rngs)
            if attack:
                xs += (byz_f,)
            if client_ledger:
                # the ledger rides the scan CARRY (donated — the store
                # buffer is reused across the chunk, like the EF store);
                # per-sub-round cohort ids ride a stacked scan input
                xs += (cohorts_f,)
            (p, o, led), ms = jax.lax.scan(
                body, (params, server_opt_state, ledger), xs
            )
            if client_ledger:
                return p, o, led, ms
            return p, o, ms  # RoundMetrics with [F]-stacked fields

        return instrument("round.fused", round_fn,
                          rounds_per_call=fuse_rounds)

    # keep the compiled program's name "jit_round_fn": profiling tools
    # (bench._parse_device_ms) identify the round program by it
    _one_round.__name__ = "round_fn"
    # the ledger input (arg 9, passed positionally by the driver) is
    # donated like the state stores — the round updates it in place
    _donate = (0, 1) + ((9,) if client_ledger else ())
    round_fn = partial(jax.jit, donate_argnums=_donate if donate else ())(
        _one_round
    )
    return instrument("round.sync", round_fn)


def make_device_round_fn(round_fn, schedule_fn, fuse, *, client_ledger=False,
                         data_sharding=None, cohort_sharding=None,
                         client_sharding=None, fused_cohort_sharding=None,
                         fused_client_sharding=None, donate=True):
    """Wrap a (donate-free) sharded engine with the device-resident
    control plane (``run.control_plane="device"``, server/device_plane):
    the [K] cohort ids, [K, steps, batch] index slab, [K, 2] spec,
    weights, and churn realization all derive IN-PROGRAM from
    ``schedule_fn(arrays, round_idx)`` — the host ships only the static
    plan tables (once) and a round index per dispatch.

    ``round_fn`` must be built with ``donate=False``: donation moves to
    this outer jit (params/opt, plus the ledger when present), since the
    inner engine's buffers are now program-internal values.

    Under ``fuse > 1`` the schedule derivation is vmapped over the
    chunk's round vector and feeds the engine's fused lax.scan directly
    — each sub-round's cohort and gates materialize inside the scan
    body's program, so host I/O collapses to flush boundaries.

    Returns ``(params, opt[, ledger], metrics, sched)`` where ``sched``
    is the realized schedule WITHOUT the index slab (cohort / spec /
    n_ex / churn-stat scalars; [F]-stacked under fuse) — fetched at
    flush so telemetry, digests, and parity pins see exactly what the
    program executed."""
    _sched_out = ("cohort", "spec", "n_ex",
                  "unavailable", "dropped", "crashed")

    def _constrain(x, sharding):
        if sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, sharding)

    def _rng_rows(rng_key, rounds):
        # the same per-round keys the host loop derives: fold_in per
        # round, normalized to raw uint32 rows iff the key is typed
        # (a restored checkpoint's rng_key comes back typed) — the
        # fused scan body consumes raw key data, identical bits
        rngs = jax.vmap(lambda r: jax.random.fold_in(rng_key, r))(rounds)
        if jax.dtypes.issubdtype(rngs.dtype, jax.dtypes.prng_key):
            rngs = jax.random.key_data(rngs)
        return rngs

    _dev_donate = (0, 1) + ((7,) if client_ledger else ())

    if fuse > 1:

        @partial(jax.jit, donate_argnums=_dev_donate if donate else ())
        def device_round_fn(params, server_opt_state, train_x, train_y,
                            arrays, round0, rng_key, ledger=None):
            rounds = round0.astype(jnp.int32) + jnp.arange(
                fuse, dtype=jnp.int32
            )
            with jax.named_scope("round_control_plane"):
                sched = jax.vmap(lambda r: schedule_fn(arrays, r))(rounds)
            idx_f = _constrain(sched["idx"], fused_cohort_sharding)
            spec_f = _constrain(sched["spec"], fused_client_sharding)
            n_ex_f = _constrain(sched["n_ex"], fused_client_sharding)
            rngs = _rng_rows(rng_key, rounds)
            tail = ()
            if client_ledger:
                tail = (ledger, _constrain(sched["cohort"], data_sharding))
            out = round_fn(params, server_opt_state, train_x, train_y,
                           idx_f, spec_f, n_ex_f, rngs, None, *tail)
            return out + ({k: sched[k] for k in _sched_out},)

        return instrument("round.device_fused", device_round_fn,
                          rounds_per_call=fuse)

    @partial(jax.jit, donate_argnums=_dev_donate if donate else ())
    def device_round_fn(params, server_opt_state, train_x, train_y,
                        arrays, round_idx, rng_key, ledger=None):
        with jax.named_scope("round_control_plane"):
            sched = schedule_fn(arrays, round_idx.astype(jnp.int32))
        idx = _constrain(sched["idx"], cohort_sharding)
        spec = _constrain(sched["spec"], client_sharding)
        n_ex = _constrain(sched["n_ex"], client_sharding)
        rng = jax.random.fold_in(rng_key, round_idx)
        tail = ()
        if client_ledger:
            # in-program ledger slot assignment: the dense store's slot
            # ids ARE the cohort ids (validate rejects the paged hot
            # set under device mode), so the _ledger_slot_ids host
            # remap vanishes from the hot path
            tail = (ledger, _constrain(sched["cohort"], data_sharding))
        out = round_fn(params, server_opt_state, train_x, train_y,
                       idx, spec, n_ex, rng, None, *tail)
        return out + ({k: sched[k] for k in _sched_out},)

    return instrument("round.device", device_round_fn)


def make_async_round_fn(model, client_cfg, dp_cfg, task, mesh, server_update,
                        buffer_size: int, window: int, donate: bool = True,
                        client_vmap_width: int = 1, local_dtype=None,
                        clip_delta_norm: float = 0.0, scan_unroll: int = 1,
                        client_ledger: bool = False,
                        ledger_ema: float = 0.2,
                        ledger_zmax: float = 3.5,
                        reputation: bool = False,
                        rep_floor: float = 0.05,
                        rep_strength: float = 6.0,
                        rep_z_gain: float = 1.0):
    """Asynchronous buffered FL (FedBuff, Nguyen et al. 2022) — one
    server step as one XLA program.

    Clients train against STALE parameter versions: ``history`` is a
    ``[window, ...]`` ring of past global params (replicated), each of
    the ``buffer_size`` completing clients gathers its own start version
    by slot index, trains, and contributes ``delta vs ITS start params``
    weighted by the host-computed staleness decay. The server applies
    the weighted mean to the CURRENT params and writes the new version
    into the ring.

    Signature of the returned fn::

        (history, server_opt_state, train_x, train_y,
         idx [K,steps,batch], mask [K,steps,batch], agg_w [K], n_ex [K],
         slots [K] int32, cur_slot int32, next_slot int32, rng)
        → (new_history, new_params, new_opt_state, RoundMetrics)

    ``agg_w`` are the full aggregation weights (base weight × (1+s)^-α,
    dropped clients 0) — staleness lives host-side in the scheduler
    (server/round_driver.py), the program just consumes weights.
    The batch axis / scaffold / robust / compression features of the
    sync engine are deliberately not composed here (config.validate
    rejects them with algorithm=fedbuff).

    ``client_ledger`` (the churn PR — fedbuff promoted onto the
    million-client plane): per-INSERT forensic stats. The lane emits
    the popped buffer's per-client delta stack instead of accumulating
    it in-scan (the sync engine's ``emit_stack`` memory shape — the
    ring, not the stack, is fedbuff's marginal HBM cost), the round fn
    gains trailing ``cohort`` [K] int32 + ``ledger`` inputs, computes
    the SAME shared stats block (obs/ledger.py ``client_round_stats``
    over the wire uploads vs the staleness-weighted aggregate) and
    scatters it by true client id, returning the updated ledger before
    the metrics::

        (..., slots, cohort, ledger, cur_slot, next_slot, rng)
        → (new_history, new_params, new_opt_state, new_ledger, metrics)

    ``reputation`` (requires ``client_ledger``) gates the
    staleness-aware reputation-weighted merge: the [K] trust weights
    derive in-program from the ledger AS CARRIED IN (this step's stats
    land after aggregation) and fold multiplicatively into the
    host-computed staleness decay — the admitted weight is
    ``base·(1+s)^-α·trust``, numerator and denominator. With both
    flags off the program is bit-identical to the pre-churn engine.

    One async-specific wrinkle the sync ledger never sees: the popped
    buffer CAN contain the same client twice (independent in-flight
    arrivals), and ``update_ledger``'s ``.at[].set`` scatter collapses
    duplicate in-range rows to one insert (last-writer-wins). The
    ledger's participation count therefore undercounts absorbed
    updates by at most the within-step duplicate rate — bounded, and
    irrelevant to aggregation (both duplicates' deltas still merge).
    """
    local_train = make_local_train_fn(
        model, client_cfg, dp_cfg, task, local_dtype=local_dtype,
        scan_unroll=scan_unroll,
    )
    server_update = _on_every_device(server_update, mesh)
    n_lanes = mesh.shape[CLIENT_AXIS]
    if buffer_size % n_lanes != 0:
        raise ValueError(
            f"buffer {buffer_size} not divisible by lanes {n_lanes}"
        )
    clients_per_lane = buffer_size // n_lanes
    width = client_vmap_width if client_vmap_width > 0 else clients_per_lane
    if width > clients_per_lane or clients_per_lane % width != 0:
        raise ValueError(
            f"client_vmap_width {width} must divide the {clients_per_lane} "
            f"clients per lane"
        )
    use_decay = client_cfg.lr_decay != 1.0
    if client_ledger:
        # Per-insert stats path: the lane emits the buffer's [K, ...]
        # per-client delta stack (client-sharded) instead of the
        # in-scan weighted accumulation — same memory shape as the
        # sync engine's emit_stack modes; aggregation, stats, and the
        # ledger scatter run as plain jnp under the round jit (GSPMD
        # handles the client-sharded axis), mirroring the sync path.
        def lane_stack_fn(history, train_x, train_y, idx, mask, slots,
                          keys, *rest):
            lr_scale = rest[0] if use_decay else None
            history = _pcast_varying(history)

            def train_one(slot, b_idx, b_mask, key):
                start = jax.tree.map(
                    lambda h: jnp.take(h, slot, axis=0), history
                )
                extra = () if lr_scale is None else (lr_scale,)
                w, m = local_train(start, train_x, train_y, b_idx, b_mask,
                                   key, *extra)
                delta = jax.tree.map(
                    lambda wi, p: (wi.astype(jnp.float32)
                                   - p.astype(jnp.float32)),
                    w, start,
                )
                return delta, m

            def per_block(_, inp):
                b_idx, b_mask, b_slot, b_keys = inp
                delta_b, m_b = jax.vmap(
                    train_one, in_axes=(0, 0, 0, 0),
                )(b_slot, b_idx, b_mask, b_keys)
                pre_b = delta_b  # ledger resid: raw Δ vs shipped Δ
                if clip_delta_norm > 0.0:
                    delta_b = _clip_block(delta_b, clip_delta_norm)
                from colearn_federated_learning_tpu.obs.ledger import (
                    upload_residual,
                )

                ys = {
                    "delta": delta_b,
                    "pc_loss": m_b.loss,
                    "pc_resid": upload_residual(pre_b, delta_b),
                }
                return None, ys

            n_blocks = idx.shape[0] // width
            blocked = jax.tree.map(
                lambda a: a.reshape((n_blocks, width) + a.shape[1:]),
                (idx, mask, slots, keys),
            )
            _, ys = jax.lax.scan(per_block, None, blocked)
            unblock = lambda t: jax.tree.map(  # noqa: E731
                lambda a: a.reshape((idx.shape[0],) + a.shape[2:]), t
            )
            return {
                "deltas": unblock(ys["delta"]),
                "pc_loss": unblock(ys["pc_loss"]),
                "pc_resid": unblock(ys["pc_resid"]),
            }

        stack_in_specs = (P(), P(), P(), P(CLIENT_AXIS), P(CLIENT_AXIS),
                          P(CLIENT_AXIS), P(CLIENT_AXIS))
        if use_decay:
            stack_in_specs += (P(),)
        sharded_stack_lane = jax.shard_map(
            lane_stack_fn, mesh=mesh, in_specs=stack_in_specs,
            out_specs={
                "deltas": P(CLIENT_AXIS),
                "pc_loss": P(CLIENT_AXIS),
                "pc_resid": P(CLIENT_AXIS),
            },
        )

        @partial(jax.jit, donate_argnums=(0, 1, 10) if donate else ())
        def ledger_round_fn(history, server_opt_state, train_x, train_y,
                            idx, mask, agg_w, n_ex, slots, cohort, ledger,
                            cur_slot, next_slot, rng):
            for leaf in jax.tree.leaves(history):
                if leaf.shape[0] != window:
                    raise ValueError(
                        f"history ring has {leaf.shape[0]} slots, engine "
                        f"was built for window={window}"
                    )
                break
            keys = jax.random.split(rng, idx.shape[0])
            extra = ()
            if use_decay:
                extra = (_decay_scale(client_cfg.lr_decay, server_opt_state),)
            with jax.named_scope("fedbuff_train_stack"):
                out = sharded_stack_lane(
                    history, train_x, train_y, idx, mask, slots, keys,
                    *extra,
                )
            wire = out["deltas"]
            trust = None
            w = agg_w.astype(jnp.float32)
            if reputation:
                # staleness-aware reputation-weighted merge: the trust
                # from the CARRIED ledger folds into the host-computed
                # staleness decay — admitted weight base·(1+s)^-α·trust
                from colearn_federated_learning_tpu.server.aggregation import (  # noqa: E501
                    reputation_weights,
                )

                trust = reputation_weights(
                    ledger, cohort.astype(jnp.int32), rep_floor,
                    rep_strength, rep_z_gain, ledger_zmax,
                )
                w = w * trust.astype(jnp.float32)
            with jax.named_scope("fedbuff_aggregate"):
                w_sum = w.sum()
                # async weights are FRACTIONAL — guard only the true
                # all-dropout case (same semantics as the psum path)
                denom = jnp.where(w_sum > 0, w_sum, 1.0)
                mean_delta = jax.tree.map(
                    lambda d: jnp.einsum("c,c...->...", w, d) / denom,
                    wire,
                )
                mean_loss = (w * out["pc_loss"]).sum() / denom
                n_total = n_ex.sum()
            with jax.named_scope("round_server_apply"):
                current = jax.tree.map(
                    lambda h: jnp.take(h, cur_slot, axis=0), history
                )
                new_params, new_opt_state = server_update(
                    current, server_opt_state, mean_delta
                )
                new_history = jax.tree.map(
                    lambda h, p: h.at[next_slot].set(p.astype(h.dtype)),
                    history, new_params,
                )
            with jax.named_scope("round_client_ledger"):
                from colearn_federated_learning_tpu.obs.ledger import (
                    client_round_stats,
                    update_ledger,
                )

                stats = client_round_stats(
                    wire, mean_delta, out["pc_loss"], out["pc_resid"],
                    n_ex, ledger_zmax,
                )
                new_ledger = update_ledger(
                    ledger, cohort.astype(jnp.int32), n_ex, stats,
                    ledger_ema,
                )
            return (new_history, new_params, new_opt_state, new_ledger,
                    RoundMetrics(mean_loss, n_total))

        return instrument("round.fedbuff_ledger", ledger_round_fn)

    def lane_fn(history, train_x, train_y, idx, mask, agg_w, n_ex, slots,
                keys, *rest):
        lr_scale = rest[0] if use_decay else None
        history = _pcast_varying(history)

        def train_one(slot, b_idx, b_mask, key):
            start = jax.tree.map(lambda h: jnp.take(h, slot, axis=0), history)
            extra = () if lr_scale is None else (lr_scale,)
            w, m = local_train(start, train_x, train_y, b_idx, b_mask, key,
                               *extra)
            delta = jax.tree.map(
                lambda wi, p: wi.astype(jnp.float32) - p.astype(jnp.float32),
                w, start,
            )
            return delta, m

        def per_block(acc, inp):
            b_idx, b_mask, b_w, b_n, b_slot, b_keys = inp
            delta_b, m_b = jax.vmap(
                train_one, in_axes=(0, 0, 0, 0),
            )(b_slot, b_idx, b_mask, b_keys)
            if clip_delta_norm > 0.0:
                delta_b = _clip_block(delta_b, clip_delta_norm)
            d_acc, w_acc, n_acc, l_acc = acc
            d_acc = jax.tree.map(
                lambda a, dd: a + jnp.einsum(
                    "c,c...->...", b_w.astype(jnp.float32), dd
                ).astype(a.dtype),
                d_acc, delta_b,
            )
            return (d_acc, w_acc + b_w.sum(), n_acc + b_n.sum(),
                    l_acc + (b_w * m_b.loss).sum()), None

        n_blocks = idx.shape[0] // width
        blocked = jax.tree.map(
            lambda a: a.reshape((n_blocks, width) + a.shape[1:]),
            (idx, mask, agg_w, n_ex, slots, keys),
        )
        d0 = jax.tree.map(
            lambda h: jnp.zeros(h.shape[1:], jnp.float32), history
        )
        acc0 = _pcast_varying(
            (d0, jnp.zeros(()), jnp.zeros(()), jnp.zeros(())),
        )
        (d_sum, w_sum, n_sum, l_sum), _ = jax.lax.scan(per_block, acc0, blocked)
        d_sum = jax.lax.psum(d_sum, CLIENT_AXIS)
        w_sum = jax.lax.psum(w_sum, CLIENT_AXIS)
        n_sum = jax.lax.psum(n_sum, CLIENT_AXIS)
        l_sum = jax.lax.psum(l_sum, CLIENT_AXIS)
        # Async weights are FRACTIONAL (staleness decay), so a max-with-1
        # floor would silently attenuate legitimate updates whenever
        # w_sum < 1 — guard only the true all-dropout case, same
        # degenerate-round semantics as the sync engine (zero delta,
        # zero loss).
        denom = jnp.where(w_sum > 0, w_sum, 1.0)
        return trees.tree_scale(d_sum, 1.0 / denom), n_sum, l_sum / denom

    in_specs = (P(), P(), P(), P(CLIENT_AXIS), P(CLIENT_AXIS),
                P(CLIENT_AXIS), P(CLIENT_AXIS), P(CLIENT_AXIS),
                P(CLIENT_AXIS))
    if use_decay:
        in_specs += (P(),)
    sharded_lane = jax.shard_map(
        lane_fn, mesh=mesh, in_specs=in_specs, out_specs=(P(), P(), P()),
    )

    @partial(jax.jit, donate_argnums=(0, 1) if donate else ())
    def round_fn(history, server_opt_state, train_x, train_y, idx, mask,
                 agg_w, n_ex, slots, cur_slot, next_slot, rng):
        # the ring size must agree with the host scheduler's slot
        # arithmetic (versions % window) — a mismatch would gather stale
        # params from the WRONG slot with no runtime error
        for leaf in jax.tree.leaves(history):
            if leaf.shape[0] != window:
                raise ValueError(
                    f"history ring has {leaf.shape[0]} slots, engine was "
                    f"built for window={window}"
                )
            break
        keys = jax.random.split(rng, idx.shape[0])
        extra = ()
        if use_decay:
            extra = (_decay_scale(client_cfg.lr_decay, server_opt_state),)
        with jax.named_scope("fedbuff_train_aggregate"):
            mean_delta, n_total, mean_loss = sharded_lane(
                history, train_x, train_y, idx, mask, agg_w, n_ex, slots, keys,
                *extra,
            )
        with jax.named_scope("round_server_apply"):
            current = jax.tree.map(
                lambda h: jnp.take(h, cur_slot, axis=0), history
            )
            new_params, new_opt_state = server_update(
                current, server_opt_state, mean_delta
            )
            new_history = jax.tree.map(
                lambda h, p: h.at[next_slot].set(p.astype(h.dtype)),
                history, new_params,
            )
        return (new_history, new_params, new_opt_state,
                RoundMetrics(mean_loss, n_total))

    return instrument("round.fedbuff", round_fn)


def make_sequential_round_fn(model, client_cfg, dp_cfg, task, server_update,
                             local_dtype=None, agg: str = "examples",
                             scaffold: bool = False, num_clients: int = 0,
                             aggregator: str = "weighted_mean",
                             trim_ratio: float = 0.1,
                             compression: str = "", topk_ratio: float = 0.01,
                             qsgd_levels: int = 256, topk_exact: bool = False,
                             clip_delta_norm: float = 0.0,
                             feddyn_alpha: float = 0.0,
                             byzantine_f: int = 0,
                             secagg: bool = False,
                             secagg_quant_step: float = 1e-4,
                             secagg_mode: str = "ring",
                             scan_unroll: int = 1,
                             client_dp_noise: float = 0.0,
                             dp_fixed_denom: float = 0.0,
                             downlink: str = "",
                             downlink_levels: int = 256,
                             error_feedback: bool = False,
                             attack: str = "",
                             attack_scale: float = 10.0,
                             attack_eps: float = 1.0,
                             on_device_mask: bool = False,
                             client_ledger: bool = False,
                             ledger_ema: float = 0.2,
                             ledger_zmax: float = 3.5,
                             reputation: bool = False,
                             rep_floor: float = 0.05,
                             rep_strength: float = 6.0,
                             rep_z_gain: float = 1.0,
                             fused_apply: bool = False):
    """Reference-semantics engine: python loop over the cohort, jitted
    per-client local training, host-side weighted mean. Used for
    single-device debugging and as the parity oracle the shard_map
    engine is tested against (SURVEY.md §4.3). ``scaffold``, ``feddyn``,
    ``error_feedback`` and ``aggregator`` mirror the sharded engine's
    signature exactly (under ``error_feedback`` the round fn takes the
    cohort's e-rows as ``c_cohort`` — ``c_global`` stays None — and
    returns ``(params, opt_state, new_e_cohort, metrics)``).
    ``on_device_mask`` mirrors the sharded engine's compact-spec mask
    input: ``mask`` arrives as the ``[K, 2]`` spec and is expanded to
    the identical full float32 mask before the loop (the loop itself is
    the oracle — it sees exactly what the lanes rebuild in-program).
    ``client_ledger`` mirrors the sharded engine: the round fn takes
    ``ledger`` + ``ledger_ids`` and returns the updated ledger before
    the metrics, built from the SAME shared stats/update helpers
    (obs/ledger.py) over the same wire-upload stack.
    There is no ``cohort_layout``: the oracle is layout-free — the
    python loop IS the reference semantics both layouts must
    reproduce. What may be combined is
    ``ExperimentConfig.validate()``'s to say
    (:func:`make_sharded_round_fn`)."""
    if agg not in ("examples", "uniform"):
        raise ValueError(f"unknown aggregation mode {agg!r}")
    if fused_apply and not hasattr(server_update, "fused_reduce"):
        raise ValueError(
            "fused_apply=True requires a server_update built by "
            "make_server_update_fn with fused_apply enabled"
        )
    if client_dp_noise > 0.0 and agg != "uniform":
        raise ValueError(
            "client-level DP requires uniform aggregation weights "
            "(the driver selects them automatically)"
        )
    feddyn, client_cfg = _feddyn_prepare(client_cfg, scaffold, feddyn_alpha)
    stateful = scaffold or feddyn
    if stateful and num_clients <= 0:
        raise ValueError("stateful algorithms require num_clients")
    if aggregator not in ("weighted_mean", "median", "trimmed_mean", "krum"):
        raise ValueError(f"unknown aggregator {aggregator!r}")
    robust = aggregator != "weighted_mean"
    from colearn_federated_learning_tpu.ops.compression import (
        downlink_quantize,
        make_compressor,
    )

    compress = make_compressor(compression, topk_ratio, qsgd_levels,
                               topk_exact=topk_exact)
    local_train = instrument(
        "seq.local_train",
        jax.jit(make_local_train_fn(model, client_cfg, dp_cfg, task,
                                    local_dtype=local_dtype,
                                    scan_unroll=scan_unroll)),
    )
    update = instrument("seq.server_apply", jax.jit(server_update))
    # the fused stacked-path entry, jitted ONCE at the factory (the
    # interpret-mode kernel would otherwise re-trace eagerly per round)
    fused_reduce = (
        instrument("seq.fused_reduce", jax.jit(server_update.fused_reduce))
        if fused_apply else None
    )

    use_decay = client_cfg.lr_decay != 1.0
    # ONE jit wrapper per factory: eager per-client pairwise uploads
    # re-trace the K-step PRG scan every call (~seconds each), and a
    # wrapper created inside round_fn would re-compile every ROUND —
    # the cache lives with the wrapper, so it must outlive the round
    pairwise_up = (
        instrument("seq.secagg_upload",
                   jax.jit(_secagg_pairwise_upload, static_argnums=(7, 8)),
                   static_argnums=(7, 8))
        if secagg and secagg_mode == "pairwise" else None
    )

    def round_fn(params, server_opt_state, train_x, train_y, idx, mask, n_ex, rng,
                 c_global=None, c_cohort=None, pair_seeds=None, byz=None,
                 ledger=None, ledger_ids=None):
        if attack and byz is None:
            raise TypeError(f"attack={attack!r} requires the byz mask input")
        if client_ledger and (ledger is None or ledger_ids is None):
            raise TypeError(
                "client_ledger requires the ledger and ledger_ids inputs"
            )
        trust = None
        if reputation:
            # the SAME shared helper as the sharded program, on the same
            # ledger-as-carried-in — trust parity across engines holds
            # by construction (client_ledger guarantees the inputs)
            from colearn_federated_learning_tpu.server.aggregation import (
                reputation_weights,
            )

            trust = reputation_weights(
                jnp.asarray(ledger),
                jnp.asarray(ledger_ids).astype(jnp.int32),
                rep_floor, rep_strength, rep_z_gain, ledger_zmax,
            )
        if on_device_mask:
            import numpy as _np

            from colearn_federated_learning_tpu.data.loader import (
                expand_mask_spec,
            )

            mask = expand_mask_spec(
                _np.asarray(mask), idx.shape[1], idx.shape[2],
                client_cfg.local_epochs,
            )
        k = idx.shape[0]
        keys = jax.random.split(rng, k)
        lr_scale = (
            _decay_scale(client_cfg.lr_decay, server_opt_state)
            if use_decay else None
        )
        extra = (lr_scale,) if use_decay else ()
        deltas, weights, losses, resids = [], [], [], []
        # the weights clients receive this round (identical dither
        # derivation as the sharded engine — parity holds)
        bcast = params
        if downlink:
            bcast = downlink_quantize(
                params, jax.random.fold_in(rng, _DOWNLINK_FOLD),
                downlink_levels,
            )
        if secagg:
            # identical mask derivation + per-client streams as the
            # sharded engine; int32 sums are order-independent mod 2^32,
            # so the two engines agree BITWISE on the aggregate. Ring
            # mode: static full-cohort ring (slot c → c+1 mod K);
            # pairwise mode: host-built seed matrix input.
            if secagg_mode == "pairwise":
                if pair_seeds is None:
                    raise TypeError("secagg_mode='pairwise' requires pair_seeds")
                part_full = jnp.asarray(n_ex) > 0
            else:
                mask_key = jax.random.fold_in(rng, _SECAGG_FOLD)
            q_acc = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.int32), params
            )
            slots = jnp.arange(k, dtype=jnp.int32)
        new_cs = []
        dc_sum = (
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if stateful else None
        )
        for c in range(k):
            if stateful:
                c_i = jax.tree.map(lambda a: a[c], c_cohort)
                if scaffold:
                    corr = jax.tree.map(lambda cg, ci: cg - ci, c_global, c_i)
                else:  # feddyn linear term
                    corr = jax.tree.map(jnp.negative, c_i)
                w_i, m_i = local_train(params, train_x, train_y, idx[c], mask[c],
                                       keys[c], lr_scale, corr)
                # width-1 block through the SAME update helper as the
                # sharded lane — the oracle can't drift from the engine
                k_valid = jnp.asarray(
                    [(jnp.asarray(mask[c]).sum(-1) > 0).sum()], jnp.float32
                )
                part = ((jnp.asarray(n_ex[c]) > 0) & (k_valid[0] > 0)).astype(
                    jnp.float32
                )[None]
                if scaffold:
                    lr_i = jnp.float32(client_cfg.lr) * (
                        lr_scale.astype(jnp.float32) if lr_scale is not None else 1.0
                    )
                    new_c_block = _scaffold_c_update(
                        jax.tree.map(lambda a: a[None], c_i), c_global, params,
                        jax.tree.map(lambda a: a[None], w_i), k_valid, lr_i, part,
                    )
                else:
                    new_c_block = _feddyn_g_update(
                        jax.tree.map(lambda a: a[None], c_i), params,
                        jax.tree.map(lambda a: a[None], w_i), part,
                        feddyn_alpha,
                    )
                new_c = jax.tree.map(lambda a: a[0], new_c_block)
                new_cs.append(new_c)
                dc_sum = jax.tree.map(
                    lambda a, nc, ci: a + (nc - ci), dc_sum, new_c, c_i
                )
            else:
                w_i, m_i = local_train(bcast, train_x, train_y, idx[c], mask[c],
                                       keys[c], *extra)
            # delta vs the RECEIVED weights (bcast == params unless
            # downlink compression is on), applied to the exact params
            delta_i = jax.tree.map(
                lambda w, p: w.astype(jnp.float32) - p.astype(jnp.float32),
                w_i, bcast,
            )
            # client_ledger resid: raw-vs-shipped on the plain path,
            # the EF accumulator residual below (same rule as the lane)
            pre_i = delta_i if client_ledger else None
            resid_c = None
            if clip_delta_norm > 0.0 or compress is not None:
                # one width-1 block through the SAME operators as the
                # sharded lane (clip first, then EF memory, then
                # compress the wire format)
                block = jax.tree.map(lambda a: a[None], delta_i)
                if clip_delta_norm > 0.0:
                    block = _clip_block(block, clip_delta_norm)
                if error_feedback:
                    e_block = jax.tree.map(
                        lambda a: a[c][None].astype(jnp.float32), c_cohort
                    )
                    acc_block = jax.tree.map(jnp.add, block, e_block)
                    comp_block = compress(acc_block, keys[c][None])
                    if client_ledger:
                        from colearn_federated_learning_tpu.obs.ledger import (
                            upload_residual,
                        )

                        resid_c = upload_residual(acc_block, comp_block)[0]
                    part_c = (jnp.asarray(n_ex[c]) > 0)
                    new_cs.append(jax.tree.map(
                        lambda a, cp, e: jnp.where(part_c, a - cp, e)[0],
                        acc_block, comp_block, e_block,
                    ))
                    block = comp_block
                elif compress is not None:
                    block = compress(block, keys[c][None])
                delta_i = jax.tree.map(lambda a: a[0], block)
            if client_ledger:
                if resid_c is None:
                    from colearn_federated_learning_tpu.obs.ledger import (
                        upload_residual,
                    )

                    resid_c = upload_residual(
                        jax.tree.map(lambda a: a[None], pre_i),
                        jax.tree.map(lambda a: a[None], delta_i),
                    )[0]
                resids.append(resid_c)
            n_c = jnp.asarray(n_ex[c])
            w_c = n_c if agg == "examples" else (n_c > 0).astype(n_c.dtype)
            if reputation:
                # identical multiply to the lane's b_w * b_tr — the
                # loss metric weights identically too
                w_c = w_c * trust[c]
            weights.append(w_c)
            losses.append(m_i.loss)
            if secagg:
                # only the masked int32 accumulator survives the loop —
                # keeping the raw f32 deltas too would retain cohort×
                # params dead memory
                if secagg_mode == "pairwise":
                    up = pairwise_up(
                        jax.tree.map(lambda a: a[None], delta_i),
                        jnp.asarray(weights[-1])[None],
                        slots[c][None], (jnp.asarray(n_ex[c]) > 0)[None],
                        part_full, pair_seeds, params, secagg_quant_step, k,
                    )
                else:
                    up = _secagg_upload(
                        jax.tree.map(lambda a: a[None], delta_i),
                        jnp.asarray(weights[-1])[None],
                        slots[c][None], (jnp.asarray(n_ex[c]) > 0)[None],
                        mask_key, params, secagg_quant_step, k,
                    )
                q_acc = jax.tree.map(lambda a, u: a + u[0], q_acc, up)
            else:
                deltas.append(delta_i)
        n_total = jnp.asarray(n_ex).sum()
        w_sum = jnp.sum(jnp.stack(weights))
        denom = jnp.where(w_sum > 0, w_sum, 1.0)
        # fixed public denominator under client DP (see the sharded lane)
        agg_denom = (
            jnp.float32(dp_fixed_denom or k)
            if client_dp_noise > 0.0 else denom
        )
        fused_out = None
        if robust or attack:
            # the per-client stack path — identical ops to the sharded
            # engine's _mean_delta (shared transform + shared stack
            # aggregation), so attacked/robust rounds agree across
            # engines by construction
            stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *deltas)
            if attack:
                from colearn_federated_learning_tpu.server.attacks import (
                    apply_upload_attack,
                )

                # same scope name as the sharded engine's _wire_stack —
                # the phase names are engine-invariant down to the
                # device-trace labels
                with jax.named_scope("round_attack_transform"):
                    stacked = apply_upload_attack(
                        stacked, jnp.asarray(byz), keys, attack, attack_scale,
                        attack_eps, participation=jnp.asarray(n_ex) > 0,
                    )
            if fused_reduce is not None and aggregator in (
                "weighted_mean", "krum",
            ):
                # fused server chain: the SAME shared weight/one-hot
                # construction as the sharded program feeds the same
                # kernel — fused-path cross-engine parity by
                # construction (ops/pallas_apply.py)
                stack_in, w_in = _fused_stack_inputs(
                    stacked, jnp.asarray(n_ex), trust, aggregator, agg,
                    byzantine_f, k,
                )
                fused_out = fused_reduce(
                    params, server_opt_state, stack_in, w_in
                )
                mean_delta = fused_out[2]
            elif robust:
                from colearn_federated_learning_tpu.server.aggregation import (
                    robust_reduce,
                    scale_deltas_by_trust,
                )

                agg_stack = stacked
                if trust is not None:
                    # same soft suppression as the sharded _mean_delta:
                    # trust scales uploads, order statistics unweighted
                    agg_stack = scale_deltas_by_trust(stacked, trust)
                mean_delta = robust_reduce(
                    agg_stack, jnp.asarray(n_ex) > 0, aggregator, trim_ratio,
                    byzantine_f,
                )
            else:
                from colearn_federated_learning_tpu.server.attacks import (
                    stack_weighted_mean,
                )

                mean_delta = stack_weighted_mean(
                    stacked, jnp.asarray(n_ex), agg, params, trust
                )
        elif secagg:
            # the cohort sum completed the ring: masks cancelled exactly
            mean_delta = jax.tree.map(
                lambda d, p: (
                    d.astype(jnp.float32) * secagg_quant_step / agg_denom
                ).astype(p.dtype),
                q_acc, params,
            )
        else:
            # deltas accumulate in f32; the final cast mirrors the sharded
            # engine's accumulator dtype (= server params dtype)
            acc = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            for d, w in zip(deltas, weights):
                acc = trees.tree_axpy(w, d, acc)
            mean_delta = jax.tree.map(
                lambda d, p: d.astype(p.dtype),
                trees.tree_scale(acc, 1.0 / agg_denom), params,
            )
        if client_dp_noise > 0.0:
            # same key derivation + per-leaf streams as the sharded
            # engine — parity holds on the noisy path too
            std = jnp.float32(
                client_dp_noise * clip_delta_norm
            ) / agg_denom
            mean_delta = _client_dp_noise(
                jax.random.fold_in(rng, _CLIENT_DP_FOLD), mean_delta, std
            )
        new_ledger = None
        if client_ledger:
            # the SAME shared stats + scatter helpers as the sharded
            # program, applied to the same wire stack — ledger parity
            # across engines holds by construction
            from colearn_federated_learning_tpu.obs.ledger import (
                client_round_stats,
                update_ledger,
            )

            wire = (
                stacked if (robust or attack)
                else jax.tree.map(lambda *ls: jnp.stack(ls), *deltas)
            )
            stats = client_round_stats(
                wire, mean_delta, jnp.stack(losses), jnp.stack(resids),
                jnp.asarray(n_ex), ledger_zmax,
            )
            new_ledger = update_ledger(
                jnp.asarray(ledger), jnp.asarray(ledger_ids),
                jnp.asarray(n_ex), stats, ledger_ema,
            )
        mean_loss = sum(w * l for w, l in zip(weights, losses)) / denom
        if stateful:
            new_c_global = jax.tree.map(
                lambda cg, dc: cg + dc / float(num_clients), c_global, dc_sum
            )
            new_c_cohort = jax.tree.map(
                lambda *ls: jnp.stack(ls), *new_cs
            )
            if feddyn:
                new_params = _feddyn_server_step(
                    params, mean_delta, new_c_global, feddyn_alpha
                )
                new_opt_state = dict(
                    server_opt_state, round=server_opt_state["round"] + 1
                )
            else:
                new_params, new_opt_state = update(
                    params, server_opt_state, mean_delta
                )
            return (new_params, new_opt_state, new_c_global, new_c_cohort,
                    RoundMetrics(mean_loss, n_total))
        if fused_out is not None:
            # params/opt state already advanced inside the fused kernel
            # pass (mean_delta above was its third output)
            new_params, new_opt_state = fused_out[0], fused_out[1]
        else:
            new_params, new_opt_state = update(
                params, server_opt_state, mean_delta
            )
        if error_feedback:
            new_e_cohort = jax.tree.map(lambda *ls: jnp.stack(ls), *new_cs)
            if client_ledger:
                return (new_params, new_opt_state, new_e_cohort, new_ledger,
                        RoundMetrics(mean_loss, n_total))
            return (new_params, new_opt_state, new_e_cohort,
                    RoundMetrics(mean_loss, n_total))
        if client_ledger:
            return (new_params, new_opt_state, new_ledger,
                    RoundMetrics(mean_loss, n_total))
        return new_params, new_opt_state, RoundMetrics(mean_loss, n_total)

    return round_fn
