"""Per-client local trainer (SURVEY.md §2 C5/C7, call stack §3.3).

The reference's client loop is E epochs of minibatch SGD on torch.cuda
(BASELINE.json:5). Here it is one pure function::

    (global_params, data_refs, idx[steps,batch], mask[steps,batch], rng)
        → (local_params, metrics)

with ``lax.scan`` over the step axis so the entire local phase is a
single fused XLA computation — no host round-trips, no Python in the
loop. Batches are gathered **inside** the scan step from HBM-resident
example arrays (``jnp.take``), so peak memory is one batch, not
steps×batch (essential for the ViT silo config).

Algorithm hooks:
- FedProx (C7): the proximal term μ/2‖w−w₀‖² enters as the exact
  gradient contribution μ·(w−w₀) added to the batch gradient — the
  identity the unit tests pin (SURVEY.md §4.1).
- DP-SGD (C12): per-example clipped + noised gradients replace the
  batch gradient (privacy/dp.py).
- Padded steps (mask all-zero) are algebraic no-ops: the parameter and
  optimizer-state updates are gated on step validity, so heterogeneous
  clients running out of data early do not drift via momentum decay.
  The per-client scan still executes them; the megabatch block trainer
  does not (``_block_steps``).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

from colearn_federated_learning_tpu.config import ClientConfig, DPConfig
from colearn_federated_learning_tpu.privacy import dp as dp_lib
from colearn_federated_learning_tpu.utils import trees


class LocalMetrics(NamedTuple):
    loss: jnp.ndarray  # mask-weighted mean train loss over the round
    examples: jnp.ndarray  # real examples processed
    # {name: mask-weighted mean over the round} of the counters a model
    # with an auxiliary output reports (``model.aux_counters``); empty
    # for every other model
    aux: Any = ()


class RoundData(NamedTuple):
    """What a round program reads in the corpus argument's place when
    the model has a frozen base (``model.lora.enabled``): the examples
    and the base, both read-only, replicated over the lanes, never
    donated. Every engine passes its ``train_x`` through to the trainer
    untouched, so the base is an argument of every round program
    without one of them knowing it; :func:`_make_step` takes the two
    apart. The driver builds it (``Experiment._round_data``)."""

    x: Any
    frozen: Any


def make_client_optimizer(cfg: ClientConfig) -> optax.GradientTransformation:
    if cfg.optimizer == "sgd":
        opt = optax.sgd(cfg.lr, momentum=cfg.momentum if cfg.momentum else None)
    elif cfg.optimizer == "adamw":
        opt = optax.adamw(cfg.lr, weight_decay=cfg.weight_decay)
    else:
        raise ValueError(f"unknown client optimizer {cfg.optimizer!r}")
    if cfg.optimizer == "sgd" and cfg.weight_decay:
        opt = optax.chain(optax.add_decayed_weights(cfg.weight_decay), opt)
    return opt


def normalize_input(x, dtype=jnp.float32):
    """uint8 image corpora are stored RAW (4× the HBM capacity and 4× the
    host→device bandwidth of f32 — data/core.py); the [0,1] scaling
    happens here on device, where XLA fuses it into the first conv's
    input handling. Float inputs pass through untouched, int token ids
    (LM task) are never uint8.

    ``dtype``: the scaled batch's dtype. The TRAIN step passes the
    model's compute dtype (bf16 on the TPU configs — the bf16-compute
    policy end-to-end: without this the scaled batch materializes in
    f32 only for the model's first op to convert it back down). uint8
    values 0..255 are exact in bf16 (8-bit mantissa); the only rounding
    vs the f32 path is the 1/255 scale, identical per element. Eval and
    model init keep the f32 default (metrics stay full precision)."""
    if x.dtype == jnp.uint8:
        return x.astype(dtype) * jnp.asarray(1.0 / 255.0, dtype)
    return x


def _variables(params, frozen):
    """``model.apply``'s variables: the trained collection, and a LoRA
    model's frozen base beside it (``models/lora.LoRAModel.apply``)."""
    if frozen is None:
        return {"params": params}
    return {"params": params, "frozen": frozen}


def make_loss_fn(model, task: str, reduction: str = "mean",
                 with_counters: bool = False):
    """Masked loss. classify: y [B] ints; lm: y [B,T] next tokens.

    ``reduction="sum"`` returns the plain mask-weighted sum — what the
    batch-sharded path needs, where the mean's denominator spans all
    batch shards and is applied after the cross-shard psum.

    Inputs are normalized straight into the model's COMPUTE dtype (see
    :func:`normalize_input`): with bf16 compute the whole train step —
    input scaling, every matmul/conv, activations, and the backward —
    runs bf16 end-to-end; the loss itself stays f32 (the cross-entropy
    head's logits are f32 by model design).

    A model may return ``(logits, aux)`` instead of logits
    (``models/keye.py``, ``models/axk1.py``): ``aux["loss"]`` ``[B]``,
    where the model has one, is an auxiliary loss per example, added to
    the example's cross-entropy before the mask (coefficient 1; what it
    moves is the model's business, through ``stop_gradient``), and
    ``aux["counters"]`` holds ``[B]`` counters named by
    ``model.aux_counters``. ``with_counters`` makes the loss
    function return ``(loss, {name: mask-weighted mean})`` for
    ``jax.value_and_grad(..., has_aux=True)``. For a model that returns
    logits alone nothing here differs from before.

    ``frozen``: the frozen base of a LoRA model (the variable collection
    ``"frozen"`` of ``models/lora.LoRAModel.apply``); it takes no
    gradient.
    """
    in_dtype = getattr(model, "compute_dtype", jnp.float32)

    def loss_fn(params, x, y, m, frozen=None):
        logits = model.apply(
            _variables(params, frozen), normalize_input(x, in_dtype),
            train=True
        )
        aux = None
        if isinstance(logits, tuple):
            logits, aux = logits
        if task == "classify":
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        else:  # lm: mean over tokens within each example
            # (a name for the device trace where the model has its own)
            with (jax.named_scope("lm_head") if aux is not None
                  else contextlib.nullcontext()):
                ce = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean(-1)
        if aux is not None and "loss" in aux:
            ce = ce + aux["loss"]
        weighted = (ce * m).sum()
        loss = weighted if reduction == "sum" else (
            weighted / jnp.maximum(m.sum(), 1.0))
        if not with_counters:
            return loss
        return loss, {
            k: (v * m).sum() / jnp.maximum(m.sum(), 1.0)
            for k, v in aux["counters"].items()
        }

    return loss_fn


def _select_tree(pred, new, old):
    return jax.tree.map(lambda n, o: jnp.where(pred, n, o), new, old)


def windowed_conv_share(params) -> float:
    """Share of parameter elements in 2-D convolution kernels with a
    window: rank 4 ``[kh, kw, I, O]``, ``kh·kw > 1``, not depthwise
    (``I > 1``). A pointwise 1x1 kernel is a GEMM over batch·H·W and does
    not count. 0.983 for ResNet-18; 0.04 for LeNet-5; 0.0 for
    MobileNetV2 (pointwise and depthwise), bert_tiny and the LSTM."""
    windowed = sum(
        int(p.size) for p in jax.tree.leaves(params)
        if jnp.ndim(p) == 4 and p.shape[2] > 1 and p.shape[0] * p.shape[1] > 1
    )
    return windowed / max(1, trees.tree_size(params))


def shared_weight_phase(params) -> bool:
    """Whether the megabatch block trainer runs its first local step on
    the shared weights (``make_local_train_fn``): not for a model of
    windowed convolutions."""
    return windowed_conv_share(params) <= 0.5


def block_group(params, width: int) -> int:
    """How many of a block's ``width`` clients take a diverged step
    together, under one conditional (``_block_steps``); a divisor of
    ``width``. One for a model of windowed convolutions: a client's
    convolutions share nothing with its neighbour's, and alone they run
    faster than batched over the block (ResNet-18 at batch 64 on the
    v5e, every step live: 2.76 / 3.22 / 3.11 / 3.09 ms a client-step in
    groups of 1 / 2 / 4 / 16, and 3.09 vmapped as before; PERF.md,
    PR 35), so each client's dead steps are skipped. The whole block
    for every other model: its diverged steps are batched GEMMs across
    the clients, which is what the megabatch layout is for and has not
    been measured in smaller groups; a step is then skipped only where
    no client of the block has a row left."""
    return width if shared_weight_phase(params) else 1


class _DecomposedLoRA:
    """Megabatch view of a LoRA model: ``apply`` delegates to
    ``apply_decomposed`` (models/lora.py) so the frozen base is never
    merged into per-client kernels — its weights stay un-batched and
    contract the flattened megabatch in every local step. Exposes only
    what the loss factory reads."""

    def __init__(self, inner):
        self._inner = inner
        self.compute_dtype = getattr(inner, "compute_dtype", jnp.float32)

    def apply(self, variables, *args, **kwargs):
        return self._inner.apply_decomposed(variables, *args, **kwargs)


def make_local_train_fn(model, client_cfg: ClientConfig, dp_cfg: DPConfig, task: str,
                        batch_axis: str | None = None, local_dtype=None,
                        scan_unroll: int = 1, megabatch: bool = False):
    """Build the pure local-training function for one client-round.

    ``megabatch`` (``run.cohort_layout="megabatch"``): return the BLOCK
    trainer instead — signature ``(global_params, train_x, train_y,
    idx [C, steps, batch], mask [C, steps, batch], keys [C, 2],
    lr_scale?) → (stacked params [C, ...], LocalMetrics with [C]
    fields)`` — which trains a lane's whole C-client block as one fused
    computation. The first local step is the SHARED-WEIGHT phase: every
    client still holds the round's identical broadcast weights, so the
    step runs with the params (and the zero optimizer state) replicated
    — the forward and activation-gradient GEMMs contract the flattened
    ``[C·batch, ...]`` megabatch against ONE un-batched weight, which
    is what finally feeds the MXU production-sized matmuls on
    small-batch FL models. Only the per-client weight-gradient
    contractions are inherently batched (their outputs differ per
    client). From step 1 on, per-client params have diverged and the
    remaining steps scan a lane-local ``vmap`` of the SAME step
    function (one batched GEMM per layer instead of C sequential
    launches). Both phases reuse the identical per-client step body and
    the identical per-client key derivation (``split(rng_c, steps)``),
    so megabatch ≡ spatial ≡ vmap-width parity holds by construction up
    to GEMM-shape reassociation (test-pinned). ``grad_corr`` (the
    stateful algorithms' per-client correction) is not supported in the
    block signature — config.validate() rejects the pairing.

    Dead steps are skipped (PR 35): the diverged steps are one loop,
    ``_block_steps``, a scan over steps of a scan over the block's
    groups of :func:`block_group` clients, and a group's step runs
    under ``lax.cond`` on whether any of its batches holds a real row.
    A client whose data ends before the grid does (most clients of a
    cross-device federation) costs nothing from there on, where the
    vmapped step computed a full forward, backward and update and
    multiplied them by zero. A skipped step leaves what the executed
    one left, to the bit (``_block_steps``).

    A model of windowed convolutions (:func:`shared_weight_phase` false:
    ``windowed_conv_share > 0.5``) runs no shared-weight phase: its
    block is what ``jax.vmap(local_train)`` computes, every step
    diverged and in that loop. A convolution's GEMM rows are batch·H·W
    already, so one shared weight buys it no rows, and on the v5e its
    shared step is the slow one (3x3 weight-gradient convolutions over
    the megabatch's activation layout; PERF.md, PR 24), while models of
    GEMMs (pointwise convolutions, Dense, attention) lose rounds/s
    without it. No model of the zoo lies between 0.04 and 0.98, and
    nothing in that range has been measured.

    ``batch_axis``: when the mesh carries a second axis that data-parallels
    each client's minibatch (mesh.py ``BATCH_AXIS``), every shard holds
    ``batch / batch_shards`` examples of each step; the batch gradient is
    the psum of per-shard mask-weighted grad sums divided by the psummed
    mask count — exactly the full-batch masked mean, so results are
    bit-close to the unsharded path.

    ``local_dtype``: cast the incoming global params to this dtype ONCE at
    local-training entry (``run.local_param_dtype``). With f32 server
    params and bf16 compute, XLA otherwise re-converts every parameter
    f32→bf16 on every local step (~17% of round time on v5e — see the
    BASELINE.md profile); casting once per client keeps the local phase
    pure-bf16 while server-side aggregation and the cross-round parameter
    trajectory stay f32. Returned params are in ``local_dtype``; the
    aggregator's delta math upcasts back to f32.

    Padded-step gating: for ``sgd`` (the FL workhorse) validity is folded
    into *scalars* instead of per-leaf ``where`` selects — the update is
    ``m ← β_eff·m + v·g;  p ← p − lr_eff·m`` with ``v = [step valid]``,
    ``β_eff = 1 − v(1−β)`` and ``lr_eff = v·lr``, which is algebraically
    identical to select-gating (v=1 ⇒ plain momentum SGD; v=0 ⇒ both m
    and p unchanged) but fuses into the existing FMAs. The profile in
    BASELINE.md measured the select version's ``broadcast_select``
    fusions at ~11% of round device time. ``adamw`` keeps the general
    optax + select path (its count/bias-correction state isn't scalar-
    gateable).
    """
    fused_sgd = client_cfg.optimizer == "sgd"
    opt = None if fused_sgd else make_client_optimizer(client_cfg)
    if megabatch and hasattr(model, "apply_decomposed"):
        # All-steps LoRA megabatch: with the merged apply, the diverged
        # phase's per-client vmap batches EVERY base GEMM (C merged
        # kernel copies); the decomposed apply keeps the frozen base
        # un-batched — only the tiny A/B factors batch — so
        # the dominant contractions stay [C·batch, ·] × un-batched
        # weight in every local step, not just step 0. Spatial and
        # non-megabatch LoRA keep the merged apply bitwise-unchanged;
        # megabatch parity vs spatial is pinned at the documented
        # GEMM-reassociation tolerance.
        model = _DecomposedLoRA(model)
    # counters of a model with an auxiliary output (models/keye.py);
    # () for every other model, whose step is the one it always was.
    # Such a model trains in the spatial layout, without DP-SGD and
    # without a batch mesh axis: config.validate() refuses the rest, by
    # name.
    aux_names = tuple(getattr(model, "aux_counters", ()))
    grad_fn = jax.value_and_grad(
        make_loss_fn(model, task, with_counters=bool(aux_names)),
        has_aux=bool(aux_names),
    )
    sum_grad_fn = jax.value_and_grad(make_loss_fn(model, task, reduction="sum"))
    mu = client_cfg.prox_mu
    if dp_cfg.enabled:
        dp_loss_fn = make_loss_fn(model, task)
        dp_grad_fn = dp_lib.make_dp_grad_fn(
            dp_loss_fn, dp_cfg, batch_axis=batch_axis
        )

    def _global_count(m):
        n = m.sum()
        return jax.lax.psum(n, batch_axis) if batch_axis else n

    def _batch_varying(tree):
        # Params arrive batch-INVARIANT (replicated over batch shards).
        # Differentiating a batch-varying loss wrt invariant params makes
        # shard_map's reverse-mode AD psum the cotangents automatically;
        # combined with our explicit psum that double-counts. Casting to
        # varying first keeps grads local so the explicit psum is the only
        # cross-shard sum (type cast only — no communication).
        return jax.tree.map(
            lambda p: jax.lax.pcast(p, (batch_axis,), to="varying"), tree
        )

    def _cast_params(global_params):
        if local_dtype is not None:
            return jax.tree.map(
                lambda p: p.astype(local_dtype), global_params
            )
        return global_params

    def _make_step(global_params, train_x, train_y, lr_scale, grad_corr):
        """The per-client step body, shared VERBATIM by the per-client
        scan path and both megabatch phases — the layouts cannot drift
        numerically because they run the same function.

        Three named scopes split it for the device trace, beneath the
        engine's ``round_local_train``: ``local_gather`` (the batch's
        rows out of the corpus), ``local_grad`` (forward and backward, and
        the batch-shard psum of the gradient where there is one —
        opened OUTSIDE the transform, so the name is a plain path
        component, ``vmap(local_grad)`` under the megabatch vmap, with
        the forward under ``jvp(...)`` and the backward under
        ``transpose(jvp(...))`` beneath it; DP's own scopes nest inside)
        and ``local_opt`` (proximal / weight-decay terms and the
        parameter and optimizer-state update). Metadata only."""
        frozen_args = ()
        step_dp_grad_fn = dp_grad_fn if dp_cfg.enabled else None
        if isinstance(train_x, RoundData):
            train_x, frozen = train_x
            frozen_args = (frozen,)
            if dp_cfg.enabled:
                step_dp_grad_fn = dp_lib.make_dp_grad_fn(
                    functools.partial(dp_loss_fn, frozen=frozen), dp_cfg,
                    batch_axis=batch_axis)

        def step(carry, inp):
            params, opt_state = carry
            step_idx, step_mask, key = inp
            with jax.named_scope("local_gather"):
                x = jnp.take(train_x, step_idx, axis=0)
                y = jnp.take(train_y, step_idx, axis=0)
            step_n = _global_count(step_mask)  # identical on all batch shards
            with jax.named_scope("local_grad"):
                if dp_cfg.enabled:
                    loss, grads = step_dp_grad_fn(params, x, y, step_mask,
                                                  key)
                elif aux_names:
                    (loss, counters), grads = grad_fn(params, x, y, step_mask,
                                                      *frozen_args)
                elif batch_axis is None:
                    loss, grads = grad_fn(params, x, y, step_mask,
                                          *frozen_args)
                else:
                    sum_loss, sum_grads = sum_grad_fn(
                        _batch_varying(params), x, y, step_mask, *frozen_args
                    )
                    denom = jnp.maximum(step_n, 1.0)
                    loss = jax.lax.psum(sum_loss, batch_axis) / denom
                    grads = jax.tree.map(
                        lambda g: jax.lax.psum(g, batch_axis) / denom,
                        sum_grads,
                    )
            with jax.named_scope("local_opt"):
                if mu > 0.0:
                    # exact ∇ of μ/2‖w−w₀‖² — FedProx's proximal pull
                    grads = jax.tree.map(
                        lambda g, p, p0: g + mu * (p - p0), grads, params, global_params
                    )
                if grad_corr is not None:
                    grads = jax.tree.map(
                        lambda g, cc: g + cc.astype(g.dtype), grads, grad_corr
                    )
                # validity must be judged on the GLOBAL mask so batch shards
                # never diverge on whether a padded step applied
                if fused_sgd:
                    v = (step_n > 0).astype(jnp.float32)
                    wd = client_cfg.weight_decay
                    if wd:
                        grads = jax.tree.map(
                            lambda g, p: g + jnp.asarray(wd, g.dtype) * p.astype(g.dtype),
                            grads, params,
                        )
                    lr_eff = jnp.float32(client_cfg.lr) * v
                    if lr_scale is not None:
                        lr_eff = lr_eff * lr_scale.astype(lr_eff.dtype)
                    beta = client_cfg.momentum
                    if beta:
                        beta_eff = 1.0 - v * (1.0 - beta)
                        opt_state = jax.tree.map(
                            lambda m_, g: beta_eff.astype(m_.dtype) * m_
                            + v.astype(g.dtype) * g.astype(m_.dtype),
                            opt_state, grads,
                        )
                        direction = opt_state
                    else:
                        direction = grads
                    params = jax.tree.map(
                        lambda p, d: p - lr_eff.astype(p.dtype) * d.astype(p.dtype),
                        params, direction,
                    )
                else:
                    updates, new_opt_state = opt.update(grads, opt_state, params)
                    if lr_scale is not None:
                        updates = jax.tree.map(
                            lambda u: u * lr_scale.astype(u.dtype), updates
                        )
                    new_params = optax.apply_updates(params, updates)
                    valid = step_n > 0
                    params = _select_tree(valid, new_params, params)
                    opt_state = _select_tree(valid, new_opt_state, opt_state)
            if aux_names:
                return (params, opt_state), (
                    loss * step_n,
                    {k: counters[k] * step_n for k in aux_names},
                )
            return (params, opt_state), loss * step_n

        return step

    def _base_opt_state(global_params):
        if fused_sgd:
            # momentum buffer (or nothing) — the whole optimizer state
            return (
                trees.tree_zeros_like(global_params) if client_cfg.momentum else ()
            )
        return opt.init(global_params)

    def local_train(global_params, train_x, train_y, idx, mask, rng,
                    lr_scale=None, grad_corr=None):
        """idx/mask: [steps, batch(/shards)]; returns (params, LocalMetrics).

        ``lr_scale``: optional traced scalar multiplying every optimizer
        update — the round-indexed client LR decay (client.lr_decay).
        Scaling the final update is exactly scaling the learning rate for
        both sgd(+momentum) and adamw (optax applies lr as the last
        scale).

        ``grad_corr``: optional params-shaped tree added to every step's
        gradient — SCAFFOLD's variance-reduction term (c − cᵢ), constant
        over the local phase (Karimireddy et al. 2020, eq. 4). Padded
        steps stay exact no-ops: the correction rides the same validity
        gate as the gradient.
        """
        global_params = _cast_params(global_params)
        step = _make_step(global_params, train_x, train_y, lr_scale, grad_corr)
        steps = idx.shape[0]
        keys = jax.random.split(rng, steps)
        # Freshly created optimizer-state leaves (e.g. adam's int32 step
        # count) are device-invariant under shard_map while the scan
        # output is varying; tie every leaf to the data (+0·Σmask, exact)
        # so the carry type is uniform in both the sharded lane and the
        # sequential engine — same trick as privacy/dp.py's accumulators.
        # Under a batch axis the tie-in must be the psummed count, which is
        # batch-invariant like the params carry itself.
        base_state = _base_opt_state(global_params)
        vary0 = 0.0 * _global_count(mask)
        opt_state0 = jax.tree.map(
            lambda x: x + vary0.astype(x.dtype), base_state
        )
        (params, _), weighted_losses = jax.lax.scan(
            step, (global_params, opt_state0), (idx, mask, keys),
            unroll=scan_unroll,
        )
        n = _global_count(mask)
        if aux_names:
            weighted_losses, weighted_aux = weighted_losses
            aux = {k: v.sum() / jnp.maximum(n, 1.0)
                   for k, v in weighted_aux.items()}
        mean_loss = weighted_losses.sum() / jnp.maximum(n, 1.0)
        if aux_names:
            return params, LocalMetrics(loss=mean_loss, examples=n, aux=aux)
        return params, LocalMetrics(loss=mean_loss, examples=n)

    local_train.aux_names = aux_names
    if not megabatch:
        return local_train

    if batch_axis is not None:
        # read off the engine's mesh: the flattened [C·batch] rows ARE
        # the axis a batch-sharded mesh splits
        raise ValueError(
            "megabatch local training is incompatible with a batch mesh "
            "axis (run.batch_shards > 1)"
        )

    def _block_steps(step, carry, xs, size):
        """The block's step loop: a scan over steps of a scan over the
        block's groups of ``size`` clients, each group's step under a
        real conditional. ``carry``: stacked ``[width, ...]`` parameters
        and optimizer state; ``xs``: ``(idx, mask, keys)`` with leading
        axes ``[steps, width]``. Returns the carry and the ``[steps,
        width]`` weighted losses.

        A group's step whose batches hold no real row is not executed.
        What it would have left behind is what it found: the step gates
        its update on the same count (``p - 0*d``, ``1*m + 0*g``,
        ``loss * 0``; the optax branch selects the old tree), so
        skipping changes no finite result. Under ``vmap`` a conditional
        on a batched predicate is a select whose both sides run, hence
        the loop over groups: one predicate a group, one compiled body
        for all of them. The group's slice is read from and written
        back into the carry in place; as the inner scan's ``xs -> ys``
        the stack would be held twice."""
        width = xs[1].shape[1]
        groups = width // size

        # vmap also where a group is one client: with the step called on
        # the squeezed slice instead, XLA for the v5e copies a whole 357 MB
        # stack into another memory space and back in every live step
        # (PERF.md, PR 35: r18_c16_k8 2.59 rounds/s against 3.81)
        group_step = jax.vmap(step)

        def one_group(carry, inp):
            start, x = inp
            rows = _global_count(x[1])

            def run(carry):
                part = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, start, size),
                    carry)
                part, weighted_loss = group_step(part, x)
                return jax.tree.map(
                    lambda a, p: jax.lax.dynamic_update_slice_in_dim(
                        a, p, start, 0),
                    carry, part), weighted_loss

            def skip(carry):
                # the step's weighted loss, loss * 0, tied to the data
                # like local_train's vary0 so both sides type alike
                # under shard_map
                return carry, jnp.zeros((size,), jnp.float32) + 0.0 * rows

            return jax.lax.cond(rows > 0, run, skip, carry)

        def all_groups(carry, x):
            grouped = jax.tree.map(
                lambda a: a.reshape((groups, size) + a.shape[1:]), x)
            starts = jnp.arange(groups, dtype=jnp.int32) * size
            carry, weighted_loss = jax.lax.scan(
                one_group, carry, (starts, grouped))
            return carry, weighted_loss.reshape(width)

        return jax.lax.scan(all_groups, carry, xs, unroll=scan_unroll)

    def local_train_block(global_params, train_x, train_y, idx, mask, keys,
                          lr_scale=None, grad_corr=None):
        """Megabatched block trainer — see the factory docstring.
        idx/mask: [C, steps, batch]; keys: [C, 2] per-client round keys
        (the engine's `_cohort_keys` chunk)."""
        if grad_corr is not None:
            raise ValueError(
                "megabatch block training does not support grad_corr "
                "(stateful algorithms are spatial-layout only)"
            )
        global_params = _cast_params(global_params)
        step = _make_step(global_params, train_x, train_y, lr_scale, None)
        width, steps = idx.shape[:2]
        n = jax.vmap(_global_count)(mask)
        # identical per-client key derivation as the per-client path:
        # split(rng_c, steps), consumed in step order
        step_keys = jax.vmap(lambda k: jax.random.split(k, steps))(keys)
        xs = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1),
                          (idx, mask, step_keys))
        base_state = _base_opt_state(global_params)
        losses = []
        if shared_weight_phase(global_params):
            # Shared-weight phase (step 0): params AND the fresh optimizer
            # state are replicated across the block — only the data is
            # batched — so XLA sees the forward / activation-gradient
            # contractions as single [C·batch, ...] × [..., d] GEMMs
            # against ONE weight. No vary0 tie-in needed here: the carry
            # leaves the vmap already data-derived (device-varying).
            carry, first = jax.vmap(
                lambda x: step((global_params, base_state), x)
            )(jax.tree.map(lambda a: a[0], xs))
            losses.append(first[None])
            xs = jax.tree.map(lambda a: a[1:], xs)
        else:
            # no shared-weight phase (factory docstring): every step on
            # the clients' own weights, from the broadcast and the fresh
            # optimizer state tied to the data as local_train ties it
            vary0 = 0.0 * n

            def stack(x, tie=None):
                x = jnp.broadcast_to(x, (width,) + x.shape)
                if tie is None:
                    return x
                return x + tie.astype(x.dtype).reshape(
                    (width,) + (1,) * (x.ndim - 1))

            carry = (jax.tree.map(stack, global_params),
                     jax.tree.map(lambda x: stack(x, vary0), base_state))
        if xs[0].shape[0]:
            # diverged steps: per-client params, the SAME step fn
            carry, rest = _block_steps(
                step, carry, xs, block_group(global_params, width))
            losses.append(rest)
        mean_loss = jnp.concatenate(losses).sum(0) / jnp.maximum(n, 1.0)
        return carry[0], LocalMetrics(loss=mean_loss, examples=n)

    return local_train_block


def make_eval_fn(model, task: str):
    """Jitted masked eval on one batch → (sum_loss, sum_correct, n)."""
    loss_core = make_loss_fn(model, task)
    del loss_core  # eval computes sums, not means; kept for symmetry

    def eval_batch(params, x, y, m, frozen=None):
        logits = model.apply(_variables(params, frozen), normalize_input(x),
                             train=False)
        if isinstance(logits, tuple):  # (logits, aux): see make_loss_fn
            logits = logits[0]
        if task == "classify":
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        else:
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean(-1)
            correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32).mean(-1)
        return (ce * m).sum(), (correct * m).sum(), m.sum()

    return eval_batch
