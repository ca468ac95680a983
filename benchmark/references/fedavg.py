"""The plain reference round of FedAvg (weighted mean, server step 1 x mean delta).

A federated round written the slow, obvious way, independent of the
round engine: a Python loop over the sampled clients and over each
client's local steps, one ``jax.grad`` of the masked cross-entropy on
``model.apply`` per step, the client optimizer (SGD with momentum, or
AdamW) written out, DP-SGD one example at a time (clip in float32,
Gaussian noise on the clipped sum), the example-weighted mean of the
client deltas in float32, the server step. No megabatch, no fusion of
rounds, no scan, no vmap, no Pallas, no mesh. Only a client's single
step is jitted (a Python-level tree update per leaf would cost minutes
of dispatch on an 86 M-parameter model); nothing is fused across steps,
clients or rounds.

What it takes from the program, because they are the run's *inputs*, not
the system under test: the seeded initial state (``init_state``), the
model family's flax module (``build_model``), the federation's example
arrays, the cohort schedule and example order of each round
(``Experiment._host_inputs(round)``, pure in seed and round: the host
pipeline's output is what the round program is *given* — the native C++
pipeline and the NumPy one order examples differently, so the order
cannot be re-derived here), and the random-key schedule the round program
documents (round key = ``fold_in(rng_key, round)``, one key per client
by ``split``, one per local step by ``split``, one per parameter leaf by
``split`` for the DP noise). The DP noise is therefore drawn exactly as
the program draws it, rather than comparing a second, noise-free
program: that would cost a second compile of the round program in every
run (PR 22 chose the cheaper one).

The dtype policy is the one the benchmark's configuration file *states*
(``configs/<config>.json`` ``dtype_policy``), not the one the experiment
happens to run with: the reference builds its own module in the stated
compute dtype, casts parameters once to the stated local dtype at the
start of local training, keeps optimizer state in the local dtype, and
deltas, aggregation, clipping and noise in float32. A system that runs
in a lower precision than the configuration states is therefore compared
with the stated precision (``tests/benchmark`` shows a bf16 run of a
float32 configuration failing). float32 matrix products run at
``highest`` precision, so a float32 configuration is held to a true
float32 result on the TPU too.

``run_rounds`` is the one entry point ``run.py`` calls, found by the
cell's ``reference.impl``; an algorithm this file refuses (FedProx,
server momentum, robust aggregators, compression, attacks) gets a
reference file of its own beside this one.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8  # optax.adamw's defaults


def _loss_one_batch(model, compute_dtype):
    def loss_fn(params, x, y, m):
        if x.dtype == jnp.uint8:
            x = x.astype(compute_dtype) * jnp.asarray(1.0 / 255.0, compute_dtype)
        logits = model.apply({"params": params}, x, train=True)
        logits = logits.astype(jnp.float32)
        ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y[:, None].astype(jnp.int32), axis=-1
        )[:, 0]
        return (ce * m).sum() / jnp.maximum(m.sum(), 1.0)

    return loss_fn


def _sgd_step(cfg_client):
    lr, beta, wd = cfg_client.lr, cfg_client.momentum, cfg_client.weight_decay

    def init(params):
        return jax.tree.map(jnp.zeros_like, params) if beta else ()

    def update(params, state, grads):
        if wd:
            grads = jax.tree.map(lambda g, p: g + wd * p.astype(g.dtype),
                                 grads, params)
        if beta:
            state = jax.tree.map(
                lambda mom, g: (beta * mom + g.astype(mom.dtype)), state, grads
            )
            direction = state
        else:
            direction = grads
        params = jax.tree.map(
            lambda p, d: p - jnp.asarray(lr, p.dtype) * d.astype(p.dtype),
            params, direction,
        )
        return params, state

    return init, update


def _adamw_step(cfg_client):
    lr, wd = cfg_client.lr, cfg_client.weight_decay

    def init(params):
        zeros = jax.tree.map(jnp.zeros_like, params)
        return (jnp.zeros((), jnp.int32), zeros, zeros)

    def update(params, state, grads):
        count, mu, nu = state
        count = count + 1
        mu = jax.tree.map(lambda m_, g: (1 - ADAM_B1) * g + ADAM_B1 * m_, mu, grads)
        nu = jax.tree.map(lambda v, g: (1 - ADAM_B2) * g * g + ADAM_B2 * v, nu, grads)
        c1 = 1 - ADAM_B1 ** count.astype(jnp.float32)
        c2 = 1 - ADAM_B2 ** count.astype(jnp.float32)

        def leaf(p, m_, v):
            step = (m_ / c1.astype(m_.dtype)) / (
                jnp.sqrt(v / c2.astype(v.dtype)) + ADAM_EPS
            )
            if wd:
                step = step + wd * p
            return p - jnp.asarray(lr, p.dtype) * step.astype(p.dtype)

        return jax.tree.map(leaf, params, mu, nu), (count, mu, nu)

    return init, update


def _client_optimizer(cfg_client):
    if cfg_client.optimizer == "sgd":
        return _sgd_step(cfg_client)
    if cfg_client.optimizer == "adamw":
        return _adamw_step(cfg_client)
    raise NotImplementedError(
        f"the reference has no client optimizer {cfg_client.optimizer!r}"
    )


def _check_supported(cfg) -> None:
    """This file covers plain FedAvg; a cell that turns on anything else
    names another reference file (``reference.impl``)."""
    unsupported = []
    if cfg.algorithm != "fedavg":
        unsupported.append(f"algorithm={cfg.algorithm}")
    if cfg.server.optimizer != "mean":
        unsupported.append(f"server.optimizer={cfg.server.optimizer}")
    if cfg.server.aggregator != "weighted_mean":
        unsupported.append(f"server.aggregator={cfg.server.aggregator}")
    if cfg.server.compression or cfg.server.clip_delta_norm:
        unsupported.append("uplink compression / delta clipping")
    if cfg.client.lr_decay != 1.0 or cfg.client.prox_mu:
        unsupported.append("client lr decay / proximal term")
    if cfg.dp.enabled and cfg.dp.clipping != "microbatch":
        unsupported.append(f"dp.clipping={cfg.dp.clipping}")
    if cfg.attack.kind:
        unsupported.append("attack")
    if cfg.model.lora.enabled:
        unsupported.append("LoRA adapters")
    if unsupported:
        raise NotImplementedError(
            "references/fedavg.py does not cover: " + ", ".join(unsupported)
        )


def run_rounds(exp, config: Dict[str, Any], seed: int,
               n_rounds: int) -> Tuple[Any, Any, List[float]]:
    """(initial params, params after ``n_rounds``, train loss per round)
    from the seeded initial state, as float32 host arrays. ``config`` is
    the benchmark's configuration file: its ``dtype_policy`` is followed."""
    from colearn_federated_learning_tpu.data.loader import mask_from_spec
    from colearn_federated_learning_tpu.models import build_model

    cfg = exp.cfg
    _check_supported(cfg)
    if exp.task != "classify":
        raise NotImplementedError(f"reference covers classify, not {exp.task}")
    policy = config["dtype_policy"]
    compute_dtype = _DTYPES[policy["compute"]]
    local_dtype = _DTYPES[policy["local_params"]]
    model = build_model(
        cfg.model.name, cfg.model.num_classes, compute_dtype=compute_dtype,
        param_dtype=_DTYPES[policy["master_params"]], **cfg.model.kwargs,
    )
    loss_fn = _loss_one_batch(model, compute_dtype)
    opt_init, opt_update = _client_optimizer(cfg.client)
    dp = cfg.dp

    @jax.jit
    def plain_step(params, opt_state, x, y, m):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, m)
        params, opt_state = opt_update(params, opt_state, grads)
        return params, opt_state, loss

    @jax.jit
    def dp_accumulate(acc, loss_sum, params, x1, y1, m1):
        """One example: its gradient, clipped in float32, added."""
        loss, grads = jax.value_and_grad(loss_fn)(
            params, x1[None], y1[None], jnp.ones((1,), jnp.float32)
        )
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, dp.l2_clip / jnp.maximum(norm, 1e-12)) * m1
        acc = jax.tree.map(lambda a, g: a + scale * g, acc, grads)
        return acc, loss_sum + loss * m1

    @jax.jit
    def dp_finish(acc, params, opt_state, n, key):
        leaves, treedef = jax.tree.flatten(acc)
        keys = jax.random.split(key, len(leaves))
        sigma = dp.noise_multiplier * dp.l2_clip
        denom = jnp.maximum(n, 1.0)
        noisy = [
            (g + sigma * jax.random.normal(k, g.shape, jnp.float32)) / denom
            for g, k in zip(leaves, keys)
        ]
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype),
                             jax.tree.unflatten(treedef, noisy), params)
        return opt_update(params, opt_state, grads)

    state = exp.init_state(seed)
    rng_key = state["rng_key"]
    params = jax.tree.map(lambda p: jnp.asarray(p, jnp.float32), state["params"])
    initial = jax.device_get(params)
    train_x, train_y = exp.fed.train_x, exp.fed.train_y
    losses: List[float] = []
    with jax.default_matmul_precision("highest"):
        for r in range(n_rounds):
            cohort, idx, mask, n_ex, _ = exp._host_inputs(r)
            if exp._spec_inputs:  # [K, 2] (examples, valid steps)
                mask = mask_from_spec(mask, exp.shape)
            idx, mask = np.asarray(idx), np.asarray(mask)
            client_keys = jax.random.split(
                jax.random.fold_in(rng_key, r), len(cohort)
            )
            delta_sum = jax.tree.map(jnp.zeros_like, params)
            w_sum = 0.0
            loss_sum = 0.0
            for c in range(len(cohort)):
                local = jax.tree.map(lambda p: p.astype(local_dtype), params)
                opt_state = opt_init(local)
                step_keys = jax.random.split(client_keys[c], idx.shape[1])
                client_loss = 0.0
                for s in range(idx.shape[1]):
                    m = mask[c, s]
                    n = float(m.sum())
                    if n == 0:
                        continue  # a padded step changes nothing
                    x = jnp.asarray(train_x[idx[c, s]])
                    y = jnp.asarray(train_y[idx[c, s]])
                    if dp.enabled:
                        acc = jax.tree.map(
                            lambda p: jnp.zeros(p.shape, jnp.float32), local
                        )
                        l_sum = jnp.zeros((), jnp.float32)
                        for e in range(x.shape[0]):
                            if m[e] == 0:
                                continue
                            acc, l_sum = dp_accumulate(
                                acc, l_sum, local, x[e], y[e],
                                jnp.float32(m[e]),
                            )
                        local, opt_state = dp_finish(
                            acc, local, opt_state, jnp.float32(n),
                            step_keys[s],
                        )
                        step_loss = float(l_sum) / max(n, 1.0)
                    else:
                        local, opt_state, step_loss = plain_step(
                            local, opt_state, x, y, jnp.asarray(m)
                        )
                    client_loss += float(step_loss) * n
                w = float(n_ex[c])
                n_real = float(mask[c].sum())
                delta_sum = jax.tree.map(
                    lambda a, lp, p: a + w * (lp.astype(jnp.float32) - p),
                    delta_sum, local, params,
                )
                w_sum += w
                loss_sum += w * client_loss / max(n_real, 1.0)
            denom = w_sum if w_sum > 0 else 1.0
            params = jax.tree.map(
                lambda p, d: p + cfg.server.server_lr * d / denom,
                params, delta_sum,
            )
            losses.append(loss_sum / denom)
    return initial, jax.device_get(params), losses

