"""The plain reference round of cross-silo FedAvg on the Mellum2-12B-A2.5B
decoder (``configs/mellum2_12b_a2p5b_ep8.json``).

The forward pass, the loss and the gradients are written here from the
layer equations (PERF.md section 4) in straightforward ``jax.numpy``,
NOT through the program's ``build_model`` or any module of its ``ops/``:
a Python loop over the layers whose kind follows from the layer's
number, a softmax over a mask that is an array, one head at a time,
YaRN's frequencies from a loop over the pairs, a stable ``argsort`` for
the experts, a Python loop over the held experts with a mask,
``jax.grad``. A wrong layer in ``models/mellum2.py`` or a wrong tile in
``ops/band_attention.py`` therefore shows. It computes in blocks (every
head of every block of queries and every layer under ``jax.checkpoint``)
so that it fits beside the system's state on the chip.

The federated round around it is ``references/fedavg.py``'s, reused
through ``catalog.load_reference``: its AdamW, its refusal of what plain
FedAvg does not cover, its dtype table. As there, the dtype policy is
the one the configuration file *states*: parameters are cast once to the
stated local dtype at the start of local training, activations and
matrix products run in the stated compute dtype (float32 products at
``highest`` precision), optimizer state in the local dtype, deltas and
aggregation in float32; inside the model the router's softmax, the
attention softmax, the logits and the loss are float32 whatever the
policy (``dtype_policy.float32_inside_the_model``). What it takes from
the program are the run's inputs: the seeded initial state, the
federation's token arrays, the cohort schedule and example order of each
round, exactly as ``fedavg.py`` does.

Everything between the two lines of dashes is a copy of
``tests/reference/mellum2_decoder.py`` (a test compares the texts).
After it, ``attention_core`` is replaced by the same softmax computed a
block of ``QUERY_BLOCK`` queries at a time against the keys the block
can see (a head's ``[16384, 16384]`` float32 scores are 1.07 GB; a
block's ``[2048, keys]`` at most 134 MB), which a test holds to the
plain one.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------

NEG = -1e30
# what the configuration states as float32 whatever the compute dtype:
# router softmax, attention softmax, logits. (The control
# ``fedavg_mellum2_lm_lowered`` sets it to bfloat16 and has to come out
# as not correct: PERF.md section 6. The loss's own arithmetic, from the
# logits on, is float32 even then.)
ISLAND = jnp.float32


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rotate_half(x, angles, factor=1.0):
    """x [T, n, dim], angles [T, dim // 2]; cosine and sine each times
    ``factor``."""
    cos = jnp.cos(angles)[:, None, :] * factor
    sin = jnp.sin(angles)[:, None, :] * factor
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def yarn(sizes):
    """(low, high, the dim // 2 blended frequencies), in float64: pair i
    keeps its published frequency below ``low``, takes it divided by
    ``rope_factor`` above ``high``, and a linear blend between."""
    dim, theta = sizes["head_dim"], sizes["rope_theta"]

    def turns(n):  # the pair that turns n times over the original context
        return dim * math.log(sizes["rope_original"] / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(turns(sizes["rope_beta_fast"])), 0)
    high = min(math.ceil(turns(sizes["rope_beta_slow"])), dim - 1)
    freqs = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        freqs.append(f * (1.0 - r) + (f / sizes["rope_factor"]) * r)
    return low, high, freqs


def rope_of(kind, positions, sizes):
    """(angles [T, dim // 2], the factor on cosine and sine) of a layer
    of ``kind``: sliding layers turn by the published frequencies, full
    layers by YaRN's, times the attention factor."""
    dim, theta = sizes["head_dim"], sizes["rope_theta"]
    if kind == "full":
        freqs, factor = yarn(sizes)[2], sizes["rope_attention_factor"]
    else:
        freqs, factor = [theta ** (-2.0 * i / dim)
                         for i in range(dim // 2)], 1.0
    return (positions[:, None].astype(jnp.float32)
            * jnp.asarray(freqs, jnp.float32)), factor


def window_of(kind, sizes):
    return sizes["sliding_window"] if kind == "sliding" else None


def band_mask(queries, keys, window):
    """keep[t, u]: u <= t and, under a window, t - u < window."""
    ahead = queries[:, None] - keys[None, :]
    keep = ahead >= 0
    return keep if window is None else keep & (ahead < window)


def layer_params(params, i):
    """Layer i's leaves: the program stacks every layer's on axis 0."""
    return {k[len("layers_"):]: v[i] for k, v in params.items()
            if k.startswith("layers_")}


def masked_attention(q, k, v, keep, compute):
    """q [Tq, heads, hd], k and v [Tk, kv, hd], keep [Tq, Tk] -> [Tq,
    heads * hd]: softmax over the kept keys, one head at a time; query
    head a reads key-value head a // (heads // kv)."""
    tq, heads, hd = q.shape

    @jax.checkpoint
    def head_output(qh, kh, vh):
        s = jnp.dot(qh, kh.T, preferred_element_type=ISLAND) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(keep, s, NEG), axis=-1)
        return jnp.dot(prob.astype(compute), vh)

    group = jnp.arange(heads) // (heads // k.shape[1])
    _, outs = jax.lax.scan(
        lambda _, a: (None, head_output(*a)), None,
        (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[group],
         v.transpose(1, 0, 2)[group]))
    return outs.transpose(1, 0, 2).reshape(tq, heads * hd)


def attention_core(q, k, v, window, compute):
    """The band over the whole sequence, the mask as one [T, T] array."""
    t = jnp.arange(q.shape[0])
    return masked_attention(q, k, v, band_mask(t, t, window), compute)


def attention(p, x, positions, sizes, compute, kind):
    """The block's output before the residual."""
    t = x.shape[0]
    hd, heads, kv = sizes["head_dim"], sizes["heads"], sizes["kv_heads"]
    eps = sizes["rms_eps"]
    mat = lambda a, w: jnp.dot(a, w.astype(compute))  # noqa: E731
    h = rms_norm(x, p["attn_norm"], eps)
    angles, factor = rope_of(kind, positions, sizes)
    q = rotate_half(rms_norm(mat(h, p["wq"]).reshape(t, heads, hd),
                             p["q_norm"], eps), angles, factor)
    k = rotate_half(rms_norm(mat(h, p["wk"]).reshape(t, kv, hd),
                             p["k_norm"], eps), angles, factor)
    v = mat(h, p["wv"]).reshape(t, kv, hd)
    out = attention_core(q, k, v, window_of(kind, sizes), compute)
    return mat(out, p["wo"])


def experts(p, x, sizes, compute, experts_held=None, expert_offset=None):
    """The held experts' part of the layer, and each token's top experts."""
    held = sizes["experts_held"] if experts_held is None else experts_held
    offset = sizes["expert_offset"] if expert_offset is None else expert_offset
    top = sizes["experts_per_token"]
    h = rms_norm(x, p["mlp_norm"], sizes["rms_eps"])
    r = jax.nn.softmax(jnp.dot(h, p["router"].astype(compute),
                               preferred_element_type=ISLAND), axis=-1)
    chosen = jnp.argsort(-r, axis=-1, stable=True)[:, :top]
    r_top = jnp.take_along_axis(r, chosen, axis=-1)
    gates = r_top / r_top.sum(-1, keepdims=True)
    if held < p["router"].shape[-1]:
        # a share of the layer: the gates' gradient is the sum over the
        # chips' shares, which one chip does not have; constants here
        gates = jax.lax.stop_gradient(gates)

    @jax.checkpoint
    def one_expert(h, w1, w3, w2, gate):
        a = jnp.dot(h, w1.astype(compute), preferred_element_type=jnp.float32)
        b = jnp.dot(h, w3.astype(compute), preferred_element_type=jnp.float32)
        mid = (jax.nn.silu(a) * b).astype(compute)
        out = jnp.dot(mid, w2.astype(compute),
                      preferred_element_type=jnp.float32)
        return gate[:, None] * out

    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):  # every token through every held expert, masked
        gate = jnp.where(chosen == offset + e, gates, 0.0).sum(-1)
        y = y + one_expert(h, p["w1"][e], p["w3"][e], p["w2"][e], gate)
    return y.astype(compute), chosen


def forward(params, tokens, sizes, compute, positions=None, remat=True):
    """One sequence ``tokens`` [T] -> (float32 logits [T, vocab], chosen
    [layers, T, top] of every layer)."""
    t = tokens.shape[0]
    if positions is None:
        positions = jnp.arange(t)
    x = params["embed"][tokens].astype(compute)

    def layer(x, p, kind):
        x = x + attention(p, x, positions, sizes, compute, kind)
        y, chosen = experts(p, x, sizes, compute)
        return x + y, chosen

    period = tuple(sizes["period"])
    seen = []
    for i in range(sizes["layers"]):  # each recomputed in the backward pass
        kind = period[i % len(period)]
        one = jax.checkpoint(layer, static_argnums=2) if remat else layer
        x, chosen = one(x, layer_params(params, i), kind)
        seen.append(chosen)
    x = rms_norm(x, params["final_norm"], sizes["rms_eps"])
    logits = jnp.dot(x, params["head"].astype(compute),
                     preferred_element_type=ISLAND)
    return logits, jnp.stack(seen)


def loss(params, tokens, targets, sizes, compute):
    """The mean token cross-entropy of one sequence."""
    logits, _ = forward(params, tokens, sizes, compute)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].mean()

# ---------------------------------------------------------------------------

QUERY_BLOCK = 2048
plain_attention_core = attention_core


def attention_core(q, k, v, window, compute):  # noqa: F811
    """``plain_attention_core`` a block of queries at a time: queries
    ``lo .. hi`` against the keys ``first .. hi`` they can see (all from
    0 without a window), the same mask cut to that rectangle."""
    outs = []
    for lo in range(0, q.shape[0], QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, q.shape[0])
        first = 0 if window is None else max(lo - window + 1, 0)
        keep = band_mask(jnp.arange(lo, hi), jnp.arange(first, hi), window)
        outs.append(masked_attention(q[lo:hi], k[first:hi], v[first:hi],
                                     keep, compute))
    return jnp.concatenate(outs, axis=0)


def run_rounds(exp, config: Dict[str, Any], seed: int,
               n_rounds: int) -> Tuple[Any, Any, List[float]]:
    """(initial params, params after ``n_rounds``, train loss per round)
    from the seeded initial state, as float32 host arrays."""
    from harness import catalog

    from colearn_federated_learning_tpu.data.loader import mask_from_spec

    fedavg = catalog.load_reference("fedavg")
    cfg = exp.cfg
    fedavg._check_supported(cfg)
    if exp.task != "lm" or cfg.dp.enabled:
        raise NotImplementedError("this reference covers plain lm rounds")
    policy = config["dtype_policy"]
    compute = fedavg._DTYPES[policy["compute"]]
    local_dtype = fedavg._DTYPES[policy["local_params"]]
    sizes = config["model"]
    opt_init, opt_update = fedavg._client_optimizer(cfg.client)

    def batch_loss(params, x, y, m):
        per_example = jnp.stack([
            loss(params, x[b], y[b], sizes, compute)
            for b in range(x.shape[0])
        ])
        return (per_example * m).sum() / jnp.maximum(m.sum(), 1.0)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x, y, m):
        value, grads = jax.value_and_grad(batch_loss)(params, x, y, m)
        params, opt_state = opt_update(params, opt_state, grads)
        return params, opt_state, value

    state = exp.init_state(seed)
    params = jax.tree.map(lambda p: jnp.asarray(p, jnp.float32),
                          state["params"])
    initial = jax.device_get(params)
    train_x, train_y = exp.fed.train_x, exp.fed.train_y
    round_losses: List[float] = []
    with jax.default_matmul_precision("highest"):
        for r in range(n_rounds):
            cohort, idx, mask, n_ex, _ = exp._host_inputs(r)
            if exp._spec_inputs:  # [K, 2] (examples, valid steps)
                mask = mask_from_spec(mask, exp.shape)
            idx, mask = np.asarray(idx), np.asarray(mask)
            delta_sum = jax.tree.map(jnp.zeros_like, params)
            w_sum = loss_sum = 0.0
            for c in range(len(cohort)):
                # fresh buffers: the step donates them (on the chip the
                # reference runs beside the system's state)
                local = jax.tree.map(
                    lambda p: jnp.array(p, dtype=local_dtype, copy=True),
                    params)
                opt_state = jax.tree.map(jnp.copy, opt_init(local))
                client_loss = 0.0
                for s in range(idx.shape[1]):
                    n = float(mask[c, s].sum())
                    if n == 0:
                        continue  # a padded step changes nothing
                    local, opt_state, step_loss = step(
                        local, opt_state, jnp.asarray(train_x[idx[c, s]]),
                        jnp.asarray(train_y[idx[c, s]]),
                        jnp.asarray(mask[c, s]),
                    )
                    client_loss += float(step_loss) * n
                w = float(n_ex[c])
                delta_sum = jax.tree.map(
                    lambda a, lp, p: a + w * (lp.astype(jnp.float32) - p),
                    delta_sum, local, params,
                )
                w_sum += w
                loss_sum += w * client_loss / max(float(mask[c].sum()), 1.0)
            denom = w_sum if w_sum > 0 else 1.0
            params = jax.tree.map(
                lambda p, d: p + cfg.server.server_lr * d / denom,
                params, delta_sum,
            )
            round_losses.append(loss_sum / denom)
    return initial, jax.device_get(params), round_losses
