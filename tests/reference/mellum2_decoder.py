"""Plain reference of the Mellum2-12B-A2.5B decoder, written from the
layer equations (ISSUE 31; PERF.md section 4), independent of ``models/``
and ``ops/``: a Python loop over the layers (layer ``l`` is of kind
``period[l mod len(period)]``), dense ``[T, T]`` scores one head at a
time (a scan over the heads) under a mask that is an array, YaRN's
frequencies from a loop over the pairs in Python's float64, a stable
``argsort`` for the experts, a loop over the held experts with a mask,
``jax.grad`` for the gradients. One sequence at a time. No kernels, no
tiles.

``sizes`` is a mapping with the keys of ``benchmark/configs/
mellum2_12b_a2p5b_ep8.json`` ``model`` (the names of ``models/
mellum2.py``'s factory); ``params`` the flat tree the program's model
holds (``layers_<name>`` stacked over the layers, ``embed``,
``final_norm``, ``head``). ``compute`` is the dtype of activations and
matrix products (the configuration's ``dtype_policy.compute``); the
router's softmax, the attention softmax, the logits and the loss are
float32 whatever it is.

Departures from the source, each also under ``assumed`` in the
configuration's file: q and k pass a per-head RMSNorm before RoPE (the
config repeats the keys of a decoder that has them and gives none for
them); no load-balancing loss (no coefficient given); no
multi-token-prediction head (no key, no width); where a share of the
experts is held, the gates are constants of the backward pass.

``benchmark/references/fedavg_mellum2_lm.py`` holds a copy of everything
below the line of dashes (a test compares the two texts) and then puts
attention a block of queries at a time in ``attention_core``'s place, so
that 16,384 positions fit beside the system's state.
"""

import math

import jax
import jax.numpy as jnp

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
