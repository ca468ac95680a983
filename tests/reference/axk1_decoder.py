"""Plain reference of the A.X-K1 decoder with rank-r adapters on the
projections of its latent attention, written from the layer equations
(ISSUE 29; PERF.md section 4), independent of ``models/`` and ``ops/``:
the merged weight ``W + (alpha / r) A B`` formed outright in float32,
dense ``[T, T]`` scores one head at a time (a scan over the heads), a
stable ``argsort`` for the groups and for the experts, a loop over the
held experts with a mask, ``jax.grad`` with respect to the adapters.
One sequence at a time.

``frozen`` is the base as the program stores it (``dense_<leaf>`` of the
leading dense layer, ``layers_<leaf>`` stacked over the expert layers,
``embed``, ``final_norm``, ``head``) and ``adapters`` the tree
``{"dense_wqa": {"lora_a", "lora_b"}, ..., "layers_wo": {...}}`` over
the five projections ``wqa``, ``wqb``, ``wkva``, ``wkvb``, ``wo``.
``sizes`` holds the model's sizes under the names of ``models/axk1.py``'s
factory, with ``lora_rank`` and ``lora_alpha``. Activations and products
against frozen weights run in ``compute``; a product against a merged
weight multiplies in float32 (the merged weight differs from the frozen
one by less than a bfloat16 ulp) and rounds once to ``compute``.

Everything between the two lines of dashes is copied into
``benchmark/references/fedavg_axk1_lora.py`` (a test compares the texts).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------

NEG = -1e30
PROJECTIONS = ("wqa", "wqb", "wkva", "wkvb", "wo")
# what the configuration states as float32 whatever the compute dtype:
# the router's sigmoid scores and the group and expert selection, the
# attention softmax, the logits. (The control ``fedavg_axk1_lora_lowered``
# sets it to bfloat16 and has to come out as not correct. The loss's own
# arithmetic, from the logits on, is float32 even then.)
ISLAND = jnp.float32


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rotate_half(x, angles):
    """x [T, n, dim], angles [T, dim // 2]."""
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def yarn_frequencies(sizes):
    """The ``qk_rope // 2`` inverse frequencies as Python floats, and
    (low, high) of the blend."""
    dim, theta = sizes["qk_rope"], sizes["rope_theta"]
    factor, original = sizes["rope_factor"], sizes["rope_original"]

    def c(n):  # the pair that turns n times over the original context
        return dim * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(c(sizes["rope_beta_fast"])), 0)
    high = min(math.ceil(c(sizes["rope_beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f * (1.0 - r) + (f / factor) * r)
    return out, (low, high)


def attention_scale(sizes):
    m = 0.1 * sizes["rope_mscale_all_dim"] * math.log(sizes["rope_factor"]) + 1
    return (sizes["qk_nope"] + sizes["qk_rope"]) ** -0.5 * m * m


def merged(w, ad, sizes):
    """W + (alpha / r) A B, in float32."""
    delta = jnp.dot(ad["lora_a"].astype(jnp.float32),
                    ad["lora_b"].astype(jnp.float32))
    return w.astype(jnp.float32) + (
        sizes["lora_alpha"] / sizes["lora_rank"]) * delta


def adapted(x, w, ad, sizes):
    """x through the adapted projection: float32 product, one rounding."""
    if ad is None:
        return jnp.dot(x, w.astype(x.dtype))
    return jnp.dot(x.astype(jnp.float32), merged(w, ad, sizes)).astype(x.dtype)


def swiglu(h, w1, w3, w2):
    cd = h.dtype
    mid = jax.nn.silu(jnp.dot(h, w1.astype(cd))) * jnp.dot(h, w3.astype(cd))
    return jnp.dot(mid, w2.astype(cd))


def attention(p, ad, x, sizes):
    """Latent attention's output before the residual. ``ad``: this
    layer's adapters by projection, or None for the base model."""
    t = x.shape[0]
    heads, nope, rope = sizes["heads"], sizes["qk_nope"], sizes["qk_rope"]
    vd, kvr, eps = sizes["v_dim"], sizes["kv_rank"], sizes["rms_eps"]
    compute = x.dtype
    proj = lambda a, n: adapted(  # noqa: E731
        a, p[n], None if ad is None else ad[n], sizes)
    freqs, _ = yarn_frequencies(sizes)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)
    h = rms_norm(x, p["attn_norm"], eps)
    c_q = rms_norm(proj(h, "wqa"), p["q_norm"], eps)
    q = proj(c_q, "wqb").reshape(t, heads, nope + rope)
    q_n, q_r = q[..., :nope], rotate_half(q[..., nope:], angles)
    kva = proj(h, "wkva")
    c_kv = rms_norm(kva[:, :kvr], p["kv_norm"], eps)
    k_r = rotate_half(kva[:, None, kvr:], angles)[:, 0]  # one per position
    kv = proj(c_kv, "wkvb").reshape(t, heads, nope + vd)
    k_n, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scale = attention_scale(sizes)

    @jax.checkpoint
    def head(qn, qr, kn, vh):  # one head's [T, T] at a time
        s = (jnp.dot(qn, kn.T, preferred_element_type=ISLAND)
             + jnp.dot(qr, k_r.T, preferred_element_type=ISLAND)) * scale
        prob = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
        return jnp.dot(prob.astype(compute), vh)

    _, outs = jax.lax.scan(
        lambda _, a: (None, head(*a)), None,
        tuple(a.transpose(1, 0, 2) for a in (q_n, q_r, k_n, v)))
    return proj(outs.transpose(1, 0, 2).reshape(t, heads * vd), "wo")


def routing(h, router, sizes):
    """(gates [T, top] over the chosen, chosen [T, top], kept groups
    [T, topk_group]): sigmoid scores; a group's score is the sum of its
    two largest; the best groups, then the best experts among them, ties
    to the lower index; gates renormalised over the chosen and scaled."""
    n, g = sizes["num_experts"], sizes["n_group"]
    top, kept = sizes["experts_per_token"], sizes["topk_group"]
    sigma = jax.nn.sigmoid(jnp.dot(h, router.astype(h.dtype),
                                   preferred_element_type=ISLAND))
    t = sigma.shape[0]
    grouped = sigma.reshape(t, g, n // g)
    best_two = -jnp.sort(-grouped, axis=-1)[..., :2]
    groups = jnp.argsort(-best_two.sum(-1), axis=-1, stable=True)[:, :kept]
    allowed = (jnp.arange(g)[None, :, None] == groups[:, None, :]).any(-1)
    allowed = jnp.repeat(allowed, n // g, axis=-1)
    chosen = jnp.argsort(jnp.where(allowed, -sigma, jnp.inf), axis=-1,
                         stable=True)[:, :top]
    s_top = jnp.take_along_axis(sigma, chosen, axis=-1).astype(jnp.float32)
    gates = sizes["gate_scale"] * s_top / s_top.sum(-1, keepdims=True)
    return gates, chosen, groups


def experts(p, x, sizes, experts_held=None, expert_offset=None):
    """The routed layer's part that the held experts give (WITHOUT the
    shared expert), each token's experts and its kept groups."""
    held = sizes["experts_held"] if experts_held is None else experts_held
    offset = sizes["expert_offset"] if expert_offset is None else expert_offset
    compute = x.dtype
    h = rms_norm(x, p["mlp_norm"], sizes["rms_eps"])
    gates, chosen, groups = routing(h, p["router"], sizes)
    if held < sizes["num_experts"]:
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

    def add_expert(y, ew):  # every token through every held expert, masked
        e, w1, w3, w2 = ew
        gate = jnp.where(chosen == offset + e, gates, 0.0).sum(-1)
        return y + one_expert(h, w1, w3, w2, gate), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros(x.shape, jnp.float32),
                        (jnp.arange(held), p["w1"], p["w3"], p["w2"]))
    return y.astype(compute), chosen, groups


def shared_expert(p, x, sizes):
    h = rms_norm(x, p["mlp_norm"], sizes["rms_eps"])
    return swiglu(h, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def _sub(tree, prefix):
    return {k[len(prefix):]: v for k, v in tree.items()
            if k.startswith(prefix)}


def forward(frozen, adapters, tokens, sizes, compute, remat=True):
    """One sequence ``tokens`` [T] -> (float32 logits [T, vocab], (kept
    groups [layers - 1, T, topk_group], chosen [layers - 1, T, top])).
    ``adapters`` None: the base model."""
    x = frozen["embed"][tokens].astype(compute)

    def dense_layer(x, p, ad):
        x = x + attention(p, ad, x, sizes)
        h = rms_norm(x, p["mlp_norm"], sizes["rms_eps"])
        return x + swiglu(h, p["w1"], p["w3"], p["w2"])

    def expert_layer(x, p_ad):
        p, ad = p_ad
        x = x + attention(p, ad, x, sizes)
        y, chosen, groups = experts(p, x, sizes)
        return x + shared_expert(p, x, sizes) + y, (groups, chosen)

    ad0 = ads = None
    if adapters is not None:
        ad0, ads = _sub(adapters, "dense_"), _sub(adapters, "layers_")
    x = (jax.checkpoint(dense_layer) if remat else dense_layer)(
        x, _sub(frozen, "dense_"), ad0)
    # the program stacks the expert layers' leaves on axis 0: scan them,
    # one layer at a time, each recomputed in the backward pass
    x, seen = jax.lax.scan(
        jax.checkpoint(expert_layer) if remat else expert_layer, x,
        (_sub(frozen, "layers_"), ads))
    x = rms_norm(x, frozen["final_norm"], sizes["rms_eps"])
    logits = jnp.dot(x, frozen["head"].astype(compute),
                     preferred_element_type=ISLAND)
    return logits, seen


def loss(adapters, frozen, tokens, targets, sizes, compute):
    """Mean token cross-entropy of one sequence."""
    logits, _ = forward(frozen, adapters, tokens, sizes, compute)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].mean()

# ---------------------------------------------------------------------------
