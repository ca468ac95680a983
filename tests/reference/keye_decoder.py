"""Plain reference of the Keye-VL-2.0-30B-A3B language decoder, written
from the layer equations (ISSUE 25; PERF.md section 4), independent of
``models/`` and ``ops/``: dense ``[T, T]`` scores one head at a time (a scan over the heads), a
stable ``argsort`` for the selection, a Python loop over the held experts
with a mask, ``jax.grad`` for the gradients. One sequence at a time.

``sizes`` is a mapping with the keys of ``benchmark/configs/
keye_vl2_30b_a3b_ep8.json`` ``model``; ``params`` the flat tree the
program's model holds (``layers_<name>`` stacked over the layers, ``embed``, ``final_norm``,
``head``). ``compute`` is the dtype of activations and matrix products
(the configuration's ``dtype_policy.compute``); the router's softmax,
the index scores, the selection, every softmax, the logits and both
losses are float32 whatever it is.

``benchmark/references/fedavg_keye_lm.py`` holds a copy of everything
below the line of dashes (a test compares the two texts).
"""

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------

NEG = -1e30
# what the configuration states as float32 whatever the compute dtype:
# router softmax, index scores and selection, attention softmax, logits.
# (The control ``fedavg_keye_lm_lowered`` sets it to bfloat16 and has to
# come out as not correct: PERF.md section 6. Both losses' own arithmetic,
# from the logits and the index scores on, is float32 even then, so that
# the control reads what lowering does INSIDE the model and not the
# rounding of the loss scalar.)
ISLAND = jnp.float32


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def rotate_half(x, angles):
    """x [T, n, dim], angles [T, dim // 2]."""
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def mrope_angles(positions, dim, theta, sections):
    """positions [3, T] -> [T, dim // 2]: pair i turns by the temporal
    position for i < sections[0], by height for the next sections[1], by
    width for the rest."""
    pairs = dim // 2
    inv = jnp.asarray([theta ** (-2.0 * i / dim) for i in range(pairs)],
                      jnp.float32)
    stream = [s for s, n in enumerate(sections) for _ in range(n)]
    assert len(stream) == pairs
    pos = jnp.stack([positions[stream[i]] for i in range(pairs)], -1)
    return pos.astype(jnp.float32) * inv


def layer_params(params, i):
    """Layer i's leaves: the program stacks every layer's on axis 0."""
    return {k[len("layers_"):]: v[i] for k, v in params.items()
            if k.startswith("layers_")}


def selection(index, topk):
    """keep[t, s]: s <= t and I[t, s] among the topk largest of row t
    over s <= t, ties to the lower s."""
    t = index.shape[0]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    order = jnp.argsort(jnp.where(causal, -index, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return causal & (rank < topk)


def attention(p, x, positions, sizes, compute):
    """(output before the residual, mean_t KL of the indexer, keep)."""
    t = x.shape[0]
    hd, heads, kv = sizes["head_dim"], sizes["heads"], sizes["kv_heads"]
    ih, idim = sizes["index_heads"], sizes["index_head_dim"]
    eps, theta = sizes["rms_eps"], sizes["rope_theta"]
    mat = lambda a, w: jnp.dot(a, w.astype(compute))  # noqa: E731
    h = rms_norm(x, p["attn_norm"], eps)
    angles = mrope_angles(positions, hd, theta, sizes["mrope_section"])
    q = rotate_half(rms_norm(mat(h, p["wq"]).reshape(t, heads, hd),
                             p["q_norm"], eps), angles)
    k = rotate_half(rms_norm(mat(h, p["wk"]).reshape(t, kv, hd),
                             p["k_norm"], eps), angles)
    v = mat(h, p["wv"]).reshape(t, kv, hd)

    # the indexer reads h as a constant
    hc = jax.lax.stop_gradient(h)
    iang = mrope_angles(jnp.stack([positions[0]] * 3), idim, theta,
                        (idim // 2, 0, 0))
    qi = rotate_half(mat(hc, p["idx_wq"]).reshape(t, ih, idim), iang)
    ki = rotate_half(layer_norm(mat(hc, p["idx_wk"]), p["idx_k_norm_scale"],
                                p["idx_k_norm_bias"], eps)[:, None, :],
                     iang)[:, 0, :]
    wi = jnp.dot(hc, p["idx_ww"].astype(compute),
                 preferred_element_type=ISLAND)
    # one index head at a time (a scan, so that one head's [T, T] lives
    # at a time; the head is recomputed in the backward pass)
    @jax.checkpoint
    def index_head(qj, wj):
        dots = jnp.dot(qj, ki.T, preferred_element_type=ISLAND)
        return wj[:, None] * jnp.maximum(dots, 0.0)

    index, _ = jax.lax.scan(
        lambda acc, qw: (acc + index_head(*qw), None),
        jnp.zeros((t, t), ISLAND), (qi.transpose(1, 0, 2), wi.T))
    index = index * (idim ** -0.5) * (ih ** -0.5)
    keep = selection(jax.lax.stop_gradient(index), sizes["index_topk"])

    def head_weights(qh, kh):
        s = jnp.dot(qh, kh.T, preferred_element_type=ISLAND) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(keep, s, NEG), axis=-1)
        return jnp.where(keep, prob, 0.0)

    @jax.checkpoint
    def head_output(qh, kh, vh):
        return jnp.dot(head_weights(qh, kh).astype(compute), vh)

    # one attention head at a time; query head h reads key-value head
    # h // (heads // kv)
    group = jnp.arange(heads) // (heads // kv)
    qh, kh, vh = (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[group],
                  v.transpose(1, 0, 2)[group])
    _, outs = jax.lax.scan(lambda _, a: (None, head_output(*a)), None,
                           (qh, kh, vh))
    out = outs.transpose(1, 0, 2).reshape(t, heads * hd)
    # the indexer's target: the heads' mean attention weights, a constant
    target, _ = jax.lax.scan(
        lambda acc, a: (acc + head_weights(*a) / heads, None),
        jnp.zeros((t, t), ISLAND), jax.lax.stop_gradient((qh, kh)))
    target = target.astype(jnp.float32)
    target = target / target.sum(-1, keepdims=True)
    logq = jax.nn.log_softmax(
        jnp.where(keep, index.astype(jnp.float32), NEG), axis=-1)
    kl = jnp.where(keep & (target > 0),
                   target * (jnp.log(jnp.where(target > 0, target, 1.0))
                             - logq), 0.0).sum(-1)
    return mat(out, p["wo"]), kl.mean(), keep


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
    """One sequence ``tokens`` [T] -> (float32 logits [T, vocab], the sum
    over the layers of the indexer's loss, (keep [layers, T, T], chosen
    [layers, T, top]) of every layer)."""
    t = tokens.shape[0]
    if positions is None:
        positions = jnp.stack([jnp.arange(t)] * 3)
    x = params["embed"][tokens].astype(compute)

    def layer(x, p):
        att, kl, keep = attention(p, x, positions, sizes, compute)
        x = x + att
        y, chosen = experts(p, x, sizes, compute)
        return x + y, (kl, keep, chosen)

    # the program stacks every layer's leaves on axis 0: scan them, one
    # layer at a time, each recomputed in the backward pass
    stacked = {k[len("layers_"):]: v for k, v in params.items()
               if k.startswith("layers_")}
    x, (kl, keep, chosen) = jax.lax.scan(
        jax.checkpoint(layer) if remat else layer, x, stacked)
    index_loss, seen = kl.sum(), (keep, chosen)
    x = rms_norm(x, params["final_norm"], sizes["rms_eps"])
    logits = jnp.dot(x, params["head"].astype(compute),
                     preferred_element_type=ISLAND)
    return logits, index_loss, seen


def losses(params, tokens, targets, sizes, compute):
    """(L_LM + L_I, (L_LM, L_I)) of one sequence."""
    logits, index_loss, _ = forward(params, tokens, sizes, compute)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lm = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].mean()
    return lm + index_loss, (lm, index_loss)
