"""Learned key selection inside causal attention (the published
DeepSeek-Sparse-Attention scheme): an indexer scores every causal
query-key pair, each query keeps its ``topk`` best keys, and grouped-query
attention runs over that set only.

Everything works on one chunk of queries at a time against the keys the
chunk can see (static extents), so no ``[T, T]`` array per head is ever
held: a chunk's index scores are ``[chunk, keys]`` float32, its attention
scores ``[heads, chunk, keys]``. The four pieces are separate functions so
that the model can put them under the scopes ``attn_indexer``,
``attn_select`` and ``attn_sparse``:

- :func:`index_scores`: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
  times the two scale factors, accumulated in float32.
- :func:`select_topk`: the exact set of the ``topk`` largest ``I[t, s]``
  over ``s <= t`` (all of them while ``t < topk``), ties to the lower
  ``s``: a k-th-value threshold found by bisection over the bits of the
  float32 scores (32 counting passes, no sort), then the first ties.
- :func:`selected_attention`: softmax attention over the kept pairs, with
  grouped key-value heads; also returns the attention weights averaged
  over the heads, the indexer's training target.
- :func:`index_kl`: ``KL(p || softmax over the kept pairs of I)`` summed
  over the chunk's queries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from colearn_federated_learning_tpu.utils.trees import zeros_varying_like

_NEG_BIG = -1e30


def index_scores(q_idx, k_idx, w_idx):
    """``q_idx``: ``[Tq, J, d]``; ``k_idx``: ``[Tk, d]`` (one key head);
    ``w_idx``: ``[Tq, J]`` float32 -> ``[Tq, Tk]`` float32."""
    j, d = q_idx.shape[1], q_idx.shape[2]
    dots = jnp.einsum("qjd,kd->jqk", q_idx, k_idx,
                      preferred_element_type=jnp.float32)
    scale = jnp.float32(d ** -0.5 * j ** -0.5)
    w = w_idx.astype(jnp.float32).T[:, :, None] * scale
    # + 0.0: a sum of negative zeros is -0.0, which would order below
    # +0.0 in the bit pattern the selection bisects over
    return (jax.nn.relu(dots) * w).sum(0) + 0.0


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    flipped = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(flipped, jnp.uint32) ^ jnp.uint32(
        0x80000000)


def select_topk(scores, causal, topk: int):
    """Keep mask ``[Tq, Tk]`` of the ``topk`` largest ``scores`` per row
    among ``causal`` pairs, ties to the lower column; every causal pair
    of a row that has at most ``topk`` of them."""
    if scores.shape[1] <= topk:
        return causal
    # 0 is below every real score's pattern (-inf maps to 0x007FFFFF)
    u = jnp.where(causal, _ordered_bits(scores), jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = (u >= cand[:, None]).sum(-1, dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, thr)

    # the largest value that at least topk entries of the row reach:
    # the row's topk-th largest (0 for a row with fewer causal pairs)
    thr = jax.lax.fori_loop(0, 32, bit, zeros_varying_like(u[:, 0]))
    above = u > thr[:, None]
    tie = u == thr[:, None]
    room = topk - above.sum(-1, dtype=jnp.int32)
    first_ties = jnp.cumsum(tie, axis=-1, dtype=jnp.int32) <= room[:, None]
    return causal & (above | (tie & first_ties))


def _scores(q, k, keep):
    tq, h, hd = q.shape
    g = k.shape[1]
    qg = q.reshape(tq, g, h // g, hd)
    s = jnp.einsum("qgrd,kgd->grqk", qg, k,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    return qg, jnp.where(keep[None, None], s, _NEG_BIG)


@jax.custom_vjp
def selected_attention(q, k, v, keep):
    """``q``: ``[Tq, H, hd]``; ``k``, ``v``: ``[Tk, G, hd]`` with ``H`` a
    multiple of ``G`` (query head ``h`` reads key-value head ``h // (H //
    G)``); ``keep``: ``[Tq, Tk]`` bool, at least one key kept per query.
    Scores, their exponentials and the row sums are float32. Returns
    (``[Tq, H * hd]`` in ``q``'s dtype, ``[Tq, Tk]`` float32 attention
    weights averaged over the heads: the indexer's target, a constant
    that carries no gradient).

    The ``[H, Tq, Tk]`` arrays are what this costs on the chip (every
    pass over them is HBM traffic), so the softmax is never normalised
    at that size: the exponentials are rounded to ``v``'s dtype once,
    their product with ``v`` is divided by the row sums, and the heads'
    mean reads the same rounded array. The backward pass is written out
    for the same reason: it keeps the output and the rows' log-sum-exp
    (``checkpoint_name`` ``attn_out`` / ``attn_lse``, so that a caller's
    rematerialisation policy can keep them and skip this forward pass),
    recomputes the scores once and needs neither a second row maximum
    nor a second product with ``v``."""
    return _selected_attention_fwd(q, k, v, keep)[0]


def _selected_attention_fwd(q, k, v, keep):
    tq, h, hd = q.shape
    _, s = _scores(q, k, keep)
    top = s.max(-1, keepdims=True)
    e = jnp.exp(s - top)  # exactly 0 where masked
    total = e.sum(-1)  # [G, R, Tq]
    inv = 1.0 / total
    e = e.astype(v.dtype)
    out = jnp.einsum("grqk,kgd->qgrd", e, v,
                     preferred_element_type=jnp.float32)
    out = (out * inv.transpose(2, 0, 1)[..., None]).astype(q.dtype)
    weights = jnp.einsum("grqk,grq->qk", e, inv.astype(e.dtype),
                         preferred_element_type=jnp.float32) / h
    out = checkpoint_name(out.reshape(tq, h * hd), "attn_out")
    lse = checkpoint_name(top[..., 0] + jnp.log(total), "attn_lse")
    return (out, weights), (q, k, v, keep, out, lse)


def _selected_attention_bwd(res, cotangents):
    q, k, v, keep, out, lse = res
    d_out = cotangents[0]  # the weights are a constant target
    tq, h, hd = q.shape
    qg, s = _scores(q, k, keep)
    p = jnp.exp(s - lse[..., None])  # normalised; exactly 0 where masked
    do = d_out.reshape(qg.shape)
    dp = jnp.einsum("qgrd,kgd->grqk", do, v,
                    preferred_element_type=jnp.float32)
    delta = (do.astype(jnp.float32)
             * out.reshape(qg.shape).astype(jnp.float32)).sum(-1)
    ds = (p * (dp - delta.transpose(1, 2, 0)[..., None])
          * (hd ** -0.5)).astype(q.dtype)
    dv = jnp.einsum("grqk,qgrd->kgd", p.astype(v.dtype), do,
                    preferred_element_type=jnp.float32)
    dq = jnp.einsum("grqk,kgd->qgrd", ds, k,
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("grqk,qgrd->kgd", ds, qg,
                    preferred_element_type=jnp.float32)
    return (dq.reshape(q.shape).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), None)


selected_attention.defvjp(_selected_attention_fwd, _selected_attention_bwd)


def index_kl(scores, keep, target):
    """``sum_t KL(target[t, .] || softmax over keep[t, .] of scores[t,
    .])``; ``target`` is constant (the caller stops its gradient)."""
    logq = jax.nn.log_softmax(jnp.where(keep, scores, _NEG_BIG), axis=-1)
    safe = jnp.where(target > 0, target, 1.0)
    return jnp.where(keep & (target > 0),
                     target * (jnp.log(safe) - logq), 0.0).sum()
