"""Causal attention of latent-attention heads, as three Pallas kernels
in which a tile of scores lives and dies in VMEM.

Latent attention (MLA) gives every head a query of ``[q_n | q_r]`` and a
key of ``[k_n | k_r]`` where the rope key ``k_r`` is ONE vector per
position shared by all heads, and reads values of another width: at the
published sizes of A.X-K1 scores over 128 + 64 dims, values of 128. So
the five operands stay apart (no ``[T, heads, 192]`` key with the rope
key copied 64 times) and a tile's scores are two products, ``q_n . k_n``
and ``q_r . k_r``, summed in float32.

``causal_attention`` is a ``custom_vjp`` over the whole sequence in
tiles of ``block`` queries by ``block`` keys. Forward: the online-softmax
recurrence over the key tiles up to the diagonal (the tiles above it are
skipped: no product, no copy). Backward, two kernels that compute a
tile's scores again from the rows' log-sum-exp, keys as rows as in
``ops/sparse_attention.py`` (the rows' statistics are lane vectors,
``dK`` / ``dV`` plain products): ``mla_attn_backward_dq`` accumulates
``dQ`` over the key tiles, ``mla_attn_backward_dkv`` accumulates ``dK``
and ``dV`` over the query tiles, each in float32 scratch, so that no
partial sum travels through HBM; the rope key's gradient leaves per head
and is summed outside. The output and the log-sum-exp are named
(``checkpoint_name`` ``attn_out`` / ``attn_lse``): a model that
rematerialises a layer at a time saves those two and runs the forward
kernel once a step. Operands in the model's compute dtype, scores,
softmax and accumulation in float32, ``p`` and ``dS`` rounded once for
their products. Off the chip the kernels run in interpret mode (the CPU
tests run this code).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from colearn_federated_learning_tpu.ops.sparse_attention import (
    _LANES,
    _NEG_BIG,
    _NT,
    _across,
    _call,
)


def _scores(rows, cols, row_tile, col_tile, keys_are_rows: bool,
            scale: float):
    """A tile's float32 scores ``sum of a . b^T over the (a, b) pairs of
    rows x cols`` times ``scale``, masked-out pairs (key after query) at
    ``_NEG_BIG``. ``rows`` / ``cols``: the two sides' (nope, rope)
    blocks; which side holds the keys decides the mask's direction."""
    s = sum(jax.lax.dot_general(a[...], b[...], _NT,
                                preferred_element_type=jnp.float32)
            for a, b in zip(rows, cols)) * scale
    row = row_tile * s.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    col = col_tile * s.shape[1] + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    return jnp.where(row <= col if keys_are_rows else col <= row, s,
                     _NEG_BIG)


def _forward_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, out_ref, lse_ref,
                    m_ref, l_ref, acc_ref, *, scale: float):
    """One tile of one head's queries against one tile of its keys: the
    online-softmax recurrence. The key tiles are the innermost grid
    axis; the running maximum, sum (lane-replicated) and accumulator
    live in scratch. A tile above the diagonal does nothing."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= i)
    def _():
        # key 0 is visible to every query, so after the first tile every
        # row's maximum is a real score
        s = _scores((qn_ref, qr_ref), (kn_ref, kr_ref), i, j, False, scale)
        v = v_ref[...]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - _across(m_new, s.shape[1]))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * _across(alpha, v.shape[1]) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        total = l_ref[...]
        out_ref[...] = (acc_ref[...] / _across(total, out_ref.shape[1])
                        ).astype(out_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(total)


def _p_and_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
              delta_ref, i, j, scale):
    """A tile's normalised weights and the scores' cotangent, keys as
    rows ``[block_k, block_q]``: exactly 0 where masked."""
    s = _scores((kn_ref, kr_ref), (qn_ref, qr_ref), j, i, True, scale)
    p = jnp.exp(s - lse_ref[...])
    dp = jax.lax.dot_general(v_ref[...], do_ref[...], _NT,
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta_ref[...]) * scale


def _backward_dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                        lse_ref, delta_ref, dqn_ref, dqr_ref, dqn_acc,
                        dqr_acc, *, scale: float):
    """``dQ`` of one tile of one head's queries, accumulated over the key
    tiles up to the diagonal (the innermost grid axis)."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dqn_acc[...] = jnp.zeros_like(dqn_acc)
        dqr_acc[...] = jnp.zeros_like(dqr_acc)

    @pl.when(j <= i)
    def _():
        _, ds = _p_and_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                          lse_ref, delta_ref, i, j, scale)
        ds_t = ds.T.astype(kn_ref.dtype)  # the one transposition
        dqn_acc[...] += jnp.dot(ds_t, kn_ref[...],
                                preferred_element_type=jnp.float32)
        dqr_acc[...] += jnp.dot(ds_t, kr_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dqn_ref[...] = dqn_acc[...].astype(dqn_ref.dtype)
        dqr_ref[...] = dqr_acc[...].astype(dqr_ref.dtype)


def _backward_dkv_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                         lse_ref, delta_ref, dkn_ref, dkr_ref, dv_ref,
                         dkn_acc, dkr_acc, dv_acc, *, scale: float):
    """``dK`` (both parts) and ``dV`` of one tile of one head's keys,
    accumulated over the query tiles from the diagonal on (the innermost
    grid axis)."""
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dkn_acc[...] = jnp.zeros_like(dkn_acc)
        dkr_acc[...] = jnp.zeros_like(dkr_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(i >= j)
    def _():
        p, ds = _p_and_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                          lse_ref, delta_ref, i, j, scale)
        ds = ds.astype(qn_ref.dtype)
        dv_acc[...] += jnp.dot(p.astype(do_ref.dtype), do_ref[...],
                               preferred_element_type=jnp.float32)
        dkn_acc[...] += jnp.dot(ds, qn_ref[...],
                                preferred_element_type=jnp.float32)
        dkr_acc[...] += jnp.dot(ds, qr_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dkn_ref[...] = dkn_acc[...].astype(dkn_ref.dtype)
        dkr_ref[...] = dkr_acc[...].astype(dkr_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _rows(x):
    """``[T, heads, d]`` -> ``[T, heads * d]``: one head per ``d``
    columns, the layout the model holds."""
    return x.reshape(x.shape[0], -1)


def _by_head(x):
    """``[T, heads, d]`` -> ``[heads, T, d]``: for the rope parts, whose
    ``d`` is no multiple of the lane width."""
    return x.transpose(1, 0, 2)


def _tiles(q_n, block: int):
    t, heads, _ = q_n.shape
    block = min(block, t)
    if t % block:
        raise ValueError(f"causal_attention: {t} positions are no multiple "
                         f"of the tile of {block}")
    return t, heads, block, t // block


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def causal_attention(q_n, q_r, k_n, k_r, v, scale: float, block: int = 512):
    """``out[t, a] = sum over u <= t of softmax_u(scale (q_n[t, a] .
    k_n[u, a] + q_r[t, a] . k_r[u])) v[u, a]``. ``q_n``, ``k_n``: ``[T,
    heads, d_nope]``; ``q_r``: ``[T, heads, d_rope]``; ``k_r``: ``[T,
    d_rope]``, shared by the heads; ``v``: ``[T, heads, d_v]``; returns
    ``[T, heads, d_v]`` in ``v``'s dtype. ``T`` is a multiple of
    ``min(block, T)``."""
    return _causal_attention_fwd(q_n, q_r, k_n, k_r, v, scale, block)[0]


def _causal_attention_fwd(q_n, q_r, k_n, k_r, v, scale, block):
    t, heads, b, n = _tiles(q_n, block)
    nope, rope, vd = q_n.shape[2], q_r.shape[2], v.shape[2]
    q_side = lambda d: pl.BlockSpec((b, d), lambda h, i, j: (i, h))  # noqa: E731
    k_side = lambda d: pl.BlockSpec(  # noqa: E731
        (b, d), lambda h, i, j: (jnp.minimum(j, i), h))
    out, lse = _call(
        "mla_attn_forward", functools.partial(_forward_kernel, scale=scale),
        (_rows(q_n), _by_head(q_r), _rows(k_n), k_r, _rows(v)),
        [((t, heads * vd), v.dtype), ((heads, t, _LANES), jnp.float32)],
        grid=(heads, n, n),
        in_specs=[
            q_side(nope),
            pl.BlockSpec((None, b, rope), lambda h, i, j: (h, i, 0)),
            k_side(nope),
            pl.BlockSpec((b, rope), lambda h, i, j: (jnp.minimum(j, i), 0)),
            k_side(vd),
        ],
        out_specs=[
            q_side(vd),
            pl.BlockSpec((None, b, _LANES), lambda h, i, j: (h, i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((b, _LANES), jnp.float32),
                        pltpu.VMEM((b, _LANES), jnp.float32),
                        pltpu.VMEM((b, vd), jnp.float32)])
    out = checkpoint_name(out.reshape(t, heads, vd), "attn_out")
    lse = checkpoint_name(lse[:, :, 0], "attn_lse")  # [heads, T]
    return out, (q_n, q_r, k_n, k_r, v, out, lse)


def _causal_attention_bwd(scale, block, res, d_out):
    q_n, q_r, k_n, k_r, v, out, lse = res
    t, heads, b, n = _tiles(q_n, block)
    nope, rope, vd = q_n.shape[2], q_r.shape[2], v.shape[2]
    # rows' sum of d_out . out: the softmax's own term of the cotangent
    delta = (d_out.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1).T
    ins = (_rows(q_n), _by_head(q_r), _rows(k_n), k_r, _rows(v),
           _rows(d_out), lse[:, None, :], delta[:, None, :])
    # dQ: query tile i, key tiles j <= i innermost
    q_tile, k_tile = (lambda h, i, j: i), (lambda h, i, j: jnp.minimum(j, i))
    dq_n, dq_r = _call(
        "mla_attn_backward_dq",
        functools.partial(_backward_dq_kernel, scale=scale),
        ins, [((t, heads * nope), q_n.dtype), ((heads, t, rope), q_r.dtype)],
        grid=(heads, n, n),
        in_specs=_in_specs(b, nope, rope, vd, q_tile, k_tile),
        out_specs=[
            pl.BlockSpec((b, nope), lambda h, i, j: (i, h)),
            pl.BlockSpec((None, b, rope), lambda h, i, j: (h, i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((b, nope), jnp.float32),
                        pltpu.VMEM((b, rope), jnp.float32)])
    # dK, dV: key tile j, query tiles i >= j innermost
    q_tile, k_tile = (lambda h, j, i: jnp.maximum(i, j)), (lambda h, j, i: j)
    dk_n, dk_r, dv = _call(
        "mla_attn_backward_dkv",
        functools.partial(_backward_dkv_kernel, scale=scale),
        ins, [((t, heads * nope), k_n.dtype),
              ((heads, t, rope), jnp.float32), ((t, heads * vd), v.dtype)],
        grid=(heads, n, n),
        in_specs=_in_specs(b, nope, rope, vd, q_tile, k_tile),
        out_specs=[
            pl.BlockSpec((b, nope), lambda h, j, i: (j, h)),
            pl.BlockSpec((None, b, rope), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((b, vd), lambda h, j, i: (j, h)),
        ],
        scratch_shapes=[pltpu.VMEM((b, nope), jnp.float32),
                        pltpu.VMEM((b, rope), jnp.float32),
                        pltpu.VMEM((b, vd), jnp.float32)])
    return (dq_n.reshape(q_n.shape), dq_r.transpose(1, 0, 2),
            dk_n.reshape(k_n.shape), dk_r.sum(0).astype(k_r.dtype),
            dv.reshape(v.shape))


def _in_specs(b, nope, rope, vd, q_tile, k_tile):
    """The eight operands of both backward kernels (q_n, q_r, k_n, k_r,
    v, d_out, log-sum-exp, delta) for a grid ``(head, a, c)`` whose
    query and key tile are ``q_tile(head, a, c)`` / ``k_tile(...)``."""
    def rows(d, tile):
        return pl.BlockSpec((b, d), lambda h, a, c: (tile(h, a, c), h))

    def stats():
        return pl.BlockSpec((None, 1, b),
                            lambda h, a, c: (h, 0, q_tile(h, a, c)))

    return [
        rows(nope, q_tile),
        pl.BlockSpec((None, b, rope), lambda h, a, c: (h, q_tile(h, a, c), 0)),
        rows(nope, k_tile),
        pl.BlockSpec((b, rope), lambda h, a, c: (k_tile(h, a, c), 0)),
        rows(vd, k_tile), rows(vd, q_tile), stats(), stats(),
    ]


causal_attention.defvjp(_causal_attention_fwd, _causal_attention_bwd)
