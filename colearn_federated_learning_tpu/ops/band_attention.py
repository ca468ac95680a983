"""Causal grouped-query attention inside a band, as three Pallas kernels
in which a tile of scores lives and dies in VMEM.

A query at position ``t`` reads the keys ``u`` with ``u <= t`` and, where
``window`` is given, ``t - u < window`` (itself and the ``window - 1``
positions before it); ``window=None`` is the whole causal triangle. The
``heads // kv_heads`` query heads of a group read one key-value head, so
a grid step holds one tile of a group's keys and values and loops over
the group's query heads (``ops/sparse_attention._heads``): a key tile is
fetched once a group, and ``dK`` / ``dV`` are summed over the group's
heads where they are accumulated.

``band_attention`` is a ``custom_vjp`` over the whole sequence in tiles
of ``block`` queries by ``block`` keys. A query tile meets the
``band_tiles`` key tiles from the diagonal one downwards and no other: a
tile that lies wholly above the diagonal or wholly below the band is
neither computed nor fetched (the grid's innermost axis has
``band_tiles`` steps, not ``T / block``, and a step that would leave the
sequence repeats the block index of the one before it). Forward: the
online-softmax recurrence, the diagonal tile first, so that every row's
maximum is a real score from the first step on. Backward, two kernels
that compute a tile's scores again from the rows' log-sum-exp, keys as
rows as in ``ops/latent_attention.py`` (the rows' statistics are lane
vectors, ``dK`` / ``dV`` plain products): ``band_attn_backward_dq``
accumulates ``dQ`` over the band's key tiles, ``band_attn_backward_dkv``
accumulates ``dK`` and ``dV`` over the query tiles from the diagonal on
and over the group's heads, each in float32 scratch, so that no partial
sum and no ``[T, T]`` array travels through HBM. The output and the
log-sum-exp are named (``checkpoint_name`` ``attn_out`` / ``attn_lse``):
a model that rematerialises a layer at a time saves those two and runs
the forward kernel once a step. Operands in the model's compute dtype,
scores, softmax and accumulation in float32, ``p`` and ``dS`` rounded
once for their products. Off the chip the kernels run in interpret mode
(the CPU tests run this code).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from colearn_federated_learning_tpu.ops.latent_attention import _rows
from colearn_federated_learning_tpu.ops.sparse_attention import (
    _LANES,
    _NEG_BIG,
    _NT,
    _across,
    _call,
    _heads,
)


def band_tiles(n: int, block: int, window: Optional[int]) -> int:
    """How many key tiles a query tile meets, the diagonal one included:
    all ``n`` without a window, else the tiles that hold a key within
    ``window - 1`` positions before the tile's first query."""
    if window is None:
        return n
    return min(n, -(-(window - 1) // block) + 1)


def kept_pairs(t: int, window: Optional[int]) -> int:
    """Query-key pairs inside the band: sum over ``t`` of ``min(t + 1,
    window)``."""
    w = t if window is None else min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def visited_pairs(t: int, block: int, window: Optional[int]) -> int:
    """Query-key pairs of the tiles the kernels visit."""
    b = min(block, t)
    n = t // b
    reach = band_tiles(n, b, window)
    return sum(min(i + 1, reach) for i in range(n)) * b * b


def _kept(q_tile, k_tile, shape, keys_are_rows: bool, window):
    """A square tile's mask: key at or before the query and, under a
    window, fewer than ``window`` positions before it. Which side holds
    the keys decides which axis counts them."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    key, query = (rows, cols) if keys_are_rows else (cols, rows)
    ahead = (q_tile - k_tile) * shape[0] + query - key
    kept = ahead >= 0
    return kept if window is None else kept & (ahead < window)


def _forward_kernel(q_ref, k_ref, v_ref, out_ref, lse_ref, m_ref, l_ref,
                    acc_ref, *, rep: int, hd: int, scale: float, window):
    """One tile of a group's queries against key tile ``i - c`` (step
    ``c`` of the innermost grid axis: the diagonal tile first, then
    downwards through the band): the online-softmax recurrence of each
    of the group's ``rep`` query heads. The running maximum, sum
    (lane-replicated) and accumulator live in scratch. A step that would
    leave the sequence does nothing."""
    i, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c <= i)
    def _():
        k, v = k_ref[...], v_ref[...]
        # every head's mask; a query keeps itself, so after the diagonal
        # tile every row's maximum is a real score and a row that keeps
        # nothing of a later tile adds exp(_NEG_BIG - m) = 0
        kept = _kept(i, i - c, (q_ref.shape[0], k.shape[0]), False, window)

        def head(r, cols, _):
            s = jax.lax.dot_general(
                q_ref[:, cols], k, _NT,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(kept, s, _NEG_BIG)
            m_prev = m_ref[r]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - _across(m_new, s.shape[1]))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r] = alpha * l_ref[r] + p.sum(-1, keepdims=True)
            m_ref[r] = m_new
            acc_ref[r] = acc_ref[r] * _across(alpha, hd) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

        _heads(rep, hd, head)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        def write(r, cols, _):
            total = l_ref[r]
            out_ref[:, cols] = (
                acc_ref[r] / _across(total, hd)).astype(out_ref.dtype)
            lse_ref[r] = m_ref[r] + jnp.log(total)

        _heads(rep, hd, write)


def _p_and_ds(q, k, v, do, lse, delta, kept, scale):
    """One head's normalised weights and the scores' cotangent for a
    tile, keys as rows ``[block_k, block_q]``: exactly 0 where masked."""
    s = jax.lax.dot_general(k, q, _NT,
                            preferred_element_type=jnp.float32) * scale
    p = jnp.exp(jnp.where(kept, s, _NEG_BIG) - lse)
    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * scale


def _backward_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_acc, *, rep: int, hd: int, scale: float,
                        window):
    """``dQ`` of one tile of a group's queries, accumulated over the
    band's key tiles ``i - c`` (the innermost grid axis)."""
    i, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(c <= i)
    def _():
        k, v = k_ref[...], v_ref[...]
        kept = _kept(i, i - c, (k.shape[0], q_ref.shape[0]), True, window)

        def head(r, cols, _):
            _, ds = _p_and_ds(q_ref[:, cols], k, v, do_ref[:, cols],
                              lse_ref[pl.ds(r, 1), :],
                              delta_ref[pl.ds(r, 1), :], kept, scale)
            # the one transposition
            dq_acc[:, cols] += jnp.dot(ds.T.astype(k.dtype), k,
                                       preferred_element_type=jnp.float32)

        _heads(rep, hd, head)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _backward_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc, *, rep: int,
                         hd: int, scale: float, window, n: int):
    """``dK`` and ``dV`` of one tile of a group's keys, accumulated over
    the query tiles ``j + c`` that meet it (the innermost grid axis) and
    over the group's ``rep`` query heads."""
    j, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j + c < n)
    def _():
        k, v = k_ref[...], v_ref[...]
        kept = _kept(j + c, j, (k.shape[0], q_ref.shape[0]), True, window)

        def head(r, cols, _):
            q, do = q_ref[:, cols], do_ref[:, cols]
            p, ds = _p_and_ds(q, k, v, do, lse_ref[pl.ds(r, 1), :],
                              delta_ref[pl.ds(r, 1), :], kept, scale)
            dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dk_acc[...] += jnp.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

        _heads(rep, hd, head)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _tiles(q, k, block: int, window):
    """(positions, key-value groups, query heads a group, head width,
    tile, tiles a side, key tiles a query tile meets)."""
    t, heads, hd = q.shape
    groups = k.shape[1]
    if heads % groups:
        raise ValueError(f"band_attention: {heads} query heads are no "
                         f"multiple of {groups} key-value heads")
    if window is not None and window < 1:
        raise ValueError(f"band_attention: window {window} keeps no key")
    b = min(block, t)
    if t % b:
        raise ValueError(f"band_attention: {t} positions are no multiple "
                         f"of the tile of {b}")
    return t, groups, heads // groups, hd, b, t // b, band_tiles(t // b, b,
                                                                 window)


def _specs(b: int, rep: int, hd: int, q_tile, k_tile):
    """Block specs on a grid ``(group, a, c)`` whose query and key tile
    are ``q_tile(a, c)`` / ``k_tile(a, c)``: a group's query-side rows
    ``[b, rep * hd]``, its key-side rows ``[b, hd]``, and the query
    side's statistics ``[rep, b]`` as lane vectors."""
    rows = pl.BlockSpec((b, rep * hd), lambda g, a, c: (q_tile(a, c), g))
    keys = pl.BlockSpec((b, hd), lambda g, a, c: (k_tile(a, c), g))
    stats = pl.BlockSpec((None, rep, b), lambda g, a, c: (g, 0, q_tile(a, c)))
    return rows, keys, stats


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def band_attention(q, k, v, window: Optional[int], scale: float,
                   block: int = 512):
    """``out[t, a] = sum over the kept u of softmax_u(scale q[t, a] .
    k[u, a // rep]) v[u, a // rep]``, kept where ``u <= t`` and (with a
    ``window``) ``t - u < window``. ``q``: ``[T, heads, d]``; ``k``,
    ``v``: ``[T, kv_heads, d]`` with ``rep = heads // kv_heads``;
    returns ``[T, heads, d]`` in ``v``'s dtype. ``T`` is a multiple of
    ``min(block, T)``."""
    return _band_attention_fwd(q, k, v, window, scale, block)[0]


def _band_attention_fwd(q, k, v, window, scale, block):
    t, groups, rep, hd, b, n, reach = _tiles(q, k, block, window)
    sizes = dict(rep=rep, hd=hd, scale=scale, window=window)
    rows, keys, _ = _specs(b, rep, hd, lambda i, c: i,
                           lambda i, c: jnp.maximum(i - c, 0))
    out, lse = _call(
        "band_attn_forward", functools.partial(_forward_kernel, **sizes),
        (_rows(q), _rows(k), _rows(v)),
        [((t, groups * rep * hd), v.dtype),
         ((groups * rep, t, _LANES), jnp.float32)],
        grid=(groups, n, reach),
        in_specs=[rows, keys, keys],
        out_specs=[rows, pl.BlockSpec((rep, b, _LANES),
                                      lambda g, i, c: (g, i, 0))],
        scratch_shapes=[pltpu.VMEM((rep, b, _LANES), jnp.float32),
                        pltpu.VMEM((rep, b, _LANES), jnp.float32),
                        pltpu.VMEM((rep, b, hd), jnp.float32)])
    out = checkpoint_name(out.reshape(q.shape), "attn_out")
    lse = checkpoint_name(lse[:, :, 0].reshape(groups, rep, t), "attn_lse")
    return out, (q, k, v, out, lse)


def _band_attention_bwd(window, scale, block, res, d_out):
    q, k, v, out, lse = res
    t, groups, rep, hd, b, n, reach = _tiles(q, k, block, window)
    sizes = dict(rep=rep, hd=hd, scale=scale, window=window)
    # rows' sum of d_out . out: the softmax's own term of the cotangent
    delta = (d_out.astype(jnp.float32) * out.astype(jnp.float32)).sum(
        -1).T.reshape(groups, rep, t)
    ins = (_rows(q), _rows(k), _rows(v), _rows(d_out), lse, delta)
    # dQ: query tile i, the band's key tiles i - c innermost
    rows, keys, stats = _specs(b, rep, hd, lambda i, c: i,
                               lambda i, c: jnp.maximum(i - c, 0))
    dq, = _call(
        "band_attn_backward_dq",
        functools.partial(_backward_dq_kernel, **sizes), ins,
        [((t, groups * rep * hd), q.dtype)], grid=(groups, n, reach),
        in_specs=[rows, keys, keys, rows, stats, stats], out_specs=[rows],
        scratch_shapes=[pltpu.VMEM((b, rep * hd), jnp.float32)])
    # dK, dV: key tile j, the query tiles j + c that meet it innermost
    rows, keys, stats = _specs(b, rep, hd,
                               lambda j, c: jnp.minimum(j + c, n - 1),
                               lambda j, c: j)
    dk, dv = _call(
        "band_attn_backward_dkv",
        functools.partial(_backward_dkv_kernel, n=n, **sizes), ins,
        [((t, groups * hd), k.dtype), ((t, groups * hd), v.dtype)],
        grid=(groups, n, reach),
        in_specs=[rows, keys, keys, rows, stats, stats],
        out_specs=[keys, keys],
        scratch_shapes=[pltpu.VMEM((b, hd), jnp.float32),
                        pltpu.VMEM((b, hd), jnp.float32)])
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


band_attention.defvjp(_band_attention_fwd, _band_attention_bwd)
