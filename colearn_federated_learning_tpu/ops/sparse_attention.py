"""Learned key selection inside causal attention (the published
DeepSeek-Sparse-Attention scheme): an indexer scores every causal
query-key pair, each query keeps its ``topk`` best keys, and grouped-query
attention runs over that set only.

Everything works on one chunk of queries at a time against the keys the
chunk can see (static extents), so no ``[T, T]`` array per head is ever
held: a chunk's index scores are ``[chunk, keys]`` float32, and its
per-head scores, the indexer's and attention's, exist one ``[block_k,
block_q]`` or ``[block_q, block_k]`` tile of one head at a time, in VMEM.
The four pieces are separate functions so that the model can put them
under the scopes ``attn_indexer``, ``attn_select`` and ``attn_sparse``:

- :func:`index_scores`: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
  times the two scale factors, accumulated in float32. Two Pallas TPU
  kernels, the forward pass and (its own differentiation rule) the
  backward pass, which recomputes a tile's dots.
- :func:`select_topk`: the exact set of the ``topk`` largest ``I[t, s]``
  over ``s <= t`` (all of them while ``t < topk``), ties to the lower
  ``s``: a k-th-value threshold found by bisection over the bits of the
  float32 scores (32 counting passes, no sort), then the first ties.
- :func:`selected_attention`: softmax attention over the kept pairs, with
  grouped key-value heads; also returns the attention weights averaged
  over the heads, the indexer's training target. Three Pallas TPU
  kernels (forward, heads' mean, backward); off the chip they run in
  interpret mode.
- :func:`index_kl`: ``KL(p || softmax over the kept pairs of I)`` summed
  over the chunk's queries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from colearn_federated_learning_tpu.ops.pallas_apply import out_struct
from colearn_federated_learning_tpu.utils.trees import zeros_varying_like

_NEG_BIG = -1e30
_LANES = 128
_VMEM_LIMIT = 96 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))  # a [m, d] . b [n, d] -> [m, n]


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


def _interpret():
    """Off the chip the kernels run in Pallas interpret mode (exact,
    slow), as ``ops/pallas_apply.py``'s do: the CPU tests run this code."""
    return jax.default_backend() != "tpu"


def _across(x, n: int):
    """``[rows, _LANES]`` with every lane of a row alike -> ``[rows, n]``."""
    if n % x.shape[1] == 0:
        return jnp.tile(x, (1, n // x.shape[1]))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _heads(rep: int, hd: int, body, carry=None):
    """``body(r, that head's hd columns of a [rows, rep * hd] block,
    carry)`` for the ``rep`` query heads of a key-value group (for the
    slabs of the indexer's heads: :func:`_index_slab`). A loop the
    lowering unrolls: a Python loop has every head traced (the round
    program's warm compile-or-load 16.3 s against 14.5, PERF.md PR 26:
    16 chunks x 3 kernels are traced in every process), a rolled loop
    keeps one head's products from overlapping the next one's vector work
    (2.32 ms against 1.81 for the three kernels at 8,192 keys)."""
    return jax.lax.fori_loop(
        0, rep,
        lambda r, c: body(r, pl.ds(pl.multiple_of(r * hd, hd), hd), c),
        carry, unroll=True)


def _forward_kernel(q_ref, k_ref, v_ref, keep_ref, out_ref, lse_ref,
                    m_ref, l_ref, acc_ref, *, rep: int, hd: int,
                    scale: float):
    """One tile of queries of one key-value group against one tile of its
    keys: the online-softmax recurrence of each of the group's ``rep``
    query heads. The key tiles are the innermost grid axis; the running
    maximum, sum (lane-replicated) and accumulator live in scratch."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kept = keep_ref[...] != 0  # [block_q, block_k], every head's mask
    k, v = k_ref[...], v_ref[...]

    def head(r, cols, _):
        s = jax.lax.dot_general(
            q_ref[:, cols], k, _NT,
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(kept, s, _NEG_BIG)
        m_prev = m_ref[r]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        # selection is scattered: while a row's first kept key lies in a
        # later tile its maximum is still _NEG_BIG and exp(s - m) is 1 on
        # the masked pairs, so p is masked itself
        p = jnp.where(kept, jnp.exp(s - _across(m_new, s.shape[1])), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[r] = alpha * l_ref[r] + p.sum(-1, keepdims=True)
        m_ref[r] = m_new
        acc_ref[r] = acc_ref[r] * _across(alpha, hd) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _heads(rep, hd, head)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        def write(r, cols, _):
            total = l_ref[r]
            out_ref[:, cols] = (
                acc_ref[r] / _across(total, hd)).astype(out_ref.dtype)
            lse_ref[r] = m_ref[r] + jnp.log(total)

        _heads(rep, hd, write)


def _head_mean_kernel(q_ref, k_ref, lse_ref, keep_ref, w_ref, *, rep: int,
                      hd: int, scale: float, heads: int):
    """The heads' mean of the attention weights of one ``[block_q,
    block_k]`` tile: the scores again, normalised by each head's final
    log-sum-exp, summed over the group's heads here and over the groups
    along the innermost grid axis. The mask goes on once, over the sum
    (an unkept pair's exponential may be anything, inf included)."""
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _():
        w_ref[...] = jnp.zeros_like(w_ref)

    k = k_ref[...]

    def head(r, cols, total):
        s = jax.lax.dot_general(
            q_ref[:, cols], k, _NT,
            preferred_element_type=jnp.float32) * scale
        return total + jnp.exp(s - _across(lse_ref[r], s.shape[1]))

    # the sum starts from zeros of its own and not from the block, whose
    # reads carry the mesh axes the operands vary over; a loop carry does not
    total = w_ref[...] + _heads(rep, hd, head, jnp.zeros(w_ref.shape,
                                                         jnp.float32))
    w_ref[...] = total

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        w_ref[...] = jnp.where(keep_ref[...] != 0, total / heads, 0.0)


def _backward_kernel(q_ref, k_ref, v_ref, keep_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, *, rep: int,
                     hd: int, scale: float):
    """One tile of keys of one group against one tile of its queries,
    keys as rows (``keep_ref`` is the mask transposed), so that the
    rows' log-sum-exp and ``delta`` are lane vectors and ``dK`` / ``dV``
    are plain products: the scores once, ``p``, ``dP``, ``dS`` in VMEM,
    ``dK`` and ``dV`` summed over the group's heads, ``dQ`` accumulated
    over the key tiles (the innermost grid axis) in float32 scratch."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    kept = keep_ref[...] != 0  # [block_k, block_q]
    k, v = k_ref[...], v_ref[...]

    def head(r, cols, sums):
        q, do = q_ref[:, cols], do_ref[:, cols]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32) * scale
        # normalised; exactly 0 where masked
        p = jnp.exp(jnp.where(kept, s, _NEG_BIG) - lse_ref[pl.ds(r, 1), :])
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[pl.ds(r, 1), :]) * scale
        dq_acc[:, cols] += jnp.dot(ds.T.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)
        return (sums[0] + jnp.dot(ds.astype(q.dtype), q,
                                  preferred_element_type=jnp.float32),
                sums[1] + jnp.dot(p.astype(do.dtype), do,
                                  preferred_element_type=jnp.float32))

    zeros = jnp.zeros(dk_ref.shape, jnp.float32)
    dk, dv = _heads(rep, hd, head, (zeros, zeros))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _rows(x, block: int):
    """``[T, heads, hd]`` -> ``[T padded to a multiple of block, heads *
    hd]``: the layout the model holds, one head per ``hd`` columns."""
    t = x.shape[0]
    return jnp.pad(x.reshape(t, -1), ((0, -t % block), (0, 0)))


def _tiled(q, k, v, keep, block_q: int, block_k: int):
    """What every kernel call starts from: block sizes ``min(block,
    extent)``, ``q`` / ``k`` / ``v`` as padded rows, the int8 keep mask
    padded with nothing kept, and the number of query and key tiles."""
    tq, tk = q.shape[0], k.shape[0]
    bq, bk = min(block_q, tq), min(block_k, tk)
    keep8 = jnp.pad(keep.astype(jnp.int8), ((0, -tq % bq), (0, -tk % bk)))
    return (bq, bk, _rows(q, bq), _rows(k, bk), _rows(v, bk), keep8,
            keep8.shape[0] // bq, keep8.shape[1] // bk)


def _call(name, kernel, ins, outs, semantics=None, **grid):
    """``pl.pallas_call`` of ``kernel`` (``name`` is what a device trace
    calls it) on ``ins`` with outputs ``outs`` (``(shape, dtype)`` each);
    the tiles a kernel accumulates over, keys or groups, are its
    innermost grid axis unless ``semantics`` says otherwise. Inside a
    manual mesh region (the round engine's client lanes) the interpreter
    cannot type the kernel's constants against operands that vary over
    the mesh, so off the chip every lane runs the call on all lanes'
    operands, which do not vary, and keeps its own result."""
    if semantics is None:
        semantics = ("parallel",) * (len(grid["grid"]) - 1) + ("arbitrary",)

    def call(*ins):
        return pl.pallas_call(
            kernel, name=name,
            out_shape=[out_struct(*o, ins) for o in outs],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics,
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=_interpret(), **grid)(*ins)

    lanes = tuple(frozenset().union(*(jax.typeof(x).vma for x in ins)))
    if not (lanes and _interpret()):
        return call(*ins)
    me = jax.lax.axis_index(lanes)
    mine = jnp.arange(jax.lax.psum(1, lanes)) == me

    def gather(x):  # a sum in which every other lane's term is zero
        slot = mine.reshape((-1,) + (1,) * x.ndim)
        return jax.lax.psum(jnp.where(slot, x[None], jnp.zeros_like(x)), lanes)

    return [o[me] for o in jax.vmap(call)(*map(gather, ins))]


def _index_slab(heads: int, hd: int) -> int:
    """How many of the indexer's heads lie in one ``_LANES``-wide slab of
    the ``[T, heads * hd]`` rows (two, at the published 64): Mosaic takes
    a traced lane index only where it can prove a multiple of ``_LANES``,
    so the kernels slice whole slabs with :func:`_heads`' traced index
    and the heads inside one statically. One head a slab where the widths
    do not divide (rehearsal sizes, which only the interpreter runs)."""
    g = _LANES // hd if hd < _LANES and _LANES % hd == 0 else 1
    return g if heads % g == 0 else 1


def _index_forward_kernel(q_ref, k_ref, w_ref, out_ref, *, heads: int,
                          hd: int):
    """One tile of keys (rows here: a head's weights are then a lane
    vector, ``w_ref`` ``[heads, block_q]`` with the scale on it) against
    the chunk's queries: each head's float32 dots, ``relu``, its weight,
    summed over the heads in index order; turned to ``[block_q,
    block_k]`` once, on the way out."""
    k = k_ref[...]
    g = _index_slab(heads, hd)

    def slab(r, cols, total):
        qs = q_ref[:, cols]
        for i in range(g):
            dots = jax.lax.dot_general(
                k, qs[:, i * hd:(i + 1) * hd], _NT,
                preferred_element_type=jnp.float32)
            total = total + jnp.maximum(dots, 0.0) * w_ref[
                pl.ds(r * g + i, 1), :]
        return total

    total = _heads(heads // g, g * hd, slab,
                   jnp.zeros(out_ref.shape[::-1], jnp.float32))
    # a sum of negative zeros is -0.0, which would order below +0.0 in the
    # bit pattern the selection bisects over (a select and not `+ 0.0`,
    # which XLA, compiling the interpreted kernel, folds away)
    total = total.T
    out_ref[...] = jnp.where(total == 0.0, 0.0, total)


def _index_backward_kernel(q_ref, k_ref, w_ref, di_ref, dq_ref, dk_ref,
                           dw_ref, dq_acc, dw_acc, *, heads: int, hd: int,
                           scale: float):
    """One tile of keys (rows) against the chunk's queries, given the
    tile of the scores' cotangent: each head's dots again, the ``relu``
    mask, ``d_dots`` in VMEM; ``dK`` summed over the heads, complete per
    key tile; ``dQ`` and the weights' gradient accumulated over the key
    tiles (the innermost grid axis) in float32 scratch."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    k = k_ref[...]
    d_i = di_ref[...].T  # [block_k, block_q]
    g = _index_slab(heads, hd)

    def slab(r, cols, dk):
        qs = q_ref[:, cols]
        dqs = []
        for i in range(g):
            q, row = qs[:, i * hd:(i + 1) * hd], pl.ds(r * g + i, 1)
            dots = jax.lax.dot_general(k, q, _NT,
                                       preferred_element_type=jnp.float32)
            live = jnp.where(dots > 0, d_i, 0.0)
            dw_acc[row, :] += (live * dots).sum(0, keepdims=True)
            d_dots = live * w_ref[row, :]
            dqs.append(jnp.dot(d_dots.T.astype(k.dtype), k,
                               preferred_element_type=jnp.float32))
            dk = dk + jnp.dot(d_dots.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)
        dq_acc[:, cols] += jnp.concatenate(dqs, axis=1)
        return dk

    dk_ref[...] = _heads(heads // g, g * hd, slab,
                         jnp.zeros(dk_ref.shape, jnp.float32)
                         ).astype(dk_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        dw_ref[...] = dw_acc[...] * scale


def _index_tiled(q_idx, k_idx, w_idx, block_q: int, block_k: int):
    """What both index kernels start from: the block sizes, ``q_idx`` and
    ``k_idx`` as padded rows, the float32 head weights times the two
    scale factors as ``[heads, queries]`` (a sixty-fourth of ``q_idx``'s
    bytes; the large operands keep the model's layout), the scale, and
    the specs of the three."""
    tq, heads, hd = q_idx.shape
    bq, bk = min(block_q, tq), min(block_k, k_idx.shape[0])
    scale = hd ** -0.5 * heads ** -0.5
    w = (w_idx.astype(jnp.float32) * jnp.float32(scale)).T
    ins = (_rows(q_idx, bq), _rows(k_idx, bk),
           jnp.pad(w, ((0, 0), (0, -tq % bq))))
    specs = [pl.BlockSpec((bq, heads * hd), lambda i, j: (i, 0)),
             pl.BlockSpec((bk, hd), lambda i, j: (j, 0)),
             pl.BlockSpec((heads, bq), lambda i, j: (0, i))]
    return bq, bk, scale, ins, specs


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def index_scores(q_idx, k_idx, w_idx, block_q: int = 512,
                 block_k: int = 512):
    """``q_idx``: ``[Tq, J, d]``; ``k_idx``: ``[Tk, d]`` (one key head);
    ``w_idx``: ``[Tq, J]`` -> ``[Tq, Tk]`` float32, ``sum_j w[t, j]
    relu(q_idx[t, j] . k_idx[s])`` times ``d ** -0.5 * J ** -0.5``: the
    products accumulate in float32, and so do ``relu``, the weights and
    the sum over the heads. Never ``-0.0``.

    Two Pallas kernels in which a ``[block_k, block_q]`` tile of one
    head's dots lives and dies in VMEM, so no ``[J, Tq, Tk]`` array
    reaches HBM: the forward pass, and the backward pass, which keeps the
    operands only, recomputes a tile's dots and forms the masked
    cotangent there (rounded to the operands' dtype once, for its
    products with ``k_idx`` and ``q_idx``). Extents that are no multiple
    of their block are padded with zeros, which score 0."""
    return _index_scores_fwd(q_idx, k_idx, w_idx, block_q, block_k)[0]


def _index_scores_fwd(q_idx, k_idx, w_idx, block_q, block_k):
    tq, heads, hd = q_idx.shape
    bq, bk, _, ins, specs = _index_tiled(q_idx, k_idx, w_idx, block_q,
                                         block_k)
    padded = (ins[0].shape[0], ins[1].shape[0])
    scores, = _call(
        "attn_index_forward",
        functools.partial(_index_forward_kernel, heads=heads, hd=hd),
        ins, [(padded, jnp.float32)],
        grid=(padded[0] // bq, padded[1] // bk), in_specs=specs,
        out_specs=[pl.BlockSpec((bq, bk), lambda i, j: (i, j))])
    return scores[:tq, :k_idx.shape[0]], (q_idx, k_idx, w_idx)


def _index_scores_bwd(block_q, block_k, res, d_scores):
    q_idx, k_idx, w_idx = res
    tq, heads, hd = q_idx.shape
    tk = k_idx.shape[0]
    bq, bk, scale, ins, specs = _index_tiled(q_idx, k_idx, w_idx, block_q,
                                             block_k)
    q2, k2, w2 = ins
    nq, nk = q2.shape[0] // bq, k2.shape[0] // bk
    d_scores = jnp.pad(d_scores.astype(jnp.float32),
                       ((0, -tq % bq), (0, -tk % bk)))
    # one tile of queries: dK leaves the kernel whole, in k_idx's dtype;
    # more: float32 partial sums, one per query tile
    sum_dtype = k_idx.dtype if nq == 1 else jnp.float32
    dq, dk, dw = _call(
        "attn_index_backward",
        functools.partial(_index_backward_kernel, heads=heads, hd=hd,
                          scale=scale),
        ins + (d_scores,),
        [(q2.shape, q_idx.dtype), ((nq,) + k2.shape, sum_dtype),
         (w2.shape, jnp.float32)],
        grid=(nq, nk),
        in_specs=specs + [pl.BlockSpec((bq, bk), lambda i, j: (i, j))],
        out_specs=[specs[0],
                   pl.BlockSpec((None, bk, hd), lambda i, j: (i, j, 0)),
                   specs[2]],
        scratch_shapes=[pltpu.VMEM((bq, heads * hd), jnp.float32),
                        pltpu.VMEM((heads, bq), jnp.float32)])
    return (dq[:tq].reshape(q_idx.shape),
            dk.sum(0)[:tk].astype(k_idx.dtype),
            dw[:, :tq].T.astype(w_idx.dtype))


index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def selected_attention(q, k, v, keep, block_q: int = 512,
                       block_k: int = 512):
    """``q``: ``[Tq, H, hd]``; ``k``, ``v``: ``[Tk, G, hd]`` with ``H`` a
    multiple of ``G`` (query head ``h`` reads key-value head ``h // (H //
    G)``); ``keep``: ``[Tq, Tk]`` bool, at least one key kept per query.
    Scores, maxima, exponentials, sums and accumulators are float32; the
    exponentials are rounded to ``v``'s dtype once for their product with
    ``v``. Returns (``[Tq, H * hd]`` in ``q``'s dtype, ``[Tq, Tk]``
    float32 attention weights averaged over the heads, exactly 0 off
    ``keep``: the indexer's target, a constant that carries no gradient).

    Three Pallas kernels in which a ``[block_q, block_k]`` tile of one
    head's scores lives and dies in VMEM, so no ``[H, Tq, Tk]`` array
    ever reaches HBM: the forward pass (online softmax over the key
    tiles; the ``H // G`` query heads of a group share its ``k`` / ``v``
    tile and the keep tile), the heads' mean (the scores a second time,
    normalised by each head's final log-sum-exp) and the backward pass,
    which keeps the output and the rows' log-sum-exp (``checkpoint_name``
    ``attn_out`` / ``attn_lse``, so that a caller's rematerialisation
    policy can keep them and skip the forward kernels), recomputes a
    tile's scores once and forms ``p``, ``dP`` and ``dS`` there. Extents
    that are no multiple of their block are padded with nothing kept."""
    return _selected_attention_fwd(q, k, v, keep, block_q, block_k)[0]


def _selected_attention_fwd(q, k, v, keep, block_q, block_k):
    tq, h, hd = q.shape
    tk, g, _ = k.shape
    rep = h // g
    bq, bk, q2, k2, v2, keep8, nq, nk = _tiled(q, k, v, keep, block_q,
                                               block_k)
    sizes = dict(rep=rep, hd=hd, scale=hd ** -0.5)
    out, lse = _call(
        "attn_sparse_forward", functools.partial(_forward_kernel, **sizes),
        (q2, k2, v2, keep8),
        [(q2.shape, q.dtype), ((h, q2.shape[0], _LANES), jnp.float32)],
        grid=(g, nq, nk),
        in_specs=[
            pl.BlockSpec((bq, rep * hd), lambda g_, i, j: (i, g_)),
            pl.BlockSpec((bk, hd), lambda g_, i, j: (j, g_)),
            pl.BlockSpec((bk, hd), lambda g_, i, j: (j, g_)),
            pl.BlockSpec((bq, bk), lambda g_, i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, rep * hd), lambda g_, i, j: (i, g_)),
            pl.BlockSpec((rep, bq, _LANES), lambda g_, i, j: (g_, i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((rep, bq, _LANES), jnp.float32),
                        pltpu.VMEM((rep, bq, _LANES), jnp.float32),
                        pltpu.VMEM((rep, bq, hd), jnp.float32)])
    weights, = _call(
        "attn_sparse_head_mean",
        functools.partial(_head_mean_kernel, heads=h, **sizes),
        (q2, k2, lse, keep8), [(keep8.shape, jnp.float32)],
        grid=(nq, nk, g),
        in_specs=[
            pl.BlockSpec((bq, rep * hd), lambda i, j, g_: (i, g_)),
            pl.BlockSpec((bk, hd), lambda i, j, g_: (j, g_)),
            pl.BlockSpec((rep, bq, _LANES), lambda i, j, g_: (g_, i, 0)),
            pl.BlockSpec((bq, bk), lambda i, j, g_: (i, j)),
        ],
        out_specs=[pl.BlockSpec((bq, bk), lambda i, j, g_: (i, j))])
    out = checkpoint_name(out[:tq], "attn_out")
    lse = checkpoint_name(lse[:, :tq, 0].reshape(g, rep, tq), "attn_lse")
    return (out, weights[:tq, :tk]), (q, k, v, keep, out, lse)


def _selected_attention_bwd(block_q, block_k, res, cotangents):
    q, k, v, keep, out, lse = res
    d_out = cotangents[0]  # the weights are a constant target
    tq, h, hd = q.shape
    tk, g, _ = k.shape
    rep = h // g
    bq, bk, q2, k2, v2, keep8, nq, nk = _tiled(q, k, v, keep, block_q,
                                               block_k)
    delta = (d_out.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
        tq, g, rep, hd).sum(-1).transpose(1, 2, 0)
    pad = ((0, 0), (0, 0), (0, -tq % bq))
    ins = (q2, k2, v2, keep8.T, _rows(d_out, bq), jnp.pad(lse, pad),
           jnp.pad(delta, pad))
    rows = pl.BlockSpec((bq, rep * hd), lambda g_, i, j: (i, g_))
    keys = pl.BlockSpec((bk, hd), lambda g_, i, j: (j, g_))
    stats = pl.BlockSpec((None, rep, bq), lambda g_, i, j: (g_, 0, i))
    sums = pl.BlockSpec((None, bk, hd), lambda g_, i, j: (i, j, g_))
    # one tile of queries: dK and dV leave the kernel whole, in k's dtype;
    # more: float32 partial sums, one per query tile
    sum_dtype = k.dtype if nq == 1 else jnp.float32
    dq, dk, dv = _call(
        "attn_sparse_backward",
        functools.partial(_backward_kernel, rep=rep, hd=hd,
                          scale=hd ** -0.5), ins,
        [(q2.shape, q.dtype)] + [((nq,) + k2.shape, sum_dtype)] * 2,
        grid=(g, nq, nk),
        in_specs=[rows, keys, keys,
                  pl.BlockSpec((bk, bq), lambda g_, i, j: (j, i)),
                  rows, stats, stats],
        out_specs=[rows, sums, sums],
        scratch_shapes=[pltpu.VMEM((bq, rep * hd), jnp.float32)])
    return (dq[:tq].reshape(q.shape),
            dk.sum(0)[:tk].reshape(k.shape).astype(k.dtype),
            dv.sum(0)[:tk].reshape(v.shape).astype(v.dtype), None)


selected_attention.defvjp(_selected_attention_fwd, _selected_attention_bwd)


def index_kl(scores, keep, target):
    """``sum_t KL(target[t, .] || softmax over keep[t, .] of scores[t,
    .])``; ``target`` is constant (the caller stops its gradient)."""
    logq = jax.nn.log_softmax(jnp.where(keep, scores, _NEG_BIG), axis=-1)
    safe = jnp.where(target > 0, target, 1.0)
    return jnp.where(keep & (target > 0),
                     target * (jnp.log(safe) - logq), 0.0).sum()
