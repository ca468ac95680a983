"""Fused flash-attention as a pallas TPU kernel.

The hot op of the transformer configs (BERT-tiny LM, ViT silo) as a
hand-tiled kernel instead of XLA's default fusion: one grid step owns a
``[block_q, head_dim]`` query tile in VMEM and streams key/value blocks
through the online-softmax recurrence (the same math as
``ops.ring_attention.blockwise_attention``) without ever materializing
the T×T score matrix in HBM. Scores and accumulators stay in f32 on the
MXU (``preferred_element_type``), inputs may be bf16.

Causality is exploited at the *grid* level: query tile ``i`` runs its
k/v loop only up to block ``i`` — for long sequences this halves the
work, which XLA's fused-but-dense attention does not do.

Backward: rematerialized through the XLA blockwise implementation via
``jax.custom_vjp`` — the forward value comes from the kernel, gradients
from re-running the mathematically identical online-softmax in XLA (the
standard remat trade: no T×T residuals saved, +1 recompute).

Model opt-in: ``build_model("bert_tiny", attention="pallas")``. On
non-TPU backends the kernel runs in pallas interpret mode (exact, slow)
so CPU tests cover the real kernel code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from colearn_federated_learning_tpu.ops.pallas_apply import out_struct
from colearn_federated_learning_tpu.ops.ring_attention import (
    _merge_heads,
    _split_heads,
    blockwise_attention,
)

_NEG_BIG = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_kv: int,
                 n_kv: int, kv_len: int, causal: bool, scale: float):
    # kv_len: number of REAL keys (< padded length when the sequence was
    # padded up to a block multiple); keys past it are masked out. Real
    # causal queries never see padded keys (q_pos < kv_len ⇒ k_pos ≤
    # q_pos < kv_len), and padded query rows are sliced off outside.
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, hd]
    hd = q.shape[-1]
    padded = kv_len < n_kv * block_kv
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0
    )

    def body(j, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_kv, block_kv), :]  # [block_kv, hd]
        v_blk = v_ref[0, pl.ds(j * block_kv, block_kv), :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_kv]
        keep = None
        if causal or padded:
            k_pos = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            keep = q_pos >= k_pos if causal else k_pos < kv_len
            if causal and padded:
                keep = keep & (k_pos < kv_len)
            s = jnp.where(keep, s, _NEG_BIG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    # causal: query tile qi only attends to k/v blocks overlapping
    # positions ≤ (qi+1)·block_q — skip the rest at the loop bound
    if causal:
        upper = pl.cdiv((qi + 1) * block_q, block_kv)
    else:
        upper = n_kv
    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _flash_fwd_impl(q, k, v, heads: int, causal: bool, block_q: int,
                    block_kv: int, interpret):
    qh = _split_heads(q, heads)  # [B, H, T, hd]
    kh = _split_heads(k, heads)
    vh = _split_heads(v, heads)
    b, h, t, hd = qh.shape
    bq = min(block_q, t)
    bkv = min(block_kv, t)
    # Non-divisible sequence lengths (e.g. ViT's 197 tokens) are padded up
    # to a block multiple; padded keys are masked inside the kernel via
    # kv_len and padded query rows are sliced off below. When padding is
    # needed both block sizes collapse to the smaller one so the pad is
    # bounded by one block — lcm of clamped ragged blocks (e.g. 50 and 32)
    # could otherwise blow the sequence up many-fold.
    if t % bq or t % bkv:
        bq = bkv = min(bq, bkv)
    tp = ((t + bkv - 1) // bkv) * bkv
    if tp != t:
        pad = [(0, 0), (0, 0), (0, tp - t), (0, 0)]
        qh, kh, vh = (jnp.pad(x, pad) for x in (qh, kh, vh))
    qh = qh.reshape(b * h, tp, hd)
    kh = kh.reshape(b * h, tp, hd)
    vh = vh.reshape(b * h, tp, hd)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = functools.partial(
        _attn_kernel, block_q=bq, block_kv=bkv, n_kv=tp // bkv, kv_len=t,
        causal=causal, scale=hd**-0.5,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, tp // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, tp, hd), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, tp, hd), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, i: (bh, i, 0)),
        out_shape=out_struct((b * h, tp, hd), q.dtype, (qh, kh, vh)),
        interpret=interpret,
    )(qh, kh, vh)
    return _merge_heads(out.reshape(b, h, tp, hd)[:, :, :t])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, heads: int, causal: bool = True,
                    block_q: int = 128, block_kv: int = 128, interpret=None):
    """[B, T, D] packed q/k/v → [B, T, D]; pallas-fused forward."""
    return _flash_fwd_impl(q, k, v, heads, causal, block_q, block_kv, interpret)


def _flash_fwd(q, k, v, heads, causal, block_q, block_kv, interpret):
    out = _flash_fwd_impl(q, k, v, heads, causal, block_q, block_kv, interpret)
    return out, (q, k, v)


def _flash_bwd(heads, causal, block_q, block_kv, interpret, residuals, g):
    q, k, v = residuals
    t = q.shape[1]
    block = min(block_q, block_kv, t)
    if t % block == 0:
        # long-context path: O(T·block) memory recompute
        def ref(q_, k_, v_):
            return blockwise_attention(q_, k_, v_, heads, block_size=block,
                                       causal=causal)

        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(g)
    if causal:
        # Non-divisible causal lengths keep the O(T·block) recompute by
        # zero-padding to a block multiple: padded keys sit at positions
        # ≥ t so no real query attends them, and the padded query rows'
        # cotangents are zero, so sliced gradients are exact.
        tp = ((t + block - 1) // block) * block
        pad = [(0, 0), (0, tp - t), (0, 0)]
        qp, kp, vp = (jnp.pad(x, pad) for x in (q, k, v))
        gp = jnp.pad(g, pad)

        def ref(q_, k_, v_):
            return blockwise_attention(q_, k_, v_, heads, block_size=block,
                                       causal=True)

        _, vjp = jax.vjp(ref, qp, kp, vp)
        return tuple(x[:, :t] for x in vjp(gp))
    # Non-causal non-divisible (ViT's 197 tokens): zero-padded keys WOULD
    # attract real attention weight, so recompute with plain attention —
    # T×T scores are fine at the scales where such lengths occur.
    from colearn_federated_learning_tpu.ops.attention import full_attention

    def ref(q_, k_, v_):
        return full_attention(q_, k_, v_, heads)

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
