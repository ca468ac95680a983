"""The language decoder of Keye-VL-2.0-30B-A3B as one chip of an
expert-parallel deployment holds it.

Every layer: RMSNorm, grouped-query attention (per-head RMSNorm on q and
k, M-RoPE) over the keys a learned indexer selects (``ops/
sparse_attention.py``), RMSNorm, a sparse-expert layer that computes the
part of its result that the ``experts_held`` experts from
``expert_offset`` on contribute (``ops/moe.py``). Untied embedding and
output head over ``vocab_size`` rows (a slice of the published
vocabulary; token ids are drawn from the slice). The vision tower is not
built: its widths are not published in the decoder's config.

The indexer learns from a loss of its own (the sparse stage of DSA):
the KL divergence from the attention weights on the selected keys,
averaged over the heads and held constant, to the softmax of the index
scores over the same keys, with the indexer's input held constant too.
That loss moves the indexer's leaves only; the language loss moves
everything else and, selection being piecewise constant, gives the
indexer nothing. (Everything else but the router, where the chip holds
a share of the experts: the gates' gradient is the sum of all chips'
shares, so ``ops/moe.route`` holds them constant then.) ``__call__`` therefore returns ``(logits, aux)`` with
``aux["loss"]`` (``[B]``, the sum of the layers' indexer losses, which
``client/trainer.make_loss_fn`` adds to each example's cross-entropy)
and ``aux["counters"]`` (``[B]`` each, named by ``aux_counters``).

Named scopes for the device trace, beneath the trainer's ``local_grad``:
``attn_indexer``, ``attn_select``, ``attn_sparse``, ``moe_route``,
``moe_experts`` here, ``lm_head`` around the logits here and around the
cross-entropy in the trainer.

Layout: activations ``[T, hidden]`` per sequence (the batch is mapped
over), attention in chunks of ``q_chunk`` queries against the keys the
chunk can see. One layer is rematerialised at a time; the selection
masks, attention's outputs, log-sum-exps and head-mean weights are
kept (``checkpoint_name`` ``attn_select``, ``attn_out``, ``attn_lse``,
``attn_weights``) and so are the expert layer's dispatch tables and
output (``moe_dispatch``, ``moe_out``), so the bisection, attention's
forward pass, the router with its top-k and sort, and the experts' run
once per layer and step. A chunk's index scores are computed once in the
forward pass (the selection and the value of the indexer's loss read the same
``[q_chunk, keys]`` float32 array) and once more in the backward pass,
on the way to the loss's gradient; attention's and the indexer's
backward kernels recompute the per-head scores of their own chunk, a
tile at a time in VMEM (``ops/sparse_attention.selected_attention``,
``index_scores``: no array of per-head scores reaches HBM).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from colearn_federated_learning_tpu.models import _INPUT_SPECS, model_registry
from colearn_federated_learning_tpu.ops import moe, sparse_attention

AUX_COUNTERS = ("indexer_loss", "held_assignment_share",
                "expert_load_max_over_mean", "selected_key_share",
                "expert_tile_fill")


class KeyeDims(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    num_experts: int
    experts_held: int
    expert_offset: int
    experts_per_token: int
    expert_width: int
    index_heads: int
    index_head_dim: int
    index_topk: int
    rms_eps: float
    q_chunk: int
    moe_tile: int


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def rope_angles(positions, dim: int, theta: float):
    """``[..., T]`` positions -> ``[..., T, dim // 2]`` angles. The
    inverse frequencies are constants, computed in float64 and rounded
    once: at position 8,000 one float32 ulp of a frequency near 1 is
    already 5e-4 rad."""
    inv = np.power(float(theta), -np.arange(0, dim, 2) / dim)
    return positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)


def mrope_angles(positions, dim: int, theta: float, sections):
    """``[3, T]`` positions (temporal, height, width) -> ``[T, dim //
    2]``: frequency pair ``i`` turns by the stream whose section holds
    ``i``. Three equal streams give plain RoPE."""
    if sum(sections) != dim // 2:
        raise ValueError(f"mrope_section {sections} must sum to {dim // 2}")
    ang = rope_angles(positions, dim, theta)
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                        total_repeat_length=dim // 2)
    return jnp.take_along_axis(ang, stream[None, None, :], axis=0)[0]


def apply_rope(x, angles, factor: float = 1.0):
    """Rotate-half on the last axis of ``x`` ``[T, ..., dim]``; cosine
    and sine are each multiplied by ``factor`` (YaRN's attention factor,
    where a model has one) before they meet ``x``."""
    shape = (angles.shape[0],) + (1,) * (x.ndim - 2) + (angles.shape[1],)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _dense(x, w):
    return jnp.dot(x, w.astype(x.dtype))


def indexer_inputs(p, h, index_angles, d: KeyeDims):
    """The indexer's queries ``[T, J, d]``, its one key head ``[T, d]``
    and the float32 head weights ``[T, J]``, from ``h`` held constant."""
    t = h.shape[0]
    h = jax.lax.stop_gradient(h)
    q_idx = apply_rope(
        _dense(h, p["idx_wq"]).reshape(t, d.index_heads, d.index_head_dim),
        index_angles)
    k_idx = apply_rope(
        layer_norm(_dense(h, p["idx_wk"]), p["idx_k_norm_scale"],
                   p["idx_k_norm_bias"], d.rms_eps), index_angles)
    w_idx = jnp.dot(h, p["idx_ww"].astype(h.dtype),
                    preferred_element_type=jnp.float32)
    return q_idx, k_idx, w_idx


def chunk_keep(q_idx, k_idx, w_idx, lo: int, hi: int, topk: int):
    """Queries ``lo .. hi`` over the keys ``0 .. hi`` they can see: their
    index scores ``[hi - lo, hi]`` float32 (constants: selection is
    piecewise constant) and the keep mask of each query's ``topk`` best."""
    causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
    with jax.named_scope("attn_indexer"):
        scores = sparse_attention.index_scores(*jax.lax.stop_gradient(
            (q_idx[lo:hi], k_idx[:hi], w_idx[lo:hi])))
    with jax.named_scope("attn_select"):
        return scores, sparse_attention.select_topk(scores, causal, topk)


@jax.custom_vjp
def chunk_index_loss(q_idx, k_idx, w_idx, scores, keep, target):
    """The indexer's KL of one chunk of queries, summed over them.
    ``scores`` are ``index_scores(q_idx, k_idx, w_idx)`` as
    :func:`chunk_keep` computed them: the value reads them, and the
    backward pass, which keeps the operands only, computes them again
    (one fused pass) on its way to the three gradients."""
    return sparse_attention.index_kl(scores, keep, target)


def _chunk_index_loss_fwd(q_idx, k_idx, w_idx, scores, keep, target):
    return (sparse_attention.index_kl(scores, keep, target),
            (q_idx, k_idx, w_idx, keep, target))


def _chunk_index_loss_bwd(res, d_loss):
    *operands, keep, target = res
    _, vjp = jax.vjp(
        lambda *a: sparse_attention.index_kl(
            sparse_attention.index_scores(*a), keep, target), *operands)
    return (*vjp(d_loss), None, None, None)


chunk_index_loss.defvjp(_chunk_index_loss_fwd, _chunk_index_loss_bwd)


def attention_block(p, x, angles, index_angles, d: KeyeDims):
    """Selected attention of one sequence ``x`` ``[T, hidden]``. Returns
    (the block's output before the residual, the indexer's loss averaged
    over the queries, selected pairs over causal pairs)."""
    t = x.shape[0]
    h = rms_norm(x, p["attn_norm"], d.rms_eps)
    q = _dense(h, p["wq"]).reshape(t, d.heads, d.head_dim)
    k = _dense(h, p["wk"]).reshape(t, d.kv_heads, d.head_dim)
    v = _dense(h, p["wv"]).reshape(t, d.kv_heads, d.head_dim)
    q = apply_rope(rms_norm(q, p["q_norm"], d.rms_eps), angles)
    k = apply_rope(rms_norm(k, p["k_norm"], d.rms_eps), angles)
    with jax.named_scope("attn_indexer"):
        q_idx, k_idx, w_idx = indexer_inputs(p, h, index_angles, d)

    outs = []
    loss = jnp.zeros((), jnp.float32)
    selected = jnp.zeros((), jnp.float32)
    chunk = min(d.q_chunk, t)
    for lo in range(0, t, chunk):
        hi = min(lo + chunk, t)  # keys 0 .. hi are all the chunk can see
        scores, keep = chunk_keep(q_idx, k_idx, w_idx, lo, hi, d.index_topk)
        keep = checkpoint_name(keep, "attn_select")
        with jax.named_scope("attn_sparse"):
            # its output, log-sum-exp and these weights are kept through
            # the layer's rematerialisation: attention's forward pass
            # runs once a step
            out, weights = sparse_attention.selected_attention(
                q[lo:hi], k[:hi], v[:hi], keep)
            weights = checkpoint_name(weights, "attn_weights")
        with jax.named_scope("attn_indexer"):
            loss += chunk_index_loss(q_idx[lo:hi], k_idx[:hi], w_idx[lo:hi],
                                     scores, keep,
                                     jax.lax.stop_gradient(weights))
        selected += keep.sum(dtype=jnp.float32)
        outs.append(out)
    out = _dense(jnp.concatenate(outs, axis=0), p["wo"])
    return out, loss / t, selected / (t * (t + 1) // 2)


def expert_stack(stacked, layer, dtype):
    """``ops/moe.expert_ffn``'s ``stack`` for layer ``layer`` of a scan
    over the leaves ``stacked``: the three expert weights of all layers,
    as constants of the scan, where the kernels can read them as they
    are stored (``None`` where they compute in another dtype: the cast
    is then a copy in any case)."""
    if stacked["w1"].dtype != dtype:
        return None
    return (*(jax.lax.stop_gradient(stacked[n]) for n in ("w1", "w3", "w2")),
            layer)


def expert_block(p, x, d: KeyeDims, stack=None):
    """This chip's share of the sparse-expert layer for one sequence."""
    h = rms_norm(x, p["mlp_norm"], d.rms_eps)
    with jax.named_scope("moe_route"):
        disp = moe.route(h, p["router"], top_k=d.experts_per_token,
                         experts_held=d.experts_held,
                         expert_offset=d.expert_offset, tile=d.moe_tile)
    with jax.named_scope("moe_experts"):
        cd = h.dtype
        y = moe.expert_ffn(h, p["w1"].astype(cd), p["w3"].astype(cd),
                           p["w2"].astype(cd), disp.row_token, disp.row_gate,
                           disp.tile_expert, disp.n_tiles, stack)
        y = checkpoint_name(y, "moe_out")
    counts = disp.counts.astype(jnp.float32)
    return (y, disp.held_share,
            counts.max() / jnp.maximum(counts.mean(), 1.0),
            moe.tile_fill(disp, d.moe_tile))


def decoder_layer(p, stack, x, angles, index_angles, d: KeyeDims):
    """One layer on one sequence: (x, the layer's ``AUX_COUNTERS``)."""
    att, loss, selected_share = attention_block(p, x, angles, index_angles, d)
    x = x + att
    y, held_share, load, fill = expert_block(p, x, d, stack)
    return x + y, jnp.stack([loss, held_share, load, selected_share, fill])


class KeyeDecoderLM(nn.Module):
    vocab_size: int
    seq_len: int
    layers: int
    dims: KeyeDims
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    aux_counters = AUX_COUNTERS

    def _layer_params(self) -> Dict[str, jnp.ndarray]:
        """Every layer's leaves stacked on a leading ``layers`` axis
        (``layers_<name>``): the layers are scanned, so one layer's
        program is compiled once."""
        d = self.dims
        q_out, kv_out = d.heads * d.head_dim, d.kv_heads * d.head_dim
        shapes = {
            "wq": (d.hidden, q_out), "wk": (d.hidden, kv_out),
            "wv": (d.hidden, kv_out), "wo": (q_out, d.hidden),
            "idx_wq": (d.hidden, d.index_heads * d.index_head_dim),
            "idx_wk": (d.hidden, d.index_head_dim),
            "idx_ww": (d.hidden, d.index_heads),
            "router": (d.hidden, d.num_experts),
            "w1": (d.experts_held, d.hidden, d.expert_width),
            "w3": (d.experts_held, d.hidden, d.expert_width),
            "w2": (d.experts_held, d.expert_width, d.hidden),
        }
        inits = {n: nn.initializers.normal(0.02) for n in shapes}
        for n, width in (("attn_norm", d.hidden), ("mlp_norm", d.hidden),
                         ("q_norm", d.head_dim), ("k_norm", d.head_dim),
                         ("idx_k_norm_scale", d.index_head_dim)):
            shapes[n], inits[n] = (width,), nn.initializers.ones
        shapes["idx_k_norm_bias"] = (d.index_head_dim,)
        inits["idx_k_norm_bias"] = nn.initializers.zeros
        return {n: self.param(f"layers_{n}", inits[n],
                              (self.layers,) + shape, self.param_dtype)
                for n, shape in shapes.items()}

    @nn.compact
    def __call__(self, tokens, train: bool = False, positions=None):
        """``tokens`` ``[B, T]`` -> (float32 logits ``[B, T, vocab]``,
        aux). ``positions`` ``[3, B, T]`` (temporal, height, width);
        text, the default, has three equal streams ``0 .. T``."""
        d = self.dims
        b, t = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t), (3, b, t))
        normal = nn.initializers.normal(0.02)
        # Embeddings at the scale of sqrt(hidden) x 0.02, as a model
        # that multiplies its embeddings by sqrt(hidden) has them. With
        # normal(0.02) the first attention's output, whose mean over the
        # keys is the same vector at every position, is 3.5 times the
        # embedding, and the untrained router sends every token to the
        # same experts (forward pass on the chip: the held experts' load
        # 3.6-14.7 times its mean; 1.1-2.0 with these; PERF.md, PR 25).
        embed = self.param("embed", nn.initializers.normal(1.0),
                           (self.vocab_size, d.hidden), self.param_dtype)
        x = jnp.take(embed, tokens, axis=0).astype(self.compute_dtype)
        angles = jax.vmap(
            lambda pos: mrope_angles(pos, d.head_dim, self.rope_theta,
                                     self.mrope_section),
            in_axes=1)(positions)
        index_angles = rope_angles(positions[0], d.index_head_dim,
                                   self.rope_theta)
        layer = jax.checkpoint(
            partial(decoder_layer, d=d),
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_select", "attn_out", "attn_lse", "attn_weights",
                "moe_dispatch", "moe_out"),
        )
        stacked = self._layer_params()
        if self.is_initializing():
            # shapes only: init need not run 8,192-token attention
            stats = jnp.ones((self.layers, b, len(AUX_COUNTERS)), jnp.float32)
        else:
            x, stats = jax.lax.scan(
                lambda x, pl: jax.vmap(layer, in_axes=(None, None, 0, 0, 0))(
                    pl[0], expert_stack(stacked, pl[1], x.dtype), x, angles,
                    index_angles),
                x, (stacked, jnp.arange(self.layers)))
            # stats: [layers, B, counters]
        final_norm = self.param("final_norm", nn.initializers.ones,
                                (d.hidden,), self.param_dtype)
        head = self.param("head", normal, (d.hidden, self.vocab_size),
                          self.param_dtype)
        with jax.named_scope("lm_head"):
            x = rms_norm(x, final_norm, d.rms_eps)
            logits = jnp.dot(x, head.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        # the indexer's loss adds up over the layers; the shares are means
        counters = dict(zip(AUX_COUNTERS, stats.mean(0).T))
        counters["indexer_loss"] = stats[:, :, 0].sum(0)
        return logits, {"loss": counters["indexer_loss"],
                        "counters": counters}


@model_registry.register("keye_decoder")
def _build(num_classes: int = 0, vocab_size: int = 18992, seq_len: int = 8192,
           layers: int = 4, hidden: int = 2048, heads: int = 32,
           kv_heads: int = 4, head_dim: int = 128, num_experts: int = 128,
           experts_held: int = 16, expert_offset: int = 0,
           experts_per_token: int = 8, expert_width: int = 768,
           index_heads: int = 16, index_head_dim: int = 64,
           index_topk: int = 2048, rope_theta: float = 1e7,
           mrope_section=(16, 24, 24), rms_eps: float = 1e-6,
           q_chunk: int = 512, moe_tile: int = 256,
           compute_dtype=jnp.float32, param_dtype=jnp.float32, **_):
    del num_classes  # LM: the output width is vocab_size
    if isinstance(mrope_section, str):  # `--set ...mrope_section=16,24,24`
        mrope_section = [int(n) for n in mrope_section.strip("[]()").split(",")]
    if heads % kv_heads:
        raise ValueError(f"heads ({heads}) must be a multiple of kv_heads "
                         f"({kv_heads})")
    if not 0 <= expert_offset <= num_experts - experts_held:
        raise ValueError(
            f"experts {expert_offset}..{expert_offset + experts_held} are "
            f"not among the router's {num_experts}")
    dims = KeyeDims(hidden, heads, kv_heads, head_dim, num_experts,
                    experts_held, expert_offset, experts_per_token,
                    expert_width, index_heads, index_head_dim, index_topk,
                    rms_eps, q_chunk, moe_tile)
    return KeyeDecoderLM(vocab_size=vocab_size, seq_len=seq_len,
                         layers=layers, dims=dims, rope_theta=rope_theta,
                         mrope_section=tuple(mrope_section),
                         compute_dtype=compute_dtype, param_dtype=param_dtype)


_build.aux_counters = AUX_COUNTERS  # models.returns_aux_loss reads it


def _lm_spec(vocab_size: int = 18992, seq_len: int = 8192, **_):
    return (seq_len,), jnp.int32


_INPUT_SPECS["keye_decoder"] = _lm_spec
