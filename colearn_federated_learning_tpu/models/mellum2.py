"""The decoder of Mellum2-12B-A2.5B as one chip of an expert-parallel
deployment holds it: layers of two kinds in a fixed period.

Every layer: RMSNorm, causal grouped-query attention (per-head RMSNorm
on q and k, RoPE), RMSNorm, a sparse-expert layer that computes the part
of its result that the ``experts_held`` experts from ``expert_offset`` on
contribute (``ops/moe.py``; softmax router over all experts, the gates
renormalised over the chosen, no shared expert). The layers come in
periods of ``period`` (published: three ``sliding`` to one ``full``). A
*sliding* layer's query reads itself and the ``window - 1`` positions
before it and turns by plain RoPE; a *full* layer's reads the whole
causal triangle and turns by YaRN's blended frequencies, cosine and sine
each times ``rope_attention_factor`` on q and on k. Both kinds run the
same three kernels (``ops/band_attention.py``: ``window`` is an
argument), which visit the band's tiles only. Untied embedding and
output head over ``vocab_size`` rows (a slice of the published
vocabulary). The multi-token-prediction head that the model's family is
described with is not built: the published config gives it no key and
no width.

``__call__`` returns ``(logits, aux)`` with ``aux["counters"]`` (``[B]``
each, named by ``aux_counters``) and no auxiliary loss.

Named scopes for the device trace, beneath the trainer's ``local_grad``:
``attn_proj`` (the norms, the q / k / v / o products, RoPE),
``attn_window`` and ``attn_full`` (the kernels and what XLA runs beside
them, by layer kind), ``moe_route``, ``moe_experts``, ``lm_head``.

Layout as ``models/keye.py``: activations ``[T, hidden]`` per sequence,
the batch mapped over; every layer's leaves stacked on a leading
``layers`` axis (``layers_<leaf>``), scanned a *period* at a time: the
scan's body holds the period's layers one after the other, each with its
kind static and each rematerialised on its own, with attention's output
and log-sum-exp and the experts' dispatch tables and output kept
(``attn_out``, ``attn_lse``, ``moe_dispatch``, ``moe_out``). The two
angle tables of a sequence are built once, outside the scan. Shared
with ``keye.py``: ``rms_norm``, ``rope_angles``, ``apply_rope``,
``_dense``, ``expert_block``, ``expert_stack``; with ``axk1.py``:
``yarn_inv_freq``; its own: the attention block, the period, the two
tables.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from colearn_federated_learning_tpu.models import _INPUT_SPECS, model_registry
from colearn_federated_learning_tpu.models.axk1 import yarn_inv_freq
from colearn_federated_learning_tpu.models.keye import (
    _dense,
    apply_rope,
    expert_block,
    expert_stack,
    rms_norm,
    rope_angles,
)
from colearn_federated_learning_tpu.ops import band_attention

AUX_COUNTERS = ("held_assignment_share", "expert_load_max_over_mean",
                "expert_tile_fill", "band_pair_share")
KINDS = ("sliding", "full")


class Mellum2Dims(NamedTuple):
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    num_experts: int
    experts_held: int
    expert_offset: int
    experts_per_token: int
    expert_width: int
    window: int
    attention_factor: float  # on the full layers' cosine and sine
    rms_eps: float
    q_chunk: int  # the kernels' tile, both kinds
    moe_tile: int


def attention_block(p, x, angles, d: Mellum2Dims, kind: str):
    """Banded attention of one sequence ``x`` ``[T, hidden]``: the
    block's output before the residual."""
    t = x.shape[0]
    sliding = kind == "sliding"
    factor = 1.0 if sliding else d.attention_factor
    with jax.named_scope("attn_proj"):
        h = rms_norm(x, p["attn_norm"], d.rms_eps)
        q = _dense(h, p["wq"]).reshape(t, d.heads, d.head_dim)
        k = _dense(h, p["wk"]).reshape(t, d.kv_heads, d.head_dim)
        v = _dense(h, p["wv"]).reshape(t, d.kv_heads, d.head_dim)
        q = apply_rope(rms_norm(q, p["q_norm"], d.rms_eps), angles, factor)
        k = apply_rope(rms_norm(k, p["k_norm"], d.rms_eps), angles, factor)
    with jax.named_scope("attn_window" if sliding else "attn_full"):
        out = band_attention.band_attention(
            q, k, v, d.window if sliding else None, d.head_dim ** -0.5,
            d.q_chunk)
    with jax.named_scope("attn_proj"):
        return _dense(out.reshape(t, d.heads * d.head_dim), p["wo"])


def decoder_layer(p, stack, x, angles, d: Mellum2Dims, kind: str):
    """One layer of ``kind`` on one sequence: (x, the expert layer's
    three counters)."""
    x = x + attention_block(p, x, angles, d, kind)
    y, held_share, load, fill = expert_block(p, x, d, stack)
    return x + y, jnp.stack([held_share, load, fill])


class Mellum2DecoderLM(nn.Module):
    vocab_size: int
    seq_len: int
    layers: int
    period: Tuple[str, ...]
    dims: Mellum2Dims
    rope_theta: float
    full_inv_freq: Any  # tuple of head_dim // 2 floats: YaRN's
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    aux_counters = AUX_COUNTERS

    def _layer_params(self) -> Dict[str, jnp.ndarray]:
        """Every layer's leaves stacked on a leading ``layers`` axis
        (``layers_<name>``), whatever the layer's kind: the kinds differ
        in mask and angles, not in leaves."""
        d = self.dims
        q_out, kv_out = d.heads * d.head_dim, d.kv_heads * d.head_dim
        shapes = {
            "wq": (d.hidden, q_out), "wk": (d.hidden, kv_out),
            "wv": (d.hidden, kv_out), "wo": (q_out, d.hidden),
            "router": (d.hidden, d.num_experts),
            "w1": (d.experts_held, d.hidden, d.expert_width),
            "w3": (d.experts_held, d.hidden, d.expert_width),
            "w2": (d.experts_held, d.expert_width, d.hidden),
        }
        inits = {n: nn.initializers.normal(0.02) for n in shapes}
        for n, width in (("attn_norm", d.hidden), ("mlp_norm", d.hidden),
                         ("q_norm", d.head_dim), ("k_norm", d.head_dim)):
            shapes[n], inits[n] = (width,), nn.initializers.ones
        return {n: self.param(f"layers_{n}", inits[n],
                              (self.layers,) + shape, self.param_dtype)
                for n, shape in shapes.items()}

    @nn.compact
    def __call__(self, tokens, train: bool = False, positions=None):
        """``tokens`` ``[B, T]`` -> (float32 logits ``[B, T, vocab]``,
        aux). ``positions`` ``[B, T]``; the default is ``0 .. T``."""
        d = self.dims
        b, t = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        # embeddings at the scale of 0.02 sqrt(hidden) (models/keye.py)
        embed = self.param("embed", nn.initializers.normal(1.0),
                           (self.vocab_size, d.hidden), self.param_dtype)
        x = jnp.take(embed, tokens, axis=0).astype(self.compute_dtype)
        # the two tables of a sequence, once for all layers
        angles = {
            "sliding": rope_angles(positions, d.head_dim, self.rope_theta),
            "full": positions[..., None].astype(jnp.float32) * jnp.asarray(
                self.full_inv_freq, jnp.float32),
        }
        keep = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse", "moe_dispatch", "moe_out")
        layer = {kind: jax.checkpoint(
            partial(decoder_layer, d=d, kind=kind), policy=keep)
            for kind in KINDS}
        stacked = self._layer_params()
        size = len(self.period)
        if self.is_initializing():
            # shapes only: init need not run 16,384-token attention
            stats = jnp.ones((self.layers, b, 3), jnp.float32)
        else:
            def one_period(x, leaves_at):
                leaves, first = leaves_at
                stats = []
                for i, kind in enumerate(self.period):
                    x, s = jax.vmap(layer[kind], in_axes=(None, None, 0, 0))(
                        {n: v[i] for n, v in leaves.items()},
                        expert_stack(stacked, first + i, x.dtype), x,
                        angles[kind])
                    stats.append(s)
                return x, jnp.stack(stats)

            x, stats = jax.lax.scan(one_period, x, (
                {n: v.reshape((-1, size) + v.shape[1:])
                 for n, v in stacked.items()},
                jnp.arange(0, self.layers, size)))
            # stats: [periods, layers a period, B, counters]
            stats = stats.reshape((self.layers,) + stats.shape[2:])
        final_norm = self.param("final_norm", nn.initializers.ones,
                                (d.hidden,), self.param_dtype)
        head = self.param("head", nn.initializers.normal(0.02),
                          (d.hidden, self.vocab_size), self.param_dtype)
        with jax.named_scope("lm_head"):
            x = rms_norm(x, final_norm, d.rms_eps)
            logits = jnp.dot(x, head.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        counters = dict(zip(AUX_COUNTERS, stats.mean(0).T))
        # kept pairs over the pairs of the tiles a sliding layer's kernels
        # visit: a constant of the shapes
        counters["band_pair_share"] = jnp.full(
            (b,), band_attention.kept_pairs(t, d.window)
            / band_attention.visited_pairs(t, d.q_chunk, d.window),
            jnp.float32)
        return logits, {"counters": counters}


@model_registry.register("mellum2_decoder")
def _build(num_classes: int = 0, vocab_size: int = 12288,
           seq_len: int = 16384, layers: int = 4,
           period=("sliding", "sliding", "sliding", "full"),
           hidden: int = 2304, heads: int = 32, kv_heads: int = 4,
           head_dim: int = 128, num_experts: int = 64, experts_held: int = 8,
           expert_offset: int = 0, experts_per_token: int = 8,
           expert_width: int = 896, sliding_window: int = 1024,
           rope_theta: float = 500000.0, rope_factor: float = 16.0,
           rope_original: int = 8192, rope_beta_fast: float = 32.0,
           rope_beta_slow: float = 1.0,
           rope_attention_factor: float = 1.2772588722239782,
           rms_eps: float = 1e-6, q_chunk: int = 512, moe_tile: int = 256,
           compute_dtype=jnp.float32, param_dtype=jnp.float32, **_):
    del num_classes  # LM: the output width is vocab_size
    if isinstance(period, str):  # `--set ...period=sliding,full`
        period = [k.strip() for k in period.strip("[]()").split(",")]
    period = tuple(period)
    if not period or set(period) - set(KINDS):
        raise ValueError(f"period {period} must name layers of kinds {KINDS}")
    if layers % len(period):
        raise ValueError(f"layers ({layers}) must be whole periods of "
                         f"{len(period)}")
    if heads % kv_heads:
        raise ValueError(f"heads ({heads}) must be a multiple of kv_heads "
                         f"({kv_heads})")
    if sliding_window < 1:
        raise ValueError(f"sliding_window ({sliding_window}) keeps no key")
    if not 0 <= expert_offset <= num_experts - experts_held:
        raise ValueError(
            f"experts {expert_offset}..{expert_offset + experts_held} are "
            f"not among the router's {num_experts}")
    dims = Mellum2Dims(hidden, heads, kv_heads, head_dim, num_experts,
                       experts_held, expert_offset, experts_per_token,
                       expert_width, sliding_window,
                       float(rope_attention_factor), rms_eps, q_chunk,
                       moe_tile)
    inv_freq = yarn_inv_freq(head_dim, rope_theta, rope_factor, rope_original,
                             rope_beta_fast, rope_beta_slow)
    return Mellum2DecoderLM(
        vocab_size=vocab_size, seq_len=seq_len, layers=layers, period=period,
        dims=dims, rope_theta=rope_theta,
        full_inv_freq=tuple(float(f) for f in inv_freq),
        compute_dtype=compute_dtype, param_dtype=param_dtype)


_build.aux_counters = AUX_COUNTERS  # models.returns_aux_loss reads it


def _lm_spec(vocab_size: int = 12288, seq_len: int = 16384, **_):
    return (seq_len,), jnp.int32


_INPUT_SPECS["mellum2_decoder"] = _lm_spec
