"""The decoder of A.X-K1 as one chip of an expert-parallel deployment
holds it, with rank-r adapters on the projections of its latent
attention applied as side products.

Every layer: RMSNorm, latent attention (MLA): a query latent ``c_q =
RMSNorm(h W_qa)`` up-projected to ``heads`` queries of ``[q_n | q_r]``,
a key-value latent ``c_kv = RMSNorm((h W_kva)[:kv_rank])`` up-projected
to ``heads`` of ``[k_n | v]``, and ONE rope key ``k_r`` per position
(the last ``qk_rope`` columns of ``h W_kva``) shared by all heads; RoPE
(rotate-half, YaRN frequencies) on ``q_r`` and ``k_r``; causal softmax
over ``q_n . k_n + q_r . k_r`` (``ops/latent_attention.py``: three kernels that
keep the five operands apart, the query-key width ``qk_nope +
qk_rope`` differing from the value width);
the output projection. Then RMSNorm and, in layer 0, a dense SwiGLU of
width ``dense_width``; in every later layer a shared SwiGLU expert that
every chip computes whole beside the part of the routed layer's result
that the ``experts_held`` experts from ``expert_offset`` on contribute
(``ops/moe.py``: sigmoid scores, group-limited top-k, gates renormalised
and scaled by ``gate_scale``). Untied embedding and output head over
``vocab_size`` rows (a slice of the published vocabulary).

Adapters (``models/lora.py``): called with ``adapters`` (the tree
``init_lora_params`` builds over ``_MLA_LEAVES``) every targeted
projection computes ``x W + lora_scale (x A) B``, both products summed
in float32 before the one rounding (:func:`_proj`); no merged weight is
formed, the
experts run ``ops/moe.expert_ffn_frozen`` (no weight gradient) and
nothing but the adapters takes a gradient. Without ``adapters`` the
module is an ordinary trainable decoder.

``__call__`` returns ``(logits, aux)`` with ``aux["counters"]`` (``[B]``
each, named by ``aux_counters``) and no auxiliary loss.

Named scopes for the device trace, beneath the trainer's ``local_grad``:
``mla_proj`` (down-projections, latent norms, up-projections, RoPE, the
output projection, with the adapters' side products), ``mla_attn``
(scores, softmax, values), ``dense_mlp``, ``moe_route``, ``moe_shared``,
``moe_experts``, ``lm_head``.

Layout as ``models/keye.py``: activations ``[T, hidden]`` per sequence,
the batch mapped over; layer 0 stands alone (``dense_<leaf>``), the
identical expert layers are scanned over leaves stacked on a leading
axis (``layers_<leaf>``); one layer is rematerialised at a time, and
attention's output and log-sum-exp and the routed experts' dispatch
tables and output are kept (``attn_out``, ``attn_lse``,
``moe_dispatch``, ``moe_out``) so that all three run once per layer and
step. Shared with ``keye.py``: ``rms_norm``, ``apply_rope``,
``_dense``; its own: the projections, YaRN's frequencies, the two kinds
of layer.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from colearn_federated_learning_tpu.models import _INPUT_SPECS, model_registry
from colearn_federated_learning_tpu.models.keye import (
    _dense,
    apply_rope,
    expert_stack,
    rms_norm,
)
from colearn_federated_learning_tpu.ops import latent_attention, moe

AUX_COUNTERS = ("held_assignment_share", "expert_load_max_over_mean",
                "held_group_hit_share", "expert_tile_fill")


class AXK1Dims(NamedTuple):
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    dense_width: int
    num_experts: int
    experts_held: int
    expert_offset: int
    experts_per_token: int
    expert_width: int
    n_group: int
    topk_group: int
    gate_scale: float
    rms_eps: float
    attn_scale: float
    q_chunk: int
    moe_tile: int


def yarn_range(dim: int, theta: float, original: int, beta_fast: float,
               beta_slow: float):
    """(low, high): the frequency pairs between which YaRN blends from
    the published frequencies (below ``low``) to the interpolated ones
    (above ``high``). ``c(n)`` is the pair that turns ``n`` times over
    the original context."""
    def c(n):
        return dim * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    return (max(math.floor(c(beta_fast)), 0),
            min(math.ceil(c(beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """``dim // 2`` inverse frequencies in float64: ``f_i (1 - r_i) +
    (f_i / factor) r_i`` with ``r_i`` the ramp over :func:`yarn_range`."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = np.power(float(theta), -2.0 * i / dim)
    low, high = yarn_range(dim, theta, original, beta_fast, beta_slow)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - r) + (f / factor) * r


def yarn_attention_factor(factor: float, mscale_all_dim: float) -> float:
    """What the softmax scale is multiplied by, squared: ``0.1
    mscale_all_dim ln(factor) + 1``."""
    if factor <= 1.0 or not mscale_all_dim:
        return 1.0
    return 0.1 * mscale_all_dim * math.log(factor) + 1.0


def _proj(x, w, ad: Optional[Dict[str, Any]], lora_scale: float):
    """``x W``, or ``x W + lora_scale (x A) B`` with both products
    summed in float32 before the one rounding to ``x``'s dtype. (The
    rank-r products at three bfloat16 passes in float32 cost 30 ms a
    round in ``axk1_silo_lora_4k`` and brought the round no nearer to
    the reference's: PERF.md, PR 29.)"""
    if ad is None:
        return _dense(x, w)
    cd = x.dtype
    y = jnp.dot(x, w.astype(cd), preferred_element_type=jnp.float32)
    u = jnp.dot(x, ad["lora_a"].astype(cd),
                preferred_element_type=jnp.float32)
    side = jnp.dot(u.astype(cd), ad["lora_b"].astype(cd),
                   preferred_element_type=jnp.float32)
    return (y + lora_scale * side).astype(cd)


def _swiglu(h, w1, w3, w2):
    return _dense(jax.nn.silu(_dense(h, w1)) * _dense(h, w3), w2)


def attention_block(p, ad, x, angles, d: AXK1Dims, lora_scale: float):
    """Latent attention of one sequence ``x`` ``[T, hidden]``: the
    block's output before the residual."""
    t = x.shape[0]
    proj = lambda a, name: _proj(a, p[name], ad.get(name), lora_scale)  # noqa: E731
    with jax.named_scope("mla_proj"):
        h = rms_norm(x, p["attn_norm"], d.rms_eps)
        c_q = rms_norm(proj(h, "wqa"), p["q_norm"], d.rms_eps)
        q = proj(c_q, "wqb").reshape(t, d.heads, d.qk_nope + d.qk_rope)
        kva = proj(h, "wkva")
        c_kv = rms_norm(kva[:, :d.kv_rank], p["kv_norm"], d.rms_eps)
        k_r = apply_rope(kva[:, None, d.kv_rank:], angles)  # one head
        kv = proj(c_kv, "wkvb").reshape(t, d.heads, d.qk_nope + d.v_dim)
        q_r = apply_rope(q[..., d.qk_nope:], angles)
    with jax.named_scope("mla_attn"):
        out = latent_attention.causal_attention(
            q[..., :d.qk_nope], q_r, kv[..., :d.qk_nope], k_r[:, 0],
            kv[..., d.qk_nope:], d.attn_scale, d.q_chunk)
    with jax.named_scope("mla_proj"):
        return proj(out.reshape(t, d.heads * d.v_dim), "wo")


def expert_block(p, h, d: AXK1Dims, frozen: bool, stack=None):
    """The shared expert, whole, plus this chip's share of the routed
    layer, for one normed sequence ``h``; and the layer's counters."""
    with jax.named_scope("moe_shared"):
        shared = _swiglu(h, p["shared_w1"], p["shared_w3"], p["shared_w2"])
    with jax.named_scope("moe_route"):
        disp = moe.route(h, p["router"], top_k=d.experts_per_token,
                         experts_held=d.experts_held,
                         expert_offset=d.expert_offset, tile=d.moe_tile,
                         scoring="sigmoid", n_group=d.n_group,
                         topk_group=d.topk_group, gate_scale=d.gate_scale)
    with jax.named_scope("moe_experts"):
        cd = h.dtype
        ffn = moe.expert_ffn_frozen if frozen else moe.expert_ffn
        y = ffn(h, p["w1"].astype(cd), p["w3"].astype(cd),
                p["w2"].astype(cd), disp.row_token, disp.row_gate,
                disp.tile_expert, disp.n_tiles, stack)
        y = checkpoint_name(y, "moe_out")
    counts = disp.counts.astype(jnp.float32)
    per_group = d.num_experts // d.n_group
    lo = d.expert_offset // per_group
    hi = (d.expert_offset + d.experts_held - 1) // per_group
    hit = ((disp.groups >= lo) & (disp.groups <= hi)).any(-1)
    return shared + y, jnp.stack([
        disp.held_share, counts.max() / jnp.maximum(counts.mean(), 1.0),
        hit.mean(dtype=jnp.float32), moe.tile_fill(disp, d.moe_tile)])


def dense_layer(p, ad, x, angles, d: AXK1Dims, lora_scale: float):
    x = x + attention_block(p, ad, x, angles, d, lora_scale)
    with jax.named_scope("dense_mlp"):
        h = rms_norm(x, p["mlp_norm"], d.rms_eps)
        return x + _swiglu(h, p["w1"], p["w3"], p["w2"])


def expert_layer(p, ad, stack, x, angles, d: AXK1Dims, lora_scale: float,
                 frozen: bool):
    """One expert layer on one sequence: (x, its ``AUX_COUNTERS``)."""
    x = x + attention_block(p, ad, x, angles, d, lora_scale)
    y, stats = expert_block(p, rms_norm(x, p["mlp_norm"], d.rms_eps), d,
                            frozen, stack)
    return x + y, stats


def _normal(stddev: float):
    """Drawn in float32 and rounded once to the stored dtype, leaf by
    leaf: under ``jit`` the draw and the cast are one fusion, so a
    bfloat16 base is never held in float32."""
    def init(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                * stddev).astype(dtype)

    return init


class AXK1DecoderLM(nn.Module):
    vocab_size: int
    seq_len: int
    layers: int  # the leading dense layer and layers - 1 expert layers
    dims: AXK1Dims
    inv_freq: Any  # tuple of qk_rope // 2 floats
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    aux_counters = AUX_COUNTERS
    applies_adapters = True  # models/lora.LoRAModel.apply

    def _attention_shapes(self):
        d = self.dims
        return {
            "wqa": (d.hidden, d.q_rank),
            "wqb": (d.q_rank, d.heads * (d.qk_nope + d.qk_rope)),
            "wkva": (d.hidden, d.kv_rank + d.qk_rope),
            "wkvb": (d.kv_rank, d.heads * (d.qk_nope + d.v_dim)),
            "wo": (d.heads * d.v_dim, d.hidden),
        }, {
            "attn_norm": d.hidden, "mlp_norm": d.hidden,
            "q_norm": d.q_rank, "kv_norm": d.kv_rank,
        }

    def _leaves(self, prefix: str, lead, matrices) -> Dict[str, jnp.ndarray]:
        shapes, norms = self._attention_shapes()
        shapes.update(matrices)
        out = {n: self.param(prefix + n, _normal(0.02), lead + shape,
                             self.param_dtype)
               for n, shape in shapes.items()}
        out.update({n: self.param(prefix + n, nn.initializers.ones,
                                  lead + (width,), self.param_dtype)
                    for n, width in norms.items()})
        return out

    @nn.compact
    def __call__(self, tokens, train: bool = False, adapters=None,
                 lora_scale: float = 0.0):
        """``tokens`` ``[B, T]`` -> (float32 logits ``[B, T, vocab]``,
        aux). ``adapters``: the factors of the targeted projections
        (``models/lora.py``); with them, everything else is frozen."""
        d = self.dims
        b, t = tokens.shape
        # embeddings at the scale of 0.02 sqrt(hidden) (models/keye.py)
        embed = self.param("embed", _normal(1.0),
                           (self.vocab_size, d.hidden), self.param_dtype)
        x = jnp.take(embed, tokens, axis=0).astype(self.compute_dtype)
        angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
            self.inv_freq, jnp.float32)
        first = self._leaves("dense_", (), {
            "w1": (d.hidden, d.dense_width), "w3": (d.hidden, d.dense_width),
            "w2": (d.dense_width, d.hidden)})
        n_moe = self.layers - 1
        stacked = self._leaves("layers_", (n_moe,), {
            "router": (d.hidden, d.num_experts),
            "shared_w1": (d.hidden, d.expert_width),
            "shared_w3": (d.hidden, d.expert_width),
            "shared_w2": (d.expert_width, d.hidden),
            "w1": (d.experts_held, d.hidden, d.expert_width),
            "w3": (d.experts_held, d.hidden, d.expert_width),
            "w2": (d.experts_held, d.expert_width, d.hidden)})
        frozen = adapters is not None
        adapters = adapters or {}
        ad_first = {k[len("dense_"):]: v for k, v in adapters.items()
                    if k.startswith("dense_")}
        ad_stacked = {k[len("layers_"):]: v for k, v in adapters.items()
                      if k.startswith("layers_")}
        keep = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse", "moe_dispatch", "moe_out")
        if self.is_initializing():
            # shapes only: init need not run 4,096-token attention
            stats = jnp.ones((n_moe, b, len(AUX_COUNTERS)), jnp.float32)
        else:
            layer0 = jax.checkpoint(
                partial(dense_layer, d=d, lora_scale=lora_scale),
                policy=keep)
            x = jax.vmap(layer0, in_axes=(None, None, 0, None))(
                first, ad_first, x, angles)
            layer = jax.checkpoint(
                partial(expert_layer, d=d, lora_scale=lora_scale,
                        frozen=frozen), policy=keep)
            x, stats = jax.lax.scan(
                lambda x, pal: jax.vmap(
                    layer, in_axes=(None, None, None, 0, None))(
                        *pal[:2], expert_stack(stacked, pal[2], x.dtype), x,
                        angles),
                x, (stacked, ad_stacked, jnp.arange(n_moe)))
            # stats: [layers, B, counters]
        final_norm = self.param("final_norm", nn.initializers.ones,
                                (d.hidden,), self.param_dtype)
        head = self.param("head", _normal(0.02), (d.hidden, self.vocab_size),
                          self.param_dtype)
        with jax.named_scope("lm_head"):
            x = rms_norm(x, final_norm, d.rms_eps)
            logits = jnp.dot(x, head.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        return logits, {"counters": dict(zip(AUX_COUNTERS, stats.mean(0).T))}


@model_registry.register("axk1_decoder")
def _build(num_classes: int = 0, vocab_size: int = 20480, seq_len: int = 4096,
           layers: int = 5, hidden: int = 7168, heads: int = 64,
           q_rank: int = 1536, kv_rank: int = 512, qk_nope: int = 128,
           qk_rope: int = 64, v_dim: int = 128, dense_width: int = 18432,
           num_experts: int = 192, experts_held: int = 12,
           expert_offset: int = 0, experts_per_token: int = 8,
           expert_width: int = 2048, n_group: int = 8, topk_group: int = 4,
           gate_scale: float = 2.5, rope_theta: float = 10000.0,
           rope_factor: float = 32.0, rope_original: int = 4096,
           rope_beta_fast: float = 32.0, rope_beta_slow: float = 1.0,
           rope_mscale_all_dim: float = 1.0, rms_eps: float = 1e-6,
           q_chunk: int = 512, moe_tile: int = 256,
           compute_dtype=jnp.float32, param_dtype=jnp.float32, **_):
    del num_classes  # LM: the output width is vocab_size
    if layers < 2:
        raise ValueError(f"layers ({layers}) counts the leading dense "
                         f"layer and at least one expert layer")
    if num_experts % n_group or not 1 <= topk_group <= n_group:
        raise ValueError(
            f"{num_experts} experts do not form {n_group} equal groups of "
            f"which {topk_group} are kept")
    if experts_per_token > topk_group * (num_experts // n_group):
        raise ValueError(
            f"{experts_per_token} experts per token do not fit the "
            f"{topk_group} kept groups of {num_experts // n_group}")
    if not 0 <= expert_offset <= num_experts - experts_held:
        raise ValueError(
            f"experts {expert_offset}..{expert_offset + experts_held} are "
            f"not among the router's {num_experts}")
    m = yarn_attention_factor(rope_factor, rope_mscale_all_dim)
    dims = AXK1Dims(hidden, heads, q_rank, kv_rank, qk_nope, qk_rope, v_dim,
                    dense_width, num_experts, experts_held, expert_offset,
                    experts_per_token, expert_width, n_group, topk_group,
                    float(gate_scale), rms_eps,
                    (qk_nope + qk_rope) ** -0.5 * m * m, q_chunk, moe_tile)
    inv_freq = yarn_inv_freq(qk_rope, rope_theta, rope_factor, rope_original,
                             rope_beta_fast, rope_beta_slow)
    return AXK1DecoderLM(vocab_size=vocab_size, seq_len=seq_len,
                         layers=layers, dims=dims,
                         inv_freq=tuple(float(f) for f in inv_freq),
                         compute_dtype=compute_dtype, param_dtype=param_dtype)


_build.aux_counters = AUX_COUNTERS  # models.returns_aux reads it


def _lm_spec(vocab_size: int = 20480, seq_len: int = 4096, **_):
    return (seq_len,), jnp.int32


_INPUT_SPECS["axk1_decoder"] = _lm_spec
