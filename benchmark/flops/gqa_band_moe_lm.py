"""A decoder of grouped-query attention under a band, layers of two
kinds in a period, sparse experts in every layer, as ``models/
mellum2.py`` builds one chip's share of Mellum2-12B-A2.5B, counted from
shapes. One example is one sequence.

Attention's pairs are counted by layer kind: a *sliding* layer's query
``t`` reads ``min(t + 1, sliding_window)`` keys (the band), a *full*
layer's ``t + 1`` (the triangle); a pair costs ``2 x head_dim`` MACs a
query head (its score and its value). ``macs_by_part`` gives the parts
``attention_window``, ``attention_full``, ``projections`` (q, k, v, o),
``experts`` (the held experts' three products for the token-expert
assignments that fall on them by expectation under a uniform router:
``experts_per_token * experts_held / num_experts`` a token, one at the
published sizes), ``router`` (over all experts) and ``head`` (the sliced
vocabulary); ``forward_macs`` is their sum. Every product has weights or
operands that take gradients, so training costs 6 FLOPs a MAC. The
masked pairs of the tiles a kernel visits, rematerialisation and the
padding rows of the expert tiles do not count. Norms, RoPE, softmax and
the embedding lookup are not products.
"""

from typing import Any, Dict, Optional, Sequence

PERIOD = ("sliding", "sliding", "sliding", "full")


def band_pairs(seq_len: int, window: Optional[int]) -> int:
    """Query-key pairs a layer's attention reads: sum over t of ``min(t
    + 1, window)``; the causal triangle without a window."""
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def layer_kinds(layers: int, period: Sequence[str]) -> Dict[str, int]:
    """How many of the ``layers`` are of each kind."""
    kinds = [period[i % len(period)] for i in range(layers)]
    return {"sliding": kinds.count("sliding"), "full": kinds.count("full")}


def macs_by_part(seq_len: int = 16384, vocab_size: int = 12288,
                 layers: int = 4, period: Sequence[str] = PERIOD,
                 hidden: int = 2304, heads: int = 32, kv_heads: int = 4,
                 head_dim: int = 128, num_experts: int = 64,
                 experts_held: int = 8, experts_per_token: int = 8,
                 expert_width: int = 896, sliding_window: int = 1024,
                 **_: Any) -> Dict[str, int]:
    t = seq_len
    n = layer_kinds(layers, period)
    pair = 2 * heads * head_dim  # a pair's score and value, every head
    held_rows = t * experts_per_token * experts_held // num_experts
    return {
        "attention_window": n["sliding"] * band_pairs(t, sliding_window)
        * pair,
        "attention_full": n["full"] * band_pairs(t, None) * pair,
        "projections": layers * t * hidden * (2 * heads + 2 * kv_heads)
        * head_dim,
        "experts": layers * held_rows * 3 * hidden * expert_width,
        "router": layers * t * hidden * num_experts,
        "head": t * hidden * vocab_size,
    }


def forward_macs(**args: Any) -> int:
    return sum(macs_by_part(**args).values())


def parameters(vocab_size: int = 12288, layers: int = 4, hidden: int = 2304,
               heads: int = 32, kv_heads: int = 4, head_dim: int = 128,
               num_experts: int = 64, experts_held: int = 8,
               expert_width: int = 896, **_: Any) -> int:
    per_layer = (
        hidden * (2 * heads + 2 * kv_heads) * head_dim
        + hidden * num_experts
        + experts_held * 3 * hidden * expert_width
        + 2 * hidden + 2 * head_dim                           # norms
    )
    return layers * per_layer + 2 * vocab_size * hidden + hidden
