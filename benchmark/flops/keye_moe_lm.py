"""The Keye-VL-2.0-30B-A3B language decoder as ``models/keye.py`` builds
one chip's share of it, counted from shapes. One example is one sequence.

``forward_macs`` is the useful work of one sequence's forward pass: the
attention and indexer projections, the index scores of every causal pair
(the indexer has to score all of them to choose), attention scores and
values over the *selected* pairs only (``min(t + 1, index_topk)`` keys
for query ``t``), the router over all experts, the held experts' three
products for the token-expert assignments that fall on them by
expectation (``experts_per_token * experts_held / num_experts`` per
token: one, at the published sizes), and the sliced head. Masked-out
pairs that a dense-masked kernel computes, rematerialisation and padding
rows of the expert tiles do not count. Norms, RoPE, softmax, selection
and the embedding lookup are not products.
"""

from typing import Any


def selected_pairs(seq_len: int, index_topk: int) -> int:
    """Query-key pairs attention reads: sum over t of min(t + 1, topk)."""
    k = min(seq_len, index_topk)
    return k * (k + 1) // 2 + (seq_len - k) * index_topk


def expert_macs(seq_len: int = 8192, hidden: int = 2048,
                num_experts: int = 128, experts_held: int = 16,
                experts_per_token: int = 8, expert_width: int = 768,
                **_: Any) -> int:
    """The held experts' products of one sequence's forward pass, by
    expectation (a uniform router)."""
    rows = seq_len * experts_per_token * experts_held // num_experts
    return rows * 3 * hidden * expert_width


def forward_macs(seq_len: int = 8192, vocab_size: int = 18992,
                 layers: int = 4, hidden: int = 2048, heads: int = 32,
                 kv_heads: int = 4, head_dim: int = 128,
                 num_experts: int = 128, experts_held: int = 16,
                 experts_per_token: int = 8, expert_width: int = 768,
                 index_heads: int = 16, index_head_dim: int = 64,
                 index_topk: int = 2048) -> int:
    t = seq_len
    causal = t * (t + 1) // 2
    per_layer = (
        t * hidden * (2 * heads + 2 * kv_heads) * head_dim   # q, o, k, v
        + t * hidden * (index_heads * index_head_dim + index_head_dim
                        + index_heads)                       # indexer
        + causal * index_heads * index_head_dim              # index scores
        + 2 * selected_pairs(t, index_topk) * heads * head_dim
        + t * hidden * num_experts                           # router
        + expert_macs(t, hidden, num_experts, experts_held,
                      experts_per_token, expert_width)
    )
    return layers * per_layer + t * hidden * vocab_size


def parameters(vocab_size: int = 18992, layers: int = 4, hidden: int = 2048,
               heads: int = 32, kv_heads: int = 4, head_dim: int = 128,
               num_experts: int = 128, experts_held: int = 16,
               expert_width: int = 768, index_heads: int = 16,
               index_head_dim: int = 64, **_: Any) -> int:
    per_layer = (
        hidden * (2 * heads + 2 * kv_heads) * head_dim
        + hidden * (index_heads * index_head_dim + index_head_dim
                    + index_heads)
        + hidden * num_experts
        + experts_held * 3 * hidden * expert_width
        + 2 * hidden + 2 * head_dim + 2 * index_head_dim      # norms
    )
    return layers * per_layer + 2 * vocab_size * hidden + hidden
