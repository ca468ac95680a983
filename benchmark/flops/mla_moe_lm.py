"""A.X-K1's decoder as ``models/axk1.py`` builds one chip's share of it,
with rank-``lora_rank`` adapters on the five projections of its latent
attention, counted from shapes. One example is one sequence.

A product is one of three kinds. *Frozen*: against a weight that takes
no gradient (every matrix of the base): the forward product and, in the
backward pass, the input's gradient, 4 FLOPs per MAC. *Trained*: the
adapters' side products ``(x A) B``, whose factors take gradients:
forward, input gradient, weight gradient, 6 FLOPs per MAC. *Attention*:
scores and values over the causal pairs, which have no weights and
whose both operands take gradients, 6 FLOPs per MAC.

``macs_by_part`` gives that split honestly. ``forward_macs`` returns the
TRAINING-EQUIVALENT count that the harness's generic ``mfu_pct`` can
multiply by 6 (``harness/flops.train_flops_per_example``): trained and
attention products once, frozen products at 2/3 (an integer, rounded
down), so that 6 x ``forward_macs`` = 6 x (trained + attention) + 4 x
frozen. It is NOT the forward pass's product count; that is the sum of
``macs_by_part``'s three kinds.

The held experts' products are counted by expectation under a uniform
router (``experts_per_token * experts_held / num_experts`` assignments a
token: a half, at the published sizes); the masked half of attention's
diagonal tiles, rematerialisation and padding rows of the expert tiles
do not count. Norms, RoPE, softmax, the selection and the embedding
lookup are not products.
"""

from typing import Any, Dict

PROJECTIONS = ("wqa", "wqb", "wkva", "wkvb", "wo")


def projection_shapes(hidden: int = 7168, heads: int = 64, q_rank: int = 1536,
                      kv_rank: int = 512, qk_nope: int = 128,
                      qk_rope: int = 64, v_dim: int = 128,
                      **_: Any) -> Dict[str, tuple]:
    """(d_in, d_out) of latent attention's five projections."""
    return {
        "wqa": (hidden, q_rank),
        "wqb": (q_rank, heads * (qk_nope + qk_rope)),
        "wkva": (hidden, kv_rank + qk_rope),
        "wkvb": (kv_rank, heads * (qk_nope + v_dim)),
        "wo": (heads * v_dim, hidden),
    }


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def macs_by_part(seq_len: int = 4096, vocab_size: int = 20480,
                 layers: int = 5, hidden: int = 7168, heads: int = 64,
                 q_rank: int = 1536, kv_rank: int = 512, qk_nope: int = 128,
                 qk_rope: int = 64, v_dim: int = 128,
                 dense_width: int = 18432, num_experts: int = 192,
                 experts_held: int = 12, experts_per_token: int = 8,
                 expert_width: int = 2048, lora_rank: int = 16,
                 **_: Any) -> Dict[str, Any]:
    """Multiply-accumulates of one sequence's forward pass: ``parts``
    (by named scope of the model; ``adapters`` is the side products
    inside ``mla_proj``) and the three kinds ``frozen``, ``trained``,
    ``attention`` that they add up to."""
    t = seq_len
    shapes = projection_shapes(hidden, heads, q_rank, kv_rank, qk_nope,
                               qk_rope, v_dim)
    expert = 3 * hidden * expert_width
    moe_layers = layers - 1
    held_rows = t * experts_per_token * experts_held // num_experts
    parts = {
        "mla_proj": layers * t * sum(i * o for i, o in shapes.values()),
        "adapters": layers * t * sum(lora_rank * (i + o)
                                     for i, o in shapes.values()),
        "mla_attn": layers * causal_pairs(t) * heads
        * (qk_nope + qk_rope + v_dim),
        "dense_mlp": t * 3 * hidden * dense_width,
        "moe_route": moe_layers * t * hidden * num_experts,
        "moe_shared": moe_layers * t * expert,
        "moe_experts": moe_layers * held_rows * expert,
        "lm_head": t * hidden * vocab_size,
    }
    trained, attention = parts["adapters"], parts["mla_attn"]
    return {"parts": parts, "trained": trained, "attention": attention,
            "frozen": sum(parts.values()) - trained - attention}


def forward_macs(**args: Any) -> int:
    """The training-equivalent count (module docstring): trained and
    attention products once, frozen products 2/3, rounded down."""
    kinds = macs_by_part(**args)
    return kinds["trained"] + kinds["attention"] + 2 * kinds["frozen"] // 3


def parameters(vocab_size: int = 20480, layers: int = 5, hidden: int = 7168,
               heads: int = 64, q_rank: int = 1536, kv_rank: int = 512,
               qk_nope: int = 128, qk_rope: int = 64, v_dim: int = 128,
               dense_width: int = 18432, num_experts: int = 192,
               experts_held: int = 12, expert_width: int = 2048,
               lora_rank: int = 16, **_: Any) -> Dict[str, int]:
    """``{"frozen": the base, "trained": the adapters}``."""
    shapes = projection_shapes(hidden, heads, q_rank, kv_rank, qk_nope,
                               qk_rope, v_dim)
    attention = sum(i * o for i, o in shapes.values())
    norms = 2 * hidden + q_rank + kv_rank
    expert = 3 * hidden * expert_width
    dense_layer = attention + norms + 3 * hidden * dense_width
    expert_layer = (attention + norms + hidden * num_experts
                    + (1 + experts_held) * expert)
    return {
        "frozen": dense_layer + (layers - 1) * expert_layer
        + 2 * vocab_size * hidden + hidden,
        "trained": layers * sum(lora_rank * (i + o)
                                for i, o in shapes.values()),
    }
