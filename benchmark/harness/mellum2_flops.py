"""Operations one trained token of the Mellum 2 decoder REQUIRES on this
chip, from a configuration file's sizes and the routing that happened
(the program's counter): matrix products 6 FLOPs per weight per token (2
forward, 4 backward); recomputed and padded work is not counted.

  projections  q k v o and the router (whole), every layer
  experts      3 products of 2 H F for every (token, held expert) pair
               that was routed
  attention    2d (scores) + 2d (values) per head and pair forward, twice
               that backward: the band's pairs in a sliding layer, the
               causal pairs in a full one
  head         the sliced vocabulary
"""
from __future__ import annotations

from harness import mellum2_weights


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def band_pairs(seq: int, window: int) -> int:
    """sum_t min(window, t + 1): the pairs a sliding layer keeps of one
    sequence."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def per_token(cfg: dict, seq: int, routed_pairs_per_token: float) -> dict:
    """{part: training FLOPs per token} of all layers and the head;
    `routed_pairs_per_token` is of one layer."""
    s = mellum2_weights.shapes(cfg)
    h, d, n = s["hidden_size"], s["head_dim"], s["num_layers"]
    heads, kvh = s["num_attention_heads"], s["num_key_value_heads"]
    sliding = sum(k == "sliding_attention" for k in s["layer_types"])
    proj = 2 * h * heads * d + 2 * h * kvh * d + h * s["num_experts"]
    pairs = (sliding * band_pairs(seq, s["sliding_window"])
             + (n - sliding) * causal_pairs(seq)) / seq
    return {
        "projections": 6.0 * n * proj,
        "experts": 6.0 * n * routed_pairs_per_token
        * 3 * h * s["moe_intermediate_size"],
        "attention": 3.0 * 4 * d * heads * pairs,
        "head": 6.0 * h * s["vocab_size"],
    }


def train_flops_per_token(cfg, seq, routed_pairs_per_token) -> float:
    return sum(per_token(cfg, seq, routed_pairs_per_token).values())
