"""Operations one trained token of the Keye-VL-2.0 decoder REQUIRES on
this chip, from a configuration file's sizes and the routing and
selection that happened (the program's counters): matrix products 6
FLOPs per weight per token (2 forward, 4 backward); recomputed and
padded work, and the indexer loss's second look at the attention
probabilities, are not counted.

  projections  q k v o, the indexer's three, the router (whole)
  experts      3 products of 2 H F for every (token, held expert) pair
               that was routed
  indexer      every causal pair: 16 heads x 64 (+ the weighted sum)
  attention    the SELECTED pairs only: 2d (scores) + 2d (values) per
               head forward, twice that backward
  head         the sliced vocabulary
"""
from __future__ import annotations

from harness import keye_weights


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def selected_pairs(seq: int, topk: int) -> int:
    """sum_t min(topk, t + 1): what an exact top-k keeps of one sequence."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def per_token(cfg: dict, seq: int, routed_pairs_per_token: float,
              kept_pairs_per_token: float | None = None) -> dict:
    """{part: training FLOPs per token} of all layers and the head."""
    s = keye_weights.shapes(cfg)
    h, d, n = s["hidden_size"], s["head_dim"], s["num_layers"]
    heads, kvh = s["num_attention_heads"], s["num_key_value_heads"]
    nj, di = s["index_n_heads"], s["index_head_dim"]
    if kept_pairs_per_token is None:
        kept_pairs_per_token = selected_pairs(seq, s["index_topk"]) / seq
    proj = (2 * h * heads * d + 2 * h * kvh * d       # q, o; k, v
            + h * nj * di + h * di + h * nj           # the indexer's
            + h * s["num_experts"])                   # the router
    return {
        "projections": 6.0 * n * proj,
        "experts": 6.0 * n * routed_pairs_per_token
        * 3 * h * s["moe_intermediate_size"],
        "indexer": 3.0 * n * (2 * nj * di + 2 * nj)
        * causal_pairs(seq) / seq,
        "attention": 3.0 * n * 4 * d * heads * kept_pairs_per_token,
        "head": 6.0 * h * s["vocab_size"],
    }


def train_flops_per_token(cfg, seq, routed_pairs_per_token,
                          kept_pairs_per_token=None) -> float:
    return sum(per_token(cfg, seq, routed_pairs_per_token,
                         kept_pairs_per_token).values())
