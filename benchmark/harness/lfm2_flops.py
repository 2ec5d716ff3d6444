"""Operations one trained token of LFM2-MoE's decoder REQUIRES on this chip,
from a configuration file's sizes and the routing that happened (the
program's counter): matrix products 6 FLOPs per weight per token (2 forward,
4 backward); recomputed and padded work is not counted.

  conv         W_in (hidden x 3 hidden) and W_out of every gated-convolution
               layer, its taps and its two gates (a product a channel each)
  attention    W_q, W_k, W_v, W_o of the attention layers; 2 x 64 (scores) +
               2 x 64 (values) per query head and causal pair forward, twice
               that backward
  dense        the leading layers' three products
  mixture      the router (whole) of every mixture layer
  experts      3 products of 2 H F for every (token, held expert) pair that
               was routed
  head         the sliced vocabulary (the tied head's product; the gather
               costs none)
"""
from __future__ import annotations

from harness import lfm2_weights


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def per_token(cfg: dict, seq: int, routed_pairs_per_token: float) -> dict:
    """{part: training FLOPs per token} of all layers and the head;
    `routed_pairs_per_token` is of one mixture layer."""
    s = lfm2_weights.shapes(cfg)
    kinds = lfm2_weights.kinds(cfg)
    n_conv, n_attn, n_dense, n_moe = (kinds.count(k) for k in (
        "conv", "attn", "dense", "moe"))
    h, heads, kv, d = (s["hidden_size"], s["num_attention_heads"],
                       s["num_key_value_heads"], s["head_dim"])
    conv = 3 * h * h + h * h + s["conv_L_cache"] * h + h
    attn = h * heads * d + 2 * h * kv * d + heads * d * h
    return {
        "conv": 6.0 * n_conv * conv,
        "attention": 6.0 * n_attn * attn
        + 3.0 * 2 * 2 * d * heads * n_attn * causal_pairs(seq) / seq,
        "dense": 6.0 * n_dense * 3 * h * s["intermediate_size"],
        "mixture": 6.0 * n_moe * h * s["num_experts"],
        "experts": 6.0 * n_moe * routed_pairs_per_token
        * 3 * h * s["moe_intermediate_size"],
        "head": 6.0 * h * s["vocab_size"],
    }


def train_flops_per_token(cfg, seq, routed_pairs_per_token) -> float:
    return sum(per_token(cfg, seq, routed_pairs_per_token).values())
