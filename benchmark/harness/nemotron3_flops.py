"""Operations one trained token of the Nemotron-H decoder REQUIRES on this
chip, from a configuration file's sizes and the routing that happened
(the program's counter): matrix products 6 FLOPs per weight per token (2
forward, 4 backward); recomputed and padded work is not counted.

  mamba        W_in and W_out of every `M` layer, and the four taps of its
               depthwise convolution
  scan         a chunk of Q steps: 2 Q^2 N a group (C B^T) + 2 Q^2 P (the
               masked product) + 4 Q N P (the state read and written) a
               head forward, twice that backward (kernels/ssd_scan_fwd.py)
  attention    q k v o of the `*` layers; 2d (scores) + 2d (values) per
               head and causal pair forward, twice that backward
  mixture      the router (whole) and the shared expert's two products
               of every `E` layer
  experts      2 products of 2 H F for every (token, held expert) pair
               that was routed
  head         the sliced vocabulary
"""
from __future__ import annotations

from harness import nemotron3_weights
from kernels import ssd_scan_fwd


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def per_token(cfg: dict, seq: int, routed_pairs_per_token: float) -> dict:
    """{part: training FLOPs per token} of all layers and the head;
    `routed_pairs_per_token` is of one mixture layer."""
    s = nemotron3_weights.shapes(cfg)
    kinds = nemotron3_weights.kinds(cfg)
    n_m, n_e, n_a = (kinds.count(k) for k in "ME*")
    h, d = s["hidden_size"], s["head_dim"]
    heads, kvh = s["num_attention_heads"], s["num_key_value_heads"]
    inner = s["mamba_num_heads"] * s["mamba_head_dim"]
    conv = inner + 2 * s["n_groups"] * s["ssm_state_size"]
    mamba = h * (inner + conv + s["mamba_num_heads"]) + inner * h \
        + s["conv_kernel"] * conv
    scan = ssd_scan_fwd.chunk_ops(
        s["chunk_size"], s["mamba_num_heads"], s["mamba_head_dim"],
        s["n_groups"], s["ssm_state_size"]) / s["chunk_size"]
    return {
        "mamba": 6.0 * n_m * mamba,
        "scan": 3.0 * n_m * scan,
        "attention": 6.0 * n_a * (2 * h * heads * d + 2 * h * kvh * d)
        + 3.0 * 4 * d * heads * n_a * causal_pairs(seq) / seq,
        "mixture": 6.0 * n_e * (
            h * s["n_routed_experts"]
            + 2 * h * s["moe_shared_expert_intermediate_size"]),
        "experts": 6.0 * n_e * routed_pairs_per_token
        * 2 * h * s["moe_intermediate_size"],
        "head": 6.0 * h * s["vocab_size"],
    }


def train_flops_per_token(cfg, seq, routed_pairs_per_token) -> float:
    return sum(per_token(cfg, seq, routed_pairs_per_token).values())
