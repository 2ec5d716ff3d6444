"""Operations one trained token of Ling-3.0's decoder REQUIRES on this chip,
from a configuration file's sizes and the routing that happened (the
program's counter): matrix products 6 FLOPs per weight per token (2 forward,
4 backward); recomputed and padded work is not counted.

  kda          W_q, W_k, W_v, W_f, W_b, W_g and W_o of every KDA layer, and
               the four taps of its three depthwise convolutions
  scan         a chunk of C tokens a head (kernels/kda_fwd.py): the pairs A
               and B, the triangular solve as one product with its inverse,
               the state read and written; twice that backward
  mla          W_q, W_kva, W_kvb, W_g, W_o of the MLA layers; 2 x 192
               (scores) + 2 x 128 (values) per head and causal pair forward,
               twice that backward
  dense        the leading layers' three products
  mixture      the router (whole) and the shared expert's three products of
               every mixture layer
  experts      3 products of 2 H F for every (token, held expert) pair that
               was routed
  head         the sliced vocabulary
"""
from __future__ import annotations

from harness import ling3_weights
from kernels import kda_fwd


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def per_token(cfg: dict, seq: int, routed_pairs_per_token: float,
              chunk: int = kda_fwd.CHUNK) -> dict:
    """{part: training FLOPs per token} of all layers and the head;
    `routed_pairs_per_token` is of one mixture layer."""
    s = ling3_weights.shapes(cfg)
    kinds = ling3_weights.kinds(cfg)
    n_kda, n_mla, n_dense, n_moe = (kinds.count(k) for k in (
        "kda", "mla", "dense", "moe"))
    h, heads, d = s["hidden_size"], s["num_attention_heads"], s["head_dim"]
    inner = heads * d
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    dv, rank = s["v_head_dim"], s["kv_lora_rank"]
    kda = 4 * h * inner + 2 * h * heads + inner * h \
        + 3 * s["short_conv_kernel_size"] * inner
    mla = (h * heads * qk + h * (rank + s["qk_rope_head_dim"])
           + rank * heads * (s["qk_nope_head_dim"] + dv) + h * heads
           + heads * dv * h)
    return {
        "kda": 6.0 * n_kda * kda,
        "scan": 3.0 * n_kda * heads * kda_fwd.chunk_ops(chunk, d, d) / chunk,
        "mla": 6.0 * n_mla * mla
        + 3.0 * 2 * (qk + dv) * heads * n_mla * causal_pairs(seq) / seq,
        "dense": 6.0 * n_dense * 3 * h * s["intermediate_size"],
        "mixture": 6.0 * n_moe * (
            h * s["num_experts"]
            + 3 * h * s["moe_shared_expert_intermediate_size"]),
        "experts": 6.0 * n_moe * routed_pairs_per_token
        * 3 * h * s["moe_intermediate_size"],
        "head": 6.0 * h * s["vocab_size"],
    }


def train_flops_per_token(cfg, seq, routed_pairs_per_token) -> float:
    return sum(per_token(cfg, seq, routed_pairs_per_token).values())
