"""Operations a GPT-3 block stack requires, from a configuration file's
sizes — never from the built model's parameter count (which holds the
embeddings).

Matrix multiplications: 6 FLOPs per weight per token (2 forward, 4
backward) over the block matrices and the tied output head; embeddings
are look-ups and count nothing. Causal attention: each of the S(S+1)/2
query-key pairs of a sequence costs 2d (scores) + 2d (values) forward
and twice that backward, per head. Recomputed operations are not counted.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 3 * h * h + h * h + h * f + f * h
    return cfg["num_layers"] * per_layer + cfg["vocab_size"] * h


def attention_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs per trained token at sequence length seq."""
    h = cfg["hidden_size"]          # = heads * head_dim
    attn = 3 * 4 * h * attention_pairs(seq) / seq
    return 6.0 * matmul_params(cfg) + cfg["num_layers"] * attn
