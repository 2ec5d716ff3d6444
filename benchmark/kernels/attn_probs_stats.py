"""attn_probs_stats: the log-sum-exp of every (head, query row) of one
sequence's query chunk over its selected keys: one product q k^T, 2d a
pair and head. Counted over the chunk's causal pairs (no selected key
lies beyond them), averaged over a sequence's chunks: t (s + 1) / 2."""


def cost(t, s, heads, kv_heads, d, itemsize=2):
    ops = 2 * d * heads * t * (s + 1) // 2
    nbytes = (heads * t * d + kv_heads * s * d) * itemsize + t * s \
        + heads * t * 4
    return ops, nbytes


def from_cell(cell, ctx=None):
    from harness import keye_weights

    s = keye_weights.shapes(cell["config"])
    return cost(cell["config"]["sa_config"]["q_chunk_size"],
                cell["traffic"]["seq"], s["num_attention_heads"],
                s["num_key_value_heads"], s["head_dim"])
