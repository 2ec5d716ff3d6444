"""attn_probs_mean: the chunk's scores again (one product, 2d a pair and
head, counted as kernels/attn_probs_stats.py does), exp(s - lse), summed
over the heads into the float32 [t, s] target."""


def cost(t, s, heads, kv_heads, d, itemsize=2):
    ops = 2 * d * heads * t * (s + 1) // 2
    nbytes = (heads * t * d + kv_heads * s * d) * itemsize + t * s \
        + heads * t * 4 + t * s * 4
    return ops, nbytes


def from_cell(cell, ctx=None):
    from harness import keye_weights

    s = keye_weights.shapes(cell["config"])
    return cost(cell["config"]["sa_config"]["q_chunk_size"],
                cell["traffic"]["seq"], s["num_attention_heads"],
                s["num_key_value_heads"], s["head_dim"])
