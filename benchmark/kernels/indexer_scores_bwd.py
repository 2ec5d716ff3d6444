"""indexer_scores_bwd: the pull-back of kernels/indexer_scores_fwd.py
along the float32 d_scores[t, s]: the product again, dq_idx and dk_idx,
6d a pair and head (dw rides the recomputed scores), over the same causal
pairs t (s + 1) / 2. Read: the three inputs and d_scores; written: the
three gradients in the inputs' types."""


def cost(t, s, heads, d, itemsize=2):
    ops = 6 * d * heads * t * (s + 1) // 2
    inputs = (heads * t * d + s * d + t * heads) * itemsize
    return ops, 2 * inputs + t * s * 4


def from_cell(cell, ctx=None):
    sa = cell["config"]["sa_config"]
    return cost(sa["q_chunk_size"], cell["traffic"]["seq"],
                sa["indexer_num_heads"], sa["indexer_head_dim"])
