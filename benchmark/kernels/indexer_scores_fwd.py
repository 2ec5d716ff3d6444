"""indexer_scores_fwd: the index scores I[t, s] = sum_j w[t, j] relu(q_idx
[t, j] . k_idx[s]) of one sequence's query chunk: one product a head, 2d a
pair and head, the float32 [t, s] written. Counted over the chunk's causal
pairs (no score beyond them is read), averaged over a sequence's chunks:
t (s + 1) / 2. Key tiles visited beyond those pairs are not required."""


def cost(t, s, heads, d, itemsize=2):
    ops = 2 * d * heads * t * (s + 1) // 2
    nbytes = (heads * t * d + s * d + t * heads) * itemsize + t * s * 4
    return ops, nbytes


def from_cell(cell, ctx=None):
    sa = cell["config"]["sa_config"]
    return cost(sa["q_chunk_size"], cell["traffic"]["seq"],
                sa["indexer_num_heads"], sa["indexer_head_dim"])
