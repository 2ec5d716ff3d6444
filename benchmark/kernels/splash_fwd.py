"""splash_fwd: causal attention forward, q k v [b, s, heads, d] bf16."""


def cost(b, s, heads, d, itemsize=2, causal=True):
    pairs = s * (s + 1) // 2 if causal else s * s
    ops = 4 * d * pairs * b * heads              # QK^T and PV, 2d each
    nbytes = 4 * b * s * heads * d * itemsize    # read q k v, write o
    nbytes += b * heads * s * 4                  # the log-sum-exp, fp32
    return ops, nbytes


def from_cell(cell, ctx=None):
    c, job = cell["config"], cell["traffic"]
    return cost(job["batch"] // cell["chips"], job["seq"],
                c["num_attention_heads"], c["head_dim"])
