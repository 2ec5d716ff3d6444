"""splash_bwd: causal attention backward. Recomputes the scores (one
product) and forms dV, dP, dQ, dK (four): five products of 2d per pair."""


def cost(b, s, heads, d, itemsize=2, causal=True):
    pairs = s * (s + 1) // 2 if causal else s * s
    ops = 10 * d * pairs * b * heads
    # read q k v o do, write dq dk dv; read the log-sum-exp and delta
    nbytes = 8 * b * s * heads * d * itemsize + 2 * b * heads * s * 4
    return ops, nbytes


def from_cell(cell, ctx=None):
    c, job = cell["config"], cell["traffic"]
    return cost(job["batch"] // cell["chips"], job["seq"],
                c["num_attention_heads"], c["head_dim"])
