"""The `window_attention` scope: causal attention over each query's
`window` latest keys, forward once and backward once. A kept pair costs 2d
(scores) + 2d (values) per head forward; backward recomputes the scores
(one product) and forms dV, dP, dQ, dK (four), as
kernels/sparse_attention.py counts. The forward that per-layer recompute
runs again, and the pairs a tile visits outside the band, are not
required and not counted."""


def band_pairs(seq, window):
    """sum_t min(window, t + 1) of one sequence."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def cost(b, s, heads, kv_heads, d, pairs, itemsize=2):
    """`pairs`: kept (query, key) pairs of all `b` sequences, one head."""
    ops = (4 + 10) * d * heads * pairs
    q_rows, kv_rows = b * s * heads * d, b * s * kv_heads * d
    # forward: read q k v, write o and the log-sum-exp; backward: read
    # q k v o do, write dq dk dv, read the log-sum-exp and delta
    nbytes = (2 * q_rows + 2 * kv_rows) * itemsize + b * heads * s * 4
    nbytes += (4 * q_rows + 4 * kv_rows) * itemsize + 2 * b * heads * s * 4
    return ops, nbytes


KIND = "sliding_attention"      # the layers whose attention this scope is


def layers(cell, kind=KIND) -> int:
    """How many layers of `kind` a step of the cell runs."""
    c = cell["config"]
    return c["layer_types"][:c["num_hidden_layers"]].count(kind)


def from_cell(cell, ctx=None):
    """One sliding LAYER's cost at the cell's shapes."""
    c, job = cell["config"], cell["traffic"]
    b = job["batch"] // cell["chips"]
    return cost(b, job["seq"], c["num_attention_heads"],
                c["num_key_value_heads"], c["head_dim"],
                b * band_pairs(job["seq"], c["sliding_window"]))
