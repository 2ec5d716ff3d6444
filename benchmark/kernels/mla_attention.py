"""The `mla_attention` scope: latent attention in its expanded form, causal
attention over every earlier key at query / key rows of 192 (128 + 64 turned)
and value rows of 128, forward once and backward once. A head and causal pair
requires 2 x 192 (the score) + 2 x 128 (the value) operations forward and
twice that backward plus the scores' recomputation (2 x 192): kernels/
window_attention.py's count (4 d forward, 10 d backward) at unequal widths.
The zeros that fill a key row to 256 lanes, recompute's second forward and
the pairs a tile forms above the diagonal are not required. Bytes: q, k
(192), v, o (128) read or written once forward; q, k, v, o, do read and dq,
dk, dv written once backward."""


def cost(b, s, heads, d_qk, d_v, pairs, itemsize=2):
    """(ops, bytes) of one layer, forward + backward; `pairs` = (query, key)
    pairs of ONE head, summed over the batch."""
    ops = heads * pairs * (2 * (d_qk + d_v) + 2 * (3 * d_qk + 2 * d_v))
    rows = b * s * heads
    nbytes = rows * itemsize * ((2 * d_qk + 2 * d_v)
                                + (4 * d_qk + 4 * d_v))
    return ops, nbytes


def layers(cell) -> int:
    """MLA layers a step of the cell runs."""
    c = cell["config"]
    return sum((i + 1) % c["layer_group_size"] == 0
               for i in range(c["num_hidden_layers"]))


def from_cell(cell, ctx=None):
    """One MLA LAYER's cost at the cell's shapes."""
    c, job = cell["config"], cell["traffic"]
    b, s = job["batch"] // cell["chips"], job["seq"]
    return cost(b, s, c["num_attention_heads"],
                c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
                c["v_head_dim"], b * s * (s + 1) // 2)
