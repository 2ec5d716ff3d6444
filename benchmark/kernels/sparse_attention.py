"""The `sparse_attention` scope: attention over each query's SELECTED keys
(one set per token, every head), forward once and backward once. A
selected pair costs 2d (scores) + 2d (values) per head forward; backward
recomputes the scores (one product) and forms dV, dP, dQ, dK (four), as
kernels/splash_bwd.py counts. The forward that per-layer recompute runs
again, and the pairs a tile visits but the selection masks, are not
required and not counted."""


def cost(b, s, heads, kv_heads, d, pairs, itemsize=2):
    """`pairs`: selected (query, key) pairs of all `b` sequences."""
    ops = (4 + 10) * d * heads * pairs
    q_rows, kv_rows = b * s * heads * d, b * s * kv_heads * d
    # forward: read q k v, write o and the log-sum-exp; backward: read
    # q k v o do, write dq dk dv, read the log-sum-exp and delta
    nbytes = (2 * q_rows + 2 * kv_rows) * itemsize + b * heads * s * 4
    nbytes += (4 * q_rows + 4 * kv_rows) * itemsize + 2 * b * heads * s * 4
    nbytes += 2 * b * s * s              # the int8 selection, both passes
    return ops, nbytes


def from_cell(cell, ctx=None):
    """One LAYER's cost at the cell's shapes, the pairs the program's
    selection kept (its counter) when the run has them."""
    from harness import keye_flops, keye_weights

    s, job = keye_weights.shapes(cell["config"]), cell["traffic"]
    b = job["batch"] // cell["chips"]
    routing = ((ctx or {}).get("counters") or {}).get("routing")
    pairs = (routing["kept_keys"] / s["num_layers"] if routing else
             b * keye_flops.selected_pairs(job["seq"], s["index_topk"]))
    return cost(b, job["seq"], s["num_attention_heads"],
                s["num_key_value_heads"], s["head_dim"], pairs)
