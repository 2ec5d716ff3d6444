"""The `moe/experts` scope: the grouped products of the routed rows. A
row (one token on one held expert) needs three products of 2 H F
forward (gate, up, down) and six backward (dWd, da, dWg, dWu and the two
halves of dh). Rows that pad an expert's last tile, the products the
backward forms again (gate, up, and the unweighted output for the gate
weight's gradient) and per-layer recompute's second forward are not
required and not counted."""


def cost(rows, hidden, inner, held, itemsize=2):
    ops = 9 * 2 * hidden * inner * rows
    weights = 3 * held * hidden * inner
    # forward and backward each read the held experts' weights once, the
    # backward writes their float32 gradient; a row's input and output
    nbytes = 2 * weights * itemsize + weights * 4
    nbytes += 4 * rows * hidden * itemsize
    return ops, nbytes


def from_cell(cell, ctx=None):
    """One LAYER's cost: the rows the program routed to held experts (its
    counter), else the expected share of the router's picks."""
    from harness import keye_weights

    s, job = keye_weights.shapes(cell["config"]), cell["traffic"]
    held = s["held_experts"][1] - s["held_experts"][0]
    routing = ((ctx or {}).get("counters") or {}).get("routing")
    tokens = job["batch"] // cell["chips"] * job["seq"]
    rows = (routing["routed_pairs"] / s["num_layers"] if routing else
            tokens * s["num_experts_per_tok"] * held / s["num_experts"])
    return cost(rows, s["hidden_size"], s["moe_intermediate_size"], held)
