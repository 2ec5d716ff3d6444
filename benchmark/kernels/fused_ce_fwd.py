"""fused_ce_fwd: output head and cross entropy in one pass over vocabulary
tiles: logits = h W^T for n tokens, never stored."""


def cost(n, hidden, vocab, itemsize=2):
    ops = 2 * n * hidden * vocab
    nbytes = (n * hidden + vocab * hidden) * itemsize + n * 4 * 2
    return ops, nbytes


def from_cell(cell, ctx=None):
    c, job = cell["config"], cell["traffic"]
    n = job["batch"] // cell["chips"] * job["seq"]
    return cost(n, c["hidden_size"], c["vocab_size"])
