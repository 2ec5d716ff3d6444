"""fused_ce_bwd: the streaming head's backward. The logits are formed
again (they were never stored), then dh = dlogits W and dW = dlogits^T h:
three products of 2 n hidden vocab. dW leaves in fp32."""


def cost(n, hidden, vocab, itemsize=2):
    ops = 6 * n * hidden * vocab
    nbytes = (2 * n * hidden + vocab * hidden) * itemsize \
        + vocab * hidden * 4 + n * 4 * 2
    return ops, nbytes


def from_cell(cell, ctx=None):
    c, job = cell["config"], cell["traffic"]
    n = job["batch"] // cell["chips"] * job["seq"]
    return cost(n, c["hidden_size"], c["vocab_size"])
