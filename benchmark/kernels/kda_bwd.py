"""kda_bwd: the chunked gated delta rule's backward kernel. Every product of
the forward has two pull-backs, so twice kernels/kda_fwd.py's operations; it
reads do and writes the cotangents of q, k, v, a and beta once (the bytes of
the forward's operands; that it reads the operands and the saved states again
and forms the system's inverse a second time is the algorithm's choice and
not counted as required)."""
from kernels import kda_fwd


def cost(b, seq, heads, k, v, chunk, itemsize=2):
    ops, nbytes = kda_fwd.cost(b, seq, heads, k, v, chunk, itemsize)
    return 2 * ops, nbytes


def from_cell(cell, ctx=None):
    """One call's cost at the cell's shapes: one KDA layer's backward."""
    return cost(*kda_fwd.shapes(cell))
