"""ssd_scan_bwd: the chunked state-space scan's backward kernel. Every
product of the forward has two pull-backs, so twice kernels/
ssd_scan_fwd.py's operations; it reads dy and writes the cotangents of x,
B, C and the step sizes once (the bytes of the forward's operands; that
it reads x, B, C and the saved states again is the algorithm's choice and
not counted as required)."""
from kernels import ssd_scan_fwd


def cost(b, seq, heads, p, groups, n, chunk, itemsize=2):
    ops, nbytes = ssd_scan_fwd.cost(b, seq, heads, p, groups, n, chunk,
                                    itemsize)
    return 2 * ops, nbytes


def from_cell(cell, ctx=None):
    """One call's cost at the cell's shapes: one `M` layer's backward."""
    return cost(*ssd_scan_fwd.shapes(cell))
