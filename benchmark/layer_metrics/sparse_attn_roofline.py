"""The `sparse_attention` scope: least time for the selected pairs
(kernels/sparse_attention.py) over the scope's traced time."""
from harness import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "sparse_attention", "sparse_attention")
