"""The `moe/experts` scope: least time for the routed rows' products
(kernels/moe_experts.py) over the scope's traced time."""
from harness import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "moe/experts", "moe_experts")
