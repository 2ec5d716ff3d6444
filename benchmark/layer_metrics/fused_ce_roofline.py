"""fused_ce_fwd + fused_ce_bwd: least time over traced device time."""
from harness import roofline


def read(ctx):
    return roofline.train_share(ctx, ["fused_ce_fwd", "fused_ce_bwd"])
