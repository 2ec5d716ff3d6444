"""splash_fwd + splash_bwd: least time over traced device time."""
from harness import roofline


def read(ctx):
    return roofline.train_share(ctx, ["splash_fwd", "splash_bwd"])
