"""attn_probs_stats + attn_probs_mean (the indexer loss's target): least
time over traced device time."""
from harness import roofline


def read(ctx):
    return roofline.train_share(ctx, ["attn_probs_stats", "attn_probs_mean"])
