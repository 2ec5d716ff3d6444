"""indexer_scores_fwd + indexer_scores_bwd (the index scores of a query
chunk and their pull-back): least time over traced device time. None
where the program has no such kernels."""
from harness import roofline


def read(ctx):
    return roofline.train_share(ctx, ["indexer_scores_fwd",
                                      "indexer_scores_bwd"])
