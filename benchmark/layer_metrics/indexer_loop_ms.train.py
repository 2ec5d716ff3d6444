"""Device self time a step under `jax.named_scope("indexer")` and under none
of its five leaves: the query-chunk loop's own slices, copies and sums
(harness/scope_tree.py: the node's bare remainder)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, "indexer")
