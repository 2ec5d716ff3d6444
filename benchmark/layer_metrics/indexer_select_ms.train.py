"""Device self time a step under `jax.named_scope("indexer/select")`: the
causal mask and the exact top-k of a query chunk's index scores
(`topk_mask`: a 32-pass radix select) (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, "indexer/select")
