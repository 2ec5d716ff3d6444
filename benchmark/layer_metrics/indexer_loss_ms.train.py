"""Device self time a step under `jax.named_scope("indexer/loss")`: the
log-softmax over the kept keys, the KL against the target and
d(L_I)/d(scores) (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, "indexer/loss")
