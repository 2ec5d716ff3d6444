"""Device self time a step under `jax.named_scope("moe/route/router")`: the
router product, softmax, top-k, the renormalised gates and the balance
term, forward and backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, "moe/route/router")
