"""Device self time a step under `jax.named_scope("head")`: the final norm
and the cross entropy over the head (the fused kernels or their XLA
tiles), forward and backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, "head")
