"""Device self time a step under `jax.named_scope("attention/projections")`:
input norm, the q / k / v products, q/k norm, rotary, the output product
and the residual, forward (twice under recompute) and backward
(harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, "attention/projections")
