"""Device self time a step under `jax.named_scope("moe/experts")`: the
grouped products of the routed rows, forward and backward
(harness/scopes.py)."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, "moe/experts")
