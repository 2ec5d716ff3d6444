"""Device self time a step under `jax.named_scope("sparse_attention")`:
attention over the selected keys, forward (twice, with per-layer
recompute) and backward (harness/scopes.py)."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, "sparse_attention")
