"""Device self time a step under `jax.named_scope("indexer")`: index
scores, the top-k selection and L_I with its gradient (harness/scopes.py)."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, "indexer")
