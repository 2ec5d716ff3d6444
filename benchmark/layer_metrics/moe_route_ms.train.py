"""Device self time a step under `jax.named_scope("moe/route")`: router,
top-8, the sort into row tables, and each tile's gather and add-back
(harness/scopes.py)."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, "moe/route")
