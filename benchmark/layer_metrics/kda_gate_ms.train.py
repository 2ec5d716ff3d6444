"""Device self time a step under `jax.named_scope("kda/gate")`: the decay a
channel (sigmoid, the bound), beta, the L2 norms of q and k and q's scale,
forward (twice under recompute) and backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("kda/gate") or None
