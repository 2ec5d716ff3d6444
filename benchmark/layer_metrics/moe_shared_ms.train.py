"""Device self time a step under `jax.named_scope("moe/shared")`: the
shared expert's two products and relu^2 (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("moe/shared") or None
