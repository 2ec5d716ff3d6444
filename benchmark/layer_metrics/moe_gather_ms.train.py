"""Device self time a step under `jax.named_scope("moe/route/gather")`: each
tile's table slices and its row gathers (`x[idx]`, `dout[idx]`) in both
tile loops (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, "moe/route/gather")
