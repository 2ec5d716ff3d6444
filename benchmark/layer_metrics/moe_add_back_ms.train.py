"""Device self time a step under `jax.named_scope("moe/route/add_back")`: the
float32 accumulator, each tile's add-back (`moe_add_rows` on TPU), its
reshape after the loop, and the tile loops' own `while` and
carries (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, "moe/route/add_back")
