"""Device self time a step under `jax.named_scope("moe/route/plan")`: the
sort of the routed pairs into row tables (`dispatch_plan`), the rows'
weights and the tiles' real rows, and the gate weights' gradient
(harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, "moe/route/plan")
