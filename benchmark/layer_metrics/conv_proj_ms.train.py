"""Device self time a step under `jax.named_scope("conv/project")`: the
gated-convolution layers' input norm and the product W_in (hidden x 3
hidden), forward (twice under recompute) and backward
(harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("conv/project") or None
