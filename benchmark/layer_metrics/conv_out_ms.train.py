"""Device self time a step under `jax.named_scope("conv/out")`: the
gated-convolution layers' product W_out and the residual, forward (twice
under recompute) and backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("conv/out") or None
