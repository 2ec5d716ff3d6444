"""Device self time a step under the `conv/*` scopes together: the gated
short-convolution layers' input norm and W_in, the gate-and-convolution
operator and W_out with the residual, forward (twice under recompute) and
backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    if own is None:
        return None
    return sum(v for k, v in own.items() if k.startswith("conv/")) or None
