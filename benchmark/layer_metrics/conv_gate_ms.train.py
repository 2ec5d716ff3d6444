"""Device self time a step under `jax.named_scope("conv/gate_conv")`: the
operator y = C * conv3(B * X) (ops/pallas/gated_conv.py), forward (twice
under recompute) and backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("conv/gate_conv") or None
