"""Device self time a step under `jax.named_scope("kda/conv")`: the three
depthwise causal convolutions of four taps and their silu, forward (twice
under recompute) and backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("kda/conv") or None
