"""Device self time a step under `jax.named_scope("ssm/conv")`: the
depthwise causal convolution, its bias and silu (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("ssm/conv") or None
