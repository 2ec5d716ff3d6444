"""Device self time a step under `jax.named_scope("ssm/gate_norm")`:
y * silu(z) and the grouped RMSNorm (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("ssm/gate_norm") or None
