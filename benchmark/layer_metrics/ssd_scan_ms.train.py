"""Device self time a step under `jax.named_scope("ssm/scan")`: the
softplus, the running sums, the chunked scan's two kernels, D x and the
small tables' layout changes (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("ssm/scan") or None
