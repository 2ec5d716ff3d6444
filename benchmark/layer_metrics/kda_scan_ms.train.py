"""Device self time a step under `jax.named_scope("kda/scan")`: beta's products
with k and v, the running sums of the decay inside each chunk, the chunked
delta rule's two kernels and their pull-backs, forward (twice under
recompute) and backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("kda/scan") or None
