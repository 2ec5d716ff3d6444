"""Device self time a step under `jax.named_scope("kda/gate_norm")`: the
RMSNorm over a head's 128 values and the head-wise sigmoid gate, forward
(twice under recompute) and backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("kda/gate_norm") or None
