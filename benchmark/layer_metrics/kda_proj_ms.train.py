"""Device self time a step under `kda/project` (the input norm, W_q, W_k,
W_v, W_f, W_b, W_g) and `kda/out` (W_o and the residual): the KDA layers'
matrix products (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    if own is None or "kda/project" not in own:
        return None
    return own["kda/project"] + own["kda/out"] or None
