"""Device self time a step under the `kda/*` scopes together: the KDA
layers' projections, convolutions, gate, scan, gated norm and output product,
forward (twice under recompute) and backward (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    if own is None:
        return None
    return sum(v for k, v in own.items() if k.startswith("kda/")) or None
