"""Device self time a step under `jax.named_scope("mla_attention")`: latent
attention's causal attention at query / key rows of 192 and value rows of
128, the rows' padding to whole lane tiles and the layout changes round the
kernels, forward (twice under recompute) and backward
(harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("mla_attention") or None
