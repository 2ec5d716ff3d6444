"""Device self time a step under `jax.named_scope("mla/project")`: latent
attention's input norm, W_q, W_kva, the latent's norm, W_kvb, the q / k
norms, the rotary turn, the head-wise gate, W_o and the residual
(harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    return None if own is None else own.get("mla/project") or None
