"""Device self time a step under `jax.named_scope("full_attention")`: the
full layers' causal attention, forward (twice, with per-layer recompute)
and backward (harness/attention_scopes.py)."""
from harness import attention_scopes


def read(ctx):
    return attention_scopes.ms(ctx, "full_attention")
