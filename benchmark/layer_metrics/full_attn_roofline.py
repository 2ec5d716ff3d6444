"""The `full_attention` scope: least time for the causal pairs
(kernels/full_attention.py) over the scope's traced time."""
from harness import attention_scopes


def read(ctx):
    return attention_scopes.share(ctx, "full_attention", "full_attention")
