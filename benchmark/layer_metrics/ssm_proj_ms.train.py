"""Device self time a step under `ssm/project` (the input norm and W_in)
and `ssm/out` (W_out and the residual): the Mamba layers' matrix
products (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    own = scope_tree.of_run(ctx)
    if own is None or "ssm/project" not in own:
        return None
    return own["ssm/project"] + own["ssm/out"] or None
