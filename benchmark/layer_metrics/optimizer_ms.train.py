"""Device self time a step under `jax.named_scope("optimizer")`: AdamW,
inside the backward scan where the scan step runs it there, and the
numerics rows of the update."""
from harness import xplane


def read(ctx):
    phases = xplane.phases_of_run(ctx)
    return None if phases is None else phases["optimizer"]
