"""Device self time a step under `jax.named_scope("backward")`: the vjp of
the head and the layers (in the scan step with its recompute), and what
the compiler adds inside that scope's loops."""
from harness import xplane


def read(ctx):
    phases = xplane.phases_of_run(ctx)
    return None if phases is None else phases["backward"]
