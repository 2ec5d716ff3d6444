"""Device self time a step of the operations traced under
`jax.named_scope("forward")` (embedding, the layers, the loss head),
from the slice's `XLA Ops` by the phase in each operation's `tf_op`."""
from harness import xplane


def read(ctx):
    phases = xplane.phases_of_run(ctx)
    return None if phases is None else phases["forward"]
