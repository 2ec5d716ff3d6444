"""Device self time a step that no node of the program's scope tree holds
and that does not stand under the `optimizer` phase: what a trace cannot
yet give a name to (harness/scope_tree.py)."""
from harness import scope_tree


def read(ctx):
    return scope_tree.ms(ctx, scope_tree.UNNAMED)
