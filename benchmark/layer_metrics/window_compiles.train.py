"""Executables the step gained inside the window (should be 0)."""


def read(ctx):
    return ctx["counters"]["window_compiles"]
