"""Mean running sequences over max_slots, sampled at every engine.step()
of the window."""


def read(ctx):
    return 100.0 * ctx["serve"]["occupancy"] if ctx["serve"]["steps"] else None
