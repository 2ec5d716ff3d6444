"""Peak share of the KV pool's pages held by requests at any engine.step()
of the window: what of the reserved pool the traffic really fills."""


def read(ctx):
    return 100.0 * ctx["serve"]["pool_fill"] if ctx["serve"]["steps"] else None
