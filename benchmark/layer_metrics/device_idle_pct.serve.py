"""Share of the traced slice in which no operation ran on the device."""


def read(ctx):
    trace = ctx.get("trace")
    return None if trace is None else 100.0 * trace["idle_share_worst"]
