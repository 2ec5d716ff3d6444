"""Rows the grouped product computed that held no routed token, % of the
rows it computed: the padding of each held expert's last tile. From the
program's own routing counters of the window's last step."""


def read(ctx):
    routing = ctx["counters"].get("routing")
    if not routing or not routing["computed_rows"]:
        return None
    return 100.0 * (1.0 - routing["routed_pairs"] / routing["computed_rows"])
