"""The `mla_attention` scope: least time for the causal pairs at 192 / 128
head widths in every MLA layer (kernels/mla_attention.py) over the scope's
traced device time a step (harness/scope_tree.py)."""
from harness import device, load, scope_tree
from kernels import least_seconds


def read(ctx):
    own = scope_tree.of_run(ctx)
    spent_ms = None if own is None else own.get("mla_attention")
    if not spent_ms:
        return None
    counts = load.module("kernels", "mla_attention")
    ops, nbytes = counts.from_cell(ctx["cell"], ctx)
    least = counts.layers(ctx["cell"]) * least_seconds(
        ops, nbytes, device.peaks(ctx["device"]["kind"]))
    return 100.0 * least / (spent_ms * 1e-3)
