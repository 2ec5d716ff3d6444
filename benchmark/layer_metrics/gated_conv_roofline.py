"""The `conv/gate_conv` scope against its roofline: the least time of one
forward and one backward of the operator y = C * conv(B * X) a gated-
convolution layer (kernels/gated_conv.py: its bytes at the chip's HBM rate;
the operations are far under the peak) over the scope's device time a step.
The scope's time holds the forward, the forward that per-layer recompute
runs again and the backward; what is required is one forward and one
backward a layer and step, so with recompute on the share cannot pass
11 / 15 of 100 %."""
from harness import device, load, scope_tree
from kernels import least_seconds


def read(ctx):
    own = scope_tree.of_run(ctx)
    spent_ms = None if own is None else own.get("conv/gate_conv")
    if not spent_ms:
        return None
    cell = ctx["cell"]
    counts = load.module("kernels", "gated_conv")
    peaks = device.peaks(ctx["device"]["kind"])
    least = counts.layers(cell) * (
        least_seconds(*counts.from_cell(cell, ctx), peaks)
        + least_seconds(*counts.from_cell(cell, ctx, backward=True), peaks))
    print(f"gated conv: {counts.layers(cell)} layers, least "
          f"{1e3 * least:.3f} ms a step (forward + backward), the scope "
          f"{spent_ms:.3f} ms", flush=True)
    return 100.0 * least / (spent_ms * 1e-3)
