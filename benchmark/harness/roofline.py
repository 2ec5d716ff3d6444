"""A kernel's share of its roofline over a traced slice: the least time
its calls could take (kernels/<name>.py and harness/device.py's peaks)
over the device time its events took (harness/trace_reduce.py)."""
from __future__ import annotations

from harness import device, load, trace_reduce
from kernels import least_seconds


def train_share(ctx, kernel_names) -> float | None:
    """Every call of a training kernel has the cell's shapes, so the
    least time is one call's times the number of events."""
    trace = ctx.get("trace")
    if trace is None:
        return None
    peaks = device.peaks(ctx["device"]["kind"])
    least = spent = 0.0
    for name in kernel_names:
        secs, events = trace_reduce.kernel_seconds(trace, [name])
        if not events:
            continue
        ops, nbytes = load.module("kernels", name).from_cell(ctx["cell"], ctx)
        least += events * least_seconds(ops, nbytes, peaks)
        spent += secs
    return 100.0 * least / spent if spent else None
