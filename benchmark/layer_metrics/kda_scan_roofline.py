"""kda_fwd + kda_bwd: the least time of one forward and one backward a KDA
layer (kernels/kda_fwd.py, kda_bwd.py) over the two kernels' traced device
time in the slice's steps. A layer's call is cut into several events (a
sequence at a time), and under per-layer recompute the forward runs twice:
what is spent is every event's time, what is required one forward and one
backward of the whole batch a layer and step. Beside it the two kernels'
events a step, which the trace counts (none where a scan fell back to XLA)."""
from harness import device, load, trace_reduce, xplane
from kernels import least_seconds


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    fwd_s, fwd_n = trace_reduce.kernel_seconds(trace, ["kda_fwd"])
    bwd_s, bwd_n = trace_reduce.kernel_seconds(trace, ["kda_bwd"])
    if not fwd_n or not bwd_n:
        return None
    cell = ctx["cell"]
    peaks = device.peaks(ctx["device"]["kind"])
    fwd, bwd = (load.module("kernels", k) for k in ("kda_fwd", "kda_bwd"))
    steps = max(len(xplane.step_programs(
        xplane.device_plane(xplane.of_run(ctx)))[1]), 1)
    least = steps * fwd.layers(cell) * (
        least_seconds(*fwd.from_cell(cell, ctx), peaks)
        + least_seconds(*bwd.from_cell(cell, ctx), peaks))
    print(f"kda scan: {fwd_n} forward and {bwd_n} backward kernel events in "
          f"the slice's {steps} steps ({fwd_n / steps:g} and "
          f"{bwd_n / steps:g} a step), {1e3 * fwd_s:.3f} + "
          f"{1e3 * bwd_s:.3f} ms; least {1e3 * least:.3f} ms", flush=True)
    return 100.0 * least / (fwd_s + bwd_s)
