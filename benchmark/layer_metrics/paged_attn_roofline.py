"""paged_attention_decode + paged_attention_chunk: the least time the
calls of the traced slice could take — the bytes of K and V of each
call's LIVE context, from the benchmark's record of every engine step in
the slice — over the device time of those kernels' events."""
from harness import device, load, trace_reduce
from kernels import least_seconds


def read(ctx):
    trace, window = ctx.get("trace"), ctx.get("trace_window")
    if trace is None or window is None:
        return None
    cell = ctx["cell"]
    c = cell["config"]
    peaks = device.peaks(ctx["device"]["kind"])
    decode = load.module("kernels", "paged_attention_decode")
    chunk = load.module("kernels", "paged_attention_chunk")
    heads, d, layers = c["num_attention_heads"], c["head_dim"], c["num_layers"]
    least = 0.0
    for s in ctx["drive"].steps:
        if not (window[0] <= s["t1"] and s["t2"] <= window[1]):
            continue
        if s["decode"]:
            ops, nbytes = decode.cost(sum(s["decode"]), heads, d, 2,
                                      len(s["decode"]))
            least += layers * least_seconds(ops, nbytes, peaks)
        for start, n in s["chunks"]:
            ops, nbytes = chunk.cost([start], n, heads, d, 2)
            least += layers * least_seconds(ops, nbytes, peaks)
    spent, events = trace_reduce.kernel_seconds(
        trace, ["paged_attention_decode", "paged_attention_chunk"])
    return 100.0 * least / spent if events and spent else None
