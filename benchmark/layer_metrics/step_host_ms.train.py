"""The host's own work in a step call: `paddle_tpu.step` minus its
`paddle_tpu.step.dispatch` child (self time), median over the window's
calls, from the program's span ring."""
from harness import xplane


def read(ctx):
    calls = xplane.step_calls(ctx)
    if calls is None:
        return None
    own = xplane.stats_ms([whole - inner for whole, inner in calls])
    launch = xplane.stats_ms([inner for _, inner in calls])
    waits = xplane.ring(ctx, "paddle_tpu.input.wait") or []
    wait_ms = 1e3 * sum(s.t1 - s.t0 for s in waits) / len(calls)
    queue_ms = 1e3 * ctx["counters"]["host_queue_s"] / max(ctx["steps"], 1)
    total = own[1] + launch[1] + wait_ms
    print(f"spans: the window's {len(calls)} step calls, ms a step (median "
          f"/ mean / min): self {own[0]:.3f} / {own[1]:.3f} / {own[2]:.3f}, "
          f"dispatch {launch[0]:.3f} / {launch[1]:.3f} / {launch[2]:.3f}; "
          f"input.wait mean {wait_ms:.3f}; means together {total:.3f} "
          f"against host_queue_ms.train {queue_ms:.3f} "
          f"({100 * (total / queue_ms - 1):+.1f} %)", flush=True)
    for part in ("extract_state", "lr", "sentinel", "inject_state"):
        spans = xplane.ring(ctx, f"paddle_tpu.step.{part}") or []
        if spans:
            med, mean, _ = xplane.stats_ms([s.t1 - s.t0 for s in spans])
            print(f"spans:   paddle_tpu.step.{part} median {med:.3f} mean "
                  f"{mean:.3f} ms", flush=True)
    lo, _ = xplane.window(ctx)
    longest = []
    for name in ("step", "step.dispatch", "input.wait", "input.h2d",
                 "host.gc"):
        spans = xplane.ring(ctx, "paddle_tpu." + name) or []
        if spans:
            top = max(spans, key=lambda s: s.t1 - s.t0)
            longest.append(f"{name} {1e3 * (top.t1 - top.t0):.1f} ms at "
                           f"{top.t0 - lo:.2f} s")
    print("spans: the longest of each name in the window (a host stall "
          "inside the program shows here): " + "; ".join(longest),
          flush=True)
    return own[0]
