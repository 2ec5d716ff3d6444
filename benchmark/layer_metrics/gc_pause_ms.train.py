"""Total of the full (generation 2) garbage collections inside the
window: the `paddle_tpu.host.gc` spans of the program's ring."""
from harness import xplane


def read(ctx):
    pauses = xplane.ring(ctx, "paddle_tpu.host.gc")
    if pauses is None:
        return None
    if pauses:
        lo, _ = xplane.window(ctx)
        print("spans: full collections in the window (s into it, ms): "
              + ", ".join(f"{s.t0 - lo:.2f} {1e3 * (s.t1 - s.t0):.1f}"
                          for s in pauses), flush=True)
    return 1e3 * sum(s.t1 - s.t0 for s in pauses)
