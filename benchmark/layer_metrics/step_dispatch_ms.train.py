"""`paddle_tpu.step.dispatch`, the compiled step's launch alone, median
over the window's calls from the program's span ring: the launch cost,
and where the call waits for a slot in the runtime's queue. The traced
slice starts with that queue empty, so its calls show the bare launch;
they also run under the profiler's Python tracer, which the window's do
not: the step call's cost of being traced is printed beside them."""
from harness import xplane


def read(ctx):
    calls = xplane.step_calls(ctx)
    if calls is None:
        return None
    launch = xplane.stats_ms([inner for _, inner in calls])
    traced = xplane.step_calls(ctx, after=True)
    if traced:
        t_launch = xplane.stats_ms([inner for _, inner in traced])
        t_own = xplane.stats_ms([whole - inner for whole, inner in traced])
        own = xplane.stats_ms([whole - inner for whole, inner in calls])
        print(f"spans: dispatch in the traced slice's {len(traced)} calls "
              f"(queue empty at its start, Python tracer on): median "
              f"{t_launch[0]:.3f} min {t_launch[2]:.3f} ms, against the "
              f"window's median {launch[0]:.3f} min {launch[2]:.3f}; the "
              f"call's self time traced {t_own[0]:.3f} against "
              f"{own[0]:.3f} untraced", flush=True)
    return launch[0]
