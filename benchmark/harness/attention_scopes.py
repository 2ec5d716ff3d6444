"""Device self time of a traced slice under the two `jax.named_scope`s of
a layer-kind decoder's attention, `window_attention` and
`full_attention` (models/mellum2.py), forward and backward together, and
each scope's share of its roofline. harness/scopes.py's reading with two
more names: its own tuple is fixed, so the walk is made again here over
its names and these, the innermost winning as there. A program that has
neither scope gives no time, and the readers then return None.
"""
from __future__ import annotations

import re

from harness import device, load, scopes, trace_reduce, xplane
from kernels import least_seconds

SCOPES = ("window_attention", "full_attention")
_FIND = re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(
    re.escape(s) for s in scopes.SCOPES + SCOPES))


def scope_seconds(plane) -> dict:
    """{scope: s} of the plane's `XLA Ops` line (self times)."""
    out = dict.fromkeys(SCOPES, 0.0)
    stack = []                      # [end, scope, self ns]

    def close(item):
        if item[1] in out:
            out[item[1]] += item[2] * 1e-9

    for e in xplane.line_events(plane, trace_reduce.OPS_LINE):
        while stack and stack[-1][0] <= e.start:
            close(stack.pop())
        found = _FIND.findall(str(e.stats.get("tf_op") or "").rstrip(":"))
        scope = found[-1] if found else None
        if stack:
            stack[-1][2] -= min(e.end, stack[-1][0]) - e.start
            if scope is None:
                scope = stack[-1][1]
        stack.append([e.end, scope, e.end - e.start])
    while stack:
        close(stack.pop())
    return out


def ms(ctx, scope):
    """ms a step of this run's traced slice under `scope`, the table
    printed once; None untraced or when the program has neither scope."""
    space = xplane.of_run(ctx)
    if space is None:
        return None
    if "attention_scopes" not in ctx:
        plane = xplane.device_plane(space)
        steps = max(len(xplane.step_programs(plane)[1]), 1)
        ctx["attention_scopes"] = {k: 1e3 * v / steps
                                   for k, v in scope_seconds(plane).items()}
        print(f"attention scopes: device self time of the slice's {steps} "
              "steps, forward and backward together, ms a step: "
              + ", ".join(f"{k} {v:.3f}"
                          for k, v in ctx["attention_scopes"].items()),
              flush=True)
    out = ctx["attention_scopes"]
    return out[scope] if sum(out.values()) > 0 else None


def share(ctx, scope: str, kernel: str) -> float | None:
    """Least time of the scope's required work in every layer of its kind
    (kernels/<kernel>.py: `from_cell`, one layer's cost, times `layers`)
    over the scope's device time a step, %."""
    spent_ms = ms(ctx, scope)
    if not spent_ms:
        return None
    counts = load.module("kernels", kernel)
    ops, nbytes = counts.from_cell(ctx["cell"], ctx)
    least = counts.layers(ctx["cell"]) * least_seconds(
        ops, nbytes, device.peaks(ctx["device"]["kind"]))
    return 100.0 * least / (spent_ms * 1e-3)
