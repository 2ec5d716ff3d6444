"""Device self time of a traced slice by the `jax.named_scope`s inside
the Keye layer: `indexer`, `sparse_attention`, `moe/route`, `moe/experts`.

Forward and backward together: a scope counts where it stands plainly on
an operation's path and where a transformation wrapped it
(`transpose(jvp(moe/experts))`). The innermost of the four wins (the
grouped product's loop body gathers and scatters under `moe/route`
inside `moe/experts`); an operation that names none (a `while`, a copy
the compiler added) takes the scope of the event it is nested in. A
program that has no such scope gives no time, and the readers then
return None.
"""
from __future__ import annotations

import re

from harness import trace_reduce, xplane

SCOPES = ("indexer", "sparse_attention", "moe/route", "moe/experts")
_FIND = re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(
    re.escape(s) for s in SCOPES))


def scope_of(tf_op: str):
    found = _FIND.findall(tf_op.rstrip(":"))
    return found[-1] if found else None


def scope_seconds(plane) -> dict:
    """{scope: s} of the plane's `XLA Ops` line (self times)."""
    out = dict.fromkeys(SCOPES, 0.0)
    stack = []                      # [end, scope, self ns]

    def close(item):
        if item[1] is not None:
            out[item[1]] += item[2] * 1e-9

    for e in xplane.line_events(plane, trace_reduce.OPS_LINE):
        while stack and stack[-1][0] <= e.start:
            close(stack.pop())
        scope = scope_of(str(e.stats.get("tf_op") or ""))
        if stack:
            stack[-1][2] -= min(e.end, stack[-1][0]) - e.start
            if scope is None:
                scope = stack[-1][1]
        stack.append([e.end, scope, e.end - e.start])
    while stack:
        close(stack.pop())
    return out


def of_run(ctx):
    """{scope: ms a step} of this run's traced slice, printed once; None
    untraced or when the program names none of the scopes."""
    space = xplane.of_run(ctx)
    if space is None:
        return None
    if "scopes" not in ctx:
        plane = xplane.device_plane(space)
        steps = max(len(xplane.step_programs(plane)[1]), 1)
        ctx["scopes"] = {k: 1e3 * v / steps
                         for k, v in scope_seconds(plane).items()}
        print(f"scopes: device self time of the slice's {steps} steps by "
              "the layer's named scopes, forward and backward together, "
              "ms a step: " + ", ".join(
                  f"{k} {v:.3f}" for k, v in ctx["scopes"].items()),
              flush=True)
    out = ctx["scopes"]
    return out if sum(out.values()) > 0 else None


def ms(ctx, scope):
    out = of_run(ctx)
    return None if out is None else out[scope]
