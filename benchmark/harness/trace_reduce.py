"""From the profiler's .xplane.pb to numbers: device busy and idle, time
per operation and per named kernel, and the longest idle gaps named by
the host span open in them.

A device plane is `/device:TPU:<n>`; its "XLA Ops" line holds one event
per executed HLO operation, nested where an operation (a `while`, a
called computation) runs others inside it. Busy time is the union of the
LEAF events: time inside a loop with no operation running is idle. An
operation's own time is its duration minus its children's. The host's
`jax.profiler.TraceAnnotation` spans (names starting "bench.") are on the
same clock in the `/host:CPU` plane.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


_SUFFIX = re.compile(r"\.\d+$")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(text: str) -> str:
    """An event's name is the whole HLO instruction; keep `<instruction
    name without its number> <result type>`, e.g. 'copy
    f32[24,1,8192,2048]' or 'transpose_jvp_splash_bwd__ (tuple)'."""
    head, _, rest = text.partition(" = ")
    base = _SUFFIX.sub("", head.strip().lstrip("%"))
    if not rest:
        return base[:80]
    kind = "(tuple)" if rest.startswith("(") else _LAYOUT.sub(
        "", rest.split(" ", 1)[0])
    return f"{base} {kind}"[:80]


def _events(line):
    """[(start_ns, end_ns, name)] sorted by start, longest first."""
    out = [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
            short_name(e.name)) for e in line.events]
    out.sort(key=lambda t: (t[0], -t[1]))
    return out


def self_times(events):
    """[(start, end, name, self_ns, is_leaf)] for nested intervals."""
    out, stack = [], []          # stack of indices into out
    for s, e, name in events:
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(e, parent[1]) - s
            parent[4] = False
        out.append([s, e, name, e - s, True])
        stack.append(len(out) - 1)
    return out


def union(intervals):
    """Merged, sorted [(start, end)]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def covered(intervals, lo, hi):
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def host_spans(profile):
    """[(start_ns, end_ns, name)] of the benchmark's own annotations."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    s = float(e.start_ns)
                    out.append((s, s + float(e.duration_ns), e.name))
    return sorted(out)


def span_at(spans, t):
    """Name of the innermost host span open at time t, or 'no span'."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "host: no benchmark span open"


def reduce_profile(profile, n_devices: int | None = None) -> dict:
    planes = [(int(m.group(1)), p) for p in profile.planes
              if (m := DEVICE_PLANE.match(p.name))]
    planes.sort()
    if n_devices:
        planes = planes[:n_devices]
    per_dev = []
    for idx, plane in planes:
        line = next((ln for ln in plane.lines if ln.name == OPS_LINE), None)
        if line is None:
            continue
        ev = self_times(_events(line))
        if ev:
            per_dev.append((idx, ev))
    if not per_dev:
        raise RuntimeError("the trace holds no device operation")
    lo = min(ev[0][0] for _, ev in per_dev)
    hi = max(max(e[1] for e in ev) for _, ev in per_dev)
    window = hi - lo
    spans = host_spans(profile)
    busy, by_op, gaps = [], {}, {}
    for idx, ev in per_dev:
        leaves = union((e[0], e[1]) for e in ev if e[4])
        busy.append(covered(leaves, lo, hi))
        for s, e, name, self_ns, _leaf in ev:
            agg = by_op.setdefault(name, [0.0, 0.0])
            agg[0] += self_ns / len(per_dev)
            agg[1] += 1.0 / len(per_dev)
        edges = [[lo, lo]] + leaves + [[hi, hi]]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b - a > 0:
                key = span_at(spans, 0.5 * (a + b))
                g = gaps.setdefault(key, [0.0, 0.0])
                g[0] += (b - a) / len(per_dev)
                g[1] = max(g[1], b - a)
    ns = 1e-9
    return {
        "window_s": window * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "busy_s_per_device": [b * ns for b in busy],
        "idle_share_worst": 1.0 - min(busy) / window,
        "devices": [i for i, _ in per_dev],
        "op_seconds": {k: (v[0] * ns, v[1]) for k, v in by_op.items()},
        "device_ops": [[k, v[0] * ns] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1][0])],
        "idle_gaps": [[k, v[0] * ns] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1][0])],
        "longest_gap_s": {k: v[1] * ns for k, v in gaps.items()},
    }


def kernel_seconds(reduced: dict, names) -> tuple[float, int]:
    """(seconds per device, events per device) of operations whose
    instruction name holds one of `names` — a Pallas kernel's `name=` is
    in its custom call's name ('splash_fwd', 'jvp_splash_fwd_',
    'transpose_jvp_splash_bwd__')."""
    secs, count = 0.0, 0
    for op, (s, n) in reduced["op_seconds"].items():
        if any(k in op.split(" ")[0] for k in names):
            secs += s
            count += n
    return secs, count


def reduce_dir(trace_dir: str, n_devices: int | None = None) -> dict:
    return reduce_profile(load(find_xplane(trace_dir)), n_devices)
