"""Device time lost between one step's program and the next: on the
`XLA Modules` line, from a step program's end to the next one's start,
less the device time of whatever ran between them (the `lr`'s
conversion), mean over the traced slice. Before the number, every idle
gap of the slice by class and by what the host had open in it."""
import collections

from harness import xplane

SHOWN = 12          # rows of the gap table, and events of the one timeline


def _timeline(lo, hi, lines):
    """The host's events that cover at least 2 % of [lo, hi], by start."""
    out = []
    for line, events in lines:
        for e in events:
            over = min(e.end, hi) - max(e.start, lo)
            if over >= 0.02 * (hi - lo):
                out.append((max(e.start, lo), line, e.name, over))
    return sorted(out)


def read(ctx):
    space = xplane.of_run(ctx)
    if space is None:
        return None
    plane = xplane.device_plane(space)
    progs, steps = xplane.step_programs(plane)
    gaps = xplane.launch_gaps(plane)
    if not gaps:
        return None

    busy, leaves = xplane.busy_share(plane)
    idle = xplane.idle_gaps(space, plane, leaves)
    total = sum(b - a for a, b, _, _ in idle) or 1.0
    by = collections.defaultdict(lambda: [0.0, 0, 0.0])
    for a, b, cls, name in idle:
        row = by[cls, name]
        row[0] += b - a
        row[1] += 1
        row[2] = max(row[2], b - a)
    slice_ms = (steps[-1].end - steps[0].start) * 1e-6
    t = ctx["trace"]
    print(f"gaps: device busy {100 * busy:.3f} % of the slice by the "
          f"trace's picoseconds; harness/trace_reduce.py reads "
          f"{100 * t['busy_s'] / t['window_s']:.3f} % from ProfileData's "
          f"rounded nanoseconds, where an operation that shares its start "
          f"with a zero-length one counts as that one's parent and drops "
          f"out of the busy union", flush=True)
    print(f"gaps: {total * 1e-6:.3f} ms idle of the {slice_ms:.3f} ms from "
          f"the first step program's start to the last one's end; "
          f"{len(progs) / len(steps):.2f} device programs a step ("
          + ", ".join(sorted({p.name for p in progs})) + ")", flush=True)
    for (cls, name), (ns, n, longest) in sorted(
            by.items(), key=lambda kv: -kv[1][0])[:SHOWN]:
        print(f"gaps:   {100 * ns / total:5.1f} %  {ns * 1e-6:9.3f} ms in "
              f"{n:6d} gaps (longest {longest * 1e-6:.3f})  {cls}  |  "
              f"{name}", flush=True)
    unnamed = sum(v[0] for (_, name), v in by.items()
                  if name == xplane.NOTHING)
    print(f"gaps: {100 * (1 - unnamed / total):.1f} % of the idle time has "
          f"a class and a name; nothing open in {100 * unnamed / total:.1f} "
          f"%", flush=True)
    # one gap between two step programs, as the host saw it
    first, second = steps[0], steps[1]
    lines = xplane.host_lines(space)
    gap_ms = (second.start - first.end) * 1e-6
    print(f"gaps: the first launch gap, {gap_ms:.3f} ms, by the host's "
          f"events (ms into the gap, thread, event, ms inside it):",
          flush=True)
    for t, line, name, over in _timeline(first.end, second.start,
                                         lines)[:2 * SHOWN]:
        print(f"gaps:   {(t - first.end) * 1e-6:8.3f}  {line:28s} {name}  "
              f"{over * 1e-6:.3f}", flush=True)
    net = [gap - busy for gap, busy, _ in gaps]
    print("gaps: launch gaps of the slice, ms (less other programs' device "
          "time): " + ", ".join(f"{g * 1e-6:.3f}" for g in net), flush=True)
    return sum(net) / len(net) * 1e-6
