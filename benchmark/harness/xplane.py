"""The profiler's .xplane.pb read whole, with what `jax.profiler.ProfileData`
leaves out: each event's METADATA stats (`tf_op` = JAX's `op_name` with the
program's `jax.named_scope`s in it, `source`), which is where a compiled
step's phases are named. No package is needed: the file is protobuf wire
format (tsl/profiler/protobuf/xplane.proto) and the few messages it uses
are decoded here.

On top of the decoder, what the train path's readers share: the device's
operations with their phase, the programs of the `XLA Modules` line, the
idle gaps with the host event open in each, and the program's own span
ring cut to the measured window.
"""
from __future__ import annotations

import collections
import re
import statistics
import struct

from harness import clock, trace_reduce

PHASES = ("forward", "backward", "optimizer")
UNSCOPED = "unscoped"
STEP_FN = "step_fn"
SPAN_PREFIX = "paddle_tpu."


# -- protobuf wire format ------------------------------------------------

def _fields(buf, lo=0, hi=None):
    """(field number, wire type, value) of one message: an int for a
    varint, (start, end) into `buf` for a length-delimited field."""
    hi = len(buf) if hi is None else hi
    while lo < hi:
        key = shift = 0
        while True:
            b = buf[lo]
            lo += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        num, wire = key >> 3, key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[lo]
                lo += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield num, wire, val
        elif wire == 2:
            n = shift = 0
            while True:
                b = buf[lo]
                lo += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield num, wire, (lo, lo + n)
            lo += n
        elif wire == 1:
            yield num, wire, buf[lo:lo + 8]
            lo += 8
        elif wire == 5:
            yield num, wire, buf[lo:lo + 4]
            lo += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {lo}")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v):
    return v - (1 << 64) if v >> 63 else v


def _stat(buf, span):
    """XStat -> (metadata id, value; a ref_value comes as ('ref', id))."""
    key, val = 0, None
    for num, wire, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num in (5, 6):
            val = _text(buf, v)
        elif num == 7:
            val = ("ref", v)
    return key, val


Event = collections.namedtuple("Event", "name start end stats")
Event.__doc__ = """start / end in ns on the trace's one clock; `stats` maps a
stat's name to its value, the event's own over its metadata's."""


class Plane:
    def __init__(self, buf, span):
        self.name = ""
        self._buf, self._lines = buf, []
        self._event_meta, self._stat_names = {}, {}
        for num, _, v in _fields(buf, *span):
            if num == 2:
                self.name = _text(buf, v)
            elif num == 3:
                self._lines.append(v)
            elif num in (4, 5):               # map entry: key = 1, value = 2
                for n2, _, v2 in _fields(buf, *v):
                    if n2 == 2 and num == 4:
                        self._read_event_meta(v2)
                    elif n2 == 2:
                        self._read_stat_meta(v2)

    def _read_event_meta(self, span):
        ident, name, stats = 0, "", []
        for num, _, v in _fields(self._buf, *span):
            if num == 1:
                ident = v
            elif num == 2:
                name = _text(self._buf, v)
            elif num == 5:
                stats.append(v)
        self._event_meta[ident] = (name, stats)

    def _read_stat_meta(self, span):
        ident, name = 0, ""
        for num, _, v in _fields(self._buf, *span):
            if num == 1:
                ident = v
            elif num == 2:
                name = _text(self._buf, v)
        self._stat_names[ident] = name

    def _stats(self, spans) -> dict:
        out = {}
        for span in spans:
            key, val = _stat(self._buf, span)
            if isinstance(val, tuple):
                val = self._stat_names.get(val[1], "")
            out[self._stat_names.get(key, str(key))] = val
        return out

    def line_names(self):
        return [name for name, _, _ in self._line_heads()]

    def _line_heads(self):
        for span in self._lines:
            name, t0, events = "", 0, []
            for num, _, v in _fields(self._buf, *span):
                if num == 2:
                    name = _text(self._buf, v)
                elif num == 3:
                    t0 = v
                elif num == 4:
                    events.append(v)
            yield name, t0, events

    def lines(self, only=None):
        """(line name, [Event]) of each line, or of those named `only`."""
        meta_stats = {}
        for name, t0, spans in self._line_heads():
            if only is not None and name not in only:
                continue
            events = []
            for span in spans:
                mid = off = dur = 0
                own = []
                for num, _, v in _fields(self._buf, *span):
                    if num == 1:
                        mid = v
                    elif num == 2:
                        off = v
                    elif num == 3:
                        dur = v
                    elif num == 4:
                        own.append(v)
                ename, mstats = self._event_meta.get(mid, (str(mid), []))
                if mid not in meta_stats:
                    meta_stats[mid] = self._stats(mstats)
                stats = meta_stats[mid]
                if own:
                    stats = dict(stats, **self._stats(own))
                start = t0 + off / 1e3
                events.append(Event(ename, start, start + dur / 1e3, stats))
            yield name, events


def line_events(plane, name):
    """[Event] of the plane's line `name`, decoded once and sorted by
    start, an enclosing event before what it encloses."""
    cache = plane.__dict__.setdefault("_decoded", {})
    if name not in cache:
        (_, events), = plane.lines(only=(name,))
        cache[name] = sorted(events, key=lambda e: (e.start, -e.end))
    return cache[name]


def planes(path: str):
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [Plane(buf, v) for num, _, v in _fields(buf) if num == 1]


def of_run(ctx):
    """The planes of this run's traced slice, read once; None untraced."""
    if ctx.get("trace") is None:
        return None
    if "xplane" not in ctx:
        ctx["xplane"] = planes(trace_reduce.find_xplane(ctx["trace_dir"]))
    return ctx["xplane"]


def device_plane(space, index=0):
    for p in space:
        m = trace_reduce.DEVICE_PLANE.match(p.name)
        if m and int(m.group(1)) == index:
            return p
    raise RuntimeError("the trace holds no device plane")


# -- the compiled step's phases --------------------------------------------

def phase_of(tf_op: str):
    """The innermost `jax.named_scope` phase on an operation's path. A
    phase inside brackets (`transpose(jvp(forward))`) says where the
    primal was traced, not which pass runs, and does not count."""
    found = None
    for part in tf_op.rstrip(":").split("/"):
        if part in PHASES:
            found = part
    return found


def _root(tf_op: str) -> str:
    """The last two steps of an operation's path: what a fusion's root
    is (`transpose(jvp())/dot_general` for a weight gradient's matmul)."""
    return "/".join(tf_op.rstrip(":").split("/")[-2:])


def phase_seconds(plane) -> dict:
    """Device self time (s) of the `XLA Ops` line by phase. An operation
    whose path names no phase (what the compiler adds: a copy reads
    `jit(step_fn)/backward/while`, a `while` or a broadcast nothing at
    all) takes the phase of the event it is nested in; failing that, of
    the first operation nested in IT that names one (a scan's `while`);
    failing that, of the operation that ran before it: `lent` is the
    time placed that last way, and what is left is `unscoped`. A fusion
    has ONE path, its root's: an update fused into a gradient's matmul
    counts as the matmul's phase. Also `by_op`: {(phase, short name,
    root of the path): s}."""
    events = line_events(plane, trace_reduce.OPS_LINE)
    out = dict.fromkeys(PHASES + (UNSCOPED, "lent"), 0.0)
    by_op = collections.defaultdict(float)
    stack = []          # [end, phase, self ns, name, root, placed how]
    before = None       # phase of the last top-level operation

    def close(item):
        out[item[1] or UNSCOPED] += item[2] * 1e-9
        out["lent"] += item[2] * 1e-9 * (item[5] == "before")
        by_op[item[1] or UNSCOPED, item[3], item[4]] += item[2] * 1e-9

    for e in events:
        while stack and stack[-1][0] <= e.start:
            done = stack.pop()
            if not stack:
                before = done[1] or before
            close(done)
        tf_op = str(e.stats.get("tf_op") or "")
        phase, how = phase_of(tf_op), "own"
        if stack:
            stack[-1][2] -= min(e.end, stack[-1][0]) - e.start
            if phase is None:
                phase, how = stack[-1][1], "parent"
            elif stack[-1][5] == "before":
                stack[-1][1], stack[-1][5] = phase, "child"
        elif phase is None:
            phase, how = before, "before"
        stack.append([e.end, phase, e.end - e.start,
                      trace_reduce.short_name(e.name), _root(tf_op), how])
    while stack:
        close(stack.pop())
    out["by_op"] = dict(by_op)
    return out


def busy_share(plane):
    """Busy time over the span of the `XLA Ops` line from its picosecond
    offsets: the union of the leaf operations, as harness/trace_reduce
    takes it from nanoseconds that `ProfileData` has rounded."""
    ev = [(e.start, e.end, "")
          for e in line_events(plane, trace_reduce.OPS_LINE)]
    leaves = trace_reduce.union(
        (e[0], e[1]) for e in trace_reduce.self_times(ev) if e[4])
    lo, hi = ev[0][0], max(e[1] for e in ev)
    return trace_reduce.covered(leaves, lo, hi) / (hi - lo), leaves


def programs(plane):
    """[Event] of the `XLA Modules` line in order of start: one per
    executed program, its name without the fingerprint."""
    return [e._replace(name=re.sub(r"\(\d+\)$", "", e.name))
            for e in line_events(plane, "XLA Modules")]


def step_programs(plane):
    """(all programs, those that are a train step): the step is the
    program that holds most of the device's time."""
    progs = programs(plane)
    total = collections.Counter()
    for e in progs:
        total[e.name] += e.end - e.start
    step = total.most_common(1)[0][0] if total else None
    return progs, [e for e in progs if e.name == step]


def phases_of_run(ctx):
    """{phase: ms a step} of this run's traced slice, with `steps`, the
    step programs in it; the table is printed once. None untraced, or
    when the program names no phase (an older program)."""
    space = of_run(ctx)
    if space is None:
        return None
    if "phases" not in ctx:
        plane = device_plane(space)
        secs = phase_seconds(plane)
        steps = max(len(step_programs(plane)[1]), 1)
        by_op, lent = secs.pop("by_op"), secs.pop("lent")
        busy = sum(secs.values())
        ctx["phases"] = out = {k: 1e3 * v / steps for k, v in secs.items()}
        out["steps"] = steps
        print(f"phases: device self time of the slice's {steps} steps by "
              f"`jax.named_scope`, ms a step: "
              + ", ".join(f"{k} {out[k]:.3f}" for k in PHASES + (UNSCOPED,))
              + f" (unscoped {100 * secs[UNSCOPED] / max(busy, 1e-30):.2f} "
              f"% of {1e3 * busy / steps:.3f} busy; {1e3 * lent / steps:.3f} "
              f"of it placed by the operation before, having no path and "
              f"no enclosing event)", flush=True)
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:16]
        print("phases: largest operations, ms a step (phase | operation "
              "[its path's root]): " + "; ".join(
                  f"{phase} | {op} [{root}] {1e3 * v / steps:.3f}"
                  for (phase, op, root), v in top), flush=True)
    out = ctx["phases"]
    return out if sum(out[k] for k in PHASES) > 0 else None


# -- the host's side, and the gaps it leaves on the device ------------------

NOTHING = "nothing open"
BETWEEN, INSIDE = "between programs", "inside a program"


def host_lines(space):
    """[(line name, [Event] sorted by start)] of the host planes, one per
    thread, the Python tracer's own frames (`$file:line fn`) left out."""
    out = []
    for p in space:
        if not p.name.startswith("/host:"):
            continue
        for name, events in p.lines():
            events = [e for e in events if not e.name.startswith("$")]
            if events:
                out.append((name, sorted(events, key=lambda e: e.start)))
    return out


def dispatch_line(lines):
    """Events of the thread that queues the step: the one that holds
    most of the step's `PjitFunction` events; [] when none does."""
    def launches(events):
        return sum(e.name == f"PjitFunction({STEP_FN})" for e in events)

    best = max(lines, key=lambda ln: launches(ln[1]), default=None)
    return best[1] if best and launches(best[1]) else []


def _innermost(events, t, prefix=""):
    best = None
    for e in events:
        if e.start > t:
            break
        if e.end >= t and e.name.startswith(prefix) and (
                best is None or e.start >= best.start):
            best = e
    return best


def name_gap(lo, hi, dispatching, lines):
    """What the host was doing in the idle gap [lo, hi]: the program's
    span open on the dispatching thread at the gap's middle; else the
    runtime's (or the benchmark's) innermost event open there on any
    thread, the shortest first; else the event that overlaps the gap
    longest; else NOTHING."""
    mid = 0.5 * (lo + hi)
    span = _innermost(dispatching, mid, SPAN_PREFIX)
    if span is not None:
        return span.name
    open_now = [e for _, events in lines
                if (e := _innermost(events, mid)) is not None]
    if open_now:
        return "runtime: " + min(open_now, key=lambda e: e.end - e.start).name
    best = None
    for _, events in lines:
        for e in events:
            if e.start >= hi:
                break
            over = min(e.end, hi) - max(e.start, lo)
            if over > 0 and (best is None or over > best[0]):
                best = (over, e.name)
    return "runtime (overlaps): " + best[1] if best else NOTHING


def idle_gaps(space, plane, leaves):
    """[(lo, hi, class, name)] of every interval of the traced slice in
    which no leaf operation (`busy_share`'s `leaves`) ran on the device,
    from the first step program's start to the last one's end."""
    progs, steps = step_programs(plane)
    if not steps or not leaves:
        return []
    lo, hi = steps[0].start, steps[-1].end
    lines = host_lines(space)
    dispatching = dispatch_line(lines)
    out = []
    edges = [[lo, lo]] + [iv for iv in leaves if iv[1] > lo and iv[0] < hi] \
        + [[hi, hi]]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b - a <= 0:
            continue
        mid = 0.5 * (a + b)
        inside = any(p.start <= mid <= p.end for p in progs)
        out.append((a, b, INSIDE if inside else BETWEEN,
                    name_gap(a, b, dispatching, lines)))
    return out


def launch_gaps(plane):
    """[(gap ns, other programs' device ns inside it, their count)] from
    one step program's end to the next one's start."""
    progs, steps = step_programs(plane)
    out = []
    for a, b in zip(steps, steps[1:]):
        between = [p for p in progs if a.end <= p.start and p.end <= b.start]
        out.append((b.start - a.end,
                    sum(p.end - p.start for p in between), len(between)))
    return out


# -- the program's span ring ---------------------------------------------------

def _spans():
    """`paddle_tpu.profiler.spans`, or None where the program keeps no
    ring (a program older than the spans)."""
    try:
        from paddle_tpu.profiler import spans
    except ImportError:
        return None
    return spans


def window(ctx):
    """(lo, hi) of the measured window on the ring's clock, which is
    harness/clock's. `process_start()` is read anew here and /proc ticks
    at 10 ms, so `lo` is a tick early: the window's first step call
    begins after the true instant, the call before it a whole step
    earlier."""
    lo = clock.process_start() + ctx["e2e"]["setup_s"] - 0.02
    return lo, lo + 0.02 + ctx["window_s"]


def ring(ctx, name, after=False):
    """The program's spans of one name that began inside the measured
    window (after it, in the traced slice, with `after`); None where
    there is no ring."""
    spans = _spans()
    if spans is None:
        return None
    lo, hi = window(ctx)
    return spans(name, hi) if after else spans(name, lo, hi)


def step_calls(ctx, after=False):
    """[(whole call s, its dispatch s)] of the window's step calls, a
    call and its `dispatch` child paired by their `step`; None where
    there is no ring or it holds no step call."""
    calls = ring(ctx, SPAN_PREFIX + "step", after)
    if not calls:
        return None
    inner = {s.step: s for s in ring(ctx, SPAN_PREFIX + "step.dispatch",
                                     after)}
    out = [(c.t1 - c.t0, inner[c.step].t1 - inner[c.step].t0)
           for c in calls if c.step in inner]
    if not after and len(out) != ctx["steps"]:
        print(f"spans: {len(out)} step calls in the ring's cut of the "
              f"window, {ctx['steps']} steps counted by the runner",
              flush=True)
    return out or None


def stats_ms(values):
    """(median, mean, min) in ms of durations in s."""
    ms = [1e3 * v for v in values]
    return statistics.median(ms), statistics.fmean(ms), min(ms)
