"""Device self time of a traced slice as ONE tree of the program's
`jax.named_scope` paths, from one pass over the slice's `XLA Ops` line.

The names are the program's: `paddle_tpu.profiler.DEVICE_SCOPES`, a tuple
of paths in which `a/b` is a leaf of `a`. A block that adds a scope adds
its path there and a reader here; there is no list of names in this file
and no further walk over the line. The rules are harness/scopes.py's: the
innermost known path on an operation's `tf_op` holds its self time, plain
or wrapped by a transformation (`transpose(jvp(moe/route/plan))`); an
operation that names none takes the node of the event it is nested in.
So a node's own time is what stands under it and under none of its
leaves (its bare remainder), and a node with its leaves is what
harness/scopes.py reads under the node's name.

One rule more, for the fusions XLA leaves without a path of their own
(its loop passes drop the metadata: the radix select's largest fusion, a
rotary's two-output fusion). The profiler lends such an operation the
path of the `while` whose body it stands in, or nothing. Where a fusion's
path is empty or ends in `/while:`, which no fusion's own path can, the
instructions fused into it stand in: the node most of their paths name
holds its time. Those paths are in the step's HLO, which the profiler
keeps in the trace (`/host:metadata`); a trace without it reads by the
first rules alone. What a node got this way is printed beside it: the
older walkers cannot see it, so a node and its leaves may read more here
than `moe_route_ms.train` or `indexer_ms.train` by that much.

Time that no node holds is `unnamed`, but for operations under the
`optimizer` phase: that scope has its own reader (`optimizer_ms.train`)
and is printed beside `unnamed`. `forward` and `backward` say which pass
an operation runs in, not what it is, and name nothing. Nodes, `unnamed`
and the optimizer's remainder add up to the line's self time.

A program without the tuple (a parent commit) gives no tree, and the
readers return None.
"""
from __future__ import annotations

import collections
import re
import time

from harness import trace_reduce, xplane

UNNAMED = "unnamed"
OPTIMIZER = "optimizer"


def vocabulary():
    """The program's scope paths, or None where it has no such tuple."""
    try:
        from paddle_tpu.profiler import DEVICE_SCOPES
    except ImportError:
        return None
    return tuple(DEVICE_SCOPES)


def finder(names):
    """-> node_of(tf_op): the innermost of `names` on an operation's
    path, None where it names none. A longer path is tried first, so a
    leaf wins over the node it stands under."""
    find = re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(
        re.escape(n) for n in sorted(names, key=len, reverse=True)))
    seen = {}

    def node_of(tf_op: str):
        if tf_op not in seen:
            found = find.findall(tf_op.rstrip(":"))
            seen[tf_op] = found[-1] if found else None
        return seen[tf_op]

    return node_of


def under(names, node):
    """`node` and the paths of `names` that stand under it."""
    return [n for n in names if n == node or n.startswith(node + "/")]


_CALLS = re.compile(r"calls=%([\w.\-]+)")


def fused_paths(space):
    """-> paths(event): the `op_name`s of the instructions fused into a
    fusion event, read from its program's HLO as the profiler keeps it in
    the trace (`/host:metadata`: an HloProto a program, under the
    program's id); () for any other event and where the trace holds no
    such program. A program is indexed when first asked for (its
    computations' names and instruction spans), a computation read when
    first asked for."""
    meta = next((p for p in space if p.name == "/host:metadata"), None)
    programs, seen = {}, {}

    def sub(span, number):
        """The length-delimited fields `number` of the message at `span`."""
        return [v for num, wire, v in xplane._fields(meta._buf, *span)
                if num == number and wire == 2]

    def computations(program_id):
        if program_id not in programs:
            _, stats = meta._event_meta.get(program_id, ("", ()))
            programs[program_id] = {
                xplane._text(meta._buf, sub(comp, 1)[0]): sub(comp, 2)
                for stat in stats
                for proto in sub(stat, 6)           # XStat.bytes_value
                for module in sub(proto, 1)         # HloProto.hlo_module
                for comp in sub(module, 3)}         # .computations
        return programs[program_id]

    def paths(event):
        if meta is None or "calls=%" not in event.name:
            return ()
        try:
            program_id = int(event.stats.get("program_id") or 0)
        except ValueError:
            return ()
        key = program_id, _CALLS.search(event.name).group(1)
        if key not in seen:
            seen[key] = tuple(
                xplane._text(meta._buf, op_name)
                for instruction in computations(program_id).get(key[1], ())
                for metadata in sub(instruction, 7)  # HloInstructionProto
                for op_name in sub(metadata, 2))     # OpMetadata.op_name
        return seen[key]

    return paths


def walk(events, names, fused=None):
    """({node: own s, UNNAMED: s, OPTIMIZER: s}, {node: s of it that came
    through fused instructions}, {(operation, root of its path): s} of
    the unnamed operations) of `events`, an `XLA Ops` line sorted by start
    with an enclosing event before what it encloses. `fused(event)` gives
    the paths of the instructions fused into an event (`fused_paths`)."""
    node_of = finder(names)
    out = dict.fromkeys(tuple(names) + (UNNAMED, OPTIMIZER), 0.0)
    lent = collections.defaultdict(float)
    nameless = collections.defaultdict(float)
    most = {}           # a fusion's paths -> the node most of them name
    stack = []          # [end, node, self ns, operation, path, lent?]

    def close(item):
        out[item[1]] += item[2] * 1e-9
        if item[5]:
            lent[item[1]] += item[2] * 1e-9
        if item[1] == UNNAMED:
            nameless[trace_reduce.short_name(item[3]),
                     "/".join(item[4].rstrip(":").split("/")[-2:])] += \
                item[2] * 1e-9

    for e in events:
        while stack and stack[-1][0] <= e.start:
            close(stack.pop())
        tf_op = str(e.stats.get("tf_op") or "")
        node, through = node_of(tf_op), False
        if fused is not None and (not tf_op or tf_op.endswith("/while:")):
            inner = fused(e)
            if inner not in most:
                named = collections.Counter(
                    n for n in map(node_of, inner) if n is not None)
                most[inner] = named.most_common(1)[0][0] if named else None
            if most[inner] is not None:
                node, through = most[inner], True
        if node is None and xplane.phase_of(tf_op) == OPTIMIZER:
            node = OPTIMIZER
        if stack:
            stack[-1][2] -= min(e.end, stack[-1][0]) - e.start
            if node is None:
                node = stack[-1][1]
        stack.append([e.end, node or UNNAMED, e.end - e.start, e.name,
                      tf_op, through])
    while stack:
        close(stack.pop())
    return out, dict(lent), dict(nameless)


def _report(names, own, lent, nameless, steps, n_events, seconds):
    step = sum(own.values())

    def row(label, ms, note=""):
        return (f"scope tree:   {label:<26}{ms:>10.3f}"
                f"{100 * ms / max(step, 1e-30):>7.2f} %{note}")

    def through(nodes):
        ms = sum(lent.get(n, 0.0) for n in nodes)
        return (f"   of it through fused instructions {ms:.3f}"
                if ms >= 0.0005 else "")

    print(f"scope tree: device self time of the slice's {steps} steps by "
          "the innermost `jax.named_scope` path on each operation "
          "(paddle_tpu.profiler.DEVICE_SCOPES), forward and backward "
          f"together, ms a step | share of the step's {step:.3f}; a fusion "
          "whose path is absent or its loop's reads by the instructions "
          "fused into it, which the older walkers cannot see; the walk "
          f"took {seconds:.2f} s over {n_events} events", flush=True)
    for name in names:
        if any(name.startswith(n + "/") for n in names):
            continue            # printed under its node
        below = under(names, name)
        if not any(own[n] for n in below):
            continue            # a block this program does not have
        if len(below) == 1:
            print(row(name, own[name], through(below)), flush=True)
            continue
        print(row(name, sum(own[n] for n in below),
                  f"   of it bare (under no leaf) {own[name]:.3f}"
                  + through(below)), flush=True)
        for leaf in below[1:]:
            print(row("  " + leaf, own[leaf], through([leaf])), flush=True)
    print(row(UNNAMED, own[UNNAMED],
              "   under no node; besides, under the `optimizer` phase "
              f"and no node {own[OPTIMIZER]:.3f} (optimizer_ms.train "
              "reads the phase)"), flush=True)
    top = sorted(nameless.items(), key=lambda kv: -kv[1])[:12]
    print("scope tree: unnamed, largest operations, ms a step (operation "
          "[its path's root]): " + "; ".join(
              f"{op} [{root}] {1e3 * v / steps:.3f}"
              for (op, root), v in top), flush=True)


def of_run(ctx):
    """{node: own ms a step, UNNAMED, OPTIMIZER} of this run's traced
    slice, walked and printed once; None untraced, for a program without
    the vocabulary, or where it names no node (a GPT cell)."""
    space = xplane.of_run(ctx)
    names = vocabulary()
    if space is None or names is None:
        return None
    if "scope_tree" not in ctx:
        plane = xplane.device_plane(space)
        events = xplane.line_events(plane, trace_reduce.OPS_LINE)
        steps = max(len(xplane.step_programs(plane)[1]), 1)
        t0 = time.perf_counter()
        secs, lent, nameless = walk(events, names, fused_paths(space))
        seconds = time.perf_counter() - t0
        ctx["scope_tree"] = own = {k: 1e3 * v / steps
                                   for k, v in secs.items()}
        if any(own[n] for n in names):
            _report(names, own, {k: 1e3 * v / steps for k, v in lent.items()},
                    nameless, steps, len(events), seconds)
    own = ctx["scope_tree"]
    return own if any(own[n] for n in names) else None


def ms(ctx, node):
    """A node's own ms a step (an inner node's: its bare remainder)."""
    own = of_run(ctx)
    return None if own is None else own[node]
