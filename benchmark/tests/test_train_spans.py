"""The train path's readers (PR 24) against recorded traces and a tiny
cell: the .xplane.pb decoder beside `jax.profiler.ProfileData`, the phase
of an operation's path, phase times recomputed the plain way, launch gaps
worked by hand from the `XLA Modules` line, the idle gaps' names, and the
ring readers on a rehearsed window. Run by hand: `pytest benchmark/tests`.

`data/train-350m-4steps.xplane.pb`: the traced slice of
gpt3-350m.train.8x1024 on one v5e chip, this tree's program (PR 24, call
1), trimmed: without the /host:metadata plane (the programs' HLO), the
device's `Async XLA Ops` line, the Python tracer's own `$file:line` frames
and the per-operation stats nobody reads (`source_stack`, ...).
`data/train-1.3b-4steps.xplane.pb` is PR 23's: a program without scopes
or spans, which is what the readers meet on a parent commit.
"""
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import load, trace_reduce, xplane  # noqa: E402

T350 = os.path.join(BENCH, "data", "train-350m-4steps.xplane.pb")
T13 = os.path.join(BENCH, "data", "train-1.3b-4steps.xplane.pb")
PHASE_READERS = ("forward_ms.train", "backward_ms.train",
                 "optimizer_ms.train")


@pytest.fixture(scope="module")
def spaces():
    return {p: xplane.planes(p) for p in (T350, T13)}


def _ctx(path, spaces):
    return {"xplane": spaces[path],
            "trace": trace_reduce.reduce_profile(trace_reduce.load(path), 1)}


def _read(name, ctx):
    return load.module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("path", [T350, T13])
def test_decoder_agrees_with_profile_data(path, spaces):
    theirs = {p.name: p for p in trace_reduce.load(path).planes}
    for plane in spaces[path]:
        lines = list(theirs[plane.name].lines)
        assert [ln.name for ln in lines] == plane.line_names()
        if not plane.name.startswith(("/device:TPU", "/host:CPU")):
            continue
        zero = None
        for (name, mine), ln in zip(plane.lines(), lines):
            want = list(ln.events)
            assert len(mine) == len(want), name
            for a, b in list(zip(mine, want))[::97]:
                assert a.name == b.name
                assert abs((a.end - a.start) - b.duration_ns) <= 1
                if zero is None and plane.name.startswith("/device"):
                    zero = a.start - b.start_ns
                if plane.name.startswith("/device"):
                    assert abs(a.start - zero - b.start_ns) <= 1


def test_event_metadata_is_read(spaces):
    ops = xplane.line_events(xplane.device_plane(spaces[T350]),
                             trace_reduce.OPS_LINE)
    paths = {str(e.stats.get("tf_op")) for e in ops}
    assert "jit(step_fn)/backward/transpose(jvp())/dot_general:" in paths
    assert any(p.startswith("jit(step_fn)/optimizer/") for p in paths)
    kernel = next(e for e in ops if "splash_bwd" in e.name.split(" ")[0])
    assert kernel.stats["source"].endswith("splash_attention.py:541")
    assert kernel.stats["device_duration_ps"] > 0       # the event's own


@pytest.mark.parametrize("path,phase", [
    ("jit(step_fn)/forward/jvp()/dot_general:", "forward"),
    ("jit(step_fn)/backward/transpose(forward)/jvp()/mul:", "backward"),
    ("jit(step_fn)/backward/while/body/closed_call/optimizer/add:",
     "optimizer"),
    ("jit(step_fn)/backward/while:", "backward"),
    ("jit(step_fn)/optimizer/numerics/reduce_sum:", "optimizer"),
    ("jit(step_fn)/while/body/closed_call/transpose(jvp())/dot_general:",
     None),
    ("jit(step_fn)/transpose(jvp(forward))/dot_general:", None),
    ("", None),
])
def test_phase_of_a_path(path, phase):
    assert xplane.phase_of(path) == phase


def test_phase_times_recomputed_the_plain_way(spaces):
    """The tape step's operations do not nest, so a phase's time is the
    plain sum over the operations whose own path names it, plus what the
    pathless ones got from the operation before them."""
    plane = xplane.device_plane(spaces[T350])
    ops = xplane.line_events(plane, trace_reduce.OPS_LINE)
    own = dict.fromkeys(xplane.PHASES + (None,), 0.0)
    lent = dict.fromkeys(xplane.PHASES, 0.0)
    before = None
    for e in ops:
        phase = xplane.phase_of(str(e.stats.get("tf_op") or ""))
        own[phase] += (e.end - e.start) * 1e-9
        if phase is None:
            lent[before] += (e.end - e.start) * 1e-9
        before = phase or before
    # by hand from the run's own print-out (PR 24, call 1), ms a step
    assert [round(250 * own[p], 3) for p in xplane.PHASES] == [
        60.286, 119.685, 1.875]
    assert round(250 * own[None], 3) == 6.837
    got = xplane.phase_seconds(plane)
    for p in xplane.PHASES:
        assert got[p] == pytest.approx(own[p] + lent[p], rel=1e-9)
    assert got[xplane.UNSCOPED] == 0.0
    assert got["lent"] == pytest.approx(own[None], rel=1e-9)
    total = sum(e.end - e.start for e in ops) * 1e-9
    assert sum(got[p] for p in xplane.PHASES) == pytest.approx(total)
    # the AdamW update rides in the weight gradients' matmul fusions
    fused = got["by_op"]["backward", "fusion (tuple)",
                         "transpose(jvp())/dot_general"]
    assert round(250 * fused, 3) == 60.793


def test_phase_readers(spaces):
    ctx = _ctx(T350, spaces)
    got = [_read(n, ctx) for n in PHASE_READERS]
    assert [round(v, 3) for v in got] == [62.631, 123.794, 2.258]
    assert ctx["phases"]["steps"] == 4
    older = _ctx(T13, spaces)          # a program without scopes
    assert [_read(n, older) for n in PHASE_READERS] == [None] * 3
    assert _read("forward_ms.train", {"trace": None}) is None


def test_launch_gaps_by_hand(spaces, capsys):
    """PR 23's 1.3b trace, `XLA Modules`: step programs end / start (ns)
    1493560415.000 / 1498234141.250, 2943519821.000 / 2947967657.250,
    4393258126.500 / 4397818586.500; between each pair one
    jit_convert_element_type of 592.4 to 593.75 ns."""
    gaps = xplane.launch_gaps(xplane.device_plane(spaces[T13]))
    assert [round(g) for g, _, _ in gaps] == [4673726, 4447836, 4560460]
    assert [n for _, _, n in gaps] == [1, 1, 1]
    value = _read("launch_gap_ms.train", _ctx(T13, spaces))
    assert value == pytest.approx(
        (4673726.25 + 4447836.25 + 4560460.0 - 593.75 - 592.42 - 592.5)
        / 3e6, rel=1e-7)
    out = capsys.readouterr().out
    assert "2.00 device programs a step" in out
    # the tape step at 350m, losses read 16 late: programs back to back
    assert _read("launch_gap_ms.train", _ctx(T350, spaces)) \
        == pytest.approx(0.017793, rel=1e-3)


@pytest.mark.parametrize("path,idle_ms", [(T350, 0.667), (T13, 15.786)])
def test_idle_gaps_get_a_class_and_a_name(path, idle_ms, spaces):
    space = spaces[path]
    plane = xplane.device_plane(space)
    gaps = xplane.idle_gaps(space, plane, xplane.busy_share(plane)[1])
    total = sum(b - a for a, b, _, _ in gaps)
    assert total * 1e-6 == pytest.approx(idle_ms, abs=5e-4)
    assert {cls for _, _, cls, _ in gaps} <= {xplane.BETWEEN, xplane.INSIDE}
    unnamed = sum(b - a for a, b, _, name in gaps if name == xplane.NOTHING)
    assert unnamed / total < 0.2
    between = [g for g in gaps if g[2] == xplane.BETWEEN]
    if path == T13:     # three launch gaps, the host inside np.asarray...
        assert len(between) == 3
        assert sum(b - a for a, b, _, _ in between) * 1e-6 \
            == pytest.approx(13.69, abs=0.01)
    else:               # ...and at 350m a program's own spans name gaps
        assert any(name == "paddle_tpu.step.dispatch"
                   for _, _, _, name in gaps)


def test_busy_share_in_picoseconds(spaces):
    """harness/trace_reduce.py reads this trace as 90.3 % busy: in
    ProfileData's rounded nanoseconds 234 operations share their start
    with a zero-length custom-call, count as its parent and drop out of
    the union of leaves. By the trace's picoseconds nothing nests."""
    plane = xplane.device_plane(spaces[T350])
    busy, leaves = xplane.busy_share(plane)
    assert busy == pytest.approx(0.99917, abs=1e-5)
    reduced = trace_reduce.reduce_profile(trace_reduce.load(T350), 1)
    assert reduced["busy_s"] / reduced["window_s"] \
        == pytest.approx(0.90324, abs=1e-5)
    line = next(ln for p in trace_reduce.load(T350).planes
                if p.name == "/device:TPU:0" for ln in p.lines
                if ln.name == trace_reduce.OPS_LINE)
    dropped = [e for e in trace_reduce.self_times(trace_reduce._events(line))
               if not e[4]]
    assert len(dropped) == 234
    assert sum(e[1] - e[0] for e in dropped) * 1e-6 \
        == pytest.approx(72.44, abs=0.01)


def test_ring_readers_on_a_rehearsed_window():
    """A tiny tape cell on the CPU through the runner, then the three
    readers of the program's span ring on its context."""
    code = textwrap.dedent(f"""
        import argparse, os, sys
        sys.path.insert(0, {BENCH!r}); sys.path.insert(0, {ROOT!r})
        import run
        from harness import clock, load
        tiny = os.path.join({HERE!r}, "tiny")
        load.SEARCH.insert(0, tiny)
        load.MANIFEST[0] = os.path.join(tiny, "BENCHMARK.tiny.json")
        from paddle_tpu.utils import flags
        flags.set_flags({{"FLAGS_pallas_force_interpret": True,
                         "FLAGS_pallas_flash_min_seqlen": 128}})
        t_start = clock.process_start()
        cell, dev, _ = run.open_cell("tiny.train.tape", allow_cpu=True)
        args = argparse.Namespace(workload="tiny.train.tape",
                                  seed=5_000_000_003, seconds=2.0, trace=0)
        ctx = {{"cell": cell, "device": dev, "args": args}}
        load.module("runners", "train_job").run(cell, args, t_start, ctx)
        from paddle_tpu.profiler import spans
        lo, hi = __import__("harness.xplane").xplane.window(ctx)
        print("CALLS", len(spans("paddle_tpu.step", lo, hi)), ctx["steps"])
        for name in ("step_host_ms.train", "step_dispatch_ms.train",
                     "gc_pause_ms.train"):
            print("VALUE", name, load.module("layer_metrics", name).read(ctx))
        print("QUEUE", 1e3 * ctx["counters"]["host_queue_s"] / ctx["steps"])
    """)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    calls = next(ln.split() for ln in lines if ln.startswith("CALLS"))
    assert calls[1] == calls[2]          # the ring's cut is the window
    values = {ln.split()[1]: float(ln.split()[2]) for ln in lines
              if ln.startswith("VALUE")}
    queue = float(next(ln for ln in lines
                       if ln.startswith("QUEUE")).split()[1])
    assert 0 < values["step_host_ms.train"] < queue
    assert 0 < values["step_dispatch_ms.train"] < 2 * queue
    assert values["gc_pause_ms.train"] >= 0
    together = next(ln for ln in lines if "means together" in ln)
    off = float(together.rsplit("(", 1)[1].split("%")[0])
    assert abs(off) < 10, together       # one clock, the runner's
