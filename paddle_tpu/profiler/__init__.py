"""paddle.profiler parity — host event recorder + XLA device traces.

Reference: python/paddle/profiler/profiler.py:358 (Profiler with
scheduler/on_trace_ready), :227 (export_chrome_tracing), :592/:641
(start/stop); RecordEvent annotations (python/paddle/profiler/utils.py);
host event collection (paddle/fluid/platform/profiler/host_event_recorder.h).

TPU-first split of responsibilities:
- *Host side*: a lightweight in-process event recorder (RecordEvent spans +
  per-step marks) — the analog of HostEventRecorder; exported as
  chrome-trace JSON and summarized in `summary()`.
- *Device side*: `jax.profiler` traces (XLA/TPU timeline, HLO cost, memory
  viewer) written to the same directory when device tracing is requested —
  CUPTI's job (cuda_tracer.cc) is done by the XLA/TSL profiler.
"""
from __future__ import annotations

import collections
import contextlib
import enum
import gc
import json
import os
import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "ProfilerState", "ProfilerTarget", "Profiler", "RecordEvent", "Span",
    "spans", "DEVICE_SCOPES",
    "make_scheduler", "export_chrome_tracing", "load_profiler_result",
]


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # last RECORD step of a cycle: trace is handed off


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


@dataclass
class _HostEvent:
    name: str
    start_ns: int
    end_ns: int
    tid: int
    step: Optional[int]


@dataclass
class _ProfileResult:
    """What on_trace_ready receives; also returned by Profiler.stop()."""

    events: list = field(default_factory=list)
    steps: list = field(default_factory=list)  # (step_idx, start_ns, end_ns)
    device_trace_dir: Optional[str] = None
    # chrome counter-track events ("ph": "C") drained from the
    # observability StepTimeline at cycle end (ISSUE 12): step metrics
    # render as counter lanes under the host spans
    counters: list = field(default_factory=list)
    # chrome request-track span events ("ph": "X"/"M") drained from the
    # observability Tracer (ISSUE 13): per-request serving timelines
    # render as their own thread tracks next to the counter lanes
    request_spans: list = field(default_factory=list)

    def chrome_trace(self) -> dict:
        evts = []
        for e in self.events:
            evts.append({
                "name": e.name, "ph": "X", "cat": "host",
                "ts": e.start_ns / 1e3, "dur": (e.end_ns - e.start_ns) / 1e3,
                "pid": 0, "tid": e.tid,
            })
        for idx, s, t in self.steps:
            evts.append({
                "name": f"ProfileStep#{idx}", "ph": "X", "cat": "step",
                "ts": s / 1e3, "dur": (t - s) / 1e3, "pid": 0, "tid": 0,
            })
        evts.extend(self.counters)
        evts.extend(self.request_spans)
        return {"traceEvents": evts, "displayTimeUnit": "ms"}


class _HostEventRecorder:
    """Process-global span recorder (host_event_recorder.h analog)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events: list = []
        self.enabled = False
        self._step: Optional[int] = None

    def record(self, name, start_ns, end_ns):
        if not self.enabled:
            return
        ev = _HostEvent(name, start_ns, end_ns,
                        threading.get_ident() & 0xFFFF, self._step)
        with self._lock:
            self._events.append(ev)

    def drain(self):
        with self._lock:
            out, self._events = self._events, []
        return out


_recorder = _HostEventRecorder()


_RING = 1024        # spans kept per name
_MAX_NAMES = 256    # names that get a ring: formatted names must not leak
_rings: dict = {}

Span = collections.namedtuple("Span", "name t0 t1 thread step")


def spans(name=None, lo=None, hi=None) -> list:
    """The spans kept in memory, oldest first: those called `name` (all
    names when None) that began within [lo, hi] on `time.perf_counter`.
    A span's parent is the span of the same thread whose interval
    encloses it."""
    rings = _rings.values() if name is None else [_rings.get(name, ())]
    out = [s for ring in rings for s in list(ring)
           if (lo is None or s.t0 >= lo) and (hi is None or s.t0 <= hi)]
    if name is None:
        out.sort(key=lambda s: s.t0)
    return out


class RecordEvent:
    """A span (reference profiler/utils.py RecordEvent).

    Usable as a context manager or begin()/end() pair. It always opens a
    `jax.profiler.TraceAnnotation(name, **attrs)`, so that it sits in the
    host plane of whatever trace is being taken, on the device trace's
    clock, whoever started the trace; it always leaves `Span(name, t0,
    t1, thread, step)` in a ring of the last 1,024 spans of its name
    (`spans()`); and it joins the paddle `Profiler`'s host events while
    that is recording. `step` is the one attribute the ring keeps.
    """

    def __init__(self, name: str, event_type=None, **attrs):
        self.name = name
        self._attrs = attrs
        self._t0 = None
        self._jax_ctx = None

    def begin(self):
        self._jax_ctx = TraceAnnotation(self.name, **self._attrs)
        self._jax_ctx.__enter__()
        self._t0 = time.perf_counter()
        return self

    def end(self):
        if self._t0 is None:
            return
        t0, t1, self._t0 = self._t0, time.perf_counter(), None
        self._jax_ctx.__exit__(None, None, None)
        self._jax_ctx = None
        ring = _rings.get(self.name)
        if ring is None and len(_rings) < _MAX_NAMES:
            ring = _rings.setdefault(self.name,
                                     collections.deque(maxlen=_RING))
        if ring is not None:
            ring.append(Span(self.name, t0, t1, threading.get_ident(),
                             self._attrs.get("step")))
        if _recorder.enabled:
            _recorder.record(self.name, int(t0 * 1e9), int(t1 * 1e9))

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()


# The device side's names: every `jax.named_scope` path the mixture models
# (models/keye_vl2.py, models/mellum2.py and what they call) put on their
# compiled step's operations. It is what an operator sees in xprof or
# Perfetto as part of each operation's name, after the step's phase
# (`jit(step_fn)/forward/...`, `.../backward/transpose(jvp(<path>))/...`),
# and what benchmark/harness/scope_tree.py builds its tree from: a path
# `a/b` is a leaf of `a`, the innermost path on an operation's name holds
# its time. No part of a path is a phase (`forward`, `backward`,
# `optimizer`). A model that adds a scope adds its path here.
DEVICE_SCOPES = (
    "embed",                    # the token embedding and its gradient
    "attention/projections",    # input norm, q / k / v products, q/k norm,
                                # rotary, output product and residual
    "indexer",                  # bare: the query-chunk loop's own copies
    "indexer/project",          # the branch's norm, projections, rotary
    "indexer/scores",           # a chunk's index scores and their pull-back
    "indexer/select",           # causal mask, exact top-k, kept count
    "indexer/target",           # the head-averaged attention probabilities
    "indexer/loss",             # log-softmax, KL and d(L_I)/d(scores)
    "sparse_attention",         # attention over the selection
    "window_attention",         # a sliding layer's attention
    "full_attention",           # a full layer's attention
    "ssm/project",              # a Mamba layer's input norm and W_in
    "ssm/conv",                 # the depthwise causal convolution and silu
    "ssm/scan",                 # softplus, the running sums, the chunked
                                # scan's two kernels and D x
    "ssm/gate_norm",            # y * silu(z) and the grouped RMSNorm
    "ssm/out",                  # W_out and the residual
    "kda/project",              # a KDA layer's input norm, W_q, W_k, W_v,
                                # W_f, W_b and W_g
    "kda/conv",                 # the three depthwise causal convolutions
    "kda/gate",                 # the decay a, beta, the L2 norms and q's scale
    "kda/scan",                 # beta's products, the running sums and the
                                # chunked delta rule's two kernels
    "kda/gate_norm",            # the head's RMSNorm and its sigmoid gate
    "kda/out",                  # W_o and the residual
    "conv/project",             # a gated-convolution layer's input norm, W_in
    "conv/gate_conv",           # y = C * conv3(B * X), one operator
    "conv/out",                 # W_out and the residual
    "mla/project",              # latent attention's norm, W_q, W_kva, W_kvb,
                                # q/k norm, rotary, gate, W_o and residual
    "mla_attention",            # attention at 192 / 128 head widths
    "mlp",                      # a leading layer's norm, dense SwiGLU, residual
    "moe/norm",                 # the post-attention norm
    "moe/route",                # bare: what XLA adds between the leaves
    "moe/route/router",         # router product, softmax, top-k, balance
    "moe/route/plan",           # sort into row tables, weights, counters
    "moe/route/gather",         # a tile's table slices and row gathers
    "moe/route/add_back",       # the accumulator, a tile's add-back, the
                                # tile loops' own carries
    "moe/experts",              # the grouped products
    "moe/shared",               # the shared expert's two products
    "moe/cast",                 # float32 staging round the tile loops: the
                                # cotangent cast up, outputs and gradients
                                # cast back to the parameters' type
    "moe/residual",             # the mixture's output added to the stream
    "head",                     # final norm, cross entropy over the head
    "picks",                    # the step's counters and recorded picks
)


_gc_span = []       # the open full-collection span, if any


def _on_gc(phase, info):
    """`gc.callbacks` hook: one `paddle_tpu.host.gc` span per FULL
    collection — the only kind long enough to stall a training loop."""
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_span.append(
            RecordEvent("paddle_tpu.host.gc", generation=2).begin())
    elif _gc_span:
        _gc_span.pop().end()


gc.callbacks.append(_on_gc)


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Reference profiler.py make_scheduler: per-step state machine
    [skip_first][closed][ready][record ... RECORD_AND_RETURN], repeating."""
    cycle = closed + ready + record
    if record <= 0 or cycle <= 0:
        raise ValueError("record must be > 0")

    def fn(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s // cycle >= repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return fn


def _default_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD  # record everything; RETURN on stop()


def export_chrome_tracing(dir_name: str,
                          worker_name: Optional[str] = None) -> Callable:
    """on_trace_ready handler writing chrome://tracing JSON
    (reference profiler.py:227)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        worker = worker_name or f"host_{os.getpid()}"
        n = getattr(prof, "_export_count", 0)
        prof._export_count = n + 1
        fname = os.path.join(dir_name, f"{worker}_time_{n}.paddle_trace.json")
        with open(fname, "w") as f:
            json.dump(prof._last_result.chrome_trace(), f)
        prof._last_export_path = fname
        return fname

    return handler


def load_profiler_result(file_name: str) -> dict:
    with open(file_name) as f:
        return json.load(f)


class Profiler:
    """Reference profiler.py:358.

    Args:
      targets: iterable of ProfilerTarget; including TPU/GPU turns on the
        XLA device tracer (`jax.profiler.start_trace`).
      scheduler: ``(start, end)`` tuple or a ``make_scheduler`` callable.
      on_trace_ready: callable(prof) fired at every RECORD_AND_RETURN step
        and at stop(); default exports chrome tracing to ./profiler_log.
      timer_only: host step timing only — never touches the device tracer.
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 trace_dir: str = "profiler_log", timer_only: bool = False):
        targets = list(targets) if targets is not None else [
            ProfilerTarget.CPU]
        self.targets = targets
        if scheduler is None:
            self._scheduler = _default_scheduler
        elif callable(scheduler):
            self._scheduler = scheduler
        else:
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start, repeat=1)
        self.on_trace_ready = on_trace_ready or export_chrome_tracing(
            trace_dir)
        self.timer_only = timer_only
        self._trace_dir = trace_dir
        self._device_on = (not timer_only) and any(
            t in (ProfilerTarget.TPU, ProfilerTarget.GPU,
                  ProfilerTarget.CUSTOM_DEVICE) for t in targets)
        self.current_state = ProfilerState.CLOSED
        self._step = 0
        self._step_start_ns = None
        self._steps: list = []
        self._device_tracing = False
        self._last_result = _ProfileResult()
        self._last_export_path = None

    # -- lifecycle ------------------------------------------------------
    def start(self):
        self.current_state = self._scheduler(self._step)
        self._apply_state()
        self._step_start_ns = time.perf_counter_ns()
        return self

    def stop(self):
        self._mark_step_end()
        # finish only a cycle that was actually recording — otherwise a
        # CLOSED tail (scheduler exhausted) would clobber the completed
        # cycle's result with an empty one and double-fire on_trace_ready
        if _recorder.enabled:
            self._finish_cycle()
        self._stop_device()
        _recorder.enabled = False
        self.current_state = ProfilerState.CLOSED
        return self._last_result

    def step(self):
        """Advance the step counter (call once per training iteration)."""
        self._mark_step_end()
        prev = self.current_state
        if prev == ProfilerState.RECORD_AND_RETURN:
            self._finish_cycle()
        self._step += 1
        self.current_state = self._scheduler(self._step)
        self._apply_state()
        self._step_start_ns = time.perf_counter_ns()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- internals ------------------------------------------------------
    def _apply_state(self):
        rec = self.current_state in (ProfilerState.RECORD,
                                     ProfilerState.RECORD_AND_RETURN)
        _recorder.enabled = rec
        _recorder._step = self._step
        if rec and self._device_on and not self._device_tracing:
            try:
                import jax.profiler as jp

                os.makedirs(self._trace_dir, exist_ok=True)
                jp.start_trace(self._trace_dir)
                self._device_tracing = True
            except Exception:
                self._device_tracing = False
        elif not rec:
            self._stop_device()

    def _stop_device(self):
        if self._device_tracing:
            try:
                import jax.profiler as jp

                jp.stop_trace()
            except Exception:
                pass
            self._device_tracing = False

    def _mark_step_end(self):
        if self._step_start_ns is not None:
            self._steps.append((self._step, self._step_start_ns,
                                time.perf_counter_ns()))
            self._step_start_ns = None

    def _finish_cycle(self):
        events = _recorder.drain()
        steps = list(self._steps)
        # the chrome buffers are process-global and may hold a long
        # backlog recorded before this profiling cycle (a timeline or
        # tracer running with no Profiler active) — keep only events
        # inside the cycle's host window (buffer ts is µs on the same
        # perf_counter timebase as the span ns timestamps)
        lo = min([s for _, s, _ in steps]
                 + [e.start_ns for e in events], default=None)
        try:
            from ..observability import drain_chrome_counters

            counters = drain_chrome_counters()
            if lo is not None:
                counters = [c for c in counters if c["ts"] * 1e3 >= lo]
        except Exception:
            counters = []
        try:
            from ..observability import drain_chrome_spans

            spans = drain_chrome_spans()
            # metadata ("ph": "M", no ts) is kept unconditionally
            if lo is not None:
                spans = [s for s in spans
                         if s.get("ph") == "M"
                         or s.get("ts", 0) * 1e3 >= lo]
        except Exception:
            spans = []
        self._last_result = _ProfileResult(
            events=events, steps=steps,
            device_trace_dir=self._trace_dir if self._device_on else None,
            counters=counters, request_spans=spans)
        self._steps = []
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    # -- reporting ------------------------------------------------------
    def summary(self, sorted_by: str = "total", max_rows: int = 50) -> str:
        """Host-event statistical table
        (profiler_statistic.py's role, host side)."""
        res = self._last_result
        agg = defaultdict(lambda: [0, 0.0, 0.0])  # name -> [count, total, max]
        for e in res.events:
            a = agg[e.name]
            dur = (e.end_ns - e.start_ns) / 1e6
            a[0] += 1
            a[1] += dur
            a[2] = max(a[2], dur)
        col = {"total": 1, "calls": 0, "max": 2, "avg": 1}.get(sorted_by, 1)
        if sorted_by == "avg":
            keyf = lambda kv: -(kv[1][1] / kv[1][0])  # noqa: E731
        else:
            keyf = lambda kv: -kv[1][col]  # noqa: E731
        rows = sorted(agg.items(), key=keyf)[:max_rows]
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}"
                 f"{'Avg(ms)':>12}{'Max(ms)':>12}"]
        for name, (cnt, total, mx) in rows:
            lines.append(f"{name[:40]:<40}{cnt:>8}{total:>12.3f}"
                         f"{total / cnt:>12.3f}{mx:>12.3f}")
        if res.steps:
            durs = [(t - s) / 1e6 for _, s, t in res.steps]
            lines.append(
                f"\nSteps: {len(durs)}  avg {sum(durs) / len(durs):.3f} ms"
                f"  min {min(durs):.3f}  max {max(durs):.3f}")
        return "\n".join(lines)

    @property
    def step_times_ms(self):
        return [(t - s) / 1e6 for _, s, t in self._last_result.steps]


@contextlib.contextmanager
def profile_step(name: str = "train_step"):
    """Tiny convenience: time one span even with no Profiler active.

    The always-on path is the observability registry — the span lands
    in the ``profile_step.<name>_ms`` histogram unconditionally
    (previously the recorder dropped it whenever no Profiler cycle was
    RECORDing, breaking this docstring's promise — ISSUE 12 satellite);
    when a Profiler IS recording, the span also joins its host events.
    """
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        t1 = time.perf_counter_ns()
        _recorder.record(name, t0, t1)
        try:
            from ..observability import registry

            registry().histogram(
                f"profile_step.{name}_ms").observe((t1 - t0) / 1e6)
        except Exception:
            pass


class SortedKeys(enum.Enum):
    """reference profiler.SortedKeys — summary_sort key choices."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(enum.Enum):
    """reference profiler.SummaryView — summary table choices."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(dir_name="profiler_log", worker_name=None):
    """reference profiler.export_protobuf scheduler-callback factory.
    The TPU backend's native trace format is chrome tracing / the jax
    profiler's TensorBoard protobufs — this returns a callback that
    routes through export_chrome_tracing and notes the format."""
    return export_chrome_tracing(dir_name, worker_name)
