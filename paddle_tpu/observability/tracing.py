"""Request-scoped distributed tracing: a lightweight Tracer/Span store.

PR 12's registry answers "what are the aggregates" (p50/p99 TTFT, queue
depth, tok/s); this module answers the question a production on-call
actually asks: *why was this specific request slow?* A `Span` is one
timed interval with attributes and children; a `Tracer` owns a bounded
ring of COMPLETED root-span trees plus the set of currently-open spans,
so a retired serving request's trace is a complete causal timeline
(queue wait -> admission -> chunked prefills -> decode bursts ->
preempt/resume -> stream delivery) and an in-flight one is inspectable
mid-run.

Design constraints (same bar as the registry):

- **O(1) begin/end, monotonic timestamps.** ``begin`` allocates one
  slotted object and appends to its parent's child list; ``end`` stamps
  ``t1`` and, for roots, rotates the bounded ring. No percentile math,
  no serialization, no device access ever happens on the hot path —
  `to_dict` trees are built at scrape time (`/tracez`, tests).
- **Bounded everywhere.** Completed roots live in a ring
  (``capacity``), tail exemplars in their own ring
  (``exemplar_capacity``), children per span are capped
  (``max_children`` — beyond it children are dropped and counted on
  the parent, so a runaway 10k-token request cannot hold 10k span
  objects live).
- **Orphan detection.** An *orphan* is a span that outlived its trace:
  still open while its root is closed (the churn-with-preemption bug
  class — a decode span leaked across a retire), or closed with a
  dangling parent that was never recorded. ``orphans()`` walks the
  open set at call time; tests/test_tracing.py asserts it is empty
  after drain + ``abort_all``.
- **Chrome export on per-request tracks.** Ended spans (when
  ``chrome=True``) land in a bounded module buffer on the same
  perf_counter timebase as the PR 12 counter tracks; the Profiler
  export drains ``drain_chrome_spans()`` next to
  ``drain_chrome_counters()``, one chrome *thread* per track (the
  request id), so traces render under the host spans in ui.perfetto.
"""
from __future__ import annotations

import collections
import threading
import time
import weakref

from .sentinel import enabled
from .registry import registry as _registry

__all__ = ["Span", "Tracer", "drain_chrome_spans"]

# chrome span-track buffer (bounded), drained by Profiler._finish_cycle
# into the exported trace next to the StepTimeline counter tracks
_span_events = collections.deque(maxlen=65536)
_span_lock = threading.Lock()
_track_tids: dict = {}          # track name -> chrome tid (bounded)
_emitted_meta: set = set()      # tracks whose "M" events are in the
#                                 CURRENT buffer (cleared per drain so
#                                 every profiler cycle gets metadata)
_next_tid = 1                   # monotonic: a tid is never reassigned
_MAX_TRACKS = 4096
_CHROME_PID = 1                 # separate process group from host spans


def drain_chrome_spans():
    """Pop all pending chrome-trace span events ("ph": "X"/"M")."""
    with _span_lock:
        out = list(_span_events)
        _span_events.clear()
        # metadata must be re-emitted into the NEXT cycle's buffer
        _emitted_meta.clear()
    return out


def _profiler_recording() -> bool:
    """Chrome span events are only consumed by the Profiler export, so
    the buffer is fed only while a profiler cycle is RECORDing — with
    no profiler active, span end() skips the chrome dict build
    entirely (the difference between ~2µs and ~5µs per span on the
    serve loop)."""
    rec = _profiler_recorder()
    return rec is not None and rec.enabled


_prof_recorder = None


def _profiler_recorder():
    global _prof_recorder
    if _prof_recorder is None:
        try:
            from ..profiler import _recorder

            _prof_recorder = _recorder
        except Exception:
            _prof_recorder = False
    return _prof_recorder or None


def _chrome_tid(track):
    """Stable tid per track name, re-announced per drain cycle via the
    thread-name metadata event (a profiler cycle after the first must
    not render bare numeric tids). Tids are MONOTONIC — never
    reassigned, so two tracks can never collide inside one export no
    matter how many recycles happen — and the name->tid map is bounded
    by evicting its oldest entries (an evicted track that reappears
    simply gets a fresh tid and fresh metadata)."""
    global _next_tid
    tid = _track_tids.get(track)
    if tid is None:
        while len(_track_tids) >= _MAX_TRACKS:
            evicted = next(iter(_track_tids))
            del _track_tids[evicted]
            _emitted_meta.discard(evicted)
        tid = _next_tid
        _next_tid += 1
        _track_tids[track] = tid
    if track not in _emitted_meta:
        if not _emitted_meta:
            _span_events.append({
                "name": "process_name", "ph": "M", "pid": _CHROME_PID,
                "tid": 0, "args": {"name": "requests"}})
        _emitted_meta.add(track)
        _span_events.append({
            "name": "thread_name", "ph": "M", "pid": _CHROME_PID,
            "tid": tid, "args": {"name": str(track)}})
    return tid


class Span:
    """One timed interval in a trace tree. Created by `Tracer.begin`;
    ``t1 is None`` while open. Attributes are a plain dict of JSON
    scalars; children are Spans appended by later ``begin`` calls.

    CYCLE-FREE by construction: the child->parent link is a weakref
    (parent->children is the only strong direction), so a trace tree
    evicted from the ring frees by refcount immediately instead of
    waiting for a gen2 cycle collection — measured in the serving
    lane, span cycles were enough extra cyclic garbage to land a
    ~170 ms full GC inside a 260 ms measured traffic window. The
    children list is lazily allocated (most spans are leaves)."""

    __slots__ = ("name", "span_id", "track", "t0", "t1", "attrs",
                 "_parent_ref", "_children", "dropped_children",
                 "__weakref__")

    def __init__(self, name, span_id, track, parent, t0, attrs):
        self.name = name
        self.span_id = span_id
        self.track = track
        self._parent_ref = (weakref.ref(parent) if parent is not None
                            else None)
        self.t0 = t0
        self.t1 = None
        self.attrs = attrs
        self._children = None
        self.dropped_children = 0

    @property
    def parent(self):
        """The parent span, or None for roots (and for spans whose
        tree was already collected)."""
        return (self._parent_ref() if self._parent_ref is not None
                else None)

    @property
    def children(self) -> list:
        return self._children if self._children is not None else []

    @property
    def closed(self):
        return self.t1 is not None

    @property
    def root(self):
        """The tree root, or None when an ancestor was collected (the
        span outlived its trace — an orphan by definition)."""
        s = self
        while s._parent_ref is not None:
            p = s._parent_ref()
            if p is None:
                return None
            s = p
        return s

    def duration_s(self):
        return None if self.t1 is None else self.t1 - self.t0

    def to_dict(self) -> dict:
        """Nested JSON-able tree (scrape-time only — never hot path)."""
        d = {"name": self.name, "track": self.track,
             "t0": round(self.t0, 6),
             "t1": None if self.t1 is None else round(self.t1, 6),
             "dur_ms": (None if self.t1 is None
                        else round((self.t1 - self.t0) * 1e3, 4)),
             "attrs": dict(self.attrs)}
        if self._children:
            d["children"] = [c.to_dict() for c in self._children]
        if self.dropped_children:
            d["dropped_children"] = self.dropped_children
        return d

    def find(self, name) -> list:
        """All descendant spans (depth-first) with the given name."""
        out = []
        stack = list(self.children)
        while stack:
            s = stack.pop()
            if s.name == name:
                out.append(s)
            if s._children:
                stack.extend(s._children)
        return out

    def __repr__(self):
        state = "open" if self.t1 is None else f"{self.duration_s():.6f}s"
        return f"<Span {self.name!r} track={self.track} {state}>"


# shared no-op span: returned when tracing is disabled or a parent's
# child budget is exhausted — begin/end on it are O(1) no-ops and it
# never enters the open set or any tree
_NOOP = Span("<noop>", -1, None, None, 0.0, {})
_NOOP.t1 = 0.0


class Tracer:
    """Bounded store of span trees.

    Args:
      capacity: completed root spans kept (ring, newest wins).
      exemplar_capacity: tail-exemplar root spans kept (separate ring —
        an exemplar survives ring churn).
      max_children: per-span child cap; excess children are dropped and
        counted on the parent (``dropped_children``).
      chrome: publish ended spans to the chrome span-track buffer
        (only while a Profiler cycle is recording — the export is the
        buffer's sole consumer, and skipping the event build otherwise
        keeps span end() at ~2µs).
      clock: monotonic clock (the serving engine passes its own so span
        times line up with TTFT bookkeeping).
      registry: MetricsRegistry for the lazy ``trace.*`` gauges.
      enabled: False builds a tracer whose ``begin`` returns a shared
        no-op span — the zero-overhead opt-out.
    """

    def __init__(self, capacity=256, exemplar_capacity=32,
                 max_children=1024, chrome=True,
                 clock=time.perf_counter, registry=None, enabled=True):
        self.capacity = int(capacity)
        self.max_children = int(max_children)
        self.chrome = bool(chrome)
        self.clock = clock
        self._on = bool(enabled)
        self._lock = threading.Lock()
        self._ring = collections.deque(maxlen=self.capacity)
        self._exemplars = collections.deque(maxlen=int(exemplar_capacity))
        self._open: dict = {}            # span_id -> Span
        self._next_id = 0
        self.spans_begun = 0
        self.spans_ended = 0
        self.spans_dropped = 0
        self.completed_total = 0
        self.bind_registry(registry if registry is not None
                           else _registry())

    def bind_registry(self, reg):
        """(Re-)register the lazy trace gauges — the serving engine
        rebinds after `reset_metrics` swaps its registry."""
        if reg is None:
            return
        reg.gauge("trace.open_spans").set_fn(lambda: len(self._open))
        reg.gauge("trace.completed_traces").set_fn(
            lambda: self.completed_total)
        reg.gauge("trace.exemplars").set_fn(lambda: len(self._exemplars))
        reg.gauge("trace.orphans").set_fn(lambda: len(self.orphans()))
        reg.gauge("trace.dropped_spans").set_fn(
            lambda: self.spans_dropped)

    # -- hot path --------------------------------------------------------
    def begin(self, name, parent=None, track=None, **attrs) -> Span:
        """Open a span. ``parent=None`` opens a root (a new trace);
        otherwise the span joins ``parent.children``. O(1)."""
        if not self._on or not enabled():
            return _NOOP
        if parent is _NOOP:
            return _NOOP
        if parent is not None:
            kids = parent._children
            if kids is not None and len(kids) >= self.max_children:
                parent.dropped_children += 1
                with self._lock:
                    self.spans_dropped += 1
                return _NOOP
        t0 = self.clock()
        with self._lock:
            self._next_id += 1
            sid = self._next_id
            span = Span(name, sid,
                        track if track is not None
                        else (parent.track if parent is not None
                              else f"t{sid}"),
                        parent, t0, attrs)
            self._open[sid] = span
            self.spans_begun += 1
        if parent is not None:
            if parent._children is None:
                parent._children = []
            parent._children.append(span)
        return span

    def end(self, span: Span, **attrs):
        """Close a span. Roots rotate into the completed ring. O(1)."""
        if span is None or span is _NOOP or span.t1 is not None:
            return
        span.t1 = self.clock()
        if attrs:
            span.attrs.update(attrs)
        with self._lock:
            self._open.pop(span.span_id, None)
            self.spans_ended += 1
            if span._parent_ref is None:       # a root completes a trace
                self._ring.append(span)
                self.completed_total += 1
        if self.chrome and _profiler_recording():
            with _span_lock:
                tid = _chrome_tid(span.track)
                _span_events.append({
                    "name": span.name, "ph": "X", "cat": "request",
                    "ts": span.t0 * 1e6,
                    "dur": (span.t1 - span.t0) * 1e6,
                    "pid": _CHROME_PID, "tid": tid,
                    "args": {k: v for k, v in span.attrs.items()
                             if isinstance(v, (int, float, str, bool))
                             or v is None}})

    def instant(self, name, parent=None, track=None, **attrs) -> Span:
        """Zero-duration marker span (admission, preemption)."""
        span = self.begin(name, parent=parent, track=track, **attrs)
        self.end(span)
        return span

    # -- scrape surface --------------------------------------------------
    def open_spans(self) -> list:
        with self._lock:
            return list(self._open.values())

    def orphans(self) -> list:
        """Spans that outlived their trace: open while the root is
        closed, or whose parent chain is gone entirely (the tree was
        collected out from under a still-open span)."""
        out = []
        for s in self.open_spans():
            if s._parent_ref is None:
                continue                        # open roots are fine
            root = s.root
            if root is None or root.closed:
                out.append(s)
        return out

    def traces(self, n=None) -> list:
        """Completed traces as nested dicts, oldest first."""
        with self._lock:
            roots = list(self._ring)
        if n is not None:
            roots = roots[-int(n):]
        return [r.to_dict() for r in roots]

    def find_trace(self, track):
        """Newest completed root on ``track`` (Span, not dict) — the
        per-request lookup (serving tracks are ``req<rid>``)."""
        with self._lock:
            roots = list(self._ring)
        for r in reversed(roots):
            if r.track == track:
                return r
        return None

    # -- tail exemplars --------------------------------------------------
    def add_exemplar(self, root: Span, reason, **attrs):
        """Pin a root span tree into the exemplar ring (bounded; the
        full tree survives ring churn). Idempotent per root."""
        if root is None or root is _NOOP:
            return
        with self._lock:
            if any(r is root for _, _, r in self._exemplars):
                return
            self._exemplars.append((reason, dict(attrs), root))

    def exemplars(self) -> list:
        """[{reason, ...attrs, trace}] oldest first (scrape surface —
        `ServingEngine.slow_requests()`)."""
        with self._lock:
            items = list(self._exemplars)
        return [{"reason": reason, **attrs, "trace": root.to_dict()}
                for reason, attrs, root in items]

    # -- lifecycle -------------------------------------------------------
    def clear(self):
        """Drop all state (e.g. after engine warmup — compile-time
        traces are noise). Counters reset too."""
        with self._lock:
            self._ring.clear()
            self._exemplars.clear()
            self._open.clear()
            self.spans_begun = 0
            self.spans_ended = 0
            self.spans_dropped = 0
            self.completed_total = 0

    def stats(self) -> dict:
        with self._lock:
            return {"open": len(self._open),
                    "completed": self.completed_total,
                    "begun": self.spans_begun,
                    "ended": self.spans_ended,
                    "dropped": self.spans_dropped,
                    "exemplars": len(self._exemplars),
                    "ring": len(self._ring)}
