"""StepTimeline: one structured JSONL record per training/serving step.

Every ``record()`` call emits a flat JSON object through the attached
sinks — a JSONL file (`JsonlSink`), any callable, and (always) the
chrome-trace counter-track buffer the `Profiler` export merges, so step
metrics render as counter lanes under the host/device spans.

Schema: every record carries ``ts`` (unix seconds), ``lane`` (e.g.
"train"/"serve") and ``step`` (int); all other fields are free-form and
should be JSON scalars (numeric fields become chrome counter tracks).
``read_jsonl()`` is the matching loader (the schema round trip is in
tests/test_observability.py).
"""
from __future__ import annotations

import collections
import json
import os
import re
import threading
import time

from .registry import registry as _registry
from .sentinel import enabled

__all__ = ["StepTimeline", "JsonlSink", "read_jsonl",
           "drain_chrome_counters"]

# chrome counter-track buffer (bounded): drained by
# Profiler._finish_cycle into the exported trace
_counter_events = collections.deque(maxlen=65536)
_counter_lock = threading.Lock()


def drain_chrome_counters():
    """Pop all pending chrome-trace counter events ("ph": "C")."""
    with _counter_lock:
        out = list(_counter_events)
        _counter_events.clear()
    return out


class JsonlSink:
    """Append-a-line-per-record file sink (flushed per record so a
    crash loses at most the in-flight line).

    ``max_bytes`` caps the live file: when the next line would cross
    the cap, the file rolls over (``timeline.jsonl`` ->
    ``timeline.jsonl.1``, existing ``.1`` -> ``.2``, ... up to
    ``backups`` segments, the oldest dropped) — a multi-hour serve or
    bench run cannot grow the per-step timeline unbounded.
    `read_jsonl` follows the rotated segments oldest-first."""

    def __init__(self, path, max_bytes=None, backups=3):
        self.path = path
        self.max_bytes = int(max_bytes) if max_bytes else None
        self.backups = max(1, int(backups))
        self._lock = threading.Lock()
        if self.max_bytes is not None:
            self._prune_beyond_cap()
        self._f = open(path, "a")
        try:
            self._size = os.path.getsize(path)
        except OSError:
            self._size = 0

    def _prune_beyond_cap(self):
        """Remove rotated segments past the current ``backups`` cap —
        leftovers from an earlier run (or a larger previous cap) would
        otherwise survive forever and prepend stale records to every
        `read_jsonl` of this path."""
        for idx, p in _rotated_segments(self.path):
            if idx > self.backups:
                try:
                    os.remove(p)
                except OSError:
                    pass

    def _rotate(self):
        # caller holds the lock. A failed rename must DEGRADE (keep
        # appending to the oversized file) — it must never leave the
        # sink holding a closed handle that turns every later step's
        # record into an IO error in the hot loop.
        self._f.flush()
        self._f.close()
        try:
            for i in range(self.backups - 1, 0, -1):
                src = f"{self.path}.{i}"
                if os.path.exists(src):
                    os.replace(src, f"{self.path}.{i + 1}")
            os.replace(self.path, f"{self.path}.1")
            self._prune_beyond_cap()
        except OSError:
            # degrade ONCE: keep appending uncapped rather than paying
            # a doomed flush/close/rename/reopen on every later record
            self.max_bytes = None
        finally:
            self._f = open(self.path, "a")
            try:
                self._size = os.path.getsize(self.path)
            except OSError:
                self._size = 0

    def __call__(self, record: dict):
        line = json.dumps(record) + "\n"
        with self._lock:
            if self._f is None:
                return
            if (self.max_bytes is not None and self._size
                    and self._size + len(line) > self.max_bytes):
                self._rotate()
            self._f.write(line)
            self._f.flush()
            self._size += len(line)

    def close(self):
        with self._lock:
            if self._f is not None:
                self._f.flush()
                self._f.close()
                self._f = None


_ROTATED_RE = re.compile(r"\.(\d+)$")


def _rotated_segments(path):
    """Existing ``path.N`` rotation siblings as [(N, path)], ascending."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    base = os.path.basename(path)
    segs = []
    try:
        names = os.listdir(d)
    except OSError:
        return []
    for name in names:
        if name.startswith(base + "."):
            m = _ROTATED_RE.search(name[len(base):])
            if m:
                segs.append((int(m.group(1)), os.path.join(d, name)))
    return sorted(segs)


def read_jsonl(path, follow_rotated=True):
    """Load a timeline JSONL file back into a list of dicts. With
    ``follow_rotated`` (default), rotated segments (``path.N`` ...
    ``path.1``) are read first — highest index = oldest — so the
    result is one in-order record stream across rollovers. A rotated
    sibling that is not valid JSONL (a stray ``path.<digits>`` file)
    is skipped rather than poisoning the read; the MAIN file still
    raises on corruption."""
    paths = [(path, True)]
    if follow_rotated:
        paths = [(p, False) for _, p in
                 sorted(_rotated_segments(path), reverse=True)] \
            + [(path, True)]
    out = []
    for p, strict in paths:
        if not os.path.exists(p):
            continue
        recs = []
        try:
            with open(p) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        recs.append(json.loads(line))
        except (json.JSONDecodeError, UnicodeDecodeError):
            if strict:
                raise
            continue
        out.extend(recs)
    return out


class StepTimeline:
    """Per-step structured telemetry emitter.

    Usage::

        tl = StepTimeline(sinks=[JsonlSink("steps.jsonl")])
        for i, batch in enumerate(loader):
            t0 = time.perf_counter()
            loss = step(*batch)
            tl.record(step=i, host_ms=(time.perf_counter() - t0) * 1e3)

    ``record`` also mirrors numeric fields into registry histograms
    (``timeline.<lane>.<field>``) and the chrome counter-track buffer.
    All host-side; never reads a device value.
    """

    def __init__(self, sinks=(), lane="train", registry=None,
                 chrome_counters=True):
        self.lane = lane
        self.sinks = list(sinks)
        self._registry = registry if registry is not None else _registry()
        self._chrome = bool(chrome_counters)
        self._step_auto = 0

    def add_sink(self, sink):
        self.sinks.append(sink)
        return sink

    def record(self, step=None, **fields) -> dict:
        if not enabled():
            return {}
        if step is None:
            step = self._step_auto
        self._step_auto = int(step) + 1
        rec = {"ts": round(time.time(), 6), "lane": self.lane,
               "step": int(step)}
        rec.update(fields)
        for k, v in fields.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            self._registry.histogram(
                f"timeline.{self.lane}.{k}").observe(v)
            if self._chrome:
                # perf_counter timebase: host spans in the Profiler
                # export use perf_counter_ns/1e3 µs, and the counter
                # tracks must land on the same axis
                with _counter_lock:
                    _counter_events.append({
                        "name": f"{self.lane}/{k}", "ph": "C",
                        "ts": time.perf_counter_ns() / 1e3, "pid": 0,
                        "args": {k: v}})
        for sink in self.sinks:
            try:
                sink(rec)
            except Exception:
                pass
        try:
            from .flight_recorder import recorder

            recorder().note("step", lane=self.lane, step=int(step))
        except Exception:
            pass
        return rec

    def close(self):
        for sink in self.sinks:
            close = getattr(sink, "close", None)
            if callable(close):
                close()
