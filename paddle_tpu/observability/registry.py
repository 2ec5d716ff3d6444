"""Process-global metrics registry: counters, gauges, ring histograms.

The unification layer ISSUE 12 asks for: every runtime producer (input
prefetcher, serving scheduler, non-finite guard, checkpoint manager,
comm bucketer, pipeline schedule) publishes into ONE registry instead
of a private dict, and every consumer (Prometheus
scrapes, chrome-trace counter tracks, the crash flight recorder) reads
the same surface.

Design constraints (tentpole):

- **Near-zero cost when nobody is scraping.** An instrument update is a
  few python ops under a per-instrument lock (~1µs); histograms are
  O(1) ring-buffer writes — percentiles are computed lazily at
  ``snapshot()``/``expose()`` time, never on the hot path. Nothing here
  ever touches a device array, so no instrument can add a host sync to
  a compiled step (lazy gauges may hold device scalars — they are only
  read when scraped).
- **Thread-safe.** The prefetcher producer thread, checkpoint
  background saver and the step loop all publish concurrently.
- **One histogram implementation.** ``percentile()`` here is the single
  nearest-rank implementation; ``serving.metrics`` re-exports it and
  its latency surface is these ``Histogram`` objects.
"""
from __future__ import annotations

import math
import re
import threading

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "registry", "percentile", "merge_histograms"]


def percentile(values, q):
    """Nearest-rank percentile (q in [0, 100]) of a sequence, None if
    empty — the single percentile implementation (serving re-exports
    it; `Histogram.percentile` calls it on the ring window)."""
    values = list(values)
    if not values:
        return None
    xs = sorted(values)
    k = max(0, min(len(xs) - 1, int(round(q / 100.0 * (len(xs) - 1)))))
    return xs[k]


class Counter:
    """Monotonic float counter."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, n=1.0):
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value

    def reset(self):
        with self._lock:
            self._value = 0.0

    def snapshot(self):
        return self._value


class Gauge:
    """Last-value gauge. ``set_fn`` makes it LAZY: the callable is
    evaluated only when the gauge is scraped — the mechanism that lets
    device-scalar state (loss scale, guard counters) publish without
    adding a per-step host sync."""

    __slots__ = ("name", "_lock", "_value", "_fn")

    def __init__(self, name):
        self.name = name
        self._lock = threading.Lock()
        self._value = None
        self._fn = None

    def set(self, v):
        with self._lock:
            self._value = v
            self._fn = None

    def set_fn(self, fn):
        with self._lock:
            self._fn = fn

    @property
    def value(self):
        fn = self._fn
        if fn is not None:
            try:
                return fn()
            except Exception:
                return None
        return self._value

    def reset(self):
        with self._lock:
            self._value = None
            self._fn = None

    def snapshot(self):
        return self.value


class Histogram:
    """O(1) ring-buffer histogram: the last ``window`` samples plus
    running count/sum/min/max over ALL samples. Percentiles are
    computed on demand from the ring (recent-window percentiles — the
    right semantics for step-time/latency telemetry)."""

    __slots__ = ("name", "window", "_lock", "_ring", "_idx", "_count",
                 "_sum", "_min", "_max")

    def __init__(self, name, window=1024):
        self.name = name
        self.window = int(window)
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with getattr(self, "_lock", threading.Lock()):
            self._ring = [0.0] * self.window
            self._idx = 0
            self._count = 0
            self._sum = 0.0
            self._min = None
            self._max = None

    def observe(self, v):
        v = float(v)
        with self._lock:
            self._ring[self._idx % self.window] = v
            self._idx += 1
            self._count += 1
            self._sum += v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v

    # list-ish aliases so producers that used to append to a plain list
    # keep reading naturally
    append = observe

    def extend(self, values):
        for v in values:
            self.observe(v)

    def samples(self):
        """The ring window, oldest first."""
        with self._lock:
            n = min(self._count, self.window)
            if self._count <= self.window:
                return self._ring[:n]
            start = self._idx % self.window
            return self._ring[start:] + self._ring[:start]

    @property
    def count(self):
        return self._count

    @property
    def total(self):
        return self._sum

    def __len__(self):
        return min(self._count, self.window)

    def __bool__(self):
        return self._count > 0

    def __iter__(self):
        return iter(self.samples())

    def percentile(self, q):
        return percentile(self.samples(), q)

    def mean(self):
        return self._sum / self._count if self._count else None

    def snapshot(self):
        xs = self.samples()
        return {
            "count": self._count,
            "sum": round(self._sum, 6),
            "mean": (round(self._sum / self._count, 6)
                     if self._count else None),
            "min": self._min,
            "max": self._max,
            "p50": percentile(xs, 50),
            "p99": percentile(xs, 99),
        }


def merge_histograms(hists, name="merged", window=None):
    """Fleet-correct percentile aggregation (ISSUE 18): one Histogram
    holding the UNION of the inputs' ring windows, so a fleet p99 is
    the p99 of merged samples. Averaging per-replica p99s is wrong the
    moment replicas are skewed — one slow replica's tail divided by N
    disappears — and quantiles don't compose any other way without the
    raw samples, which the rings keep.

    The merged window defaults to the sum of the input windows so no
    input sample ages out during the merge. Lifetime count/sum/min/max
    fold ALL samples each input ever observed, not just the windows,
    so ``snapshot()["count"]`` stays the true fleet event count.
    """
    hists = list(hists)
    if window is None:
        window = max(1, sum(h.window for h in hists))
    out = Histogram(name, window=int(window))
    for h in hists:
        out.extend(h.samples())
    with out._lock:
        counts = [h.count for h in hists]
        out._count = sum(counts)
        out._sum = sum(h.total for h in hists)
        mins = [h._min for h in hists if h._min is not None]
        maxs = [h._max for h in hists if h._max is not None]
        out._min = min(mins) if mins else None
        out._max = max(maxs) if maxs else None
    return out


_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")
_PROM_NAME_OK = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


def _prom_name(name):
    """Sanitize an instrument name into a VALID Prometheus metric name
    (`[a-zA-Z_:][a-zA-Z0-9_:]*`): invalid characters become `_`, a
    leading digit gets a `_` prefix, and an empty/fully-invalid name
    degrades to `_` rather than an empty (spec-violating) token."""
    n = _NAME_RE.sub("_", str(name))
    if not n:
        n = "_"
    if n[0].isdigit():
        n = "_" + n
    return n


def _prom_value(v):
    """Render one sample value per the text-format spec: non-finite
    floats are `+Inf`/`-Inf`/`NaN` (repr()'s `inf`/`nan` are NOT valid
    exposition tokens)."""
    if v is None:
        return "NaN"
    if isinstance(v, bool):
        return "1" if v else "0"
    try:
        f = float(v)
    except (TypeError, ValueError):
        return "NaN"
    if math.isnan(f):
        return "NaN"
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    return repr(f)


class MetricsRegistry:
    """Named instruments, get-or-create. One process-global instance
    (``registry()``) is the default publish target; private instances
    (one per ServingEngine) isolate concurrent engines."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments = {}

    def _get(self, name, cls, **kw):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = cls(name, **kw)
                self._instruments[name] = inst
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(inst).__name__}, requested {cls.__name__}")
            return inst

    def counter(self, name) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name, window=1024) -> Histogram:
        return self._get(name, Histogram, window=window)

    def names(self, prefix=None):
        with self._lock:
            return sorted(n for n in self._instruments
                          if prefix is None or n.startswith(prefix))

    def get(self, name):
        with self._lock:
            return self._instruments.get(name)

    def reset(self, prefix=None):
        """Zero instruments (all, or those under ``prefix``) — the
        instruments stay registered so held references keep working."""
        with self._lock:
            insts = [i for n, i in self._instruments.items()
                     if prefix is None or n.startswith(prefix)]
        for i in insts:
            i.reset()

    def snapshot(self, prefix=None) -> dict:
        """{name: scalar-or-histogram-dict} for every instrument."""
        out = {}
        for name in self.names(prefix):
            inst = self.get(name)
            if inst is not None:
                out[name] = inst.snapshot()
        return out

    def expose(self, prefix=None) -> str:
        """Prometheus text exposition (0.0.4): counters and gauges as
        single samples, histograms as summaries (quantile 0.5/0.9/0.99
        + _sum/_count)."""
        lines = []
        seen = set()
        for name in self.names(prefix):
            inst = self.get(name)
            if inst is None:
                continue
            pn = _prom_name(name)
            # two distinct instrument names may sanitize to the same
            # prom name ("a.b" and "a/b") — duplicate unlabeled samples
            # violate the format, so later collisions get a suffix
            if pn in seen:
                k = 2
                while f"{pn}_{k}" in seen:
                    k += 1
                pn = f"{pn}_{k}"
            seen.add(pn)
            if isinstance(inst, Counter):
                lines.append(f"# TYPE {pn} counter")
                lines.append(f"{pn} {_prom_value(inst.value)}")
            elif isinstance(inst, Gauge):
                lines.append(f"# TYPE {pn} gauge")
                lines.append(f"{pn} {_prom_value(inst.value)}")
            elif isinstance(inst, Histogram):
                xs = inst.samples()
                lines.append(f"# TYPE {pn} summary")
                for q in (0.5, 0.9, 0.99):
                    lines.append(
                        f'{pn}{{quantile="{q}"}} '
                        f"{_prom_value(percentile(xs, q * 100))}")
                lines.append(f"{pn}_sum {_prom_value(inst.total)}")
                lines.append(f"{pn}_count {inst.count}")
        return "\n".join(lines) + ("\n" if lines else "")


_global_lock = threading.Lock()
_global_registry = None


def registry() -> MetricsRegistry:
    """The process-global registry every built-in producer publishes
    into by default."""
    global _global_registry
    if _global_registry is None:
        with _global_lock:
            if _global_registry is None:
                _global_registry = MetricsRegistry()
    return _global_registry
