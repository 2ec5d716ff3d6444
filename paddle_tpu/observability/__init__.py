"""paddle.observability — unified runtime telemetry (ISSUE 12).

One always-on, cheap, exportable telemetry layer across training and
serving:

- `MetricsRegistry` / `registry()` — process-global counters, gauges
  and O(1) ring-buffer histograms with p50/p99; Prometheus text via
  ``registry().expose()``. Every built-in producer (input prefetcher,
  serving scheduler, non-finite guard, checkpoint manager, comm
  bucketer, pipeline schedule) publishes here.
- `StepTimeline` — one structured JSONL record per step through
  pluggable sinks, mirrored into chrome-trace counter tracks that the
  `paddle.profiler` export merges.
- `RetraceSentinel` — wraps every jitted step path; an unexpected
  recompile becomes one attributed log line naming the argument leaf
  whose shape/dtype/weak-type/placement changed, and a hard error
  under `set_strict_retrace(True)` (tests/test_observability.py).
- `hlo_costs` — ``compiled.cost_analysis()`` flops/bytes per step and
  the per-mesh-axis collective byte census (`cost_analysis()` of the
  step classes).
- `FlightRecorder` / `recorder()` — a bounded black box of recent
  events dumped (with a registry snapshot) on crashes;
  `install_signal_dump()` adds SIGQUIT hung-process dumps (ring +
  all-thread stacks, process keeps running).
- `faults` (ISSUE 19) — process-global seeded-deterministic fault
  injection: named fault points across the stack (replica crash/stuck,
  KV hand-off corruption, host-ring drop, checkpoint chunk flip,
  stragglers), scriptable one-shot/probabilistic/scheduled triggers,
  every firing logged to the flight recorder and counted on the
  registry. The substrate behind tests/test_chaos.py and the fleet's
  self-healing rehearsals.
- `Tracer` / `Span` (ISSUE 13) — request-scoped causal timelines: a
  bounded ring of span trees with O(1) begin/end, tail-exemplar
  retention, orphan detection, chrome-trace export on per-request
  tracks merged into the profiler export. The serving tier traces
  every request end to end (`ServingEngine.slow_requests()`).
- `SLOTracker` — declared objectives ("TTFT p99 <= X ms") with
  rolling-window burn-rate gauges on the registry.
- `DebugServer` — stdlib-only loopback HTTP: `/metrics` (Prometheus),
  `/healthz`, `/tracez`, `/flightz` (opt-in from ServingEngine/bench).
- `numerics` (ISSUE 15) — in-graph training-numerics observatory:
  per-layer-chunk grad/update/activation health computed INSIDE the
  compiled step scans ([chunks, k] stats block, one deferred readback
  per logging boundary, zero added collectives), NaN provenance
  through the flight recorder (``nan_provenance`` events,
  ``numerics.first_bad_chunk``), an EWMA spike detector
  (``numerics.anomaly.count``), ``numerics.*`` lazy gauges and the
  `/numericsz` endpoint.
- `memory` (ISSUE 14) — device-memory accounting:
  `CompiledMemoryProfile` (AOT buffer-assignment stats + top-K
  buffers of any compiled step, `step.memory_profile()` everywhere,
  ``mem.compiled.*`` gauges), `live_buffer_report()` (resident bytes
  attributed to params / scan shards / optimizer state / KV pools /
  prefetch ring vs untagged, ``mem.live.*`` gauges, `/memz`), and
  `dump_oom` OOM forensics through the flight recorder.

Quickstart::

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs

    tl = obs.StepTimeline(sinks=[obs.JsonlSink("steps.jsonl")])
    for i, (ids, labels) in enumerate(loader):
        t0 = time.perf_counter()
        loss = step(ids, labels)
        tl.record(step=i, host_ms=(time.perf_counter() - t0) * 1e3)
    print(obs.registry().expose())        # Prometheus text
    print(obs.retrace_summary())          # compile/retrace receipt
"""
from . import faults  # noqa: F401
from .debug_server import DebugServer  # noqa: F401
from .faults import FaultError, FaultInjector  # noqa: F401
from .flight_recorder import (  # noqa: F401
    FlightRecorder, install, install_signal_dump, recorder,
    thread_stacks,
)
from .hlo_costs import (  # noqa: F401
    cost_analysis_of, load_hlo_overlap,
)
from .memory import (  # noqa: F401
    CompiledMemoryProfile, LiveBufferRegistry, dump_oom, is_oom_error,
    last_oom_report, live_buffer_report, live_registry, memz_payload,
    oom_guard, parse_hlo_buffers,
)
from .numerics import (  # noqa: F401
    NumericsMonitor, chunk_of_layer, monitor_enabled, numericsz_payload,
)
from .registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, merge_histograms,
    percentile, registry,
)
from .sentinel import (  # noqa: F401
    RetraceError, RetraceSentinel, enabled, retrace_summary,
    set_strict_retrace, strict_retrace,
)
from .slo import SLO, SLOTracker  # noqa: F401
from .timeline import (  # noqa: F401
    JsonlSink, StepTimeline, drain_chrome_counters, read_jsonl,
)
from .tracing import Span, Tracer, drain_chrome_spans  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "percentile", "merge_histograms", "StepTimeline", "JsonlSink", "read_jsonl",
    "drain_chrome_counters", "RetraceSentinel", "RetraceError",
    "set_strict_retrace", "strict_retrace", "retrace_summary",
    "enabled", "FlightRecorder", "recorder", "install",
    "install_signal_dump", "thread_stacks",
    "cost_analysis_of", "load_hlo_overlap",
    "Span", "Tracer", "drain_chrome_spans", "SLO", "SLOTracker",
    "DebugServer",
    "CompiledMemoryProfile", "LiveBufferRegistry", "live_registry",
    "live_buffer_report", "parse_hlo_buffers", "is_oom_error",
    "dump_oom", "oom_guard", "last_oom_report", "memz_payload",
    "NumericsMonitor", "monitor_enabled", "numericsz_payload",
    "chunk_of_layer", "faults", "FaultError", "FaultInjector",
]
