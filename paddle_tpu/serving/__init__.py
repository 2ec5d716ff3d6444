"""Continuous-batching serving tier over the paged KV-cache decode engine.

The layer that turns the PR-2 decode engine (inference/kv_cache.py +
jit/decode_step.py) into a server: requests arrive at any time, join the
running batch as soon as a KV slot and pages are free, stream their
tokens out as they are sampled, and leave the moment they finish — no
sequence ever waits for another's tail (ROADMAP item 1).

* ``ServingEngine`` — the loop: admits, chunk-prefills, decodes, streams
  and retires over ONE compiled decode program (retrace-free) and one
  compiled prefill program per chunk bucket.
* ``RequestScheduler`` — admission/preemption/retirement policy over the
  paged cache's slot + page bookkeeping (FIFO within priority,
  lowest-priority victim when the page pool runs dry).
* ``ServingMetrics`` — queue depth, TTFT, inter-token latency, tok/s,
  preemption counters.
* ``OnlineTuner`` — opt-in closed loop (ISSUE 17) nudging admission
  watermark / prefill aggressiveness / decode burst from live SLO-burn
  and queue-depth gauges; bounded, hysteretic, flight-recorded.
* ``FleetRouter`` — the multi-replica tier (ISSUE 18): session-affinity
  + power-of-two-choices routing over N engine replicas,
  prefill/decode disaggregation with KV page hand-off, host-memory KV
  eviction (``HostKVRing``), and SLO-burn autoscaling
  (``SLOBurnAutoscaler``).
"""
from .engine import ServingEngine
from .fleet import FleetRouter, HostKVRing, SLOBurnAutoscaler
from .metrics import ServingMetrics, percentile
from .request import Request, RequestHandle, RequestState
from .router import ReplicaRouter
from .scheduler import RequestScheduler
from .tuner import OnlineTuner, TunerLimits

__all__ = ["ServingEngine", "RequestScheduler", "ServingMetrics",
           "Request", "RequestHandle", "RequestState", "percentile",
           "OnlineTuner", "TunerLimits", "FleetRouter", "HostKVRing",
           "SLOBurnAutoscaler", "ReplicaRouter"]
