"""In-graph training-numerics observatory (ISSUE 15 tentpole).

The non-finite guard (PR 4) reports only "a bad step happened"; inside
the fused/sharded scans, per-layer gradient magnitudes, update ratios
and activation scales were invisible — exactly the signals needed to
debug loss spikes and AMP scale churn. This module is the missing
layer: every train-step path computes, INSIDE its compiled program, a
small fixed-shape ``[rows, NFIELDS]`` fp32 stats block — one row per
layer chunk plus one ``outer`` row (embedding / ln_f / LM head) — and
hands the DEVICE array to a host-side `NumericsMonitor` that defers
every readback to a logging/scrape boundary.

Traced field layout (``assemble_stats`` builds it; all fields are
SUM-reducible so multi-rank partials fold by plain addition):

  F_GRAD_SQ      squared norm of the chunk's (unscaled, dp-mean) grads
  F_PARAM_SQ     squared norm of the chunk's params (master values)
  F_UPD_SQ       squared norm of the optimizer update ‖Δw‖²
  F_ACT_SQ       sum of squares of the chunk's output activations
  F_ACT_N        element count behind F_ACT_SQ (RMS = sqrt(sq/n))
  F_GRAD_BAD     count of ranks whose chunk grads are non-finite
  F_ACT_ORIGIN   chunk input finite AND output non-finite (the forward
                 origin of a NaN — the provenance primary). The input
                 flag threads through the scan carry; the output flag
                 derives from the fp32 square-sum (NaN/inf propagate
                 through it), so health costs ONE extra pass per chunk
                 output, not three.
  F_GRAD_ORIGIN  explicit backward origin where a path records one
                 (reserved; currently 0 — the host rule recovers the
                 backward origin as the highest-index non-finite-grad
                 chunk, since NaN cotangents contaminate from the
                 break point toward layer 0)

Zero added reductions: on the mesh paths the stats block is emitted
as a PER-RANK PARTIAL ([1, rows, NFIELDS] with the reduction-axis
out_spec), so the mesh stacks — it never psums — and the host fold
sums rank partials at readback time. The grad sq-norms share the
clip's per-bucket shard reductions (the monitor reads the same
per-chunk terms the `ClipGradByGlobalNorm` carry folds, and computes
them only when clipping is off), so the compiled sharded step carries
exactly the collectives it carried before — the per-axis census in
tests/test_numerics.py is the receipt. The ONE exception is the
pipeline ring: the input-finiteness flag hops stages as a scalar
ppermute per ring tick riding beside the existing activation ppermute
(the flag cannot thread a same-device carry there — its producer is
the previous RANK); the census asserts those scalar permutes are the
pipeline's only delta.

Host side (`NumericsMonitor`):

- ``on_step(stats_dev)`` enqueues the device array — O(1), no sync.
- ``flush()`` (called by the lazy ``numerics.*`` gauges, `/numericsz`,
  and `summary()`) performs THE deferred readback, folds rank
  partials, derives per-chunk grad/param norms, update ratios
  ‖Δw‖/‖w‖ and activation RMS, and runs:
  * **NaN provenance** — a non-finite step is attributed to its first
    offending chunk (activation origin, else backward origin, else the
    earliest non-finite-grad chunk); the flight recorder gets a
    ``nan_provenance`` event plus a crash-style dump carrying the
    bounded ring of recent per-layer history, and
    ``numerics.first_bad_chunk`` points at the culprit.
  * **EWMA spike detection** — a per-chunk z-score on grad norms
    (warmup-gated) emits ``numerics_anomaly`` events and bumps the
    ``numerics.anomaly.count`` counter.
- per-step records go through a ``lane="numerics"`` `StepTimeline`, so
  grad norm / update ratio / act RMS render as chrome counter tracks
  in the profiler export.

Everything stays off the hot path: the per-step cost is one deque
append; all derivation happens at scrape time.
"""
from __future__ import annotations

import collections
import math
import threading
import weakref

import numpy as np

__all__ = [
    "NFIELDS", "F_GRAD_SQ", "F_PARAM_SQ", "F_UPD_SQ", "F_ACT_SQ",
    "F_ACT_N", "F_GRAD_BAD", "F_ACT_ORIGIN", "F_GRAD_ORIGIN",
    "NumericsMonitor", "assemble_stats", "outer_row",
    "monitor_enabled", "numericsz_payload", "chunk_of_layer",
]

(F_GRAD_SQ, F_PARAM_SQ, F_UPD_SQ, F_ACT_SQ, F_ACT_N, F_GRAD_BAD,
 F_ACT_ORIGIN, F_GRAD_ORIGIN) = range(8)
NFIELDS = 8


def monitor_enabled() -> bool:
    """Default-on policy (DECISIONS §21): the monitor rides every
    compiled step unless FLAGS_numerics_monitor=0 or the global
    telemetry kill-switch (PADDLE_TPU_TELEMETRY=0) is set."""
    from .sentinel import enabled

    if not enabled():
        return False
    try:
        from ..utils import flags as _flags

        return bool(_flags.get_flag("FLAGS_numerics_monitor"))
    except Exception:
        return True


def chunk_of_layer(layer, layer_chunk=1) -> int:
    """Logical layer index -> stats row (the chunk that owns it)."""
    return int(layer) // int(layer_chunk)


# ---------------------------------------------------------------------------
# traced assembly helpers (called inside the step programs)
# ---------------------------------------------------------------------------

def assemble_stats(grad_sq, param_sq, upd_sq, act_sq, act_n, grad_bad,
                   act_origin, grad_origin, outer=None):
    """Stack per-chunk [C] f32 columns (field order above) into the
    ``[C(+1), NFIELDS]`` stats block; ``outer`` is the optional
    trailing [NFIELDS] row for the non-scanned params."""
    import jax.numpy as jnp

    cols = [grad_sq, param_sq, upd_sq, act_sq, act_n, grad_bad,
            act_origin, grad_origin]
    C = None
    for c in cols:
        if c is not None and getattr(c, "ndim", 0) == 1:
            C = c.shape[0]
            break
    assert C is not None, "at least one per-chunk column is required"
    z = jnp.zeros((C,), jnp.float32)
    cols = [z if c is None else jnp.asarray(c, jnp.float32) for c in cols]
    block = jnp.stack(cols, axis=1)
    if outer is not None:
        block = jnp.concatenate(
            [block, jnp.asarray(outer, jnp.float32)[None, :]], axis=0)
    return block


def outer_row(grad_sq=0.0, param_sq=0.0, upd_sq=0.0, grad_bad=0.0,
              grad_origin=0.0):
    """The trailing ``outer`` row (embed/ln_f/head group): no scanned
    activation, so the act fields stay zero."""
    import jax.numpy as jnp

    f = jnp.float32
    return jnp.stack([f(grad_sq), f(param_sq), f(upd_sq), f(0.0),
                      f(0.0), f(grad_bad), f(0.0), f(grad_origin)])


# ---------------------------------------------------------------------------
# the host-side monitor
# ---------------------------------------------------------------------------

_monitors_lock = threading.Lock()
_monitors: list = []          # weakrefs, like sentinel's registry
_live_monitor_ref = None      # most recently stepped monitor
_gauges_registered = False


def _live_monitor():
    ref = _live_monitor_ref
    return ref() if ref is not None else None


def _register_gauges():
    """One-time global ``numerics.*`` lazy gauges over the most
    recently active monitor — evaluated only at scrape time, so the
    deferred readback happens exactly at the logging boundary."""
    global _gauges_registered
    if _gauges_registered:
        return
    _gauges_registered = True
    from .registry import registry

    reg = registry()

    def field(name):
        def get():
            m = _live_monitor()
            if m is None:
                return None
            return m.summary().get(name)

        return get

    reg.gauge("numerics.global_grad_norm").set_fn(field("grad_norm"))
    reg.gauge("numerics.update_ratio_max").set_fn(
        field("update_ratio_max"))
    reg.gauge("numerics.act_rms_max").set_fn(field("act_rms_max"))
    reg.gauge("numerics.finite_frac").set_fn(field("finite_frac"))
    reg.gauge("numerics.first_bad_chunk").set_fn(
        field("first_bad_chunk"))


class NumericsMonitor:
    """Deferred-readback consumer of one step path's stats blocks.

    Args:
      name: label (step class name) for events and `/numericsz`.
      rows: number of stats rows (layer chunks + the outer row).
      row_labels: optional per-row labels (chunk -> layer range, param
        names on the generic TrainStep path).
      ring: bounded per-layer history retention (steps).
      ewma_alpha / warmup / z_threshold: spike-detector knobs — the
        z-score of each chunk's grad norm against its EWMA mean/var,
        gated until ``warmup`` finite steps have been folded.
    """

    def __init__(self, name, rows, row_labels=None, ring=64,
                 ewma_alpha=0.1, warmup=10, z_threshold=8.0,
                 registry=None, timeline=None):
        self.name = name
        self.rows = int(rows)
        self.row_labels = (list(row_labels) if row_labels is not None
                           else [f"chunk{i}" for i in range(rows)])
        self._lock = threading.Lock()          # queue/counter state
        # serializes _ingest across threads. RLock, not Lock: a
        # provenance dump inside _ingest snapshots the registry, whose
        # lazy numerics gauges call summary() -> flush() on THIS
        # monitor — same-thread re-entry must drain the (now empty)
        # queue, not deadlock
        self._flush_lock = threading.RLock()
        self._pending = collections.deque(maxlen=max(int(ring), 8))
        self._ring = collections.deque(maxlen=int(ring))
        self._bad_steps = 0
        self._auto_step = 0
        self._steps_seen = 0
        self._latest = None
        self._clean = True
        self._provenance = None
        self._anomalies = collections.deque(maxlen=32)
        self._ewma_alpha = float(ewma_alpha)
        self._warmup = int(warmup)
        self._z_threshold = float(z_threshold)
        self._ewma_n = 0
        self._ewma_mean = np.zeros(self.rows)
        self._ewma_var = np.zeros(self.rows)
        from .registry import registry as _reg

        self._registry = registry if registry is not None else _reg()
        if timeline is None:
            from .timeline import StepTimeline

            timeline = StepTimeline(sinks=(), lane="numerics",
                                    registry=self._registry)
        self._timeline = timeline
        with _monitors_lock:
            _monitors.append(weakref.ref(self))

    # -- hot path --------------------------------------------------------
    def on_step(self, stats_dev, step=None):
        """Enqueue one step's device stats block. O(1) amortized;
        never reads the CURRENT array. When the pending queue fills
        (no scrape/log boundary for a whole ring depth), the OLDEST
        block is folded instead of dropped — it is ring-depth steps
        old, long computed, so its readback cannot stall the dispatch
        pipeline, and a transient bad step cannot silently age out of
        finite_frac / provenance."""
        global _live_monitor_ref
        with self._lock:
            if step is None:
                step = self._auto_step
            self._auto_step = int(step) + 1
            full = len(self._pending) >= (self._pending.maxlen or 0)
            if not full:
                self._pending.append((int(step), stats_dev))
        if full:
            with self._flush_lock:
                with self._lock:
                    old = (self._pending.popleft()
                           if len(self._pending)
                           >= (self._pending.maxlen or 0) else None)
                    self._pending.append((int(step), stats_dev))
                if old is not None:
                    try:
                        self._ingest(old[0], self._fold(old[1]))
                    except Exception:
                        pass
        _live_monitor_ref = weakref.ref(self)
        _register_gauges()

    # -- the deferred readback -------------------------------------------
    @staticmethod
    def _fold(stats_dev):
        """Device block -> host [rows, NFIELDS]: rank partials (a
        leading stacking axis from the mesh out_spec) sum away."""
        arr = np.asarray(stats_dev, dtype=np.float64)
        while arr.ndim > 2:
            arr = arr.sum(axis=0)
        return arr

    def flush(self):
        """Fold every pending block (ONE readback boundary) and run
        derivation + provenance + spike detection. Returns the latest
        summary (None if nothing has ever been folded). Serialized:
        the training thread, a debug-server scrape and a gauge read
        may all flush concurrently — _ingest's ring/EWMA folds must
        not interleave."""
        with self._flush_lock:
            with self._lock:
                pending = list(self._pending)
                self._pending.clear()
            for step, dev in pending:
                try:
                    rows = self._fold(dev)
                except Exception:
                    continue
                self._ingest(step, rows)
            return self._latest

    def _derive(self, rows):
        out = []
        for i in range(rows.shape[0]):
            r = rows[i]
            grad_norm = math.sqrt(max(float(r[F_GRAD_SQ]), 0.0)) \
                if np.isfinite(r[F_GRAD_SQ]) else float("inf")
            param_norm = math.sqrt(max(float(r[F_PARAM_SQ]), 0.0)) \
                if np.isfinite(r[F_PARAM_SQ]) else float("inf")
            upd = math.sqrt(max(float(r[F_UPD_SQ]), 0.0)) \
                if np.isfinite(r[F_UPD_SQ]) else float("inf")
            ratio = (upd / param_norm) if param_norm > 0 else 0.0
            act_n = float(r[F_ACT_N])
            act_rms = (math.sqrt(max(float(r[F_ACT_SQ]), 0.0) / act_n)
                       if act_n > 0 and np.isfinite(r[F_ACT_SQ])
                       else None)
            out.append({
                "row": i,
                "label": (self.row_labels[i]
                          if i < len(self.row_labels) else f"row{i}"),
                "grad_norm": grad_norm,
                "param_norm": param_norm,
                "update_ratio": ratio,
                "act_rms": act_rms,
                "grad_finite": bool(float(r[F_GRAD_BAD]) == 0.0
                                    and np.isfinite(r[F_GRAD_SQ])),
                "act_origin": bool(float(r[F_ACT_ORIGIN]) > 0.0),
                "grad_origin": bool(float(r[F_GRAD_ORIGIN]) > 0.0),
            })
        return out

    @staticmethod
    def _first_bad(rows, derived):
        """Provenance rule: the FORWARD origin (input finite, output
        not) wins — earliest such chunk; else the explicit backward
        origin where a path recorded one; else the HIGHEST-index chunk
        with non-finite grads — the backward scan contaminates from
        the break point DOWN (NaN cotangents flow toward layer 0), so
        the bad chunk closest to the loss is where it started."""
        act = [d["row"] for d in derived if d["act_origin"]]
        if act:
            return min(act), "activation"
        grad = [d["row"] for d in derived if d["grad_origin"]]
        if grad:
            return max(grad), "grad"
        bad = [d["row"] for d in derived if not d["grad_finite"]]
        if bad:
            return max(bad), "grad_nonfinite"
        return None, None

    def _ingest(self, step, rows):
        derived = self._derive(rows)
        finite = bool(np.isfinite(rows).all()) and all(
            d["grad_finite"] for d in derived)
        self._steps_seen += 1
        if not finite:
            self._bad_steps += 1
        gn = math.sqrt(max(float(rows[:, F_GRAD_SQ].sum()), 0.0)) \
            if np.isfinite(rows[:, F_GRAD_SQ]).all() else float("inf")
        entry = {"step": step, "finite": finite,
                 "grad_norm": gn, "rows": derived}
        self._ring.append(entry)
        first_bad = None
        if not finite:
            first_bad, origin = self._first_bad(rows, derived)
            # "origin", not "kind": the flight-recorder event's own
            # kind field is "nan_provenance"
            prov = {"step": step, "first_bad_chunk": first_bad,
                    "origin": origin,
                    "label": (self.row_labels[first_bad]
                              if first_bad is not None
                              and first_bad < len(self.row_labels)
                              else None),
                    "monitor": self.name}
            self._provenance = prov
            if self._clean:
                # one dump per clean->bad transition, not per bad step
                self._clean = False
                try:
                    from .flight_recorder import recorder

                    rec = recorder()
                    rec.note("nan_provenance", **prov)
                    rec.dump(reason=(
                        f"nan_provenance: {self.name} step {step} "
                        f"first bad chunk {first_bad} ({origin})"))
                except Exception:
                    pass
        else:
            self._clean = True
            self._spike_check(step, derived)
        ratios = [d["update_ratio"] for d in derived]
        rmss = [d["act_rms"] for d in derived
                if d["act_rms"] is not None]
        self._latest = {
            "step": step, "finite": finite, "grad_norm": gn,
            "update_ratio_max": max(ratios) if ratios else None,
            "act_rms_max": max(rmss) if rmss else None,
            # CUMULATIVE, not windowed: bench_compare's absolute gate
            # ("a run that produced even one non-finite step is
            # broken") must see a bad step from ANY point in the run
            # — a ring-windowed fraction would age it out after
            # `ring` clean steps
            "finite_frac": ((self._steps_seen - self._bad_steps)
                            / self._steps_seen
                            if self._steps_seen else None),
            "first_bad_chunk": (-1 if finite else
                                (-1 if first_bad is None
                                 else first_bad)),
            "steps_seen": self._steps_seen,
        }
        try:
            self._timeline.record(
                step=step,
                grad_norm=(gn if math.isfinite(gn) else -1.0),
                update_ratio_max=(self._latest["update_ratio_max"]
                                  or 0.0),
                act_rms_max=(self._latest["act_rms_max"] or 0.0),
                finite=1 if finite else 0)
        except Exception:
            pass

    # -- EWMA spike detector ---------------------------------------------
    def _spike_check(self, step, derived):
        x = np.asarray([d["grad_norm"] for d in derived])
        if self._ewma_n >= self._warmup:
            std = np.sqrt(np.maximum(self._ewma_var, 0.0)) \
                + 1e-12 + 1e-3 * np.abs(self._ewma_mean)
            z = (x - self._ewma_mean) / std
            for i in np.nonzero(z > self._z_threshold)[0]:
                ev = {"step": step, "chunk": int(i),
                      "label": (self.row_labels[i]
                                if i < len(self.row_labels)
                                else f"row{i}"),
                      "grad_norm": float(x[i]),
                      "ewma_mean": float(self._ewma_mean[i]),
                      "z": float(z[i]), "monitor": self.name}
                self._anomalies.append(ev)
                self._registry.counter("numerics.anomaly.count").inc()
                try:
                    from .flight_recorder import recorder

                    recorder().note("numerics_anomaly", **ev)
                except Exception:
                    pass
        a = self._ewma_alpha
        if self._ewma_n == 0:
            self._ewma_mean = x.astype(np.float64)
            self._ewma_var = np.zeros_like(self._ewma_mean)
        else:
            d = x - self._ewma_mean
            self._ewma_mean = self._ewma_mean + a * d
            self._ewma_var = (1 - a) * (self._ewma_var + a * d * d)
        self._ewma_n += 1

    # -- read surface ----------------------------------------------------
    def summary(self):
        """Flush + the latest global summary ({} before any step)."""
        return self.flush() or {}

    def latest_rows(self):
        """Flush + the latest per-chunk table ([] before any step)."""
        self.flush()
        return list(self._ring[-1]["rows"]) if self._ring else []

    def history(self):
        """The bounded ring of recent per-step entries (flushed)."""
        self.flush()
        return list(self._ring)

    def provenance(self):
        """The most recent NaN-provenance record (None when clean)."""
        self.flush()
        return self._provenance

    def anomalies(self):
        self.flush()
        return list(self._anomalies)

    def payload(self):
        """JSON-able `/numericsz` block for this monitor."""
        s = self.summary()
        return {"name": self.name, "rows": self.rows,
                "summary": s, "per_chunk": self.latest_rows(),
                "provenance": self._provenance,
                "anomalies": list(self._anomalies),
                "ring_depth": len(self._ring)}


def numericsz_payload() -> dict:
    """`/numericsz` debug-server endpoint: every live monitor's latest
    per-chunk health table + provenance + anomaly ring."""
    out = []
    with _monitors_lock:
        refs = list(_monitors)
    for ref in refs:
        m = ref()
        if m is None:
            continue
        try:
            out.append(m.payload())
        except Exception as e:
            out.append({"error": f"{type(e).__name__}: {e}"[:200]})
    return {"monitors": out}
