"""Device-side input prefetching: double-buffered H2D/compute overlap.

The DataLoader stack stops at host batch assembly — without this layer
every step pays a synchronous host→device transfer while the chip idles
(the input-stall gap the reference's DataLoader/buffer-reader stack exists
to close, python/paddle/io/ + fluid's buffered_reader.cc). `DevicePrefetcher`
wraps any `DataLoader`/iterable and keeps a depth-K ring of batches staged
ON DEVICE ahead of the consumer:

- a background thread pulls assembled host batches and stages them via
  sharding-aware `jax.device_put` — on a dp/sharding mesh each device
  receives only its 1/N shard of the batch, placed directly on the step's
  input sharding (so the compiled step never reshards, and no device ever
  sees the full global batch);
- the ring is donation-safe by construction: every stage allocates FRESH
  device buffers (`device_put` never aliases the producer's host memory,
  asserted by tests that mutate a reused host buffer), and a slot is only
  released when the consumer takes the batch — a buffer can never be
  rewritten while an in-flight step may still read it;
- placement is identical for every batch of a stream, so feeding a jitted
  train step adds ZERO retraces (compile-count probe in
  tests/test_input_pipeline.py).

Instrumented end to end: per-step `input_stall_ms` (how long `next()`
blocked waiting for data — ≈0 when the pipeline keeps up) and `h2d_ms`
(host→device transfer time on the producer thread), exposed via
`get_stats()` and as profiler `RecordEvent` spans
("paddle_tpu.input.h2d" / "paddle_tpu.input.wait").

Usage::

    loader = io.DataLoader(ds, batch_size=32, num_workers=4)
    for ids, labels in io.DevicePrefetcher(loader, depth=2):
        loss = step(ids, labels)         # input delivery fully overlapped
    # or bound to a step's input sharding in one call:
    for ids, labels in step.prefetch(loader):
        loss = step(ids, labels)
"""
from __future__ import annotations

import queue
import threading
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..framework.tensor import Tensor
from ..observability import registry as _obs_registry
from ..profiler import RecordEvent

__all__ = ["DevicePrefetcher"]

_SENTINEL = object()


def _tree_map(fn, obj):
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_map(fn, o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_map(fn, v) for k, v in obj.items()}
    return fn(obj)


def _tree_leaves(obj, out=None):
    if out is None:
        out = []
    if isinstance(obj, (list, tuple)):
        for o in obj:
            _tree_leaves(o, out)
    elif isinstance(obj, dict):
        for v in obj.values():
            _tree_leaves(v, out)
    else:
        out.append(obj)
    return out


class _Epoch:
    """One epoch's producer thread + bounded device-side ring."""

    def __init__(self, prefetcher):
        self._pf = prefetcher
        self._q = queue.Queue(maxsize=prefetcher.depth)
        self._stop = threading.Event()
        self._err = None
        self._thread = threading.Thread(
            target=self._produce, name="DevicePrefetcher", daemon=True)
        self._thread.start()

    def _produce(self):
        pf = self._pf
        try:
            for batch in pf._host_batches():
                if self._stop.is_set():
                    return
                t0 = time.perf_counter()
                with RecordEvent("paddle_tpu.input.h2d"):
                    staged = _tree_map(pf._stage_leaf, batch)
                    # block here (on the PRODUCER thread, never the step
                    # loop) so h2d_ms is the true transfer time and the
                    # ring holds at most `depth` fully-resident batches
                    for leaf in _tree_leaves(staged):
                        if isinstance(leaf, jax.Array):
                            leaf.block_until_ready()
                pf._note_h2d((time.perf_counter() - t0) * 1e3)
                while not self._stop.is_set():
                    try:
                        self._q.put(staged, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                else:
                    return
        except Exception as e:  # surfaced on the consumer at next()
            self._err = e
        finally:
            while not self._stop.is_set():
                try:
                    self._q.put(_SENTINEL, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def close(self):
        self._stop.set()
        while True:  # unblock a producer waiting on a full ring
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10)


class DevicePrefetcher:
    """Stage host batches onto device(s) ahead of the consumer.

    Args:
      loader: a `DataLoader` or any (re-)iterable of batches. Batches may
        be (nested) Tensors / numpy arrays / jax arrays; non-array leaves
        pass through untouched.
      depth: ring depth K — how many batches may be resident on device
        ahead of the consumer (2 = classic double buffering).
      sharding: target placement for every array leaf — a
        `jax.sharding.Sharding` (a `PartitionSpec` longer than a leaf's
        rank is trimmed; scalars replicate), a `jax.Device`, or a callable
        ``leaf -> sharding``. Default: the plain default-device
        `device_put` (same placement `paddle.to_tensor` produces, so a
        warmed-up jitted step sees identical input layouts).
      mesh/axis: convenience — equivalent to
        ``sharding=NamedSharding(mesh, P(axis))`` (dim 0 split over the
        dp axis, rest replicated). `axis` defaults to the first of
        sharding/dp/data with degree > 1.
      to_tensor: wrap staged jax arrays into Tensors on delivery.
      process_local: multi-process SPMD — the loader yields only this
        process's 1/N batch shard (a `DistributedBatchSampler` loader) and
        leaves are assembled into the global sharded array without any
        cross-host transfer.
    """

    def __init__(self, loader, depth=2, sharding=None, mesh=None,
                 axis=None, device=None, to_tensor=True,
                 process_local=False, stats_window=4096):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if sharding is None and mesh is not None:
            from ..distributed import env as denv

            sharding = denv.data_sharding(mesh=mesh, axis=axis)
        if sharding is None and device is not None:
            sharding = device
        self._loader = loader
        self.depth = int(depth)
        self._sharding = sharding
        self._to_tensor = to_tensor
        self._process_local = process_local
        self._stats_window = int(stats_window)
        self._epoch = None
        self._lock = threading.Lock()
        self.reset_stats()
        # live-buffer attribution (ISSUE 14): staged ring batches claim
        # their device bytes at mem.live scrape time (weakly tracked)
        from ..observability.memory import live_registry

        live_registry().track(self)

    def _mem_owners(self):
        """observability.memory provider: the device arrays currently
        staged in the ring (a snapshot of the queue — scrape-time only,
        never on the hot path)."""
        ep = self._epoch
        if ep is None:
            return {"prefetch_ring": []}
        try:
            with ep._q.mutex:
                staged = list(ep._q.queue)
        except Exception:
            staged = []
        return {"prefetch_ring": [b for b in staged
                                  if b is not _SENTINEL]}

    # -- staging ---------------------------------------------------------
    @staticmethod
    def _cpu_backend(target):
        if target is None:
            return jax.default_backend() == "cpu"
        if isinstance(target, jax.Device):
            return target.platform == "cpu"
        devs = getattr(target, "device_set", None)
        if devs:
            return next(iter(devs)).platform == "cpu"
        return jax.default_backend() == "cpu"

    def _placement_for(self, leaf):
        sh = self._sharding
        if callable(sh) and not isinstance(sh, (jax.sharding.Sharding,
                                                jax.Device)):
            return sh(leaf)
        if isinstance(sh, NamedSharding):
            nd = getattr(leaf, "ndim", 0)
            spec = list(sh.spec)
            while len(spec) > nd or (spec and spec[-1] is None):
                spec.pop()           # trim to rank; normalize trailing None
            if tuple(spec) != tuple(sh.spec):
                return NamedSharding(sh.mesh, PartitionSpec(*spec))
        return sh

    def _stage_leaf(self, leaf):
        if isinstance(leaf, Tensor):
            leaf = leaf._data
        if not isinstance(leaf, (np.ndarray, np.generic, jax.Array)):
            return leaf              # python scalars / strings / None
        target = self._placement_for(leaf)
        if isinstance(leaf, np.ndarray) and self._cpu_backend(target):
            # CPU-backend device_put ZERO-COPIES an aligned numpy buffer —
            # a loader that reuses its host buffer would then rewrite a
            # staged (possibly in-flight) batch. Donation safety demands
            # every stage own fresh memory; on accelerators the H2D
            # transfer itself is that copy.
            leaf = np.array(leaf, copy=True)
        if target is None:
            return jax.device_put(leaf)
        if self._process_local and jax.process_count() > 1:
            make = getattr(jax, "make_array_from_process_local_data", None)
            if make is None:
                raise RuntimeError(
                    "process_local staging needs "
                    "jax.make_array_from_process_local_data; this jax "
                    "predates it — shard with device_put on a "
                    "single-controller mesh instead")
            return make(target, np.asarray(leaf))
        return jax.device_put(leaf, target)

    def _host_batches(self):
        loader = self._loader
        from . import DataLoader, numpy_collate_fn

        if isinstance(loader, DataLoader) \
                and not getattr(loader, "_user_collate", True):
            # default collate builds device Tensors INSIDE the loader —
            # that is the synchronous transfer this layer exists to hide.
            # Iterate a shallow clone collating to numpy so the only H2D
            # is the staged, overlapped one (the clone shares dataset +
            # sampler; only the collate differs).
            import copy

            clone = copy.copy(loader)
            clone.collate_fn = numpy_collate_fn
            clone._user_collate = True
            return iter(clone)
        return iter(loader)

    # -- stats -----------------------------------------------------------
    def _note_h2d(self, ms):
        with self._lock:
            self._h2d_ms.append(ms)
            if len(self._h2d_ms) > self._stats_window:
                del self._h2d_ms[: -self._stats_window]
            self._h2d_total += ms
            self._h2d_count += 1
        # unified telemetry (ISSUE 12): the same sample lands in the
        # process-global registry so scrapes/timelines see input health
        _obs_registry().histogram("input.h2d_ms").observe(ms)

    def _note_stall(self, ms):
        with self._lock:
            self._stall_ms.append(ms)
            if len(self._stall_ms) > self._stats_window:
                del self._stall_ms[: -self._stats_window]
            self._stall_total += ms
            self._stall_count += 1
        _obs_registry().histogram("input.stall_ms").observe(ms)

    def reset_stats(self):
        with self._lock:
            self._stall_ms = []
            self._h2d_ms = []
            self._stall_total = 0.0
            self._h2d_total = 0.0
            self._stall_count = 0
            self._h2d_count = 0

    def get_stats(self):
        """Per-step input_stall_ms / h2d_ms (last `stats_window` steps)
        plus aggregates. input_stall_ms is the time `next()` blocked on
        data — ≈0 means the device never waited on the host."""
        with self._lock:
            def agg(samples, total, count):
                return {
                    "total": round(total, 3),
                    "mean": round(total / count, 4) if count else None,
                    "max": round(max(samples), 3) if samples else None,
                    "count": count,
                }

            return {
                "depth": self.depth,
                "batches": self._stall_count,
                "input_stall_ms": agg(self._stall_ms, self._stall_total,
                                      self._stall_count),
                "h2d_ms": agg(self._h2d_ms, self._h2d_total,
                              self._h2d_count),
                "per_step_input_stall_ms": [round(v, 4)
                                            for v in self._stall_ms],
                "per_step_h2d_ms": [round(v, 4) for v in self._h2d_ms],
            }

    # -- iteration -------------------------------------------------------
    def __len__(self):
        return len(self._loader)

    def __iter__(self):
        # a fresh epoch when none is live; mid-epoch iter() continues the
        # current stream (so `next(pf)` + `for b in pf` compose). close()
        # abandons a live epoch explicitly.
        if self._epoch is None:
            self._epoch = _Epoch(self)
        return self

    def __next__(self):
        # the span is the whole call (the layer's boundary); the stall
        # counter inside it is the wait on the ring alone
        with RecordEvent("paddle_tpu.input.wait"):
            return self._next()

    def _next(self):
        ep = self._epoch
        if ep is None:
            raise StopIteration
        t0 = time.perf_counter()
        item = ep._q.get()
        if item is _SENTINEL:
            self._epoch = None
            ep._thread.join(timeout=10)
            if ep._err is not None:
                raise ep._err
            raise StopIteration
        self._note_stall((time.perf_counter() - t0) * 1e3)
        if self._to_tensor:
            return _tree_map(
                lambda l: Tensor._wrap(l)
                if isinstance(l, jax.Array) else l, item)
        return item

    def close(self):
        """Stop the producer and release the ring (idempotent; also runs
        at GC). Safe mid-epoch — a producer blocked on the full ring
        unblocks and joins. A producer blocked inside the wrapped
        loader's own `next()` cannot be interrupted from outside: the
        join times out (10s) and the daemon thread exits on its own when
        the pull returns and sees the stop flag."""
        ep, self._epoch = self._epoch, None
        if ep is not None:
            ep.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
