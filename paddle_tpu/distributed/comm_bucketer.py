"""Bucketed gradient collectives: coalesce per-parameter grads into
size-capped flat buckets and issue ONE collective per bucket.

Reference parity: the EagerReducer's bucketed all-reduce
(paddle/fluid/distributed/collective/reducer.cc:484 — group_size-capped
gradient groups, deterministic var→group assignment, fused flat buffers)
and the sharding-V2 fused reduce-scatter buffers
(dygraph_sharding_optimizer V2 :571).

TPU-first, two modes:

- **pin** (GSPMD, stage-2 "os_g"): the flat bucket gets a sharded layout
  constraint over the sharding axis; the XLA partitioner then materializes
  the whole bucket through ONE reduce-scatter instead of one collective per
  parameter ("Automatic Cross-Replica Sharding of Weight Update",
  PAPERS.md). Because the bucket is flat and padded to the axis degree,
  parameters with no degree-divisible dim — which the per-parameter
  constraint path must leave replicated — shard too.
- **explicit** (`bucketed_all_reduce` / `bucketed_reduce_scatter`): for
  grads produced per-rank outside GSPMD's reach (``_is_partial_grad``
  producers, reference fused_allreduce_gradients), one eager/traced
  collective per bucket, optionally with compressed payloads
  (FLAGS_comm_quant → collective.all_reduce_quantized).

The param→bucket assignment is deterministic (parameter order, one dtype
per bucket, FLAGS_comm_bucket_mb cap) and recorded as `BucketAssignment`
so the optimizer's scatter-back — and tests — can address each grad slice
by (bucket, offset, numel).
"""
from __future__ import annotations

import re
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework.tensor import Tensor
from ..utils import flags as _flags
from . import env

MB = 1 << 20


class BucketEntry(NamedTuple):
    key: str          # parameter name (or index for anonymous tensors)
    offset: int       # flat offset inside the bucket
    numel: int
    shape: tuple


class Bucket(NamedTuple):
    index: int
    dtype: object         # jnp dtype shared by every entry
    entries: tuple        # tuple[BucketEntry]
    numel: int            # padded flat length (multiple of pad_multiple)

    @property
    def keys(self):
        return [e.key for e in self.entries]

    @property
    def nbytes(self):
        return self.numel * jnp.dtype(self.dtype).itemsize


class BucketAssignment(NamedTuple):
    buckets: tuple        # tuple[Bucket]
    bucket_bytes: int
    pad_multiple: int

    def bucket_of(self, key):
        for b in self.buckets:
            for e in b.entries:
                if e.key == key:
                    return b, e
        raise KeyError(key)

    def describe(self):
        return [{"bucket": b.index, "dtype": str(jnp.dtype(b.dtype)),
                 "numel": b.numel, "bytes": b.nbytes, "params": b.keys}
                for b in self.buckets]


def default_bucket_bytes():
    return int(_flags.get_flag("FLAGS_comm_bucket_mb") or 0) * MB


def build_buckets(named_shapes, bucket_bytes=None, pad_multiple=1):
    """Deterministic greedy packing: iterate (key, shape, dtype) in the
    given order, open a new bucket when the dtype changes or the size cap
    would be exceeded (a single oversized param still gets its own
    bucket). Each bucket's flat length is padded up to `pad_multiple` so a
    reduce_scatter over the group axis tiles evenly."""
    if bucket_bytes is None:
        bucket_bytes = default_bucket_bytes()
    bucket_bytes = max(int(bucket_bytes), 1)
    pad_multiple = max(int(pad_multiple), 1)
    buckets = []
    cur_entries, cur_dtype, cur_numel = [], None, 0

    def close():
        nonlocal cur_entries, cur_dtype, cur_numel
        if not cur_entries:
            return
        padded = -(-cur_numel // pad_multiple) * pad_multiple
        buckets.append(Bucket(len(buckets), cur_dtype,
                              tuple(cur_entries), padded))
        cur_entries, cur_dtype, cur_numel = [], None, 0

    for key, shape, dtype in named_shapes:
        dtype = jnp.dtype(dtype)
        numel = int(np.prod(shape)) if len(shape) else 1
        nbytes = numel * dtype.itemsize
        if cur_entries and (dtype != cur_dtype
                            or (cur_numel * cur_dtype.itemsize + nbytes
                                > bucket_bytes)):
            close()
        cur_dtype = dtype
        cur_entries.append(BucketEntry(key, cur_numel, numel, tuple(shape)))
        cur_numel += numel
    close()
    return BucketAssignment(tuple(buckets), bucket_bytes, pad_multiple)


def _flatten_bucket(bucket, grad_for_key):
    """Concat the bucket's grads (raveled, cast to the bucket dtype) into
    one flat array, padded with zeros to the bucket's padded length."""
    parts = []
    for e in bucket.entries:
        g = grad_for_key(e.key)
        if g is None:
            parts.append(jnp.zeros((e.numel,), bucket.dtype))
        else:
            parts.append(g.reshape(-1).astype(bucket.dtype))
    pad = bucket.numel - sum(e.numel for e in bucket.entries)
    if pad:
        parts.append(jnp.zeros((pad,), bucket.dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _scatter_back(bucket, flat, write_for_key):
    """The recorded-assignment scatter-back: hand each entry its slice."""
    for e in bucket.entries:
        write_for_key(e.key, flat[e.offset:e.offset + e.numel]
                      .reshape(e.shape))


class GradBucketer:
    """Stage-2 grad-comm planner over a model's trainable parameters.

    Backward hooks only *mark* params pending under trace; the comm
    boundary (`sync_pending`, reached from the model wrapper's
    apply_collective_grads — called by TrainStep after the LAST microbatch
    backward — or from the sharding optimizer's step) flattens each dirty
    bucket, pins it sharded over the axis (GSPMD → one reduce-scatter per
    bucket), and scatters the slices back into the param grads. With
    gradient accumulation the k microbatch backwards therefore run
    collective-free and the per-bucket collectives issue once, where XLA
    can overlap them with the optimizer/next-step compute.
    """

    def __init__(self, named_params, mesh=None, axis=None, bucket_mb=None):
        self._params = dict(named_params)           # key -> Parameter
        self._mesh = mesh if mesh is not None else env.get_mesh()
        self._axis = axis or self._mesh.axis_names[0]
        degree = int(self._mesh.shape[self._axis])
        bucket_bytes = (None if bucket_mb is None else int(bucket_mb) * MB)
        self.assignment = build_buckets(
            [(k, tuple(p.shape), p._data.dtype)
             for k, p in self._params.items()],
            bucket_bytes=bucket_bytes, pad_multiple=max(degree, 1))
        self._pending = set()

    @property
    def num_buckets(self):
        return len(self.assignment.buckets)

    def mark_pending(self, key):
        self._pending.add(key)

    def has_pending(self):
        return bool(self._pending)

    def sync_pending(self):
        """Issue the deferred bucket collectives; returns #buckets issued.
        Idempotent per backward: pending marks are consumed, so the
        TrainStep boundary call and a sharding optimizer's step()-time
        call cannot double-sync."""
        if not self._pending:
            return 0
        sharding = NamedSharding(self._mesh, P(self._axis))
        issued = 0
        issued_bytes = 0
        for bucket in self.assignment.buckets:
            if not any(k in self._pending for k in bucket.keys):
                continue
            issued_bytes += bucket.nbytes
            flat = _flatten_bucket(
                bucket, lambda k: (self._params[k].grad._data
                                   if self._params[k].grad is not None
                                   else None))
            # the single constraint that replaces one-per-param: GSPMD
            # materializes the bucket's summed grads via ONE
            # reduce-scatter over the sharding axis
            flat = env.pin_sharding(flat, sharding)
            issued += 1

            def write(key, slc):
                p = self._params[key]
                if p.grad is None:
                    # param took no grad this backward (unused/frozen):
                    # its zero filler must NOT materialize as a real
                    # grad — that would make the optimizer decay it
                    return
                p.grad._data = slc.astype(p.grad._data.dtype)

            _scatter_back(bucket, flat, write)
        self._pending.clear()
        # unified telemetry (ISSUE 12): payload bytes per collective
        # leg. Under trace this runs ONCE (the collectives are baked
        # into the compiled step), so the per-step budget is published
        # as a gauge rather than a counter
        try:
            from ..observability import registry as _obs

            reg = _obs()
            reg.counter("comm.bucket_syncs").inc(issued)
            reg.counter("comm.bucket_sync_bytes").inc(issued_bytes)
            reg.gauge("comm.bucket_bytes_per_step").set(issued_bytes)
        except Exception:
            pass
        return issued


# ---------------------------------------------------------------------------
# explicit bucketed collectives (eager or traced)
# ---------------------------------------------------------------------------

def _as_tensors(tensors):
    return [t if isinstance(t, Tensor) else Tensor(t) for t in tensors]


def bucketed_all_reduce(tensors, group=None, bucket_mb=None, quant=None):
    """Sum a list of tensors across the group IN PLACE with one all_reduce
    per size-capped flat bucket (vs one per tensor). `quant` defaults to
    FLAGS_comm_quant: 'int8'/'bf16' route each bucket through the
    compressed collective path."""
    from . import collective as coll

    group = group or coll._world_group()
    ts = _as_tensors(tensors)
    if not ts:
        return tensors
    if quant is None:
        quant = _flags.get_flag("FLAGS_comm_quant") or ""
    assignment = build_buckets(
        [(i, tuple(t.shape), t._data.dtype) for i, t in enumerate(ts)],
        bucket_bytes=None if bucket_mb is None else int(bucket_mb) * MB)
    for bucket in assignment.buckets:
        flat = Tensor._wrap(_flatten_bucket(
            bucket, lambda i: ts[i]._data))
        if quant:
            coll.all_reduce_quantized(flat, group=group, qformat=quant)
        else:
            coll.all_reduce(flat, group=group)
        _scatter_back(bucket, flat._data,
                      lambda i, slc: setattr(
                          ts[i], "_data", slc.astype(ts[i]._data.dtype)))
    # Tensor inputs were reduced in place (ts[i] IS tensors[i]); raw
    # arrays can't be — return the reduced wrappers so no caller ever
    # silently gets un-summed values back
    return ts


def bucketed_reduce_scatter(tensors, group=None, bucket_mb=None):
    """Sum-and-scatter a list of tensors IN PLACE with one reduce_scatter
    per flat bucket. Global-view semantics match collective.reduce_scatter:
    each result keeps its global shape, laid out sharded over the group
    axis along the flat bucket dim — values are bit-identical to the
    per-tensor reduce_scatter (same psum-scatter reduction tree)."""
    from . import collective as coll

    group = group or coll._world_group()
    ts = _as_tensors(tensors)
    if not ts:
        return tensors
    assignment = build_buckets(
        [(i, tuple(t.shape), t._data.dtype) for i, t in enumerate(ts)],
        bucket_bytes=None if bucket_mb is None else int(bucket_mb) * MB,
        pad_multiple=group.nranks)
    for bucket in assignment.buckets:
        flat = Tensor._wrap(_flatten_bucket(bucket,
                                            lambda i: ts[i]._data))
        out = coll.reduce_scatter(None, flat, group=group, axis=0)
        _scatter_back(bucket, out._data,
                      lambda i, slc: setattr(
                          ts[i], "_data", slc.astype(ts[i]._data.dtype)))
    # see bucketed_all_reduce: in place for Tensor inputs, and the
    # returned wrappers carry the result for raw-array inputs
    return ts


# ---------------------------------------------------------------------------
# HLO collective-count probe (tests/test_comm_bucketed.py)
# ---------------------------------------------------------------------------

_COLLECTIVE_RE = {
    "reduce_scatter": re.compile(r"\breduce-scatter(?:-start)?\("),
    "all_reduce": re.compile(r"\ball-reduce(?:-start)?\("),
    "all_gather": re.compile(r"\ball-gather(?:-start)?\("),
    "all_to_all": re.compile(r"\ball-to-all(?:-start)?\("),
    "collective_permute": re.compile(r"\bcollective-permute(?:-start)?\("),
}


def count_hlo_collectives(fn, *args):
    """Compile `fn(*args)` and count collective ops in the optimized HLO —
    the op-count probe the acceptance criteria name (one number per
    collective kind, post-XLA-combiner, i.e. what actually hits the
    interconnect)."""
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return {name: len(rx.findall(txt))
            for name, rx in _COLLECTIVE_RE.items()}
