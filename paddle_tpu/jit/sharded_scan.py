"""Sharded fused-scan train step: weight-update sharding INSIDE the scan,
and (ISSUE 11) sharded PARAMETER STORAGE with gather-on-use.

`FusedScanTrainStep` made the 1.3b north star fit one chip by fusing the
Adam update into a manual per-layer reverse scan. This module is its
multi-chip form, per Xu et al., "Automatic Cross-Replica Sharding of
Weight Update in Data-Parallel Training" (PAPERS.md): gradients, moments,
masters and the update computation are 1/N-sharded per rank — and, with
``param_storage="sharded"`` (the default since ISSUE 11), the weights
THEMSELVES live as 1/N flat bucket shards between steps, all-gathered on
use inside the forward scan (double-buffered prefetch), re-gathered by
the backward recompute, and written back as shards by the update scan —
no full replicated parameter pytree exists at any point between steps
(ZeRO-3-style storage on the same ``__scan_shard_*__`` flat layout the
optimizer state uses). ``param_storage="replicated"`` restores the
original layout (the bit-parity reference). The replicated-mode
structure —

  backward scan (reverse, per chunk of K layers):
      dp      = vjp(block chunk)(dy)                 (full, dies here)
      flat    = bucket-pack(dp)   [K, F]             (comm_bucketer layout)
      gshard  = reduce_scatter(flat) over the axis   [K, F/N]  <- survives
      sq     += ||gshard/N||^2                       (in the scan carry)
  one scalar all-reduce:  gnorm = sqrt(psum(sq));  clip = min(c/gnorm, 1)
  update scan (per chunk):
      adam on the 1/N shard (clip applied, moments/masters sharded)
      all_gather(updated shard) -> write the chunk's param slices
  outer params (embed/ln_f/head): same, without the scan.

Because only the 1/N grad shard outlives a scan iteration, the whole
gradient set per rank is full_grads/N — which is what makes the fused
GLOBAL-NORM CLIP affordable here (the single-device step needs a second
backward pass for it, docs/DECISIONS.md §12) and keeps grad memory off
the per-layer OOM cliff. The per-bucket reduce-scatter reuses the
comm_bucketer packing (deterministic entry offsets, FLAGS_comm_bucket_mb
cap, padding to the axis degree) and optionally the EQuARX-style
compressed wire format (FLAGS_comm_quant -> int8/bf16 scatter leg,
collective.quantized_psum_scatter_traced). Inside one scan iteration the
reduce-scatter of bucket b is independent of bucket b+1's packing and of
the norm accumulation, and the update scan's all_gather of bucket b is
independent of bucket b+1's Adam math — with scan_unroll >= 2 adjacent
layers' collectives and compute land in ONE while-loop body where XLA's
latency-hiding scheduler can overlap them (tools/hlo_overlap.py is the
receipt; the multichip lane records its verdict).

Dropout rides the carry-free per-layer PRNG offset scheme of the base
class, with the dp-axis rank folded in so each rank draws distinct masks
for its own batch rows.

Semantics note: the per-rank loss is the criterion's mean over the
rank's batch shard and the returned loss is their mean — equal to the
full-batch mean when every rank holds the same number of unmasked
tokens (the standard data-parallel contract; ragged -100 masks make it
a weighted mean, same as the reference DataParallel).

Round 12 (ISSUE 8) generalized the whole machinery from ONE mesh axis
to an axis tuple: grads scatter and params gather over the flattened
(dp, mp[, pp]) product (first axis major — `_flat_rank` mirrors the
tuple-collective split order), optimizer shards are 1/(dp·mp·pp), and
the per-rank loss/grad carry a uniform ×(mp·pp) joint-vjp replication
factor (every mp/pp rank computes the identical loss) that the 1/N
normalization divides back out. On top of that ride:

* dp×mp Megatron tensor parallelism (`mp_axis=`): `_setup_mp` compiles
  the spmd_rules role table into per-leaf slicers (head-interleaved
  qkv / column fc1 / row out_proj+fc2 with bias/mp) bound into the
  SAME block template at trace time, one psum per row-parallel
  projection, and the vocab-parallel sharded fused CE as `_head_fn` —
  each rank's grads cover its slice (zero-padded), so the axis-tuple
  scatter IS the tensor-parallel gradient assembly.
* dp×pp ring pipelining: jit/pipeline_step.py overrides `_grads` (the
  seam this module exposes) with the ppermute ring schedule and reuses
  the clip/guard/update machinery unchanged.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .fused_scan_step import FusedScanTrainStep, _key
from ..utils import flags as _flags


# ---------------------------------------------------------------------------
# flat bucket packing (the comm_bucketer layout, applied per layer chunk)
# ---------------------------------------------------------------------------

def pack_flat(leaf_of_key, bucket, lead=(), dtype=None):
    """Pack per-leaf arrays (each [*lead, *entry.shape]) into the
    bucket's flat layout [*lead, bucket.numel] (zero-padded), matching
    comm_bucketer._flatten_bucket offsets exactly. `dtype` overrides the
    bucket dtype (moment packing)."""
    dt = dtype or bucket.dtype
    parts = []
    for e in bucket.entries:
        parts.append(leaf_of_key(e.key).reshape(lead + (-1,)).astype(dt))
    pad = bucket.numel - sum(e.numel for e in bucket.entries)
    if pad:
        parts.append(jnp.zeros(lead + (pad,), dt))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)


def unpack_flat(flat, bucket):
    """[*lead, bucket.numel] -> {entry.key: [*lead, *entry.shape]}."""
    lead = flat.shape[:-1]
    return {e.key: flat[..., e.offset:e.offset + e.numel]
            .reshape(lead + tuple(e.shape)) for e in bucket.entries}


def scatter_flat(flat, axes, nranks, quant=""):
    """Reduce-scatter a packed flat bucket over `axes` (a single axis
    name or a tuple — the dp×mp/pp hybrid steps scatter over the
    FLATTENED product, first axis major) along its LAST dim: one
    collective per bucket (vs one per leaf), bit-identical to
    comm_bucketer.bucketed_reduce_scatter's per-bucket psum_scatter on
    the same packing for the single-axis case. `quant` routes the
    compressed scatter leg — since ISSUE 11 the int8/bf16 all_to_all
    wire format covers flattened axis tuples too (the chunk split is
    first-axis-major, matching tuple psum_scatter; see
    collective.comm_quant_multiaxis_selftest)."""
    if isinstance(axes, (tuple, list)) and len(axes) == 1:
        axes = axes[0]
    if quant:
        from ..distributed.collective import quantized_psum_scatter_traced

        return quantized_psum_scatter_traced(axes, nranks, quant)(flat)
    return lax.psum_scatter(flat, axes, scatter_dimension=flat.ndim - 1,
                            tiled=True)


def gather_flat(shard, axes, axis, quant=""):
    """Inverse of `scatter_flat`'s split: tiled all_gather over the same
    (possibly flattened) axes. `quant` routes the compressed gather leg
    (collective.quantized_all_gather_traced — the sharded-param-storage
    gather-on-use wire format, lossy and therefore opt-in via
    FLAGS_comm_quant like the scatter leg)."""
    if isinstance(axes, (tuple, list)) and len(axes) == 1:
        axes = axes[0]
    if quant:
        from ..distributed.collective import quantized_all_gather_traced

        return quantized_all_gather_traced(axes, quant,
                                           gather_axis=axis)(shard)
    return lax.all_gather(shard, axes, axis=axis, tiled=True)


# ---------------------------------------------------------------------------
# sharded parameter storage (ISSUE 11): params live as 1/N flat shards
# ---------------------------------------------------------------------------
# Between steps the ONLY param bytes on the devices are the per-bucket
# flat shards (the same __scan_shard_*__ layout the optimizer state
# uses); the full per-leaf arrays the rest of the framework reads
# (eval, checkpointing, tests) are materialized LAZILY on first access
# and dropped again after every step. The mechanics: each trainable
# Parameter of a sharded-storage step has its class swapped to a thin
# subclass whose `_data` property (shadowing the Tensor slot) gathers
# its bucket on a stale read and marks the bucket dirty on an external
# write — so `p._data = ...` (checkpoint restore, test poking, user
# init) transparently flows back into the shards at the next step.

_STALE = object()          # sentinel living in the Tensor._data slot
_TENSOR_DATA_SLOT = None   # resolved lazily (framework import order)
_RAW_DATA = [0]            # >0: passthrough reads/writes (inside a step)
_LAZY_CLS_CACHE = {}


def _data_slot():
    global _TENSOR_DATA_SLOT
    if _TENSOR_DATA_SLOT is None:
        from ..framework.tensor import Tensor

        _TENSOR_DATA_SLOT = Tensor.__dict__["_data"]
    return _TENSOR_DATA_SLOT


class _raw_param_access:
    """Context: Parameter._data reads/writes bypass the lazy-shard
    machinery (used around the compiled step call and its trace, where
    `_bind` shuffles tracers through the live Parameter objects)."""

    def __enter__(self):
        _RAW_DATA[0] += 1

    def __exit__(self, *exc):
        _RAW_DATA[0] -= 1


def _lazy_param_class(cls):
    lazy = _LAZY_CLS_CACHE.get(cls)
    if lazy is not None:
        return lazy
    slot = _data_slot()

    def _get(self):
        d = slot.__get__(self)
        if d is _STALE and not _RAW_DATA[0]:
            ref = self.__dict__.get("_shard_ref")
            if ref is not None:
                ref[0]._materialize_bucket_params(ref[1], ref[2])
                d = slot.__get__(self)
            if d is _STALE:
                raise RuntimeError(
                    f"parameter {getattr(self, 'name', '?')} is stored "
                    "as 1/N shards but its owning sharded-storage step "
                    "is gone; keep the train step alive or use "
                    "param_storage='replicated'")
        return d

    def _set(self, v):
        slot.__set__(self, v)
        if not _RAW_DATA[0] and v is not _STALE:
            ref = self.__dict__.get("_shard_ref")
            if ref is not None:
                ref[0]._dirty_param_buckets.add((ref[1], ref[2]))

    lazy = type(f"_ShardStored{cls.__name__}", (cls,),
                {"_data": property(_get, _set), "__module__": __name__,
                 "_shard_backed": True})
    _LAZY_CLS_CACHE[cls] = lazy
    return lazy


def _unwrap_layers(model):
    """Follow wrapper chains (GroupShardedStage2, fleet MetaParallelBase,
    DataParallel) to the Layer that owns the parameters."""
    seen = set()
    while hasattr(model, "_layers") and id(model) not in seen:
        seen.add(id(model))
        model = model._layers
    return model


def _vec_or_scalar(values, entries, numel, pad_value=0.0):
    """Per-entry hyperparameters as ONE flat [numel] fp32 vector — or a
    python float when uniform (padding entries update to zero regardless
    of the hyperparameter, so a uniform scalar is exact)."""
    uniq = set(values)
    if len(uniq) == 1:
        return float(values[0])
    vec = np.full((numel,), pad_value, np.float32)
    for e, v in zip(entries, values):
        vec[e.offset:e.offset + e.numel] = v
    return jnp.asarray(vec)


class ShardedFusedScanTrainStep(FusedScanTrainStep):
    """Multi-chip FusedScanTrainStep over a dp/sharding mesh axis —
    and, with ``mp_axis``, a 2-D dp×mp mesh with Megatron tensor
    parallelism inside the scan body.

    Usage (directly, or via fleet distributed_model /
    jit.select_train_step which resolve mesh+axes)::

        mesh = dist.env.build_mesh({"sharding": 8}); dist.env.set_mesh(mesh)
        step = ShardedFusedScanTrainStep(model, opt)   # scan_layers model
        loss = step(ids, labels)       # ids [global_batch, seq]

        mesh = dist.env.build_mesh({"dp": 4, "mp": 2})  # dp×mp hybrid
        step = ShardedFusedScanTrainStep(model, opt, mesh=mesh,
                                         axis="dp", mp_axis="mp")

    Optimizer state (moments + masters) lives as flat bucket-packed
    arrays sharded 1/N over the FLATTENED reduction axes (N = dp·mp;
    inspect `opt._accumulators["moment1"]["__scan_shard_s0__"]` etc.);
    ClipGradByGlobalNorm costs one scalar all-reduce, ClipGradByValue is
    elementwise on the shard, and dropout is rank-folded per layer.
    Under mp the block compute runs head-/column-/row-sliced per rank
    with one psum per row-parallel projection, and the LM head is the
    vocab-parallel sharded fused CE (see _setup_mp / _head_fn).

    ``param_storage="sharded"`` (the default; "replicated" restores the
    pre-ISSUE-11 layout, FLAGS_param_storage overrides globally) stores
    the PARAMETERS the same way: 1/N flat bucket shards between steps,
    gathered on use inside the scans with a double-buffered prefetch
    slot — bit-parity with replicated storage, param_bytes×(1−1/N)
    less steady-state HBM per device. Between steps, reads of a
    shard-stored `p._data` gather lazily (eval/checkpoints just work)
    and external writes repack at the next step.
    """

    def __init__(self, model, optimizer, criterion=None, fused_head=False,
                 compute_dtype=None, layer_chunk=1, scan_unroll=1,
                 mesh=None, axis=None, mp_axis=None, ep_axis=None,
                 group=None, comm_bucket_mb=None, comm_quant=None,
                 scaler=None, guard_nonfinite=None, param_storage=None,
                 numerics=None):
        model = _unwrap_layers(model)
        super().__init__(model, optimizer, criterion=criterion,
                         fused_head=fused_head,
                         compute_dtype=compute_dtype,
                         layer_chunk=layer_chunk, scan_unroll=scan_unroll,
                         scaler=scaler, guard_nonfinite=guard_nonfinite,
                         numerics=numerics)
        from ..distributed import env as denv

        if group is not None:
            mesh, axis = group.mesh, group.axes[0]
        if mesh is None:
            mesh = denv.get_mesh()
        if axis is None:
            # prefer a >1 data axis; else a PRESENT degree-1 dp/sharding
            # axis (a dp1×pp2 mesh still batches over "dp", not "pp");
            # else the first mesh axis
            axis = next((a for a in ("sharding", "dp")
                         if a in mesh.axis_names and mesh.shape[a] > 1),
                        None) or next(
                (a for a in ("sharding", "dp")
                 if a in mesh.axis_names), mesh.axis_names[0])
        if mp_axis is None:
            mp_axis = next((a for a in ("mp",)
                            if a in mesh.axis_names and a != axis
                            and mesh.shape[a] > 1), None)
        elif mp_axis not in mesh.axis_names or \
                int(mesh.shape[mp_axis]) <= 1:
            mp_axis = None
        if mp_axis is not None and mp_axis == axis:
            raise ValueError(
                f"mp_axis {mp_axis!r} is also the batch/data axis — a "
                "pure-mp mesh has no axis to shard the batch over; "
                "build the mesh with an explicit (degree-1 is fine) "
                "data axis, e.g. build_mesh({'dp': 1, 'mp': N})")
        if axis not in mesh.axis_names:
            raise ValueError(
                f"batch/data axis {axis!r} is not a mesh axis "
                f"(mesh axes: {mesh.axis_names}); include it in the "
                "mesh (degree 1 is fine) or pass axis= explicitly")
        # expert parallelism (ISSUE 9): an ``ep`` axis shards the
        # template's MoE expert stacks 1/ep and splits the batch over
        # the FLATTENED (dp, ep) product — every (dp, ep) rank sees
        # distinct rows, and the MoE dispatch/combine become explicit
        # ep-axis all_to_alls inside the scan body (moe_layer's EP
        # path). Auto-detected from the mesh for MoE templates only.
        moe_template = bool(self._aux_layers)
        if ep_axis is None:
            ep_axis = next(
                (a for a in ("ep",) if a in mesh.axis_names
                 and int(mesh.shape[a]) > 1 and a != axis), None)
            if ep_axis is not None and not moe_template:
                ep_axis = None      # dense model: ep replicates
        elif ep_axis not in mesh.axis_names:
            # an explicit but unknown axis name is a config typo — the
            # silent fallback would train with experts fully replicated
            # while the user believes EP is active
            raise ValueError(
                f"ep_axis {ep_axis!r} is not a mesh axis (mesh axes: "
                f"{mesh.axis_names}); include it in the mesh or drop "
                "ep_axis")
        elif int(mesh.shape[ep_axis]) <= 1:
            ep_axis = None
        if ep_axis is not None:
            if not moe_template:
                raise ValueError(
                    f"ep_axis {ep_axis!r} given but the block template "
                    "has no MoE layers to expert-shard; build the model "
                    "with GPTConfig(num_experts=...) or drop ep_axis")
            if ep_axis == axis:
                raise ValueError(
                    f"ep_axis {ep_axis!r} is also the batch/data axis; "
                    "build the mesh with distinct dp and ep axes, e.g. "
                    "build_mesh({'dp': N, 'ep': E})")
            if mp_axis is not None:
                raise NotImplementedError(
                    "mp×ep composition is not supported: the Megatron "
                    "block slicing and the expert all_to_all dispatch "
                    "have not been validated together — use dp×ep or "
                    "dp×mp")
        self._mesh, self._axis = mesh, axis
        self._mp_axis = mp_axis
        self._ep_axis = ep_axis
        self._dp_degree = int(mesh.shape[axis])
        self._mp_degree = int(mesh.shape[mp_axis]) if mp_axis else 1
        self._ep_degree = int(mesh.shape[ep_axis]) if ep_axis else 1
        # grad-reduction axes, FIRST AXIS MAJOR: every flat bucket
        # scatters/gathers over the flattened product, so optimizer
        # shards are 1/(dp*mp*ep); the flat rank below must match the
        # tuple-collective split order. Subclasses (the pipeline step)
        # append further axes via _extra_reduction_axes.
        self._axes = (axis,)
        if mp_axis is not None:
            self._axes = self._axes + (mp_axis,)
        if ep_axis is not None:
            self._axes = self._axes + (ep_axis,)
        self._degree = (self._dp_degree * self._mp_degree
                        * self._ep_degree)
        # the batch splits over (dp, ep) — under ep every rank holds
        # distinct rows (pure data parallelism everywhere except the
        # expert FFN, where the all_to_all exchanges tokens)
        self._batch_axes = ((axis,) if ep_axis is None
                            else (axis, ep_axis))
        self._batch_degree = self._dp_degree * self._ep_degree
        for a in self._extra_reduction_axes(mesh):
            if a in self._axes:
                raise ValueError(
                    f"reduction axis {a!r} doubles as the batch/data "
                    f"axis (resolved axes {self._axes}) — a pp-only "
                    "mesh has no axis to shard the batch over; build "
                    "the mesh with an explicit (degree-1 is fine) data "
                    "axis, e.g. build_mesh({'dp': 1, 'pp': N})")
            self._axes = self._axes + (a,)
            self._degree *= int(mesh.shape[a])
        if self._degree <= 1 and not getattr(
                self, "_allow_degree_one", False):
            raise ValueError(
                f"axes {self._axes!r} have total degree {self._degree}; "
                "weight-update sharding needs a >1 dp/sharding (or mp) "
                "axis — use FusedScanTrainStep on one chip")
        # dp-rank folded into the per-layer dropout offsets. mp ranks
        # MUST draw identical masks (they jointly compute the same batch
        # rows; divergent hidden-dropout masks would desynchronize the
        # replicated residual stream), so only the dp index folds in —
        # but ep ranks hold DISTINCT rows, so under ep the flattened
        # (dp, ep) batch rank folds in instead.
        self._rng_nranks = self._batch_degree
        if mp_axis is not None:
            self._setup_mp()
        if ep_axis is not None:
            self._setup_ep()
        if comm_quant is None:
            comm_quant = _flags.get_flag("FLAGS_comm_quant") or ""
        # since ISSUE 11 the int8/bf16 wire format covers flattened axis
        # tuples (first-axis-major all_to_all split, verified by
        # comm_quant_multiaxis_selftest) — the PR-8 warn-off/reject for
        # multi-axis steps is gone
        self._comm_quant = comm_quant
        # sharded parameter storage (ISSUE 11): params live as 1/N flat
        # bucket shards (gathered on use inside the scans) instead of
        # replicated per-leaf stacks; default ON for the sharded steps —
        # the compiled step is bit-parity with replicated storage
        if param_storage is None:
            param_storage = (_flags.get_flag("FLAGS_param_storage")
                             or "sharded")
        if param_storage not in ("sharded", "replicated"):
            raise ValueError(
                f"param_storage {param_storage!r} (sharded|replicated)")
        self._param_storage = param_storage
        self._param_shards = {"s": [], "o": []}
        self._dirty_param_buckets = set()
        self._pack_jits = {}       # (grp, bucket idx) -> jitted packer
        self._gather_jit = None    # shard -> replicated resharder
        from ..distributed.collective import QUANT_SCATTER_BLOCK
        from ..distributed.comm_bucketer import MB, build_buckets

        pad = self._degree * (QUANT_SCATTER_BLOCK if comm_quant else 1)
        if comm_bucket_mb is None:
            comm_bucket_mb = int(
                _flags.get_flag("FLAGS_comm_bucket_mb") or 0)
        bucket_bytes = (comm_bucket_mb * MB if comm_bucket_mb > 0
                        else 1 << 62)
        # stacked leaves bucket by their PER-LAYER shard shape (the scan
        # scatters one chunk at a time); outer leaves by full shape
        self._s_train = [(j, p) for j, p in enumerate(self._s_params)
                         if p.trainable]
        self._s_trainable_idx = {j for j, _ in self._s_train}
        self._s_assign = build_buckets(
            [(j, tuple(p.shape[1:]), p._data.dtype)
             for j, p in self._s_train],
            bucket_bytes=bucket_bytes, pad_multiple=pad)
        self._o_assign = build_buckets(
            [(j, tuple(p.shape), p._data.dtype)
             for j, (_, p) in enumerate(self._o_params)],
            bucket_bytes=bucket_bytes, pad_multiple=pad)
        # master-weight use per bucket, resolved NOW (reads p._data
        # dtypes) — after shardification the live Parameters may hold
        # the stale sentinel, and build-time metadata must not trigger
        # a gather
        self._bucket_use_mw = {
            grp: [any(self._opt._use_master(p)
                      for p in self._bucket_params(grp, b))
                  for b in assign.buckets]
            for grp, assign in (("s", self._s_assign),
                                ("o", self._o_assign))}

    def _cost_axis_degrees(self):
        return {a: int(self._mesh.shape[a])
                for a in self._mesh.axis_names}

    def _publish_comm_gauges(self):
        """Static comm-budget gauges (ISSUE 12): global payload bytes
        per step of the grad reduce-scatter leg (every bucket, every
        chunk) and — under sharded parameter storage — the param
        all-gather leg, labeled with the reduction-axis tuple."""
        from ..observability import registry as _oreg

        s_bytes = sum(b.nbytes for b in self._s_assign.buckets) \
            * self.model.config.num_layers
        o_bytes = sum(b.nbytes for b in self._o_assign.buckets)
        reg = _oreg()
        axes = "+".join(self._axes)
        reg.gauge("comm.grad_scatter_bytes_per_step").set(
            s_bytes + o_bytes)
        reg.gauge("comm.reduction_axes").set(axes)
        if self._param_storage == "sharded":
            # forward gather + backward re-gather + outer gather ≈ 2x
            # the stacked payload + outer (update writes shards back)
            reg.gauge("comm.param_gather_bytes_per_step").set(
                2 * s_bytes + o_bytes)

    def _rng_rank(self):
        r = lax.axis_index(self._axis)
        if self._ep_axis is not None:
            r = r * self._ep_degree + lax.axis_index(self._ep_axis)
        return r

    def _extra_reduction_axes(self, mesh):
        """Hook: further mesh axes the grad scatter / optimizer shard
        should flatten in (the pipeline step adds its pp axis)."""
        return ()

    def _flat_rank(self):
        """Flattened rank over the grad-reduction axes, first axis
        major — the split order of tuple-axis psum_scatter/all_gather
        (verified against jax's flattened-product layout)."""
        r = lax.axis_index(self._axes[0])
        for a in self._axes[1:]:
            r = r * int(self._mesh.shape[a]) + lax.axis_index(a)
        return r

    # -- Megatron tensor parallelism over the mp axis --------------------
    # COMPUTE is tensor-parallel (storage is flat-sharded 1/N by default
    # since ISSUE 11 — the mp slicers below operate on the gathered full
    # leaves either way): each mp rank binds head-/column-sliced views of
    # qkv+fc1 and row-sliced views of out_proj+fc2 into the block
    # template, and the two row-parallel outputs psum over mp inside the
    # block — the Megatron layout the SPMD rule table
    # (distributed/auto_parallel/spmd_rules.py) assigns, realized as
    # manual collectives inside the scan body.
    def _setup_mp(self):
        from ..distributed.auto_parallel.spmd_rules import (
            _assign_roles, _is_fused_proj,
        )

        mp = self._mp_degree
        tmpl = self._template
        cfg = self.model.config
        if cfg.num_attention_heads % mp:
            raise ValueError(
                f"num_attention_heads {cfg.num_attention_heads} not "
                f"divisible by mp degree {mp}")
        if cfg.vocab_size % mp:
            raise ValueError(
                f"vocab_size {cfg.vocab_size} not divisible by mp "
                f"degree {mp} (vocab-parallel LM head)")
        if getattr(cfg, "attention_dropout_prob", 0.0):
            raise ValueError(
                "attention dropout under mp>1 would draw the same mask "
                "stream for every rank's head slice; train with "
                "attention_dropout_prob=0 (hidden dropout is fine)")
        from ..models.gpt import GPTPretrainingCriterion

        if not isinstance(self._crit, GPTPretrainingCriterion):
            raise ValueError(
                "mp>1 routes the LM head through the vocab-parallel "
                "sharded fused CE; custom criteria are not representable "
                "there — use the default GPTPretrainingCriterion")
        # sublayer path -> object, for role/ownership lookups
        subs = dict(tmpl.named_sublayers(include_self=True))
        roles = _assign_roles(tmpl)

        def owner_of(pname):
            path = pname.rsplit(".", 1)[0] if "." in pname else ""
            return subs.get(path), path

        def head_slicer(nh, hd, dim):
            """Head-interleaved slice of a fused multi-projection dim
            (qkv [.., 3*nh*hd]): view [.., 3, nh, hd], slice nh."""
            nh_loc = nh // mp

            def fn(d, r):
                lead = d.shape[:dim]
                k = d.shape[dim] // (nh * hd)
                v = d.reshape(lead + (k, nh, hd))
                v = lax.dynamic_slice_in_dim(v, r * nh_loc, nh_loc,
                                             dim + 1)
                return v.reshape(lead + (k * nh_loc * hd,))

            return fn

        def dim_slicer(dim, degree=mp):
            def fn(d, r):
                loc = d.shape[dim] // degree
                return lax.dynamic_slice_in_dim(d, r * loc, loc, dim)

            return fn

        slicers = []
        row_parallel = []          # (parent path, attr name)
        for pname, p in tmpl.named_parameters():
            sub, path = owner_of(pname)
            role = roles.get(id(sub)) if sub is not None else None
            tname = type(sub).__name__ if sub is not None else ""
            leaf = pname.rsplit(".", 1)[-1]
            if tname == "Linear" and role == "column":
                parent_path = path.rsplit(".", 1)[0] if "." in path \
                    else ""
                parent = subs.get(parent_path)
                nh = getattr(parent, "num_heads", None)
                hd = getattr(parent, "head_dim", None)
                fused = _is_fused_proj(sub, attr_name=path.rsplit(
                    ".", 1)[-1])
                if fused and not (nh and hd):
                    raise ValueError(
                        f"{pname}: fused multi-projection column layer "
                        "needs a parent exposing num_heads/head_dim for "
                        "the head-interleaved mp slice (a contiguous "
                        "column slice would split q|k|v wrongly)")
                if fused:
                    slicers.append(head_slicer(nh, hd,
                                               0 if leaf == "bias"
                                               else 1))
                elif leaf == "weight":
                    if sub.weight.shape[1] % mp:
                        raise ValueError(
                            f"{pname}: out dim {sub.weight.shape[1]} "
                            f"not divisible by mp {mp}")
                    slicers.append(dim_slicer(1))
                else:
                    slicers.append(dim_slicer(0))
            elif tname == "Linear" and role == "row":
                if leaf == "weight":
                    slicers.append(dim_slicer(0))
                else:
                    # row-parallel bias: every rank adds bias/mp, the
                    # in-block psum reconstructs it once (exact in real
                    # arithmetic; fp noise is far under the parity bar)
                    inv = 1.0 / mp
                    slicers.append(lambda d, r, inv=inv: d * inv)
                if leaf == "weight":
                    parent_path = path.rsplit(".", 1)[0] if "." in path \
                        else ""
                    row_parallel.append((subs.get(parent_path),
                                         path.rsplit(".", 1)[-1]))
            else:
                slicers.append(None)       # replicated (norms etc.)
        self._mp_slicers = slicers
        self._mp_row_parallel = [(o, a) for o, a in row_parallel
                                 if o is not None]
        # attention modules whose head count narrows to nh/mp while the
        # local views are bound
        self._mp_heads = [
            (s, int(s.num_heads)) for _, s in subs.items()
            if hasattr(s, "num_heads") and hasattr(s, "head_dim")
            and isinstance(getattr(s, "num_heads"), int)
            and s.num_heads % mp == 0
        ]

    # -- expert parallelism over the ep axis -----------------------------
    # COMPUTE is expert-parallel (storage flat-sharded 1/N by default,
    # like mp above): each ep rank binds the 1/ep slice
    # of every MoE expert stack into the template, and the MoE layer —
    # seeing sliced stacks inside a shard_map that binds the axis —
    # dispatches tokens to expert owners with explicit capacity-padded
    # lax.all_to_alls (moe_layer.py's EP path). Per-rank expert grads
    # are zero outside the rank's slice, so the (dp, ep) axis-tuple
    # scatter is simultaneously the data-parallel reduction and the
    # expert-parallel gradient assembly.
    def _setup_ep(self):
        from ..incubate.distributed.models.moe.moe_layer import MoELayer

        ep = self._ep_degree
        tmpl = self._template
        subs = dict(tmpl.named_sublayers(include_self=True))
        for path, sub in subs.items():
            if isinstance(sub, MoELayer):
                if sub.num_experts % ep:
                    raise ValueError(
                        f"{path or 'moe'}: num_experts "
                        f"{sub.num_experts} not divisible by ep degree "
                        f"{ep}")
                if sub.ep_degree not in (None, ep):
                    raise ValueError(
                        f"{path or 'moe'}: MoELayer(ep_degree="
                        f"{sub.ep_degree}) disagrees with the mesh's "
                        f"ep degree {ep}")
        def expert_slicer(degree):
            def fn(d, r):
                loc = d.shape[0] // degree
                return lax.dynamic_slice_in_dim(d, r * loc, loc, 0)

            return fn

        slicers = []
        for pname, p in tmpl.named_parameters():
            path = pname.rsplit(".", 1)[0] if "." in pname else ""
            leaf = pname.rsplit(".", 1)[-1]
            owner = subs.get(path)
            if isinstance(owner, MoELayer) and \
                    leaf.startswith("experts__"):
                slicers.append(expert_slicer(ep))
            else:
                slicers.append(None)     # gate weight, attention, norms
        if not any(s is not None for s in slicers):
            raise ValueError(
                "ep axis active but no expert-stacked parameters found "
                "in the block template")
        self._ep_slicers = slicers

    class _RowParallelPsum:
        """Call-through shim over a row-parallel Linear: local partial
        matmul (+ bias/mp), then one psum over the mp axis — the
        Megatron g-operator, inserted at trace time."""

        __slots__ = ("_inner", "_axis")

        def __init__(self, inner, axis):
            self._inner, self._axis = inner, axis

        def __call__(self, x):
            from ..framework.tensor import Tensor

            y = self._inner(x)
            return Tensor._wrap(lax.psum(y._data, self._axis))

    def _block_fn(self, leaf_datas, x, rng_off=None):
        if self._ep_axis is not None:
            r = lax.axis_index(self._ep_axis)
            local = [d if fn is None else fn(d, r)
                     for fn, d in zip(self._ep_slicers, leaf_datas)]
            # the bound 1/ep expert slices + the bound ep axis are what
            # flip MoELayer.forward onto its all_to_all dispatch path
            return super()._block_fn(local, x, rng_off=rng_off)
        if self._mp_axis is None:
            return super()._block_fn(leaf_datas, x, rng_off=rng_off)
        r = lax.axis_index(self._mp_axis)
        local = [d if fn is None else fn(d, r)
                 for fn, d in zip(self._mp_slicers, leaf_datas)]
        mp = self._mp_degree
        patched = []
        try:
            for obj, attr in self._mp_row_parallel:
                inner = getattr(obj, attr)
                object.__setattr__(
                    obj, attr, self._RowParallelPsum(inner,
                                                     self._mp_axis))
                patched.append((obj, attr))
            for obj, nh in self._mp_heads:
                object.__setattr__(obj, "num_heads", nh // mp)
            return super()._block_fn(local, x, rng_off=rng_off)
        finally:
            for obj, attr in patched:
                object.__delattr__(obj, attr)
            for obj, nh in self._mp_heads:
                object.__setattr__(obj, "num_heads", nh)

    def _head_fn(self, o_datas, xL, labels):
        """Vocab-parallel LM head under mp: ln_f on the replicated
        hiddens, then the PR-7 vocab-tiled fused CE over THIS rank's
        [vocab/mp, H] row shard of the head — per-rank losses are
        identical (the shard stats combine over mp inside the kernel's
        custom vjp), and the head grads each rank produces cover exactly
        its shard rows (zero-padded elsewhere), which is what lets the
        ordinary (dp, mp) grad scatter reassemble them with no full
        [vocab, H] gradient ever built."""
        if self._mp_axis is None:
            return super()._head_fn(o_datas, xL, labels)
        import jax.numpy as jnp

        from ..framework.autograd import no_grad
        from ..framework.tensor import Tensor
        from ..ops.pallas.fused_cross_entropy import (
            sharded_fused_cross_entropy,
        )

        m = self.model
        with no_grad():
            saved = self._bind([p for _, p in self._o_params],
                               self._cc(o_datas))
            try:
                h = m.gpt.ln_f(Tensor._wrap(xL))._data
                if m.lm_head is None:
                    w = m.gpt.wte.weight._data           # [V, H]
                else:
                    w = m.lm_head.weight._data.T         # [H, V] -> [V, H]
                vloc = w.shape[0] // self._mp_degree
                r = lax.axis_index(self._mp_axis)
                wl = lax.dynamic_slice_in_dim(w, r * vloc, vloc, 0)
                hid = h.reshape(-1, h.shape[-1])
                lbl = labels.reshape(-1)
                losses = sharded_fused_cross_entropy(
                    hid, wl, lbl, r * vloc, self._mp_axis)
                mask = (lbl != -100).astype(losses.dtype)
                return jnp.sum(losses * mask) / jnp.clip(
                    jnp.sum(mask), 1.0, None)
            finally:
                self._bind([p for _, p in self._o_params], saved)

    def input_sharding(self):
        """Batches stage dim-0-sharded 1/N over the batch axes (dp, or
        the flattened dp×ep product under expert parallelism) — each
        device receives only its shard of the global batch (the
        weight-update sharding lesson applied to ingestion), and the
        placement matches the step's shard_map batch spec so jit never
        reshards."""
        ba = (self._batch_axes if len(self._batch_axes) > 1
              else self._axis)
        return NamedSharding(self._mesh, P(ba))

    # -- flat sharded optimizer state -----------------------------------
    def _flat_key(self, grp, index):
        return f"__scan_shard_{grp}{index}__"

    def _bucket_params(self, grp, bucket):
        src = (dict(self._s_train) if grp == "s"
               else {j: p for j, (_, p) in enumerate(self._o_params)})
        return [src[e.key] for e in bucket.entries]

    def _bucket_uses_master(self, grp, bucket):
        return self._bucket_use_mw[grp][bucket.index]

    def _materialize_flat_state(self):
        """Build (or repack) the optimizer state as per-bucket flat
        arrays sharded 1/N over the axis. Fresh state is created
        SHARDED from the start (jit with out_shardings — zeros for
        moments, fp32 casts of the params for masters), so the first
        build never materializes the full replicated optimizer state
        the sharding exists to avoid; a continuation from per-param
        state (prior TrainStep run, old checkpoint) packs the existing
        full-shape entries once. Idempotent: an existing flat entry
        (second build, checkpoint restore) is reused as-is."""
        opt = self._opt
        mesh = self._mesh
        ax = self._axes if len(self._axes) > 1 else self._axis
        n_layers = self.model.config.num_layers
        for grp, assign in (("s", self._s_assign), ("o", self._o_assign)):
            stacked = grp == "s"
            sharding = NamedSharding(
                mesh, P(None, ax) if stacked else P(ax))
            lead = (n_layers,) if stacked else ()
            for bucket in assign.buckets:
                fkey = self._flat_key(grp, bucket.index)
                params = dict(zip([e.key for e in bucket.entries],
                                  self._bucket_params(grp, bucket)))
                use_mw = self._bucket_uses_master(grp, bucket)
                md = self._moment_dtype(bucket, use_mw)

                def packed(leaves, dtype):
                    return jax.jit(
                        lambda lv: pack_flat(lambda k: lv[k], bucket,
                                             lead=lead, dtype=dtype),
                        out_shardings=sharding)(leaves)

                for name in ("moment1", "moment2"):
                    store = opt._accumulators.setdefault(name, {})
                    if fkey not in store:
                        if all(_key(p) in store
                               for p in params.values()):
                            store[fkey] = packed(
                                {k: store[_key(p)]
                                 for k, p in params.items()}, md)
                        else:
                            shape = lead + (bucket.numel,)
                            store[fkey] = jax.jit(
                                lambda s=shape, d=md: jnp.zeros(s, d),
                                out_shardings=sharding)()
                    for p in params.values():
                        store.pop(_key(p), None)
                if use_mw:
                    if fkey not in opt._master_weights:
                        opt._master_weights[fkey] = packed(
                            {k: opt._master_weights.get(_key(p),
                                                        p._data)
                             for k, p in params.items()},
                            jnp.float32)
                    for p in params.values():
                        opt._master_weights.pop(_key(p), None)

    def _moment_dtype(self, bucket, use_mw):
        md = self._opt._moment_dtype
        if md is not None:
            return md
        return jnp.float32 if use_mw else bucket.dtype

    # -- sharded parameter storage (ISSUE 11) ---------------------------
    def _shard_sharding(self, grp):
        ax = self._axes if len(self._axes) > 1 else self._axis
        return NamedSharding(self._mesh,
                             P(None, ax) if grp == "s" else P(ax))

    def _shard_stored_params(self, grp, bucket):
        """The live Parameter objects whose storage the (grp, bucket)
        flat shard owns."""
        if grp == "s":
            by_j = dict(self._s_train)
            return [by_j[e.key] for e in bucket.entries]
        return [self._o_params[e.key][1] for e in bucket.entries]

    def _pack_param_bucket(self, grp, bucket):
        """Pack the bucket's params from their CURRENT full `_data` into
        one flat array sharded 1/N over the reduction axes (the same
        layout/jit-out_shardings pattern `_materialize_flat_state`
        uses). Reads materialize stale entries first, so a partial
        external write (checkpoint restore touching one leaf) composes
        with shard-resident neighbours. The jitted packer is cached per
        (grp, bucket) — repack is a steady-state path (every restore /
        external write), and a fresh jit per call would recompile."""
        n_layers = self.model.config.num_layers
        lead = (n_layers,) if grp == "s" else ()
        params = self._shard_stored_params(grp, bucket)
        leaves = {e.key: p._data
                  for e, p in zip(bucket.entries, params)}

        def pack(lv):
            return pack_flat(lambda k: lv[k], bucket, lead=lead)

        src = next(iter(next(iter(leaves.values())).devices()))
        if src not in set(self._mesh.devices.flat):
            # a model built off the mesh (on the host, `set_device('cpu')`):
            # pack where the leaves live and ship each chip only its 1/N.
            # Uncommitted arrays follow the default device into any jitted
            # program — the mesh's pack program, or the slicing device_put
            # runs on a jax.Array — and would all pile on the default chip
            # first; numpy is sliced on the host.
            with jax.default_device(src):
                flat = np.asarray(jax.jit(pack)(leaves))
            return jax.device_put(flat, self._shard_sharding(grp))
        fn = self._pack_jits.get((grp, bucket.index))
        if fn is None:
            fn = jax.jit(pack, out_shardings=self._shard_sharding(grp))
            self._pack_jits[(grp, bucket.index)] = fn
        return fn(leaves)

    def _materialize_param_shards(self):
        """Flip parameter STORAGE to 1/N flat bucket shards: pack every
        trainable leaf once, swap the live Parameters to the lazy
        shard-backed class, and drop the full arrays (the stale
        sentinel) — from here on no full replicated parameter pytree
        exists between steps; reads gather on demand, external writes
        repack at the next step."""
        if self._param_storage != "sharded" or self._param_shards["s"] \
                or self._param_shards["o"]:
            if self._param_storage == "sharded":
                self._repack_dirty_param_buckets()
            return
        slot = _data_slot()
        for grp, assign in (("s", self._s_assign), ("o", self._o_assign)):
            for bucket in assign.buckets:
                # NOTE: _pack_param_bucket reads p._data through the
                # lazy property, so a param still shard-backed by a
                # PREVIOUS step (rebuild-the-step workflow: new
                # optimizer, phase-2 fine-tune) materializes its
                # current values from the old step's shards first —
                # the takeover below then rebinds it to this step.
                # (Two steps training one model CONCURRENTLY remains
                # undefined, exactly as with replicated storage.)
                self._param_shards[grp].append(
                    self._pack_param_bucket(grp, bucket))
                for p in self._shard_stored_params(grp, bucket):
                    if not getattr(type(p), "_shard_backed", False):
                        p.__class__ = _lazy_param_class(type(p))
                    p.__dict__["_shard_ref"] = (self, grp, bucket.index)
                    slot.__set__(p, _STALE)
        self._dirty_param_buckets.clear()

    def _materialize_bucket_params(self, grp, bucket_index):
        """Lazy-read path: gather ONE bucket's flat shard back to a
        replicated array and fill the full `_data` of every entry that
        is still stale (an externally written entry keeps its new
        value). Called by the lazy Parameter's `_data` getter."""
        bucket = (self._s_assign if grp == "s"
                  else self._o_assign).buckets[bucket_index]
        flat = self._param_shards[grp][bucket_index]
        # one cached resharder for every bucket read: materialization is
        # a steady-state path (eval / checkpoint save between steps)
        if self._gather_jit is None:
            self._gather_jit = jax.jit(
                lambda v: v,
                out_shardings=NamedSharding(self._mesh, P()))
        full = self._gather_jit(flat)
        slot = _data_slot()
        n_layers = self.model.config.num_layers
        for e, p in zip(bucket.entries,
                        self._shard_stored_params(grp, bucket)):
            if slot.__get__(p) is not _STALE:
                continue
            leaf = full[..., e.offset:e.offset + e.numel]
            shape = ((n_layers,) + tuple(e.shape) if grp == "s"
                     else tuple(e.shape))
            slot.__set__(p, leaf.reshape(shape))

    def _invalidate_param_caches(self):
        """Post-step: drop any materialized full arrays so the shards
        stay the only live parameter bytes between steps."""
        slot = _data_slot()
        for grp, assign in (("s", self._s_assign), ("o", self._o_assign)):
            for bucket in assign.buckets:
                for p in self._shard_stored_params(grp, bucket):
                    slot.__set__(p, _STALE)

    def _repack_dirty_param_buckets(self):
        """Pre-step: fold external `p._data` writes (checkpoint restore,
        test poking) back into the authoritative flat shards."""
        if not self._dirty_param_buckets:
            return
        for grp, bi in sorted(self._dirty_param_buckets):
            assign = self._s_assign if grp == "s" else self._o_assign
            self._param_shards[grp][bi] = self._pack_param_bucket(
                grp, assign.buckets[bi])
        self._dirty_param_buckets.clear()
        self._invalidate_param_caches()

    def _mem_owners(self):
        """Live-buffer attribution (ISSUE 14): under sharded storage
        the trainable params live as ``__scan_shard_*__`` 1/N flat
        bucket shards — claimed as ``params.scan_shards`` — and a
        scrape must NOT materialize them, so shard-backed leaves are
        read through the raw data slot (stale entries simply are not
        resident and claim nothing). Replicated storage falls through
        to the base attribution."""
        if self._param_storage != "sharded":
            return super()._mem_owners()
        owners = {"params.scan_shards":
                  [a for a in (self._param_shards["s"]
                               + self._param_shards["o"])
                   if a is not None],
                  "buffers": [b._data for b in self._buffers]}
        slot = _data_slot()
        live_full = []
        with _raw_param_access():
            for grp, assign in (("s", self._s_assign),
                                ("o", self._o_assign)):
                for bucket in assign.buckets:
                    for p in self._shard_stored_params(grp, bucket):
                        d = slot.__get__(p)
                        if d is not _STALE and d is not None:
                            live_full.append(d)
        # non-shard-stored leaves (non-trainable stacked params) keep
        # ordinary storage
        live_full.extend(p._data for j, p in enumerate(self._s_params)
                         if j not in self._s_trainable_idx)
        owners["params"] = live_full
        owners["opt_state"] = self._opt_state_arrays()
        return owners

    def full_params(self):
        """Materialize every shard-stored parameter's full `_data`
        (eval/export convenience; the next step drops the copies
        again). No-op under replicated storage."""
        if self._param_storage == "sharded":
            for _, p in self._s_train:
                _ = p._data
            for _, p in self._o_params:
                _ = p._data

    def ensure_built(self):
        if self._jitted is not None:
            return
        self._materialize_flat_state()
        self._materialize_param_shards()
        # canonicalize replicated-state layouts BEFORE the first trace:
        # the step's outputs come back mesh-committed, so an uncommitted
        # single-device param on call 1 would key a SECOND executable on
        # call 2 (the TrainStep._build layout lesson — one extra 1.3b
        # compile)
        rep = NamedSharding(self._mesh, P())
        shard_stored = (self._s_trainable_idx
                        if self._param_storage == "sharded" else set())
        for j, p in enumerate(self._s_params):
            if j not in shard_stored:
                p._data = jax.device_put(p._data, rep)
        if self._param_storage != "sharded":
            for _, p in self._o_params:
                p._data = jax.device_put(p._data, rep)
        for b in self._buffers:
            b._data = jax.device_put(b._data, rep)
        self._step_count = jax.device_put(
            jnp.asarray(int(self._opt._step_count), jnp.int32), rep)
        self._opt._step_count = self._step_count
        if self._guard is not None and self._guard.scaler is not None:
            # the scaler's traced mirrors must start mesh-committed too,
            # or call 2 (committed jit outputs) keys a second executable
            self._guard.writeback(jax.tree_util.tree_map(
                lambda v: jax.device_put(v, rep),
                self._guard.init_state()))
        self._build()
        self._publish_comm_gauges()
        # live-buffer attribution (ISSUE 14): weakly tracked provider
        from ..observability.memory import live_registry

        live_registry().track(self)

    def _extract_state(self):
        opt = self._opt
        self._step_count = opt._step_count   # restore-aware (base class)
        if self._param_storage == "sharded":
            st = {
                "s": {"p": [None if j in self._s_trainable_idx
                            else p._data
                            for j, p in enumerate(self._s_params)],
                      "fp": list(self._param_shards["s"])},
                "o": {"p": [None] * len(self._o_params),
                      "fp": list(self._param_shards["o"])},
                "buf": [b._data for b in self._buffers],
                "step": jnp.asarray(self._step_count, jnp.int32),
            }
        else:
            st = {
                "s": {"p": [p._data for p in self._s_params]},
                "o": {"p": [p._data for _, p in self._o_params]},
                "buf": [b._data for b in self._buffers],
                "step": jnp.asarray(self._step_count, jnp.int32),
            }
        for grp, assign in (("s", self._s_assign), ("o", self._o_assign)):
            st[grp]["m"] = [opt._accumulators["moment1"]
                            [self._flat_key(grp, b.index)]
                            for b in assign.buckets]
            st[grp]["v"] = [opt._accumulators["moment2"]
                            [self._flat_key(grp, b.index)]
                            for b in assign.buckets]
            st[grp]["mw"] = [opt._master_weights.get(
                self._flat_key(grp, b.index)) for b in assign.buckets]
        if self._guard is not None:
            st["guard"] = self._guard.init_state()
        return st

    def _inject_state(self, state):
        opt = self._opt
        if self._param_storage == "sharded":
            self._param_shards["s"] = list(state["s"]["fp"])
            self._param_shards["o"] = list(state["o"]["fp"])
            for j, (p, d) in enumerate(zip(self._s_params,
                                           state["s"]["p"])):
                if j not in self._s_trainable_idx:
                    p._data = d
            # full-param caches are stale now (and their device buffers
            # must die): the shards are the only live parameter bytes
            self._invalidate_param_caches()
        else:
            for p, d in zip(self._s_params, state["s"]["p"]):
                p._data = d
            for (_, p), d in zip(self._o_params, state["o"]["p"]):
                p._data = d
        for grp, assign in (("s", self._s_assign), ("o", self._o_assign)):
            for b in assign.buckets:
                fkey = self._flat_key(grp, b.index)
                opt._accumulators["moment1"][fkey] = \
                    state[grp]["m"][b.index]
                opt._accumulators["moment2"][fkey] = \
                    state[grp]["v"][b.index]
                mw = state[grp]["mw"][b.index]
                if mw is not None:
                    opt._master_weights[fkey] = mw
        for b, d in zip(self._buffers, state["buf"]):
            b._data = d
        opt._step_count = state["step"]
        self._step_count = state["step"]
        if self._guard is not None and "guard" in state:
            self._guard.writeback(state["guard"])

    def _state_specs(self):
        ax = self._axes if len(self._axes) > 1 else self._axis
        rep = P()
        if self._param_storage == "sharded":
            specs = {
                "s": {"p": [None if j in self._s_trainable_idx else rep
                            for j in range(len(self._s_params))],
                      "fp": [P(None, ax)] * len(self._s_assign.buckets)},
                "o": {"p": [None] * len(self._o_params),
                      "fp": [P(ax)] * len(self._o_assign.buckets)},
                "buf": [rep] * len(self._buffers),
                "step": rep,
            }
        else:
            specs = {
                "s": {"p": [rep] * len(self._s_params)},
                "o": {"p": [rep] * len(self._o_params)},
                "buf": [rep] * len(self._buffers),
                "step": rep,
            }
        if self._guard is not None:
            specs["guard"] = {"scale": rep, "good": rep, "bad": rep,
                              "found": rep, "skipped": rep}
        for grp, assign in (("s", self._s_assign), ("o", self._o_assign)):
            sp = P(None, ax) if grp == "s" else P(ax)
            nb = len(assign.buckets)
            specs[grp]["m"] = [sp] * nb
            specs[grp]["v"] = [sp] * nb
            specs[grp]["mw"] = [
                sp if self._bucket_uses_master(grp, b) else None
                for b in assign.buckets]
        return specs

    # -- the compiled sharded step --------------------------------------
    def _build_prologue(self):
        """Host-side per-bucket hyperparameter tables shared by the
        grads pass and the update scan (built once per _build)."""
        opt = self._opt

        def hyper(p):
            return (float(opt._decoupled_wd(p)), float(opt._l2_coeff(p)),
                    float(opt._param_lr_scale(p)))

        def bucket_hp(grp, bucket):
            params = self._bucket_params(grp, bucket)
            hs = [hyper(p) for p in params]
            ent = bucket.entries
            wd = _vec_or_scalar([h[0] for h in hs], ent, bucket.numel)
            l2 = _vec_or_scalar([h[1] for h in hs], ent, bucket.numel)
            lrs = _vec_or_scalar([h[2] for h in hs], ent, bucket.numel,
                                 pad_value=1.0)
            ncs = [1.0 if getattr(p, "need_clip", True) else 0.0
                   for p in params]
            # None = "everything clips" (the common case, no masking);
            # a uniform 0.0 or a mixed vector masks the clip per entry
            nc = (None if all(v == 1.0 for v in ncs)
                  else _vec_or_scalar(ncs, ent, bucket.numel))
            return wd, l2, lrs, nc

        self._s_hp = [bucket_hp("s", b) for b in self._s_assign.buckets]
        self._o_hp = [bucket_hp("o", b) for b in self._o_assign.buckets]
        self._t_idx = {j: tj for tj, (j, _)
                       in enumerate(self._s_train)}

    @staticmethod
    def _shard_of(vec, rank, shard_len):
        """Own-rank slice of a replicated flat [F] constant (no-op for
        uniform scalars)."""
        if vec is None or isinstance(vec, float):
            return vec
        return lax.dynamic_slice_in_dim(vec, rank * shard_len,
                                        shard_len, 0)

    def _sq_of(self, gs, nc_shard):
        g32 = gs.astype(jnp.float32) * (1.0 / self._degree)
        if nc_shard is not None:
            g32 = g32 * nc_shard
        return jnp.sum(jnp.square(g32))

    def _clip_monitor_sq(self, gs, nc, clip_on, mon_on):
        """ONE shard reduction feeding BOTH the clip's norm carry and
        the monitor's grad sq-norm row (ISSUE 15 dedup — the single
        implementation behind every grads path, replicated / sharded-
        storage / pipeline). Returns ``(clip_term, monitor_term)``:
        ``clip_term`` is None with clipping off; ``monitor_term`` is
        None with the monitor off, reads the clip's sum when both are
        on, and only a need_clip mask (``nc``) forces a second,
        differently-masked sum — the monitor's row must be the
        UNMASKED norm."""
        s_b = self._sq_of(gs, nc if clip_on else None)
        mon = None
        if mon_on:
            mon = (s_b if nc is None or not clip_on
                   else self._sq_of(gs, None))
        return (s_b if clip_on else None), mon

    # -- gather-on-use plumbing (sharded parameter storage) --------------
    def _stacked_nontrainable(self, s_state):
        """[(leaf index j, data)] for the frozen stacked leaves riding
        `state['s']['p']` beside the shard-stored trainable ones."""
        return [(j, d) for j, d in enumerate(s_state["p"])
                if j not in self._s_trainable_idx]

    def _leaves_of(self, trainable, nontrainable):
        """Compose the full per-chunk leaf list (template order) from
        the gathered trainable tuple (ordered like `_s_train`) and the
        scanned non-trainable chunk slices."""
        lv = [None] * len(self._s_params)
        for (j, _), d in zip(self._s_train, trainable):
            lv[j] = d
        for (j, _), d in nontrainable:
            lv[j] = d
        return lv

    def _gather_outer_full(self, o_state):
        """Gather the outer params' flat shards back to full leaf
        arrays (ordered like `_o_params`) — once per step, at the top
        of the traced body; the full set dies with the step."""
        quant = self._comm_quant
        full = [None] * len(self._o_params)
        for bkt in self._o_assign.buckets:
            fb = gather_flat(o_state["fp"][bkt.index], self._axes,
                             axis=0, quant=quant)
            for key, leaf in unpack_flat(fb, bkt).items():
                full[key] = leaf
        return full

    def _gather_stacked_chunk(self, fp_c, i):
        """All-gather chunk ``i``'s params from the [C, K, F/N] flat
        shard stacks: one (optionally quantized) tiled all_gather per
        bucket over the flattened reduction axes, unpacked to the
        per-leaf [K, ...] views the block template binds. Returns a
        tuple ordered like `_s_train`."""
        quant = self._comm_quant
        out = {}
        for bkt in self._s_assign.buckets:
            fs = lax.dynamic_index_in_dim(fp_c[bkt.index], i,
                                          keepdims=False)     # [K, F/N]
            fb = gather_flat(fs, self._axes, axis=1, quant=quant)
            out.update(unpack_flat(fb, bkt))                  # [K, F]
        return tuple(out[j] for j, _ in self._s_train)

    def _grads(self, state, ids, labels, t32, ct):
        """Forward + backward producing the SCATTERED gradient shards:
        returns (loss, G, o_gs, sq, fin) where G[bucket] is [C, K, F/N]
        (this rank's 1/N shard per layer chunk), o_gs[bucket] is [F/N],
        sq the local shard's squared-norm contribution and fin the local
        finiteness fold. Default implementation is the in-scan
        reduce-scatter backward; the pipeline step overrides this with
        the ring schedule while reusing everything downstream. Under
        ``param_storage='sharded'`` the forward/backward scans gather
        each chunk's params on use (double-buffered prefetch) instead of
        reading replicated stacks."""
        if self._param_storage == "sharded":
            return self._grads_sharded_storage(state, ids, labels, t32,
                                               ct)
        from .fused_scan_step import _act_stats
        from .nonfinite_guard import all_finite

        s, o = state["s"], state["o"]
        axes, N = self._axes, self._degree
        K = self._layer_chunk
        n_layers = self.model.config.num_layers
        C = n_layers // K
        quant = self._comm_quant
        s_assign, o_assign = self._s_assign, self._o_assign
        clip_norm = self._clip_global
        guard = self._guard
        nm = self._numerics is not None
        rank = self._flat_rank()
        chunk_apply = self._chunk_apply
        b, seq = ids.shape          # LOCAL batch rows
        pos = jnp.arange(seq, dtype=ids.dtype)[None, :]

        aux_active = self._aux_active
        aux_w = self._aux_weight / n_layers

        # ---- forward (replicated params, local batch shard)
        x0 = self._embed_fn(o["p"], ids, pos,
                            rng_off=self._rng_base(t32, n_layers))
        sp_c = tuple(a.reshape((C, K) + tuple(a.shape[1:]))
                     for a in s["p"])

        def fwd_body(carry, scanned):
            h, h_fin = carry if nm else (carry, None)
            p_chunk, i = scanned
            rng0 = self._rng_chunk_base(t32, i)
            if aux_active:
                h2, aux = chunk_apply(p_chunk, h, rng0)
            else:
                h2, aux = chunk_apply(p_chunk, h, rng0), None
            ys = {"x": h}
            if aux_active:
                ys["aux"] = aux
            if not nm:
                return h2, ys
            ys["act"], out_fin = _act_stats(h_fin, h2)  # local rows:
            return (h2, out_fin), ys          # rank partials sum at host

        fwd0 = ((x0, jnp.isfinite(x0).all()) if nm else x0)
        fwd_c, ys = lax.scan(fwd_body, fwd0, (sp_c, jnp.arange(C)),
                             unroll=self._scan_unroll)
        xL = fwd_c[0] if nm else fwd_c
        xs, auxs = ys["x"], ys.get("aux")
        act_cols = ys.get("act")

        loss, head_vjp = jax.vjp(
            lambda od, x: self._head_fn(od, x, labels),
            o["p"], xL)
        d_o_head, dxL = head_vjp(ct.astype(loss.dtype))
        aux_ct = None
        if aux_active:
            # total per-rank loss = CE + (w/L)*sum(aux); the chunk vjps
            # get the matching loss-scaled cotangent
            loss = loss + jnp.float32(aux_w) * jnp.sum(auxs)
            aux_ct = jnp.float32(aux_w) * ct.astype(jnp.float32)

        # ---- backward scan: vjp one chunk, reduce-scatter its
        # bucket-packed grads over the FLATTENED reduction axes (dp, or
        # dp×mp); ONLY the 1/N shard, the running squared norm, and the
        # finiteness fold survive the iteration. Under mp the per-rank
        # dp covers only the rank's head/column slice (zero-padded
        # elsewhere), so the axis-tuple sum is simultaneously the
        # data-parallel reduction AND the tensor-parallel grad
        # assembly — no full-gradient gather exists at any point.
        G0 = tuple(jnp.zeros((C, K, bkt.numel // N), bkt.dtype)
                   for bkt in s_assign.buckets)

        def bwd_body(carry, scanned):
            dy, sq, fin, G = carry
            x_i, i = scanned
            p_i = tuple(
                lax.dynamic_index_in_dim(a, i, keepdims=False)
                for a in sp_c)
            rng0 = self._rng_chunk_base(t32, i)
            _, vjp = jax.vjp(
                lambda pl, xx: chunk_apply(pl, xx, rng0),
                p_i, x_i)
            dp, dx = vjp((dy, aux_ct) if aux_active else dy)
            newG = []
            c_sq = jnp.float32(0.0)
            c_fin = jnp.bool_(True)
            for bkt in s_assign.buckets:
                flat = pack_flat(lambda j: dp[j], bkt, lead=(K,))
                gs = scatter_flat(flat, axes, N, quant)  # [K,F/N]
                # one set of shard reductions feeds BOTH the clip's
                # norm carry and the monitor's per-chunk row (ISSUE 15
                # dedup); only a need_clip mask forces a second,
                # differently-masked sum
                if clip_norm is not None or nm:
                    nc = self._shard_of(self._s_hp[bkt.index][3], rank,
                                        bkt.numel // N)
                    ct_b, mt_b = self._clip_monitor_sq(
                        gs, nc, clip_norm is not None, nm)
                    if ct_b is not None:
                        sq = sq + ct_b
                    if nm:
                        c_sq = c_sq + mt_b
                if guard is not None:
                    # exact isfinite for the guard's skip decision
                    b_fin = all_finite([gs])
                    c_fin = c_fin & b_fin
                    fin = fin & b_fin
                newG.append(lax.dynamic_update_index_in_dim(
                    G[bkt.index], gs, i, 0))
            row = None
            if nm:
                if guard is None:
                    c_fin = jnp.isfinite(c_sq)   # no extra grad pass
                row = jnp.stack([
                    c_sq, (~c_fin).astype(jnp.float32),
                    jnp.float32(0.0)])
            return (dx, sq, fin, tuple(newG)), row

        (dx0, sq, fin, G), grad_cols = lax.scan(
            bwd_body,
            (dxL, jnp.float32(0.0), jnp.bool_(True), G0),
            (xs, jnp.arange(C)), reverse=True,
            unroll=self._scan_unroll)

        # ---- outer grads: same pack + reduce-scatter
        _, emb_vjp = jax.vjp(
            lambda od: self._embed_fn(
                od, ids, pos,
                rng_off=self._rng_base(t32, n_layers)), o["p"])
        (d_o_emb,) = emb_vjp(dx0)
        o_gs = []
        o_sq = jnp.float32(0.0)
        o_fin = jnp.bool_(True)
        for bkt in o_assign.buckets:
            flat = pack_flat(
                lambda j: (d_o_head[j].astype(jnp.float32)
                           + d_o_emb[j].astype(jnp.float32)),
                bkt)
            gs = scatter_flat(flat, axes, N, quant)      # [F/N]
            if clip_norm is not None or nm:
                nc = self._shard_of(self._o_hp[bkt.index][3], rank,
                                    bkt.numel // N)
                ct_b, mt_b = self._clip_monitor_sq(
                    gs, nc, clip_norm is not None, nm)
                if ct_b is not None:
                    sq = sq + ct_b
                if nm:
                    o_sq = o_sq + mt_b
            if guard is not None:
                b_fin = all_finite([gs])
                o_fin = o_fin & b_fin
                fin = fin & b_fin
            o_gs.append(gs)
        nrows = None
        if nm:
            if guard is None:
                o_fin = jnp.isfinite(o_sq)       # no extra grad pass
            nrows = {"grad": grad_cols, "act": act_cols,
                     "outer": jnp.stack([
                         o_sq, (~o_fin).astype(jnp.float32)])}
        return loss, G, o_gs, sq, fin, nrows

    def _grads_sharded_storage(self, state, ids, labels, t32, ct):
        """The gather-on-use form of `_grads` (ISSUE 11): params enter
        as 1/N flat bucket shards. The forward scan carries chunk i's
        GATHERED params while issuing the gather for chunk i+1 — a
        double-buffered prefetch slot, so the (independent) all_gather
        and the block compute land in the same while-body for XLA's
        latency-hiding scheduler at any scan_unroll (>=2 additionally
        interleaves adjacent chunks, mirroring the update-scan
        overlap). The backward scan re-gathers each chunk the same way
        (reverse direction, same double buffer) for its vjp recompute,
        so at most TWO chunks' full params are ever live and no full
        parameter set exists at any point. Outer params gather once at
        the top and die with the step. Values are bit-identical to the
        replicated-storage step: the shards hold exactly the bytes the
        replicated stacks would (pack/gather is concat/slice), unless
        FLAGS_comm_quant compresses the gather leg (opt-in, lossy)."""
        from .fused_scan_step import _act_stats
        from .nonfinite_guard import all_finite

        s, o = state["s"], state["o"]
        axes, N = self._axes, self._degree
        K = self._layer_chunk
        n_layers = self.model.config.num_layers
        C = n_layers // K
        quant = self._comm_quant
        s_assign, o_assign = self._s_assign, self._o_assign
        clip_norm = self._clip_global
        guard = self._guard
        nm = self._numerics is not None
        rank = self._flat_rank()
        chunk_apply = self._chunk_apply
        b, seq = ids.shape          # LOCAL batch rows
        pos = jnp.arange(seq, dtype=ids.dtype)[None, :]
        aux_active = self._aux_active
        aux_w = self._aux_weight / n_layers

        o_full = self._gather_outer_full(o)
        fp_c = [a.reshape((C, K, -1)) for a in s["fp"]]
        nt = self._stacked_nontrainable(s)
        nt_c = tuple(d.reshape((C, K) + tuple(d.shape[1:]))
                     for _, d in nt)

        def gather_chunk(i):
            return self._gather_stacked_chunk(fp_c, i)

        def leaves_of(tr, nt_i):
            return self._leaves_of(tr, list(zip([j for j, _ in nt],
                                                nt_i)))

        # ---- forward: double-buffered gather-on-use over the chunks
        x0 = self._embed_fn(o_full, ids, pos,
                            rng_off=self._rng_base(t32, n_layers))

        def fwd_body(carry, scanned):
            if nm:
                h, cur, h_fin = carry
            else:
                (h, cur), h_fin = carry, None
            nt_i, i = scanned
            # prefetch: chunk i+1's gather is data-independent of chunk
            # i's compute below (the wrap at i=C-1 re-gathers chunk 0 —
            # one wasted gather per scan, 1/C of the param traffic)
            nxt = gather_chunk(jnp.remainder(i + 1, C))
            rng0 = self._rng_chunk_base(t32, i)
            if aux_active:
                h2, aux = chunk_apply(leaves_of(cur, nt_i), h, rng0)
            else:
                h2 = chunk_apply(leaves_of(cur, nt_i), h, rng0)
                aux = None
            ys = {"x": h}
            if aux_active:
                ys["aux"] = aux
            if not nm:
                return (h2, nxt), ys
            ys["act"], out_fin = _act_stats(h_fin, h2)
            return (h2, nxt, out_fin), ys

        g0 = gather_chunk(jnp.int32(0))
        fwd0 = ((x0, g0, jnp.isfinite(x0).all()) if nm else (x0, g0))
        fwd_c, ys = lax.scan(
            fwd_body, fwd0,
            (nt_c, jnp.arange(C)), unroll=self._scan_unroll)
        xL = fwd_c[0]
        xs, auxs = ys["x"], ys.get("aux")
        act_cols = ys.get("act")

        loss, head_vjp = jax.vjp(
            lambda od, x: self._head_fn(od, x, labels), o_full, xL)
        d_o_head, dxL = head_vjp(ct.astype(loss.dtype))
        aux_ct = None
        if aux_active:
            loss = loss + jnp.float32(aux_w) * jnp.sum(auxs)
            aux_ct = jnp.float32(aux_w) * ct.astype(jnp.float32)

        # ---- backward: re-gather each chunk (reverse double buffer)
        # for the vjp recompute; only the scattered 1/N grad shards,
        # the norm scalar and the finiteness fold survive an iteration
        G0 = tuple(jnp.zeros((C, K, bkt.numel // N), bkt.dtype)
                   for bkt in s_assign.buckets)

        def bwd_body(carry, scanned):
            dy, sq, fin, G, cur = carry
            x_i, nt_i, i = scanned
            prv = gather_chunk(jnp.remainder(i - 1 + C, C))
            rng0 = self._rng_chunk_base(t32, i)
            p_i = tuple(leaves_of(cur, nt_i))
            _, vjp = jax.vjp(
                lambda pl, xx: chunk_apply(pl, xx, rng0), p_i, x_i)
            dp, dx = vjp((dy, aux_ct) if aux_active else dy)
            newG = []
            c_sq = jnp.float32(0.0)
            c_fin = jnp.bool_(True)
            for bkt in s_assign.buckets:
                flat = pack_flat(lambda j: dp[j], bkt, lead=(K,))
                gs = scatter_flat(flat, axes, N, quant)  # [K, F/N]
                # clip carry + monitor row share one shard reduction
                # (ISSUE 15 dedup; see the replicated _grads)
                if clip_norm is not None or nm:
                    nc = self._shard_of(self._s_hp[bkt.index][3], rank,
                                        bkt.numel // N)
                    ct_b, mt_b = self._clip_monitor_sq(
                        gs, nc, clip_norm is not None, nm)
                    if ct_b is not None:
                        sq = sq + ct_b
                    if nm:
                        c_sq = c_sq + mt_b
                if guard is not None:
                    # exact isfinite for the guard's skip decision
                    b_fin = all_finite([gs])
                    c_fin = c_fin & b_fin
                    fin = fin & b_fin
                newG.append(lax.dynamic_update_index_in_dim(
                    G[bkt.index], gs, i, 0))
            row = None
            if nm:
                if guard is None:
                    c_fin = jnp.isfinite(c_sq)   # no extra grad pass
                row = jnp.stack([
                    c_sq, (~c_fin).astype(jnp.float32),
                    jnp.float32(0.0)])
            return (dx, sq, fin, tuple(newG), prv), row

        (dx0, sq, fin, G, _), grad_cols = lax.scan(
            bwd_body,
            (dxL, jnp.float32(0.0), jnp.bool_(True), G0,
             gather_chunk(jnp.int32(C - 1))),
            (xs, nt_c, jnp.arange(C)), reverse=True,
            unroll=self._scan_unroll)

        # ---- outer grads: same pack + reduce-scatter as replicated
        _, emb_vjp = jax.vjp(
            lambda od: self._embed_fn(
                od, ids, pos,
                rng_off=self._rng_base(t32, n_layers)), o_full)
        (d_o_emb,) = emb_vjp(dx0)
        o_gs = []
        o_sq = jnp.float32(0.0)
        o_fin = jnp.bool_(True)
        for bkt in o_assign.buckets:
            flat = pack_flat(
                lambda j: (d_o_head[j].astype(jnp.float32)
                           + d_o_emb[j].astype(jnp.float32)),
                bkt)
            gs = scatter_flat(flat, axes, N, quant)      # [F/N]
            if clip_norm is not None or nm:
                nc = self._shard_of(self._o_hp[bkt.index][3], rank,
                                    bkt.numel // N)
                ct_b, mt_b = self._clip_monitor_sq(
                    gs, nc, clip_norm is not None, nm)
                if ct_b is not None:
                    sq = sq + ct_b
                if nm:
                    o_sq = o_sq + mt_b
            if guard is not None:
                b_fin = all_finite([gs])
                o_fin = o_fin & b_fin
                fin = fin & b_fin
            o_gs.append(gs)
        nrows = None
        if nm:
            if guard is None:
                o_fin = jnp.isfinite(o_sq)       # no extra grad pass
            nrows = {"grad": grad_cols, "act": act_cols,
                     "outer": jnp.stack([
                         o_sq, (~o_fin).astype(jnp.float32)])}
        return loss, G, o_gs, sq, fin, nrows

    def _build(self):
        opt = self._opt
        mesh, N = self._mesh, self._degree
        axes = self._axes
        K = self._layer_chunk
        n_layers = self.model.config.num_layers
        C = n_layers // K
        s_assign, o_assign = self._s_assign, self._o_assign
        inv_n = 1.0 / N
        self._build_prologue()
        s_hp, o_hp = self._s_hp, self._o_hp
        t_idx = self._t_idx
        cv = self._clip_value
        clip_norm = self._clip_global
        guard = self._guard
        scaling = guard is not None and guard.scaling
        nm = self._numerics is not None
        shard_of = self._shard_of

        def g_shard_f32(gs, nc_shard, scale, inv_s=None):
            """Scatter output -> the fp32 gradient the update consumes:
            1/N for the data-parallel mean (and the uniform replication
            factor the joint mp/pp vjp carries), loss-scale unscale,
            value clip, global-norm scale (need_clip-masked)."""
            g32 = gs.astype(jnp.float32) * inv_n
            if inv_s is not None:
                g32 = g32 * inv_s
            if cv is not None:
                clipped = jnp.clip(g32, cv[0], cv[1])
                g32 = (clipped if nc_shard is None
                       else nc_shard * clipped + (1 - nc_shard) * g32)
            if scale is not None:
                eff = (scale if nc_shard is None
                       else nc_shard * scale + (1 - nc_shard))
                g32 = g32 * eff
            return g32

        def adam_shard(pv, g32, m, v, lr_lrs, tf, wd, l2):
            if not (isinstance(l2, float) and l2 == 0.0):
                g32 = g32 + l2 * pv.astype(jnp.float32)
            return opt._adam_math(pv, g32, m, v, None, lr_lrs, tf, wd)

        from ..nn.functional.flash_attention import attention_segments

        def _assemble_stats(nrows, pu_cols, o_p_sq, o_u_sq, inv_s):
            """The [1, C+1, NFIELDS] per-rank numerics partial
            (ISSUE 15): the leading length-1 axis carries the
            reduction-axis out_spec, so the mesh STACKS rank partials
            (no collective) and the host fold sums them."""
            from ..observability import numerics as _num

            g_cols, act, og = nrows["grad"], nrows["act"], nrows["outer"]
            g_sq, og_sq = g_cols[:, 0], og[0]
            if inv_s is not None:
                s2 = inv_s * inv_s    # shard grads carried the scale
                g_sq = g_sq * s2
                og_sq = og_sq * s2
            # sums are per-rank partials: every sq/count/flag field
            # folds by addition at readback time
            return _num.assemble_stats(
                g_sq, pu_cols[:, 0], pu_cols[:, 1], act[:, 0],
                act[:, 1], g_cols[:, 1], act[:, 2], g_cols[:, 2],
                outer=_num.outer_row(og_sq, o_p_sq, o_u_sq,
                                     og[1]))[None]

        def step_fn(state, lr, ids, labels, seg=None):
            s, o = state["s"], state["o"]
            saved_buf = self._bind(self._buffers, state["buf"])
            # packed-sequence segment ids (local batch rows, sharded
            # like ids) published to the in-scan attention layers
            seg_ctx = attention_segments(seg)
            seg_ctx.__enter__()
            try:
                gst = state.get("guard")
                inv_s = (1.0 / gst["scale"]) if scaling else None
                t = state["step"] + 1
                tf = t.astype(jnp.float32)
                t32 = t.astype(jnp.int32)
                rank = self._flat_rank()
                ct = (gst["scale"] if scaling
                      else jnp.ones((), jnp.float32))

                loss, G, o_gs, sq, fin, nrows = self._grads(
                    state, ids, labels, t32, ct)
                sharded_storage = self._param_storage == "sharded"
                if not sharded_storage:
                    sp_c = tuple(a.reshape((C, K) + tuple(a.shape[1:]))
                                 for a in s["p"])

                # ---- the fused global-norm clip + cross-rank found_inf:
                # still ONE scalar all-reduce (a length-2 psum when the
                # guard is on — norm and finiteness ride together)
                scale = None
                found = None
                if clip_norm is not None or guard is not None:
                    bad_local = (jnp.float32(0.0) if guard is None
                                 else (~fin).astype(jnp.float32))
                    tot = lax.psum(jnp.stack([sq, bad_local]), axes)
                    if guard is not None:
                        found = tot[1] > 0
                    if clip_norm is not None:
                        # shard grads carry the loss scale: true norm is
                        # sqrt(psum(sq))/loss_scale
                        gnorm = jnp.sqrt(tot[0])
                        if inv_s is not None:
                            gnorm = gnorm * inv_s
                        scale = jnp.minimum(
                            jnp.float32(clip_norm)
                            / jnp.maximum(gnorm, 1e-12), 1.0)

                # ---- update scan: sharded Adam on each chunk's grad
                # shard. Replicated storage then all_gathers the updated
                # shard back into the replicated param stacks (bucket
                # b's gather is independent of bucket b+1's math — the
                # overlap the HLO probe checks for); sharded storage
                # just WRITES the shard back — the gather moved to the
                # next step's forward (gather-on-use).
                sM = [m.reshape((C, K, -1)) for m in s["m"]]
                sV = [v.reshape((C, K, -1)) for v in s["v"]]
                sMW = [mw.reshape((C, K, -1)) if mw is not None else None
                       for mw in s["mw"]]
                if sharded_storage:
                    FP0 = [a.reshape((C, K, -1)) for a in s["fp"]]

                    def upd_body_sharded(carry, i):
                        FP, M, V, MW = carry
                        p_sq = u_sq = jnp.float32(0.0)
                        for bkt in s_assign.buckets:
                            bi = bkt.index
                            shard_len = bkt.numel // N
                            wd, l2, lrs, nc = (
                                shard_of(h, rank, shard_len)
                                for h in s_hp[bi])
                            g32 = g_shard_f32(
                                lax.dynamic_index_in_dim(
                                    G[bi], i, keepdims=False),
                                nc, scale, inv_s)
                            m_i = lax.dynamic_index_in_dim(
                                M[bi], i, keepdims=False)
                            v_i = lax.dynamic_index_in_dim(
                                V[bi], i, keepdims=False)
                            if MW[bi] is not None:
                                pv = lax.dynamic_index_in_dim(
                                    MW[bi], i, keepdims=False)
                            else:
                                # fp32-stored params ARE the master, and
                                # the stored shard IS this rank's slice
                                pv = lax.dynamic_index_in_dim(
                                    FP[bi], i, keepdims=False)
                            out32, mn, vn, _ = adam_shard(
                                pv, g32, m_i, v_i, lr * lrs, tf, wd, l2)
                            if found is not None:
                                # bad step: the stored shard passes
                                # through bit-identical (no rebuild
                                # needed — storage IS the shard)
                                out32 = jnp.where(found, pv, out32)
                                mn = jnp.where(found, m_i, mn)
                                vn = jnp.where(found, v_i, vn)
                            if nm:
                                pv32 = pv.astype(jnp.float32)
                                p_sq = p_sq + jnp.sum(jnp.square(pv32))
                                u_sq = u_sq + jnp.sum(jnp.square(
                                    out32.astype(jnp.float32) - pv32))
                            M[bi] = lax.dynamic_update_index_in_dim(
                                M[bi], mn.astype(M[bi].dtype), i, 0)
                            V[bi] = lax.dynamic_update_index_in_dim(
                                V[bi], vn.astype(V[bi].dtype), i, 0)
                            if MW[bi] is not None:
                                MW[bi] = lax.dynamic_update_index_in_dim(
                                    MW[bi], out32, i, 0)
                            FP[bi] = lax.dynamic_update_index_in_dim(
                                FP[bi], out32.astype(bkt.dtype), i, 0)
                        return (FP, M, V, MW), (
                            jnp.stack([p_sq, u_sq]) if nm else {})

                    (FP, sM, sV, sMW), pu_cols = lax.scan(
                        upd_body_sharded,
                        (list(FP0), list(sM), list(sV), list(sMW)),
                        jnp.arange(C), unroll=self._scan_unroll)
                    new_sp = list(s["p"])
                    new_s_fp = [a.reshape((n_layers, -1)) for a in FP]

                    # ---- outer update (no scan): shard in, shard out
                    new_op = list(o["p"])
                    new_o_fp = []
                    new_om, new_ov, new_omw = [], [], []
                    o_p_sq = o_u_sq = jnp.float32(0.0)
                    for bkt in o_assign.buckets:
                        bi = bkt.index
                        shard_len = bkt.numel // N
                        wd, l2, lrs, nc = (shard_of(h, rank, shard_len)
                                           for h in o_hp[bi])
                        g32 = g_shard_f32(o_gs[bi], nc, scale, inv_s)
                        m_i, v_i = o["m"][bi], o["v"][bi]
                        pv = (o["mw"][bi] if o["mw"][bi] is not None
                              else o["fp"][bi])
                        out32, mn, vn, _ = adam_shard(
                            pv, g32, m_i, v_i, lr * lrs, tf, wd, l2)
                        if found is not None:
                            out32 = jnp.where(found, pv, out32)
                            mn = jnp.where(found, m_i, mn)
                            vn = jnp.where(found, v_i, vn)
                        if nm:
                            pv32 = pv.astype(jnp.float32)
                            o_p_sq = o_p_sq + jnp.sum(jnp.square(pv32))
                            o_u_sq = o_u_sq + jnp.sum(jnp.square(
                                out32.astype(jnp.float32) - pv32))
                        new_om.append(mn.astype(m_i.dtype))
                        new_ov.append(vn.astype(v_i.dtype))
                        new_omw.append(out32 if o["mw"][bi] is not None
                                       else None)
                        new_o_fp.append(out32.astype(bkt.dtype))

                    new_state = {
                        "s": {"p": new_sp, "fp": new_s_fp,
                              "m": [m.reshape((n_layers, -1))
                                    for m in sM],
                              "v": [v.reshape((n_layers, -1))
                                    for v in sV],
                              "mw": [mw.reshape((n_layers, -1))
                                     if mw is not None else None
                                     for mw in sMW]},
                        "o": {"p": new_op, "fp": new_o_fp,
                              "m": new_om, "v": new_ov, "mw": new_omw},
                        "buf": state["buf"],
                        "step": (t if found is None
                                 else jnp.where(found, state["step"],
                                                t)),
                    }
                    if guard is not None:
                        new_state["guard"] = guard.update(gst, found)
                    loss_out = lax.psum(loss, axes) * inv_n
                    if not nm:
                        return loss_out, new_state
                    return loss_out, new_state, _assemble_stats(
                        nrows, pu_cols, o_p_sq, o_u_sq, inv_s)

                P_tr0 = tuple(sp_c[j] for j, _ in self._s_train)

                def upd_body(carry, i):
                    P_tr, M, V, MW = carry
                    p_sq = u_sq = jnp.float32(0.0)
                    for bkt in s_assign.buckets:
                        bi = bkt.index
                        shard_len = bkt.numel // N
                        wd, l2, lrs, nc = (shard_of(h, rank, shard_len)
                                           for h in s_hp[bi])
                        g32 = g_shard_f32(
                            lax.dynamic_index_in_dim(G[bi], i,
                                                     keepdims=False),
                            nc, scale, inv_s)
                        m_i = lax.dynamic_index_in_dim(M[bi], i,
                                                       keepdims=False)
                        v_i = lax.dynamic_index_in_dim(V[bi], i,
                                                       keepdims=False)
                        if MW[bi] is not None:
                            pv = lax.dynamic_index_in_dim(
                                MW[bi], i, keepdims=False)
                        else:
                            # fp32-stored params ARE the master: slice
                            # this rank's shard out of the replicated
                            # chunk (bit-exact round trip via the
                            # gather below)
                            flat_p = pack_flat(
                                lambda j: lax.dynamic_index_in_dim(
                                    P_tr[t_idx[j]], i, keepdims=False),
                                bkt, lead=(K,))
                            pv = lax.dynamic_slice_in_dim(
                                flat_p, rank * shard_len, shard_len, 1)
                        out32, mn, vn, _ = adam_shard(
                            pv, g32, m_i, v_i, lr * lrs, tf, wd, l2)
                        if found is not None:
                            # bad step: shard passes through bit-
                            # identical; the gather below then rebuilds
                            # the OLD params exactly (astype(master) is
                            # the same deterministic cast that produced
                            # them)
                            out32 = jnp.where(found, pv, out32)
                            mn = jnp.where(found, m_i, mn)
                            vn = jnp.where(found, v_i, vn)
                        if nm:
                            pv32 = pv.astype(jnp.float32)
                            p_sq = p_sq + jnp.sum(jnp.square(pv32))
                            u_sq = u_sq + jnp.sum(jnp.square(
                                out32.astype(jnp.float32) - pv32))
                        M[bi] = lax.dynamic_update_index_in_dim(
                            M[bi], mn.astype(M[bi].dtype), i, 0)
                        V[bi] = lax.dynamic_update_index_in_dim(
                            V[bi], vn.astype(V[bi].dtype), i, 0)
                        if MW[bi] is not None:
                            MW[bi] = lax.dynamic_update_index_in_dim(
                                MW[bi], out32, i, 0)
                        full = gather_flat(
                            out32.astype(bkt.dtype), axes,
                            axis=1)                         # [K, F]
                        for e_key, leaf in unpack_flat(full, bkt).items():
                            tj = t_idx[e_key]
                            P_tr = P_tr[:tj] + (
                                lax.dynamic_update_index_in_dim(
                                    P_tr[tj],
                                    leaf.astype(P_tr[tj].dtype), i, 0),
                            ) + P_tr[tj + 1:]
                    return (P_tr, M, V, MW), (
                        jnp.stack([p_sq, u_sq]) if nm else {})

                (P_tr, sM, sV, sMW), pu_cols = lax.scan(
                    upd_body, (P_tr0, list(sM), list(sV), list(sMW)),
                    jnp.arange(C), unroll=self._scan_unroll)

                new_sp = list(s["p"])
                for tj, (j, _) in enumerate(self._s_train):
                    new_sp[j] = P_tr[tj].reshape(
                        (-1,) + tuple(P_tr[tj].shape[2:]))

                # ---- outer update (no scan)
                new_op = list(o["p"])
                new_om, new_ov, new_omw = [], [], []
                o_p_sq = o_u_sq = jnp.float32(0.0)
                for bkt in o_assign.buckets:
                    bi = bkt.index
                    shard_len = bkt.numel // N
                    wd, l2, lrs, nc = (shard_of(h, rank, shard_len)
                                       for h in o_hp[bi])
                    g32 = g_shard_f32(o_gs[bi], nc, scale, inv_s)
                    m_i, v_i = o["m"][bi], o["v"][bi]
                    if o["mw"][bi] is not None:
                        pv = o["mw"][bi]
                    else:
                        flat_p = pack_flat(lambda j: o["p"][j], bkt)
                        pv = lax.dynamic_slice_in_dim(
                            flat_p, rank * shard_len, shard_len, 0)
                    out32, mn, vn, _ = adam_shard(
                        pv, g32, m_i, v_i, lr * lrs, tf, wd, l2)
                    if found is not None:
                        out32 = jnp.where(found, pv, out32)
                        mn = jnp.where(found, m_i, mn)
                        vn = jnp.where(found, v_i, vn)
                    if nm:
                        pv32 = pv.astype(jnp.float32)
                        o_p_sq = o_p_sq + jnp.sum(jnp.square(pv32))
                        o_u_sq = o_u_sq + jnp.sum(jnp.square(
                            out32.astype(jnp.float32) - pv32))
                    new_om.append(mn.astype(m_i.dtype))
                    new_ov.append(vn.astype(v_i.dtype))
                    new_omw.append(out32 if o["mw"][bi] is not None
                                   else None)
                    full = gather_flat(out32.astype(bkt.dtype), axes,
                                       axis=0)
                    for e_key, leaf in unpack_flat(full, bkt).items():
                        new_op[e_key] = leaf.astype(
                            o["p"][e_key].dtype)

                new_state = {
                    "s": {"p": new_sp,
                          "m": [m.reshape((n_layers, -1)) for m in sM],
                          "v": [v.reshape((n_layers, -1)) for v in sV],
                          "mw": [mw.reshape((n_layers, -1))
                                 if mw is not None else None
                                 for mw in sMW]},
                    "o": {"p": new_op, "m": new_om, "v": new_ov,
                          "mw": new_omw},
                    "buf": state["buf"],
                    "step": (t if found is None
                             else jnp.where(found, state["step"], t)),
                }
                if guard is not None:
                    new_state["guard"] = guard.update(gst, found)
                # loss identical across mp/pp ranks -> the axis-tuple
                # psum over-counts by exactly the replication factor the
                # inv_n (= 1/(dp*mp)) divides back out: a dp-mean
                loss_out = lax.psum(loss, axes) * inv_n
                if not nm:
                    return loss_out, new_state
                return loss_out, new_state, _assemble_stats(
                    nrows, pu_cols, o_p_sq, o_u_sq, inv_s)
            finally:
                seg_ctx.__exit__(None, None, None)
                self._bind(self._buffers, saved_buf)

        specs = self._state_specs()
        batch_spec = P(self._batch_axes if len(self._batch_axes) > 1
                       else self._axis, None)
        # numerics partials stack over the FLATTENED reduction axes
        # (ISSUE 15: stats never psum — the host fold sums rank
        # partials, so the monitor adds zero collectives)
        stats_ax = self._axes if len(self._axes) > 1 else self._axis
        out_specs = ((P(), specs) if not nm
                     else (P(), specs, P(stats_ax)))
        # the trailing batch_spec covers the optional segment-id arg —
        # a None there is an empty pytree, so the spec binds no leaves
        wrapped = jax.shard_map(
            step_fn, mesh=mesh,
            in_specs=(specs, P(), batch_spec, batch_spec, batch_spec),
            out_specs=out_specs, check_vma=False)
        from .compile_cache import cached_jit

        self._jitted = cached_jit(wrapped,
                                  donate_argnums=(0,),
                                  label=type(self).__name__)

    def grads_probe(self, ids, labels):
        """Test/debug surface: run ONLY the grads pass and return
        (loss, stacked_grads, outer_grads) as FULL (gathered, 1/N-
        normalized = dp-mean) fp32 flat buckets — stacked_grads[b] is
        [C, K, bucket.numel], outer_grads[b] is [bucket.numel]. Lets
        tests compare gradient content across mesh layouts without
        reverse-engineering shard layouts. Not used by training."""
        from ..framework.tensor import Tensor

        self.ensure_built()
        self._pre_step()
        state = self._extract_state()
        ids_d = ids._data if isinstance(ids, Tensor) else ids
        lab_d = labels._data if isinstance(labels, Tensor) else labels
        specs = self._state_specs()
        axes = self._axes
        inv = 1.0 / self._degree
        ns = len(self._s_assign.buckets)
        no = len(self._o_assign.buckets)

        def fn(state, ids, labels):
            saved_buf = self._bind(self._buffers, state["buf"])
            try:
                t32 = state["step"].astype(jnp.int32) + 1
                ct = jnp.ones((), jnp.float32)
                loss, G, o_gs, _, _, _ = self._grads(state, ids,
                                                     labels, t32, ct)
                Gf = tuple(
                    gather_flat(g.astype(jnp.float32) * inv, axes,
                                axis=g.ndim - 1) for g in G)
                of = tuple(
                    gather_flat(g.astype(jnp.float32) * inv, axes,
                                axis=0) for g in o_gs)
                return lax.psum(loss, axes) * inv, Gf, of
            finally:
                self._bind(self._buffers, saved_buf)

        batch_spec = P(self._batch_axes if len(self._batch_axes) > 1
                       else self._axis, None)
        wrapped = jax.shard_map(
            fn, mesh=self._mesh,
            in_specs=(specs, batch_spec, batch_spec),
            out_specs=(P(), (P(),) * ns, (P(),) * no),
            check_vma=False)
        with self._step_guard():
            return jax.jit(wrapped)(state, ids_d, lab_d)

    def _pre_step(self):
        if self._param_storage == "sharded":
            self._repack_dirty_param_buckets()

    def _step_guard(self):
        if self._param_storage == "sharded":
            return _raw_param_access()
        return super()._step_guard()

    def __call__(self, ids, labels, segment_ids=None):
        shape = getattr(ids, "shape", None)
        if shape and shape[0] % self._batch_degree:
            raise ValueError(
                f"global batch {shape[0]} is not divisible by the "
                f"batch-axis degree {self._batch_degree} "
                f"(axes {self._batch_axes})")
        # host-side fault points (ISSUE 19): a scripted straggler /
        # crash fires BEFORE the compiled step dispatches, so an
        # injected failure never leaves donated buffers half-consumed
        from ..observability import faults

        faults.maybe_delay("train.step.straggler")
        faults.maybe_raise("train.step.crash")
        return super().__call__(ids, labels, segment_ids=segment_ids)


# ---------------------------------------------------------------------------
# selection wiring (group_sharded / fleet distributed_model entry points)
# ---------------------------------------------------------------------------

def select_train_step(model, optimizer, criterion=None, mesh=None,
                      axis=None, auto=False, global_batch=None,
                      hbm_gb=16.0, **kw):
    """The train-step chooser (GroupShardedStage2 / fleet
    ShardingParallel / TensorParallel / PipelineParallel entry point).

    Explicit mesh: scan_layers GPT dispatches by the mesh's active axes
    — a >1 ``pp`` axis -> `PipelineScanTrainStep`, a >1 ``mp`` axis ->
    `ShardedFusedScanTrainStep` in dp×mp mode, a >1 dp/sharding axis ->
    the dp-only sharded scan, degree 1 -> `FusedScanTrainStep`;
    non-scan models get the generic `TrainStep`.

    ``auto=True`` promotes the validated cost-model planner to the
    decision-maker (ISSUE 8): given the model + ``global_batch`` and
    the available device count, `auto_tuner.pick_layout` prunes the
    (dp, mp, pp, micro) grid with the reference feasibility rules,
    ranks survivors with `estimate_step_ms` under cached
    backend-calibrated constants, BUILDS the winning mesh (installed
    via `distributed.env.set_mesh`) and returns the matching step with
    the sweep-calibrated scan_unroll/layer_chunk. The
    ``PADDLE_HYBRID_LAYOUT`` env override is honored. The decision
    record lands on ``step.layout_decision``.
    """
    from ..distributed import env as denv
    from ..models.gpt import GPTStackedBlocks

    layers = _unwrap_layers(model)
    blocks = getattr(getattr(layers, "gpt", None), "blocks", None)
    scan = isinstance(blocks, GPTStackedBlocks)

    if auto:
        if not scan:
            raise ValueError(
                "select_train_step(auto=True) plans layouts for "
                "scan_layers GPT models; build with "
                "GPTConfig(scan_layers=True)")
        if global_batch is None:
            raise ValueError(
                "auto layout planning needs global_batch (the pruning "
                "rules and the cost model are batch-dependent)")
        import jax as _jax

        from ..distributed.auto_tuner.select import (
            calibrate_backend_cached, pick_layout, spec_of_model,
        )

        if mesh is not None:
            devices = list(mesh.devices.flat)
        else:
            devices = list(_jax.devices())
        spec = spec_of_model(layers.config, global_batch=global_batch)
        backend = calibrate_backend_cached(devices)
        decision = pick_layout(spec, len(devices), hbm_gb=hbm_gb,
                               backend=backend)
        cand = decision["candidate"]
        mesh = denv.build_mesh(decision["mesh_degrees"], devices=devices)
        denv.set_mesh(mesh)
        step_kw = dict(kw)
        step_kw.setdefault("scan_unroll", decision["scan_unroll"])
        step_kw.setdefault("layer_chunk", decision["layer_chunk"])
        step_kw.setdefault("comm_bucket_mb", decision["comm_bucket_mb"])
        if cand.pp > 1:
            from .pipeline_step import PipelineScanTrainStep

            step = PipelineScanTrainStep(
                layers, optimizer, criterion=criterion, mesh=mesh,
                axis="dp", pp_axis="pp",
                num_micro=decision["num_micro"], **step_kw)
        elif cand.degree > 1:
            step = ShardedFusedScanTrainStep(
                layers, optimizer, criterion=criterion, mesh=mesh,
                axis="dp", mp_axis="mp" if cand.mp > 1 else None,
                ep_axis="ep" if getattr(cand, "ep", 1) > 1 else None,
                **step_kw)
        else:
            step = FusedScanTrainStep(
                layers, optimizer, criterion=criterion,
                **{k: v for k, v in step_kw.items()
                   if k in ("fused_head", "compute_dtype",
                            "layer_chunk", "scan_unroll",
                            "numerics")})
        step.layout_decision = decision
        return step

    if mesh is None and denv.is_initialized():
        mesh = denv.get_mesh()
    degree = mp_degree = pp_degree = ep_degree = 1
    mp_axis = pp_axis = ep_axis = None
    if mesh is not None:
        if axis is None:
            axis = next((a for a in ("sharding", "dp")
                         if a in mesh.axis_names and mesh.shape[a] > 1),
                        None)
        if axis is not None:
            degree = int(mesh.shape[axis])
        if "mp" in mesh.axis_names and int(mesh.shape["mp"]) > 1 \
                and axis != "mp":
            mp_axis, mp_degree = "mp", int(mesh.shape["mp"])
        if "pp" in mesh.axis_names and int(mesh.shape["pp"]) > 1 \
                and axis != "pp":
            pp_axis, pp_degree = "pp", int(mesh.shape["pp"])
        if "ep" in mesh.axis_names and int(mesh.shape["ep"]) > 1 \
                and axis != "ep" \
                and getattr(getattr(layers, "config", None),
                            "num_experts", 0):
            ep_axis, ep_degree = "ep", int(mesh.shape["ep"])
    if scan and pp_degree > 1:
        from .pipeline_step import PipelineScanTrainStep

        if axis is None:
            # a degree-1 dp/sharding axis still names the batch axis; a
            # mesh with NEITHER cannot place the batch — say so rather
            # than let the constructor trip over a duplicate-axis error
            axis = next((a for a in ("sharding", "dp")
                         if a in mesh.axis_names), None)
            if axis is None:
                raise ValueError(
                    f"pp mesh {mesh.axis_names} has no dp/sharding "
                    "axis to place the batch on; build it with one "
                    "(degree 1 is fine): build_mesh({'dp': 1, "
                    "'pp': N})")
        return PipelineScanTrainStep(layers, optimizer,
                                     criterion=criterion, mesh=mesh,
                                     axis=axis, pp_axis=pp_axis,
                                     **kw)
    if scan and (degree > 1 or mp_degree > 1 or ep_degree > 1):
        if ep_degree > 1 and axis is None:
            # a dp1×epN mesh still batches over "dp" — the constructor
            # needs the (degree-1) data axis named
            axis = next((a for a in ("sharding", "dp")
                         if a in mesh.axis_names), None)
            if axis is None:
                raise ValueError(
                    f"ep mesh {mesh.axis_names} has no dp/sharding "
                    "axis to place the batch on; build it with one "
                    "(degree 1 is fine): build_mesh({'dp': 1, "
                    "'ep': N})")
        return ShardedFusedScanTrainStep(layers, optimizer,
                                         criterion=criterion, mesh=mesh,
                                         axis=axis, mp_axis=mp_axis,
                                         ep_axis=ep_axis, **kw)
    if scan:
        return FusedScanTrainStep(layers, optimizer, criterion=criterion,
                                  **{k: v for k, v in kw.items()
                                     if k in ("fused_head",
                                              "compute_dtype",
                                              "layer_chunk",
                                              "scan_unroll",
                                              "numerics")})
    from .train_step import TrainStep

    if criterion is not None:
        return TrainStep(model, lambda m, a, b: criterion(m(a), b),
                         optimizer)
    return TrainStep(model, lambda m, a, b: m.loss(a, b), optimizer)


# ---------------------------------------------------------------------------
# HLO probe program (tools/hlo_overlap.py --probe, the HLO receipts in tests/)
# ---------------------------------------------------------------------------

def build_probe_lowered(n_devices=8, scan_unroll=2, layer_chunk=1,
                        mp=1, pp=1, num_micro=2, ep=1,
                        param_storage=None):
    """Lower (not run) the sharded step for a tiny scan GPT on an
    n-device host mesh — the program the overlap checker inspects.
    ``mp``/``pp``/``ep`` > 1 build the hybrid variants (dp×mp Megatron
    sharding / the dp×pp ring pipeline / the dp×ep expert-parallel MoE
    step) instead of the dp-only step. ``param_storage`` selects the
    storage format (None = the step default, i.e. sharded)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    devs = jax.devices("cpu")[:n_devices] if jax.default_backend() == \
        "cpu" else jax.devices()[:n_devices]
    if len(devs) < n_devices:
        raise RuntimeError(
            f"{len(devs)} devices < {n_devices} "
            "(set --xla_force_host_platform_device_count)")
    from jax.sharding import Mesh

    if sum(int(d) > 1 for d in (mp, pp, ep)) > 1:
        raise NotImplementedError("combined mp×pp×ep probe")
    if mp > 1:
        dp = n_devices // mp
        mesh = Mesh(np.asarray(devs).reshape(dp, mp), ("dp", "mp"))
    elif pp > 1:
        dp = n_devices // pp
        mesh = denv.build_mesh({"dp": dp, "pp": pp}, devices=devs)
    elif ep > 1:
        dp = n_devices // ep
        mesh = Mesh(np.asarray(devs).reshape(dp, ep), ("dp", "ep"))
    else:
        mesh = Mesh(np.asarray(devs), ("sharding",))
    denv.set_mesh(mesh)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                    num_attention_heads=2, max_position_embeddings=32,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    scan_layers=True,
                    num_experts=(2 * ep if ep > 1 else 0))
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters(),
                     grad_clip=nn.ClipGradByGlobalNorm(1.0))
    if pp > 1:
        from .pipeline_step import PipelineScanTrainStep

        step = PipelineScanTrainStep(model, opt, mesh=mesh, axis="dp",
                                     pp_axis="pp", num_micro=num_micro,
                                     scan_unroll=scan_unroll,
                                     layer_chunk=layer_chunk,
                                     param_storage=param_storage)
    else:
        step = ShardedFusedScanTrainStep(
            model, opt, mesh=mesh,
            axis="dp" if (mp > 1 or ep > 1) else "sharding",
            mp_axis="mp" if mp > 1 else None,
            ep_axis="ep" if ep > 1 else None,
            scan_unroll=scan_unroll, layer_chunk=layer_chunk,
            param_storage=param_storage)
    step.ensure_built()
    state = step._extract_state()
    lr = jnp.float32(1e-3)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (n_devices, 16)),
                      jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (n_devices, 16)), jnp.int32)
    with step._step_guard():
        return step._jitted.lower(state, lr, ids, labels, None)
