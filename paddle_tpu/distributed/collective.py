"""Collective communication API.

Reference parity: python/paddle/distributed/communication/ (15 files) +
Group management (python/paddle/distributed/collective.py:151-180) over
ProcessGroupNCCL (paddle/phi/core/distributed/collective/process_group.h:48).

TPU-first: a Group is a set of named mesh axes on the global Mesh. Each
collective has two modes:

- **traced** (inside `shard_map`/pjit): lowers directly to the XLA
  collective (`lax.psum` / `all_gather` / `psum_scatter` / `all_to_all` /
  `ppermute`) over ICI with replica groups from the axis — the
  ProcessGroupXLA north star of SURVEY.md §5.8.
- **eager** (single-controller): wraps the same lax op in a `shard_map` over
  the group's axes. A replicated input behaves like "every rank holds this
  value" (reference per-rank semantics); an input sharded over the group
  axis uses its true per-device shards.

All collectives record on the autograd tape (they are jax-differentiable),
matching the reference's PyLayer comm ops (fleet/layers/mpu/mp_ops.py:91-341).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map

from ..framework.tensor import Tensor
from ..framework.autograd import apply_op
from . import env


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group = named axes of the global mesh (reference
    Group, python/paddle/distributed/communication/group.py)."""

    _next_id = 0

    def __init__(self, mesh: Mesh, axes, name=None):
        self.mesh = mesh
        self.axes = tuple(axes) if not isinstance(axes, str) else (axes,)
        for a in self.axes:
            if a not in mesh.axis_names:
                raise ValueError(f"axis {a!r} not in mesh {mesh.axis_names}")
        Group._next_id += 1
        self.id = Group._next_id
        self.name = name or f"group_{self.id}"

    @property
    def nranks(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))

    world_size = nranks

    @property
    def rank(self) -> int:
        """Single-controller semantics: one Python process drives ALL group
        ranks, so "my rank" is only meaningful per host process. Returns 0
        single-host (parity with reference rank-0 driver code); multi-host
        SPMD returns the group index of the first device this process owns,
        so per-rank branches (logging, checkpoint writes) stay correct."""
        import jax

        if jax.process_count() == 1:
            return 0
        me = jax.process_index()
        mesh_axes = list(self.mesh.axis_names)
        group_dims = [self.mesh.shape[a] for a in self.axes]
        it = np.nditer(self.mesh.devices, flags=["multi_index", "refs_ok"])
        for _ in it:
            d = self.mesh.devices[it.multi_index]
            if d.process_index == me:
                # project the mesh coordinate onto the GROUP's axes and
                # linearize — a flat mesh index would exceed nranks-1 for
                # sub-axis groups
                coord = [it.multi_index[mesh_axes.index(a)]
                         for a in self.axes]
                rank = 0
                for c, dim in zip(coord, group_dims):
                    rank = rank * dim + int(c)
                return rank
        return -1  # this process owns no device of the group

    @property
    def process_ids(self):
        return list(range(self.nranks))

    ranks = process_ids

    def get_group_rank(self, rank):
        return rank if 0 <= rank < self.nranks else -1

    def __repr__(self):
        return f"Group(axes={self.axes}, nranks={self.nranks})"


_default_group = None


def _world_group() -> Group:
    global _default_group
    mesh = env.get_mesh()
    if _default_group is None or _default_group.mesh is not mesh:
        _default_group = Group(mesh, mesh.axis_names, name="world")
    return _default_group


def get_group(gid=None) -> Group:
    return _world_group()


def new_group(ranks=None, backend=None, timeout=None, axes=None, mesh=None) -> Group:
    """Reference collective.py:151 new_group. TPU-native extension: pass
    `axes=` to bind the group to mesh axes (the common case via topology);
    explicit `ranks` builds a 1-axis sub-mesh over those devices."""
    mesh = mesh or env.get_mesh()
    if axes is not None:
        return Group(mesh, axes)
    flat = list(mesh.devices.flat)
    if ranks is None or len(ranks) == len(flat):
        return _world_group()
    sub = np.asarray([flat[r] for r in ranks])
    return Group(Mesh(sub, ("sub",)), ("sub",))


def _axis_bound(axis: str) -> bool:
    """True when called inside a shard_map/pmap context binding `axis`."""
    try:
        jax.lax.axis_index(axis)
        return True
    except Exception:
        return False


def _group_axes(group) -> tuple:
    group = group or _world_group()
    return group.axes if isinstance(group, Group) else tuple(group)


def _input_spec(data, mesh) -> P:
    sh = getattr(data, "sharding", None)
    if isinstance(sh, NamedSharding) and sh.mesh.axis_names == mesh.axis_names:
        return sh.spec
    return P()


# Eager-mode composed-callable cache (VERDICT r3 weak #6): rebuilding the
# shard_map wrapper per call made every eager collective a fresh callable,
# so jax's executable cache missed and RETRACED each call — fine in tests,
# a trap in a hot eager loop. Keyed by the collective's semantic identity
# (name + baked-in args), mesh, axes and specs; jax's own cache then keys
# shapes/dtypes under the stable callable.
_eager_fn_cache: dict = {}


def _run(group, data, traced_fn, out_spec=None, cache_key=None):
    """Execute traced_fn (using lax collectives over group.axes) on `data`:
    directly if the axes are bound (already inside shard_map), else wrapped
    in an eager shard_map over the group's mesh (cached per `cache_key`)."""
    group = group or _world_group()
    axes = group.axes
    if isinstance(data, jax.core.Tracer) and _axis_bound(axes[0]):
        return traced_fn(data)
    mesh = group.mesh
    in_spec = _input_spec(data, mesh)
    o_spec = out_spec if out_spec is not None else in_spec
    if cache_key is not None:
        full_key = (cache_key, mesh, axes, in_spec, o_spec)
        fn = _eager_fn_cache.get(full_key)
        if fn is None:
            # bounded LRU instead of evict-all-other-meshes: sub-group
            # collectives (new_group sub-mesh) alternating with world-
            # group ones must not evict each other per call — that
            # silently reintroduced the per-call retrace this cache fixed
            # (ADVICE r4). Replaced meshes (elastic re-rendezvous, tests)
            # age out of the LRU instead of being evicted eagerly.
            while len(_eager_fn_cache) >= 128:
                _eager_fn_cache.pop(next(iter(_eager_fn_cache)))
            fn = jax.jit(shard_map(traced_fn, mesh=mesh,
                                   in_specs=(in_spec,),
                                   out_specs=o_spec, check_vma=False))
            _eager_fn_cache[full_key] = fn
        else:
            _eager_fn_cache[full_key] = _eager_fn_cache.pop(full_key)
        return fn(data)
    fn = shard_map(traced_fn, mesh=mesh, in_specs=(in_spec,),
                   out_specs=o_spec, check_vma=False)
    return fn(data)


def _axis_arg(axes):
    return axes if len(axes) > 1 else axes[0]


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def _reduce_traced(axes, op):
    ax = _axis_arg(axes)
    if op in (ReduceOp.SUM, "sum"):
        return lambda s: jax.lax.psum(s, ax)
    if op in (ReduceOp.MAX, "max"):
        return lambda s: jax.lax.pmax(s, ax)
    if op in (ReduceOp.MIN, "min"):
        return lambda s: jax.lax.pmin(s, ax)
    if op in (ReduceOp.AVG, "avg"):
        return lambda s: jax.lax.pmean(s, ax)
    if op in (ReduceOp.PROD, "prod"):
        return lambda s: jnp.exp(jax.lax.psum(jnp.log(s), ax))
    raise ValueError(f"unsupported reduce op {op}")


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Reference communication/all_reduce.py; in-place on `tensor`."""
    group = group or _world_group()
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)
    fn = _reduce_traced(group.axes, op)
    out = apply_op(lambda x: _run(group, x, fn,
                              cache_key=("all_reduce", str(op))),
               [t], name="all_reduce")
    t._inplace_from(out)
    return t


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # with a single controller, reduce == all_reduce (dst holds the value;
    # every device materializes it — XLA replicates for free)
    return all_reduce(tensor, op=op, group=group)


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    """Reference communication/all_gather.py: gathers per-rank tensors into
    tensor_list (stack on a new leading dim per rank). For a tiled gather
    along an existing dim use `all_gather_concat(tensor, axis=...)`."""
    if axis != 0:
        raise NotImplementedError(
            "all_gather stacks on a new leading dim (reference "
            "semantics); for a concat along an existing axis use "
            "all_gather_concat(tensor, axis=...)")
    group = group or _world_group()
    ax = _axis_arg(group.axes)
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)

    def traced(s):
        return jax.lax.all_gather(s, ax, axis=0, tiled=False)

    out = apply_op(lambda x: _run(group, x, traced, out_spec=P(),
                                  cache_key=("all_gather",)), [t],
                   name="all_gather")
    if tensor_list is not None:
        del tensor_list[:]
        for i in range(group.nranks):
            tensor_list.append(out[i])
        return tensor_list
    return out


def all_gather_concat(tensor, group=None, axis=0):
    """TPU-native helper: gather and concat along `axis` (tiled all-gather —
    what SP/mp layers actually want)."""
    group = group or _world_group()
    ax = _axis_arg(group.axes)
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)

    def traced(s):
        return jax.lax.all_gather(s, ax, axis=axis, tiled=True)

    return apply_op(lambda x: _run(group, x, traced, out_spec=P(),
                                   cache_key=("all_gather_concat",
                                              axis)), [t],
                    name="all_gather_concat")


def reduce_scatter(tensor, tensor_or_tensor_list=None, op=ReduceOp.SUM,
                   group=None, sync_op=True, axis=0):
    """Reference communication/reduce_scatter.py: sum across ranks, then
    scatter slices along dim `axis`.

    Global-view semantics (single controller): the result keeps the GLOBAL
    shape, laid out sharded over the group axis along `axis` — device i
    holds slice i. Code that wants the per-rank slice shape of the
    reference API should index the result. The in-place form therefore
    requires `tensor` to already have the global shape (ADVICE r1)."""
    group = group or _world_group()
    ax = _axis_arg(group.axes)
    src = tensor_or_tensor_list if tensor_or_tensor_list is not None else tensor
    t = src if isinstance(src, Tensor) else Tensor(src)

    def traced(s):
        return jax.lax.psum_scatter(s, ax, scatter_dimension=axis, tiled=True)

    spec_axes = [None] * t.ndim
    spec_axes[axis] = ax
    out = apply_op(
        lambda x: _run(group, x, traced, out_spec=P(*spec_axes),
                       cache_key=("reduce_scatter", str(op), axis)),
        [t],
        name="reduce_scatter",
    )
    if tensor_or_tensor_list is not None and isinstance(tensor, Tensor):
        if tuple(tensor.shape) != tuple(out.shape):
            raise ValueError(
                f"reduce_scatter out tensor has shape {tuple(tensor.shape)} "
                f"but the global-view result has shape {tuple(out.shape)}; "
                "pass a global-shaped out tensor or use the return value")
        tensor._inplace_from(out)
        return tensor
    return out


def _quantized_sum_traced(axes, nranks, qformat):
    """EQuARX-style compressed all-reduce (PAPERS.md): decompose the ring
    all-reduce into its scatter leg (all_to_all of per-destination chunks)
    and gather leg (all_gather of the locally reduced chunk) and carry BOTH
    legs' payloads compressed — int8 with symmetric per-block scales on
    each side (the "two-sided" scales: the scatter leg ships each source
    rank's block scales, the gather leg ships the reduced chunk's), or
    bf16. Accumulation is fp32 on every path, so only the wire format is
    lossy; the fp32-parity contract is asserted by `comm_quant_selftest`
    (tests/test_comm_bucketed.py)."""
    ax = _axis_arg(axes)
    n = int(nranks)
    if qformat not in ("int8", "bf16"):
        raise ValueError(
            f"unsupported comm quant format {qformat!r} (int8|bf16)")

    # scaling-block granularity, both legs (EQuARX block scaling): one
    # fp32 scale per 32 int8 payload bytes (+12.5% wire) holds the L2
    # relative error near 6e-3 at n=8 — a whole-chunk max-based scale
    # floors at ~1e-2 because one outlier sets every element's step
    QBLOCK = 32

    def _q_blocks(x, b):
        """Symmetric int8 per-block: x [..., c] -> (q int8 [..., c/b, b],
        scales fp32 [..., c/b])."""
        blocks = x.reshape(x.shape[:-1] + (x.shape[-1] // b, b))
        return quantize_symmetric_q8(blocks)

    def traced(s):
        orig_shape, orig_dtype = s.shape, s.dtype
        flat = s.astype(jnp.float32).reshape(-1)
        # pad to a multiple of n*QBLOCK so every per-rank chunk splits
        # into whole scaling blocks — padding only to n would silently
        # collapse a non-32-aligned chunk to ONE whole-chunk scale,
        # reintroducing the ~1e-2 outlier floor
        pad = (-flat.shape[0]) % (n * QBLOCK)
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        chunks = flat.reshape(n, -1)
        c = chunks.shape[1]
        assert c % QBLOCK == 0, (c, QBLOCK)   # guaranteed by the padding
        b = QBLOCK
        if qformat == "int8":
            # scatter leg: per-block scales, shipped on the same
            # all_to_all route as their chunks so they stay paired
            q, s1 = _q_blocks(chunks, b)           # [n, c/b, b], [n, c/b]
            recv = jax.lax.all_to_all(q, ax, split_axis=0, concat_axis=0)
            src_scales = jax.lax.all_to_all(s1, ax, split_axis=0,
                                            concat_axis=0)     # [n, c/b]
            red = jnp.sum(recv.astype(jnp.float32)
                          * src_scales[..., None], axis=0)     # [c/b, b]
            # gather leg: requantize the reduced chunk per block
            q2, s2 = _q_blocks(red.reshape(-1), b)
            gathered = jax.lax.all_gather(q2, ax)         # [n, c/b, b]
            out_scales = jax.lax.all_gather(s2, ax)       # [n, c/b]
            out = (gathered.astype(jnp.float32)
                   * out_scales[..., None]).reshape(-1)
        else:  # bf16
            recv = jax.lax.all_to_all(chunks.astype(jnp.bfloat16), ax,
                                      split_axis=0, concat_axis=0)
            red = jnp.sum(recv.astype(jnp.float32), axis=0)
            out = jax.lax.all_gather(red.astype(jnp.bfloat16), ax) \
                .astype(jnp.float32).reshape(-1)
        if pad:
            out = out[:-pad]
        return out.reshape(orig_shape).astype(orig_dtype)

    return traced


QUANT_SCATTER_BLOCK = 32      # int8 scaling-block, same as _quantized_sum


def quantize_symmetric_q8(x, axis=-1):
    """Symmetric int8 quantization along `axis` — THE wire/storage
    format of the comm stack (EQuARX per-block scales, PAPERS.md) and,
    since ISSUE 16, of the int8 paged KV pools (inference/kv_cache.py):
    one fp32 scale per `axis`-row, payload = round(x / scale) clipped to
    [-127, 127]. Returns (q int8, scales fp32 with `axis` removed); the
    1e-30 floor keeps all-zero rows from dividing by zero."""
    sc = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis),
                     1e-30) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32)
                           / jnp.expand_dims(sc, axis)),
                 -127, 127).astype(jnp.int8)
    return q, sc


def dequantize_q8(q, scales, axis=-1, dtype=jnp.float32):
    """Inverse of `quantize_symmetric_q8`: q * scale broadcast along
    `axis` (scales has `axis` removed)."""
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scales, axis)).astype(dtype)


def quantized_psum_scatter_traced(axis, nranks, qformat):
    """The SCATTER LEG of the compressed all-reduce above, as a traced
    psum_scatter replacement for use INSIDE shard_map (the sharded
    fused-scan step's per-layer grad reduce-scatter): per-destination
    chunks ship int8 with per-block symmetric scales (or bf16), the sum
    accumulates in fp32. Input [..., n*c] on the LAST dim (c must split
    into whole QUANT_SCATTER_BLOCKs for int8 — callers pad the flat
    layout to nranks*QUANT_SCATTER_BLOCK); returns the local reduced
    chunk [..., c], numerically ≈ lax.psum_scatter to the comm_quant
    tolerance (rel err ~7e-3 int8, bf16 rounding for bf16).

    ``axis`` may be a TUPLE of mesh axis names (ISSUE 11): the
    all_to_all then exchanges chunks over the flattened first-axis-major
    product — the same split order as tuple-axis ``lax.psum_scatter`` —
    so the dp×mp/pp/ep hybrid steps' flattened grad scatter gets the
    same wire format as the single-axis path (``nranks`` is the
    flattened product; verified against the exact tuple psum_scatter by
    ``comm_quant_multiaxis_selftest``, tests/test_sharded_storage.py)."""
    n = int(nranks)
    if isinstance(axis, (list, tuple)):
        axis = tuple(axis) if len(axis) > 1 else axis[0]
    if qformat not in ("int8", "bf16"):
        raise ValueError(
            f"unsupported comm quant format {qformat!r} (int8|bf16)")
    b = QUANT_SCATTER_BLOCK

    def traced(x):
        lead = x.shape[:-1]
        c = x.shape[-1] // n
        chunks = x.astype(jnp.float32).reshape(lead + (n, c))
        split_ax = len(lead)
        if qformat == "int8":
            if c % b:
                raise ValueError(
                    f"chunk {c} not a multiple of the {b}-wide int8 "
                    "scaling block; pad the flat layout to "
                    "nranks*QUANT_SCATTER_BLOCK")
            blocks = chunks.reshape(lead + (n, c // b, b))
            q, sc = quantize_symmetric_q8(blocks)
            recv = jax.lax.all_to_all(q, axis, split_axis=split_ax,
                                      concat_axis=split_ax)
            src_sc = jax.lax.all_to_all(sc, axis, split_axis=split_ax,
                                        concat_axis=split_ax)
            red = jnp.sum(recv.astype(jnp.float32) * src_sc[..., None],
                          axis=split_ax)
            return red.reshape(lead + (c,)).astype(x.dtype)
        recv = jax.lax.all_to_all(chunks.astype(jnp.bfloat16), axis,
                                  split_axis=split_ax,
                                  concat_axis=split_ax)
        return jnp.sum(recv.astype(jnp.float32),
                       axis=split_ax).astype(x.dtype)

    return traced


def quantized_all_gather_traced(axis, qformat, gather_axis=-1):
    """The GATHER LEG as a standalone traced collective: a tiled
    all_gather whose wire payload is int8 with symmetric per-block
    scales (or bf16) — the EQuARX gather-leg wire format applied to the
    sharded-parameter-storage gather-on-use path (ISSUE 11). Each rank
    quantizes its own shard ONCE, ships payload + scales on the same
    gather route so they stay paired, and dequantizes the concatenated
    result; there is no accumulation, so the elementwise error is
    bounded by one block's quantization step (rel err ~5e-3 int8 on
    standard-normal data, bf16 rounding for bf16).

    ``axis`` may be a tuple of mesh axes: the chunks concatenate in
    flattened first-axis-major order, identical to tuple-axis
    ``lax.all_gather(tiled=True)`` (the split order `gather_flat`
    depends on). The gathered dim (``gather_axis``, default last) must
    split into whole QUANT_SCATTER_BLOCKs for int8 — the flat-bucket
    layouts pad to nranks*QUANT_SCATTER_BLOCK already."""
    if isinstance(axis, (list, tuple)):
        axis = tuple(axis) if len(axis) > 1 else axis[0]
    if qformat not in ("int8", "bf16"):
        raise ValueError(
            f"unsupported comm quant format {qformat!r} (int8|bf16)")
    b = QUANT_SCATTER_BLOCK

    def traced(x):
        ga = gather_axis % x.ndim
        if ga != x.ndim - 1:                    # quantize blocks on last
            x = jnp.moveaxis(x, ga, -1)
        lead, c = x.shape[:-1], x.shape[-1]
        if qformat == "int8":
            if c % b:
                raise ValueError(
                    f"gather dim {c} not a multiple of the {b}-wide "
                    "int8 scaling block; pad the flat layout to "
                    "nranks*QUANT_SCATTER_BLOCK")
            blocks = x.astype(jnp.float32).reshape(lead + (c // b, b))
            q, sc = quantize_symmetric_q8(blocks)
            gq = jax.lax.all_gather(q, axis, axis=len(lead), tiled=True)
            gsc = jax.lax.all_gather(sc, axis, axis=len(lead),
                                     tiled=True)
            out = (gq.astype(jnp.float32) * gsc[..., None]).reshape(
                lead + (-1,)).astype(x.dtype)
        else:  # bf16
            g = jax.lax.all_gather(x.astype(jnp.bfloat16), axis,
                                   axis=len(lead), tiled=True)
            out = g.astype(x.dtype)
        if ga != out.ndim - 1:
            out = jnp.moveaxis(out, -1, ga)
        return out

    return traced


def comm_quant_multiaxis_selftest(qformat="int8", numel_per_rank=2048,
                                  seed=0, mesh=None, axes=None):
    """Rel-err selftest for the FLATTENED-axis-tuple compressed legs
    (ISSUE 11 satellite): on a dp×mp-shaped host mesh, the tuple-axis
    quantized scatter must match exact tuple-axis psum_scatter, and the
    tuple-axis quantized all_gather must match exact tiled all_gather,
    both within the comm_quant bound (int8 rel err < 1e-2 — same gate
    as `comm_quant_selftest`; the gather leg has no accumulation so it
    lands tighter). Every rank holds distinct data with a distinct
    magnitude so chunk/scale mispairing or a wrong flat-rank split
    order would blow the gate, not hide under symmetry."""
    if mesh is None:
        mesh = env.get_mesh()
    if axes is None:
        axes = tuple(mesh.axis_names[:2])
    axes = tuple(axes)
    degrees = [int(mesh.shape[a]) for a in axes]
    n = int(np.prod(degrees))
    b = QUANT_SCATTER_BLOCK
    c = -(-int(numel_per_rank) // b) * b
    rng = np.random.default_rng(seed)
    data = (rng.standard_normal((n, n * c))
            * (1.0 + 0.1 * np.arange(n))[:, None]).astype(np.float32)
    flat = jax.device_put(jnp.asarray(data.reshape(-1)),
                          NamedSharding(mesh, P(axes)))

    def legs(x):
        exact_s = jax.lax.psum_scatter(x, axes, scatter_dimension=0,
                                       tiled=True)
        quant_s = quantized_psum_scatter_traced(axes, n, qformat)(x)
        shard = exact_s
        exact_g = jax.lax.all_gather(shard, axes, axis=0, tiled=True)
        quant_g = quantized_all_gather_traced(axes, qformat)(shard)
        return exact_s, quant_s, exact_g, quant_g

    es, qs, eg, qg = jax.jit(shard_map(
        legs, mesh=mesh, in_specs=(P(axes),),
        out_specs=(P(axes), P(axes), P(), P()), check_vma=False))(flat)

    def rel(got, ref):
        return float(jnp.linalg.norm(got.astype(jnp.float32)
                                     - ref.astype(jnp.float32))) / max(
            float(jnp.linalg.norm(ref.astype(jnp.float32))), 1e-30)

    r_s, r_g = rel(qs, es), rel(qg, eg)
    return {"qformat": qformat, "axes": list(axes),
            "degrees": degrees, "nranks": n,
            "scatter_rel_err": r_s, "gather_rel_err": r_g,
            "pass": bool(r_s < 1e-2 and r_g < 1e-2)}


def all_reduce_quantized(tensor, op=ReduceOp.SUM, group=None, qformat=None,
                         sync_op=True):
    """Compressed all_reduce (SUM only); in-place on `tensor` like
    all_reduce. `qformat` defaults to FLAGS_comm_quant; with the flag unset
    ('') this is exactly all_reduce — the compressed path is opt-in."""
    if qformat is None:
        from ..utils import flags as _flags

        qformat = _flags.get_flag("FLAGS_comm_quant") or ""
    if not qformat:
        return all_reduce(tensor, op=op, group=group)
    if op not in (ReduceOp.SUM, "sum"):
        raise ValueError(
            f"quantized collectives support ReduceOp.SUM only, got {op}")
    group = group or _world_group()
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)
    fn = _quantized_sum_traced(group.axes, group.nranks, qformat)
    out = apply_op(
        lambda x: _run(group, x, fn,
                       cache_key=("all_reduce_quantized", qformat)),
        [t], name="all_reduce_quantized")
    t._inplace_from(out)
    return t


def comm_quant_selftest(group=None, qformat="int8", numel=4096, seed=0):
    """fp32-parity self-test for the compressed collective path: sums
    random grads through the quantized all-reduce and reports the relative
    error against the exact fp32 psum. Contract (ISSUE/EQuARX): int8
    relative error < 1e-2 on standard-normal grads.

    The grads are SHARDED over the group axis with a different magnitude
    per rank, so every rank holds distinct data and a distinct bucket
    scale — a bug that mispairs recv chunks with source scales (or the
    scatter/gather-leg scales) changes the result here; a replicated
    input would mask it (identical rows, identical scales)."""
    group = group or _world_group()
    rng = np.random.default_rng(seed)
    n = group.nranks
    # distinct data AND distinct scales per rank, but only a 10% spread:
    # a mispaired scale still shifts the result by ~10% of a chunk
    # (far above the 1e-2 gate), while an order-of-magnitude spread
    # would unfairly inflate the honest quantization error itself
    per_rank = (rng.standard_normal((n, numel))
                * (1.0 + 0.1 * np.arange(n))[:, None]).astype(np.float32)
    data = jnp.asarray(per_rank.reshape(-1))
    if len(group.axes) == 1:
        data = jax.device_put(data, NamedSharding(
            group.mesh, P(group.axes[0])))
    ref = all_reduce(Tensor(data), group=group)
    got = all_reduce_quantized(Tensor(data), group=group, qformat=qformat)
    err = got._data - ref._data
    # rel_err: L2-norm ratio (the standard vector relative error; the
    # gate). max_rel: worst element over the result's max — reported for
    # visibility, intrinsically ~2/254 for two-leg int8
    rel = float(jnp.linalg.norm(err)) / max(
        float(jnp.linalg.norm(ref._data)), 1e-30)
    max_rel = float(jnp.max(jnp.abs(err))) / max(
        float(jnp.max(jnp.abs(ref._data))), 1e-30)
    return {"qformat": qformat, "nranks": n, "numel": numel,
            "rel_err": rel, "max_rel": max_rel,
            "pass": bool(rel < 1e-2)}


def broadcast(tensor, src=0, group=None, sync_op=True):
    """Reference communication/broadcast.py: every rank gets src's value."""
    group = group or _world_group()
    ax = _axis_arg(group.axes)
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)

    def traced(s):
        idx = jax.lax.axis_index(ax)
        contrib = jnp.where(idx == src, s, jnp.zeros_like(s))
        return jax.lax.psum(contrib, ax)

    out = apply_op(lambda x: _run(group, x, traced,
                              cache_key=("broadcast", src)),
               [t], name="broadcast")
    t._inplace_from(out)
    return t


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """src's list entry i goes to rank i. Global view: returns the stacked
    [nranks, ...] tensor laid out so device i holds row i (the DTensor form
    of "each rank has its row"). Traced context: takes this rank's row."""
    group = group or _world_group()
    ax = _axis_arg(group.axes)
    if tensor_list is not None:
        stacked = Tensor(jnp.stack([x._data if isinstance(x, Tensor) else x
                                    for x in tensor_list]))
    else:
        stacked = tensor if isinstance(tensor, Tensor) else Tensor(tensor)

    if isinstance(stacked._data, jax.core.Tracer) and _axis_bound(ax):
        def pick(s):
            return jnp.take(s, jax.lax.axis_index(ax), axis=0)

        return apply_op(pick, [stacked], name="scatter")

    spec = P(ax, *([None] * (stacked.ndim - 1)))
    sharding = NamedSharding(group.mesh, spec)
    out = apply_op(lambda x: jax.device_put(x, sharding), [stacked],
                   name="scatter")
    if isinstance(tensor, Tensor):
        tensor._inplace_from(out)
        return tensor
    return out


def alltoall(out_tensor_list, in_tensor_list=None, group=None, sync_op=True):
    """Reference communication/all_to_all.py."""
    group = group or _world_group()
    ax = _axis_arg(group.axes)
    if in_tensor_list is None:
        in_tensor_list = out_tensor_list
    stacked = Tensor(jnp.stack([x._data if isinstance(x, Tensor) else x
                                for x in in_tensor_list]))

    def traced(s):
        # s: [nranks, ...] rows destined per rank
        return jax.lax.all_to_all(s, ax, split_axis=0, concat_axis=0,
                                  tiled=False)

    out = apply_op(lambda x: _run(group, x, traced, out_spec=P(),
                                  cache_key=("alltoall",)), [stacked],
                   name="alltoall")
    if out_tensor_list is not None:
        del out_tensor_list[:]
        for i in range(group.nranks):
            out_tensor_list.append(out[i])
        return out_tensor_list
    return out


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    group = group or _world_group()
    ax = _axis_arg(group.axes)
    for splits in (in_split_sizes, out_split_sizes):
        if splits and len(set(splits)) > 1:
            raise NotImplementedError(
                "alltoall_single with unequal split sizes is not supported "
                "on the XLA all_to_all path (equal splits only)")
    t = in_tensor if isinstance(in_tensor, Tensor) else Tensor(in_tensor)

    def traced(s):
        return jax.lax.all_to_all(s, ax, split_axis=0, concat_axis=0,
                                  tiled=True)

    out = apply_op(lambda x: _run(group, x, traced,
                                  cache_key=("alltoall_single",)), [t],
                   name="alltoall_single")
    if isinstance(out_tensor, Tensor):
        out_tensor._inplace_from(out)
        return out_tensor
    return out


class _P2PTask:
    """Completed-task handle (reference core.task): eager single-controller
    p2p completes synchronously, so wait() is a no-op."""

    def wait(self):
        return True

    def is_completed(self):
        return True


# One FIFO mailbox per group (keyed by mesh identity + axes, holding a
# strong mesh ref so id() can't be reused for a different mesh while
# messages are pending). Single-controller semantics: EVERY group rank is
# this process (the all_gather_object convention), so peer arguments are
# range-validated routing metadata, not matching keys — a recv returns
# the oldest unconsumed send in the group. For a symmetric SPMD program
# (each rank sends to next / receives from prev) this is exactly the
# value the real exchange would deliver, since all ranks run this same
# code on the same process-local data. destroy_process_group drains it.
_p2p_mailbox: dict[tuple, tuple] = {}
_p2p_multidst_warned: list = []  # once-per-process latch


def _p2p_box(group):
    from collections import deque

    key = (id(group.mesh), group.axes)
    entry = _p2p_mailbox.get(key)
    if entry is None or entry[0] is not group.mesh:
        entry = (group.mesh, deque())
        _p2p_mailbox[key] = entry
    return entry[1]


def send(tensor, dst=0, group=None, sync_op=True):
    """Point-to-point send (reference communication/send.py:27).

    Eager single-controller: the tensor is enqueued to the group's
    in-process mailbox; `recv` dequeues it (see _p2p_mailbox). Inside
    traced code use `p2p_permute` (lax.ppermute) — XLA has no
    rank-conditional send."""
    group = group or _world_group()
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)
    if isinstance(t._data, jax.core.Tracer):
        raise RuntimeError(
            "send() inside traced code is not expressible (per-rank "
            "branches don't trace); use p2p_permute() / the pipeline ring")
    if not 0 <= dst < group.nranks:
        raise ValueError(f"dst {dst} out of range for {group!r}")
    _p2p_box(group).append((int(dst), t._data))
    return _P2PTask()


def recv(tensor, src=0, group=None, sync_op=True):
    """Point-to-point receive (reference communication/recv.py:27): fills
    `tensor` in place with the group's oldest unconsumed `send`. Shape
    and dtype must match the sent tensor (reference send/recv metadata
    contract)."""
    group = group or _world_group()
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)
    if isinstance(t._data, jax.core.Tracer):
        raise RuntimeError(
            "recv() inside traced code is not expressible; use "
            "p2p_permute() / the pipeline ring")
    if not 0 <= src < group.nranks:
        raise ValueError(f"src {src} out of range for {group!r}")
    box = _p2p_box(group)
    if not box:
        raise RuntimeError(
            f"recv(src={src}): no matching send in flight (single-"
            "controller p2p completes in-process; send must happen first)")
    # The single-controller mailbox delivers in send order. That is correct
    # for translation-symmetric SPMD patterns — including bidirectional
    # halo exchanges (two dsts in flight), where every rank issues the
    # same sends/recvs in the same program order — but it cannot verify a
    # genuinely non-symmetric pattern (e.g. rank 0 sending different
    # tensors to ranks 1 and 2), which would silently deliver the oldest
    # send to the wrong logical receiver. Warn once per process when
    # multiple distinct dsts are in flight so that case is auditable.
    dsts = {d for d, _ in box}
    if len(dsts) > 1 and not _p2p_multidst_warned:
        import warnings

        _p2p_multidst_warned.append(True)
        warnings.warn(
            f"recv(src={src}): sends to multiple distinct dst ranks "
            f"{sorted(dsts)} are in flight; the in-process mailbox "
            "delivers in send order, which is only correct for "
            "symmetric SPMD p2p programs (every rank issuing the same "
            "sends/recvs in the same order). For non-symmetric patterns "
            "use p2p_permute() inside traced code.", RuntimeWarning,
            stacklevel=2)
    _, data = box.popleft()
    if tuple(data.shape) != tuple(t._data.shape):
        raise ValueError(
            f"recv buffer shape {tuple(t._data.shape)} != sent shape "
            f"{tuple(data.shape)}")
    if data.dtype != t._data.dtype:
        raise ValueError(
            f"recv buffer dtype {t._data.dtype} != sent dtype "
            f"{data.dtype} (send/recv metadata must match)")
    t._inplace_from(Tensor._wrap(data))
    return _P2PTask()


isend = send
irecv = recv


class P2POp:
    """Batched p2p descriptor (reference communication/batch_isend_irecv.py:34)."""

    def __init__(self, op, tensor, peer, group=None):
        if op not in (isend, irecv, send, recv):
            raise ValueError(
                "op must be paddle.distributed.isend or irecv")
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """Execute a batch of P2POps (reference batch_isend_irecv.py:132).
    Sends run before receives so a rank's paired ops can't deadlock —
    the single-controller analog of the reference's grouped NCCL calls."""
    if not p2p_op_list:
        raise ValueError("p2p_op_list must not be empty")
    for p in p2p_op_list:
        if not isinstance(p, P2POp):
            raise TypeError("batch_isend_irecv takes a list of P2POp")
    tasks = []
    sends = [p for p in p2p_op_list if p.op in (send, isend)]
    recvs = [p for p in p2p_op_list if p.op in (recv, irecv)]
    for p in sends:
        tasks.append(p.op(p.tensor, p.peer, group=p.group))
    for p in recvs:
        tasks.append(p.op(p.tensor, p.peer, group=p.group))
    return tasks


def p2p_permute(tensor, perm, group=None):
    """Traced-context point-to-point: permute values across the group axis.
    perm: list of (src, dst) pairs (reference P2pHelper's send/recv pattern,
    fleet/meta_parallel/pp_utils/p2p_communication.py:570)."""
    group = group or _world_group()
    ax = _axis_arg(group.axes)
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)

    def traced(s):
        return jax.lax.ppermute(s, ax, perm)

    return apply_op(
        lambda x: _run(group, x, traced,
                       cache_key=("p2p_permute", tuple(map(tuple, perm)))),
        [t], name="p2p_permute")


def barrier(group=None):
    """Synchronize: a tiny psum forced to completion. The blocking wait is
    guarded by the comm watchdog (reference: comm_task_manager.h:37 watches
    every outstanding collective) so a dead peer interrupts instead of
    hanging forever."""
    group = group or _world_group()
    fn = _reduce_traced(group.axes, ReduceOp.SUM)
    out = _run(group, jnp.zeros((), jnp.int32), fn,
               cache_key=("barrier",))
    from . import comm_watchdog

    with comm_watchdog.watch(f"barrier(axes={group.axes})"):
        jax.block_until_ready(out)


def all_gather_object(object_list, obj, group=None):
    """Host-side object gather; single-controller: every rank is this
    process, so the list is nranks copies (parity with references tests)."""
    group = group or _world_group()
    del object_list[:]
    object_list.extend([obj] * group.nranks)
    return object_list


def broadcast_object_list(object_list, src=0, group=None):
    return object_list


def get_world_size(group=None) -> int:
    if group is not None:
        return group.nranks
    return env.get_world_size()


def get_rank(group=None) -> int:
    if group is not None:
        return group.rank
    return env.get_rank()


def is_initialized() -> bool:
    return env.is_initialized()


def destroy_process_group(group=None):
    global _default_group
    _default_group = None
    _p2p_mailbox.clear()   # drop pending p2p messages (and mesh refs)
    _eager_fn_cache.clear()  # drop mesh refs + compiled executables
