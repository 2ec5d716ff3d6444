"""MoE layer with expert parallelism.

Reference parity: python/paddle/incubate/distributed/models/moe/moe_layer.py
(MoELayer :263, global_scatter :119, global_gather :140) with gshard/switch
gates (gate/).

TPU-first: the reference routes tokens with index-list global_scatter/
global_gather collectives (NCCL alltoall of ragged buffers). Here routing is
the GShard einsum formulation — dense [T,E,C] dispatch/combine masks, expert
params STACKED on a leading E dim sharded over the ``ep`` mesh axis, and a
vmap over experts; XLA GSPMD lowers the dispatch/combine einsums to the
all-to-alls on ICI. Static shapes (capacity) keep it jit-compilable; drops
are mask zeros, not ragged buffers.

Real expert parallelism (ISSUE 9): when the forward traces inside a
`shard_map` that binds the ``ep`` axis AND the bound expert stacks are the
rank's 1/ep slice (the dp×ep scan train step's layout —
jit/sharded_scan.py `_setup_ep`), the dispatch/combine become EXPLICIT
`jax.lax.all_to_all`s: the [E, C, H] capacity-padded dispatch buffer
splits its expert dim over ep and concatenates capacity, each rank runs
its E/ep local experts over the ep·C tokens it received, and the inverse
all_to_all brings expert outputs home. Capacity padding is what makes the
equal-split wire format legal for ragged per-expert token counts — the
same trick `global_scatter`/`global_gather` use for ragged count vectors.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..... import nn
from .....framework.tensor import Tensor
from .....framework.autograd import apply_op, no_grad
from .....nn.layer.layers import Parameter
from .gate import NaiveGate

__all__ = ["MoELayer", "ExpertFFN", "global_scatter", "global_gather"]


class ExpertFFN(nn.Layer):
    """Default expert: fc1 -> gelu -> fc2 (the reference examples' expert)."""

    def __init__(self, d_model, d_hidden):
        super().__init__()
        self.fc1 = nn.Linear(d_model, d_hidden)
        self.fc2 = nn.Linear(d_hidden, d_model)

    def forward(self, x):
        return self.fc2(nn.functional.gelu(self.fc1(x)))


class MoELayer(nn.Layer):
    """Mixture-of-experts over an expert-parallel mesh axis: the CAPACITY
    path. Top-1 / top-2 gates, [T, E, C] dispatch masks, and a token
    past an expert's capacity is dropped. For top-k routing that drops
    nothing (tokens sorted by expert, a grouped product over the experts
    held, `held_experts` for one chip's share) see `dropless.DroplessMoE`.

    Args:
      d_model: token feature size.
      experts: list of identically-structured expert Layers (their initial
        params are stacked onto a leading num_experts dim).
      gate: "gshard" (top-2) | "switch" (top-1) | a NaiveGate instance.
      capacity_factor: per-expert slots = ceil(cf * T / E). float("inf")
        disables dropping (capacity = T).
      axis: expert-parallel mesh axis name; stacked params are sharded over
        it when the ambient mesh has the axis.

    After forward, ``self.l_aux`` holds the load-balancing loss Tensor
    (add it to the training loss, reference MoELayer semantics).
    """

    def __init__(self, d_model, experts, gate="gshard",
                 capacity_factor=1.25, axis="ep", mesh=None, group=None,
                 ep_degree=None):
        super().__init__()
        self.d_model = int(d_model)
        self.num_experts = len(experts)
        self.capacity_factor = capacity_factor
        self.gate = gate if isinstance(gate, NaiveGate) else NaiveGate(gate)
        self._axis = axis
        self._mesh = group.mesh if group is not None else mesh
        # ep_degree: declared expert-parallel degree (validated here so a
        # bad layout fails at construction, not at trace time); None =
        # whatever the ambient mesh's ep axis provides
        if ep_degree is not None:
            ep_degree = int(ep_degree)
            if ep_degree < 1 or self.num_experts % ep_degree:
                raise ValueError(
                    f"num_experts {self.num_experts} not divisible by "
                    f"ep_degree {ep_degree}")
        self.ep_degree = ep_degree
        self.gate_weight = self.create_parameter(
            [self.d_model, self.num_experts])

        template = experts[0]
        object.__setattr__(self, "_template", template)
        names = [n for n, _ in template.named_parameters()]
        self._stacked_names = []
        for pname in names:
            stacked = jnp.stack([
                dict(e.named_parameters())[pname]._data for e in experts])
            flat = "experts__" + pname.replace(".", "__")
            self.add_parameter(flat, Parameter(stacked))
            self._stacked_names.append((flat, pname))
        self.l_aux = None
        self._shard_params()

    def _resolve_mesh(self):
        mesh = self._mesh
        if mesh is None:
            from .....distributed import env as denv

            if denv.is_initialized():
                mesh = denv.get_mesh()
        if mesh is not None and self._axis in mesh.axis_names \
                and mesh.shape[self._axis] > 1:
            return mesh
        return None

    def _shard_params(self):
        mesh = self._resolve_mesh()
        if mesh is None:
            return
        for flat, _ in self._stacked_names:
            p = self._parameters[flat]
            if p._data.shape[0] % mesh.shape[self._axis] == 0:
                spec = P(self._axis, *([None] * (p._data.ndim - 1)))
                p._data = jax.device_put(p._data,
                                         NamedSharding(mesh, spec))

    def _capacity(self, num_tokens):
        if math.isinf(self.capacity_factor):
            return int(num_tokens)
        return max(1, int(math.ceil(
            self.capacity_factor * num_tokens / self.num_experts)))

    def forward(self, x):
        orig_shape = x.shape
        hidden = orig_shape[-1]
        if hidden != self.d_model:
            raise ValueError(f"expected feature dim {self.d_model}, "
                             f"got {hidden}")
        num_tokens = 1
        for s in orig_shape[:-1]:
            num_tokens *= s
        capacity = self._capacity(num_tokens)
        gate_fn = self.gate
        mesh = self._resolve_mesh()
        axis = self._axis
        template = self._template
        leaves = [p for _, p in template.named_parameters()]
        stacked = [self._parameters[flat] for flat, _ in self._stacked_names]

        def expert_apply(layer_leaves, xe):
            with no_grad():
                saved = [p._data for p in leaves]
                for p, d in zip(leaves, layer_leaves):
                    p._data = d
                try:
                    out = template(Tensor._wrap(xe))._data
                finally:
                    for p, d in zip(leaves, saved):
                        p._data = d
            return out

        num_experts = self.num_experts

        def moe_fn(xa, wg, *stacked_leaves):
            xt = xa.reshape(num_tokens, hidden)
            logits = (xt.astype(jnp.float32)
                      @ wg.astype(jnp.float32))
            combine, dispatch, aux = gate_fn(logits, capacity)
            combine = combine.astype(xt.dtype)
            expert_in = jnp.einsum(
                "tec,th->ech", dispatch.astype(xt.dtype), xt)
            e_loc = int(stacked_leaves[0].shape[0])
            if e_loc != num_experts:
                # REAL expert parallelism: the bound stacks are this
                # rank's 1/ep expert slice inside a shard_map binding the
                # ep axis (the dp×ep scan step). Ship each expert's
                # capacity-padded token block to its owner (split the
                # expert dim, concatenate capacity), run the local
                # experts over the ep·C tokens received, and all_to_all
                # the outputs home. Shapes are static — capacity padding
                # is what makes the equal-split wire format legal.
                from .....distributed.collective import _axis_bound

                if not _axis_bound(axis):
                    raise RuntimeError(
                        f"MoELayer bound {e_loc}/{num_experts} expert "
                        f"slices but mesh axis {axis!r} is not bound in "
                        "this trace — expert-parallel dispatch needs the "
                        "shard_map context that sliced the experts")
                recv = jax.lax.all_to_all(
                    expert_in, axis, split_axis=0, concat_axis=1,
                    tiled=True)                   # [E/ep, ep*C, H]
                out = jax.vmap(expert_apply)(list(stacked_leaves), recv)
                expert_out = jax.lax.all_to_all(
                    out, axis, split_axis=1, concat_axis=0,
                    tiled=True)                   # [E, C, H]
            else:
                if mesh is not None:
                    from .....distributed.env import pin_sharding

                    spec = P(axis, *([None] * (expert_in.ndim - 1)))
                    expert_in = pin_sharding(expert_in,
                                             NamedSharding(mesh, spec))
                expert_out = jax.vmap(expert_apply)(list(stacked_leaves),
                                                    expert_in)
            y = jnp.einsum("tec,ech->th", combine, expert_out)
            return y.reshape(orig_shape), aux.astype(jnp.float32)

        y, aux = apply_op(moe_fn, [x, self.gate_weight] + stacked,
                          name="moe")
        self.l_aux = aux
        return y


def _default_group():
    """World group when the distributed env is up, else None (count checks
    that need a group are skipped outside a mesh)."""
    from .....distributed import env as denv

    if not denv.is_initialized():
        return None
    from .....distributed.collective import get_group

    return get_group()


def _validated_counts(local_count, global_count, name, x=None, group=None):
    """The reference kernels move count-shaped ragged buffers
    (distributed/utils/moe_utils.py global_scatter/global_gather). The XLA
    all_to_all wire is equal-split, so the counts are VERIFIED rather than
    silently ignored, then routed: uniform counts describe exactly the
    equal-split exchange (fast path); ragged counts run through the
    capacity-padded equal-split exchange (`_ragged_exchange` — pad every
    bucket to the max count, all_to_all the padded blocks, compact). The
    remaining errors mark genuinely unsupported shapes: traced counts
    (the layout must be host-known to build the pad/compact maps),
    local/global count vectors that disagree (the single-controller
    global view runs every rank's identical program, so the receive
    layout IS derived from the send layout), mismatched totals, and
    count vectors that don't tile over the group.

    Returns (lc, gc) as host numpy arrays (or None)."""
    import numpy as np

    counts = []
    for c in (local_count, global_count):
        if c is None:
            counts.append(None)
            continue
        data = c._data if isinstance(c, Tensor) else c
        if isinstance(data, jax.core.Tracer):
            raise NotImplementedError(
                f"{name} with traced counts cannot drive the host-built "
                "pad/compact maps; use MoELayer's dense capacity "
                "dispatch inside jit")
        counts.append(np.asarray(data))
    lc, gc = counts
    if lc is not None and gc is not None and lc.sum() != gc.sum():
        raise ValueError(
            f"{name}: local_count total ({int(lc.sum())}) != global_count "
            f"total ({int(gc.sum())}) — the exchange would lose tokens")
    if lc is not None and gc is not None and (
            lc.size != gc.size or not np.array_equal(lc, gc)):
        raise ValueError(
            f"{name}: local_count {lc.tolist()} and global_count "
            f"{gc.tolist()} disagree. In the single-controller global "
            "view every rank runs the same program over the same count "
            "vector, so the receive layout is derived from the send "
            "layout — per-rank-distinct count vectors are not "
            "representable here (run the reference per-rank API under "
            "multi-process SPMD for that)")
    # counts must actually describe the exchange: length a multiple of
    # nranks (n_expert * world entries) and totals covering x's rows
    # (global leading dim = nranks * per-rank rows)
    if group is not None and lc is not None:
        nranks = group.nranks
        if lc.size % nranks:
            raise ValueError(
                f"{name}: counts length {lc.size} is not a multiple of "
                f"the group's nranks ({nranks})")
        if x is not None:
            rows = (x._data if isinstance(x, Tensor)
                    else jnp.asarray(x)).shape[0]
            if int(lc.sum()) * nranks != rows:
                raise ValueError(
                    f"{name}: counts route {int(lc.sum())} rows/rank x "
                    f"{nranks} ranks but x has {rows} rows")
    return lc, gc


def _ragged_exchange(x, counts, group, inverse=False):
    """Capacity-padded equal-split exchange of ragged per-expert buckets
    (single-controller global view).

    Layout contract (destination-major, the reference moe_utils layout):
    rank r's section of `x` holds, for each bucket b = d*n_e + e,
    ``counts[b]`` rows destined to rank d's local expert e
    (``inverse=False``); the result is source-major — rank r's section
    holds, for each source s and local expert e, the ``counts[r*n_e+e]``
    rows s sent it. ``inverse=True`` applies the exact inverse map (the
    gather direction). The wire carries ONE equal-split all_to_all of
    [nranks · n_expert · capacity] blocks, capacity = max(counts); pad
    rows are zeros and never reach the output.
    """
    import numpy as np

    from .....distributed.collective import alltoall_single

    data = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    W = group.nranks
    counts = np.asarray(counts, np.int64)
    B = counts.size
    n_e = B // W
    S = int(counts.sum())
    cap = max(1, int(counts.max()))
    feat = data.shape[1:]
    off = np.zeros(B, np.int64)
    off[1:] = np.cumsum(counts)[:-1]
    grp_sum = counts.reshape(W, n_e).sum(axis=1)          # per-rank-group
    grp_off = np.zeros((W, n_e), np.int64)
    grp_off[:, 1:] = np.cumsum(counts.reshape(W, n_e), axis=1)[:, :-1]
    # scattered-layout section offsets (sections are W*sum(group_r) rows)
    sec = np.zeros(W + 1, np.int64)
    sec[1:] = np.cumsum(W * grp_sum)

    # pack map: padded[r, d, e, c] <- x row (or -1 = zero pad).
    pack = np.full((W, W, n_e, cap), -1, np.int64)
    # unpack map: out_row <- padded-recv flat index (r, s, e, c)
    if inverse:
        total_out = W * S
    else:
        total_out = int(sec[-1])
    unpack = np.zeros(total_out, np.int64)
    for r in range(W):
        for d in range(W):
            for e in range(n_e):
                if inverse:
                    cnt = int(counts[r * n_e + e])
                    src = (sec[r] + d * grp_sum[r] + grp_off[r, e]
                           + np.arange(cnt))
                else:
                    cnt = int(counts[d * n_e + e])
                    src = r * S + off[d * n_e + e] + np.arange(cnt)
                pack[r, d, e, :cnt] = src
                # receive side of block (r<-s=d): where its rows land
                if inverse:
                    # gather: rows return to destination-major order
                    cnt_in = int(counts[d * n_e + e])
                    dst = r * S + off[d * n_e + e] + np.arange(cnt_in)
                    flat = (((r * W + d) * n_e + e) * cap
                            + np.arange(cnt_in))
                else:
                    cnt_in = int(counts[r * n_e + e])
                    dst = (sec[r] + d * grp_sum[r] + grp_off[r, e]
                           + np.arange(cnt_in))
                    flat = (((r * W + d) * n_e + e) * cap
                            + np.arange(cnt_in))
                unpack[dst] = flat

    pack_flat = pack.reshape(-1)
    mask = jnp.asarray((pack_flat >= 0).reshape(-1, *([1] * len(feat))),
                       data.dtype)
    pack_idx = jnp.asarray(np.maximum(pack_flat, 0))
    unpack_idx = jnp.asarray(unpack)

    def pad_fn(d):
        return jnp.take(d, pack_idx, axis=0) * mask

    padded = apply_op(pad_fn, [x if isinstance(x, Tensor)
                               else Tensor._wrap(data)], name="moe_pad")
    # shard the rank-major padded buffer over the group axis and run the
    # REAL equal-split collective
    if len(group.axes) == 1:
        spec = P(group.axes[0], *([None] * len(feat)))
        padded._data = jax.device_put(
            padded._data, NamedSharding(group.mesh, spec))
    exchanged = alltoall_single(None, padded, group=group)

    def compact_fn(d):
        return jnp.take(d, unpack_idx, axis=0)

    return apply_op(compact_fn, [exchanged], name="moe_compact")


def global_scatter(x, local_count, global_count, group=None):
    """Reference moe_layer.py:119 — alltoall token push. Counts are
    validated, never silently ignored: uniform counts ride the direct
    equal-split all_to_all; ragged per-expert counts ride the
    capacity-padded equal-split exchange (`_ragged_exchange`)."""
    from .....distributed.collective import alltoall_single

    group = group or _default_group()
    lc, _ = _validated_counts(local_count, global_count,
                              "global_scatter", x=x, group=group)
    if lc is not None and len(set(lc.tolist())) > 1:
        if group is None:
            raise ValueError(
                "global_scatter with ragged counts needs a group/mesh "
                "(the exchange layout depends on nranks)")
        return _ragged_exchange(x, lc, group, inverse=False)
    out = Tensor(jnp.zeros_like(x._data if isinstance(x, Tensor)
                                else jnp.asarray(x)))
    alltoall_single(out, x, group=group)
    return out


def global_gather(x, local_count, global_count, group=None):
    """Reference moe_layer.py:140 — inverse alltoall pull (the exact
    inverse of `global_scatter`, incl. the ragged capacity-padded
    path)."""
    from .....distributed.collective import alltoall_single

    group = group or _default_group()
    lc, _ = _validated_counts(local_count, global_count,
                              "global_gather", x=x, group=group)
    if lc is not None and len(set(lc.tolist())) > 1:
        if group is None:
            raise ValueError(
                "global_gather with ragged counts needs a group/mesh "
                "(the exchange layout depends on nranks)")
        return _ragged_exchange(x, lc, group, inverse=True)
    out = Tensor(jnp.zeros_like(x._data if isinstance(x, Tensor)
                                else jnp.asarray(x)))
    alltoall_single(out, x, group=group)
    return out
