"""Auto-parallel DTensor API.

Reference parity: python/paddle/distributed/auto_parallel/ — ProcessMesh
(process_mesh.py), shard_tensor/reshard/shard_layer/dtensor_from_local
(api.py:179,675,776,589), DistAttr placements (Shard/Replicate/Partial,
paddle/phi/core/distributed/auto_parallel/placement_types.h).

TPU-first: a DistTensor IS a jax.Array with a NamedSharding — placement and
layout are native to the runtime, and "reshard" is a device_put with a new
sharding (XLA emits the collective-permute/all-gather/all-to-all under the
hood, replacing the reference's 15 hand-written reshard transition functions
in phi/core/distributed/auto_parallel/reshard/).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...framework.tensor import Tensor
from ...framework.autograd import apply_op
from .. import env


class Placement:
    pass


class Replicate(Placement):
    def __repr__(self):
        return "Replicate()"

    def __eq__(self, other):
        return isinstance(other, Replicate)

    def __hash__(self):
        return hash("Replicate")


class Shard(Placement):
    def __init__(self, dim):
        self.dim = dim

    def get_dim(self):
        return self.dim

    def __repr__(self):
        return f"Shard(dim={self.dim})"

    def __eq__(self, other):
        return isinstance(other, Shard) and other.dim == self.dim

    def __hash__(self):
        return hash(("Shard", self.dim))


class Partial(Placement):
    """Pending-reduction placement. XLA tracks partials internally during
    propagation; materializing a Partial DTensor eagerly reduces it."""

    def __init__(self, reduce_type="sum"):
        self.reduce_type = reduce_type

    def __repr__(self):
        return f"Partial({self.reduce_type})"

    def __eq__(self, other):
        return isinstance(other, Partial)

    def __hash__(self):
        return hash("Partial")


class ProcessMesh:
    """Reference process_mesh.py — N-D logical mesh with dim names."""

    def __init__(self, mesh, dim_names=None, shape=None, process_ids=None):
        arr = np.asarray(mesh)
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(arr.ndim)]
        self._shape = list(arr.shape)
        self._dim_names = list(dim_names)
        self._process_ids = list(arr.flatten())
        self._jax_mesh = None

    @property
    def shape(self):
        return self._shape

    @property
    def dim_names(self):
        return self._dim_names

    @property
    def process_ids(self):
        return self._process_ids

    @property
    def ndim(self):
        return len(self._shape)

    def get_dim_size(self, name):
        return self._shape[self._dim_names.index(name)]

    def get_mesh_with_dim(self, name, index=None):
        axis = self._dim_names.index(name)
        arr = np.asarray(self._process_ids).reshape(self._shape)
        if index is None:
            order = [axis] + [i for i in range(self.ndim) if i != axis]
            return ProcessMesh(arr.transpose(order),
                               [self._dim_names[i] for i in order])
        taken = np.take(arr, index, axis=axis)
        names = [n for i, n in enumerate(self._dim_names) if i != axis]
        return ProcessMesh(taken, names or ["d0"])

    def jax_mesh(self) -> Mesh:
        if self._jax_mesh is None:
            devs = jax.devices()
            chosen = np.asarray([devs[pid % len(devs)]
                                 for pid in self._process_ids])
            self._jax_mesh = Mesh(chosen.reshape(self._shape),
                                  tuple(self._dim_names))
        return self._jax_mesh

    def __eq__(self, other):
        return (isinstance(other, ProcessMesh)
                and self._shape == other._shape
                and self._process_ids == other._process_ids)

    def __repr__(self):
        return (f"ProcessMesh(shape={self._shape}, "
                f"dim_names={self._dim_names})")


def _placements_to_spec(placements, ndim, mesh: ProcessMesh) -> P:
    axes = [None] * ndim
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            name = mesh.dim_names[mesh_dim]
            if axes[pl.dim] is None:
                axes[pl.dim] = name
            elif isinstance(axes[pl.dim], tuple):
                axes[pl.dim] = axes[pl.dim] + (name,)
            else:
                axes[pl.dim] = (axes[pl.dim], name)
    return P(*axes)


def _spec_to_placements(spec: P, mesh: Mesh) -> list:
    placements = [Replicate() for _ in mesh.axis_names]
    if spec is None:
        return placements
    for tdim, entry in enumerate(spec):
        if entry is None:
            continue
        for name in (entry if isinstance(entry, tuple) else (entry,)):
            placements[mesh.axis_names.index(name)] = Shard(tdim)
    return placements


def shard_tensor(data, mesh: ProcessMesh, placements, dtype=None,
                 stop_gradient=None):
    """Reference api.py:179 — place a tensor on the mesh per placements.
    Differentiable: recorded on the tape as a device_put."""
    t = data if isinstance(data, Tensor) else Tensor(data, dtype=dtype)
    jmesh = mesh.jax_mesh()
    spec = _placements_to_spec(placements, t.ndim, mesh)
    sharding = NamedSharding(jmesh, spec)
    out = apply_op(lambda x: jax.device_put(x, sharding), [t],
                   name="shard_tensor")
    if stop_gradient is not None:
        out.stop_gradient = stop_gradient
    else:
        out.stop_gradient = t.stop_gradient
    # keep Parameter-ness by rebinding storage in place for leaf params
    if t is data and getattr(t, "is_leaf", True) and t.stop_gradient is False:
        pass
    out.process_mesh = mesh
    out.placements = list(placements)
    return out


def dtensor_from_local(local_tensor, mesh: ProcessMesh, placements):
    """Reference api.py:589 — single-controller: the 'local' tensor is the
    global value; apply the placements."""
    return shard_tensor(local_tensor, mesh, placements)


def dtensor_to_local(dist_tensor, mesh=None, placements=None):
    t = dist_tensor if isinstance(dist_tensor, Tensor) else Tensor(dist_tensor)
    return apply_op(lambda x: jax.device_put(
        x, NamedSharding(env.get_mesh(), P())), [t], name="dtensor_to_local")


def reshard(dist_tensor, mesh: ProcessMesh, placements):
    """Reference api.py:675 + the reshard function registry
    (reshard_function_registry.cc): any placement transition. XLA emits the
    transfer; differentiable."""
    return shard_tensor(dist_tensor, mesh, placements)


def shard_layer(layer, process_mesh: ProcessMesh, shard_fn=None,
                input_fn=None, output_fn=None):
    """Reference api.py:776 — apply shard_fn(name, layer, mesh) to every
    sublayer's params (default: replicate all)."""
    def default_shard(name, sublayer, mesh):
        for pname, param in list(sublayer._parameters.items()):
            if param is None:
                continue
            nd = param.ndim
            out = shard_tensor(param, mesh,
                               [Replicate() for _ in mesh.dim_names])
            param._data = out._data

    fn = shard_fn or default_shard
    for name, sub in layer.named_sublayers(include_self=True):
        fn(name, sub, process_mesh)
    return layer


def get_placements(tensor) -> list:
    t = tensor if isinstance(tensor, Tensor) else tensor
    sh = getattr(t._data, "sharding", None)
    if isinstance(sh, NamedSharding):
        return _spec_to_placements(sh.spec, sh.mesh)
    return [Replicate()]


def moe_global_mesh_tensor(local_tensor_list, mesh=None, placements=None,
                           local_mesh_dim=-1):
    """Reference api.py moe_global_mesh_tensor — assemble per-EP-rank
    expert tensors into ONE global dist tensor sharded over the
    expert-parallel mesh dim (ISSUE 9 satellite; the EP module's storage
    convention: expert params stacked on a leading num_experts dim,
    sharded 1/ep).

    ``local_tensor_list``: each EP rank's slice of the stacked expert
    tensor (e.g. [E/ep, ...]); ``local_mesh_dim`` names (index or dim
    name) the mesh dim the experts split over — its placement must be a
    ``Shard`` giving the concat dim. The result is the concatenated
    global tensor placed per ``placements`` (expert dim sharded over the
    ep axis, everything else as given), so GSPMD sees exactly the
    1/ep-expert layout `MoELayer` computes with.
    """
    if not local_tensor_list:
        raise ValueError("moe_global_mesh_tensor needs a non-empty "
                         "local_tensor_list")
    if mesh is None:
        jm = env.get_mesh()
        mesh = ProcessMesh(
            np.arange(jm.devices.size).reshape(jm.devices.shape),
            list(jm.axis_names))
    if isinstance(local_mesh_dim, str):
        local_mesh_dim = mesh.dim_names.index(local_mesh_dim)
    local_mesh_dim = local_mesh_dim % mesh.ndim
    if placements is None:
        # default EP layout: experts split on dim 0 over the local mesh
        # dim, replicated elsewhere
        placements = [Replicate()] * mesh.ndim
        placements[local_mesh_dim] = Shard(0)
    pl = placements[local_mesh_dim]
    if not isinstance(pl, Shard):
        raise ValueError(
            f"the expert-parallel mesh dim "
            f"{mesh.dim_names[local_mesh_dim]!r} must carry a Shard "
            f"placement (the expert concat dim); got {pl!r}")
    degree = mesh.shape[local_mesh_dim]
    if len(local_tensor_list) != degree:
        raise ValueError(
            f"{len(local_tensor_list)} local tensors for an ep degree "
            f"of {degree} (one slice per EP rank)")
    datas = [t._data if isinstance(t, Tensor) else jnp.asarray(t)
             for t in local_tensor_list]
    global_data = jnp.concatenate(datas, axis=pl.dim)
    return shard_tensor(Tensor._wrap(global_data), mesh, placements)


from .engine import DistModel, Strategy, to_static  # noqa: E402,F401
from .planner import Plan, infer_model_spec, plan  # noqa: E402,F401


def apply_sharding_rules(layer, rules, mesh=None):
    """Place every parameter of `layer` per (regex, axis-spec) `rules` —
    the generic per-layer SPMD entry (the role of the reference's 93
    per-op spmd_rules files, applied at the weight level where GSPMD then
    propagates). Axes are dropped per-param when the dim is absent from
    the mesh or not divisible, so one rule set serves any mesh shape.

    rules: list of (pattern, spec) where spec is a tuple of mesh-axis
    names (or None) per dim — the format of gpt/llama_sharding_rules.
    """
    from ...models.gpt import match_sharding

    if mesh is None:
        mesh = env.get_mesh()

    for name, p in layer.named_parameters():
        spec = match_sharding(name, rules) or ()
        axes = [a if (a and a in mesh.axis_names
                      and p._data.shape[i] % mesh.shape[a] == 0) else None
                for i, a in enumerate(spec)]
        p._data = jax.device_put(
            p._data, NamedSharding(mesh, P(*axes) if axes else P()))
    return layer

from .spmd_rules import (  # noqa: E402,F401
    auto_shard_layer, plan_layer_specs, register_layer_rule, LAYER_RULES,
)
