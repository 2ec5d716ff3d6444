"""Distributed environment: global device mesh + rendezvous.

Reference parity: init_parallel_env / env contract
(python/paddle/distributed/parallel.py:978,1098-1131 — PADDLE_TRAINER_ID,
PADDLE_TRAINERS_NUM, PADDLE_TRAINER_ENDPOINTS, MASTER_ADDR/PORT) and the
CommContextManager store-based bring-up
(paddle/phi/core/distributed/comm_context_manager.h:43).

TPU-first: one *controller per host*, all devices visible through jax. The
"world" is a `jax.sharding.Mesh` with named axes (SURVEY.md §5.8 north star);
multi-host joins via `jax.distributed.initialize` (PJRT coordination service
plays the TCPStore role). Collectives ride ICI within a slice and DCN across
slices — XLA picks per the mesh topology from `mesh_utils`.
"""
from __future__ import annotations

import os
import threading

import numpy as np
import jax
from jax.sharding import Mesh

from ..utils.log_helper import get_logger

_logger = get_logger(__name__)
_lock = threading.Lock()
_state = {
    "initialized": False,
    "mesh": None,          # the global Mesh
    "axis_degrees": {},    # axis name -> size
}

# canonical axis order mirrors the reference topology order
# [pipe, data, sharding, sep, model] (fleet/base/topology.py:66)
AXIS_ORDER = ("pp", "dp", "sharding", "sep", "mp")


def init_parallel_env():
    """paddle.distributed.init_parallel_env parity (parallel.py:978).

    Multi-host: reads MASTER_ADDR/MASTER_PORT (or PADDLE_MASTER) +
    PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM and joins the jax coordination
    service. Single-host: no-op beyond building the default 1-axis mesh.
    """
    with _lock:
        if _state["initialized"]:
            return
        n_hosts = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        if n_hosts > 1 and not jax.distributed.is_initialized():
            addr = os.environ.get("MASTER_ADDR")
            port = os.environ.get("MASTER_PORT")
            coord = (
                f"{addr}:{port}" if addr and port
                else os.environ.get("PADDLE_MASTER")
            )
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=n_hosts,
                process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")),
            )
        devs = jax.devices()
        _state["mesh"] = Mesh(np.asarray(devs), ("dp",))
        _state["axis_degrees"] = {"dp": len(devs)}
        _state["initialized"] = True
        _logger.debug("parallel env initialized: %d device(s), mesh=%s",
                      len(devs), _state["mesh"])


def is_initialized() -> bool:
    return _state["initialized"]


def reset():
    """Drop the ambient mesh/degrees AND the fleet HCG (tests and
    single-device reference runs next to a hybrid run use this; fleet
    re-init starts clean — a stale HybridCommunicateGroup would keep
    handing its old mesh to mp layers)."""
    _state["initialized"] = False
    _state["mesh"] = None
    _state["axis_degrees"] = {}
    # groups built on the dropped mesh are orphaned: clear their cached
    # eager-collective executables here too, or a reset()+re-init turnover
    # (where set_mesh sees no previous mesh) would keep them pinned
    from . import collective as _c

    _c._eager_fn_cache.clear()
    try:
        from .fleet import topology as _topo
    except ImportError:  # fleet never imported in this process: no HCG
        return
    _topo.set_hybrid_communicate_group(None)


def pin_sharding(x, sharding):
    """Pin a raw jax value to a sharding: `with_sharding_constraint` under
    trace, `device_put` eager. The one shared home for this dispatch rule
    (mpu layers, stage-2 grad hooks, MoE dispatch all use it)."""
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)


def set_mesh(mesh: Mesh):
    """Install a custom global mesh (built by fleet.init or user code)."""
    with _lock:
        replaced = _state["mesh"] is not None and _state["mesh"] != mesh
        _state["mesh"] = mesh
        _state["axis_degrees"] = dict(zip(mesh.axis_names,
                                          (int(s) for s in mesh.devices.shape)))
        _state["initialized"] = True
    if replaced:
        # a replaced world mesh orphans every group built on it (sub-group
        # meshes derive from it) — drop their cached eager-collective
        # executables here, the one place mesh turnover is visible, instead
        # of per-call eviction (which evicted live sub-group entries on
        # every alternating world/sub call, ADVICE r4)
        from . import collective as _c

        _c._eager_fn_cache.clear()


def get_mesh() -> Mesh:
    if _state["mesh"] is None:
        init_parallel_env()
    return _state["mesh"]


def build_mesh(degrees: dict, devices=None) -> Mesh:
    """Build a mesh from axis-name → degree, ordered per AXIS_ORDER with
    unknown axes appended; degree-1 axes are kept so sharding specs can
    reference them uniformly."""
    names = [a for a in AXIS_ORDER if a in degrees]
    names += [a for a in degrees if a not in names]
    sizes = [int(degrees[a]) for a in names]
    total = int(np.prod(sizes)) if sizes else 1
    if devices is None:
        devices = jax.devices()
    if len(devices) < total:
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {total} devices, "
            f"have {len(devices)}"
        )
    arr = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(arr, tuple(names))


def get_world_size() -> int:
    return int(np.prod(get_mesh().devices.shape))


def get_rank() -> int:
    """Process index × local size + ... — in single-controller mode the
    controller acts as rank 0 (the reference's per-process ranks become mesh
    coordinates; see collective.Group for per-axis ranks)."""
    return jax.process_index() * max(1, get_world_size() // jax.process_count())


def device_count() -> int:
    return len(jax.devices())


def data_sharding(mesh=None, axis=None):
    """The batch-input sharding for a data-parallel mesh: dim 0 split over
    the dp-like axis (first of sharding/dp/data with degree > 1), all
    other dims replicated — what `io.DevicePrefetcher` and the train
    steps' `input_sharding()` place batches on, so each device receives
    only its 1/N shard of every batch. Returns a fully-replicated sharding
    when the mesh has no >1 data axis, and None when no mesh is installed
    (single chip: default-device placement)."""
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        if not is_initialized():
            return None
        mesh = get_mesh()
        if mesh is None:
            return None
    if axis is None:
        axis = next((a for a in ("sharding", "dp", "data")
                     if a in mesh.axis_names and mesh.shape[a] > 1), None)
    if axis is None:
        return NamedSharding(mesh, PartitionSpec())
    return NamedSharding(mesh, PartitionSpec(axis))
