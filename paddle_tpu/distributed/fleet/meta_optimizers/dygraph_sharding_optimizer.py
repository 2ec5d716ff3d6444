"""Sharding stage 1 (ZeRO-1): optimizer-state partitioning.

Reference parity: DygraphShardingOptimizer
(fleet/meta_optimizers/dygraph_optimizer/dygraph_sharding_optimizer.py:44) —
each sharding rank owns a slice of the optimizer states, updates its slice,
then the updated params are broadcast (V2 :571 fuses buffers into
reduce-scatter/all-gather).

TPU-first: "owning a slice" is a layout, not a code path — the inner
optimizer's accumulators and master weights get a NamedSharding over the
"sharding" mesh axis. XLA then computes each state update shard-locally and
all-gathers the fresh params exactly once per step (the V2 fused behavior),
because params remain replicated while the update operands are sharded.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ....optimizer.optimizer import Optimizer


def _shardable_dim(shape, degree):
    for i, s in enumerate(shape):
        if s % degree == 0 and s >= degree:
            return i
    return None


def shard_state_arrays(state_dict_like, mesh, axis="sharding"):
    """Place every array in {key: array} whose shape allows it on the
    sharding axis (dim chosen per-array)."""
    degree = int(mesh.shape[axis])
    if degree <= 1:
        return state_dict_like
    out = {}
    for k, v in state_dict_like.items():
        dim = _shardable_dim(getattr(v, "shape", ()), degree)
        if dim is None:
            out[k] = v
        else:
            axes = [None] * v.ndim
            axes[dim] = axis
            out[k] = jax.device_put(v, NamedSharding(mesh, P(*axes)))
    return out


class DygraphShardingOptimizer:
    """Wraps an inner Optimizer; shards its accumulators + master weights
    over the sharding axis lazily after they are created."""

    def __init__(self, optimizer: Optimizer, hcg=None, group=None):
        self._inner_opt = optimizer
        if group is not None:
            self._mesh, self._axis = group.mesh, group.axes[0]
        else:
            from ... import env as _env

            hcg = hcg
            if hcg is not None:
                self._mesh = hcg.mesh
                self._axis = "sharding"
            else:
                self._mesh = _env.get_mesh()
                self._axis = ("sharding" if "sharding" in
                              self._mesh.axis_names else
                              self._mesh.axis_names[0])
        self._sharded_once = False
        self._comm_bucketer = None

    def __getattr__(self, item):
        return getattr(self._inner_opt, item)

    def attach_comm_bucketer(self, bucketer):
        """Record the stage-2 grad bucketer (its BucketAssignment is the
        deterministic param→bucket map the scatter-back uses). step()
        flushes any still-pending bucket collectives first, so an eager
        `loss.backward(); opt.step()` loop — or a user-jitted step that
        never calls apply_collective_grads — still syncs at the
        microbatch boundary."""
        self._comm_bucketer = bucketer

    def grad_bucket_assignment(self):
        return (self._comm_bucketer.assignment
                if self._comm_bucketer is not None else None)

    def _apply_shardings(self):
        opt = self._inner_opt
        for name, per in opt._accumulators.items():
            opt._accumulators[name] = shard_state_arrays(
                per, self._mesh, self._axis)
        opt._master_weights.update(
            shard_state_arrays(opt._master_weights, self._mesh, self._axis))

    def step(self):
        if (self._comm_bucketer is not None
                and self._comm_bucketer.has_pending()):
            self._comm_bucketer.sync_pending()
        self._inner_opt.step()
        if not self._sharded_once:
            self._apply_shardings()
            self._sharded_once = True

    def reshard_state(self):
        """Apply shardings now (used by TrainStep warmup so the very first
        compiled step already has sharded states)."""
        self._apply_shardings()
        self._sharded_once = True

    def minimize(self, loss, *a, **k):
        loss.backward()
        self.step()
        return None, None

    def clear_grad(self, set_to_zero=True):
        self._inner_opt.clear_grad(set_to_zero)

    clear_gradients = clear_grad

    def state_dict(self):
        return self._inner_opt.state_dict()

    def set_state_dict(self, sd):
        out = self._inner_opt.set_state_dict(sd)
        if self._sharded_once:
            self._apply_shardings()
        return out
