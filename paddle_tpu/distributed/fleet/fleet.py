"""Fleet orchestration.

Reference parity: fleet.init (fleet.py:166), _init_hybrid_parallel_env
(:598), distributed_model (model.py:32), distributed_optimizer (:1325),
DistributedStrategy (fleet/base/distributed_strategy.py:175 over
distributed_strategy.proto:361).

TPU-first: `init` builds the global device Mesh from hybrid_configs degrees
(order [pp, dp, sharding, sep, mp] — topology.py) and installs it;
`distributed_model` wraps by active axes exactly like the reference
(model.py:134-162) but the wrappers annotate shardings instead of creating
NCCL reducers.
"""
from __future__ import annotations

import numpy as np

from .. import env
from . import topology as topo_mod
from .topology import (
    CommunicateTopology, HybridCommunicateGroup,
    set_hybrid_communicate_group, get_hybrid_communicate_group,
)


class DistributedStrategy:
    """Reference distributed_strategy.py:175 — knobs the TPU build honors
    plus accepted-for-parity fields."""

    def __init__(self):
        self.hybrid_configs = {
            "dp_degree": 1,
            "mp_degree": 1,
            "pp_degree": 1,
            "sharding_degree": 1,
            "sep_degree": 1,
            "mp_configs": {},
            "pp_configs": {},
            "sharding_configs": {},
        }
        self.amp = False
        self.amp_configs = {}
        self.recompute = False
        self.recompute_configs = {}
        self.sharding = False
        self.sharding_configs = {}
        self.pipeline = False
        self.pipeline_configs = {"accumulate_steps": 1}
        self.gradient_merge = False
        self.gradient_merge_configs = {}
        self.find_unused_parameters = False
        self.tensor_parallel = False
        self.tensor_parallel_configs = {}

    def __repr__(self):
        return f"DistributedStrategy(hybrid={self.hybrid_configs})"


class Fleet:
    """Reference fleet.py Fleet singleton."""

    def __init__(self):
        self._strategy = None
        self._hcg = None
        self._initialized = False

    def init(self, role_maker=None, is_collective=True, strategy=None, log_level=None):
        self._strategy = strategy or DistributedStrategy()
        hc = self._strategy.hybrid_configs
        dims = [int(hc.get("pp_degree", 1)), int(hc.get("dp_degree", 1)),
                int(hc.get("sharding_degree", 1)), int(hc.get("sep_degree", 1)),
                int(hc.get("mp_degree", 1))]
        # reference fleet.py:647: -1 degree → fill from world size
        import jax

        avail = len(jax.devices())
        known = int(np.prod([d for d in dims if d > 0]))
        dims = [avail // known if d == -1 else d for d in dims]
        topology = CommunicateTopology(dims=dims)
        self._hcg = HybridCommunicateGroup(topology)
        set_hybrid_communicate_group(self._hcg)
        self._initialized = True
        return self

    @property
    def worker_num(self):
        return env.get_world_size()

    def worker_index(self):
        return env.get_rank()

    def is_first_worker(self):
        return env.get_rank() == 0

    def get_hybrid_communicate_group(self):
        return self._hcg

    def distributed_model(self, model):
        """Reference model.py:32 — wrap by active axes."""
        if self._hcg is None:
            self.init()
        hcg = self._hcg
        from ..parallel import DataParallel
        from .meta_parallel import (
            TensorParallel, SegmentParallel, ShardingParallel,
        )
        from .meta_parallel.pipeline_parallel import PipelineParallel
        from .meta_parallel.pp_layers import PipelineLayer

        if hcg.get_pipe_parallel_world_size() > 1 and isinstance(
            model, PipelineLayer
        ):
            return PipelineParallel(model, hcg, self._strategy)
        if hcg.get_pipe_parallel_world_size() > 1:
            # non-PipelineLayer model on a pp mesh (e.g. a scan_layers
            # GPT): the compiled ring step owns the schedule —
            # HybridParallel.train_step builds it via select_train_step
            from .meta_parallel import HybridParallel

            return HybridParallel(model, hcg, strategy=self._strategy)
        if hcg.get_model_parallel_world_size() > 1:
            return TensorParallel(model, hcg, strategy=self._strategy)
        if hcg.get_sep_parallel_world_size() > 1:
            return SegmentParallel(model, hcg, strategy=self._strategy)
        if hcg.get_sharding_parallel_world_size() > 1:
            return ShardingParallel(model, hcg, strategy=self._strategy)
        if hcg.get_data_parallel_world_size() > 1:
            return DataParallel(model, group=hcg.get_data_parallel_group())
        return model

    def distributed_optimizer(self, optimizer, strategy=None):
        """Reference fleet.py:1325 → HybridParallelOptimizer."""
        if self._hcg is None:
            self.init()
        from .meta_optimizers.hybrid_parallel_optimizer import (
            HybridParallelOptimizer,
        )

        return HybridParallelOptimizer(optimizer, self._hcg,
                                       strategy or self._strategy)

    # barrier/stop parity
    def barrier_worker(self):
        from .. import collective

        collective.barrier()

    def stop_worker(self):
        pass


fleet = Fleet()


def init(role_maker=None, is_collective=True, strategy=None, log_level=None):
    return fleet.init(role_maker, is_collective, strategy, log_level)


def distributed_model(model):
    return fleet.distributed_model(model)


def distributed_optimizer(optimizer, strategy=None):
    return fleet.distributed_optimizer(optimizer, strategy)


def get_hybrid_communicate_group_():
    return get_hybrid_communicate_group()
