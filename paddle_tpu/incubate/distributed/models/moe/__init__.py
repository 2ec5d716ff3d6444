"""Expert-parallel mixture-of-experts
(reference python/paddle/incubate/distributed/models/moe/)."""
from .dropless import DroplessMoE, dropless_moe  # noqa: F401
from .gate import NaiveGate, top1_gating, top2_gating  # noqa: F401
from .grad_clip import ClipGradForMOEByGlobalNorm  # noqa: F401
from .moe_layer import (  # noqa: F401
    ExpertFFN, MoELayer, global_gather, global_scatter,
)
