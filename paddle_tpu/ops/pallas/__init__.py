"""Pallas TPU kernel pack — the fused-kernel library.

Reference parity: paddle/phi/kernels/fusion/ (~90k LoC of fused CUDA
kernels) and the flash-attn entry paddle/phi/kernels/gpu/flash_attn_kernel.cu.
TPU-first: the hot fused ops are hand-written Pallas kernels over the MXU
(flash attention here; more land as profiling demands), everything else is
left to XLA fusion.

Not every kernel is a product: `moe_rows` adds the dropless mixture's
tiles back, a row an asynchronous copy in and one out (`moe_add_rows` into
a float32 [T, 1, K] accumulator in place, padding rows never written),
where XLA's scatter-add pays for the tiled layout of [T, K] on every row.
"""
from . import flash_attention  # noqa: F401
from . import fused_cross_entropy  # noqa: F401
from . import moe_rows  # noqa: F401
from . import paged_attention  # noqa: F401
from . import splash_attention  # noqa: F401
