"""Operations and bytes each Pallas kernel's algorithm needs for one call,
from shapes. One file per kernel `name=`: `cost(...) -> (ops, bytes)` of
one call, and for training kernels `from_cell(cell, ctx)`, the cost of one
call at the cell's shapes."""


def least_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])
