"""A named scope's share of its roofline over a traced slice: the least
time the scope's required work could take in every layer of a step
(kernels/<name>.py `from_cell`, one layer's cost) over the device time
the scope took a step (harness/scopes.py)."""
from __future__ import annotations

from harness import device, load, scopes
from kernels import least_seconds


def share(ctx, scope: str, kernel: str) -> float | None:
    spent_ms = scopes.ms(ctx, scope)
    if not spent_ms:
        return None
    ops, nbytes = load.module("kernels", kernel).from_cell(ctx["cell"], ctx)
    layers = ctx["cell"]["config"]["num_hidden_layers"]
    least = layers * least_seconds(ops, nbytes,
                                   device.peaks(ctx["device"]["kind"]))
    return 100.0 * least / (spent_ms * 1e-3)
