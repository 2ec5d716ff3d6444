"""Kernel routing for the Pallas pack: the one platform probe and the one
kernel-or-XLA decision every wrapper in this package takes.

The rule (same for every kernel):

* On TPU a supported geometry runs the COMPILED kernel; a compiler
  refusal raises — nothing catches it and substitutes the XLA path.
* On TPU an unsupported geometry takes the XLA path and is recorded in
  `xla_fallbacks` (and logged once per kernel and geometry).
* On CPU the XLA path is the path. Interpret mode is reached only by an
  explicit `interpret=True` or `FLAGS_pallas_force_interpret` (tests and
  the `chip_smoke.py --tiny` rehearsal).
"""
from __future__ import annotations

import collections
import math
import re

import jax
from jax.experimental import pallas as pl

from ...utils import flags as _flags
from ...utils.log_helper import get_logger

_logger = get_logger(__name__)

# (kernel, geometry) -> calls that asked for a kernel and got the XLA path
xla_fallbacks: collections.Counter = collections.Counter()


def on_tpu() -> bool:
    """Platform the next computation lands on: the `jax.default_device`
    in effect, else the default backend's first device. Asked on every
    call, so a caller inside `jax.default_device(cpu)` gets the CPU
    answer and leaves nothing behind for the next caller."""
    dev = jax.config.jax_default_device
    if dev is None:
        dev = jax.devices()[0]
    return (dev if isinstance(dev, str) else dev.platform) == "tpu"


def force_interpret() -> bool:
    return bool(_flags.get_flag("FLAGS_pallas_force_interpret"))


def kernels_wanted() -> bool:
    """Would a supported geometry run a kernel here and now?"""
    return force_interpret() or on_tpu()


def note_fallback(kernel: str, geometry) -> None:
    key = (kernel, geometry)
    if not xla_fallbacks[key]:
        _logger.warning("%s: no Pallas kernel for %s — XLA path", kernel,
                        geometry)
    xla_fallbacks[key] += 1


def pallas_call(*args, **kwargs):
    """`pl.pallas_call` with the kernel body and index maps traced under
    x64 OFF. The framework turns x64 on globally (framework/__init__.py);
    under it a Python scalar inside a kernel traces as a 64-bit value —
    an i64 index-map result, the i64 operand of `%`/`//`, an f64
    `jnp.where(c, 1.0, 0.0)` tile — and Mosaic refuses those or aborts
    the process on them. Operands are 32-bit or narrower already."""
    call = pl.pallas_call(*args, **kwargs)

    def run(*operands):
        with jax.enable_x64(False):
            return call(*operands)

    return run


_MOSAIC_RE = re.compile(r'op_name="[^"]*?(\w+)\)*/pallas_call"')


def mosaic_kernels(hlo_text: str) -> collections.Counter:
    """Kernel name (the `name=` of its pallas_call) -> Mosaic custom calls
    in a COMPILED module's text. Empty when the program took the XLA
    path or ran its kernels interpreted."""
    return collections.Counter(
        name for line in hlo_text.splitlines()
        if "tpu_custom_call" in line
        for name in _MOSAIC_RE.findall(line))


_SHAPE_RE = re.compile(r"(?:f32|f16|bf16|f64)\[([0-9,]+)\]")


def forbidden_shapes(hlo_text: str, batch: int, seq: int, vocab: int):
    """Buffers the fused train step must never hold, found in its HLO
    text: logits-shaped (last dim == vocab with >= batch*seq rows behind
    it) and attention-scores-shaped (>= 3d, trailing [seq, seq])."""
    bad = []
    for m in _SHAPE_RE.finditer(hlo_text):
        dims = [int(x) for x in m.group(1).split(",") if x]
        if len(dims) >= 2 and dims[-1] == vocab \
                and math.prod(dims[:-1]) >= batch * seq:
            bad.append(dims)
        if len(dims) >= 3 and dims[-1] == seq and dims[-2] == seq:
            bad.append(dims)
    return bad


def route(kernel: str, supported: bool, geometry, interpret=None,
          use_kernel=None):
    """-> (use_kernel, interpret) for one call of `kernel`.

    `interpret=None` follows FLAGS_pallas_force_interpret; `use_kernel`
    overrides the platform half of the decision (True on an unsupported
    geometry raises)."""
    interpret = force_interpret() if interpret is None else bool(interpret)
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
        if use_kernel and not supported:
            note_fallback(kernel, geometry)
            use_kernel = False
    elif use_kernel and not supported:
        raise ValueError(f"{kernel} kernel does not support {geometry}")
    return bool(use_kernel), interpret
