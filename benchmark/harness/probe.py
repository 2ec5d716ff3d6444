"""A direction-sensitive reading of a large array from a few scalars.

The gap between two gradients' NORMS hides zero-mean rounding noise (it
adds in quadrature), which is exactly what a lower precision brings. So
each side also reports K signed sums of every leaf, sum_i s_k(i) x_i with
s_k(i) = +-1 a fixed hash of the element's flat index: for two arrays a
and b, the root mean square over k of (sum_k(a) - sum_k(b)) estimates
||a - b|| without either side ever seeing the other's array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

K = 8
_U = np.uint32


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _read(x, offset, part, parts, k):
    """(sum of squares, [k] signed sums) in float32 of slice `part` of
    `parts` along x's last axis, fused: no copy of x is made. Element j
    (row-major) of the slice has the flat index offset + j."""
    if parts > 1:
        w = x.shape[-1] // parts
        x = x[..., part * w:(part + 1) * w]
    flat = x.reshape(-1).astype(jnp.float32)
    idx = jax.lax.iota(jnp.uint32, flat.size) + offset.astype(jnp.uint32)
    out = []
    for j in range(k):
        h = idx * _U(2654435761) + _U((j * 0x9E3779B1) & 0xFFFFFFFF)
        h = (h ^ (h >> _U(15))) * _U(2246822519)
        h = (h ^ (h >> _U(13))) * _U(3266489917)
        h = h ^ (h >> _U(16))
        sign = 1.0 - 2.0 * (h & _U(1)).astype(jnp.float32)
        out.append(jnp.sum(flat * sign))
    return jnp.sum(jnp.square(flat)), jnp.stack(out)


def read(x, offset=0, part=0, parts=1):
    """-> (sum(x ** 2), the K signed sums as float64) of one slice of x."""
    sq, sums = _read(x, np.uint32(offset), part, parts, K)
    return float(sq), np.asarray(sums, np.float64)


def sums(x, offset=0):
    return read(x, offset)[1]


def direction_gap(program: dict, reference: dict, ref_norms: dict):
    """Worst leaf of rms_k(program sums - reference sums) over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. -> (gap, leaf)"""
    import statistics

    floor = statistics.median(ref_norms.values())
    worst, name = 0.0, ""
    for leaf, ref in reference.items():
        d = np.asarray(program[leaf], np.float64) - np.asarray(ref)
        gap = float(np.sqrt(np.mean(d * d))) / max(ref_norms[leaf], floor,
                                                   1e-30)
        if not gap <= worst:
            worst, name = gap, leaf
    return worst, name
