"""The one general generator of open-loop request schedules.

A traffic file gives a rate, a block size, and a distribution each for
prompt and output length. Every seed gets the SAME set of arrival gaps
and lengths in another order: a block of `block` requests always holds
the same `block` quantiles of each distribution (gaps exponential at the
file's rate, scaled so a block lasts exactly block / rate seconds), and
the seed permutes gaps, prompt lengths and output lengths independently
inside each block. So every run offers the same work at the same rate,
and only its arrangement — which prompt meets which burst — is drawn.
Token ids are uniform over the vocabulary (1 .. vocab - 1) from the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantiles(n):
    return [(j + 0.5) / n for j in range(n)]


def length_quantiles(dist: dict, n: int):
    """n quantiles of a lognormal (median, sigma) length, clipped to
    [min, max]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = NormalDist()
    return [int(min(max(round(dist["median"] * math.exp(
        dist["sigma"] * z.inv_cdf(u))), dist["min"]), dist["max"]))
        for u in _quantiles(n)]


def gap_quantiles(rate: float, n: int):
    """n inter-arrival gaps: quantiles of the exponential (Poisson
    arrivals), scaled to sum to exactly n / rate."""
    raw = [-math.log(1.0 - u) for u in _quantiles(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


def schedule(traffic: dict, vocab: int, seed: int, horizon_s: float,
             rate: float | None = None):
    """[(due_s, prompt ids int32, max_new_tokens)] covering horizon_s."""
    rate = float(rate if rate is not None else traffic["rate_rps"])
    n = int(traffic["block"])
    gaps = gap_quantiles(rate, n)
    prompts = length_quantiles(traffic["prompt"], n)
    outputs = length_quantiles(traffic["output"], n)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    out, t = [], 0.0
    while t < horizon_s:
        g, p, o = (rng.permutation(x) for x in (gaps, prompts, outputs))
        for j in range(n):
            t += float(g[j])
            ids = rng.integers(1, vocab, (int(p[j]),), dtype=np.int32)
            out.append((t, ids, int(o[j])))
    return [r for r in out if r[0] < horizon_s]
