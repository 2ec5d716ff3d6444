#!/usr/bin/env python3
"""Time `ops/pallas/ssd_scan.py`'s pieces alone on the chip, and hold the
kernels against the step-by-step recurrence there.

    python3 tools/time_ssd_scan.py [--batch 4 --seq 8192]

Prints one line `ssd_scan: {...}`: milliseconds of the forward kernel, of
forward + backward (the custom VJP's two kernels and XLA's share: the
running sums, D x, the tables' layouts), and the largest error of y and of
each cotangent against the recurrence on one short sequence, relative to
the cotangent's largest entry.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def recurrence(x, dt, A, B, C, D):
    """The plain reference's step-by-step recurrence (benchmark/reference/
    nemotron_h.py) on `ssd_scan`'s operands."""
    from reference import nemotron_h as ref

    b, s, heads, p = x.shape
    g = B.shape[2]

    def grouped(v):
        return v.reshape(v.shape[:-1] + (g, heads // g))

    return ref.recurrence(x.reshape(b, s, g, heads // g, p), grouped(dt),
                          grouped(A), B, C, grouped(D), 128).reshape(x.shape)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu  # noqa: F401
    from paddle_tpu.ops.pallas import ssd_scan as S

    heads, p, groups, n = 64, 64, 8, 128

    def draw(b, seq, dtype, seed):
        rng = np.random.default_rng(seed)
        f = jnp.float32
        return (jnp.asarray(rng.normal(size=(b, seq, heads, p)), dtype),
                jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                               (b, seq, heads))), f),
                -jnp.asarray(rng.uniform(1, 16, heads), f),
                jnp.asarray(rng.normal(size=(b, seq, groups, n)) / 4, dtype),
                jnp.asarray(rng.normal(size=(b, seq, groups, n)) / 4, dtype),
                jnp.asarray(rng.normal(size=heads), f))

    def both(f):
        def run(dy, *a):
            y, pull = jax.vjp(f, *a)
            return (y,) + pull(dy.astype(y.dtype))
        return jax.jit(run)

    out = {"device": str(jax.devices()[0].device_kind)}
    # numerics: one short sequence, float32 and bfloat16 operands
    for dtype in (jnp.float32, jnp.bfloat16):
        a = draw(1, 1024, dtype, 1)
        dy = jnp.asarray(np.random.default_rng(2).normal(size=a[0].shape),
                         jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = both(recurrence)(dy, *(v.astype(jnp.float32) for v in a))
        got = both(lambda *v: S.ssd_scan(*v))(dy, *a)
        out["err_" + jnp.dtype(dtype).name] = {
            k: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                     / jnp.max(jnp.abs(w)))
            for k, g, w in zip(("y", "dx", "ddt", "dA", "dB", "dC", "dD"),
                               got, want)}
    # times at the cell's shapes
    a = draw(args.batch, args.seq, jnp.bfloat16, 3)
    dy = a[0]

    def timed(fn, *v):
        jax.block_until_ready(fn(*v))
        t = time.perf_counter()
        for _ in range(args.reps):
            r = fn(*v)
        jax.block_until_ready(r)
        return 1e3 * (time.perf_counter() - t) / args.reps

    out["fwd_ms"] = timed(jax.jit(lambda *v: S.ssd_scan(*v)), *a)
    out["fwd_bwd_ms"] = timed(both(lambda *v: S.ssd_scan(*v)), dy, *a)
    out["visited_chunks"] = S.visited_chunks(args.batch, args.seq, 128)
    print("ssd_scan: " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
