#!/usr/bin/env python3
"""Time `ops/pallas/gated_conv.py`'s two kernels alone on the chip, beside
XLA's fusion of the same function, at the LFM2 cell's shapes.

    python3 tools/time_gated_conv.py [--batch 4 --seq 8192 --hidden 2048
                                      --blocks 256x32x256,512x32x256]

Prints one line `gated_conv: {...}`: for each `rows x slab x lanes` of a
grid step's block, milliseconds of the forward kernel, of the backward
kernel and of forward + backward through the VJP, the bytes each has to
move (`benchmark/kernels/gated_conv.py`) and GB/s against the chip's 819;
the same for the `jnp` form (XLA's fusion of the forward, and JAX's
pull-back of it); and the largest error of y, dbcx and dw against the
`jnp` form in float32, relative to the largest entry.
`JAX_PLATFORMS=cpu ... --batch 1 --seq 128 --hidden 256 --reps 1` rehearses
it in the sandbox, kernels interpreted.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--taps", type=int, default=3)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--blocks", default="256x32x256",
                    help="comma list of rows x slab x lanes")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu  # noqa: F401
    from kernels import gated_conv as counts
    from paddle_tpu.ops.pallas import gated_conv as G

    interpret = jax.devices()[0].platform != "tpu"
    b, s, h = args.batch, args.seq, args.hidden
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    bcx = jnp.asarray(rng.normal(size=(b, s, 3 * h)), bf)
    w = jnp.asarray(rng.uniform(-0.58, 0.58, (args.taps, h)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(b, s, h)), bf)

    def timed(fn, *v):
        jax.block_until_ready(fn(*v))
        t = time.perf_counter()
        for _ in range(args.reps):
            r = fn(*v)
        jax.block_until_ready(r)
        return 1e3 * (time.perf_counter() - t) / args.reps

    def both(f):
        def run(bcx, w, dy):
            y, pull = jax.vjp(f, bcx, w)
            return (y,) + pull(dy)
        return jax.jit(run)

    cost = counts.cost(b * s, h, args.taps)
    peak = 819e9

    def rate(ms, passes):
        gbs = sum(cost[p]["bytes"] for p in passes) / ms / 1e6
        return {"ms": round(ms, 4), "GB/s": round(gbs, 1),
                "roofline_pct": round(100e9 * gbs / peak, 1)}

    out = {"shape": [b, s, h], "taps": args.taps,
           "bytes": {k: v["bytes"] for k, v in cost.items()}}
    xla_fwd = jax.jit(G.gated_conv_xla)
    xla_both = both(G.gated_conv_xla)
    out["xla"] = {"fwd": rate(timed(xla_fwd, bcx, w), ["fwd"]),
                  "fwd+bwd": rate(timed(xla_both, bcx, w, dy),
                                  ["fwd", "bwd"])}
    small = tuple(x[:1, :min(s, 1024)] for x in (bcx, dy))
    want = both(G.gated_conv_xla)(small[0].astype(jnp.float32), w,
                                  small[1].astype(jnp.float32))
    for block in args.blocks.split(","):
        G.ROWS, G.SLAB, G.COLS = (int(x) for x in block.split("x"))
        jax.clear_caches()

        def kernel(bcx, w):
            return G.gated_conv(bcx, w, use_kernel=True, interpret=interpret)

        try:
            fwd = jax.jit(lambda a, c: G.gated_conv_fwd(a, c, interpret))
            bwd = jax.jit(lambda g, a, c: G.gated_conv_bwd(g, a, c,
                                                           interpret))
            got = both(kernel)(small[0], w, small[1])
            errors = {
                name: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b_))
                            / jnp.max(jnp.abs(b_)))
                for name, a, b_ in zip(("y", "dbcx", "dw"), got, want)}
            out[block] = {
                "fwd": rate(timed(fwd, bcx, w), ["fwd"]),
                "bwd": rate(timed(bwd, dy, bcx, w), ["bwd"]),
                "fwd+bwd": rate(timed(both(kernel), bcx, w, dy),
                                ["fwd", "bwd"]),
                "error": errors}
        except Exception as e:      # a block the compiler refuses
            out[block] = {"refused": repr(e)[:300]}
    print("gated_conv: " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
