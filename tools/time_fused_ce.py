#!/usr/bin/env python3
"""Time `ops/pallas/fused_cross_entropy.py`'s kernel pair alone on the
chip at the heads of the benchmark's five cells, beside its XLA tiles.

    python3 tools/time_fused_ce.py [--cells keye,mellum2] [--reps 5]
    JAX_PLATFORMS=cpu python3 tools/time_fused_ce.py --rehearse

(`--rehearse`: the same steps on the CPU at 1/64 of every size with the
kernels interpreted; its times mean nothing and the line says so.)

Prints one line `fused_ce: {...}`: for each head (rows, hidden, vocab),
bf16, milliseconds of the forward and of forward + backward (value and
both gradients) through the kernels and through the XLA tiles, the
kernels' share of `benchmark/kernels/fused_ce_fwd.py` +
`fused_ce_bwd.py`'s least time on this device, and the largest gap
between the two paths' loss, dh and dW, relative to each one's largest
entry.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

# (rows a step, hidden, the head's rows on this chip)
HEADS = {
    "gpt3-1.3b": (8192, 2048, 50304),
    "gpt3-350m": (8192, 1024, 50304),
    "keye": (32768, 2048, 18992),
    "mellum2": (32768, 2304, 24576),
    "nemotron3": (32768, 2688, 16384),
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(HEADS))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu  # noqa: F401
    from harness import device
    from kernels import fused_ce_bwd, fused_ce_fwd, least_seconds
    from paddle_tpu.ops.pallas import fused_cross_entropy as fce

    kind = jax.devices()[0].device_kind
    if args.rehearse:
        from paddle_tpu.utils import flags
        flags.set_flags({"FLAGS_pallas_force_interpret": True})
        peaks = device.peaks("TPU v5 lite")
    else:
        peaks = device.peaks(kind)

    def timed(fn, *v):
        jax.block_until_ready(fn(*v))
        t = time.perf_counter()
        for _ in range(args.reps):
            r = fn(*v)
        jax.block_until_ready(r)
        return 1e3 * (time.perf_counter() - t) / args.reps

    def pair(use_kernel, block_v):
        def loss(h, w, lbl):
            return fce.fused_cross_entropy(h, w, lbl, use_kernel=use_kernel,
                                           block_v=block_v)

        def both(h, w, lbl):
            def total(h, w):
                losses = loss(h, w, lbl)
                return jnp.sum(losses), losses
            (_, losses), grads = jax.value_and_grad(
                total, argnums=(0, 1), has_aux=True)(h, w)
            return (losses,) + grads
        return jax.jit(loss), jax.jit(both)

    out = {"device": kind, "rehearsal": args.rehearse}
    for cell in args.cells.split(","):
        n, hidden, vocab = HEADS[cell]
        if args.rehearse:
            n, hidden, vocab = n // 64, hidden // 64, vocab // 64
        rng = np.random.default_rng(vocab)
        h = jnp.asarray(rng.standard_normal((n, hidden)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((vocab, hidden)) * 0.02,
                        jnp.bfloat16)
        lbl = jnp.asarray(rng.integers(0, vocab, (n,)), jnp.int32)
        lbl = lbl.at[:2].set(vocab - 1).at[2::97].set(-100)
        block_v = fce._pick_block_v(vocab, hidden, 2, fce._pick_block_n(n))
        row = {"rows": n, "hidden": hidden, "vocab": vocab,
               "block_v": block_v}
        got = {}
        for name, use_kernel in (("kernel", True), ("xla", False)):
            fwd, both = pair(use_kernel, block_v)
            row[name + "_fwd_ms"] = timed(fwd, h, w, lbl)
            row[name + "_fwd_bwd_ms"] = timed(both, h, w, lbl)
            got[name] = both(h, w, lbl)
        least = sum(least_seconds(*m.cost(n, hidden, vocab), peaks)
                    for m in (fused_ce_fwd, fused_ce_bwd))
        row["least_ms"] = 1e3 * least
        row["kernel_roofline_pct"] = 1e5 * least / row["kernel_fwd_bwd_ms"]
        row["gap"] = {
            k: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
                     / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for k, a, b in zip(("loss", "dh", "dw"), got["kernel"],
                               got["xla"])}
        row["nan"] = bool(any(jnp.isnan(a.astype(jnp.float32)).any()
                              for a in got["kernel"]))
        out[cell] = row
        del h, w, lbl, got
    print("fused_ce: " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
