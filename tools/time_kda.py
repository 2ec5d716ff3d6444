#!/usr/bin/env python3
"""Time `ops/pallas/kda.py`'s pieces alone on the chip, and hold the kernels
against the token-by-token recurrence there.

    python3 tools/time_kda.py [--batch 2 --seq 8192 --blocks 1,2,4 --mla
                               --rows 512,1024,2048]

Prints one line `kda: {...}`: milliseconds of the forward kernel (alone, and
as the VJP runs it, saving each chunk's state), of the backward kernel and
of forward + backward (the custom VJP's two kernels and XLA's share: beta's
products) at the Ling-3.0 cell's shapes, nanoseconds a chunk-head of either
kernel, for each number of chunks a grid step takes (one chunk: the
[64, 64] system; two or four: pairs as [128, 128] systems), and the largest
error of o and of each
cotangent against the recurrence on one short sequence, relative to the
cotangent's largest entry, with float32 and with bfloat16 operands. With
`--mla`, the attention kernels at 192 / 128 head widths too: their error
against the XLA form on 1,024 tokens and their times at the cell's shapes.
With `--rows`, the four row kernels of `ops/pallas/kda_rows.py` alone at the
cell's shapes, for each number of rows a grid step's block takes: ms, the
bytes each has to move, GB/s against the chip's 819, forward + pull-back
through the VJP, and the same function's XLA form beside them.
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
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--blocks", default="4")
    ap.add_argument("--inverse", default="float32", help="comma list of "
                    "float32 (the program's), bf16x3, bf16: the precision "
                    "of the triangular inverse's ten products, timed and "
                    "held against the recurrence")
    ap.add_argument("--mla", action="store_true", help="also the attention "
                    "kernels at latent attention's 192 / 128 head widths")
    ap.add_argument("--rows", default="", help="also the row kernels "
                    "(kda_rows.py), at each of these rows a block")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu  # noqa: F401
    from paddle_tpu.ops.pallas import kda as K
    from reference import ling3 as ref

    heads, d = 32, 128

    def draw(b, seq, dtype, seed):
        rng = np.random.default_rng(seed)
        f = jnp.float32

        def unit(v):
            return v / np.linalg.norm(v, axis=-1, keepdims=True)

        q = unit(rng.normal(size=(b, seq, heads, d))) * d ** -0.5
        k = unit(rng.normal(size=(b, seq, heads, d)) + 0.5)
        return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
                jnp.asarray(rng.normal(size=(b, seq, heads, d)), dtype),
                jnp.asarray(rng.uniform(-5, 0, (b, seq, heads, d)), f),
                jnp.asarray(rng.uniform(0.05, 0.95, (b, seq, heads)), f))

    def both(f):
        def run(do, *a):
            o, pull = jax.vjp(f, *a)
            return (o,) + pull(do.astype(o.dtype))
        return jax.jit(run)

    # off the chip: a rehearsal of this script, the kernels interpreted
    interpret = jax.default_backend() != "tpu"

    def scanner():      # a new function a variant: jit caches by function
        def scan(*v):
            return K.kda(*v, chunk=args.chunk, interpret=interpret or None)
        return scan

    def dot_at(precision, dtype):
        def mm(a, b):
            return jax.lax.dot_general(
                a.astype(dtype), b.astype(dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
        return mm

    inverses = {"float32": K._mm,
                "bf16x3": dot_at(jax.lax.Precision.HIGH, jnp.float32),
                "bf16": dot_at(jax.lax.Precision.DEFAULT, jnp.bfloat16)}

    out = {"device": str(jax.devices()[0].device_kind), "chunk": args.chunk}
    for dtype in (jnp.float32, jnp.bfloat16):
        a = draw(1, min(512, args.seq), dtype, 1)
        do = jnp.asarray(np.random.default_rng(2).normal(size=a[2].shape),
                         jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = both(ref.kda_recurrence)(
                do, *(v.astype(jnp.float32) for v in a))
        for name in args.inverse.split(","):
            K._mm = inverses[name]
            jax.clear_caches()      # `kda_fwd` / `kda_bwd` are jitted
            try:
                got = both(scanner())(do, *a)
            except Exception as e:      # a precision Mosaic does not lower
                out[f"err_{jnp.dtype(dtype).name}.{name}"] = repr(e)[:200]
                continue
            out[f"err_{jnp.dtype(dtype).name}.{name}"] = {
                k: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                         / jnp.max(jnp.abs(w)))
                for k, g, w in zip(("o", "dq", "dk", "dv", "da", "dbeta"),
                                   got, want)}
    a = draw(args.batch, args.seq, jnp.bfloat16, 3)
    do = a[2]

    def timed(fn, *v):
        jax.block_until_ready(fn(*v))
        t = time.perf_counter()
        for _ in range(args.reps):
            r = fn(*v)
        jax.block_until_ready(r)
        return 1e3 * (time.perf_counter() - t) / args.reps

    flat = [x.reshape(args.batch, args.seq, -1)
            for x in K._operands(*a, args.chunk)]
    chunk_heads = args.batch * heads * (args.seq // args.chunk)
    for name in args.inverse.split(","):
        K._mm = inverses[name]
        for n in [int(x) for x in args.blocks.split(",")]:
            K._block = lambda seq, chunk, n=n: n * chunk
            jax.clear_caches()
            tag = f"{n}" + ("" if name == "float32" else "." + name)
            try:
                saving = jax.jit(lambda *v: K.kda_fwd(
                    *v, args.chunk, save=True, interpret=interpret))
                out[f"fwd_kernel_ms.{tag}"] = timed(jax.jit(
                    lambda *v: K.kda_fwd(*v, args.chunk,
                                         interpret=interpret)), *flat)
                out[f"fwd_save_kernel_ms.{tag}"] = timed(saving, *flat)
                out[f"bwd_kernel_ms.{tag}"] = timed(jax.jit(
                    lambda *v: K.kda_bwd(*v, args.chunk,
                                         interpret=interpret)),
                    *flat, saving(*flat)[1], flat[3])
                for k in ("fwd", "bwd"):
                    out[f"{k}_ns_a_chunk_head.{tag}"] = (
                        1e6 * out[f"{k}_kernel_ms.{tag}"] / chunk_heads)
                out[f"fwd_ms.{tag}"] = timed(jax.jit(scanner()), *a)
                out[f"fwd_bwd_ms.{tag}"] = timed(both(scanner()), do, *a)
            except Exception as e:
                out[f"failed.{tag}"] = repr(e)[:200]
    if args.mla:
        from paddle_tpu.ops.pallas import splash_attention as S

        def qkv(b, seq, seed):
            rng = np.random.default_rng(seed)
            return tuple(jnp.asarray(rng.normal(size=(b, seq, heads, w)),
                                     jnp.bfloat16) for w in (192, 192, 128))

        def attend(*v):
            return S.splash_attention(*v, causal=True, scale=192 ** -0.5,
                                      interpret=interpret or None)

        v = qkv(1, min(1024, args.seq), 4)
        want = both(lambda *a: S.splash_attention_xla(
            *a, causal=True, scale=192 ** -0.5))(v[2], *v)
        got = both(attend)(v[2], *v)
        out["mla_err_bfloat16"] = {
            k: float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - w.astype(jnp.float32)))
                     / jnp.max(jnp.abs(w.astype(jnp.float32))))
            for k, g, w in zip(("o", "dq", "dk", "dv"), got, want)}
        v = qkv(args.batch, args.seq, 5)
        out["mla_fwd_ms"] = timed(jax.jit(attend), *v)
        out["mla_fwd_bwd_ms"] = timed(both(attend), v[2], *v)
    if args.rows:
        out.update(time_rows(args, interpret, timed))
    print("kda: " + json.dumps(out), flush=True)


def time_rows(args, interpret, timed):
    """-> the `rows_*` keys of the line: `kda_rows.py`'s two entries at
    (batch, seq, 32 x 128) bfloat16, kernels and XLA form."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import kda_rows as R

    heads, d, bf16, f32 = 32, 128, jnp.bfloat16, jnp.float32
    rng = np.random.default_rng(6)
    n, width = args.batch * args.seq, heads * d

    def table(dtype, *shape):
        return jnp.asarray(rng.standard_normal((args.batch, args.seq)
                                               + shape), dtype)

    ins = ([table(bf16, width) for _ in range(3)]
           + [table(f32, width), table(bf16, heads),
              jnp.asarray(np.log(rng.uniform(1, 16, heads)), bf16),
              jnp.asarray(0.3 * rng.standard_normal(width), bf16)])
    gated = [table(bf16, width), table(bf16, heads),
             jnp.asarray(1 + 0.1 * rng.standard_normal(d), bf16)]
    wide, wide32, logits = 2 * n * width, 4 * n * width, 2 * n * heads
    moved = {   # bytes in + out, every table once
        "inputs_fwd": 3 * wide + wide32 + logits + 4 * wide + wide32,
        "inputs_bwd": (7 * wide + 2 * wide32 + logits + 3 * wide + wide32
                       + 2 * logits),
        "gated_norm_fwd": wide + logits + wide,
        "gated_norm_bwd": 2 * wide + logits + wide + 2 * logits}
    kw = dict(interpret=interpret or None)
    entries = {
        "inputs": (lambda *v: R.kda_inputs(*v, lower_bound=-5.0, **kw),
                   lambda *v: R.kda_inputs_xla(*v, lower_bound=-5.0), ins),
        "gated_norm": (lambda *v: R.kda_gated_norm(*v, eps=1e-6, **kw),
                       lambda *v: R.kda_gated_norm_xla(*v, eps=1e-6), gated)}

    def through_vjp(f):
        def run(*v):
            o, pull = jax.vjp(f, *v)
            return pull(o)
        return jax.jit(run)

    out = {}
    for name, (_, xla, v) in entries.items():
        out[f"rows_{name}_fwd_ms.xla"] = timed(jax.jit(xla), *v)
        out[f"rows_{name}_fwd_bwd_ms.xla"] = timed(through_vjp(xla), *v)
    for rows in [int(x) for x in args.rows.split(",")]:
        R.ROWS = rows
        jax.clear_caches()          # the four wrappers are jitted
        scale, bias = R._channel_rows(ins[5], ins[6], d)
        made = R.kda_inputs_fwd(*ins[:5], scale, bias, -5.0, interpret)
        alone = {
            "inputs_fwd": (lambda *v: R.kda_inputs_fwd(
                *v, -5.0, interpret), (*ins[:5], scale, bias)),
            "inputs_bwd": (lambda *v: R.kda_inputs_bwd(
                v[:5], *v[5:], -5.0, interpret),
                (*made, *ins[:5], scale, bias)),
            "gated_norm_fwd": (lambda *v: R.kda_gated_norm_fwd(
                *v, 1e-6, interpret), gated),
            "gated_norm_bwd": (lambda *v: R.kda_gated_norm_bwd(
                *v, 1e-6, interpret), (gated[0], *gated))}
        for name, (f, v) in alone.items():
            ms = timed(jax.jit(f), *v)
            out[f"rows_{name}_ms.{rows}"] = ms
            out[f"rows_{name}_gb_s.{rows}"] = moved[name] / ms / 1e6
        for name, (kernel, _, v) in entries.items():
            out[f"rows_{name}_fwd_bwd_ms.{rows}"] = timed(
                through_vjp(kernel), *v)
    out["rows_bytes"] = moved
    out["rows_peak_gb_s"] = 819
    return out


if __name__ == "__main__":
    main()
