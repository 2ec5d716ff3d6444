"""The gated short convolution of an LFM2 `conv` layer, as ONE operator on
the flat [b, s, 3 h] product `[B | C | X] = h W_in`:

  z_t = B_t * X_t
  c_t = sum_k w[k] z_{t - (taps - 1) + k}      depthwise, causal, zeros
                                                 before a sequence's start,
                                                 NO activation
  y_t = C_t * c_t                               [b, s, h]

and its pull-back along dy: dC = dy * c; with dc = dy * C,
dz_t = sum_k w[k] dc_{t + (taps - 1) - k} (the taps reversed, zeros after a
sequence's end), dB = dz * X, dX = dz * B, dw[k] = sum_{b, t} dc_t
z_{t - (taps - 1) + k}. Pure memory traffic: three tables read and one
written forward; four read and three written backward.

Two kernels on row blocks of the flat [b s, .] tables, a block inside one
sequence, the table's whole width a block so that B, C and X (and dB, dC,
dX) are lane slices of ONE block and no third of the product is ever copied
out. The convolution's two rows of history come from the 16 rows before the
block (a second BlockSpec on the same table, zeroed where the block starts a
sequence), the pull-back's two rows of future from the 16 rows after it.
Inside a block the work goes by column chunk and row slab, a slab's z (or
dc) shifted against the slab before (after) it by a sublane roll of the
slab with 8 rows of its neighbour on top. float32 inside; bf16 (or float32)
in and out; dw as one float32 partial sum a row block, which XLA adds up.

Where no kernel runs (CPU, a width that is no multiple of 128 lanes) the
`jnp` form `gated_conv_xla` is the path, differentiated by JAX: it is also
the kernels' test oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import routing
from .flash_attention import _LANES, _Z, pl, pltpu

__all__ = ["gated_conv", "gated_conv_xla", "supports"]

F32 = jnp.float32
ROWS = 256          # rows of a grid step's block, the most
SLAB = 32           # rows worked at a time inside it
COLS = 256          # lanes worked at a time
HALO = 16           # rows of the neighbouring block a step is shown
EDGE = 8            # of which the nearest 8 are used: a float32 tile's rows
VMEM_LIMIT = 64 * 2 ** 20


def supports(shape, taps, dtype) -> bool:
    """Whether the kernels take a [b, s, 3 h] product and `taps` taps."""
    b, s, width = shape
    return (dtype in (jnp.float32, jnp.bfloat16) and width % (3 * _LANES) == 0
            and s % HALO == 0 and 1 <= taps <= EDGE)


def gated_conv_xla(bcx, w):
    """`gated_conv` in plain `jax.numpy`: bcx [b, s, 3 h], w [taps, h] (tap
    `taps - 1` weighs the step itself) -> [b, s, h] in bcx's type."""
    taps, h = w.shape
    s = bcx.shape[1]
    gate_in, gate_out, x = (bcx[..., i * h:(i + 1) * h].astype(F32)
                            for i in range(3))
    padded = jnp.pad(gate_in * x, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + s] * w[k].astype(F32) for k in range(taps))
    return (gate_out * conv).astype(bcx.dtype)


# -- the kernels --------------------------------------------------------------

def _blocks(n, s):
    """(rows a block, rows a slab) for flat tables of n rows in sequences of
    s: a block lies inside one sequence."""
    rows = next(r for r in (256, 128, 64, 32, 16) if r <= ROWS and s % r == 0)
    return rows, (SLAB if rows % SLAB == 0 else HALO)


def _chunks(h):
    cols = COLS if h % COLS == 0 else _LANES
    return [(j * cols, cols) for j in range(h // cols)]


def _f32(ref, rows, first, cols):
    return ref[rows, first:first + cols].astype(F32)


def _shifted(ext, taps, back):
    """[x_{t - (taps - 1) + k} for k] of a slab x: `ext` is the slab with
    EDGE rows of the slab before it on top (`back`), or [x_{t + (taps - 1)
    - k}] with EDGE rows of the slab after it below."""
    n = ext.shape[0]
    if back:
        return [pltpu.roll(ext, taps - 1 - k, 0)[EDGE:] if k < taps - 1
                else ext[EDGE:] for k in range(taps)]
    return [pltpu.roll(ext, n - (taps - 1 - k), 0)[:n - EDGE]
            if k < taps - 1 else ext[:n - EDGE] for k in range(taps)]


def _z_before(before_ref, starts, h, first, cols):
    """B * X of the EDGE rows before a block; zeros where the block starts a
    sequence."""
    edge = pl.ds(HALO - EDGE, EDGE)
    return jnp.where(starts, 0.0,
                     _f32(before_ref, edge, first, cols)
                     * _f32(before_ref, edge, 2 * h + first, cols))


def _fwd_kernel(bcx_ref, before_ref, w_ref, y_ref, *, seq, slab):
    rows, h = y_ref.shape
    taps = w_ref.shape[0]
    starts = (pl.program_id(0) * rows) % seq == 0
    for first, cols in _chunks(h):
        w = [w_ref[k:k + 1, first:first + cols] for k in range(taps)]
        z_before = _z_before(before_ref, starts, h, first, cols)

        def one(r, z_prev, first=first, cols=cols, w=w):
            at = pl.ds(pl.multiple_of(r * slab, slab), slab)
            z = _f32(bcx_ref, at, first, cols) \
                * _f32(bcx_ref, at, 2 * h + first, cols)
            zs = _shifted(jnp.concatenate([z_prev, z], 0), taps, True)
            conv = sum(zk * wk for zk, wk in zip(zs, w))
            y_ref[at, first:first + cols] = (
                _f32(bcx_ref, at, h + first, cols) * conv).astype(y_ref.dtype)
            return z[slab - EDGE:]

        jax.lax.fori_loop(0, rows // slab, one, z_before)


def _bwd_kernel(dy_ref, bcx_ref, before_ref, dy_after_ref, c_after_ref, w_ref,
                dbcx_ref, dw_ref, *, seq, slab):
    rows, h = dy_ref.shape
    taps = w_ref.shape[0]
    i = pl.program_id(0)
    starts = (i * rows) % seq == 0
    ends = ((i + 1) * rows) % seq == 0
    n_slabs = rows // slab
    out = dbcx_ref.dtype
    near = pl.ds(0, EDGE)
    for first, cols in _chunks(h):
        w = [w_ref[k:k + 1, first:first + cols] for k in range(taps)]
        z_before = _z_before(before_ref, starts, h, first, cols)
        dc_after = jnp.where(ends, 0.0,
                             _f32(dy_after_ref, near, first, cols)
                             * _f32(c_after_ref, near, first, cols))

        def one(r, carry, first=first, cols=cols, w=w, dc_after=dc_after):
            z_prev, sums = carry
            at = pl.ds(pl.multiple_of(r * slab, slab), slab)
            gate_in = _f32(bcx_ref, at, first, cols)
            gate_out = _f32(bcx_ref, at, h + first, cols)
            x = _f32(bcx_ref, at, 2 * h + first, cols)
            dy = _f32(dy_ref, at, first, cols)
            z = gate_in * x
            zs = _shifted(jnp.concatenate([z_prev, z], 0), taps, True)
            conv = sum(zk * wk for zk, wk in zip(zs, w))
            dbcx_ref[at, h + first:h + first + cols] = (dy * conv).astype(out)
            dc = dy * gate_out
            # the slab after this one: its first rows' dc, from the block
            # itself or, after the block's last slab, from the rows after it
            nxt = pl.ds(pl.multiple_of(
                jnp.minimum((r + 1) * slab, rows - HALO), HALO), HALO)
            dc_next = jnp.where(
                r == n_slabs - 1, dc_after,
                (_f32(dy_ref, nxt, first, cols)
                 * _f32(bcx_ref, nxt, h + first, cols))[:EDGE])
            dcs = _shifted(jnp.concatenate([dc, dc_next], 0), taps, False)
            dz = sum(dk * wk for dk, wk in zip(dcs, w))
            dbcx_ref[at, first:first + cols] = (dz * x).astype(out)
            dbcx_ref[at, 2 * h + first:2 * h + first + cols] = (
                dz * gate_in).astype(out)
            # dw[k] += sum_t dc_t z_{t - (taps - 1) + k}, eight rows apart
            sums = tuple(
                acc + sum((dc * zk)[m:m + EDGE]
                          for m in range(0, slab, EDGE))
                for acc, zk in zip(sums, zs))
            return z[slab - EDGE:], sums

        zero = jnp.zeros((EDGE, cols), F32)
        _, sums = jax.lax.fori_loop(0, n_slabs, one,
                                    (z_before, (zero,) * taps))
        for k in range(taps):
            dw_ref[k:k + 1, first:first + cols] = jnp.sum(
                sums[k], 0, keepdims=True)


def _specs(n, h, rows, taps):
    """The BlockSpecs of flat tables of n rows: a block of the [n, 3 h]
    product, the HALO rows before it, a block of an [n, h] table, the HALO
    rows after it (of an [n, h] table, and of the product's middle third),
    the [taps, h] taps whole, a row block's partial sum of their
    cotangent."""
    per, last = rows // HALO, n // HALO - 1
    return {
        "product": pl.BlockSpec((rows, 3 * h), lambda i: (i, _Z)),
        "before": pl.BlockSpec(
            (HALO, 3 * h), lambda i: (jnp.maximum(i * per - 1, 0), _Z)),
        "table": pl.BlockSpec((rows, h), lambda i: (i, _Z)),
        "after": pl.BlockSpec(
            (HALO, h), lambda i: (jnp.minimum((i + 1) * per, last), _Z)),
        "after_c": pl.BlockSpec(
            (HALO, h), lambda i: (jnp.minimum((i + 1) * per, last),
                                  _Z + 1)),
        "taps": pl.BlockSpec((taps, h), lambda i: (_Z, _Z)),
        "sums": pl.BlockSpec((None, taps, h), lambda i: (i, _Z, _Z)),
    }


# jitted: a model's layers call them with the same shapes, so they share one
# trace and one lowering of each kernel's body (`kda.kda_fwd`)
@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_conv_fwd(bcx, w, interpret=False):
    """bcx [b, s, 3 h], w [taps, h] float32 -> y [b, s, h] in bcx's type."""
    b, s, width = bcx.shape
    n, h = b * s, width // 3
    rows, slab = _blocks(n, s)
    spec = _specs(n, h, rows, w.shape[0])
    flat = bcx.reshape(n, width)
    y = routing.pallas_call(
        functools.partial(_fwd_kernel, seq=s, slab=slab),
        name="gated_conv_fwd", grid=(n // rows,),
        in_specs=[spec["product"], spec["before"], spec["taps"]],
        out_specs=spec["table"],
        out_shape=jax.ShapeDtypeStruct((n, h), bcx.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(flat, flat, w)
    return y.reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_conv_bwd(dy, bcx, w, interpret=False):
    """The pull-back of `gated_conv_fwd` along dy [b, s, h] -> (dbcx [b, s,
    3 h] in bcx's type, dw [taps, h] float32)."""
    b, s, width = bcx.shape
    n, h = b * s, width // 3
    rows, slab = _blocks(n, s)
    spec = _specs(n, h, rows, w.shape[0])
    flat, dflat = bcx.reshape(n, width), dy.reshape(n, h)
    dbcx, dw = routing.pallas_call(
        functools.partial(_bwd_kernel, seq=s, slab=slab),
        name="gated_conv_bwd", grid=(n // rows,),
        in_specs=[spec["table"], spec["product"], spec["before"],
                  spec["after"], spec["after_c"], spec["taps"]],
        out_specs=[spec["product"], spec["sums"]],
        out_shape=[jax.ShapeDtypeStruct((n, width), bcx.dtype),
                   jax.ShapeDtypeStruct((n // rows,) + w.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(dflat, flat, flat, dflat, flat, w)
    return dbcx.reshape(bcx.shape), jnp.sum(dw, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gated_conv(bcx, w, interpret):
    return gated_conv_fwd(bcx, w.astype(F32), interpret)


def _gated_conv_fwd(bcx, w, interpret):
    return gated_conv_fwd(bcx, w.astype(F32), interpret), (bcx, w)


def _gated_conv_bwd(interpret, res, dy):
    bcx, w = res
    dbcx, dw = gated_conv_bwd(dy, bcx, w.astype(F32), interpret)
    return dbcx, dw.astype(w.dtype)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def gated_conv(bcx, w, *, interpret=None, use_kernel=None):
    """y = C * conv(B * X) of the product bcx = [B | C | X] [b, s, 3 h] and
    the taps w [taps, h] (module docstring), differentiable in both. On a
    TPU by the two kernels; else by `gated_conv_xla`."""
    use_kernel, interpret = routing.route(
        "gated_conv", supports(bcx.shape, w.shape[0], bcx.dtype),
        (bcx.shape, w.shape[0], str(bcx.dtype)), interpret, use_kernel)
    if not use_kernel:
        return gated_conv_xla(bcx, w)
    return _gated_conv(bcx, w, interpret)
