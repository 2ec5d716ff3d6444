"""Chunked state-space scan (Mamba-2's SSD form), forward and backward.

Per head h of group g = h // (H / G), with a state S [N, P] carried over
time (x_t [P], B_t and C_t [N] of the head's group, dt_t > 0, A < 0):

  S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T        y_t = S_t^T C_t + D x_t

The program computes it a chunk of Q steps at a time. With a = dt A and c
its running sum INSIDE the chunk (c_i = a_1 + .. + a_i <= 0):

  Y      = (C B^T o L)(dt o X) + diag(exp c) C S_prev + D X
  L_ij   = exp(c_i - c_j) for i >= j, else 0
  S_next = exp(c_Q) S_prev + B^T (exp(c_Q - c) o dt o X)

Always the exponent of a difference that is <= 0: with the published A in
[1, 16] and dt up to 0.1 a chunk's c reaches -200, where exp(c_i) and
exp(-c_j) apart leave float32. dt, A, c, L and the carried state are
float32; the products' operands are x's type (bfloat16 in training), their
accumulation float32.

* `ssd_scan_xla`: the algebra above in `jnp` ([b, H, chunks, Q, Q] decay
  matrices written out; a `lax.scan` over the chunks carries S): the CPU
  path, and the kernels' second opinion.
* `ssd_scan_fwd`, grid (batch, group, chunk), the chunk axis sequential:
  the group's heads' states stay in VMEM scratch [N, heads P] across the
  chunk steps; C B^T is formed once a grid step and shared by the group's
  heads; heads are taken 128 lanes at a time (two heads of 64), so every
  product's shapes are multiples of 128: a head's [Q, Q] x [Q, 128]
  product runs over the whole slab and a lane select keeps its own half.
  Under a VJP it also writes each chunk's S_prev out (float32).
* `ssd_scan_bwd`, the same grid with the chunks in reverse, dS carried in
  scratch: reads x, B, C, dt, c, S_prev and dY once and writes dx, dB, dC
  and the three small cotangents (dt's, and c's in two layouts: the row
  sums of dL o L come out as a column, the column sums as a row; XLA adds
  them after a transposition of an [r, Q] table).

What stays XLA's: c's running sum and its pull-back, D x, and the layout
changes of the [b, L, H] tables (a few MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import routing
from .flash_attention import _LANES, _Z, _dot, pl, pltpu

__all__ = ["ssd_scan", "ssd_scan_xla", "ssd_scan_fwd", "ssd_scan_bwd",
           "supports", "visited_chunks"]

F32 = jnp.float32


def visited_chunks(batch: int, seq: int, chunk: int) -> int:
    """Chunks one scan of [batch, seq] visits (every head group visits the
    same ones); refuses a sequence the chunk does not divide."""
    if seq % chunk:
        raise ValueError(f"ssd_scan: seq {seq} is not a multiple of "
                         f"chunk {chunk}")
    return batch * (seq // chunk)


def supports(x_shape, groups, state, chunk, dtype) -> bool:
    """Whether the kernels take this problem on a TPU."""
    _, seq, heads, p = x_shape
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return (_LANES % p == 0 and heads % groups == 0
            and (heads // groups * p) % _LANES == 0
            and state % _LANES == 0 and chunk % _LANES == 0
            and seq % chunk == 0)


def _within_chunk_sums(dt, A, chunk):
    """c [b, L, H] float32: the running sum of dt A inside each chunk."""
    b, seq, heads = dt.shape
    a = (dt.astype(F32) * A.astype(F32)).reshape(b, seq // chunk, chunk, heads)
    return jnp.cumsum(a, axis=2).reshape(b, seq, heads)


# -- the same algebra in jnp --------------------------------------------------

def _core_xla(x, dt, c, B, C, chunk):
    """y without D x. x [b, L, H, P]; dt, c [b, L, H] float32; B, C
    [b, L, G, N] -> [b, L, H, P] float32."""
    b, seq, heads, p = x.shape
    groups, n = B.shape[2:]
    r, nc, q = heads // groups, seq // chunk, chunk
    op = x.dtype

    def ein(spec, *ops):
        return jnp.einsum(spec, *ops, preferred_element_type=F32)

    xs = x.reshape(b, nc, q, groups, r, p)
    dts = dt.reshape(b, nc, q, groups, r)
    cs = c.reshape(b, nc, q, groups, r)
    Bs, Cs = B.reshape(b, nc, q, groups, n), C.reshape(b, nc, q, groups, n)
    xd = (xs.astype(F32) * dts[..., None]).astype(op)
    g = ein("bkign,bkjgn->bkgij", Cs, Bs)
    diff = cs[:, :, :, None] - cs[:, :, None]              # [b,k,i,j,g,r]
    keep = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])
    decay = jnp.exp(jnp.where(keep[None, None, :, :, None, None], diff,
                              -jnp.inf))
    m = (g.transpose(0, 1, 3, 4, 2)[..., None] * decay).astype(op)
    y = ein("bkijgr,bkjgrp->bkigrp", m, xd)
    last = cs[:, :, -1:]                                    # [b,k,1,g,r]
    xw = (xd.astype(F32) * jnp.exp(last - cs)[..., None]).astype(op)
    add = ein("bkjgn,bkjgrp->bkgrnp", Bs, xw)               # each chunk's own

    def step(s, ins):
        grow, keep_ = ins
        return keep_[..., None, None] * s + grow, s

    s0 = jnp.zeros((b, groups, r, n, p), F32)
    _, s_prev = jax.lax.scan(
        step, s0, (add.swapaxes(0, 1),
                   jnp.exp(last[:, :, 0]).swapaxes(0, 1)))
    s_prev = s_prev.swapaxes(0, 1)                          # [b,k,g,r,n,p]
    y = y + jnp.exp(cs)[..., None] * ein("bkign,bkgrnp->bkigrp", Cs,
                                         s_prev.astype(op))
    return y.reshape(b, seq, heads, p)


def ssd_scan_xla(x, dt, A, B, C, D, chunk=128):
    """`ssd_scan` by XLA alone (module docstring)."""
    visited_chunks(x.shape[0], x.shape[1], chunk)
    c = _within_chunk_sums(dt, A, chunk)
    y = _core_xla(x, dt.astype(F32), c, B, C, chunk)
    return (y + D.astype(F32)[:, None] * x.astype(F32)).astype(x.dtype)


# -- the kernels ----------------------------------------------------------------

def _spread(cols, p, shape, axis):
    """Per-head scalars (`cols[j]` broadcastable to `shape`) laid over the
    slab's lanes: head j's over lanes j p .. (j + 1) p - 1."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    out = jnp.broadcast_to(cols[0], shape)
    for j in range(1, len(cols)):
        out = jnp.where(lane >= j * p, cols[j], out)
    return out


def _head_sums(v, p, hp):
    """[rows, 128] -> hp columns [rows, 1]: each head's sum over its lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return [jnp.sum(jnp.where((lane >= j * p) & (lane < (j + 1) * p), v, 0.0),
                    axis=1, keepdims=True) for j in range(hp)]


def _decay(cc, cr, h, keep):
    """L of head h: exp(c_i - c_j) for i >= j, else 0 (float32 [Q, Q])."""
    return jnp.exp(jnp.where(keep, cc[:, h:h + 1] - cr[h:h + 1, :],
                             -jnp.inf))


def _fwd_kernel(x_ref, b_ref, c_ref, dtc_ref, cc_ref, cr_ref, y_ref, *rest,
                p, save):
    s_ref = rest[-1]
    q, width = x_ref.shape
    op = x_ref.dtype
    hp = _LANES // p

    @pl.when(pl.program_id(2) == 0)
    def _first():
        s_ref[...] = jnp.zeros_like(s_ref)

    if save:
        rest[0][...] = s_ref[...]
    bm, cm = b_ref[...], c_ref[...]
    g = _dot(cm, bm, ((1,), (1,)))                           # [Q, Q]
    keep = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    dtc, cc, cr = dtc_ref[...], cc_ref[...], cr_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
    for slab in range(width // _LANES):
        sl = slice(slab * _LANES, (slab + 1) * _LANES)
        heads = [slab * hp + j for j in range(hp)]
        ccol = _spread([cc[:, h:h + 1] for h in heads], p, (q, _LANES), 1)
        dcol = _spread([dtc[:, h:h + 1] for h in heads], p, (q, _LANES), 1)
        last = _spread([cc[q - 1:q, h:h + 1] for h in heads], p,
                       (1, _LANES), 1)
        xd32 = x_ref[:, sl].astype(F32) * dcol
        xd = xd32.astype(op)
        s = s_ref[:, sl]
        y = jnp.exp(ccol) * _dot(cm, s.astype(op), ((1,), (0,)))
        for j, h in enumerate(heads):
            m = (g * _decay(cc, cr, h, keep)).astype(op)
            z = _dot(m, xd, ((1,), (0,)))
            y = y + jnp.where((lane >= j * p) & (lane < (j + 1) * p), z, 0.0)
        y_ref[:, sl] = y.astype(y_ref.dtype)
        xw = (xd32 * jnp.exp(last - ccol)).astype(op)
        s_ref[:, sl] = jnp.exp(last) * s + _dot(bm, xw, ((0,), (0,)))


def _bwd_kernel(x_ref, b_ref, c_ref, dtc_ref, cc_ref, cr_ref, s_in_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcc_ref, dcr_ref,
                ds_ref, *, p):
    q, width = x_ref.shape
    r = dtc_ref.shape[1]
    op = x_ref.dtype
    hp = _LANES // p

    @pl.when(pl.program_id(2) == 0)
    def _first():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    bm, cm = b_ref[...], c_ref[...]
    g = _dot(cm, bm, ((1,), (1,)))
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    keep = row >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    dtc, cc, cr = dtc_ref[...], cc_ref[...], cr_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
    col_of = jax.lax.broadcasted_iota(jnp.int32, (q, r), 1)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (r, q), 0)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dg = jnp.zeros((q, q), F32)
    db = jnp.zeros(db_ref.shape, F32)
    dc = jnp.zeros(dc_ref.shape, F32)
    ddt = jnp.zeros((q, r), F32)
    dcc = jnp.zeros((q, r), F32)
    dcr = jnp.zeros((r, q), F32)
    for slab in range(width // _LANES):
        sl = slice(slab * _LANES, (slab + 1) * _LANES)
        heads = [slab * hp + j for j in range(hp)]
        ccol = _spread([cc[:, h:h + 1] for h in heads], p, (q, _LANES), 1)
        dcol = _spread([dtc[:, h:h + 1] for h in heads], p, (q, _LANES), 1)
        last = _spread([cc[q - 1:q, h:h + 1] for h in heads], p,
                       (1, _LANES), 1)
        x32 = x_ref[:, sl].astype(F32)
        xd32 = x32 * dcol
        xd = xd32.astype(op)
        dy = dy_ref[:, sl]
        dy32 = dy.astype(F32)
        s, dsn = s_in_ref[:, sl], ds_ref[:, sl]
        s_op, dsn_op = s.astype(op), dsn.astype(op)
        # y's part through the carried state: diag(exp c) C S_prev
        e = jnp.exp(ccol)
        edy = (e * dy32).astype(op)
        dc += _dot(edy, s_op, ((1,), (1,)))                   # [Q, N]
        ds_prev = _dot(cm, edy, ((0,), (0,)))                 # [N, 128]
        dc_lane = dy32 * e * _dot(cm, s_op, ((1,), (0,)))
        # S_next = exp(c_Q) S_prev + B^T (w o dt o X), w = exp(c_Q - c)
        w = jnp.exp(last - ccol)
        t = _dot(bm, dsn_op, ((1,), (0,)))                    # [Q, 128]
        db += _dot((xd32 * w).astype(op), dsn_op, ((1,), (1,)))
        dxd = w * t
        dw_lane = dxd * xd32
        keep_s = jnp.exp(last)
        dlast_lane = jnp.sum(dsn * keep_s * s, axis=0, keepdims=True)
        ds_ref[:, sl] = keep_s * dsn + ds_prev
        rows, cols = [], []
        for j, h in enumerate(heads):
            mine = (lane >= j * p) & (lane < (j + 1) * p)
            decay = _decay(cc, cr, h, keep)
            m32 = g * decay
            dxd = dxd + jnp.where(
                mine, _dot(m32.astype(op), dy, ((0,), (0,))), 0.0)
            dm = _dot(jnp.where(mine, dy, jnp.zeros_like(dy)), xd,
                      ((1,), (1,)))                           # [Q, Q]
            dg = dg + dm * decay
            rl = dm * m32
            rows.append(jnp.sum(rl, axis=1, keepdims=True))   # [Q, 1]
            cols.append(jnp.sum(rl, axis=0, keepdims=True))   # [1, Q]
        dx_ref[:, sl] = (dxd * dcol).astype(dx_ref.dtype)
        from_y = _head_sums(dc_lane, p, hp)
        from_w = _head_sums(dw_lane, p, hp)
        from_last = _head_sums(dlast_lane, p, hp)             # [1, 1] each
        from_dt = _head_sums(dxd * x32, p, hp)
        for j, h in enumerate(heads):
            at_last = jnp.sum(from_w[j], axis=0, keepdims=True) + from_last[j]
            col = (rows[j] + from_y[j] - from_w[j]
                   + jnp.where(is_last, at_last, 0.0))
            dcc = jnp.where(col_of == h, col, dcc)
            ddt = jnp.where(col_of == h, from_dt[j], ddt)
            dcr = jnp.where(row_of == h, -cols[j], dcr)
    dg_op = dg.astype(op)
    dc_ref[...] = (dc + _dot(dg_op, bm, ((1,), (0,)))).astype(dc_ref.dtype)
    db_ref[...] = (db + _dot(dg_op, cm, ((0,), (0,)))).astype(db_ref.dtype)
    ddt_ref[...] = ddt
    dcc_ref[...] = dcc
    dcr_ref[...] = dcr


def _tables(v, groups):
    """[b, L, H] -> ([b, G, L, r] (a head's entries a column), [b, G, r, L]
    (a row))."""
    b, seq, heads = v.shape
    v = v.reshape(b, seq, groups, heads // groups)
    return v.transpose(0, 2, 1, 3), v.transpose(0, 2, 3, 1)


def _saved_spec(n, width, groups, nc, chunk_of):
    """A chunk's incoming state, rows ((batch, group, chunk), N) of ONE
    two-dimensional table: a table of more dimensions XLA lays out anew
    between the two kernels (1.2 ms a sequence and layer on a v5e)."""
    return pl.BlockSpec((n, width), lambda bi, g, k: (
        (bi * np.int32(groups) + g) * np.int32(nc) + chunk_of(k), _Z))


def _specs(q, n, r, width, chunk_of):
    def at(*tail):
        return lambda bi, g, k: (bi,) + tuple(
            {"g": g, "k": chunk_of(k), "0": _Z}[t] for t in tail)
    wide = pl.BlockSpec((None, q, width), at("k", "g"))
    state = pl.BlockSpec((None, q, n), at("k", "g"))
    col = pl.BlockSpec((None, None, q, r), at("g", "k", "0"))
    rows = pl.BlockSpec((None, None, r, q), at("g", "0", "k"))
    return wide, state, col, rows


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _geometry(x, dt, B, groups):
    b, seq, hp_all = x.shape
    heads = dt.shape[2]
    return b, seq, heads, hp_all // heads, B.shape[2] // groups


def ssd_scan_fwd(x, dt, c, B, C, chunk, groups, save=False, interpret=False):
    """x [b, L, H P]; dt, c [b, L, H] float32 (c the running sum of dt A
    inside each chunk); B, C [b, L, G N] -> y [b, L, H P] in x's type
    (without D x) and, with `save`, each chunk's incoming state float32
    [(b, G, chunks, N), H P / G]."""
    b, seq, heads, p, n = _geometry(x, dt, B, groups)
    r, nc = heads // groups, seq // chunk
    width = r * p
    wide, state, col, rows = _specs(chunk, n, r, width, lambda k: k)
    dtc, _ = _tables(dt, groups)
    cc, cr = _tables(c, groups)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [wide]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b * groups * nc * n, width),
                                              F32))
        out_specs.append(_saved_spec(n, width, groups, nc, lambda k: k))
    out = routing.pallas_call(
        functools.partial(_fwd_kernel, p=p, save=save),
        name="ssd_scan_fwd",
        grid=(b, groups, nc),
        in_specs=[wide, state, state, col, col, rows],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, width), F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(x, B, C, dtc, cc, cr)
    return tuple(out) if save else out[0]


def ssd_scan_bwd(x, dt, c, B, C, states, dy, chunk, groups, interpret=False):
    """The pull-back of `ssd_scan_fwd` along dy [b, L, H P], given the
    states it saved -> (dx, ddt, dc, dB, dC) shaped as the operands."""
    b, seq, heads, p, n = _geometry(x, dt, B, groups)
    r, nc = heads // groups, seq // chunk
    width = r * p
    def reverse(k):
        return np.int32(nc - 1) - k

    wide, state, col, rows = _specs(chunk, n, r, width, reverse)
    saved = _saved_spec(n, width, groups, nc, reverse)
    dtc, _ = _tables(dt, groups)
    cc, cr = _tables(c, groups)
    small_col = jax.ShapeDtypeStruct((b, groups, seq, r), F32)
    dx, db, dc, ddt, dcc, dcr = routing.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        name="ssd_scan_bwd",
        grid=(b, groups, nc),
        in_specs=[wide, state, state, col, col, rows, saved, wide],
        out_specs=[wide, state, state, col, col, rows],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype),
                   small_col, small_col,
                   jax.ShapeDtypeStruct((b, groups, r, seq), F32)],
        scratch_shapes=[pltpu.VMEM((n, width), F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(x, B, C, dtc, cc, cr, states, dy)

    def heads_last(v):          # [b, G, L, r] -> [b, L, H]
        return v.transpose(0, 2, 1, 3).reshape(b, seq, heads)

    return (dx, heads_last(ddt),
            heads_last(dcc) + heads_last(dcr.transpose(0, 1, 3, 2)), db, dc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _core(x, dt, c, B, C, chunk, groups, interpret):
    return ssd_scan_fwd(x, dt, c, B, C, chunk, groups, False, interpret)


def _core_fwd(x, dt, c, B, C, chunk, groups, interpret):
    y, states = ssd_scan_fwd(x, dt, c, B, C, chunk, groups, True, interpret)
    return y, (x, dt, c, B, C, states)


def _core_bwd(chunk, groups, interpret, res, dy):
    return ssd_scan_bwd(*res, dy.astype(res[0].dtype), chunk, groups, interpret)


_core.defvjp(_core_fwd, _core_bwd)


def ssd_scan(x, dt, A, B, C, D, chunk=128, interpret=None, use_kernel=None):
    """The state-space scan of the module docstring, differentiable in all
    six operands.

    x [b, L, H, P]; dt [b, L, H] (> 0, the step sizes as they enter the
    recurrence: after the softplus); A [H] (< 0); B, C [b, L, G, N] (head
    h reads group h // (H / G)); D [H] -> y [b, L, H, P] in x's type.
    On a TPU, for a geometry `supports` names, by the two kernels; else
    by `ssd_scan_xla`. `seq` has to be a multiple of `chunk`."""
    b, seq, heads, p = x.shape
    groups, n = B.shape[2:]
    visited_chunks(b, seq, chunk)
    geometry = (x.shape, groups, n, chunk, str(x.dtype))
    use_kernel, interpret = routing.route(
        "ssd_scan", supports(x.shape, groups, n, chunk, x.dtype), geometry,
        interpret, use_kernel)
    if not use_kernel:
        return ssd_scan_xla(x, dt, A, B, C, D, chunk)
    dt = dt.astype(F32)
    c = _within_chunk_sums(dt, A, chunk)
    y = _core(x.reshape(b, seq, heads * p), dt, c,
              B.reshape(b, seq, groups * n).astype(x.dtype),
              C.reshape(b, seq, groups * n).astype(x.dtype), chunk, groups,
              interpret).reshape(x.shape)
    return (y.astype(F32) + D.astype(F32)[:, None] * x.astype(F32)
            ).astype(x.dtype)
