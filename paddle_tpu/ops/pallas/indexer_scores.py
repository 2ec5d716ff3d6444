"""Index scores of one query chunk and their pull-back, the per-head
scores never leaving VMEM (`ops/sparse_attention.py indexer_scores`):

  a[t, j, s] = q_idx[t, j] . k_idx[s]
  I[t, s]    = sum_j w[t, j] * relu(a[t, j, s])

XLA writes `a` out (float32 [512, 16, 8192] = 268 MB a chunk at Keye's
widths) and walks it five times, forward and backward: each pass bound
by that one intermediate. The two kernels below form a key tile's `a`
one head at a time and keep it:

* `indexer_scores_fwd`, grid (key tile): per head one [t, d] x [d, bk]
  product, relu, times w[:, j], summed into the resident output tile.
* `indexer_scores_bwd`, grid (key tile): the products again, then
  dw[t, j] += sum_s g relu(a_j), dq[j] += (g | a_j > 0) @ k (both
  resident) and dk[tile] = sum_j (g | a_j > 0)^T @ (w_j q_j).

Causal work only: a chunk whose first query is `t0` (scalar prefetch)
reaches keys 0 .. t0 + t - 1; a key tile beyond them has no body and no
copy. Its part of I is left unwritten (the selection reads scores only
under `valid`), its part of dk is written as zeros.

Precision. XLA's step feeds the MXU the bfloat16 q and k as they are and
the float32 cotangent `g w (a > 0)` at operand precision `highest`, three
bfloat16 addends. Here `g` is cut into its three bfloat16 addends once a
tile (`_addends`: exact), masked per head; w is applied in float32, to
dq's rows after the product and to q's rows before dk's (`w_j q_j` is
exact in two bfloat16 addends, side by side in one 128-wide operand), so
no operand is rounded. float32 inputs take float32 products.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import routing
from .flash_attention import _LANES, _Z, _dot, pl, pltpu

__all__ = ["causal_indexer_scores", "indexer_scores_fwd",
           "indexer_scores_bwd", "supports"]

F32 = jnp.float32


def _pick_block(s: int):
    # the chunk's own width: the causal edge then falls on a tile's end,
    # and a [512, 512] float32 tile, its three addends and the operands'
    # double buffers fit the 16 MiB of VMEM a kernel gets unasked. 1024
    # measured the same on a v5e (398 us a chunk's backward both)
    for blk in (512, 256, 128):
        if s % blk == 0:
            return blk
    return None


def supports(q_shape, k_shape, dtype) -> bool:
    t, _, d = q_shape
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    # d <= 64: the two addends of w q side by side fill the 128 lanes
    return d <= 64 and t % 16 == 0 and _pick_block(k_shape[0]) is not None


def _addends(x, dtype, n):
    """float32 x as addends exact in `dtype`: x itself for float32, else
    the first n of its bfloat16 expansion (three hold any float32; two a
    product of two bfloat16). Kept in float32."""
    if dtype == F32:
        return [x]
    out = []
    for _ in range(n - 1):
        out.append(x.astype(dtype).astype(F32))
        x = x - out[-1]
    return out + [x]


def _last_tile(t0_ref, t, bk):
    return jax.lax.div(t0_ref[0] + np.int32(t - 1), np.int32(bk))


def _fwd_kernel(t0_ref, q_ref, k_ref, wb_ref, o_ref, *, bk):
    heads, t, _ = q_ref.shape

    @pl.when(pl.program_id(0) <= _last_tile(t0_ref, t, bk))
    def _tile():
        k = k_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

        def head(j, carry):
            a = _dot(q_ref[j], k, ((1,), (1,)))              # [t, bk] fp32
            o_ref[...] += jnp.maximum(a, 0.0) * wb_ref[j][:, :1]
            return carry

        jax.lax.fori_loop(0, heads, head, 0)


def _bwd_kernel(t0_ref, q_ref, qw_ref, k_ref, g_ref, dq_ref, dk_ref, dw_ref,
                *, bk):
    heads, t, _ = q_ref.shape
    i = pl.program_id(0)
    last = _last_tile(t0_ref, t, bk)

    @pl.when(i == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(i > last)
    def _beyond():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(i <= last)
    def _tile():
        k, g = k_ref[...], g_ref[...]
        parts = _addends(g, k.dtype, 3)
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape, 1)

        def head(j, dk):
            a = _dot(q_ref[j], k, ((1,), (1,)))              # [t, bk] fp32
            live = a > 0.0
            dw_ref[...] += jnp.where(
                lane == j, jnp.sum(jnp.where(live, a, 0.0) * g, axis=1,
                                   keepdims=True), 0.0)
            dq = jnp.zeros(dq_ref.shape[1:], F32)
            for p in parts:
                p = jnp.where(live, p, 0.0).astype(k.dtype)
                dq += _dot(p, k, ((1,), (0,)))               # [t, d]
                dk += _dot(p, qw_ref[j], ((0,), (0,)))       # [bk, parts d]
            dq_ref[j] += dq
            return dk

        dk_ref[...] = jax.lax.fori_loop(0, heads, head,
                                        jnp.zeros(dk_ref.shape, F32))


def _tiles(t, bk):
    """Index maps of a [.., bk]-tiled operand: its own tile up to the
    chunk's last causal one, that one again beyond (no copy)."""
    def held(i, t0):
        return jax.lax.min(i, _last_tile(t0, t, bk))
    return (lambda i, t0: (held(i, t0), _Z)), (lambda i, t0: (_Z, held(i, t0)))


def _whole(rank):
    return lambda i, t0: (_Z,) * rank


def indexer_scores_fwd(q_idx, k_idx, w, t0, block_k=None, interpret=False):
    """q_idx [t, j, d], k_idx [s, d], w [t, j], t0 int32 (the chunk's
    first query) -> I float32 [t, s]; columns of key tiles beyond the
    chunk's last query are not written."""
    t, heads, d = q_idx.shape
    s = k_idx.shape[0]
    bk = block_k or _pick_block(s)
    rows, cols = _tiles(t, bk)
    wb = jnp.broadcast_to(w.astype(F32).T[:, :, None], (heads, t, _LANES))
    return routing.pallas_call(
        functools.partial(_fwd_kernel, bk=bk),
        name="indexer_scores_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s // bk,),
            in_specs=[pl.BlockSpec((heads, t, d), _whole(3)),
                      pl.BlockSpec((bk, d), rows),
                      pl.BlockSpec((heads, t, _LANES), _whole(3))],
            out_specs=pl.BlockSpec((t, bk), cols)),
        out_shape=jax.ShapeDtypeStruct((t, s), F32),
        interpret=interpret,
    )(jnp.reshape(t0, (1,)).astype(jnp.int32),
      jnp.transpose(q_idx, (1, 0, 2)), k_idx, wb)


def indexer_scores_bwd(q_idx, k_idx, w, t0, g, block_k=None,
                       interpret=False):
    """The pull-back of `indexer_scores_fwd` along g float32 [t, s], zero
    beyond each query's own position -> (dq_idx [t, j, d], dk_idx [s, d],
    dw [t, j]) in float32; dk_idx of a key tile beyond the chunk's last
    query is an exact zero."""
    t, heads, d = q_idx.shape
    s = k_idx.shape[0]
    bk = block_k or _pick_block(s)
    rows, cols = _tiles(t, bk)
    qh = jnp.transpose(q_idx, (1, 0, 2))
    w32 = w.astype(F32)
    # w_j q_j, float32 rows as addends of the operands' type side by side
    qw = jnp.concatenate([
        p.astype(q_idx.dtype) for p in _addends(
            qh.astype(F32) * w32.T[:, :, None], q_idx.dtype, 2)], axis=-1)
    dq, dk, dw = routing.pallas_call(
        functools.partial(_bwd_kernel, bk=bk),
        name="indexer_scores_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s // bk,),
            in_specs=[pl.BlockSpec((heads, t, d), _whole(3)),
                      pl.BlockSpec(qw.shape, _whole(3)),
                      pl.BlockSpec((bk, d), rows),
                      pl.BlockSpec((t, bk), cols)],
            out_specs=[pl.BlockSpec((heads, t, d), _whole(3)),
                       pl.BlockSpec((bk, qw.shape[-1]),
                                    lambda i, t0: (i, _Z)),
                       pl.BlockSpec((t, heads), _whole(2))]),
        out_shape=[jax.ShapeDtypeStruct((heads, t, d), F32),
                   jax.ShapeDtypeStruct((s, qw.shape[-1]), F32),
                   jax.ShapeDtypeStruct((t, heads), F32)],
        interpret=interpret,
    )(jnp.reshape(t0, (1,)).astype(jnp.int32), qh, qw, k_idx, g)
    return (jnp.transpose(dq, (1, 0, 2)) * w32[:, :, None],
            sum(dk[:, c:c + d] for c in range(0, dk.shape[1], d)), dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _scores(q_idx, k_idx, w, t0, block_k, interpret):
    return indexer_scores_fwd(q_idx, k_idx, w, t0, block_k, interpret)


def _scores_fwd(q_idx, k_idx, w, t0, block_k, interpret):
    return (indexer_scores_fwd(q_idx, k_idx, w, t0, block_k, interpret),
            (q_idx, k_idx, w, t0))


def _scores_bwd(block_k, interpret, res, g):
    grads = indexer_scores_bwd(*res, g, block_k, interpret)
    return tuple(x.astype(r.dtype) for x, r in zip(grads, res)) + (None,)


_scores.defvjp(_scores_fwd, _scores_bwd)


def causal_indexer_scores(q_idx, k_idx, w, t0, block_k=None,
                          interpret=False):
    """`indexer_scores` of the chunk of queries t0 .. t0 + t - 1 by the
    two kernels, differentiable in q_idx, k_idx and w (the residuals are
    those three). What it returns beyond the chunk's last query, and what
    its cotangent holds there, is unspecified and unread."""
    return _scores(q_idx, k_idx, w, t0, block_k, bool(interpret))
