"""Head-averaged attention probabilities over selected keys — the target
of a sparse-attention indexer's loss (`ops/sparse_attention.py`).

  out[t, s] = mean_h softmax_s(q[t, h] . k[s, kv(h)] * scale | sel[t, s])

for one sequence's chunk of queries against all its keys. XLA forms the
[heads, t, s] logits in HBM and walks them several times (two fusions of
56 ms each per 512-query chunk at 32 heads x 8192 keys on a v5e: 43 s of
a 46 s step); the kernels below never store them. Two passes, as the
softmax needs each row's statistics before any probability:

* `attn_probs_stats`, grid (head, key tile): running row-max / row-sum
  of the selected scores -> the log-sum-exp of every (head, row).
* `attn_probs_mean`, grid (key tile, head): the tile's scores again,
  exp(s - lse), accumulated over the heads in the resident output tile.

Causal work only. The caller gives the chunk's first query position `t0`
(scalar prefetch) and with it its word that no selected key lies beyond
t0 + t - 1. A key tile beyond that position is a grid step with no body
and no copy (the index maps hold the last causal tile's blocks); the
statistics are what they were and the target there is written as exact
zeros, once: what every tile's arithmetic gives where nothing is
selected, so the result is the same to the bit. Without `t0` every tile
is visited. `visited_pairs` counts the entries formed.

GQA: head h reads kv head h // group. Not differentiable (the target is
cut from the graph). Routing is the pack's (`routing.py`): the kernels on
TPU, the XLA expression on CPU, interpret mode on request.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import routing
from .flash_attention import _LANES, _Z, _dot, _pick_block, pl, pltpu
from .indexer_scores import _last_tile

__all__ = ["head_mean_probs", "head_mean_probs_xla", "supports",
           "visited_pairs"]


def supports(q_shape, k_shape, dtype) -> bool:
    t, heads, d = q_shape
    s, kvh, _ = k_shape
    if dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return False
    return (d <= 256 and heads % kvh == 0 and t % 32 == 0
            and _pick_block(s) is not None)


def head_mean_probs_xla(q, k, sel, scale):
    t, heads, d = q.shape
    kvh = k.shape[1]
    logits = jnp.einsum("tkgd,skd->kgts",
                        q.reshape(t, kvh, heads // kvh, d), k,
                        preferred_element_type=jnp.float32) * scale
    return jnp.mean(jax.nn.softmax(
        jnp.where(sel != 0, logits, -jnp.inf), axis=-1), axis=(0, 1))


def _scores(q_ref, k_ref, sel_ref, scale):
    s = _dot(q_ref[0], k_ref[0], ((1,), (1,))) * scale       # [t, bk] fp32
    return jnp.where(sel_ref[...].astype(jnp.int32) != 0, s, -jnp.inf)


def _stats_kernel(t0_ref, q_ref, k_ref, sel_ref, lse_ref, m_ref, l_ref, *,
                  scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j <= _last_tile(t0_ref, *sel_ref.shape))
    def _tile():
        s = _scores(q_ref, k_ref, sel_ref, scale)
        m_prev, l_prev = m_ref[...], l_ref[...]              # [t, LANES]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(
            jnp.max(s, axis=1, keepdims=True), m_prev.shape))
        dead = m_new == -jnp.inf      # no selected key in any tile so far
        corr = jnp.where(dead, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(dead[:, :1], 0.0, jnp.exp(s - m_new[:, :1]))
        l_ref[...] = corr * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        m_ref[...] = m_new

    # on a tile beyond too: the scratch still holds the row's statistics
    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        lse_ref[0] = jnp.where(l_ref[...] > 0.0,
                               m_ref[...] + jnp.log(l_ref[...]), jnp.inf)


def _mean_kernel(t0_ref, q_ref, k_ref, sel_ref, lse_ref, o_ref, *, scale,
                 heads):
    h = pl.program_id(1)
    live = pl.program_id(0) <= _last_tile(t0_ref, *sel_ref.shape)

    @pl.when(h == 0)
    def _init():          # all a tile beyond ever holds: exact zeros
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _tile():
        s = _scores(q_ref, k_ref, sel_ref, scale)
        # lse = +inf on a row with no selected key: every p an exact 0
        o_ref[...] += jnp.exp(s - lse_ref[0][:, :1])

    @pl.when(live & (h == heads - 1))
    def _finish():
        o_ref[...] = o_ref[...] * (1.0 / heads)


def visited_pairs(t, s, block_k=None, t0=None):
    """Score entries each of the two kernels forms for one head of the
    chunk of `t` queries whose first is `t0` (None: every tile), against
    `s` keys; the pairs a causal selection can keep are t (s + 1) / 2 a
    chunk, averaged over a sequence's chunks."""
    bk = block_k or _pick_block(s)
    tiles = s // bk if t0 is None else min((t0 + t - 1) // bk + 1, s // bk)
    return t * bk * tiles


def _kernels(q, k, sel, t0, scale, bk, interpret):
    heads, t, d = q.shape
    kvh, s, _ = k.shape
    grp, nk, top = np.int32(heads // kvh), s // bk, np.int32(heads - 1)

    # Index maps. A key tile is its own up to the chunk's last causal one
    # and that one again beyond; `attn_probs_mean`, whose inner axis is the
    # head, also stays on the last head there: a step beyond copies nothing.
    def tile(j, t0):
        return jax.lax.min(j, _last_tile(t0, t, bk))

    def head(j, h, t0):
        return jax.lax.select(jax.lax.gt(j, _last_tile(t0, t, bk)), top, h)

    def kv(h):
        return jax.lax.div(h, grp)

    lse = routing.pallas_call(
        functools.partial(_stats_kernel, scale=scale),
        name="attn_probs_stats",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, nk),
            in_specs=[
                pl.BlockSpec((1, t, d), lambda h, j, t0: (h, _Z, _Z)),
                pl.BlockSpec((1, bk, d),
                             lambda h, j, t0: (kv(h), tile(j, t0), _Z)),
                pl.BlockSpec((t, bk), lambda h, j, t0: (_Z, tile(j, t0))),
            ],
            out_specs=pl.BlockSpec((1, t, _LANES),
                                   lambda h, j, t0: (h, _Z, _Z)),
            scratch_shapes=[pltpu.VMEM((t, _LANES), jnp.float32),
                            pltpu.VMEM((t, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((heads, t, _LANES), jnp.float32),
        interpret=interpret,
    )(t0, q, k, sel)
    return routing.pallas_call(
        functools.partial(_mean_kernel, scale=scale, heads=heads),
        name="attn_probs_mean",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nk, heads),
            in_specs=[
                pl.BlockSpec((1, t, d),
                             lambda j, h, t0: (head(j, h, t0), _Z, _Z)),
                pl.BlockSpec((1, bk, d), lambda j, h, t0: (
                    kv(head(j, h, t0)), tile(j, t0), _Z)),
                pl.BlockSpec((t, bk), lambda j, h, t0: (_Z, tile(j, t0))),
                pl.BlockSpec((1, t, _LANES),
                             lambda j, h, t0: (head(j, h, t0), _Z, _Z)),
            ],
            out_specs=pl.BlockSpec((t, bk), lambda j, h, t0: (_Z, j))),
        out_shape=jax.ShapeDtypeStruct((t, s), jnp.float32),
        interpret=interpret,
    )(t0, q, k, sel, lse)


def head_mean_probs(q, k, sel, scale=None, t0=None, block_k=None,
                    interpret=None, use_kernel=None):
    """q [t, heads, d], k [s, kv_heads, d], sel int8 [t, s] (non-zero: the
    key is selected) -> float32 [t, s]. `t0` (traced int32): the chunk's
    first query position, the caller's word that no selected key lies
    beyond t0 + t - 1; None for a selection that is not causal."""
    t, heads, d = q.shape
    if scale is None:
        scale = 1.0 / d ** 0.5
    use_kernel, interpret = routing.route(
        "attention_probs", supports(q.shape, k.shape, q.dtype),
        (f"q{tuple(q.shape)}", f"k{tuple(k.shape)}", str(q.dtype)),
        interpret, use_kernel)
    if not use_kernel:
        return head_mean_probs_xla(q, k, sel, scale)
    bk = block_k or _pick_block(k.shape[0])
    # no t0: as a chunk after the last key, which no tile lies beyond
    t0 = k.shape[0] if t0 is None else t0
    return _kernels(jnp.transpose(q, (1, 0, 2)), jnp.transpose(k, (1, 0, 2)),
                    sel.astype(jnp.int8),
                    jnp.reshape(t0, (1,)).astype(jnp.int32), float(scale),
                    int(bk), bool(interpret))
