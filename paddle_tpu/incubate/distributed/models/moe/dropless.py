"""Dropless top-k mixture of experts, one chip's share of the experts.

`moe_layer.MoELayer` is the capacity path: [T, E, C] dispatch masks, a
token over capacity is dropped. At tens of thousands of tokens and a
hundred experts a mask that drops nothing cannot exist, so this layer
routes by index instead:

  p = softmax(h Wr) over ALL `num_experts`, in float32
  E_t = the top `top_k` of p[t]; g[t, e] = p[t, e] / sum_{E_t} p
  y[t] = sum_{e in E_t and held} g[t, e] * Wd_e(silu(Wg_e h) * Wu_e h)

`held_experts=(lo, hi)` names the contiguous experts whose weights this
layer holds (all by default). It routes over all of them and computes its
own part: what the absent experts would add is left out, and the partial
sum goes on (in an expert-parallel deployment the other chips' parts are
added by the combine). No token is dropped, whatever the imbalance.

How: the (token, expert) pairs of held experts are sorted by expert, each
expert's rows padded to a multiple of `tile_rows`, and a loop runs over
the tiles that hold a routed row — a tile gathers its tokens, runs the
three products with its expert's weights and adds its rows back, so no
buffer of the worst routing's size exists beyond the int32 row tables
([T * top_k + held * tile_rows]) and the work follows the routing that
happened. The loop's trip count is data; its backward is a second loop
(custom VJP).

How a tile's rows move. The gather is XLA's (`x[idx]`). The add-back on
TPU, for a row of whole lanes, is by row DMAs (`ops/pallas/moe_rows.py
row_adds`): the float32 accumulator is born as [T, 1, K], where a row is
one contiguous copy, `moe_add_rows` adds a tile's rows into it in place,
and it is reshaped once after the loop. On CPU, and for any other width,
XLA's scatter-add on [T, K]. Either way the additions within a token keep
the tiles' order and their float32 width. A padding row names token 0
with weight 0: it is computed, and the kernel never writes it (the first
`tile_real` rows of a tile are real), for under read-modify-write its copy
back would meet token 0's own row; XLA's scatter adds its 0.0.

In a device trace (xprof, Perfetto) every operation of the layer carries
one of these `jax.named_scope` paths, forward and backward (they are part
of `paddle_tpu.profiler.DEVICE_SCOPES`):
  moe/route/router    the router product, softmax, top-k, renormalised
                      gates, the picks' count and the balance term
  moe/route/plan      `dispatch_plan` (counts, the stable sort, the row
                      tables), `row_w`, `tile_real`, the counters
  moe/route/gather    a tile's slices of the tables, `x[idx]`, `dout[idx]`
  moe/route/add_back  the accumulator's zeros and final reshape, a tile's
                      add-back, `drow`'s update, and both tile loops'
                      `while` with the copies of its carry
  moe/experts         the three products of a tile and their gradients
  moe/cast            float32 staging round the loops: the cotangent cast
                      up, the output and the gradients cast back
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..... import nn
from .....ops._dispatch import nary
from .....ops.pallas.moe_rows import row_adds

__all__ = ["DroplessMoE", "dropless_moe", "route_topk", "dispatch_plan",
           "grouped_ffn"]

F32 = jnp.float32
I32 = jnp.int32


def route_topk(logits, top_k, renormalise=True):
    """-> (p [T, E] float32, experts [T, k] int32, gates [T, k] float32)."""
    p = jax.nn.softmax(logits.astype(F32), axis=-1)
    top, experts = jax.lax.top_k(p, top_k)         # ties: lower index
    if renormalise:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return p, experts.astype(I32), top


def plan_rows(pairs: int, held: int, tile: int) -> int:
    """Rows of the row tables: every pair on a held expert, and each held
    expert's last tile padded."""
    return -(-(pairs + held * (tile - 1)) // tile) * tile


def dispatch_plan(experts, held, tile):
    """Row tables of one routing. experts int32 [T, k]; held = (lo, hi).

    -> row_token [M] (the token each row computes; 0 on padding),
       row_pair [M] (its flat pair index t * k + slot; T * k on padding),
       tile_expert [M / tile] (local expert of each tile), n_tiles,
       counts [hi - lo] (pairs routed to each held expert)."""
    lo, hi = held
    g = hi - lo
    t, k = experts.shape
    pairs = t * k
    m = plan_rows(pairs, g, tile)
    flat = experts.reshape(-1)
    local = jnp.where((flat >= lo) & (flat < hi), flat - lo, g)
    counts = jnp.zeros((g + 1,), I32).at[local].add(1)
    order = jnp.argsort(local, stable=True).astype(I32)
    sorted_local = local[order]
    starts = jnp.cumsum(counts) - counts
    padded = -(-counts[:g] // tile) * tile
    ends = jnp.cumsum(padded)
    offsets = jnp.concatenate([ends - padded, jnp.full((1,), m, I32)])
    rank = jnp.arange(pairs, dtype=I32) - starts[sorted_local]
    dest = jnp.where(sorted_local < g, offsets[sorted_local] + rank, m)
    row_token = jnp.zeros((m,), I32).at[dest].set(order // k, mode="drop")
    row_pair = jnp.full((m,), pairs, I32).at[dest].set(order, mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(m // tile, dtype=I32) * tile,
                         side="right"), g - 1).astype(I32)
    return row_token, row_pair, tile_expert, ends[-1] // tile, counts[:g]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=F32)


def _tile(i, tile, row_token, row_w, tile_expert, tile_real):
    with jax.named_scope("moe/route/gather"):
        idx = jax.lax.dynamic_slice(row_token, (i * tile,), (tile,))
        w = jax.lax.dynamic_slice(row_w, (i * tile,), (tile,))
        return idx, w, tile_expert[i], tile_real[i]


def _ffn_fwd_loop(x, wg, wu, wd, row_token, row_w, tile_expert, tile_real,
                  n_tiles, tile):
    adds = row_adds(x.shape[1], tile)

    def body(i, out):
        idx, w, e, n_real = _tile(i, tile, row_token, row_w, tile_expert,
                                  tile_real)
        with jax.named_scope("moe/route/gather"):
            h = x[idx]
        with jax.named_scope("moe/experts"):
            a = (jax.nn.silu(_dot(h, wg[e], ((1,), (0,))))
                 * _dot(h, wu[e], ((1,), (0,)))).astype(x.dtype)
            y = _dot(a, wd[e], ((1,), (0,))) * w[:, None]
        with jax.named_scope("moe/route/add_back"):
            return adds.add(out, idx, y, n_real)

    with jax.named_scope("moe/route/add_back"):
        return adds.whole(jax.lax.fori_loop(0, n_tiles, body,
                                            adds.zeros(x.shape)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def grouped_ffn(x, wg, wu, wd, row_token, row_w, tile_expert, tile_real,
                n_tiles, tile):
    """sum over the rows r of a routing of row_w[r] * FFN_e(x[row_token[r]])
    added at row_token[r] -> float32 [T, K]. x [T, K]; wg, wu [G, K, N];
    wd [G, N, K]; the tables are `dispatch_plan`'s, `tile_real` [M / tile]
    the rows of each tile that hold a routed pair (its first rows: the
    tokens of a tile's real rows are distinct, and the rest are padding,
    which is computed and never added back). Tiles from `n_tiles` on are
    not computed."""
    return _ffn_fwd_loop(x, wg, wu, wd, row_token, row_w, tile_expert,
                         tile_real, n_tiles, tile)


def _grouped_ffn_fwd(x, wg, wu, wd, row_token, row_w, tile_expert, tile_real,
                     n_tiles, tile):
    out = _ffn_fwd_loop(x, wg, wu, wd, row_token, row_w, tile_expert,
                        tile_real, n_tiles, tile)
    return out, (x, wg, wu, wd, row_token, row_w, tile_expert, tile_real,
                 n_tiles)


def _grouped_ffn_bwd(tile, res, dout):
    x, wg, wu, wd, row_token, row_w, tile_expert, tile_real, n_tiles = res
    adds = row_adds(x.shape[1], tile)
    with jax.named_scope("moe/cast"):
        dout = dout.astype(F32)

    def body(i, carry):
        dx, dwg, dwu, dwd, drow = carry
        idx, w, e, n_real = _tile(i, tile, row_token, row_w, tile_expert,
                                  tile_real)
        with jax.named_scope("moe/route/gather"):
            h = x[idx]
            dy_rows = dout[idx]
        with jax.named_scope("moe/experts"):
            g = _dot(h, wg[e], ((1,), (0,)))
            u = _dot(h, wu[e], ((1,), (0,)))
            sg = jax.nn.sigmoid(g)
            act = g * sg
            a = (act * u).astype(x.dtype)
            # the gate weight's gradient needs the unweighted output
            drow_i = jnp.sum(dy_rows * _dot(a, wd[e], ((1,), (0,))), -1)
            dy = (dy_rows * w[:, None]).astype(x.dtype)
            da = _dot(dy, wd[e], ((1,), (1,)))
            du = (da * act).astype(x.dtype)
            dg = (da * u * sg * (1.0 + g * (1.0 - sg))).astype(x.dtype)
            dh = (_dot(dg, wg[e], ((1,), (1,)))
                  + _dot(du, wu[e], ((1,), (1,))))
            dwg = dwg.at[e].add(_dot(h, dg, ((0,), (0,))))
            dwu = dwu.at[e].add(_dot(h, du, ((0,), (0,))))
            dwd = dwd.at[e].add(_dot(a, dy, ((0,), (0,))))
        with jax.named_scope("moe/route/add_back"):
            dx = adds.add(dx, idx, dh, n_real)
            drow = jax.lax.dynamic_update_slice(drow, drow_i, (i * tile,))
        return dx, dwg, dwu, dwd, drow

    # the loop stands under a leaf, so that its `while` and the copies of
    # its carry are the add-back's, whose accumulator they move
    with jax.named_scope("moe/route/add_back"):
        init = (adds.zeros(x.shape), jnp.zeros(wg.shape, F32),
                jnp.zeros(wu.shape, F32), jnp.zeros(wd.shape, F32),
                jnp.zeros(row_w.shape, F32))
        dx, dwg, dwu, dwd, drow = jax.lax.fori_loop(0, n_tiles, body, init)
        dx = adds.whole(dx)
    with jax.named_scope("moe/cast"):
        return (dx.astype(x.dtype), dwg.astype(wg.dtype),
                dwu.astype(wu.dtype), dwd.astype(wd.dtype), None,
                drow.astype(row_w.dtype), None, None, None)


grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


def dropless_moe(h, wr, wg, wu, wd, *, top_k, held, tile_rows,
                 renormalise=True, balance_coef=0.0):
    """The layer on arrays: h [T, K] -> (y [T, K] in h's type, the
    load-balancing term, stats float32 [3], the picked experts int32
    [T, top_k]).

    stats = (pairs routed to held experts, rows the grouped product
    computed, the held experts' largest load). The
    balancing term is `balance_coef * E * sum_e f_e P_e` over the whole
    router: f_e the share of tokens that picked e (no gradient), P_e the
    mean of p[:, e]."""
    n_experts = wr.shape[-1]
    with jax.named_scope("moe/route/router"):
        p, experts, gates = route_topk(
            _dot(h, wr, ((1,), (0,))), top_k, renormalise)
        picked = jnp.zeros((n_experts,), F32).at[experts.reshape(-1)].add(1.0)
        balance = balance_coef * n_experts * jnp.sum(
            jax.lax.stop_gradient(picked / h.shape[0]) * jnp.mean(p, 0))
    with jax.named_scope("moe/route/plan"):
        row_token, row_pair, tile_expert, n_tiles, counts = dispatch_plan(
            experts, held, tile_rows)
        row_w = jnp.concatenate([gates.reshape(-1),
                                 jnp.zeros((1,), F32)])[row_pair]
        tile_real = jnp.sum(
            (row_pair < experts.size).reshape(-1, tile_rows), 1, dtype=I32)
    y = grouped_ffn(h, wg, wu, wd, row_token, row_w, tile_expert, tile_real,
                    n_tiles, tile_rows)
    with jax.named_scope("moe/route/plan"):
        load = counts.astype(F32)
        stats = jnp.stack([jnp.sum(load), (n_tiles * tile_rows).astype(F32),
                           jnp.max(load)])
    with jax.named_scope("moe/cast"):
        return y.astype(h.dtype), balance, stats, experts


class DroplessMoE(nn.Layer):
    """Top-k dropless mixture of SiLU-gated experts (module docstring).

    Args:
      d_model, d_expert: token width and each expert's inner width.
      num_experts, top_k: the router's width and picks per token.
      held_experts: (lo, hi), the contiguous experts this layer holds and
        computes; None holds all. The router is always whole.
      renormalise: divide the picked probabilities by their sum.
      balance_coef: weight of the load-balancing term (0: none).
      tile_rows: rows of one step of the grouped product; each held
        expert's rows are padded to a multiple of it.

    forward(x [..., d_model]) -> (y, balance term, stats [3], picks int32
    [tokens, top_k]); `stats` is `dropless_moe`'s.
    """

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 held_experts=None, renormalise=True, balance_coef=0.0,
                 tile_rows=512):
        super().__init__()
        lo, hi = held_experts or (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"held_experts {held_experts} outside the "
                             f"router's {num_experts}")
        self.held_experts = (int(lo), int(hi))
        self.top_k, self.tile_rows = int(top_k), int(tile_rows)
        self.renormalise, self.balance_coef = renormalise, balance_coef
        held = hi - lo
        self.router = self.create_parameter([d_model, num_experts])
        self.gate_proj = self.create_parameter([held, d_model, d_expert])
        self.up_proj = self.create_parameter([held, d_model, d_expert])
        self.down_proj = self.create_parameter([held, d_expert, d_model])

    def forward(self, x):
        shape = x.shape

        def run(h, wr, wg, wu, wd):
            y, balance, stats, picks = dropless_moe(
                h.reshape(-1, shape[-1]), wr, wg, wu, wd, top_k=self.top_k,
                held=self.held_experts, tile_rows=self.tile_rows,
                renormalise=self.renormalise,
                balance_coef=self.balance_coef)
            return y.reshape(shape), balance, stats, picks

        return nary(run, [x, self.router, self.gate_proj, self.up_proj,
                          self.down_proj], "dropless_moe")
