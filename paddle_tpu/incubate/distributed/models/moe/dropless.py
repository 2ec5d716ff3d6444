"""Dropless top-k mixture of experts, one chip's share of the experts.

`moe_layer.MoELayer` is the capacity path: [T, E, C] dispatch masks, a
token over capacity is dropped. At tens of thousands of tokens and a
hundred experts a mask that drops nothing cannot exist, so this layer
routes by index instead:

  p = score(h Wr) over ALL `num_experts`, in float32
  E_t = the top `top_k` of p[t] (+ a selection bias); g[t, e] from p[t, E_t]
  y[t] = sum_{e in E_t and held} g[t, e] * FFN_e(h)

The layer is told the router's score and the experts' form, two of each:

  score "softmax": p = softmax(logits); g = p[E_t], divided by its sum
      when `renormalise`
  score "sigmoid": p = sigmoid(logits); E_t = the top k of p + `bias` (a
      buffer no gradient reaches; it moves the selection only);
      g = p[E_t] / (sum + eps) when `renormalise`, times `scale`; the
      balance term reads p / sum_e p. With `n_group` > 1 the picks are
      limited to groups: the experts lie in `n_group` contiguous groups, a
      group's score is the sum of its two largest p + bias, and only the
      `topk_group` best groups' experts can be picked (DeepSeek-V3's)
  gated (three weights):   FFN_e(h) = Wd_e (silu(Wg_e h) * Wu_e h)
  ungated (`wg` is None):  FFN_e(h) = Wd_e relu(Wu_e h)^2

`held_experts=(lo, hi)` names the contiguous experts whose weights this
layer holds (all by default). It routes over all of them and computes its
own part: what the absent experts would add is left out, and the partial
sum goes on (in an expert-parallel deployment the other chips' parts are
added by the combine). No token is dropped, whatever the imbalance.

How: the (token, expert) pairs are sorted by expert once, stably, with
their pair index and gate weight as the sort's payloads (`sort_pairs`):
the held experts' pairs become one compact list in expert order. Only the
per-tile tables (`dispatch_plan`: a tile's expert, where it starts in the
list, how many of its rows are real; prefix sums over the held experts)
pad an expert's rows to a multiple of `tile_rows`, so no table of the
worst routing's size is written. A loop runs over the tiles that hold a
routed row — a tile takes `tile_rows` CONSECUTIVE entries of the list,
masks those past its real rows, gathers its tokens, runs the three (two,
ungated) products with its expert's weights and adds its rows back: the work
follows the routing that happened. The loop's trip count is data; its
backward is a second loop (custom VJP) that leaves the gate weights'
gradient in the list's order, and a second sort on the pair index takes
it back. Only vector operations move the routing (compare-and-sum
histograms, two sorts, prefix sums, slices): on TPU an indexed scatter or
gather over the pairs runs an element at a time, ~9 ns each.

How a tile's rows move. The gather is XLA's (`x[idx]`). The add-back on
TPU, for a row of whole lanes, is by row DMAs (`ops/pallas/moe_rows.py
row_adds`): the float32 accumulator is born as [T, 1, K], where a row is
one contiguous copy, `moe_add_rows` adds a tile's rows into it in place,
and it is reshaped once after the loop. On CPU, and for any other width,
XLA's scatter-add on [T, K]. Either way the additions within a token keep
the tiles' order and their float32 width. A row past a tile's real ones
(in the list, the next expert's first pair) is masked to token 0 with
weight 0: it is computed, and the kernel never writes it (a tile's first
`tile_real` rows are real), for under read-modify-write its copy back
would meet token 0's own row; XLA's scatter adds its 0.0.

In a device trace (xprof, Perfetto) every operation of the layer carries
one of these `jax.named_scope` paths, forward and backward (they are part
of `paddle_tpu.profiler.DEVICE_SCOPES`):
  moe/route/router    the router product, softmax, top-k, renormalised
                      gates, the picks' count and the balance term
  moe/route/plan      `dispatch_plan` (`sort_pairs` and its pull-back,
                      the counts, the per-tile tables), the counters
  moe/route/gather    a tile's slices of the list and their mask,
                      `x[idx]`, `dout[idx]`
  moe/route/add_back  the accumulator's zeros and final reshape, a tile's
                      add-back, `drow`'s update, and both tile loops'
                      `while` with the copies of its carry
  moe/experts         the products of a tile and their gradients
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

__all__ = ["DroplessMoE", "dropless_moe", "route_topk", "sort_pairs",
           "dispatch_plan", "grouped_ffn"]

F32 = jnp.float32
I32 = jnp.int32


def route_topk(logits, top_k, renormalise=True, score="softmax", bias=None,
               scale=1.0, n_group=1, topk_group=1, eps=1e-20):
    """-> (p [T, E] float32 (what the balance term reads), experts [T, k]
    int32, gates [T, k] float32). `score`, `bias`, `scale`, `n_group`,
    `topk_group`: module docstring; `eps` is added to the picked sigmoid
    scores' sum before the renormalisation divides by it."""
    if score == "softmax":
        p = jax.nn.softmax(logits.astype(F32), axis=-1)
        top, experts = jax.lax.top_k(p, top_k)     # ties: lower index
        if renormalise:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return p, experts.astype(I32), top
    if score != "sigmoid":
        raise ValueError(f"route_topk: unknown score {score!r}")
    s = jax.nn.sigmoid(logits.astype(F32))
    chosen = s if bias is None else s + jax.lax.stop_gradient(
        bias.astype(F32))
    if n_group > 1:
        by_group = chosen.reshape(chosen.shape[0], n_group, -1)
        best = jax.lax.top_k(jnp.sum(jax.lax.top_k(by_group, 2)[0], -1),
                             topk_group)[1]
        kept = jnp.any(best[:, :, None] == jnp.arange(n_group, dtype=I32),
                       axis=1)
        chosen = jnp.where(kept[:, :, None], by_group,
                           -jnp.inf).reshape(chosen.shape)
    experts = jax.lax.top_k(chosen, top_k)[1]      # ties: lower index
    top = jnp.take_along_axis(s, experts, axis=-1)
    if renormalise:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + eps)
    return (s / jnp.sum(s, axis=-1, keepdims=True), experts.astype(I32),
            top * scale)


def plan_rows(pairs: int, held: int, tile: int) -> int:
    """Most rows a routing can ask of the tile loops: every pair on a held
    expert, and each held expert's last tile padded."""
    return -(-(pairs + held * (tile - 1)) // tile) * tile


def _count(ids, n):
    """Histogram of `ids` over 0..n-1 as a compare and a sum, int32 [n]."""
    return jnp.sum(ids[..., None] == jnp.arange(n, dtype=I32),
                   tuple(range(ids.ndim)), dtype=I32)


@jax.custom_vjp
def sort_pairs(local, gates):
    """local int32 [P], gates float32 [P] -> (pair index, gate) in `local`'s
    order, by one stable sort that carries both. The gates' pull-back is
    the sort back on the pair index: JAX's own (`lax._sort_jvp`) gathers
    the tangents, and that gather's transpose is a scatter-add over P."""
    pair = jnp.arange(local.shape[0], dtype=I32)
    return jax.lax.sort((local, pair, gates), num_keys=1, is_stable=True)[1:]


def _sort_pairs_fwd(local, gates):
    pair, gates = sort_pairs(local, gates)
    return (pair, gates), pair


def _sort_pairs_bwd(pair, cts):
    with jax.named_scope("moe/route/plan"):
        # the keys are distinct: a stable sort would carry an iota more
        return None, jax.lax.sort((pair, cts[1]), num_keys=1,
                                  is_stable=False)[1]


sort_pairs.defvjp(_sort_pairs_fwd, _sort_pairs_bwd)


def dispatch_plan(experts, gates, held, tile):
    """One routing as the tile loops take it. experts int32, gates float32
    [T, k]; held = (lo, hi).

    -> row_token, row_w [T * k + tile]: the pairs' tokens and gate weights
       in expert order, the held experts' first (a tile's slice may run
       past the pairs, into `tile` more entries),
       tile_expert, tile_start, tile_real [plan_rows / tile]: a tile's
       local expert, its first entry in the lists, how many of its rows
       (its first) hold a routed pair,
       n_tiles, counts [hi - lo] (pairs routed to each held expert)."""
    lo, hi = held
    g = hi - lo
    t, k = experts.shape
    local = jnp.where((experts >= lo) & (experts < hi), experts - lo, g)
    pair, row_w = sort_pairs(local.reshape(-1), gates.reshape(-1))
    counts = _count(local, g)
    starts = jnp.cumsum(counts) - counts
    padded = -(-counts // tile) * tile
    ends = jnp.cumsum(padded)
    # a tile's first row, were each expert's rows padded in one table
    rows = jnp.arange(plan_rows(t * k, g, tile) // tile, dtype=I32) * tile
    tile_expert = jnp.minimum(
        jnp.sum(ends <= rows[:, None], 1, dtype=I32), g - 1)
    own = tile_expert[:, None] == jnp.arange(g, dtype=I32)

    def of_tile(per_expert):
        return jnp.sum(jnp.where(own, per_expert, 0), 1, dtype=I32)

    return (jnp.pad(pair // k, (0, tile)), jnp.pad(row_w, (0, tile)),
            tile_expert, of_tile(starts - ends + padded) + rows,
            jnp.clip(of_tile(counts + ends - padded) - rows, 0, tile),
            ends[-1] // tile, counts)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=F32)


def _tile(i, tile, row_token, row_w, tile_expert, tile_start, tile_real):
    with jax.named_scope("moe/route/gather"):
        start, n_real = tile_start[i], tile_real[i]
        real = jnp.arange(tile, dtype=I32) < n_real
        idx = jax.lax.dynamic_slice(row_token, (start,), (tile,))
        w = jax.lax.dynamic_slice(row_w, (start,), (tile,))
        return (jnp.where(real, idx, 0), jnp.where(real, w, 0.0),
                tile_expert[i], n_real, real)


def _ffn_fwd_loop(x, wg, wu, wd, *tables, n_tiles, tile):
    adds = row_adds(x.shape[1], tile)

    def body(i, out):
        idx, w, e, n_real, _ = _tile(i, tile, *tables)
        with jax.named_scope("moe/route/gather"):
            h = x[idx]
        with jax.named_scope("moe/experts"):
            if wg is None:
                a = jnp.square(jnp.maximum(_dot(h, wu[e], ((1,), (0,))), 0.0))
            else:
                a = (jax.nn.silu(_dot(h, wg[e], ((1,), (0,))))
                     * _dot(h, wu[e], ((1,), (0,))))
            y = _dot(a.astype(x.dtype), wd[e], ((1,), (0,))) * w[:, None]
        with jax.named_scope("moe/route/add_back"):
            return adds.add(out, idx, y, n_real)

    with jax.named_scope("moe/route/add_back"):
        return adds.whole(jax.lax.fori_loop(0, n_tiles, body,
                                            adds.zeros(x.shape)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(10,))
def grouped_ffn(x, wg, wu, wd, row_token, row_w, tile_expert, tile_start,
                tile_real, n_tiles, tile):
    """sum over the rows r of a routing of row_w[r] * FFN_e(x[row_token[r]])
    added at row_token[r] -> float32 [T, K]. x [T, K]; wg, wu [G, K, N];
    wd [G, N, K] (`wg` None: the ungated relu^2 expert); the lists and tables are `dispatch_plan`'s: tile i
    computes `tile` entries from tile_start[i] with expert tile_expert[i];
    its first tile_real[i] rows are real (their tokens are distinct), the
    rest computed as token 0 with weight 0 and never added back. Tiles
    from `n_tiles` on are not computed. `row_w`'s gradient is in the
    lists' order, 0 where no tile has a real row."""
    return _ffn_fwd_loop(x, wg, wu, wd, row_token, row_w, tile_expert,
                         tile_start, tile_real, n_tiles=n_tiles, tile=tile)


def _grouped_ffn_fwd(x, wg, wu, wd, *rest):
    *tables, n_tiles, tile = rest
    out = _ffn_fwd_loop(x, wg, wu, wd, *tables, n_tiles=n_tiles, tile=tile)
    return out, (x, wg, wu, wd, tables, n_tiles)


def _grouped_ffn_bwd(tile, res, dout):
    x, wg, wu, wd, tables, n_tiles = res
    _, row_w, _, tile_start, _ = tables
    adds = row_adds(x.shape[1], tile)
    with jax.named_scope("moe/cast"):
        dout = dout.astype(F32)

    gated = wg is not None

    def body(i, carry):
        dx, dwg, dwu, dwd, drow = carry
        idx, w, e, n_real, real = _tile(i, tile, *tables)
        with jax.named_scope("moe/route/gather"):
            h = x[idx]
            dy_rows = dout[idx]
        with jax.named_scope("moe/experts"):
            if gated:
                g = _dot(h, wg[e], ((1,), (0,)))
                u = _dot(h, wu[e], ((1,), (0,)))
                sg = jax.nn.sigmoid(g)
                act = g * sg
                a = (act * u).astype(x.dtype)
            else:
                act = jnp.maximum(_dot(h, wu[e], ((1,), (0,))), 0.0)
                a = jnp.square(act).astype(x.dtype)
            # the gate weight's gradient needs the unweighted output
            drow_i = jnp.sum(dy_rows * _dot(a, wd[e], ((1,), (0,))), -1)
            dy = (dy_rows * w[:, None]).astype(x.dtype)
            da = _dot(dy, wd[e], ((1,), (1,)))
            if gated:
                du = (da * act).astype(x.dtype)
                dg = (da * u * sg * (1.0 + g * (1.0 - sg))).astype(x.dtype)
                dh = (_dot(dg, wg[e], ((1,), (1,)))
                      + _dot(du, wu[e], ((1,), (1,))))
                dwg = dwg.at[e].add(_dot(h, dg, ((0,), (0,))))
            else:
                du = (da * 2.0 * act).astype(x.dtype)
                dh = _dot(du, wu[e], ((1,), (1,)))
            dwu = dwu.at[e].add(_dot(h, du, ((0,), (0,))))
            dwd = dwd.at[e].add(_dot(a, dy, ((0,), (0,))))
        with jax.named_scope("moe/route/add_back"):
            dx = adds.add(dx, idx, dh, n_real)
            # a row past the real ones is the next tile's entry: tiles run
            # in order, so its 0 is overwritten there or is nobody's
            drow = jax.lax.dynamic_update_slice(
                drow, jnp.where(real, drow_i, 0.0), (tile_start[i],))
        return dx, dwg, dwu, dwd, drow

    # the loop stands under a leaf, so that its `while` and the copies of
    # its carry are the add-back's, whose accumulator they move
    with jax.named_scope("moe/route/add_back"):
        init = (adds.zeros(x.shape),
                jnp.zeros(wg.shape, F32) if gated else None,
                jnp.zeros(wu.shape, F32), jnp.zeros(wd.shape, F32),
                jnp.zeros(row_w.shape, F32))
        dx, dwg, dwu, dwd, drow = jax.lax.fori_loop(0, n_tiles, body, init)
        dx = adds.whole(dx)
    with jax.named_scope("moe/cast"):
        return (dx.astype(x.dtype), dwg.astype(wg.dtype) if gated else None,
                dwu.astype(wu.dtype), dwd.astype(wd.dtype), None,
                drow.astype(row_w.dtype), None, None, None, None)


grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


def dropless_moe(h, wr, wg, wu, wd, *, top_k, held, tile_rows,
                 renormalise=True, balance_coef=0.0, score="softmax",
                 bias=None, scale=1.0, n_group=1, topk_group=1, eps=1e-20):
    """The layer on arrays: h [T, K] -> (y [T, K] in h's type, the
    load-balancing term, stats float32 [3], the picked experts int32
    [T, top_k]). `wg` None: ungated experts; `score`, `bias`, `scale`,
    `n_group`, `topk_group`, `eps`: `route_topk`'s.

    stats = (pairs routed to held experts, rows the grouped product
    computed, the held experts' largest load). The
    balancing term is `balance_coef * E * sum_e f_e P_e` over the whole
    router: f_e the share of tokens that picked e (no gradient), P_e the
    mean of p[:, e]."""
    n_experts = wr.shape[-1]
    with jax.named_scope("moe/route/router"):
        p, experts, gates = route_topk(
            _dot(h, wr, ((1,), (0,))), top_k, renormalise, score, bias,
            scale, n_group, topk_group, eps)
        picked = _count(experts, n_experts).astype(F32)
        balance = balance_coef * n_experts * jnp.sum(
            jax.lax.stop_gradient(picked / h.shape[0]) * jnp.mean(p, 0))
    with jax.named_scope("moe/route/plan"):
        *tables, n_tiles, counts = dispatch_plan(experts, gates, held,
                                                 tile_rows)
    y = grouped_ffn(h, wg, wu, wd, *tables, n_tiles, tile_rows)
    with jax.named_scope("moe/route/plan"):
        load = counts.astype(F32)
        stats = jnp.stack([jnp.sum(load), (n_tiles * tile_rows).astype(F32),
                           jnp.max(load)])
    with jax.named_scope("moe/cast"):
        return y.astype(h.dtype), balance, stats, experts


class DroplessMoE(nn.Layer):
    """Top-k dropless mixture of experts, SiLU-gated or ungated relu^2,
    routed by softmax or by sigmoid scores (module docstring).

    Args:
      d_model, d_expert: token width and each expert's inner width.
      num_experts, top_k: the router's width and picks per token.
      held_experts: (lo, hi), the contiguous experts this layer holds and
        computes; None holds all. The router is always whole.
      renormalise: divide the picked scores by their sum.
      balance_coef: weight of the load-balancing term (0: none).
      tile_rows: rows of one step of the grouped product; each held
        expert's rows are padded to a multiple of it.
      gated: True, three weights an expert (`gate_proj`, `up_proj`,
        `down_proj`); False, two (no `gate_proj`), relu^2 between them.
      score: "softmax" or "sigmoid"; with "sigmoid" the layer holds the
        buffer `score_bias` [num_experts] (zeros; selection only) and
        multiplies the gates by `gate_scale`.
      n_group, topk_group: the group limit of the picks (sigmoid scores;
        1, 1: none).
      renorm_eps: added to the picked sigmoid scores' sum before the
        renormalisation divides by it.

    forward(x [..., d_model]) -> (y, balance term, stats [3], picks int32
    [tokens, top_k]); `stats` is `dropless_moe`'s.
    """

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 held_experts=None, renormalise=True, balance_coef=0.0,
                 tile_rows=512, gated=True, score="softmax",
                 gate_scale=1.0, n_group=1, topk_group=1, renorm_eps=1e-20):
        super().__init__()
        lo, hi = held_experts or (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"held_experts {held_experts} outside the "
                             f"router's {num_experts}")
        self.held_experts = (int(lo), int(hi))
        self.top_k, self.tile_rows = int(top_k), int(tile_rows)
        self.renormalise, self.balance_coef = renormalise, balance_coef
        self.score, self.gate_scale = score, float(gate_scale)
        if num_experts % n_group or not 1 <= topk_group <= n_group:
            raise ValueError(f"DroplessMoE: {num_experts} experts in "
                             f"{n_group} groups, {topk_group} kept")
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.renorm_eps = float(renorm_eps)
        held = hi - lo
        self.router = self.create_parameter([d_model, num_experts])
        if score == "sigmoid":
            from .....framework.tensor import Tensor

            self.register_buffer("score_bias", Tensor._wrap(
                jnp.zeros((num_experts,), F32)))
        elif score != "softmax":
            raise ValueError(f"DroplessMoE: unknown score {score!r}")
        self.gate_proj = (self.create_parameter([held, d_model, d_expert])
                          if gated else None)
        self.up_proj = self.create_parameter([held, d_model, d_expert])
        self.down_proj = self.create_parameter([held, d_expert, d_model])

    def forward(self, x):
        shape = x.shape

        gated, biased = self.gate_proj is not None, self.score == "sigmoid"

        def run(h, wr, *rest):
            wg, wu, wd = rest[:3] if gated else (None,) + rest[:2]
            y, balance, stats, picks = dropless_moe(
                h.reshape(-1, shape[-1]), wr, wg, wu, wd, top_k=self.top_k,
                held=self.held_experts, tile_rows=self.tile_rows,
                renormalise=self.renormalise,
                balance_coef=self.balance_coef, score=self.score,
                bias=rest[-1] if biased else None, scale=self.gate_scale,
                n_group=self.n_group, topk_group=self.topk_group,
                eps=self.renorm_eps)
            return y.reshape(shape), balance, stats, picks

        ins = [x, self.router, self.gate_proj, self.up_proj, self.down_proj,
               self.score_bias if biased else None]
        return nary(run, [t for t in ins if t is not None], "dropless_moe")
