"""Splash-style training attention — tiled Pallas TPU kernels, fwd + bwd.

The training-side sibling of `flash_attention.py` (which this file's
pipeline tricks come from) and `paged_attention.py` (whose routing
contract it mirrors). Three capabilities the flash kernels lack, all
needed by the packed-sequence pretraining path (ROADMAP open item 2):

* **Segment IDs**: packed sequences attend only within their own
  document. Query/key segment ids ride into the kernel lane-replicated
  ([b, s, 128] for the q side, [b, 8, s] for the kv side — the layout
  jax's own splash kernel uses, Mosaic wants full-lane tiles), and the
  mask is fused into the score tile: no [s, s] mask tensor exists.
* **Selection mask**: learned sparse attention (an indexer's top-k per
  query) hands the kernel `selection` int8 [b, sq, sk], ONE set per
  token for every head: a score survives only where it is non-zero
  (and causal). Its (block_q, block_k) tile is read beside the score
  tile and serves a kv head's whole query group; a row whose tile
  holds no selected key is the segment path's "dead" row.
* **Sliding window**: `window` (a static int, with `causal`) keeps for
  query t the keys s with 0 <= t - s < window. The grid of a windowed
  call is the band's — `_band_steps` k tiles a q tile, the index maps
  offset by the q tile — not the causal grid with steps skipped. The
  backward's dK/dV accumulators get one plane a grid step, so that a k
  block's visits by neighbouring q tiles never meet in one buffer.
* **GQA**: `num_heads` a multiple of `num_kv_heads`. The group dim is
  folded into the q-row axis — q is laid out [b*kvh, grp*sq, d] with a
  kv head's `grp` query heads stacked back to back — so one grid pass
  over (b*kvh, q-row, kv-tile) serves every group size, and the dK/dV
  accumulators naturally sum over the group's query heads. Row
  positions recover as `row % sq` (q tiles never straddle a head:
  block_q divides sq).
* **Online-softmax fwd + stats-recompute bwd at every length**: forward
  keeps only running row-max/row-sum (emitted as one fused LSE
  residual, lane-replicated like the in-kernel stats); backward
  recomputes each score tile from (q, k, LSE) — the [s, s] score
  matrix never exists in HBM in either pass. dK/dV accumulate in fp32
  HBM via `input_output_aliases` exactly like the flash tiled backward,
  with the same hazard-free per-q-row fallback for interpret mode and
  short revisit distances.
* **Causal work only** — the tiling rule: a k tile above the diagonal has
  no body and copies nothing (read-only k-side blocks repeat the q tile's
  last index; the dK/dV accumulators park on one spare block); where one
  square tile holds the whole sequence it is cut into two strips and only
  their squares on the diagonal are compared (`_strips`, `computed_pairs`).

Two paths, one contract (the `paged_attention.py` pattern):

* **Pallas kernel** — TPU (or `interpret=True` for hermetic CPU
  parity runs; see `tests/test_splash_attention.py`).
* **XLA path** (`splash_attention_xla`) — CPU, unsupported geometry: one
  dense masked attention with identical mask + empty-row semantics,
  parity-tested against the interpret-mode kernel.

Layouts: q [batch, sq, num_heads, head_dim]; k/v [batch, sk,
num_kv_heads, head_dim]; segment_ids int [batch, s] (self-attention:
one table serves both sides). Rows whose segment matches no key
(impossible under causal self-attention, where the diagonal always
matches) produce zero output and zero gradients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import routing
from .flash_attention import (  # noqa: F401  (shared kernel helpers)
    _LANES, _REVISIT_MIN, _Z, _causal_mask, _dot,
    _pick_block, pl, pltpu,
)

__all__ = ["splash_attention", "splash_attention_xla", "supports",
           "computed_pairs"]

_SUB = 8  # sublane replication of the kv-side segment-id plane


def supports(q_shape, num_kv_heads, dtype, sk=None, d_v=None) -> bool:
    """Whether the Pallas kernel can take this problem (else XLA). `d_v`:
    the values' width where it is not the keys' (latent attention's
    expanded form: 192 / 128)."""
    if dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return False
    b, sq, h, d = q_shape
    if max(d, d_v or d) > 256 or h % num_kv_heads:
        return False
    if sk is None:
        sk = sq
    return _pick_block(sq) is not None and _pick_block(sk) is not None


# ---------------------------------------------------------------------------
# XLA fallback: dense masked attention, identical mask semantics
# ---------------------------------------------------------------------------

def splash_attention_xla(q, k, v, causal=True, segment_ids=None,
                         scale=None, selection=None, window=None):
    """Reference-parity path: one dense masked attention (GQA via a
    grouped einsum). Rows with no valid key get zero output AND zero
    gradient (the whole-row zeroing below keeps AD away from the
    all--inf softmax nan)."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    grp = h // kvh
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    qg = q.reshape(b, sq, kvh, grp, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * sc
    mask = jnp.ones((b, sq, sk), bool)
    if causal:
        mask = mask & jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)[None]
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        segk = seg if sk == sq else seg[:, :sk]
        mask = mask & (seg[:, :, None] == segk[:, None, :])
    if selection is not None:
        mask = mask & (selection != 0)
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((sq, sk), bool),
                                k=sk - sq - window)[None]
    m5 = mask[:, None, None]                          # [b, 1, 1, sq, sk]
    any_valid = jnp.any(m5, axis=-1, keepdims=True)
    s = jnp.where(m5, s, -jnp.inf)
    s = jnp.where(any_valid, s, 0.0)    # empty rows: keep AD finite
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(any_valid, p, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# causal geometry: which tiles a q-row tile visits, how the tile on the
# diagonal is cut, which blocks a grid step reads. The kernels, the index
# maps and `computed_pairs` all take it from here.
# ---------------------------------------------------------------------------

def _strips(block_q, block_k, causal, num_k, window=None):
    """Strips the tile on the diagonal is cut into (1: one masked tile).
    A function of the shapes alone, measured on a v5e (PERF.md, PR 28):

    * cut only where a q row's keys all sit in ONE square tile. There the
      diagonal tile is all the work; in a grid of several k tiles it is 1
      of up to `num_k` a row, and a strip is one more body of kernel text
      for every call site to trace and lower before its first step — at
      seq 8192 a second body cost more set-up than it saved in steps.
    * two strips (75 % of the square formed). Four form 62.5 % and ran
      2-3 % slower at head widths 64 and 128 alike, eight 20-30 % slower:
      the MXU streams a strip's rows past each key tile it loads, and
      time follows the pairs only while those rows stay in the hundreds.
    """
    if not causal or block_q != block_k or num_k != 1 or window is not None:
        return 1
    return 2 if block_q % (2 * _LANES) == 0 else 1


def _div(a, b):
    """a // b and, below, a % b for a >= 0, b > 0 (grid indices, tile
    counts). Traced — in an index map or a kernel — `lax.div` / `lax.rem`
    are ONE equation where `//` / `%` are a nested jit of a dozen, traced
    anew by every map of every call site: seconds of a program's set-up."""
    return a // b if isinstance(a, int) else jax.lax.div(a, np.int32(b))


def _rem(a, b):
    return a % b if isinstance(a, int) else jax.lax.rem(a, np.int32(b))


def _last_tile(i, nqs, block_q, block_k):
    """Last k tile the causal q tile `i` visits (its own last row's)."""
    return _div(_rem(i, nqs) * block_q + (block_q - 1), block_k)


def _band_back(window, block):
    """k tiles a windowed q tile reaches back beyond its own (square
    tiles of `block`): its first row's oldest key is window - 1 before."""
    return (window + block - 2) // block


def _band_steps(window, block, nqs):
    """Grid steps along k of a windowed call: the widest band's tiles."""
    return min(nqs, _band_back(window, block) + 1)


def _band_tile(i, nqs, steps, step):
    """The k tile grid step `step` of the windowed q tile `i` stands on:
    the band ENDS on the q tile's own, so a q tile near the sequence's
    start begins below tile 0, on steps that have no body. (Begun at
    tile 0 instead, the first q tiles would all meet k tile 0 on step 0:
    one block of one plane of the backward's accumulators, visits a grid
    step or two apart, which the chip's self-check refused.)"""
    return _rem(i, nqs) - (steps - 1) + step


def _window_block(seq):
    """Tile side of a windowed call (q and k tiles are square there):
    `_pick_block`'s, measured on a v5e at (4, 8192, 32 / 4 x 128) bf16,
    window 1024, forward + backward a call (PERF.md, PR 35): 28.0 ms on
    1024-key tiles (2.0 x the band's pairs formed) against 32.7 on
    512-key tiles (1.5 x); the causal call 61.6."""
    return _pick_block(seq)


def computed_pairs(sq, block_q=None, block_k=None, causal=True,
                   window=None):
    """Score entries the forward kernel (and the backward at the same
    blocks) forms for one head over a self-attention sequence of `sq`;
    the pairs the mask can keep are sq (sq + 1) / 2 under `causal`, and
    under a `window` the band's: sum_t min(t + 1, window)."""
    if window is None:
        bq, bk = block_q or _pick_block(sq), block_k or _pick_block(sq)
    else:
        bq = bk = block_q or block_k or _window_block(sq)
    nqs, num_k = sq // bq, sq // bk
    if not causal:
        return sq * sq
    if window is not None:
        steps = _band_steps(window, bq, nqs)
        return sum(_band_tile(i, nqs, steps, j) >= 0 for i in range(nqs)
                   for j in range(steps)) * bq * bk
    n = _strips(bq, bk, causal, num_k)
    if n > 1:       # the one tile there is
        return bq * bk * (n + 1) // (2 * n)
    return sum(_last_tile(i, nqs, bq, bk) + 1 for i in range(nqs)) * bq * bk


# ---------------------------------------------------------------------------
# kernel helpers
# ---------------------------------------------------------------------------

def _lower_triangle(s):
    """Keep row >= column of a square piece that sits on the diagonal."""
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(row >= col, s, -jnp.inf)


def _band_mask(s, row0, col0, window):
    """Keep 0 <= row - col < window of a tile whose corner is (row0,
    col0). Every tile compares: on the square tiles `_window_block` picks
    each of a q tile's k tiles is crossed by one of the band's two edges,
    and on narrower ones a `lax.cond` around the compare cost more than
    the compares it saved on the tiles inside (PERF.md, PR 35)."""
    ahead = (row0 - col0) + (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    return jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)


def _scores(refs, rows, cols, scale, diag):
    """Masked fp32 scores of the tile's q rows [r0, r1) x keys [c0, c1)
    (static bounds). `diag`: None = no causal compare; (pos0, k0) =
    compare by sequence position, the tile's corner traced; (pos0, k0,
    window) = the same with a sliding window's far edge; "square" =
    a strip of the tile on the diagonal, whose upper right corner is the
    only part compared: a square of the strip's shorter side.
    segq tile: [bq, LANES] lane-replicated; segk tile: [SUB, bk]
    sublane-replicated; sel tile: int8 [bq, bk]."""
    q_ref, k_ref, segq_ref, segk_ref, sel_ref = refs
    (r0, r1), (c0, c1) = rows, cols
    nr, nc = r1 - r0, c1 - c0
    s = _dot(q_ref[0, r0:r1, :], k_ref[0, c0:c1, :],
             ((1,), (1,))) * scale                       # [nr, nc] fp32
    if diag == "square":
        if nr < nc:         # a row strip: its last nr keys
            s = jnp.concatenate(
                [s[:, :nc - nr], _lower_triangle(s[:, nc - nr:])], axis=1)
        elif nc < nr:       # a column strip: its first nc rows
            s = jnp.concatenate(
                [_lower_triangle(s[:nc]), s[nc:]], axis=0)
        else:
            s = _lower_triangle(s)
    elif diag is not None and len(diag) == 3:
        s = _band_mask(s, *diag)
    elif diag is not None:
        s = _causal_mask(s, diag[0], diag[1], nr, nc)    # a whole tile
    if segq_ref is not None:
        qseg = segq_ref[0, r0:r1, :]                     # [nr, LANES]
        kseg = segk_ref[0, :, c0:c1][:1]                 # [1, nc]
        reps = nc // _LANES
        qfull = qseg if reps == 1 else pltpu.repeat(qseg, reps, axis=1)
        s = jnp.where(qfull[:, :nc] == kseg, s, -jnp.inf)
    if sel_ref is not None:
        s = jnp.where(sel_ref[0, r0:r1, c0:c1].astype(jnp.int32) != 0, s,
                      -jnp.inf)
    return s


def _split_refs(refs, n_in, with_seg, with_sel):
    """(leading operands, segq, segk, sel, the rest) of a kernel's refs:
    the optional mask operands sit after the `n_in` leading ones."""
    refs = list(refs)
    lead, rest = refs[:n_in], refs[n_in:]
    segq = segk = sel = None
    if with_seg:
        segq, segk, rest = rest[0], rest[1], rest[2:]
    if with_sel:
        sel, rest = rest[0], rest[1:]
    return lead, segq, segk, sel, rest


def _k_steps(window, block, nqs, num_k):
    """Grid steps along k a q tile takes: every k tile, or the band's."""
    return num_k if window is None else _band_steps(window, block, nqs)


def _k_tile(qi, nqs, block, num_k, window):
    """(this grid step along k, the k tile it stands on, the steps a q
    tile takes): the causal grid walks every k tile, a windowed one the
    band's (`_band_tile`)."""
    step, steps = pl.program_id(2), _k_steps(window, block, nqs, num_k)
    return (step, step if window is None
            else _band_tile(qi, nqs, steps, step), steps)


def _visited(ki, pos0, block_q, block_k, window):
    """Whether a causal grid step has a body: its k tile lies on or below
    the diagonal; under a window, on or after the sequence's start."""
    if window is None:
        return ki * block_k <= pos0 + block_q - 1
    return ki >= 0


def _window_arg(window):
    """The kernels' `window=` keyword, absent from a call without one:
    its `functools.partial` is then the one such a call always had."""
    return {} if window is None else {"window": window}


def _corner(pos0, k0, causal, window):
    """`_scores`' `diag` of a whole tile."""
    if not causal:
        return None
    return (pos0, k0) if window is None else (pos0, k0, window)


# ---------------------------------------------------------------------------
# forward: online softmax over kv tiles, grid (b*kvh, qi, ki)
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, block_q, block_k, nqs, num_k, strips,
                with_seg, with_sel=False, window=None):
    (q_ref, k_ref, v_ref), segq_ref, segk_ref, sel_ref, rest = _split_refs(
        refs, 3, with_seg, with_sel)
    o_ref, lse_ref = rest[:2]
    mask_refs = (q_ref, k_ref, segq_ref, segk_ref, sel_ref)
    # a piece can be FULLY masked under segments or a selection (unlike
    # pure causal, where a row's first piece always holds the diagonal),
    # so m_new may still be -inf: exp(-inf - -inf) would poison the stats
    # with nan — pin those rows' exponentials to 0 instead; under a
    # window a row's first tile may hold none of its keys
    may_die = with_seg or with_sel or window is not None

    def update(state, rows, c1, diag):
        """Online-softmax step of (m [r, LANES], l [r, LANES], acc [r, d])
        — None: fresh rows — over q rows `rows` x keys [0, c1)."""
        s = _scores(mask_refs, rows, (0, c1), scale, diag)
        v = v_ref[0, :c1, :]
        m_new = jnp.broadcast_to(jnp.max(s, axis=1, keepdims=True),
                                 (rows[1] - rows[0], _LANES))
        corr = None
        if state is not None:
            m_prev, l_prev, acc = state
            m_new = jnp.maximum(m_prev, m_new)
            corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                    # [r, c1] fp32
        if may_die:
            dead = m_new == -jnp.inf                     # [r, LANES]
            p = jnp.where(dead[:, :1], 0.0, p)
            if corr is not None:
                corr = jnp.where(dead, 0.0, corr)
        l_new = jnp.broadcast_to(jnp.sum(p, axis=1, keepdims=True),
                                 m_new.shape)
        pv = _dot(p.astype(v.dtype), v, ((1,), (0,)))    # [r, d]
        if corr is not None:
            l_new = corr * l_prev + l_new
            pv = acc * corr[:, :1] + pv
        return m_new, l_new, pv

    def finish(r0, r1, state):
        m, l, acc = state
        l1 = l[:, :1]                                    # [r, 1]
        o_ref[0, r0:r1, :] = (acc / jnp.where(l1 == 0.0, 1.0, l1)
                              ).astype(o_ref.dtype)
        # empty rows carry lse=+inf: backward's exp(s - lse) is then an
        # exact 0 (even for masked s=-inf), no special-casing needed
        lse_ref[0, r0:r1, :] = jnp.where(l > 0.0, m + jnp.log(l), jnp.inf)

    if strips > 1:
        # the one tile there is, on the diagonal: strip r of the q rows
        # meets keys [0, (r + 1) t) and is a whole softmax, so no running
        # state, no scratch, and nothing above the diagonal is formed
        t = block_q // strips
        for r0 in range(0, block_q, t):
            finish(r0, r0 + t,
                   update(None, (r0, r0 + t), r0 + t, "square"))
        return

    acc_ref, m_ref, l_ref = rest[2:]
    qi = pl.program_id(1)
    step, ki, steps = _k_tile(qi, nqs, block_q, num_k, window)
    pos0 = _rem(qi, nqs) * block_q  # sequence position of the tile's row 0

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        m_ref[...], l_ref[...], acc_ref[...] = update(
            (m_ref[...], l_ref[...], acc_ref[...]), (0, block_q), block_k,
            _corner(pos0, ki * block_k, causal, window))

    if causal:      # a tile above the diagonal: no body, no copy (`_specs`)
        pl.when(_visited(ki, pos0, block_q, block_k, window))(_step)
    else:
        _step()

    @pl.when(step == steps - 1)
    def _finish():
        finish(0, block_q, (m_ref[...], l_ref[...], acc_ref[...]))


def _specs(bq, bk, d, nqs, kvh, with_seg, with_sel, causal, num_k,
           qi_base=0, window=None, dv=None):
    """Block specs shared by forward and fused backward; the last three
    are spec_q, spec_k and spec_acc again at the values' width `dv` (o and
    do, v, dV's accumulator). q-side tiles
    (q/do/o/lse) index the [bh, grp*sq, ...] layout by grid dim 1; the
    segment planes recover (batch, seq-position) as (g // kvh,
    qi % nqs) — q tiles never straddle a head boundary. A step above the
    diagonal copies nothing: its read-only k-side blocks (k, v, the
    k-side segment plane, the selection tile) repeat the q tile's last
    visited index, and the aliased dK/dV accumulators (`spec_acc`), whose
    blocks pass through every step, park on ONE spare block past the keys
    (index `num_k`; `_acc_zeros`) — held at a visited block instead, the
    pass-through would write that block's stale input over its sum.
    Under a `window` grid step j stands on k tile `_band_tile`, a step
    below tile 0 copying nothing either, and the accumulators have one
    plane of num_k + 1 blocks a step: a k block is then met in plane j by
    ONE q tile of a head, where in one plane the q tile after would meet
    it a grid step or two later, before its sum is written back."""
    if window is not None:
        steps = _band_steps(window, bq, nqs)
        zero = np.int32(0)

        def kv(i, j):       # a step below tile 0 holds tile 0: the next
            return jax.lax.max(_band_tile(qi_base + i, nqs, steps, j), zero)

        def acc(i, j):
            t = _band_tile(qi_base + i, nqs, steps, j)
            return (jax.lax.select(jax.lax.lt(t, zero), np.int32(num_k), t)
                    + j * np.int32(num_k + 1))
    else:
        def last(i):
            return _last_tile(qi_base + i, nqs, bq, bk)

        def kv(i, j):
            return jax.lax.min(j, last(i)) if causal else j

        def acc(i, j):
            if not causal:
                return j
            return jax.lax.select(jax.lax.gt(j, last(i)), np.int32(num_k), j)

    def wide(d):
        return (pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, _Z)),
                pl.BlockSpec((1, bk, d), lambda g, i, j: (g, kv(i, j), _Z)),
                pl.BlockSpec((1, bk, d), lambda g, i, j: (g, acc(i, j), _Z)))

    spec_q, spec_k, spec_acc = wide(d)
    spec_lse = pl.BlockSpec((1, bq, _LANES), lambda g, i, j: (g, i, _Z))
    seg = []
    if with_seg:
        seg = [
            pl.BlockSpec((1, bq, _LANES),
                         lambda g, i, j: (_div(g, kvh), _rem(i, nqs), _Z)),
            pl.BlockSpec((1, _SUB, bk),
                         lambda g, i, j: (_div(g, kvh), _Z, kv(i, j))),
        ]
    if with_sel:
        seg.append(pl.BlockSpec(
            (1, bq, bk),
            lambda g, i, j: (_div(g, kvh), _rem(i, nqs), kv(i, j))))
    return (spec_q, spec_k, spec_acc, spec_lse, seg) + wide(dv or d)


def _acc_zeros(bh, sk, bk, d, causal, planes=1):
    """A fresh fp32 dK or dV accumulator: the keys' rows and, under
    `causal`, the spare block that steps above the diagonal park on;
    `planes` of them back to back for a windowed call (`_specs`)."""
    return jnp.zeros((bh, planes * (sk + (bk if causal else 0)), d),
                     jnp.float32)


def _acc_sum(acc, sk, planes, dtype):
    """The keys' rows of an accumulator, its planes added."""
    if planes > 1:
        acc = jnp.sum(acc.reshape(acc.shape[0], planes, -1, acc.shape[2]), 1)
    return acc[:, :sk].astype(dtype)


def _fwd(q, k, v, segq, segk, scale, causal, bq, bk, sq, kvh, with_seg,
         interpret, sel=None, window=None):
    bh, sq_all, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    nqs, num_k = sq // bq, sk // bk
    with_sel = sel is not None
    spec_q, spec_k, _, spec_lse, seg_specs, spec_o, spec_v, _ = _specs(
        bq, bk, d, nqs, kvh, with_seg, with_sel, causal, num_k,
        window=window, dv=dv)
    strips = _strips(bq, bk, causal, num_k, window)
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        nqs=nqs, num_k=num_k, strips=strips, with_seg=with_seg,
        with_sel=with_sel, **_window_arg(window))
    args = ([q, k, v] + ([segq, segk] if with_seg else [])
            + ([sel] if with_sel else []))
    out, lse = routing.pallas_call(
        kern,
        name="splash_fwd",
        grid=(bh, sq_all // bq, _k_steps(window, bq, nqs, num_k)),
        in_specs=[spec_q, spec_k, spec_v] + seg_specs,
        out_specs=[spec_o, spec_lse],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_all, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, sq_all, _LANES), jnp.float32),
        ],
        scratch_shapes=[] if strips > 1 else [
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out, lse


# ---------------------------------------------------------------------------
# backward: single-pass fused sweep (dQ in scratch, dK/dV in aliased fp32
# HBM accumulators, delta in-kernel) — the flash_attention.py §bwd design
# with segment masking and mod-sq causal positions folded in
# ---------------------------------------------------------------------------

def _bwd_kernel(*refs, scale, causal, block_q, block_k, nqs, num_k, strips,
                with_seg, qi_base, once, with_sel=False, window=None):
    lead, segq_ref, segk_ref, sel_ref, rest = _split_refs(
        refs, 6, with_seg, with_sel)
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref = lead
    if once:
        # every k block is met by this one grid step alone (one q tile in
        # all): dK/dV leave as they are formed, in the operands' dtype —
        # no fp32 planes of zeros in, none out, no cast after
        dq_ref, dk_ref, dv_ref, dq_acc, delta_ref = rest
    else:
        dki_ref, dvi_ref, dq_ref, dk_ref, dv_ref, dq_acc, delta_ref = rest
        # pass the accumulators through unconditionally: a block's sum
        # so far, or the spare block a step above the diagonal parks on
        dk_ref[0] = dki_ref[0]
        dv_ref[0] = dvi_ref[0]
    mask_refs = (q_ref, k_ref, segq_ref, segk_ref, sel_ref)
    qi = qi_base + pl.program_id(1)
    step, ki, steps = _k_tile(qi, nqs, block_q, num_k, window)
    pos0 = _rem(qi, nqs) * block_q

    def gradients(rows, cols, diag):
        """(dq [r, d], dk [c, d], dv [c, d]) fp32 of q rows x keys."""
        (r0, r1), (c0, c1) = rows, cols
        q = q_ref[0, r0:r1, :]
        do = do_ref[0, r0:r1, :]
        k = k_ref[0, c0:c1, :]
        lse = lse_ref[0, r0:r1, :][:, :1]                # [r, 1]
        delta = delta_ref[r0:r1, :][:, :1]
        s = _scores(mask_refs, rows, cols, scale, diag)
        # lse=+inf on empty rows makes every p an exact 0 (s - lse is
        # -inf even where s itself is -inf) — zero grads fall out free
        p = jnp.exp(s - lse)                             # [r, c]
        dv = _dot(p.astype(do.dtype), do, ((0,), (0,)))  # [c, d]
        dp = _dot(do, v_ref[0, c0:c1, :], ((1,), (1,)))  # [r, c] fp32
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk = _dot(ds, q, ((0,), (0,)))                   # [c, d]
        return _dot(ds, k, ((1,), (0,))), dk, dv

    def _delta():
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        delta_ref[...] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1, keepdims=True), delta_ref.shape)

    if strips > 1:
        # the one tile there is, on the diagonal (so `once`): strip c of
        # the keys meets the q rows from c t on; its dK/dV are whole, and
        # dQ gathers over the strips, the first of which has every row
        _delta()
        t = block_k // strips
        for c0 in range(0, block_k, t):
            dq, dk, dv = gradients((c0, block_q), (c0, c0 + t), "square")
            dk_ref[0, c0:c0 + t, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, c0:c0 + t, :] = dv.astype(dv_ref.dtype)
            if c0:
                dq_acc[c0:, :] += dq
            else:
                dq_acc[...] = dq
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        return

    @pl.when(step == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        _delta()

    def _step():
        dq, dk, dv = gradients((0, block_q), (0, block_k),
                               _corner(pos0, ki * block_k, causal, window))
        if once:
            dk_ref[0] = dk.astype(dk_ref.dtype)
            dv_ref[0] = dv.astype(dv_ref.dtype)
        else:
            dv_ref[0] += dv
            dk_ref[0] += dk
        dq_acc[...] += dq

    if causal:
        pl.when(_visited(ki, pos0, block_q, block_k, window))(_step)
    else:
        _step()

    @pl.when(step == steps - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_call(q, k, v, do, out, lse, segq, segk, dk_acc, dv_acc, scale,
              causal, bq, bk, sq, kvh, with_seg, num_q, qi_base,
              interpret, sel=None, window=None):
    bh, _, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    nqs, num_k = sq // bq, sk // bk
    # q-side operands arrive pre-sliced to the processed rows (the
    # rowloop passes one q-row per call), so q-side specs index from 0
    # (the rowloop's single segment block hits index 0 either way);
    # qi_base offsets the causal/segment positions in the kernel and the
    # k-side index maps' hold.
    # `dk_acc` None: the call's one q tile is every k block's only visit,
    # so dK/dV have nothing to add to and leave in k's dtype (`once`).
    once = dk_acc is None
    with_sel = sel is not None
    (spec_q, spec_k, spec_acc, spec_lse, seg_specs, spec_o, spec_v,
     spec_vacc) = _specs(bq, bk, d, nqs, kvh, with_seg, with_sel, causal,
                         num_k, qi_base, window, dv)
    kern = functools.partial(
        _bwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        nqs=nqs, num_k=num_k,
        strips=_strips(bq, bk, causal, num_k, window) if once else 1,
        with_seg=with_seg, qi_base=qi_base, once=once, with_sel=with_sel,
        **_window_arg(window))
    n_in = 6 + (2 if with_seg else 0) + (1 if with_sel else 0)
    args = ([q, k, v, do, out, lse]
            + ([segq, segk] if with_seg else [])
            + ([sel] if with_sel else [])
            + ([] if once else [dk_acc, dv_acc]))
    acc_dtype = k.dtype if once else jnp.float32
    acc_rows = sk if once else dk_acc.shape[1]
    return routing.pallas_call(
        kern,
        name="splash_bwd",
        grid=(bh, num_q, _k_steps(window, bq, nqs, num_k)),
        in_specs=[spec_q, spec_k, spec_v, spec_o, spec_o, spec_lse]
        + seg_specs + ([] if once else [spec_acc, spec_vacc]),
        out_specs=[spec_q, spec_acc, spec_vacc],
        out_shape=[
            jax.ShapeDtypeStruct((bh, num_q * bq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, acc_rows, d), acc_dtype),
            jax.ShapeDtypeStruct((bh, acc_rows, dv), acc_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        # dk/dv accumulators alias their inputs (last two -> outs 1, 2)
        input_output_aliases={} if once else {n_in: 1, n_in + 1: 2},
        interpret=interpret,
    )(*args)


def _bwd_rowloop(q, k, v, do, out, lse, segq, segk, dk_acc, dv_acc, scale,
                 causal, bq, bk, sq, kvh, with_seg, num_q, interpret,
                 sel=None, window=None):
    """Hazard-free backward: one q-row per pallas call, threading dk/dv
    through as aliased call inputs (each aliased block visited once per
    call) — interpret mode replays revisited aliased blocks from the
    original input, and short revisit distances are not trusted
    compiled either (flash_attention.py _REVISIT_MIN rationale)."""
    nqs = sq // bq
    dq_rows = []
    for qi in range(num_q):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=qi * bq, slice_size=bq, axis=1)
        sq_seg = sel_rows = None
        pos0 = (qi % nqs) * bq
        if with_seg:
            sq_seg = jax.lax.dynamic_slice_in_dim(segq, pos0, bq, 1)
        if sel is not None:
            sel_rows = jax.lax.dynamic_slice_in_dim(sel, pos0, bq, 1)
        dq_row, dk_acc, dv_acc = _bwd_call(
            sl(q), k, v, sl(do), sl(out), sl(lse), sq_seg, segk,
            dk_acc, dv_acc, scale, causal, bq, bk, sq, kvh, with_seg,
            1, qi, interpret, sel=sel_rows, window=window)
        dq_rows.append(dq_row)
    return jnp.concatenate(dq_rows, axis=1), dk_acc, dv_acc


_alias_checked: set = set()


def _alias_selfcheck(dtype, d, scale, causal, bq, bk, sk, window=None):
    """One-time (per config, per process) on-device check of the fused
    full-grid backward against the hazard-free per-row path — the
    flash_attention.py guard applied to the splash kernels, so a Mosaic
    pipeline-ordering change that breaks the aliased dK/dV revisit
    fails loudly instead of training on wrong gradients."""
    from ...utils import flags as _flags

    key = (str(dtype), d, causal, bq, bk, sk) + (
        () if window is None else (window,))
    if key in _alias_checked or not _flags.get_flag(
            "FLAGS_pallas_alias_selfcheck"):
        return
    # >= 2 q rows so every kv block is revisited; under a window two
    # heads (a plane's block is met once a head) of a sequence one tile
    # longer than the band, so that no q tile's band is cut short
    sq, grp = 2 * bq, 1
    if window is not None:
        sq = sk = min(sk, (_band_back(window, bq) + 2) * bq)
        grp = 2
    planes = _k_steps(window, bq, sq // bq, 1)

    def _run():
        rng = np.random.default_rng(0)
        mk = lambda s: jnp.asarray(  # noqa: E731
            rng.standard_normal((1, s, d)) * 0.5, dtype)
        q, do = mk(grp * sq), mk(grp * sq)
        k, v = mk(sk), mk(sk)
        out, lse = _fwd(q, k, v, None, None, scale, causal, bq, bk, sq,
                        1, False, False, window=window)
        z = lambda: _acc_zeros(1, sk, bk, d, causal, planes)  # noqa: E731
        f = _bwd_call(q, k, v, do, out, lse, None, None, z(), z(),
                      scale, causal, bq, bk, sq, 1, False,
                      grp * sq // bq, 0, False, window=window)
        r = _bwd_rowloop(q, k, v, do, out, lse, None, None, z(), z(),
                         scale, causal, bq, bk, sq, 1, False,
                         grp * sq // bq, False, window=window)
        f32 = jnp.float32
        errs = {"dq": jnp.max(jnp.abs(f[0].astype(f32) - r[0].astype(f32)))}
        for n, a, b in zip(("dk", "dv"), f[1:], r[1:]):
            errs[n] = jnp.max(jnp.abs(_acc_sum(a, sk, planes, f32)
                                      - _acc_sum(b, sk, planes, f32)))
        return {n: float(e) for n, e in errs.items()}

    # run eagerly even when tracing (fresh thread has no trace context)
    import concurrent.futures
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        errs = pool.submit(_run).result()
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, err in errs.items():
        if not err < tol:
            raise RuntimeError(
                f"splash backward self-check FAILED ({name} max err "
                f"{err:.3e}, tol {tol:.0e}, config {key}): the aliased "
                "dK/dV accumulator round-trip no longer matches the "
                "hazard-free path. Set FLAGS_splash_attn=0 to route "
                "attention to the flash/XLA paths, and report this.")
    _alias_checked.add(key)   # only memoize a PASSING check


def _bwd(q, k, v, out, lse, do, segq, segk, scale, causal, bq, bk, sq,
         kvh, with_seg, interpret, sel=None, window=None):
    bh, sq_all, d = q.shape
    sk = k.shape[1]
    num_q = sq_all // bq
    if num_q == 1:
        return _bwd_call(
            q, k, v, do, out, lse, segq, segk, None, None, scale,
            causal, bq, bk, sq, kvh, with_seg, num_q, 0, interpret,
            sel=sel, window=window)
    planes = 1
    if window is None:
        # shrink the backward k-block until the aliased-revisit distance
        # is safe (the forward keeps its own block_k: no aliased
        # accumulators)
        bkb = bk
        while sk // bkb < _REVISIT_MIN and bkb % 2 == 0 \
                and (bkb // 2) % _LANES == 0 and sk % (bkb // 2) == 0:
            bkb //= 2
        fused = not interpret and sk // bkb >= _REVISIT_MIN
        if fused:
            bk = bkb
    else:
        # a plane's k block is met once a head, by the same q tile of the
        # next head of the group a whole head's grid steps later
        planes = _band_steps(window, bq, sq // bq)
        fused = not interpret and (
            num_q == sq // bq or sq // bq * planes >= _REVISIT_MIN)
    dk_acc = _acc_zeros(bh, sk, bk, d, causal, planes)
    dv_acc = _acc_zeros(bh, sk, bk, v.shape[2], causal, planes)
    if fused:
        _alias_selfcheck(q.dtype, d, scale, causal, bq, bk, sk, window)
        dq, dk_acc, dv_acc = _bwd_call(
            q, k, v, do, out, lse, segq, segk, dk_acc, dv_acc, scale,
            causal, bq, bk, sq, kvh, with_seg, num_q, 0, interpret,
            sel=sel, window=window)
    else:
        dq, dk_acc, dv_acc = _bwd_rowloop(
            q, k, v, do, out, lse, segq, segk, dk_acc, dv_acc, scale,
            causal, bq, bk, sq, kvh, with_seg, num_q, interpret, sel=sel,
            window=window)
    return (dq, _acc_sum(dk_acc, sk, planes, k.dtype),
            _acc_sum(dv_acc, sk, planes, v.dtype))


# ---------------------------------------------------------------------------
# custom_vjp wrapper + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11,
                                                    12, 13, 14))
def _splash(q, k, v, segq, segk, sel, scale, causal, bq, bk, sq, kvh,
            with_seg, interpret, window=None):
    out, _ = _fwd(q, k, v, segq, segk, scale, causal, bq, bk, sq, kvh,
                  with_seg, interpret, sel=sel, window=window)
    return out


def _splash_fwd(q, k, v, segq, segk, sel, scale, causal, bq, bk, sq, kvh,
                with_seg, interpret, window):
    out, lse = _fwd(q, k, v, segq, segk, scale, causal, bq, bk, sq, kvh,
                    with_seg, interpret, sel=sel, window=window)
    return out, (q, k, v, segq, segk, sel, out, lse)


def _splash_bwd(scale, causal, bq, bk, sq, kvh, with_seg, interpret, window,
                res, do):
    q, k, v, segq, segk, sel, out, lse = res
    dq, dk, dv = _bwd(q, k, v, out, lse, do, segq, segk, scale, causal,
                      bq, bk, sq, kvh, with_seg, interpret, sel=sel,
                      window=window)

    def no_grad(ints):
        return (None if ints is None
                else np.zeros(ints.shape, dtype=jax.dtypes.float0))

    return dq, dk, dv, no_grad(segq), no_grad(segk), no_grad(sel)


_splash.defvjp(_splash_fwd, _splash_bwd)


def splash_attention(q, k, v, causal=True, segment_ids=None, scale=None,
                     block_q=None, block_k=None, interpret=None,
                     use_kernel=None, selection=None, window=None):
    """Splash training attention (see module docstring for layouts).
    `selection`, int8 [batch, sq, sk], keeps for every head of a query
    only the keys where it is non-zero (and causal, when `causal`).
    `window`, a static int with `causal`, keeps of query t's keys the
    `window` latest: those with 0 <= t - s < window.

    Routes to the Pallas kernel on TPU when the geometry qualifies
    (`supports`), the XLA dense fallback otherwise. `interpret=True`
    forces the kernel in interpret mode (hermetic CPU testing);
    `use_kernel` overrides the routing outright. Differentiable
    (custom tiled backward) in q/k/v."""
    b, sq, h, d = q.shape
    sk, kvh, d_v = k.shape[1], k.shape[2], v.shape[3]
    if causal and sq != sk:
        raise ValueError("causal splash attention needs equal seq lens")
    if h % kvh:
        raise ValueError(f"num_heads {h} not a multiple of kv heads {kvh}")
    if window is not None:
        if selection is not None or not causal:
            raise ValueError("a sliding window takes causal attention and "
                             "no selection")
        window = int(window)
        if window < 1:
            raise ValueError(f"window {window} keeps no key")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    use_kernel, interpret = routing.route(
        "splash_attention",
        supports((b, sq, h, d), kvh, q.dtype, sk=sk, d_v=d_v),
        (f"q{(b, sq, h, d)}", f"kv_heads={kvh}", f"sk={sk}", str(q.dtype))
        + ((f"d_v={d_v}",) if d_v != d else ()),
        interpret, use_kernel)
    if not use_kernel:
        return splash_attention_xla(q, k, v, causal=causal,
                                    segment_ids=segment_ids, scale=scale,
                                    selection=selection, window=window)
    grp = h // kvh
    if d != d_v and d % _LANES:
        # query / key rows of one and a half lane tiles (192 beside values
        # of 128): zeros fill them to whole tiles, which moves no score
        q, k = (jnp.pad(x, ((0, 0),) * 3 + ((0, -d % _LANES),))
                for x in (q, k))
        d = q.shape[3]
    if window is not None:      # square tiles (`_band_back`)
        block_q = block_k = block_q or block_k or _window_block(sq)
    if block_q is None:
        block_q = _pick_block(sq)
    if block_k is None:
        block_k = _pick_block(sk)
        if d > _LANES:
            # rows of two lane tiles: a 1,024-key tile's backward asks for
            # 18.6 MiB of a v5e's 16 (its float32 dK accumulator doubles)
            block_k = min(block_k, 512)

    # fold the group dim into the q-row axis: kv head kh serves q rows
    # [kh*grp*sq, (kh+1)*grp*sq) — q head index = row // sq within them
    q2 = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * kvh, grp * sq, d)
    k2 = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * kvh, sk, d)
    v2 = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * kvh, sk, d_v)
    segq = segk = None
    with_seg = segment_ids is not None
    if with_seg:
        seg = (segment_ids.astype(jnp.int32)
               if hasattr(segment_ids, "astype")
               else jnp.asarray(segment_ids, jnp.int32))
        kseg = seg if sk == sq else seg[:, :sk]
        segq = jnp.broadcast_to(seg[:, :, None], (b, sq, _LANES))
        segk = jnp.broadcast_to(kseg[:, None, :], (b, _SUB, sk))
    sel = None if selection is None else selection.astype(jnp.int8)
    out2 = _splash(q2, k2, v2, segq, segk, sel, float(scale), bool(causal),
                   int(block_q), int(block_k), int(sq), int(kvh),
                   with_seg, bool(interpret), window)
    return jnp.transpose(out2.reshape(b, kvh * grp, sq, d_v), (0, 2, 1, 3))
