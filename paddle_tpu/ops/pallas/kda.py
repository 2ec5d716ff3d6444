"""Kimi Delta Attention's scan (arXiv:2510.26692 section 3): the gated delta
rule with a decay a CHANNEL, forward and backward, a chunk at a time.

Per head, with a state S [K, V] carried over time (q_t, k_t [K], v_t [V],
a_t [K] <= 0 the logarithm of the channel's decay, 0 < beta_t < 1):

  S_t = (I - beta_t k_t k_t^T) Diag(exp a_t) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t

The program computes it C tokens at a time. With G the running sum of `a`
INSIDE the chunk (G_t = a_1 + .. + a_t <= 0) and S_0 the state at its start:

  A_ti = sum_c beta_t k_tc k_ic exp(G_tc - G_ic)   t > i   (else 0)
  B_ti = sum_c      q_tc k_ic exp(G_tc - G_ic)   t >= i  (else 0)
  U    = (I + A)^-1 (beta o V - (beta o K o exp G) S_0)   the pseudo-values
  O    = (Q o exp G) S_0 + B U
  S_C  = Diag(exp G_C) S_0 + (K o exp(G_C - G))^T U

**The exponent rule.** exp(G_t - G_i) does not factor over a chunk: with `a`
down to -5 a channel, exp(-G_i) leaves float32 after 18 tokens. A and B are
formed a sub-block of 16 rows at a time, split at the block's first row b:
exp(G_t - G_b) (t in the block, <= 1) on the left operand and
exp(G_b - G_i) on the right: <= 1 for every earlier i, and at most
exp(15 x 5) = exp(75) < exp(88.7) inside the block itself, which is what the
published bound of -5 is for. Entries above the diagonal are computed (their
exponent clamped at 80) and masked.

**The triangular system** (float32): the 16 x 16 diagonal blocks of I + A are
inverted by the Neumann doubling (I + N)^-1 = (I - N)(I + N^2)(I + N^4)
(I + N^8), exact because N^16 = 0, all of them at once; the blocks below them
by the same identity one level up, X = D^-1 L, (I + X)^-1 = (I - X)(I + X^2)
(X^4 = 0 at C = 64). Doubling the whole [64, 64] matrix six times instead
squares its entries up to N^32, whose binomial growth costs float32 its
digits; two levels of at most three doublings do not. Ten products, every one
of two block-diagonal matrices (16 x 16 blocks at the first level, a chunk's
at the second), so the left one enters with its blocks SIDE BY SIDE, [16, C]
or [C, C']: against the right one whole that is every block's own product,
the same sums without the zeros. The MXU's float32 mode takes a row of the
left operand every eighth cycle whatever the row holds, so the rows are what
a product costs.

**Two chunks, one system.** Where a grid step holds two or four chunks they
pair off: G of a pair is one product with a block-diagonal triangle of ones
(the sums start again at row C), A of a pair is block-diagonal [2 C, 2 C]
from each chunk's own pair products beside exact zeros (never computed and
masked), and its inverse is the same ten products, the first level's blocks
eight instead of four, the second level's X block-diagonal with A and
X^4 = 0 still, so no doubling is added; the two inverses come back side by
side, [C, 2 C]. What the chain of dependent products costs is paid once for
two chunks. A grid step of one chunk (seq 192) runs the [C, C] system; chunks
of 16 and 32 pair the same way.

`a`, G, A, the inverse and the carried state are float32; every other
product's operands are q's type (bfloat16 in training) with float32 sums.
The state is kept TRANSPOSED, [V, K]: its decay is a channel of K, which
then lies along the lanes like G's rows.

* `kda_xla`: the chunk algebra above in `jnp`, a `lax.scan` over the chunks
  under a `vmap` over batch and heads; differentiated by JAX. The CPU path,
  and the kernels' second opinion.
* `kda_fwd`, grid (batch, head, block of chunks), the last axis sequential,
  the head's state in VMEM scratch across the grid steps; under a VJP it
  also writes each chunk's incoming state out.
* `kda_bwd`, the same grid in reverse with dS carried in scratch: recomputes
  A, B, the inverse and U of a chunk from the saved state and writes dq, dk,
  d(beta k), d(beta v) and da.

**A grid step is a loop over its pairs.** One iteration forms what of the
pair no state enters (`_state_free`: G, the pair products, the inverse, exp G
and its products with q, beta k and k: nine tenths of a chunk's instructions)
and then carries the state through its two chunks on what only the state can
give: forward `from_state`, r, U = T r, o and the next state (`_chunk_state`,
four products); backward the same from the SAVED state, then dU, dr and dS on
the chain and the rest of the pull-back behind them (`_chunk_backward`), and
dG of both chunks summed from a row to its chunk's end as one product. The
state-free part as a pass of its own, through VMEM scratch, was measured and
is slower (PERF.md section 6, PR 42): the state's chain is a tenth of a
chunk, and the scratch's round trip costs more than it hides. The loop stays
a loop: two pairs written out compile the step 40 s longer, and the
scheduler does not overlap them.

G is formed inside the kernels too, as a product with a triangle of ones
(XLA's running sum over a [b, L, H 128] float32 table and its pull-back cost
a fifth of the forward kernel's time beside it). Beta's products with k and
v are formed beside the kernels: by `kda_rows.kda_inputs` for `kda_flat`, the
model's entry, which takes the five operands as flat [b, L, H 128] rows;
by XLA (`_operands`) for `kda`, which takes [b, L, H, d] tables.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import routing
from .flash_attention import _LANES, _Z, _dot, pl, pltpu

__all__ = ["kda", "kda_flat", "kda_xla", "kda_fwd", "kda_bwd",
           "supports"]

F32 = jnp.float32
SUB = 16            # rows of a sub-block: SUB x 5 < 88.7 (module docstring)
_CLAMP = 80.0       # exponent of an entry that the mask removes


def supports(q_shape, v_shape, chunk, dtype) -> bool:
    """Whether the kernels take this problem on a TPU."""
    _, seq, _, dk = q_shape
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return (dk == _LANES and v_shape[-1] == _LANES and chunk % SUB == 0
            and seq % chunk == 0)


# -- one chunk, in jnp (XLA and the kernels' bodies alike) -------------------

def _iota2(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _mm(a, b):
    """a b of two float32 matrices, at float32's own precision."""
    return _dot(a, b, ((1,), (0,)))


def _same_chunk(rows, chunk):
    """row, col of a [rows, rows] system and whether they share a chunk."""
    row, col = _iota2(rows)
    if rows == chunk:
        return row, col, True
    return row, col, (row >= chunk) == (col >= chunk)        # two chunks


def _side_by_side(m, size):
    """m [C, C], block-diagonal in size x size blocks -> the blocks side by
    side, [size, C]: their lanes are disjoint, so the sum adds zeros."""
    return functools.reduce(
        jnp.add, [m[lo:lo + size] for lo in range(0, m.shape[0], size)])


def _block_diagonal(s, same):
    """[size, C] side by side -> [C, C] block-diagonal; `same` is its mask."""
    size, c = s.shape
    return s if size == c else jnp.where(same, jnp.tile(s, (c // size, 1)),
                                         0.0)


def _neumann(n, eye, same, index):
    """(I + N)^-1 = (I - N)(I + N^2)(I + N^4) .. for N block-diagonal and
    nilpotent of `index`; N, I and the result side by side. A product of two
    block-diagonal matrices needs the left one side by side only: against the
    right one whole that is every block's own product, the same sums without
    the zeros, for as many times fewer rows through the MXU as there are
    blocks."""
    inv, power = eye - n, n
    for _ in range(max(index - 1, 1).bit_length() - 1):
        power = _mm(power, _block_diagonal(power, same))
        inv = _mm(inv, _block_diagonal(eye + power, same))
    return inv


def _tri_inverse(a, chunk=None):
    """(I + a)^-1 for a strictly lower [C, C] float32, by the two levels of
    Neumann doubling of the module docstring. With `chunk` = C / 2, `a` is
    block-diagonal in two chunks (a pair as ONE system): the second level's
    X = D^-1 L is block-diagonal with it and X^(chunk / SUB) = 0 still, so
    no doubling is added. -> the chunks' inverses side by side, [chunk, C]."""
    c = a.shape[0]
    chunk = chunk or c
    row, col, same_chunk = _same_chunk(c, chunk)
    eye = (row == col).astype(F32)
    shift = SUB.bit_length() - 1
    same_sub = (row >> shift) == (col >> shift)
    diag = jnp.where(same_sub, a, 0.0)
    inv = _neumann(_side_by_side(diag, SUB), _side_by_side(eye, SUB),
                   same_sub, SUB)
    if chunk == SUB:
        return inv
    dinv = _block_diagonal(inv, same_sub)
    x = _mm(_side_by_side(dinv, chunk), a - diag)
    inv = _neumann(x, _side_by_side(eye, chunk), same_chunk, chunk // SUB)
    return _mm(inv, dinv)


def _pairs(q32, k32, kb32, g, op):
    """The two pair products of ONE chunk before their masks, float32
    [C, C]: (beta k) k^T and q k^T under exp(G_t - G_i), and per sub-block
    what the backward reuses: (left [2 SUB, K] = the block's rows of q and
    beta k times exp(G - G_b); right [C, K] = k exp(G_b - G); the two
    factors float32)."""
    a_rows, b_rows, kept = [], [], []
    for lo in range(0, g.shape[0], SUB):
        first = g[lo:lo + 1]
        el = jnp.exp(g[lo:lo + SUB] - first)
        er = jnp.exp(jnp.minimum(first - g, _CLAMP))
        left = jnp.concatenate([q32[lo:lo + SUB] * el,
                                kb32[lo:lo + SUB] * el]).astype(op)
        right = (k32 * er).astype(op)
        p = _dot(left, right, ((1,), (1,)))                  # [2 SUB, C]
        b_rows.append(p[:SUB])
        a_rows.append(p[SUB:])
        kept.append((left, right, el, er))
    return jnp.concatenate(a_rows), jnp.concatenate(b_rows), kept


def _running_sums(a, chunk, pull_back=False):
    """G of a [R, K] float32, R rows of one or two chunks (G_t = a_1 + .. +
    a_t from the chunk's first row), as a product with a (block-diagonal)
    triangle of ones, exact in any precision's first addend; with
    `pull_back` its transpose: the sums from t to the chunk's end."""
    row, col, same = _same_chunk(a.shape[0], chunk)
    ones = ((row >= col) & same).astype(F32)
    return _dot(ones, a, ((0,), (0,)) if pull_back else ((1,), (0,)))


# what of a chunk no state enters: keep = exp(G_C) [1, K] float32, b = tril(B)
# and t = (I + A)^-1 [C, C], qg = q exp G, wg = beta k exp G, kd = k tail
# [C, K] in the operands' type; and for the backward alone e = exp G, tail =
# exp(G_C - G) [C, K] float32 and `_pairs`' kept operands and factors
_Chunk = collections.namedtuple("_Chunk", "keep b t qg wg kd e tail kept")


def _state_free(q, k, kb, a, chunk):
    """q, k, kb = beta k [R, K] (one type), a [R, K] float32, R rows of one
    chunk or of two consecutive ones -> a `_Chunk` each. Two chunks are ONE
    [2 C, 2 C] system: G one product with a block-diagonal triangle, A
    block-diagonal from each chunk's own pair products beside exact zeros,
    and its inverse the same ten float32 products as one chunk's."""
    rows, op = q.shape[0], q.dtype
    cuts = [slice(lo, lo + chunk) for lo in range(0, rows, chunk)]
    q32, k32, kb32 = q.astype(F32), k.astype(F32), kb.astype(F32)
    g = _running_sums(a, chunk)
    e = jnp.exp(g)
    qg, wg = (q32 * e).astype(op), (kb32 * e).astype(op)
    raw = [_pairs(q32[s], k32[s], kb32[s], g[s], op) for s in cuts]
    if len(cuts) == 1:
        below = raw[0][0]
    else:
        zero = jnp.zeros((chunk, chunk), F32)
        below = jnp.concatenate([
            jnp.concatenate([raw[0][0], zero], axis=1),
            jnp.concatenate([zero, raw[1][0]], axis=1)])
    row, col = _iota2(rows)
    t = _tri_inverse(jnp.where(row > col, below, 0.0), chunk)
    row, col = _iota2(chunk)
    out = []
    for s, (_, b, kept) in zip(cuts, raw):
        last = g[s][chunk - 1:chunk]
        tail = jnp.exp(last - g[s])
        out.append(_Chunk(
            jnp.exp(last), jnp.where(row >= col, b, 0.0).astype(op),
            t[:, s].astype(op), qg[s], wg[s], (k32[s] * tail).astype(op),
            e[s], tail, kept))
    return out


def _chunk_state(vb, st, ch):
    """What of a chunk the state enters: vb = beta v [C, V], st the incoming
    state [V, K] float32 -> (o [C, V] float32, the outgoing state, the
    pseudo-values U in the operands' type)."""
    c, op = vb.shape[0], vb.dtype
    from_state = _dot(jnp.concatenate([ch.qg, ch.wg]), st.astype(op),
                      ((1,), (1,)))                          # [2 C, V]
    r = vb.astype(F32) - from_state[c:]
    u = _dot(ch.t, r.astype(op), ((1,), (0,))).astype(op)
    o = from_state[:c] + _dot(ch.b, u, ((1,), (0,)))
    st1 = st * ch.keep + _dot(u, ch.kd, ((0,), (0,)))
    return o, st1, u


def _chunk_backward(st, do, dst1, st1, u, ch):
    """The pull-back of one chunk (`_state_free` + `_chunk_state`) along
    (do [C, V], dst1 [V, K] float32) -> (dq, dk, dkb, dvb, dG float32; the
    incoming state's)."""
    c, op = u.shape[0], u.dtype
    st_op, dst1_op, do = st.astype(op), dst1.astype(op), do.astype(op)
    du = (_dot(ch.b, do, ((0,), (0,)))
          + _dot(ch.kd, dst1_op, ((1,), (1,))))              # [C, V]
    dr32 = _dot(ch.t, du.astype(op), ((0,), (0,)))
    dr = dr32.astype(op)
    row, col = _iota2(c)
    db = jnp.where(row >= col, _dot(do, u, ((1,), (1,))), 0.0)
    da = jnp.where(row > col, -_dot(dr, u, ((1,), (1,))), 0.0)
    dst0 = (dst1 * ch.keep + _dot(do, ch.qg, ((0,), (0,)))
            - _dot(dr, ch.wg, ((0,), (0,))))                 # [V, K]
    both = _dot(jnp.concatenate([do, dr]), st_op, ((1,), (0,)))  # [2 C, K]
    dkd = _dot(u, dst1_op, ((1,), (0,)))
    dk = dkd * ch.tail
    # dG is q o dq + (beta k) o d(beta k) - k o dk, in which every pair (t, i)
    # of rows stands twice with opposite signs, once on each row: its running
    # sum keeps the pairs that straddle a row and has the others cancel. They
    # cancel only if both copies are the SAME number, so dG is summed from
    # each product's operands as they entered the product (rounded to the
    # operands' type) times their raw cotangents, not from q, k and the
    # scaled dq, dk: formed that way the two copies differ by a rounding of
    # different factors, and at bfloat16 what failed to cancel was a tenth
    # of the decay's gradient
    dg = (ch.qg.astype(F32) * both[:c] - ch.wg.astype(F32) * both[c:]
          - ch.kd.astype(F32) * dkd)
    dq_rows, dkb_rows, dg_rows = [], [], []
    for i, (left, right, el, er) in enumerate(ch.kept):
        lo = i * SUB
        dp = jnp.concatenate([db[lo:lo + SUB], da[lo:lo + SUB]]).astype(op)
        dleft = _dot(dp, right, ((1,), (0,)))                # [2 SUB, K]
        dright = _dot(dp, left, ((0,), (0,)))                # [C, K]
        dq_rows.append(dleft[:SUB] * el)
        dkb_rows.append(dleft[SUB:] * el)
        dk = dk + dright * er
        by_row = left.astype(F32) * dleft
        dg_rows.append(by_row[:SUB] + by_row[SUB:])
        dg = dg - right.astype(F32) * dright
    dq = both[:c] * ch.e + jnp.concatenate(dq_rows)
    dkb = jnp.concatenate(dkb_rows) - both[c:] * ch.e
    at_last = jnp.sum(dst1 * st1, axis=0, keepdims=True)     # [1, K]
    is_last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    dg = dg + jnp.concatenate(dg_rows) + jnp.where(is_last, at_last, 0.0)
    return (dq, dk, dkb, dr32, dg), dst0


# -- XLA's driver -------------------------------------------------------------

def _core_xla(q, k, kb, vb, a, chunk):
    """[b, L, H, K | V] operands (a float32) -> o [b, L, H, V] float32."""
    b, seq, heads, dk = q.shape
    dv = vb.shape[-1]

    def head(*operands):                         # [L, K | V] each
        def step(st, xs):
            q, k, kb, vb, a = xs
            o, st1, _ = _chunk_state(
                vb, st, _state_free(q, k, kb, a, chunk)[0])
            return st1, o

        xs = [x.reshape(seq // chunk, chunk, -1) for x in operands]
        _, o = jax.lax.scan(step, jnp.zeros((dv, dk), F32), xs)
        return o.reshape(seq, dv)

    over = jax.vmap(jax.vmap(head, in_axes=1, out_axes=1))
    return over(q, k, kb, vb, a)


# -- the kernels --------------------------------------------------------------

def _chunk_rows(i, rows):
    return pl.ds(pl.multiple_of(i * rows, rows), rows)


def _systems(block, chunk):
    """Chunks in each system a grid step of `block` tokens forms: pairs
    where its chunks pair off (four or two a step), else one (seq 192)."""
    n = 2 if (block // chunk) % 2 == 0 else 1
    return [n] * (block // chunk // n)


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, a_ref, o_ref, *rest, chunk,
                save):
    st_ref = rest[-1]
    systems = _systems(q_ref.shape[0], chunk)
    n = systems[0]

    @pl.when(pl.program_id(2) == 0)
    def _first():
        st_ref[...] = jnp.zeros_like(st_ref)

    def one(p, st):
        both = _chunk_rows(p, n * chunk)
        free = _state_free(q_ref[both, :], k_ref[both, :], kb_ref[both, :],
                           a_ref[both, :], chunk)
        for at, ch in enumerate(free):
            i = p * n + at
            rows = _chunk_rows(i, chunk)
            if save:
                rest[0][_chunk_rows(i, _LANES), :] = st.astype(rest[0].dtype)
            o, st, _ = _chunk_state(vb_ref[rows, :], st, ch)
            o_ref[rows, :] = o.astype(o_ref.dtype)
        return st

    # a loop over the systems, not an unrolling: a step's 18 copies of these
    # kernels are most of what its compilation costs, and two pairs written
    # out compiled the step 40 s longer for nothing (the scheduler does not
    # overlap them)
    st_ref[...] = jax.lax.fori_loop(0, len(systems), one, st_ref[...])


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, a_ref, s_ref, do_ref, dq_ref,
                dk_ref, dkb_ref, dvb_ref, da_ref, dst_ref, *, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _first():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    systems = _systems(q_ref.shape[0], chunk)
    n = systems[0]

    def one(j, dst):
        p = len(systems) - 1 - j
        both = _chunk_rows(p, n * chunk)
        free = _state_free(q_ref[both, :], k_ref[both, :], kb_ref[both, :],
                           a_ref[both, :], chunk)
        dgs = [None] * n
        for at in reversed(range(n)):
            i = p * n + at
            rows = _chunk_rows(i, chunk)
            st = s_ref[_chunk_rows(i, _LANES), :].astype(F32)
            _, st1, u = _chunk_state(vb_ref[rows, :], st, free[at])
            grads, dst = _chunk_backward(st, do_ref[rows, :], dst, st1, u,
                                         free[at])
            for ref, v in zip((dq_ref, dk_ref, dkb_ref, dvb_ref), grads):
                ref[rows, :] = v.astype(ref.dtype)
            dgs[at] = grads[4]
        da_ref[both, :] = _running_sums(jnp.concatenate(dgs), chunk,
                                        pull_back=True)
        return dst

    dst_ref[...] = jax.lax.fori_loop(0, len(systems), one, dst_ref[...])


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _block(seq, chunk):
    """Tokens a grid step takes: up to four chunks, the state's round trip
    through scratch and the step's own cost paid once for them."""
    return next(n * chunk for n in (4, 2, 1) if seq % (n * chunk) == 0)


def _specs(seq, heads, chunk, block_of):
    block = _block(seq, chunk)
    nb, per = seq // block, block // chunk
    wide = pl.BlockSpec((None, block, _LANES),
                        lambda bi, h, j: (bi, block_of(j, nb), h))
    # a chunk's incoming state, rows ((batch, head, chunk), V) of ONE
    # two-dimensional table (ssd_scan.py `_saved_spec`)
    saved = pl.BlockSpec((per * _LANES, _LANES), lambda bi, h, j: (
        (bi * np.int32(heads) + h) * np.int32(nb) + block_of(j, nb), _Z))
    return wide, saved, nb


# jitted, both: a model's layers call them with the same shapes, and a jitted
# function is traced (its kernel's body with it) and lowered once for them
# all, where a bare `pallas_call` traces its kernel anew at every call
@functools.partial(jax.jit, static_argnames=("chunk", "save", "interpret"))
def kda_fwd(q, k, kb, vb, a, chunk, save=False, interpret=False):
    """q, k, kb = beta k, vb = beta v [b, L, H 128] (one type); a [b, L,
    H 128] float32 -> o in q's type and, with `save`, each chunk's incoming
    state (q's type, [(b, H, chunks, 128), 128], transposed: [V, K])."""
    b, seq, width = q.shape
    heads = width // _LANES
    wide, saved, nb = _specs(seq, heads, chunk, lambda j, nb: j)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [wide]
    if save:
        out_shape.append(jax.ShapeDtypeStruct(
            (b * heads * (seq // chunk) * _LANES, _LANES), q.dtype))
        out_specs.append(saved)
    out = routing.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, save=save),
        name="kda_fwd",
        grid=(b, heads, nb),
        in_specs=[wide] * 5,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_LANES, _LANES), F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(q, k, kb, vb, a)
    return tuple(out) if save else out[0]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kda_bwd(q, k, kb, vb, a, states, do, chunk, interpret=False):
    """The pull-back of `kda_fwd` along do, given the states it saved ->
    (dq, dk, dkb, dvb in q's type, da float32)."""
    b, seq, width = q.shape
    heads = width // _LANES
    wide, saved, nb = _specs(seq, heads, chunk,
                             lambda j, nb: np.int32(nb - 1) - j)
    like = jax.ShapeDtypeStruct(q.shape, q.dtype)
    return routing.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        name="kda_bwd",
        grid=(b, heads, nb),
        in_specs=[wide] * 5 + [saved, wide],
        out_specs=[wide] * 5,
        out_shape=[like] * 4 + [jax.ShapeDtypeStruct(q.shape, F32)],
        scratch_shapes=[pltpu.VMEM((_LANES, _LANES), F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(q, k, kb, vb, a, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q, k, kb, vb, a, chunk, interpret):
    return kda_fwd(q, k, kb, vb, a, chunk, False, interpret)


def _core_fwd(q, k, kb, vb, a, chunk, interpret):
    o, states = kda_fwd(q, k, kb, vb, a, chunk, True, interpret)
    return o, (q, k, kb, vb, a, states)


def _core_bwd(chunk, interpret, res, do):
    return tuple(kda_bwd(*res, do.astype(res[0].dtype), chunk, interpret))


_core.defvjp(_core_fwd, _core_bwd)


def _operands(q, k, v, a, beta, chunk):
    """-> q, k, beta k, beta v in q's type and a float32, all [b, L, H, d]."""
    if q.shape[1] % chunk:
        raise ValueError(f"kda: seq {q.shape[1]} is not a multiple of chunk "
                         f"{chunk}")
    op = q.dtype
    beta = beta.astype(F32)[..., None]
    return (q, k.astype(op), (k.astype(F32) * beta).astype(op),
            (v.astype(F32) * beta).astype(op), a.astype(F32))


def kda_xla(q, k, v, a, beta, chunk=64):
    """`kda` by XLA alone (module docstring)."""
    return _core_xla(*_operands(q, k, v, a, beta, chunk), chunk).astype(
        q.dtype)


def kda(q, k, v, a, beta, chunk=64, interpret=None, use_kernel=None):
    """The scan of the module docstring, differentiable in all five operands.

    q, k [b, L, H, K] (as they enter the recurrence: normalised, q scaled);
    v [b, L, H, V]; a [b, L, H, K] (<= 0, and >= -5 for the exponent rule to
    hold); beta [b, L, H] -> o [b, L, H, V] in q's type. On a TPU, for a
    geometry `supports` names, by the two kernels; else by `kda_xla`. `seq`
    has to be a multiple of `chunk`."""
    b, seq, heads, dk = q.shape
    geometry = (q.shape, v.shape, chunk, str(q.dtype))
    use_kernel, interpret = routing.route(
        "kda", supports(q.shape, v.shape, chunk, q.dtype), geometry,
        interpret, use_kernel)
    if not use_kernel:
        return kda_xla(q, k, v, a, beta, chunk)
    flat = [x.reshape(b, seq, -1)
            for x in _operands(q, k, v, a, beta, chunk)]
    return _core(*flat, chunk, interpret).reshape(v.shape).astype(q.dtype)


def kda_flat(q, k, kb, vb, a, heads, chunk=64, interpret=None,
             use_kernel=None):
    """`kda` on the kernels' own operands, flat rows in and out: q, k,
    kb = beta k, vb = beta v [b, L, heads d] in one type, a [b, L, heads d]
    float32 (`kda_rows.kda_inputs` makes the five) -> o [b, L, heads d] in
    q's type. Kernels and fallback as `kda`; no [b, L, heads, d] table is
    formed on the kernels' path."""
    b, seq, width = q.shape
    if seq % chunk:
        raise ValueError(f"kda: seq {seq} is not a multiple of chunk {chunk}")
    cut = (b, seq, heads, width // heads)
    use_kernel, interpret = routing.route(
        "kda", supports(cut, cut, chunk, q.dtype),
        (cut, cut, chunk, str(q.dtype)), interpret, use_kernel)
    if use_kernel:
        return _core(q, k, kb, vb, a, chunk, interpret)
    o = _core_xla(*(x.reshape(cut) for x in (q, k, kb, vb, a)), chunk)
    return o.reshape(q.shape).astype(q.dtype)
