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
(I + N^8), exact because N^16 = 0, all four at once as one block-diagonal
[C, C] product; the blocks below them by the same identity one level up,
X = D^-1 L, (I + X)^-1 = (I - X)(I + X^2) (X^4 = 0 at C = 64). Doubling the
whole [64, 64] matrix six times instead squares its entries up to N^32, whose
binomial growth costs float32 its digits; two levels of at most three
doublings do not.

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

G is formed inside a chunk's body too, as a product with a triangle of ones
(XLA's running sum over a [b, L, H 128] float32 table and its pull-back cost
a fifth of the forward kernel's time beside it). What stays XLA's: beta's
products with k and v and their pull-backs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import routing
from .flash_attention import _LANES, _Z, _dot, pl, pltpu

__all__ = ["kda", "kda_xla", "kda_fwd", "kda_bwd", "supports"]

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
    """a b of two float32 squares, at float32's own precision."""
    return _dot(a, b, ((1,), (0,)))


def _tri_inverse(a):
    """(I + a)^-1 for a strictly lower [C, C] float32, by the two levels of
    Neumann doubling of the module docstring."""
    c = a.shape[0]
    row, col = _iota2(c)
    eye = (row == col).astype(F32)
    shift = SUB.bit_length() - 1
    same = (row >> shift) == (col >> shift)
    diag = jnp.where(same, a, 0.0)

    def doubled(n, index):
        # (I + n)^-1 for n nilpotent of `index`
        inv, power = eye - n, n
        for _ in range(max(index - 1, 1).bit_length() - 1):
            power = _mm(power, power)
            inv = _mm(inv, eye + power)
        return inv

    dinv = doubled(diag, SUB)
    if c == SUB:
        return dinv
    return _mm(doubled(_mm(dinv, a - diag), c // SUB), dinv)


def _pairs(q, k, kb, g):
    """A (strictly lower) and B (lower) of a chunk, float32 [C, C], and per
    sub-block what the backward reuses: (left [2 SUB, K] = the block's rows
    of q and beta k times exp(G - G_b); right [C, K] = k exp(G_b - G); the
    two factors float32)."""
    c, op = q.shape[0], q.dtype
    q32, k32, kb32 = q.astype(F32), k.astype(F32), kb.astype(F32)
    a_rows, b_rows, kept = [], [], []
    for lo in range(0, c, SUB):
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
    row, col = _iota2(c)
    a = jnp.where(row > col, jnp.concatenate(a_rows), 0.0)
    b = jnp.where(row >= col, jnp.concatenate(b_rows), 0.0)
    return a, b, kept


def _running_sums(a, pull_back=False):
    """G of a chunk's a [C, K] float32 (G_t = a_1 + .. + a_t) as a product
    with a triangle of ones, exact in any precision's first addend; with
    `pull_back` its transpose: the sums from t to the chunk's end."""
    row, col = _iota2(a.shape[0])
    ones = (row >= col).astype(F32)
    return _dot(ones, a, ((0,), (0,)) if pull_back else ((1,), (0,)))


def _chunk_forward(q, k, kb, vb, a, st):
    """One chunk: q, k, kb = beta k [C, K], vb = beta v [C, V] (one type),
    a [C, K] float32, st the incoming state [V, K] float32 -> (o [C, V]
    float32, the outgoing state, what the backward reuses)."""
    c, op = q.shape[0], q.dtype
    g = _running_sums(a)
    below, b, kept = _pairs(q, k, kb, g)
    t = _tri_inverse(below).astype(op)
    e, last = jnp.exp(g), g[c - 1:c]
    qg = (q.astype(F32) * e).astype(op)
    wg = (kb.astype(F32) * e).astype(op)
    kd = (k.astype(F32) * jnp.exp(last - g)).astype(op)
    from_state = _dot(jnp.concatenate([qg, wg]), st.astype(op),
                      ((1,), (1,)))                          # [2 C, V]
    r = vb.astype(F32) - from_state[c:]
    u = _dot(t, r.astype(op), ((1,), (0,))).astype(op)
    o = from_state[:c] + _dot(b.astype(op), u, ((1,), (0,)))
    st1 = st * jnp.exp(last) + _dot(u, kd, ((0,), (0,)))
    return o, st1, (g, b, t, e, last, qg, wg, kd, u, kept)


def _chunk_backward(q, k, kb, st, do, dst1, fwd):
    """The pull-back of `_chunk_forward` along (do [C, V], dst1 [V, K]
    float32) -> (dq, dk, dkb, dvb, da float32; the incoming state's)."""
    c, op = q.shape[0], q.dtype
    st1, (g, b, t, e, last, qg, wg, kd, u, kept) = fwd
    st_op, dst1_op, do = st.astype(op), dst1.astype(op), do.astype(op)
    du = (_dot(b.astype(op), do, ((0,), (0,)))
          + _dot(kd, dst1_op, ((1,), (1,))))                 # [C, V]
    dr32 = _dot(t, du.astype(op), ((0,), (0,)))
    dr = dr32.astype(op)
    row, col = _iota2(c)
    db = jnp.where(row >= col, _dot(do, u, ((1,), (1,))), 0.0)
    da = jnp.where(row > col, -_dot(dr, u, ((1,), (1,))), 0.0)
    keep = jnp.exp(last)
    dst0 = (dst1 * keep + _dot(do, qg, ((0,), (0,)))
            - _dot(dr, wg, ((0,), (0,))))                    # [V, K]
    both = _dot(jnp.concatenate([do, dr]), st_op, ((1,), (0,)))  # [2 C, K]
    dkd = _dot(u, dst1_op, ((1,), (0,)))
    dk = dkd * jnp.exp(last - g)
    # dG is q o dq + (beta k) o d(beta k) - k o dk, in which every pair (t, i)
    # of rows stands twice with opposite signs, once on each row: its running
    # sum keeps the pairs that straddle a row and has the others cancel. They
    # cancel only if both copies are the SAME number, so dG is summed from
    # each product's operands as they entered the product (rounded to the
    # operands' type) times their raw cotangents, not from q, k and the
    # scaled dq, dk: formed that way the two copies differ by a rounding of
    # different factors, and at bfloat16 what failed to cancel was a tenth
    # of the decay's gradient
    dg = (qg.astype(F32) * both[:c] - wg.astype(F32) * both[c:]
          - kd.astype(F32) * dkd)
    dq_rows, dkb_rows, dg_rows = [], [], []
    for i, (left, right, el, er) in enumerate(kept):
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
    dq = both[:c] * e + jnp.concatenate(dq_rows)
    dkb = jnp.concatenate(dkb_rows) - both[c:] * e
    at_last = jnp.sum(dst1 * st1, axis=0, keepdims=True)     # [1, K]
    is_last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    dg = dg + jnp.concatenate(dg_rows) + jnp.where(is_last, at_last, 0.0)
    return (dq, dk, dkb, dr32, _running_sums(dg, pull_back=True)), dst0


# -- XLA's driver -------------------------------------------------------------

def _core_xla(q, k, kb, vb, a, chunk):
    """[b, L, H, K | V] operands (a float32) -> o [b, L, H, V] float32."""
    b, seq, heads, dk = q.shape
    dv = vb.shape[-1]

    def head(*operands):                         # [L, K | V] each
        def step(st, xs):
            o, st1, _ = _chunk_forward(*xs, st)
            return st1, o

        xs = [x.reshape(seq // chunk, chunk, -1) for x in operands]
        _, o = jax.lax.scan(step, jnp.zeros((dv, dk), F32), xs)
        return o.reshape(seq, dv)

    over = jax.vmap(jax.vmap(head, in_axes=1, out_axes=1))
    return over(q, k, kb, vb, a)


# -- the kernels --------------------------------------------------------------

def _chunk_rows(i, rows):
    return pl.ds(pl.multiple_of(i * rows, rows), rows)


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, a_ref, o_ref, *rest, chunk,
                save):
    st_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _first():
        st_ref[...] = jnp.zeros_like(st_ref)

    def one(i, st):
        rows = _chunk_rows(i, chunk)
        if save:
            rest[0][_chunk_rows(i, _LANES), :] = st.astype(rest[0].dtype)
        o, st, _ = _chunk_forward(q_ref[rows, :], k_ref[rows, :],
                                  kb_ref[rows, :], vb_ref[rows, :],
                                  a_ref[rows, :], st)
        o_ref[rows, :] = o.astype(o_ref.dtype)
        return st

    # a loop, not an unrolling: a step's 36 copies of these kernels are most
    # of what the step's compilation costs, and four chunks written out
    # compile four times as long for a tenth of the kernel's time
    st_ref[...] = jax.lax.fori_loop(0, q_ref.shape[0] // chunk, one,
                                    st_ref[...])


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, a_ref, s_ref, do_ref, dq_ref,
                dk_ref, dkb_ref, dvb_ref, da_ref, dst_ref, *, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _first():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    per = q_ref.shape[0] // chunk

    def one(j, dst):
        i = per - 1 - j
        rows = _chunk_rows(i, chunk)
        q, k, kb = q_ref[rows, :], k_ref[rows, :], kb_ref[rows, :]
        st = s_ref[_chunk_rows(i, _LANES), :].astype(F32)
        _, st1, kept = _chunk_forward(q, k, kb, vb_ref[rows, :],
                                      a_ref[rows, :], st)
        grads, dst = _chunk_backward(q, k, kb, st, do_ref[rows, :], dst,
                                     (st1, kept))
        for ref, v in zip((dq_ref, dk_ref, dkb_ref, dvb_ref, da_ref), grads):
            ref[rows, :] = v.astype(ref.dtype)
        return dst

    dst_ref[...] = jax.lax.fori_loop(0, per, one, dst_ref[...])


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
