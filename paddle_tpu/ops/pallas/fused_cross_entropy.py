"""Fused LM-head cross entropy — vocab-tiled Pallas TPU kernels, fwd + bwd.

The [tokens, vocab] logits matrix of a 50k-vocab LM head is the largest
single tensor of the GPT training step (fp32 it is ~1.6G at the 1.3b
bench config) and, under the stock path, both a forward HBM round-trip
and a vjp residual held across the whole backward. This kernel streams
the head matmul through **vocab tiles** instead:

* **forward**: for each vocab tile `W_t [bv, H]`, compute the tile's
  logits `h @ W_t^T [bn, bv]` on the MXU and fold them into running
  row-max / row-sumexp stats (online logsumexp, the flash-attention
  trick applied to the softmax over the vocab axis) plus the gathered
  label logit (a masked row-sum — only the matching column survives).
  Only `loss = lse - picked` and the LSE residual leave the kernel; the
  logits tile dies in VMEM.
* **backward**: recompute each tile's logits from (h, W_t, LSE), form
  `d_logits_t = (softmax_t - onehot_t) * g` in registers, and fold it
  immediately into both outputs: `dh += d_logits_t @ W_t` (fp32 VMEM
  scratch per token tile) and `dW_t += d_logits_t^T @ h` (fp32 HBM
  accumulator via `input_output_aliases`, revisited once per token tile
  — the flash_attention.py aliased-accumulator design, with the same
  hazard-free per-token-tile rowloop for interpret mode and short
  revisit distances). The [tokens, vocab] d_logits never exists either.

Two paths, one contract (the `paged_attention.py` routing pattern):

* **Pallas kernel** — TPU (or `interpret=True` for hermetic CPU parity).
  Any vocabulary: the grid has `cdiv(vocab, bv)` vocab tiles, and a last
  tile that runs past the head (a vocabulary-parallel slice of 18,992 =
  148 * 128 + 48 rows) is masked INSIDE the kernels: its columns beyond
  `vocab` are -inf before the running max, the sum and the picked logit,
  so p = 0 and d_logits = 0 there, and W's rows beyond `vocab` (whatever
  an out-of-range block holds) are read as 0. The head stays
  [vocab, hidden] and so does dW: the boundary block's rows beyond
  `vocab` are never written. A vocabulary that is whole tiles (the bench
  vocab 50304 = 393 * 128) traces none of this.
* **XLA path** (`impl="xla"`) — CPU / unsupported dtypes: a
  `lax.scan` over the same vocab tiles in the same order with the same
  fp32 accumulation, so kernel-vs-fallback parity is tight; handles
  arbitrary vocab sizes by padding the last tile (padded columns are
  masked to -inf and can never match a label).

Weight layout is [vocab, hidden] (`transpose_y=True`, the tied-embedding
layout); `nn.functional.fused_linear_cross_entropy` adapts [H, V] heads
outside. Labels equal to `ignore_index` yield loss 0 and zero gradients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import routing
from .flash_attention import (  # noqa: F401  (shared kernel helpers)
    _LANES, _REVISIT_MIN, _Z, _dot, pl, pltpu,
)

__all__ = ["fused_cross_entropy", "sharded_fused_cross_entropy",
           "supports"]


def supports(vocab, hidden, dtype) -> bool:
    """Whether the Pallas kernel can take this head (else XLA tiles): any
    vocabulary, a last partial tile is masked inside the kernel."""
    return dtype in (jnp.float32, jnp.bfloat16, jnp.float16)


# what the backward may keep in VMEM beside its [bn, bv] score tiles: a
# v5e's scoped limit is 16 MiB
_VMEM_BUDGET = 14 * 2 ** 20


def _bwd_vmem_bytes(bn, bv, hidden, itemsize):
    """Bytes the backward kernel's blocks hold: h, W and dh twice each
    (the pipeline's two buffers), the fp32 dW accumulator in and out
    twice each, and dh's fp32 scratch."""
    return hidden * (4 * bn * itemsize + 2 * bv * itemsize + 16 * bv
                     + 4 * bn)


def _pick_block_v(vocab, hidden=None, itemsize=2, bn=256):
    """The widest vocab tile that divides `vocab` rounded up to whole
    lanes (18,992 -> 19,072 = 149 * 128: only the last tile is partial)
    and, when the caller says how wide the rows are, whose backward fits
    VMEM: at hidden 2304 a 512-row tile's two fp32 accumulator blocks
    alone are 19 MiB. The narrowest is returned when none fits."""
    fits = [bv for bv in (512, 256, _LANES)
            if pl.cdiv(vocab, _LANES) * _LANES % bv == 0]
    for bv in fits:
        if hidden is None or _bwd_vmem_bytes(bn, bv, hidden,
                                             itemsize) <= _VMEM_BUDGET:
            return bv
    return fits[-1]


def _pick_block_n(n):
    for bn in (256, 128, 64, 32, 16, 8):
        if n % bn == 0:
            return bn
    return 8  # pad rows up to a multiple of 8


# ---------------------------------------------------------------------------
# forward kernel: grid (token tile, vocab tile), online logsumexp scratch
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, lbl_ref, loss_ref, lse_ref, m_ref, l_ref,
                pk_ref, *, block_v, ignore_index, vocab):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        pk_ref[...] = jnp.zeros_like(pk_ref)

    h = h_ref[0]                                         # [bn, H]
    w = w_ref[0]                                         # [bv, H]
    logits = _dot(h, w, ((1,), (1,)))                    # [bn, bv] fp32
    lbl = lbl_ref[0][:, :1]                              # [bn, 1] int32
    col = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    if vocab % block_v:                 # a test on a shape, at trace time
        # columns of the last tile beyond the head: whatever the
        # out-of-range rows of the W block held, they weigh nothing
        logits = jnp.where(col < vocab, logits, -jnp.inf)
    m_prev = m_ref[...]                                  # [bn, LANES]
    l_prev = l_ref[...]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    corr = jnp.exp(m_prev - m_new)   # tile 0: exp(-inf - finite) = 0
    p = jnp.exp(logits - m_new[:, :1])
    l_ref[...] = corr * l_prev + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
    m_ref[...] = m_new
    pk_ref[...] += jnp.broadcast_to(
        jnp.sum(jnp.where(col == lbl, logits, 0.0), axis=1,
                keepdims=True), pk_ref.shape)

    @pl.when(vi == nv - 1)
    def _finish():
        lse = m_ref[...] + jnp.log(l_ref[...])
        valid = lbl != ignore_index                      # [bn, 1]
        loss_ref[0] = jnp.where(valid, lse - pk_ref[...], 0.0)
        lse_ref[0] = lse


def _fwd_pallas(h, w, lbl_b, bn, bv, ignore_index, interpret, vocab=None):
    """`vocab`: the head's rows where `w`'s storage holds more (a test
    that plants rows beyond them); the grid covers `vocab` alone."""
    n, hidden = h.shape
    vocab = w.shape[0] if vocab is None else vocab
    spec_h = pl.BlockSpec((1, bn, hidden), lambda i, j: (_Z, i, _Z))
    spec_w = pl.BlockSpec((1, bv, hidden), lambda i, j: (_Z, j, _Z))
    spec_r = pl.BlockSpec((1, bn, _LANES), lambda i, j: (_Z, i, _Z))
    loss, lse = routing.pallas_call(
        functools.partial(_fwd_kernel, block_v=bv,
                          ignore_index=ignore_index, vocab=vocab),
        name="fused_ce_fwd",
        grid=(n // bn, pl.cdiv(vocab, bv)),
        in_specs=[spec_h, spec_w, spec_r],
        out_specs=[spec_r, spec_r],
        out_shape=[
            jax.ShapeDtypeStruct((1, n, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, n, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(h[None], w[None], lbl_b[None])
    return loss[0, :, 0], lse[0, :, 0]


# ---------------------------------------------------------------------------
# backward kernel: recompute tile logits from LSE, fold d_logits into
# dh (VMEM scratch per token tile) and dW (aliased fp32 HBM accumulator)
# ---------------------------------------------------------------------------

def _bwd_kernel(h_ref, w_ref, lbl_ref, lse_ref, g_ref, dwi_ref,
                dh_ref, dw_ref, dh_acc, *, block_v, vocab):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        dh_acc[...] = jnp.zeros_like(dh_acc)

    # pass the accumulator through unconditionally
    dw_ref[0] = dwi_ref[0]

    h = h_ref[0]                                         # [bn, H]
    w = w_ref[0]                                         # [bv, H]
    if vocab % block_v:
        # rows of the last tile beyond the head: d is 0 in their columns,
        # and 0 times whatever an out-of-range block holds may be NaN.
        # Compared on every tile: under a `lax.cond` that spares the 148
        # whole tiles the pair took 108.8 ms for 103.0 at keye's head
        row = vi * block_v + jax.lax.broadcasted_iota(
            jnp.int32, (block_v, 1), 0)
        w = jnp.where(row < vocab, w, jnp.zeros_like(w))
    lse = lse_ref[0][:, :1]                              # [bn, 1]
    g = g_ref[0][:, :1]                                  # [bn, 1] fp32
    lbl = lbl_ref[0][:, :1]
    logits = _dot(h, w, ((1,), (1,)))                    # [bn, bv] fp32
    p = jnp.exp(logits - lse)
    col = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    if vocab % block_v:
        p = jnp.where(col < vocab, p, 0.0)     # as exp(-inf): d = 0 there
    d = (p - jnp.where(col == lbl, 1.0, 0.0)) * g        # [bn, bv] fp32
    dlow = d.astype(h.dtype)       # grads ride the MXU in the op dtype
    dh_acc[...] += _dot(dlow, w, ((1,), (0,)))           # [bn, H]
    dw_ref[0] += _dot(dlow, h, ((0,), (0,)))             # [bv, H]

    @pl.when(vi == nv - 1)
    def _finish():
        dh_ref[0] = dh_acc[...].astype(dh_ref.dtype)


def _bwd_call(h, w, lbl_b, lse_b, g_b, dw_acc, bn, bv, interpret,
              vocab=None):
    """`dw_acc` has `w`'s rows; `vocab` as `_fwd_pallas`'s."""
    n, hidden = h.shape
    vocab = w.shape[0] if vocab is None else vocab
    spec_h = pl.BlockSpec((1, bn, hidden), lambda i, j: (_Z, i, _Z))
    spec_w = pl.BlockSpec((1, bv, hidden), lambda i, j: (_Z, j, _Z))
    spec_r = pl.BlockSpec((1, bn, _LANES), lambda i, j: (_Z, i, _Z))
    # rows so wide that even the narrowest vocabulary tile's blocks pass
    # the scoped default (hidden 2688: 16.3 MiB of a v5e's 16) ask for
    # what they hold and their score tiles' room; any other call asks for
    # nothing and compiles as before
    need = _bwd_vmem_bytes(bn, bv, hidden, h.dtype.itemsize)
    params = ({} if need <= _VMEM_BUDGET else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=need + 8 * 2 ** 20)})
    dh, dw = routing.pallas_call(
        functools.partial(_bwd_kernel, block_v=bv, vocab=vocab),
        name="fused_ce_bwd",
        grid=(n // bn, pl.cdiv(vocab, bv)),
        **params,
        in_specs=[spec_h, spec_w, spec_r, spec_r, spec_r, spec_w],
        out_specs=[spec_h, spec_w],
        out_shape=[
            jax.ShapeDtypeStruct((1, n, hidden), h.dtype),
            jax.ShapeDtypeStruct((1,) + dw_acc.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, hidden), jnp.float32)],
        # dW accumulator aliases its input (position 5 -> output 1)
        input_output_aliases={5: 1},
        interpret=interpret,
    )(h[None], w[None], lbl_b[None], lse_b[None], g_b[None], dw_acc[None])
    return dh[0], dw[0]


_alias_checked: set = set()


def _alias_selfcheck(dtype, hidden, bn, bv, last=0):
    """One-time (per config, per process) on-device check of the fused
    dW aliased-accumulator backward against the hazard-free per-token-
    tile path (the flash_attention.py guard applied to the CE kernel).
    `last`: the rows of a partial last vocab tile (0: whole tiles); the
    check's head ends in a tile of as many, so its boundary block is the
    caller's."""
    from ...utils import flags as _flags

    key = (str(dtype), hidden, bn, bv, last)
    if key in _alias_checked or not _flags.get_flag(
            "FLAGS_pallas_alias_selfcheck"):
        return
    n, vocab = 2 * bn, bv * (_REVISIT_MIN - 1) + (last or bv)

    def _run():
        rng = np.random.default_rng(0)
        h = jnp.asarray(rng.standard_normal((n, hidden)) * 0.5, dtype)
        w = jnp.asarray(rng.standard_normal((vocab, hidden)) * 0.1,
                        dtype)
        lbl = _lane_bcast(jnp.asarray(
            rng.integers(0, vocab, (n,)), jnp.int32), jnp.int32)
        _, lse = _fwd_pallas(h, w, lbl, bn, bv, -100, False)
        g = _lane_bcast(jnp.ones((n,), jnp.float32), jnp.float32)
        z = lambda: jnp.zeros((vocab, hidden), jnp.float32)  # noqa: E731
        lse_b = _lane_bcast(lse, jnp.float32)
        dh_f, dw_f = _bwd_call(h, w, lbl, lse_b, g, z(), bn, bv, False)
        dh_rows, dw_r = [], z()
        for ti in range(n // bn):
            sl = slice(ti * bn, (ti + 1) * bn)
            dh_row, dw_r = _bwd_call(h[sl], w, lbl[sl], lse_b[sl],
                                     g[sl], dw_r, bn, bv, False)
            dh_rows.append(dh_row)
        dh_r = jnp.concatenate(dh_rows, axis=0)
        return {n_: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
                for n_, a, b in (("dh", dh_f, dh_r), ("dw", dw_f, dw_r))}

    import concurrent.futures
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        errs = pool.submit(_run).result()
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, err in errs.items():
        if not err < tol:
            raise RuntimeError(
                f"fused-CE backward self-check FAILED ({name} max err "
                f"{err:.3e}, tol {tol:.0e}, config {key}): the aliased "
                "dW accumulator round-trip no longer matches the "
                "hazard-free path. F.cross_entropy on dense logits does "
                "not use this kernel; report this.")
    _alias_checked.add(key)   # only memoize a PASSING check


def _bwd_pallas(h, w, lbl_b, lse_b, g_b, bn, bv, interpret, vocab=None):
    n = h.shape[0]
    hidden = w.shape[1]
    dw_acc = jnp.zeros(w.shape, jnp.float32)
    vocab = w.shape[0] if vocab is None else vocab
    nt = n // bn
    # the aliased dW blocks are revisited once per token tile, a full
    # vocab sweep apart; below _REVISIT_MIN (or in interpret mode, which
    # replays revisited aliased blocks from the original input) fall
    # back to one hazard-free call per token tile
    if not interpret and (nt == 1 or pl.cdiv(vocab, bv) >= _REVISIT_MIN):
        if nt > 1:
            _alias_selfcheck(h.dtype, hidden, bn, bv, vocab % bv)
        return _bwd_call(h, w, lbl_b, lse_b, g_b, dw_acc, bn, bv,
                         interpret, vocab)
    dh_rows = []
    for ti in range(nt):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=ti * bn, slice_size=bn, axis=0)
        dh_row, dw_acc = _bwd_call(sl(h), w, sl(lbl_b), sl(lse_b),
                                   sl(g_b), dw_acc, bn, bv, interpret,
                                   vocab)
        dh_rows.append(dh_row)
    return jnp.concatenate(dh_rows, axis=0), dw_acc


# ---------------------------------------------------------------------------
# XLA fallback: the same vocab tiles as a lax.scan (identical math/order)
# ---------------------------------------------------------------------------

def _tiles_xla(w, bv):
    vocab, hidden = w.shape
    nv = -(-vocab // bv)
    pad = nv * bv - vocab
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    return w.reshape(nv, bv, hidden), nv, pad


def _fwd_xla(h, w, labels, bv, ignore_index):
    n = h.shape[0]
    vocab = w.shape[0]
    wt, nv, pad = _tiles_xla(w, bv)
    lbl = labels[:, None]                                # [n, 1]

    def body(carry, xs):
        m, l, pk = carry
        w_t, t = xs
        logits = _dot(h, w_t, ((1,), (1,)))              # [n, bv] fp32
        col = t * bv + jnp.arange(bv, dtype=jnp.int32)[None]
        if pad:
            logits = jnp.where(col < vocab, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l = corr * l + jnp.sum(p, axis=1)
        pk = pk + jnp.sum(jnp.where(col == lbl, logits, 0.0), axis=1)
        return (m_new, l, pk), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    (m, l, pk), _ = jax.lax.scan(
        body, init, (wt, jnp.arange(nv, dtype=jnp.int32)))
    lse = m + jnp.log(l)
    losses = jnp.where(labels != ignore_index, lse - pk, 0.0)
    return losses, lse


def _bwd_xla(h, w, labels, lse, g_eff, bv):
    n, hidden = h.shape
    vocab = w.shape[0]
    wt, nv, pad = _tiles_xla(w, bv)
    lbl = labels[:, None]

    def body(dh, xs):
        w_t, t = xs
        logits = _dot(h, w_t, ((1,), (1,)))
        col = t * bv + jnp.arange(bv, dtype=jnp.int32)[None]
        if pad:
            logits = jnp.where(col < vocab, logits, -jnp.inf)
        p = jnp.exp(logits - lse[:, None])
        d = (p - jnp.where(col == lbl, 1.0, 0.0)) * g_eff[:, None]
        dlow = d.astype(h.dtype)
        dh = dh + _dot(dlow, w_t, ((1,), (0,)))
        dw_t = _dot(dlow, h, ((0,), (0,)))               # [bv, H] fp32
        return dh, dw_t

    dh, dws = jax.lax.scan(
        body, jnp.zeros((n, hidden), jnp.float32),
        (wt, jnp.arange(nv, dtype=jnp.int32)))
    dw = dws.reshape(nv * bv, hidden)[:vocab]
    return dh.astype(h.dtype), dw


# ---------------------------------------------------------------------------
# custom_vjp wrapper + public entry
# ---------------------------------------------------------------------------

def _lane_bcast(x, dtype):
    return jnp.broadcast_to(x.astype(dtype)[:, None], x.shape + (_LANES,))


def _pad_rows(x, pad, value):
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=value) if pad else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_ce(h, w, labels, ignore_index, bn, bv, impl):
    losses, _ = _fused_ce_fwd(h, w, labels, ignore_index, bn, bv, impl)
    return losses


def _fused_ce_fwd(h, w, labels, ignore_index, bn, bv, impl):
    n = h.shape[0]
    if impl == "xla":
        losses, lse = _fwd_xla(h, w, labels, bv, ignore_index)
    else:
        pad = (-n) % bn
        hp = _pad_rows(h, pad, 0)
        lblp = _pad_rows(labels.astype(jnp.int32), pad, ignore_index)
        losses, lse = _fwd_pallas(hp, w, _lane_bcast(lblp, jnp.int32),
                                  bn, bv, ignore_index,
                                  interpret=(impl == "interpret"))
        losses, lse = losses[:n], lse[:n]
    return losses, (h, w, labels, lse)


def _fused_ce_bwd(ignore_index, bn, bv, impl, res, g):
    h, w, labels, lse = res
    n = h.shape[0]
    # ignored rows contribute a constant 0 loss: zero their cotangent so
    # the recomputed (p - onehot) term cannot leak gradient through them
    g_eff = jnp.where(labels != ignore_index, g.astype(jnp.float32), 0.0)
    if impl == "xla":
        dh, dw = _bwd_xla(h, w, labels.astype(jnp.int32), lse, g_eff, bv)
    else:
        pad = (-n) % bn
        hp = _pad_rows(h, pad, 0)
        lblp = _pad_rows(labels.astype(jnp.int32), pad, ignore_index)
        dh, dw = _bwd_pallas(
            hp, w, _lane_bcast(lblp, jnp.int32),
            _lane_bcast(_pad_rows(lse, pad, 0), jnp.float32),
            _lane_bcast(_pad_rows(g_eff, pad, 0), jnp.float32),
            bn, bv, interpret=(impl == "interpret"))
        dh = dh[:n]
    ct_labels = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return dh.astype(h.dtype), dw.astype(w.dtype), ct_labels


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_cross_entropy(hidden, weight, labels, ignore_index=-100,
                        block_n=None, block_v=None, interpret=None,
                        use_kernel=None):
    """Per-token CE of `softmax(hidden @ weight^T)` with the [N, vocab]
    logits streamed through vocab tiles (see module docstring).

    hidden: [N, H]; weight: [vocab, H]; labels: int [N]. Returns fp32
    losses [N] (0 where labels == ignore_index). Differentiable in
    hidden and weight (custom tiled backward). Routes to the Pallas
    kernel on TPU when the geometry qualifies (`supports`), the XLA
    tiled fallback otherwise; `interpret=True` forces the kernel in
    interpret mode (hermetic CPU parity testing)."""
    n, h = hidden.shape
    vocab = weight.shape[0]
    use_kernel, interpret = routing.route(
        "fused_cross_entropy", supports(vocab, h, hidden.dtype),
        (f"vocab={vocab}", f"hidden={h}", str(hidden.dtype)),
        interpret, use_kernel)
    if use_kernel:
        impl = "interpret" if interpret else "pallas"
        bn = block_n if block_n is not None else _pick_block_n(n)
        if block_v is None:
            block_v = _pick_block_v(vocab, h, hidden.dtype.itemsize, bn)
    else:
        impl, bn = "xla", 1
        if block_v is None:
            block_v = _LANES
    return _fused_ce(hidden, weight, labels.astype(jnp.int32),
                     int(ignore_index), int(bn), int(block_v), impl)


# ---------------------------------------------------------------------------
# vocab-PARALLEL variant: each mesh rank holds a [vocab/mp, H] row shard
# of the head and tiles ONLY its shard; the online-logsumexp stats and
# the picked label logit combine across the `axis` ranks with one pmax +
# one (stacked) psum. Used inside jax.shard_map by the dp×mp hybrid
# train step (jit/sharded_scan.py) — the PR-7 vocab-tiled CE applied to
# the LOCAL vocab shard, so no rank ever materializes [tokens, vocab] OR
# [tokens, vocab/mp] logits.
# ---------------------------------------------------------------------------

def _fwd_xla_sharded(h, w, labels, off, bv, ignore_index):
    """Local online pass over the rank's vocab shard — same tiles, same
    order, same fp32 accumulation as `_fwd_xla`, with global column ids
    `off + tile columns` so label matching uses GLOBAL label values.
    Returns the PRE-combine per-rank stats (m, l, pk)."""
    vloc = w.shape[0]
    wt, nv, pad = _tiles_xla(w, bv)
    lbl = labels[:, None]
    n = h.shape[0]

    def body(carry, xs):
        m, l, pk = carry
        w_t, t = xs
        logits = _dot(h, w_t, ((1,), (1,)))              # [n, bv] fp32
        col = off + t * bv + jnp.arange(bv, dtype=jnp.int32)[None]
        # padded columns carry GLOBAL ids beyond this shard's range —
        # which ALIAS the next rank's real ids, so the label match must
        # be masked to valid local columns, not just the logits
        valid = col < off + vloc
        if pad:
            logits = jnp.where(valid, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l = corr * l + jnp.sum(p, axis=1)
        pk = pk + jnp.sum(jnp.where((col == lbl) & valid, logits, 0.0),
                          axis=1)
        return (m_new, l, pk), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    (m, l, pk), _ = jax.lax.scan(
        body, init, (wt, jnp.arange(nv, dtype=jnp.int32)))
    return m, l, pk


def _bwd_xla_sharded(h, w, labels, off, lse, g_all, bv):
    """Local tiled backward against the GLOBAL lse: d_logits_t =
    (softmax_t - onehot_t) * g for the rank's tiles only. dh is the
    rank's PARTIAL contribution (the caller's grad reduction sums the
    mp ranks); dw covers exactly the local shard rows."""
    n, hidden = h.shape
    vloc = w.shape[0]
    wt, nv, pad = _tiles_xla(w, bv)
    lbl = labels[:, None]

    def body(dh, xs):
        w_t, t = xs
        logits = _dot(h, w_t, ((1,), (1,)))
        col = off + t * bv + jnp.arange(bv, dtype=jnp.int32)[None]
        valid = col < off + vloc
        if pad:
            logits = jnp.where(valid, logits, -jnp.inf)
        p = jnp.exp(logits - lse[:, None])
        d = (p - jnp.where((col == lbl) & valid, 1.0, 0.0)) \
            * g_all[:, None]
        dlow = d.astype(h.dtype)
        dh = dh + _dot(dlow, w_t, ((1,), (0,)))
        dw_t = _dot(dlow, h, ((0,), (0,)))               # [bv, H] fp32
        return dh, dw_t

    dh, dws = jax.lax.scan(
        body, jnp.zeros((n, hidden), jnp.float32),
        (wt, jnp.arange(nv, dtype=jnp.int32)))
    dw = dws.reshape(nv * bv, hidden)[:vloc]
    return dh.astype(h.dtype), dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _sharded_ce(h, w, labels, off, axis, ignore_index, bv):
    losses, _ = _sharded_ce_fwd(h, w, labels, off, axis, ignore_index,
                                bv)
    return losses


def _sharded_ce_fwd(h, w, labels, off, axis, ignore_index, bv):
    m, l, pk = _fwd_xla_sharded(h, w, labels, off, bv, ignore_index)
    # cross-shard combine: one pmax for the running max, then the
    # sumexp correction and the picked logit ride ONE stacked psum
    mg = jax.lax.pmax(m, axis)
    both = jax.lax.psum(jnp.stack([jnp.exp(m - mg) * l, pk]), axis)
    lse = mg + jnp.log(both[0])
    losses = jnp.where(labels != ignore_index, lse - both[1], 0.0)
    return losses, (h, w, labels, off, lse)


def _sharded_ce_bwd(axis, ignore_index, bv, res, g):
    h, w, labels, off, lse = res
    g_eff = jnp.where(labels != ignore_index, g.astype(jnp.float32), 0.0)
    # joint-function transpose of the forward psums: every rank's loss
    # row consumed this rank's local stats, so the effective cotangent
    # is the axis-sum of the per-rank seeds (identical seeds -> mp * g;
    # the caller's 1/(dp*mp) grad normalization divides it back out —
    # the same uniform factor every replicated-compute grad carries)
    g_all = jax.lax.psum(g_eff, axis)
    dh, dw = _bwd_xla_sharded(h, w, labels.astype(jnp.int32), off, lse,
                              g_all, bv)
    ct_labels = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    ct_off = np.zeros((), dtype=jax.dtypes.float0)
    return dh.astype(h.dtype), dw, ct_labels, ct_off


_sharded_ce.defvjp(_sharded_ce_fwd, _sharded_ce_bwd)


def sharded_fused_cross_entropy(hidden, weight_local, labels,
                                vocab_start, axis, ignore_index=-100,
                                block_v=None):
    """Vocab-parallel `fused_cross_entropy` for use inside `shard_map`.

    hidden: [N, H] (replicated over `axis`); weight_local:
    [vocab/mp, H] — this rank's row shard of the [vocab, H] head;
    labels: GLOBAL int labels [N]; vocab_start: traced int32 scalar, the
    first global vocab id of this rank's shard; axis: the mesh axis name
    the vocab is sharded over. Returns fp32 losses [N] (0 at
    ignore_index rows), identical across ranks. Differentiable in
    hidden (partial per-rank contribution) and weight_local (exactly
    the shard's rows) via the custom tiled backward — the joint
    collective transpose is exact under shard_map (check_vma=False).
    """
    vloc = weight_local.shape[0]
    if block_v is None:
        block_v = _pick_block_v(vloc) or _LANES
    return _sharded_ce(hidden, weight_local, labels.astype(jnp.int32),
                       jnp.asarray(vocab_start, jnp.int32), axis,
                       int(ignore_index), int(block_v))
