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

Two paths, one contract (the `paged_attention.py` pattern):

* **Pallas kernel** — TPU (or `interpret=True` for hermetic CPU
  parity runs; see `paddle_tpu/ops/pallas/training_selftest.py`).
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
           "kernel_active"]

_SUB = 8  # sublane replication of the kv-side segment-id plane


def supports(q_shape, num_kv_heads, dtype, sk=None) -> bool:
    """Whether the Pallas kernel can take this problem (else XLA)."""
    if dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return False
    b, sq, h, d = q_shape
    if d > 256 or h % num_kv_heads:
        return False
    if sk is None:
        sk = sq
    return _pick_block(sq) is not None and _pick_block(sk) is not None


def kernel_active(q_shape, num_kv_heads, dtype) -> bool:
    """Would `splash_attention` run the compiled kernel here and now?
    (Flag + geometry + on-TPU; the bench records this per config.)"""
    from ...utils import flags as _flags

    if not _flags.get_flag("FLAGS_splash_attn"):
        return False
    return (supports(tuple(q_shape), num_kv_heads, dtype)
            and routing.on_tpu())


# ---------------------------------------------------------------------------
# XLA fallback: dense masked attention, identical mask semantics
# ---------------------------------------------------------------------------

def splash_attention_xla(q, k, v, causal=True, segment_ids=None,
                         scale=None, selection=None):
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
    m5 = mask[:, None, None]                          # [b, 1, 1, sq, sk]
    any_valid = jnp.any(m5, axis=-1, keepdims=True)
    s = jnp.where(m5, s, -jnp.inf)
    s = jnp.where(any_valid, s, 0.0)    # empty rows: keep AD finite
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(any_valid, p, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel helpers
# ---------------------------------------------------------------------------

def _seg_mask(s, segq_ref, segk_ref, block_k):
    """Apply the segment mask to a score tile. segq tile: [bq, LANES]
    lane-replicated; segk tile: [SUB, bk] sublane-replicated."""
    qseg = segq_ref[0]                                   # [bq, LANES]
    kseg = segk_ref[0][:1]                               # [1, bk]
    reps = block_k // _LANES
    qfull = qseg if reps == 1 else pltpu.repeat(qseg, reps, axis=1)
    return jnp.where(qfull[:, :block_k] == kseg, s, -jnp.inf)


def _sel_mask(s, sel_ref):
    """Keep the scores of the selected keys: sel tile int8 [bq, bk]."""
    return jnp.where(sel_ref[0].astype(jnp.int32) != 0, s, -jnp.inf)


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


# ---------------------------------------------------------------------------
# forward: online softmax over kv tiles, grid (b*kvh, qi, ki)
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, block_q, block_k, sq, nqs, with_seg,
                with_sel=False):
    (q_ref, k_ref, v_ref), segq_ref, segk_ref, sel_ref, rest = _split_refs(
        refs, 3, with_seg, with_sel)
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)
    pos0 = (qi % nqs) * block_q     # sequence position of the tile's row 0

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    active = (ki * block_k <= pos0 + block_q - 1) if causal else ki >= 0

    @pl.when(active)
    def _step():
        q = q_ref[0]                                     # [bq, d]
        k = k_ref[0]                                     # [bk, d]
        v = v_ref[0]
        s = _dot(q, k, ((1,), (1,))) * scale             # [bq, bk] fp32
        if causal:
            s = _causal_mask(s, pos0, ki * block_k, block_q, block_k)
        if with_seg:
            s = _seg_mask(s, segq_ref, segk_ref, block_k)
        if with_sel:
            s = _sel_mask(s, sel_ref)
        m_prev = m_ref[...]                              # [bq, LANES]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        # a tile can be FULLY masked under segments (unlike pure causal,
        # where the first visited tile always holds the diagonal), so
        # m_new may still be -inf: exp(-inf - -inf) would poison the
        # stats with nan — pin those rows' exponentials to 0 instead
        dead = m_new == -jnp.inf                         # [bq, LANES]
        corr = jnp.where(dead, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(dead[:, :1], 0.0,
                      jnp.exp(s - m_new[:, :1]))         # [bq, bk] fp32
        l_new = corr * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        m_ref[...] = m_new
        l_ref[...] = l_new
        pv = _dot(p.astype(v.dtype), v, ((1,), (0,)))    # [bq, d]
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv

    @pl.when(ki == num_k - 1)
    def _finish():
        l = l_ref[...][:, :1]                            # [bq, 1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # empty rows carry lse=+inf: backward's exp(s - lse) is then an
        # exact 0 (even for masked s=-inf), no special-casing needed
        lse_ref[0] = jnp.where(
            l_ref[...] > 0.0, m_ref[...] + jnp.log(l_ref[...]), jnp.inf)


def _specs(bh, bq, bk, d, nqs, kvh, with_seg, with_sel=False):
    """Block specs shared by forward and fused backward. q-side tiles
    (q/do/o/lse) index the [bh, grp*sq, ...] layout by grid dim 1; the
    segment planes recover (batch, seq-position) as (g // kvh,
    qi % nqs) — q tiles never straddle a head boundary."""
    spec_q = pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, _Z))
    spec_k = pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, _Z))
    spec_lse = pl.BlockSpec((1, bq, _LANES), lambda g, i, j: (g, i, _Z))
    seg = []
    if with_seg:
        seg = [
            pl.BlockSpec((1, bq, _LANES),
                         lambda g, i, j: (g // kvh, i % nqs, _Z)),
            pl.BlockSpec((1, _SUB, bk),
                         lambda g, i, j: (g // kvh, _Z, j)),
        ]
    if with_sel:
        seg.append(pl.BlockSpec((1, bq, bk),
                                lambda g, i, j: (g // kvh, i % nqs, j)))
    return spec_q, spec_k, spec_lse, seg


def _fwd(q, k, v, segq, segk, scale, causal, bq, bk, sq, kvh, with_seg,
         interpret, sel=None):
    bh, sq_all, d = q.shape
    sk = k.shape[1]
    nqs = sq // bq
    with_sel = sel is not None
    spec_q, spec_k, spec_lse, seg_specs = _specs(
        bh, bq, bk, d, nqs, kvh, with_seg, with_sel)
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        sq=sq, nqs=nqs, with_seg=with_seg, with_sel=with_sel)
    args = ([q, k, v] + ([segq, segk] if with_seg else [])
            + ([sel] if with_sel else []))
    out, lse = routing.pallas_call(
        kern,
        name="splash_fwd",
        grid=(bh, sq_all // bq, sk // bk),
        in_specs=[spec_q, spec_k, spec_k] + seg_specs,
        out_specs=[spec_q, spec_lse],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_all, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq_all, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
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

def _bwd_kernel(*refs, scale, causal, block_q, block_k, sq, nqs, with_seg,
                qi_base, with_sel=False):
    lead, segq_ref, segk_ref, sel_ref, rest = _split_refs(
        refs, 6, with_seg, with_sel)
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref = lead
    (dki_ref, dvi_ref, dq_ref, dk_ref, dv_ref, dq_acc, delta_ref) = rest
    qi = qi_base + pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)
    pos0 = (qi % nqs) * block_q

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        delta_ref[...] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1, keepdims=True), delta_ref.shape)

    active = (ki * block_k <= pos0 + block_q - 1) if causal else ki >= 0

    # pass the accumulators through unconditionally (skipped causal
    # blocks must still round-trip their current value)
    dk_ref[0] = dki_ref[0]
    dv_ref[0] = dvi_ref[0]

    @pl.when(active)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                          # [bq, 1]
        delta = delta_ref[...][:, :1]
        s = _dot(q, k, ((1,), (1,))) * scale             # [bq, bk] fp32
        if causal:
            s = _causal_mask(s, pos0, ki * block_k, block_q, block_k)
        if with_seg:
            s = _seg_mask(s, segq_ref, segk_ref, block_k)
        if with_sel:
            s = _sel_mask(s, sel_ref)
        # lse=+inf on empty rows makes every p an exact 0 (s - lse is
        # -inf even where s itself is -inf) — zero grads fall out free
        p = jnp.exp(s - lse)                             # [bq, bk]
        pc = p.astype(do.dtype)
        dv_ref[0] += _dot(pc, do, ((0,), (0,)))          # [bk, d]
        dp = _dot(do, v, ((1,), (1,)))                   # [bq, bk] fp32
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_ref[0] += _dot(ds, q, ((0,), (0,)))           # [bk, d]
        dq_acc[...] += _dot(ds, k, ((1,), (0,)))         # [bq, d]

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_call(q, k, v, do, out, lse, segq, segk, dk_acc, dv_acc, scale,
              causal, bq, bk, sq, kvh, with_seg, num_q, qi_base,
              interpret, sel=None):
    bh, _, d = q.shape
    sk = k.shape[1]
    nqs = sq // bq
    # q-side operands arrive pre-sliced to the processed rows (the
    # rowloop passes one q-row per call), so q-side specs index from 0
    # (the rowloop's single segment block hits index 0 either way);
    # qi_base only offsets the causal/segment positions in the kernel.
    with_sel = sel is not None
    spec_q, spec_k, spec_lse, seg_specs = _specs(
        bh, bq, bk, d, nqs, kvh, with_seg, with_sel)
    kern = functools.partial(
        _bwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        sq=sq, nqs=nqs, with_seg=with_seg, qi_base=qi_base,
        with_sel=with_sel)
    n_in = 6 + (2 if with_seg else 0) + (1 if with_sel else 0)
    args = ([q, k, v, do, out, lse]
            + ([segq, segk] if with_seg else [])
            + ([sel] if with_sel else []) + [dk_acc, dv_acc])
    return routing.pallas_call(
        kern,
        name="splash_bwd",
        grid=(bh, num_q, sk // bk),
        in_specs=[spec_q, spec_k, spec_k, spec_q, spec_q, spec_lse]
        + seg_specs + [spec_k, spec_k],
        out_specs=[spec_q, spec_k, spec_k],
        out_shape=[
            jax.ShapeDtypeStruct((bh, num_q * bq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        # dk/dv accumulators alias their inputs (last two -> outs 1, 2)
        input_output_aliases={n_in: 1, n_in + 1: 2},
        interpret=interpret,
    )(*args)


def _bwd_rowloop(q, k, v, do, out, lse, segq, segk, dk_acc, dv_acc, scale,
                 causal, bq, bk, sq, kvh, with_seg, num_q, interpret,
                 sel=None):
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
            1, qi, interpret, sel=sel_rows)
        dq_rows.append(dq_row)
    return jnp.concatenate(dq_rows, axis=1), dk_acc, dv_acc


_alias_checked: set = set()


def _alias_selfcheck(dtype, d, scale, causal, bq, bk, sk):
    """One-time (per config, per process) on-device check of the fused
    full-grid backward against the hazard-free per-row path — the
    flash_attention.py guard applied to the splash kernels, so a Mosaic
    pipeline-ordering change that breaks the aliased dK/dV revisit
    fails loudly instead of training on wrong gradients."""
    from ...utils import flags as _flags

    key = (str(dtype), d, causal, bq, bk, sk)
    if key in _alias_checked or not _flags.get_flag(
            "FLAGS_pallas_alias_selfcheck"):
        return
    sq = 2 * bq   # >= 2 q rows so every kv block is revisited

    def _run():
        rng = np.random.default_rng(0)
        mk = lambda s: jnp.asarray(  # noqa: E731
            rng.standard_normal((1, s, d)) * 0.5, dtype)
        q, do = mk(sq), mk(sq)
        k, v = mk(sk), mk(sk)
        out, lse = _fwd(q, k, v, None, None, scale, causal, bq, bk, sq,
                        1, False, False)
        z = lambda: jnp.zeros((1, sk, d), jnp.float32)  # noqa: E731
        f = _bwd_call(q, k, v, do, out, lse, None, None, z(), z(),
                      scale, causal, bq, bk, sq, 1, False,
                      sq // bq, 0, False)
        r = _bwd_rowloop(q, k, v, do, out, lse, None, None, z(), z(),
                         scale, causal, bq, bk, sq, 1, False,
                         sq // bq, False)
        return {n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                         - b.astype(jnp.float32))))
                for n, a, b in zip(("dq", "dk", "dv"), f, r)}

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
         kvh, with_seg, interpret, sel=None):
    bh, sq_all, d = q.shape
    sk = k.shape[1]
    num_q = sq_all // bq
    dk_acc = jnp.zeros((bh, sk, d), jnp.float32)
    dv_acc = jnp.zeros((bh, sk, d), jnp.float32)
    if not interpret and num_q == 1:
        dq, dk_acc, dv_acc = _bwd_call(
            q, k, v, do, out, lse, segq, segk, dk_acc, dv_acc, scale,
            causal, bq, bk, sq, kvh, with_seg, num_q, 0, interpret,
            sel=sel)
        return dq, dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)
    # shrink the backward k-block until the aliased-revisit distance is
    # safe (the forward keeps its own block_k: no aliased accumulators)
    bkb = bk
    while sk // bkb < _REVISIT_MIN and bkb % 2 == 0 \
            and (bkb // 2) % _LANES == 0 and sk % (bkb // 2) == 0:
        bkb //= 2
    if not interpret and sk // bkb >= _REVISIT_MIN:
        _alias_selfcheck(q.dtype, d, scale, causal, bq, bkb, sk)
        dq, dk_acc, dv_acc = _bwd_call(
            q, k, v, do, out, lse, segq, segk, dk_acc, dv_acc, scale,
            causal, bq, bkb, sq, kvh, with_seg, num_q, 0, interpret,
            sel=sel)
    else:
        dq, dk_acc, dv_acc = _bwd_rowloop(
            q, k, v, do, out, lse, segq, segk, dk_acc, dv_acc, scale,
            causal, bq, bk, sq, kvh, with_seg, num_q, interpret, sel=sel)
    return dq, dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrapper + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11,
                                                    12, 13))
def _splash(q, k, v, segq, segk, sel, scale, causal, bq, bk, sq, kvh,
            with_seg, interpret):
    out, _ = _fwd(q, k, v, segq, segk, scale, causal, bq, bk, sq, kvh,
                  with_seg, interpret, sel=sel)
    return out


def _splash_fwd(q, k, v, segq, segk, sel, scale, causal, bq, bk, sq, kvh,
                with_seg, interpret):
    out, lse = _fwd(q, k, v, segq, segk, scale, causal, bq, bk, sq, kvh,
                    with_seg, interpret, sel=sel)
    return out, (q, k, v, segq, segk, sel, out, lse)


def _splash_bwd(scale, causal, bq, bk, sq, kvh, with_seg, interpret,
                res, do):
    q, k, v, segq, segk, sel, out, lse = res
    dq, dk, dv = _bwd(q, k, v, out, lse, do, segq, segk, scale, causal,
                      bq, bk, sq, kvh, with_seg, interpret, sel=sel)

    def no_grad(ints):
        return (None if ints is None
                else np.zeros(ints.shape, dtype=jax.dtypes.float0))

    return dq, dk, dv, no_grad(segq), no_grad(segk), no_grad(sel)


_splash.defvjp(_splash_fwd, _splash_bwd)


def splash_attention(q, k, v, causal=True, segment_ids=None, scale=None,
                     block_q=None, block_k=None, interpret=None,
                     use_kernel=None, selection=None):
    """Splash training attention (see module docstring for layouts).
    `selection`, int8 [batch, sq, sk], keeps for every head of a query
    only the keys where it is non-zero (and causal, when `causal`).

    Routes to the Pallas kernel on TPU when the geometry qualifies
    (`supports`), the XLA dense fallback otherwise. `interpret=True`
    forces the kernel in interpret mode (hermetic CPU testing);
    `use_kernel` overrides the routing outright. Differentiable
    (custom tiled backward) in q/k/v."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    if causal and sq != sk:
        raise ValueError("causal splash attention needs equal seq lens")
    if h % kvh:
        raise ValueError(f"num_heads {h} not a multiple of kv heads {kvh}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    use_kernel, interpret = routing.route(
        "splash_attention", supports((b, sq, h, d), kvh, q.dtype, sk=sk),
        (f"q{(b, sq, h, d)}", f"kv_heads={kvh}", f"sk={sk}", str(q.dtype)),
        interpret, use_kernel)
    if not use_kernel:
        return splash_attention_xla(q, k, v, causal=causal,
                                    segment_ids=segment_ids, scale=scale,
                                    selection=selection)
    grp = h // kvh
    if block_q is None:
        block_q = _pick_block(sq)
    if block_k is None:
        block_k = _pick_block(sk)

    # fold the group dim into the q-row axis: kv head kh serves q rows
    # [kh*grp*sq, (kh+1)*grp*sq) — q head index = row // sq within them
    q2 = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * kvh, grp * sq, d)
    k2 = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * kvh, sk, d)
    v2 = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * kvh, sk, d)
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
                   with_seg, bool(interpret))
    return jnp.transpose(out2.reshape(b, kvh * grp, sq, d), (0, 2, 1, 3))
