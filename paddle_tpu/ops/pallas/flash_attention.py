"""Flash attention — Pallas TPU kernels, forward + backward.

Reference parity: the CUDA flash-attn kernel the reference dispatches to
(paddle/phi/kernels/gpu/flash_attn_kernel.cu, declared in
paddle/phi/kernels/flash_attn_kernel.h). TPU-first design, two paths:

* **Single-block path** (seq <= 1024): the whole row of scores fits one
  VMEM tile, so forward is an exact (non-online) softmax fused into one
  grid step per batch*head, and backward is one fused step that
  recomputes the softmax in-register — no LSE or delta tensors ever
  touch HBM. This is the training hot path (seq 1024-class models).
* **Tiled path** (longer seq): online-softmax forward with LSE
  residuals, and a *single-pass* fused backward: one sweep of the
  (q-block, k-block) grid computes dQ (fp32 scratch, resident per
  q-row), dK/dV (fp32 HBM accumulators via input_output_aliases), and
  delta (in-kernel from dO·O) — where the classic FA2 decomposition
  runs two sweeps and recomputes the score / dO·V^T matmuls (the
  MXU-unfriendly d=64 contractions) twice.

The TPU pipeline semantics these rely on were validated empirically:
output blocks with a constant index stay resident in VMEM and can be
read back for accumulation (both compiled and interpret mode), while
revisited aliased blocks round-trip through HBM correctly only in
compiled mode — so in interpret mode (CPU tests) the tiled backward
runs the same kernel body in a per-q-row loop, threading the dK/dV
accumulators through as aliased call inputs (each block visited once
per call, which interpret mode handles).

Internal layout is [batch*heads, seq, head_dim]; the public entry takes
the reference's [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401 (re-exported)

from . import routing

_LANES = 128  # VPU lane count: row stats are kept lane-replicated in VMEM
_Z = np.int32(0)  # index-map zero
_SINGLE_BLOCK_MAX = 1024  # whole-row tile above this busts VMEM (fp32 s)


def supports(q_shape, dtype, causal) -> bool:
    """Whether the kernel can take this problem (else callers use XLA)."""
    if dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return False
    b, s, h, d = q_shape
    if d > 256:
        return False
    if s <= _SINGLE_BLOCK_MAX:
        return s % 16 == 0  # Mosaic pads sublane/lane tiles from 16
    return _pick_block(s) is not None


def _pick_block(seq: int):
    # Measured on v5e (seq 4096, bf16, d=64, fwd+bwd): 1024-blocks run
    # ~1.7x faster than 512 (fewer grid steps, better MXU occupancy);
    # 2048 gains only ~5% more while quadrupling the fp32 score tile's
    # VMEM, so 1024 is the default ceiling.
    for blk in (1024, 512, 256, 128):
        if seq % blk == 0:
            return blk
    return None


def _dot(a, b, contract):
    """dot_general with fp32 accumulation; HIGHEST precision only for f32
    operands. Mosaic rejects contract_precision<fp32> on bf16 vectors, and
    the framework sets jax_default_matmul_precision="float32" globally, so
    bf16 dots must pass an explicit DEFAULT to override that config."""
    prec = (jax.lax.Precision.HIGHEST
            if a.dtype == jnp.float32 and b.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=prec)


def _causal_mask(s, row0, col0, bq, bk):
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(row >= col, s, -jnp.inf)


# ---------------------------------------------------------------------------
# single-block path: whole sequence in one tile, grid (bh,)
# ---------------------------------------------------------------------------

def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal):
    q = q_ref[0]                                         # [sq, d]
    k = k_ref[0]
    v = v_ref[0]
    s = _dot(q, k, ((1,), (1,))) * scale                 # [sq, sk] fp32
    if causal:
        s = _causal_mask(s, 0, 0, q.shape[0], k.shape[0])
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = _dot((p / l).astype(v.dtype), v, ((1,), (0,)))   # [sq, d]
    o_ref[0] = o.astype(o_ref.dtype)


def _bwd_single_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
                       *, scale, causal):
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    s = _dot(q, k, ((1,), (1,))) * scale                 # [sq, sk] fp32
    if causal:
        s = _causal_mask(s, 0, 0, q.shape[0], k.shape[0])
    m = jnp.max(s, axis=1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=1, keepdims=True)            # exact softmax
    pc = p.astype(do.dtype)
    dv = _dot(pc, do, ((0,), (0,)))                      # [sk, d]
    dp = _dot(do, v, ((1,), (1,)))                       # [sq, sk] fp32
    delta = jnp.sum(p * dp, axis=1, keepdims=True)       # = rowsum(do*o)
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    dq = _dot(ds, k, ((1,), (0,)))                       # [sq, d]
    dk = _dot(ds, q, ((0,), (0,)))                       # [sk, d]
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _fwd_single(q, k, v, scale, causal, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    spec_q = pl.BlockSpec((1, sq, d), lambda b: (b, _Z, _Z))
    spec_k = pl.BlockSpec((1, sk, d), lambda b: (b, _Z, _Z))
    return routing.pallas_call(
        functools.partial(_fwd_single_kernel, scale=scale, causal=causal),
        name="flash_fwd_single",
        grid=(bh,),
        in_specs=[spec_q, spec_k, spec_k],
        out_specs=spec_q,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
    )(q, k, v)


def _bwd_single(q, k, v, do, scale, causal, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    spec_q = pl.BlockSpec((1, sq, d), lambda b: (b, _Z, _Z))
    spec_k = pl.BlockSpec((1, sk, d), lambda b: (b, _Z, _Z))
    return routing.pallas_call(
        functools.partial(_bwd_single_kernel, scale=scale, causal=causal),
        name="flash_bwd_single",
        grid=(bh,),
        in_specs=[spec_q, spec_k, spec_k, spec_q],
        out_specs=[spec_q, spec_k, spec_k],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do)


# ---------------------------------------------------------------------------
# tiled path: online-softmax forward (grid bh x qi x ki)
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip blocks strictly above the diagonal band
    active = (ki * block_k <= qi * block_q + block_q - 1) if causal else ki >= 0

    @pl.when(active)
    def _step():
        q = q_ref[0]                                     # [bq, d]
        k = k_ref[0]                                     # [bk, d]
        v = v_ref[0]
        s = _dot(q, k, ((1,), (1,))) * scale   # [bq, bk]
        if causal:
            s = _causal_mask(s, qi * block_q, ki * block_k, block_q, block_k)
        m_prev = m_ref[...]                              # [bq, LANES]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        corr = jnp.exp(m_prev - m_new)                   # [bq, LANES]
        p = jnp.exp(s - m_new[:, :1])                    # [bq, bk] fp32
        l_new = corr * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        m_ref[...] = m_new
        l_ref[...] = l_new
        pv = _dot(p.astype(v.dtype), v, ((1,), (0,)))          # [bq, d]
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv

    @pl.when(ki == num_k - 1)
    def _finish():
        l = l_ref[...][:, :1]                            # [bq, 1]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # lse layout [bh, sq, LANES], lane-replicated like the scratch
        # stats (Mosaic wants full-lane tiles; jax's own flash kernel does
        # the same with MIN_BLOCK_SIZE=128)
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])


def _fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    grid = (bh, sq // block_q, sk // block_k)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k)
    out, lse = routing.pallas_call(
        kern,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, _Z)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, _Z)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, _Z)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, _Z)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, _Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# tiled path: fused single-pass backward (grid bh x qi x ki)
#
# dQ accumulates in fp32 scratch (its block index is constant over the
# inner ki sweep, so the scratch is flushed once per q-row). dK/dV
# accumulate in fp32 HBM buffers passed as aliased inputs — their blocks
# are revisited once per outer qi step, a full sweep apart, which the
# compiled pipeline handles (write-back completes long before the next
# visit's prefetch). delta (= rowsum(dO*O)) is computed in-kernel at
# ki == 0, so no [bh, sq, LANES] delta tensor is ever materialized.
# ---------------------------------------------------------------------------

def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      dki_ref, dvi_ref, dq_ref, dk_ref, dv_ref,
                      dq_acc, delta_ref,
                      *, scale, causal, block_q, block_k, qi_base):
    qi = qi_base + pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        delta_ref[...] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1, keepdims=True), delta_ref.shape)

    active = (ki * block_k <= qi * block_q + block_q - 1) if causal else ki >= 0

    # pass the accumulators through unconditionally (skipped causal blocks
    # must still round-trip their current value)
    dk_ref[0] = dki_ref[0]
    dv_ref[0] = dvi_ref[0]

    @pl.when(active)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                          # [bq, 1]
        delta = delta_ref[...][:, :1]                    # [bq, 1]
        s = _dot(q, k, ((1,), (1,))) * scale             # [bq, bk] fp32
        if causal:
            s = _causal_mask(s, qi * block_q, ki * block_k, block_q, block_k)
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


def _bwd_fused_call(q, k, v, do, out, lse, dk_acc, dv_acc, scale, causal,
                    block_q, block_k, num_q, qi_base, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    # q/do/out/lse arrive pre-sliced to the processed rows (the interpret
    # loop passes one q-row per call), so their specs always index from 0;
    # qi_base only offsets the causal mask inside the kernel.
    spec_q = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, _Z))
    spec_k = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, _Z))
    spec_lse = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, _Z))
    kern = functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k,
                             qi_base=qi_base)
    return routing.pallas_call(
        kern,
        name="flash_bwd",
        grid=(bh, num_q, sk // block_k),
        in_specs=[spec_q, spec_k, spec_k, spec_q, spec_q, spec_lse,
                  spec_k, spec_k],
        out_specs=[spec_q, spec_k, spec_k],
        out_shape=[
            jax.ShapeDtypeStruct((bh, num_q * block_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        # dk/dv accumulators alias their inputs (positions 6, 7 -> 1, 2)
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
    )(q, k, v, do, out, lse, dk_acc, dv_acc)


# The aliased dK/dV round-trip (write-back → HBM → re-prefetch) is only
# trusted when consecutive visits of a kv block are at least this many
# grid steps apart (one full ki sweep = sk // block_k steps). Below it
# the write-back and the next visit's prefetch share a step window, and
# correctness would hinge on undocumented Mosaic pipeline ordering.
_REVISIT_MIN = 4
_alias_checked: set = set()


def _bwd_rowloop(q, k, v, do, out, lse, dk_acc, dv_acc, scale, causal,
                 block_q, block_k, num_q, interpret):
    """Hazard-free tiled backward: one q-row per pallas call, threading the
    dk/dv accumulators through as aliased call inputs — each aliased block
    is visited exactly once per call, so no revisit ordering is relied on.
    Used by interpret mode (which replays revisited aliased blocks from
    the original input) and as the compiled fallback when the fused
    grid's revisit distance would be < _REVISIT_MIN."""
    dq_rows = []
    for qi in range(num_q):
        row = jax.lax.dynamic_slice_in_dim(q, qi * block_q, block_q, 1)
        do_row = jax.lax.dynamic_slice_in_dim(do, qi * block_q, block_q, 1)
        out_row = jax.lax.dynamic_slice_in_dim(out, qi * block_q, block_q, 1)
        lse_row = jax.lax.dynamic_slice_in_dim(lse, qi * block_q, block_q, 1)
        dq_row, dk_acc, dv_acc = _bwd_fused_call(
            row, k, v, do_row, out_row, lse_row, dk_acc, dv_acc,
            scale, causal, block_q, block_k, 1, qi, interpret)
        dq_rows.append(dq_row)
    return jnp.concatenate(dq_rows, axis=1), dk_acc, dv_acc


def _alias_selfcheck(dtype, d, scale, causal, block_q, block_k, sk):
    """One-time (per config, per process) on-device check of the fused
    full-grid backward against the hazard-free per-row path, so a future
    Mosaic scheduling change that breaks the aliased-accumulator
    round-trip fails loudly instead of training on wrong gradients.
    Runs eagerly (concrete inputs) even when called from inside a trace."""
    from ...utils import flags as _flags

    key = (str(dtype), d, causal, block_q, block_k, sk)
    if key in _alias_checked or not _flags.get_flag(
            "FLAGS_pallas_alias_selfcheck"):
        return
    sq = 2 * block_q  # >= 2 q-rows so every kv block is revisited

    # _bwd is typically being traced inside a jit backward when this runs;
    # the check must execute eagerly, so run it in a fresh thread (trace
    # contexts are thread-local — a new thread has none active).
    def _run():
        rng = np.random.default_rng(0)
        mk = lambda s: jnp.asarray(  # noqa: E731
            rng.standard_normal((1, s, d)) * 0.5, dtype)
        q, do = mk(sq), mk(sq)
        k, v = mk(sk), mk(sk)
        out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, False)
        z = lambda: jnp.zeros((1, sk, d), jnp.float32)  # noqa: E731
        dq_f, dk_f, dv_f = _bwd_fused_call(
            q, k, v, do, out, lse, z(), z(), scale, causal, block_q,
            block_k, sq // block_q, 0, False)
        dq_r, dk_r, dv_r = _bwd_rowloop(
            q, k, v, do, out, lse, z(), z(), scale, causal, block_q,
            block_k, sq // block_q, False)
        return {name: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                            - b.astype(jnp.float32))))
                for name, a, b in (("dq", dq_f, dq_r), ("dk", dk_f, dk_r),
                                   ("dv", dv_f, dv_r))}

    import concurrent.futures
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        errs = pool.submit(_run).result()
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, err in errs.items():
        if not err < tol:
            raise RuntimeError(
                f"pallas flash backward self-check FAILED ({name} max err "
                f"{err:.3e}, tol {tol:.0e}, config {key}): the aliased "
                "dK/dV accumulator round-trip no longer matches the "
                "hazard-free path — a Mosaic pipeline-ordering change "
                "likely broke input_output_aliases revisits. Set "
                "FLAGS_pallas_flash_min_seqlen high to route attention "
                "to XLA, and report this.")
    _alias_checked.add(key)  # only memoize a PASSING check


def _bwd(q, k, v, out, lse, do, scale, causal, block_q, block_k, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    num_q = sq // block_q
    dk_acc = jnp.zeros((bh, sk, d), jnp.float32)
    dv_acc = jnp.zeros((bh, sk, d), jnp.float32)
    # with a single q-row every kv block is visited exactly once — no
    # revisit, no hazard, keep the full fused grid untouched
    if not interpret and num_q == 1:
        dq, dk_acc, dv_acc = _bwd_fused_call(
            q, k, v, do, out, lse, dk_acc, dv_acc, scale, causal,
            block_q, block_k, num_q, 0, interpret)
        return dq, dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)
    # shrink the backward's k-block until the revisit distance is safe
    # (the forward keeps its own block_k: it has no aliased accumulators)
    bk = block_k
    while sk // bk < _REVISIT_MIN and bk % 2 == 0 and (bk // 2) % 128 == 0 \
            and sk % (bk // 2) == 0:
        bk //= 2
    if not interpret and sk // bk >= _REVISIT_MIN:
        _alias_selfcheck(q.dtype, d, scale, causal, block_q, bk, sk)
        dq, dk_acc, dv_acc = _bwd_fused_call(
            q, k, v, do, out, lse, dk_acc, dv_acc, scale, causal,
            block_q, bk, num_q, 0, interpret)
    else:
        dq, dk_acc, dv_acc = _bwd_rowloop(
            q, k, v, do, out, lse, dk_acc, dv_acc, scale, causal,
            block_q, block_k, num_q, interpret)
    return dq, dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrappers + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _bwd(q, k, v, out, lse, do, scale, causal, block_q,
                      block_k, interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_single(q, k, v, scale, causal, interpret):
    return _fwd_single(q, k, v, scale, causal, interpret)


def _flash_single_fwd(q, k, v, scale, causal, interpret):
    return _fwd_single(q, k, v, scale, causal, interpret), (q, k, v)


def _flash_single_bwd(scale, causal, interpret, res, do):
    q, k, v = res
    return _bwd_single(q, k, v, do, scale, causal, interpret)


_flash_single.defvjp(_flash_single_fwd, _flash_single_bwd)


def flash_attention(q, k, v, causal=True, scale=None, block_q=None,
                    block_k=None, interpret=None):
    """q/k/v: [batch, seq, heads, head_dim] (reference layout). Returns the
    attention output in the same layout. Differentiable (custom flash
    backward). Requires seq % block == 0 (see `supports`)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if causal and sq != sk:
        raise ValueError("causal flash attention needs equal q/k seq lens")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = routing.force_interpret()

    def to_bh(x, s):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, x.shape[-1])

    qb, kb, vb = to_bh(q, sq), to_bh(k, sk), to_bh(v, sk)

    single = (sq <= _SINGLE_BLOCK_MAX and sk <= _SINGLE_BLOCK_MAX
              and sq % 16 == 0 and sk % 16 == 0
              and block_q is None and block_k is None)
    if single:
        ob = _flash_single(qb, kb, vb, float(scale), bool(causal),
                           bool(interpret))
    else:
        if block_q is None:
            block_q = _pick_block(sq)
        if block_k is None:
            block_k = _pick_block(sk)
        if block_q is None or block_k is None:
            raise ValueError(
                f"unsupported seq lens ({sq}, {sk}) for flash blocks")
        ob = _flash(qb, kb, vb, float(scale), bool(causal), int(block_q),
                    int(block_k), bool(interpret))
    return jnp.transpose(ob.reshape(b, h, sq, d), (0, 2, 1, 3))
