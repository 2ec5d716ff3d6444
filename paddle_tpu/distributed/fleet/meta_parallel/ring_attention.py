"""Ring attention — exact long-context attention over a sequence-parallel
mesh axis.

Beyond-reference (SURVEY.md §5.7): the reference snapshot has only SEP
data-style sequence sharding (segment_parallel.py:26) and Megatron-SP; it
has NO ring/blockwise context parallelism. Here each device holds one
sequence block of q/k/v; k/v blocks rotate around the ring via
`ppermute` while an online-softmax accumulator (flash-attention math)
folds in one block per tick — memory O(seq/n) per device, comms riding
the ICI ring, and compute/transfer overlapped by XLA. The backward is the
reverse ring, derived by jax AD through the scan + ppermute (no
hand-written p2p bookkeeping).

Layout contract: q/k/v are [batch, seq, heads, head_dim] global arrays
sharded P(None, axis) on the sequence dim (SegmentParallel's layout).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

_NEG_INF = -1e30  # finite mask value: keeps exp/where AD clean vs real -inf


import functools


@functools.partial(jax.checkpoint, static_argnums=(5, 6))
def _block_attend(q, k, v, row0, col0, scale, causal):
    """One q-block × kv-block flash step.

    q: [b, sq, h, d], k/v: [b, sk, h, d]; row0/col0: global offsets of the
    blocks on the sequence axis. Returns (scores_max m [b,h,sq], partial
    numerator acc [b,sq,h,d], partial denominator l [b,h,sq]).

    Rematerialized: without the checkpoint, AD through the ring scan saves
    every tick's [b,h,blk,blk] score/prob residuals — O(seq^2/n) per
    device, the exact blow-up ring attention exists to avoid. Remat keeps
    backward memory at one block and recomputes scores in the reverse
    ring (flash-attention-style compute/memory trade).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [b,h,q]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: m == NEG_INF -> p would be exp(0)=1; zero them
    alive = (m > _NEG_INF / 2)[..., None]
    p = jnp.where(alive, p, 0.0)
    # score/prob HBM residency in the input precision (the r2 bf16-score
    # lever, FLAGS_attention_fp32_scores restores fp32) — accumulation
    # and softmax stats stay fp32
    from ....utils import flags as _flags

    if (q.dtype in (jnp.bfloat16, jnp.float16)
            and not _flags.get_flag("FLAGS_attention_fp32_scores")):
        p = p.astype(q.dtype)
    l = jnp.sum(p.astype(jnp.float32), axis=-1)               # [b,h,q]
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype),
                     preferred_element_type=jnp.float32)
    return m, acc, l


def ring_attention(q, k, v, *, mesh, axis="sep", causal=True, scale=None):
    """Exact attention with q/k/v sequence-sharded over `axis`.

    Returns [batch, seq, heads, head_dim] with the same sharding as q.
    Differentiable (AD reverses the ring). Requires seq % mesh.shape[axis]
    == 0.
    """
    b, s, h, d = q.shape
    n = int(mesh.shape[axis])
    if s % n:
        raise ValueError(f"ring size {n} must divide seq {s}")
    blk = s // n
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(qb, kb, vb):
        # local blocks [b, blk, h, d]; manual over `axis` only
        idx = jax.lax.axis_index(axis)
        row0 = idx * blk

        m0 = jnp.full((b, h, blk), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, blk), jnp.float32)
        a0 = jnp.zeros((b, blk, h, d), jnp.float32)

        def tick(carry, t):
            m_run, l_run, acc_run, kv = carry
            kt, vt = kv
            src = (idx - t) % n             # whose block we hold this tick
            m_b, acc_b, l_b = _block_attend(qb, kt, vt, row0, src * blk,
                                            scale, causal)
            m_new = jnp.maximum(m_run, m_b)
            c_run = jnp.exp(m_run - m_new)      # [b,h,q]
            c_b = jnp.exp(m_b - m_new)
            l_new = l_run * c_run + l_b * c_b
            acc_new = (acc_run * jnp.transpose(c_run, (0, 2, 1))[..., None]
                       + acc_b * jnp.transpose(c_b, (0, 2, 1))[..., None])
            kv = jax.lax.ppermute((kt, vt), axis, perm)
            return (m_new, l_new, acc_new, kv), None

        (m_f, l_f, acc_f, _), _ = jax.lax.scan(
            tick, (m0, l0, a0, (kb, vb)), jnp.arange(n))
        l_safe = jnp.maximum(l_f, 1e-30)
        out = acc_f / jnp.transpose(l_safe, (0, 2, 1))[..., None]
        return out.astype(qb.dtype)

    spec = P(None, axis, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=frozenset({axis}),
        check_vma=False,
    )(q, k, v)


def sep_sharding(mesh, axis="sep"):
    """The NamedSharding ring_attention expects on q/k/v."""
    return NamedSharding(mesh, P(None, axis, None, None))


# ---------------------------------------------------------------------------
# flash-ring attention: the pallas flash kernels INSIDE the ring
#
# The plain ring above computes each tick's block attention as an XLA
# einsum — an O(blk^2) score tile in HBM per tick. Here each tick runs
# the pallas tiled flash kernel (ops/pallas/flash_attention._fwd), so
# per-device memory is O(blk*d) at every point, and the backward is a
# HAND-WRITTEN reverse ring (custom_vjp): dk/dv accumulators rotate with
# their kv blocks (n ticks = back home) and each tick runs the fused
# single-pass pallas backward with the GLOBAL lse/delta — the ring
# generalization of flash-attention-2, with jax AD nowhere on the
# O(seq^2) path.
# ---------------------------------------------------------------------------


def _flash_ring_local(axis, n, blk, scale, causal, interpret):
    """Build the per-shard (q,k,v)->out function with a custom ring VJP.
    Layout inside: kernel-native [b*h, blk, d]."""
    from ....ops.pallas import flash_attention as fa

    perm = [(i, (i + 1) % n) for i in range(n)]
    bq = fa._pick_block(blk)

    def fwd_pass(qb, kb, vb):
        idx = jax.lax.axis_index(axis)
        bh, _, d = qb.shape
        neg = jnp.float32(_NEG_INF)

        def attend(mode, kt, vt):
            # mode 0: diagonal (causal within block), 1: full, 2: skip
            def diag(_):
                return fa._fwd(qb, kt, vt, scale, True, bq, bq, interpret)

            def full(_):
                return fa._fwd(qb, kt, vt, scale, False, bq, bq, interpret)

            def skip(_):
                return (jnp.zeros((bh, blk, d), qb.dtype),
                        jnp.full((bh, blk, fa._LANES), neg, jnp.float32))

            return jax.lax.switch(mode, [diag, full, skip], None)

        def tick(carry, t):
            out_run, lse_run, kv = carry
            kt, vt = kv
            src = (idx - t) % n
            if causal:
                mode = jnp.where(src == idx, 0,
                                 jnp.where(src < idx, 1, 2))
            else:
                mode = jnp.ones((), jnp.int32)
            out_b, lse_b = attend(mode, kt, vt)
            l1 = lse_run[:, :, :1]
            l2 = lse_b[:, :, :1]
            lse_new = jnp.logaddexp(l1, l2)
            w1 = jnp.exp(l1 - lse_new)
            w2 = jnp.exp(l2 - lse_new)
            # out_run stays fp32 across the whole scan: casting back to the
            # input dtype every tick would accumulate O(n) rounding error
            # in the rescale-and-add merge instead of rounding once at end
            out_new = out_run * w1 + out_b.astype(jnp.float32) * w2
            kv = jax.lax.ppermute((kt, vt), axis, perm)
            lse_full = jnp.broadcast_to(lse_new, lse_run.shape)
            return (out_new, lse_full, kv), None

        out0 = jnp.zeros(qb.shape, jnp.float32)
        lse0 = jnp.full((bh, blk, fa._LANES), neg, jnp.float32)
        (out, lse, _), _ = jax.lax.scan(
            tick, (out0, lse0, (kb, vb)), jnp.arange(n))
        return out.astype(qb.dtype), lse

    @jax.custom_vjp
    def ring(qb, kb, vb):
        out, _ = fwd_pass(qb, kb, vb)
        return out

    def ring_fwd(qb, kb, vb):
        out, lse = fwd_pass(qb, kb, vb)
        return out, (qb, kb, vb, out, lse)

    def ring_bwd(res, do):
        qb, kb, vb, out, lse = res
        idx = jax.lax.axis_index(axis)
        bh, _, d = qb.shape

        def grads(mode, kt, vt):
            def diag(_):
                return fa._bwd(qb, kt, vt, out, lse, do, scale, True,
                               bq, bq, interpret)

            def full(_):
                return fa._bwd(qb, kt, vt, out, lse, do, scale, False,
                               bq, bq, interpret)

            def skip(_):
                return (jnp.zeros((bh, blk, d), qb.dtype),
                        jnp.zeros((bh, blk, d), kb.dtype),
                        jnp.zeros((bh, blk, d), vb.dtype))

            return jax.lax.switch(mode, [diag, full, skip], None)

        def tick(carry, t):
            dq_run, ring_state = carry
            kt, vt, dk_run, dv_run = ring_state
            src = (idx - t) % n
            if causal:
                mode = jnp.where(src == idx, 0,
                                 jnp.where(src < idx, 1, 2))
            else:
                mode = jnp.ones((), jnp.int32)
            dq_b, dk_b, dv_b = grads(mode, kt, vt)
            dq_run = dq_run + dq_b.astype(jnp.float32)
            dk_run = dk_run + dk_b.astype(jnp.float32)
            dv_run = dv_run + dv_b.astype(jnp.float32)
            ring_state = jax.lax.ppermute(
                (kt, vt, dk_run, dv_run), axis, perm)
            return (dq_run, ring_state), None

        dq0 = jnp.zeros((bh, blk, d), jnp.float32)
        dkv0 = (kb, vb, jnp.zeros((bh, blk, d), jnp.float32),
                jnp.zeros((bh, blk, d), jnp.float32))
        (dq, (_, _, dk, dv)), _ = jax.lax.scan(
            tick, (dq0, dkv0), jnp.arange(n))
        return (dq.astype(qb.dtype), dk.astype(kb.dtype),
                dv.astype(vb.dtype))

    ring.defvjp(ring_fwd, ring_bwd)
    return ring


def ring_flash_attention(q, k, v, *, mesh, axis="sep", causal=True,
                         scale=None, interpret=None):
    """Ring attention with the pallas flash kernels per tick (forward AND
    the reverse-ring backward). Same contract as `ring_attention`;
    requires the per-device block to be a multiple of 128 (kernel tiles)
    and q/k/v the same shape."""
    from ....ops.pallas import flash_attention as fa

    b, s, h, d = q.shape
    n = int(mesh.shape[axis])
    if s % n:
        raise ValueError(f"ring size {n} must divide seq {s}")
    blk = s // n
    if fa._pick_block(blk) is None:
        raise ValueError(f"flash ring needs block {blk} % 128 == 0")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        # the kernels run on the mesh's devices, so ask the mesh
        interpret = mesh.devices.flat[0].platform != "tpu"
    local_ring = _flash_ring_local(axis, n, blk, float(scale),
                                   bool(causal), bool(interpret))

    def local(qb, kb, vb):
        # [b, blk, h, d] -> kernel layout [b*h, blk, d]
        def to_bh(x):
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, blk, d)

        ob = local_ring(to_bh(qb), to_bh(kb), to_bh(vb))
        return jnp.transpose(ob.reshape(b, h, blk, d), (0, 2, 1, 3))

    spec = P(None, axis, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=frozenset({axis}),
        check_vma=False,
    )(q, k, v)
