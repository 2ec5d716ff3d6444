"""Ragged paged decode attention — Pallas TPU kernel + XLA gather fallback.

The decode-step kernel of the serving stack (PAPERS.md "Ragged Paged
Attention"): each sequence's KV history lives in fixed-size pages drawn
from a shared pool, a per-sequence page table maps logical positions to
pages, and per-sequence lengths are ragged — so a mixed batch of short
and long contexts shares one static-shape kernel with no padding to the
longest sequence's history.

Layouts (one transformer layer):

* ``k_pages`` / ``v_pages``: ``[num_kv_heads, num_pages, page_size,
  head_dim]`` — the shared pool. Page 0 is conventionally the trash
  page (ragged writes of padding tokens land there; see
  inference/kv_cache.py).
* ``page_tables``: ``[batch, pages_per_seq] int32`` — pool page ids per
  sequence slot, position ``t`` of slot ``b`` lives in page
  ``page_tables[b, t // page_size]`` at offset ``t % page_size``.
* ``seq_lens``: ``[batch] int32`` — valid keys per slot (ragged).
* ``q``: ``[batch, num_heads, head_dim]`` — ONE new token per slot (the
  decode step). GQA is supported (``num_heads`` a multiple of
  ``num_kv_heads``).

Two paths, one contract:

* **Pallas kernel** (TPU): grid ``(batch, kv_head, page)`` with the page
  table and seq_lens scalar-prefetched, so each grid step DMAs exactly
  one page of K/V picked by the table — the pool itself never streams
  densely. Pages past a slot's length are skipped (``pl.when``), which
  is where the ragged win comes from: compute per slot is proportional
  to its own context length, not the batch max.
* **XLA path** (CPU, unsupported geometry): one gather densifies each slot's
  pages to ``[batch, pages_per_seq * page_size, ...]`` followed by a
  masked attention. Same numerics, used for parity tests and
  non-TPU runs.

Two extensions since ISSUE 16:

* **int8 pools** — when ``k_scales``/``v_scales`` (``[num_kv_heads,
  num_pages, page_size]`` fp32, one symmetric scale per cached row —
  the comm stack's `quantize_symmetric_q8` format) are passed, the
  pools are int8 and dequantization fuses into the page gather: the
  kernel DMAs int8 pages + their scales and multiplies in registers;
  the XLA fallback multiplies right after the densifying gather. HBM
  for KV halves (+1/head_dim for scales), doubling page-pool capacity
  at equal memory.
* **multi-token verify / chunk attention** (``paged_attention_chunk``)
  — ``q`` is ``[batch, c, num_heads, head_dim]``: c queries per slot at
  ragged positions ``start_i + t`` attending the slot's full paged
  context (causal within the chunk). One call scores a whole
  speculative-decoding verify window (or one chunk of a long prompt —
  the serving chunk-prefill shape) instead of c decode dispatches.

And since ISSUE 20:

* **int4 pools** — uint8 pages packing TWO values per byte
  (``[..., head_dim // 2]``, nn/quant ``pack_q4`` nibble format: high
  nibble = even lane, offset-binary +8) with the same per-row fp32
  scale layout as int8. The quant mode is inferred from the pool
  dtype (``int8`` -> int8, ``uint8`` -> int4) whenever scales are
  passed; dequant fuses into the gather as a nibble unpack
  (``(v >> 4) - 8`` / ``(v & 0xF) - 8``) ahead of the scale multiply,
  in the kernels and the XLA fallbacks alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import routing
from .flash_attention import (  # noqa: F401  (shared kernel helpers)
    _LANES, _dot, pl, pltpu,
)

__all__ = ["paged_attention", "paged_attention_xla",
           "paged_attention_chunk", "paged_attention_chunk_xla",
           "supports"]


def supports(num_heads, num_kv_heads, head_dim, page_size) -> bool:
    """Whether the Pallas kernel can take this cache geometry."""
    if num_heads % num_kv_heads:
        return False
    if head_dim > 256:
        return False
    # Mosaic pads sublane/lane tiles from 8/16 upward; tiny pages would
    # waste most of each tile anyway
    return page_size % 8 == 0


# ---------------------------------------------------------------------------
# XLA gather fallback
# ---------------------------------------------------------------------------

def _quant_mode(pages, scales):
    """None / "int8" / "int4", inferred from the pool dtype (scales
    present means a quantized pool; uint8 is the packed-nibble form)."""
    if scales is None:
        return None
    return "int4" if pages.dtype == jnp.dtype(jnp.uint8) else "int8"


def _unpack_nib(p):
    """uint8 [..., d//2] -> int32 [..., d] nibble values in [-8, 7]
    (pack_q4 layout: high nibble first, offset-binary +8). Inlined here
    — the kernels run it on register-resident page blocks."""
    v = p.astype(jnp.int32)
    hi = (v >> 4) - 8
    lo = (v & 0xF) - 8
    return jnp.stack([hi, lo], axis=-1).reshape(
        *p.shape[:-1], p.shape[-1] * 2)


def _densify(pages, page_tables, scales=None):
    """Gather a [b, kvh, pp*ps, d] dense view of each slot's pages;
    quantized pools dequantize right here (fused into the gather's
    consumer — per-row fp32 scale, comm-stack symmetric format; int4
    additionally nibble-unpacks the packed payload)."""
    kvh, _, page_size, d = pages.shape
    b, pp = page_tables.shape
    g = jnp.take(pages, page_tables, axis=1)        # [kvh, b, pp, ps, d]
    g = jnp.moveaxis(g, 0, 1).reshape(b, kvh, pp * page_size, d)
    if scales is not None:
        if _quant_mode(pages, scales) == "int4":
            g = _unpack_nib(g)                      # [..., 2*d] values
        s = jnp.take(scales, page_tables, axis=1)   # [kvh, b, pp, ps]
        s = jnp.moveaxis(s, 0, 1).reshape(b, kvh, pp * page_size)
        g = g.astype(jnp.float32) * s[..., None]
    return g


def paged_attention_xla(q, k_pages, v_pages, page_tables, seq_lens,
                        scale=None, k_scales=None, v_scales=None):
    """Reference-parity path: densify via gather, mask, one attention."""
    b, nh, d = q.shape
    kvh, _, page_size, _ = k_pages.shape
    grp = nh // kvh
    pp = page_tables.shape[1]
    sc = scale if scale is not None else 1.0 / (d ** 0.5)

    k = _densify(k_pages, page_tables, k_scales)
    v = _densify(v_pages, page_tables, v_scales)
    qg = q.reshape(b, kvh, grp, d)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * sc
    valid = (jnp.arange(pp * page_size)[None, :]
             < seq_lens[:, None])                      # [b, L]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # all-masked rows (empty slots): zero output, not NaN
    p = jnp.where(valid[:, None, None, :].any(-1, keepdims=True), p, 0.0)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, nh, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: grid (batch, kv_head, page), scalar-prefetched page table
# ---------------------------------------------------------------------------

def _decode_kernel(pt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page_size):
    b = pl.program_id(0)
    p = pl.program_id(2)
    num_p = pl.num_programs(2)
    sl = sl_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p * page_size < sl)
    def _step():
        q = q_ref[0, 0]                                  # [grp, d]
        k = k_ref[0, 0]                                  # [ps, d]
        v = v_ref[0, 0]
        s = _dot(q, k, ((1,), (1,))) * scale             # [grp, ps]
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < sl, s, -jnp.inf)
        m_prev = m_ref[...]                              # [grp, LANES]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        corr = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new[:, :1])
        l_ref[...] = corr * l_prev + jnp.broadcast_to(
            jnp.sum(e, axis=1, keepdims=True), l_prev.shape)
        m_ref[...] = m_new
        pv = _dot(e.astype(v.dtype), v, ((1,), (0,)))    # [grp, d]
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv

    @pl.when(p == num_p - 1)
    def _finish():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)   # empty slot -> zeros, not NaN
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_kernel_q(pt_ref, sl_ref, q_ref, k_ref, v_ref, ks_ref,
                     vs_ref, o_ref, acc_ref, m_ref, l_ref, *, scale,
                     page_size, quant):
    """`_decode_kernel` over quantized pools: per-row fp32 scales ride
    along as (ps, 1) blocks picked by the same page-table index map,
    and dequant is a register-resident row broadcast fused ahead of the
    dots — the pool never exists in fp anywhere. ``quant="int4"`` adds
    a nibble unpack of the packed (ps, d//2) uint8 block before the
    scale multiply."""
    b = pl.program_id(0)
    p = pl.program_id(2)
    num_p = pl.num_programs(2)
    sl = sl_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p * page_size < sl)
    def _step():
        q = q_ref[0, 0]                                  # [grp, d]
        kq, vq = k_ref[0, 0], v_ref[0, 0]                # [ps, d(/2)]
        if quant == "int4":
            kq, vq = _unpack_nib(kq), _unpack_nib(vq)    # [ps, d]
        k = kq.astype(jnp.float32) * ks_ref[0, 0]        # [ps, d]
        v = vq.astype(jnp.float32) * vs_ref[0, 0]
        s = _dot(q.astype(jnp.float32), k,
                 ((1,), (1,))) * scale                   # [grp, ps]
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < sl, s, -jnp.inf)
        m_prev = m_ref[...]                              # [grp, LANES]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        corr = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new[:, :1])
        l_ref[...] = corr * l_prev + jnp.broadcast_to(
            jnp.sum(e, axis=1, keepdims=True), l_prev.shape)
        m_ref[...] = m_new
        pv = _dot(e, v, ((1,), (0,)))                    # [grp, d]
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv

    @pl.when(p == num_p - 1)
    def _finish():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)   # empty slot -> zeros, not NaN
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _page_specs(pp, page_size, d, quant):
    """BlockSpecs for (k_pages, v_pages[, k_scales, v_scales]) — every
    block picked by the scalar-prefetched flat page table. int4 pools
    DMA the PACKED (ps, d//2) uint8 block; the kernel unpacks in
    registers."""

    def page(bb, h, p, pt, sl):
        return (h, pt[bb * pp + p], 0, 0)

    dp = d // 2 if quant == "int4" else d
    specs = [pl.BlockSpec((1, 1, page_size, dp), page),
             pl.BlockSpec((1, 1, page_size, dp), page)]
    if quant is not None:
        specs += [pl.BlockSpec((1, 1, page_size, 1), page),
                  pl.BlockSpec((1, 1, page_size, 1), page)]
    return specs


def _paged_attention_pallas(q, k_pages, v_pages, page_tables, seq_lens,
                            scale, interpret, k_scales=None,
                            v_scales=None):
    b, nh, d = q.shape
    kvh, num_pages, page_size, _ = k_pages.shape
    grp = nh // kvh
    pp = page_tables.shape[1]
    qg = q.reshape(b, kvh, grp, d)
    flat_pt = page_tables.reshape(-1).astype(jnp.int32)
    quant = _quant_mode(k_pages, k_scales)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page table + seq_lens
        grid=(b, kvh, pp),
        in_specs=[
            pl.BlockSpec((1, 1, grp, d),
                         lambda bb, h, p, pt, sl: (bb, h, 0, 0)),
            *_page_specs(pp, page_size, d, quant),
        ],
        out_specs=pl.BlockSpec((1, 1, grp, d),
                               lambda bb, h, p, pt, sl: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((grp, d), jnp.float32),
            pltpu.VMEM((grp, _LANES), jnp.float32),
            pltpu.VMEM((grp, _LANES), jnp.float32),
        ],
    )
    if quant is not None:
        kernel = functools.partial(_decode_kernel_q, quant=quant)
        extra = (k_scales.reshape(kvh, num_pages, page_size, 1),
                 v_scales.reshape(kvh, num_pages, page_size, 1))
    else:
        kernel, extra = _decode_kernel, ()
    out = routing.pallas_call(
        functools.partial(kernel, scale=scale, page_size=page_size),
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, grp, d), q.dtype),
        interpret=interpret,
    )(flat_pt, seq_lens.astype(jnp.int32), qg, k_pages, v_pages,
      *extra)
    return out.reshape(b, nh, d)


def paged_attention(q, k_pages, v_pages, page_tables, seq_lens,
                    scale=None, interpret=None, use_kernel=None,
                    k_scales=None, v_scales=None):
    """Ragged paged decode attention (see module docstring for layouts).

    Routes to the Pallas kernel on TPU when the geometry qualifies
    (`supports`), the XLA gather fallback otherwise. `interpret=True`
    forces the kernel in interpret mode (hermetic CPU testing);
    `use_kernel` overrides the routing outright. Passing
    `k_scales`/`v_scales` selects the quantized-pool path (fused
    dequant; int8 or — for uint8 packed pools — int4 nibble unpack).
    """
    b, nh, d = q.shape
    kvh, _, page_size, _ = k_pages.shape
    if _quant_mode(k_pages, k_scales) == "int4" and d % 2:
        raise ValueError(
            f"int4 paged attention needs an even head_dim (two values "
            f"per byte), got head_dim={d}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    use_kernel, interpret = routing.route(
        "paged_attention", supports(nh, kvh, d, page_size),
        (f"heads={nh}/{kvh}", f"head_dim={d}", f"page_size={page_size}"),
        interpret, use_kernel)
    if use_kernel:
        return _paged_attention_pallas(
            q, k_pages, v_pages, page_tables, seq_lens, float(scale),
            interpret, k_scales=k_scales, v_scales=v_scales)
    return paged_attention_xla(q, k_pages, v_pages, page_tables,
                               seq_lens, scale=float(scale),
                               k_scales=k_scales, v_scales=v_scales)


# ---------------------------------------------------------------------------
# multi-token chunk / speculative-verify attention (ISSUE 16)
# ---------------------------------------------------------------------------

def paged_attention_chunk_xla(q, k_pages, v_pages, page_tables, start,
                              scale=None, k_scales=None, v_scales=None):
    """c queries per slot over the slot's full paged context, causal
    within the chunk: query t of slot i sits at absolute position
    ``start[i] + t`` and attends context positions ``<= start[i] + t``.

    q: [b, c, nh, d]; page_tables: the b slots' GATHERED table rows
    ``[b, pages_per_seq]`` (callers index the pool-wide table first);
    start: [b] int32. This is the exact chunk-prefill attention of
    `GPTAttention.forward_prefill_chunk` — kept operation-for-operation
    identical so chunked prefill numerics don't move — and also the
    spec-decode verify shape (c = k+1 draft positions)."""
    b, c, nh, d = q.shape
    kvh, _, page_size, _ = k_pages.shape
    grp = nh // kvh
    L = page_tables.shape[1] * page_size
    sc = scale if scale is not None else 1.0 / (d ** 0.5)

    ctx_k = _densify(k_pages, page_tables, k_scales)
    ctx_v = _densify(v_pages, page_tables, v_scales)
    qg = jnp.moveaxis(q, 1, 2).reshape(b, kvh, grp, c, d)
    s = jnp.einsum("bhgcd,bhld->bhgcl", qg.astype(jnp.float32),
                   ctx_k.astype(jnp.float32)) * sc
    # query i (abs pos start+i) sees ctx positions j <= start+i; the
    # rest of the gathered window is stale/unwritten pool data
    jpos = jnp.arange(L, dtype=jnp.int32)
    ipos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
    mask = jpos[None, None, :] <= ipos[:, :, None]      # [b, c, L]
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgcl,bhld->bhgcd", p, ctx_v.astype(jnp.float32))
    o = jnp.moveaxis(o.reshape(b, nh, c, d), 1, 2)
    return o.astype(q.dtype)


def _chunk_kernel(pt_ref, st_ref, q_ref, k_ref, v_ref, *rest, scale,
                  page_size, chunk, quant):
    """Ragged multi-token kernel: like `_decode_kernel` but the q block
    carries grp*c rows (row r = head-group g*c + chunk index i) and the
    causal mask compares each row's absolute position start+i against
    the page's key positions. Pages fully above start+c-1 are skipped,
    so verify cost tracks each slot's own context length. ``quant`` is
    None / "int8" / "int4" (int4 nibble-unpacks the packed block before
    the scale multiply)."""
    if quant is not None:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(2)
    num_p = pl.num_programs(2)
    st = st_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p * page_size < st + chunk)
    def _step():
        q = q_ref[0, 0]                                  # [grp*c, d]
        if quant is not None:
            kq, vq = k_ref[0, 0], v_ref[0, 0]            # [ps, d(/2)]
            if quant == "int4":
                kq, vq = _unpack_nib(kq), _unpack_nib(vq)
            k = kq.astype(jnp.float32) * ks_ref[0, 0]
            v = vq.astype(jnp.float32) * vs_ref[0, 0]
        else:
            k = k_ref[0, 0]
            v = v_ref[0, 0]
        s = _dot(q.astype(jnp.float32), k.astype(jnp.float32),
                 ((1,), (1,))) * scale                   # [grp*c, ps]
        kpos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        qpos = st + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) % chunk
        s = jnp.where(kpos <= qpos, s, -jnp.inf)
        m_prev = m_ref[...]                              # [grp*c, LANES]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        corr = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new[:, :1])
        l_ref[...] = corr * l_prev + jnp.broadcast_to(
            jnp.sum(e, axis=1, keepdims=True), l_prev.shape)
        m_ref[...] = m_new
        pv = _dot(e, v.astype(jnp.float32), ((1,), (0,)))  # [grp*c, d]
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv

    @pl.when(p == num_p - 1)
    def _finish():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_attention_chunk_pallas(q, k_pages, v_pages, page_tables,
                                  start, scale, interpret,
                                  k_scales=None, v_scales=None):
    b, c, nh, d = q.shape
    kvh, num_pages, page_size, _ = k_pages.shape
    grp = nh // kvh
    pp = page_tables.shape[1]
    rows = grp * c
    # [b, c, nh, d] -> [b, kvh, grp*c, d], row r = g*c + i
    qg = jnp.moveaxis(q, 1, 2).reshape(b, kvh, rows, d)
    flat_pt = page_tables.reshape(-1).astype(jnp.int32)
    quant = _quant_mode(k_pages, k_scales)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page table + start offsets
        grid=(b, kvh, pp),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda bb, h, p, pt, st: (bb, h, 0, 0)),
            *_page_specs(pp, page_size, d, quant),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda bb, h, p, pt, st: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
        ],
    )
    extra = ((k_scales.reshape(kvh, num_pages, page_size, 1),
              v_scales.reshape(kvh, num_pages, page_size, 1))
             if quant is not None else ())
    out = routing.pallas_call(
        functools.partial(_chunk_kernel, scale=scale,
                          page_size=page_size, chunk=c,
                          quant=quant),
        name="paged_attention_chunk",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, d), q.dtype),
        interpret=interpret,
    )(flat_pt, start.astype(jnp.int32), qg, k_pages, v_pages, *extra)
    # [b, kvh, grp*c, d] -> [b, c, nh, d]
    return jnp.moveaxis(out.reshape(b, nh, c, d), 2, 1)


def paged_attention_chunk(q, k_pages, v_pages, page_tables, start,
                          scale=None, interpret=None, use_kernel=None,
                          k_scales=None, v_scales=None):
    """Multi-token chunk/verify attention (see
    `paged_attention_chunk_xla` for the contract). Same routing rules
    as `paged_attention`."""
    b, c, nh, d = q.shape
    kvh, _, page_size, _ = k_pages.shape
    if _quant_mode(k_pages, k_scales) == "int4" and d % 2:
        raise ValueError(
            f"int4 paged attention needs an even head_dim (two values "
            f"per byte), got head_dim={d}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    use_kernel, interpret = routing.route(
        "paged_attention_chunk", supports(nh, kvh, d, page_size),
        (f"heads={nh}/{kvh}", f"head_dim={d}", f"page_size={page_size}"),
        interpret, use_kernel)
    if use_kernel:
        return _paged_attention_chunk_pallas(
            q, k_pages, v_pages, page_tables, start, float(scale),
            interpret, k_scales=k_scales, v_scales=v_scales)
    return paged_attention_chunk_xla(
        q, k_pages, v_pages, page_tables, start, scale=float(scale),
        k_scales=k_scales, v_scales=v_scales)
