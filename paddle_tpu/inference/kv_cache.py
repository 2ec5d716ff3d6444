"""KV caches for the decode engine — dense per-slot and paged-pool forms.

Two cache shapes back `GPTForCausalLM.generate()` (models/gpt.py) and the
compiled decode step (jit/decode_step.py):

* ``DenseKVCache`` — per layer ``[2, batch, num_heads, max_len,
  head_dim]`` buffers (the reference `masked_multihead_attention`
  cache_kv layout) with ONE shared write position. The aligned-batch
  fast path: each decode step is a single ``dynamic_update_slice`` per
  layer (no O(seq) concat, no scatter), which is what lets the jitted
  step stay retrace-free with donated buffers.
* ``PagedKVCache`` — the Ragged-Paged-Attention layout (PAPERS.md): per
  layer K/V page pools ``[num_kv_heads, num_pages, page_size,
  head_dim]`` (the ops/pallas/paged_attention.py contract) + per-slot
  page tables and ragged ``seq_lens``. Slots allocate/free
  independently (continuous batching): a finished sequence's pages
  return to the pool while the rest of the batch keeps decoding, and
  mixed-length batches waste no cache on padding.

Device state lives in plain jnp arrays exposed via ``state()`` /
``load_state()`` so the jitted decode step can thread (and donate) it as
a pytree. Host-side bookkeeping (free lists, slot maps) never enters the
trace — it only rewrites ``page_tables`` rows between steps, which is an
ordinary input refresh, not a retrace.

Page 0 of every pool is the **trash page**: ragged writes of padding /
inactive-slot tokens are routed there so scatters stay static-shape with
no masking branches. It is never mapped in any page table.

``PagedKVCache(..., quant="int8")`` stores the pools as int8 with one
fp32 symmetric scale per cached row (``k_scales``/``v_scales``:
``[num_kv_heads, num_pages, page_size]``) — the comm stack's
`quantize_symmetric_q8` wire format (distributed/collective.py), at
block = head_dim. KV HBM halves (scales add 1/head_dim), so the same
memory holds ~2x the pages; dequant fuses into the paged-attention
gather (ops/pallas/paged_attention.py). The ``*_q8`` write helpers
quantize each incoming row and scatter payload + scale with the same
flat-index trick as their fp twins.

``quant="int4"`` (ISSUE 20) halves the payload again: two values per
byte in ``[num_kv_heads, num_pages, page_size, head_dim // 2]`` uint8
pools (nn/quant ``pack_q4`` nibble format — high nibble = even lane,
offset-binary +8), same per-row fp32 scale pools as int8 (scale =
max|row|/7). ``head_dim`` must be even. Dequant is again fused into
the paged-attention gather as a nibble unpack; the ``*_q4`` writers
mirror the ``*_q8`` ones with a pack step before the scatter.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DenseKVCache", "PagedKVCache", "blob_checksum",
           "paged_write_decode", "paged_write_prefill",
           "dense_write_prefill", "paged_write_decode_q8",
           "paged_write_prefill_q8", "paged_write_decode_q4",
           "paged_write_prefill_q4", "dense_write_chunk"]


def blob_checksum(blob: dict) -> int:
    """CRC32 over an export blob's payload arrays, in wire order.

    ``export_slot`` stamps it as ``blob["crc32"]``; ``import_slot``
    re-derives and compares BEFORE allocating, so a blob corrupted in
    flight (host ring, cross-replica hand-off, future cross-host
    transport) is rejected while the destination pools are still
    untouched."""
    crc = 0
    for key in ("k", "v", "k_scales", "v_scales"):
        for a in blob.get(key, ()):
            crc = zlib.crc32(np.ascontiguousarray(a).data, crc)
    return crc & 0xFFFFFFFF


# ---------------------------------------------------------------------------
# pure-jnp write helpers (used inside the jitted decode/prefill steps)
# ---------------------------------------------------------------------------

def dense_write_prefill(cache_l, k_new, v_new):
    """Prompt K/V at positions [0, s) of one layer's dense cache.

    cache_l: [2, b, nh, max_len, d]; k_new/v_new: [b, s, nh, d].
    One dynamic-update-slice (static start)."""
    upd = jnp.stack([jnp.swapaxes(k_new, 1, 2),
                     jnp.swapaxes(v_new, 1, 2)]).astype(cache_l.dtype)
    z = jnp.int32(0)
    return jax.lax.dynamic_update_slice(cache_l, upd, (z, z, z, z, z))


def dense_write_chunk(cache_l, start, valid_len, k_new, v_new):
    """Multi-token ragged write into one layer's dense cache: token t of
    row i lands at position start[i] + t; positions >= valid_len[i] (or
    past max_len) are dropped. The dense-cache face of the verify write
    (spec decode scores k+1 tokens per slot whose accepted prefix varies
    per slot — the over-written tail is masked by valid_len on the next
    read and overwritten by the next dispatch).

    cache_l: [2, b, nh, max_len, d]; k_new/v_new: [b, t, nh, d];
    start/valid_len: [b] int32."""
    _, b, nh, max_len, d = cache_l.shape
    t = k_new.shape[1]
    pos = start[:, None].astype(jnp.int32) \
        + jnp.arange(t, dtype=jnp.int32)[None, :]           # [b, t]
    ok = pos < jnp.minimum(valid_len[:, None], max_len)
    pos = jnp.where(ok, pos, max_len)       # out of range -> dropped
    bidx = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None],
                            pos.shape)
    upd = jnp.stack([k_new, v_new]).astype(cache_l.dtype)   # [2,b,t,nh,d]
    upd = jnp.moveaxis(upd, 0, 2)                           # [b,t,2,nh,d]
    return cache_l.at[:, bidx, :, pos].set(upd, mode="drop")


def _page_flat_index(page_tables, pos, page_size):
    """Flat [num_pages * page_size) pool index of logical position `pos`
    per slot; pos broadcast against page_tables rows."""
    page = jnp.take_along_axis(page_tables, pos // page_size, axis=-1)
    return page * page_size + pos % page_size


def paged_write_decode(k_pages, v_pages, page_tables, seq_lens, active,
                       k_new, v_new):
    """One decode token per slot at its own ragged position seq_lens[i].

    k_pages/v_pages: [kvh, num_pages, page_size, d] (one layer);
    k_new/v_new: [b, kvh, d]; active: [b] bool — inactive slots write to
    the trash page (page 0, never mapped, collisions are garbage-only).
    Returns the updated pools. Scatter-based (positions differ per slot).
    """
    kvh, num_pages, page_size, d = k_pages.shape
    flat = _page_flat_index(page_tables, seq_lens[:, None],
                            page_size)[:, 0]                # [b]
    flat = jnp.where(active, flat, seq_lens % page_size)    # page 0 trash

    def wr(pool, upd):
        view = pool.reshape(kvh, num_pages * page_size, d)
        view = view.at[:, flat].set(
            jnp.moveaxis(upd, 1, 0).astype(pool.dtype))
        return view.reshape(pool.shape)

    return wr(k_pages, k_new), wr(v_pages, v_new)


def paged_write_prefill(k_pages, v_pages, page_tables, slot_ids,
                        seq_lens_new, k_new, v_new, start=None):
    """Prompt K/V for `len(slot_ids)` slots, token t of row i landing at
    logical position start_i + t of slot slot_ids[i]; positions past
    seq_lens_new[i] (right padding) go to the trash page.

    k_new/v_new: [b, s, kvh, d] (padded); slot_ids/seq_lens_new: [b];
    start: [b] int32 or None (0 = fresh prompt)."""
    kvh, num_pages, page_size, d = k_pages.shape
    b, s = k_new.shape[:2]
    t = jnp.arange(s, dtype=jnp.int32)[None, :]             # [1, s]
    pos = t if start is None else start[:, None] + t        # [b, s]
    flat = _page_flat_index(page_tables[slot_ids], pos, page_size)
    valid = pos < seq_lens_new[:, None]
    flat = jnp.where(valid, flat, pos % page_size).reshape(-1)

    def wr(pool, upd):
        view = pool.reshape(kvh, num_pages * page_size, d)
        view = view.at[:, flat].set(
            jnp.moveaxis(upd, 2, 0).reshape(kvh, b * s, d)
            .astype(pool.dtype))
        return view.reshape(pool.shape)

    return wr(k_pages, k_new), wr(v_pages, v_new)


def paged_write_decode_q8(k_pages, v_pages, k_scales, v_scales,
                          page_tables, seq_lens, active, k_new, v_new):
    """`paged_write_decode` for int8 pools: each incoming [d] row is
    symmetric-int8 quantized (comm-stack format, one fp32 scale per
    row) and payload + scale scatter to the same flat pool index.

    k_scales/v_scales: [kvh, num_pages, page_size] fp32. Returns
    (k_pages, v_pages, k_scales, v_scales) updated."""
    from ..distributed.collective import quantize_symmetric_q8

    kvh, num_pages, page_size, d = k_pages.shape
    flat = _page_flat_index(page_tables, seq_lens[:, None],
                            page_size)[:, 0]                # [b]
    flat = jnp.where(active, flat, seq_lens % page_size)    # page 0 trash

    def wr(pool, spool, upd):
        q, sc = quantize_symmetric_q8(upd)      # [b, kvh, d], [b, kvh]
        view = pool.reshape(kvh, num_pages * page_size, d)
        view = view.at[:, flat].set(jnp.moveaxis(q, 1, 0))
        sview = spool.reshape(kvh, num_pages * page_size)
        sview = sview.at[:, flat].set(
            jnp.moveaxis(sc, 1, 0).astype(spool.dtype))
        return view.reshape(pool.shape), sview.reshape(spool.shape)

    k2, ks2 = wr(k_pages, k_scales, k_new)
    v2, vs2 = wr(v_pages, v_scales, v_new)
    return k2, v2, ks2, vs2


def paged_write_prefill_q8(k_pages, v_pages, k_scales, v_scales,
                           page_tables, slot_ids, seq_lens_new,
                           k_new, v_new, start=None):
    """`paged_write_prefill` for int8 pools (see `paged_write_decode_q8`
    for the scale layout). k_new/v_new: [b, s, kvh, d] fp."""
    from ..distributed.collective import quantize_symmetric_q8

    kvh, num_pages, page_size, d = k_pages.shape
    b, s = k_new.shape[:2]
    t = jnp.arange(s, dtype=jnp.int32)[None, :]             # [1, s]
    pos = t if start is None else start[:, None] + t        # [b, s]
    flat = _page_flat_index(page_tables[slot_ids], pos, page_size)
    valid = pos < seq_lens_new[:, None]
    flat = jnp.where(valid, flat, pos % page_size).reshape(-1)

    def wr(pool, spool, upd):
        q, sc = quantize_symmetric_q8(upd)   # [b,s,kvh,d], [b,s,kvh]
        view = pool.reshape(kvh, num_pages * page_size, d)
        view = view.at[:, flat].set(
            jnp.moveaxis(q, 2, 0).reshape(kvh, b * s, d))
        sview = spool.reshape(kvh, num_pages * page_size)
        sview = sview.at[:, flat].set(
            jnp.moveaxis(sc, 2, 0).reshape(kvh, b * s)
            .astype(spool.dtype))
        return view.reshape(pool.shape), sview.reshape(spool.shape)

    k2, ks2 = wr(k_pages, k_scales, k_new)
    v2, vs2 = wr(v_pages, v_scales, v_new)
    return k2, v2, ks2, vs2


def paged_write_decode_q4(k_pages, v_pages, k_scales, v_scales,
                          page_tables, seq_lens, active, k_new, v_new):
    """`paged_write_decode` for int4 pools: each incoming [d] row is
    symmetric-int4 quantized (one fp32 scale per row, max|x|/7) and
    nibble-PACKED to [d//2] uint8 before the scatter.

    k_pages/v_pages: [kvh, num_pages, page_size, d//2] uint8;
    k_scales/v_scales: [kvh, num_pages, page_size] fp32. Returns
    (k_pages, v_pages, k_scales, v_scales) updated."""
    from ..nn.quant import pack_q4, quantize_symmetric_q4

    kvh, num_pages, page_size, dp = k_pages.shape   # dp == head_dim // 2
    flat = _page_flat_index(page_tables, seq_lens[:, None],
                            page_size)[:, 0]                # [b]
    flat = jnp.where(active, flat, seq_lens % page_size)    # page 0 trash

    def wr(pool, spool, upd):
        q, sc = quantize_symmetric_q4(upd)      # [b, kvh, d], [b, kvh]
        p = pack_q4(q)                          # [b, kvh, d//2]
        view = pool.reshape(kvh, num_pages * page_size, dp)
        view = view.at[:, flat].set(jnp.moveaxis(p, 1, 0))
        sview = spool.reshape(kvh, num_pages * page_size)
        sview = sview.at[:, flat].set(
            jnp.moveaxis(sc, 1, 0).astype(spool.dtype))
        return view.reshape(pool.shape), sview.reshape(spool.shape)

    k2, ks2 = wr(k_pages, k_scales, k_new)
    v2, vs2 = wr(v_pages, v_scales, v_new)
    return k2, v2, ks2, vs2


def paged_write_prefill_q4(k_pages, v_pages, k_scales, v_scales,
                           page_tables, slot_ids, seq_lens_new,
                           k_new, v_new, start=None):
    """`paged_write_prefill` for int4 pools (see `paged_write_decode_q4`
    for the packed layout). k_new/v_new: [b, s, kvh, d] fp."""
    from ..nn.quant import pack_q4, quantize_symmetric_q4

    kvh, num_pages, page_size, dp = k_pages.shape   # dp == head_dim // 2
    b, s = k_new.shape[:2]
    t = jnp.arange(s, dtype=jnp.int32)[None, :]             # [1, s]
    pos = t if start is None else start[:, None] + t        # [b, s]
    flat = _page_flat_index(page_tables[slot_ids], pos, page_size)
    valid = pos < seq_lens_new[:, None]
    flat = jnp.where(valid, flat, pos % page_size).reshape(-1)

    def wr(pool, spool, upd):
        q, sc = quantize_symmetric_q4(upd)   # [b,s,kvh,d], [b,s,kvh]
        p = pack_q4(q)                       # [b,s,kvh,d//2]
        view = pool.reshape(kvh, num_pages * page_size, dp)
        view = view.at[:, flat].set(
            jnp.moveaxis(p, 2, 0).reshape(kvh, b * s, dp))
        sview = spool.reshape(kvh, num_pages * page_size)
        sview = sview.at[:, flat].set(
            jnp.moveaxis(sc, 2, 0).reshape(kvh, b * s)
            .astype(spool.dtype))
        return view.reshape(pool.shape), sview.reshape(spool.shape)

    k2, ks2 = wr(k_pages, k_scales, k_new)
    v2, vs2 = wr(v_pages, v_scales, v_new)
    return k2, v2, ks2, vs2


# ---------------------------------------------------------------------------
# cache objects: device state + host bookkeeping
# ---------------------------------------------------------------------------

class DenseKVCache:
    """Aligned-batch dense cache: shared write position, one DUS/layer."""

    kind = "dense"

    def __init__(self, num_layers, batch, max_len, num_heads, head_dim,
                 dtype=jnp.float32):
        self.num_layers = num_layers
        self.batch = batch
        self.max_len = max_len
        self.num_heads = num_heads
        self.head_dim = head_dim
        shape = (2, batch, num_heads, max_len, head_dim)
        self.layers = [jnp.zeros(shape, dtype) for _ in range(num_layers)]
        self.pos = jnp.zeros((), jnp.int32)     # tokens already cached
        # live-buffer attribution (ISSUE 14): the cache claims its
        # pools at mem.live scrape time (weakly tracked)
        from ..observability.memory import live_registry

        live_registry().track(self)

    def _mem_owners(self):
        return {"kv_cache": list(self.layers)}

    def layer(self, l):
        return self.layers[l]

    def set_layer(self, l, value):
        self.layers[l] = value

    def state(self):
        return {"layers": list(self.layers), "pos": self.pos}

    def load_state(self, state):
        self.layers = list(state["layers"])
        self.pos = state["pos"]


class PagedKVCache:
    """Paged pools + page tables + ragged lengths + slot bookkeeping.

    Host-side: `allocate(prompt_len)` claims a slot and maps enough
    pages; `reserve(slot, total_len)` maps more as decoding grows a
    sequence; `free(slot)` returns its pages to the pool. Device-side
    state (pools, tables, seq_lens, active) threads through the jitted
    step; only the jitted step mutates seq_lens/pools, only the host
    bookkeeping mutates page_tables/active.
    """

    kind = "paged"

    def __init__(self, num_layers, num_kv_heads, head_dim, num_pages,
                 page_size, max_slots, pages_per_seq,
                 dtype=jnp.float32, quant=None):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the trash page)")
        if quant not in (None, "int8", "int4"):
            raise ValueError(f"unknown KV quant mode {quant!r}")
        if quant == "int4" and head_dim % 2:
            raise ValueError(
                f"int4 KV packs two values per byte along head_dim: "
                f"head_dim must be even, got {head_dim}")
        self.num_layers = num_layers
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_slots = max_slots
        self.pages_per_seq = pages_per_seq
        self.quant = quant
        self.dtype = jnp.dtype({"int8": jnp.int8, "int4": jnp.uint8}
                               .get(quant, dtype))
        # int4 pools are nibble-PACKED: the last pool dim is head_dim//2
        # bytes holding head_dim values (pack_q4 layout). Everything
        # downstream that touches raw pool shapes reads `pool_head_dim`.
        self.pool_head_dim = head_dim // 2 if quant == "int4" else head_dim
        shape = (num_kv_heads, num_pages, page_size, self.pool_head_dim)
        self.k_layers = [jnp.zeros(shape, self.dtype)
                         for _ in range(num_layers)]
        self.v_layers = [jnp.zeros(shape, self.dtype)
                         for _ in range(num_layers)]
        if quant is not None:
            # one fp32 scale per cached row (block = head_dim); scale
            # pools thread/donate through the step alongside the payload
            sshape = (num_kv_heads, num_pages, page_size)
            self.k_scales = [jnp.zeros(sshape, jnp.float32)
                             for _ in range(num_layers)]
            self.v_scales = [jnp.zeros(sshape, jnp.float32)
                             for _ in range(num_layers)]
        # host-mutated metadata lives as NUMPY between steps: the slot
        # bookkeeping (allocate/reserve/free/set_active) runs every
        # scheduler iteration, and a jnp `.at[].set` per call would be
        # an XLA dispatch each — measured ~5x the whole serving step on
        # the continuous-batching loop. jax converts these small arrays
        # at jit dispatch; the compiled steps hand back device arrays,
        # which `_host()` pulls down again on the next host mutation.
        self.page_tables = np.zeros((max_slots, pages_per_seq),
                                    np.int32)
        self.seq_lens = np.zeros((max_slots,), np.int32)
        self.active = np.zeros((max_slots,), bool)
        # host bookkeeping — page 0 reserved as trash
        self._free_pages = list(range(num_pages - 1, 0, -1))
        self._free_slots = list(range(max_slots - 1, -1, -1))
        self._slot_pages: dict[int, list[int]] = {}
        # live-buffer attribution (ISSUE 14): the page pools claim
        # their resident bytes at mem.live scrape time (weakly tracked)
        from ..observability.memory import live_registry

        live_registry().track(self)

    @property
    def quantized(self):
        return self.quant is not None

    def _mem_owners(self):
        bufs = list(self.k_layers) + list(self.v_layers)
        if self.quantized:
            bufs += list(self.k_scales) + list(self.v_scales)
        return {"kv_pages": bufs}

    # -- host bookkeeping ------------------------------------------------
    def _host(self, name):
        """Writable host copy of a metadata array (seq_lens/active/
        page_tables may hold the device output of the last compiled
        step — never mutated during a trace, so the pull-down here is
        always a concrete tiny D2H)."""
        arr = getattr(self, name)
        if not isinstance(arr, np.ndarray):
            arr = np.array(getattr(arr, "_data", arr))
            setattr(self, name, arr)
        return arr

    @property
    def free_page_count(self):
        return len(self._free_pages)

    @property
    def free_slot_count(self):
        return len(self._free_slots)

    def pages_needed(self, total_len: int) -> int:
        """Pages required to hold `total_len` tokens of one sequence."""
        return -(-int(total_len) // self.page_size)   # ceil

    def can_allocate(self, prompt_len: int) -> bool:
        """Admission probe: would `allocate(prompt_len)` succeed? Pure
        host check — no state is touched, so the serving scheduler can
        make admission decisions without try/except control flow."""
        need = self.pages_needed(prompt_len)
        return (bool(self._free_slots) and need <= self.pages_per_seq
                and need <= len(self._free_pages))

    def can_reserve(self, slot: int, total_len: int) -> bool:
        """Growth probe: would `reserve(slot, total_len)` succeed?"""
        pages = self._slot_pages.get(slot)
        if pages is None:
            return False
        need = self.pages_needed(total_len)
        return (need <= self.pages_per_seq
                and need - len(pages) <= len(self._free_pages))

    def allocate(self, prompt_len: int) -> int:
        """Claim a slot with pages covering `prompt_len` tokens.

        Atomic: a failed allocation (no slot / pool dry / over
        pages_per_seq) raises BEFORE any state is touched — page
        tables, seq_lens, active and the free lists read exactly as
        they did on entry."""
        if not self._free_slots:
            raise RuntimeError("no free cache slots (batch full)")
        self._check_reservable(self.pages_needed(prompt_len), 0,
                               prompt_len)
        # lowest free slot, NOT stack order: the generation engines
        # free-all/reallocate between calls and every compiled step
        # indexes the batch as row i == slot i — a LIFO pop hands the
        # slots back permuted after the first reuse, silently crossing
        # rows between sequences (and blowing the spec loop's host/
        # device seq_lens bookkeeping apart). O(max_slots) on a small
        # host list, once per admission.
        slot = min(self._free_slots)
        self._free_slots.remove(slot)
        self._slot_pages[slot] = []
        self._host("seq_lens")[slot] = 0
        self._host("active")[slot] = True
        self.reserve(slot, prompt_len)
        return slot

    def _check_reservable(self, need, have, total_len):
        if need > self.pages_per_seq:
            raise RuntimeError(
                f"sequence of {total_len} tokens exceeds pages_per_seq="
                f"{self.pages_per_seq} * page_size={self.page_size}")
        if need - have > len(self._free_pages):
            raise RuntimeError("KV page pool exhausted")

    def reserve(self, slot: int, total_len: int):
        """Map pages so slot `slot` can hold `total_len` tokens.

        Atomic like `allocate`: the capacity check happens before the
        first page is mapped, so a failed reserve leaves the slot, the
        page tables and the free list untouched."""
        pages = self._slot_pages[slot]
        need = self.pages_needed(total_len)
        self._check_reservable(need, len(pages), total_len)
        pt = self._host("page_tables")
        while len(pages) < need:
            page = self._free_pages.pop()
            pt[slot, len(pages)] = page
            pages.append(page)

    def pool_stats(self) -> dict:
        """Page-pool occupancy/fragmentation snapshot (ISSUE 14
        satellite) — pure host bookkeeping, O(free + slots), no device
        sync, safe to call from a debug-server scrape thread while the
        serve loop mutates the bookkeeping (everything is snapshotted
        before iteration; a scrape racing a mutation sees one coherent
        moment, never a changed-size-during-iteration crash).
        ``fragmentation`` compares the longest CONTIGUOUS run of
        free page ids against the free count (0.0 = one solid free
        extent, →1.0 = free pages scattered singly). Contiguity is a
        locality/diagnostic signal, not an allocation constraint —
        page tables map pages individually — but a pool that churns
        toward high fragmentation is a pool whose sequences
        interleave heavily. Invariant: used + free == total."""
        free = sorted(list(self._free_pages))     # atomic snapshot
        slot_items = list(self._slot_pages.items())
        max_contig = run = 0
        prev = None
        for p in free:
            run = run + 1 if prev is not None and p == prev + 1 else 1
            max_contig = max(max_contig, run)
            prev = p
        used = sum(len(p) for _, p in slot_items)
        total = self.num_pages - 1            # page 0 is trash
        # capacity receipt (ISSUE 16/20): bytes per cached token across
        # all layers, K+V, counting the fp32 scale pools honestly —
        # int8 pays head_dim + 4 bytes, int4 head_dim//2 + 4 (packed) —
        # the "Nx slots at equal HBM" math
        per_tok = self.num_layers * 2 * self.num_kv_heads * (
            self.pool_head_dim * self.dtype.itemsize
            + (4 if self.quantized else 0))
        # same geometry held in bf16 pools, the capacity baseline
        bf16_per_tok = self.num_layers * 2 * self.num_kv_heads \
            * self.head_dim * 2
        return {
            "kv_dtype": (self.quant or str(self.dtype)),
            "bytes_per_token": per_tok,
            "effective_slots_vs_bf16": round(bf16_per_tok / per_tok, 4),
            "page_bytes": per_tok * self.page_size,
            "pool_bytes": per_tok * self.page_size * self.num_pages,
            "total_pages": total,
            "free_pages": len(free),
            "used_pages": used,
            "trash_pages": 1,
            "page_size": self.page_size,
            "slot_pages": {int(s): len(p)
                           for s, p in sorted(slot_items)},
            "max_contiguous_free": max_contig,
            "fragmentation": (round(1.0 - max_contig / len(free), 4)
                              if free else 0.0),
            "occupancy": round(used / total, 4) if total else 0.0,
        }

    def set_active(self, slot: int, flag: bool):
        """Host toggle for decode participation: the serving tier keeps
        a slot inactive while its prompt is still chunk-prefilling so
        the decode step neither advances its seq_len nor attends its
        half-written context."""
        self._host("active")[slot] = bool(flag)

    def free(self, slot: int):
        """Return the slot's pages to the pool (continuous batching)."""
        pages = self._slot_pages.pop(slot, [])
        self._free_pages.extend(reversed(pages))
        self._free_slots.append(slot)
        self._host("page_tables")[slot] = 0
        self._host("seq_lens")[slot] = 0
        self._host("active")[slot] = False

    # -- slot migration (ISSUE 18) ----------------------------------------
    # fused migration kernels: ONE dispatch moves every layer's pages
    # (plus scale rows when quantized) instead of an op-by-op call per
    # pool — measured ~4x latency cut on the hand-off path, where each
    # op-by-op dispatch cost ~1ms under fleet GIL contention. jit
    # caches by aval, so the bucketed index shape keeps the executable
    # count at O(log pages) and _warm_migration can cover them all.
    @staticmethod
    @jax.jit
    def _migrate_gather(pools, idx):
        return tuple(p[:, idx] for p in pools)

    @staticmethod
    @jax.jit
    def _migrate_scatter(pools, idx, updates):
        return tuple(p.at[:, idx].set(u.astype(p.dtype))
                     for p, u in zip(pools, updates))

    def migration_bucket(self, n: int) -> int:
        """Gather/scatter width used to move `n` pages: the smallest
        power of two >= n, capped at the most pages ONE slot can map
        (a blob always covers a single slot, so wider signatures are
        unreachable). Bucketing keeps the device index shape one of
        O(log pages) signatures instead of one per page count, so the
        fused executables behind ``export_slot``/``import_slot`` are
        warmable (same trick as the prefill chunk buckets) — an
        eviction or hand-off mid-stream never pays an XLA compile.
        Padding lanes point at page 0, the trash page, whose whole job
        is absorbing garbage writes."""
        cap = min(self.num_pages - 1, self.pages_per_seq)
        w = 1
        while w < n:
            w *= 2
        return min(max(w, 1), max(cap, n))

    def migration_buckets(self) -> list:
        """Every distinct migration gather width this pool can hit."""
        out, w = [], 1
        cap = min(self.num_pages - 1, self.pages_per_seq)
        while w < cap:
            out.append(w)
            w *= 2
        out.append(cap)
        return sorted(set(out))

    def export_slot(self, slot: int) -> dict:
        """Copy one slot's KV out of the device pools into a host blob.

        The blob carries exactly the pages that cover the slot's
        ``seq_len`` (in page-table order), the matching int8 scale rows
        when quantized, and enough geometry to validate an import on a
        DIFFERENT cache instance. Neighbour slots are never touched:
        the gather indexes only this slot's mapped pages, and the
        source cache's bookkeeping is left as-is — pair with ``free()``
        for a move, or leave the slot resident for a copy.

        Host-side numpy throughout: the blob is the hand-off/eviction
        wire format, so it must survive the donor pools being donated
        into the next compiled step.
        """
        pages = self._slot_pages.get(slot)
        if pages is None:
            raise KeyError(f"slot {slot} is not allocated")
        seq_len = int(self._host("seq_lens")[slot])
        n = self.pages_needed(seq_len)
        if n > len(pages):
            raise RuntimeError(
                f"slot {slot}: seq_len {seq_len} spans {n} pages but only "
                f"{len(pages)} are mapped")
        # gather at the bucket width (padding lanes read the trash
        # page) and slice back to `n` host-side: the blob is exact, but
        # the device executable is shared across every export in the
        # same bucket — and ONE fused dispatch moves all pools
        w = self.migration_bucket(n)
        idx = np.zeros((w,), np.int32)
        idx[:n] = pages[:n]
        pools = list(self.k_layers) + list(self.v_layers)
        if self.quantized:
            pools += list(self.k_scales) + list(self.v_scales)
        host = jax.device_get(
            self._migrate_gather(tuple(pools), jnp.asarray(idx)))
        L = self.num_layers

        def take(block):
            lo = block * L
            # materialize the slice: `a[:, :n]` is a VIEW whose base is
            # the full bucket-width gather — keeping views alive pins up
            # to ~2x the bytes the blob claims (`nbytes` counts the
            # view's logical size), so a byte-capped HostKVRing would
            # silently hold more host memory than its budget charges
            return [np.ascontiguousarray(a[:, :n])
                    for a in host[lo:lo + L]]

        blob = {
            "geometry": (self.num_layers, self.num_kv_heads,
                         self.head_dim, self.page_size),
            "quant": self.quant,
            "dtype": str(self.dtype),
            "seq_len": seq_len,
            "pages": int(n),
            "active": bool(self._host("active")[slot]),
            "k": take(0),
            "v": take(1),
        }
        if self.quantized:
            blob["k_scales"] = take(2)
            blob["v_scales"] = take(3)
        blob["nbytes"] = sum(
            a.nbytes for key in ("k", "v", "k_scales", "v_scales")
            for a in blob.get(key, ()))
        blob["crc32"] = blob_checksum(blob)
        return blob

    def import_slot(self, blob: dict, active: bool = False) -> int:
        """Land an exported blob in a freshly allocated slot; returns it.

        Validation happens BEFORE allocation so a rejected blob leaves
        the pools untouched; allocation itself is the standard
        ``allocate()`` path, so the trash-page invariant (page 0 never
        mapped) and used+free conservation hold by construction. The
        payload lands via ``.at[:, idx].set`` on the destination's own
        freshly-mapped pages — same avals/placement as the resident
        pools, so the next compiled dispatch sees an input refresh,
        never a new signature. Page-table rows of other slots are never
        written, so no stale aliasing can survive the import.
        """
        geo = (self.num_layers, self.num_kv_heads, self.head_dim,
               self.page_size)
        if tuple(blob["geometry"]) != geo:
            raise ValueError(
                f"blob geometry {tuple(blob['geometry'])} != cache {geo}")
        if blob["quant"] != self.quant:
            raise ValueError(
                f"blob quant {blob['quant']!r} != cache {self.quant!r}")
        seq_len = int(blob["seq_len"])
        n = int(blob["pages"])
        if n != self.pages_needed(seq_len):
            raise ValueError(
                f"blob covers {n} pages but seq_len {seq_len} needs "
                f"{self.pages_needed(seq_len)}")
        # int4 blobs carry the PACKED payload (head_dim//2 bytes/row)
        want = (self.num_kv_heads, n, self.page_size, self.pool_head_dim)
        for key in ("k", "v"):
            if len(blob[key]) != self.num_layers:
                raise ValueError(f"blob {key!r} has {len(blob[key])} "
                                 f"layers, cache has {self.num_layers}")
            for a in blob[key]:
                if tuple(a.shape) != want:
                    raise ValueError(
                        f"blob {key!r} page block {tuple(a.shape)} != "
                        f"{want}")
        if "crc32" in blob and blob_checksum(blob) != blob["crc32"]:
            raise ValueError(
                f"blob payload corrupt: crc32 {blob_checksum(blob):#x} "
                f"!= stamped {blob['crc32']:#x}")
        slot = self.allocate(seq_len)
        if n:
            # scatter at the bucket width: real pages first, padding
            # lanes aimed at the trash page with zero payloads (dup
            # writes to page 0 are garbage-only by invariant) — one
            # fused dispatch per import, one executable per bucket
            w = self.migration_bucket(n)
            idx = np.zeros((w,), np.int32)
            idx[:n] = self._slot_pages[slot][:n]

            def widen(a):
                a = np.asarray(a)
                if w == n:
                    return a
                pad = [(0, 0)] * a.ndim
                pad[1] = (0, w - n)
                return np.pad(a, pad)

            pools = list(self.k_layers) + list(self.v_layers)
            updates = ([widen(a) for a in blob["k"]]
                       + [widen(a) for a in blob["v"]])
            if self.quantized:
                pools += list(self.k_scales) + list(self.v_scales)
                updates += [widen(a) for a in blob["k_scales"]]
                updates += [widen(a) for a in blob["v_scales"]]
            new = self._migrate_scatter(tuple(pools), jnp.asarray(idx),
                                        tuple(updates))
            L = self.num_layers
            self.k_layers = list(new[:L])
            self.v_layers = list(new[L:2 * L])
            if self.quantized:
                self.k_scales = list(new[2 * L:3 * L])
                self.v_scales = list(new[3 * L:])
        self._host("seq_lens")[slot] = seq_len
        self._host("active")[slot] = bool(active)
        return slot

    # -- device state ------------------------------------------------------
    def state(self):
        out = {"k_layers": list(self.k_layers),
               "v_layers": list(self.v_layers),
               "page_tables": self.page_tables,
               "seq_lens": self.seq_lens, "active": self.active}
        if self.quantized:
            out["k_scales"] = list(self.k_scales)
            out["v_scales"] = list(self.v_scales)
        return out

    def load_state(self, state):
        self.k_layers = list(state["k_layers"])
        self.v_layers = list(state["v_layers"])
        self.page_tables = state["page_tables"]
        self.seq_lens = state["seq_lens"]
        self.active = state["active"]
        if self.quantized:
            self.k_scales = list(state["k_scales"])
            self.v_scales = list(state["v_scales"])
