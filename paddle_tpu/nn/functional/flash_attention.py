"""Attention functionals.

Reference parity: python/paddle/nn/functional/flash_attention.py
(flash_attention :195, scaled_dot_product_attention :976) backed by the CUDA
flash-attn kernel (paddle/phi/kernels/gpu/flash_attn_kernel.cu). TPU-first:
the default path is XLA dot-softmax-dot (which XLA already pipelines well at
moderate seq len); a Pallas splash/flash kernel is used for long sequences
when available (paddle_tpu.ops.pallas.flash_attention).

Layouts follow the reference: q/k/v are [batch, seqlen, num_heads, head_dim].
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ...framework.tensor import Tensor
from ...framework.random import next_key
from ...ops._dispatch import nary, ensure_tensor


# -- packed-sequence segment context ----------------------------------------
# Layers deep inside a model (GPTAttention under the scan template) have
# no signature room for per-batch segment ids; the model's outer forward
# publishes them here for the duration of its trace and attention layers
# pick them up. The value is a [batch, seq] int Tensor/array (tokens
# attend only within their own segment) or None (dense attention).
_segment_ctx = [None]


@contextlib.contextmanager
def attention_segments(segment_ids):
    """Publish packed-sequence segment ids to every attention layer
    traced inside the block (None = plain dense/causal attention)."""
    _segment_ctx.append(segment_ids)
    try:
        yield
    finally:
        _segment_ctx.pop()


def current_segment_ids():
    return _segment_ctx[-1]


def _sdpa_ref(q, k, v, mask, scale, causal, dropout_p, key):
    # q,k,v: [b, s, h, d] — dots run in the input dtype on the MXU with fp32
    # accumulation (preferred_element_type); softmax math in fp32.
    #
    # Score storage dtype: the [b, h, s, s] score matrix is the dominant
    # HBM traffic of non-flash attention (written fwd, re-read/rewritten
    # under remat and in backward). With bf16/fp16 inputs we round the
    # accumulated scores back to the input dtype for HBM residency — the
    # same storage precision the reference's fused softmax path keeps
    # (fp16 scores, fp32 softmax internals) — halving that traffic.
    # FLAGS_attention_fp32_scores restores full-fp32 storage.
    from ...utils import flags as _flags

    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if (q.dtype in (jnp.bfloat16, jnp.float16)
            and not _flags.get_flag("FLAGS_attention_fp32_scores")):
        logits = logits.astype(q.dtype)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cmask, logits, jnp.asarray(-jnp.inf, logits.dtype))
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits,
                               jnp.asarray(-jnp.inf, logits.dtype))
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True,
                                 segment_ids=None, name=None):
    """`segment_ids` ([batch, seq] int, or unset to consult the ambient
    `attention_segments` context) restricts attention to within-segment
    pairs — the packed-sequence training mask. Routed through the splash
    kernel (TPU) or its XLA fallback; with dropout active it lowers to a
    dense boolean mask instead."""
    query = ensure_tensor(query)
    key_t = ensure_tensor(key)
    value = ensure_tensor(value)
    head_dim = query.shape[-1]
    scale = 1.0 / (head_dim ** 0.5)
    drop = dropout_p if training else 0.0
    rng = next_key() if drop > 0.0 else None

    from ...ops.pallas import flash_attention as pallas_flash
    from ...ops.pallas import routing
    from ...ops.pallas import splash_attention as pallas_splash
    from ...utils import flags as _flags

    seqlen = query.shape[1]
    min_seq = int(_flags.get_flags(["FLAGS_pallas_flash_min_seqlen"])
                  ["FLAGS_pallas_flash_min_seqlen"])

    if segment_ids is None:
        segment_ids = current_segment_ids()
    splash_on = bool(_flags.get_flag("FLAGS_splash_attn"))
    kvh = key_t.shape[2]

    if segment_ids is not None and attn_mask is not None:
        # combining an arbitrary user mask with the document-isolation
        # mask is not plumbed; dropping either silently would train
        # across document boundaries (or without the user's mask)
        raise ValueError(
            "scaled_dot_product_attention got both attn_mask and "
            "segment_ids (explicit or via attention_segments): the "
            "masks are not combinable — fold the segment mask into "
            "attn_mask yourself, or drop one")

    if segment_ids is not None:
        seg = ensure_tensor(segment_ids)
        if splash_on and drop == 0.0:
            # splash owns the segment mask: fused into the score tiles
            # on TPU (or interpret mode), dense-equivalent XLA path
            # elsewhere — no [s, s] mask tensor either way
            def f_seg(q, k, v, s):
                return pallas_splash.splash_attention(
                    q, k, v, causal=is_causal, segment_ids=s,
                    scale=scale)

            return nary(f_seg, [query, key_t, value, seg],
                        "splash_attention_segments")
        # dropout (or splash off): lower segments to a dense bool mask
        segd = seg.astype("int32")

        def f_mask(q, k, v, s):
            m = (s[:, None, :, None] == s[:, None, None, :])
            return _sdpa_ref(q, k, v, m, scale, is_causal, drop, rng)

        return nary(f_mask, [query, key_t, value, segd],
                    "sdpa_segment_mask")

    # long-sequence training slot: a Pallas kernel on TPU (splash first,
    # flash for what it cannot take), XLA everywhere else. A TPU call in
    # this slot that neither kernel supports is recorded, not silent.
    if (seqlen >= min_seq and attn_mask is None and drop == 0.0
            and routing.kernels_wanted()):
        if splash_on and pallas_splash.supports(
                tuple(query.shape), kvh, query._data.dtype):
            return nary(
                lambda q, k, v: pallas_splash.splash_attention(
                    q, k, v, causal=is_causal, scale=scale),
                [query, key_t, value], "splash_attention")
        if (query.shape == key_t.shape == value.shape
                and pallas_flash.supports(tuple(query.shape),
                                          query._data.dtype, is_causal)):
            return nary(
                lambda q, k, v: pallas_flash.flash_attention(
                    q, k, v, causal=is_causal, scale=scale),
                [query, key_t, value], "flash_attention_pallas")
        routing.note_fallback(
            "training_attention",
            (f"q{tuple(query.shape)}", f"kv_heads={kvh}",
             str(query._data.dtype)))

    inputs = [query, key_t, value]
    if attn_mask is not None:
        inputs.append(ensure_tensor(attn_mask))

        def f(q, k, v, m):
            return _sdpa_ref(q, k, v, m, scale, is_causal, drop, rng)
    else:

        def f(q, k, v):
            return _sdpa_ref(q, k, v, None, scale, is_causal, drop, rng)

    return nary(f, inputs, "scaled_dot_product_attention")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """flash_attention parity (reference :195). Returns (out, softmax or None)."""
    out = scaled_dot_product_attention(
        query, key, value, attn_mask=None, dropout_p=dropout,
        is_causal=causal, training=training,
    )
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen ("unpadded") attention parity (reference flash_attention.py's
    flash_attn_unpadded over flash_attn_varlen CUDA kernels).

    q/k/v: [total_tokens, num_heads, head_dim] — sequences packed back to
    back; cu_seqlens_*: [batch+1] cumulative boundaries. TPU-first: instead
    of ragged kernels, segment-id masking — one dense masked attention with
    static shapes (block-diagonal over segments, causal within a segment),
    which XLA fuses like any other attention. Returns (out, None).
    """
    query = ensure_tensor(query)
    key_t = ensure_tensor(key)
    value = ensure_tensor(value)
    cu_q = ensure_tensor(cu_seqlens_q, dtype="int32")
    cu_k = ensure_tensor(cu_seqlens_k, dtype="int32")
    drop = float(dropout) if training else 0.0
    rng = next_key() if drop > 0.0 else None

    def f(q, k, v, cq, ck):
        tq, tk = q.shape[0], k.shape[0]
        iq = jnp.arange(tq, dtype=jnp.int32)
        ik = jnp.arange(tk, dtype=jnp.int32)
        seg_q = jnp.searchsorted(cq, iq, side="right")      # [tq] 1-based
        seg_k = jnp.searchsorted(ck, ik, side="right")
        pos_q = iq - cq[seg_q - 1]                          # pos in own seq
        pos_k = ik - ck[seg_k - 1]
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        logits = jnp.einsum("qhd,khd->hqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask[None], logits, jnp.float32(-jnp.inf))
        probs = jax.nn.softmax(logits, axis=-1)
        # rows whose segment is empty (shouldn't happen) -> nan guard
        probs = jnp.where(jnp.any(mask, axis=1)[None, :, None], probs, 0.0)
        if drop > 0.0 and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - drop, probs.shape)
            probs = jnp.where(keep, probs / (1.0 - drop), 0.0)
        out = jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.astype(q.dtype)

    out = nary(f, [query, key_t, value, cu_q, cu_k], "flash_attn_unpadded")
    return out, None


def sparse_attention(*args, **kwargs):
    raise NotImplementedError("sparse attention is not in the TPU v1 op set")
