"""Learned sparse attention: an indexer scores every earlier key, the
top-k per query are kept, and attention runs over the kept keys only
(the DeepSeek-Sparse-Attention scheme).

  I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          s <= t
  S_t     = the min(k, t + 1) keys of largest I[t, .], ties to the
            lower index: ONE set per token, for every head
  L_I     = mean_t KL(p_t || softmax(I[t, S_t]))            p_t: the main
            attention's probabilities over S_t, averaged over heads

The selection is handed on as an int8 mask [b, s, s] (one per token, not
per head) that `ops.pallas.splash_attention(selection=)` reads tile by
tile; nothing of size [s, s] per head is ever formed. Everything here is
XLA, a query chunk of one sequence at a time, but the two things whose
[heads, chunk, s] intermediate XLA would write out and re-read: the
chunk's index scores with their pull-back (`ops/pallas/
indexer_scores.py`, on TPU; `indexer_scores` below elsewhere) and the
indexer's target (`ops/pallas/attention_probs.py`: the head-averaged
probabilities of a chunk). Both are handed the chunk's first position and
visit no key tile beyond its last query:

* `topk_mask` finds each row's k-th largest score exactly, with no sort:
  32 counting passes over the order-preserving integer image of the
  float32 scores (a radix select), then the lower-index rule among the
  scores equal to it.
* `indexer_select` is one pass that returns the mask, L_I and the count
  of kept keys. A top-k passes no gradient, so the indexer learns from
  L_I alone: the pass forms L_I's gradient with respect to (qI, kI, w)
  while the chunk's scores are at hand (a custom VJP hands them out
  scaled), and q and k, the main attention's side, get none.

In a device trace the chunk loop's operations carry these
`jax.named_scope` paths (`paddle_tpu.profiler.DEVICE_SCOPES`; the model
stands the whole branch under `indexer` and its projections under
`indexer/project`):
  indexer/scores   a chunk's index scores and their pull-back
  indexer/select   the causal mask, `topk_mask`, the kept count
  indexer/target   the selection's int8 form and `head_mean_probs`
  indexer/loss     log-softmax over the kept scores, the KL sum, dL_I/dI
What is left under bare `indexer` is the chunk loop's own slicing, copies
and sums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas import indexer_scores as _kernels, routing
from .pallas.attention_probs import head_mean_probs

__all__ = ["mrope_angles", "apply_rotary", "indexer_scores", "topk_mask",
           "indexer_select"]

F32 = jnp.float32
_U = jnp.uint32


def mrope_angles(positions, head_dim, theta, sections):
    """cos, sin [b, s, head_dim / 2] in float32 of multimodal rotary
    positions `positions` int [3, b, s]: frequency i of theta ** (-2i /
    head_dim) turns by row 0 for i < sections[0], by row 1 for the next
    sections[1] frequencies, by row 2 for the rest (sections in blocks).
    Three equal rows give plain RoPE."""
    half = head_dim // 2
    if sum(sections) != half:
        raise ValueError(f"mrope sections {sections} do not add up to "
                         f"{half} frequencies")
    inv = 1.0 / (F32(theta) ** (jnp.arange(half, dtype=F32) / F32(half)))
    row = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                     total_repeat_length=half)            # [half]
    pos = jnp.moveaxis(positions.astype(F32), 0, -1)       # [b, s, 3]
    ang = jnp.take(pos, row, axis=-1) * inv                # [b, s, half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """Rotate-half RoPE of x [b, s, heads, d] (or [b, s, d]) in float32."""
    if x.ndim == 4:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    d2 = x.shape[-1] // 2
    x32 = x.astype(F32)
    x1, x2 = x32[..., :d2], x32[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def indexer_scores(q_idx, k_idx, w):
    """I [t, s] float32 of one sequence's query chunk: q_idx [t, j, d],
    k_idx [s, d], w [t, j]."""
    a = jnp.einsum("tjd,sd->tjs", q_idx, k_idx,
                   preferred_element_type=F32)
    return jnp.einsum("tjs,tj->ts", jax.nn.relu(a), w.astype(F32))


def _chunk_scores(q_idx, k_idx, w, t0):
    """`indexer_scores` of the chunk whose first query is t0: on TPU the
    kernel pair of `ops/pallas/indexer_scores.py`, which leaves key tiles
    beyond the chunk's last query unwritten; the expression above
    elsewhere, and for a geometry the kernels do not take."""
    use_kernel, interpret = routing.route(
        "indexer_scores", _kernels.supports(q_idx.shape, k_idx.shape,
                                            q_idx.dtype),
        (f"q{tuple(q_idx.shape)}", f"k{tuple(k_idx.shape)}",
         str(q_idx.dtype)))
    if not use_kernel:
        return indexer_scores(q_idx, k_idx, w)
    return _kernels.causal_indexer_scores(q_idx, k_idx, w, t0,
                                          interpret=interpret)


def _sortable(x):
    """float32 -> uint32 with the same order (-0.0 counted as 0.0)."""
    u = jax.lax.bitcast_convert_type(jnp.where(x == 0, F32(0), x), _U)
    return jnp.where(u >> _U(31) == _U(1), ~u, u | _U(0x80000000))


def topk_mask(scores, valid, k):
    """bool [..., s]: the min(k, number valid) largest `scores` among
    `valid` of each row, ties to the lower index. Exact."""
    u = jnp.where(valid, _sortable(scores.astype(F32)), _U(0))

    def grow(i, prefix):
        cand = prefix | (_U(1) << (_U(31) - i.astype(_U)))
        n = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, prefix)

    # the k-th largest image of each row (0 where fewer than k are valid)
    kth = jax.lax.fori_loop(0, 32, grow,
                            jnp.zeros(u.shape[:-1], _U))[..., None]
    above = u > kth
    need = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    equal = (u == kth) & valid
    rank = jnp.cumsum(equal.astype(jnp.int32), axis=-1)
    return (above & valid) | (equal & (rank <= need))


def _chunk(x, n):
    """[s, ...] -> [n, s / n, ...]"""
    return x.reshape((n, x.shape[0] // n) + x.shape[1:])


def _select_sequence(q, k, q_idx, k_idx, w, topk, chunk, scale, tokens):
    """One sequence: (mask int8 [s, s], sum_t KL_t, kept, dq_idx, dk_idx,
    dw), the last three being d(L_I)/d(.) with L_I = sum_t KL_t /
    tokens."""
    s = q.shape[0]
    n = s // chunk
    cols = jnp.arange(s, dtype=jnp.int32)

    def step(dk_acc, xs):
        t0, qc, qic, wc = xs
        with jax.named_scope("indexer/select"):
            valid = cols[None, :] <= (
                t0 + jnp.arange(chunk, dtype=jnp.int32))[:, None]
        with jax.named_scope("indexer/scores"):
            scores, pull = jax.vjp(
                functools.partial(_chunk_scores, t0=t0), qic, k_idx, wc)
        with jax.named_scope("indexer/select"):
            keep = topk_mask(scores, valid, topk)
        # the main attention's probabilities over the kept keys, the
        # mean over heads: the indexer's target, cut from the graph
        with jax.named_scope("indexer/target"):
            selection = keep.astype(jnp.int8)
            target = head_mean_probs(qc, k, selection, scale, t0)
        with jax.named_scope("indexer/loss"):
            log_mine = jax.nn.log_softmax(
                jnp.where(keep, scores, -jnp.inf), axis=-1)
            kl = jnp.sum(jnp.where(
                keep & (target > 0),
                target * (jnp.log(jnp.where(target > 0, target, 1.0))
                          - jnp.where(keep, log_mine, 0.0)), 0.0))
            d_scores = jnp.where(keep, jnp.exp(log_mine) - target,
                                 0.0) / tokens
        with jax.named_scope("indexer/scores"):
            dqi, dki, dwc = pull(d_scores)
            dk_acc = dk_acc + dki.astype(F32)
        with jax.named_scope("indexer/select"):
            kept = jnp.sum(keep, dtype=jnp.int32)
        return dk_acc, (selection, kl, kept, dqi, dwc)

    dk_idx, (mask, kl, kept, dq_idx, dw) = jax.lax.scan(
        step, jnp.zeros(k_idx.shape, F32),
        (jnp.arange(n, dtype=jnp.int32) * chunk, _chunk(q, n), _chunk(q_idx, n),
         _chunk(w, n)))
    return (mask.reshape(s, s), jnp.sum(kl), jnp.sum(kept),
            dq_idx.reshape(q_idx.shape), dk_idx.astype(k_idx.dtype),
            dw.reshape(w.shape))


def _select(q, k, q_idx, k_idx, w, topk, chunk):
    b, s, _, d = q.shape
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of the query "
                         f"chunk {chunk}")
    one = functools.partial(_select_sequence, topk=topk, chunk=chunk,
                            scale=1.0 / d ** 0.5, tokens=b * s)
    mask, kl, kept, dqi, dki, dw = jax.lax.map(
        lambda xs: one(*xs), (q, k, q_idx, k_idx, w))
    return ((mask, jnp.sum(kl) / (b * s), jnp.sum(kept, dtype=jnp.int32)),
            (dqi, dki, dw))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def indexer_select(q, k, q_idx, k_idx, w, topk, chunk=512):
    """-> (selection int8 [b, s, s], L_I, kept keys int32).

    q [b, s, heads, d] and k [b, s, kv_heads, d] are the main
    attention's (rotated) queries and keys, q_idx [b, s, j, di], k_idx
    [b, s, di] and w [b, s, j] the indexer's. Causal. L_I differentiates
    with respect to q_idx, k_idx and w only."""
    return _select(q, k, q_idx, k_idx, w, topk, chunk)[0]


def _indexer_select_fwd(q, k, q_idx, k_idx, w, topk, chunk):
    return _select(q, k, q_idx, k_idx, w, topk, chunk)


def _indexer_select_bwd(topk, chunk, grads, cts):
    g = cts[1]
    return (None, None) + tuple((g * x).astype(x.dtype) for x in grads)


indexer_select.defvjp(_indexer_select_fwd, _indexer_select_bwd)
