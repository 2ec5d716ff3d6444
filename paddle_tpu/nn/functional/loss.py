"""Loss functionals (python/paddle/nn/functional/loss.py parity;
reference kernels cross_entropy (softmax_with_cross_entropy), bce, mse...).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.tensor import Tensor
from ...ops._dispatch import unary, binary, nary, ensure_tensor


def _reduce(out, reduction):
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0,
                  name=None):
    """softmax_with_cross_entropy parity. Computed in fp32 via log_softmax
    (numerically-stable fused form — XLA fuses the exp/sum/sub chain)."""
    if soft_label and ignore_index != -100:
        # reference cross_entropy raises here (python/paddle/nn/functional/
        # loss.py): with soft labels there is no integer class to compare
        # against ignore_index, silently ignoring it would hide a bug
        raise ValueError(
            "When soft_label == True, the value of ignore_index should "
            f"be -100 (got {ignore_index}): ignore_index is only usable "
            "with hard (integer) labels")
    input = ensure_tensor(input)
    label = ensure_tensor(label)

    def f(logits, lbl, *maybe_w):
        is_soft = soft_label or (
            lbl.ndim == logits.ndim and lbl.shape[axis] == logits.shape[axis]
            and jnp.issubdtype(lbl.dtype, jnp.floating))
        # hard-label fast path: loss = logsumexp - picked_logit. Unlike the
        # log_softmax form this never materializes (or stores as a vjp
        # residual) an fp32 [tokens, vocab] tensor — the fp32 upcast fuses
        # into the reduction and backward recomputes softmax from the
        # native-dtype logits. Same numbers, ~2x less LM-head HBM traffic
        # in bf16 training.
        if (use_softmax and not is_soft and label_smoothing == 0.0
                and not maybe_w):
            idx = lbl.astype(jnp.int32)
            if idx.ndim == logits.ndim:
                idx = jnp.squeeze(idx, axis=axis)
            safe_idx = jnp.where(idx == ignore_index, 0, idx)
            lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=axis)
            picked = jnp.take_along_axis(
                jnp.moveaxis(logits, axis, -1), safe_idx[..., None], axis=-1,
            )[..., 0].astype(jnp.float32)
            valid = idx != ignore_index
            loss = jnp.where(valid, lse - picked, 0.0)
            if reduction == "mean":
                denom = jnp.sum(valid.astype(jnp.float32))
                return jnp.sum(loss) / jnp.maximum(denom, 1.0)
            return _reduce(loss, reduction)
        x32 = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(x32, axis=axis) if use_softmax else jnp.log(jnp.maximum(x32, 1e-30))
        if is_soft:
            soft = lbl.astype(jnp.float32)
            if label_smoothing > 0:
                k = logits.shape[axis]
                soft = soft * (1 - label_smoothing) + label_smoothing / k
            loss = -jnp.sum(soft * logp, axis=axis)
        else:
            idx = lbl.astype(jnp.int32)
            if idx.ndim == logits.ndim:
                idx = jnp.squeeze(idx, axis=axis)
            k = logits.shape[axis]
            safe_idx = jnp.where(idx == ignore_index, 0, idx)
            picked = jnp.take_along_axis(
                jnp.moveaxis(logp, axis, -1),
                safe_idx[..., None],
                axis=-1,
            )[..., 0]
            if label_smoothing > 0:
                smooth_term = jnp.mean(logp, axis=axis)
                picked = (1 - label_smoothing) * picked + label_smoothing * smooth_term
            loss = -picked
            valid = idx != ignore_index
            loss = jnp.where(valid, loss, 0.0)
            if maybe_w:
                w = maybe_w[0].astype(jnp.float32)[safe_idx]
                loss = loss * jnp.where(valid, w, 0.0)
                if reduction == "mean":
                    denom = jnp.sum(jnp.where(valid, w, 0.0))
                    return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
            if reduction == "mean":
                denom = jnp.sum(valid.astype(jnp.float32))
                return jnp.sum(loss) / jnp.maximum(denom, 1.0)
        return _reduce(loss, reduction)

    inputs = [input, label]
    if weight is not None:
        inputs.append(ensure_tensor(weight))
    return nary(f, inputs, "cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    loss = loss.unsqueeze(axis)
    if return_softmax:
        from .activation import softmax

        return loss, softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    return _nll(input, label, weight, ignore_index, reduction)


def _nll(input, label, weight, ignore_index, reduction):
    input = ensure_tensor(input)
    label = ensure_tensor(label)

    def f(logp, lbl, *maybe_w):
        idx = lbl.astype(jnp.int32)
        safe_idx = jnp.where(idx == ignore_index, 0, idx)
        picked = jnp.take_along_axis(logp, safe_idx[..., None], axis=-1)[..., 0]
        loss = -picked
        valid = idx != ignore_index
        loss = jnp.where(valid, loss, 0.0)
        if maybe_w:
            w = maybe_w[0][safe_idx]
            loss = loss * jnp.where(valid, w, 0.0)
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(jnp.where(valid, w, 0.0)), 1e-12)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
        return _reduce(loss, reduction)

    inputs = [input, label]
    if weight is not None:
        inputs.append(ensure_tensor(weight))
    return nary(f, inputs, "nll_loss")


def mse_loss(input, label, reduction="mean", name=None):
    return binary(lambda a, b: _reduce(jnp.square(a - b), reduction),
                  ensure_tensor(input), ensure_tensor(label), "mse_loss")


def l1_loss(input, label, reduction="mean", name=None):
    return binary(lambda a, b: _reduce(jnp.abs(a - b), reduction),
                  ensure_tensor(input), ensure_tensor(label), "l1_loss")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def f(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d, delta * (d - 0.5 * delta))
        return _reduce(loss, reduction)

    return binary(f, ensure_tensor(input), ensure_tensor(label), "smooth_l1_loss")


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    def f(p, y, *maybe_w):
        p32 = jnp.clip(p.astype(jnp.float32), 1e-12, 1 - 1e-7)
        loss = -(y * jnp.log(p32) + (1 - y) * jnp.log(1 - p32))
        if maybe_w:
            loss = loss * maybe_w[0]
        return _reduce(loss, reduction)

    inputs = [ensure_tensor(input), ensure_tensor(label)]
    if weight is not None:
        inputs.append(ensure_tensor(weight))
    return nary(f, inputs, "bce")


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    def f(z, y, *rest):
        z32 = z.astype(jnp.float32)
        y32 = y.astype(jnp.float32)
        i = 0
        pw = None
        w = None
        if weight is not None:
            w = rest[i]; i += 1
        if pos_weight is not None:
            pw = rest[i]; i += 1
        # log(1+exp(-|z|)) stable form
        max_val = jnp.maximum(-z32, 0)
        if pw is not None:
            log_w = (pw - 1) * y32 + 1
            loss = (1 - y32) * z32 + log_w * (jnp.log(jnp.exp(-max_val) + jnp.exp(-z32 - max_val)) + max_val)
        else:
            loss = (1 - y32) * z32 + max_val + jnp.log(jnp.exp(-max_val) + jnp.exp(-z32 - max_val))
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)

    inputs = [ensure_tensor(logit), ensure_tensor(label)]
    if weight is not None:
        inputs.append(ensure_tensor(weight))
    if pos_weight is not None:
        inputs.append(ensure_tensor(pos_weight))
    return nary(f, inputs, "bce_with_logits")


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def f(lp, y):
        if log_target:
            loss = jnp.exp(y) * (y - lp)
        else:
            loss = y * (jnp.log(jnp.maximum(y, 1e-12)) - lp)
        if reduction == "batchmean":
            return jnp.sum(loss) / lp.shape[0]
        return _reduce(loss, reduction)

    return binary(f, ensure_tensor(input), ensure_tensor(label), "kl_div")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    def f(x, y):
        loss = jnp.where(y == 1, x, jnp.maximum(0.0, margin - x))
        return _reduce(loss, reduction)

    return binary(f, ensure_tensor(input), ensure_tensor(label), "hinge_embedding")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    return nary(
        lambda x1, x2, y: _reduce(jnp.maximum(0.0, -y * (x1 - x2) + margin), reduction),
        [ensure_tensor(input), ensure_tensor(other), ensure_tensor(label)],
        "margin_ranking",
    )


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean", name=None):
    def f(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / (
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1) + 1e-12
        )
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce(loss, reduction)

    return nary(f, [ensure_tensor(input1), ensure_tensor(input2), ensure_tensor(label)],
                "cosine_embedding")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2, eps=1e-6,
                        swap=False, reduction="mean", name=None):
    def f(a, pos, neg):
        dp = jnp.power(jnp.sum(jnp.power(jnp.abs(a - pos) + eps, p), axis=-1), 1 / p)
        dn = jnp.power(jnp.sum(jnp.power(jnp.abs(a - neg) + eps, p), axis=-1), 1 / p)
        if swap:
            dn2 = jnp.power(jnp.sum(jnp.power(jnp.abs(pos - neg) + eps, p), axis=-1), 1 / p)
            dn = jnp.minimum(dn, dn2)
        return _reduce(jnp.maximum(dp - dn + margin, 0.0), reduction)

    return nary(f, [ensure_tensor(input), ensure_tensor(positive), ensure_tensor(negative)],
                "triplet_margin")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def f(z, y, *maybe_norm):
        p = jax.nn.sigmoid(z.astype(jnp.float32))
        ce = binary_ce_logits_raw(z.astype(jnp.float32), y.astype(jnp.float32))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * jnp.power(1 - p_t, gamma) * ce
        if maybe_norm:
            loss = loss / maybe_norm[0]
        return _reduce(loss, reduction)

    inputs = [ensure_tensor(logit), ensure_tensor(label)]
    if normalizer is not None:
        inputs.append(ensure_tensor(normalizer))
    return nary(f, inputs, "sigmoid_focal")


def binary_ce_logits_raw(z, y):
    max_val = jnp.maximum(-z, 0)
    return (1 - y) * z + max_val + jnp.log(jnp.exp(-max_val) + jnp.exp(-z - max_val))


def square_error_cost(input, label):
    return binary(lambda a, b: jnp.square(a - b), ensure_tensor(input), ensure_tensor(label),
                  "square_error_cost")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC loss (reference nn/functional/loss.py ctc_loss over the warpctc
    kernel). TPU-first: the standard log-semiring forward algorithm as a
    `lax.scan` over time — static shapes, jit/grad-friendly; per-sample
    lengths are handled by freezing alpha past input_lengths and gathering
    the final states at 2*label_lengths.

    log_probs: [max_T, batch, num_classes] logits (log_softmax is applied
    internally, matching warpctc's built-in softmax); labels: [batch,
    max_label_len] int; reduction "mean" divides each loss by its
    label_length then averages (reference semantics).
    """
    from jax import lax

    if norm_by_times:
        raise NotImplementedError("ctc_loss norm_by_times")
    if reduction not in ("mean", "sum", "none"):
        raise ValueError(
            f"reduction should be 'mean', 'sum' or 'none', got {reduction!r}")
    log_probs = ensure_tensor(log_probs)
    labels = ensure_tensor(labels)
    input_lengths = ensure_tensor(input_lengths)
    label_lengths = ensure_tensor(label_lengths)

    def f(lp, lbl, ilen, llen):
        T, B, C = lp.shape
        L = lbl.shape[1]
        S = 2 * L + 1
        lp = jax.nn.log_softmax(lp.astype(jnp.float32), axis=-1)
        lbl = lbl.astype(jnp.int32)
        ilen = ilen.astype(jnp.int32)
        llen = llen.astype(jnp.int32)
        neg_inf = jnp.float32(-1e30)

        # extended sequence z = [blank, l1, blank, l2, ..., blank]: [B, S]
        z = jnp.full((B, S), blank, jnp.int32)
        z = z.at[:, 1::2].set(lbl)
        s_idx = jnp.arange(S)
        in_seq = s_idx[None, :] < (2 * llen[:, None] + 1)
        # skip transition allowed into odd (label) states whose label
        # differs from the one two back
        z_m2 = jnp.concatenate([jnp.full((B, 2), blank, jnp.int32),
                                z[:, :-2]], axis=1)
        allow_skip = (s_idx[None, :] >= 2) & (z != blank) & (z != z_m2)

        def emit(lp_t):
            # lp_t: [B, C] -> [B, S] log-prob of each extended state's symbol
            return jnp.take_along_axis(lp_t, z, axis=1)

        alpha0 = jnp.full((B, S), neg_inf)
        alpha0 = alpha0.at[:, 0].set(lp[0, :, blank])
        if L > 0:
            first_lbl = jnp.take_along_axis(lp[0], z[:, 1:2], axis=1)[:, 0]
            alpha0 = alpha0.at[:, 1].set(
                jnp.where(llen > 0, first_lbl, neg_inf))

        def step(alpha, inp):
            lp_t, t = inp
            a0 = alpha
            a1 = jnp.concatenate(
                [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
            a2 = jnp.concatenate(
                [jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
            a2 = jnp.where(allow_skip, a2, neg_inf)
            merged = jnp.logaddexp(jnp.logaddexp(a0, a1), a2)
            new = merged + emit(lp_t)
            new = jnp.where(in_seq, new, neg_inf)
            # freeze finished sequences (t >= their input length)
            active = (t < ilen)[:, None]
            new = jnp.where(active, new, alpha)
            return new, None

        alpha, _ = lax.scan(step, alpha0,
                            (lp[1:], jnp.arange(1, T)))
        # final: logaddexp(alpha[2*llen], alpha[2*llen - 1])
        e0 = 2 * llen
        e1 = jnp.maximum(e0 - 1, 0)
        a_end0 = jnp.take_along_axis(alpha, e0[:, None], axis=1)[:, 0]
        a_end1 = jnp.take_along_axis(alpha, e1[:, None], axis=1)[:, 0]
        a_end1 = jnp.where(llen > 0, a_end1, neg_inf)
        loss = -jnp.logaddexp(a_end0, a_end1)
        if reduction == "mean":
            return jnp.mean(loss / jnp.maximum(llen.astype(jnp.float32),
                                               1.0))
        if reduction == "sum":
            return jnp.sum(loss)
        return loss

    return nary(f, [log_probs, labels, input_lengths, label_lengths],
                "ctc_loss")


# ---------------------------------------------------------------------------
# Fused LM head: linear projection + softmax cross entropy without ever
# materializing the [tokens, vocab] logits matrix.
#
# Reference parity: the role of Paddle's fused CE stack —
# c_softmax_with_cross_entropy (paddle/phi/kernels/gpu/
# c_softmax_with_cross_entropy_kernel.cu) and fused_softmax_mask — which fuse
# the softmax/CE chain to avoid logits round-trips. TPU-first: at a 50k vocab
# the fp32 logits tensor (batch*seq x vocab) dominates the LM-head HBM traffic
# and is held across the whole backward as a vjp residual; the vocab-tiled
# kernel (ops/pallas/fused_cross_entropy.py) never builds it.
# ---------------------------------------------------------------------------


def fused_linear_cross_entropy(hidden, weight, labels, transpose_y=True,
                               ignore_index=-100, reduction="mean",
                               name=None):
    """Cross entropy of `softmax(hidden @ weight)` with the full logits
    matrix never hitting HBM: logits stream through vocab tiles — online
    logsumexp + gathered label logit in forward, d_logits folded into
    dhidden/dweight per tile in backward (ops/pallas/fused_cross_entropy.py
    — Pallas kernel on TPU, lax.scan tiles elsewhere). No [tokens, vocab]
    array exists in either pass.

    hidden: [..., H] activations; weight: [V, H] (transpose_y=True — the
    tied-embedding layout) or [H, V]; labels: int [...] matching hidden's
    leading dims. reduction "mean" averages over non-ignored tokens.
    """
    from ...ops.pallas import fused_cross_entropy as _fce

    hidden = ensure_tensor(hidden)
    weight = ensure_tensor(weight)
    labels = ensure_tensor(labels)

    def f(h, w, lbl):
        hsz = h.shape[-1]
        flat_h = h.reshape(-1, hsz)
        flat_l = lbl.reshape(-1).astype(jnp.int32)
        # kernel layout is [vocab, hidden]; an [H, V] head transposes
        # outside (AD routes dweight back through the transpose)
        w_vh = w if transpose_y else w.T
        losses = _fce.fused_cross_entropy(
            flat_h, w_vh, flat_l, ignore_index=ignore_index)
        if reduction == "none":
            return losses.reshape(lbl.shape)
        if reduction == "sum":
            return jnp.sum(losses)
        valid = (lbl.reshape(-1) != ignore_index).astype(jnp.float32)
        return jnp.sum(losses) / jnp.maximum(jnp.sum(valid), 1.0)

    return nary(f, [hidden, weight, labels], "fused_linear_cross_entropy")


def log_loss(input, label, epsilon=1e-4, name=None):
    """Negative log likelihood of probabilities (reference log_loss_kernel.h):
    -label*log(p+eps) - (1-label)*log(1-p+eps)."""
    from ...ops._dispatch import nary

    def f(p, y):
        return (-y * jnp.log(p + epsilon)
                - (1.0 - y) * jnp.log(1.0 - p + epsilon))

    return nary(f, [input, label], "log_loss")


def identity_loss(x, reduction="none"):
    """Marks a value as the loss for IPU-style pipelines (reference
    identity_loss_kernel.h); numerically the reduction of x."""
    from ...ops._dispatch import unary

    red = {0: "sum", 1: "mean", 2: "none", "sum": "sum", "mean": "mean",
           "none": "none"}[reduction]

    def f(v):
        if red == "sum":
            return jnp.sum(v)
        if red == "mean":
            return jnp.mean(v)
        return v

    return unary(f, x, "identity_loss")


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference hsigmoid_loss_kernel.h),
    default complete-binary-tree coding: num_classes-1 internal nodes;
    class c's path/code derive from the tree layout the reference uses
    (node ids from (c + num_classes) walking to the root)."""
    import numpy as np

    from ...ops._dispatch import nary

    if path_table is not None or path_code is not None:
        raise NotImplementedError(
            "custom-tree hsigmoid (path_table/path_code) is descoped — "
            "default complete-binary-tree mode only")
    # precompute per-class paths host-side (static num_classes)
    depth = int(np.ceil(np.log2(max(num_classes, 2))))
    paths = np.zeros((num_classes, depth), np.int32)
    codes = np.zeros((num_classes, depth), np.float32)
    valid = np.zeros((num_classes, depth), np.float32)
    for c in range(num_classes):
        node = c + num_classes          # leaf id in the implicit heap
        d = 0
        while node > 1 and d < depth:
            codes[c, d] = float(node % 2)
            node //= 2
            paths[c, d] = node - 1      # internal node row in weight
            valid[c, d] = 1.0
            d += 1
    pathsj = jnp.asarray(paths)
    codesj = jnp.asarray(codes)
    validj = jnp.asarray(valid)

    def f(x, y, w, *rest):
        b = rest[0] if bias is not None else None
        y = y.reshape(-1).astype(jnp.int32)   # accept [N, 1] labels
        yp = pathsj[y]                  # [N, depth]
        yc = codesj[y]
        yv = validj[y]
        wsel = w[yp]                    # [N, depth, D]
        logits = jnp.einsum("nd,nkd->nk", x.astype(jnp.float32),
                            wsel.astype(jnp.float32))
        if b is not None:
            logits = logits + b[yp].astype(jnp.float32)
        # sigmoid CE per node with target = code
        per = jnp.maximum(logits, 0) - logits * yc \
            + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        return jnp.sum(per * yv, axis=1, keepdims=True)

    args = [input, label, weight] + ([bias] if bias is not None else [])
    return nary(f, args, name="hsigmoid_loss")


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean"):
    """ArcFace/CosFace-family margin softmax CE (reference
    margin_cross_entropy_kernel.h): cos(m1*θ + m2) - m3 on the target
    logit, then scaled softmax CE. Single-group (non-model-parallel)
    path; logits are cosines in [-1, 1]."""
    from ...ops._dispatch import nary

    def f(lg, y):
        lf = lg.astype(jnp.float32)
        n = lf.shape[0]
        y = y.reshape(-1).astype(jnp.int32)   # accept [N, 1] labels
        tgt = jnp.take_along_axis(lf, y[:, None], 1)[:, 0]
        theta = jnp.arccos(jnp.clip(tgt, -1.0 + 1e-7, 1.0 - 1e-7))
        tgt_m = jnp.cos(margin1 * theta + margin2) - margin3
        onehot = jax.nn.one_hot(y, lf.shape[1], dtype=lf.dtype)
        adj = lf + onehot * (tgt_m - tgt)[:, None]
        adj = adj * scale
        lse = jax.scipy.special.logsumexp(adj, axis=1)
        loss = lse - jnp.take_along_axis(adj, y[:, None], 1)[:, 0]
        sm = jnp.exp(adj - lse[:, None])
        return loss[:, None], sm

    import jax

    loss, sm = nary(f, [logits, label], name="margin_cross_entropy")
    # Tensor-level reduction (the jnp-level _reduce would break the tape
    # — and broke "mean" outright when this fn moved here in r4)
    if reduction == "mean":
        loss = loss.mean()
    elif reduction == "sum":
        loss = loss.sum()
    if return_softmax:
        return loss, sm
    return loss


def class_center_sample(label, num_classes, num_samples, group=None):
    """Sample negative class centers (reference
    class_center_sample_kernel.h / PartialFC): returns remapped labels +
    the sampled class index set (positives first, padded with uniformly
    sampled negatives to num_samples)."""
    import numpy as np

    from ...framework import random as _random
    from ...framework.tensor import Tensor
    from ...ops._dispatch import ensure_tensor

    y = np.asarray(ensure_tensor(label)._data).astype(np.int64)
    pos = np.unique(y)
    rng = np.random.default_rng(int(_random.default_generator().seed_) + 1
                                if hasattr(_random.default_generator(),
                                           "seed_") else 0)
    neg_pool = np.setdiff1d(np.arange(num_classes), pos)
    n_neg = max(0, num_samples - len(pos))
    neg = (rng.choice(neg_pool, size=n_neg, replace=False)
           if n_neg <= len(neg_pool) else neg_pool)
    sampled = np.concatenate([pos, neg])
    remap = -np.ones(num_classes, np.int64)
    remap[sampled] = np.arange(len(sampled))
    return (Tensor._wrap(jnp.asarray(remap[y])),
            Tensor._wrap(jnp.asarray(sampled)))


