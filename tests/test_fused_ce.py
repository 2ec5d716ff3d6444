"""fused_linear_cross_entropy: numeric parity (loss + grads) against the
unfused matmul→cross_entropy path, which is itself OpTest-verified.
Reference role: c_softmax_with_cross_entropy / fused CE kernels
(paddle/phi/kernels/gpu/c_softmax_with_cross_entropy_kernel.cu)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import ops


def _setup(n=37, h=16, v=53, ignore=None, seed=0):
    rng = np.random.default_rng(seed)
    hidden = paddle.to_tensor(rng.standard_normal((n, h)), dtype="float32")
    weight = paddle.to_tensor(rng.standard_normal((v, h)) * 0.1,
                              dtype="float32")
    lbl = rng.integers(0, v, (n,))
    if ignore is not None:
        lbl[:: 5] = ignore
    labels = paddle.to_tensor(lbl, dtype="int64")
    return hidden, weight, labels


def _unfused(hidden, weight, labels, reduction, ignore_index):
    logits = ops.matmul(hidden, weight, transpose_y=True)
    return F.cross_entropy(logits, labels, reduction=reduction,
                           ignore_index=ignore_index)


@pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
def test_fused_ce_loss_parity(reduction):
    hidden, weight, labels = _setup()
    got = F.fused_linear_cross_entropy(hidden, weight, labels,
                                       reduction=reduction)
    want = _unfused(hidden, weight, labels, reduction, -100)
    np.testing.assert_allclose(np.asarray(got._data), np.asarray(want._data),
                               rtol=2e-5, atol=2e-5)


def test_fused_ce_ignore_index_and_grads():
    hidden, weight, labels = _setup(ignore=-1)
    hidden.stop_gradient = False
    weight.stop_gradient = False
    loss = F.fused_linear_cross_entropy(hidden, weight, labels,
                                        ignore_index=-1)
    loss.backward()
    gh, gw = np.asarray(hidden.grad._data), np.asarray(weight.grad._data)

    hidden2, weight2, labels2 = _setup(ignore=-1)
    hidden2.stop_gradient = False
    weight2.stop_gradient = False
    loss2 = _unfused(hidden2, weight2, labels2, "mean", -1)
    loss2.backward()
    np.testing.assert_allclose(float(loss._data), float(loss2._data),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(gh, np.asarray(hidden2.grad._data),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(gw, np.asarray(weight2.grad._data),
                               rtol=2e-4, atol=2e-5)


def test_fused_ce_untransposed_weight():
    hidden, weight, labels = _setup()
    w_hv = paddle.to_tensor(np.asarray(weight._data).T.copy())
    w_hv.stop_gradient = False
    loss = F.fused_linear_cross_entropy(hidden, w_hv, labels,
                                        transpose_y=False)
    loss.backward()
    weight.stop_gradient = False
    want = _unfused(hidden, weight, labels, "mean", -100)
    want.backward()
    np.testing.assert_allclose(float(loss._data), float(want._data),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(w_hv.grad._data),
                               np.asarray(weight.grad._data).T,
                               rtol=2e-4, atol=2e-5)


def test_gpt_model_fused_loss_parity():
    from paddle_tpu.models import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=16,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(1)
    ids = paddle.to_tensor(rng.integers(0, 97, (2, 16)), dtype="int64")
    labels = paddle.to_tensor(rng.integers(0, 97, (2, 16)), dtype="int64")
    mask = paddle.to_tensor((rng.random((2, 16)) > 0.3).astype("float32"))

    crit = GPTPretrainingCriterion()
    want = crit(model(ids), labels, mask)
    got = model.loss(ids, labels, mask)
    np.testing.assert_allclose(float(got._data), float(want._data),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# vocab-tiled streaming CE (ops/pallas/fused_cross_entropy.py, ISSUE 7):
# interpret-mode kernel == XLA tile scan == the unfused dense path, for
# loss AND both gradients.
# ---------------------------------------------------------------------------

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import fused_cross_entropy as fce


def _dense_ref(h, w, lbl, ii):
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
    lse = jax.nn.logsumexp(logits, axis=-1)
    safe = jnp.where(lbl == ii, 0, lbl)
    picked = jnp.take_along_axis(logits, safe[:, None], -1)[:, 0]
    return jnp.where(lbl != ii, lse - picked, 0.0)


@pytest.mark.parametrize("n,vocab,ii", [(64, 256, -100), (100, 384, -1)])
def test_vocab_tiled_kernel_parity(n, vocab, ii):
    """Interpret kernel vs XLA tiles vs dense: loss, dhidden, dweight.
    n=100 exercises the token-tile padding path."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((n, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((vocab, 32)) * 0.1, jnp.float32)
    lbl = rng.integers(0, vocab, (n,))
    lbl[::5] = ii
    lbl = jnp.asarray(lbl, jnp.int32)

    def kern(h, w):
        return jnp.sum(jnp.sin(fce.fused_cross_entropy(
            h, w, lbl, ignore_index=ii, interpret=True)))

    def xla(h, w):
        return jnp.sum(jnp.sin(fce.fused_cross_entropy(
            h, w, lbl, ignore_index=ii, use_kernel=False)))

    def dense(h, w):
        return jnp.sum(jnp.sin(_dense_ref(h, w, lbl, ii)))

    lk, lx, ld = kern(h, w), xla(h, w), dense(h, w)
    assert abs(float(lk) - float(lx)) < 1e-4
    assert abs(float(lk) - float(ld)) < 1e-4
    gk = jax.grad(kern, (0, 1))(h, w)
    gx = jax.grad(xla, (0, 1))(h, w)
    gd = jax.grad(dense, (0, 1))(h, w)
    for a, b, c in zip(gk, gx, gd):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-4
        assert float(jnp.max(jnp.abs(a - c))) < 2e-4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n,vocab,block_v", [
    (64, 200, None),       # one 256-column tile, 56 of them beyond the head
    (100, 53, None),       # a head narrower than a tile; padded token rows
    (96, 432, 128),        # 3 x 128 + 48: keye's last tile (18,992 % 128)
    (96, 432, None),       # the same head as one 512-column tile
])
def test_a_last_partial_vocab_tile_is_masked_inside_the_kernel(n, vocab,
                                                               block_v,
                                                               dtype):
    """A vocabulary that is not whole tiles (a vocabulary-parallel slice
    of 18,992 rows): interpret kernel vs XLA tiles vs dense, loss, dh and
    dW each; labels inside the partial tile, the label `vocab - 1`,
    `ignore_index` rows. Then the same through the internal calls with W
    (and the dW accumulator) stored PADDED to whole tiles and NaN planted
    in the rows beyond `vocab`: a boundary block's out-of-range rows may
    hold anything, and none of it may reach loss, lse, dh or dW's rows."""
    dtype = jnp.dtype(dtype)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2   # of the largest entry
    rng = np.random.default_rng(vocab)
    hidden = 32
    h = jnp.asarray(rng.standard_normal((n, hidden)), dtype)
    w = jnp.asarray(rng.standard_normal((vocab, hidden)) * 0.1, dtype)
    lbl = rng.integers(0, vocab, (n,))
    lbl[::5] = -100
    lbl[1], lbl[2], lbl[3] = vocab - 1, vocab - 2, vocab - vocab % 128
    lbl = jnp.asarray(lbl, jnp.int32)
    seed = jnp.asarray(rng.standard_normal((n,)), jnp.float32)

    def through(loss_fn):
        def total(h, w):
            losses = loss_fn(h, w)
            return jnp.sum(losses * seed), losses
        (_, losses), (dh, dw) = jax.value_and_grad(
            total, (0, 1), has_aux=True)(h, w)
        return losses, dh.astype(jnp.float32), dw.astype(jnp.float32)

    kern = through(lambda h, w: fce.fused_cross_entropy(
        h, w, lbl, block_v=block_v, interpret=True))
    xla = through(lambda h, w: fce.fused_cross_entropy(
        h, w, lbl, block_v=block_v, use_kernel=False))
    dense = through(lambda h, w: _dense_ref(h, w, lbl, -100))
    assert kern[2].shape == (vocab, hidden)
    for got, a, b in zip(kern, xla, dense):
        assert not bool(jnp.isnan(got).any())
        for want in (a, b):
            assert float(jnp.max(jnp.abs(got - want))) \
                < tol * max(1.0, float(jnp.max(jnp.abs(want))))

    # the internal calls on storage padded to whole tiles, NaN beyond
    bn = fce._pick_block_n(n)
    bv = block_v or fce._pick_block_v(vocab, hidden, dtype.itemsize, bn)
    pad_n, pad_v = (-n) % bn, (-vocab) % bv
    assert pad_v                     # every case has a partial last tile
    hp = fce._pad_rows(h, pad_n, 0)
    lblp = fce._lane_bcast(fce._pad_rows(lbl, pad_n, -100), jnp.int32)
    w_nan = fce._pad_rows(w, pad_v, jnp.nan)
    assert bool(jnp.isnan(w_nan[vocab:]).all())
    losses, lse = fce._fwd_pallas(hp, w_nan, lblp, bn, bv, -100, True,
                                  vocab=vocab)
    np.testing.assert_array_equal(np.asarray(losses[:n]),
                                  np.asarray(kern[0]))
    assert not bool(jnp.isnan(lse).any())
    g = jnp.where(lbl != -100, seed, 0.0)
    dh, dw = fce._bwd_pallas(
        hp, w_nan, lblp, fce._lane_bcast(lse, jnp.float32),
        fce._lane_bcast(fce._pad_rows(g, pad_n, 0), jnp.float32), bn, bv,
        True, vocab=vocab)
    assert dw.shape == w_nan.shape   # the accumulator has the storage's rows
    np.testing.assert_array_equal(
        np.asarray(dh[:n].astype(jnp.float32)), np.asarray(kern[1]))
    np.testing.assert_array_equal(
        np.asarray(dw[:vocab].astype(dtype).astype(jnp.float32)),
        np.asarray(kern[2]))


def test_vocab_tiled_ignored_rows_zero_grads():
    """An all-ignored batch must yield exactly zero dh/dw (the masked
    cotangent can't leak the recomputed softmax term)."""
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 8)), jnp.float32)
    lbl = jnp.full((16,), -100, jnp.int32)
    gh, gw = jax.grad(
        lambda h, w: jnp.sum(fce.fused_cross_entropy(
            h, w, lbl, interpret=True)), (0, 1))(h, w)
    assert float(jnp.max(jnp.abs(gh))) == 0.0
    assert float(jnp.max(jnp.abs(gw))) == 0.0


def test_vocab_tiled_bf16():
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((32, 16)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((256, 16)) * 0.1, jnp.bfloat16)
    lbl = jnp.asarray(rng.integers(0, 256, (32,)), jnp.int32)
    got = fce.fused_cross_entropy(h, w, lbl, interpret=True)
    want = _dense_ref(h, w, lbl, -100)
    assert float(jnp.max(jnp.abs(got - want))) < 3e-2


def test_supports_gate():
    assert fce.supports(50304, 2048, jnp.bfloat16)   # the bench vocab
    assert fce.supports(384, 32, jnp.float32)
    assert fce.supports(53, 32, jnp.float32)    # a partial tile is masked
    assert fce.supports(18992, 2048, jnp.bfloat16)   # keye's sliced head
    assert not fce.supports(256, 32, jnp.int32)


@pytest.mark.parametrize("vocab,hidden,itemsize,want", [
    (50304, 2048, 2, 128),      # the GPT cells' head: 128 alone divides it
    (50304, 1024, 2, 128),
    (24576, 1024, 2, 512),      # narrow rows: the widest tile fits
    (24576, 2304, 2, 128),      # 512 rows: 25.5 MiB of a v5e's 16 (PR 35)
    (24576, 2304, 4, 128),      # nothing fits: the narrowest
    (16384, 2688, 2, 128),      # nothing fits: the narrowest (PR 39)
    (18992, 2048, 2, 128),      # keye's slice: 19,072 = 149 x 128
    (1000, 64, 4, 512),         # 1,024 columns in two tiles, 24 beyond
    (53, 32, 4, 128),
])
def test_the_vocab_tile_fits_the_backwards_vmem(vocab, hidden, itemsize,
                                                want):
    """The tile divides the vocabulary ROUNDED UP to whole lanes: a head
    of whole lanes keeps the tile it had."""
    assert fce._pick_block_v(vocab, hidden, itemsize) == want
    whole = -(-vocab // 128) * 128
    assert fce._pick_block_v(vocab) == max(      # no width given: as before
        bv for bv in (512, 256, 128) if whole % bv == 0)


def test_cross_entropy_soft_label_ignore_index_raises():
    """Reference parity regression (ISSUE 7 satellite): ignore_index has
    no meaning for soft labels — the reference raises, we silently
    ignored it."""
    logits = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((4, 8)), dtype="float32")
    soft = paddle.to_tensor(np.full((4, 8), 1 / 8), dtype="float32")
    with pytest.raises(ValueError, match="ignore_index"):
        F.cross_entropy(logits, soft, soft_label=True, ignore_index=3)
    # the default -100 sentinel stays legal with soft labels
    loss = F.cross_entropy(logits, soft, soft_label=True)
    assert np.isfinite(float(loss._data))
