"""Pallas kernel pack tests.

Run the real kernels in interpret mode (hermetic on any backend,
pallas_guide.md debugging section) against the plain-XLA reference path.
Tolerances: flash-attn recomputes softmax from LSE in backward, so grads
carry the formulation's intrinsic f32 floor (~1e-4), not pure rounding.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa

HP = jax.lax.Precision.HIGHEST


def _ref(q, k, v, causal, scale):
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=HP).astype(jnp.float32) * scale
    if causal:
        s = logits.shape[-1]
        m = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(m, logits, -jnp.inf)
    p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v, precision=HP)


def _rand_qkv(b=2, s=128, h=3, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    return mk(), mk(), mk()


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_xla(self, causal):
        q, k, v = _rand_qkv()
        scale = 1.0 / q.shape[-1] ** 0.5
        out = fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                 interpret=True)
        want = _ref(q, k, v, causal, scale)
        assert float(jnp.max(jnp.abs(out - want))) < 2e-5

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_xla(self, causal):
        q, k, v = _rand_qkv()
        scale = 1.0 / q.shape[-1] ** 0.5

        def loss_fa(q, k, v):
            return jnp.sum(jnp.sin(fa.flash_attention(
                q, k, v, causal=causal, scale=scale, interpret=True)))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(_ref(q, k, v, causal, scale)))

        got = jax.grad(loss_fa, (0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            assert float(jnp.max(jnp.abs(g - w))) < 3e-4

    def test_multi_block_online_softmax(self):
        # force several k blocks so the online rescale path runs
        q, k, v = _rand_qkv(b=1, s=256, h=2, d=32)
        scale = 0.17
        out = fa.flash_attention(q, k, v, causal=True, scale=scale,
                                 block_q=64, block_k=64, interpret=True)
        want = _ref(q, k, v, True, scale)
        assert float(jnp.max(jnp.abs(out - want))) < 2e-5

    @pytest.mark.parametrize("causal", [False, True])
    def test_tiled_fused_backward_grads(self, causal):
        """The single-pass fused backward (dK/dV HBM accumulators via
        aliasing, in-kernel delta, qi_base causal offsets) — forced via
        explicit blocks so the single-block path can't take it."""
        q, k, v = _rand_qkv(b=1, s=256, h=2, d=32, seed=3)
        scale = 1.0 / 32 ** 0.5

        def loss_fa(q, k, v):
            return jnp.sum(jnp.sin(fa.flash_attention(
                q, k, v, causal=causal, scale=scale,
                block_q=64, block_k=64, interpret=True)))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(_ref(q, k, v, causal, scale)))

        got = jax.grad(loss_fa, (0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            assert float(jnp.max(jnp.abs(g - w))) < 3e-4

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("seq", [80, 208])
    def test_backward_non_tile_multiple_seq(self, causal, seq):
        """Non-multiple-of-128 (but %16) lengths take the single-block
        path; parity-check BACKWARD there too, not just tile-aligned
        forward shapes (ISSUE 7 satellite)."""
        q, k, v = _rand_qkv(b=2, s=seq, h=2, d=32, seed=11)
        scale = 1.0 / 32 ** 0.5
        out = fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                 interpret=True)
        want = _ref(q, k, v, causal, scale)
        assert float(jnp.max(jnp.abs(out - want))) < 2e-5

        def loss_fa(q, k, v):
            return jnp.sum(jnp.sin(fa.flash_attention(
                q, k, v, causal=causal, scale=scale, interpret=True)))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(_ref(q, k, v, causal, scale)))

        got = jax.grad(loss_fa, (0, 1, 2))(q, k, v)
        wantg = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for g, w in zip(got, wantg):
            assert float(jnp.max(jnp.abs(g - w))) < 3e-4

    def test_bf16(self):
        q, k, v = _rand_qkv(dtype=jnp.bfloat16)
        out = fa.flash_attention(q, k, v, causal=True, interpret=True)
        want = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), True, 1.0 / 8.0)
        assert out.dtype == jnp.bfloat16
        assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - want))) < 3e-2

    def test_supports_gate(self):
        assert fa.supports((2, 1024, 8, 64), jnp.bfloat16, True)
        assert not fa.supports((2, 1021, 8, 64), jnp.float32, True)  # prime seq
        assert not fa.supports((2, 1024, 8, 512), jnp.float32, True)  # huge d


class TestFunctionalIntegration:
    def test_sdpa_routes_to_pallas(self, monkeypatch):
        """With the min-seqlen flag lowered, F.scaled_dot_product_attention
        must route through the pallas kernel and agree with the XLA path."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu.utils import flags

        calls = {}
        orig = fa.flash_attention

        def spy(*a, **kw):
            calls["hit"] = True
            kw.setdefault("interpret", True)
            return orig(*a, **kw)

        monkeypatch.setattr(fa, "flash_attention", spy)
        # off TPU the kernel slot is reached only through the explicit
        # interpret flag (ops/pallas/routing.py)
        flags.set_flags({"FLAGS_pallas_flash_min_seqlen": 64,
                         "FLAGS_pallas_force_interpret": True})
        try:
            q, k, v = _rand_qkv(b=1, s=64, h=2, d=32)
            qt, kt, vt = (paddle.to_tensor(np.asarray(x)) for x in (q, k, v))
            out = F.scaled_dot_product_attention(qt, kt, vt, is_causal=True)
            assert calls.get("hit"), "pallas path not taken"
            want = _ref(q, k, v, True, 1.0 / 32 ** 0.5)
            np.testing.assert_allclose(np.asarray(out._data), np.asarray(want),
                                       atol=2e-5)
        finally:
            flags.set_flags({"FLAGS_pallas_flash_min_seqlen": 1024,
                             "FLAGS_pallas_force_interpret": False})

    def test_sdpa_backward_through_pallas(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu.utils import flags

        flags.set_flags({"FLAGS_pallas_flash_min_seqlen": 64,
                         "FLAGS_pallas_force_interpret": True})
        try:
            qn = np.random.default_rng(1).standard_normal(
                (1, 64, 2, 32)).astype(np.float32)
            q = paddle.to_tensor(qn, stop_gradient=False)
            k = paddle.to_tensor(qn * 0.5, stop_gradient=False)
            v = paddle.to_tensor(qn * 0.25, stop_gradient=False)
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            out.sum().backward()
            assert q.grad is not None and np.isfinite(
                np.asarray(q.grad._data)).all()
        finally:
            flags.set_flags({"FLAGS_pallas_flash_min_seqlen": 1024,
                             "FLAGS_pallas_force_interpret": False})
