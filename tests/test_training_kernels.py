"""Training-kernel integration (ISSUE 7): the splash-attention + fused-CE
kernels wired into the scan train steps — parity vs the unfused paths,
zero added retraces (with and without segment ids), and the HLO probe
asserting the [tokens, vocab] logits / [b, h, s, s] scores never exist
in the compiled step."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.jit import FusedScanTrainStep, TrainStep
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
)
from paddle_tpu.ops.pallas.routing import forbidden_shapes
from paddle_tpu.utils import flags as _flags

TINY = dict(vocab_size=384, hidden_size=32, num_layers=2,
            num_attention_heads=2, max_position_embeddings=128,
            hidden_dropout_prob=0.0, attention_dropout_prob=0.0)

# kernels ON, interpret-forced so the CPU runs the real kernel code
# paths (not their XLA paths)
KERNEL_FLAGS = {"FLAGS_splash_attn": True,
                "FLAGS_pallas_force_interpret": True,
                "FLAGS_pallas_flash_min_seqlen": 128}
# the stock path: dense attention, and (through the criterion on the
# model's logits, not model.loss) the dense head
STOCK_FLAGS = {"FLAGS_splash_attn": False,
               "FLAGS_pallas_force_interpret": False,
               "FLAGS_pallas_flash_min_seqlen": 128}


@pytest.fixture
def restore_flags():
    saved = {k: _flags.get_flag(k) for k in KERNEL_FLAGS}
    yield
    _flags.set_flags(saved)


def _batch(b=2, s=128, seed=3):
    rng = np.random.default_rng(seed)
    return (paddle.to_tensor(rng.integers(0, TINY["vocab_size"], (b, s)),
                             dtype="int64"),
            paddle.to_tensor(rng.integers(0, TINY["vocab_size"], (b, s)),
                             dtype="int64"))


def _assert_params_close(m_a, m_b, tol):
    """Largest elementwise gap of each parameter, over its largest
    magnitude."""
    pb = dict(m_b.named_parameters())
    for name, p in m_a.named_parameters():
        a, b = np.asarray(p._data), np.asarray(pb[name]._data)
        rel = np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)
        assert rel < tol, (name, rel)


def _train(kind, steps, ids, labels, lr=1e-2):
    """Both kinds train the SAME scan_layers architecture (identical
    init draws); only the step machinery differs — eager TrainStep over
    the generic scan forward and the dense head vs the fused
    in-scan-update step."""
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(scan_layers=True, **TINY))
    opt = popt.AdamW(learning_rate=lr, parameters=model.parameters())
    if kind == "fused":
        step = FusedScanTrainStep(model, opt, fused_head=True)
    else:
        crit = GPTPretrainingCriterion()
        step = TrainStep(model, lambda m, a, b: crit(m(a), b), opt)
    losses = [float(step(ids, labels)) for _ in range(steps)]
    return model, step, losses


def test_fused_scan_step_kernel_parity(restore_flags):
    """FusedScanTrainStep with BOTH kernels engaged (interpret mode) ==
    eager TrainStep on the stock dense paths over the SAME scan model:
    loss trajectory + final params at fp32 tolerance, compile count 1."""
    ids, labels = _batch()
    _flags.set_flags(KERNEL_FLAGS)
    m_f, step_f, loss_f = _train("fused", 3, ids, labels)
    assert step_f._jitted._cache_size() == 1
    _flags.set_flags(STOCK_FLAGS)
    m_e, _, loss_e = _train("eager", 3, ids, labels)

    assert max(abs(a - b) for a, b in zip(loss_f, loss_e)) < 5e-4
    _assert_params_close(m_f, m_e, 5e-3)


def test_fused_scan_step_segments_no_retrace(restore_flags):
    """Segment ids ride the compiled step as a normal traced arg: the
    same executable serves every step with segments (one trace for the
    no-seg signature, one for the seg signature, none beyond)."""
    _flags.set_flags(KERNEL_FLAGS)
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(scan_layers=True, **TINY))
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = FusedScanTrainStep(model, opt, fused_head=True)
    ids, labels = _batch()
    seg = paddle.to_tensor(
        np.repeat([[0] * 64 + [1] * 64], 2, 0), dtype="int32")
    losses_seg = [float(step(ids, labels, segment_ids=seg))
                  for _ in range(2)]
    assert step._jitted._cache_size() == 1
    losses = [float(step(ids, labels)) for _ in range(2)]
    assert step._jitted._cache_size() == 2   # one more for the no-seg sig
    float(step(ids, labels, segment_ids=seg))
    assert step._jitted._cache_size() == 2   # both signatures stay warm
    # the segment mask must actually change the math
    assert abs(losses_seg[0] - losses[0]) > 1e-6
    assert all(np.isfinite(losses_seg + losses))


def test_segmented_scan_step_matches_eager_segmented(restore_flags):
    """Packed-sequence training end to end: the fused scan step with
    segment ids == eager TrainStep feeding the same segments through
    model.loss, at fp32 tolerance.

    Adam's epsilon is 1e-6 here, not the default 1e-8. The first update
    is lr * g / (|g| + eps), so where |g| is of the order of eps it
    multiplies the rounding difference between two reduction orders by
    up to lr / eps. With 1e-8 one fc1 element of 8,192 has |g| = 3e-10:
    its update was 0.031 lr in one program and 0.097 lr in the other
    (6.6e-4 apart, 0.0069 of the largest weight; the XLA-path fused
    step shows the same, so no kernel is involved) while every other
    element agreed to 9e-7. At 1e-6 that worst case is 1e-5, the max
    over elements measures the step and not one element's rounding, and
    the bound is 1e-3 (the largest found: 1.4e-4, fc2.weight)."""
    ids, labels = _batch()
    seg_np = np.repeat([[0] * 48 + [1] * 80], 2, 0)
    seg = paddle.to_tensor(seg_np, dtype="int32")

    def build():
        paddle.seed(7)
        m = GPTForCausalLM(GPTConfig(scan_layers=True, **TINY))
        opt = popt.AdamW(learning_rate=1e-2, epsilon=1e-6,
                         parameters=m.parameters())
        return m, opt

    _flags.set_flags(KERNEL_FLAGS)
    m_f, opt_f = build()
    step_f = FusedScanTrainStep(m_f, opt_f, fused_head=True)
    loss_f = [float(step_f(ids, labels, segment_ids=seg))
              for _ in range(2)]

    _flags.set_flags(STOCK_FLAGS)
    m_e, opt_e = build()
    crit = GPTPretrainingCriterion()
    step_e = TrainStep(
        m_e, lambda m, a, b: crit(m(a, segment_ids=seg), b), opt_e)
    loss_e = [float(step_e(ids, labels)) for _ in range(2)]

    assert max(abs(a - b) for a, b in zip(loss_f, loss_e)) < 5e-4
    _assert_params_close(m_f, m_e, 1e-3)


def test_hlo_probe_no_logits_no_scores(restore_flags):
    """The compiled fused train step with both kernels engaged holds no
    [tokens, vocab] logits and no [b, h, s, s] scores. seq=256 here so
    score-shaped [s, s] is distinguishable from the lane-replicated
    [*, 128] kernel stat planes."""
    b, s = 2, 256
    _flags.set_flags(KERNEL_FLAGS)
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        scan_layers=True, **{**TINY, "max_position_embeddings": s}))
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = FusedScanTrainStep(model, opt, fused_head=True)
    step.ensure_built()
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, TINY["vocab_size"], (b, s)), jnp.int32)
    text = step._jitted.lower(
        step._extract_state(), jnp.float32(1e-3), ids, ids,
        None).compile().as_text()
    bad = forbidden_shapes(text, b, s, TINY["vocab_size"])
    assert not bad, f"forbidden buffers in train-step HLO: {bad[:5]}"


def test_forbidden_shapes_probe_detects_dense():
    """The probe itself must flag the buffers it exists to forbid."""
    assert forbidden_shapes("f32[2,128,384] x", 2, 128, 384)
    assert forbidden_shapes("f32[256,384] x", 2, 128, 384)
    assert forbidden_shapes("bf16[2,2,128,128] x", 2, 128, 384)
    # params, grads and kernel tiles stay legal
    assert not forbidden_shapes(
        "f32[384,32] f32[128,384] f32[2,128,32] f32[128,128] x",
        2, 128, 384)


def test_kernels_under_checkpoint_scan(restore_flags):
    """Custom-VJP kernels must trace under jax.checkpoint + lax.scan
    (the recompute path): the remat replay re-runs the splash/CE
    forwards inside the stored jaxpr."""
    _flags.set_flags(KERNEL_FLAGS)
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(scan_layers=True, use_recompute=True,
                                 **TINY))
    opt = popt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = TrainStep(m, lambda mm, a, b: mm.loss(a, b), opt)
    ids, labels = _batch(seed=5)
    losses = [float(step(ids, labels)) for _ in range(2)]
    assert all(np.isfinite(losses)) and losses[1] < losses[0]
