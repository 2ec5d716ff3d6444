"""FusedScanTrainStep parity: the in-scan-optimizer reverse scan must
produce the same training trajectory as the generic TrainStep over the
same scan_layers model (tight, fp32) and over the unrolled model (loose,
bf16 reorder tolerance). This is the memory-bounded path that makes the
gpt3-1.3b north star fit one 16G chip (jit/fused_scan_step.py docstring;
docs/DECISIONS.md §7)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.jit import FusedScanTrainStep, TrainStep
from paddle_tpu.models import (
    GPTForCausalLM, GPTPretrainingCriterion, GPTConfig,
)

TINY = dict(vocab_size=96, hidden_size=32, num_layers=3,
            num_attention_heads=2, max_position_embeddings=16,
            hidden_dropout_prob=0.0, attention_dropout_prob=0.0)


def _batch(bs=4, seq=16, vocab=96, seed=0):
    rng = np.random.default_rng(seed)
    ids = paddle.to_tensor(rng.integers(0, vocab, (bs, seq)), dtype="int64")
    labels = paddle.to_tensor(rng.integers(0, vocab, (bs, seq)),
                              dtype="int64")
    return ids, labels


def _run(step_cls, scan_layers, steps=4, bf16=False, tie=True,
         opt_kw=None, **cfg_over):
    cfg = GPTConfig(**{**TINY, **cfg_over}, scan_layers=scan_layers,
                    tie_word_embeddings=tie)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if bf16:
        model.bfloat16()
    crit = GPTPretrainingCriterion()
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters(),
                     **(opt_kw or {}))
    if step_cls is TrainStep:
        step = TrainStep(model, lambda m, a, b: crit(m(a), b), opt)
    else:
        step = FusedScanTrainStep(model, opt, criterion=crit)
    ids, labels = _batch(vocab=cfg.vocab_size)
    losses = [float(step(ids, labels)) for _ in range(steps)]
    return losses, model


def test_parity_fp32_vs_scan_trainstep():
    """fp32, same scan structure: trajectories must agree to fp32 noise."""
    base, m_base = _run(TrainStep, scan_layers=True)
    fused, m_fused = _run(FusedScanTrainStep, scan_layers=True)
    np.testing.assert_allclose(base, fused, rtol=2e-5, atol=1e-6)
    for (n1, p1), (n2, p2) in zip(m_base.named_parameters(),
                                  m_fused.named_parameters()):
        assert n1 == n2
        np.testing.assert_allclose(
            np.asarray(p1._data, np.float32),
            np.asarray(p2._data, np.float32), rtol=1e-4, atol=1e-5,
            err_msg=n1)


def test_parity_fp32_vs_unrolled_trainstep():
    """fp32 vs the unrolled tape path (different program, same math).
    The stacked init draws RNG in different shapes than per-layer init,
    so the unrolled model's weights are copied into the scan model."""
    import jax.numpy as jnp

    cfg_u = GPTConfig(**TINY, scan_layers=False)
    paddle.seed(0)
    m_u = GPTForCausalLM(cfg_u)
    cfg_s = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    m_s = GPTForCausalLM(cfg_s)
    blocks = m_s.gpt.blocks
    tmpl_names = [n for n, _ in blocks._template.named_parameters()]
    for flat, pname in blocks._stacked_names:
        assert pname in tmpl_names
        per_layer = []
        for blk in m_u.gpt.blocks:
            d = dict(blk.named_parameters())
            per_layer.append(d[pname]._data)
        blocks._parameters[flat]._data = jnp.stack(per_layer)
    u_outer = dict(m_u.named_parameters())
    for n, p in m_s.named_parameters():
        if "blocks__" not in n:
            # fresh copy: step_u donates its state buffers, which would
            # delete an aliased array out from under the scan model
            p._data = jnp.array(u_outer[n]._data)

    crit = GPTPretrainingCriterion()
    opt_u = popt.AdamW(learning_rate=1e-3, parameters=m_u.parameters())
    step_u = TrainStep(m_u, lambda m, a, b: crit(m(a), b), opt_u)
    opt_s = popt.AdamW(learning_rate=1e-3, parameters=m_s.parameters())
    step_s = FusedScanTrainStep(m_s, opt_s, criterion=crit)
    ids, labels = _batch(vocab=TINY["vocab_size"])
    base = [float(step_u(ids, labels)) for _ in range(4)]
    fused = [float(step_s(ids, labels)) for _ in range(4)]
    np.testing.assert_allclose(base, fused, rtol=5e-4, atol=1e-5)


def test_parity_bench_config_bf16_masters():
    """The 1.3b bench layout: bf16 params + fp32 masters + bf16 moments."""
    kw = dict(opt_kw=dict(multi_precision=True, moment_dtype="bfloat16"),
              bf16=True)
    base, _ = _run(TrainStep, scan_layers=True, **kw)
    fused, m = _run(FusedScanTrainStep, scan_layers=True, **kw)
    np.testing.assert_allclose(base, fused, rtol=3e-2, atol=1e-2)


def test_untied_head():
    fused, m = _run(FusedScanTrainStep, scan_layers=True, tie=False)
    assert np.isfinite(fused).all() and fused[-1] < fused[0]
    assert m.lm_head is not None


def test_loss_decreases_and_state_advances():
    fused, m = _run(FusedScanTrainStep, scan_layers=True, steps=6)
    assert fused[-1] < fused[0]


def test_rejects_unrolled_model_and_unsupported_clip():
    cfg = GPTConfig(**TINY, scan_layers=False)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    with pytest.raises(ValueError, match="scan_layers"):
        FusedScanTrainStep(model, opt)

    import paddle_tpu.nn as nn

    # ClipGradByGlobalNorm and ClipGradByValue are SUPPORTED now (the
    # deferred-norm two-pass / elementwise in-scan paths); per-tensor
    # ClipGradByNorm needs a whole stacked leaf's grad — precise error
    cfg2 = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    model2 = GPTForCausalLM(cfg2)
    opt2 = popt.AdamW(learning_rate=1e-3, parameters=model2.parameters(),
                      grad_clip=nn.ClipGradByGlobalNorm(1.0))
    FusedScanTrainStep(model2, opt2)   # accepted

    paddle.seed(0)
    model3 = GPTForCausalLM(GPTConfig(**TINY, scan_layers=True))
    opt3 = popt.AdamW(learning_rate=1e-3, parameters=model3.parameters(),
                      grad_clip=nn.ClipGradByNorm(1.0))
    with pytest.raises(ValueError, match="ClipGradByNorm"):
        FusedScanTrainStep(model3, opt3)


def test_global_norm_clip_parity():
    """ClipGradByGlobalNorm via the deferred-norm two-pass must track the
    eager TrainStep trajectory exactly in fp32. lr is large so the clip
    is ACTIVE (scale < 1) from step 1 — an inert clip would pass
    trivially."""
    import paddle_tpu.nn as nn

    kw = dict(opt_kw=dict(grad_clip=nn.ClipGradByGlobalNorm(0.1)))
    base, m_base = _run(TrainStep, scan_layers=True, **kw)
    fused, m_fused = _run(FusedScanTrainStep, scan_layers=True, **kw)
    np.testing.assert_allclose(base, fused, rtol=2e-5, atol=1e-6)
    for (n1, p1), (n2, p2) in zip(m_base.named_parameters(),
                                  m_fused.named_parameters()):
        np.testing.assert_allclose(
            np.asarray(p1._data, np.float32),
            np.asarray(p2._data, np.float32), rtol=1e-4, atol=1e-5,
            err_msg=n1)


def test_value_clip_parity():
    import paddle_tpu.nn as nn

    kw = dict(opt_kw=dict(grad_clip=nn.ClipGradByValue(0.001)))
    base, _ = _run(TrainStep, scan_layers=True, **kw)
    fused, _ = _run(FusedScanTrainStep, scan_layers=True, **kw)
    np.testing.assert_allclose(base, fused, rtol=2e-5, atol=1e-6)


def test_dropout_deterministic_and_trains():
    """Dropout inside the scan: the per-layer PRNG offset scheme must be
    deterministic across fresh builds (same seed -> bit-identical
    trajectory) and actually active (differs from the p=0 trajectory)."""
    kw = dict(hidden_dropout_prob=0.1, attention_dropout_prob=0.0)
    a, _ = _run(FusedScanTrainStep, scan_layers=True, steps=3, **kw)
    b, _ = _run(FusedScanTrainStep, scan_layers=True, steps=3, **kw)
    assert a == b, (a, b)
    base, _ = _run(FusedScanTrainStep, scan_layers=True, steps=3)
    assert a != base
    assert np.isfinite(a).all()


def test_fused_head_parity():
    """fused_head (chunked-logsumexp CE) must match the dense criterion
    head: same trajectory in fp32."""
    base, _ = _run(FusedScanTrainStep, scan_layers=True)
    cfg = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = FusedScanTrainStep(model, opt, fused_head=True)
    ids, labels = _batch(vocab=cfg.vocab_size)
    fused = [float(step(ids, labels)) for _ in range(4)]
    np.testing.assert_allclose(base, fused, rtol=2e-5, atol=1e-6)


def test_compute_dtype_fp32_master_layout():
    """compute_dtype='bfloat16' with fp32-stored params must track the
    bf16-params+fp32-masters TrainStep trajectory (initial masters differ
    by one bf16 rounding of the init, hence the loose tolerance), with no
    master_weights allocated at all."""
    kw = dict(opt_kw=dict(multi_precision=True, moment_dtype="bfloat16"),
              bf16=True)
    base, _ = _run(TrainStep, scan_layers=True, **kw)

    cfg = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)          # stays fp32
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters(),
                     moment_dtype="bfloat16")
    step = FusedScanTrainStep(model, opt, compute_dtype="bfloat16")
    ids, labels = _batch(vocab=cfg.vocab_size)
    fused = [float(step(ids, labels)) for _ in range(4)]
    np.testing.assert_allclose(base, fused, rtol=3e-2, atol=1e-2)
    assert not opt._master_weights
    import jax.numpy as jnp
    assert all(p._data.dtype == jnp.float32 for p in model.parameters())


def test_compute_dtype_rejects_bf16_params():
    cfg = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    with pytest.raises(ValueError, match="fp32-stored"):
        FusedScanTrainStep(model, opt, compute_dtype="bfloat16")


def test_layer_chunk_parity():
    """scan-over-chunks (K layers unrolled per scan step) must be exactly
    the same math as K=1 — and as the generic TrainStep."""
    base, _ = _run(FusedScanTrainStep, scan_layers=True)
    for K in (3,):
        cfg = GPTConfig(**TINY, scan_layers=True)
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
        step = FusedScanTrainStep(model, opt, layer_chunk=K)
        ids, labels = _batch(vocab=cfg.vocab_size)
        fused = [float(step(ids, labels)) for _ in range(4)]
        np.testing.assert_allclose(base, fused, rtol=2e-5, atol=1e-6,
                                   err_msg=f"K={K}")


def test_layer_chunk_must_divide():
    cfg = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    with pytest.raises(ValueError, match="divide"):
        FusedScanTrainStep(model, opt, layer_chunk=2)  # 3 layers


# -- the compiled update scan: stacks updated in place (ISSUE 25) ---------
#
# A reader of a carried stack's old slice that runs after the slot write
# makes XLA copy the WHOLE stack in and out of the backward while body,
# every layer (705 ms of a 1,445 ms step at gpt3-1.3b on the v5e, the
# numerics monitor's sums; docs/DECISIONS.md §21). These tests read the
# compiled step for it.

_SCAN_VARIANTS = {
    "monitor": dict(numerics=True),
    "monitor-off": dict(numerics=False),
    "monitor+clip": dict(numerics=True, clip=True),
    "monitor-off+clip": dict(numerics=False, clip=True),
    "monitor+clip+guard": dict(numerics=True, clip=True, guard=True),
    "monitor+chunk2": dict(numerics=True, layer_chunk=2),
    "monitor+masters": dict(numerics=True, masters=True),
    "monitor-off+masters": dict(numerics=False, masters=True),
}


def _scan_step(cfg_kw, numerics, clip=False, guard=False, layer_chunk=1,
               masters=False, bf16_compute=False):
    """A FusedScanTrainStep as the 1.3b cell builds it (fp32-stored
    parameters, bf16 moments; `masters`: bf16 parameters + fp32
    masters), built but not run."""
    import paddle_tpu.nn as nn

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(**cfg_kw, scan_layers=True))
    if masters:
        model.bfloat16()
    opt = popt.AdamW(
        learning_rate=1e-4, weight_decay=0.01,
        parameters=model.parameters(), moment_dtype="bfloat16",
        multi_precision=masters,
        grad_clip=nn.ClipGradByGlobalNorm(1.0) if clip else None)
    step = FusedScanTrainStep(
        model, opt, criterion=GPTPretrainingCriterion(),
        fused_head=bf16_compute,
        compute_dtype="bfloat16" if bf16_compute else None,
        layer_chunk=layer_chunk, numerics=numerics,
        guard_nonfinite=guard or None)
    step.ensure_built()
    return step


def _traced(step, batch, sharding=None):
    """The step's jit traced on the shapes of its own state (on
    `sharding`'s device when given: a described chip holds no array)."""
    import jax
    import jax.numpy as jnp

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    state = jax.tree_util.tree_map(spec, step._extract_state())
    ids = jax.ShapeDtypeStruct(batch, jnp.int32, sharding=sharding)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)
    return state, step._jitted._jit.trace(state, lr, ids, ids, None)


def _while_bodies(text):
    """{name: instruction lines} of the computations some `while` of the
    optimized module runs as its body."""
    import re

    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
            cur = m.group(1) if m else None
            if cur:
                comps[cur] = []
        elif cur:
            comps[cur].append(line)
    names = set(re.findall(r"body=%?([\w.\-]+)", text))
    return {n: comps[n] for n in names if n in comps}


def _stack_copies(text, chunks, k):
    """`copy` instructions of a whole [chunks, k, ...] array at the top
    level of the while body that updates the stacks, or None when the
    text shows no such body."""
    import re

    shape = re.compile(
        r"= (?:f32|bf16)\[%d,%d,[0-9,]+\]\S* copy\(" % (chunks, k))
    update = [ls for ls in _while_bodies(text).values()
              if any("dynamic-update-slice" in x
                     or "dynamic_update_slice" in x for x in ls)]
    if not update:
        return None
    return [x.strip()[:120] for ls in update for x in ls
            if shape.search(x)]


def _stack_bytes(state):
    return sum(a.size * a.dtype.itemsize
               for kind in ("p", "m", "v", "mw")
               for a in state["s"][kind] if a is not None)


@pytest.mark.parametrize("variant", sorted(_SCAN_VARIANTS))
def test_update_scan_state_is_aliased(variant):
    """The donated stacks and the step's outputs share buffers: the
    compiled step aliases at least the stacks' bytes."""
    kw = _SCAN_VARIANTS[variant]
    step = _scan_step({**TINY, "num_layers": 4}, **kw)
    state, traced = _traced(step, (4, 16))
    compiled = traced.lower().compile()
    assert "input_output_alias" in compiled.as_text()
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= _stack_bytes(state))


@pytest.mark.parametrize("variant", sorted(_SCAN_VARIANTS))
def test_update_scan_orders_reads_before_writes(variant):
    """What gives the in-place update, as the step is lowered: with the
    monitor on (or with masters) every trainable stacked leaf's new slot
    values leave one optimization barrier, with the monitor's two sums,
    before the slot writes; without either there is none."""
    kw = _SCAN_VARIANTS[variant]
    step = _scan_step({**TINY, "num_layers": 4}, **kw)
    _, traced = _traced(step, (4, 16))
    n = traced.lower().as_text().count("optimization_barrier")
    leaves = sum(p.trainable for p in step._s_params)
    assert n == (leaves if kw["numerics"] or kw.get("masters") else 0)


def _cpu_stack_copies(**kw):
    step = _scan_step({**TINY, "num_layers": 4}, **kw)
    k = step._layer_chunk
    _, traced = _traced(step, (4, 16))
    return _stack_copies(traced.lower().compile().as_text(), 4 // k, k)


@pytest.fixture(scope="module")
def cpu_control_copies():
    """The stack copies this backend makes with no reader at all."""
    return _cpu_stack_copies(numerics=False)


@pytest.mark.parametrize("variant", sorted(_SCAN_VARIANTS))
def test_update_scan_copies_no_stack_cpu(cpu_control_copies, variant):
    """No `copy` of a whole [C, K, ...] parameter or moment stack in the
    update scan's while body. Skipped where this backend copies the
    stacks with the monitor off and no clip as well: its text then
    cannot show what a reader costs (the CPU backend copies every
    carried stack it updates by slice; the `slow` test below asks the
    v5e's compiler)."""
    if cpu_control_copies is None:
        pytest.skip("no while body updates a stack by slice in this "
                    "backend's optimized text")
    if cpu_control_copies:
        pytest.skip(f"this backend copies {len(cpu_control_copies)} whole "
                    "stacks in the update scan with the monitor off: its "
                    "text cannot show the monitor's reader")
    assert _cpu_stack_copies(**_SCAN_VARIANTS[variant]) == []


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a DESCRIBED v5e (nothing attached: the TPU compiler
    is installed, so a step compiles for it), with the Pallas wrappers
    routed to their kernels and the compile cache off (an executable for
    a described chip cannot be read back)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.pallas import routing
    from paddle_tpu.utils import flags

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    on_tpu = routing.on_tpu
    selfcheck = flags.get_flag("FLAGS_pallas_alias_selfcheck")
    cache = jax.config.jax_enable_compilation_cache
    routing.on_tpu = lambda: True
    # its eager probe runs the kernel, which no described chip can
    flags.set_flags({"FLAGS_pallas_alias_selfcheck": False})
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    finally:
        routing.on_tpu = on_tpu
        flags.set_flags({"FLAGS_pallas_alias_selfcheck": selfcheck})
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()


@pytest.mark.slow
@pytest.mark.parametrize("variant", [
    "monitor", "monitor-off", "monitor+clip+guard", "monitor+chunk2",
    "monitor+masters", "monitor-off+masters"])
def test_update_scan_copies_no_stack_v5e(v5e_chip, variant):
    """The same assertion where it bites: the step compiled for a v5e at
    gpt3-1.3b widths (hidden 2048, FFN 8192, 4 layers, 8 x 1024: a stack
    of 256 MiB cannot sit in VMEM, where a copy is a memory-space move),
    as the 1.3b cell builds it. The parent of ISSUE 25 compiled to 8
    whole-stack copies with the monitor on, with and without clip and
    guard, and to 16 with masters, monitor on or off. ~2 min a case:

        JAX_PLATFORMS=cpu python -m pytest tests/test_fused_scan_step.py \\
            -m slow -k v5e -p no:cacheprovider
    """
    kw = _SCAN_VARIANTS[variant]
    cfg = dict(vocab_size=8192, hidden_size=2048, num_layers=4,
               num_attention_heads=32, intermediate_size=8192,
               max_position_embeddings=1024)
    # with bf16-stored parameters the fused-CE kernel asks for more VMEM
    # than a v5e has at this vocabulary: the dense head there
    step = _scan_step(cfg, bf16_compute=not kw.get("masters"), **kw)
    k = step._layer_chunk
    state, traced = _traced(step, (8, 1024), sharding=v5e_chip)
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    copies = _stack_copies(compiled.as_text(), 4 // k, k)
    assert copies is not None, "no update scan in the compiled text"
    assert copies == []
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= _stack_bytes(state))
