"""Nemotron-H's language model (Mamba-2 / attention / sigmoid-routed relu^2
mixture layers) against the plain reference (benchmark/reference/
nemotron_h.py, which imports nothing of the program and computes the
state-space layer as the step-by-step recurrence): tiny widths, float32,
seeded weights; and the chunked scan's kernels against that recurrence."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
from paddle_tpu.ops.pallas import ssd_scan as ssd
from paddle_tpu.profiler import DEVICE_SCOPES

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmark"))
from reference import nemotron_h as ref  # noqa: E402

B, S, VOCAB, EXPERTS, HIDDEN, CHUNK = 2, 32, 61, 8, 64, 8
PATTERN = "MEM*E"


def config(held=None, pattern=PATTERN, **kw):
    return NemotronHConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=len(pattern),
        hybrid_override_pattern=pattern, mamba_num_heads=4, mamba_head_dim=16,
        ssm_state_size=16, n_groups=2, chunk_size=CHUNK,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        n_routed_experts=EXPERTS, num_experts_per_tok=2,
        moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
        moe_tile_rows=8, held_experts=held, router_aux_loss_coef=0.01, **kw)


def ref_config(c):
    lo, hi = c.held_experts or (0, c.n_routed_experts)
    keys = ("hidden_size", "num_layers", "hybrid_override_pattern",
            "layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
            "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "n_routed_experts", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "router_aux_loss_coef")
    return dict({k: getattr(c, k) for k in keys}, held_experts=(lo, hi))


def build(c, seed=0):
    """The program's model with every leaf drawn anew (gains 1 + normal,
    so that a dropped one shows; A and the step sizes in the published
    ranges; a selection bias that is not zero)."""
    paddle.seed(seed)
    model = NemotronHForCausalLM(c)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        x = rng.standard_normal(p.shape).astype(np.float32)
        if name.endswith("A_log"):
            x = np.log(rng.uniform(1, 16, p.shape)).astype(np.float32)
        elif name.endswith("dt_bias"):
            dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), p.shape))
            x = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        elif name.endswith(("norm.weight", "mixer.D")):
            x = 1.0 + 0.1 * x
        else:
            x = 0.3 * x
        p._data = jnp.asarray(x)
    for name, b in model.named_buffers():
        if name.endswith("score_bias"):
            b._data = jnp.asarray(
                0.05 * rng.standard_normal(b.shape).astype(np.float32))
    return model


def ref_params(model):
    """(outer, layers) of the reference: copies of the program's
    parameters (a `TrainStep` donates the originals) and of the mixtures'
    selection bias."""
    named = {k: jnp.array(v._data) for k, v in model.named_parameters()}
    buffers = {k: jnp.array(v._data) for k, v in model.named_buffers()}
    outer = {"embed_tokens.weight": named["model.embed_tokens.weight"],
             "norm.weight": named["model.norm.weight"],
             "lm_head": named["lm_head"]}
    layers, count = [], 3
    for i, kind in enumerate(model.config.kinds):
        layers.append({k: named[f"model.layers.{i}.{k}"]
                       for k in ref.LEAVES[kind]})
        count += len(ref.LEAVES[kind])
        if kind == ref.MIXTURE:
            layers[-1][ref.BIAS] = buffers[f"model.layers.{i}.{ref.BIAS}"]
    assert len(named) == count
    return outer, layers


def batch(seed=1):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, VOCAB, (B, S)), rng.integers(0, VOCAB, (B, S)))


def program_grads(model, ids, labels):
    loss = model.loss(paddle.to_tensor(ids), paddle.to_tensor(labels))
    loss.backward()
    grads = {k: (None if p.grad is None else np.asarray(p.grad._data))
             for k, p in model.named_parameters()}
    model.clear_gradients()
    return float(loss), grads


def flat_leaves(tree):
    flat = {"model.embed_tokens.weight": tree["outer"]["embed_tokens.weight"],
            "model.norm.weight": tree["outer"]["norm.weight"],
            "lm_head": tree["outer"]["lm_head"]}
    for i, layer in enumerate(tree["layers"]):
        for k, g in layer.items():
            flat[f"model.layers.{i}.{k}"] = g
    return flat


def assert_leaves_match(got, want, tol=2e-4, stray=0.0):
    """`stray`: the share of a leaf's elements that may lie outside the
    tolerance (after an AdamW update, elements whose gradient is rounding
    noise: its sign decides a whole step of the learning rate)."""
    assert set(want) == set(got)
    for k, g in want.items():
        g = np.asarray(g)
        scale = max(float(np.abs(g).max()), 1e-6)
        assert got[k] is not None, k
        close = np.isclose(got[k], g, atol=tol * scale + 1e-7, rtol=10 * tol)
        assert np.mean(~close) <= stray, (k, float(np.mean(~close)))


# -- the model against the reference ----------------------------------------

@pytest.mark.parametrize("held", [None, (2, 4)],
                         ids=["all-experts", "2-of-8-held"])
def test_logits_loss_and_every_gradient_match_the_reference(held):
    c = config(held)
    model = build(c)
    ids, labels = batch()
    outer, layers = ref_params(model)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._data)
    np.testing.assert_allclose(
        got, ref.logits(outer, layers, ref_config(c), ids), atol=2e-3,
        rtol=2e-4)
    loss, grads = program_grads(model, ids, labels)
    want_loss, parts, want = ref.loss_and_grads(
        outer, layers, ref_config(c), ids, labels)
    assert parts[1] > 0                       # the balance term is live
    np.testing.assert_allclose(loss, want_loss, rtol=2e-5)
    assert_leaves_match(grads, flat_leaves(want))
    counters = model.routing_counters()
    assert counters["computed_rows"] >= counters["routed_pairs"] > 0
    if held is not None:
        assert 0 < counters["routed_pairs"] < 2 * B * S * 2


@pytest.mark.parametrize("wrong", [{"zero_state": True}, {"skip_d": True}],
                         ids=["chunks-from-zero-state", "no-D-x"])
def test_a_reference_of_a_wrong_program_differs(wrong):
    """What the benchmark's wrong-reference runs rest on: a scan whose
    chunks forget the state, or without D x, changes the loss by far more
    than the tolerance."""
    c = config((2, 4))
    model = build(c)
    ids, labels = batch()
    outer, layers = ref_params(model)
    loss, _ = program_grads(model, ids, labels)
    wrong_loss, _, _ = ref.loss_and_grads(outer, layers, ref_config(c), ids,
                                          labels, **wrong)
    assert abs(loss - wrong_loss) / wrong_loss > 1e-3


def test_a_dropped_selection_bias_changes_the_picks():
    c = config()
    model = build(c)
    ids, labels = batch()
    outer, layers = ref_params(model)
    for p in layers:
        if ref.BIAS in p:
            p[ref.BIAS] = jnp.zeros_like(p[ref.BIAS])
    loss, _ = program_grads(model, ids, labels)
    other, _, _ = ref.loss_and_grads(outer, layers, ref_config(c), ids,
                                     labels)
    assert abs(loss - other) / other > 1e-5


def test_two_adamw_updates_match_the_reference():
    import paddle_tpu.optimizer as popt
    from paddle_tpu.jit import TrainStep

    c = config((2, 4), use_recompute=True)
    model = build(c)
    outer, layers = ref_params(model)
    hyper = (3e-3, 0.9, 0.999, 1e-8, 0.01)
    opt = popt.AdamW(learning_rate=hyper[0], beta1=hyper[1], beta2=hyper[2],
                     epsilon=hyper[3], weight_decay=hyper[4],
                     parameters=model.parameters())
    step = TrainStep(model, lambda m, a, b: m.loss(a, b), opt)
    batches = [batch(seed) for seed in (1, 2, 3)]
    losses = []
    for k, (a, b) in enumerate(batches):
        losses.append(float(step(paddle.to_tensor(a), paddle.to_tensor(b))))
        if k == 1:      # after two updates, before the third
            got = {n: np.array(p._data) for n, p in model.named_parameters()}
    assert step._jitted._cache_size() == 1
    trainer = ref.RefTrainer(outer, layers, ref_config(c), hyper)
    trainer.run(batches)
    np.testing.assert_allclose(losses, trainer.losses, rtol=1e-4)
    assert_leaves_match(got, flat_leaves(
        {"outer": trainer.outer, "layers": trainer.layers}), tol=1e-4,
        stray=1e-3)
    counters = model.routing_counters()
    assert counters["computed_rows"] >= counters["routed_pairs"] > 0
    assert counters["max_load_over_mean"] >= 1.0
    # recompute (a Mamba layer then runs a sequence at a time) changes
    # nothing of the mathematics
    plain, _ = program_grads(build(config((2, 4))), *batch())
    again, _ = program_grads(build(c), *batch())
    np.testing.assert_allclose(plain, again, rtol=1e-6)


def test_the_shares_of_a_mixture_layer_add_up_to_the_uncut_reference():
    """Every share routes over all experts and computes its own; the four
    shares' outputs, the shared expert and the residual counted once, are
    the uncut layer's (model-configs guide, section 4)."""
    whole = build(config(None, pattern="E"))
    rng = np.random.default_rng(3)
    x = rng.standard_normal((B, S, HIDDEN)).astype(np.float32)

    def layer_out(model):
        with paddle.no_grad():
            return np.asarray(model.model.layers[0](
                paddle.to_tensor(x))[0]._data)

    named = dict(whole.named_parameters())
    bias = dict(whole.named_buffers())[f"model.layers.0.{ref.BIAS}"]._data
    # the shared expert and the residual alone: a share that holds one
    # expert, given weights that add nothing
    def share_of(lo, hi, zero=False):
        share = build(config((lo, hi), pattern="E"))
        for k, p in share.named_parameters():
            src = named[k]._data
            p._data = src[lo:hi] if src.shape != p._data.shape else src
            if zero and k.endswith("experts.down_proj"):
                p._data = jnp.zeros_like(p._data)
        dict(share.named_buffers())[
            f"model.layers.0.{ref.BIAS}"]._data = bias
        return layer_out(share)

    once = share_of(0, 2, zero=True)
    total = once.copy()
    for lo in range(0, EXPERTS, 2):
        total += share_of(lo, lo + 2) - once
    _, layers = ref_params(whole)
    cfg = ref_config(whole.config)
    p = {k: v for k, v in layers[0].items() if k != ref.BIAS}
    want = np.stack([np.asarray(ref.mixture(
        p, layers[0][ref.BIAS], jnp.asarray(x[b]), cfg, "float32")[0])
        for b in range(B)])
    np.testing.assert_allclose(total, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(layer_out(whole), want, atol=2e-4, rtol=2e-4)


def test_recorded_picks_are_the_references_and_can_be_handed_to_it():
    c = config((2, 6))
    model = build(c)
    model.record_picks(B, S)
    ids, labels = batch()
    _, grads = program_grads(model, ids, labels)
    experts = model.picks()
    assert experts.shape == (2, B * S, 2)
    outer, layers = ref_params(model)
    _, _, want = ref.loss_and_grads(outer, layers, ref_config(c), ids,
                                    labels, given=experts)
    assert_leaves_match(grads, flat_leaves(want))
    hyper = (0.0, 0.9, 0.95, 1e-8, 0.0)
    trainer = ref.RefTrainer(outer, layers, ref_config(c), hyper,
                             given=experts)
    trainer.run([(ids, labels)] * 3)
    assert trainer.miss == {"expert_pick_miss": 0.0}
    assert trainer.counts["routed_pairs"] == \
        model.routing_counters()["routed_pairs"]
    trainer = ref.RefTrainer(outer, layers, ref_config(c), hyper,
                             given=(experts + 1) % EXPERTS)
    trainer.run([(ids, labels)] * 3)
    assert trainer.miss["expert_pick_miss"] > 0.2


def test_the_pattern_is_checked_and_the_models_are_exported():
    with pytest.raises(ValueError):
        NemotronHConfig(num_layers=53)
    with pytest.raises(ValueError):
        config(pattern="MXE")
    assert paddle.models.NemotronHForCausalLM is NemotronHForCausalLM
    c = NemotronHConfig()
    assert (c.kinds.count("M"), c.kinds.count("E"), c.kinds.count("*")) \
        == (23, 23, 6)
    assert NemotronHConfig(num_layers=9).kinds == tuple("MEMEM*EME")
    assert c.conv_dim == 6144 and c.mamba_inner == 4096


# -- the scan against the recurrence ----------------------------------------

def scan_inputs(seed, b, seq, heads, p, groups, n, dtype=jnp.float32):
    """x, dt, A, B, C, D with A in [1, 16] and the step sizes in [1e-3,
    1e-1], head 0 at the top of both ranges: its running sum reaches -200
    in a chunk of 128, where exp(c_i) and exp(-c_j) apart overflow
    float32."""
    rng = np.random.default_rng(seed)
    f = jnp.float32
    dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), (b, seq, heads)))
    a = rng.uniform(1, 16, heads)
    dt[..., 0], a[0] = 0.1, 16.0
    return (jnp.asarray(rng.normal(size=(b, seq, heads, p)), dtype),
            jnp.asarray(dt, f), -jnp.asarray(a, f),
            jnp.asarray(rng.normal(size=(b, seq, groups, n)) / 4, dtype),
            jnp.asarray(rng.normal(size=(b, seq, groups, n)) / 4, dtype),
            jnp.asarray(rng.normal(size=heads), f))


def recurrence(x, dt, A, B, C, D, **kw):
    """The reference's step-by-step recurrence on `ssd_scan`'s operands."""
    b, s, heads, p = x.shape
    g = B.shape[2]

    def grouped(v):
        return v.reshape(v.shape[:-1] + (g, heads // g))

    return ref.recurrence(x.reshape(b, s, g, heads // g, p), grouped(dt),
                          grouped(A), B, C, grouped(D), 8, **kw).reshape(
                              x.shape)


def pulled_back(f, args, seed=9):
    dy = jnp.asarray(np.random.default_rng(seed).normal(size=args[0].shape),
                     jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, pull = jax.vjp(f, *args)
        return (y,) + pull(dy)


@pytest.mark.parametrize("path", ["xla", "kernels-interpreted"])
def test_ssd_scan_matches_the_recurrence_forward_and_all_six_cotangents(path):
    args = scan_inputs(0, 1, 512, 4, 64, 2, 128)      # four chunks of 128
    # the overflow case is in the inputs
    assert float(jnp.min(ssd._within_chunk_sums(args[1], args[2], 128))) < -100
    want = pulled_back(recurrence, args)
    if path == "xla":
        got = pulled_back(lambda *a: ssd.ssd_scan_xla(*a, chunk=128), args)
    else:
        got = pulled_back(lambda *a: ssd.ssd_scan(*a, chunk=128,
                                                  interpret=True), args)
    for name, g, w in zip(("y", "dx", "ddt", "dA", "dB", "dC", "dD"), got,
                          want):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.max(
            jnp.abs(w))), rtol=2e-4, err_msg=name)


def test_ssd_scan_interpreted_runs_the_kernels_not_the_xla_path(monkeypatch):
    args = scan_inputs(1, 1, 256, 2, 64, 1, 128)
    monkeypatch.setattr(ssd, "ssd_scan_xla", None)
    y = ssd.ssd_scan(*args, chunk=128, interpret=True)
    np.testing.assert_allclose(y, recurrence(*args), atol=2e-4, rtol=2e-4)


def test_a_sequence_that_is_not_whole_chunks_is_refused_by_name():
    args = scan_inputs(2, 1, 200, 2, 64, 1, 128)
    for f in (ssd.ssd_scan, ssd.ssd_scan_xla):
        with pytest.raises(ValueError, match="seq 200 .* chunk 128"):
            f(*args, chunk=128)
    with pytest.raises(ValueError, match="chunk"):
        ssd.visited_chunks(4, 100, 128)
    assert ssd.visited_chunks(4, 8192, 128) == 256


@pytest.mark.parametrize("wrong", [{"zero_state": True}, "no-D"])
def test_the_recurrence_of_a_wrong_program_differs(wrong):
    args = scan_inputs(3, 1, 64, 2, 16, 1, 16)
    want = recurrence(*args)
    if wrong == "no-D":
        other = recurrence(*args[:5], jnp.zeros_like(args[5]))
    else:
        other = recurrence(*args, **wrong)
    assert float(jnp.max(jnp.abs(want - other))) > 1e-2 * float(
        jnp.max(jnp.abs(want)))


# -- the dropless layer's two forms ------------------------------------------

def _parent_gated_softmax(h, wr, wg, wu, wd, top_k, held):
    """The layer as the parent commit wrote it, dense: softmax over all
    experts, renormalised top-k gates, SiLU-gated experts."""
    p = jax.nn.softmax(h @ wr, axis=-1)
    top, experts = jax.lax.top_k(p, top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(*held):
        gate = jnp.sum(jnp.where(experts == e, top, 0.0), -1)
        y = y + gate[:, None] * ((jax.nn.silu(h @ wg[e - held[0]])
                                  * (h @ wu[e - held[0]])) @ wd[e - held[0]])
    return y


def test_the_gated_softmax_form_is_the_parents_on_fixed_inputs():
    """`dropless_moe` told nothing new is the layer it was: told the
    defaults by name it is bit-equal, run to run too, and it is the dense
    form of the parent's equations (that the two mixture steps compile to
    the parent's instructions is shown on their lowered steps: PERF.md
    section 6, PR 39)."""
    rng = np.random.default_rng(0)
    t, k, n, held = 64, 32, 16, (2, 6)
    h, wr = (jnp.asarray(rng.normal(size=s), jnp.float32)
             for s in ((t, k), (k, EXPERTS)))
    wg, wu = (jnp.asarray(rng.normal(size=(4, k, n)) / 4, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(4, n, k)) / 4, jnp.float32)

    def layer(*a, **kw):
        return dropless.dropless_moe(*a, top_k=2, held=held, tile_rows=8,
                                     **kw)[0]

    with jax.default_matmul_precision("highest"):
        told = layer(h, wr, wg, wu, wd, score="softmax", bias=None, scale=1.0)
        plain = layer(h, wr, wg, wu, wd)
        assert np.array_equal(np.asarray(told), np.asarray(plain))
        np.testing.assert_allclose(
            plain, _parent_gated_softmax(h, wr, wg, wu, wd, 2, held),
            atol=1e-5, rtol=1e-5)
        again = layer(h, wr, wg, wu, wd)
        assert np.array_equal(np.asarray(again), np.asarray(plain))
    # and the ungated sigmoid form against its own dense equations
    bias = jnp.asarray(0.1 * rng.normal(size=EXPERTS), jnp.float32)

    def dense(h, wr, wu, wd):
        s = jax.nn.sigmoid(h @ wr)
        experts = jax.lax.top_k(s + bias, 2)[1]
        top = jnp.take_along_axis(s, experts, -1)
        top = top / (top.sum(-1, keepdims=True) + 1e-20) * 2.5
        y = jnp.zeros_like(h)
        for e in range(*held):
            gate = jnp.sum(jnp.where(experts == e, top, 0.0), -1)
            y = y + gate[:, None] * (jnp.square(jnp.maximum(
                h @ wu[e - 2], 0)) @ wd[e - 2])
        return y

    def ungated(h, wr, wu, wd):
        return layer(h, wr, None, wu, wd, score="sigmoid", bias=bias,
                     scale=2.5)

    dy = jnp.asarray(rng.normal(size=(t, k)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, pull_w = jax.vjp(dense, h, wr, wu, wd)
        got, pull_g = jax.vjp(ungated, h, wr, wu, wd)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
        for g, w in zip(pull_g(dy), pull_w(dy)):
            np.testing.assert_allclose(g, w, atol=1e-4 * float(
                jnp.max(jnp.abs(w))), rtol=1e-4)
    with pytest.raises(ValueError, match="score"):
        dropless.route_topk(h @ wr, 2, score="tanh")


# -- the chip's compiler, with no chip ---------------------------------------

from test_keye_vl2 import v5e_chip  # noqa: E402,F401  (the fixture)


@pytest.mark.parametrize("what", ["ssd_scan", "step"])
def test_the_kernels_and_the_step_compile_for_a_v5e_at_published_widths(
        v5e_chip, what):
    """`ssd_scan`: both kernels at the cell's shapes (4 x 8192 tokens, 64
    heads of 64, 8 groups of 128). `step`: loss and every gradient of one
    period-shaped model at the published widths on one 1,024-token
    sequence (`M`, `E`, `*` once each): every kernel of the step lowers,
    and every `DEVICE_SCOPES` path the model names reaches the compiled
    step's metadata."""
    from paddle_tpu.ops.pallas import routing

    bf16 = jnp.bfloat16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    if what == "ssd_scan":
        def fn(*a):
            y, pull = jax.vjp(lambda *v: ssd.ssd_scan(*v), *a)
            return y, pull(y)
        args = (spec((4, 8192, 64, 64), bf16), spec((4, 8192, 64), jnp.float32),
                spec((64,), jnp.float32), spec((4, 8192, 8, 128), bf16),
                spec((4, 8192, 8, 128), bf16), spec((64,), jnp.float32))
        want = {"ssd_scan_fwd", "ssd_scan_bwd"}
        scopes = ()
    else:
        c = NemotronHConfig(num_layers=3, hybrid_override_pattern="ME*",
                            vocab_size=2048, held_experts=(0, 8))
        model = NemotronHForCausalLM(c)
        model.bfloat16()
        params = list(model.parameters())
        buffers = list(model.buffers())

        def fn(ids, labels, pvals, bvals):
            def loss(pvals):
                for p, v in zip(params, pvals):
                    p._data = v
                for b, v in zip(buffers, bvals):
                    b._data = v
                # the outer gradient owns the differentiation, as under
                # `fleet.recompute`: the tape's own vjp stays out of it
                with paddle.no_grad():
                    return model.loss(paddle.Tensor._wrap(ids),
                                      paddle.Tensor._wrap(labels))._data
            return jax.value_and_grad(loss)(pvals)

        args = (spec((1, 1024), jnp.int32), spec((1, 1024), jnp.int32),
                [spec(p._data.shape, p._data.dtype) for p in params],
                [spec(b._data.shape, b._data.dtype) for b in buffers])
        want = {"ssd_scan_fwd", "ssd_scan_bwd", "splash_fwd"}
        scopes = ("ssm/project", "ssm/conv", "ssm/scan", "ssm/gate_norm",
                  "ssm/out", "moe/shared", "moe/experts", "moe/route/router",
                  "attention/projections", "full_attention", "head")
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert want <= set(routing.mosaic_kernels(text)), \
        routing.mosaic_kernels(text)
    assert not routing.xla_fallbacks.get(("ssd_scan",), 0)
    for scope in scopes:
        assert scope in DEVICE_SCOPES
        assert f"/{scope}/" in text or f"{scope})" in text, scope


def test_the_new_scopes_are_device_scopes():
    for scope in ("ssm/project", "ssm/conv", "ssm/scan", "ssm/gate_norm",
                  "ssm/out", "moe/shared"):
        assert scope in DEVICE_SCOPES
