"""LFM2-MoE's language model (gated short-convolution layers three to one
with grouped-query attention, a dense SwiGLU or sigmoid-routed SwiGLU
experts, a tied head) against the plain reference
(benchmark/reference/lfm2.py, which imports nothing of the program and
computes the convolution as three shifted sums): tiny widths, float32, seeded
weights; the gated-convolution operator and its VJP against the `jnp` form;
the kernels compiled for a described v5e at the cell's shapes."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.ops.pallas import gated_conv as G
from paddle_tpu.profiler import DEVICE_SCOPES

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmark"))
from reference import lfm2 as ref  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# what names no block: a seeded batch, the tape's gradients by leaf, leaves
# compared within a tolerance relative to the leaf's largest entry
from test_ling3 import (  # noqa: E402
    assert_leaves_match, batch, program_grads)

B, S, VOCAB, EXPERTS, HIDDEN = 2, 32, 61, 16, 64
KINDS = ("conv", "full_attention", "conv", "conv")


def config(held=None, kinds=KINDS, dense=1, **kw):
    return Lfm2MoeConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_hidden_layers=len(kinds),
        layer_types=kinds, num_dense_layers=dense, intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        rope_theta=1e4, num_experts=EXPERTS, num_experts_per_tok=2,
        moe_intermediate_size=24, moe_tile_rows=8, held_experts=held,
        router_aux_loss_coef=0.01, **kw)


def ref_config(c):
    lo, hi = c.held_experts or (0, c.num_experts)
    keys = ("hidden_size", "num_hidden_layers", "layer_types",
            "num_dense_layers", "norm_eps", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rope_theta", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "router_aux_loss_coef")
    return dict({k: getattr(c, k) for k in keys}, held_experts=(lo, hi))


def build(c, seed=0):
    """The program's model with every leaf drawn anew (gains 1 + normal, so
    that a dropped one shows; a selection bias that is not zero)."""
    paddle.seed(seed)
    model = Lfm2MoeForCausalLM(c)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        x = rng.standard_normal(p.shape).astype(np.float32)
        x = 1.0 + 0.1 * x if name.endswith("norm.weight") else 0.3 * x
        p._data = jnp.asarray(x)
    for name, b in model.named_buffers():
        if name.endswith("score_bias"):
            b._data = jnp.asarray(
                0.05 * rng.standard_normal(b.shape).astype(np.float32))
    return model


def sub_layers(model):
    """(published layer, kind) of the reference's 2 L sub-layers."""
    out = []
    for i, layer in enumerate(model.model.layers):
        out.append((i, ref.CONV if layer.kind == "conv" else ref.ATTN))
        out.append((i, ref.DENSE if layer.ffn == "dense" else ref.MIXTURE))
    return out


def ref_params(model):
    """(outer, sub-layers) of the reference: copies of the program's
    parameters (a `TrainStep` donates the originals) and of the mixtures'
    selection bias. The model has no `lm_head` leaf."""
    named = {k: jnp.array(v._data) for k, v in model.named_parameters()}
    buffers = {k: jnp.array(v._data) for k, v in model.named_buffers()}
    assert "lm_head" not in named
    outer = {"embed_tokens.weight": named["model.embed_tokens.weight"],
             "norm.weight": named["model.norm.weight"]}
    layers, count = [], 2
    for i, kind in sub_layers(model):
        layers.append({k: named[f"model.layers.{i}.{k}"]
                       for k in ref.LEAVES[kind]})
        count += len(ref.LEAVES[kind])
        if kind == ref.MIXTURE:
            layers[-1][ref.BIAS] = buffers[f"model.layers.{i}.{ref.BIAS}"]
    assert len(named) == count
    assert tuple(k for _, k in sub_layers(model)) == ref.kinds_of(
        ref_config(model.config))
    return outer, layers


def flat_leaves(tree, model):
    flat = {"model.embed_tokens.weight": tree["outer"]["embed_tokens.weight"],
            "model.norm.weight": tree["outer"]["norm.weight"]}
    for (i, _), layer in zip(sub_layers(model), tree["layers"]):
        for k, g in layer.items():
            flat[f"model.layers.{i}.{k}"] = g
    return flat


# -- the model against the reference ----------------------------------------

@pytest.mark.parametrize("held", [None, (4, 8)],
                         ids=["all-experts", "4-of-16-held"])
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
    assert_leaves_match(grads, flat_leaves(want, model))
    counters = model.routing_counters()
    assert counters["computed_rows"] >= counters["routed_pairs"] > 0
    if held is not None:
        assert 0 < counters["routed_pairs"] < 3 * B * S * 2


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_reference_of_a_wrong_program_differs(wrong):
    """What the benchmark's wrong-reference runs rest on: a convolution
    without its input gate or with its taps one step late changes the loss,
    and an untied head the embedding's gradient, by far more than the
    tolerance."""
    c = config((4, 8))
    model = build(c)
    ids, labels = batch()
    outer, layers = ref_params(model)
    loss, grads = program_grads(model, ids, labels)
    wrong_loss, _, tree = ref.loss_and_grads(
        outer, layers, ref_config(c), ids, labels, wrong=(wrong,))
    if wrong == "untied_head":
        g = grads["model.embed_tokens.weight"]
        w = np.asarray(tree["outer"]["embed_tokens.weight"])
        assert np.linalg.norm(g - w) > 0.1 * np.linalg.norm(g)
    else:
        assert abs(loss - wrong_loss) / wrong_loss > 1e-4
    with pytest.raises(ValueError):
        ref.RefTrainer(outer, layers, ref_config(c), (0,) * 5,
                       wrong=("no_such_program",))


def test_two_adamw_updates_match_the_reference():
    import paddle_tpu.optimizer as popt
    from paddle_tpu.jit import TrainStep

    c = config((4, 8), use_recompute=True)
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
        {"outer": trainer.outer, "layers": trainer.layers}, model), tol=1e-4,
        stray=1e-3)
    # recompute (one segment a layer) changes nothing of the mathematics
    plain, _ = program_grads(build(config((4, 8))), *batch())
    again, _ = program_grads(build(c), *batch())
    np.testing.assert_allclose(plain, again, rtol=1e-6)


def test_the_tied_heads_gradient_is_the_gathers_part_plus_the_heads():
    """No `lm_head` leaf; the embedding's gradient is the sum of what the
    gather alone and the head alone send it (the reference with the head's
    part cut off gives the first; the difference is the head's dW)."""
    c = config((4, 8))
    model = build(c)
    assert [n for n, _ in model.named_parameters() if "lm_head" in n] == []
    assert model.head is model.model.embed_tokens.weight
    ids, labels = batch()
    outer, layers = ref_params(model)
    _, grads = program_grads(model, ids, labels)
    _, _, both = ref.loss_and_grads(outer, layers, ref_config(c), ids, labels)
    _, _, gather = ref.loss_and_grads(outer, layers, ref_config(c), ids,
                                      labels, wrong=("untied_head",))
    whole = np.asarray(both["outer"]["embed_tokens.weight"])
    gathered = np.asarray(gather["outer"]["embed_tokens.weight"])
    # the gather touches the batch's tokens only; the head every row
    unseen = np.setdiff1d(np.arange(VOCAB), ids.reshape(-1))
    assert len(unseen) and np.all(gathered[unseen] == 0)
    assert np.abs(whole[unseen]).max() > 0
    got = grads["model.embed_tokens.weight"]
    np.testing.assert_allclose(got, whole, atol=2e-4 * np.abs(whole).max())
    np.testing.assert_allclose(got[unseen], (whole - gathered)[unseen],
                               atol=2e-4 * np.abs(whole).max())


def test_the_shares_of_a_mixture_layer_add_up_to_the_uncut_reference():
    """Every share routes over all experts and computes its own; the eight
    shares' routed sums, with what every chip computes alike (the residual)
    counted once, are the uncut layer's (model-configs guide, section 4)."""
    whole = build(config(None, kinds=("conv",), dense=0))
    rng = np.random.default_rng(3)
    x = rng.standard_normal((B, S, HIDDEN)).astype(np.float32)

    def ffn_out(model):
        layer = model.model.layers[0]
        with paddle.no_grad():
            return np.asarray(paddle.models.decoder_parts.mixture(
                paddle.to_tensor(x), layer.ffn_norm,
                layer.feed_forward)[0]._data)

    named = dict(whole.named_parameters())
    bias = dict(whole.named_buffers())[f"model.layers.0.{ref.BIAS}"]._data

    def share_of(lo, hi):
        share = build(config((lo, hi), kinds=("conv",), dense=0))
        for k, p in share.named_parameters():
            src = named[k]._data
            p._data = src[lo:hi] if src.shape != p._data.shape else src
        dict(share.named_buffers())[
            f"model.layers.0.{ref.BIAS}"]._data = bias
        return ffn_out(share)

    total = x.copy()                        # the residual, once
    for lo in range(0, EXPERTS, 2):         # eight shares of two experts
        total += share_of(lo, lo + 2) - x
    _, layers = ref_params(whole)
    cfg = ref._config(ref_config(whole.config))
    p = {k: v for k, v in layers[1].items() if k != ref.BIAS}
    want = np.stack([np.asarray(ref.mixture(
        p, layers[1][ref.BIAS], jnp.asarray(x[b]), cfg, "float32")[0])
        for b in range(B)])
    np.testing.assert_allclose(total, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(ffn_out(whole), want, atol=2e-4, rtol=2e-4)


def test_recorded_picks_are_the_references_and_can_be_handed_to_it():
    c = config((4, 12))
    model = build(c)
    model.record_picks(B, S)
    ids, labels = batch()
    _, grads = program_grads(model, ids, labels)
    experts = model.picks()
    assert experts.shape == (3, B * S, 2)
    outer, layers = ref_params(model)
    _, _, want = ref.loss_and_grads(outer, layers, ref_config(c), ids,
                                    labels, given=experts)
    assert_leaves_match(grads, flat_leaves(want, model))
    hyper = (0.0, 0.9, 0.95, 1e-8, 0.0)
    trainer = ref.RefTrainer(outer, layers, ref_config(c), hyper,
                             given=experts)
    trainer.run([(ids, labels)] * 3)
    assert trainer.miss == {"expert_pick_miss": 0.0}
    assert trainer.counts["routed_pairs"] == \
        model.routing_counters()["routed_pairs"]


def test_the_published_defaults_and_the_exports():
    assert paddle.models.Lfm2MoeForCausalLM is Lfm2MoeForCausalLM
    c = Lfm2MoeConfig()
    assert (c.layer_types.count("conv"),
            c.layer_types.count("full_attention")) == (30, 10)
    assert [i for i, k in enumerate(c.layer_types)
            if k == "full_attention"] == list(range(2, 40, 4))
    assert (c.hidden_size, c.intermediate_size, c.moe_intermediate_size,
            c.num_experts, c.num_experts_per_tok, c.conv_L_cache,
            c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        2048, 11776, 1536, 64, 4, 3, 32, 8, 64)
    with pytest.raises(ValueError):
        Lfm2MoeConfig(num_hidden_layers=3)
    with pytest.raises(ValueError):
        Lfm2MoeConfig(num_hidden_layers=1, layer_types=("mamba",))
    for scope in ("conv/project", "conv/gate_conv", "conv/out"):
        assert scope in DEVICE_SCOPES


# -- the gated convolution as one operator -----------------------------------

def gated_conv_by_hand(bcx, w):
    """y_t = C_t * sum_k w_k (B X)_{t-(taps-1)+k}, a loop over the tokens."""
    bcx, w = np.asarray(bcx, np.float64), np.asarray(w, np.float64)
    taps, h = w.shape
    z = bcx[..., :h] * bcx[..., 2 * h:]
    y = np.zeros(z.shape)
    for t in range(z.shape[1]):
        for k in range(taps):
            if t - (taps - 1) + k >= 0:
                y[:, t] += w[k] * z[:, t - (taps - 1) + k]
    return bcx[..., h:2 * h] * y


@pytest.mark.parametrize("shape, taps", [
    ((2, 48, 128), 3),      # one 16-row block short of a 32-row slab a block
    ((2, 80, 256), 3),      # 16-row blocks: every edge is a block's edge
    ((1, 544, 128), 3),     # 32-row blocks across 17 of them
    ((2, 512, 256), 3),     # 256-row blocks, 32-row slabs, two a sequence
    ((1, 64, 128), 4),      # another number of taps
], ids=["48x128", "80x256", "544x128", "512x256", "4-taps"])
def test_the_gated_convolution_kernels_match_the_jnp_form(shape, taps):
    """Forward, dbcx and dw of the kernel pair (interpreted) against the
    `jnp` form differentiated by JAX, at lengths that are not a multiple of
    the largest row block, across a block's edge (the two rows of history,
    the two rows of future) and across a sequence's start (no history)."""
    b, s, h = shape
    rng = np.random.default_rng(0)
    bcx = jnp.asarray(rng.standard_normal((b, s, 3 * h)), jnp.float32)
    w = jnp.asarray(rng.uniform(-0.6, 0.6, (taps, h)), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((b, s, h)), jnp.float32)
    assert G.supports(bcx.shape, taps, bcx.dtype)
    want, pull = jax.vjp(G.gated_conv_xla, bcx, w)
    got, pull_k = jax.vjp(
        lambda a, c: G.gated_conv(a, c, interpret=True, use_kernel=True),
        bcx, w)
    np.testing.assert_allclose(want, gated_conv_by_hand(bcx, w), atol=1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for a, e, name in zip(pull_k(dy), pull(dy), ("dbcx", "dw")):
        np.testing.assert_allclose(a, e, atol=2e-6 * float(jnp.abs(e).max())
                                   + 1e-6, err_msg=name)


def test_the_gated_convolution_in_bfloat16_and_where_no_kernel_runs():
    rng = np.random.default_rng(1)
    bcx = jnp.asarray(rng.standard_normal((2, 64, 384)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-0.6, 0.6, (3, 128)), jnp.bfloat16)
    dy = jnp.asarray(rng.standard_normal((2, 64, 128)), jnp.bfloat16)
    want, pull = jax.vjp(G.gated_conv_xla, bcx.astype(jnp.float32),
                         w.astype(jnp.float32))
    got, pull_k = jax.vjp(
        lambda a, c: G.gated_conv(a, c, interpret=True, use_kernel=True),
        bcx, w)
    assert got.dtype == jnp.bfloat16
    dbcx, dw = pull_k(dy)
    assert dbcx.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16
    e_bcx, e_w = pull(dy.astype(jnp.float32))
    # one rounding to bfloat16 of each output: 2^-8 of the entry
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=2 ** -7,
                               atol=1e-3)
    np.testing.assert_allclose(dbcx.astype(jnp.float32), e_bcx, rtol=2 ** -7,
                               atol=2e-3)
    np.testing.assert_allclose(dw.astype(jnp.float32), e_w, rtol=2 ** -7,
                               atol=2 ** -7 * float(jnp.abs(e_w).max()))
    # a width that is no multiple of 128 lanes, a length that is no multiple
    # of 16 rows: the `jnp` form is the path, and says so on request
    assert not G.supports((2, 64, 3 * 64), 3, jnp.float32)
    assert not G.supports((2, 40, 384), 3, jnp.float32)
    narrow = jnp.asarray(rng.standard_normal((2, 40, 3 * 64)), jnp.float32)
    taps = jnp.asarray(rng.uniform(-0.6, 0.6, (3, 64)), jnp.float32)
    np.testing.assert_allclose(G.gated_conv(narrow, taps),
                               gated_conv_by_hand(narrow, taps), atol=1e-5)
    with pytest.raises(ValueError):
        G.gated_conv(narrow, taps, use_kernel=True)


# -- compiled for a described v5e (no chip: the compiler alone) ---------------

from test_keye_vl2 import v5e_chip  # noqa: E402,F401  (the fixture)


@pytest.mark.parametrize("what", ["gated_conv", "step"])
def test_the_kernels_and_the_step_compile_for_a_v5e_at_published_widths(
        v5e_chip, what):
    """`gated_conv`: both kernels at the cell's shapes (4 x 8,192 tokens,
    2,048 channels: 256-row blocks of the [., 6,144] product; the VMEM
    fits). `step`: loss and every gradient of a three-layer model at the
    published widths on one 1,024-token sequence (conv + dense, attention +
    mixture at 64-wide heads in groups of four, conv + mixture): every
    kernel of the step lowers, and every `DEVICE_SCOPES` path the model
    names reaches the compiled step's metadata."""
    from paddle_tpu.ops.pallas import routing

    bf16 = jnp.bfloat16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    scopes = ()
    if what == "gated_conv":
        def fn(bcx, w, dy):
            y, pull = jax.vjp(G.gated_conv, bcx, w)
            return y, pull(dy)

        args = (spec((4, 8192, 3 * 2048), bf16), spec((3, 2048), bf16),
                spec((4, 8192, 2048), bf16))
        want = {"gated_conv_fwd", "gated_conv_bwd"}
    else:
        c = Lfm2MoeConfig(num_hidden_layers=3, num_dense_layers=1,
                          layer_types=("conv", "full_attention", "conv"),
                          vocab_size=2048, held_experts=(0, 8))
        model = Lfm2MoeForCausalLM(c)
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
        want = {"gated_conv_fwd", "gated_conv_bwd", "splash_fwd",
                "splash_bwd", "fused_ce_fwd", "fused_ce_bwd"}
        scopes = ("conv/project", "conv/gate_conv", "conv/out",
                  "attention/projections", "full_attention", "mlp",
                  "moe/experts", "moe/route/router", "head")
    fell_back = dict(routing.xla_fallbacks)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert want <= set(routing.mosaic_kernels(text)), \
        routing.mosaic_kernels(text)
    assert not [k for k, n in routing.xla_fallbacks.items()
                if k[0] in ("gated_conv", "splash_attention")
                and n != fell_back.get(k, 0)]
    for scope in scopes:
        assert scope in DEVICE_SCOPES
        assert f"/{scope}/" in text or f"{scope})" in text, scope
