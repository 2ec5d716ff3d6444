"""Ling-3.0's language model (Kimi Delta Attention and latent attention
layers, a dense SwiGLU or group-limited sigmoid-routed SwiGLU experts beside
a shared expert) against the plain reference (benchmark/reference/ling3.py,
which imports nothing of the program and computes KDA as the token-by-token
recurrence): tiny widths, float32, seeded weights."""
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.models import Ling3Config, Ling3ForCausalLM
from paddle_tpu.ops.pallas.splash_attention import (splash_attention,
                                                    splash_attention_xla,
                                                    supports)
from paddle_tpu.profiler import DEVICE_SCOPES

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmark"))
from reference import ling3 as ref  # noqa: E402

B, S, VOCAB, EXPERTS, HIDDEN = 2, 32, 61, 16, 64


def config(held=None, layers=3, dense=1, **kw):
    return Ling3Config(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_hidden_layers=layers,
        layer_group_size=3, first_k_dense_replace=dense,
        intermediate_size=96, num_attention_heads=2, head_dim=16,
        kda_chunk_size=16, kv_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e4,
        num_experts=EXPERTS, num_experts_per_tok=2, n_group=4, topk_group=2,
        moe_intermediate_size=24, moe_shared_expert_intermediate_size=24,
        moe_tile_rows=8, held_experts=held, router_aux_loss_coef=0.01, **kw)


def ref_config(c):
    lo, hi = c.held_experts or (0, c.num_experts)
    keys = ("hidden_size", "num_hidden_layers", "layer_group_size",
            "first_k_dense_replace", "rms_norm_eps", "num_attention_heads",
            "head_dim", "kda_lower_bound", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "num_experts", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor",
            "router_aux_loss_coef")
    return dict({k: getattr(c, k) for k in keys}, held_experts=(lo, hi),
                kda_run=c.kda_chunk_size)


def build(c, seed=0):
    """The program's model with every leaf drawn anew (gains 1 + normal, so
    that a dropped one shows; A_log and dt_bias so that the decay spreads
    over (-5, 0); a selection bias that is not zero)."""
    paddle.seed(seed)
    model = Ling3ForCausalLM(c)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        x = rng.standard_normal(p.shape).astype(np.float32)
        if name.endswith("A_log"):
            x = np.log(rng.uniform(1, 16, p.shape)).astype(np.float32)
        elif name.endswith("dt_bias"):
            x = 0.3 * x
        elif name.endswith("norm.weight"):
            x = 1.0 + 0.1 * x
        else:
            x = 0.3 * x
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
        out.append((i, layer.kind))
        out.append((i, ref.DENSE if layer.dense else ref.MIXTURE))
    return out


def ref_params(model):
    """(outer, sub-layers) of the reference: copies of the program's
    parameters (a `TrainStep` donates the originals) and of the mixtures'
    selection bias."""
    named = {k: jnp.array(v._data) for k, v in model.named_parameters()}
    buffers = {k: jnp.array(v._data) for k, v in model.named_buffers()}
    outer = {"embed_tokens.weight": named["model.embed_tokens.weight"],
             "norm.weight": named["model.norm.weight"],
             "lm_head": named["lm_head"]}
    layers, count = [], 3
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


def flat_leaves(tree, model):
    flat = {"model.embed_tokens.weight": tree["outer"]["embed_tokens.weight"],
            "model.norm.weight": tree["outer"]["norm.weight"],
            "lm_head": tree["outer"]["lm_head"]}
    for (i, _), layer in zip(sub_layers(model), tree["layers"]):
        for k, g in layer.items():
            flat[f"model.layers.{i}.{k}"] = g
    return flat


def assert_leaves_match(got, want, tol=2e-4, stray=0.0):
    assert set(want) == set(got)
    for k, g in want.items():
        g = np.asarray(g)
        scale = max(float(np.abs(g).max()), 1e-6)
        assert got[k] is not None, k
        close = np.isclose(got[k], g, atol=tol * scale + 1e-7, rtol=10 * tol)
        assert np.mean(~close) <= stray, (k, float(np.mean(~close)))


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
    # two mixture layers, half the groups kept: about half the tokens a
    # layer pick inside the held experts' group
    assert 0.25 * 2 * B * S < counters["group_hit_tokens"] < 0.8 * 2 * B * S
    if held is not None:
        assert 0 < counters["routed_pairs"] < 2 * B * S * 2


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_reference_of_a_wrong_program_differs(wrong):
    """What the benchmark's wrong-reference runs rest on: a recurrence
    without its correction term, with one decay a head, whose chunks forget
    the state, or a router without the group limit, changes the loss by far
    more than the tolerance."""
    c = config((4, 8))
    model = build(c)
    ids, labels = batch()
    outer, layers = ref_params(model)
    loss, _ = program_grads(model, ids, labels)
    wrong_loss, _, _ = ref.loss_and_grads(outer, layers, ref_config(c), ids,
                                          labels, wrong=(wrong,))
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


def test_the_shares_of_a_mixture_layer_add_up_to_the_uncut_reference():
    """Every share routes over all experts (and their groups) and computes
    its own; the shares' outputs, the shared expert and the residual counted
    once, are the uncut layer's (model-configs guide, section 4)."""
    whole = build(config(None, layers=1, dense=0))
    rng = np.random.default_rng(3)
    x = rng.standard_normal((B, S, HIDDEN)).astype(np.float32)

    def ffn_out(model):
        with paddle.no_grad():
            return np.asarray(model.model.layers[0]._mixture(
                paddle.to_tensor(x))[0]._data)

    named = dict(whole.named_parameters())
    bias = dict(whole.named_buffers())[f"model.layers.0.{ref.BIAS}"]._data

    def share_of(lo, hi, zero=False):
        share = build(config((lo, hi), layers=1, dense=0))
        for k, p in share.named_parameters():
            src = named[k]._data
            p._data = src[lo:hi] if src.shape != p._data.shape else src
            if zero and k.endswith("experts.down_proj"):
                p._data = jnp.zeros_like(p._data)
        dict(share.named_buffers())[
            f"model.layers.0.{ref.BIAS}"]._data = bias
        return ffn_out(share)

    # the shared expert and the residual alone: a share whose experts'
    # weights add nothing
    once = share_of(0, 2, zero=True)
    total = once.copy()
    for lo in range(0, EXPERTS, 2):         # eight shares of two experts
        total += share_of(lo, lo + 2) - once
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
    assert experts.shape == (2, B * S, 2)
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
    trainer = ref.RefTrainer(outer, layers, ref_config(c), hyper,
                             given=(experts + 1) % EXPERTS)
    trainer.run([(ids, labels)] * 3)
    assert trainer.miss["expert_pick_miss"] > 0.2


def test_the_rule_of_the_index_and_the_exports():
    assert paddle.models.Ling3ForCausalLM is Ling3ForCausalLM
    c = Ling3Config()
    assert (c.mixers.count("kda"), c.mixers.count("mla")) == (35, 7)
    assert [i for i, k in enumerate(c.mixers) if k == "mla"] == [
        5, 11, 17, 23, 29, 35, 41]
    cut = Ling3Config(num_hidden_layers=7, first_k_dense_replace=1)
    assert cut.mixers == ("kda",) * 5 + ("mla", "kda")
    with pytest.raises(ValueError):
        Ling3Config(expert_swiglu_limit=4.0)
    with pytest.raises(ValueError):
        dropless.DroplessMoE(8, 8, 12, 2, score="sigmoid", n_group=5)


# -- the group limit ----------------------------------------------------------

def plain_picks(s, bias, k, n_group, topk_group):
    c = np.asarray(s, np.float64) + np.asarray(bias, np.float64)
    size = c.shape[1] // n_group
    out = []
    for row in c:
        score = [np.sort(row[g * size:(g + 1) * size])[-2:].sum()
                 for g in range(n_group)]
        kept = np.argsort(-np.asarray(score), kind="stable")[:topk_group]
        masked = np.full_like(row, -np.inf)
        for g in kept:
            masked[g * size:(g + 1) * size] = row[g * size:(g + 1) * size]
        out.append(np.argsort(-masked, kind="stable")[:k])
    return np.asarray(out)


@pytest.mark.parametrize("groups,kept", [(8, 4), (4, 1), (2, 2)])
def test_route_topk_limits_its_picks_to_the_best_groups(groups, kept):
    rng = np.random.default_rng(groups)
    logits = jnp.asarray(rng.standard_normal((96, 64)), jnp.float32)
    bias = jnp.asarray(0.01 * rng.standard_normal(64), jnp.float32)
    p, experts, gates = dropless.route_topk(
        logits, 4, True, "sigmoid", bias, 2.5, groups, kept)
    s = 1 / (1 + np.exp(-np.asarray(logits, np.float64)))
    want = plain_picks(s, bias, 4, groups, kept)
    np.testing.assert_array_equal(np.asarray(experts), want)
    top = np.take_along_axis(s, want, 1)
    np.testing.assert_allclose(
        gates, top / top.sum(-1, keepdims=True) * 2.5, rtol=1e-5)


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_route_topk_without_groups_is_what_it_was_bit_for_bit(score):
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    bias = None if score == "softmax" else jnp.asarray(
        0.01 * rng.standard_normal(32), jnp.float32)
    was = dropless.route_topk(logits, 4, True, score, bias, 2.5)
    now = dropless.route_topk(logits, 4, True, score, bias, 2.5, n_group=1,
                              topk_group=1)
    for a, b in zip(was, now):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and its lowered text names no group
    import jax
    text = jax.jit(lambda x: dropless.route_topk(
        x, 4, True, "sigmoid", None, 2.5)).lower(logits).as_text()
    assert "top_k" in text or "topk" in text.lower()
    assert text == jax.jit(lambda x: dropless.route_topk(
        x, 4, True, "sigmoid", None, 2.5, 1, 1)).lower(logits).as_text()


# -- latent attention's widths through the attention kernel -------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_splash_attention_at_192_and_128_matches_its_xla_form(dtype, tol):
    import jax

    rng = np.random.default_rng(7)
    b, s, h = 1, 256, 2
    q = jnp.asarray(rng.standard_normal((b, s, h, 192)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, h, 192)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, h, 128)), dtype)
    w = jnp.asarray(rng.standard_normal((b, s, h, 128)), jnp.float32)
    assert supports(q.shape, h, dtype, d_v=128)
    assert not supports((b, s, h, 320), h, dtype, d_v=128)

    def pulled(f):
        return jax.value_and_grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w),
            argnums=(0, 1, 2))(q, k, v)

    got = pulled(lambda *a: splash_attention(
        *a, causal=True, scale=192 ** -0.5, interpret=True, block_q=128,
        block_k=128))
    want = pulled(lambda *a: splash_attention_xla(*a, causal=True,
                                                  scale=192 ** -0.5))
    assert [g.shape for g in got[1]] == [q.shape, k.shape, v.shape]
    np.testing.assert_allclose(got[0], want[0], rtol=tol)
    for g, w_ in zip(got[1], want[1]):
        scale = float(jnp.max(jnp.abs(w_.astype(jnp.float32))))
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w_, np.float32),
                                   atol=tol * scale)


def test_the_new_scopes_are_device_scopes():
    for scope in ("kda/project", "kda/conv", "kda/gate", "kda/scan",
                  "kda/gate_norm", "kda/out", "mla/project", "mla_attention",
                  "mlp"):
        assert scope in DEVICE_SCOPES


# -- a KDA layer's tables stay flat rows --------------------------------------

def _kda_layer_loss_and_grads(head_dim, interpret):
    """-> (lowered text, loss, gradients) of a one-layer model (KDA + dense,
    two heads of `head_dim`, 2 x 128 tokens) with the kernels interpreted or
    on the CPU's XLA path."""
    import dataclasses

    import jax
    from paddle_tpu.utils import flags

    c = dataclasses.replace(config(layers=1), head_dim=head_dim,
                            kda_chunk_size=64)
    model = build(c)
    params = list(model.parameters())
    rng = np.random.default_rng(7)
    ids, labels = (jnp.asarray(rng.integers(0, VOCAB, (2, 128)))
                   for _ in range(2))

    def fn(pvals):
        def loss(pvals):
            for p, v in zip(params, pvals):
                p._data = v
            with paddle.no_grad():
                return model.loss(paddle.Tensor._wrap(ids),
                                  paddle.Tensor._wrap(labels))._data
        return jax.value_and_grad(loss)(pvals)

    pvals = [p._data for p in params]
    flags.set_flags({"FLAGS_pallas_force_interpret": interpret})
    try:
        lowered = jax.jit(fn).lower(pvals)
        loss, grads = lowered.compile()(pvals)
    finally:
        flags.set_flags({"FLAGS_pallas_force_interpret": False})
        for p, v in zip(params, pvals):
            p._data = v
    return lowered.as_text(), float(loss), [np.asarray(g) for g in grads]


def test_a_kda_layer_holds_no_heads_by_128_table_and_64_wide_heads_fall_back():
    """At 128-wide heads, kernels interpreted: between the convolutions and
    `kda/out` every table is [b, s, heads 128] rows or a kernel's [rows, 128]
    block; no [2, 128, 2, 128] tensor of any type is in the lowered loss +
    gradients, and nothing fell back. At 64-wide heads the row kernels and
    the scan are counted in `routing.xla_fallbacks`, a geometry each, the
    [2, 128, 2, 64] tables of the `jnp` forms ARE there (the expression
    finds what it looks for), and loss and gradients are the CPU path's."""
    import re

    from paddle_tpu.ops.pallas import routing

    def tables(text, d):
        return re.findall(rf"tensor<2x128x2x{d}x\w+>", text)

    def fell_back():
        return {k: n for k, n in routing.xla_fallbacks.items()
                if k[0].startswith("kda")}

    before = fell_back()
    text, loss, grads = _kda_layer_loss_and_grads(128, interpret=True)
    assert not tables(text, 128), sorted(set(tables(text, 128)))
    assert fell_back() == before
    cpu_text, want_loss, want = _kda_layer_loss_and_grads(128,
                                                          interpret=False)
    assert tables(cpu_text, 128)        # the `jnp` forms' own, found
    assert abs(loss - want_loss) < 1e-5 * abs(want_loss)
    for g, w in zip(grads, want):
        assert np.abs(g - w).max() <= 2e-4 * max(np.abs(w).max(), 1e-6)

    text, loss, grads = _kda_layer_loss_and_grads(64, interpret=True)
    assert tables(text, 64)
    new = {k: n - before.get(k, 0) for k, n in fell_back().items()
           if n != before.get(k, 0)}
    assert sorted(k[0] for k in new) == ["kda", "kda_gated_norm",
                                         "kda_inputs"], new
    _, want_loss, want = _kda_layer_loss_and_grads(64, interpret=False)
    assert loss == want_loss
    for g, w in zip(grads, want):
        np.testing.assert_array_equal(g, w)


# -- the chip's compiler, with no chip ---------------------------------------

from test_keye_vl2 import v5e_chip  # noqa: E402,F401  (the fixture)


@pytest.mark.parametrize("what", ["kda", "kda-one-chunk", "mla_attention",
                                  "step"])
def test_the_kernels_and_the_step_compile_for_a_v5e_at_published_widths(
        v5e_chip, what):
    """`kda`: both kernels at the cell's shapes (2 x 8192 tokens, 32 heads of
    128 keys and values: grid steps of four chunks, paired); `kda-one-chunk`:
    at 192 tokens, grid steps of one chunk, the [64, 64] system.
    `mla_attention`: the attention kernels at 192 / 128 head widths over
    8,192 tokens. `step`: loss and every gradient of a
    three-layer model at the published widths on one 1,024-token sequence
    (KDA + dense, KDA + mixture, MLA + mixture): every kernel of the step
    lowers, and every `DEVICE_SCOPES` path the model names reaches the
    compiled step's metadata."""
    import jax
    from paddle_tpu.ops.pallas import kda as K
    from paddle_tpu.ops.pallas import routing

    bf16 = jnp.bfloat16
    fell_back = dict(routing.xla_fallbacks)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def both(f):
        def fn(*a):
            y, pull = jax.vjp(f, *a)
            return y, pull(y)
        return fn

    scopes = ()
    if what.startswith("kda"):
        shape = (2, 8192, 32, 128) if what == "kda" else (1, 192, 2, 128)
        fn = both(lambda *v: K.kda(*v))
        wide = spec(shape, bf16)
        args = (wide, wide, wide, spec(shape, jnp.float32),
                spec(shape[:3], jnp.float32))
        want = {"kda_fwd", "kda_bwd"}
    elif what == "mla_attention":
        fn = both(lambda *v: splash_attention(*v, causal=True,
                                              scale=192 ** -0.5))
        args = (spec((1, 8192, 32, 192), bf16), spec((1, 8192, 32, 192), bf16),
                spec((1, 8192, 32, 128), bf16))
        want = {"splash_fwd", "splash_bwd"}
    else:
        c = Ling3Config(num_hidden_layers=3, layer_group_size=3,
                        first_k_dense_replace=1, vocab_size=2048,
                        held_experts=(0, 8))
        model = Ling3ForCausalLM(c)
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
        want = {"kda_fwd", "kda_bwd", "splash_fwd", "splash_bwd",
                "kda_inputs_fwd", "kda_inputs_bwd", "kda_gated_norm_fwd",
                "kda_gated_norm_bwd"}
        scopes = ("kda/project", "kda/conv", "kda/gate", "kda/scan",
                  "kda/gate_norm", "kda/out", "mla/project", "mla_attention",
                  "mlp", "moe/shared", "moe/experts", "moe/route/router",
                  "head")
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert want <= set(routing.mosaic_kernels(text)), \
        routing.mosaic_kernels(text)
    assert not [k for k, n in routing.xla_fallbacks.items()
                if k[0].startswith("kda") and n != fell_back.get(k, 0)]
    for scope in scopes:
        assert scope in DEVICE_SCOPES
        assert f"/{scope}/" in text or f"{scope})" in text, scope
