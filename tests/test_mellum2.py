"""Mellum 2's language model against the plain reference
(benchmark/reference/mellum2.py, which imports nothing of the program):
tiny widths, float32, seeded weights; two periods of (sliding, sliding,
sliding, full), a window of 8 in sequences of 32."""
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import Mellum2Config, Mellum2ForCausalLM
from paddle_tpu.models.mellum2 import FULL, SLIDING, yarn_inv_freq

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmark"))
from reference import mellum2 as ref  # noqa: E402

B, S, VOCAB, EXPERTS, HIDDEN = 2, 32, 61, 8, 64
PERIOD = (SLIDING, SLIDING, SLIDING, FULL)


def config(held=None, periods=2, kinds=None, **kw):
    kinds = tuple(kinds or PERIOD * periods)
    return Mellum2Config(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=len(kinds),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        layer_types=kinds, sliding_window=8, rope_theta=100.0,
        yarn_factor=4.0, yarn_original_positions=16, yarn_beta_fast=2.0,
        yarn_beta_slow=0.5, yarn_attention_factor=0.1 * math.log(4.0) + 1,
        num_experts=EXPERTS, num_experts_per_tok=2, moe_intermediate_size=24,
        moe_tile_rows=8, held_experts=held, router_aux_loss_coef=0.01, **kw)


def ref_config(c):
    lo, hi = c.held_experts or (0, c.num_experts)
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_attention_heads,
        num_key_value_heads=c.num_key_value_heads, head_dim=c.head_dim,
        rms_norm_eps=c.rms_norm_eps, layer_types=c.layer_types,
        sliding_window=c.sliding_window, rope_theta=c.rope_theta,
        yarn_factor=c.yarn_factor,
        yarn_original_positions=c.yarn_original_positions,
        yarn_beta_fast=c.yarn_beta_fast, yarn_beta_slow=c.yarn_beta_slow,
        yarn_attention_factor=c.yarn_attention_factor,
        num_experts=c.num_experts,
        num_experts_per_tok=c.num_experts_per_tok,
        norm_topk_prob=c.norm_topk_prob,
        router_aux_loss_coef=c.router_aux_loss_coef, held_experts=(lo, hi))


def build(c, seed=0):
    """The program's model with every leaf drawn anew (gains 1 + normal,
    so that a dropped one shows)."""
    paddle.seed(seed)
    model = Mellum2ForCausalLM(c)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        x = rng.standard_normal(p.shape).astype(np.float32)
        gain = "norm" in name and name.endswith("weight")
        p._data = jnp.asarray(1.0 + 0.1 * x if gain else 0.3 * x)
    return model


def ref_params(model):
    """(outer, layers) of the reference: copies of the program's
    parameters (a `TrainStep` donates the originals)."""
    named = {k: jnp.array(v._data) for k, v in model.named_parameters()}
    outer = {"embed_tokens.weight": named["model.embed_tokens.weight"],
             "norm.weight": named["model.norm.weight"],
             "lm_head": named["lm_head"]}
    layers = [{k: named[f"model.layers.{i}.{k}"] for k in ref.LAYER_LEAVES}
              for i in range(model.config.num_layers)]
    assert len(named) == 3 + len(layers) * len(ref.LAYER_LEAVES)
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


def flat_leaves(tree, layers):
    flat = {"model.embed_tokens.weight": tree["outer"]["embed_tokens.weight"],
            "model.norm.weight": tree["outer"]["norm.weight"],
            "lm_head": tree["outer"]["lm_head"]}
    for i in range(layers):
        for k, g in tree["layers"][i].items():
            flat[f"model.layers.{i}.{k}"] = g
    return flat


def assert_leaves_match(got, want, tol=2e-4):
    assert set(want) == set(got)
    for k, g in want.items():
        g = np.asarray(g)
        scale = max(float(np.abs(g).max()), 1e-6)
        assert got[k] is not None, k
        np.testing.assert_allclose(got[k], g, atol=tol * scale + 1e-7,
                                   rtol=10 * tol, err_msg=k)


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
    assert_leaves_match(grads, flat_leaves(want, c.num_layers))
    if held is not None:
        routed = model.routing_counters()["routed_pairs"]
        assert 0 < routed < c.num_layers * B * S * c.num_experts_per_tok


@pytest.mark.parametrize("wrong", [{"window": 16}, {"yarn": False}],
                         ids=["window-16-for-8", "plain-rope-on-full"])
def test_a_reference_with_the_wrong_window_or_rotation_differs(wrong):
    """What the benchmark's wrong-reference runs rest on: both switches
    change the loss and the gradients by far more than the tolerance."""
    c = config((2, 4))
    model = build(c)
    ids, labels = batch()
    outer, layers = ref_params(model)
    loss, _ = program_grads(model, ids, labels)
    wrong_loss, _, _ = ref.loss_and_grads(outer, layers, ref_config(c), ids,
                                          labels, **wrong)
    assert abs(loss - wrong_loss) / wrong_loss > 1e-3


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
    # the third loss saw both updates; the parameters after them too
    assert_leaves_match(got, flat_leaves(
        {"outer": trainer.outer, "layers": trainer.layers}, c.num_layers),
        tol=1e-4)
    counters = model.routing_counters()
    assert counters["computed_rows"] >= counters["routed_pairs"] > 0
    assert counters["max_load_over_mean"] >= 1.0
    # recompute changes nothing of the mathematics
    plain, _ = program_grads(build(config((2, 4))), *batch())
    again, _ = program_grads(build(c), *batch())
    np.testing.assert_allclose(plain, again, rtol=1e-6)


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(kind):
    """Every share routes over all experts and computes its own; the four
    shares' outputs, attention's part counted once, are the uncut
    layer's (model-configs guide, section 4)."""
    whole = build(config(None, kinds=(kind,)))
    rng = np.random.default_rng(3)
    x = rng.standard_normal((B, S, HIDDEN)).astype(np.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def layer_out(model):
        with paddle.no_grad():
            return np.asarray(model.model.layers[0](
                paddle.to_tensor(x), paddle.to_tensor(pos))[0]._data)

    with paddle.no_grad():
        attended = np.asarray(whole.model.layers[0]._attend(
            paddle.to_tensor(x), paddle.to_tensor(pos))._data)
    total = attended.copy()
    named = dict(whole.named_parameters())
    for lo in range(0, EXPERTS, 2):
        share = build(config((lo, lo + 2), kinds=(kind,)))
        for k, p in share.named_parameters():
            src = named[k]._data
            p._data = src[lo:lo + 2] if src.shape != p._data.shape else src
        total += layer_out(share) - attended
    _, layers = ref_params(whole)
    cfg = ref_config(whole.config)
    rot = ref.rotation(dict(cfg, yarn_on=True), kind)
    want = np.stack([np.asarray(ref.layer(
        layers[0], jnp.asarray(x[b]), pos[b], cfg, rot, "float32")[0])
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
    assert experts.shape == (c.num_layers, B * S, 2)
    outer, layers = ref_params(model)
    _, _, want = ref.loss_and_grads(outer, layers, ref_config(c), ids,
                                    labels, given=experts)
    assert_leaves_match(grads, flat_leaves(want, c.num_layers))
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


def test_the_yarn_table_of_the_published_configuration():
    """low 18, high 35, the factor 0.1 ln 16 + 1, the first and the last
    frequency (transformers' `_compute_yarn_parameters`, truncate on)."""
    c = Mellum2Config()
    inv, low, high = yarn_inv_freq(
        c.head_dim, c.rope_theta, c.yarn_factor, c.yarn_original_positions,
        c.yarn_beta_fast, c.yarn_beta_slow)
    assert (low, high) == (18, 35)
    assert c.yarn_attention_factor == pytest.approx(0.1 * math.log(16) + 1,
                                                    abs=1e-15)
    plain = 5e5 ** (-np.arange(64) / 64.0)
    assert inv[0] == 1.0 and inv[18] == plain[18]      # turns fast: kept
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-15)
    np.testing.assert_allclose(inv[-1], 5e5 ** (-63 / 64) / 16, rtol=1e-15)
    mid = (26 - 18) / 17.0
    np.testing.assert_allclose(
        inv[26], plain[26] * (1 - mid) + plain[26] / 16 * mid, rtol=1e-12)
    # the reference computes the same table by its own code
    cfg = dict(head_dim=128, rope_theta=5e5, yarn_factor=16.0,
               yarn_original_positions=8192, yarn_beta_fast=32.0,
               yarn_beta_slow=1.0, yarn_attention_factor=1.2772588722239782,
               yarn_on=True)
    assert ref.yarn_range(cfg) == (18, 35)
    freq, factor = ref.frequencies(cfg, FULL)
    np.testing.assert_allclose(freq, inv, rtol=2e-6)
    assert factor == c.yarn_attention_factor
    np.testing.assert_allclose(ref.frequencies(cfg, SLIDING)[0], plain,
                               rtol=2e-6)


def test_layer_kinds_are_checked_and_the_models_are_exported():
    with pytest.raises(ValueError):
        Mellum2Config(num_layers=3)
    with pytest.raises(ValueError):
        config(kinds=("sliding_attention", "linear_attention"))
    assert paddle.models.Mellum2ForCausalLM is Mellum2ForCausalLM
    c = Mellum2Config()
    assert c.layer_types.count(FULL) == 7 and c.layer_types[3] == FULL
