"""Keye-VL-2.0's language model against the plain reference
(benchmark/reference/keye_vl2.py, which imports nothing of the program):
tiny widths, float32, seeded weights."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import KeyeVL2Config, KeyeVL2ForCausalLM
from paddle_tpu.ops import sparse_attention as sa

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmark"))
from reference import keye_vl2 as ref  # noqa: E402

B, S, VOCAB, EXPERTS = 2, 32, 61, 8


def config(held=None, topk=8, layers=2, **kw):
    return KeyeVL2Config(
        vocab_size=VOCAB, hidden_size=32, num_layers=layers,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mrope_section=(2, 3, 3), num_experts=EXPERTS, num_experts_per_tok=2,
        moe_intermediate_size=24, index_n_heads=2, index_head_dim=8,
        index_topk=topk, index_q_chunk=8, moe_tile_rows=8,
        held_experts=held, router_aux_loss_coef=0.01, **kw)


def ref_config(c):
    lo, hi = c.held_experts or (0, c.num_experts)
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_attention_heads,
        num_key_value_heads=c.num_key_value_heads, head_dim=c.head_dim,
        rms_norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
        mrope_section=tuple(c.mrope_section), num_experts=c.num_experts,
        num_experts_per_tok=c.num_experts_per_tok,
        norm_topk_prob=c.norm_topk_prob,
        router_aux_loss_coef=c.router_aux_loss_coef,
        index_n_heads=c.index_n_heads, index_head_dim=c.index_head_dim,
        index_topk=c.index_topk, held_experts=(lo, hi))


def build(c, seed=0):
    """The program's model with every leaf drawn anew (gains 1 + normal,
    so that a dropped one shows)."""
    paddle.seed(seed)
    model = KeyeVL2ForCausalLM(c)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        x = rng.standard_normal(p.shape).astype(np.float32)
        gain = "norm" in name and name.endswith("weight")
        p._data = jnp.asarray(1.0 + 0.1 * x if gain else 0.3 * x)
    return model


def ref_params(model):
    """(outer, layers) of the reference from the program's parameters."""
    named = {k: v._data for k, v in model.named_parameters()}
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


def equal_rows():
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (3, B, S))


def unequal_rows(seed=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.sort(rng.integers(0, 4 * S, (3, B, S)), axis=-1),
                       jnp.int32)


def program_grads(model, ids, labels, positions=None):
    pos = None if positions is None else paddle.to_tensor(positions)
    loss = model.loss(paddle.to_tensor(ids), paddle.to_tensor(labels), pos)
    loss.backward()
    grads = {k: (None if p.grad is None else np.asarray(p.grad._data))
             for k, p in model.named_parameters()}
    model.clear_gradients()
    return float(loss), grads


def assert_grads_match(grads, want, layers):
    flat = {"model.embed_tokens.weight": want["outer"]["embed_tokens.weight"],
            "model.norm.weight": want["outer"]["norm.weight"],
            "lm_head": want["outer"]["lm_head"]}
    for i in range(layers):
        for k, g in want["layers"][i].items():
            flat[f"model.layers.{i}.{k}"] = g
    assert set(flat) == set(grads)
    for k, g in flat.items():
        g = np.asarray(g)
        scale = max(float(np.abs(g).max()), 1e-6)
        assert grads[k] is not None, k
        np.testing.assert_allclose(grads[k], g, atol=2e-4 * scale + 1e-7,
                                   rtol=2e-3, err_msg=k)


@pytest.mark.parametrize("held,positions", [
    (None, "equal"), ((2, 4), "equal"), (None, "unequal")],
    ids=["all-experts", "2-of-8-held", "unequal-position-rows"])
def test_loss_and_every_gradient_match_the_reference(held, positions):
    c = config(held)
    model = build(c)
    ids, labels = batch()
    pos = None if positions == "equal" else unequal_rows()
    loss, grads = program_grads(model, ids, labels, pos)
    outer, layers = ref_params(model)
    want_loss, parts, want = ref.loss_and_grads(
        outer, layers, ref_config(c), ids, labels, pos)
    assert parts[1] > 0 and parts[2] > 0      # both auxiliary terms live
    np.testing.assert_allclose(loss, want_loss, rtol=2e-5)
    assert_grads_match(grads, want, c.num_layers)
    if held is not None:
        routed = model.routing_counters()["routed_pairs"]
        assert 0 < routed < c.num_layers * B * S * c.num_experts_per_tok


def _layer_out(model, x, positions):
    with paddle.no_grad():
        out = model.model.layers[0](paddle.to_tensor(x),
                                    paddle.to_tensor(positions))[0]
    return np.asarray(out._data)


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Every share routes over all experts and computes its own; the
    shares' outputs, attention's part counted once, are the uncut
    layer's."""
    whole = build(config(None, layers=1))
    rng = np.random.default_rng(3)
    x = rng.standard_normal((B, S, 32)).astype(np.float32)
    pos = equal_rows()
    layer0 = whole.model.layers[0]
    with paddle.no_grad():
        sel, _, _ = layer0.select(paddle.to_tensor(x), paddle.to_tensor(pos))
        attended = np.asarray(layer0._attend(
            paddle.to_tensor(x), sel, paddle.to_tensor(pos))._data)
    total = attended.copy()
    named = dict(whole.named_parameters())
    for lo in range(0, EXPERTS, 2):
        share = build(config((lo, lo + 2), layers=1))
        for k, p in share.named_parameters():
            src = named[k]._data
            p._data = src[lo:lo + 2] if src.shape != p._data.shape else src
        total += _layer_out(share, x, pos) - attended
    outer, layers = ref_params(whole)
    cfg = ref_config(whole.config)
    want = np.stack([np.asarray(ref.layer(
        layers[0], jnp.asarray(x[b]), pos[:, b], cfg, "float32")[0])
        for b in range(B)])
    np.testing.assert_allclose(total, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(_layer_out(whole, x, pos), want, atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("topk", [S, 2 * S, 5, 12])
def test_selection_is_the_references_and_dense_when_topk_covers(topk):
    """topk >= S is full causal attention; topk < S is the mask form,
    with the selected sets equal to the reference's."""
    model = build(config(None, topk=topk, layers=1), seed=4)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((B, S, 32)).astype(np.float32)
    pos = equal_rows()
    layer0 = model.model.layers[0]
    with paddle.no_grad():
        sel, _, kept = layer0.select(paddle.to_tensor(x),
                                     paddle.to_tensor(pos))
    sel = np.asarray(sel._data) != 0
    _, layers = ref_params(model)
    cfg = ref_config(model.config)
    want = np.stack([np.asarray(ref.selected(
        layers[0], jnp.asarray(x[b]), pos[:, b], cfg)) for b in range(B)])
    assert (sel == want).all()
    assert int(kept._data) == want.sum()
    assert (sel.sum(-1) == np.minimum(topk, np.arange(S) + 1)).all()
    if topk >= S:
        assert (sel == np.tril(np.ones((S, S), bool))).all()
        # ... and the layer is then the plain causal one
        dense = config(None, topk=topk, layers=1)
        cfg_dense = dict(ref_config(dense), index_topk=10 ** 6)
        out = _layer_out(model, x, pos)
        full = np.stack([np.asarray(ref.layer(
            layers[0], jnp.asarray(x[b]), pos[:, b], cfg_dense,
            "float32")[0]) for b in range(B)])
        np.testing.assert_allclose(out, full, atol=2e-4, rtol=2e-4)


def test_ties_go_to_the_lower_index():
    scores = jnp.asarray([[1.0, 0.0, -0.0, 0.0, 2.0, 0.0],
                          [3.0, 3.0, 3.0, 3.0, 3.0, 3.0]])
    valid = jnp.asarray([[True] * 6, [True, True, True, True, False, False]])
    got = np.asarray(sa.topk_mask(scores, valid, 3))
    assert got.tolist() == [[True, True, False, False, True, False],
                            [True, True, True, False, False, False]]
    assert (got == np.asarray(ref.selection(scores, valid, 3))).all()


def test_three_equal_position_rows_are_llamas_rope():
    from paddle_tpu.models.llama import _rope_tables, apply_rotary_pos_emb

    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((B, S, 4, 16)), jnp.float32)
    cos, sin = sa.mrope_angles(equal_rows(), 16, 1e4, (2, 3, 3))
    lcos, lsin = _rope_tables(S, 16, 1e4)
    np.testing.assert_allclose(sa.apply_rotary(x, cos, sin),
                               apply_rotary_pos_emb(x, lcos, lsin),
                               atol=1e-5)
    # unequal rows: each block of frequencies follows its own row
    pos = unequal_rows()
    cos, _ = sa.mrope_angles(pos, 16, 1e4, (2, 3, 3))
    inv = 1e4 ** (-np.arange(8) / 8.0)
    row = np.repeat(np.arange(3), (2, 3, 3))
    want = np.cos(np.asarray(pos, np.float64)[row].transpose(1, 2, 0) * inv)
    np.testing.assert_allclose(cos, want, atol=1e-4)


def test_every_token_on_one_held_expert_and_none_dropped():
    c = config((3, 4), layers=1)
    model = build(c, seed=7)
    router = model.model.layers[0].mlp.router
    rigged = np.zeros(router.shape, np.float32)
    rigged[:, 3], rigged[:, 5] = 0.5, 0.4     # every token picks 3 and 5
    router._data = jnp.asarray(rigged)
    gain = model.model.layers[0].post_attention_layernorm.weight
    gain._data = jnp.abs(gain._data)          # keeps the rigged order
    rng = np.random.default_rng(9)
    x = np.abs(rng.standard_normal((B, S, 32))).astype(np.float32) + 0.1
    layer0 = model.model.layers[0]
    with paddle.no_grad():
        y, _, stats, _ = layer0.mlp(
            layer0.post_attention_layernorm(paddle.to_tensor(x)))
    stats = np.asarray(stats._data)
    assert stats[0] == B * S            # every token's pair on expert 3
    assert stats[2] == B * S
    assert stats[1] >= B * S            # rows computed cover every pair
    _, layers = ref_params(model)
    cfg = ref_config(c)
    want = np.stack([np.asarray(ref.experts(
        layers[0], jnp.asarray(x[b]), cfg, "float32")[0]) - x[b]
        for b in range(B)])
    np.testing.assert_allclose(np.asarray(y._data), want, atol=2e-4,
                               rtol=2e-4)
    assert np.abs(want).min(axis=-1).max() > 0     # nobody got nothing


@pytest.mark.parametrize("term", ["index-loss", "lm-and-balance"])
def test_the_indexer_learns_from_its_own_loss_alone(term):
    model = build(config(None))
    ids, labels = batch()
    lm, balance, index_loss = model.loss_terms(paddle.to_tensor(ids),
                                               paddle.to_tensor(labels))
    (index_loss if term == "index-loss" else lm + balance).backward()
    for name, p in model.named_parameters():
        moved = p.grad is not None and float(jnp.abs(p.grad._data).max()) > 0
        assert moved == (("indexer." in name) == (term == "index-loss")), \
            name


@pytest.mark.parametrize("overridden", [False, True])
def test_a_global_initializer_stands_in_for_the_models_own_draw(overridden):
    """Before a checkpoint is loaded: under set_global_initializer the
    caller's initializer stands (no host-side draw, no residual
    scaling); without one the matrices are normal(0, initializer_range)
    and the two residual projections 1/sqrt(2L) of that."""
    from paddle_tpu.nn import initializer

    c = config(layers=2)
    if overridden:
        initializer.set_global_initializer(initializer.Constant(0.25),
                                           initializer.Constant(0.0))
    try:
        paddle.seed(3)
        model = KeyeVL2ForCausalLM(c)
    finally:
        initializer.set_global_initializer(None)
    named = {k: np.asarray(p._data) for k, p in model.named_parameters()}
    if overridden:
        assert all((a == (0.0 if k.endswith("bias") else 0.25)).all()
                   for k, a in named.items())
        return
    q = named["model.layers.0.self_attn.q_proj.weight"]
    o = named["model.layers.0.self_attn.o_proj.weight"]
    assert abs(q.std() / c.initializer_range - 1) < 0.2
    assert abs(o.std() * 2.0 / c.initializer_range - 1) < 0.2
    assert abs(named["lm_head"].std() / c.initializer_range - 1) < 0.2
    assert (named["model.norm.weight"] == 1).all()


def test_one_compiled_step_trains_and_counts():
    import paddle_tpu.optimizer as popt
    from paddle_tpu.jit import TrainStep

    c = config((2, 6), layers=1, use_recompute=True)
    model = build(c)
    opt = popt.AdamW(learning_rate=3e-3, parameters=model.parameters())
    step = TrainStep(model, lambda m, a, b: m.loss(a, b), opt)
    ids, labels = (paddle.to_tensor(a) for a in batch())
    losses = [float(step(ids, labels)) for _ in range(3)]
    assert losses[-1] < losses[0]
    assert step._jitted._cache_size() == 1
    counters = model.routing_counters()
    assert counters["kept_keys"] == c.num_layers * B * sum(
        min(c.index_topk, t + 1) for t in range(S))
    assert counters["computed_rows"] >= counters["routed_pairs"] > 0
    assert counters["max_load_over_mean"] >= 1.0
    # recompute changes nothing of the mathematics
    plain = build(config((2, 6), layers=1))
    a, _ = program_grads(plain, *batch())
    b, _ = program_grads(build(c), *batch())
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_recorded_picks_are_the_references_and_can_be_handed_to_it():
    """`record_picks` keeps which keys and experts a step picked; in
    float32 they are the reference's own, and the reference run on them
    gives the gradients it gives on its own."""
    c = config((2, 6))
    model = build(c)
    model.record_picks(B, S)
    ids, labels = batch()
    _, grads = program_grads(model, ids, labels)
    keys, experts = model.picks()
    assert keys.shape == (2, B, S, S) and experts.shape == (2, B, S, 2)
    assert (keys.sum(-1) == np.minimum(8, np.arange(S) + 1)).all()
    outer, layers = ref_params(model)
    _, _, want = ref.loss_and_grads(outer, layers, ref_config(c), ids,
                                    labels, given=(keys, experts))
    assert_grads_match(grads, want, c.num_layers)
    trainer = ref.RefTrainer(outer, layers, ref_config(c),
                             (0.0, 0.9, 0.95, 1e-8, 0.0),
                             given=(keys, experts))
    trainer.run([(ids, labels)] * 3)
    assert trainer.miss == {"key_pick_miss": 0.0, "expert_pick_miss": 0.0}
    # picks that are not its own: the share shows
    wrong = (experts + 1) % EXPERTS
    trainer = ref.RefTrainer(outer, layers, ref_config(c),
                             (0.0, 0.9, 0.95, 1e-8, 0.0),
                             given=(keys, wrong))
    trainer.run([(ids, labels)] * 3)
    assert trainer.miss["expert_pick_miss"] > 0.2


# -- the kernels: interpreted against their XLA forms, compiled for a v5e --

def test_selection_mask_in_the_splash_kernel_matches_xla():
    from paddle_tpu.ops.pallas.splash_attention import splash_attention

    rng = np.random.default_rng(10)
    b, s, h, kvh, d = 1, 256, 4, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    sel = jnp.asarray(rng.random((b, s, s)) < 0.3, jnp.int8)

    def run(kernel):
        def f(q, k, v):
            o = splash_attention(q, k, v, causal=True, selection=sel,
                                 interpret=kernel, use_kernel=kernel,
                                 block_q=128, block_k=128)
            return jnp.sum(o * jnp.cos(o)), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (_, out_k), grads_k = run(True)
    (_, out_x), grads_x = run(False)
    np.testing.assert_allclose(out_k, out_x, atol=2e-5)
    for a, b_ in zip(grads_k, grads_x):
        np.testing.assert_allclose(a, b_, atol=1e-4)


def test_head_mean_probs_kernels_match_xla():
    from paddle_tpu.ops.pallas.attention_probs import (
        head_mean_probs, head_mean_probs_xla)

    rng = np.random.default_rng(11)
    t, s, h, kvh, d = 64, 256, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, kvh, d)), jnp.float32)
    sel = jnp.asarray(rng.random((t, s)) < 0.3, jnp.int8).at[:, 0].set(1)
    got = head_mean_probs(q, k, sel, interpret=True, use_kernel=True,
                          block_k=128)
    want = head_mean_probs_xla(q, k, sel, 1.0 / d ** 0.5)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-5)


@pytest.mark.parametrize("t0,poison", [
    (0, False),       # the first chunk: one tile of four visited
    (128, False),     # its last query (191) falls inside tile 1
    (192, False),     # it ends on tile 1's edge (255)
    (448, False),     # the last chunk: every tile visited
    (0, True), (128, True), (192, True),
])
def test_head_mean_probs_stops_at_the_chunks_last_causal_tile(t0, poison):
    """`head_mean_probs(t0=)` over a 512-key sequence in four tiles, GQA
    4 : 1: the XLA expression's target, the kernels' own without `t0` to
    the bit, exact zeros beyond the last causal tile. Poisoned: NaN keys
    and a full selection beyond that tile change nothing (not read)."""
    from paddle_tpu.ops.pallas.attention_probs import (
        head_mean_probs, head_mean_probs_xla)

    rng = np.random.default_rng(14)
    t, s, h, kvh, d, bk = 64, 512, 4, 1, 32, 128
    q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, kvh, d)), jnp.float32)
    valid = np.arange(s)[None, :] <= t0 + np.arange(t)[:, None]
    sel = jnp.asarray((rng.random((t, s)) < 0.3) & valid,
                      jnp.int8).at[:, 0].set(1)
    beyond = ((t0 + t - 1) // bk + 1) * bk
    want = head_mean_probs_xla(q, k, sel, 1.0 / d ** 0.5)
    every_tile = head_mean_probs(q, k, sel, interpret=True, use_kernel=True,
                                 block_k=bk)
    if poison:
        k, sel = k.at[beyond:].set(jnp.nan), sel.at[:, beyond:].set(1)
    got = jax.jit(lambda *a: head_mean_probs(
        *a[:3], t0=a[3], interpret=True, use_kernel=True, block_k=bk))(
            q, k, sel, jnp.int32(t0))
    np.testing.assert_array_equal(got, every_tile)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert not np.asarray(got)[:, beyond:].any()       # exact zeros


def test_visited_pairs_by_hand():
    from paddle_tpu.ops.pallas.attention_probs import visited_pairs

    t, s = 512, 8192
    causal = s * (s + 1) // 2           # a sequence's, one head

    def tiles(bk, causal_only=True):
        return sum(visited_pairs(t, s, bk, t0 if causal_only else None)
                   for t0 in range(0, s, t)) // (t * bk)

    # 1, 1, 2, 2, .. 8, 8 of the 8 tiles a chunk has: 72 of 128
    assert tiles(1024) == 72 and tiles(1024, False) == 128
    assert tiles(512) == 136 and tiles(512, False) == 256      # 17 / 32
    assert visited_pairs(t, s, t0=3584) == t * 1024 * 4    # _pick_block's
    assert round(72 * t * 1024 / causal, 3) == 1.125
    assert round(136 * t * 512 / causal, 4) == 1.0624
    assert round(128 * t * 1024 / causal, 3) == 2.0


@pytest.mark.parametrize("t0,dtype", [
    (160, jnp.float32),     # the causal edge inside key tile 1; tile 2 skipped
    (0, jnp.float32),       # a first chunk: every tile but one skipped
    (352, jnp.float32),     # the last chunk: every tile visited
    (160, jnp.bfloat16),    # bf16 operands: the cotangent as exact addends
])
def test_indexer_scores_kernels_match_xla_and_its_vjp(t0, dtype):
    from paddle_tpu.ops.pallas import indexer_scores as isc

    rng = np.random.default_rng(12)
    t, s, j, d, bk = 32, 384, 4, 16, 128
    q, k, w = (jnp.asarray(rng.standard_normal(shape), dtype)
               for shape in ((t, j, d), (s, d), (t, j)))
    valid = jnp.arange(s)[None, :] <= t0 + jnp.arange(t)[:, None]
    g = jnp.where(valid, jnp.asarray(rng.standard_normal((t, s)),
                                     jnp.float32), 0.0)
    want, pull = jax.vjp(sa.indexer_scores,
                         *(x.astype(jnp.float32) for x in (q, k, w)))
    got = isc.indexer_scores_fwd(q, k, w, jnp.int32(t0), bk, True)
    np.testing.assert_allclose(jnp.where(valid, got, 0.0),
                               jnp.where(valid, want, 0.0), atol=1e-5)
    grads = isc.indexer_scores_bwd(q, k, w, jnp.int32(t0), g, bk, True)
    for a, b_ in zip(grads, pull(g)):
        np.testing.assert_allclose(a, b_, atol=1e-4)
    beyond = ((t0 + t - 1) // bk + 1) * bk
    assert not np.asarray(grads[1])[beyond:].any()     # exact zeros
    # and joined by the custom VJP, cotangents in the operands' types
    out, pull_k = jax.vjp(lambda *a: isc.causal_indexer_scores(
        *a, jnp.int32(t0), bk, True), q, k, w)
    np.testing.assert_array_equal(out, got)
    for a, b_ in zip(pull_k(g), grads):
        assert a.dtype == dtype
        np.testing.assert_array_equal(a, b_.astype(dtype))


def test_the_selection_through_the_kernels_is_the_xla_paths():
    """`indexer_select` with its chunk scores from the interpreted kernel
    pair (four chunks, so skipped tiles and a traced t0): the same picks,
    loss and gradients as with the XLA expression."""
    from paddle_tpu.utils import flags

    rng = np.random.default_rng(13)
    b, s, h, kvh, d, j, di = 1, 256, 2, 1, 16, 2, 16
    q, k, qi, ki, w = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                       for shape in ((b, s, h, d), (b, s, kvh, d),
                                     (b, s, j, di), (b, s, di), (b, s, j)))

    def run():
        def loss(qi, ki, w):
            sel, li, kept = sa.indexer_select(q, k, qi, ki, w, 24, 64)
            return li, (sel, kept)
        return jax.value_and_grad(loss, argnums=(0, 1, 2),
                                  has_aux=True)(qi, ki, w)

    (li_x, (sel_x, kept_x)), grads_x = run()
    flags.set_flags({"FLAGS_pallas_force_interpret": True})
    try:
        (li_k, (sel_k, kept_k)), grads_k = run()
    finally:
        flags.set_flags({"FLAGS_pallas_force_interpret": False})
    np.testing.assert_array_equal(sel_k, sel_x)
    assert int(kept_k) == int(kept_x)
    np.testing.assert_allclose(li_k, li_x, rtol=1e-5)
    for a, b_ in zip(grads_k, grads_x):
        np.testing.assert_allclose(a, b_, atol=1e-6)


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a DESCRIBED v5e (tests/test_fused_scan_step.py has the
    pattern): the kernels compile for it with nothing attached."""
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


@pytest.mark.parametrize("kernel", ["attn_probs", "splash_selection",
                                    "indexer_scores", "splash_window",
                                    "fused_ce_2304", "fused_ce_18992",
                                    "moe_rows_2048", "moe_rows_2304"])
def test_the_new_kernels_compile_for_a_v5e_at_published_widths(v5e_chip,
                                                               kernel):
    """`splash_window` and `fused_ce_2304` are the window + mixture
    block's (tests/test_mellum2.py; kept here because one process
    describes the chip): the banded splash kernels at its geometry, and
    fused CE at hidden 2304 over a 24,576-row head, where a 512-row
    vocabulary tile's backward asked for 25.5 MiB of a v5e's 16.
    `fused_ce_18992`: this block's own head, 151,936 / 8 rows = 148 x 128
    + 48: value and both gradients through the kernels, the last
    vocabulary tile a boundary block on the unpadded [18992, 2048] head
    and float32 accumulator, and no loop of the XLA tiles left.
    `moe_rows_*`: one dropless layer's forward and backward tile loops at
    both blocks' widths, 32,768 tokens in 512-row tiles: `moe_add_rows`
    asks its two float32 tiles and 2 MiB of VMEM (10.0 and 11.0 MiB)."""
    from paddle_tpu.incubate.distributed.models.moe import dropless
    from paddle_tpu.ops.pallas import moe_rows
    from paddle_tpu.ops.pallas import routing
    from paddle_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy
    from paddle_tpu.ops.pallas.attention_probs import head_mean_probs
    from paddle_tpu.ops.pallas.indexer_scores import causal_indexer_scores
    from paddle_tpu.ops.pallas.splash_attention import splash_attention

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    bf16 = jnp.bfloat16
    if kernel == "attn_probs":
        def fn(q, k, sel, t0):
            return head_mean_probs(q, k, sel, t0=t0)
        args, want = (spec((512, 32, 128), bf16), spec((8192, 4, 128), bf16),
                      spec((512, 8192), jnp.int8), spec((), jnp.int32)), {
                          "attn_probs_stats", "attn_probs_mean"}
    elif kernel == "indexer_scores":
        def fn(q, k, w, t0, g):
            out, pull = jax.vjp(lambda *a: causal_indexer_scores(*a, t0),
                                q, k, w)
            return out, pull(g)
        args, want = (spec((512, 16, 64), bf16), spec((8192, 64), bf16),
                      spec((512, 16), bf16), spec((), jnp.int32),
                      spec((512, 8192), jnp.float32)), {
                          "indexer_scores_fwd", "indexer_scores_bwd"}
    elif kernel == "splash_window":
        def fn(q, k, v):
            return jax.grad(lambda *a: jnp.sum(splash_attention(
                *a, causal=True, window=1024).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)
        args, want = (spec((4, 8192, 32, 128), bf16),
                      spec((4, 8192, 4, 128), bf16),
                      spec((4, 8192, 4, 128), bf16)), {"splash_fwd",
                                                       "splash_bwd"}
    elif kernel.startswith("fused_ce"):
        vocab, hidden = {"fused_ce_2304": (24576, 2304),
                         "fused_ce_18992": (18992, 2048)}[kernel]

        def fn(h, w, labels):
            return jax.value_and_grad(lambda h, w: jnp.sum(
                fused_cross_entropy(h, w, labels)), argnums=(0, 1))(h, w)
        args, want = (spec((32768, hidden), bf16),
                      spec((vocab, hidden), bf16),
                      spec((32768,), jnp.int32)), {"fused_ce_fwd",
                                                   "fused_ce_bwd"}
    elif kernel.startswith("moe_rows"):
        k = int(kernel.rsplit("_", 1)[1])
        t, g, tile, n = 32768, 16, 512, {2048: 768, 2304: 896}[k]
        m = dropless.plan_rows(t * 8, g, tile)
        vmem = moe_rows._add_rows_vmem(tile, k)
        assert vmem == {2048: 10_485_760, 2304: 11_534_336}[k]

        def fn(x, wg, wu, wd, row_w, tokens, tile_expert, tile_start,
               tile_real, n_tiles, dout):
            out, pull = jax.vjp(
                lambda *a: dropless.grouped_ffn(
                    *a[:4], tokens, a[4], tile_expert, tile_start, tile_real,
                    n_tiles, tile), x, wg, wu, wd, row_w)
            return out, pull(dout)
        args, want = (spec((t, k), bf16), spec((g, k, n), bf16),
                      spec((g, k, n), bf16), spec((g, n, k), bf16),
                      spec((t * 8 + tile,), jnp.float32),
                      spec((t * 8 + tile,), jnp.int32),
                      spec((m // tile,), jnp.int32),
                      spec((m // tile,), jnp.int32),
                      spec((m // tile,), jnp.int32), spec((), jnp.int32),
                      spec((t, k), jnp.float32)), {"moe_add_rows"}
    else:
        def fn(q, k, v, sel):
            return jax.grad(lambda *a: jnp.sum(splash_attention(
                *a, causal=True, selection=sel).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)
        args, want = (spec((1, 8192, 32, 128), bf16),
                      spec((1, 8192, 4, 128), bf16),
                      spec((1, 8192, 4, 128), bf16),
                      spec((1, 8192, 8192), jnp.int8)), {"splash_fwd",
                                                         "splash_bwd"}
    compiled = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert set(routing.mosaic_kernels(text)) == want
    # no per-head score tensor outside the kernels
    assert "f32[512,16,8192]" not in text
    if kernel == "fused_ce_18992":
        assert routing.mosaic_kernels(text) == {"fused_ce_fwd": 1,
                                                "fused_ce_bwd": 1}
        assert " while(" not in text            # XLA's 149 tiles are gone
        assert "f32[32768,2048]" not in text    # and their dx accumulator
        assert not [k for k in routing.xla_fallbacks
                    if k[0] == "fused_cross_entropy"
                    and "vocab=18992" in k[1]]
        # dW leaves as the head's rows, no padded copy of it or of W
        assert "[19072,2048]" not in text
    if kernel.startswith("moe_rows"):
        # the forward loop's add-back and the backward's, at the VMEM
        # asked, in XLA's scatter-add's place
        assert routing.mosaic_kernels(text) == {"moe_add_rows": 2}
        assert str(vmem) in text
        assert "moe/route/scatter-add" not in text


@pytest.mark.parametrize("rows,hidden,vocab,eqns", [
    (8192, 2048, 50304, (41, 37)),      # the GPT cells' head
    (32768, 2304, 24576, (41, 37)),     # mellum2's slice
    (32768, 2688, 16384, (41, 37)),     # nemotron3's slice
    (32768, 2048, 18992, (43, 45)),     # this block's: the masked tile
])
def test_a_head_of_whole_tiles_traces_the_kernels_without_the_mask(
        rows, hidden, vocab, eqns):
    """The partial last tile is a Python test on the head's shape: a
    vocabulary of whole tiles traces the two kernel bodies PR 39 traced
    (41 and 37 equations, no compare against the vocabulary), so keye's
    mask cannot leak into the other cells' steps; keye's own bodies carry
    it (a compare + select on the logits; backward that and W's rows)."""
    from paddle_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy
    from paddle_tpu.utils import flags

    def fn(h, w, labels):
        return jax.value_and_grad(lambda h, w: jnp.sum(fused_cross_entropy(
            h, w, labels, use_kernel=True, interpret=False)), (0, 1))(h, w)

    selfcheck = flags.get_flag("FLAGS_pallas_alias_selfcheck")
    flags.set_flags({"FLAGS_pallas_alias_selfcheck": False})
    try:
        jaxpr = jax.make_jaxpr(fn)(
            jax.ShapeDtypeStruct((rows, hidden), jnp.bfloat16),
            jax.ShapeDtypeStruct((vocab, hidden), jnp.bfloat16),
            jax.ShapeDtypeStruct((rows,), jnp.int32))
    finally:
        flags.set_flags({"FLAGS_pallas_alias_selfcheck": selfcheck})
    bodies = {}

    def walk(j):
        for e in j.eqns:
            if e.primitive.name == "pallas_call":
                bodies[e.params["name"]] = e.params
            for v in e.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jaxpr.jaxpr)
    assert sorted(bodies) == ["fused_ce_bwd", "fused_ce_fwd"]
    got = tuple(len(bodies[k]["jaxpr"].eqns)
                for k in ("fused_ce_fwd", "fused_ce_bwd"))
    assert got == eqns
    masked = vocab % 128 != 0
    for params in bodies.values():
        assert params["grid_mapping"].grid[1] == -(-vocab // 128)
        compares = [e for e in params["jaxpr"].eqns
                    if e.primitive.name == "lt"]
        assert bool(compares) == masked
