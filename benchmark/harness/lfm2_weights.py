"""Seeded weights of LFM2-MoE's decoder, drawn on the device: what
harness/ling3_weights.py is to the Ling-3.0 block, by the same hashed
Box-Muller normals and in one program for the whole model. The reference
walks SUB-LAYERS (reference/lfm2.py: a published layer's mixer and its ffn
are one each), of four kinds, so a layer leaf is `<kind>.<leaf>` (`conv.`,
`attn.`, `dense.`, `moe.`), stacked over the sub-layers of its kind in order.

Matrices normal(0, 0.02); the residual products (the convolution's W_out,
o_proj, the dense MLP's and the experts' down products) divided by sqrt(2 x
the PUBLISHED depth, 40: two residual branches a layer); the embedding
normal(0, 4) so that tokens route apart (nemotron3_weights.EMBED_STD's
reason); norm gains 1 + normal(0, 0.02) so that a dropped one shows; the
convolutions' taps uniform(+-3^-1/2), torch's Conv1d at one input channel a
group. The head is TIED: with an embedding of spread 4 a final norm of unit
gain would give the input token's own logit |E_t|^2 / 4 = 8,192 and a
softmax with all its mass there, so the final norm's gain is 2^-10 (1 +
normal(0, 0.02)): the input token's logit about 8, a quarter of the mass,
the other logits of spread 0.18, the first loss near ln 8,192 + 0.3. The
mixtures' selection bias (a BUFFER: no gradient, no update) is normal(0,
0.002), not zero, so that a dropped bias shows. The experts' leaves hold the
HELD experts only.
"""
from __future__ import annotations

import functools  # noqa: F401  (the borrowed functions' names)
import math  # noqa: F401

from harness import keye_weights as kw
from harness import nemotron3_weights as nw
from harness.ling3_weights import borrow
from reference import lfm2 as ref

OUTER = ref.OUTER_LEAVES
PROGRAM_NAME = kw.PROGRAM_NAME
EMBED_STD = nw.EMBED_STD
BIAS_STD = nw.BIAS_STD
HEAD_GAIN = 2.0 ** -10
KINDS = tuple(ref.KIND_NAMES)
STACKED = tuple(name + "." for name in ref.KIND_NAMES.values())


def layer_types(cfg: dict) -> tuple:
    """The kept layers' mixer kinds, from the file's `layer_kinds`
    ("<mixer>+<ffn>" a layer; `layer_types` is the published list, whole)."""
    kinds = [k.split("+") for k in cfg["layer_kinds"]]
    dense = [ffn == "dense" for _, ffn in kinds]
    if len(kinds) != cfg["num_hidden_layers"] or dense != sorted(
            dense, reverse=True) or sum(dense) != cfg["num_dense_layers"]:
        raise SystemExit("benchmark: layer_kinds does not name "
                         "num_hidden_layers layers, the num_dense_layers "
                         "dense ones first")
    return tuple(mixer for mixer, _ in kinds)


def shapes(cfg: dict) -> dict:
    """The sizes both sides are built from, out of a configuration file:
    `Lfm2MoeConfig`'s fields, which the reference reads under the same
    names."""
    lo, hi = cfg["held_experts"]
    if hi - lo != cfg["num_experts"]:
        raise SystemExit("benchmark: held_experts does not hold "
                         "num_experts experts")
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_dense_layers", "intermediate_size", "norm_eps",
            "conv_L_cache", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_experts_per_tok", "moe_intermediate_size",
            "norm_topk_prob", "routed_scaling_factor",
            "router_aux_loss_coef")
    return dict({k: cfg[k] for k in same},
                layer_types=layer_types(cfg),
                rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
                num_experts=cfg["published"]["num_experts"],
                held_experts=(lo, hi))


def kinds(cfg: dict) -> tuple:
    """The 2 L sub-layers' kinds (reference/lfm2.py `kinds_of`)."""
    return ref.kinds_of(shapes(cfg))


def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, kind, std); a layer leaf is '<kind>.<leaf>' with its
    kind's sub-layers on a leading axis. BIAS leaves are buffers."""
    s = shapes(cfg)
    h, heads, kv, d = (s["hidden_size"], s["num_attention_heads"],
                       s["num_key_value_heads"], s["head_dim"])
    taps = s["conv_L_cache"]
    held = s["held_experts"][1] - s["held_experts"][0]
    f, e, fd = (s["moe_intermediate_size"], s["num_experts"],
                s["intermediate_size"])
    depth = cfg["published"].get("num_hidden_layers",
                                 s["num_hidden_layers"])
    std, res = kw.STD, kw.STD / (2.0 * depth) ** 0.5
    per_kind = {
        ref.CONV: {
            "operator_norm.weight": ((h,), "gain", std),
            "conv.in_proj.weight": ((h, 3 * h), "w", std),
            "conv.conv_weight": ((taps, h), "uniform", taps ** -0.5),
            "conv.out_proj.weight": ((h, h), "w", res)},
        ref.ATTN: {
            "operator_norm.weight": ((h,), "gain", std),
            "self_attn.q_proj.weight": ((h, heads * d), "w", std),
            "self_attn.k_proj.weight": ((h, kv * d), "w", std),
            "self_attn.v_proj.weight": ((h, kv * d), "w", std),
            "self_attn.q_norm.weight": ((d,), "gain", std),
            "self_attn.k_norm.weight": ((d,), "gain", std),
            "self_attn.o_proj.weight": ((heads * d, h), "w", res)},
        ref.DENSE: {
            "ffn_norm.weight": ((h,), "gain", std),
            "feed_forward.gate_proj.weight": ((h, fd), "w", std),
            "feed_forward.up_proj.weight": ((h, fd), "w", std),
            "feed_forward.down_proj.weight": ((fd, h), "w", res)},
        ref.MIXTURE: {
            "ffn_norm.weight": ((h,), "gain", std),
            "feed_forward.router": ((h, e), "w", std),
            "feed_forward.gate_proj": ((held, h, f), "w", std),
            "feed_forward.up_proj": ((held, h, f), "w", std),
            "feed_forward.down_proj": ((held, f, h), "w", res),
            ref.BIAS: ((e,), "w", BIAS_STD)},
    }
    specs = {"embed_tokens.weight": ((s["vocab_size"], h), "w", EMBED_STD),
             "norm.weight": ((h,), "scaled_gain", (HEAD_GAIN, std))}
    present = kinds(cfg)
    for kind, leaves in per_kind.items():
        count = present.count(kind)
        for name, (shape, what, dev) in leaves.items():
            if count:
                specs[kind + "." + name] = ((count,) + shape, what, dev)
    return specs


def _names(kind):
    return ref.LEAVES[kind] + ((ref.BIAS,) if kind == ref.MIXTURE else ())


def reference_params(cfg: dict, seed: int):
    """(outer dict, list of per-sub-layer dicts, a mixture's with its BIAS)
    in float32."""
    specs = leaf_specs(cfg)
    drawn = _draw(specs, dict.fromkeys(specs, "float32"), seed)
    outer = {k: drawn[k][0] for k in OUTER}
    seen, layers = dict.fromkeys(KINDS, 0), []
    for kind in kinds(cfg):
        layers.append({k: drawn[kind + "." + k][seen[kind]]
                       for k in _names(kind)})
        seen[kind] += 1
    return outer, layers


def program_leaves(model, cfg: dict):
    """[(leaf name, index among its kind's sub-layers or None, Parameter)]
    of the program's Lfm2MoeForCausalLM: sub-layer j is half of the
    program's layer j // 2; the model has no `lm_head` leaf."""
    named = dict(model.named_parameters())
    out = [(k, None, named[PROGRAM_NAME[k]]) for k in OUTER]
    for kind in KINDS:
        where = [j for j, k in enumerate(kinds(cfg)) if k == kind]
        for name in ref.LEAVES[kind]:
            for n, j in enumerate(where):
                out.append((kind + "." + name, n,
                            named[f"model.layers.{j // 2}.{name}"]))
    if len(out) != len(named):
        raise RuntimeError(
            f"the model has {len(named)} parameters, the benchmark's "
            f"leaf table covers {len(out)}")
    return out


def program_biases(model, cfg: dict):
    """[the selection-bias buffer of every mixture layer, in order]."""
    named = dict(model.named_buffers())
    return [named[f"model.layers.{j // 2}.{ref.BIAS}"]
            for j, k in enumerate(kinds(cfg)) if k == ref.MIXTURE]


def _shaped(x, kind, par):
    """nemotron3_weights' kinds of leaf, and `uniform`: in (-par, par);
    `scaled_gain`: par[0] (1 + normal(0, par[1]))."""
    import jax
    import jax.numpy as jnp

    if kind == "uniform":
        return jax.lax.erf(x * jnp.float32(2.0 ** -0.5)) * jnp.float32(par)
    if kind == "scaled_gain":
        return jnp.float32(par[0]) * (1.0 + x * jnp.float32(par[1]))
    return nw._shaped(x, kind, par)


# What names no block, looking its names up HERE (ling3_weights.py's note)
_over_layers = borrow(nw._over_layers, globals())
_items = borrow(nw._items, globals())
_draw = borrow(nw._draw, globals())
_drawer = borrow(nw._drawer, globals(), cached=True)
compile_reference_drawer = borrow(nw.compile_reference_drawer, globals())
load_into = borrow(nw.load_into, globals())
_delta_reader = borrow(nw._delta_reader, globals(), cached=True)
sq_deltas = borrow(nw.sq_deltas, globals())
