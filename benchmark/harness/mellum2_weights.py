"""Seeded weights of the Mellum 2 decoder, drawn on the device: what
harness/keye_weights.py is to the Keye block, by its own machinery (one
hashed Box-Muller drawing program for the whole model; matrices
normal(0, 0.02), o_proj and the experts' down_proj scaled by 1/sqrt(2L),
the embedding normal(0, 1) so that tokens route apart, norm gains 1 +
normal(0, 0.02) so that a dropped gain shows). The experts' leaves hold
the HELD experts only ([L, held, ...]).
"""
from __future__ import annotations

from harness import keye_weights as kw
from reference import mellum2 as ref

OUTER = ref.OUTER_LEAVES
PROGRAM_NAME = kw.PROGRAM_NAME


def shapes(cfg: dict) -> dict:
    """The sizes both sides are built from, out of a configuration file:
    `Mellum2Config`'s fields, which the reference reads under the same
    names."""
    lo, hi = cfg["held_experts"]
    if hi - lo != cfg["num_experts"]:
        raise SystemExit("benchmark: held_experts does not hold "
                         "num_experts experts")
    rope = cfg["rope_parameters"]
    yarn, plain = rope["full_attention"], rope["sliding_attention"]
    if yarn["rope_theta"] != plain["rope_theta"]:
        raise SystemExit("benchmark: the two kinds of layer turn by "
                         "different bases")
    n = cfg["num_hidden_layers"]
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=n, layer_types=tuple(cfg["layer_types"][:n]),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rms_norm_eps=cfg["rms_norm_eps"],
        sliding_window=cfg["sliding_window"],
        rope_theta=float(plain["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original_positions=yarn["original_max_position_embeddings"],
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_attention_factor=yarn["attention_factor"],
        num_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        router_aux_loss_coef=cfg["router_aux_loss_coef"],
        held_experts=(lo, hi))


def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, kind, std); layer leaves are 'layers.<name>'."""
    s = shapes(cfg)
    h, n, d = s["hidden_size"], s["num_layers"], s["head_dim"]
    heads, kvh = s["num_attention_heads"], s["num_key_value_heads"]
    held = s["held_experts"][1] - s["held_experts"][0]
    f, e = s["moe_intermediate_size"], s["num_experts"]
    std, res = kw.STD, kw.STD / (2.0 * n) ** 0.5
    layer = {
        "input_layernorm.weight": ((h,), "gain", std),
        "self_attn.q_proj.weight": ((h, heads * d), "w", std),
        "self_attn.k_proj.weight": ((h, kvh * d), "w", std),
        "self_attn.v_proj.weight": ((h, kvh * d), "w", std),
        "self_attn.o_proj.weight": ((heads * d, h), "w", res),
        "self_attn.q_norm.weight": ((d,), "gain", std),
        "self_attn.k_norm.weight": ((d,), "gain", std),
        "post_attention_layernorm.weight": ((h,), "gain", std),
        "mlp.router": ((h, e), "w", std),
        "mlp.gate_proj": ((held, h, f), "w", std),
        "mlp.up_proj": ((held, h, f), "w", std),
        "mlp.down_proj": ((held, f, h), "w", res),
    }
    specs = {"embed_tokens.weight": ((s["vocab_size"], h), "w", kw.EMBED_STD),
             "norm.weight": ((h,), "gain", std),
             "lm_head": ((s["vocab_size"], h), "w", std)}
    for name in ref.LAYER_LEAVES:
        shape, kind, dev = layer[name]
        specs["layers." + name] = ((n,) + shape, kind, dev)
    return specs


def reference_params(cfg: dict, seed: int):
    """(outer dict, list of per-layer dicts) in float32."""
    specs = leaf_specs(cfg)
    drawn = kw._drawer(kw._items(specs, dict.fromkeys(specs, "float32")))(
        *kw._key_args(seed))
    outer = {k: drawn[k][0] for k in OUTER}
    layers = [{k: drawn["layers." + k][i] for k in ref.LAYER_LEAVES}
              for i in range(cfg["num_hidden_layers"])]
    return outer, layers


def program_leaves(model, cfg: dict):
    """[(leaf name, layer index or None, Parameter)] of the program's
    Mellum2ForCausalLM."""
    named = dict(model.named_parameters())
    out = [(k, None, named[PROGRAM_NAME[k]]) for k in OUTER]
    for name in ref.LAYER_LEAVES:
        for i in range(cfg["num_hidden_layers"]):
            out.append(("layers." + name, i,
                        named[f"model.layers.{i}.{name}"]))
    if len(out) != len(named):
        raise RuntimeError(
            f"the model has {len(named)} parameters, the benchmark's "
            f"leaf table covers {len(out)}")
    return out


def load_into(model, cfg: dict, seed: int):
    """Re-draw every parameter of `model` from `seed`, on the device, in
    the type the model stores it in."""
    specs, held = leaf_specs(cfg), {}
    for leaf, _, p in program_leaves(model, cfg):
        held.setdefault(leaf, []).append(p)
    drawn = kw._drawer(kw._items(specs, {k: v[0]._data.dtype
                                         for k, v in held.items()}))(
        *kw._key_args(seed))
    for leaf, params in held.items():
        for p, a in zip(params, drawn[leaf]):
            if tuple(a.shape) != tuple(p._data.shape):
                raise RuntimeError(f"{leaf}: drew {a.shape}, the program "
                                   f"holds {p._data.shape}")
            p._data = a


def sq_deltas(cfg: dict, seed: int, arrays: dict, dtypes: dict) -> dict:
    """{leaf: sum((arrays[leaf] - the seeded leaf, rounded through
    dtypes[leaf]) ** 2)} in ONE program (keye_weights.sq_deltas)."""
    fn = kw._delta_reader(kw._items(leaf_specs(cfg), dtypes))
    return {k: float(v) for k, v in fn(arrays, *kw._key_args(seed)).items()}
