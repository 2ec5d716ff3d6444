"""Seeded weights of Ling-3.0's decoder, drawn on the device: what
harness/nemotron3_weights.py is to the Nemotron-H block, by the same hashed
Box-Muller normals and in one program for the whole model. The reference
walks SUB-LAYERS (reference/ling3.py: a published layer's mixer and its ffn
are one each), of four kinds, so a layer leaf is `<kind>.<leaf>` (`kda.`,
`mla.`, `dense.`, `moe.`), stacked over the sub-layers of its kind in order.

Matrices normal(0, 0.02); the residual products (o_proj, the dense MLP's,
the experts' and the shared expert's down products) divided by sqrt(2 x the
PUBLISHED depth, 42: two residual branches a layer), the embedding
normal(0, 4) so that tokens route apart (nemotron3_weights.EMBED_STD's
reason: a top-8 of 512 sits further out still); norm gains 1 + normal(0,
0.02) so that a dropped one shows; the convolutions' taps normal(0, 0.29)
(the spread of uniform(-1/2, 1/2)). A_log = log(A), A uniform in [0.5, 2],
and dt_bias normal(-2, 1): with W_f h of unit spread the gate's argument
exp(A_log)(W_f h + dt_bias) is then about -2 +- 2 and a = -5 sigmoid(.) spreads
over the whole of (-5, 0) with its weight near the slow end: half the
(token, channel) entries keep more than half of a state a token, one in
twelve less than a tenth, one in fifty less than e^-3.6, so that a state is
carried across chunks AND the exponent rule meets its hard end
(flash-linear-attention's own initialisation, which the model file keeps,
starts every channel near a = 0: a cell seeded so would never leave the easy
end; dt_bias normal(0, 1), the cell's first form, forgot a state within two
tokens, and the reference of a program without the delta rule's correction
term then read inside the sound runs' range on every number: PERF.md,
section 6). The
mixtures' selection bias (a BUFFER: no gradient, no update) is normal(0,
0.002), not zero, so that a dropped bias shows. The experts' leaves hold
the HELD experts only.
"""
from __future__ import annotations

import functools  # noqa: F401  (the borrowed functions' names)
import math  # noqa: F401
import types

from harness import keye_weights as kw
from harness import nemotron3_weights as nw
from reference import ling3 as ref

OUTER = ref.OUTER_LEAVES
PROGRAM_NAME = kw.PROGRAM_NAME
CONV_STD = nw.CONV_STD
EMBED_STD = nw.EMBED_STD
BIAS_STD = nw.BIAS_STD
DT_BIAS = (-2.0, 1.0)       # mean, spread
A_RANGE = (0.5, 2.0)
KINDS = tuple(ref.KIND_NAMES)
STACKED = tuple(name + "." for name in ref.KIND_NAMES.values())


def shapes(cfg: dict) -> dict:
    """The sizes both sides are built from, out of a configuration file:
    `Ling3Config`'s fields, which the reference reads under the same names."""
    lo, hi = cfg["held_experts"]
    if hi - lo != cfg["num_experts"]:
        raise SystemExit("benchmark: held_experts does not hold "
                         "num_experts experts")
    kept = cfg["num_hidden_layers"]
    limits = (cfg["expert_swiglu_limit_list"][:kept]
              + cfg["share_expert_swiglu_limit_list"][:kept])
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "layer_group_size", "first_k_dense_replace", "intermediate_size",
            "rms_norm_eps", "num_attention_heads", "head_dim",
            "short_conv_kernel_size", "kda_lower_bound", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "n_group", "topk_group",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "norm_topk_prob", "routed_scaling_factor",
            "router_aux_loss_coef")
    return dict({k: cfg[k] for k in same},
                rope_theta=float(cfg["rope_theta"]),
                num_experts=cfg["published"]["num_experts"],
                expert_swiglu_limit=float(max(limits)),
                held_experts=(lo, hi))


def kinds(cfg: dict) -> tuple:
    """The 2 L sub-layers' kinds (reference/ling3.py `kinds_of`)."""
    return ref.kinds_of(cfg)


def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, kind, std); a layer leaf is '<kind>.<leaf>' with its
    kind's sub-layers on a leading axis. BIAS leaves are buffers."""
    s = shapes(cfg)
    h, heads, d = s["hidden_size"], s["num_attention_heads"], s["head_dim"]
    inner, taps = heads * d, s["short_conv_kernel_size"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    rank, rope, dv = s["kv_lora_rank"], s["qk_rope_head_dim"], s["v_head_dim"]
    held = s["held_experts"][1] - s["held_experts"][0]
    f, fs, e, fd = (s["moe_intermediate_size"],
                    s["moe_shared_expert_intermediate_size"],
                    s["num_experts"], s["intermediate_size"])
    depth = cfg["published"].get("num_hidden_layers",
                                 s["num_hidden_layers"])
    std, res = kw.STD, kw.STD / (2.0 * depth) ** 0.5
    per_kind = {
        ref.KDA: {
            "input_norm.weight": ((h,), "gain", std),
            "mixer.q_proj.weight": ((h, inner), "w", std),
            "mixer.k_proj.weight": ((h, inner), "w", std),
            "mixer.v_proj.weight": ((h, inner), "w", std),
            "mixer.q_conv": ((taps, inner), "w", CONV_STD),
            "mixer.k_conv": ((taps, inner), "w", CONV_STD),
            "mixer.v_conv": ((taps, inner), "w", CONV_STD),
            "mixer.f_proj.weight": ((h, inner), "w", std),
            "mixer.A_log": ((heads,), "a_log", A_RANGE),
            "mixer.dt_bias": ((inner,), "shifted", DT_BIAS),
            "mixer.b_proj.weight": ((h, heads), "w", std),
            "mixer.g_proj.weight": ((h, heads), "w", std),
            "mixer.o_norm.weight": ((d,), "gain", std),
            "mixer.o_proj.weight": ((inner, h), "w", res)},
        ref.MLA: {
            "input_norm.weight": ((h,), "gain", std),
            "mixer.q_proj.weight": ((h, heads * qk), "w", std),
            "mixer.kv_a_proj.weight": ((h, rank + rope), "w", std),
            "mixer.kv_a_norm.weight": ((rank,), "gain", std),
            "mixer.kv_b_proj.weight": (
                (rank, heads * (s["qk_nope_head_dim"] + dv)), "w", std),
            "mixer.q_norm.weight": ((qk,), "gain", std),
            "mixer.k_norm.weight": ((qk,), "gain", std),
            "mixer.g_proj.weight": ((h, heads), "w", std),
            "mixer.o_proj.weight": ((heads * dv, h), "w", res)},
        ref.DENSE: {
            "post_norm.weight": ((h,), "gain", std),
            "ffn.gate_proj.weight": ((h, fd), "w", std),
            "ffn.up_proj.weight": ((h, fd), "w", std),
            "ffn.down_proj.weight": ((fd, h), "w", res)},
        ref.MIXTURE: {
            "post_norm.weight": ((h,), "gain", std),
            "ffn.experts.router": ((h, e), "w", std),
            "ffn.experts.gate_proj": ((held, h, f), "w", std),
            "ffn.experts.up_proj": ((held, h, f), "w", std),
            "ffn.experts.down_proj": ((held, f, h), "w", res),
            "ffn.shared_gate.weight": ((h, fs), "w", std),
            "ffn.shared_up.weight": ((h, fs), "w", std),
            "ffn.shared_down.weight": ((fs, h), "w", res),
            ref.BIAS: ((e,), "w", BIAS_STD)},
    }
    specs = {"embed_tokens.weight": ((s["vocab_size"], h), "w", EMBED_STD),
             "norm.weight": ((h,), "gain", std),
             "lm_head": ((s["vocab_size"], h), "w", std)}
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
    of the program's Ling3ForCausalLM: sub-layer j is half of the program's
    layer j // 2."""
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


# What names no block, looking its names up HERE (`ref`, `STACKED`,
# `leaf_specs`, `program_leaves`, `program_biases`): the drawing in one
# program, the loader and the reader of the masters' distance from the
# seeded leaves. nemotron3_weights.py's, whose `_shaped` knows `a_log`.
def borrow(f, namespace, cached=False):
    """`f` rebuilt to look its global names up in `namespace`; an
    `lru_cache`d function from what it wraps, and cached again."""
    f = getattr(f, "__wrapped__", f)
    g = types.FunctionType(f.__code__, namespace, f.__name__, f.__defaults__)
    return functools.lru_cache(maxsize=None)(g) if cached else g


def _shaped(x, kind, par):
    """nemotron3_weights' kinds of leaf, and `shifted`: normal(par)."""
    if kind == "shifted":
        return par[0] + x * par[1]
    return nw._shaped(x, kind, par)


_over_layers = borrow(nw._over_layers, globals())
_items = borrow(nw._items, globals())
_draw = borrow(nw._draw, globals())
_drawer = borrow(nw._drawer, globals(), cached=True)
compile_reference_drawer = borrow(nw.compile_reference_drawer, globals())
load_into = borrow(nw.load_into, globals())
_delta_reader = borrow(nw._delta_reader, globals(), cached=True)
sq_deltas = borrow(nw.sq_deltas, globals())
