"""Seeded weights of the Nemotron-H decoder, drawn on the device: what
harness/keye_weights.py is to the Keye block, by its hashed Box-Muller
normals and in one program for the whole model. The layers are of three
kinds, so a layer leaf is `<kind name>.<leaf>` (`mamba.`, `mixture.`,
`attention.`), stacked over the layers of its kind in the pattern's order.

Matrices normal(0, 0.02), the residual products (out_proj, o_proj, the
experts' and the shared expert's down products) divided by sqrt(the
published depth, 52),
the embedding normal(0, 4) so that tokens route apart (EMBED_STD below), norm gains and D 1 + normal(0, 0.02) so that a
dropped one shows, the convolution's taps and bias normal(0, 0.29) (the
spread of mamba_ssm's uniform(-1/2, 1/2)); A_log = log(A), A uniform in
[1, 16], and dt_bias the inverse softplus of a step size log-uniform in
[time_step_min, time_step_max] with floor time_step_floor, both from a
hashed uniform (the normal's distribution function), as mamba_ssm
initialises them. The mixtures' selection bias (a BUFFER: no gradient, no
update) is normal(0, 0.002), not zero, so that a dropped bias shows (at 0.02
the pairs on the held experts swung 6.9 % from seed to seed). The
experts' leaves hold the HELD experts only.
"""
from __future__ import annotations

import functools
import math

from harness import keye_weights as kw
from reference import nemotron_h as ref

OUTER = ref.OUTER_LEAVES
PROGRAM_NAME = kw.PROGRAM_NAME
CONV_STD = 0.29
# keye_weights.EMBED_STD's reason, four times over. The top 6 of 128 sigmoid
# scores sit 1.7 standard deviations out, where a common offset of 0.09 of
# one (what ONE Mamba layer's output, a sixth of a unit-variance embedding,
# adds to every token's logits) moves an expert's popularity +-18 %: at 1.0
# the fullest held expert had 1.4-1.8 x the mean and the pairs on the eight
# held experts ran 45,296-54,819 a step over four seeds, 0.9 % of
# train_tok_s_chip end to end (my chip run, PR 39, call 3). A trained router
# is held level by its correction bias; a seeded one by tokens that differ
EMBED_STD = 4.0
BIAS_STD = 0.002
KINDS = tuple(ref.KIND_NAMES)                   # "M", "E", "*"
STACKED = tuple(name + "." for name in ref.KIND_NAMES.values())


def shapes(cfg: dict) -> dict:
    """The sizes both sides are built from, out of a configuration file:
    `NemotronHConfig`'s fields, which the reference reads under the same
    names."""
    lo, hi = cfg["held_experts"]
    if hi - lo != cfg["n_routed_experts"]:
        raise SystemExit("benchmark: held_experts does not hold "
                         "n_routed_experts experts")
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        hybrid_override_pattern=cfg["hybrid_override_pattern"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        router_aux_loss_coef=cfg["router_aux_loss_coef"],
        held_experts=(lo, hi))


def kinds(cfg: dict) -> tuple:
    return tuple(cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]])


def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, kind, std); a layer leaf is '<kind name>.<leaf>'
    with its kind's layers on a leading axis. BIAS leaves are buffers."""
    s = shapes(cfg)
    h, n = s["hidden_size"], s["num_layers"]
    heads, p = s["mamba_num_heads"], s["mamba_head_dim"]
    inner = heads * p
    conv = inner + 2 * s["n_groups"] * s["ssm_state_size"]
    d, qh, kvh = s["head_dim"], s["num_attention_heads"], \
        s["num_key_value_heads"]
    held = s["held_experts"][1] - s["held_experts"][0]
    f, fs, e = (s["moe_intermediate_size"],
                s["moe_shared_expert_intermediate_size"],
                s["n_routed_experts"])
    # the residual products by the depth of the WHOLE model, as the source's
    # `rescale_prenorm_residual` divides them: by the nine layers held here
    # a branch's output is 2.4 x what the deployment's is beside the
    # embedding, every token leans the same way, and the pairs on the held
    # experts swing +-8 % from seed to seed (44,151-54,698 over 8 seeds; my
    # chip run, PR 39, call 2)
    depth = cfg["published"].get("num_hidden_layers", n)
    std, res = kw.STD, kw.STD / depth ** 0.5
    steps = (s["time_step_min"], s["time_step_max"], s["time_step_floor"])
    per_kind = {
        ref.MAMBA: {
            "norm.weight": ((h,), "gain", std),
            "mixer.in_proj.weight": ((h, inner + conv + heads), "w", std),
            "mixer.conv_weight": ((s["conv_kernel"], conv), "w", CONV_STD),
            "mixer.conv_bias": ((conv,), "w", CONV_STD),
            "mixer.dt_bias": ((heads,), "dt_bias", steps),
            "mixer.A_log": ((heads,), "a_log", (1.0, 16.0)),
            "mixer.D": ((heads,), "gain", std),
            "mixer.norm.weight": ((inner,), "gain", std),
            "mixer.out_proj.weight": ((inner, h), "w", res)},
        ref.ATTENTION: {
            "norm.weight": ((h,), "gain", std),
            "mixer.q_proj.weight": ((h, qh * d), "w", std),
            "mixer.k_proj.weight": ((h, kvh * d), "w", std),
            "mixer.v_proj.weight": ((h, kvh * d), "w", std),
            "mixer.o_proj.weight": ((qh * d, h), "w", res)},
        ref.MIXTURE: {
            "norm.weight": ((h,), "gain", std),
            "mixer.experts.router": ((h, e), "w", std),
            "mixer.experts.up_proj": ((held, h, f), "w", std),
            "mixer.experts.down_proj": ((held, f, h), "w", res),
            "mixer.shared_up.weight": ((h, fs), "w", std),
            "mixer.shared_down.weight": ((fs, h), "w", res),
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
                specs[ref.KIND_NAMES[kind] + "." + name] = (
                    (count,) + shape, what, dev)
    return specs


# -- the drawing: keye_weights' normals, with two more kinds of leaf ----------

def _shaped(x, kind, par):
    """A leaf of `kind` from float32 standard normals x."""
    import jax
    import jax.numpy as jnp

    if kind == "gain":
        return 1.0 + x * jnp.float32(par)
    if kind == "w":
        return x * jnp.float32(par)
    u = 0.5 * (1.0 + jax.lax.erf(x * jnp.float32(2.0 ** -0.5)))   # uniform
    if kind == "a_log":
        return jnp.log(par[0] + (par[1] - par[0]) * u)
    if kind == "dt_bias":
        lo, hi, floor = par
        dt = jnp.maximum(jnp.exp(math.log(lo) + u * (math.log(hi)
                                                     - math.log(lo))), floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown kind of leaf {kind!r}")


def _over_layers(name, spec, index, lo, hi, use, arrays=None):
    """keye_weights._over_layers with `_shaped` in the place of its scale:
    use(float32 layer of the leaf, the matching one of `arrays`) for every
    layer of a stacked leaf in ONE loop, the results stacked."""
    import jax
    import jax.numpy as jnp

    shape, kind, par = spec
    word = kw._mix(lo ^ kw._mix(hi ^ kw._mix(jnp.uint32(index + 1))))
    if not name.startswith(STACKED):
        shape = (1,) + shape
    size = math.prod(shape[1:])

    def one(a):
        x = kw._normals(shape[1:], word, a[0] * kw._U(size))
        return use(_shaped(x, kind, par), a[1])

    given = None if arrays is None else jnp.stack(arrays)
    return jax.lax.map(one, (jnp.arange(shape[0], dtype=jnp.uint32), given))


def _items(specs, dtypes):
    """((leaf, spec, dtype) ...) of the leaves in `dtypes`, each keeping
    the index (and so the hashed word) it has among all of `specs`."""
    return tuple((k, specs[k], str(dtypes[k])) if k in dtypes else None
                 for k in specs)


def reference_params(cfg: dict, seed: int):
    """(outer dict, list of per-layer dicts, a mixture's with its BIAS)
    in float32."""
    specs = leaf_specs(cfg)
    drawn = _draw(specs, dict.fromkeys(specs, "float32"), seed)
    outer = {k: drawn[k][0] for k in OUTER}
    seen, layers = dict.fromkeys(KINDS, 0), []
    for kind in kinds(cfg):
        prefix = ref.KIND_NAMES[kind] + "."
        names = ref.LEAVES[kind] + ((ref.BIAS,) if kind == ref.MIXTURE
                                    else ())
        layers.append({k: drawn[prefix + k][seen[kind]] for k in names})
        seen[kind] += 1
    return outer, layers


def compile_reference_drawer(cfg: dict):
    """Compile, executing nothing, the program `reference_params` runs."""
    specs = leaf_specs(cfg)
    lo, hi = kw._key_args(0)
    _drawer(_items(specs, dict.fromkeys(specs, "float32"))).lower(
        lo, hi).compile()


def _draw(specs, dtypes, seed):
    return _drawer(_items(specs, dtypes))(*kw._key_args(seed))


@functools.lru_cache(maxsize=None)
def _drawer(items):
    import jax

    def draw(lo, hi):
        return {item[0]: list(_over_layers(
            item[0], item[1], i, lo, hi,
            lambda x, _, dtype=item[2]: x.astype(dtype)))
            for i, item in enumerate(items) if item is not None}

    return jax.jit(draw)


def program_leaves(model, cfg: dict):
    """[(leaf name, index among its kind's layers or None, Parameter)] of
    the program's NemotronHForCausalLM."""
    named = dict(model.named_parameters())
    out = [(k, None, named[PROGRAM_NAME[k]]) for k in OUTER]
    per_kind = {kind: [i for i, k in enumerate(kinds(cfg)) if k == kind]
                for kind in KINDS}
    for kind, where in per_kind.items():
        for name in ref.LEAVES[kind]:
            for j, i in enumerate(where):
                out.append((ref.KIND_NAMES[kind] + "." + name, j,
                            named[f"model.layers.{i}.{name}"]))
    if len(out) != len(named):
        raise RuntimeError(
            f"the model has {len(named)} parameters, the benchmark's "
            f"leaf table covers {len(out)}")
    return out


def program_biases(model, cfg: dict):
    """[the selection-bias buffer of every mixture layer, in order]."""
    named = dict(model.named_buffers())
    return [named[f"model.layers.{i}.{ref.BIAS}"]
            for i, k in enumerate(kinds(cfg)) if k == ref.MIXTURE]


def load_into(model, cfg: dict, seed: int):
    """Re-draw every parameter of `model`, and the mixtures' selection
    bias, from `seed`, on the device, in the type the model stores it in."""
    specs, held = leaf_specs(cfg), {}
    for leaf, _, p in program_leaves(model, cfg):
        held.setdefault(leaf, []).append(p)
    biases = program_biases(model, cfg)
    if biases:
        held[ref.KIND_NAMES[ref.MIXTURE] + "." + ref.BIAS] = biases
    drawn = _draw(specs, {k: v[0]._data.dtype for k, v in held.items()},
                  seed)
    for leaf, params in held.items():
        for p, a in zip(params, drawn[leaf]):
            if tuple(a.shape) != tuple(p._data.shape):
                raise RuntimeError(f"{leaf}: drew {a.shape}, the program "
                                   f"holds {p._data.shape}")
            p._data = a


@functools.lru_cache(maxsize=None)
def _delta_reader(items):
    import jax
    import jax.numpy as jnp

    def sq_deltas(arrays, lo, hi):
        out = {}
        for i, item in enumerate(items):
            if item is None:
                continue
            name, spec, dtype = item
            # an explicit rounding: inside one program XLA may skip a
            # float32 -> bfloat16 -> float32 pair of converts
            info = jnp.finfo(dtype)
            out[name] = jnp.sum(_over_layers(
                name, spec, i, lo, hi,
                lambda x, a, info=info: jnp.sum(jnp.square(
                    a.astype(jnp.float32) - jax.lax.reduce_precision(
                        x, info.nexp, info.nmant))), arrays[name]))
        return out

    return jax.jit(sq_deltas)


def sq_deltas(cfg: dict, seed: int, arrays: dict, dtypes: dict) -> dict:
    """{leaf: sum((arrays[leaf] - the seeded leaf, rounded through
    dtypes[leaf]) ** 2)} in ONE program (keye_weights.sq_deltas)."""
    fn = _delta_reader(_items(leaf_specs(cfg), dtypes))
    return {k: float(v) for k, v in fn(arrays, *kw._key_args(seed)).items()}
