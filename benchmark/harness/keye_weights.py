"""Seeded weights of the Keye-VL-2.0 decoder, drawn on the device.

Every leaf is a function of (--seed, leaf name) alone, layer leaves
stacked on a leading [L] axis; matrices normal(0, 0.02), the two residual
projections (o_proj, the experts' down_proj) scaled by 1/sqrt(2L), the
embedding normal(0, 1) (see EMBED_STD), every norm gain 1 + normal(0,
0.02) and the indexer key norm's bias normal(0, 0.02), so that a dropped
gain or bias is a visible error. The experts' leaves hold the HELD
experts only ([L, held, ...]): the chip's share is what the deployment
stores.

The normals are Box-Muller over two integer hashes of the element's
index, not `jax.random`: elementwise on an iota and drawn in one loop
over a leaf's layers, so the whole model is ONE program that the
compiler takes 5 s for. harness/weights.py's threefry drawer is one
program a leaf, and the compiler's time for it grows with the leaf (18 s
for one [6, 16, 2048, 768] leaf, compiled for a v5e in the sandbox):
twenty of them were 115 s of a run that starts with no compiled code (my
chip run, PR 26), and twenty more sat inside the readers of the
parameters' change. The program's parameters, the reference's copy and
the reader of the change (`sq_deltas`) are three programs of the same
function.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from reference import keye_vl2 as ref

STD = 0.02
# At 0.02 the embedding is a twentieth of what the first attention adds
# to the residual stream, and attention adds nearly ONE vector to every
# token (the mean of ~2,048 values): 91-95 % of each layer's input is
# then common to all tokens, every token picks the same 8 of 128 experts
# (fullest over mean 16), and the pairs that fall on the 16 held experts
# are a lottery of the seed (157k-300k a step; my chip runs, PR 26). A
# trained model's residual stream is token-specific; unit-variance
# embeddings make the seeded one so (fullest over mean ~2 over the 128).
EMBED_STD = 1.0
OUTER = ref.OUTER_LEAVES
PROGRAM_NAME = {"embed_tokens.weight": "model.embed_tokens.weight",
                "norm.weight": "model.norm.weight", "lm_head": "lm_head"}


def shapes(cfg: dict) -> dict:
    """The sizes both sides are built from, out of a configuration file."""
    sa = cfg["sa_config"]
    lo, hi = cfg["held_experts"]
    if hi - lo != cfg["num_experts"]:
        raise SystemExit("benchmark: held_experts does not hold "
                         "num_experts experts")
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
        num_experts=cfg["num_local_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        router_aux_loss_coef=cfg["router_aux_loss_coef"],
        index_n_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        held_experts=(lo, hi))


def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, kind, std); layer leaves are 'layers.<name>'."""
    s = shapes(cfg)
    h, n, d = s["hidden_size"], s["num_layers"], s["head_dim"]
    heads, kvh = s["num_attention_heads"], s["num_key_value_heads"]
    nj, di = s["index_n_heads"], s["index_head_dim"]
    held = s["held_experts"][1] - s["held_experts"][0]
    f, e = s["moe_intermediate_size"], s["num_experts"]
    res = STD / (2.0 * n) ** 0.5
    layer = {
        "input_layernorm.weight": ((h,), "gain", STD),
        "self_attn.q_proj.weight": ((h, heads * d), "w", STD),
        "self_attn.k_proj.weight": ((h, kvh * d), "w", STD),
        "self_attn.v_proj.weight": ((h, kvh * d), "w", STD),
        "self_attn.o_proj.weight": ((heads * d, h), "w", res),
        "self_attn.q_norm.weight": ((d,), "gain", STD),
        "self_attn.k_norm.weight": ((d,), "gain", STD),
        "indexer.wq.weight": ((h, nj * di), "w", STD),
        "indexer.wk.weight": ((h, di), "w", STD),
        "indexer.k_norm.weight": ((di,), "gain", STD),
        "indexer.k_norm.bias": ((di,), "w", STD),
        "indexer.weights_proj.weight": ((h, nj), "w", STD),
        "post_attention_layernorm.weight": ((h,), "gain", STD),
        "mlp.router": ((h, e), "w", STD),
        "mlp.gate_proj": ((held, h, f), "w", STD),
        "mlp.up_proj": ((held, h, f), "w", STD),
        "mlp.down_proj": ((held, f, h), "w", res),
    }
    specs = {"embed_tokens.weight": ((s["vocab_size"], h), "w", EMBED_STD),
             "norm.weight": ((h,), "gain", STD),
             "lm_head": ((s["vocab_size"], h), "w", STD)}
    for name in ref.LAYER_LEAVES:
        shape, kind, std = layer[name]
        specs["layers." + name] = ((n,) + shape, kind, std)
    return specs


# -- the drawing ----------------------------------------------------------

_U = np.uint32


def _mix(h):
    """An integer hash (lowbias32): every bit of the result depends on
    every bit of `h`."""
    h = (h ^ (h >> _U(16))) * _U(0x7FEB352D)
    h = (h ^ (h >> _U(15))) * _U(0x846CA68B)
    return h ^ (h >> _U(16))


def _normals(shape, word, offset):
    """float32 standard normals [shape]: element j (row-major) is a
    function of (word, offset + j) alone."""
    import jax
    import jax.numpy as jnp

    j = jax.lax.iota(jnp.uint32, math.prod(shape)).reshape(shape) \
        + jnp.asarray(offset, jnp.uint32)
    a = _mix(j * _U(0x9E3779B1) + word)
    b = _mix(j * _U(0x85EBCA77) + (word ^ _U(0x68E31DA4)))
    u1 = ((a >> _U(8)).astype(jnp.float32) + 1.0) * jnp.float32(2.0 ** -24)
    u2 = (b >> _U(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        jnp.float32(2.0 * math.pi) * u2)


def _over_layers(name, spec, index, lo, hi, use, arrays=None):
    """use(float32 layer of the leaf, the matching one of `arrays`) for
    every layer of a layer leaf, in ONE loop over them (the compiler's
    time follows the program's length), the results stacked; for another
    leaf, for the leaf itself alone."""
    import jax
    import jax.numpy as jnp

    shape, kind, std = spec
    word = _mix(lo ^ _mix(hi ^ _mix(jnp.uint32(index + 1))))
    if not name.startswith("layers."):
        shape = (1,) + shape
    size = math.prod(shape[1:])

    def one(a):
        x = _normals(shape[1:], word, a[0] * _U(size)) * jnp.float32(std)
        return use(x + 1.0 if kind == "gain" else x, a[1])

    given = None if arrays is None else jnp.stack(arrays)
    return jax.lax.map(one, (jnp.arange(shape[0], dtype=jnp.uint32), given))


def _key_args(seed: int):
    return _U(seed & 0xFFFFFFFF), _U((seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=None)
def _drawer(items):
    """items: ((leaf, (shape, kind, std), dtype), ...) -> jitted (lo, hi)
    -> {leaf: [arrays of dtype]}."""
    import jax

    def draw(lo, hi):
        return {name: list(_over_layers(name, spec, i, lo, hi,
                                        lambda x, _: x.astype(dtype)))
                for i, (name, spec, dtype) in enumerate(items)}

    return jax.jit(draw)


def _items(specs: dict, dtypes: dict):
    return tuple((k, v, str(dtypes[k])) for k, v in specs.items())


def reference_params(cfg: dict, seed: int):
    """(outer dict, list of per-layer dicts) in float32."""
    specs = leaf_specs(cfg)
    drawn = _drawer(_items(specs, dict.fromkeys(specs, "float32")))(
        *_key_args(seed))
    outer = {k: drawn[k][0] for k in OUTER}
    layers = [{k: drawn["layers." + k][i] for k in ref.LAYER_LEAVES}
              for i in range(cfg["num_hidden_layers"])]
    return outer, layers


def program_leaves(model, cfg: dict):
    """[(leaf name, layer index or None, Parameter)] of the program's
    KeyeVL2ForCausalLM."""
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


def _by_leaf(model, cfg: dict) -> dict:
    out = {}
    for leaf, _, p in program_leaves(model, cfg):
        out.setdefault(leaf, []).append(p)
    return out


def load_into(model, cfg: dict, seed: int):
    """Re-draw every parameter of `model` from `seed`, on the device, in
    the type the model stores it in."""
    specs, held = leaf_specs(cfg), _by_leaf(model, cfg)
    drawn = _drawer(_items(specs, {k: v[0]._data.dtype
                                   for k, v in held.items()}))(
        *_key_args(seed))
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
        for i, (name, spec, dtype) in enumerate(items):
            # an explicit rounding: inside one program XLA may skip a
            # float32 -> bfloat16 -> float32 pair of converts
            info = jnp.finfo(dtype)
            out[name] = jnp.sum(_over_layers(
                name, spec, i, lo, hi,
                lambda x, a: jnp.sum(jnp.square(
                    a.astype(jnp.float32) - jax.lax.reduce_precision(
                        x, info.nexp, info.nmant))), arrays[name]))
        return out

    return jax.jit(sq_deltas)


def sq_deltas(cfg: dict, seed: int, arrays: dict, dtypes: dict) -> dict:
    """{leaf: sum((arrays[leaf] - the seeded leaf, rounded through
    dtypes[leaf]) ** 2)} in ONE program: the seeded leaves are drawn
    again inside it, so nothing of a leaf's size is left on the device
    beside the program's own state. arrays[leaf]: the leaf's layers one
    by one (or the leaf alone)."""
    fn = _delta_reader(_items(leaf_specs(cfg), dtypes))
    return {k: float(v) for k, v in fn(arrays, *_key_args(seed)).items()}
