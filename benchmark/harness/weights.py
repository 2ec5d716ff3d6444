"""Seeded weights, drawn on the device by the benchmark.

Every leaf of the GPT-3 stack is a function of (--seed, leaf name) alone:
normal(0, 0.02) matrices and embeddings (the two residual projections
scaled by 1/sqrt(2L), the GPT-2 rule the program's own init follows),
biases normal(0, 0.02) rather than zero and LayerNorm gains 1 + normal(0,
0.02), so that a dropped bias or gain is a visible error. Block leaves are
stacked on a leading [L] axis. The program is handed these arrays (cast to
the storage type its step class keeps); the plain reference draws its own
copy with the same function.
"""
from __future__ import annotations

import functools

BLOCK_LEAVES = (
    "ln_1.weight", "ln_1.bias", "attn.qkv.weight", "attn.qkv.bias",
    "attn.out_proj.weight", "attn.out_proj.bias", "ln_2.weight",
    "ln_2.bias", "mlp.fc1.weight", "mlp.fc1.bias", "mlp.fc2.weight",
    "mlp.fc2.bias")
STD = 0.02


def leaf_parts(leaf: str) -> tuple:
    """The names a leaf is read under: qkv's last axis is q | k | v,
    three leaves to every reading (the key bias has no gradient)."""
    return tuple(f"{leaf}.{p}" for p in "qkv") if ".qkv." in leaf else (leaf,)


def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, kind, std); block leaves are 'blocks.<name>'."""
    h, f, n = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_layers"]
    v, positions = cfg["vocab_size"], cfg["max_position_embeddings"]
    res = STD / (2.0 * n) ** 0.5
    block = {
        "ln_1.weight": ((h,), "gain", STD), "ln_1.bias": ((h,), "w", STD),
        "attn.qkv.weight": ((h, 3 * h), "w", STD),
        "attn.qkv.bias": ((3 * h,), "w", STD),
        "attn.out_proj.weight": ((h, h), "w", res),
        "attn.out_proj.bias": ((h,), "w", STD),
        "ln_2.weight": ((h,), "gain", STD), "ln_2.bias": ((h,), "w", STD),
        "mlp.fc1.weight": ((h, f), "w", STD),
        "mlp.fc1.bias": ((f,), "w", STD),
        "mlp.fc2.weight": ((f, h), "w", res),
        "mlp.fc2.bias": ((h,), "w", STD),
    }
    specs = {"wte": ((v, h), "w", STD), "wpe": ((positions, h), "w", STD),
             "ln_f.weight": ((h,), "gain", STD),
             "ln_f.bias": ((h,), "w", STD)}
    for name in BLOCK_LEAVES:
        shape, kind, std = block[name]
        specs["blocks." + name] = ((n,) + shape, kind, std)
    return specs


@functools.lru_cache(maxsize=None)
def _drawer(shape, kind, std):
    import jax
    import jax.numpy as jnp

    def draw(lo, hi, idx):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(lo), hi), idx)
        x = jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)
        return x + 1.0 if kind == "gain" else x

    return jax.jit(draw)


def _key_args(specs: dict, name: str, seed: int):
    import numpy as np

    return (np.uint32(seed & 0xFFFFFFFF),
            np.uint32((seed >> 32) & 0xFFFFFFFF),
            np.uint32(list(specs).index(name)))


def draw_leaf(specs: dict, name: str, seed: int):
    """The fp32 leaf `name` for `seed` (any whole number up to 2**63)."""
    shape, kind, std = specs[name]
    return _drawer(shape, kind, float(std))(*_key_args(specs, name, seed))


@functools.lru_cache(maxsize=None)
def _delta_reader(shape, kind, std, store_dtype, per_layer, parts):
    import jax
    import jax.numpy as jnp

    draw = _drawer(shape, kind, std)
    # an explicit rounding: inside one program XLA may skip a
    # float32 -> bfloat16 -> float32 pair of converts (excess precision)
    info = jnp.finfo(store_dtype)

    def sq_delta(arrays, lo, hi, idx):
        init = jax.lax.reduce_precision(draw(lo, hi, idx), info.nexp,
                                        info.nmant)
        inits = [init[i] for i in range(shape[0])] if per_layer else [init]
        return sum(jnp.sum(jnp.square(a.astype(jnp.float32) - b).reshape(
            -1, parts, shape[-1] // parts), axis=(0, 2))
            for a, b in zip(arrays, inits))

    return jax.jit(sq_delta)


def sq_delta_from_seed(specs, name, seed, arrays, store_dtype, parts=1):
    """sum((arrays - the seeded leaf, rounded through store_dtype) ** 2)
    over each of `parts` slices of the last axis, in ONE fused program: the seeded leaf is drawn again inside it, so
    nothing of a leaf's size is left on the device beside the program's
    own state. `arrays` is the stacked leaf, or its layers one by one."""
    shape, kind, std = specs[name]
    per_layer = len(arrays) > 1
    fn = _delta_reader(shape, kind, float(std), str(store_dtype), per_layer,
                       parts)
    return [float(x) for x in fn(tuple(arrays),
                                 *_key_args(specs, name, seed))]


def reference_params(cfg: dict, seed: int):
    """(outer dict, list of per-layer dicts) in float32: the reference's
    own copy of the seeded weights."""
    specs = leaf_specs(cfg)
    outer = {k: draw_leaf(specs, k, seed)
             for k in ("wte", "wpe", "ln_f.weight", "ln_f.bias")}
    layers = [{} for _ in range(cfg["num_layers"])]
    for k in BLOCK_LEAVES:
        stacked = draw_leaf(specs, "blocks." + k, seed)
        for i, layer in enumerate(layers):
            layer[k] = stacked[i]
        del stacked
    return specs, outer, layers


def program_leaves(model, cfg: dict):
    """[(leaf name, layer index or None, Parameter)] of a GPTForCausalLM
    built with scan_layers on (stacked) or off (one block per layer)."""
    named = dict(model.named_parameters())
    out = [("wte", None, named["gpt.wte.weight"]),
           ("wpe", None, named["gpt.wpe.weight"]),
           ("ln_f.weight", None, named["gpt.ln_f.weight"]),
           ("ln_f.bias", None, named["gpt.ln_f.bias"])]
    for name in BLOCK_LEAVES:
        stacked = "gpt.blocks.blocks__" + name.replace(".", "__")
        if stacked in named:
            out.append(("blocks." + name, None, named[stacked]))
        else:
            for i in range(cfg["num_layers"]):
                out.append(("blocks." + name, i,
                            named[f"gpt.blocks.{i}.{name}"]))
    if len(out) != len(named):
        raise RuntimeError(
            f"the model has {len(named)} parameters, the benchmark's "
            f"GPT-3 leaf table covers {len(out)}")
    return out


def load_into(model, cfg: dict, seed: int):
    """Re-draw every parameter of `model` from `seed`, on the device the
    parameter lives on, in the type the model stores it in."""
    specs = leaf_specs(cfg)
    cache = {}
    for leaf, layer, p in program_leaves(model, cfg):
        if leaf not in cache:
            cache.clear()           # one stacked leaf alive at a time
            cache[leaf] = draw_leaf(specs, leaf, seed)
        a = cache[leaf] if layer is None else cache[leaf][layer]
        if tuple(a.shape) != tuple(p._data.shape):
            raise RuntimeError(f"{leaf}: drew {a.shape}, the program "
                               f"holds {p._data.shape}")
        p._data = a.astype(p._data.dtype)
