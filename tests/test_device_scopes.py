"""The mixture steps as a tree of named scopes (ISSUE 37): the vocabulary
`paddle_tpu.profiler.DEVICE_SCOPES` against what the programs trace.

Every equation of the dropless layer and of the indexer branch stands
under a LEAF of the tree, both tiny models' lowered steps carry every
name they should, forward and backward, and `op_scope` is what carries a
name through the tape's vjp. The paths are read with the benchmark
reader's own matcher (benchmark/harness/scope_tree.py `finder`), so
program, vocabulary and reader cannot drift apart. That outputs and
gradients are unchanged is what the parity tests of tests/test_keye_vl2.py,
tests/test_mellum2.py and tests/test_moe.py hold (scopes are metadata)."""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.framework.autograd import op_scope
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.jit import TrainStep, train_step
from paddle_tpu.models import keye_vl2
from paddle_tpu.profiler import DEVICE_SCOPES

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "benchmark"))
from harness import scope_tree  # noqa: E402

PHASES = ("forward", "backward", "optimizer")
node_of = scope_tree.finder(DEVICE_SCOPES)
INNER = {n for n in DEVICE_SCOPES
         if any(m.startswith(n + "/") for m in DEVICE_SCOPES)}
# equations that run nothing themselves: their bodies are walked instead
CONTAINERS = {"pjit", "jit", "closed_call", "core_call", "custom_jvp_call",
              "custom_vjp_call", "custom_vjp_call_jaxpr", "checkpoint",
              "remat"}


def equations(jaxpr, prefix=""):
    """(primitive, path) of every equation of `jaxpr` and of the jaxprs
    inside it, the path composed as the lowering composes `op_name`."""
    for eqn in jaxpr.eqns:
        path = "/".join(p for p in (prefix, str(eqn.source_info.name_stack))
                        if p)
        inner = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name not in CONTAINERS:
            yield eqn.primitive.name, path
        for sub in inner:
            yield from equations(
                sub, path + "/" + eqn.primitive.name if inner else path)


def test_the_vocabulary_is_a_tree_without_phases():
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)
    for path in DEVICE_SCOPES:
        parts = path.split("/")
        assert all(parts) and not set(parts) & set(PHASES), path
    assert INNER == {"indexer", "moe/route"}
    # the six names the two older walkers match are nodes of the tree
    assert {"indexer", "sparse_attention", "moe/route", "moe/experts",
            "window_attention", "full_attention"} <= set(DEVICE_SCOPES)


@pytest.mark.parametrize("path,node", [
    ("jit(step_fn)/forward/jvp(moe/route/router)/dot_general:",
     "moe/route/router"),
    ("jit(step_fn)/backward/transpose(jvp(forward))/jvp()/checkpoint/"
     "moe/route/add_back/while/body/moe/route/gather/gather",
     "moe/route/gather"),
    ("jit(step_fn)/backward/checkpoint/moe/route/add_back/while/body/"
     "moe/experts/dot_general", "moe/experts"),
    ("jit(step_fn)/backward/transpose(jvp(moe/route/plan))/scatter-add",
     "moe/route/plan"),
    ("jit(step_fn)/forward/jvp(indexer)/jvp()/while/body/dynamic_slice",
     "indexer"),
    ("jit(step_fn)/forward/jvp(indexer)/jvp()/while/body/closed_call/"
     "indexer/scores/transpose(indexer/scores)/jvp()/mul", "indexer/scores"),
    ("indexer/loss/exp", "indexer/loss"),
    ("jit(step_fn)/forward/jvp(attention/projections)/dot_general",
     "attention/projections"),
    ("jit(step_fn)/forward/jvp(full_attention)/pallas_call",
     "full_attention"),
    ("jit(step_fn)/forward/my_attention/projections_x/y", None),
    ("jit(step_fn)/forward/moe/routes/x", None),
    ("jit(step_fn)/optimizer/numerics/reduce_sum", None),
    ("", None),
])
def test_the_innermost_path_on_an_operations_name(path, node):
    assert node_of(path) == node


@pytest.mark.parametrize("held", [(0, 8), (2, 6)])
def test_every_equation_of_the_dropless_layer_stands_under_a_leaf(held):
    """Forward and backward (the custom VJP's second loop, the router's
    pull-back and the sort's, the un-sort): no equation under bare
    `moe/route`, none under no name, and the only `while`s the two tile
    loops', under the add-back; both sorts stand under the plan."""
    t, k, n, e = 64, 32, 24, 8
    rng = np.random.default_rng(0)
    # bfloat16, as the cells run it: the float32 staging's casts are real
    args = [jnp.asarray(rng.standard_normal(s), jnp.bfloat16) for s in (
        (t, k), (k, e), (held[1] - held[0], k, n),
        (held[1] - held[0], k, n), (held[1] - held[0], n, k))]

    def layer(*a):
        y, balance, _, _ = dropless.dropless_moe(
            *a, top_k=2, held=held, tile_rows=8, balance_coef=0.01)
        return y, balance

    def both(cot, *a):
        out, pull = jax.vjp(layer, *a)
        return out, pull(cot)

    cot = (jnp.ones((t, k), jnp.bfloat16), jnp.ones((), jnp.float32))
    found = list(equations(jax.make_jaxpr(both)(cot, *args).jaxpr))
    leaves = {"moe/route/router", "moe/route/plan", "moe/route/gather",
              "moe/route/add_back", "moe/experts", "moe/cast"}
    assert {node_of(path) for _, path in found} == leaves
    loops = [path for prim, path in found if prim == "while"]
    assert len(loops) == 2
    assert all(node_of(p) == "moe/route/add_back" for p in loops)
    sorts = [node_of(path) for prim, path in found if prim == "sort"]
    assert sorts == ["moe/route/plan"] * 2


def test_every_equation_of_the_indexer_branch_stands_under_a_leaf():
    """Under bare `indexer` only the query-chunk loop's own equations:
    the scan, its slicing and the sums over its chunks."""
    sys.modules.pop("test_keye_vl2", None)
    import test_keye_vl2 as tk

    c = tk.config()
    layer = tk.build(c).model.layers[0]
    idx = tuple(p._data for p in layer.indexer.parameters_in_order())
    main = tuple(p._data for p in layer._main_parameters())
    x = jnp.ones((tk.B, tk.S, c.hidden_size), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(tk.S, dtype=jnp.int32),
                                 (3, tk.B, tk.S))

    def branch(idx, x):
        with jax.named_scope("indexer"):
            out, pull = jax.vjp(
                lambda p: layer._branch(p, x, main, positions)[1], idx)
            return out, pull(jnp.ones_like(out))

    found = list(equations(jax.make_jaxpr(branch)(idx, x).jaxpr))
    nodes = {node_of(path) for _, path in found}
    assert nodes == {"indexer", "indexer/project", "indexer/scores",
                     "indexer/select", "indexer/target", "indexer/loss"}
    bare = {prim for prim, path in found if node_of(path) == "indexer"}
    assert bare <= {"scan", "while", "reshape", "squeeze", "slice",
                    "dynamic_slice", "dynamic_update_slice", "iota",
                    "broadcast_in_dim", "convert_element_type", "mul",
                    "add", "div", "reduce_sum", "lt", "select_n",
                    "concatenate", "transpose"}, bare
    assert "scan" in bare or "while" in bare


def _lowered_paths(step, ids):
    """The `op_name`s of the step's lowered program, by phase. A function
    the lowering emits once and calls (the indexer's per-chunk
    `closed_call`) carries its paths from its own root, without the
    step's: those are `inner`, and the compiled program composes them
    under the caller's path."""
    float(step(ids, ids))
    text = step._jitted.lower(
        step._extract_state(), jnp.asarray(step._opt.get_lr(), jnp.float32),
        train_step._tree_data([ids, ids])).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    whole = {p for p in paths if p.startswith("jit(step_fn)")}
    assert whole
    out = {phase: {p for p in whole if p.split("/")[1:2] == [phase]}
           for phase in PHASES}
    out["inner"] = {p for p in paths - whole if node_of(p)}
    return out


def _step(model):
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    return TrainStep(model, lambda m, a, b: m.loss(a, b), opt)


@pytest.mark.parametrize("block", ["keye", "mellum2", "nemotron_h", "ling3",
                                   "lfm2"])
def test_a_lowered_step_carries_every_name_of_its_block(block):
    """Forward and backward, plain and wrapped by a transformation, with
    per-layer recompute as the cells run it; the matcher finds each."""
    sys.modules.pop("test_keye_vl2", None)
    sys.modules.pop("test_mellum2", None)
    # the state-space block's own: a Mamba layer's leaves, a shared expert
    ssm = {n for n in DEVICE_SCOPES if n.startswith("ssm/")}
    # the linear-attention block's own: a KDA layer's leaves, latent
    # attention's two, the leading layer's dense MLP
    kda = {n for n in DEVICE_SCOPES if n.startswith("kda/")} | {
        "mla/project", "mla_attention", "mlp"}
    indexer = {n for n in DEVICE_SCOPES if n.startswith("indexer")}
    # the gated-convolution block's own: a convolution layer's leaves
    conv = {n for n in DEVICE_SCOPES if n.startswith("conv/")}
    if block == "nemotron_h":
        import test_nemotron_h as t

        model = t.build(t.config((2, 6), use_recompute=True))
        absent = indexer | {"sparse_attention", "window_attention"} | kda \
            | conv
        forward_only = set()
    elif block == "ling3":
        import test_ling3 as t

        model = t.build(t.config((4, 12), use_recompute=True))
        absent = indexer | ssm | conv | {
            "sparse_attention", "window_attention", "full_attention",
            "attention/projections"}
        forward_only = set()
    elif block == "lfm2":
        import test_lfm2 as t

        model = t.build(t.config((4, 12), use_recompute=True))
        absent = indexer | ssm | (kda - {"mlp"}) | {
            "sparse_attention", "window_attention", "moe/shared"}
        forward_only = set()
    elif block == "keye":
        import test_keye_vl2 as t

        model = t.build(t.config((2, 6), layers=2, use_recompute=True))
        absent = {"window_attention", "full_attention", "moe/shared"} \
            | ssm | kda | conv
        forward_only = {n for n in DEVICE_SCOPES if n.startswith("indexer/")}
    else:
        import test_mellum2 as t

        model = t.build(t.config((2, 6), periods=1, use_recompute=True))
        absent = indexer | {"sparse_attention", "moe/shared"} | ssm | kda \
            | conv
        forward_only = set()
    model.bfloat16()            # as the cells run it (AMP O2)
    model.record_picks(t.B, t.S)
    rng = np.random.default_rng(1)
    ids = paddle.to_tensor(rng.integers(0, t.VOCAB, (t.B, t.S)).astype(
        "int64"))
    paths = _lowered_paths(_step(model), ids)
    want = set(DEVICE_SCOPES) - absent - {"moe/route"}
    forward = {node_of(p) for p in paths["forward"] | paths["inner"]}
    backward = {node_of(p) for p in paths["backward"]}
    assert want <= forward, want - forward
    # no gradient flows through the counters, the residual add's
    # pull-back is the identity, and L_I's gradient is formed in the
    # forward pass (the branch's backward only scales it)
    assert want - forward_only - {"picks", "moe/residual"} <= backward, \
        want - backward
    assert not (forward | backward) & absent
    assert {node_of(p) for p in paths["optimizer"]} == {None}
    named = [p for p in paths["forward"] | paths["backward"]
             | paths["inner"] if node_of(p)]
    plain = [p for p in named if f"/{node_of(p)}/" in "/" + p + "/"]
    wrapped = [p for p in named if f"({node_of(p)})" in p]
    assert plain and wrapped
    assert all(p in plain or p in wrapped for p in named)


def test_a_gpt_step_carries_no_name_of_the_tree():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_attention_heads=2,
        intermediate_size=128, max_position_embeddings=32,
        tie_word_embeddings=True))
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 128, (2, 32)).astype("int64"))
    paths = _lowered_paths(_step(model), ids)
    assert {node_of(p) for phase in PHASES for p in paths[phase]} == {None}
    assert not paths["inner"]


def test_op_scope_names_a_tape_ops_forward_and_its_backward():
    """A `with jax.named_scope` around a layer call would name the
    forward only: the tape pulls the vjp back outside it."""
    from paddle_tpu.framework.tensor import Tensor

    def grad_of(x, scoped):
        t = Tensor._wrap(x, stop_gradient=False)
        with op_scope("head") if scoped else jax.named_scope("head"):
            y = paddle.sum(t * t)
        y.backward()
        return t.grad._data

    x = jnp.arange(4.0)
    for scoped in (True, False):
        paths = [p for _, p in equations(jax.make_jaxpr(
            lambda x: grad_of(x, scoped))(x).jaxpr)]
        backward = [p for p in paths if "transpose" in p]
        assert backward and any(node_of(p) == "head" for p in paths)
        assert all(node_of(p) == "head" for p in backward) == scoped
    np.testing.assert_array_equal(grad_of(x, True), grad_of(x, False))
    # the innermost holds, and nothing is left behind
    with op_scope("embed"):
        with op_scope("head"):
            pass
        inner = jax.make_jaxpr(lambda x: (Tensor._wrap(x) * 2.0)._data)(x)
    assert {node_of(p) for _, p in equations(inner.jaxpr)} == {"embed"}
    after = jax.make_jaxpr(lambda x: (Tensor._wrap(x) * 2.0)._data)(x)
    assert {node_of(p) for _, p in equations(after.jaxpr)} == {None}
