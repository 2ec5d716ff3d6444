"""The seam between the five mixture models and what they share
(`paddle_tpu/models/decoder_parts.py`, ISSUE 44): no model's file imports
another's or builds its classes out of another's, the shared module
imports none of them, nothing below `models/` imports it, and the names
the benchmark's weights files set and the optimizer lays its state out by
(`benchmark/harness/*_weights.py`: `dict(model.named_parameters())`) are
the ones written here, in this order."""
import ast
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
MODELS = ("keye_vl2", "mellum2", "nemotron_h", "ling3", "lfm2")
SHARED = "decoder_parts"


def imported(path):
    """The absolute dotted names the file at `path` imports (`from a import
    b` gives both `a` and `a.b`), relative ones resolved against its
    package."""
    package = os.path.relpath(path, ROOT).split(os.sep)[:-1]
    out = set()
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            module = ".".join(base + ([node.module] if node.module else []))
            out.add(module)
            out.update(f"{module}.{a.name}" for a in node.names)
    return out


def model_file(name):
    return os.path.join(ROOT, "paddle_tpu", "models", name + ".py")


@pytest.mark.parametrize("name", MODELS)
def test_a_model_file_imports_no_other_model_file(name):
    others = {f"paddle_tpu.models.{m}" for m in MODELS if m != name}
    found = imported(model_file(name))
    assert f"paddle_tpu.models.{SHARED}" in found
    assert not {i for i in found
                if any(i == o or i.startswith(o + ".") for o in others)}


def test_the_shared_module_imports_no_model_file_and_names_no_model():
    found = imported(model_file(SHARED))
    assert not {i for i in found for m in MODELS
                if i.startswith(f"paddle_tpu.models.{m}")}
    with open(model_file(SHARED)) as f:
        tree = ast.parse(f.read())
    tree.body = tree.body[1:]       # the docstring may say who uses it
    code = ast.unparse(tree).lower()
    for word in ("keye", "mellum", "nemotron", "ling3", "ling-3", "lfm",
                 "isinstance", "hasattr", "model_type"):
        assert word not in code, word


@pytest.mark.parametrize("below", ["ops", "incubate"])
def test_nothing_below_the_models_imports_them(below):
    seen = 0
    for folder, _, files in os.walk(os.path.join(ROOT, "paddle_tpu", below)):
        for name in files:
            if name.endswith(".py"):
                seen += 1
                path = os.path.join(folder, name)
                assert not {i for i in imported(path)
                            if i == "paddle_tpu.models"
                            or i.startswith("paddle_tpu.models.")}, path
    assert seen > 10


@pytest.mark.parametrize("name", MODELS)
def test_no_class_is_built_out_of_another_class_s_attributes(name):
    """`picks = OtherForCausalLM.picks` in a class body: a method taken
    from a class that is not a base."""
    with open(model_file(name)) as f:
        tree = ast.parse(f.read())
    for cls in (n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)):
        for stmt in cls.body:
            value = getattr(stmt, "value", None)
            if isinstance(stmt, (ast.Assign, ast.AnnAssign)) and isinstance(
                    value, ast.Attribute) and isinstance(
                        value.value, ast.Name):
                assert not value.value.id[:1].isupper(), (
                    cls.name, ast.unparse(stmt))


GQA = ["q_proj.weight", "k_proj.weight", "v_proj.weight", "o_proj.weight"]
EXPERTS = ["router", "gate_proj", "up_proj", "down_proj"]
KEYE = (["input_layernorm.weight"] + [f"self_attn.{n}" for n in GQA + [
    "q_norm.weight", "k_norm.weight"]] + [f"indexer.{n}" for n in (
        "wq.weight", "wk.weight", "k_norm.weight", "k_norm.bias",
        "weights_proj.weight")] + ["post_attention_layernorm.weight"]
    + [f"mlp.{n}" for n in EXPERTS])
MELLUM2 = [n for n in KEYE if not n.startswith("indexer.")]
NEMOTRON = {
    "M": ["conv_weight", "conv_bias", "dt_bias", "A_log", "D",
          "in_proj.weight", "norm.weight", "out_proj.weight"],
    "*": GQA,
    "E": ["experts.router", "experts.up_proj", "experts.down_proj",
          "shared_up.weight", "shared_down.weight"]}
LING3_MIXER = {
    "kda": ["q_conv", "k_conv", "v_conv", "A_log", "dt_bias",
            "q_proj.weight", "k_proj.weight", "v_proj.weight",
            "f_proj.weight", "b_proj.weight", "g_proj.weight",
            "o_norm.weight", "o_proj.weight"],
    "mla": ["q_proj.weight", "kv_a_proj.weight", "kv_a_norm.weight",
            "kv_b_proj.weight", "q_norm.weight", "k_norm.weight",
            "g_proj.weight", "o_proj.weight"]}
LING3_FFN = {
    True: ["gate_proj.weight", "up_proj.weight", "down_proj.weight"],
    False: [f"experts.{n}" for n in EXPERTS] + [
        "shared_gate.weight", "shared_up.weight", "shared_down.weight"]}


LFM2_MIXER = {
    "conv": ["conv.conv_weight", "conv.in_proj.weight",
             "conv.out_proj.weight"],
    "full_attention": [f"self_attn.{n}" for n in GQA + ["q_norm.weight",
                                                        "k_norm.weight"]]}


def _keye():
    import test_keye_vl2 as t
    c = t.config()
    return t, c, [KEYE] * c.num_layers, [], ["selection_bits"]


def _mellum2():
    import test_mellum2 as t
    c = t.config()
    return t, c, [MELLUM2] * c.num_layers, [], []


def _nemotron_h():
    import test_nemotron_h as t
    c = t.config()
    layers = [["norm.weight"] + [f"mixer.{n}" for n in NEMOTRON[k]]
              for k in t.PATTERN]
    bias = [f"model.layers.{i}.mixer.experts.score_bias"
            for i, k in enumerate(t.PATTERN) if k == "E"]
    return t, c, layers, bias, []


def _ling3():
    import test_ling3 as t
    c = t.config()
    kinds = [("mla" if (i + 1) % 3 == 0 else "kda", i < 1) for i in range(3)]
    layers = [["input_norm.weight"] + [f"mixer.{n}" for n in LING3_MIXER[m]]
              + ["post_norm.weight"] + [f"ffn.{n}" for n in LING3_FFN[dense]]
              for m, dense in kinds]
    bias = [f"model.layers.{i}.ffn.experts.score_bias"
            for i, (_, dense) in enumerate(kinds) if not dense]
    return t, c, layers, bias, []


def _lfm2():
    import test_lfm2 as t
    c = t.config()
    layers = [["operator_norm.weight"] + LFM2_MIXER[kind]
              + ["ffn_norm.weight"] + [
                  f"feed_forward.{n}" for n in (LING3_FFN[True] if i < 1
                                                else EXPERTS)]
              for i, kind in enumerate(t.KINDS)]
    bias = [f"model.layers.{i}.feed_forward.score_bias"
            for i in range(1, len(t.KINDS))]
    return t, c, layers, bias, []


@pytest.mark.parametrize("case", [_keye, _mellum2, _nemotron_h, _ling3,
                                  _lfm2])
def test_the_names_the_weights_files_set_and_their_order(case):
    """Parameters: the head (a leaf of its own unless the model ties it to
    the embedding), the embedding, each layer's leaves, the final norm.
    Buffers: `routing`, after `record_picks` the picks' (the selection's
    first), then the layers' own."""
    t, c, layers, layer_buffers, own_picks = case()
    model = t.build(c)
    assert model.tied == (case is _lfm2)
    want = ([] if model.tied else ["lm_head"]) + [
        "model.embed_tokens.weight"] + [
        f"model.layers.{i}.{n}" for i, names in enumerate(layers)
        for n in names] + ["model.norm.weight"]
    assert [n for n, _ in model.named_parameters()] == want
    assert [n for n, _ in model.named_buffers()] == ["routing"] \
        + layer_buffers
    model.record_picks(t.B, t.S)
    assert [n for n, _ in model.named_parameters()] == want
    assert [n for n, _ in model.named_buffers()] == ["routing"] \
        + own_picks + ["expert_picks"] + layer_buffers


def test_a_tied_head_is_the_embedding_and_creates_no_leaf():
    """`MixtureCausalLM(..., tied=True)`: the head's weight IS the
    embedding's parameter, the logits are its product with the final hidden
    state, and one backward pass sends the embedding both gradients."""
    import numpy as np

    import paddle_tpu as paddle
    import test_mellum2 as t
    from paddle_tpu.models.decoder_parts import MixtureCausalLM
    from paddle_tpu.models.mellum2 import Mellum2Model

    c = t.config()
    paddle.seed(0)
    tied = MixtureCausalLM(c, Mellum2Model(c), tied=True)
    paddle.seed(0)
    untied = MixtureCausalLM(c, Mellum2Model(c))
    assert tied.head is tied.model.embed_tokens.weight
    assert untied.head is untied.lm_head
    assert "lm_head" not in dict(tied.named_parameters())
    assert len(list(tied.parameters())) == len(list(untied.parameters())) - 1
    # the untied model with its head set to the embedding computes the same
    # loss; its two leaves' gradients add up to the tied leaf's
    untied.lm_head._data = untied.model.embed_tokens.weight._data
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, c.vocab_size, (t.B, t.S)))
    labels = paddle.to_tensor(rng.integers(0, c.vocab_size, (t.B, t.S)))
    grads = []
    for model in (tied, untied):
        loss = model.loss(ids, labels)
        loss.backward()
        grads.append((float(loss), {k: np.asarray(p.grad._data)
                                    for k, p in model.named_parameters()}))
    (loss_t, g_t), (loss_u, g_u) = grads
    np.testing.assert_allclose(loss_t, loss_u, rtol=1e-6)
    whole = g_u["model.embed_tokens.weight"] + g_u["lm_head"]
    np.testing.assert_allclose(g_t["model.embed_tokens.weight"], whole,
                               atol=1e-6 * np.abs(whole).max() + 1e-9)
    assert np.abs(g_u["lm_head"]).max() > 0
