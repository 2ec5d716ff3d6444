"""Keye-VL-2.0's language model: a GQA decoder whose every layer is a
dropless top-k mixture of experts and whose attention is learned-sparse
(an indexer picks each token's top-k earlier keys, one set for all heads).

One layer, x [b, s, hidden], positions [3, b, s] (M-RoPE; text has three
equal rows and the layer is then plain RoPE):

  h  = RMSNorm(x);  q, k, v = h Wq, h Wk, h Wv  (no biases)
       RMSNorm over each q and k head, M-RoPE on q and k
  indexer: qI = rope(h WqI) [16 x 64], kI = rope(LayerNorm(h WkI)) [64],
       w = h Ww / sqrt(16 * 64);  I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
       S_t = the top `index_topk` keys s <= t of I[t, .], ties to the lower
  o[t] = sum_{s in S_t} softmax_s(q[t] . k[s] / sqrt(d)) v[s];  x = x + o Wo
  h2 = RMSNorm(x);  x = x + dropless_moe(h2)      (incubate/.../moe/dropless.py)

  loss = CE(head(RMSNorm(x_L))) + mean_l balance_l + mean_l L_I,l

`balance_l` is the router's load-balancing term; `L_I` trains the indexer
(ops/sparse_attention.py): its input and its target are cut from the
graph, so the indexer learns from L_I alone and everything else from the
language-model loss alone. The vision tower is not part of this module;
what it forces on the language model, three rows of position ids, is.

`held_experts=(lo, hi)` builds the layer's share of an expert-parallel
deployment: the weights of experts lo..hi-1 only, the router whole.

Layout of one step: the indexer branch is one tape operation that is NOT
recomputed (it hands on the int8 selection, and forms its own gradient in
its forward pass); the rest of the layer is `fleet.recompute`d around that
selection when `use_recompute` is on. The projections, the mixture's
wiring, the stack and the causal LM are `decoder_parts.py`'s.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.autograd import op_scope
from ..framework.tensor import Tensor
from ..ops import sparse_attention as sa
from ..ops._dispatch import nary
from .decoder_parts import (DecoderStack, GQAProjections, MixtureCausalLM,
                            dropless_experts, mixture, queries_keys,
                            recomputed, rms)
from .llama import LlamaRMSNorm

__all__ = ["KeyeVL2Config", "KeyeVL2Model", "KeyeVL2ForCausalLM"]

F32 = jnp.float32


@dataclass
class KeyeVL2Config:
    """Shapes; the defaults are Keye-VL-2.0-30B-A3B's as published."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    mrope_section: tuple = (16, 24, 24)
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.001
    index_n_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_q_chunk: int = 512        # tiling of the selection, not mathematics
    moe_tile_rows: int = 512        # tiling of the grouped product
    held_experts: tuple = None      # (lo, hi): this chip's experts; None: all
    initializer_range: float = 0.02
    use_recompute: bool = False

    def index_sections(self):
        """The indexer's rotary sections: the published ones scaled to
        its head width (the last takes what rounding leaves)."""
        scale = self.head_dim // self.index_head_dim
        a, b = (s // scale for s in self.mrope_section[:2])
        return (a, b, self.index_head_dim // 2 - a - b)


def _layer_norm(x, w, b, eps):
    x32 = x.astype(F32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32)
            + b.astype(F32)).astype(x.dtype)


def _indexer_branch(c, idx, x, main, positions):
    """-> (selection int8 [b, s, s], L_I, kept keys). `idx` = (WqI, WkI,
    kI norm gain, kI norm bias, Ww); `main` = (norm gain, Wq, Wk, q norm,
    k norm), used for the indexer's input and target only."""
    wqi, wki, knw, knb, ww = idx
    ln, wq, wk, qn, kn = main
    b, s, _ = x.shape
    with jax.named_scope("indexer/project"):
        h = rms(x, ln, c.rms_norm_eps)
        cos, sin = sa.mrope_angles(positions, c.head_dim, c.rope_theta,
                                   c.mrope_section)
        q, k = queries_keys(c, h, wq, wk, qn, kn, cos, sin)
        icos, isin = sa.mrope_angles(positions, c.index_head_dim,
                                     c.rope_theta, c.index_sections())
        q_idx = sa.apply_rotary(
            (h @ wqi).reshape(b, s, c.index_n_heads, c.index_head_dim),
            icos, isin)
        k_idx = sa.apply_rotary(
            _layer_norm(h @ wki, knw, knb, c.rms_norm_eps), icos, isin)
        w = ((h @ ww) * (c.index_n_heads * c.index_head_dim) ** -0.5
             ).astype(h.dtype)
    return sa.indexer_select(q, k, q_idx, k_idx, w, c.index_topk,
                             c.index_q_chunk)


def _differentiable_branch(c):
    """`_indexer_branch` for the shapes `c`, differentiable in the
    indexer's own parameters only: L_I's gradient is formed in the
    forward pass, so nothing of the branch but a gradient of the
    parameters' size waits for the backward pass."""

    @jax.custom_vjp
    def branch(idx, x, main, positions):
        return _indexer_branch(c, idx, x, main, positions)

    def fwd(idx, x, main, positions):
        out, pull = jax.vjp(
            lambda p: _indexer_branch(c, p, x, main, positions), idx)
        mask, loss, kept = out
        (grads,) = pull((np.zeros(mask.shape, jax.dtypes.float0),
                         jnp.ones_like(loss),
                         np.zeros(kept.shape, jax.dtypes.float0)))
        return out, grads

    def bwd(grads, cts):
        return (jax.tree.map(lambda a: (cts[1] * a).astype(a.dtype), grads),
                None, None, None)

    branch.defvjp(fwd, bwd)
    return branch


class KeyeIndexer(nn.Layer):
    def __init__(self, c: KeyeVL2Config):
        super().__init__()
        h, d = c.hidden_size, c.index_head_dim
        self.wq = nn.Linear(h, c.index_n_heads * d, bias_attr=False)
        self.wk = nn.Linear(h, d, bias_attr=False)
        self.k_norm = nn.LayerNorm(d, epsilon=c.rms_norm_eps)
        self.weights_proj = nn.Linear(h, c.index_n_heads, bias_attr=False)

    def parameters_in_order(self):
        return [self.wq.weight, self.wk.weight, self.k_norm.weight,
                self.k_norm.bias, self.weights_proj.weight]


class KeyeDecoderLayer(nn.Layer):
    def __init__(self, c: KeyeVL2Config):
        super().__init__()
        self.config = c
        self.input_layernorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = GQAProjections(c, qk_norm_eps=c.rms_norm_eps)
        self.indexer = KeyeIndexer(c)
        self.post_attention_layernorm = LlamaRMSNorm(c.hidden_size,
                                                     c.rms_norm_eps)
        self.mlp = dropless_experts(c)
        self._branch = _differentiable_branch(c)

    def _main_parameters(self):
        a = self.self_attn
        return [self.input_layernorm.weight, a.q_proj.weight,
                a.k_proj.weight, a.q_norm.weight, a.k_norm.weight]

    def select(self, x, positions):
        """The indexer branch -> (selection int8 [b, s, s], L_I, kept)."""
        c = self.config
        idx, main = self.indexer.parameters_in_order(), \
            self._main_parameters()

        def run(x, positions, *params):
            with jax.named_scope("indexer"):
                return self._branch(tuple(params[:len(idx)]), x,
                                    tuple(params[len(idx):]), positions)

        return nary(run, [x, positions] + idx + main, "keye_indexer")

    def _attend(self, x, selection, positions):
        c, a = self.config, self.self_attn

        def run(x, selection, positions, ln, wq, wk, qn, kn, wv, wo):
            from ..ops.pallas.splash_attention import splash_attention

            b, s, _ = x.shape
            with jax.named_scope("attention/projections"):
                h = rms(x, ln, c.rms_norm_eps)
                cos, sin = sa.mrope_angles(positions, c.head_dim,
                                           c.rope_theta, c.mrope_section)
                q, k = queries_keys(c, h, wq, wk, qn, kn, cos, sin)
                v = (h @ wv).reshape(b, s, c.num_key_value_heads,
                                     c.head_dim)
            with jax.named_scope("sparse_attention"):
                o = splash_attention(q, k, v, causal=True,
                                     selection=selection)
            with jax.named_scope("attention/projections"):
                return x + o.reshape(b, s, -1) @ wo

        return nary(run, [x, selection, positions]
                    + self._main_parameters()
                    + [a.v_proj.weight, a.o_proj.weight], "keye_attention")

    def _rest(self, x, selection, positions):
        return mixture(self._attend(x, selection, positions),
                       self.post_attention_layernorm, self.mlp)

    def forward(self, x, positions):
        """-> (x, balance term, L_I, counters int32 [4]: pairs routed to
        held experts, rows the grouped product computed, the fullest held
        expert's pairs (the mixture's three), keys the selection kept;
        the picks: selection int8 [b, s, s], experts int32 [b * s, k])."""
        selection, index_loss, kept = self.select(x, positions)
        x, balance, stats, picks = recomputed(self, self._rest, x, selection,
                                              positions)
        with op_scope("picks"):
            counters = nary(
                lambda st, kept: jnp.concatenate(
                    [st.astype(jnp.int32), kept[None]]),
                [stats, kept], "keye_counters")
        return x, balance, index_loss, counters, (selection, picks)


class KeyeVL2Model(DecoderStack):
    def __init__(self, config: KeyeVL2Config):
        n = config.num_layers
        super().__init__(config, config.rms_norm_eps,
                         (KeyeDecoderLayer(config) for _ in range(n)),
                         mixes=(True,) * n, terms=4,
                         scaled=("o_proj.weight", "mlp.down_proj"),
                         factor=math.sqrt(2.0 * n))

    def forward(self, input_ids, position_ids=None):
        """-> (hidden [b, s, h], [per-layer balance terms], [per-layer
        L_I], [per-layer counters], [per-layer (selection, experts)])."""
        if position_ids is None:
            b, s = input_ids.shape
            position_ids = Tensor._wrap(jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (3, b, s)))
        return super().forward(input_ids, position_ids)


class KeyeVL2ForCausalLM(MixtureCausalLM):
    """The language model with its untied head [vocab, hidden]
    (`decoder_parts.MixtureCausalLM`), whose layers also return L_I and
    count the keys the selection kept.

    `loss(ids, labels, position_ids=None)` is the training loss (module
    docstring) and `loss_terms` its three terms (language-model loss, mean
    balance term, mean L_I); `routing_counters()` also reads what the
    selection counted, and the recorded `picks()` hold its keys too."""

    def __init__(self, config: KeyeVL2Config):
        # a row of `routing`: the mixture's three counters, then keys kept
        super().__init__(config, KeyeVL2Model(config), counters=4)

    def record_picks(self, batch, seq):
        """Keep every step's picks in two more buffers of the model: the
        selection, one bit a (query, key) pair, uint8 [layers, batch, seq,
        seq / 8], and the experts int32 [layers, batch * seq, top_k].
        Changes nothing of what a step computes."""
        self.register_buffer("selection_bits", Tensor._wrap(jnp.zeros(
            (self.mixtures, batch, seq, seq // 8), jnp.uint8)))
        super().record_picks(batch, seq)

    def picks(self):
        """-> (selection bool [layers, batch, seq, seq], experts int32
        [layers, batch, seq, top_k]) of the last step."""
        bits = np.asarray(self.selection_bits._data)
        layers, batch, seq, _ = bits.shape
        return (np.unpackbits(bits, axis=-1).astype(bool),
                super().picks().reshape(layers, batch, seq, -1))

    def keep_picks(self, picks):
        self.selection_bits._data = jnp.stack(
            [jnp.packbits(s._data.astype(bool), axis=-1) for s, _ in picks])
        super().keep_picks([e for _, e in picks])

    def routing_counters(self) -> dict:
        """The mixture's totals over the layers of the last step and
        `kept_keys` (query-key pairs the selection kept)."""
        kept = np.asarray(self.routing._data, np.int64)[:, 3].sum()
        return dict(super().routing_counters(), kept_keys=int(kept))
