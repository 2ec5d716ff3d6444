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
selection when `use_recompute` is on.
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
from ..incubate.distributed.models.moe.dropless import DroplessMoE
from ..ops import sparse_attention as sa
from ..ops._dispatch import nary
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


def _rms(x, w, eps):
    x32 = x.astype(F32)
    out = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                              + eps)
    return (out * w.astype(F32)).astype(x.dtype)


def _layer_norm(x, w, b, eps):
    x32 = x.astype(F32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32)
            + b.astype(F32)).astype(x.dtype)


def _queries_keys(c, h, wq, wk, qn, kn, cos, sin):
    """The main attention's normed, rotated q [b,s,heads,d], k [b,s,kv,d]."""
    b, s, _ = h.shape
    q = (h @ wq).reshape(b, s, c.num_attention_heads, c.head_dim)
    k = (h @ wk).reshape(b, s, c.num_key_value_heads, c.head_dim)
    return (sa.apply_rotary(_rms(q, qn, c.rms_norm_eps), cos, sin),
            sa.apply_rotary(_rms(k, kn, c.rms_norm_eps), cos, sin))


def _indexer_branch(c, idx, x, main, positions):
    """-> (selection int8 [b, s, s], L_I, kept keys). `idx` = (WqI, WkI,
    kI norm gain, kI norm bias, Ww); `main` = (norm gain, Wq, Wk, q norm,
    k norm), used for the indexer's input and target only."""
    wqi, wki, knw, knb, ww = idx
    ln, wq, wk, qn, kn = main
    b, s, _ = x.shape
    with jax.named_scope("indexer/project"):
        h = _rms(x, ln, c.rms_norm_eps)
        cos, sin = sa.mrope_angles(positions, c.head_dim, c.rope_theta,
                                   c.mrope_section)
        q, k = _queries_keys(c, h, wq, wk, qn, kn, cos, sin)
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


def _mixture(layer, x):
    """x + the mixture of the layer's post-attention norm of x -> (x,
    balance term, stats, picks)."""
    with op_scope("moe/norm"):
        h = layer.post_attention_layernorm(x)
    y, balance, stats, picks = layer.mlp(h)
    with op_scope("moe/residual"):
        return x + y, balance, stats, picks


def routing_totals(rows, config) -> dict:
    """Totals of a step's per-layer mixture counters `rows` int [layers,
    >= 3] (pairs routed to held experts, rows computed, the fullest held
    expert's pairs)."""
    lo, hi = config.held_experts or (0, config.num_experts)
    mean = rows[:, 0] / float(hi - lo)
    return {"routed_pairs": int(rows[:, 0].sum()),
            "computed_rows": int(rows[:, 1].sum()),
            "max_load_over_mean": float(np.max(
                rows[:, 2] / np.maximum(mean, 1e-30)))}


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


class KeyeAttention(nn.Layer):
    def __init__(self, c: KeyeVL2Config):
        super().__init__()
        h, d = c.hidden_size, c.head_dim
        self.q_proj = nn.Linear(h, c.num_attention_heads * d,
                                bias_attr=False)
        self.k_proj = nn.Linear(h, c.num_key_value_heads * d,
                                bias_attr=False)
        self.v_proj = nn.Linear(h, c.num_key_value_heads * d,
                                bias_attr=False)
        self.o_proj = nn.Linear(c.num_attention_heads * d, h,
                                bias_attr=False)
        self.q_norm = LlamaRMSNorm(d, c.rms_norm_eps)
        self.k_norm = LlamaRMSNorm(d, c.rms_norm_eps)


class KeyeDecoderLayer(nn.Layer):
    def __init__(self, c: KeyeVL2Config):
        super().__init__()
        self.config = c
        self.input_layernorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = KeyeAttention(c)
        self.indexer = KeyeIndexer(c)
        self.post_attention_layernorm = LlamaRMSNorm(c.hidden_size,
                                                     c.rms_norm_eps)
        self.mlp = DroplessMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, held_experts=c.held_experts,
            renormalise=c.norm_topk_prob,
            balance_coef=c.router_aux_loss_coef,
            tile_rows=c.moe_tile_rows)
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
                h = _rms(x, ln, c.rms_norm_eps)
                cos, sin = sa.mrope_angles(positions, c.head_dim,
                                           c.rope_theta, c.mrope_section)
                q, k = _queries_keys(c, h, wq, wk, qn, kn, cos, sin)
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
        return _mixture(self, self._attend(x, selection, positions))

    def forward(self, x, positions):
        """-> (x, balance term, L_I, counters int32 [4]: pairs routed to
        held experts, rows the grouped product computed, the fullest held
        expert's pairs (the mixture's three), keys the selection kept;
        the picks: selection int8 [b, s, s], experts int32 [b * s, k])."""
        selection, index_loss, kept = self.select(x, positions)
        if self.config.use_recompute and self.training:
            from ..distributed.fleet import recompute

            x, balance, stats, picks = recompute(self._rest, x, selection,
                                                 positions)
        else:
            x, balance, stats, picks = self._rest(x, selection, positions)
        with op_scope("picks"):
            counters = nary(
                lambda st, kept: jnp.concatenate(
                    [st.astype(jnp.int32), kept[None]]),
                [stats, kept], "keye_counters")
        return x, balance, index_loss, counters, (selection, picks)


class KeyeVL2Model(nn.Layer):
    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([KeyeDecoderLayer(config)
                                    for _ in range(config.num_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        self._init_weights(config)

    def _init_weights(self, config):
        from ..framework.random import host_normal
        from ..nn.initializer import get_global_initializer

        if get_global_initializer() is not None:
            return      # the caller's initializer overrides the model's own
        std = config.initializer_range
        for name, p in self.named_parameters():
            if p.ndim >= 2:
                p._data = host_normal(p._data.shape, std)
                if name.endswith(("o_proj.weight", "mlp.down_proj")):
                    p._data = p._data / math.sqrt(2.0 * config.num_layers)

    def forward(self, input_ids, position_ids=None):
        """-> (hidden [b, s, h], [per-layer balance terms], [per-layer
        L_I], [per-layer counters], [per-layer (selection, experts)])."""
        if position_ids is None:
            b, s = input_ids.shape
            position_ids = Tensor._wrap(jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (3, b, s)))
        with op_scope("embed"):
            x = self.embed_tokens(input_ids)
        balance, index_loss, counters, picks = [], [], [], []
        for layer in self.layers:
            x, bal, li, cnt, picked = layer(x, position_ids)
            balance.append(bal)
            index_loss.append(li)
            counters.append(cnt)
            picks.append(picked)
        with op_scope("head"):
            return self.norm(x), balance, index_loss, counters, picks


class KeyeVL2ForCausalLM(nn.Layer):
    """The language model with its untied head [vocab, hidden].

    `loss(ids, labels, position_ids=None)` is the training loss (module
    docstring); `routing_counters()` reads what the last step's routing
    and selection counted; after `record_picks(batch, seq)` the steps
    also keep WHICH keys and experts they picked (`picks()`)."""

    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        from ..framework.random import host_normal
        from ..nn.initializer import get_global_initializer

        self.config = config
        self.model = KeyeVL2Model(config)
        self.lm_head = self.create_parameter(
            [config.vocab_size, config.hidden_size])
        if get_global_initializer() is None:
            self.lm_head._data = host_normal(self.lm_head._data.shape,
                                             config.initializer_range)
        # per layer: pairs routed to held experts, rows computed, the
        # fullest held expert's pairs, keys kept; the last step's
        self.register_buffer("routing", Tensor._wrap(
            jnp.zeros((config.num_layers, 4), jnp.int32)))

    def record_picks(self, batch, seq):
        """Keep every step's picks in two more buffers of the model: the
        selection, one bit a (query, key) pair, uint8 [layers, batch, seq,
        seq / 8], and the experts int32 [layers, batch * seq, top_k].
        Changes nothing of what a step computes."""
        c = self.config
        self.register_buffer("selection_bits", Tensor._wrap(jnp.zeros(
            (c.num_layers, batch, seq, seq // 8), jnp.uint8)))
        self.register_buffer("expert_picks", Tensor._wrap(jnp.zeros(
            (c.num_layers, batch * seq, c.num_experts_per_tok), jnp.int32)))

    def picks(self):
        """-> (selection bool [layers, batch, seq, seq], experts int32
        [layers, batch, seq, top_k]) of the last step."""
        bits = np.asarray(self.selection_bits._data)
        layers, batch, seq, _ = bits.shape
        experts = np.asarray(self.expert_picks._data)
        return (np.unpackbits(bits, axis=-1).astype(bool),
                experts.reshape(layers, batch, seq, -1))

    def forward(self, input_ids, position_ids=None):
        from .. import ops

        hidden = self.model(input_ids, position_ids)[0]
        return ops.matmul(hidden, self.lm_head, transpose_y=True)

    def loss_terms(self, input_ids, labels, position_ids=None):
        """-> (language-model loss, mean balance term, mean L_I)."""
        from .gpt import fused_lm_loss

        hidden, balance, index_loss, counters, picks = self.model(
            input_ids, position_ids)
        with jax.named_scope("picks"):
            self.routing._data = jnp.stack([c._data for c in counters])
            if "selection_bits" in self._buffers:
                self.selection_bits._data = jnp.stack(
                    [jnp.packbits(s._data.astype(bool), axis=-1)
                     for s, _ in picks])
                self.expert_picks._data = jnp.stack(
                    [e._data for _, e in picks])
        n = float(len(balance))
        with op_scope("head"):
            lm = fused_lm_loss(hidden, self.lm_head, True, labels)
        return (lm, sum(balance[1:], balance[0]) / n,
                sum(index_loss[1:], index_loss[0]) / n)

    def loss(self, input_ids, labels, position_ids=None):
        lm, balance, index_loss = self.loss_terms(input_ids, labels,
                                                  position_ids)
        return lm + balance + index_loss

    def routing_counters(self) -> dict:
        """Totals over the layers of the last step: `routed_pairs`
        (token-expert pairs on held experts), `computed_rows` (rows the
        grouped product computed, padding included), `max_load_over_mean`
        (the fullest held expert of any layer over the mean load) and
        `kept_keys` (query-key pairs the selection kept)."""
        r = np.asarray(self.routing._data, np.int64)
        return dict(routing_totals(r, self.config),
                    kept_keys=int(r[:, 3].sum()))
