"""Mellum 2's language model (JetBrains Mellum2-12B-A2.5B): a GQA decoder
whose layers differ by KIND, three sliding-window layers to one full
layer, and whose every MLP is a dropless top-k mixture of experts.

Layer l of kind `layer_types[l]`, x [b, s, hidden], positions int [b, s]:

  h  = RMSNorm(x);  q, k, v = h Wq, h Wk, h Wv  (no biases)
       RMSNorm over each q and k head, then the kind's rotary table
  sliding: query t sees keys s with 0 <= t - s < `sliding_window`;
       plain RoPE, inv_freq_i = theta ** (-2i / d)
  full:    causal over everything; YaRN (`yarn_inv_freq`), cos and sin
       times `attention_factor`, so the logits carry its square
  x  = x + splash_attention(q, k, v, causal, window) Wo
  h2 = RMSNorm(x);  x = x + dropless_moe(h2)      (incubate/.../moe/dropless.py)

  loss = CE(head(RMSNorm(x_L))) + mean_l balance_l

Built from what `keye_vl2.py` is built from: its attention projections
with per-head q/k norm, `DroplessMoE(held_experts=)` and its counters.
`held_experts=(lo, hi)` builds the layer's share of an expert-parallel
deployment: the weights of experts lo..hi-1 only, the router whole.
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
from ..ops._dispatch import nary
from .keye_vl2 import (KeyeAttention, KeyeVL2Model, _mixture, _queries_keys,
                       _rms, routing_totals)
from .llama import LlamaRMSNorm

__all__ = ["Mellum2Config", "Mellum2Model", "Mellum2ForCausalLM",
           "yarn_inv_freq"]

F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class Mellum2Config:
    """Shapes; the defaults are Mellum2-12B-A2.5B's as published."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL) * 7
    sliding_window: int = 1024
    rope_theta: float = 5e5
    yarn_factor: float = 16.0
    yarn_original_positions: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782   # 0.1 ln 16 + 1
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.001
    moe_tile_rows: int = 512        # tiling of the grouped product
    held_experts: tuple = None      # (lo, hi): this chip's experts; None: all
    initializer_range: float = 0.02
    use_recompute: bool = False

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_layers or set(
                self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types} does not name "
                             f"a kind for each of {self.num_layers} layers")


def yarn_inv_freq(head_dim, theta, factor, original_positions, beta_fast,
                  beta_slow):
    """-> (float64 [head_dim / 2] frequencies, low, high), as
    transformers' `_compute_yarn_parameters` (truncate on): frequency i
    that turns more than `beta_fast` times over the original context is
    kept, one that turns less than `beta_slow` times is divided by
    `factor`, and between the two dimensions `low` and `high` a linear
    ramp blends them."""
    def dim_of(turns):
        return (head_dim * math.log(original_positions
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    half = head_dim // 2
    plain = float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return plain * (1 - ramp) + plain / factor * ramp, low, high


def rotary_table(c: Mellum2Config, kind, positions):
    """cos, sin float32 [b, s, head_dim / 2] of the layer kind's rotation
    at positions int [b, s]."""
    half = c.head_dim // 2
    if kind == FULL:
        inv = yarn_inv_freq(c.head_dim, c.rope_theta, c.yarn_factor,
                            c.yarn_original_positions, c.yarn_beta_fast,
                            c.yarn_beta_slow)[0]
        scale = c.yarn_attention_factor
    else:
        inv = float(c.rope_theta) ** (-np.arange(half, dtype=np.float64)
                                      / half)
        scale = 1.0
    ang = positions.astype(F32)[..., None] * jnp.asarray(inv, F32)
    return jnp.cos(ang) * F32(scale), jnp.sin(ang) * F32(scale)


class Mellum2DecoderLayer(nn.Layer):
    def __init__(self, c: Mellum2Config, kind: str):
        super().__init__()
        self.config, self.kind = c, kind
        self.input_layernorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = KeyeAttention(c)
        self.post_attention_layernorm = LlamaRMSNorm(c.hidden_size,
                                                     c.rms_norm_eps)
        self.mlp = DroplessMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, held_experts=c.held_experts,
            renormalise=c.norm_topk_prob,
            balance_coef=c.router_aux_loss_coef,
            tile_rows=c.moe_tile_rows)

    def _attend(self, x, positions):
        c, a, kind = self.config, self.self_attn, self.kind
        window, scope = ((c.sliding_window, "window_attention")
                         if kind == SLIDING else (None, "full_attention"))

        def run(x, positions, ln, wq, wk, qn, kn, wv, wo):
            from ..ops.pallas.splash_attention import splash_attention

            b, s, _ = x.shape
            with jax.named_scope("attention/projections"):
                h = _rms(x, ln, c.rms_norm_eps)
                cos, sin = rotary_table(c, kind, positions)
                q, k = _queries_keys(c, h, wq, wk, qn, kn, cos, sin)
                v = (h @ wv).reshape(b, s, c.num_key_value_heads,
                                     c.head_dim)
            with jax.named_scope(scope):
                o = splash_attention(q, k, v, causal=True, window=window)
            with jax.named_scope("attention/projections"):
                return x + o.reshape(b, s, -1) @ wo

        return nary(run, [x, positions, self.input_layernorm.weight,
                          a.q_proj.weight, a.k_proj.weight, a.q_norm.weight,
                          a.k_norm.weight, a.v_proj.weight, a.o_proj.weight],
                    "mellum2_attention")

    def _whole(self, x, positions):
        return _mixture(self, self._attend(x, positions))

    def forward(self, x, positions):
        """-> (x, balance term, the mixture's stats float32 [3] (pairs
        routed to held experts, rows the grouped product computed, the
        fullest held expert's pairs), the experts picked int32 [b * s, k])."""
        if self.config.use_recompute and self.training:
            from ..distributed.fleet import recompute

            return recompute(self._whole, x, positions)
        return self._whole(x, positions)


class Mellum2Model(nn.Layer):
    def __init__(self, config: Mellum2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([Mellum2DecoderLayer(config, kind)
                                    for kind in config.layer_types])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        KeyeVL2Model._init_weights(self, config)

    def forward(self, input_ids, position_ids=None):
        """-> (hidden [b, s, h], [per-layer balance terms], [per-layer
        stats], [per-layer picked experts])."""
        if position_ids is None:
            b, s = input_ids.shape
            position_ids = Tensor._wrap(jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (b, s)))
        with op_scope("embed"):
            x = self.embed_tokens(input_ids)
        balance, stats, picks = [], [], []
        for layer in self.layers:
            x, bal, st, picked = layer(x, position_ids)
            balance.append(bal)
            stats.append(st)
            picks.append(picked)
        with op_scope("head"):
            return self.norm(x), balance, stats, picks


class Mellum2ForCausalLM(nn.Layer):
    """The language model with its untied head [vocab, hidden].

    `loss(ids, labels, position_ids=None)` is the training loss (module
    docstring); `routing_counters()` reads what the last step's routing
    counted; after `record_picks(batch, seq)` the steps also keep WHICH
    experts they picked (`picks()`)."""

    def __init__(self, config: Mellum2Config):
        super().__init__()
        from ..framework.random import host_normal
        from ..nn.initializer import get_global_initializer

        self.config = config
        self.model = Mellum2Model(config)
        self.lm_head = self.create_parameter(
            [config.vocab_size, config.hidden_size])
        if get_global_initializer() is None:
            self.lm_head._data = host_normal(self.lm_head._data.shape,
                                             config.initializer_range)
        # per layer: pairs routed to held experts, rows computed, the
        # fullest held expert's pairs; the last step's
        self.register_buffer("routing", Tensor._wrap(
            jnp.zeros((config.num_layers, 3), jnp.int32)))

    def record_picks(self, batch, seq):
        """Keep every step's expert picks in one more buffer of the
        model, int32 [layers, batch * seq, top_k]. Changes nothing of
        what a step computes."""
        c = self.config
        self.register_buffer("expert_picks", Tensor._wrap(jnp.zeros(
            (c.num_layers, batch * seq, c.num_experts_per_tok), jnp.int32)))

    def picks(self):
        """-> experts int32 [layers, batch * seq, top_k] of the last step."""
        return np.asarray(self.expert_picks._data)

    def forward(self, input_ids, position_ids=None):
        from .. import ops

        hidden = self.model(input_ids, position_ids)[0]
        return ops.matmul(hidden, self.lm_head, transpose_y=True)

    def loss_terms(self, input_ids, labels, position_ids=None):
        """-> (language-model loss, mean balance term)."""
        from .gpt import fused_lm_loss

        hidden, balance, stats, picks = self.model(input_ids, position_ids)
        with jax.named_scope("picks"):
            self.routing._data = jnp.stack(
                [s._data.astype(jnp.int32) for s in stats])
            if "expert_picks" in self._buffers:
                self.expert_picks._data = jnp.stack(
                    [e._data for e in picks])
        with op_scope("head"):
            lm = fused_lm_loss(hidden, self.lm_head, True, labels)
        return lm, sum(balance[1:], balance[0]) / float(len(balance))

    def loss(self, input_ids, labels, position_ids=None):
        lm, balance = self.loss_terms(input_ids, labels, position_ids)
        return lm + balance

    def routing_counters(self) -> dict:
        """Totals over the layers of the last step: `routed_pairs`,
        `computed_rows`, `max_load_over_mean` (keye_vl2 `routing_totals`)."""
        return routing_totals(np.asarray(self.routing._data, np.int64),
                              self.config)
