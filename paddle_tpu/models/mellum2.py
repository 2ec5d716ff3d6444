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

Built from `decoder_parts.py`: the attention projections with per-head
q/k norm, the mixture wiring round `DroplessMoE(held_experts=)`, the stack
and the causal LM with its counters.
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
from ..framework.tensor import Tensor
from ..ops._dispatch import nary
from .decoder_parts import (DecoderStack, GQAProjections, MixtureCausalLM,
                            dropless_experts, mixture, queries_keys,
                            recomputed, rms)
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
        self.self_attn = GQAProjections(c, qk_norm_eps=c.rms_norm_eps)
        self.post_attention_layernorm = LlamaRMSNorm(c.hidden_size,
                                                     c.rms_norm_eps)
        self.mlp = dropless_experts(c)

    def _attend(self, x, positions):
        c, a, kind = self.config, self.self_attn, self.kind
        window, scope = ((c.sliding_window, "window_attention")
                         if kind == SLIDING else (None, "full_attention"))

        def run(x, positions, ln, wq, wk, qn, kn, wv, wo):
            from ..ops.pallas.splash_attention import splash_attention

            b, s, _ = x.shape
            with jax.named_scope("attention/projections"):
                h = rms(x, ln, c.rms_norm_eps)
                cos, sin = rotary_table(c, kind, positions)
                q, k = queries_keys(c, h, wq, wk, qn, kn, cos, sin)
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
        return mixture(self._attend(x, positions),
                       self.post_attention_layernorm, self.mlp)

    def forward(self, x, positions):
        """-> (x, balance term, the mixture's stats float32 [3] (pairs
        routed to held experts, rows the grouped product computed, the
        fullest held expert's pairs), the experts picked int32 [b * s, k])."""
        return recomputed(self, self._whole, x, positions)


class Mellum2Model(DecoderStack):
    def __init__(self, config: Mellum2Config):
        super().__init__(config, config.rms_norm_eps,
                         (Mellum2DecoderLayer(config, kind)
                          for kind in config.layer_types),
                         mixes=(True,) * config.num_layers,
                         scaled=("o_proj.weight", "mlp.down_proj"),
                         factor=math.sqrt(2.0 * config.num_layers))

    def forward(self, input_ids, position_ids=None):
        """-> (hidden [b, s, h], [per-layer balance terms], [per-layer
        stats], [per-layer picked experts])."""
        if position_ids is None:
            b, s = input_ids.shape
            position_ids = Tensor._wrap(jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (b, s)))
        return super().forward(input_ids, position_ids)


class Mellum2ForCausalLM(MixtureCausalLM):
    """The language model with its untied head [vocab, hidden], its
    counters and picks (`decoder_parts.MixtureCausalLM`): `loss(ids,
    labels, position_ids=None)` is the module docstring's training loss."""

    def __init__(self, config: Mellum2Config):
        super().__init__(config, Mellum2Model(config))
