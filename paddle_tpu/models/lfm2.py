"""LFM2-MoE's language model (LiquidAI LFM2-24B-A2B, `model_type`
`lfm2_moe`): a decoder whose mixers are gated short convolutions, three to
one with grouped-query attention, each followed by a dense SwiGLU (the
leading `num_dense_layers`) or a sigmoid-routed mixture of experts:

  u <- u + mixer(RMSNorm(u));  u <- u + ffn(RMSNorm(u))      every layer
  a final RMSNorm, the head TIED to the embedding

Layer l's mixer by `layer_types[l]`, h = RMSNorm(u) (`operator_norm`):
  conv:  [B | C | X] = h W_in                 (hidden x 3 hidden, no bias)
         z = B * X;  c_t = sum_k w_k z_{t - (L - 1) + k}   depthwise over the
         channels, causal, `conv_L_cache` = L taps, zeros before the
         sequence's start, NO activation
         out = (C * c) W_out                  (`ops/pallas/gated_conv.py`:
                                              y = C * conv(B * X) is one
                                              operator with its own VJP)
  full_attention:  q, k, v = h W_q, h W_k, h W_v (GQA, no biases); an RMSNorm
         gain of `head_dim` over each q and each k head, then rotate-half
         RoPE over the whole head at `rope_theta`, positions 0..s-1 a
         sequence; causal softmax at head_dim^-1/2; W_o
Its ffn, h = RMSNorm(u) (`ffn_norm`):
  dense (l < `num_dense_layers`):  (silu(h W_g) * h W_u) W_d at
         `intermediate_size`
  mixture:  s = sigmoid(h W_r) float32 over all `num_experts`; picks = the
         top `num_experts_per_tok` of s + b (b the buffer `score_bias`:
         selection only, no gradient reaches it; ties: lower index);
         g = s[picks] / (sum + 1e-6) * `routed_scaling_factor`;
         y = sum_{e picked and held} g_e W_d,e (silu(W_g,e h) * W_u,e h)
         (`DroplessMoE(gated=True, score="sigmoid", renorm_eps=1e-6)`; no
         shared expert)

  loss = CE(E RMSNorm(u_L)) + mean over the mixture layers of balance_l
  (E the embedding: its gradient is the gather's scatter plus the head's dW)

`held_experts=(lo, hi)` builds the layer's share of an expert-parallel
deployment: the weights of experts lo..hi-1 only, the router whole.
Initialisation: matrices normal(0, `initializer_range`), the residual
products (W_out, W_o, the down products) divided by sqrt(2 x layers), the
taps uniform(+-L^-1/2) (torch's Conv1d at one input channel a group). The
mixture's wiring, the stack and the causal LM are `decoder_parts.py`'s.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..ops._dispatch import nary
from .decoder_parts import (DecoderStack, GQAProjections, MixtureCausalLM,
                            dropless_experts, mixture, queries_keys,
                            recomputed, rms, swiglu)
from .llama import LlamaRMSNorm

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM"]

F32 = jnp.float32
CONV, FULL = "conv", "full_attention"
DENSE, MIXTURE = "dense", "moe"


@dataclass
class Lfm2MoeConfig:
    """Shapes; the defaults are LFM2-24B-A2B's as published."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: tuple = (CONV, CONV) + (FULL, CONV, CONV, CONV) * 9 \
        + (FULL, CONV)
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    router_aux_loss_coef: float = 1e-4
    moe_tile_rows: int = 512        # tiling of the grouped product
    held_experts: tuple = None      # (lo, hi): this chip's experts; None: all
    initializer_range: float = 0.02
    use_recompute: bool = False

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {CONV, FULL}:
            raise ValueError(f"layer_types {self.layer_types} does not name "
                             f"a kind for each of {self.num_hidden_layers} "
                             "layers")

    @property
    def rms_norm_eps(self):         # `decoder_parts`' name for it
        return self.norm_eps


def rotary_table(c: Lfm2MoeConfig, seq):
    """cos, sin float32 [1, seq, head_dim / 2] at the positions 0..seq-1."""
    half = c.head_dim // 2
    inv = float(c.rope_theta) ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.arange(seq, dtype=F32)[None, :, None] * jnp.asarray(inv, F32)
    return jnp.cos(ang), jnp.sin(ang)


class GatedShortConv(nn.Layer):
    def __init__(self, c: Lfm2MoeConfig):
        super().__init__()
        h = c.hidden_size
        self.in_proj = nn.Linear(h, 3 * h, bias_attr=False)
        self.conv_weight = self.create_parameter([c.conv_L_cache, h])
        self.out_proj = nn.Linear(h, h, bias_attr=False)


class Lfm2MoeMLP(nn.Layer):
    def __init__(self, c: Lfm2MoeConfig):
        super().__init__()
        self.gate_proj = nn.Linear(c.hidden_size, c.intermediate_size,
                                   bias_attr=False)
        self.up_proj = nn.Linear(c.hidden_size, c.intermediate_size,
                                 bias_attr=False)
        self.down_proj = nn.Linear(c.intermediate_size, c.hidden_size,
                                   bias_attr=False)


class Lfm2MoeDecoderLayer(nn.Layer):
    """A layer = mixer `kind` (conv | full_attention) x `ffn` (dense | moe)."""

    def __init__(self, c: Lfm2MoeConfig, kind: str, ffn: str):
        super().__init__()
        self.config, self.kind, self.ffn = c, kind, ffn
        self.operator_norm = LlamaRMSNorm(c.hidden_size, c.norm_eps)
        if kind == CONV:
            self.conv = GatedShortConv(c)
        else:
            self.self_attn = GQAProjections(c, qk_norm_eps=c.norm_eps)
        self.ffn_norm = LlamaRMSNorm(c.hidden_size, c.norm_eps)
        self.feed_forward = (
            Lfm2MoeMLP(c) if ffn == DENSE else dropless_experts(
                c, gated=True, score="sigmoid",
                gate_scale=c.routed_scaling_factor, renorm_eps=1e-6))

    def _conv(self, x):
        c, m = self.config, self.conv

        def run(x, ln, w_in, taps, w_out):
            from ..ops.pallas.gated_conv import gated_conv

            with jax.named_scope("conv/project"):
                bcx = rms(x, ln, c.norm_eps) @ w_in
            with jax.named_scope("conv/gate_conv"):
                y = gated_conv(bcx, taps)
            with jax.named_scope("conv/out"):
                return x + y @ w_out

        return nary(run, [x, self.operator_norm.weight, m.in_proj.weight,
                          m.conv_weight, m.out_proj.weight], "lfm2_conv")

    def _attend(self, x):
        c, a = self.config, self.self_attn

        def run(x, ln, wq, wk, qn, kn, wv, wo):
            from ..ops.pallas.splash_attention import splash_attention

            b, s, _ = x.shape
            with jax.named_scope("attention/projections"):
                h = rms(x, ln, c.norm_eps)
                q, k = queries_keys(c, h, wq, wk, qn, kn,
                                    *rotary_table(c, s))
                v = (h @ wv).reshape(b, s, c.num_key_value_heads,
                                     c.head_dim)
            with jax.named_scope("full_attention"):
                o = splash_attention(q, k, v, causal=True)
            with jax.named_scope("attention/projections"):
                return x + o.reshape(b, s, -1) @ wo

        return nary(run, [x, self.operator_norm.weight, a.q_proj.weight,
                          a.k_proj.weight, a.q_norm.weight, a.k_norm.weight,
                          a.v_proj.weight, a.o_proj.weight],
                    "lfm2_attention")

    def _dense(self, x):
        c, m = self.config, self.feed_forward

        def run(x, ln, gate, up, down):
            with jax.named_scope("mlp"):
                return x + swiglu(rms(x, ln, c.norm_eps), gate, up, down)

        return nary(run, [x, self.ffn_norm.weight, m.gate_proj.weight,
                          m.up_proj.weight, m.down_proj.weight], "lfm2_mlp")

    def _whole(self, x):
        x = (self._conv if self.kind == CONV else self._attend)(x)
        if self.ffn == DENSE:
            return self._dense(x)
        return mixture(x, self.ffn_norm, self.feed_forward)

    def forward(self, x):
        """-> x for a dense layer; for a mixture layer (x, balance term, the
        mixture's stats float32 [3], the experts picked int32 [b * s, k])
        (`dropless_moe`). One recomputed segment a layer."""
        return recomputed(self, self._whole, x)


class Lfm2MoeModel(DecoderStack):
    def __init__(self, c: Lfm2MoeConfig):
        ffns = [DENSE if i < c.num_dense_layers else MIXTURE
                for i in range(c.num_hidden_layers)]
        bound = c.conv_L_cache ** -0.5
        super().__init__(
            c, c.norm_eps,
            (Lfm2MoeDecoderLayer(c, kind, ffn)
             for kind, ffn in zip(c.layer_types, ffns)),
            mixes=[ffn == MIXTURE for ffn in ffns],
            scaled=("o_proj.weight", "out_proj.weight", "down_proj.weight",
                    "feed_forward.down_proj"),
            factor=math.sqrt(2.0 * c.num_hidden_layers),
            special={"conv_weight":
                     lambda rng, shape: rng.uniform(-bound, bound, shape)})


class Lfm2MoeForCausalLM(MixtureCausalLM):
    """The language model with its head tied to the embedding, its counters
    and picks a mixture layer (`decoder_parts.MixtureCausalLM`): `loss(ids,
    labels)` is the module docstring's training loss."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__(config, Lfm2MoeModel(config), tied=True)
