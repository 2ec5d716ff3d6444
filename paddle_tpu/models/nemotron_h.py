"""Nemotron-H's language model (NVIDIA-Nemotron-3-Nano-30B-A3B,
`model_type` `nemotron_h`): a decoder whose every layer is ONE mixer under
a pre-norm and a residual, of three kinds chosen by a letter of
`hybrid_override_pattern`:

  u <- u + mixer(RMSNorm(u))   every layer;   a final RMSNorm, an untied head

`M`, Mamba-2 (h = RMSNorm(u) [b, s, hidden]):
  [z | xBC | dt] = h W_in                       (inner | inner + 2 G N | H)
  xBC = silu(conv(xBC) + b)      depthwise, causal, `conv_kernel` taps
  x, B, C = split(xBC)           H heads of P; G groups of N; head h reads
                                 group h // (H / G)
  D_t = softplus(dt_t + dt_bias) a head;  A = -exp(A_log) a head
  S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T;  y_t = S_t C_t + D x_t
                                 (`ops/pallas/ssd_scan.py`, chunks of
                                 `chunk_size`; the state float32)
  y = RMSNorm_g(y * silu(z)) w   the mean square over each of the G groups
                                 of inner / G channels; the gate BEFORE it
  out = y W_out
`*`, attention: q, k, v = h Wq, h Wk, h Wv (no biases); causal softmax
  attention at scale head_dim^-1/2 over `num_attention_heads` in
  `num_key_value_heads` groups; out = o Wo. NO rotary turn and no q/k
  norm: the Nemotron-H models use no position embedding (the Mamba layers
  carry order); `rope_theta` of the published file is read by nothing.
`E`, mixture: s = sigmoid(h W_r) float32 over all `n_routed_experts`; the
  picks are the top `num_experts_per_tok` of s + b (b the correction bias,
  a buffer no gradient reaches); g = s[picks] / (sum + 1e-20) *
  `routed_scaling_factor`;
  out = sum_{e picked and held} g_e Wd_e relu(Wu_e h)^2 + Wd_s relu(Wu_s h)^2
  (`DroplessMoE(gated=False, score="sigmoid")` and a shared expert of the
  same form beside it, counted once).

  loss = CE(head(RMSNorm(u_L))) + mean over the E layers of balance_l

`held_experts=(lo, hi)` builds the layer's share of an expert-parallel
deployment: the weights of experts lo..hi-1 only, router and shared expert
whole. Initialisation as `mamba_ssm`'s: A uniform in [1, 16], the step
sizes log-uniform in [time_step_min, time_step_max] (floor
`time_step_floor`) through the inverse softplus into `dt_bias`, D = 1,
matrices normal(0, `initializer_range`), and out_proj, o_proj and the
experts' down products divided by sqrt(num_layers)
(`rescale_prenorm_residual`). No parameter is exempt from the optimizer's
weight decay here (`mamba_ssm` marks A_log, D and dt_bias `_no_weight_decay`;
a caller that wants that passes AdamW's `apply_decay_param_fun`). The
mixture's wiring, the stack and the causal LM are `decoder_parts.py`'s.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.autograd import op_scope
from ..ops._dispatch import nary
from .decoder_parts import (DecoderStack, GQAProjections, MixtureCausalLM,
                            causal_conv, dropless_experts, mixture,
                            recomputed, recomputes, rms, state_space_leaves)
from .llama import LlamaRMSNorm

__all__ = ["NemotronHConfig", "NemotronHModel", "NemotronHForCausalLM"]

F32 = jnp.float32
MAMBA, MIXTURE, ATTENTION = "M", "E", "*"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass
class NemotronHConfig:
    """Shapes; the defaults are NVIDIA-Nemotron-3-Nano-30B-A3B's as
    published. The first `num_layers` letters of the pattern are built."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_layers: int = 52
    hybrid_override_pattern: str = PATTERN
    layer_norm_epsilon: float = 1e-5
    # M
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    # *
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # E
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    router_aux_loss_coef: float = 1e-4
    moe_tile_rows: int = 512        # tiling of the grouped product
    held_experts: tuple = None      # (lo, hi): this chip's experts; None: all
    initializer_range: float = 0.02
    use_recompute: bool = False

    def __post_init__(self):
        self.kinds = tuple(self.hybrid_override_pattern[:self.num_layers])
        if len(self.kinds) != self.num_layers or set(self.kinds) - {
                MAMBA, MIXTURE, ATTENTION}:
            raise ValueError(
                f"hybrid_override_pattern {self.hybrid_override_pattern!r} "
                f"does not name a kind for each of {self.num_layers} layers")

    @property
    def num_experts(self):          # the name `decoder_parts.py` reads
        return self.n_routed_experts

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


def gated_group_norm(y, z, w, groups, eps):
    """RMSNorm over each of `groups` runs of channels of y * silu(z)."""
    v = y.astype(F32) * jax.nn.silu(z.astype(F32))
    g = v.reshape(v.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return (g.reshape(v.shape) * w.astype(F32)).astype(y.dtype)


class Mamba2Mixer(nn.Layer):
    def __init__(self, c: NemotronHConfig):
        super().__init__()
        from ..nn.initializer import Constant

        inner, heads = c.mamba_inner, c.mamba_num_heads
        self.in_proj = nn.Linear(c.hidden_size, inner + c.conv_dim + heads,
                                 bias_attr=False)
        self.conv_weight = self.create_parameter([c.conv_kernel, c.conv_dim])
        self.conv_bias = self.create_parameter([c.conv_dim], is_bias=True)
        self.dt_bias = self.create_parameter([heads], is_bias=True)
        self.A_log = self.create_parameter([heads], is_bias=True)
        self.D = self.create_parameter([heads],
                                       default_initializer=Constant(1.0))
        self.norm = LlamaRMSNorm(inner, c.layer_norm_epsilon)
        self.out_proj = nn.Linear(inner, c.hidden_size, bias_attr=False)

    def parameters_in_order(self):
        return [self.in_proj.weight, self.conv_weight, self.conv_bias,
                self.dt_bias, self.A_log, self.D, self.norm.weight,
                self.out_proj.weight]


class NemotronHMixture(nn.Layer):
    def __init__(self, c: NemotronHConfig):
        super().__init__()
        self.experts = dropless_experts(
            c, gated=False, score="sigmoid",
            gate_scale=c.routed_scaling_factor)
        self.shared_up = nn.Linear(c.hidden_size,
                                   c.moe_shared_expert_intermediate_size,
                                   bias_attr=False)
        self.shared_down = nn.Linear(c.moe_shared_expert_intermediate_size,
                                     c.hidden_size, bias_attr=False)


def _relu2_expert(h, up, down):
    return jnp.square(jnp.maximum(h @ up, 0)) @ down


class NemotronHLayer(nn.Layer):
    """One layer of the kind `kind` (a letter of the pattern)."""

    def __init__(self, c: NemotronHConfig, kind: str):
        super().__init__()
        self.config, self.kind = c, kind
        self.norm = LlamaRMSNorm(c.hidden_size, c.layer_norm_epsilon)
        self.mixer = {MAMBA: Mamba2Mixer, ATTENTION: GQAProjections,
                      MIXTURE: NemotronHMixture}[kind](c)

    def _mamba(self, x):
        c = self.config

        def run(x, ln, w_in, conv_w, conv_b, dt_bias, a_log, d, gn, w_out):
            from ..ops.pallas.ssd_scan import ssd_scan

            b, s, _ = x.shape
            inner, gn_ = c.mamba_inner, c.n_groups * c.ssm_state_size
            with jax.named_scope("ssm/project"):
                h = rms(x, ln, c.layer_norm_epsilon) @ w_in
                z, xbc, dt = (h[..., :inner],
                              h[..., inner:inner + c.conv_dim],
                              h[..., inner + c.conv_dim:])
            with jax.named_scope("ssm/conv"):
                xbc = causal_conv(xbc, conv_w, conv_b)
            with jax.named_scope("ssm/scan"):
                def cut(a, groups):
                    return a.reshape(b, s, groups, -1)

                y = ssd_scan(
                    cut(xbc[..., :inner], c.mamba_num_heads),
                    jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32)),
                    -jnp.exp(a_log.astype(F32)),
                    cut(xbc[..., inner:inner + gn_], c.n_groups),
                    cut(xbc[..., inner + gn_:], c.n_groups), d,
                    chunk=c.chunk_size).reshape(b, s, inner)
            with jax.named_scope("ssm/gate_norm"):
                y = gated_group_norm(y, z, gn, c.n_groups,
                                     c.layer_norm_epsilon)
            with jax.named_scope("ssm/out"):
                return x + y @ w_out

        return nary(run, [x, self.norm.weight]
                    + self.mixer.parameters_in_order(), "mamba2_mixer")

    def _attention(self, x):
        c, a = self.config, self.mixer

        def run(x, ln, wq, wk, wv, wo):
            from ..ops.pallas.splash_attention import splash_attention

            b, s, _ = x.shape
            with jax.named_scope("attention/projections"):
                h = rms(x, ln, c.layer_norm_epsilon)
                q = (h @ wq).reshape(b, s, c.num_attention_heads, c.head_dim)
                k = (h @ wk).reshape(b, s, c.num_key_value_heads, c.head_dim)
                v = (h @ wv).reshape(b, s, c.num_key_value_heads, c.head_dim)
            with jax.named_scope("full_attention"):
                o = splash_attention(q, k, v, causal=True)
            with jax.named_scope("attention/projections"):
                return x + o.reshape(b, s, -1) @ wo

        return nary(run, [x, self.norm.weight, a.q_proj.weight,
                          a.k_proj.weight, a.v_proj.weight, a.o_proj.weight],
                    "nemotron_h_attention")

    def _mixture(self, x):
        m = self.mixer
        return mixture(x, self.norm, m.experts, _relu2_expert,
                       [m.shared_up.weight, m.shared_down.weight])

    def forward(self, x):
        """-> x for an `M` or `*` layer; for an `E` layer (x, balance
        term, the mixture's stats float32 [3], the experts picked int32
        [b * s, k]) (`dropless_moe`)."""
        whole = {MAMBA: self._mamba, ATTENTION: self._attention,
                 MIXTURE: self._mixture}[self.kind]
        if self.kind == MAMBA and x.shape[0] > 1 and recomputes(self):
            from .. import ops

            # a sequence at a time, each its own segment: one layer's
            # backward at 4 x 8,192 tokens holds 6.4 GiB of temporaries
            # whole and 3.2 this way (one segment that loops over the
            # sequences with a checkpointed body compiles 9 % sooner and
            # holds 1.0 GiB more)
            with op_scope("ssm/project"):
                parts = ops.split(x, x.shape[0], axis=0)
            parts = [recomputed(self, whole, part) for part in parts]
            with op_scope("ssm/out"):
                return ops.concat(parts, axis=0)
        return recomputed(self, whole, x)


class NemotronHModel(DecoderStack):
    def __init__(self, c: NemotronHConfig):
        super().__init__(c, c.layer_norm_epsilon,
                         (NemotronHLayer(c, kind) for kind in c.kinds),
                         mixes=[kind == MIXTURE for kind in c.kinds],
                         scaled=("out_proj.weight", "o_proj.weight",
                                 "down_proj", "shared_down.weight"),
                         factor=math.sqrt(c.num_layers),
                         special=state_space_leaves(
                             ("conv_weight", "conv_bias"), c.conv_kernel,
                             c.time_step_min, c.time_step_max,
                             c.time_step_floor))


class NemotronHForCausalLM(MixtureCausalLM):
    """The language model with its untied head [vocab, hidden], its
    counters and picks a mixture layer (`decoder_parts.MixtureCausalLM`):
    `loss(ids, labels)` is the module docstring's training loss."""

    def __init__(self, config: NemotronHConfig):
        super().__init__(config, NemotronHModel(config))
