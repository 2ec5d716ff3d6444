"""Ling-3.0-flash-VL's language model (inclusionAI; the vision tower is not
built): a decoder of two kinds of mixer, chosen by a rule of the layer's
index, each followed by a dense SwiGLU or a mixture of experts:

  u <- u + mixer(RMSNorm(u));  u <- u + ffn(RMSNorm(u))      every layer
  a final RMSNorm, an untied head

Layer i is latent attention (MLA) where (i + 1) % `layer_group_size` == 0 and
Kimi Delta Attention (KDA) otherwise; its ffn is the dense SwiGLU of
`intermediate_size` for i < `first_k_dense_replace`, else the mixture.

KDA (arXiv:2510.26692 section 3, with this config's switches), h = RMSNorm(u):
  q~, k~, v~ = W_q h, W_k h, W_v h        heads x 128 each, no bias
  each through a depthwise causal convolution of `short_conv_kernel_size`
  taps and silu; q, k L2-normalised a head, q times 128^-1/2
  a_t = kda_lower_bound * sigmoid(exp(A_log) (W_f h + dt_bias))   float32, in
        (-5, 0) a channel (A_log a head, dt_bias a channel); alpha = exp a
  beta_t = sigmoid(W_b h) a head
  S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t                        (`ops/pallas/kda.py`, chunks of
                                         `kda_chunk_size`; the state float32,
                                         zero at a sequence's start)
  out = W_o [sigmoid(W_g h) a head * RMSNorm_128(o_t)]
  No rotary turn in a KDA layer.
MLA, expanded form:
  q = W_q h (heads x (128 + 64));  [c | k_r] = W_kva h (512 | 64, k_r one
  row shared by the heads);  [k_n | v] = W_kvb RMSNorm(c) (heads x (128 + 128))
  RMSNorm over a head's q and over k = [k_n | k_r] (`use_qk_norm`), then the
  rotary turn (rotate-half, `rope_theta`) of the last 64 of each; causal
  softmax at scale 192^-1/2; values of 128
  out = W_o [sigmoid(W_g h) a head * o]
Mixture: s = sigmoid(h W_r) float32 over all `num_experts`; c = s + b (b a
  buffer no gradient reaches); a group's score is the sum of its two largest
  c (`n_group` groups); the `topk_group` best groups stay; the picks are the
  top `num_experts_per_tok` of c inside them (ties: lower index);
  g = s[picks] / (sum + 1e-20) * `routed_scaling_factor`;
  y = sum_{e picked and held} g_e W_d,e (silu(W_g,e h) * W_u,e h)
      + W_d,s (silu(W_g,s h) * W_u,s h)
  (`DroplessMoE(gated=True, score="sigmoid", n_group, topk_group)` and a
  shared expert of the same form beside it, counted once).

  loss = CE(head(RMSNorm(u_L))) + mean over the mixture layers of balance_l

`held_experts=(lo, hi)` builds the layer's share of an expert-parallel
deployment: the weights of experts lo..hi-1 only, router and shared expert
whole. The clamped SwiGLU of the published layers 35..41
(`expert_swiglu_limit_list`) is not built: a non-zero limit is refused.
Initialisation: matrices normal(0, `initializer_range`), the convolutions
uniform(+-taps^-1/2), A_log the logarithm of uniform(1, 16) and dt_bias the
inverse softplus of a step log-uniform in [0.001, 0.1], as
flash-linear-attention's KDA layer draws them. The mixture's wiring, the
stack and the causal LM are `decoder_parts.py`'s.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..ops._dispatch import nary
from .decoder_parts import (DecoderStack, MixtureCausalLM, causal_conv,
                            dropless_experts, mixture, recomputed, rms,
                            state_space_leaves, swiglu)
from .llama import LlamaRMSNorm, _rope_tables, apply_rotary_pos_emb

__all__ = ["Ling3Config", "Ling3Model", "Ling3ForCausalLM"]

F32 = jnp.float32
KDA, MLA = "kda", "mla"


@dataclass
class Ling3Config:
    """Shapes; the defaults are Ling-3.0-flash-VL's as published."""
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    intermediate_size: int = 6144
    rms_norm_eps: float = 1e-6
    num_attention_heads: int = 32
    # KDA
    head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk_size: int = 64
    # MLA
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    # mixture
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    router_aux_loss_coef: float = 1e-4
    expert_swiglu_limit: float = 0.0
    moe_tile_rows: int = 512        # tiling of the grouped product
    held_experts: tuple = None      # (lo, hi): this chip's experts; None: all
    initializer_range: float = 0.02
    use_recompute: bool = False

    def __post_init__(self):
        if self.expert_swiglu_limit:
            raise ValueError(
                "Ling3Config: a clamped SwiGLU (expert_swiglu_limit_list of "
                "the published layers 35..41) is not built")
        self.mixers = tuple(
            MLA if (i + 1) % self.layer_group_size == 0 else KDA
            for i in range(self.num_hidden_layers))

    @property
    def kda_inner(self):
        return self.num_attention_heads * self.head_dim


@jax.custom_vjp
def _product_f32(h, w):
    """h [b, s, n] @ w [n, m] left in float32: the decay's logits, whose
    rounding to h's type would add up along a chunk's running sums (at
    bfloat16, a tenth of the exponent over 64 tokens). The pull-back's two
    products take the cotangent in h's type, as every other product's."""
    return jnp.einsum("bsn,nm->bsm", h, w, preferred_element_type=F32)


def _product_f32_fwd(h, w):
    return _product_f32(h, w), (h, w)


def _product_f32_bwd(res, g):
    h, w = res
    g = g.astype(h.dtype)
    return (jnp.einsum("bsm,nm->bsn", g, w),
            jnp.einsum("bsn,bsm->nm", h, g).astype(w.dtype))


_product_f32.defvjp(_product_f32_fwd, _product_f32_bwd)


def _turn(x, theta):
    """Rotate-half turn of x [b, s, heads, r] by the positions 0..s-1."""
    return apply_rotary_pos_emb(
        x.astype(F32), *_rope_tables(x.shape[1], x.shape[3], theta)
    ).astype(x.dtype)


class KDAMixer(nn.Layer):
    def __init__(self, c: Ling3Config):
        super().__init__()
        h, inner, heads = c.hidden_size, c.kda_inner, c.num_attention_heads
        taps = c.short_conv_kernel_size
        self.q_proj = nn.Linear(h, inner, bias_attr=False)
        self.k_proj = nn.Linear(h, inner, bias_attr=False)
        self.v_proj = nn.Linear(h, inner, bias_attr=False)
        self.q_conv = self.create_parameter([taps, inner])
        self.k_conv = self.create_parameter([taps, inner])
        self.v_conv = self.create_parameter([taps, inner])
        self.f_proj = nn.Linear(h, inner, bias_attr=False)
        self.A_log = self.create_parameter([heads], is_bias=True)
        self.dt_bias = self.create_parameter([inner], is_bias=True)
        self.b_proj = nn.Linear(h, heads, bias_attr=False)
        self.g_proj = nn.Linear(h, heads, bias_attr=False)
        self.o_norm = LlamaRMSNorm(c.head_dim, c.rms_norm_eps)
        self.o_proj = nn.Linear(inner, h, bias_attr=False)

    def parameters_in_order(self):
        return [self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
                self.q_conv, self.k_conv, self.v_conv, self.f_proj.weight,
                self.A_log, self.dt_bias, self.b_proj.weight,
                self.g_proj.weight, self.o_norm.weight, self.o_proj.weight]


class MLAMixer(nn.Layer):
    def __init__(self, c: Ling3Config):
        super().__init__()
        h, heads = c.hidden_size, c.num_attention_heads
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        self.q_proj = nn.Linear(h, heads * qk, bias_attr=False)
        self.kv_a_proj = nn.Linear(h, c.kv_lora_rank + c.qk_rope_head_dim,
                                   bias_attr=False)
        self.kv_a_norm = LlamaRMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = nn.Linear(
            c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim),
            bias_attr=False)
        self.q_norm = LlamaRMSNorm(qk, c.rms_norm_eps)
        self.k_norm = LlamaRMSNorm(qk, c.rms_norm_eps)
        self.g_proj = nn.Linear(h, heads, bias_attr=False)
        self.o_proj = nn.Linear(heads * c.v_head_dim, h, bias_attr=False)

    def parameters_in_order(self):
        return [self.q_proj.weight, self.kv_a_proj.weight,
                self.kv_a_norm.weight, self.kv_b_proj.weight,
                self.q_norm.weight, self.k_norm.weight, self.g_proj.weight,
                self.o_proj.weight]


class Ling3MLP(nn.Layer):
    def __init__(self, c: Ling3Config):
        super().__init__()
        self.gate_proj = nn.Linear(c.hidden_size, c.intermediate_size,
                                   bias_attr=False)
        self.up_proj = nn.Linear(c.hidden_size, c.intermediate_size,
                                 bias_attr=False)
        self.down_proj = nn.Linear(c.intermediate_size, c.hidden_size,
                                   bias_attr=False)


class Ling3Mixture(nn.Layer):
    def __init__(self, c: Ling3Config):
        super().__init__()
        self.experts = dropless_experts(
            c, gated=True, score="sigmoid",
            gate_scale=c.routed_scaling_factor, n_group=c.n_group,
            topk_group=c.topk_group)
        width = c.moe_shared_expert_intermediate_size
        self.shared_gate = nn.Linear(c.hidden_size, width, bias_attr=False)
        self.shared_up = nn.Linear(c.hidden_size, width, bias_attr=False)
        self.shared_down = nn.Linear(width, c.hidden_size, bias_attr=False)


class Ling3Layer(nn.Layer):
    """Layer `index`: its mixer by the rule of the index, then its ffn."""

    def __init__(self, c: Ling3Config, index: int):
        super().__init__()
        self.config, self.kind = c, c.mixers[index]
        self.dense = index < c.first_k_dense_replace
        self.input_norm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.mixer = (KDAMixer if self.kind == KDA else MLAMixer)(c)
        self.post_norm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.ffn = (Ling3MLP if self.dense else Ling3Mixture)(c)

    def _kda(self, x):
        c = self.config

        def run(x, ln, wq, wk, wv, cq, ck, cv, wf, a_log, dt_bias, wb, wg,
                gn, wo):
            from ..ops.pallas.kda import kda_flat
            from ..ops.pallas.kda_rows import kda_gated_norm, kda_inputs

            # flat [b, s, heads d] rows from the convolutions to `kda/out`:
            # what works on one head's channels runs on column blocks of
            # them (ops/pallas/kda_rows.py)
            with jax.named_scope("kda/project"):
                h = rms(x, ln, c.rms_norm_eps)
                q, k, v, f = h @ wq, h @ wk, h @ wv, _product_f32(h, wf)
                beta, gate = h @ wb, h @ wg
            with jax.named_scope("kda/conv"):
                q, k, v = (causal_conv(q, cq), causal_conv(k, ck),
                           causal_conv(v, cv))
            with jax.named_scope("kda/gate"):
                q, k, kb, vb, a = kda_inputs(
                    q, k, v, f, beta, a_log, dt_bias,
                    lower_bound=c.kda_lower_bound)
            with jax.named_scope("kda/scan"):
                o = kda_flat(q, k, kb, vb, a, c.num_attention_heads,
                             chunk=c.kda_chunk_size)
            with jax.named_scope("kda/gate_norm"):
                o = kda_gated_norm(o, gate, gn, eps=c.rms_norm_eps)
            with jax.named_scope("kda/out"):
                return x + o @ wo

        return nary(run, [x, self.input_norm.weight]
                    + self.mixer.parameters_in_order(), "kda_mixer")

    def _mla(self, x):
        c = self.config

        def run(x, ln, wq, wkva, gc, wkvb, gq, gk, wg, wo):
            from ..ops.pallas.splash_attention import splash_attention

            b, s, _ = x.shape
            heads, nope, rope, rank = (c.num_attention_heads,
                                       c.qk_nope_head_dim,
                                       c.qk_rope_head_dim, c.kv_lora_rank)
            eps = c.rms_norm_eps
            with jax.named_scope("mla/project"):
                h = rms(x, ln, eps)
                q = (h @ wq).reshape(b, s, heads, nope + rope)
                kva = h @ wkva
                kvb = (rms(kva[..., :rank], gc, eps) @ wkvb).reshape(
                    b, s, heads, nope + c.v_head_dim)
                k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
                    kva[:, :, None, rank:], (b, s, heads, rope))], -1)
                v = kvb[..., nope:]
                q, k = rms(q, gq, eps), rms(k, gk, eps)
                q = jnp.concatenate([q[..., :nope], _turn(q[..., nope:],
                                                          c.rope_theta)], -1)
                k = jnp.concatenate([k[..., :nope], _turn(k[..., nope:],
                                                          c.rope_theta)], -1)
                gate = jax.nn.sigmoid((h @ wg).astype(F32))
            with jax.named_scope("mla_attention"):
                o = splash_attention(q, k, v, causal=True,
                                     scale=(nope + rope) ** -0.5)
            with jax.named_scope("mla/project"):
                o = (o.astype(F32) * gate[..., None]).astype(x.dtype)
                return x + o.reshape(b, s, -1) @ wo

        return nary(run, [x, self.input_norm.weight]
                    + self.mixer.parameters_in_order(), "mla_mixer")

    def _dense(self, x):
        c, m = self.config, self.ffn

        def run(x, ln, gate, up, down):
            with jax.named_scope("mlp"):
                return x + swiglu(rms(x, ln, c.rms_norm_eps), gate, up,
                                   down)

        return nary(run, [x, self.post_norm.weight, m.gate_proj.weight,
                          m.up_proj.weight, m.down_proj.weight], "ling3_mlp")

    def _mixture(self, x):
        m = self.ffn
        return mixture(x, self.post_norm, m.experts, swiglu,
                       [m.shared_gate.weight, m.shared_up.weight,
                        m.shared_down.weight])

    def _whole(self, x):
        x = (self._kda if self.kind == KDA else self._mla)(x)
        return self._dense(x) if self.dense else self._mixture(x)

    def forward(self, x):
        """-> x for a dense layer; for a mixture layer (x, balance term, the
        mixture's stats float32 [3], the experts picked int32 [b * s, k])
        (`dropless_moe`)."""
        # one segment a layer. A KDA mixer a sequence at a time (as
        # nemotron_h.py's Mamba layers run) holds no less here, 5.78
        # against 5.83 GiB of temporaries compiled for a described v5e,
        # and its twelve more segments compile a third longer
        return recomputed(self, self._whole, x)


class Ling3Model(DecoderStack):
    def __init__(self, c: Ling3Config):
        layers = range(c.num_hidden_layers)
        super().__init__(c, c.rms_norm_eps,
                         (Ling3Layer(c, i) for i in layers),
                         mixes=[i >= c.first_k_dense_replace for i in layers],
                         special=state_space_leaves(
                             "_conv", c.short_conv_kernel_size, 1e-3, 1e-1))


class Ling3ForCausalLM(MixtureCausalLM):
    """The language model with its untied head [vocab, hidden], its
    counters and picks a mixture layer (`decoder_parts.MixtureCausalLM`),
    which also counts the tokens with a pick in the held experts' group:
    `loss(ids, labels)` is the module docstring's training loss."""

    def __init__(self, config: Ling3Config):
        super().__init__(config, Ling3Model(config), counters=4)

    def counter_row(self, stats, picks):
        """The mixture's three counters, then the tokens with at least one
        pick in the group of experts that the held ones lie in."""
        c, row = self.config, super().counter_row(stats, picks)
        size = c.num_experts // c.n_group
        mine = (c.held_experts or (0, c.num_experts))[0] // size
        hits = jnp.sum(jnp.any(picks._data // size == mine, axis=-1),
                       dtype=jnp.int32)
        return jnp.concatenate([row, hits[None]])

    def routing_counters(self) -> dict:
        """The mixture's totals over the mixture layers of the last step
        and `group_hit_tokens`: tokens a layer with at least one pick in
        the held experts' group, summed over the layers (a kept group
        nearly always holds a pick: half the tokens at 4 groups of 8)."""
        hits = np.asarray(self.routing._data, np.int64)[:, 3].sum()
        return dict(super().routing_counters(), group_hit_tokens=int(hits))
