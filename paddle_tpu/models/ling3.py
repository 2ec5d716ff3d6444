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
flash-linear-attention's KDA layer draws them.
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
from .keye_vl2 import _rms, routing_totals
from .llama import LlamaRMSNorm, _rope_tables, apply_rotary_pos_emb
from .nemotron_h import NemotronHForCausalLM, causal_conv

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
        self.experts = DroplessMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, held_experts=c.held_experts,
            renormalise=c.norm_topk_prob,
            balance_coef=c.router_aux_loss_coef, tile_rows=c.moe_tile_rows,
            gated=True, score="sigmoid", gate_scale=c.routed_scaling_factor,
            n_group=c.n_group, topk_group=c.topk_group)
        width = c.moe_shared_expert_intermediate_size
        self.shared_gate = nn.Linear(c.hidden_size, width, bias_attr=False)
        self.shared_up = nn.Linear(c.hidden_size, width, bias_attr=False)
        self.shared_down = nn.Linear(width, c.hidden_size, bias_attr=False)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


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
                h = _rms(x, ln, c.rms_norm_eps)
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
                h = _rms(x, ln, eps)
                q = (h @ wq).reshape(b, s, heads, nope + rope)
                kva = h @ wkva
                kvb = (_rms(kva[..., :rank], gc, eps) @ wkvb).reshape(
                    b, s, heads, nope + c.v_head_dim)
                k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
                    kva[:, :, None, rank:], (b, s, heads, rope))], -1)
                v = kvb[..., nope:]
                q, k = _rms(q, gq, eps), _rms(k, gk, eps)
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
                return x + _swiglu(_rms(x, ln, c.rms_norm_eps), gate, up,
                                   down)

        return nary(run, [x, self.post_norm.weight, m.gate_proj.weight,
                          m.up_proj.weight, m.down_proj.weight], "ling3_mlp")

    def _mixture(self, x):
        m = self.ffn
        with op_scope("moe/norm"):
            h = self.post_norm(x)
        y, balance, stats, picks = m.experts(h)

        def shared(h, gate, up, down):
            with jax.named_scope("moe/shared"):
                return _swiglu(h, gate, up, down)

        y_shared = nary(shared, [h, m.shared_gate.weight, m.shared_up.weight,
                                 m.shared_down.weight], "shared_expert")
        with op_scope("moe/residual"):
            return x + y + y_shared, balance, stats, picks

    def _whole(self, x):
        x = (self._kda if self.kind == KDA else self._mla)(x)
        return self._dense(x) if self.dense else self._mixture(x)

    def forward(self, x):
        """-> x for a dense layer; for a mixture layer (x, balance term, the
        mixture's stats float32 [3], the experts picked int32 [b * s, k])
        (`dropless_moe`)."""
        if self.config.use_recompute and self.training:
            from ..distributed.fleet import recompute

            # one segment a layer. A KDA mixer a sequence at a time (as
            # nemotron_h.py's Mamba layers run) holds no less here, 5.78
            # against 5.83 GiB of temporaries compiled for a described v5e,
            # and its twelve more segments compile a third longer
            return recompute(self._whole, x)
        return self._whole(x)


class Ling3Model(nn.Layer):
    def __init__(self, config: Ling3Config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([Ling3Layer(config, i) for i in range(
            config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        self._init_weights(config)

    def _init_weights(self, c):
        from ..framework.random import host_normal, host_rng
        from ..nn.initializer import get_global_initializer

        if get_global_initializer() is not None:
            return      # the caller's initializer overrides the model's own
        rng = host_rng() or np.random.default_rng(0)
        for name, p in self.named_parameters():
            shape = tuple(p._data.shape)
            if name.endswith("_conv"):
                bound = c.short_conv_kernel_size ** -0.5
                p._data = jnp.asarray(rng.uniform(-bound, bound, shape), F32)
            elif p.ndim >= 2:
                p._data = host_normal(shape, c.initializer_range)
            elif name.endswith("A_log"):
                p._data = jnp.asarray(np.log(rng.uniform(1, 16, shape)), F32)
            elif name.endswith("dt_bias"):
                dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1),
                                        shape))
                p._data = jnp.asarray(dt + np.log(-np.expm1(-dt)), F32)

    def forward(self, input_ids):
        """-> (hidden [b, s, h], per mixture layer: [balance terms],
        [stats], [picked experts])."""
        with op_scope("embed"):
            x = self.embed_tokens(input_ids)
        balance, stats, picks = [], [], []
        for layer in self.layers:
            if layer.dense:
                x = layer(x)
            else:
                x, bal, st, picked = layer(x)
                balance.append(bal)
                stats.append(st)
                picks.append(picked)
        with op_scope("head"):
            return self.norm(x), balance, stats, picks


class Ling3ForCausalLM(nn.Layer):
    """The language model with its untied head [vocab, hidden].

    `loss(ids, labels)` is the training loss (module docstring);
    `routing_counters()` reads what the last step's routing counted; after
    `record_picks(batch, seq)` the steps also keep WHICH experts they picked
    (`picks()`)."""

    def __init__(self, config: Ling3Config):
        super().__init__()
        from ..framework.random import host_normal
        from ..nn.initializer import get_global_initializer

        self.config = config
        self.model = Ling3Model(config)
        self.lm_head = self.create_parameter(
            [config.vocab_size, config.hidden_size])
        if get_global_initializer() is None:
            self.lm_head._data = host_normal(self.lm_head._data.shape,
                                             config.initializer_range)
        self.mixtures = sum(not layer.dense for layer in self.model.layers)
        # per mixture layer: pairs routed to held experts, rows computed,
        # the fullest held expert's pairs, tokens with a pick in the held
        # experts' group: the last step's
        self.register_buffer("routing", Tensor._wrap(
            jnp.zeros((max(self.mixtures, 1), 4), jnp.int32)))

    record_picks = NemotronHForCausalLM.record_picks
    picks = NemotronHForCausalLM.picks
    forward = NemotronHForCausalLM.forward
    loss = NemotronHForCausalLM.loss

    def _group_hits(self, picks):
        """Tokens with at least one pick in the group of experts that the
        held ones lie in, int32 [1]."""
        c = self.config
        size = c.num_experts // c.n_group
        mine = (c.held_experts or (0, c.num_experts))[0] // size
        return jnp.sum(jnp.any(picks // size == mine, axis=-1),
                       dtype=jnp.int32)[None]

    def loss_terms(self, input_ids, labels):
        """-> (language-model loss, mean balance term)."""
        from .gpt import fused_lm_loss

        hidden, balance, stats, picks = self.model(input_ids)
        with jax.named_scope("picks"):
            if stats:
                self.routing._data = jnp.stack([jnp.concatenate(
                    [s._data.astype(jnp.int32), self._group_hits(e._data)])
                    for s, e in zip(stats, picks)])
            if "expert_picks" in self._buffers and picks:
                self.expert_picks._data = jnp.stack(
                    [e._data for e in picks])
        with op_scope("head"):
            lm = fused_lm_loss(hidden, self.lm_head, True, labels)
        if not balance:
            return lm, lm * 0.0
        return lm, sum(balance[1:], balance[0]) / float(len(balance))

    def routing_counters(self) -> dict:
        """Totals over the mixture layers of the last step (`routed_pairs`,
        `computed_rows`, `max_load_over_mean`: keye_vl2 `routing_totals`)
        and `group_hit_tokens`: tokens a layer with at least one pick in
        the held experts' group, summed over the layers (a kept group
        nearly always holds a pick: half the tokens at 4 groups of 8)."""
        rows = np.asarray(self.routing._data, np.int64)
        return dict(routing_totals(rows, self.config),
                    group_hit_tokens=int(rows[:, 3].sum()))
