"""What the decoders with dropless mixtures of experts share, each part
defined once: the program-side half of `benchmark/ADDING_A_BLOCK.md`.

A new model's file holds what is that model's alone: its config dataclass,
its mixers (`nary` operations under `jax.named_scope` paths of
`paddle_tpu.profiler.DEVICE_SCOPES`), its layer, whose `forward(x,
*positions)` returns x or, for a mixture layer, what `mixture` returns
(segments through `recomputed`), a `DecoderStack` that says which layers
those are and how the weights are drawn, and a `MixtureCausalLM` that is
handed that stack and overrides `counter_row` / `keep_picks` /
`routing_counters` where a step counts more than the mixture's three
counters. The rest it takes from here. A difference between models is an
argument passed or a method overridden: nothing here asks which model is
calling, and this module imports no model's file.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn, ops
from ..framework.autograd import op_scope
from ..framework.random import host_normal, host_rng
from ..framework.tensor import Tensor
from ..incubate.distributed.models.moe.dropless import DroplessMoE
from ..nn.initializer import get_global_initializer
from ..ops import sparse_attention as sa
from ..ops._dispatch import nary
from .gpt import fused_lm_loss
from .llama import LlamaRMSNorm

F32 = jnp.float32


def rms(x, w, eps):
    x32 = x.astype(F32)
    out = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                              + eps)
    return (out * w.astype(F32)).astype(x.dtype)


def queries_keys(c, h, wq, wk, qn, kn, cos, sin):
    """Grouped-query attention's normed, rotated q [b,s,heads,d] and
    k [b,s,kv,d] of h [b,s,hidden] (the widths are `c`'s)."""
    b, s, _ = h.shape
    q = (h @ wq).reshape(b, s, c.num_attention_heads, c.head_dim)
    k = (h @ wk).reshape(b, s, c.num_key_value_heads, c.head_dim)
    return (sa.apply_rotary(rms(q, qn, c.rms_norm_eps), cos, sin),
            sa.apply_rotary(rms(k, kn, c.rms_norm_eps), cos, sin))


def causal_conv(x, w, b=None):
    """Depthwise causal convolution over the sequence and silu: x [b, s, c],
    w [taps, c] (tap `taps - 1` weighs the step itself), b [c] or None."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    bias = None if b is None else b.astype(F32)
    out = sum(padded[:, k:k + s].astype(F32) * w[k].astype(F32)
              for k in range(taps))
    return jax.nn.silu(out if b is None else bias + out).astype(x.dtype)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


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


class GQAProjections(nn.Layer):
    """Grouped-query attention's four products at `c`'s widths, no biases;
    with `qk_norm_eps` an RMSNorm gain over each q and each k head."""

    def __init__(self, c, qk_norm_eps=None):
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
        if qk_norm_eps is not None:
            self.q_norm = LlamaRMSNorm(d, qk_norm_eps)
            self.k_norm = LlamaRMSNorm(d, qk_norm_eps)


def dropless_experts(c, **kw):
    """The dropless layer at `c`'s widths, holding `c.held_experts`."""
    return DroplessMoE(
        c.hidden_size, c.moe_intermediate_size, c.num_experts,
        c.num_experts_per_tok, held_experts=c.held_experts,
        renormalise=c.norm_topk_prob, balance_coef=c.router_aux_loss_coef,
        tile_rows=c.moe_tile_rows, **kw)


def mixture(x, norm, experts, shared=None, weights=()):
    """x + experts(norm(x)) -> (x, balance term, stats, picks), as the
    dropless layer `experts` returns the last three. With `shared`, the
    shared expert `shared(h, *weights)` (jnp level: its activation is the
    caller's) is added beside the routed ones, counted once."""
    with op_scope("moe/norm"):
        h = norm(x)
    y, balance, stats, picks = experts(h)
    if shared is not None:
        def run(h, *weights):
            with jax.named_scope("moe/shared"):
                return shared(h, *weights)

        y_shared = nary(run, [h, *weights], "shared_expert")
    with op_scope("moe/residual"):
        x = x + y
        return (x if shared is None else x + y_shared), balance, stats, picks


def recomputes(layer):
    """Whether `layer`'s segments are recomputed in the backward pass."""
    return layer.config.use_recompute and layer.training


def recomputed(layer, segment, *args):
    """segment(*args), one `fleet.recompute` segment where `layer`'s config
    asks for it and the layer trains. `segment` is a bound method of the
    layer: its parameters are found through it."""
    if recomputes(layer):
        from ..distributed.fleet import recompute

        return recompute(segment, *args)
    return segment(*args)


def state_space_leaves(conv, taps, dt_low, dt_high, dt_floor=0.0):
    """`init_weights`' rules for a state-space mixer's leaves, as `mamba_ssm`
    and flash-linear-attention draw them: the depthwise convolutions (names
    ending in `conv`) uniform(+-taps^-1/2), A_log the logarithm of
    uniform(1, 16), dt_bias the inverse softplus of a step size log-uniform
    in [dt_low, dt_high] and not below `dt_floor`."""
    bound = taps ** -0.5

    def dt_bias(rng, shape):
        dt = np.maximum(np.exp(rng.uniform(
            math.log(dt_low), math.log(dt_high), shape)), dt_floor)
        return dt + np.log(-np.expm1(-dt))

    return {conv: lambda rng, shape: rng.uniform(-bound, bound, shape),
            "A_log": lambda rng, shape: np.log(rng.uniform(1, 16, shape)),
            "dt_bias": dt_bias}


def init_weights(model, std, scaled=(), factor=1.0, special=None):
    """Draw `model`'s leaves from the host generator in the order of
    `named_parameters()`: a leaf whose name ends in a key of `special`
    ({suffix or tuple of suffixes: draw(rng, shape)}) by that rule, every
    other matrix normal(0, std), divided by `factor` where its name ends
    in one of `scaled`; the remaining vectors stay as they were built. A
    caller's global initializer overrides all of it."""
    if get_global_initializer() is not None:
        return
    special = special or {}
    # the special leaves' generator is drawn (one host seed) only for a
    # model that has such leaves
    rng = (host_rng() or np.random.default_rng(0)) if special else None
    for name, p in model.named_parameters():
        shape = tuple(p._data.shape)
        draw = next((d for suffix, d in special.items()
                     if name.endswith(suffix)), None)
        if draw is not None:
            p._data = jnp.asarray(draw(rng, shape), F32)
        elif p.ndim >= 2:
            p._data = host_normal(shape, std)
            if name.endswith(scaled):
                p._data = p._data / factor


class DecoderStack(nn.Layer):
    """embed -> layers -> final norm, then its seeded draw
    (`init_weights(**init)`). `layers` is an iterable of the model's
    layers, built after the embedding (so leave it lazy: a generator);
    `mixes` says of each whether it returns a mixture layer's tuple (x,
    then `terms` more) or x alone."""

    def __init__(self, config, eps, layers, mixes, terms=3, **init):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(list(layers))
        self.norm = LlamaRMSNorm(config.hidden_size, eps)
        self.mixes, self.terms = tuple(mixes), terms
        init_weights(self, config.initializer_range, **init)

    def forward(self, input_ids, *positions):
        """-> (hidden [b, s, h], then `terms` lists with an entry a mixture
        layer: what those layers returned after x, by position)."""
        with op_scope("embed"):
            x = self.embed_tokens(input_ids)
        gathered = []
        for layer, mixes in zip(self.layers, self.mixes):
            if mixes:
                x, *rest = layer(x, *positions)
                gathered.append(rest)
            else:
                x = layer(x, *positions)
        with op_scope("head"):
            return (self.norm(x), *([g[j] for g in gathered]
                                    for j in range(self.terms)))


def _layer_mean(terms, like):
    if not terms:
        return like * 0.0
    return sum(terms[1:], terms[0]) / float(len(terms))


class MixtureCausalLM(nn.Layer):
    """The language model `model` (a `DecoderStack`) with its head [vocab,
    hidden]: a leaf of its own, `lm_head`, or with `tied` the embedding
    itself (no `lm_head` leaf: the embedding's gradient is then the
    gather's scatter plus the cross entropy's dW).

    `loss(ids, labels, *positions)` is the training loss;
    `routing_counters()` reads what the last step's routing counted, from
    the buffer `routing` int32 [mixture layers, `counters`] (a row:
    pairs routed to held experts, rows computed, the fullest held expert's
    pairs, then the model's own); after `record_picks(batch, seq)` the
    steps also keep WHICH experts they picked (`picks()`)."""

    def __init__(self, config, model, counters=3, tied=False):
        super().__init__()
        self.config = config
        self.model = model
        self.tied = tied
        if not tied:
            self.lm_head = self.create_parameter(
                [config.vocab_size, config.hidden_size])
            if get_global_initializer() is None:
                self.lm_head._data = host_normal(self.lm_head._data.shape,
                                                 config.initializer_range)
        self.mixtures = sum(model.mixes)
        self.register_buffer("routing", Tensor._wrap(
            jnp.zeros((max(self.mixtures, 1), counters), jnp.int32)))

    def record_picks(self, batch, seq):
        """Keep every step's expert picks in one more buffer of the
        model, int32 [mixture layers, batch * seq, top_k]. Changes nothing
        of what a step computes."""
        self.register_buffer("expert_picks", Tensor._wrap(jnp.zeros(
            (self.mixtures, batch * seq, self.config.num_experts_per_tok),
            jnp.int32)))

    def picks(self):
        """-> experts int32 [mixture layers, batch * seq, top_k] of the
        last step."""
        return np.asarray(self.expert_picks._data)

    @property
    def head(self):
        """The head's weight [vocab, hidden]."""
        return self.model.embed_tokens.weight if self.tied else self.lm_head

    def forward(self, input_ids, *positions):
        return ops.matmul(self.model(input_ids, *positions)[0], self.head,
                          transpose_y=True)

    def counter_row(self, stats, picks):
        """A mixture layer's row of `routing`, int32 [`counters`]."""
        return stats._data.astype(jnp.int32)

    def keep_picks(self, picks):
        self.expert_picks._data = jnp.stack([e._data for e in picks])

    def loss_terms(self, input_ids, labels, *positions):
        """-> (language-model loss, then the mean over the mixture layers
        of each loss term they return: the balance term first)."""
        hidden, *terms, stats, picks = self.model(input_ids, *positions)
        with jax.named_scope("picks"):
            if stats:
                self.routing._data = jnp.stack(
                    [self.counter_row(s, e) for s, e in zip(stats, picks)])
            if "expert_picks" in self._buffers and picks:
                self.keep_picks(picks)
        with op_scope("head"):
            lm = fused_lm_loss(hidden, self.head, True, labels)
        return (lm, *(_layer_mean(t, lm) for t in terms))

    def loss(self, input_ids, labels, *positions):
        terms = self.loss_terms(input_ids, labels, *positions)
        return sum(terms[1:], terms[0])

    def routing_counters(self) -> dict:
        """Totals over the mixture layers of the last step: `routed_pairs`
        (token-expert pairs on held experts), `computed_rows` (rows the
        grouped product computed, padding included), `max_load_over_mean`
        (the fullest held expert of any layer over the mean load)."""
        return routing_totals(np.asarray(self.routing._data, np.int64),
                              self.config)
