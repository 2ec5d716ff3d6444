"""GPT model family — the flagship pretraining model (BASELINE config 4:
GPT-3 1.3B, sharding stage 2/3 + recompute).

Reference parity: the GPT nets used by Paddle's Fleet examples
(python/paddle/incubate/ layers + nn/layer/transformer.py building blocks).
TPU-first: the model is plain dygraph Layers whose params carry stable names;
`sharding_rules()` maps those names to `jax.sharding.PartitionSpec`s so the
same model runs single-chip, tensor-parallel (Megatron layout over the "mp"
mesh axis), fully-sharded ("fsdp"/dp axis) or both — XLA GSPMD inserts the
collectives (SURVEY.md §5.8 north star).

Megatron TP layout (reference fleet/layers/mpu/mp_layers.py:47,334,541):
  - qkv / fc1: column-parallel — weight [in, out] sharded on out → "mp"
  - out-proj / fc2: row-parallel — weight sharded on in → "mp"
  - token embedding: vocab-parallel — sharded on vocab dim
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

from .. import nn
from ..nn import functional as F
from ..framework.tensor import Tensor
from ..ops import creation as C


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 0          # 0 → 4 * hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.0
    attention_dropout_prob: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    use_recompute: bool = False
    # remat granularity: None = full (reference semantics), "dots" = keep
    # linear/MLP dot outputs, recompute only attention (less recompute
    # FLOPs for a modest activation-memory cost)
    recompute_policy: str = None
    # long-context: route attention through the sep-axis ppermute ring
    # (meta_parallel/ring_attention.py) instead of GSPMD's k/v all-gather —
    # O(seq/n) activation memory per device on a sep mesh
    use_ring_attention: bool = False
    # compile-time lever: stack the identical decoder blocks on a leading
    # [num_layers] dim and run them as ONE lax.scan body instead of
    # num_layers inlined copies. XLA compiles one block instead of 24+ —
    # the standard big-model trick on TPU (the 1.3b whole-step compile
    # drops from ~17 min to minutes; see PERF.md). Same math; param names
    # become blocks__<template-name> with a stacked leading dim.
    scan_layers: bool = False
    # Mixture-of-experts FFN (ISSUE 9): num_experts > 0 swaps every
    # block's GPTMLP for an MoEBlock (top-k gated ExpertFFNs, GShard
    # capacity dropping). Expert stacks shard 1/ep over a dp×ep mesh in
    # ShardedFusedScanTrainStep (token dispatch via lax.all_to_all); the
    # load-balance aux loss (weight moe_aux_weight, mean over MoE
    # layers) is added to the training loss by `loss()` and by the scan
    # train steps.
    num_experts: int = 0
    moe_capacity_factor: float = 2.0
    moe_gate: str = "gshard"        # "gshard" (top-2) | "switch" (top-1)
    moe_aux_weight: float = 1e-2
    # self-speculative draft heads (ISSUE 20): k Medusa-style heads off
    # the final hidden state — head j predicts the token j+2 positions
    # ahead (the base LM head predicts position +1), sharing the LM
    # head projection. Serving proposes k tokens per dispatch from the
    # TARGET's own forward (draft_model="self"), so speculation needs
    # no second checkpoint and no draft KV pools. Heads train as an
    # auxiliary CE on shifted targets (weight below); zero-init makes
    # an untrained head start as the base head (identity residual).
    num_draft_heads: int = 0
    draft_head_loss_weight: float = 0.1

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size


# Named configs (sizes follow the GPT-3 paper table; 1.3B is the BASELINE
# north-star pretrain config).
GPT_CONFIGS = {
    "gpt3-125m": dict(hidden_size=768, num_layers=12, num_attention_heads=12),
    "gpt3-350m": dict(hidden_size=1024, num_layers=24, num_attention_heads=16),
    "gpt3-1.3b": dict(hidden_size=2048, num_layers=24, num_attention_heads=32),
    "gpt3-2.7b": dict(hidden_size=2560, num_layers=32, num_attention_heads=32),
    "gpt3-6.7b": dict(hidden_size=4096, num_layers=32, num_attention_heads=32),
    "gpt3-13b": dict(hidden_size=5120, num_layers=40, num_attention_heads=40),
}


def gpt_config(name: str, **overrides) -> GPTConfig:
    kw = dict(GPT_CONFIGS[name])
    kw.update(overrides)
    return GPTConfig(**kw)


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.head_dim = h // self.num_heads
        self.qkv = nn.Linear(h, 3 * h)
        self.out_proj = nn.Linear(h, h)
        self.dropout_p = config.attention_dropout_prob
        self._use_ring = config.use_ring_attention

    def _ring_mesh(self):
        if not self._use_ring:
            return None
        from ..distributed import env as denv

        if not denv.is_initialized():
            return None
        mesh = denv.get_mesh()
        if "sep" in mesh.axis_names and mesh.shape["sep"] > 1:
            return mesh
        return None

    def _ring_attention(self, q, k, v, mesh):
        from ..distributed.fleet.meta_parallel import ring_attention
        from ..framework.autograd import apply_op

        return apply_op(
            lambda qq, kk, vv: ring_attention(qq, kk, vv, mesh=mesh,
                                              causal=True),
            [q, k, v], name="ring_attention")

    def forward_prefill(self, x, cache, layer_idx, seq_lens=None,
                        slot_ids=None):
        """Prompt pass: causal self-attention (the flash/SDPA prefill
        path) + write this layer's K/V into the decode cache.

        x: [b, s, h] post-LN prompt hiddens (right-padded for ragged
        batches — padding K/V goes to the paged trash page; the dense
        cache overwrites its tail before any decode step can attend it).
        """
        from ..inference import kv_cache as _kv
        from ..ops._dispatch import nary

        b, s, h = x.shape
        qkv = self.qkv(x).reshape(
            [b, s, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=False)
        if cache.kind == "dense":
            cache.set_layer(layer_idx, nary(
                _kv.dense_write_prefill, [cache.layer(layer_idx), k, v],
                "dense_prefill_write"))
        elif getattr(cache, "quantized", False):
            q4 = cache.quant == "int4"
            new_k, new_v, new_ks, new_vs = nary(
                _kv.paged_write_prefill_q4 if q4
                else _kv.paged_write_prefill_q8,
                [cache.k_layers[layer_idx], cache.v_layers[layer_idx],
                 cache.k_scales[layer_idx], cache.v_scales[layer_idx],
                 cache.page_tables, slot_ids, seq_lens, k, v],
                "paged_prefill_write_q4" if q4
                else "paged_prefill_write_q8")
            cache.k_layers[layer_idx] = new_k
            cache.v_layers[layer_idx] = new_v
            cache.k_scales[layer_idx] = new_ks
            cache.v_scales[layer_idx] = new_vs
        else:
            new_k, new_v = nary(
                _kv.paged_write_prefill,
                [cache.k_layers[layer_idx], cache.v_layers[layer_idx],
                 cache.page_tables, slot_ids, seq_lens, k, v],
                "paged_prefill_write")
            cache.k_layers[layer_idx] = new_k
            cache.v_layers[layer_idx] = new_v
        return self.out_proj(out.reshape([b, s, h]))

    def forward_decode(self, x, cache, layer_idx):
        """One-token decode step over the cache.

        Dense: the real `incubate.nn.functional.masked_multihead_
        attention` — fused qkv in, ONE dynamic_update_slice cache
        append, masked attention over the cache. Paged: scatter the
        token into this layer's page pool and run the ragged paged
        attention kernel (ops/pallas/paged_attention.py — Pallas on
        TPU, XLA gather elsewhere).
        """
        import jax.numpy as jnp

        from ..inference import kv_cache as _kv
        from ..ops._dispatch import nary
        from ..ops.pallas.paged_attention import paged_attention

        b, _, h = x.shape
        if cache.kind == "dense":
            from ..incubate.nn import functional as IF

            qkv_flat = self.qkv(x).reshape([b, 3 * h])
            out, new_l = IF.masked_multihead_attention(
                qkv_flat, cache.layer(layer_idx),
                sequence_lengths=cache.pos)
            cache.set_layer(layer_idx, new_l)
            return self.out_proj(out.reshape([b, 1, h]))

        qkv = self.qkv(x).reshape(
            [b, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]      # [b, nh, hd]

        if getattr(cache, "quantized", False):
            q4 = cache.quant == "int4"
            wfn = (_kv.paged_write_decode_q4 if q4
                   else _kv.paged_write_decode_q8)

            def step_q(qq, kk, vv, kp, vp, ksc, vsc, pt, sl, act):
                kp2, vp2, ks2, vs2 = wfn(
                    kp, vp, ksc, vsc, pt, sl, act, kk, vv)
                lens = jnp.where(act, sl + 1, 0)
                o = paged_attention(qq, kp2, vp2, pt, lens,
                                    k_scales=ks2, v_scales=vs2)
                return o, kp2, vp2, ks2, vs2

            out, new_k, new_v, new_ks, new_vs = nary(
                step_q, [q, k, v, cache.k_layers[layer_idx],
                         cache.v_layers[layer_idx],
                         cache.k_scales[layer_idx],
                         cache.v_scales[layer_idx],
                         cache.page_tables, cache.seq_lens,
                         cache.active],
                "paged_decode_attention_q4" if q4
                else "paged_decode_attention_q8")
            cache.k_scales[layer_idx] = new_ks
            cache.v_scales[layer_idx] = new_vs
        else:
            def step(qq, kk, vv, kp, vp, pt, sl, act):
                kp2, vp2 = _kv.paged_write_decode(kp, vp, pt, sl, act,
                                                  kk, vv)
                lens = jnp.where(act, sl + 1, 0)
                o = paged_attention(qq, kp2, vp2, pt, lens)
                return o, kp2, vp2

            out, new_k, new_v = nary(
                step, [q, k, v, cache.k_layers[layer_idx],
                       cache.v_layers[layer_idx], cache.page_tables,
                       cache.seq_lens, cache.active],
                "paged_decode_attention")
        cache.k_layers[layer_idx] = new_k
        cache.v_layers[layer_idx] = new_v
        return self.out_proj(out.reshape([b, 1, h]))

    def forward_prefill_chunk(self, x, cache, layer_idx, slot_ids,
                              start, seq_lens_new):
        """One bounded multi-token window per slot: write the window's
        K/V at logical positions [start, start+c) of each slot, then
        attend the window's queries over the slot's FULL cached context
        so far (earlier tokens + this window, causal within it).

        Two callers share this shape (ISSUE 16): the serving tier's
        chunked prompt prefill, and the spec-decode VERIFY pass (c =
        k+1 draft positions scored in one dispatch — the multi-token
        ragged attention lives in ops/pallas/paged_attention.py as
        `paged_attention_chunk`, Pallas kernel on TPU / XLA gather
        elsewhere).

        x: [b, c, h] window hiddens (right-padded to the bucket);
        start/seq_lens_new: [b] int32 — window offset and the total
        cached length after this window; positions past seq_lens_new
        land on the trash page (paged) or are dropped (dense) and their
        queries' outputs are discarded by the caller. The context
        gather is static-shape so every window in a bucket shares one
        compiled program.
        """
        import jax
        import jax.numpy as jnp

        from ..inference import kv_cache as _kv
        from ..ops._dispatch import nary
        from ..ops.pallas.paged_attention import paged_attention_chunk

        b, c, h = x.shape
        nh, hd = self.num_heads, self.head_dim
        qkv = self.qkv(x).reshape([b, c, 3, nh, hd])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

        if cache.kind == "dense":
            # dense verify path: ragged multi-token scatter + masked
            # attention over the aligned cache
            def dstep(qq, kk, vv, cl, st, ln):
                cl2 = _kv.dense_write_chunk(cl, st, ln, kk, vv)
                ctx_k, ctx_v = cl2[0], cl2[1]    # [b, nh, max_len, d]
                L = ctx_k.shape[2]
                s = jnp.einsum("bcnd,bnld->bncl",
                               qq.astype(jnp.float32),
                               ctx_k.astype(jnp.float32)) / (hd ** 0.5)
                jpos = jnp.arange(L, dtype=jnp.int32)
                ipos = st[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
                mask = jpos[None, None, :] <= ipos[:, :, None]
                s = jnp.where(mask[:, None], s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("bncl,bnld->bncd", p,
                               ctx_v.astype(jnp.float32))
                return jnp.moveaxis(o, 1, 2).astype(qq.dtype), cl2

            out, new_l = nary(
                dstep, [q, k, v, cache.layer(layer_idx), start,
                        seq_lens_new],
                "dense_prefill_chunk")
            cache.set_layer(layer_idx, new_l)
        elif getattr(cache, "quantized", False):
            q4 = cache.quant == "int4"
            wfn = (_kv.paged_write_prefill_q4 if q4
                   else _kv.paged_write_prefill_q8)

            def qstep(qq, kk, vv, kp, vp, ksc, vsc, pt, sid, st, ln):
                kp2, vp2, ks2, vs2 = wfn(
                    kp, vp, ksc, vsc, pt, sid, ln, kk, vv, start=st)
                o = paged_attention_chunk(qq, kp2, vp2, pt[sid], st,
                                          k_scales=ks2, v_scales=vs2)
                return o, kp2, vp2, ks2, vs2

            out, new_k, new_v, new_ks, new_vs = nary(
                qstep, [q, k, v, cache.k_layers[layer_idx],
                        cache.v_layers[layer_idx],
                        cache.k_scales[layer_idx],
                        cache.v_scales[layer_idx], cache.page_tables,
                        slot_ids, start, seq_lens_new],
                "paged_prefill_chunk_q4" if q4
                else "paged_prefill_chunk_q8")
            cache.k_layers[layer_idx] = new_k
            cache.v_layers[layer_idx] = new_v
            cache.k_scales[layer_idx] = new_ks
            cache.v_scales[layer_idx] = new_vs
        else:
            def step(qq, kk, vv, kp, vp, pt, sid, st, ln):
                kp2, vp2 = _kv.paged_write_prefill(kp, vp, pt, sid, ln,
                                                   kk, vv, start=st)
                o = paged_attention_chunk(qq, kp2, vp2, pt[sid], st)
                return o, kp2, vp2

            out, new_k, new_v = nary(
                step, [q, k, v, cache.k_layers[layer_idx],
                       cache.v_layers[layer_idx], cache.page_tables,
                       slot_ids, start, seq_lens_new],
                "paged_prefill_chunk")
            cache.k_layers[layer_idx] = new_k
            cache.v_layers[layer_idx] = new_v
        return self.out_proj(out.reshape([b, c, h]))

    def forward(self, x):
        b, s, h = x.shape
        qkv = self.qkv(x)                              # [b, s, 3h]
        qkv = qkv.reshape([b, s, 3, self.num_heads, self.head_dim])
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]                               # [b, s, nh, hd]
        ring_mesh = self._ring_mesh()
        # packed-sequence segment ids published by GPTModel.forward
        # (attention_segments context): each document attends only
        # itself — routed through the splash kernel / its XLA fallback
        seg = F.current_segment_ids()
        # ring requirements: seq divisible by the ring, no attention
        # dropout (the ring kernel has no dropout plumbing), and no
        # segment mask — otherwise fall back to the dense path rather
        # than diverge or crash
        drop_active = self.dropout_p > 0.0 and self.training
        if (ring_mesh is not None and not drop_active and seg is None
                and s % int(ring_mesh.shape["sep"]) == 0):
            out = self._ring_attention(q, k, v, ring_mesh)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.dropout_p,
                training=self.training, segment_ids=seg,
            )                                           # [b, s, nh, hd]
        # num_heads * head_dim, NOT h: under tensor parallelism the
        # sharded step binds this layer with a head-sliced qkv (local
        # num_heads = nh/mp), so the attention output is narrower than
        # the residual-stream hidden size
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.fc1 = nn.Linear(config.hidden_size, config.intermediate_size)
        self.fc2 = nn.Linear(config.intermediate_size, config.hidden_size)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class MoEBlock(nn.Layer):
    """MoE variant of the GPT FFN (ISSUE 9): a `MoELayer` over
    num_experts `ExpertFFN`s in the GPTMLP geometry. Slots into GPTBlock
    wherever GPTMLP does; after forward, ``l_aux`` holds the layer's
    load-balance loss (collected by `GPTModel`/the scan train steps)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        from ..incubate.distributed.models.moe import ExpertFFN, MoELayer

        self.moe = MoELayer(
            config.hidden_size,
            [ExpertFFN(config.hidden_size, config.intermediate_size)
             for _ in range(config.num_experts)],
            gate=config.moe_gate,
            capacity_factor=config.moe_capacity_factor)

    @property
    def l_aux(self):
        return self.moe.l_aux

    def forward(self, x):
        return self.moe(x)


class GPTBlock(nn.Layer):
    """Pre-LN transformer decoder block."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.mlp = (MoEBlock(config) if config.num_experts
                    else GPTMLP(config))
        self.dropout = nn.Dropout(config.hidden_dropout_prob)
        self._use_recompute = config.use_recompute
        self._recompute_policy = config.recompute_policy

    def _inner(self, x):
        x = x + self.dropout(self.attn(self.ln_1(x)))
        x = x + self.dropout(self.mlp(self.ln_2(x)))
        return x

    def forward(self, x):
        if self._use_recompute and self.training:
            from ..distributed.fleet import recompute

            return recompute(self._inner, x,
                             policy=self._recompute_policy)
        return self._inner(x)

    # -- decode-engine paths (inference: no dropout, cache-backed attn) --
    def forward_prefill(self, x, cache, layer_idx, seq_lens=None,
                        slot_ids=None):
        x = x + self.attn.forward_prefill(self.ln_1(x), cache, layer_idx,
                                          seq_lens=seq_lens,
                                          slot_ids=slot_ids)
        return x + self.mlp(self.ln_2(x))

    def forward_decode(self, x, cache, layer_idx):
        x = x + self.attn.forward_decode(self.ln_1(x), cache, layer_idx)
        return x + self.mlp(self.ln_2(x))

    def forward_prefill_chunk(self, x, cache, layer_idx, slot_ids,
                              start, seq_lens_new):
        x = x + self.attn.forward_prefill_chunk(
            self.ln_1(x), cache, layer_idx, slot_ids, start,
            seq_lens_new)
        return x + self.mlp(self.ln_2(x))


class GPTStackedBlocks(nn.Layer):
    """The decoder stack as ONE scanned block over [num_layers]-stacked
    parameters (see GPTConfig.scan_layers). Mirrors the stage-stacking of
    models/gpt_pipe.py (which scans within a pipeline stage); this is the
    single-chip/whole-model variant."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        n = config.num_layers
        object.__setattr__(self, "_template", GPTBlock(config))
        self._stacked_names = []
        from ..framework.random import host_normal
        import jax.numpy as jnp

        std = config.initializer_range
        for pname, p in self._template.named_parameters():
            shape = (n,) + tuple(p.shape)
            # name-gated, not ndim-gated: MoE expert biases are stacked
            # to [E, dim] (ndim 2) but must keep their zero init like
            # the dense twin's 1-D biases
            if p.ndim >= 2 and not pname.endswith("bias"):
                data = host_normal(shape, std)
                # residual-scaled init for the projections feeding the
                # residual stream — incl. the MoE experts' second linear
                # (stacked under the flat experts__fc2__weight name)
                if re.search(r"(out_proj\.weight|fc2\.weight"
                             r"|__fc2__weight)$", pname):
                    data = data / (2.0 * n) ** 0.5
            else:
                data = jnp.broadcast_to(p._data, shape)
            flat = "blocks__" + pname.replace(".", "__")
            from ..nn.layer.layers import Parameter

            param = Parameter(jnp.asarray(data))
            param.layer_stacked = True   # optimizer chunks the update
            self.add_parameter(flat, param)
            self._stacked_names.append((flat, pname))

    # PRNG draws reserved per scanned layer (2 hidden dropouts +
    # attention dropout + slack): the scan body traces ONCE, so without
    # a per-layer generator offset every layer would share one dropout
    # mask. Binding offset = base + layer_index * _RNG_SLOTS inside the
    # body gives each layer its own key stream — deterministic under
    # paddle.seed, replayed identically by jax.checkpoint's recompute.
    _RNG_SLOTS = 8

    def forward(self, x):
        import jax

        from ..framework.autograd import apply_op, no_grad
        from ..framework.tensor import Tensor
        from ..framework import random as _random

        template = self._template
        leaves = [p for _, p in template.named_parameters()]
        training = self.training
        # the template is attached via object.__setattr__ (not a
        # registered sublayer), so model.train()/eval() never reach its
        # children — propagate the mode explicitly or the template's
        # Dropout layers would stay training=True in eval forever
        template.train() if training else template.eval()
        cfg = self.config
        n = cfg.num_layers
        drop_active = training and (cfg.hidden_dropout_prob
                                    or cfg.attention_dropout_prob)
        gen = _random.default_generator()
        base_off = None
        if drop_active:
            base_off = gen._offset
            if isinstance(base_off, jax.Array) and not isinstance(
                    base_off, jax.core.Tracer):
                base_off = int(base_off)

        moe = isinstance(getattr(template, "mlp", None), MoEBlock)

        def one_layer(h, scanned):
            idx, layer_leaves = scanned[0], scanned[1:]
            with no_grad():
                saved = [p._data for p in leaves]
                saved_off = gen._offset
                if base_off is not None:
                    gen._offset = base_off + idx * self._RNG_SLOTS
                for p, d in zip(leaves, layer_leaves):
                    p._data = d
                template.training = training
                try:
                    y = template._inner(Tensor._wrap(h))._data
                    aux = template.mlp.l_aux._data if moe else None
                finally:
                    gen._offset = saved_off
                    for p, d in zip(leaves, saved):
                        p._data = d
            return y, aux

        if cfg.use_recompute and training:
            policy = (jax.checkpoint_policies
                      .dots_with_no_batch_dims_saveable
                      if cfg.recompute_policy == "dots" else None)
            one_layer = (jax.checkpoint(one_layer, policy=policy)
                         if policy is not None
                         else jax.checkpoint(one_layer))

        stacked = [self._parameters[flat] for flat, _ in
                   self._stacked_names]

        if moe:
            def scanfn(h, *stk):
                out, auxs = jax.lax.scan(
                    one_layer, h, (jax.numpy.arange(n),) + tuple(stk))
                # per-layer MoE aux losses escape the scan as ys — mean
                # over layers is the model-level aux loss loss() consumes
                return out, jax.numpy.sum(auxs) / n

            out, aux = apply_op(scanfn, [x] + stacked,
                                name="gpt_scan_blocks")
            self.last_moe_aux = aux
        else:
            def scanfn(h, *stk):
                out, _ = jax.lax.scan(
                    one_layer, h, (jax.numpy.arange(n),) + tuple(stk))
                return out

            out = apply_op(scanfn, [x] + stacked, name="gpt_scan_blocks")
            self.last_moe_aux = None
        if base_off is not None:
            # reserve the layers' draw window so later eager consumers
            # (and the next forward) don't collide with in-scan keys
            gen._offset = base_off + n * self._RNG_SLOTS
        return out


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size)
        self.drop = nn.Dropout(config.hidden_dropout_prob)
        if config.scan_layers:
            self.blocks = GPTStackedBlocks(config)
        else:
            self.blocks = nn.LayerList([GPTBlock(config)
                                        for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self._init_weights(config)

    def _init_weights(self, config):
        import jax

        from ..framework.random import host_normal
        import jax.numpy as jnp

        std = config.initializer_range
        for name, p in self.named_parameters():
            if "blocks__" in name:
                continue  # stacked scan params init in GPTStackedBlocks
            # bias params keep zeros even when expert-stacked to ndim 2
            if p.ndim >= 2 and not name.endswith("bias"):
                p._data = host_normal(p._data.shape, std)
                if re.search(r"(out_proj\.weight|fc2\.weight"
                             r"|__fc2__weight)$", name):
                    # GPT-2 residual-scaled init (incl. MoE expert fc2
                    # stacks)
                    p._data = p._data / math.sqrt(2.0 * config.num_layers)

    def forward(self, input_ids, position_ids=None, segment_ids=None):
        """`segment_ids` ([b, s] int) marks packed-sequence document
        boundaries: published to every attention layer for this forward
        (attention_segments context), so tokens attend only within
        their own document. None = plain causal attention."""
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = C.arange(0, s, dtype="int64").unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(position_ids)
        x = self.drop(x)
        with F.attention_segments(segment_ids):
            if self.config.scan_layers:
                x = self.blocks(x)
            else:
                for block in self.blocks:
                    x = block(x)
        return self.ln_f(x)

    def moe_aux(self):
        """Mean per-layer MoE load-balance loss of the last forward
        (None for dense models) — what `GPTForCausalLM.loss` weights by
        ``moe_aux_weight`` and adds to the CE loss."""
        if not self.config.num_experts:
            return None
        if self.config.scan_layers:
            return self.blocks.last_moe_aux
        auxs = [b.mlp.l_aux for b in self.blocks]
        total = auxs[0]
        for a in auxs[1:]:
            total = total + a
        return total / len(auxs)

    def _check_decodable(self):
        if self.config.scan_layers:
            raise NotImplementedError(
                "generate()/decode over scan_layers=True models is not "
                "plumbed (the stacked-param scan body has no per-layer "
                "cache slot yet); build the model with "
                "scan_layers=False for serving")

    def prefill(self, input_ids, cache, seq_lens=None, slot_ids=None):
        """Prompt pass writing every layer's K/V into `cache`.

        input_ids: [b, s] (right-padded to the engine's length bucket);
        seq_lens: true prompt lengths — a 0-d/py int for the aligned
        dense cache, [b] for the ragged paged cache. Returns the full
        [b, s, hidden] hiddens (caller gathers the last valid position).
        """
        self._check_decodable()
        b, s = input_ids.shape
        position_ids = C.arange(0, s, dtype="int64").unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(position_ids)
        for l, block in enumerate(self.blocks):
            x = block.forward_prefill(x, cache, l, seq_lens=seq_lens,
                                      slot_ids=slot_ids)
        return self.ln_f(x)

    def decode_step(self, tokens, cache, position_ids):
        """One cached decode step: tokens [b, 1] -> hiddens [b, 1, h].
        The caller owns advancing cache.pos / cache.seq_lens."""
        self._check_decodable()
        x = self.wte(tokens) + self.wpe(position_ids)
        for l, block in enumerate(self.blocks):
            x = block.forward_decode(x, cache, l)
        return self.ln_f(x)

    def prefill_chunk(self, input_ids, cache, slot_ids, start,
                      seq_lens_new):
        """Multi-token cached pass: process one bounded window of each
        slot's tokens at logical positions [start, start+c), attending
        over the context cached so far. Serves both chunked prompt
        prefill (serving tier) and the spec-decode verify pass (c =
        k+1 draft positions, ISSUE 16); works over paged AND dense
        caches (slot_ids is ignored for dense).

        input_ids: [b, c] window tokens right-padded to the bucket;
        start/seq_lens_new: [b] int32 Tensors. Returns the window
        hiddens [b, c, hidden] (caller gathers the last valid
        position). The caller owns advancing cache.seq_lens to
        seq_lens_new."""
        self._check_decodable()
        b, c = input_ids.shape
        pos = start.unsqueeze(1) + C.arange(0, c, dtype="int32") \
            .unsqueeze(0)
        # padded tail positions of the last chunk can poke past the
        # position table — clamp them (their outputs are discarded)
        pos = pos.clip(0, self.config.max_position_embeddings - 1)
        x = self.wte(input_ids) + self.wpe(pos.astype("int64"))
        for l, block in enumerate(self.blocks):
            x = block.forward_prefill_chunk(x, cache, l, slot_ids,
                                            start, seq_lens_new)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    """GPT + LM head; forward returns logits, `loss()` the CE training loss."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)
        if config.num_draft_heads:
            import jax.numpy as jnp

            # one residual block per head: logits_j = LM(h + silu(W_j h))
            # — hidden^2 params each, logits through the SHARED LM head.
            # Zero-init so an untrained head IS the base head: the
            # residual vanishes and proposals start sane, the aux-CE
            # gradient is nonzero (silu'(0) = 1/2) so training moves it.
            self.draft_heads = nn.LayerList([
                nn.Linear(config.hidden_size, config.hidden_size)
                for _ in range(config.num_draft_heads)])
            for p in self.draft_heads.parameters():
                p._data = jnp.zeros_like(p._data)
        else:
            self.draft_heads = None

    def forward(self, input_ids, position_ids=None, segment_ids=None):
        return self.head(self.gpt(input_ids, position_ids,
                                  segment_ids=segment_ids))

    def head(self, hidden):
        """LM head over hiddens [..., hidden] -> logits [..., vocab]."""
        if self.lm_head is None:
            from .. import ops

            return ops.matmul(hidden, self.gpt.wte.weight,
                              transpose_y=True)
        return self.lm_head(hidden)

    def draft_hidden(self, hidden, j):
        """Draft head j's residual block over hiddens [..., hidden]:
        ``h + silu(W_j h)``. Feed the result through :meth:`head` for
        the head's logits — kept separate so the compiled spec step can
        batch the k head outputs through ONE shared LM-head matmul."""
        return hidden + F.silu(self.draft_heads[j](hidden))

    def draft_logits(self, hidden):
        """All k draft heads' logits off one final hidden state:
        [..., hidden] -> [..., k, vocab] (head j at index j predicts
        the token j+2 positions ahead of the hidden's position)."""
        from .. import ops

        cat = ops.stack([self.draft_hidden(hidden, j)
                         for j in range(len(self.draft_heads))], axis=-2)
        return self.head(cat)

    def generate(self, input_ids, max_new_tokens=20, seq_lens=None,
                 use_cache="dense", do_sample=False, top_k=0, top_p=1.0,
                 temperature=1.0, seed=None, eos_token_id=None,
                 compiled=True, return_logits=False, **engine_kwargs):
        """Autoregressive generation with a prefill/decode split.

        Prefill pads the prompt to a length bucket and runs the full
        causal forward (flash path) once, writing the KV cache; decode
        then runs a jitted single-token step with donated cache buffers
        — compiled exactly once per engine (retrace-free steady state).

        use_cache: "dense" (aligned batch, one dynamic_update_slice per
        layer) or "paged" (ragged seq_lens + page-pool cache, the
        Ragged-Paged-Attention serving shape). `seq_lens` gives ragged
        true prompt lengths for right-padded `input_ids` (paged only).
        do_sample enables temperature/top-k/top-p sampling; otherwise
        greedy. Returns int32 Tensor [batch, max_new_tokens].

        Engines are cached on the model per (cache kind, batch,
        lengths, sampling, compiled) signature, so repeated calls reuse
        the compiled steps.
        """
        from ..jit.decode_step import GenerationEngine

        ids = input_ids.numpy() if hasattr(input_ids, "numpy") \
            else input_ids
        import numpy as _np

        ids = _np.asarray(ids)
        b, s = ids.shape
        # round the cache capacity up to a shared granularity so nearby
        # (prompt, max_new) shapes REUSE one engine (one KV cache + one
        # compiled decode step) instead of keying an engine per exact
        # length; capped at the position-embedding capacity
        need = s + int(max_new_tokens)
        cap = self.config.max_position_embeddings
        max_len = min(cap, -(-need // 64) * 64)
        if need > cap:
            raise ValueError(
                f"prompt {s} + {max_new_tokens} new tokens exceeds "
                f"max_position_embeddings={cap}")
        # the param-structure fingerprint keeps a stale engine from
        # surviving a weight swap (e.g. quantize_for_decode): same
        # sampling signature, different parameter set -> new engine
        struct = hash(tuple((n, str(p.dtype), tuple(p.shape))
                            for n, p in self.named_parameters()))
        key = (use_cache, b, max_len, bool(do_sample), int(top_k),
               float(top_p), float(temperature), bool(compiled), struct,
               tuple(sorted(engine_kwargs.items())))
        engines = self.__dict__.setdefault("_generation_engines", {})
        engine = engines.pop(key, None)
        if engine is not None:
            engines[key] = engine   # LRU refresh: hits move to the end
        else:
            engine = GenerationEngine(
                self, kind=use_cache, batch=b, max_len=max_len,
                do_sample=do_sample, top_k=top_k, top_p=top_p,
                temperature=temperature, compiled=compiled,
                **engine_kwargs)
            engines[key] = engine
            # bound the cache: each engine owns KV buffers + compiled
            # programs; evict oldest beyond a small working set
            while len(engines) > 4:
                engines.pop(next(iter(engines)))
        return engine.generate(ids, max_new_tokens, seq_lens=seq_lens,
                               eos_token_id=eos_token_id, seed=seed,
                               return_logits=return_logits)

    def sharding_rules(self, tp_axis="mp", fsdp_axis=None):
        """Advertise the Megatron TP placement to the auto-parallel
        planner (distributed/auto_parallel/planner.py)."""
        return gpt_sharding_rules(tp_axis=tp_axis, fsdp_axis=fsdp_axis)

    def loss(self, input_ids, labels, loss_mask=None, position_ids=None,
             segment_ids=None):
        """Training loss via the fused LM head: hidden states go straight
        into F.fused_linear_cross_entropy, so the [tokens, vocab] logits
        are never materialized (vocab-tiled streaming CE). `segment_ids` packs
        multiple documents per row (see GPTModel.forward). Numerically
        equal to GPTPretrainingCriterion(self(ids), labels)."""
        hidden = self.gpt(input_ids, position_ids,
                          segment_ids=segment_ids)
        if self.lm_head is None:
            w, t_y = self.gpt.wte.weight, True
        else:
            w, t_y = self.lm_head.weight, False
        loss = fused_lm_loss(hidden, w, t_y, labels, loss_mask)
        aux = self.gpt.moe_aux()
        if aux is not None:
            loss = loss + self.config.moe_aux_weight * aux
        if self.draft_heads is not None:
            loss = loss + self.config.draft_head_loss_weight \
                * draft_head_loss(self, hidden, w, t_y, labels,
                                  loss_mask)
        return loss


def draft_head_loss(model, hidden, weight, transpose_y, labels,
                    loss_mask=None):
    """Auxiliary CE of the self-speculative draft heads (ISSUE 20):
    head j at position i predicts ``labels[i + j + 1]`` (the base LM
    head predicts ``labels[i]``), through the SAME fused LM-head path
    as the base loss. Mean over heads, so the weight knob is
    independent of k. Used by `GPTForCausalLM.loss` and the fused-scan
    train step's head function — pass the final (ln_f'd) hiddens."""
    total = None
    k = len(model.draft_heads)
    for j in range(k):
        hj = model.draft_hidden(hidden[:, :-(j + 1)], j)
        lj = labels[:, j + 1:]
        mj = loss_mask[:, j + 1:] if loss_mask is not None else None
        lj_loss = fused_lm_loss(hj, weight, transpose_y, lj, mj)
        total = lj_loss if total is None else total + lj_loss
    return total / k


def fused_lm_loss(hidden, weight, transpose_y, labels, loss_mask=None):
    """Shared fused-LM-head loss used by the GPT/LLaMA `model.loss()`
    paths: fused CE, then the criterion's masked-mean reduction."""
    if loss_mask is None:
        return F.fused_linear_cross_entropy(hidden, weight, labels,
                                            transpose_y=transpose_y)
    from .. import ops

    losses = F.fused_linear_cross_entropy(hidden, weight, labels,
                                          transpose_y=transpose_y,
                                          reduction="none")
    m = loss_mask.astype(losses.dtype)
    return ops.sum(losses * m) / ops.clip(ops.sum(m), min=1.0)


class GPTPretrainingCriterion(nn.Layer):
    """Shifted-token cross entropy: mean over non-masked positions (and,
    like F.cross_entropy, over non-ignore_index labels — keeping this
    numerically equal to the fused `model.loss()` path when labels carry
    -100 padding)."""

    def forward(self, logits, labels, loss_mask=None):
        from .. import ops
        from ..distributed.fleet.layers.mpu import ParallelCrossEntropy

        # ParallelCrossEntropy owns the routing: an active mp axis that
        # divides the vocab → explicit sharded-logsumexp CE (no replicated
        # [tokens, vocab] buffer per device); otherwise plain CE. Its mesh
        # resolution happens per forward, so one criterion instance works
        # across fleet re-inits. Constructed lazily (no params).
        if not hasattr(self, "_ce"):
            object.__setattr__(self, "_ce", ParallelCrossEntropy())
        vocab = logits.shape[-1]
        flat_logits = logits.reshape([-1, vocab])
        flat_labels = labels.reshape([-1])
        loss = self._ce(flat_logits, flat_labels)         # [N], 0 at -100
        if loss_mask is None:
            m = (flat_labels != -100).astype(loss.dtype)
        else:
            m = loss_mask.reshape([-1]).astype(loss.dtype)
        return ops.sum(loss * m) / ops.clip(ops.sum(m), min=1.0)


# ---------------------------------------------------------------------------
# Sharding rules: param-name regex → PartitionSpec axes per dim.
# Axis names: "dp" (data/fsdp), "mp" (tensor), "pp" (pipeline — handled by
# the pipeline module, not these specs).
# ---------------------------------------------------------------------------

def gpt_sharding_rules(tp_axis="mp", fsdp_axis=None):
    """Megatron TP placement (+optional ZeRO-3 sharding of the other dim).

    Returns list of (regex, spec) where spec is a tuple of mesh-axis names
    (or None) per tensor dim. First match wins; unmatched params replicate.
    """
    def spec(*axes):
        return tuple(axes)

    rules = [
        # column-parallel: [in, out] → shard out on mp, in on fsdp
        (r"\.qkv\.weight$", spec(fsdp_axis, tp_axis)),
        (r"\.fc1\.weight$", spec(fsdp_axis, tp_axis)),
        (r"\.qkv\.bias$", spec(tp_axis)),
        (r"\.fc1\.bias$", spec(tp_axis)),
        # row-parallel: [in, out] → shard in on mp, out on fsdp
        (r"\.out_proj\.weight$", spec(tp_axis, fsdp_axis)),
        (r"\.fc2\.weight$", spec(tp_axis, fsdp_axis)),
        # vocab-parallel embedding: [vocab, hidden]
        (r"\bwte\.weight$", spec(tp_axis, fsdp_axis)),
        (r"\bwpe\.weight$", spec(None, fsdp_axis)),
        (r"lm_head\.weight$", spec(fsdp_axis, tp_axis)),
    ]
    return rules


def match_sharding(name, rules):
    for pat, spec in rules:
        if re.search(pat, name):
            return spec
    return ()
