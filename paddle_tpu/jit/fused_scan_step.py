"""Memory-bounded whole-step training for scan-layers GPT models.

The generic TrainStep differentiates the whole scanned stack with jax.grad,
so the backward scan materializes EVERY layer's gradient before the
optimizer consumes any of them — measured to exceed a 16G chip by ~1.8G at
gpt3-1.3b (docs/DECISIONS.md §7). This module is the round-5 answer: a
manual, layer-at-a-time reverse scan with the Adam/AdamW update fused into
the scan carry, so exactly ONE layer's gradient is live at any point and
the program XLA compiles/loads is one block, not num_layers inlined copies.

Structure of the compiled step (all one jitted XLA program, donated state):

  forward:   x0 = embed(ids);  (xL, xs) = lax.scan(block, x0, P)
             — xs saves only each layer's INPUT (bf16, [L, b, s, h]);
             block intermediates die inside the scan step (manual remat).
  head:      loss, head_vjp = jax.vjp(ln_f ∘ lm_head ∘ CE);  dxL = vjp(1)
  backward:  carry = (dy, P, M1, M2, MASTER); reverse scan over (xs, i):
               p_i   = dynamic_index_in_dim(P, i)         (read old slice)
               dp,dx = vjp(block)(p_i, x_i)(dy)           (recompute fwd)
               adam  = Optimizer._adam_math(...)          (shared rule)
               P,M,V,MASTER updated at slot i via dynamic_update_index —
               the in-place pattern XLA aliases through while-loop carries,
               so the donated input stacks and the outputs share buffers.
  outer:     embedding/ln_f/head params update from head_vjp + embed vjp
             (tied embeddings sum both contributions, like the tape).

Why this fits: state floor (bf16 params 2x + fp32 masters 4x + bf16
moments 4x ≈ 10 bytes/param) plus ONE layer's grads and the [L,b,s,h]
bf16 input stash — vs the generic scan path's +2 bytes/param all-grads
set. The program is O(1 block): compile and load time do not grow with
depth the way the 24-layer unrolled step's do.

Reference parity: the roles of Paddle's gradient-merge + sharded optimizer
fusion passes (python/paddle/distributed/passes/auto_parallel_gradient_merge.py,
fuse_optimizer passes) — done here as one functional scan instead of IR
surgery. The update math is Optimizer._adam_math, the same single source
the eager and multi-tensor paths use, so parity with TrainStep is exact
in fp32 (tests/test_fused_scan_step.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.tensor import Tensor
from ..framework.autograd import no_grad
from ..framework import random as _random
from ..observability import RetraceSentinel
from ..profiler import RecordEvent
from .train_step import _commit_uncommitted

# PRNG draws reserved per layer forward (2 hidden dropouts + attention
# dropout + slack). The per-layer offset scheme below is
#   offset = ((step * num_layers + layer) * nranks + rank) * _RNG_SLOTS
# — collision-free across (step, layer, rank) until int32 wrap (~10^6
# steps at 24 layers), and identical between the forward trace and the
# backward's vjp recompute, which is what makes dropout legal inside the
# manually-rematerialized scan.
_RNG_SLOTS = 8


def _key(p):
    return p.name or str(id(p))


def _act_stats(in_fin, h_out):
    """Per-chunk activation health (ISSUE 15): ([sum(out²), count,
    origin], out_finite) where origin = input finite AND output
    non-finite — the forward provenance of a NaN (the chunk whose math
    broke, not the chunks its output then poisoned). ONE pass over the
    chunk output: finiteness is derived from the fp32 square-sum
    (NaN/inf propagate through it; the only false positive is a
    legitimately finite activation beyond ~1.8e19 whose square
    overflows fp32 — training is numerically dead long before that),
    and the input flag is the previous chunk's output flag threaded
    through the scan carry rather than a second pass."""
    o32 = h_out.astype(jnp.float32)
    sq = jnp.sum(jnp.square(o32))
    out_fin = jnp.isfinite(sq)
    return jnp.stack([sq, jnp.float32(o32.size),
                      (in_fin & ~out_fin).astype(jnp.float32)]), out_fin


class FusedScanTrainStep:
    """One-XLA-program train step for a scan_layers GPTForCausalLM (or any
    model with the same stacked-blocks shape) + Adam/AdamW.

    Usage matches TrainStep::

        step = FusedScanTrainStep(model, opt)   # model: scan_layers=True
        loss = step(ids, labels)                # one fused launch

    Constraints (asserted): Adam/AdamW without amsgrad.

    Grad clip: ClipGradByValue applies elementwise inside the scan (free);
    ClipGradByGlobalNorm runs a DEFERRED-NORM two-pass backward — pass 1
    re-scans the vjp accumulating only the squared norm in the carry (each
    layer's grad still dies inside its iteration), pass 2 applies the
    clipped update. ~2x backward FLOPs, still O(1 layer) grad memory; the
    sharded step (jit/sharded_scan.py) gets the same clip for one scalar
    all-reduce instead, because its 1/N grad shards DO fit. Per-tensor
    ClipGradByNorm would need a whole stacked [L, ...] leaf's grad at
    once — unsupported here, use ClipGradByGlobalNorm or the sharded step.

    Dropout: supported. Each layer's dropout keys derive from
    (step, layer, rank) via a generator offset bound inside the scan body
    (_RNG_SLOTS scheme above), so the backward's recompute of layer i's
    forward draws exactly the masks the forward used.
    """

    def __init__(self, model, optimizer, criterion=None, fused_head=False,
                 compute_dtype=None, layer_chunk=1, scan_unroll=1,
                 scaler=None, guard_nonfinite=None, numerics=None):
        from ..models.gpt import GPTStackedBlocks, GPTPretrainingCriterion
        from ..optimizer import Adam
        from .nonfinite_guard import GuardSpec

        # in-graph non-finite guard: found_inf rides the backward pass as
        # a running scalar folded per layer chunk (alongside the squared
        # norm when clipping); all updates are where-gated so a NaN step
        # leaves params/moments/step bit-identical. Without a global-norm
        # clip the guard forces the same two-pass backward the clip uses
        # (grads must be inspected before the in-scan update consumes
        # them) — docs/DECISIONS.md §13.
        self._guard = (GuardSpec(scaler)
                       if (scaler is not None or guard_nonfinite)
                       else None)

        self.model = model
        blocks = model.gpt.blocks
        if not isinstance(blocks, GPTStackedBlocks):
            raise ValueError(
                "FusedScanTrainStep needs GPTConfig(scan_layers=True) "
                "(stacked [L, ...] block params); got an unrolled model — "
                "use jit.TrainStep there")
        self.optimizer = optimizer
        opt = optimizer
        seen = set()
        while hasattr(opt, "_inner_opt") and id(opt) not in seen:
            seen.add(id(opt))
            opt = opt._inner_opt
        if not isinstance(opt, Adam):
            raise ValueError("fused scan step supports Adam/AdamW only")
        self._clip_global = None      # ClipGradByGlobalNorm clip_norm
        self._clip_value = None       # ClipGradByValue (min, max)
        clip = opt._grad_clip
        if clip is not None:
            from ..nn.clip import (
                ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue,
            )

            if type(clip) is ClipGradByGlobalNorm:
                self._clip_global = float(clip.clip_norm)
            elif type(clip) is ClipGradByValue:
                self._clip_value = (float(clip.min), float(clip.max))
            elif isinstance(clip, ClipGradByNorm):
                raise ValueError(
                    "ClipGradByNorm clips each tensor by its OWN norm, "
                    "which for a stacked [L, ...] leaf needs all L "
                    "layers' grads at once — exactly what this step "
                    "never materializes. Use ClipGradByGlobalNorm "
                    "(deferred-norm two-pass here, one scalar "
                    "all-reduce in ShardedFusedScanTrainStep) or "
                    "ClipGradByValue (elementwise, free in-scan)")
            else:
                raise ValueError(
                    f"unsupported grad_clip {type(clip).__name__}: the "
                    "fused scan step supports ClipGradByGlobalNorm and "
                    "ClipGradByValue (subclasses with custom semantics "
                    "would be silently miscomputed, so they are "
                    "rejected)")
        if opt._amsgrad:
            raise ValueError("amsgrad moment2_max not supported")
        cfg = model.config
        # dropout is legal here: the per-layer PRNG offset binding
        # (_RNG_SLOTS scheme) makes the backward's block recompute draw
        # the same masks the forward did
        self._dropout_active = bool(
            getattr(cfg, "hidden_dropout_prob", 0.0)
            or getattr(cfg, "attention_dropout_prob", 0.0))
        self._opt = opt
        self._crit = criterion or GPTPretrainingCriterion()
        # fused_head=True routes the LM head through the chunked-logsumexp
        # fused CE (F.fused_linear_cross_entropy) instead of dense logits +
        # criterion: the dense head's [tokens, vocab] logits + fp32 CE
        # residuals are ~2.5G of the 1.3b step's temps — the measured
        # difference between fitting 16G HBM and not (tools/diag_fused_mem).
        # Numerically equal to the criterion path (models/gpt.fused_lm_loss).
        self._fused_head = bool(fused_head)
        # compute_dtype="bfloat16" with FP32-STORED params is the
        # memory-optimal single-chip AMP-O2 layout: rather than keeping a
        # bf16 param stack AND an fp32 master stack (2+4 bytes/param),
        # store only fp32 and materialize the bf16 view per layer inside
        # the scan (transient ~one layer). Identical math — the bf16 copy
        # the masters scheme computes with IS cast(master) at all times —
        # but 2 bytes/param less HBM: at 1.3b that is the 2.45G between
        # the 15.3G measured-OOM peak and a fitting 12.9G
        # (tools/diag_fused_mem.py).
        from ..framework.dtype import to_jax_dtype

        self._compute_dtype = (to_jax_dtype(compute_dtype)
                               if compute_dtype is not None else None)
        self._blocks = blocks
        self._template = blocks._template
        self._t_leaves = [p for _, p in self._template.named_parameters()]
        # MoE blocks (ISSUE 9): the template's MoE layers publish a
        # load-balance aux loss per forward; it rides the scan as a ys
        # output and is folded into the training loss with weight
        # moe_aux_weight/num_layers (the model-level layer mean), with
        # matching cotangents injected into every chunk vjp
        from ..incubate.distributed.models.moe.moe_layer import MoELayer

        self._aux_layers = [
            s for _, s in self._template.named_sublayers(
                include_self=True) if isinstance(s, MoELayer)]
        self._aux_active = bool(self._aux_layers)
        self._aux_weight = (float(getattr(cfg, "moe_aux_weight", 0.0))
                            if self._aux_active else 0.0)
        self._s_params = [blocks._parameters[flat]
                          for flat, _ in blocks._stacked_names]
        self._o_params = [(n, p) for n, p in model.named_parameters()
                          if "blocks__" not in n and p.trainable]
        self._buffers = list(model.buffers())
        # scan-over-chunks: unroll `layer_chunk` layers inside each scan
        # step. One scan iteration per layer serializes at every layer
        # boundary (the iteration barrier stops XLA from overlapping one
        # layer's optimizer slices/HBM traffic with the next layer's
        # compute — measured 7% under the unrolled program at 1.3b);
        # unrolling K layers per step restores intra-chunk overlap while
        # keeping the program O(K blocks) and the simultaneous-grad set
        # O(K layers). Memory cost ≈ K× the per-layer vjp residuals.
        # scan_unroll: lax.scan-native iteration unrolling — K iterations
        # merged per while-loop step, so XLA can overlap adjacent layers'
        # optimizer traffic with compute WITHOUT changing the per-layer
        # vjp/remat structure (unlike layer_chunk, whose K-layer vjp was
        # measured slower at 1.3b: 10.7k vs 12.0k tok/s).
        self._scan_unroll = int(scan_unroll)
        n_layers = model.config.num_layers
        self._layer_chunk = int(layer_chunk)
        if self._layer_chunk < 1 or n_layers % self._layer_chunk:
            raise ValueError(
                f"layer_chunk {layer_chunk} must divide num_layers "
                f"{n_layers}")
        if self._compute_dtype is not None:
            for p in self._s_params + [p for _, p in self._o_params]:
                if p._data.dtype != jnp.float32:
                    raise ValueError(
                        "compute_dtype expects fp32-stored params (the "
                        f"param IS the master); got {p._data.dtype}")
        # training-numerics observatory (ISSUE 15): per-layer-chunk
        # grad/param/update/activation stats ride the scans as one
        # fixed-shape [chunks+1, k] block (the trailing row is the
        # outer embed/ln_f/head group), consumed lazily by the monitor
        # — default ON (FLAGS_numerics_monitor; DECISIONS §21)
        from ..observability.numerics import (
            NumericsMonitor, monitor_enabled,
        )

        self._numerics = None
        if (bool(numerics) if numerics is not None
                else monitor_enabled()):
            K0 = self._layer_chunk
            C0 = n_layers // K0
            labels = [(f"chunk{c}(layer {c * K0})" if K0 == 1 else
                       f"chunk{c}(layers {c * K0}-{(c + 1) * K0 - 1})")
                      for c in range(C0)] + ["outer"]
            self._numerics = NumericsMonitor(
                type(self).__name__, C0 + 1, row_labels=labels)
        self._jitted = None
        # retrace sentinel (ISSUE 12): the optional segment-id arg is a
        # declared presence-varying signature (None and seg each
        # compile once); anything else that recompiles is attributed
        self._sentinel = RetraceSentinel(type(self).__name__,
                                         optional=("segment_ids",))
        self._canon_done = False   # one-time layout canon at first call
        self._calls = 0     # host-side: `_step_count` lives on the device
        # adopt the optimizer's existing step count: continuing a run
        # that already trained under TrainStep must not reset the Adam
        # bias corrections to t=1 (r5 review finding)
        self._step_count = int(opt._step_count)

    # -- input pipeline -------------------------------------------------
    def input_sharding(self):
        """Single-chip step: None → default-device placement (identical
        to `paddle.to_tensor`, so prefetched batches hit the same
        executable). ShardedFusedScanTrainStep overrides with its
        dp-sharded batch spec."""
        return None

    def prefetch(self, loader, depth=2, **kw):
        """Wrap `loader` in an `io.DevicePrefetcher` bound to this step's
        input sharding (see TrainStep.prefetch)."""
        from ..io.device_prefetcher import DevicePrefetcher

        kw.setdefault("sharding", self.input_sharding())
        return DevicePrefetcher(loader, depth=depth, **kw)

    # -- per-layer PRNG plumbing (dropout inside the scan) --------------
    # the sharded subclass overrides these with the dp-axis rank so every
    # rank draws distinct masks for its own batch rows
    _rng_nranks = 1

    def _rng_rank(self):
        return 0

    def _rng_base(self, t32, layer):
        """Traced generator offset for `layer` at step t32 (int32); slot
        `num_layers` is the embedding dropout. None when the model has no
        dropout."""
        if not self._dropout_active:
            return None
        n_slots = self.model.config.num_layers + 1
        return ((t32 * n_slots + layer) * self._rng_nranks
                + self._rng_rank()) * _RNG_SLOTS

    def _rng_chunk_base(self, t32, chunk_i):
        if not self._dropout_active:
            return None
        return self._rng_base(t32, chunk_i * self._layer_chunk)

    def _chunk_apply(self, chunk_leaves, h, rng0=None):
        """layer_chunk layers unrolled: chunk_leaves are [K, ...]
        slices; rng0 is the chunk's first-layer PRNG offset (None
        without dropout). Shared by the single-device and sharded
        builds — the rng stride here and _rng_base are one scheme.
        MoE templates return (h, aux_sum) — the chunk's summed
        load-balance loss rides alongside the activations."""
        stride = self._rng_nranks * _RNG_SLOTS
        if not self._aux_active:
            for j in range(self._layer_chunk):
                off = None if rng0 is None else rng0 + j * stride
                h = self._block_fn([a[j] for a in chunk_leaves], h,
                                   rng_off=off)
            return h
        aux = jnp.float32(0.0)
        for j in range(self._layer_chunk):
            off = None if rng0 is None else rng0 + j * stride
            h, a = self._block_fn([a2[j] for a2 in chunk_leaves], h,
                                  rng_off=off)
            aux = aux + a
        return h, aux

    # -- pure functional views over the live layers ---------------------
    def _bind(self, params, datas):
        saved = [p._data for p in params]
        for p, d in zip(params, datas):
            p._data = d
        return saved

    def _cc(self, datas):
        """The compute-dtype view of fp32-stored params (identity when
        compute_dtype is unset). Differentiable: the cast's vjp upcasts
        the bf16 cotangent, exactly what the masters scheme feeds Adam."""
        if self._compute_dtype is None:
            return datas
        return [d.astype(self._compute_dtype) for d in datas]

    def _block_fn(self, leaf_datas, x, rng_off=None):
        """One decoder block as a pure jax function of (leaves, x).

        `rng_off` (traced int32 or None) pins the global generator's
        offset for the duration of the block, so every dropout draw
        inside is a pure function of (seed, rng_off, draw index) — the
        backward's vjp recompute passes the SAME rng_off and reproduces
        the forward's masks exactly."""
        tmpl = self._template
        gen = _random.default_generator()
        with no_grad():
            saved = self._bind(self._t_leaves, self._cc(leaf_datas))
            saved_off = gen._offset
            if rng_off is not None:
                gen._offset = rng_off
            try:
                # train() (not just .training=True): the template is no
                # registered sublayer, so its Dropout children only see
                # the mode set this way
                tmpl.train()
                out = tmpl._inner(Tensor._wrap(x))._data
                if self._aux_active:
                    aux = self._aux_layers[0].l_aux._data
                    for lyr in self._aux_layers[1:]:
                        aux = aux + lyr.l_aux._data
                    return out, aux.astype(jnp.float32)
                return out
            finally:
                gen._offset = saved_off
                self._bind(self._t_leaves, saved)

    def _embed_fn(self, o_datas, ids, pos, rng_off=None):
        m = self.model
        gen = _random.default_generator()
        with no_grad():
            saved = self._bind([p for _, p in self._o_params],
                               self._cc(o_datas))
            saved_off = gen._offset
            if rng_off is not None:
                gen._offset = rng_off
            try:
                x = m.gpt.wte(Tensor._wrap(ids)) + m.gpt.wpe(
                    Tensor._wrap(pos))
                if self._dropout_active:
                    # the eager forward applies embedding dropout
                    # (GPTModel.forward: self.drop) — keep parity
                    m.gpt.drop.training = True
                    x = m.gpt.drop(x)
                return x._data
            finally:
                gen._offset = saved_off
                self._bind([p for _, p in self._o_params], saved)

    def _head_fn(self, o_datas, xL, labels):
        """ln_f + LM head + criterion as a pure function of ALL outer
        params (unused ones get zero cotangents — that is how tied/untied
        heads are handled uniformly)."""
        m = self.model
        from .. import ops

        with no_grad():
            saved = self._bind([p for _, p in self._o_params],
                               self._cc(o_datas))
            try:
                h = m.gpt.ln_f(Tensor._wrap(xL))
                yT = Tensor._wrap(labels)
                if m.lm_head is None:
                    w, t_y = m.gpt.wte.weight, True
                else:
                    w, t_y = m.lm_head.weight, False
                if self._fused_head:
                    from ..models.gpt import fused_lm_loss

                    loss = fused_lm_loss(h, w, t_y, yT)
                else:
                    if m.lm_head is None:
                        logits = ops.matmul(h, m.gpt.wte.weight,
                                            transpose_y=True)
                    else:
                        logits = m.lm_head(h)
                    loss = self._crit(logits, yT)
                if getattr(m, "draft_heads", None) is not None:
                    # self-spec draft heads (ISSUE 20): same aux CE the
                    # eager loss() adds — heads are outer params, so
                    # their grads ride the o-param cotangents
                    from ..models.gpt import draft_head_loss

                    loss = loss + m.config.draft_head_loss_weight \
                        * draft_head_loss(m, h, w, t_y, yT)
                return loss._data
            finally:
                self._bind([p for _, p in self._o_params], saved)

    # -- state plumbing --------------------------------------------------
    def _extract_state(self):
        opt = self._opt
        m1 = opt._accumulators["moment1"]
        m2 = opt._accumulators["moment2"]

        def pack(params):
            return {
                "p": [p._data for p in params],
                "m": [m1[_key(p)] for p in params],
                "v": [m2[_key(p)] for p in params],
                "mw": [opt._master_weights.get(_key(p)) for p in params],
            }

        # the optimizer owns the step count: a checkpoint restore writes
        # opt._step_count (load_opt_state_pytree) and this read is what
        # makes the next compiled step see it
        self._step_count = opt._step_count
        state = {
            "s": pack(self._s_params),
            "o": pack([p for _, p in self._o_params]),
            "buf": [b._data for b in self._buffers],
            "step": jnp.asarray(self._step_count, jnp.int32),
        }
        if self._guard is not None:
            state["guard"] = self._guard.init_state()
        return state

    def _inject_state(self, state):
        opt = self._opt

        def unpack(params, st):
            for p, d, m, v, mw in zip(params, st["p"], st["m"], st["v"],
                                      st["mw"]):
                p._data = d
                opt._accumulators["moment1"][_key(p)] = m
                opt._accumulators["moment2"][_key(p)] = v
                if mw is not None:
                    opt._master_weights[_key(p)] = mw

        unpack(self._s_params, state["s"])
        unpack([p for _, p in self._o_params], state["o"])
        for b, d in zip(self._buffers, state["buf"]):
            b._data = d
        opt._step_count = state["step"]
        self._step_count = state["step"]
        if self._guard is not None and "guard" in state:
            self._guard.writeback(state["guard"])

    # -- the compiled step ----------------------------------------------
    def _build(self):
        opt = self._opt
        # per-param host-side hyperparameters (static in the trace)
        def hyper(p):
            return (float(opt._decoupled_wd(p)), float(opt._l2_coeff(p)),
                    float(opt._param_lr_scale(p)))

        s_hyp = [hyper(p) for p in self._s_params]
        o_hyp = [hyper(p) for _, p in self._o_params]
        n_leaves = len(self._s_params)
        K = self._layer_chunk
        chunk_apply = self._chunk_apply

        def adam(pv, g32, m, v, lr, tf, wd, l2):
            if l2:
                g32 = g32 + l2 * pv.astype(jnp.float32)
            return opt._adam_math(pv, g32, m, v, None, lr, tf, wd)

        cv = self._clip_value
        guard = self._guard
        scaling = guard is not None and guard.scaling
        nm = self._numerics is not None
        aux_active = self._aux_active
        # per-chunk aux cotangent: total loss adds
        # (moe_aux_weight / L) * sum(per-layer aux)
        aux_w = self._aux_weight / self.model.config.num_layers

        def clip_g32(g32, p):
            """The per-grad transforms that are legal inside the scan:
            elementwise value clip, and the deferred global-norm scale
            (traced scalar, resolved before the update scan runs)."""
            if cv is not None and getattr(p, "need_clip", True):
                g32 = jnp.clip(g32, cv[0], cv[1])
            return g32

        def scaled(g32, p, scale):
            if scale is not None and getattr(p, "need_clip", True):
                g32 = g32 * scale
            return g32

        from ..nn.functional.flash_attention import attention_segments

        def step_fn(state, lr, ids, labels, seg=None):
            s, o = state["s"], state["o"]
            saved_buf = self._bind(self._buffers, state["buf"])
            # publish packed-sequence segment ids to every attention
            # layer traced in this step (forward scan, the norm/guard
            # pre-pass, and the backward recompute all see the same
            # traced value — the vjp replays attention with the same
            # mask the forward used)
            seg_ctx = attention_segments(seg)
            seg_ctx.__enter__()
            try:
                gst = state.get("guard")
                # loss-scale: seed the head cotangent with the traced
                # scale instead of 1.0 — every grad in both backward
                # passes comes out scaled, the loss itself stays unscaled
                inv_s = (1.0 / gst["scale"]) if scaling else None
                t = state["step"] + 1
                tf = t.astype(jnp.float32)
                b, seq = ids.shape
                pos = jnp.arange(seq, dtype=ids.dtype)[None, :]

                t32 = t.astype(jnp.int32)
                n_layers = self.model.config.num_layers

                # ---- forward: embed + scan over chunks of K layers,
                # saving only each CHUNK's input
                with jax.named_scope("forward"):
                    x0 = self._embed_fn(
                        o["p"], ids, pos,
                        rng_off=self._rng_base(t32, n_layers))
                sp_c = tuple(a.reshape((a.shape[0] // K, K)
                                       + tuple(a.shape[1:]))
                             for a in s["p"])
                sm_c = tuple(a.reshape((a.shape[0] // K, K)
                                       + tuple(a.shape[1:]))
                             for a in s["m"])
                sv_c = tuple(a.reshape((a.shape[0] // K, K)
                                       + tuple(a.shape[1:]))
                             for a in s["v"])
                smw_c = tuple(a.reshape((a.shape[0] // K, K)
                                        + tuple(a.shape[1:]))
                              if a is not None else None
                              for a in s["mw"])

                C = sp_c[0].shape[0]

                def fwd_body(carry, scanned):
                    h, h_fin = carry if nm else (carry, None)
                    p_chunk, i = scanned
                    rng0 = self._rng_chunk_base(t32, i)
                    if aux_active:
                        h2, aux = chunk_apply(p_chunk, h, rng0)
                    else:
                        h2, aux = chunk_apply(p_chunk, h, rng0), None
                    ys = {"x": h}
                    if aux_active:
                        ys["aux"] = aux
                    if not nm:
                        return h2, ys
                    ys["act"], out_fin = _act_stats(h_fin, h2)
                    return (h2, out_fin), ys

                fwd0 = ((x0, jnp.isfinite(x0).all()) if nm else x0)
                with jax.named_scope("forward"):
                    fwd_c, ys = lax.scan(
                        fwd_body, fwd0, (sp_c, jnp.arange(C)),
                        unroll=self._scan_unroll)
                xL = fwd_c[0] if nm else fwd_c
                xs, auxs = ys["x"], ys.get("aux")
                act_cols = ys.get("act")           # [C, 3] when nm

                # ---- head (+ its whole vjp: small params, one buffer)
                with jax.named_scope("forward"):
                    loss, head_vjp = jax.vjp(
                        lambda od, x: self._head_fn(od, x, labels),
                        o["p"], xL)
                ct = (gst["scale"].astype(loss.dtype) if scaling
                      else jnp.ones((), loss.dtype))
                with jax.named_scope("backward"):
                    d_o_head, dxL = head_vjp(ct)
                aux_ct = None
                if aux_active:
                    # total loss = CE + (w/L) * sum(aux); the chunk vjps
                    # below receive the matching (loss-scaled) cotangent
                    loss = loss + jnp.float32(aux_w) * jnp.sum(auxs)
                    aux_ct = jnp.float32(aux_w) * ct.astype(jnp.float32)

                # ---- deferred global-norm clip / non-finite pre-pass
                # (pass 1 of 2): re-scan the vjp accumulating ONLY
                # scalars in the carry — the squared grad norm (clip)
                # and the finiteness fold (guard) — each layer's grad
                # still dies inside its iteration, so the memory plan is
                # unchanged; cost is a second backward
                # (docs/DECISIONS.md §12, §13). The embed-side outer
                # grads fall out of this pass's dx0 and are reused by
                # the update below (their math is identical).
                scale = None
                d_o_emb = None
                found = None
                grad_rows = None       # [C, 3] (sq, bad, origin) — nm
                if self._clip_global is not None or guard is not None:
                    from .nonfinite_guard import all_finite

                    want_norm = self._clip_global is not None

                    def norm_body(carry, scanned):
                        dy, sq, fin = carry
                        x_i, i = scanned
                        p_i = tuple(
                            lax.dynamic_index_in_dim(a, i, keepdims=False)
                            for a in P0)
                        rng0 = self._rng_chunk_base(t32, i)
                        _, vjp = jax.vjp(
                            lambda pl, xx: chunk_apply(pl, xx, rng0),
                            p_i, x_i)
                        dp, dx = vjp((dy, aux_ct) if aux_active else dy)
                        c_fin = None
                        if guard is not None:
                            # the guard's fold stays an EXACT isfinite
                            # (its skip decision must not inherit the
                            # square-sum overflow caveat)
                            c_fin = all_finite(
                                [dp[j] for j in range(n_leaves)
                                 if self._s_params[j].trainable])
                            fin = fin & c_fin
                        # the clip carry and the monitor's per-chunk
                        # grad sq-norm share one set of per-leaf
                        # reductions (ISSUE 15 dedup: the monitor
                        # reads the clip's terms when clipping is on,
                        # computes them only when off)
                        c_sq = jnp.float32(0.0)
                        for j in range(n_leaves):
                            p = self._s_params[j]
                            if not p.trainable:
                                continue
                            clipped = want_norm and getattr(
                                p, "need_clip", True)
                            if not (clipped or nm):
                                continue
                            s_j = jnp.sum(jnp.square(
                                dp[j].astype(jnp.float32)))
                            if clipped:
                                sq = sq + s_j
                            if nm:
                                c_sq = c_sq + s_j
                        row = None
                        if nm:
                            # without a guard the finite flag derives
                            # from the sq-norm (NaN/inf propagate) —
                            # no extra pass over the grads
                            if c_fin is None:
                                c_fin = jnp.isfinite(c_sq)
                            row = jnp.stack([
                                c_sq, (~c_fin).astype(jnp.float32),
                                jnp.float32(0.0)])
                        return (dx, sq, fin), row

                    P0 = sp_c
                    with jax.named_scope("backward"):
                        (dx0, sq, fin), grad_rows = lax.scan(
                            norm_body,
                            (dxL, jnp.float32(0.0), jnp.bool_(True)),
                            (xs, jnp.arange(C)), reverse=True,
                            unroll=self._scan_unroll)
                        _, emb_vjp = jax.vjp(
                            lambda od: self._embed_fn(
                                od, ids, pos,
                                rng_off=self._rng_base(t32, n_layers)),
                            o["p"])
                        (d_o_emb,) = emb_vjp(dx0)
                    o_g32 = [(d_o_head[j].astype(jnp.float32)
                              + d_o_emb[j].astype(jnp.float32))
                             for j in range(len(o["p"]))]
                    if guard is not None:
                        found = ~(fin & all_finite(o_g32))
                    if want_norm:
                        for j in range(len(o["p"])):
                            if not getattr(self._o_params[j][1],
                                           "need_clip", True):
                                continue
                            sq = sq + jnp.sum(jnp.square(o_g32[j]))
                        # grads (hence sq) carry the loss scale: the
                        # true norm is sqrt(sq)/loss_scale
                        gnorm = jnp.sqrt(sq)
                        if inv_s is not None:
                            gnorm = gnorm * inv_s
                        scale = jnp.minimum(
                            jnp.float32(self._clip_global)
                            / jnp.maximum(gnorm, 1e-12), 1.0)

                # ---- reverse scan: vjp one CHUNK, update its slices
                def bwd_body(carry, scanned):
                    dy, P, M, V, MW = carry
                    x_i, i = scanned
                    p_i = tuple(
                        lax.dynamic_index_in_dim(a, i, keepdims=False)
                        for a in P)          # [K, ...] slices
                    rng0 = self._rng_chunk_base(t32, i)
                    _, vjp = jax.vjp(
                        lambda pl, xx: chunk_apply(pl, xx, rng0), p_i, x_i)
                    dp, dx = vjp((dy, aux_ct) if aux_active else dy)
                    ys_b = {}
                    p_sq = u_sq = None
                    if nm:
                        p_sq = jnp.float32(0.0)
                        u_sq = jnp.float32(0.0)
                        if grad_rows is None:
                            # no clip/guard pre-pass ran: the monitor's
                            # grad stats come from THIS backward's dp
                            # (finiteness derives from the sq-norm)
                            c_sq = jnp.float32(0.0)
                            for j in range(n_leaves):
                                if not self._s_params[j].trainable:
                                    continue
                                c_sq = c_sq + jnp.sum(jnp.square(
                                    dp[j].astype(jnp.float32)))
                            ys_b["g"] = jnp.stack([
                                c_sq,
                                (~jnp.isfinite(c_sq))
                                .astype(jnp.float32),
                                jnp.float32(0.0)])
                    nP, nM, nV, nMW = [], [], [], []
                    with jax.named_scope("optimizer"):
                        for j in range(n_leaves):
                            if not self._s_params[j].trainable:
                                # frozen stacked leaf: no update (XLA DCEs
                                # its unused dp slice); parity with the
                                # tape path's stop_gradient handling
                                nP.append(P[j])
                                nM.append(M[j])
                                nV.append(V[j])
                                nMW.append(MW[j])
                                continue
                            wd, l2, lrs = s_hyp[j]
                            m_j = lax.dynamic_index_in_dim(M[j], i,
                                                           keepdims=False)
                            v_j = lax.dynamic_index_in_dim(V[j], i,
                                                           keepdims=False)
                            mw_j = (lax.dynamic_index_in_dim(
                                MW[j], i, keepdims=False)
                                if MW[j] is not None else None)
                            pv = mw_j if mw_j is not None else p_i[j]
                            g32 = dp[j].astype(jnp.float32)
                            if inv_s is not None:
                                g32 = g32 * inv_s
                            g32 = scaled(clip_g32(g32, self._s_params[j]),
                                         self._s_params[j], scale)
                            out, mn, vn, _ = adam(
                                pv, g32, m_j, v_j,
                                lr * lrs, tf, jnp.float32(wd), l2)
                            if nm:
                                pv32 = pv.astype(jnp.float32)
                                d_upd = out.astype(jnp.float32) - pv32
                                if found is not None:
                                    d_upd = jnp.where(
                                        found, jnp.zeros_like(d_upd), d_upd)
                                ps_j = jnp.sum(jnp.square(pv32))
                                us_j = jnp.sum(jnp.square(d_upd))
                            out_p = out.astype(P[j].dtype)
                            mn_c = mn.astype(M[j].dtype)
                            vn_c = vn.astype(V[j].dtype)
                            if found is not None:
                                # bad step: every slot passes through
                                # bit-identical (selection, not arithmetic)
                                out_p = jnp.where(found, p_i[j], out_p)
                                mn_c = jnp.where(found, m_j, mn_c)
                                vn_c = jnp.where(found, v_j, vn_c)
                                if mw_j is not None:
                                    out = jnp.where(found, mw_j, out)
                            # A reader of a carried stack's OLD slice
                            # that also needs this layer's gradient runs
                            # after the slot write that makes the
                            # gradient (XLA fuses that write into the
                            # gradient's matmul), so XLA protects the
                            # stack with a whole-stack copy in and a copy
                            # out, every iteration. Two such readers
                            # exist: the monitor's sums (of `pv`'s
                            # stack), and, with masters, the second
                            # parameter write re-deriving Adam from the
                            # old moments. Leaving ONE barrier with the
                            # new slot values orders every read before
                            # every write: the sums fuse into the matmul
                            # and the stacks are updated in place
                            # (DECISIONS §21; tests/test_fused_scan_step
                            # .py reads the compiled step for it).
                            if mw_j is not None:
                                new = (out_p, mn_c, vn_c, out)
                                if nm:
                                    new, ps_j, us_j = \
                                        lax.optimization_barrier(
                                            (new, ps_j, us_j))
                                else:
                                    new = lax.optimization_barrier(new)
                                out_p, mn_c, vn_c, out = new
                            elif nm:
                                out_p, ps_j, us_j = \
                                    lax.optimization_barrier(
                                        (out_p, ps_j, us_j))
                            if nm:
                                p_sq = p_sq + ps_j
                                u_sq = u_sq + us_j
                            nP.append(lax.dynamic_update_index_in_dim(
                                P[j], out_p, i, 0))
                            nM.append(lax.dynamic_update_index_in_dim(
                                M[j], mn_c, i, 0))
                            nV.append(lax.dynamic_update_index_in_dim(
                                V[j], vn_c, i, 0))
                            nMW.append(lax.dynamic_update_index_in_dim(
                                MW[j], out, i, 0)
                                if MW[j] is not None else None)
                    if nm:
                        ys_b["pu"] = jnp.stack([p_sq, u_sq])
                    return (dx, tuple(nP), tuple(nM), tuple(nV),
                            tuple(nMW)), ys_b

                carry0 = (dxL, sp_c, sm_c, sv_c, smw_c)
                with jax.named_scope("backward"):
                    (dx0, nP, nM, nV, nMW), bwd_ys = lax.scan(
                        bwd_body, carry0, (xs, jnp.arange(C)),
                        reverse=True, unroll=self._scan_unroll)
                # back to the [L, ...] stacked layout
                nP = [a.reshape((-1,) + tuple(a.shape[2:])) for a in nP]
                nM = [a.reshape((-1,) + tuple(a.shape[2:])) for a in nM]
                nV = [a.reshape((-1,) + tuple(a.shape[2:])) for a in nV]
                nMW = [a.reshape((-1,) + tuple(a.shape[2:]))
                       if a is not None else None for a in nMW]

                # ---- embedding-side grads for outer params + update
                # (already computed by the norm pass when clipping)
                if d_o_emb is None:
                    with jax.named_scope("backward"):
                        _, emb_vjp = jax.vjp(
                            lambda od: self._embed_fn(
                                od, ids, pos,
                                rng_off=self._rng_base(t32, n_layers)),
                            o["p"])
                        (d_o_emb,) = emb_vjp(dx0)
                new_o = {"p": [], "m": [], "v": [], "mw": []}
                if nm:
                    o_g_sq = jnp.float32(0.0)
                    o_p_sq = jnp.float32(0.0)
                    o_u_sq = jnp.float32(0.0)
                with jax.named_scope("optimizer"):
                    for j in range(len(o["p"])):
                        wd, l2, lrs = o_hyp[j]
                        g32 = (d_o_head[j].astype(jnp.float32)
                               + d_o_emb[j].astype(jnp.float32))
                        if nm:
                            # raw (still loss-scaled) grads — the inv_s²
                            # unscale is applied once at assembly below
                            o_g_sq = o_g_sq + jnp.sum(jnp.square(g32))
                        if inv_s is not None:
                            g32 = g32 * inv_s
                        g32 = scaled(clip_g32(g32, self._o_params[j][1]),
                                     self._o_params[j][1], scale)
                        pv = (o["mw"][j] if o["mw"][j] is not None
                              else o["p"][j])
                        out, mn, vn, _ = adam(pv, g32, o["m"][j], o["v"][j],
                                              lr * lrs, tf, jnp.float32(wd),
                                              l2)
                        if nm:
                            pv32 = pv.astype(jnp.float32)
                            d_upd = out.astype(jnp.float32) - pv32
                            if found is not None:
                                d_upd = jnp.where(
                                    found, jnp.zeros_like(d_upd), d_upd)
                            o_p_sq = o_p_sq + jnp.sum(jnp.square(pv32))
                            o_u_sq = o_u_sq + jnp.sum(jnp.square(d_upd))
                        out_p = out.astype(o["p"][j].dtype)
                        mn_c = mn.astype(o["m"][j].dtype)
                        vn_c = vn.astype(o["v"][j].dtype)
                        if found is not None:
                            out_p = jnp.where(found, o["p"][j], out_p)
                            mn_c = jnp.where(found, o["m"][j], mn_c)
                            vn_c = jnp.where(found, o["v"][j], vn_c)
                            if o["mw"][j] is not None:
                                out = jnp.where(found, o["mw"][j], out)
                        new_o["p"].append(out_p)
                        new_o["m"].append(mn_c)
                        new_o["v"].append(vn_c)
                        new_o["mw"].append(out if o["mw"][j] is not None
                                           else None)

                new_state = {
                    "s": {"p": list(nP), "m": list(nM), "v": list(nV),
                          "mw": list(nMW)},
                    "o": new_o,
                    "buf": state["buf"],
                    "step": (t if found is None
                             else jnp.where(found, state["step"], t)),
                }
                if guard is not None:
                    new_state["guard"] = guard.update(gst, found)
                if not nm:
                    return loss, new_state
                # ---- the [C+1, NFIELDS] numerics block (ISSUE 15):
                # grad rows come from the clip/guard pre-pass when it
                # ran (shared reductions), else from the update
                # backward; act rows rode the forward scan's ys
                from ..observability import numerics as _num

                g_cols = (grad_rows if grad_rows is not None
                          else bwd_ys["g"])            # [C, 3]
                g_sq, g_bad, g_orig = (g_cols[:, 0], g_cols[:, 1],
                                       g_cols[:, 2])
                og_sq = o_g_sq
                if inv_s is not None:
                    s2 = inv_s * inv_s       # grads carried the scale
                    g_sq = g_sq * s2
                    og_sq = og_sq * s2
                stats = _num.assemble_stats(
                    g_sq, bwd_ys["pu"][:, 0], bwd_ys["pu"][:, 1],
                    act_cols[:, 0], act_cols[:, 1], g_bad,
                    act_cols[:, 2], g_orig,
                    outer=_num.outer_row(
                        og_sq, o_p_sq, o_u_sq,
                        (~jnp.isfinite(o_g_sq))
                        .astype(jnp.float32)))
                return loss, new_state, stats
            finally:
                seg_ctx.__exit__(None, None, None)
                self._bind(self._buffers, saved_buf)

        from .compile_cache import cached_jit

        self._jitted = cached_jit(step_fn,
                                  donate_argnums=(0,),
                                  label=type(self).__name__)

    def _pre_step(self):
        """Hook: runs at the top of __call__, before state extraction.
        The sharded-parameter-storage step folds external `p._data`
        writes (checkpoint restore, test poking) back into its 1/N
        flat shards here."""

    def _step_guard(self):
        """Hook: context wrapping the compiled-step dispatch (and its
        first-call trace). The sharded-parameter-storage step returns
        its raw-access guard so `_bind`'s tracer shuffling through the
        live Parameters bypasses the lazy shard machinery."""
        import contextlib

        return contextlib.nullcontext()

    def ensure_built(self):
        """Create the Adam state and trace the step (idempotent). Split
        out so diagnostics can AOT-lower the program (memory_analysis)
        without executing a step. warmup_state's dry-run is NOT used: it
        would eagerly execute the whole layer-chunked update chain —
        ~1.7k pointless eager dispatches at 1.3b."""
        if self._jitted is not None:
            return
        opt = self._opt
        for p in self._s_params + [p for _, p in self._o_params]:
            if opt._use_master(p):
                opt._master_weight(p)
            opt._get_accumulator("moment1", p, dtype=opt._moment_dtype)
            opt._get_accumulator("moment2", p, dtype=opt._moment_dtype)
        self._build()
        # live-buffer attribution (ISSUE 14): weakly tracked provider
        from ..observability.memory import live_registry

        live_registry().track(self)

    # -- telemetry surface ----------------------------------------------
    def retrace_stats(self):
        """Sentinel receipt (see TrainStep.retrace_stats)."""
        return self._sentinel.stats()

    def _cost_axis_degrees(self):
        """Mesh {axis: degree} for the per-axis comm census (None on a
        single chip; the sharded subclass reports its mesh)."""
        return None

    def cost_analysis(self, ids, labels, segment_ids=None):
        """HLO-derived per-step accounting: ``compiled.cost_analysis``
        flops/bytes + per-mesh-axis collective byte census, published
        as ``hlo.*`` registry gauges (ISSUE 12)."""
        from ..observability.hlo_costs import cost_analysis_of

        ids_d = ids._data if isinstance(ids, Tensor) else ids
        lab_d = labels._data if isinstance(labels, Tensor) else labels
        seg_d = (segment_ids._data if isinstance(segment_ids, Tensor)
                 else segment_ids)
        self.ensure_built()
        self._pre_step()
        state = self._extract_state()
        lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        with self._step_guard():
            return cost_analysis_of(
                self._jitted, state, lr, ids_d, lab_d, seg_d,
                axis_degrees=self._cost_axis_degrees())

    def memory_profile(self, ids, labels, segment_ids=None, top_k=8,
                       publish=True):
        """Compiled-step HBM accounting (ISSUE 14): AOT buffer-
        assignment stats of THIS step's compiled program — peak /
        argument / output / temp / alias bytes plus the top-K largest
        buffers with shapes and op provenance — without executing a
        step (see TrainStep.memory_profile). Published as
        ``mem.compiled.<step>.*`` gauges."""
        from ..observability.memory import CompiledMemoryProfile

        ids_d = ids._data if isinstance(ids, Tensor) else ids
        lab_d = labels._data if isinstance(labels, Tensor) else labels
        seg_d = (segment_ids._data if isinstance(segment_ids, Tensor)
                 else segment_ids)
        self.ensure_built()
        self._pre_step()
        state = self._extract_state()
        lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        with self._step_guard():
            prof = CompiledMemoryProfile.from_jitted(
                self._jitted, state, lr, ids_d, lab_d, seg_d,
                top_k=top_k)
        if publish:
            prof.publish(name=type(self).__name__)
        return prof

    def _opt_state_arrays(self):
        """Every optimizer-state array this step's update touches
        (flat moment buckets + master weights) — ONE collection
        implementation shared by both storage modes' attribution."""
        opt = self._opt
        acc = []
        for store in opt._accumulators.values():
            acc.extend(store.values())
        acc.extend(v for v in opt._master_weights.values()
                   if v is not None)
        return acc

    def _mem_owners(self):
        """Live-buffer attribution providers (observability.memory):
        params, flat optimizer-state buckets, model buffers. The
        sharded-parameter-storage subclass overrides the param leg so
        a scrape never gathers a shard."""
        return {"params": [p._data for p in self._s_params]
                + [p._data for _, p in self._o_params],
                "buffers": [b._data for b in self._buffers],
                "opt_state": self._opt_state_arrays()}

    def __call__(self, ids, labels, segment_ids=None):
        with RecordEvent("paddle_tpu.step", step=self._calls):
            return self._call(ids, labels, segment_ids)

    def _call(self, ids, labels, segment_ids):
        n = self._calls
        self._calls = n + 1
        ids_d = ids._data if isinstance(ids, Tensor) else ids
        lab_d = labels._data if isinstance(labels, Tensor) else labels
        seg_d = (segment_ids._data if isinstance(segment_ids, Tensor)
                 else segment_ids)
        if self._jitted is None:
            self.ensure_built()
        self._pre_step()
        if not self._canon_done:
            # first call AFTER any restore (ensure_built may predate it,
            # quickstart order): a restored checkpoint leaves the params
            # device-committed while fresh scalars are uncommitted, which
            # would key one extra executable on the second call
            # (train_step._commit_uncommitted)
            canon = _commit_uncommitted(self._extract_state())
            if canon is not None:
                self._inject_state(canon)
            self._canon_done = True
        with RecordEvent("paddle_tpu.step.extract_state", step=n):
            state = self._extract_state()
        with RecordEvent("paddle_tpu.step.lr", step=n):
            lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        with RecordEvent("paddle_tpu.step.sentinel", step=n):
            self._sentinel.observe(
                (state, lr, ids_d, lab_d, seg_d),
                names=("state", "lr", "ids", "labels", "segment_ids"))
        from ..observability.memory import oom_guard as _oom_guard

        with self._step_guard(), \
                _oom_guard(
                    step=type(self).__name__,
                    profile=lambda: self.memory_profile(
                        ids_d, lab_d, seg_d, publish=False)), \
                RecordEvent("paddle_tpu.step.dispatch", step=n):
            out = self._jitted(state, lr, ids_d, lab_d, seg_d)
        if self._numerics is not None:
            loss, new_state, nstats = out
            # deferred: the device block is enqueued, never read here —
            # the readback happens at the next gauge/endpoint flush
            self._numerics.on_step(nstats)
        else:
            loss, new_state = out
        with RecordEvent("paddle_tpu.step.inject_state", step=n):
            self._inject_state(new_state)
            sched = getattr(self._opt, "_learning_rate", None)
            if hasattr(sched, "step"):
                sched.step()
        return Tensor._wrap(loss)
