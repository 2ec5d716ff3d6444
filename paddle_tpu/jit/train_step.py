"""Whole-step compilation of the eager dygraph tape.

This is the TPU answer to the reference's per-op dispatch hot loop
(SURVEY.md §3.1, §7 "hard parts: eager-on-XLA latency"): instead of launching
one XLA computation per op like Paddle launches one CUDA kernel per op, the
entire train step — forward, tape backward, grad clip, optimizer update —
is traced ONCE into a single jitted function over a state pytree, then
executed as one fused XLA program per step with donated buffers.

It works because the eager engine is already trace-transparent: `Tensor._data`
is a jax value, every op is a jnp call recorded through `jax.vjp`, and the
optimizer's update rules are jnp expressions. We thread all mutable state
(parameters, buffers, optimizer accumulators, master weights, step count, RNG
offset) through the traced function as explicit inputs/outputs, temporarily
binding tracers into the live objects during tracing.

Reference parity: replaces the roles of StandaloneExecutor/PirInterpreter
(paddle/fluid/framework/new_executor/pir_interpreter.h:32) and the CINN
compiler entry (paddle/fluid/pir/transforms/build_cinn_pass.cc) — XLA is the
compiler, PJRT the executor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..framework import random as _random
from ..observability import RetraceSentinel
from ..profiler import RecordEvent


def _tree_data(x):
    """Map Tensors (possibly nested in lists/tuples/dicts) to jax arrays."""
    if isinstance(x, Tensor):
        return x._data
    if isinstance(x, (list, tuple)):
        return type(x)(_tree_data(v) for v in x)
    if isinstance(x, dict):
        return {k: _tree_data(v) for k, v in x.items()}
    return x


def _tree_wrap(x):
    if isinstance(x, jax.Array):
        return Tensor._wrap(x)
    if isinstance(x, (list, tuple)):
        return type(x)(_tree_wrap(v) for v in x)
    if isinstance(x, dict):
        return {k: _tree_wrap(v) for k, v in x.items()}
    return x


def _commit_uncommitted(state):
    """Single-device flavor of the layout canonicalization: a checkpoint
    restore leaves the params committed to their device while freshly
    created scalars (guard state, rng offset, step count) are uncommitted.
    jit keys committed and uncommitted arguments differently, and every
    output of the first call comes back committed — so the second call
    after a restore would compile one extra executable. Returns the state
    with the uncommitted leaves committed to the same device, or None when
    nothing is committed (fresh run: leave everything uncommitted, jit
    outputs then stay uncommitted too and the cache key is stable)."""
    leaves = [l for l in jax.tree_util.tree_leaves(state)
              if isinstance(l, jax.Array)]
    dev = next((next(iter(l.devices())) for l in leaves
                if getattr(l, "_committed", False)), None)
    if dev is None or not all(
            len(l.devices()) == 1 for l in leaves):   # mesh programs: no-op
        return None

    def _commit(leaf):
        if isinstance(leaf, jax.Array) and not getattr(
                leaf, "_committed", True):
            return jax.device_put(leaf, dev)
        return leaf

    return jax.tree_util.tree_map(_commit, state)


def _unwrap_optimizer(opt):
    """Follow wrapper chains (HybridParallelOptimizer, sharding wrappers) to
    the Optimizer that owns the state dicts."""
    seen = set()
    while hasattr(opt, "_inner_opt") and id(opt) not in seen:
        seen.add(id(opt))
        opt = opt._inner_opt
    return opt


class TrainStep:
    """Compile `(batch) -> loss` + backward + optimizer into one XLA program.

    Usage::

        step = TrainStep(model, loss_fn, optimizer)     # loss_fn(model, *batch)
        for batch in loader:
            loss = step(*batch)                          # one fused XLA launch

    `loss_fn(model, *batch_tensors)` must return a scalar loss Tensor. All
    batch entries with a given set of shapes/dtypes compile once (shape-keyed
    executable cache — jax.jit's own).
    """

    def __init__(self, model, loss_fn, optimizer, donate=True,
                 accumulate_steps=1, accum_steps=None, scaler=None,
                 guard_nonfinite=None, numerics=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer             # outer (may be a wrapper)
        self._opt = _unwrap_optimizer(optimizer)  # state owner
        # in-graph non-finite guard (jit/nonfinite_guard.py): gate the
        # whole state update on a traced found_inf so one NaN/inf step
        # cannot destroy the only copy of the donated params; a bound
        # GradScaler additionally runs its dynamic loss scale as traced
        # state (zero host syncs, zero retraces)
        from .nonfinite_guard import GuardSpec

        self._guard = (GuardSpec(scaler)
                       if (scaler is not None or guard_nonfinite)
                       else None)

        self._params = None   # resolved lazily: optimizer may create accums on 1st step
        self._buffers = None
        self._jitted = None
        self._step_count = 0
        # training-numerics observatory (ISSUE 15): the generic tape
        # path has no layer chunks, so each trainable PARAMETER is its
        # own stats row (grad/param sq-norm, update ratio, finite flag;
        # no scanned activations). Monitor built lazily in _build once
        # the param set is resolved.
        self._numerics_opt = numerics
        self._numerics = None
        # retrace sentinel (ISSUE 12): every dispatch records its
        # abstract signature; an unexpected executable-cache miss is
        # attributed to the argument leaf that changed
        self._sentinel = RetraceSentinel(type(self).__name__)
        self._donate = donate
        # gradient accumulation INSIDE the fused program (the reference's
        # no_sync/gradient-merge loop, compiled): the batch's dim 0 splits
        # into `accumulate_steps` micro-batches; micro backwards accumulate
        # on the tape's leaf grads and the optimizer steps once. Gradient
        # COMM happens only at the boundary: after the last microbatch the
        # model wrapper's apply_collective_grads() issues the bucket
        # collectives (stage-2 bucketer), so under GSPMD the per-bucket
        # reduce-scatters overlap the optimizer/next-step compute instead
        # of serializing after every microbatch.
        if accum_steps is not None:
            if int(accumulate_steps) not in (1, int(accum_steps)):
                raise ValueError(
                    f"conflicting accumulate_steps={accumulate_steps} "
                    f"and accum_steps={accum_steps}")
            accumulate_steps = accum_steps
        self.accumulate_steps = int(accumulate_steps)

    # -- input pipeline -------------------------------------------------
    def input_sharding(self):
        """The placement batches should be staged on so the compiled step
        never reshards its inputs: on a dp/sharding mesh, dim 0 split 1/N
        over the data axis; on one chip, None (default-device placement —
        identical to what `paddle.to_tensor` produces, so prefetched and
        hand-fed batches hit the same executable)."""
        from jax.sharding import NamedSharding

        from ..distributed import env as denv

        mesh = next(
            (p._data.sharding.mesh for p in self.model.parameters()
             if isinstance(getattr(p._data, "sharding", None),
                           NamedSharding)), None)
        if mesh is None:
            return None
        return denv.data_sharding(mesh=mesh)

    def prefetch(self, loader, depth=2, **kw):
        """Wrap `loader` in an `io.DevicePrefetcher` bound to this step's
        input sharding — batches land on device, already placed, while
        the previous step computes (zero-stall input delivery)::

            step = TrainStep(model, loss_fn, opt)
            for ids, labels in step.prefetch(loader):
                loss = step(ids, labels)
        """
        from ..io.device_prefetcher import DevicePrefetcher

        kw.setdefault("sharding", self.input_sharding())
        return DevicePrefetcher(loader, depth=depth, **kw)

    # -- state plumbing -------------------------------------------------
    def _resolve_slots(self):
        self._params = [p for p in self.model.parameters() if p.trainable]
        self._buffers = list(self.model.buffers())

    def _extract_state(self):
        state = {
            "params": [p._data for p in self._params],
            "buffers": [b._data for b in self._buffers],
            "opt": self._opt.opt_state_pytree(),
            "rng_offset": jnp.asarray(_random.default_generator()._offset, jnp.int64
                                      if jax.config.jax_enable_x64 else jnp.int32),
        }
        if self._guard is not None:
            state["guard"] = self._guard.init_state()
        return state

    def _inject_state(self, state):
        for p, d in zip(self._params, state["params"]):
            p._data = d
        for b, d in zip(self._buffers, state["buffers"]):
            b._data = d
        self._opt.load_opt_state_pytree(state["opt"])
        _random.default_generator()._offset = state["rng_offset"]
        if self._guard is not None and "guard" in state:
            self._guard.writeback(state["guard"])

    # -- the traced step ------------------------------------------------
    def _build(self, example_batch):
        self._resolve_slots()
        opt = self.optimizer        # outer wrapper drives the step
        inner = self._opt           # state owner gets the lr patch
        from ..observability.numerics import (
            NumericsMonitor, monitor_enabled,
        )

        if (bool(self._numerics_opt) if self._numerics_opt is not None
                else monitor_enabled()) and self._params:
            self._numerics = NumericsMonitor(
                type(self).__name__, len(self._params),
                row_labels=[p.name or f"param{i}"
                            for i, p in enumerate(self._params)])
        nm = self._numerics is not None

        # pin state OUTPUT layouts to the input layouts: without this,
        # GSPMD may choose a different sharding for an updated param than
        # the one the user placed, so call 2 sees new input layouts and
        # recompiles (one stray executable per divergent layout)
        from jax.sharding import NamedSharding, PartitionSpec

        # ... and canonicalize the INPUT layouts first: on a mesh program
        # every output lands mesh-committed, so any state leaf that starts
        # uncommitted/single-device (fresh optimizer scalars, rng offset)
        # would key one extra executable on call 2. Replicate those onto
        # the params' mesh up front.
        mesh = next((p._data.sharding.mesh for p in self._params
                     if isinstance(getattr(p._data, "sharding", None),
                                   NamedSharding)), None)
        if mesh is not None:
            def _canon(leaf):
                if not isinstance(leaf, jax.Array):
                    return leaf
                sh = getattr(leaf, "sharding", None)
                if not isinstance(sh, NamedSharding):
                    return jax.device_put(leaf, NamedSharding(
                        mesh, PartitionSpec()))
                # normalize trailing Nones: P('mp', None) and P('mp')
                # are the same placement but UNEQUAL jit cache keys, and
                # compiled outputs come back in the stripped form
                axes = list(sh.spec)
                while axes and axes[-1] is None:
                    axes.pop()
                norm = PartitionSpec(*axes)
                if norm != sh.spec:
                    return jax.device_put(leaf,
                                          NamedSharding(sh.mesh, norm))
                return leaf

            canon_state = jax.tree_util.tree_map(_canon,
                                                 self._extract_state())
            self._inject_state(canon_state)
        else:
            canon_state = _commit_uncommitted(self._extract_state())
            if canon_state is not None:
                self._inject_state(canon_state)

        ref_state = self._extract_state()
        ref_shardings = jax.tree_util.tree_map(
            lambda leaf: leaf.sharding
            if isinstance(leaf, jax.Array)
            and isinstance(getattr(leaf, "sharding", None), NamedSharding)
            else None, ref_state)

        def _repin(new_state):
            return jax.tree_util.tree_map(
                lambda leaf, sh: jax.lax.with_sharding_constraint(leaf, sh)
                if sh is not None else leaf,
                new_state, ref_shardings)

        acc = self.accumulate_steps
        if acc > 1:
            # every top-level batch Tensor splits along dim 0; a mixed bag
            # of batch-major tensors and e.g. [seq, seq] masks would be
            # silently mis-sliced, so insist on one shared batch size
            sizes = {d.shape[0] for d in example_batch
                     if hasattr(d, "shape") and d.ndim > 0}
            if len(sizes) > 1:
                raise ValueError(
                    f"accumulate_steps={acc} needs all batch tensors "
                    f"batch-major with one shared dim-0 size; got {sizes}")
            if sizes and next(iter(sizes)) % acc:
                raise ValueError(
                    f"batch size {next(iter(sizes))} is not divisible by "
                    f"accumulate_steps={acc}")

        guard = self._guard
        scaling = guard is not None and guard.scaling

        def step_fn(state, lr, batch):
            self._inject_state(state)
            gst = state.get("guard")
            scale_t = gst["scale"] if scaling else None
            batch_t = _tree_wrap(batch)

            def backward(loss_tensor):
                # dynamic loss scaling: backward through loss*scale, so
                # small bf16 grads survive; the unscale happens on the
                # grads below, fused into the same program
                if scale_t is None:
                    loss_tensor.backward()
                else:
                    (loss_tensor
                     * Tensor._wrap(scale_t.astype(
                         loss_tensor._data.dtype))).backward()

            if acc > 1:
                losses = []
                for m in range(acc):
                    micro = [
                        Tensor._wrap(t._data.reshape(
                            (acc, t._data.shape[0] // acc)
                            + tuple(t._data.shape[1:]))[m])
                        if isinstance(t, Tensor) else t for t in batch_t]
                    with jax.named_scope("forward"):
                        ml = self.loss_fn(self.model, *micro) * (1.0 / acc)
                    with jax.named_scope("backward"):
                        backward(ml)
                    losses.append(ml._data)
                loss = Tensor._wrap(sum(losses))
            else:
                with jax.named_scope("forward"):
                    loss = self.loss_fn(self.model, *batch_t)
                with jax.named_scope("backward"):
                    backward(loss)
            # gradient-comm boundary: all microbatch backwards are done,
            # flush the deferred bucket collectives (one per bucket)
            sync = getattr(self.model, "apply_collective_grads", None)
            if callable(sync):
                with jax.named_scope("backward"):
                    sync()
            # the in-graph guard: ONE fused finiteness reduction over
            # the (still scaled) grads; unscale in the same program
            found = None
            if guard is not None:
                from .nonfinite_guard import all_finite

                grads = [p.grad._data for p in self._params
                         if p.grad is not None]
                found = ~all_finite(grads)
                if scale_t is not None:
                    inv = 1.0 / scale_t
                    for p in self._params:
                        if p.grad is None:
                            continue
                        g = p.grad._data
                        p.grad._data = (g.astype(jnp.float32)
                                        * inv).astype(g.dtype)
            # numerics rows read the (unscaled) tape grads — captured
            # before opt.step()/clear_grad consumes them
            nm_grads = None
            if nm:
                nm_grads = [p.grad._data if p.grad is not None else None
                            for p in self._params]
            # freeze lr at the traced scalar for this step (declared
            # protocol: Optimizer.get_lr honors _lr_override)
            with inner.lr_frozen(lr), jax.named_scope("optimizer"):
                if inner.get_lr() is not lr:
                    raise RuntimeError(
                        f"{type(inner).__name__}.get_lr() ignores "
                        "_lr_override — it would bake a stale host lr "
                        "into the compiled step; honor the traced-step "
                        "protocol (call super().get_lr() or check "
                        "self._lr_override)")
                opt.step()
            opt.clear_grad()
            new_state = _repin(self._extract_state())
            if guard is not None:
                from .nonfinite_guard import gate

                core = {k: v for k, v in new_state.items()
                        if k != "guard"}
                old = {k: v for k, v in state.items() if k != "guard"}
                new_state = gate(found, core, old)
                new_state["guard"] = guard.update(gst, found)
            if not nm:
                return loss._data, new_state
            # ---- per-parameter numerics rows (ISSUE 15): grads were
            # unscaled above, updates read the GATED new params (zero
            # on a guard-skipped step); no scanned activations here
            with jax.named_scope("optimizer"), jax.named_scope("numerics"):
                rows = []
                f32 = jnp.float32
                for i in range(len(self._params)):
                    g = nm_grads[i]
                    old_p = state["params"][i].astype(f32)
                    new_p = new_state["params"][i].astype(f32)
                    if g is not None and jnp.issubdtype(g.dtype,
                                                        jnp.floating):
                        g32 = g.astype(f32)
                        g_sq = jnp.sum(jnp.square(g32))
                        # finiteness DERIVES from the square-sum like the
                        # scan paths (DECISIONS §21) — no second O(params)
                        # pass; the guard keeps its own exact fold
                        g_bad = (~jnp.isfinite(g_sq)).astype(f32)
                    else:
                        g_sq = f32(0.0)
                        g_bad = f32(0.0)
                    rows.append(jnp.stack([
                        g_sq, jnp.sum(jnp.square(old_p)),
                        jnp.sum(jnp.square(new_p - old_p)),
                        f32(0.0), f32(0.0), g_bad, f32(0.0), f32(0.0)]))
            return loss._data, new_state, jnp.stack(rows)

        donate = (0,) if self._donate else ()
        # persistent AOT executable cache (ISSUE 17): with
        # PADDLE_TPU_COMPILE_CACHE set, a warm process deserializes the
        # previously compiled step instead of retracing+recompiling;
        # unset, this IS jax.jit
        from .compile_cache import cached_jit

        self._jitted = cached_jit(step_fn, donate_argnums=donate,
                                  label=type(self).__name__)
        # live-buffer attribution (ISSUE 14): params/opt-state/buffers
        # claim their resident bytes at mem.live scrape time (weakly
        # tracked — a dropped step stops claiming)
        from ..observability.memory import live_registry

        live_registry().track(self)

    def __call__(self, *batch):
        with RecordEvent("paddle_tpu.step", step=self._step_count):
            return self._call(batch)

    def _call(self, batch):
        n = self._step_count
        batch_data = _tree_data(list(batch))
        if self._jitted is None:
            # the global generator offset may be a device array committed to
            # another step's mesh (jit outputs rebind it); a foreign sharding
            # on the first call would key one extra executable, so drop the
            # commitment before the initial trace
            gen = _random.default_generator()
            if isinstance(gen._offset, jax.Array):
                gen._offset = int(gen._offset)
            # run optimizer accumulator creation eagerly once so the state
            # pytree is complete before tracing (Optimizer.warmup_state —
            # the declared dry-run protocol)
            self._warmup_accumulators()
            self._build(batch_data)
        with RecordEvent("paddle_tpu.step.extract_state", step=n):
            state = self._extract_state()
        with RecordEvent("paddle_tpu.step.lr", step=n):
            lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        with RecordEvent("paddle_tpu.step.sentinel", step=n):
            self._sentinel.observe((state, lr, batch_data),
                                   names=("state", "lr", "batch"))
        try:
            # comm watchdog (reference comm_task_manager.h:37): the dispatch
            # blocks when the device queue is full behind a dead collective,
            # so guard it — without forcing a sync that would break async
            # dispatch pipelining
            from ..distributed import comm_watchdog

            with comm_watchdog.watch(f"TrainStep#{n}"), \
                    RecordEvent("paddle_tpu.step.dispatch", step=n):
                out = self._jitted(state, lr, batch_data)
            if self._numerics is not None:
                loss_data, new_state, nstats = out
                self._numerics.on_step(nstats)   # deferred readback
            else:
                loss_data, new_state = out
            self._step_count += 1
        except Exception as e:
            # OOM forensics (ISSUE 14): a RESOURCE_EXHAUSTED at the
            # dispatch boundary dumps the live-buffer attribution + the
            # step's compiled memory profile through the flight
            # recorder before propagating (AOT analysis — re-lowering
            # reads only avals, so consumed donated buffers are fine)
            from ..observability import memory as _mem

            if _mem.is_oom_error(e):
                _mem.dump_oom(
                    e, step=type(self).__name__,
                    profile=lambda: _mem.CompiledMemoryProfile
                    .from_jitted(self._jitted, state, lr, batch_data))
            # a tracing error leaves tracers bound in the live objects;
            # restore the concrete state so the model stays usable
            self._inject_state(state)
            raise
        with RecordEvent("paddle_tpu.step.inject_state", step=n):
            self._inject_state(new_state)
            # advance host-side schedulers
            sched = getattr(self._opt, "_learning_rate", None)
            if hasattr(sched, "step"):
                sched.step()
        return Tensor._wrap(loss_data)

    # -- telemetry surface ----------------------------------------------
    def retrace_stats(self):
        """The sentinel's receipt: {'signatures', 'calls', 'hits',
        'unexpected', 'events'} — signatures is the trace/compile count
        the old hand-written probes asserted on."""
        return self._sentinel.stats()

    def cost_analysis(self, *batch):
        """HLO-derived per-step accounting (ISSUE 12): flops and bytes
        per step from ``compiled.cost_analysis()`` plus the per-axis
        collective byte census, published as ``hlo.*`` registry gauges.
        Requires the step to have run (or at least traced) once."""
        if self._jitted is None:
            raise RuntimeError(
                "cost_analysis needs a built step — call the step once "
                "(or warm it up) first")
        from ..observability.hlo_costs import cost_analysis_of

        state = self._extract_state()
        lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        return cost_analysis_of(self._jitted, state, lr,
                                _tree_data(list(batch)))

    def memory_profile(self, *batch, top_k=8, publish=True):
        """Compiled-step HBM accounting (ISSUE 14): AOT lower+compile
        this step for ``batch`` and read the XLA buffer-assignment
        stats — argument/output/temp/alias bytes, the peak they imply,
        and the top-K largest buffers with shapes and op provenance —
        WITHOUT executing anything. Publishes ``mem.compiled.<step>.*``
        gauges; with the persistent compile cache warm this is cheap.
        Requires the step to have run (or at least traced) once."""
        if self._jitted is None:
            raise RuntimeError(
                "memory_profile needs a built step — call the step "
                "once (or warm it up) first")
        from ..observability.memory import CompiledMemoryProfile

        state = self._extract_state()
        lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        prof = CompiledMemoryProfile.from_jitted(
            self._jitted, state, lr, _tree_data(list(batch)),
            top_k=top_k)
        if publish:
            prof.publish(name=type(self).__name__)
        return prof

    def _mem_owners(self):
        """Live-buffer attribution providers (observability.memory):
        which resident arrays this step's state accounts for."""
        if self._params is None:
            self._resolve_slots()
        # shard-backed params (owned by a sharded-storage scan step)
        # are skipped: reading them would gather on scrape
        owners = {"params": [p._data for p in self._params
                             if not getattr(type(p), "_shard_backed",
                                            False)],
                  "buffers": [b._data for b in self._buffers]}
        try:
            owners["opt_state"] = jax.tree_util.tree_leaves(
                self._opt.opt_state_pytree())
        except Exception:
            pass
        return owners

    def _warmup_accumulators(self):
        """Complete the optimizer state pytree before tracing via the
        declared Optimizer.warmup_state protocol (no monkeypatching — a
        subclass overriding step()/_append_optimize_op keeps working as
        long as it honors the traced-step protocol, optimizer.py)."""
        self._resolve_slots()
        self._opt.warmup_state(self._params)
        # sharded-optimizer wrappers place their state layouts now so the
        # first compile already sees them (ZeRO-1 as sharding annotations)
        outer = self.optimizer
        while outer is not self._opt:
            if hasattr(outer, "reshard_state"):
                outer.reshard_state()
            outer = getattr(outer, "_inner_opt", self._opt)
