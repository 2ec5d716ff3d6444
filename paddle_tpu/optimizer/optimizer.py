"""Optimizer base class.

Reference parity: python/paddle/optimizer/optimizer.py (grad clip, regularizer,
multi-precision master weights) with fused phi kernels
(paddle/phi/kernels/gpu/adamw_kernel.cu) replaced by jnp update rules that XLA
fuses into one kernel per parameter; the jit train-step path fuses across
parameters too.
"""
from __future__ import annotations

from contextlib import contextmanager as _contextmanager

import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..framework import no_grad
from .lr import LRScheduler


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        self._learning_rate = learning_rate
        # param groups (reference optimizer.py:140: list of dicts whose
        # 'learning_rate' is a SCALE of the base lr and whose
        # 'weight_decay' overrides the optimizer default for that group) —
        # flattened here; per-param attrs carry the overrides
        self._lr_scale = 1.0
        # group overrides live on THIS optimizer (keyed by param), never on
        # the param objects — params outlive optimizers, and stale attrs
        # would leak group settings into later optimizers over the same
        # params. ParamAttr(learning_rate=...) on the param itself remains
        # the per-param fallback.
        self._group_lr_scale = {}
        self._group_wd = {}
        if parameters is not None:
            flat = []
            for entry in parameters:
                if isinstance(entry, dict):
                    group_params = list(entry["params"])
                    for p in group_params:
                        k = p.name or str(id(p))
                        if "learning_rate" in entry:
                            self._group_lr_scale[k] = float(
                                entry["learning_rate"])
                        if "weight_decay" in entry:
                            wd = entry["weight_decay"]
                            self._group_wd[k] = (
                                float(wd) if isinstance(wd, (int, float))
                                else getattr(wd, "_coeff", 0.0))
                    flat.extend(group_params)
                else:
                    flat.append(entry)
            self._parameter_list = flat
        else:
            self._parameter_list = None
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        if isinstance(weight_decay, (int, float)):
            self._weight_decay = float(weight_decay)
        else:
            self._weight_decay = weight_decay  # None or regularizer-like
        self._accumulators = {}  # name -> {param_name: jax array}
        self._master_weights = {}  # param_name -> fp32 jax array
        self._step_count = 0
        # traced-step protocol fields (see the "traced-step protocol"
        # section): a frozen lr tracer and the dry-run switch
        self._lr_override = None
        self._dry_run = False

    # -- lr ----------------------------------------------------------------
    def get_lr(self):
        if self._lr_override is not None:
            return self._lr_override
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate.get_lr()
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # -- accumulators -------------------------------------------------------
    def _get_accumulator(self, name, param, init=None, dtype=None):
        store = self._accumulators.setdefault(name, {})
        key = param.name or str(id(param))
        if key not in store:
            d = dtype or (jnp.float32 if self._use_master(param) else param._data.dtype)
            store[key] = jnp.zeros(param._data.shape, d) if init is None else init
        return store[key]

    def _set_accumulator(self, name, param, value):
        if self._dry_run:
            return
        key = param.name or str(id(param))
        self._accumulators[name][key] = value

    def _use_master(self, param):
        return self._multi_precision and param._data.dtype in (jnp.float16, jnp.bfloat16)

    def _master_weight(self, param):
        key = param.name or str(id(param))
        if key not in self._master_weights:
            self._master_weights[key] = param._data.astype(jnp.float32)
        return self._master_weights[key]

    def _write_param(self, param, new_value_f32_or_native):
        if self._dry_run:
            return
        if self._use_master(param):
            key = param.name or str(id(param))
            self._master_weights[key] = new_value_f32_or_native
        param._data = new_value_f32_or_native.astype(param._data.dtype)

    def _param_value(self, param):
        if self._use_master(param):
            return self._master_weight(param)
        return param._data

    # -- step ----------------------------------------------------------------
    def _collect_params_grads(self):
        if self._parameter_list is None:
            raise ValueError(
                "optimizer was created without a parameter list; pass parameters="
            )
        pgs = []
        for p in self._parameter_list:
            if p.stop_gradient or p.grad is None:
                continue
            pgs.append((p, p.grad))
        return pgs

    def _param_lr_scale(self, p):
        k = p.name or str(id(p))
        if k in self._group_lr_scale:
            return self._group_lr_scale[k]
        return (getattr(p, "optimize_attr", None) or {}).get(
            "learning_rate", 1.0)

    def _param_group_wd(self, p):
        return self._group_wd.get(p.name or str(id(p)))

    def _cur_lr(self):
        """Base lr times the current param's group scale (set by step())."""
        lr = self.get_lr()
        return lr * self._lr_scale if self._lr_scale != 1.0 else lr

    def _apply_decay(self, param, grad_data):
        """L2 regularization folded into the gradient (reference: the
        regularizer path in optimizer.py; AdamW overrides with decoupled decay)."""
        wd = self._param_group_wd(param)
        if wd is None:
            wd = self._weight_decay
        if wd is None:
            return grad_data
        coeff = wd if isinstance(wd, float) else getattr(wd, "_coeff", 0.0)
        if coeff == 0.0 or getattr(param, "regularizer", None) is not None:
            return grad_data
        return grad_data + coeff * self._param_value(param).astype(grad_data.dtype)

    @no_grad()
    def step(self):
        params_grads = self._collect_params_grads()
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        self._step_count += 1
        if self._maybe_fused_step(params_grads):
            return
        for p, g in params_grads:
            g_data = g._data if isinstance(g, Tensor) else g
            if self._use_master(p) and not getattr(p, "layer_stacked",
                                                   False):
                # layer-stacked params skip the whole-stack fp32 upcast:
                # their update is layer-chunked (adam _adam_math upcasts
                # per slice) and a [L, ...] fp32 grad temp OOMs at 1.3b
                g_data = g_data.astype(jnp.float32)
            g_data = self._apply_decay(p, g_data)
            self._lr_scale = self._param_lr_scale(p)
            try:
                self._append_optimize_op(p, g_data)
            finally:
                self._lr_scale = 1.0

    def _maybe_fused_step(self, params_grads):
        """Subclass hook: apply ALL param updates as one jitted program (the
        reference's multi_tensor_adam, python/paddle/optimizer/adam.py
        `use_multi_tensor`). Return True when handled. Base: per-param path."""
        return False

    def _append_optimize_op(self, param, grad_data):
        raise NotImplementedError

    @no_grad()
    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        self.step()
        return None, None

    def clear_grad(self, set_to_zero=True):
        if self._parameter_list is not None:
            for p in self._parameter_list:
                if isinstance(p, Tensor):
                    p.clear_grad()

    clear_gradients = clear_grad

    # -- traced-step protocol (the TrainStep contract) ----------------------
    # TrainStep compiles step() into one XLA program by threading ALL
    # numeric optimizer state through the traced function. The contract a
    # subclass must keep for that to work:
    #   * every mutable numeric value lives in `_accumulators`,
    #     `_master_weights`, or `_step_count` (exposed by
    #     `opt_state_pytree`); NAdam's mu_product shows the pattern for
    #     extra scalars — store them in the accumulator dicts.
    #   * `warmup_state(params)` must create every accumulator the real
    #     step will touch, without changing values — the default runs the
    #     update ops with writes disabled (`_dry_run`), so subclasses that
    #     use `_get_accumulator`/`_set_accumulator`/`_write_param` get it
    #     for free. Override it only for exotic state.
    #   * `get_lr()` must respect `_lr_override` (call super or check the
    #     field) so the step's lr can be a traced input.

    def opt_state_pytree(self):
        """The numeric state threaded through a compiled train step."""
        accum = {
            name: {k: v for k, v in per.items()}
            for name, per in self._accumulators.items()
        }
        return {
            "accumulators": accum,
            "master_weights": dict(self._master_weights),
            "step": jnp.asarray(self._step_count, jnp.int32),
        }

    def load_opt_state_pytree(self, state):
        for name, per in state["accumulators"].items():
            self._accumulators.setdefault(name, {}).update(per)
        self._master_weights.update(state["master_weights"])
        self._step_count = state["step"]

    def warmup_state(self, params):
        """Create (at init values) every accumulator/master weight that
        step() will use for `params`, mutating nothing else."""
        self._dry_run = True
        try:
            for p in params:
                if self._use_master(p):
                    self._master_weight(p)
                pv = self._param_value(p)
                self._append_optimize_op(p, jnp.zeros(pv.shape, pv.dtype))
        finally:
            self._dry_run = False

    @_contextmanager
    def lr_frozen(self, lr):
        """Context: step() sees `lr` (typically a traced scalar) from
        get_lr() — the reference's LRScheduler stays host-side."""
        prev = self._lr_override
        self._lr_override = lr
        try:
            yield
        finally:
            self._lr_override = prev

    # -- state dict -----------------------------------------------------------
    def state_dict(self):
        import numpy as np

        state = {"accumulators": {}, "master_weights": {}, "step": self._step_count}
        for name, store in self._accumulators.items():
            state["accumulators"][name] = {k: np.asarray(v) for k, v in store.items()}
        state["master_weights"] = {
            k: np.asarray(v) for k, v in self._master_weights.items()
        }
        if isinstance(self._learning_rate, LRScheduler):
            state["LR_Scheduler"] = self._learning_rate.state_dict()
        return state

    def set_state_dict(self, state_dict):
        for name, store in state_dict.get("accumulators", {}).items():
            tgt = self._accumulators.setdefault(name, {})
            for k, v in store.items():
                tgt[k] = jnp.asarray(v)
        for k, v in state_dict.get("master_weights", {}).items():
            self._master_weights[k] = jnp.asarray(v)
        self._step_count = state_dict.get("step", 0)
        if "LR_Scheduler" in state_dict and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])

    load_state_dict = set_state_dict
