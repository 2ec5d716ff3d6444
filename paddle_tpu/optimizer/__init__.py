"""Optimizers (python/paddle/optimizer/ parity: 14+ optimizers).

Update rules are jnp expressions — XLA fuses each into a single fused kernel
(the analog of the reference's fused CUDA optimizer kernels, e.g.
paddle/phi/kernels/gpu/adamw_kernel.cu).
"""
from __future__ import annotations

import jax.numpy as jnp

from .optimizer import Optimizer
from . import lr  # noqa: F401


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        self._write_param(p, self._param_value(p) - lr_v * g)


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        v = self._get_accumulator("velocity", p)
        v_new = self._momentum * v + g
        self._set_accumulator("velocity", p, v_new)
        if self._nesterov:
            update = g + self._momentum * v_new
        else:
            update = v_new
        self._write_param(p, self._param_value(p) - lr_v * update)


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=False, use_multi_tensor=None, amsgrad=False,
                 moment_dtype=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._amsgrad = amsgrad
        # multi-tensor fused update (reference: adam.py use_multi_tensor /
        # multi_tensor_adam kernels). Default ON here: in eager mode every
        # per-param jnp update is its own XLA dispatch (~10 launches x
        # n_params per step); the fused path jits ONE program over the whole
        # param set. Identical math, so unlike the reference it is the
        # default; pass use_multi_tensor=False to fall back.
        self._use_multi_tensor = (True if use_multi_tensor is None
                                  else bool(use_multi_tensor))
        self._fused_fn = None
        # TPU-first knob: store moments in a narrower dtype (e.g. "bfloat16")
        # to cut optimizer-state HBM traffic; the update math still runs in
        # fp32 (read → upcast → update → downcast-store). bf16's 8 mantissa
        # bits round away second-moment increments once (1-beta2)*g^2 falls
        # ~256x below v, so the option trades a slightly stale v for
        # bandwidth — measure before enabling at scale (PERF.md).
        from ..framework.dtype import to_jax_dtype

        self._moment_dtype = (to_jax_dtype(moment_dtype)
                              if moment_dtype is not None else None)

    def _adam_math(self, pv, g, m, v, vmax, lr, t, wd):
        """The single source of the Adam/AdamW update rule, shared by the
        per-param (traced) and multi-tensor (fused-jit) paths: all math in
        fp32; returns (new_pv, new_m, new_v, new_vmax) in fp32 — callers
        downcast to their storage dtypes. `vmax` is None unless amsgrad;
        `wd` is the decoupled (AdamW) coefficient."""
        pv32 = pv.astype(jnp.float32)
        g = g.astype(jnp.float32)
        m_new = self._beta1 * m.astype(jnp.float32) + (1 - self._beta1) * g
        v_new = self._beta2 * v.astype(jnp.float32) + (1 - self._beta2) * g * g
        m_hat = m_new / (1 - self._beta1 ** t)
        if vmax is not None:
            vmax_new = jnp.maximum(vmax.astype(jnp.float32), v_new)
            v_hat = vmax_new / (1 - self._beta2 ** t)
        else:
            vmax_new = None
            v_hat = v_new / (1 - self._beta2 ** t)
        update = m_hat / (jnp.sqrt(v_hat) + self._epsilon)
        out = pv32 * (1 - lr * wd) - lr * update
        return out, m_new, v_new, vmax_new

    def _adam_update(self, p, g, decoupled_wd=0.0):
        lr_v = self._cur_lr()
        md = self._moment_dtype
        m = self._get_accumulator("moment1", p, dtype=md)
        v = self._get_accumulator("moment2", p, dtype=md)
        vmax = (self._get_accumulator("moment2_max", p, dtype=md)
                if self._amsgrad else None)
        t = jnp.asarray(self._step_count, jnp.float32)
        lr = jnp.asarray(lr_v, jnp.float32)
        wd = jnp.float32(decoupled_wd)
        pv = self._param_value(p)
        if getattr(p, "layer_stacked", False) and pv.ndim >= 2 \
                and vmax is None:
            # layer-stacked params (scan_layers models): running the
            # update on the whole [L, ...] stack materializes whole-stack
            # fp32 temps (g/m/v upcasts + outputs ~ 4 x 4 bytes/param) —
            # measured to OOM a 16G chip at 1.3b. Update layer-by-layer
            # with in-place .at[i].set chains seeded from the CURRENT
            # buffers, so XLA aliases the donated state through the chain
            # (a lax.scan assembling fresh outputs defeats that aliasing —
            # also measured to OOM). Temps shrink by L; state traffic
            # unchanged.
            out, m_new, v_new = pv, m, v
            for i in range(pv.shape[0]):
                o_i, mn_i, vn_i, _ = self._adam_math(
                    pv[i], g[i], m[i], v[i], None, lr, t, wd)
                out = out.at[i].set(o_i.astype(pv.dtype))
                m_new = m_new.at[i].set(mn_i.astype(m.dtype))
                v_new = v_new.at[i].set(vn_i.astype(v.dtype))
            self._set_accumulator("moment1", p, m_new)
            self._set_accumulator("moment2", p, v_new)
            self._write_param(p, out)
            return
        out, m_new, v_new, vmax_new = self._adam_math(
            pv, g, m, v, vmax, lr, t, wd)
        self._set_accumulator("moment1", p, m_new.astype(m.dtype))
        self._set_accumulator("moment2", p, v_new.astype(v.dtype))
        if vmax_new is not None:
            self._set_accumulator("moment2_max", p, vmax_new.astype(vmax.dtype))
        self._write_param(p, out)

    def _append_optimize_op(self, p, g):
        self._adam_update(p, g)

    # -- multi-tensor fused step -------------------------------------------
    def _decoupled_wd(self, p):
        """AdamW's per-param decoupled decay coefficient (0 for plain Adam,
        whose L2 decay folds into the gradient instead)."""
        return 0.0

    def _l2_coeff(self, p):
        wd = self._param_group_wd(p)
        if wd is None:
            wd = self._weight_decay
        if wd is None:
            return 0.0
        coeff = wd if isinstance(wd, float) else getattr(wd, "_coeff", 0.0)
        if coeff == 0.0 or getattr(p, "regularizer", None) is not None:
            return 0.0
        return float(coeff)

    def _maybe_fused_step(self, params_grads):
        if not self._use_multi_tensor or not params_grads:
            return False
        import jax

        first = params_grads[0][1]
        d = first._data if hasattr(first, "_data") else first
        if isinstance(d, jax.core.Tracer):
            # under TrainStep's whole-step trace the per-param path is
            # traced once into the same single program anyway; a nested
            # jit would only add a fusion barrier
            return False
        if self._fused_fn is None:
            self._fused_fn = self._build_fused_fn()
        keys, pvs, gs, ms, vs, vmaxs = [], {}, {}, {}, {}, {}
        wds, l2s, lrs = {}, {}, {}
        md = self._moment_dtype
        for p, g in params_grads:
            k = p.name or str(id(p))
            keys.append((k, p))
            g_data = g._data if hasattr(g, "_data") else g
            pvs[k] = self._param_value(p)
            gs[k] = g_data.astype(jnp.float32)
            ms[k] = self._get_accumulator("moment1", p, dtype=md)
            vs[k] = self._get_accumulator("moment2", p, dtype=md)
            if self._amsgrad:
                vmaxs[k] = self._get_accumulator("moment2_max", p, dtype=md)
            wds[k] = jnp.float32(self._decoupled_wd(p))
            l2s[k] = jnp.float32(self._l2_coeff(p))
            lrs[k] = jnp.float32(self._param_lr_scale(p))
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        t = jnp.asarray(self._step_count, jnp.float32)
        new_p, new_m, new_v, new_vmax = self._fused_fn(
            pvs, gs, ms, vs, vmaxs, wds, l2s, lrs, lr, t)
        for k, p in keys:
            self._accumulators["moment1"][k] = new_m[k]
            self._accumulators["moment2"][k] = new_v[k]
            if self._amsgrad:
                self._accumulators["moment2_max"][k] = new_vmax[k]
            self._write_param(p, new_p[k])
        return True

    def _build_fused_fn(self):
        import jax

        amsgrad = self._amsgrad

        def f(pvs, gs, ms, vs, vmaxs, wds, l2s, lrs, lr, t):
            new_p, new_m, new_v, new_vmax = {}, {}, {}, {}
            for k in pvs:
                g = gs[k] + l2s[k] * pvs[k].astype(jnp.float32)
                out, m_n, v_n, vmax_n = self._adam_math(
                    pvs[k], g, ms[k], vs[k],
                    vmaxs[k] if amsgrad else None, lr * lrs[k], t, wds[k])
                new_p[k] = out.astype(pvs[k].dtype)
                new_m[k] = m_n.astype(ms[k].dtype)
                new_v[k] = v_n.astype(vs[k].dtype)
                if vmax_n is not None:
                    new_vmax[k] = vmax_n.astype(vmaxs[k].dtype)
            return new_p, new_m, new_v, new_vmax

        return jax.jit(f)


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py,
    fused kernel adamw_kernel.cu)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, lazy_mode=False,
                 multi_precision=False, amsgrad=False, moment_dtype=None,
                 use_multi_tensor=None, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision,
                         use_multi_tensor=use_multi_tensor, amsgrad=amsgrad,
                         moment_dtype=moment_dtype, name=name)
        self._wd_coeff = float(weight_decay) if weight_decay else 0.0
        self._apply_decay_param_fun = apply_decay_param_fun

    def _append_optimize_op(self, p, g):
        self._adam_update(p, g, decoupled_wd=self._decoupled_wd(p))

    def _decoupled_wd(self, p):
        if (self._apply_decay_param_fun is not None
                and not self._apply_decay_param_fun(p.name)):
            return 0.0
        gwd = self._param_group_wd(p)
        return self._wd_coeff if gwd is None else gwd

    # AdamW's decay is decoupled (applied in the update rule) — it must
    # never ALSO be L2-folded into the gradient, including param-group
    # weight_decay overrides (which _decoupled_wd above consumes)
    def _l2_coeff(self, p):
        return 0.0

    def _apply_decay(self, param, grad_data):
        return grad_data


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        m = self._get_accumulator("moment", p)
        u = self._get_accumulator("inf_norm", p)
        t = jnp.asarray(self._step_count, jnp.float32)
        m_new = self._beta1 * m + (1 - self._beta1) * g
        u_new = jnp.maximum(self._beta2 * u, jnp.abs(g))
        self._set_accumulator("moment", p, m_new)
        self._set_accumulator("inf_norm", p, u_new)
        self._write_param(
            p,
            self._param_value(p)
            - (lr_v / (1 - self._beta1 ** t)) * m_new / (u_new + self._epsilon),
        )


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._rho, self._epsilon = rho, epsilon

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        avg_sq = self._get_accumulator("avg_squared_grad", p)
        avg_up = self._get_accumulator("avg_squared_update", p)
        avg_sq_new = self._rho * avg_sq + (1 - self._rho) * g * g
        update = jnp.sqrt(avg_up + self._epsilon) / jnp.sqrt(avg_sq_new + self._epsilon) * g
        avg_up_new = self._rho * avg_up + (1 - self._rho) * update * update
        self._set_accumulator("avg_squared_grad", p, avg_sq_new)
        self._set_accumulator("avg_squared_update", p, avg_up_new)
        self._write_param(p, self._param_value(p) - lr_v * update)


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        acc = self._get_accumulator(
            "moment", p, init=jnp.full(p._data.shape, self._initial, jnp.float32)
        )
        acc_new = acc + g.astype(acc.dtype) * g.astype(acc.dtype)
        self._set_accumulator("moment", p, acc_new)
        self._write_param(
            p, self._param_value(p) - lr_v * g / (jnp.sqrt(acc_new) + self._epsilon)
        )


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        ms = self._get_accumulator("mean_square", p)
        mom = self._get_accumulator("momentum", p)
        ms_new = self._rho * ms + (1 - self._rho) * g * g
        self._set_accumulator("mean_square", p, ms_new)
        if self._centered:
            mg = self._get_accumulator("mean_grad", p)
            mg_new = self._rho * mg + (1 - self._rho) * g
            self._set_accumulator("mean_grad", p, mg_new)
            denom = jnp.sqrt(ms_new - mg_new * mg_new + self._epsilon)
        else:
            denom = jnp.sqrt(ms_new + self._epsilon)
        mom_new = self._momentum * mom + lr_v * g / denom
        self._set_accumulator("momentum", p, mom_new)
        self._write_param(p, self._param_value(p) - mom_new)


class ASGD(Optimizer):
    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._batch_num = batch_num

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        d = self._get_accumulator("d", p)
        ys = self._get_accumulator("ys", p)
        y = g  # current grad replaces the oldest in the window (window=1 simplification)
        d_new = d - ys + y
        self._set_accumulator("d", p, d_new)
        self._set_accumulator("ys", p, y)
        self._write_param(p, self._param_value(p) - (lr_v / self._batch_num) * d_new)


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._wd = lamb_weight_decay
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        m = self._get_accumulator("moment1", p)
        v = self._get_accumulator("moment2", p)
        t = jnp.asarray(self._step_count, jnp.float32)
        m_new = self._beta1 * m + (1 - self._beta1) * g
        v_new = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_accumulator("moment1", p, m_new)
        self._set_accumulator("moment2", p, v_new)
        m_hat = m_new / (1 - self._beta1 ** t)
        v_hat = v_new / (1 - self._beta2 ** t)
        pv = self._param_value(p)
        r = m_hat / (jnp.sqrt(v_hat) + self._epsilon)
        wd = 0.0 if (self._exclude_fn is not None and self._exclude_fn(p)) else self._wd
        update = r + wd * pv
        w_norm = jnp.linalg.norm(pv)
        u_norm = jnp.linalg.norm(update)
        trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        self._write_param(p, pv - lr_v * trust * update)


class NAdam(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 momentum_decay=0.004, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._psi = momentum_decay

    @property
    def _mu_product(self):
        # lives in the accumulator store so it is checkpointed by
        # state_dict and threaded through the jitted train step
        store = self._accumulators.setdefault("nadam_mu_product", {})
        if "_global" not in store:
            store["_global"] = jnp.ones((), jnp.float32)
        return store["_global"]

    @_mu_product.setter
    def _mu_product(self, value):
        self._accumulators.setdefault("nadam_mu_product", {})["_global"] = value

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        t = jnp.asarray(self._step_count, jnp.float32)
        m = self._get_accumulator("moment1", p)
        v = self._get_accumulator("moment2", p)
        mu_t = self._beta1 * (1 - 0.5 * 0.96 ** (t * self._psi))
        mu_t1 = self._beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self._psi))
        mu_prod = self._mu_product * mu_t
        m_new = self._beta1 * m + (1 - self._beta1) * g
        v_new = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_accumulator("moment1", p, m_new)
        self._set_accumulator("moment2", p, v_new)
        v_hat = v_new / (1 - self._beta2 ** t)
        update = (
            mu_t1 * m_new / (1 - mu_prod * mu_t1)
            + (1 - mu_t) * g / (1 - mu_prod)
        ) / (jnp.sqrt(v_hat) + self._epsilon)
        self._write_param(p, self._param_value(p) - lr_v * update)

    def step(self):
        super().step()
        t = jnp.asarray(self._step_count, jnp.float32)
        mu_t = self._beta1 * (1 - 0.5 * 0.96 ** (t * self._psi))
        self._mu_product *= mu_t


class RAdam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _append_optimize_op(self, p, g):
        lr_v = self._cur_lr()
        t = jnp.asarray(self._step_count, jnp.float32)
        m = self._get_accumulator("moment1", p)
        v = self._get_accumulator("moment2", p)
        m_new = self._beta1 * m + (1 - self._beta1) * g
        v_new = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_accumulator("moment1", p, m_new)
        self._set_accumulator("moment2", p, v_new)
        m_hat = m_new / (1 - self._beta1 ** t)
        rho_inf = 2 / (1 - self._beta2) - 1
        rho_t = rho_inf - 2 * t * self._beta2 ** t / (1 - self._beta2 ** t)
        # branchless: t may be a traced value inside the jitted train step
        v_hat = jnp.sqrt(v_new / (1 - self._beta2 ** t))
        r_sq = ((rho_t - 4) * (rho_t - 2) * rho_inf) / (
            (rho_inf - 4) * (rho_inf - 2) * rho_t
        )
        r = jnp.sqrt(jnp.maximum(r_sq, 0.0))
        update = jnp.where(rho_t > 5.0, r * m_hat / (v_hat + self._epsilon), m_hat)
        self._write_param(p, self._param_value(p) - lr_v * update)


class Rprop(Optimizer):
    def __init__(self, learning_rate=0.01, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, False, name)
        self._lr_min, self._lr_max = learning_rate_range
        self._eta_neg, self._eta_pos = etas

    def _append_optimize_op(self, p, g):
        prev_g = self._get_accumulator("prev_grad", p)
        lr_acc = self._get_accumulator(
            "lr", p, init=jnp.full(p._data.shape, self.get_lr(), jnp.float32)
        )
        sign = jnp.sign(g * prev_g)
        lr_new = jnp.clip(
            jnp.where(sign > 0, lr_acc * self._eta_pos,
                      jnp.where(sign < 0, lr_acc * self._eta_neg, lr_acc)),
            self._lr_min, self._lr_max,
        )
        g_eff = jnp.where(sign < 0, 0.0, g)
        self._set_accumulator("prev_grad", p, g_eff)
        self._set_accumulator("lr", p, lr_new)
        self._write_param(p, self._param_value(p) - lr_new * jnp.sign(g_eff))


class LBFGS(Optimizer):
    """Limited-memory BFGS — only the closure-free SGD-fallback step for now;
    full two-loop recursion lands with the scientific-computing pack."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9, history_size=100,
                 line_search_fn=None, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)

    def _append_optimize_op(self, p, g):
        self._write_param(p, self._param_value(p) - self.get_lr() * g)

    def step(self, closure=None):
        if closure is not None:
            loss = closure()
            super().step()
            return loss
        super().step()
