"""paddle_tpu — a TPU-native deep learning framework with PaddlePaddle's
capabilities (reference: /root/reference, see SURVEY.md).

Public namespace mirrors `paddle.*`: tensor ops at the top level, `nn`,
`optimizer`, `amp`, `io`, `distributed`, `vision`, `jit`, `static`-less —
but the engine underneath is jax/XLA/PJRT, designed TPU-first (SURVEY.md §7).
"""

__version__ = "0.1.0"

from .framework import (  # noqa: F401
    # dtypes
    DType,
    bool_ as bool,  # noqa: A001 — paddle exposes paddle.bool
    uint8,
    int8,
    int16,
    int32,
    int64,
    float16,
    bfloat16,
    float32,
    float64,
    complex64,
    complex128,
    set_default_dtype,
    get_default_dtype,
    # device
    CPUPlace,
    TPUPlace,
    CUDAPlace,
    CUDAPinnedPlace,
    set_device,
    get_device,
    device_count,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    # tensor & autograd
    Tensor,
    to_tensor,
    no_grad,
    enable_grad,
    is_grad_enabled,
    set_grad_enabled,
    # rng
    seed,
    get_rng_state,
    set_rng_state,
    Generator,
)

# CUDA-rng compat aliases (single accelerator RNG stream on TPU) + float8
# storage dtypes (jnp-native)
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state
import jax.numpy as _jnp_f8  # noqa: E402

float8_e4m3fn = _jnp_f8.float8_e4m3fn
float8_e5m2 = _jnp_f8.float8_e5m2

from .ops import *  # noqa: F401,F403  — paddle.* tensor ops
from . import ops  # noqa: F401

from . import nn  # noqa: E402,F401
from . import optimizer  # noqa: E402,F401
from . import autograd  # noqa: E402,F401
from . import amp  # noqa: E402,F401
from . import io  # noqa: E402,F401
from . import metric  # noqa: E402,F401
from . import device  # noqa: E402,F401
from . import utils  # noqa: E402,F401

from .hapi import Model  # noqa: E402,F401
from .hapi.model_summary import summary  # noqa: E402,F401
from .utils.flags import get_flags, set_flags  # noqa: E402,F401
from .distributed import DataParallel  # noqa: E402,F401
from . import distributed  # noqa: E402,F401
from . import jit  # noqa: E402,F401
from . import observability  # noqa: E402,F401
from . import profiler  # noqa: E402,F401
from . import fft  # noqa: E402,F401
from . import signal  # noqa: E402,F401
from . import sparse  # noqa: E402,F401
from . import distribution  # noqa: E402,F401
from . import incubate  # noqa: E402,F401
from . import models  # noqa: E402,F401
from . import audio  # noqa: E402,F401
from . import callbacks  # noqa: E402,F401
from . import dataset  # noqa: E402,F401
from . import geometric  # noqa: E402,F401
from . import hub  # noqa: E402,F401
from . import inference  # noqa: E402,F401
# the ops star-import above already bound `linalg` to ops.linalg (the
# reference tensor.linalg surface); the PACKAGE paddle_tpu.linalg
# wraps that same surface and adds `.distributed` — import it
# explicitly (a plain `from . import linalg` would see the existing
# attribute and skip the submodule import) and rebind
import importlib as _importlib  # noqa: E402

linalg = _importlib.import_module(".linalg", __name__)
from . import onnx  # noqa: E402,F401
from . import quantization  # noqa: E402,F401
from . import reader  # noqa: E402,F401
from . import regularizer  # noqa: E402,F401
from . import static  # noqa: E402,F401
from . import sysconfig  # noqa: E402,F401
from . import tensor  # noqa: E402,F401
from . import text  # noqa: E402,F401
from . import version  # noqa: E402,F401
from . import vision  # noqa: E402,F401
from .framework.io import save, load  # noqa: E402,F401
from .nn import ParamAttr  # noqa: E402,F401


def grad(outputs, inputs, grad_outputs=None, retain_graph=None, create_graph=False,
         only_inputs=True, allow_unused=False, no_grad_vars=None):
    """paddle.grad parity (python/paddle/autograd/__init__.py; C++
    general_grad.h partial-graph path)."""
    from .framework import run_backward
    from .framework.tensor import Tensor as _T

    if create_graph:
        raise NotImplementedError(
            "create_graph=True (double backward) is not supported yet by the "
            "tape engine; higher-order grads land with the functional "
            "autograd transform"
        )
    outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if grad_outputs is not None and not isinstance(grad_outputs, (list, tuple)):
        grad_outputs = [grad_outputs]
    capture = {id(t): t for t in inputs}
    captured = run_backward(
        list(outputs),
        list(grad_outputs) if grad_outputs is not None else None,
        # NB: plain `bool` is shadowed here by the paddle.bool DType export
        retain_graph=((not not retain_graph) if retain_graph is not None
                      else create_graph),
        capture=capture,
        accumulate_leaf=False,
    )
    results = []
    for t in inputs:
        g = captured.get(id(t))
        if g is None:
            if allow_unused:
                results.append(None)
            else:
                raise ValueError(
                    "one of the input tensors was not used in the graph; set "
                    "allow_unused=True to return None for it (reference "
                    "general_grad.h unused-input check)"
                )
        else:
            results.append(_T._wrap(g))
    return results


from .hapi.model_summary import flops  # noqa: E402,F401


# -- dtype info + mode-switch parity shims ---------------------------------
from .framework.dtype import DType as dtype  # noqa: E402,F401


def iinfo(t):
    """paddle.iinfo parity over framework dtypes."""
    import numpy as _np

    from .framework.dtype import to_jax_dtype as _tj

    return _np.iinfo(_np.dtype(_tj(t)))


def finfo(t):
    """paddle.finfo parity (bfloat16 via ml_dtypes)."""
    import ml_dtypes as _ml
    import numpy as _np

    from .framework.dtype import to_jax_dtype as _tj

    d = _np.dtype(_tj(t))
    return _ml.finfo(d) if d.name == "bfloat16" else _np.finfo(d)


_dynamic_mode = True


def in_dynamic_mode():
    return _dynamic_mode


def disable_static():
    """Reference paddle.disable_static — dygraph IS the default here."""
    global _dynamic_mode
    _dynamic_mode = True


def enable_static():
    """The legacy static-graph Program world has no TPU equivalent (jit/
    to_static is the compiled path); scripts calling this get a clear
    error instead of silently-wrong eager semantics."""
    raise NotImplementedError(
        "paddle_tpu has no legacy static-graph mode; use paddle_tpu.jit."
        "to_static / TrainStep for compiled execution")


class LazyGuard:
    """Reference LazyGuard defers param init to the first forward; params
    here are cheap host-side jax arrays, so eager init is fine and the
    guard is a no-op context."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

# Tensor method completion: attach the reference's tensor_method_func
# surface once every namespace above exists (framework/tensor_methods.py)
import sys as _sys  # noqa: E402

from .framework import tensor_methods as _tensor_methods  # noqa: E402

_tensor_methods.install(_sys.modules[__name__])
