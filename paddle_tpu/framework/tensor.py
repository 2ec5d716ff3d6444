"""The eager Tensor.

Reference parity: `paddle::Tensor` + AutogradMeta
(paddle/phi/api/include/tensor.h:82, paddle/fluid/eager/autograd_meta.h) and
the pybind method surface (paddle/fluid/pybind/eager_method.cc). TPU-first:
the storage is a `jax.Array` (PJRT buffer) — XLA owns layout/placement; views
and "in-place" ops are functional rebinds, with buffer donation left to the
jit path.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import autograd
from .autograd import apply_op
from .dtype import DType, convert_dtype, to_jax_dtype, get_default_dtype
from .device import Place, current_place, TPUPlace, CPUPlace


class Tensor:
    __slots__ = (
        "_data",
        "stop_gradient",
        "_grad",
        "_grad_node",
        "_out_index",
        "_retain_grads",
        "_backward_hooks",
        "name",
        "persistable",
        "trainable",
        "__weakref__",
        "__dict__",
    )

    def __init__(self, data, dtype=None, place=None, stop_gradient=True, name=None):
        if isinstance(data, Tensor):
            data = data._data
        if not isinstance(data, jax.Array):
            if dtype is not None:
                data = np.asarray(data)
                data = jnp.asarray(data, dtype=to_jax_dtype(dtype))
            else:
                arr = np.asarray(data)
                if arr.dtype == np.float64:
                    # python floats default to the framework default dtype
                    arr = arr.astype(to_jax_dtype(get_default_dtype()))
                data = jnp.asarray(arr)
        elif dtype is not None and data.dtype != to_jax_dtype(dtype):
            data = data.astype(to_jax_dtype(dtype))
        if place is not None and isinstance(place, Place):
            data = jax.device_put(data, place.jax_device())
        self._data = data
        self.stop_gradient = stop_gradient
        self._grad = None
        self._grad_node = None
        self._out_index = 0
        self._retain_grads = False
        self._backward_hooks = []
        self.name = name or ""
        self.persistable = False
        self.trainable = True

    # -- construction helpers ------------------------------------------
    @staticmethod
    def _wrap(data, stop_gradient=True, grad_node=None, out_index=0):
        t = Tensor.__new__(Tensor)
        t._data = data
        t.stop_gradient = stop_gradient
        t._grad = None
        t._grad_node = grad_node
        t._out_index = out_index
        t._retain_grads = False
        t._backward_hooks = []
        t.name = ""
        t.persistable = False
        t.trainable = True
        return t

    # -- metadata -------------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    @property
    def dtype(self) -> DType:
        return convert_dtype(self._data.dtype)

    @property
    def place(self) -> Place:
        try:
            dev = self._data.devices().pop()
            plat = dev.platform.lower()
        except Exception:
            return current_place()
        if plat == "tpu":
            return TPUPlace(dev.id)
        return CPUPlace(dev.id)

    @property
    def is_leaf(self):
        return self._grad_node is None

    @property
    def T(self):
        from .. import ops

        perm = list(range(self.ndim))[::-1]
        return ops.transpose(self, perm)

    # -- grad -----------------------------------------------------------
    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    def _accumulate_grad(self, g_data):
        # leaf accumulation (reference: GradNodeAccumulation,
        # paddle/fluid/eager/accumulation/accumulation_node.cc)
        if g_data.dtype != self._data.dtype:
            g_data = g_data.astype(self._data.dtype)
        if self._grad is None:
            self._grad = Tensor._wrap(g_data)
        else:
            self._grad._data = self._grad._data + g_data

    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.run_backward(
            [self],
            [grad_tensor] if grad_tensor is not None else None,
            retain_graph=retain_graph,
        )

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def _pin_to_node(self):
        """Keep this output tensor alive from its grad node so hooks /
        retain_grads fire even if user code drops the reference (the node's
        weakref would otherwise die with it)."""
        if self._grad_node is not None:
            node = self._grad_node
            me = self

            class _Strong:
                def __call__(self):
                    return me

            node.outputs[self._out_index] = _Strong()

    def retain_grads(self):
        self._retain_grads = True
        self._pin_to_node()

    def register_hook(self, hook):
        self._backward_hooks.append(hook)
        self._pin_to_node()

        class _Handle:
            def remove(handle_self):
                if hook in self._backward_hooks:
                    self._backward_hooks.remove(hook)

        return _Handle()

    def detach(self):
        t = Tensor._wrap(self._data, stop_gradient=True)
        t.name = self.name
        return t

    def detach_(self):
        self._grad_node = None
        self._out_index = 0
        self.stop_gradient = True
        return self

    def clone(self):
        return apply_op(lambda x: x + 0, [self], name="clone")

    # -- conversion ------------------------------------------------------
    def numpy(self):
        return np.asarray(self._data)

    def item(self, *args):
        if args:
            return self._data[args].item() if len(args) > 1 else np.asarray(self._data).flat[args[0]].item()
        return self._data.item()

    def tolist(self):
        return np.asarray(self._data).tolist()

    def astype(self, dtype):
        jd = to_jax_dtype(dtype)
        return apply_op(lambda x: x.astype(jd), [self], name="cast")

    cast = astype

    def to(self, *args, **kwargs):
        # .to(device) / .to(dtype) / .to(device, dtype)
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (str, Place)):
                if isinstance(a, str) and a in ("cpu", "tpu", "gpu") or isinstance(a, Place):
                    place = a if isinstance(a, Place) else (
                        CPUPlace() if a == "cpu" else TPUPlace()
                    )
                    data = jax.device_put(out._data, place.jax_device())
                    new = Tensor._wrap(data, stop_gradient=out.stop_gradient,
                                       grad_node=out._grad_node, out_index=out._out_index)
                    out = new
                else:
                    out = out.astype(a)
            elif isinstance(a, DType):
                out = out.astype(a)
        return out

    def cpu(self):
        return self.to("cpu")

    def cuda(self, *a, **k):
        return self.to("tpu")

    def pin_memory(self):
        return self

    # -- value mutation ---------------------------------------------------
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        self._data = jnp.asarray(value, dtype=self._data.dtype).reshape(self._data.shape)
        return self

    def copy_(self, other):
        return self.set_value(other)

    def zero_(self):
        self._data = jnp.zeros_like(self._data)
        return self

    def fill_(self, value):
        self._data = jnp.full_like(self._data, value)
        return self

    def _inplace_from(self, result: "Tensor"):
        """Adopt the data+autograd identity of `result` (functional in-place).

        If `result`'s grad node recorded `self` as an input, that input slot
        must keep pointing at the PRE-op identity (old grad_node), not the
        rebound tensor — otherwise the node cycles onto itself and the
        upstream graph is dropped (reference: inplace version counting,
        paddle/fluid/eager/tensor_wrapper.h).
        """
        import weakref

        node = result._grad_node
        if node is not None:
            if self._grad_node is None and not self.stop_gradient:
                raise RuntimeError(
                    "a leaf Tensor that requires grad is being used in an "
                    "in-place operation; wrap it in paddle.no_grad() or "
                    "detach() first"
                )
            for i, t in enumerate(node.inputs):
                if t is self:
                    alias = Tensor._wrap(
                        self._data, stop_gradient=self.stop_gradient,
                        grad_node=self._grad_node, out_index=self._out_index,
                    )
                    node.inputs[i] = alias
            # the op's output is now this tensor: repoint the weakref so
            # hooks/retain_grads fire on it
            if node.outputs[result._out_index] is not None:
                node.outputs[result._out_index] = weakref.ref(self)
        self._data = result._data
        self._grad_node = node
        self._out_index = result._out_index
        self.stop_gradient = result.stop_gradient
        return self

    # -- indexing ---------------------------------------------------------
    def __getitem__(self, idx):
        idx = _normalize_index(idx)
        return apply_op(lambda x: x[idx], [self], name="getitem")

    def __setitem__(self, idx, value):
        idx = _normalize_index(idx)
        if isinstance(value, Tensor):
            out = apply_op(
                lambda x, v: x.at[idx].set(v.astype(x.dtype)), [self, value],
                name="setitem",
            )
        else:
            out = apply_op(lambda x: x.at[idx].set(value), [self], name="setitem")
        self._inplace_from(out)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- python scalar conversions ----------------------------------------
    def __float__(self):
        return float(self._data)

    def __int__(self):
        return int(self._data)

    def __bool__(self):
        return bool(self._data)

    def __index__(self):
        return int(self._data)

    def __repr__(self):
        grad_part = f", stop_gradient={self.stop_gradient}"
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
            f"place={self.place}{grad_part},\n       {np.asarray(self._data)})"
        )

    def __hash__(self):
        return id(self)

    # -- arithmetic (delegates to ops; wired in ops/__init__) --------------
    # populated by paddle_tpu.ops._install_tensor_methods()


def _normalize_index(idx):
    """Convert Tensor indices to jax arrays inside an index expression."""
    if isinstance(idx, Tensor):
        return idx._data
    if isinstance(idx, tuple):
        return tuple(i._data if isinstance(i, Tensor) else i for i in idx)
    if isinstance(idx, list):
        return jnp.asarray(idx)
    return idx


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor parity (python/paddle/tensor/creation.py)."""
    if isinstance(data, Tensor):
        t = data.astype(dtype) if dtype is not None else data.clone()
        t.stop_gradient = stop_gradient
        return t
    if dtype is None and isinstance(data, (bool, int, float, list, tuple)):
        arr = np.asarray(data)
        if arr.dtype == np.float64:
            dtype = get_default_dtype()
    t = Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)
    return t
