"""Eager dygraph autograd engine.

Reference parity: the eager autograd graph + backward engine
(paddle/fluid/eager/grad_node_info.h:197, paddle/fluid/eager/backward.cc:105).
TPU-first design: instead of hand-written per-op grad kernels, each op records
a `jax.vjp` closure at call time. The closure is itself traceable, so an entire
dygraph step (forward + backward + optimizer) can be wrapped in `jax.jit` — the
shape-keyed-executable-cache bet flagged in SURVEY.md §7 "hard parts".
"""
from __future__ import annotations

import contextlib
import threading
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

_state = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_state, "grad_enabled", True)


def set_grad_enabled(mode: bool):
    _state.grad_enabled = bool(mode)


class no_grad:
    """Context manager & decorator disabling grad recording (paddle.no_grad)."""

    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(False)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with no_grad():
                return fn(*args, **kwargs)

        return wrapper


class enable_grad:
    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(True)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


class GradNode:
    """One recorded op on the tape.

    `vjp` maps a tuple of output cotangents to a tuple of input cotangents
    (one per recorded input). `inputs` are the input Tensors (kept to route
    cotangents onward / accumulate into leaves).
    """

    __slots__ = ("vjp", "inputs", "outputs_meta", "num_outputs", "name",
                 "outputs", "__weakref__")

    def __init__(self, vjp, inputs, outputs_meta, name=""):
        self.vjp = vjp
        self.inputs = inputs  # list[Tensor]
        # list of (shape, jax_dtype) per output, to build zero cotangents
        self.outputs_meta = outputs_meta
        self.num_outputs = len(outputs_meta)
        self.name = name
        # weakrefs to output Tensors, set by apply_op — used to run grad
        # hooks / retain_grads on the *accumulated* output cotangent
        self.outputs = [None] * self.num_outputs

    def release(self):
        self.vjp = None
        self.inputs = ()


def _is_float0(x):
    return getattr(x, "dtype", None) == jax.dtypes.float0


def _zero_cotangent(meta):
    shape, dtype = meta
    if not jnp.issubdtype(dtype, jnp.floating) and not jnp.issubdtype(
        dtype, jnp.complexfloating
    ):
        return np.zeros(shape, jax.dtypes.float0)
    return jnp.zeros(shape, dtype)


def _topo_order(root_nodes):
    """Reverse-topological order (outputs first) over the node graph.

    Mirrors the in-degree BFS of the reference backward engine
    (paddle/fluid/eager/backward.cc:224 getInDegreeMap).
    """
    visited = set()
    order = []
    # iterative DFS postorder, then reverse
    for root in root_nodes:
        if id(root) in visited:
            continue
        stack = [(root, False)]
        while stack:
            node, processed = stack.pop()
            if processed:
                order.append(node)
                continue
            if id(node) in visited:
                continue
            visited.add(id(node))
            stack.append((node, True))
            for t in node.inputs:
                child = t._grad_node
                if child is not None and id(child) not in visited:
                    stack.append((child, False))
    order.reverse()
    return order


def run_backward(
    tensors,
    grad_tensors=None,
    retain_graph=False,
    capture=None,
    accumulate_leaf=True,
):
    """The backward engine (reference: egr::RunBackward, backward.cc:105).

    tensors: list of output Tensors to seed.
    grad_tensors: optional list of seed cotangents (Tensor or None).
    capture: optional dict id(tensor)->tensor; when given, returns the
        accumulated cotangent for each captured tensor (paddle.grad path).
    accumulate_leaf: write `.grad` on leaf tensors (loss.backward path).
    """
    from .tensor import Tensor

    # node -> list of cotangents (one slot per output)
    cotangents: dict[int, list] = {}
    # leaf accumulation buffer: id -> [tensor, cotangent]. Leaves accumulate
    # here so their hooks run ONCE on the total gradient (reference:
    # GradNodeAccumulation fires hooks on the accumulated grad).
    leaf_acc: dict[int, list] = {}
    captured = {} if capture is not None else None

    def seed(node, idx, value):
        slots = cotangents.setdefault(id(node), [None] * node.num_outputs)
        slots[idx] = value if slots[idx] is None else slots[idx] + value

    def route(t, g):
        """Send a cotangent toward tensor t (accumulates at t's node slot or
        the leaf buffer; hooks fire later, on the total)."""
        child = t._grad_node
        if child is None:
            ent = leaf_acc.setdefault(id(t), [t, None])
            ent[1] = g if ent[1] is None else ent[1] + g
        else:
            seed(child, t._out_index, g)

    def apply_hooks(t, g):
        for hook in t._backward_hooks:
            out = hook(Tensor._wrap(g))
            if out is not None:
                g = out._data if isinstance(out, Tensor) else jnp.asarray(out)
        return g

    root_nodes = []
    for i, t in enumerate(tensors):
        if grad_tensors is not None and grad_tensors[i] is not None:
            g = grad_tensors[i]
            g = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        else:
            if t._data.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {list(t._data.shape)}"
                )
            g = jnp.ones_like(t._data)
        node = t._grad_node
        if node is None:
            if not t.stop_gradient:
                route(t, g)
            elif capture is not None and id(t) in capture:
                captured[id(t)] = g
            continue
        root_nodes.append(node)
        seed(node, t._out_index, g)

    order = _topo_order(root_nodes)

    for node in order:
        slots = cotangents.pop(id(node), None)
        if slots is None:
            continue
        if node.vjp is None:
            raise RuntimeError(
                "trying to backward through the graph a second time; "
                "specify retain_graph=True if needed"
            )
        full = [
            s if s is not None else _zero_cotangent(m)
            for s, m in zip(slots, node.outputs_meta)
        ]
        # each slot now holds the TOTAL cotangent of that output tensor:
        # run its hooks / capture / retain_grads here
        for i in range(node.num_outputs):
            ref = node.outputs[i]
            t = ref() if ref is not None else None
            if t is None:
                continue
            if t._backward_hooks and slots[i] is not None:
                full[i] = apply_hooks(t, full[i])
            if captured is not None and id(t) in capture:
                captured[id(t)] = full[i]
            if accumulate_leaf and t._retain_grads:
                t._accumulate_grad(full[i])
        if node.num_outputs == 1:
            in_cots = node.vjp(full[0])
        else:
            in_cots = node.vjp(tuple(full))
        for t, g in zip(node.inputs, in_cots):
            if _is_float0(g) or t.stop_gradient:
                continue
            route(t, g)
        if not retain_graph:
            node.release()

    for tid, (t, g) in leaf_acc.items():
        g = apply_hooks(t, g)
        if captured is not None and tid in capture:
            captured[tid] = g
        if accumulate_leaf:
            t._accumulate_grad(g)

    return captured


# active (pack, unpack) pair installed by autograd.saved_tensors_hooks
_saved_tensor_hooks = None


_OP_OBSERVER = None     # set by amp.debugging operator-stats collection


def set_op_observer(observer):
    """Install (or clear, with None) a callback `observer(name, inputs)`
    invoked for every apply_op call — checked INSIDE apply_op so every
    module that imported apply_op by value is still observed."""
    global _OP_OBSERVER
    prev = _OP_OBSERVER
    _OP_OBSERVER = observer
    return prev


_op_scope = threading.local()


@contextlib.contextmanager
def op_scope(name):
    """Every op applied inside runs under `jax.named_scope(name)` in its
    forward AND its backward. A `with jax.named_scope(...)` around a
    layer's call names the forward only: the tape pulls an op's vjp back
    later, outside the `with`, so the scope has to stand inside the
    function `apply_op` differentiates. The innermost `op_scope` holds."""
    prev = getattr(_op_scope, "name", None)
    _op_scope.name = name
    try:
        yield
    finally:
        _op_scope.name = prev


def _in_scope(fn, scope):
    def scoped(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)

    return scoped


def apply_op(fn, inputs, attrs=None, name="", num_outputs=None):
    """Execute `fn(*jax_arrays, **attrs)` and record a GradNode if needed.

    Mirrors the generated ad_func pattern
    (paddle/fluid/eager/api/manual/eager_manual/forwards/multiply_fwd_func.cc:40):
    run forward, then wire a grad node if any input requires grad.
    Returns Tensor or tuple of Tensors matching fn's output structure.
    """
    from .tensor import Tensor

    if _OP_OBSERVER is not None:
        _OP_OBSERVER(name or getattr(fn, "__name__", "op"), inputs)
    scope = getattr(_op_scope, "name", None)
    if scope is not None:
        fn = _in_scope(fn, scope)
    attrs = attrs or {}
    datas = [t._data for t in inputs]
    needs_grad = is_grad_enabled() and any(not t.stop_gradient for t in inputs)

    hooks = _saved_tensor_hooks
    if needs_grad and hooks is not None:
        # saved_tensors_hooks contract (autograd.saved_tensors_hooks):
        # the tape keeps only pack_hook(input) per input and RECOMPUTES
        # the op's vjp from unpack_hook at backward time — the genuine
        # offload-saved-tensors semantics (recompute trades the fwd once
        # more for whatever memory the pack moved off-device)
        pack, unpack = hooks
        f = (lambda *xs: fn(*xs, **attrs)) if attrs else fn
        packed = [pack(d) for d in datas]
        outs = fn(*datas, **attrs)

        def vjp(cts, _f=f, _packed=packed, _unpack=unpack):
            redone = [_unpack(p) for p in _packed]
            _, inner = jax.vjp(_f, *redone)
            return inner(cts)
    elif needs_grad:
        f = (lambda *xs: fn(*xs, **attrs)) if attrs else fn
        outs, vjp = jax.vjp(f, *datas)
    else:
        outs = fn(*datas, **attrs)
        vjp = None

    single = not isinstance(outs, (tuple, list))
    outs_tuple = (outs,) if single else tuple(outs)

    if needs_grad:
        import weakref

        meta = [(o.shape, o.dtype) for o in outs_tuple]
        node = GradNode(vjp, list(inputs), meta, name=name)
        wrapped = tuple(
            Tensor._wrap(o, stop_gradient=False, grad_node=node, out_index=i)
            for i, o in enumerate(outs_tuple)
        )
        node.outputs = [weakref.ref(t) for t in wrapped]
    else:
        wrapped = tuple(Tensor._wrap(o, stop_gradient=True) for o in outs_tuple)

    return wrapped[0] if single else wrapped
