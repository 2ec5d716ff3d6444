"""The plain reference: the GPT-3 decoder (Brown et al. 2020; the GPT-2
block of Radford et al. 2019) in straightforward `jax.numpy`.

  x0 = wte[ids] + wpe[positions]
  block: x = x + out_proj(causal_mha(ln_1(x)));  x = x + fc2(gelu(fc1(ln_2(x))))
  logits = ln_f(xL) @ wte.T                       (tied output head)
  loss = mean over tokens of -log softmax(logits)[label]
  AdamW (Loshchilov & Hutter 2019): p <- p (1 - lr wd) - lr m^ / (sqrt(v^) + eps)

float32 throughout under `jax.default_matmul_precision("highest")`, no
kernels, no cache, no batching tricks. It imports nothing of the program
and is handed only arrays the benchmark drew from the seed. Departures
from the papers, both the program's: GELU is the tanh approximation
(GPT-2's own), and qkv is one [H, 3H] matrix whose columns are q | k | v,
each split into heads of `head_dim`.

To fit a 16 GB chip at published widths it walks layer by layer and
sequence by sequence, and keeps AdamW's state after the first update as
the first gradient g1 (m1 = (1 - b1) g1 and v1 = (1 - b2) g1**2 exactly),
which is why `RefTrainer` follows two updates and three losses.

`precision` is "float32" for the reference proper. "fp8" is the control
(the next precision below bf16 compute): the same mathematics with every
matrix product's operands rounded to e4m3 with a per-tensor scale (the
usual fp8 recipe), accumulated in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
BLOCK_LEAVES = (
    "ln_1.weight", "ln_1.bias", "attn.qkv.weight", "attn.qkv.bias",
    "attn.out_proj.weight", "attn.out_proj.bias", "ln_2.weight",
    "ln_2.bias", "mlp.fc1.weight", "mlp.fc1.bias", "mlp.fc2.weight",
    "mlp.fc2.bias")


# -- one matrix product, in the stated precision ---------------------------

def _fp8(x):
    """Round to e4m3 with a per-tensor scale; straight-through gradient."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    return x + jax.lax.stop_gradient(q - x)


def dot(a, b, precision):
    if precision == "float32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        return jnp.matmul(_fp8(a), _fp8(b),
                          precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


# -- the forward pass ---------------------------------------------------------

def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def attention(q, k, v, precision):
    """Causal attention of one sequence: q, k, v [S, heads, d]."""
    s, _, d = q.shape
    scores = dot(q.transpose(1, 0, 2), k.transpose(1, 2, 0),
                 precision) / jnp.sqrt(F32(d))          # [heads, S, S]
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return dot(p, v.transpose(1, 0, 2), precision).transpose(1, 0, 2)


def block(p, x, heads, eps, precision):
    """One decoder block on x [B, S, H]; p maps BLOCK_LEAVES to arrays."""
    b, s, h = x.shape
    a = layer_norm(x, p["ln_1.weight"], p["ln_1.bias"], eps)
    qkv = dot(a, p["attn.qkv.weight"], precision) + p["attn.qkv.bias"]
    qkv = qkv.reshape(b, s, 3, heads, h // heads)
    ctx = jax.lax.map(                      # one sequence at a time
        lambda t: attention(t[:, 0], t[:, 1], t[:, 2], precision), qkv)
    x = x + dot(ctx.reshape(b, s, h), p["attn.out_proj.weight"],
                precision) + p["attn.out_proj.bias"]
    a = layer_norm(x, p["ln_2.weight"], p["ln_2.bias"], eps)
    a = gelu(dot(a, p["mlp.fc1.weight"], precision) + p["mlp.fc1.bias"])
    return x + dot(a, p["mlp.fc2.weight"], precision) + p["mlp.fc2.bias"]


def embed(outer, ids):
    return outer["wte"][ids] + outer["wpe"][jnp.arange(ids.shape[-1])]


def logits_of(outer, x, eps, precision):
    a = layer_norm(x, outer["ln_f.weight"], outer["ln_f.bias"], eps)
    return dot(a, outer["wte"].T, precision)


def head_loss_sum(outer, x, labels, eps, precision):
    """Sum of the token losses of one sequence x [S, H]."""
    logp = jax.nn.log_softmax(logits_of(outer, x, eps, precision), -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1))


def forward_logits(outer, layers, ids, heads, eps, precision="float32"):
    """Logits [S, V] of one sequence of token ids [S] (serving's check)."""
    x = embed(outer, ids)[None]
    for p in layers:
        x = _block(p, x, heads, eps, precision)
    return _logits(outer, x[0], eps, precision)


_block = jax.jit(block, static_argnums=(2, 3, 4))
_head_fwd = jax.jit(head_loss_sum, static_argnums=(3, 4))
_logits = jax.jit(logits_of, static_argnums=(2, 3))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _block_bwd(p, x, dy, heads, eps, precision):
    """(dp, dx) of one block, one sequence at a time (a row's residuals
    and the running sum of dp are all that is alive)."""
    def row(acc, xd):
        _, vjp = jax.vjp(
            lambda pp, xx: block(pp, xx[None], heads, eps, precision)[0],
            p, xd[0])
        dp, dx = vjp(xd[1])
        return jax.tree.map(jnp.add, acc, dp), dx

    return jax.lax.scan(row, jax.tree.map(jnp.zeros_like, p), (x, dy))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_bwd(outer, x, labels, eps, precision):
    return jax.value_and_grad(head_loss_sum, argnums=(0, 1))(
        outer, x, labels, eps, precision)


# -- AdamW ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(5,))
def adamw(p, g, m, v, t, hyper):
    lr, b1, b2, eps, wd = hyper
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    m_hat = m / (1 - b1 ** t)
    v_hat = v / (1 - b2 ** t)
    return p * (1 - lr * wd) - lr * m_hat / (jnp.sqrt(v_hat) + eps)


def parts(name, a):
    """[(leaf name, array)]: qkv's last axis is q | k | v, three leaves
    to every reading (the key bias has no gradient: softmax does not see
    a constant added to every key's score)."""
    if ".qkv." not in name:
        return [(name, a)]
    w = a.shape[-1] // 3
    return [(f"{name}.{p}", a[..., j * w:(j + 1) * w])
            for j, p in enumerate("qkv")]


def _sq(tree):
    return {n: jnp.sum(jnp.square(x)) for k, a in tree.items()
            for n, x in parts(k, a)}


class RefTrainer:
    """Three losses and two AdamW updates of the whole model.

    `outer` holds wte, wpe, ln_f.*; `layers` is a list of per-layer dicts.
    After `run`, `losses` has three entries, `grad_norms` the per-leaf
    norm of the first gradient and `delta_norms(initial_leaf)` the
    per-leaf norm of the change after the two updates (block leaves over
    all layers together).
    """

    def __init__(self, outer, layers, heads, eps, hyper,
                 precision="float32", probe=None):
        self.outer, self.layers = dict(outer), [dict(p) for p in layers]
        self.heads, self.eps, self.precision = heads, eps, precision
        self.hyper = tuple(float(x) for x in hyper)   # lr b1 b2 eps wd
        self.losses, self.grad_norms = [], {}
        # optional reading of the first gradient, leaf by leaf as it is
        # formed: probe(leaf name, layer index or None, array)
        self.probe = probe or (lambda leaf, layer, array: None)
        self._g1 = None

    def _forward(self, ids):
        x = embed(self.outer, ids)
        xs = []
        for p in self.layers:
            xs.append(x)
            x = _block(p, x, self.heads, self.eps, self.precision)
        return x, xs

    def _head(self, x, labels, want_grads=True):
        n = labels.size
        loss, d_outer, dx = F32(0), None, []
        for r in range(x.shape[0]):
            if not want_grads:
                loss += _head_fwd(self.outer, x[r], labels[r],
                                  self.eps, self.precision) / n
                continue
            l, (go, gx) = _head_bwd(self.outer, x[r], labels[r],
                                    self.eps, self.precision)
            loss += l / n
            dx.append(gx / n)
            d_outer = go if d_outer is None else jax.tree.map(
                jnp.add, d_outer, go)
        if not want_grads:
            return loss, None, None
        return loss, jax.tree.map(lambda a: a / n, d_outer), jnp.stack(dx)

    def _update(self, p, g, g1, t):
        """AdamW update t (1 or 2) of one dict of leaves."""
        b1, b2 = self.hyper[1], self.hyper[2]
        out = {}
        for k in p:
            m = 0.0 * g[k] if g1 is None else (1 - b1) * g1[k]
            v = 0.0 * g[k] if g1 is None else (1 - b2) * g1[k] ** 2
            out[k] = adamw(p[k], g[k], m, v, F32(t), self.hyper)
        return out

    def _step(self, ids, labels, t):
        x, xs = self._forward(ids)
        loss, d_outer, dy = self._head(x, labels)
        self.losses.append(float(loss))
        first = self._g1 is None
        g1 = {"layers": [None] * len(self.layers)} if first else self._g1
        sq = {}
        for i in reversed(range(len(self.layers))):
            dp, dy = _block_bwd(self.layers[i], xs[i], dy, self.heads,
                                self.eps, self.precision)
            xs[i] = None
            if first:
                for k, val in _sq(dp).items():
                    sq["blocks." + k] = sq.get("blocks." + k, 0.0) + val
                for k, a in dp.items():
                    for n, x in parts(k, a):
                        self.probe("blocks." + n, i, x)
                g1["layers"][i] = dp
            self.layers[i] = self._update(
                self.layers[i], dp, None if first else g1["layers"][i], t)
            if not first:
                g1["layers"][i] = None
        d_outer["wte"] = d_outer["wte"].at[ids.reshape(-1)].add(
            dy.reshape(-1, dy.shape[-1]))
        d_outer["wpe"] = d_outer["wpe"].at[:ids.shape[1]].add(dy.sum(0))
        if first:
            sq.update(_sq(d_outer))
            for k, a in d_outer.items():
                self.probe(k, None, a)
            g1["outer"] = d_outer
            self.grad_norms = {k: float(jnp.sqrt(v)) for k, v in sq.items()}
        self.outer = self._update(self.outer, d_outer,
                                  None if first else g1["outer"], t)
        self._g1 = g1 if first else None

    def run(self, batches):
        """`batches`: three (ids, labels) pairs of int arrays [B, S]."""
        with jax.default_matmul_precision("highest"):
            for t, (ids, labels) in enumerate(batches[:2], start=1):
                self._step(jnp.asarray(ids, jnp.int32),
                           jnp.asarray(labels, jnp.int32), t)
            ids, labels = batches[2]
            x, _ = self._forward(jnp.asarray(ids, jnp.int32))
            loss, _, _ = self._head(x, jnp.asarray(labels, jnp.int32),
                                    want_grads=False)
            self.losses.append(float(loss))
        return self

    def delta_norms(self, initial_leaf):
        """Per-leaf norm of (current - initial); `initial_leaf(name)`
        returns the seeded leaf (block leaves stacked on [L])."""
        out = {}
        for k, a in self.outer.items():
            out[k] = float(jnp.sqrt(jnp.sum(jnp.square(a - initial_leaf(k)))))
        for k in BLOCK_LEAVES:
            init = initial_leaf("blocks." + k)
            sq = {}
            for i, p in enumerate(self.layers):
                for n, val in _sq({k: p[k] - init[i]}).items():
                    sq[n] = sq.get(n, 0.0) + val
            out.update(("blocks." + n, float(jnp.sqrt(v)))
                       for n, v in sq.items())
        return out
