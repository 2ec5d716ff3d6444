"""The plain reference of LFM2-MoE's language model (the decoder of
https://huggingface.co/LiquidAI/LFM2-24B-A2B, config.json, `model_type`
`lfm2_moe`) in straightforward `jax.numpy`. It imports nothing of the
program.

A published layer is u <- u + mixer(rms(u) g1), then u <- u + ffn(rms(u) g2).
Here each half is a SUB-LAYER of its own kind, u <- u + f(rms(u) g), so a
model of L layers is a walk over 2 L of them (reference/ling3.py's walk):
`conv` or `attn` by the layer's type, then `dense` (the leading
`num_dense_layers`) or `moe`. A final rms, and the head is the embedding.

`conv` (the gated short convolution), h = rms(u) g, one sequence:
  [B | C | X] = h W_in                     [3 x hidden], no bias
  z = B * X
  c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t   three shifted sums, a channel;
                                           zeros before the sequence's start;
                                           no activation
  out = (C * c) W_out
`attn` (grouped-query attention), one sequence:
  q = h Wq [32 x 64], k = h Wk, v = h Wv [8 x 64]; q, k <- rms over the head's
  64, gains gq, gk; both turned by position (rotate-half over all 64, theta);
  causal softmax at 64^-1/2, a block of queries at a time over all keys, the
  four query heads of a group on their key-value head;  out = o Wo
`dense`: out = (silu(h Wg) * (h Wu)) Wd
`moe`: s = sigmoid(h Wr) over all `num_experts`; E_t = the top k of s + bias
  (ties: lower index); g[t, e] = s[t, e] / (sum_{E_t} s + 1e-6) * scale;
  out = sum over the HELD experts e (a loop) of g[t, e]
  (silu(h Wg_e) * (h Wu_e)) Wd_e; no shared expert

  loss = mean CE(E rms(u_L) gf) + mean over the moe sub-layers of
         [coef * num_experts * sum_e f_e P_e]
  E the embedding [V, H]: its gradient is the gather's rows plus the head's.
  f_e: share of the batch's tokens that picked e (no gradient); P_e: mean
  over tokens of s[t, e] / sum_e' s[t, e'].

float32 under `jax.default_matmul_precision("highest")`. `precision="fp8"`
is the control: every matrix product's operands rounded to e4m3 with a
per-tensor scale (the gates and the convolution are elementwise: float32 as
written). Three wrong programs, for benchmark/calibrate_wrong.py:
`no_input_gate` convolves X alone (z = X), `late_tap` reads the taps one step
late (c_t = w_0 z_{t-1} + w_1 z_t + w_2 z_{t+1}: not causal), `untied_head`
gives the embedding no gradient from the head.
"""
from __future__ import annotations

import concurrent.futures
import functools
import types

import jax
import jax.numpy as jnp

# what does not name a block (reference/nemotron_h.py's note)
from reference.keye_vl2 import (  # noqa: F401
    _embed_grad, _freeze, _sq_diff, _sq_tree, _update, mm, rms)
from reference.ling3 import swiglu, turn
from reference.nemotron_h import _layer_scalars  # noqa: F401

F32 = jnp.float32
CONV, ATTN, DENSE, MIXTURE = "conv", "attn", "dense", "moe"
KIND_NAMES = {k: k for k in (CONV, ATTN, DENSE, MIXTURE)}
LEAVES = {
    CONV: ("operator_norm.weight", "conv.in_proj.weight", "conv.conv_weight",
           "conv.out_proj.weight"),
    ATTN: ("operator_norm.weight", "self_attn.q_proj.weight",
           "self_attn.k_proj.weight", "self_attn.v_proj.weight",
           "self_attn.q_norm.weight", "self_attn.k_norm.weight",
           "self_attn.o_proj.weight"),
    DENSE: ("ffn_norm.weight", "feed_forward.gate_proj.weight",
            "feed_forward.up_proj.weight", "feed_forward.down_proj.weight"),
    MIXTURE: ("ffn_norm.weight", "feed_forward.router",
              "feed_forward.gate_proj", "feed_forward.up_proj",
              "feed_forward.down_proj"),
}
BIAS = "feed_forward.score_bias"        # a buffer: no gradient, no update
OUTER_LEAVES = ("embed_tokens.weight", "norm.weight")
QUERY_BLOCK = 256
RENORM_EPS = 1e-6
WRONG = ("no_input_gate", "late_tap", "untied_head")


def kinds_of(cfg):
    """The 2 L sub-layers' kinds, in order."""
    out = []
    for i, kind in enumerate(cfg["layer_types"]):
        out.append(ATTN if kind == "full_attention" else CONV)
        out.append(DENSE if i < cfg["num_dense_layers"] else MIXTURE)
    return tuple(out)


# -- the sub-layers -----------------------------------------------------------

def conv(p, x, cfg, precision):
    """x [S, H] -> x + the gated short convolution of one sequence."""
    s, width = x.shape
    h = rms(x, p["operator_norm.weight"], cfg["norm_eps"])
    bcx = mm("sh,hd->sd", h, p["conv.in_proj.weight"], precision)
    gate_in, gate_out, inner = (bcx[:, i * width:(i + 1) * width]
                                for i in range(3))
    z = inner if cfg["no_input_gate"] else gate_in * inner
    w = p["conv.conv_weight"]
    taps, late = w.shape[0], int(cfg["late_tap"])
    padded = jnp.pad(z, ((taps - 1, late), (0, 0)))
    c = sum(padded[k + late:k + late + s] * w[k] for k in range(taps))
    return x + mm("sd,dh->sh", gate_out * c, p["conv.out_proj.weight"],
                  precision)


def attn(p, x, cfg, precision):
    """x [S, H] -> x + grouped-query attention of one sequence."""
    s, _ = x.shape
    heads, kv, d, eps = (cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"],
                         cfg["norm_eps"])
    h = rms(x, p["operator_norm.weight"], eps)

    def product(name, n):
        return mm("sh,hd->sd", h, p[f"self_attn.{name}_proj.weight"],
                  precision).reshape(s, n, d)

    q = turn(rms(product("q", heads), p["self_attn.q_norm.weight"], eps),
             cfg["rope_theta"]).reshape(s, kv, heads // kv, d)
    k = turn(rms(product("k", kv), p["self_attn.k_norm.weight"], eps),
             cfg["rope_theta"])
    v = product("v", kv)
    block = min(QUERY_BLOCK, s)
    cols = jnp.arange(s, dtype=jnp.int32)

    @jax.checkpoint
    def rows(args):
        t0, qb = args
        keep = (t0 + jnp.arange(block, dtype=jnp.int32))[:, None] \
            >= cols[None, :]
        logits = mm("tngd,snd->ngts", qb, k, precision) / jnp.sqrt(F32(d))
        prob = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        return mm("ngts,snd->tngd", prob, v, precision)

    n = s // block
    o = jax.lax.map(rows, (jnp.arange(n, dtype=jnp.int32) * block,
                           q.reshape((n, block) + q.shape[1:])))
    return x + mm("sd,dh->sh", o.reshape(s, heads * d),
                  p["self_attn.o_proj.weight"], precision)


def dense(p, x, cfg, precision):
    """x [S, H] -> x + the dense SwiGLU."""
    h = rms(x, p["ffn_norm.weight"], cfg["norm_eps"])
    return x + swiglu(h, p["feed_forward.gate_proj.weight"],
                      p["feed_forward.up_proj.weight"],
                      p["feed_forward.down_proj.weight"], precision)


def scores(p, x, cfg, precision):
    h2 = rms(x, p["ffn_norm.weight"], cfg["norm_eps"])
    return h2, jax.nn.sigmoid(
        mm("sh,he->se", h2, p["feed_forward.router"], precision))


def own_picks(s, bias, cfg):
    """The router's picks of scores s [S, E]: the top k of s + bias."""
    return jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])[1]


def mixture(p, bias, x, cfg, precision, picks=None):
    """x [S, H] -> (x + the held experts' part, sum_t of the normalised
    scores [E], tokens that picked each expert [E], the picks [S, k]).
    `picks` given: those experts are taken in place of the router's own
    (their weights still this function's own scores)."""
    lo, hi = cfg["held_experts"]
    h2, s = scores(p, x, cfg, precision)
    if picks is None:
        picks = own_picks(s, bias, cfg)
    top = jnp.take_along_axis(s, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + RENORM_EPS)
    top = top * cfg["routed_scaling_factor"]

    def one(y, xs):
        e, wg, wu, wd = xs
        gate = jnp.sum(jnp.where(picks == e, top, 0.0), axis=-1)
        return y + gate[:, None] * swiglu(h2, wg, wu, wd, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(lo, hi), p["feed_forward.gate_proj"],
                         p["feed_forward.up_proj"],
                         p["feed_forward.down_proj"]))
    picked = jnp.zeros((s.shape[-1],), F32).at[picks.reshape(-1)].add(1.0)
    return x + y, jnp.sum(s / jnp.sum(s, -1, keepdims=True), 0), picked, picks


def mixture_given(p, bias, x, cfg, precision, picks):
    """`mixture` on given picks, and the share of them that are not the
    sub-layer's own."""
    out = mixture(p, bias, x, cfg, precision, picks)
    if picks is None:
        return out + (F32(0),)
    mine = own_picks(scores(p, x, cfg, precision)[1], bias, cfg)
    hit = jnp.any(picks[:, :, None] == mine[:, None, :], axis=-1)
    return out + (1.0 - jnp.mean(hit),)


PLAIN = {CONV: conv, ATTN: attn, DENSE: dense}


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _layer_fwd(kind, p, bias, x, picks, cfg, precision):
    """Every sequence through one sub-layer of the kind -> x, and for a
    mixture (x, sum_t scores, picked, picks, given picks not its own)."""
    cfg = dict(cfg)
    if kind in PLAIN:
        return jax.lax.map(lambda a: PLAIN[kind](p, a, cfg, precision), x)
    return jax.lax.map(
        lambda a: mixture_given(p, bias, a[0], cfg, precision, a[1]),
        (x, picks))


@functools.partial(jax.jit, static_argnums=(0, 7, 8))
def _layer_bwd(kind, p, bias, x, picks, dy, f_weight, cfg, precision):
    """(dp, dx) of one sub-layer, a sequence at a time (nemotron_h.py
    `_layer_bwd`: a mixture's share of the loss is also sum_e f_weight[e]
    * sum_t scores[t, e])."""
    cfg = dict(cfg)

    def row(acc, a):
        xs, ps, dys = a
        if kind in PLAIN:
            dp, dx = jax.vjp(
                lambda pp, xx: PLAIN[kind](pp, xx, cfg, precision), p,
                xs)[1](dys)
        else:
            def f(pp, xx):
                y, score_sum = mixture(pp, bias, xx, cfg, precision, ps)[:2]
                return y, jnp.sum(f_weight * score_sum)

            dp, dx = jax.vjp(f, p, xs)[1]((dys, jnp.ones((), F32)))
        return jax.tree.map(jnp.add, acc, dp), dx

    return jax.lax.scan(row, jax.tree.map(jnp.zeros_like, p), (x, picks, dy))


# -- the tied head ------------------------------------------------------------

def head_loss_sum(outer, x, labels, cfg, precision):
    """Sum of the token losses of one sequence x [S, H]; the head is the
    embedding."""
    a = rms(x, outer["norm.weight"], cfg["norm_eps"])
    head = outer["embed_tokens.weight"]
    if cfg["untied_head"]:
        head = jax.lax.stop_gradient(head)
    logp = jax.nn.log_softmax(mm("sh,vh->sv", a, head, precision), -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_loss(outer, x, labels, cfg, precision):
    """Mean token loss over every sequence x [B, S, H]."""
    cfg = dict(cfg)
    rows = jax.lax.map(
        lambda a: head_loss_sum(outer, a[0], a[1], cfg, precision),
        (x, labels))
    return jnp.sum(rows) / labels.size


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_grads(outer, x, labels, cfg, precision):
    """(mean token loss, its gradient in `outer` (the embedding's: the
    head's part), in x [B, S, H]), one sequence at a time."""
    cfg = dict(cfg)

    def row(acc, a):
        xs, ls = a
        loss, (go, gx) = jax.value_and_grad(
            lambda o, xx: head_loss_sum(o, xx, ls, cfg, precision),
            argnums=(0, 1))(outer, xs)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], go)), gx

    (loss, go), dx = jax.lax.scan(
        row, (F32(0), jax.tree.map(jnp.zeros_like, outer)), (x, labels))
    n = labels.size
    return loss / n, jax.tree.map(lambda a: a / n, go), dx / n


@jax.jit
def _tied_grad(head_part, weight, ids, dx):
    """The embedding's gradient: the head's dW plus dx's rows added into
    their tokens' rows."""
    return head_part + _embed_grad(weight, ids, dx)


def _config(cfg, wrong=()):
    """The configuration as the walk reads it: the names the shared loop
    (nemotron_h.py's) knows beside the published ones, and the wrong
    programs' switches."""
    unknown = set(wrong) - set(WRONG)
    if unknown:
        raise ValueError(f"unknown wrong program {sorted(unknown)}")
    cfg = dict(cfg, n_routed_experts=cfg["num_experts"],
               **{k: k in wrong for k in WRONG})
    cfg["held_experts"] = tuple(cfg["held_experts"])
    cfg["layer_types"] = tuple(cfg["layer_types"])
    return cfg


class RefTrainer:
    """Three losses and two AdamW updates of the whole model.

    `outer` holds embed_tokens.weight [V, H] (the head too) and norm.weight;
    `layers` is a list of 2 L per-sub-layer dicts of the kind's LEAVES
    (`kinds_of`; the experts' leaves hold the held experts only,
    cfg["held_experts"] = [lo, hi]; a mixture's dict also holds BIAS, which
    gets no gradient and no update). After `run`: `losses`, `parts`,
    `grad_norms` (a sub-layer leaf `<kind>.<leaf>` over all sub-layers of
    the kind), `counts`, `picks`, `miss`, `delta_norms(outer0, layers0)`,
    and `probe(tree, sub-layer)`: reference/nemotron_h.py `RefTrainer`'s,
    whose walk this is but for `_step`'s tied embedding. `wrong` names wrong
    programs (WRONG, module docstring).
    """

    def __init__(self, outer, layers, cfg, hyper, precision="float32",
                 probe=None, given=None, wrong=()):
        cfg = _config(cfg, wrong)
        self.kinds = kinds_of(cfg)
        self.outer = dict(outer)
        self.biases = [p.get(BIAS) for p in layers]
        self.layers = [{k: v for k, v in p.items() if k != BIAS}
                       for p in layers]
        self.cfg, self.precision = _freeze(cfg), precision
        self.hyper = tuple(float(x) for x in hyper)   # lr b1 b2 eps wd
        self.losses, self.parts, self.grad_norms = [], [], {}
        self.counts = None
        self.given, self.miss, self.picks = given, None, None
        self.probe = probe or (lambda tree, layer: None)
        self._g1 = None

    def _step(self, ids, labels, t):
        """nemotron_h.RefTrainer._step, the embedding's gradient the head's
        part plus the gather's."""
        n = len(self.layers)
        first = self._g1 is None
        x, xs, weights, balance, counts = self._forward(
            ids, given=self.given if first else None)
        lm, d_outer, dy = _head_grads(self.outer, x, labels, self.cfg,
                                      self.precision)
        self._note(lm, balance)
        if first:
            self.counts = {"routed_pairs": counts[0],
                           "max_load_over_mean": counts[1]}
            self.picks = [a[1] for a, k in zip(xs, self.kinds)
                          if k == MIXTURE]
        g1 = {"layers": [None] * n} if first else self._g1
        sq = {}
        for i in reversed(range(n)):
            kind = self.kinds[i]
            dp, dy = _layer_bwd(kind, self.layers[i], self.biases[i],
                                xs[i][0], xs[i][1], dy, weights[i],
                                self.cfg, self.precision)
            xs[i] = None
            if first:
                for k, v in _sq_tree(dp).items():
                    name = KIND_NAMES[kind] + "." + k
                    sq[name] = sq.get(name, 0.0) + float(v)
                self.probe(dp, i)
                g1["layers"][i] = dp
            self.layers[i] = _update(
                self.layers[i], dp, None if first else g1["layers"][i],
                F32(t), self.hyper)
            if not first:
                g1["layers"][i] = None
        d_outer["embed_tokens.weight"] = _tied_grad(
            d_outer["embed_tokens.weight"],
            self.outer["embed_tokens.weight"], ids, dy)
        if first:
            self.probe(d_outer, None)
            g1["outer"] = d_outer
            norms = {k: float(v) for k, v in _sq_tree(d_outer).items()}
            norms.update(sq)
            self.grad_norms = {k: v ** 0.5 for k, v in norms.items()}
        self.outer = _update(self.outer, d_outer,
                             None if first else g1["outer"], F32(t),
                             self.hyper)
        self._g1 = g1 if first else None


def _borrow(cls, names):
    """nemotron_h.RefTrainer's methods, looking their names up HERE: the
    walk names no block (sub-layers by kind, `_layer_fwd`, `_layer_bwd`,
    MIXTURE, KIND_NAMES, BIAS, `_head_loss`)."""
    from reference import nemotron_h

    for name in names:
        f = getattr(nemotron_h.RefTrainer, name)
        setattr(cls, name, types.FunctionType(
            f.__code__, globals(), name, f.__defaults__))


_borrow(RefTrainer, ("_forward", "_note", "run", "delta_norms"))


def compile_ahead(outer, layers, cfg, batch, seq, hyper, precision="float32"):
    """Lower and compile, executing nothing, the programs that
    `RefTrainer.run` calls for these shapes (reference/ling3.py
    `compile_ahead`). `outer` maps leaf names to shapes; `layers` maps a
    kind to {leaf: shape}."""
    def spec(shape, dtype=F32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    c = _config(cfg)
    frozen = _freeze(c)
    outer = {k: spec(v) for k, v in outer.items()}
    x = spec((batch, seq, c["hidden_size"]))
    ids = spec((batch, seq), jnp.int32)
    picks = spec((batch, seq, c["num_experts_per_tok"]), jnp.int32)
    e = spec((c["num_experts"],))
    hyper = tuple(float(v) for v in hyper)

    jobs = []

    def later(fn, *args):
        jobs.append(lambda: fn.lower(*args).compile())

    def small(p):
        for g1 in (None, p):
            later(_update, p, p, g1, spec(()), hyper)
        later(_sq_tree, p)
        later(_sq_diff, p, p)

    small(outer)
    embedding = outer["embed_tokens.weight"]
    later(_tied_grad, embedding, embedding, ids, x)
    for kind in sorted(set(kinds_of(c))):
        p = {k: spec(v) for k, v in layers[kind].items()}
        small(p)
        if kind == MIXTURE:
            for given in (picks, None):
                later(_layer_fwd, kind, p, e, x, given, frozen, precision)
            later(_layer_bwd, kind, p, e, x, picks, x, e, frozen, precision)
        else:
            later(_layer_fwd, kind, p, None, x, None, frozen, precision)
            later(_layer_bwd, kind, p, None, x, None, x, None, frozen,
                  precision)
    later(_head_grads, outer, x, ids, frozen, precision)
    later(_head_loss, outer, x, ids, frozen, precision)

    def work(job):
        # the precision is a thread's own setting
        with jax.default_matmul_precision("highest"):
            job()

    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        list(pool.map(work, jobs))


def loss_and_grads(outer, layers, cfg, ids, labels, precision="float32",
                   given=None, wrong=()):
    """(loss, (lm, balance), grads of every leaf) of one batch: the first
    half-step of `RefTrainer`, for tests. grads = {"outer": {...},
    "layers": [{...}]}."""
    t = RefTrainer(outer, layers, cfg, (0.0, 0.9, 0.95, 1e-8, 0.0), precision,
                   given=given, wrong=wrong)
    with jax.default_matmul_precision("highest"):
        t._step(jnp.asarray(ids, jnp.int32), jnp.asarray(labels, jnp.int32),
                1)
    return t.losses[0], t.parts[0], t._g1


def logits(outer, layers, cfg, ids, precision="float32", wrong=()):
    """float32 [B, S, V] of the model's own picks: the forward alone, for
    tests."""
    t = RefTrainer(outer, layers, cfg, (0.0, 0.9, 0.95, 1e-8, 0.0), precision,
                   wrong=wrong)
    with jax.default_matmul_precision("highest"):
        x = t._forward(jnp.asarray(ids, jnp.int32), want_grads=False)[0]
        a = rms(x, t.outer["norm.weight"], dict(t.cfg)["norm_eps"])
        return mm("bsh,vh->bsv", a, t.outer["embed_tokens.weight"], precision)
