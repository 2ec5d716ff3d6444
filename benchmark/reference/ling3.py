"""The plain reference of Ling-3.0-flash-VL's language model (the decoder of
https://huggingface.co/inclusionAI/Ling-3.0-flash-VL, config.json) in
straightforward `jax.numpy`. It imports nothing of the program.

A published layer is u <- u + mixer(rms(u) g1), then u <- u + ffn(rms(u) g2).
Here each half is a SUB-LAYER of its own kind, u <- u + f(rms(u) g), so a
model of L layers is a walk over 2 L of them: `kda` or `mla` (layer i is
`mla` where (i + 1) % layer_group_size == 0), then `dense` (i <
first_k_dense_replace) or `moe`. A final rms and an untied head.

`kda` (Kimi Delta Attention, arXiv:2510.26692 section 3), h = rms(u) g:
  q~, k~, v~ = h Wq, h Wk, h Wv                      [heads x 128 each]
  each through x[t] = silu(sum_j w[j] x[t - 3 + j])  4 taps, causal, no bias
  q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(128), k likewise without the scale
  a_t = lower_bound * sigmoid(exp(A_log) (h Wf + dt_bias))   a channel, in
        (lower_bound, 0); A_log a head, dt_bias a channel
  beta_t = sigmoid(h Wb)                             a head
  THE RECURRENCE ITSELF, one token after another (a `lax.scan` over the
  tokens, in runs of 64 under `jax.checkpoint` so that its backward holds
  a state a run, not a token), S [128 keys, 128 values] from zero:
      S <- Diag(exp a_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T
      o_t = S^T q_t
  out = [sigmoid(h Wg) a head * (o_t / sqrt(mean_128 o_t^2 + eps) * gn)] Wo
`mla` (latent attention, expanded form), one sequence:
  q = h Wq [heads x 192];  [c | kr] = h Wkva (512 | 64)
  [kn | v] = (rms(c) gc) Wkvb [heads x (128 | 128)];  k = [kn | kr] a head
  q, k <- rms over the head's 192, gains gq, gk; the last 64 of each
  turned by position (rotate-half, theta);  causal softmax at 192^-1/2
  out = [sigmoid(h Wg) a head * o] Wo
`dense`: out = (silu(h Wg) * (h Wu)) Wd
`moe`: s = sigmoid(h Wr) over all `num_experts`; c = s + bias; a group's
  score (n_group contiguous groups) is the sum of its two largest c; only
  the topk_group best groups' experts can be picked; E_t = the top k of c
  there (ties: lower index); g[t, e] = s[t, e] / (sum_{E_t} s + 1e-20) *
  scale;  out = sum over the HELD experts e (a loop) of g[t, e]
  (silu(h Wg_e) * (h Wu_e)) Wd_e + the shared expert's, once

  loss = mean CE(head(rms(u_L) gf)) + mean over the moe sub-layers of
         [coef * num_experts * sum_e f_e P_e]
  f_e: share of the batch's tokens that picked e (no gradient); P_e: mean
  over tokens of s[t, e] / sum_e' s[t, e'].

float32 under `jax.default_matmul_precision("highest")`. `precision="fp8"`
is the control: every matrix product's operands rounded to e4m3 with a
per-tensor scale (the recurrence has no matrix product: it is sums of
float32 products as written). Four wrong programs, for
benchmark/calibrate_wrong.py: `no_correction` drops beta k k^T S from the
recurrence (gated linear attention), `head_decay` gives every channel of a
head the head's mean a, `zero_state` starts every run of 64 tokens from a
zero state, `no_group_limit` picks the top k of c over all experts.
"""
from __future__ import annotations

import concurrent.futures
import functools

import jax
import jax.numpy as jnp

# what does not name a block (reference/nemotron_h.py's note)
from reference.keye_vl2 import (  # noqa: F401
    _embed_grad, _freeze, _head_grads, _head_loss, _sq_diff, _sq_tree,
    _update, mm, rms)
from reference.nemotron_h import _layer_scalars

F32 = jnp.float32
KDA, MLA, DENSE, MIXTURE = "kda", "mla", "dense", "moe"
KIND_NAMES = {k: k for k in (KDA, MLA, DENSE, MIXTURE)}
LEAVES = {
    KDA: ("input_norm.weight", "mixer.q_proj.weight", "mixer.k_proj.weight",
          "mixer.v_proj.weight", "mixer.q_conv", "mixer.k_conv",
          "mixer.v_conv", "mixer.f_proj.weight", "mixer.A_log",
          "mixer.dt_bias", "mixer.b_proj.weight", "mixer.g_proj.weight",
          "mixer.o_norm.weight", "mixer.o_proj.weight"),
    MLA: ("input_norm.weight", "mixer.q_proj.weight",
          "mixer.kv_a_proj.weight", "mixer.kv_a_norm.weight",
          "mixer.kv_b_proj.weight", "mixer.q_norm.weight",
          "mixer.k_norm.weight", "mixer.g_proj.weight",
          "mixer.o_proj.weight"),
    DENSE: ("post_norm.weight", "ffn.gate_proj.weight", "ffn.up_proj.weight",
            "ffn.down_proj.weight"),
    MIXTURE: ("post_norm.weight", "ffn.experts.router",
              "ffn.experts.gate_proj", "ffn.experts.up_proj",
              "ffn.experts.down_proj", "ffn.shared_gate.weight",
              "ffn.shared_up.weight", "ffn.shared_down.weight"),
}
BIAS = "ffn.experts.score_bias"        # a buffer: no gradient, no update
OUTER_LEAVES = ("embed_tokens.weight", "norm.weight", "lm_head")
QUERY_BLOCK = 256
RUN = 64
WRONG = ("no_correction", "head_decay", "zero_state", "no_group_limit")


def kinds_of(cfg):
    """The 2 L sub-layers' kinds, in order."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out.append(MLA if (i + 1) % cfg["layer_group_size"] == 0 else KDA)
        out.append(DENSE if i < cfg["first_k_dense_replace"] else MIXTURE)
    return tuple(out)


# -- the sub-layers -----------------------------------------------------------

def kda_recurrence(q, k, v, a, beta, run=RUN, zero_state=False,
                   no_correction=False):
    """The gated delta rule, token by token. q, k, a [B, S, H, K]; v [B, S,
    H, V]; beta [B, S, H] -> o like v. `run` tokens make one checkpointed
    inner scan."""
    def step(s, ins):
        qt, kt, vt, at, bt = ins
        s = s * jnp.exp(at)[..., None]
        seen = 0.0 if no_correction else jnp.sum(kt[..., None] * s, axis=-2)
        s = s + (bt[..., None] * kt)[..., None] * (vt - seen)[..., None, :]
        return s, jnp.sum(qt[..., None] * s, axis=-2)

    @jax.checkpoint
    def steps(s, ins):
        if zero_state:
            s = jnp.zeros_like(s)
        return jax.lax.scan(step, s, ins)

    def runs(x):            # [B, S, ...] -> [S / run, run, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((x.shape[0] // run, run) + x.shape[1:])

    s0 = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], F32)
    o = jax.lax.scan(steps, s0, tuple(runs(x) for x in (q, k, v, a, beta)))[1]
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)


def short_conv(x, w):
    """x [B, S, C], w [taps, C] (the last tap weighs the token itself) ->
    silu of the depthwise causal convolution."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + s] * w[j] for j in range(taps)))


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def decay(p, h, cfg, precision):
    """a [B, S, heads, 128] float32 in (lower_bound, 0)."""
    heads = cfg["num_attention_heads"]
    f = mm("bsh,hd->bsd", h, p["mixer.f_proj.weight"], precision)
    f = (f + p["mixer.dt_bias"]).reshape(f.shape[:2] + (heads, -1))
    a = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["mixer.A_log"])[:, None] * f)
    if cfg["head_decay"]:
        a = jnp.broadcast_to(jnp.mean(a, -1, keepdims=True), a.shape)
    return a


def kda(p, x, cfg, precision):
    """x [B, S, H] -> x + the KDA mixer of every sequence."""
    bsz, s, _ = x.shape
    heads, d, eps = (cfg["num_attention_heads"], cfg["head_dim"],
                     cfg["rms_norm_eps"])
    h = rms(x, p["input_norm.weight"], eps)

    def branch(name):
        y = mm("bsh,hd->bsd", h, p[f"mixer.{name}_proj.weight"], precision)
        return short_conv(y, p[f"mixer.{name}_conv"]).reshape(bsz, s, heads,
                                                              d)

    q, k, v = unit(branch("q")) / jnp.sqrt(F32(d)), unit(branch("k")), \
        branch("v")
    beta = jax.nn.sigmoid(mm("bsh,hn->bsn", h, p["mixer.b_proj.weight"],
                             precision))
    o = kda_recurrence(q, k, v, decay(p, h, cfg, precision), beta,
                       min(cfg["kda_run"], s), cfg["zero_state"],
                       cfg["no_correction"])
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps) \
        * p["mixer.o_norm.weight"]
    gate = jax.nn.sigmoid(mm("bsh,hn->bsn", h, p["mixer.g_proj.weight"],
                             precision))
    return x + mm("bsd,dh->bsh", (o * gate[..., None]).reshape(bsz, s, -1),
                  p["mixer.o_proj.weight"], precision)


def turn(x, theta):
    """Rotate-half turn of x [S, heads, r] by the positions 0..S-1."""
    s, _, r = x.shape
    inv = F32(theta) ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + half * sin


def mla(p, x, cfg, precision):
    """x [S, H] -> x + latent attention (expanded) of one sequence."""
    s, _ = x.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, dv, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    h = rms(x, p["input_norm.weight"], eps)
    q = mm("sh,hd->sd", h, p["mixer.q_proj.weight"],
           precision).reshape(s, heads, nope + rope)
    kva = mm("sh,hd->sd", h, p["mixer.kv_a_proj.weight"], precision)
    kvb = mm("sr,rd->sd", rms(kva[:, :rank], p["mixer.kv_a_norm.weight"],
                              eps),
             p["mixer.kv_b_proj.weight"], precision).reshape(s, heads,
                                                             nope + dv)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        kva[:, None, rank:], (s, heads, rope))], -1)
    v = kvb[..., nope:]
    q = rms(q, p["mixer.q_norm.weight"], eps)
    k = rms(k, p["mixer.k_norm.weight"], eps)
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:],
                                             cfg["rope_theta"])], -1)
    k = jnp.concatenate([k[..., :nope], turn(k[..., nope:],
                                             cfg["rope_theta"])], -1)
    block = min(QUERY_BLOCK, s)
    cols = jnp.arange(s, dtype=jnp.int32)

    @jax.checkpoint
    def rows(args):
        t0, qb = args
        keep = (t0 + jnp.arange(block, dtype=jnp.int32))[:, None] \
            >= cols[None, :]
        logits = mm("tnd,snd->nts", qb, k, precision) \
            / jnp.sqrt(F32(nope + rope))
        prob = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        return mm("nts,snd->tnd", prob, v, precision)

    n = s // block
    o = jax.lax.map(rows, (jnp.arange(n, dtype=jnp.int32) * block,
                           q.reshape((n, block) + q.shape[1:])))
    gate = jax.nn.sigmoid(mm("sh,hn->sn", h, p["mixer.g_proj.weight"],
                             precision))
    return x + mm("sd,dh->sh",
                  (o.reshape(s, heads, dv) * gate[..., None]).reshape(s, -1),
                  p["mixer.o_proj.weight"], precision)


def swiglu(h, wg, wu, wd, precision):
    a = jax.nn.silu(mm("sh,hn->sn", h, wg, precision)) \
        * mm("sh,hn->sn", h, wu, precision)
    return mm("sn,nh->sh", a, wd, precision)


def dense(p, x, cfg, precision):
    """x [S, H] -> x + the dense SwiGLU."""
    h = rms(x, p["post_norm.weight"], cfg["rms_norm_eps"])
    return x + swiglu(h, p["ffn.gate_proj.weight"], p["ffn.up_proj.weight"],
                      p["ffn.down_proj.weight"], precision)


def scores(p, x, cfg, precision):
    h2 = rms(x, p["post_norm.weight"], cfg["rms_norm_eps"])
    return h2, jax.nn.sigmoid(
        mm("sh,he->se", h2, p["ffn.experts.router"], precision))


def own_picks(s, bias, cfg):
    """The router's picks of scores s [S, E]: the top k of s + bias inside
    the topk_group best of n_group groups."""
    c = s + bias
    groups = cfg["n_group"]
    if groups > 1 and not cfg["no_group_limit"]:
        by_group = c.reshape(c.shape[0], groups, -1)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], -1)
        best = jax.lax.top_k(group_score, cfg["topk_group"])[1]
        kept = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
        c = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(c.shape)
    return jax.lax.top_k(c, cfg["num_experts_per_tok"])[1]


def mixture(p, bias, x, cfg, precision, picks=None):
    """x [S, H] -> (x + the held experts' part + the shared expert, sum_t
    of the normalised scores [E], tokens that picked each expert [E], the
    picks [S, k]). `picks` given: those experts are taken in place of the
    router's own (their weights still this function's own scores)."""
    lo, hi = cfg["held_experts"]
    h2, s = scores(p, x, cfg, precision)
    if picks is None:
        picks = own_picks(s, bias, cfg)
    top = jnp.take_along_axis(s, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]

    def one(y, xs):
        e, wg, wu, wd = xs
        gate = jnp.sum(jnp.where(picks == e, top, 0.0), axis=-1)
        return y + gate[:, None] * swiglu(h2, wg, wu, wd, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(lo, hi), p["ffn.experts.gate_proj"],
                         p["ffn.experts.up_proj"],
                         p["ffn.experts.down_proj"]))
    y = y + swiglu(h2, p["ffn.shared_gate.weight"], p["ffn.shared_up.weight"],
                   p["ffn.shared_down.weight"], precision)
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


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _layer_fwd(kind, p, bias, x, picks, cfg, precision):
    """Every sequence through one sub-layer of the kind -> x, and for a
    mixture (x, sum_t scores, picked, picks, given picks not its own)."""
    cfg = dict(cfg)
    if kind == KDA:
        return kda(p, x, cfg, precision)
    if kind == MLA:
        return jax.lax.map(lambda a: mla(p, a, cfg, precision), x)
    if kind == DENSE:
        return jax.lax.map(lambda a: dense(p, a, cfg, precision), x)
    return jax.lax.map(
        lambda a: mixture_given(p, bias, a[0], cfg, precision, a[1]),
        (x, picks))


@functools.partial(jax.jit, static_argnums=(0, 7, 8))
def _layer_bwd(kind, p, bias, x, picks, dy, f_weight, cfg, precision):
    """(dp, dx) of one sub-layer, a sequence at a time (nemotron_h.py
    `_layer_bwd`: a mixture's share of the loss is also sum_e f_weight[e]
    * sum_t scores[t, e])."""
    cfg = dict(cfg)
    plain = {KDA: lambda pp, xx: kda(pp, xx[None], cfg, precision)[0],
             MLA: lambda pp, xx: mla(pp, xx, cfg, precision),
             DENSE: lambda pp, xx: dense(pp, xx, cfg, precision)}

    def row(acc, a):
        xs, ps, dys = a
        if kind in plain:
            dp, dx = jax.vjp(plain[kind], p, xs)[1](dys)
        else:
            def f(pp, xx):
                y, score_sum = mixture(pp, bias, xx, cfg, precision, ps)[:2]
                return y, jnp.sum(f_weight * score_sum)

            dp, dx = jax.vjp(f, p, xs)[1]((dys, jnp.ones((), F32)))
        return jax.tree.map(jnp.add, acc, dp), dx

    return jax.lax.scan(row, jax.tree.map(jnp.zeros_like, p), (x, picks, dy))


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
    cfg.setdefault("kda_run", RUN)      # tokens of a checkpointed run
    return cfg


class RefTrainer:
    """Three losses and two AdamW updates of the whole model.

    `outer` holds embed_tokens.weight, norm.weight, lm_head [V, H];
    `layers` is a list of 2 L per-sub-layer dicts of the kind's LEAVES
    (`kinds_of`; the experts' leaves hold the held experts only,
    cfg["held_experts"] = [lo, hi]; a mixture's dict also holds BIAS, which
    gets no gradient and no update). After `run`: `losses`, `parts`,
    `grad_norms` (a sub-layer leaf `<kind>.<leaf>` over all sub-layers of
    the kind), `counts`, `picks`, `miss`, `delta_norms(outer0, layers0)`,
    and `probe(tree, sub-layer)`: reference/nemotron_h.py `RefTrainer`'s,
    whose walk this is. `wrong` names wrong programs (WRONG, module
    docstring).
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


def _borrow(cls, names):
    """nemotron_h.RefTrainer's methods, looking their names up HERE: the
    walk names no block (sub-layers by kind, `_layer_fwd`, `_layer_bwd`,
    MIXTURE, KIND_NAMES, BIAS)."""
    import types

    from reference import nemotron_h

    for name in names:
        f = getattr(nemotron_h.RefTrainer, name)
        setattr(cls, name, types.FunctionType(
            f.__code__, globals(), name, f.__defaults__))


_borrow(RefTrainer, ("_forward", "_note", "_step", "run", "delta_norms"))


def compile_ahead(outer, layers, cfg, batch, seq, hyper, precision="float32"):
    """Lower and compile, executing nothing, the programs that
    `RefTrainer.run` calls for these shapes (reference/nemotron_h.py
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
    later(_embed_grad, outer["embed_tokens.weight"], ids, x)
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

    # four at a time: a program is compiled on one core or two, the step's
    # own compilation holds a few more, and one after another the fifty
    # programs of four kinds of sub-layer outlast it (a first traced run's
    # set-up waited for them: PERF.md section 6, PR 41)
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
        a = rms(x, t.outer["norm.weight"], dict(t.cfg)["rms_norm_eps"])
        return mm("bsh,vh->bsv", a, t.outer["lm_head"], precision)
