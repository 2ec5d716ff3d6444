"""The plain reference of Nemotron-H's language model (the decoder of
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
config.json, `model_type` `nemotron_h`) in straightforward `jax.numpy`. It
imports nothing of the program.

Every layer is u <- u + mixer(rms(u) g), the mixer chosen by the layer's
letter of `hybrid_override_pattern`; a final rms and an untied head.

`M` (Mamba-2), h = rms(u) g [S, hidden] of one sequence:
  [z | xBC | dt] = h W_in            (inner | inner + 2 G N | heads)
  xBC[t] = silu(b + sum_k w[k] xBC[t - (K - 1) + k])      K taps, causal
  x [heads, P], B, C [G, N] = split(xBC);  head h reads group h // (heads / G)
  D_t = softplus(dt_t + dt_bias), A = -exp(A_log)          a head
  THE RECURRENCE ITSELF, one time step after another (a `lax.scan` over the
  steps, in runs of `chunk_size` steps under `jax.checkpoint` so that its
  backward holds a state a run, not a step):
      S_t = exp(D_t A) S_{t-1} + D_t B_t x_t^T;   y_t = S_t C_t + D x_t
  v = y * silu(z);  v <- v / sqrt(mean over each of the G groups of
  inner / G channels of v^2 + eps) * gn;   out = v W_out
`*` (attention): q = h Wq [heads x d], k = h Wk, v = h Wv [kv x d];
  o[t] = sum_{s <= t} softmax_s(q[t] . k[s] / sqrt(d)) v[s];  out = o Wo.
  No rotary turn, no q/k norm.
`E` (mixture): s = sigmoid(h W_r) over all `n_routed_experts`
  E_t = top k of s[t] + bias;  g[t, e] = s[t, e] / (sum_{E_t} s + 1e-20) * scale
  out = sum over the HELD experts e (a loop) of g[t, e] relu(h Wu_e)^2 Wd_e
        + relu(h Wu_s)^2 Wd_s                       (the shared expert, once)

  loss = mean CE(head(rms(u_L) gf)) + mean over the E layers of
         [coef * n_routed_experts * sum_e f_e P_e]
  f_e: share of the batch's tokens that picked e (no gradient); P_e: mean
  over tokens of s[t, e] / sum_e' s[t, e'].

float32 under `jax.default_matmul_precision("highest")`. Attention and the
mixture walk sequence by sequence (attention block of queries by block of
queries); the recurrence carries every sequence's state at once forward
and walks sequence by sequence backward. AdamW's state after the first
update is kept as the first gradient (as reference/gpt.py does).
`precision="fp8"` is the control: every matrix
product's operands rounded to e4m3 with a per-tensor scale (the recurrence
has no matrix product: it is sums of float32 products as written).
`zero_state=True` starts every run of `chunk_size` steps from a zero state
and `skip_d=True` leaves D x out: what two wrong programs would compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# what does not name a block: the product in the stated precision (`mm`),
# rms, the head and its loss, AdamW and the jitted helpers over whole dicts
# of leaves. One copy, keye_vl2.py's.
from reference.keye_vl2 import (  # noqa: F401
    _embed_grad, _freeze, _head_grads, _head_loss, _sq_diff, _sq_tree,
    _update, mm, rms)

F32 = jnp.float32
MAMBA, MIXTURE, ATTENTION = "M", "E", "*"
KIND_NAMES = {MAMBA: "mamba", MIXTURE: "mixture", ATTENTION: "attention"}
LEAVES = {
    MAMBA: ("norm.weight", "mixer.in_proj.weight", "mixer.conv_weight",
            "mixer.conv_bias", "mixer.dt_bias", "mixer.A_log", "mixer.D",
            "mixer.norm.weight", "mixer.out_proj.weight"),
    ATTENTION: ("norm.weight", "mixer.q_proj.weight", "mixer.k_proj.weight",
                "mixer.v_proj.weight", "mixer.o_proj.weight"),
    MIXTURE: ("norm.weight", "mixer.experts.router", "mixer.experts.up_proj",
              "mixer.experts.down_proj", "mixer.shared_up.weight",
              "mixer.shared_down.weight"),
}
BIAS = "mixer.experts.score_bias"      # a buffer: no gradient, no update
OUTER_LEAVES = ("embed_tokens.weight", "norm.weight", "lm_head")
QUERY_BLOCK = 256


def kinds_of(cfg):
    return tuple(cfg["hybrid_override_pattern"][:cfg["num_layers"]])


# -- the layers -------------------------------------------------------------

def recurrence(x, delta, a, b, c, d, run, zero_state=False):
    """The state-space recurrence, step by step. x [B, S, G, r, P] (G
    groups of r heads); delta [B, S, G, r]; a, d [G, r]; b, c [B, S, G, N]
    (a group's, read by its r heads) -> y like x. `run` steps make one
    checkpointed inner scan."""
    def step(s, ins):
        xt, dt, bt, ct = ins
        s = (jnp.exp(dt * a)[..., None, None] * s
             + (dt[..., None, None] * bt[:, :, None, :, None])
             * xt[..., None, :])
        return s, jnp.sum(s * ct[:, :, None, :, None], axis=-2) \
            + d[..., None] * xt

    @jax.checkpoint
    def steps(s, ins):
        if zero_state:
            s = jnp.zeros_like(s)
        return jax.lax.scan(step, s, ins)

    def runs(v):            # [B, S, ...] -> [S / run, run, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((v.shape[0] // run, run) + v.shape[1:])

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:4] + (b.shape[-1], x.shape[4]),
                   F32)
    y = jax.lax.scan(steps, s0, tuple(runs(v) for v in (x, delta, b, c)))[1]
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)


def mamba(p, x, cfg, precision):
    """x [B, S, H] -> x + the Mamba-2 mixer of every sequence."""
    bsz, s, _ = x.shape
    heads, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n, taps = cfg["n_groups"], cfg["ssm_state_size"], \
        cfg["conv_kernel"]
    inner, eps, r = heads * hp, cfg["layer_norm_epsilon"], heads // groups
    h = rms(x, p["norm.weight"], eps)
    zxd = mm("bsh,hd->bsd", h, p["mixer.in_proj.weight"], precision)
    z, xbc, dt = (zxd[..., :inner], zxd[..., inner:-heads], zxd[..., -heads:])
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["mixer.conv_bias"] + sum(
        padded[:, k:k + s] * p["mixer.conv_weight"][k] for k in range(taps)))

    def grouped(v):         # a per-head vector [.., heads] -> [.., G, r]
        return v.reshape(v.shape[:-1] + (groups, r))

    y = recurrence(
        xbc[..., :inner].reshape(bsz, s, groups, r, hp),
        grouped(jax.nn.softplus(dt + p["mixer.dt_bias"])),
        grouped(-jnp.exp(p["mixer.A_log"])),
        xbc[..., inner:inner + groups * n].reshape(bsz, s, groups, n),
        xbc[..., inner + groups * n:].reshape(bsz, s, groups, n),
        grouped(jnp.zeros_like(p["mixer.D"]) if cfg["skip_d"]
                else p["mixer.D"]),
        cfg["chunk_size"], cfg["zero_state"]).reshape(bsz, s, inner)
    v = (y * jax.nn.silu(z)).reshape(bsz, s, groups, inner // groups)
    v = v / jnp.sqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + eps)
    return x + mm("bsd,dh->bsh", v.reshape(bsz, s, inner)
                  * p["mixer.norm.weight"], p["mixer.out_proj.weight"],
                  precision)


def attention(p, x, cfg, precision):
    """x [S, H] -> x + causal attention of one sequence."""
    s, _ = x.shape
    heads, kvh, d = (cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    h = rms(x, p["norm.weight"], cfg["layer_norm_epsilon"])
    q = mm("sh,hd->sd", h, p["mixer.q_proj.weight"],
           precision).reshape(s, heads, d)
    k = mm("sh,hd->sd", h, p["mixer.k_proj.weight"],
           precision).reshape(s, kvh, d)
    v = mm("sh,hd->sd", h, p["mixer.v_proj.weight"],
           precision).reshape(s, kvh, d)
    block = min(QUERY_BLOCK, s)
    cols = jnp.arange(s, dtype=jnp.int32)

    @jax.checkpoint
    def rows(args):
        t0, qb = args
        keep = (t0 + jnp.arange(block, dtype=jnp.int32))[:, None] \
            >= cols[None, :]
        logits = mm("tkgd,skd->kgts",
                    qb.reshape(block, kvh, heads // kvh, d), k,
                    precision) / jnp.sqrt(F32(d))
        prob = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        return mm("kgts,skd->tkgd", prob, v,
                  precision).reshape(block, heads * d)

    n = s // block
    out = jax.lax.map(rows, (jnp.arange(n, dtype=jnp.int32) * block,
                             q.reshape((n, block) + q.shape[1:])))
    return x + mm("sd,dh->sh", out.reshape(s, heads * d),
                  p["mixer.o_proj.weight"], precision)


def scores(p, x, cfg, precision):
    h2 = rms(x, p["norm.weight"], cfg["layer_norm_epsilon"])
    return h2, jax.nn.sigmoid(
        mm("sh,he->se", h2, p["mixer.experts.router"], precision))


def mixture(p, bias, x, cfg, precision, picks=None):
    """x [S, H] -> (x + the held experts' part + the shared expert, sum_t
    of the normalised scores [E], tokens that picked each expert [E], the
    picks [S, k]). `picks` given: those experts are taken in place of the
    top k (their weights still this function's own scores)."""
    lo, hi = cfg["held_experts"]
    h2, s = scores(p, x, cfg, precision)
    if picks is None:
        picks = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(s, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]

    def relu2(w_up, w_down):
        a = jnp.square(jnp.maximum(mm("sh,hn->sn", h2, w_up, precision), 0))
        return mm("sn,nh->sh", a, w_down, precision)

    def one(y, xs):
        e, wu, wd = xs
        gate = jnp.sum(jnp.where(picks == e, top, 0.0), axis=-1)
        return y + gate[:, None] * relu2(wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(lo, hi), p["mixer.experts.up_proj"],
                         p["mixer.experts.down_proj"]))
    y = y + relu2(p["mixer.shared_up.weight"], p["mixer.shared_down.weight"])
    picked = jnp.zeros((s.shape[-1],), F32).at[picks.reshape(-1)].add(1.0)
    return x + y, jnp.sum(s / jnp.sum(s, -1, keepdims=True), 0), picked, picks


def mixture_given(p, bias, x, cfg, precision, picks):
    """`mixture` on given picks, and the share of them that are not the
    layer's own."""
    out = mixture(p, bias, x, cfg, precision, picks)
    if picks is None:
        return out + (F32(0),)
    mine = jax.lax.top_k(scores(p, x, cfg, precision)[1] + bias,
                         cfg["num_experts_per_tok"])[1]
    hit = jnp.any(picks[:, :, None] == mine[:, None, :], axis=-1)
    return out + (1.0 - jnp.mean(hit),)


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _layer_fwd(kind, p, bias, x, picks, cfg, precision):
    """Every sequence through one layer of the kind -> x, and for a
    mixture (x, sum_t scores, picked, picks, given picks not its own)."""
    cfg = dict(cfg)
    if kind == MAMBA:
        return mamba(p, x, cfg, precision)
    if kind == ATTENTION:
        return jax.lax.map(lambda a: attention(p, a, cfg, precision), x)
    return jax.lax.map(
        lambda a: mixture_given(p, bias, a[0], cfg, precision, a[1]),
        (x, picks))


@functools.partial(jax.jit, static_argnums=(0, 7, 8))
def _layer_bwd(kind, p, bias, x, picks, dy, f_weight, cfg, precision):
    """(dp, dx) of one layer. A mixture's share of the loss is also
    sum_e f_weight[e] * sum_t scores[t, e]: f_weight holds the batch's
    picks, which have no gradient; `picks` [B, S, k] are the forward
    walk's."""
    cfg = dict(cfg)

    def row(acc, a):
        xs, ps, dys = a
        if kind == MAMBA:       # a sequence at a time: its float32
            _, vjp = jax.vjp(   # intermediates are 3 GB a sequence
                lambda pp, xx: mamba(pp, xx[None], cfg, precision)[0], p, xs)
            dp, dx = vjp(dys)
        elif kind == ATTENTION:
            _, vjp = jax.vjp(lambda pp, xx: attention(pp, xx, cfg, precision),
                             p, xs)
            dp, dx = vjp(dys)
        else:
            def f(pp, xx):
                y, score_sum = mixture(pp, bias, xx, cfg, precision, ps)[:2]
                return y, jnp.sum(f_weight * score_sum)

            _, vjp = jax.vjp(f, p, xs)
            dp, dx = vjp((dys, jnp.ones((), F32)))
        return jax.tree.map(jnp.add, acc, dp), dx

    return jax.lax.scan(row, jax.tree.map(jnp.zeros_like, p), (x, picks, dy))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _layer_scalars(score_sum, picked, pick_miss, scale, tokens, coef, held):
    """What one mixture layer's forward walk adds to a step's numbers: the
    balance term, pairs on held experts, the fullest held expert's pairs,
    the worst sequence's share of given picks that are not the layer's
    own, and the balance term's weight on sum_t scores[t, :] [E]."""
    f = jnp.sum(picked, 0) / tokens
    mine = jnp.sum(picked, 0)[held[0]:held[1]]
    return (coef * jnp.sum(f * jnp.sum(score_sum, 0) / tokens),
            jnp.sum(mine), jnp.max(mine), jnp.max(pick_miss), f * scale)


class RefTrainer:
    """Three losses and two AdamW updates of the whole model.

    `outer` holds embed_tokens.weight, norm.weight, lm_head [V, H];
    `layers` is a list of per-layer dicts of the layer's kind's LEAVES (the
    experts' leaves hold the held experts only, cfg["held_experts"] =
    [lo, hi]; a mixture's dict also holds BIAS, which gets no gradient and
    no update); cfg["hybrid_override_pattern"] names each layer's kind.
    After `run`, `losses` has three entries (each the sum of the two
    `parts` of its step), `grad_norms` the per-leaf norm of the first
    gradient (a layer leaf `<kind name>.<leaf>` over all layers of the
    kind), `counts` the first step's routed pairs and fullest expert,
    `picks` the first step's experts per mixture layer, `miss` how far the
    `given` picks are from the reference's own, and
    `delta_norms(outer0, layers0)` the per-leaf norm of the change after
    the two updates. `probe(tree, layer)` is handed every dict of first
    gradients, a layer's with its index, the outer leaves' with None.
    `zero_state` and `skip_d`: the references of two wrong programs
    (module docstring).
    """

    def __init__(self, outer, layers, cfg, hyper, precision="float32",
                 probe=None, given=None, zero_state=False, skip_d=False):
        self.kinds = kinds_of(cfg)
        self.outer = dict(outer)
        self.biases = [p.get(BIAS) for p in layers]
        self.layers = [{k: v for k, v in p.items() if k != BIAS}
                       for p in layers]
        cfg = dict(cfg, zero_state=bool(zero_state), skip_d=bool(skip_d))
        # the head's helpers read keye's name for the norm's epsilon
        cfg["rms_norm_eps"] = cfg["layer_norm_epsilon"]
        self.cfg, self.precision = _freeze(cfg), precision
        self.hyper = tuple(float(x) for x in hyper)   # lr b1 b2 eps wd
        self.losses, self.parts, self.grad_norms = [], [], {}
        self.counts = None
        # `given` = experts int [mixture layers, B, S, k]: the FIRST step
        # runs on these picks in place of its own top-k, and `miss` is the
        # largest share, over the layers, of the given experts that are
        # not the reference's own
        self.given, self.miss, self.picks = given, None, None
        self.probe = probe or (lambda tree, layer: None)
        self._g1 = None

    def _forward(self, ids, want_grads=True, given=None):
        """-> (x_L, per layer (input, experts or None), per layer the
        balance term's weight on sum_t scores [E] (None off a mixture),
        the balance term, (routed pairs, fullest expert over the mean,
        worst layer))."""
        cfg = dict(self.cfg)
        tokens = ids.size
        n_mix = max(self.kinds.count(MIXTURE), 1)
        x = self.outer["embed_tokens.weight"][ids]
        xs, weights, scalars = [], [], []
        coef = cfg["router_aux_loss_coef"] * cfg["n_routed_experts"]
        held = tuple(cfg["held_experts"])
        seen = 0
        for i, (kind, p) in enumerate(zip(self.kinds, self.layers)):
            x_in, picks, weight = x, None, None
            if kind == MIXTURE:
                ps = None if given is None else jnp.asarray(
                    given[seen], jnp.int32).reshape(ids.shape + (-1,))
                seen += 1
                x, score_sum, picked, picks, pm = _layer_fwd(
                    kind, p, self.biases[i], x, ps, self.cfg, self.precision)
                out = _layer_scalars(score_sum, picked, pm,
                                     F32(coef / tokens / n_mix), tokens,
                                     coef, held)
                weight = out[-1]
                scalars.append(out[:-1])
            else:
                x = _layer_fwd(kind, p, None, x, None, self.cfg,
                               self.precision)
            xs.append((x_in, picks) if want_grads else None)
            weights.append(weight)
        if not scalars:
            return x, xs, weights, 0.0, (0, 0.0)
        balance, routed, fullest, pm = (
            [float(v) for v in col] for col in zip(*scalars))
        if given is not None:
            self.miss = {"expert_pick_miss": max(pm)}
        load = max(f / max(r / (held[1] - held[0]), 1e-30)
                   for f, r in zip(fullest, routed))
        return (x, xs, weights, sum(balance) / n_mix,
                (int(sum(routed)), load))

    def _note(self, lm, balance):
        self.parts.append((float(lm), float(balance)))
        self.losses.append(sum(self.parts[-1]))

    def _step(self, ids, labels, t):
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
        d_outer["embed_tokens.weight"] = _embed_grad(
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

    def run(self, batches):
        """`batches`: three (ids, labels) pairs of int arrays [B, S]."""
        with jax.default_matmul_precision("highest"):
            for t, (ids, labels) in enumerate(batches[:2], start=1):
                self._step(jnp.asarray(ids, jnp.int32),
                           jnp.asarray(labels, jnp.int32), t)
            ids, labels = batches[2]
            x, _, _, balance, _ = self._forward(
                jnp.asarray(ids, jnp.int32), want_grads=False)
            self._note(_head_loss(self.outer, x,
                                  jnp.asarray(labels, jnp.int32), self.cfg,
                                  self.precision), balance)
        return self

    def delta_norms(self, outer0, layers0):
        """Per-leaf norm of (current - initial), the initial leaves as
        the constructor took them."""
        out = {k: float(v) for k, v in _sq_diff(
            self.outer, {k: outer0[k] for k in self.outer}).items()}
        for kind, p, p0 in zip(self.kinds, self.layers, layers0):
            for k, v in _sq_diff(p, {k: p0[k] for k in p}).items():
                name = KIND_NAMES[kind] + "." + k
                out[name] = out.get(name, 0.0) + float(v)
        return {k: v ** 0.5 for k, v in out.items()}


def compile_ahead(outer, layers, cfg, batch, seq, hyper, precision="float32"):
    """Lower and compile, executing nothing, the programs that
    `RefTrainer.run` calls for these shapes: per kind of layer the forward
    walk (a mixture's twice: given picks, and its own), the backward walk,
    both AdamW updates (`hyper` as the trainer takes it) and the sums of
    squares, then the head with and without its gradient, the embedding's
    gradient and the outer leaves' updates: with three kinds of layer the
    small programs are forty. `outer` maps leaf names to shapes; `layers`
    maps a kind's letter to {leaf: shape}. `run` then finds them compiled:
    for a caller that has two minutes of compiling of its own to wait for
    meanwhile, on another thread."""
    def spec(shape, dtype=F32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    c = dict(cfg, zero_state=False, skip_d=False,
             rms_norm_eps=cfg["layer_norm_epsilon"])
    frozen = _freeze(c)
    outer = {k: spec(v) for k, v in outer.items()}
    x = spec((batch, seq, c["hidden_size"]))
    ids = spec((batch, seq), jnp.int32)
    picks = spec((batch, seq, c["num_experts_per_tok"]), jnp.int32)
    e = spec((c["n_routed_experts"],))
    hyper = tuple(float(v) for v in hyper)

    def small(p):
        for g1 in (None, p):
            _update.lower(p, p, g1, spec(()), hyper).compile()
        _sq_tree.lower(p).compile()
        _sq_diff.lower(p, p).compile()

    with jax.default_matmul_precision("highest"):
        small(outer)
        _embed_grad.lower(outer["embed_tokens.weight"], ids, x).compile()
        for kind in sorted(set(kinds_of(c))):
            p = {k: spec(v) for k, v in layers[kind].items()}
            small(p)
            if kind == MIXTURE:
                for given in (picks, None):
                    _layer_fwd.lower(kind, p, e, x, given, frozen,
                                     precision).compile()
                _layer_bwd.lower(kind, p, e, x, picks, x, e, frozen,
                                 precision).compile()
            else:
                _layer_fwd.lower(kind, p, None, x, None, frozen,
                                 precision).compile()
                _layer_bwd.lower(kind, p, None, x, None, x, None, frozen,
                                 precision).compile()
        _head_grads.lower(outer, x, ids, frozen, precision).compile()
        _head_loss.lower(outer, x, ids, frozen, precision).compile()


def loss_and_grads(outer, layers, cfg, ids, labels, precision="float32",
                   given=None, zero_state=False, skip_d=False):
    """(loss, (lm, balance), grads of every leaf) of one batch: the first
    half-step of `RefTrainer`, for tests. grads = {"outer": {...},
    "layers": [{...}]}."""
    t = RefTrainer(outer, layers, cfg, (0.0, 0.9, 0.95, 1e-8, 0.0), precision,
                   given=given, zero_state=zero_state, skip_d=skip_d)
    with jax.default_matmul_precision("highest"):
        t._step(jnp.asarray(ids, jnp.int32), jnp.asarray(labels, jnp.int32),
                1)
    return t.losses[0], t.parts[0], t._g1


def logits(outer, layers, cfg, ids, precision="float32", zero_state=False,
           skip_d=False):
    """float32 [B, S, V] of the model's own picks: the forward alone, for
    tests."""
    t = RefTrainer(outer, layers, cfg, (0.0, 0.9, 0.95, 1e-8, 0.0), precision,
                   zero_state=zero_state, skip_d=skip_d)
    with jax.default_matmul_precision("highest"):
        x = t._forward(jnp.asarray(ids, jnp.int32), want_grads=False)[0]
        a = rms(x, t.outer["norm.weight"], dict(t.cfg)["rms_norm_eps"])
        return mm("bsh,vh->bsv", a, t.outer["lm_head"], precision)
