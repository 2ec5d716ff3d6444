"""The plain reference of Mellum 2's language model (the decoder of
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, config.json)
in straightforward `jax.numpy`. It imports nothing of the program.

Layer l on x [S, H] of one sequence, positions [S], its kind
`layer_types[l]`:

  h = rms(x) g1;  q = h Wq [32 x 128], k = h Wk, v = h Wv [4 x 128]
      q, k: rms over each head (gains qn, kn), then rotate-half RoPE,
      pairs (i, i + 64), frequency f_i, cos and sin times `a`:
        sliding: f_i = theta ** (-i / 64), a = 1
        full:    YaRN, f_i = e_i (1 - r_i) + (e_i / 16) r_i with e_i the
                 plain frequency, r_i = clip((i - low) / (high - low), 0, 1),
                 low = floor(c(32)), high = ceil(c(1)),
                 c(n) = 128 ln(8192 / (2 pi n)) / (2 ln theta);
                 a = attention_factor (0.1 ln 16 + 1)
  o[t] = sum_s softmax_s(q[t] . k[s] / sqrt(128)) v[s] over the keys s
      with 0 <= t - s (every layer) and t - s < sliding_window (sliding)
  x = x + o Wo
  h2 = rms(x) g2;  p = softmax(h2 Wr) over all 64 experts
      E_t = top 8 of p[t];  g[t, e] = p[t, e] / sum_{E_t} p
      x = x + sum over the HELD experts e (a loop) of
              g[t, e] * (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

  loss = mean CE(head(rms(x_L) gf)) + mean over layers of
         [coef * 64 * sum_e f_e P_e]
  f_e: share of the batch's tokens that picked e (no gradient); P_e: mean
  of p[:, e].

Departures from the published description, each `assumed` in the
configuration's file: the per-head q/k norm (Qwen3-MoE's convention; no
key), the balance term and its 0.001 (no key), the window's inclusive
edge (t - s < window keeps `window` keys, the query's own among them),
YaRN's `truncate` on, the MTP head left out (no key or width for it), and
the chip's share: experts `held_experts` of the router's 64 and the
sliced vocabulary.

float32 under `jax.default_matmul_precision("highest")`. To fit a 16 GB
chip at published widths it walks layer by layer, sequence by sequence
and, inside attention, block of queries by block of queries (dense masked
scores against every key); AdamW's state after the first update is kept
as the first gradient (as reference/gpt.py does). `precision="fp8"` is the
control: every matrix product's operands rounded to e4m3 with a
per-tensor scale. `window=` and `yarn=False` run it with another window or
with plain RoPE on the full layers: runs that have to come out wrong.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# what does not name a block: the product in the stated precision (`mm`),
# rms, the held experts' loop, the head and its loss, AdamW and the jitted
# helpers over whole dicts of leaves. One copy, keye_vl2.py's.
from reference.keye_vl2 import (  # noqa: F401
    _embed_grad, _freeze, _head_grads, _head_loss, _sq_diff, _sq_tree,
    _update, experts, head_loss_sum, mm, rms)

F32 = jnp.float32
LAYER_LEAVES = (
    "input_layernorm.weight", "self_attn.q_proj.weight",
    "self_attn.k_proj.weight", "self_attn.v_proj.weight",
    "self_attn.o_proj.weight", "self_attn.q_norm.weight",
    "self_attn.k_norm.weight", "post_attention_layernorm.weight",
    "mlp.router", "mlp.gate_proj", "mlp.up_proj", "mlp.down_proj")
OUTER_LEAVES = ("embed_tokens.weight", "norm.weight", "lm_head")
QUERY_BLOCK = 256
SLIDING, FULL = "sliding_attention", "full_attention"



# -- the layer ---------------------------------------------------------------


def yarn_range(cfg):
    """(low, high): the dimensions between which YaRN's ramp runs."""
    d = cfg["head_dim"]

    def c(n):
        return d * math.log(cfg["yarn_original_positions"]
                            / (2 * math.pi * n)) \
            / (2 * math.log(cfg["rope_theta"]))

    return (max(math.floor(c(cfg["yarn_beta_fast"])), 0),
            min(math.ceil(c(cfg["yarn_beta_slow"])), d - 1))


def frequencies(cfg, kind):
    """(f [d / 2], a): the kind's rotary frequencies and the factor on
    cos and sin."""
    half = cfg["head_dim"] // 2
    i = jnp.arange(half, dtype=F32)
    plain = F32(cfg["rope_theta"]) ** (-i / half)
    if kind != FULL or not cfg["yarn_on"]:
        return plain, 1.0
    low, high = yarn_range(cfg)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (plain * (1 - ramp) + plain / cfg["yarn_factor"] * ramp,
            cfg["yarn_attention_factor"])


def rotary(x, positions, freq, factor):
    """x [S, heads, d]; positions [S]; rotate-half pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[:, None] * freq              # [S, half]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rotation(cfg, kind):
    """(f [d / 2], a, window): what tells one kind of layer from the
    other, as arrays, so that both kinds run ONE compiled program; a full
    layer's window is wider than any sequence."""
    freq, factor = frequencies(cfg, kind)
    window = cfg["sliding_window"] if kind == SLIDING else 2 ** 30
    return freq, F32(factor), jnp.int32(window)


def attention(p, x, positions, cfg, rot, precision):
    """x [S, H] -> x + attention of one sequence; `rot` = `rotation` of
    the layer's kind."""
    s, _ = x.shape
    heads, kvh, d = (cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    freq, factor, window = rot
    h = rms(x, p["input_layernorm.weight"], eps)
    q = mm("sh,hd->sd", h, p["self_attn.q_proj.weight"],
           precision).reshape(s, heads, d)
    k = mm("sh,hd->sd", h, p["self_attn.k_proj.weight"],
           precision).reshape(s, kvh, d)
    v = mm("sh,hd->sd", h, p["self_attn.v_proj.weight"],
           precision).reshape(s, kvh, d)
    q = rotary(rms(q, p["self_attn.q_norm.weight"], eps), positions, freq,
               factor)
    k = rotary(rms(k, p["self_attn.k_norm.weight"], eps), positions, freq,
               factor)
    block = min(QUERY_BLOCK, s)
    cols = jnp.arange(s, dtype=jnp.int32)

    @jax.checkpoint
    def rows(args):
        t0, qb = args
        ahead = (t0 + jnp.arange(block, dtype=jnp.int32))[:, None] \
            - cols[None, :]
        keep = (ahead >= 0) & (ahead < window)
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
                  p["self_attn.o_proj.weight"], precision)



def own_picks(p, x, cfg, precision="float32"):
    """The experts [S, k] the router itself picks at the mixture's input
    x: what given picks are held against."""
    h2 = rms(x, p["post_attention_layernorm.weight"], cfg["rms_norm_eps"])
    return jax.lax.top_k(
        jax.nn.softmax(mm("sh,he->se", h2, p["mlp.router"], precision), -1),
        cfg["num_experts_per_tok"])[1]


def layer(p, x, positions, cfg, rot, precision, picks=None):
    """One sequence through one layer of the kind `rot` (`rotation`)
    tells -> (x, sum_t p[t, :], tokens that picked each expert, the
    experts picked [S, k], the share of given `picks` that are not the
    layer's own); `picks` given take the place of the layer's own top-k."""
    xa = attention(p, x, positions, cfg, rot, precision)
    out = experts(p, xa, cfg, precision, picks)
    if picks is None:
        return out + (F32(0),)
    mine = own_picks(p, xa, cfg, precision)
    hit = jnp.any(picks[:, :, None] == mine[:, None, :], axis=-1)
    return out + (1.0 - jnp.mean(hit),)



@functools.partial(jax.jit, static_argnums=(5, 6))
def _layer_fwd(p, x, positions, picks, rot, cfg, precision):
    """Every sequence through one layer. `picks` [B, S, k] given: the
    layer runs on them, and the last result says which share of them is
    not the layer's own."""
    cfg = dict(cfg)

    return jax.lax.map(
        lambda a: layer(p, a[0], a[1], cfg, rot, precision, a[2]),
        (x, positions, picks))


@functools.partial(jax.jit, static_argnums=(7, 8))
def _layer_bwd(p, x, positions, picks, dy, f_weight, rot, cfg, precision):
    """(dp, dx) of one layer, one sequence at a time. The layer's share
    of the loss is sum_e f_weight[e] * sum_t p[t, e]: f_weight holds the
    batch's picks, which have no gradient; `picks` [B, S, k] are the
    forward walk's."""
    cfg = dict(cfg)

    def row(acc, a):
        xs, pos, ps, dys = a

        def f(pp, xx):
            y, prob_sum = layer(pp, xx, pos, cfg, rot, precision, ps)[:2]
            return y, jnp.sum(f_weight * prob_sum)

        _, vjp = jax.vjp(f, p, xs)
        dp, dx = vjp((dys, jnp.ones((), F32)))
        return jax.tree.map(jnp.add, acc, dp), dx

    return jax.lax.scan(row, jax.tree.map(jnp.zeros_like, p),
                        (x, positions, picks, dy))



@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _layer_scalars(prob_sum, picked, pick_miss, scale, tokens, coef, held):
    """What one layer's forward walk adds to a step's numbers: the
    balance term, pairs on held experts, the fullest held expert's pairs,
    the worst sequence's share of given picks that are not the layer's
    own, and the balance term's weight on sum_t p[t, :] [E]."""
    f = jnp.sum(picked, 0) / tokens
    mine = jnp.sum(picked, 0)[held[0]:held[1]]
    return (coef * jnp.sum(f * jnp.sum(prob_sum, 0) / tokens),
            jnp.sum(mine), jnp.max(mine), jnp.max(pick_miss), f * scale)




class RefTrainer:
    """Three losses and two AdamW updates of the whole model.

    `outer` holds embed_tokens.weight, norm.weight, lm_head [V, H];
    `layers` is a list of per-layer dicts of LAYER_LEAVES (the experts'
    leaves hold the held experts only, cfg["held_experts"] = [lo, hi]);
    cfg["layer_types"] names each layer's kind. After `run`, `losses` has
    three entries (each the sum of the two `parts` of its step),
    `grad_norms` the per-leaf norm of the first gradient, `counts` the
    first step's routed pairs and fullest expert, `picks` the first
    step's experts per layer, `miss` how far the `given` picks are from
    the reference's own, and `delta_norms(outer0, layers0)` the per-leaf
    norm of the change after the two updates (layer leaves over all
    layers together). `probe(tree, layer)` is handed every dict of first
    gradients, a layer's with its index, the outer leaves' with None.
    `window` and `yarn=False` replace the configuration's window and the
    full layers' YaRN table by plain RoPE: what a wrong program would
    compute.
    """

    def __init__(self, outer, layers, cfg, hyper, precision="float32",
                 probe=None, given=None, window=None, yarn=True):
        self.outer, self.layers = dict(outer), [dict(p) for p in layers]
        cfg = dict(cfg, yarn_on=bool(yarn))
        if window is not None:
            cfg["sliding_window"] = int(window)
        self.rots = [rotation(cfg, kind) for kind in cfg.pop("layer_types")]
        del cfg["sliding_window"], cfg["yarn_on"]    # in `rots` now
        self.cfg, self.precision = _freeze(cfg), precision
        self.hyper = tuple(float(x) for x in hyper)   # lr b1 b2 eps wd
        self.losses, self.parts, self.grad_norms = [], [], {}
        self.counts = None
        # `given` = experts int [L, B, S, k]: the FIRST step runs on these
        # picks in place of its own top-k (its gradient is then the
        # gradient at those picks), and `miss` is the largest share, over
        # the layers, of the given experts that are not the reference's own
        self.given, self.miss, self.picks = given, None, None
        self.probe = probe or (lambda tree, layer: None)
        self._g1 = None

    def _forward(self, ids, positions, want_grads=True, given=None):
        """-> (x_L, per layer (input, experts), per layer the balance
        term's weight on sum_t p[t, :] [E], the balance term, (routed
        pairs, fullest expert over the mean, worst layer))."""
        cfg = dict(self.cfg)
        tokens, n = ids.size, len(self.layers)
        x = self.outer["embed_tokens.weight"][ids]
        xs, weights, scalars = [], [], []
        coef = cfg["router_aux_loss_coef"] * cfg["num_experts"]
        held = tuple(cfg["held_experts"])
        for i, p in enumerate(self.layers):
            x_in = x
            ps = None if given is None else jnp.asarray(
                given[i], jnp.int32).reshape(ids.shape + (-1,))
            x, prob_sum, picked, picks, pm = _layer_fwd(
                p, x, positions, ps, self.rots[i], self.cfg, self.precision)
            out = _layer_scalars(prob_sum, picked, pm,
                                 F32(coef / tokens / n), tokens, coef, held)
            xs.append((x_in, picks) if want_grads else None)
            weights.append(out[-1])
            scalars.append(out[:-1])
        balance, routed, fullest, pm = (
            [float(v) for v in col] for col in zip(*scalars))
        if given is not None:
            self.miss = {"expert_pick_miss": max(pm)}
        load = max(f / max(r / (held[1] - held[0]), 1e-30)
                   for f, r in zip(fullest, routed))
        return x, xs, weights, sum(balance) / n, (int(sum(routed)), load)

    def _note(self, lm, balance):
        self.parts.append((float(lm), float(balance)))
        self.losses.append(sum(self.parts[-1]))

    def _step(self, ids, labels, positions, t):
        n = len(self.layers)
        first = self._g1 is None
        x, xs, weights, balance, counts = self._forward(
            ids, positions, given=self.given if first else None)
        lm, d_outer, dy = _head_grads(self.outer, x, labels, self.cfg,
                                      self.precision)
        self._note(lm, balance)
        if first:
            self.counts = {"routed_pairs": counts[0],
                           "max_load_over_mean": counts[1]}
            self.picks = [a[1] for a in xs]
        g1 = {"layers": [None] * n} if first else self._g1
        sq = []
        for i in reversed(range(n)):
            dp, dy = _layer_bwd(
                self.layers[i], xs[i][0], positions, xs[i][1], dy,
                weights[i], self.rots[i], self.cfg, self.precision)
            xs[i] = None
            if first:
                sq.append(_sq_tree(dp))
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
            for k in LAYER_LEAVES:
                norms["layers." + k] = sum(float(row[k]) for row in sq)
            self.grad_norms = {k: v ** 0.5 for k, v in norms.items()}
        self.outer = _update(self.outer, d_outer,
                             None if first else g1["outer"], F32(t),
                             self.hyper)
        self._g1 = g1 if first else None

    def run(self, batches, positions=None):
        """`batches`: three (ids, labels) pairs of int arrays [B, S];
        `positions` int [B, S], 0..S-1 in every row when None."""
        positions = _positions(batches[0][0].shape, positions)
        with jax.default_matmul_precision("highest"):
            for t, (ids, labels) in enumerate(batches[:2], start=1):
                self._step(jnp.asarray(ids, jnp.int32),
                           jnp.asarray(labels, jnp.int32), positions, t)
            ids, labels = batches[2]
            x, _, _, balance, _ = self._forward(
                jnp.asarray(ids, jnp.int32), positions, want_grads=False)
            self._note(_head_loss(self.outer, x,
                                  jnp.asarray(labels, jnp.int32), self.cfg,
                                  self.precision), balance)
        return self

    def delta_norms(self, outer0, layers0):
        """Per-leaf norm of (current - initial), the initial leaves as
        the constructor took them."""
        out = {k: float(v) for k, v in _sq_diff(
            self.outer, {k: outer0[k] for k in self.outer}).items()}
        rows = [_sq_diff(p, {k: p0[k] for k in p})
                for p, p0 in zip(self.layers, layers0)]
        for k in LAYER_LEAVES:
            out["layers." + k] = sum(float(row[k]) for row in rows)
        return {k: v ** 0.5 for k, v in out.items()}


def _positions(shape, positions=None):
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(shape[1], dtype=jnp.int32),
                                     shape)
    return jnp.asarray(positions, jnp.int32)


def compile_ahead(outer, layer, cfg, batch, seq, precision="float32"):
    """Lower and compile, executing nothing, the large programs that
    `RefTrainer.run` calls for these shapes: both forward walks (given
    picks, and its own) and the backward walk, one program each for both
    kinds of layer (`rotation`), then the head with and without its
    gradient. `outer` and `layer` map leaf
    names to shapes. `run` then finds them compiled and compiles none of
    them again: for a caller that has a minute of compiling of its own to
    wait for meanwhile, on another thread."""
    def spec(shape, dtype=F32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    c = dict(cfg)
    for key in ("layer_types", "sliding_window"):
        del c[key]
    frozen = _freeze(c)
    outer = {k: spec(v) for k, v in outer.items()}
    p = {k: spec(v) for k, v in layer.items()}
    x = spec((batch, seq, c["hidden_size"]))
    ids = spec((batch, seq), jnp.int32)
    picks = spec((batch, seq, c["num_experts_per_tok"]), jnp.int32)
    rot = (spec((c["head_dim"] // 2,)), spec(()), spec((), jnp.int32))
    with jax.default_matmul_precision("highest"):
        for given in (picks, None):
            _layer_fwd.lower(p, x, ids, given, rot, frozen,
                             precision).compile()
        _layer_bwd.lower(p, x, ids, picks, x, spec((c["num_experts"],)),
                         rot, frozen, precision).compile()
        _head_grads.lower(outer, x, ids, frozen, precision).compile()
        _head_loss.lower(outer, x, ids, frozen, precision).compile()


def loss_and_grads(outer, layers, cfg, ids, labels, positions=None,
                   precision="float32", given=None, window=None, yarn=True):
    """(loss, (lm, balance), grads of every leaf) of one batch: the first
    half-step of `RefTrainer`, for tests. grads = {"outer": {...},
    "layers": [{...}]}."""
    t = RefTrainer(outer, layers, cfg, (0.0, 0.9, 0.95, 1e-8, 0.0), precision,
                   given=given, window=window, yarn=yarn)
    with jax.default_matmul_precision("highest"):
        t._step(jnp.asarray(ids, jnp.int32), jnp.asarray(labels, jnp.int32),
                _positions(ids.shape, positions), 1)
    return t.losses[0], t.parts[0], t._g1


def logits(outer, layers, cfg, ids, positions=None, precision="float32"):
    """float32 [B, S, V] of the model's own picks: the forward alone, for
    tests."""
    t = RefTrainer(outer, layers, cfg, (0.0, 0.9, 0.95, 1e-8, 0.0), precision)
    with jax.default_matmul_precision("highest"):
        x = t._forward(jnp.asarray(ids, jnp.int32),
                       _positions(ids.shape, positions), want_grads=False)[0]
        a = rms(x, t.outer["norm.weight"], dict(t.cfg)["rms_norm_eps"])
        return mm("bsh,vh->bsv", a, t.outer["lm_head"], precision)
