"""The plain reference of Keye-VL-2.0's language model (the decoder of
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, config.json) in
straightforward `jax.numpy`. It imports nothing of the program.

One layer on x [S, H] of one sequence, positions [3, S]:

  h = rms(x) g1;  q = h Wq [32 x 128], k = h Wk, v = h Wv [4 x 128]
      q, k: rms over each head (gains qn, kn), then M-RoPE: frequency i
      of theta ** (-i / 64) turns by positions[0] for i < 16, by
      positions[1] for 16 <= i < 40, by positions[2] for the rest
  indexer: qI = rope(h WqI) [16 x 64], kI = rope(layer_norm(h WkI)) [64]
      (sections 8 | 12 | 12), w = h Ww / sqrt(16 * 64)
      I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])          s <= t
      S_t = the min(2048, t + 1) keys of largest I[t, .], ties to the
      lower index (a stable sort); the selection is a dense mask here
  o[t] = sum_s softmax_s(q[t] . k[s] / sqrt(128) | s in S_t) v[s]
  x = x + o Wo
  h2 = rms(x) g2;  p = softmax(h2 Wr) over all 128 experts
      E_t = top 8 of p[t];  g[t, e] = p[t, e] / sum_{E_t} p
      x = x + sum over the HELD experts e (a loop) of
              g[t, e] * (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

  loss = mean CE(head(rms(x_L) gf)) + mean over layers of
         [0.001 * 128 * sum_e f_e P_e]  +  mean over layers of
         [mean_t KL(pbar_t || softmax(I[t, S_t]))]
  f_e: share of the batch's tokens that picked e (no gradient); P_e: mean
  of p[:, e]; pbar_t: the main attention's probabilities over S_t, mean
  over heads. The indexer's input h and pbar are cut from the graph.

float32 under `jax.default_matmul_precision("highest")`. To fit a 16 GB
chip at published widths it walks layer by layer, sequence by sequence
and, inside attention, block of queries by block of queries; AdamW's
state after the first update is kept as the first gradient (as
reference/gpt.py does). `precision="fp8"` is the control: every matrix
product's operands rounded to e4m3 with a per-tensor scale.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
LAYER_LEAVES = (
    "input_layernorm.weight", "self_attn.q_proj.weight",
    "self_attn.k_proj.weight", "self_attn.v_proj.weight",
    "self_attn.o_proj.weight", "self_attn.q_norm.weight",
    "self_attn.k_norm.weight", "indexer.wq.weight", "indexer.wk.weight",
    "indexer.k_norm.weight", "indexer.k_norm.bias",
    "indexer.weights_proj.weight", "post_attention_layernorm.weight",
    "mlp.router", "mlp.gate_proj", "mlp.up_proj", "mlp.down_proj")
OUTER_LEAVES = ("embed_tokens.weight", "norm.weight", "lm_head")
QUERY_BLOCK = 256


# -- one matrix product, in the stated precision ---------------------------

def _fp8(x):
    """Round to e4m3 with a per-tensor scale; straight-through gradient."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    return x + jax.lax.stop_gradient(q - x)


def mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


# -- the layer ---------------------------------------------------------------

def rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * gain


def layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gain + bias


def rotary(x, positions, theta, sections):
    """x [S, heads, d]; positions [3, S]; rotate-half pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    row = jnp.concatenate([jnp.full((n,), r, jnp.int32)
                           for r, n in enumerate(sections)])
    ang = positions.astype(F32)[row].T * inv                 # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_sections(cfg):
    scale = cfg["head_dim"] // cfg["index_head_dim"]
    a, b = (s // scale for s in cfg["mrope_section"][:2])
    return (a, b, cfg["index_head_dim"] // 2 - a - b)


def selection(scores, valid, k):
    """Dense mask of each row's k largest valid scores, ties to the
    lower index (the first k of a stable descending sort): everything
    above the row's k-th largest value, and of the scores equal to it
    the first that are still needed. A sort of the values alone: a
    stable argsort takes the TPU compiler 40 s a program."""
    masked = jnp.where(valid, scores, -jnp.inf)
    kth = -jnp.sort(-masked, axis=-1)[:, k - 1:k] if k <= scores.shape[-1] \
        else jnp.full((scores.shape[0], 1), -jnp.inf, scores.dtype)
    above = masked > kth
    equal = (masked == kth) & valid
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above & valid) | (equal & (jnp.cumsum(equal, axis=-1) <= need))


def projections(p, x, positions, cfg, precision):
    """-> (q [S,32,128], k, v [S,4,128], qI [S,16,64], kI [S,64], w [S,16])
    of one sequence; the indexer's three from the input cut loose."""
    s, _ = x.shape
    heads, kvh, d = (cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    nj, di, eps = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["rms_norm_eps"]
    theta = F32(cfg["rope_theta"])
    h = rms(x, p["input_layernorm.weight"], eps)
    q = mm("sh,hd->sd", h, p["self_attn.q_proj.weight"],
           precision).reshape(s, heads, d)
    k = mm("sh,hd->sd", h, p["self_attn.k_proj.weight"],
           precision).reshape(s, kvh, d)
    v = mm("sh,hd->sd", h, p["self_attn.v_proj.weight"],
           precision).reshape(s, kvh, d)
    q = rotary(rms(q, p["self_attn.q_norm.weight"], eps), positions, theta,
               cfg["mrope_section"])
    k = rotary(rms(k, p["self_attn.k_norm.weight"], eps), positions, theta,
               cfg["mrope_section"])
    hi = jax.lax.stop_gradient(h)       # the indexer's input: no gradient
    sec = index_sections(cfg)
    q_idx = rotary(mm("sh,hd->sd", hi, p["indexer.wq.weight"],
                      precision).reshape(s, nj, di), positions, theta, sec)
    k_idx = rotary(layer_norm(
        mm("sh,hd->sd", hi, p["indexer.wk.weight"], precision),
        p["indexer.k_norm.weight"], p["indexer.k_norm.bias"],
        eps)[:, None, :], positions, theta, sec)[:, 0]
    w = mm("sh,hj->sj", hi, p["indexer.weights_proj.weight"],
           precision) * (nj * di) ** -0.5
    return q, k, v, q_idx, k_idx, w


def index_scores(q_idx, k_idx, w, precision):
    """I [t, s] of a block of queries against every key."""
    return jnp.einsum(
        "tjs,tj->ts",
        jax.nn.relu(mm("tjd,sd->tjs", q_idx, k_idx, precision)), w,
        precision=jax.lax.Precision.HIGHEST)


def selected(p, x, positions, cfg, precision="float32"):
    """bool [S, S]: S_t as a dense mask, of one sequence."""
    s = x.shape[0]
    _, _, _, q_idx, k_idx, w = projections(p, x, positions, cfg, precision)
    return selection(index_scores(q_idx, k_idx, w, precision),
                     jnp.tril(jnp.ones((s, s), bool)), cfg["index_topk"])


def attention(p, x, positions, cfg, precision, keep=None):
    """x [S, H] -> (x + attention, sum over the sequence's tokens of
    KL_t, the selection [S, S]). `keep` hands back a selection this very
    function made from the same arguments (the backward pass's second
    walk does not sort again)."""
    s, _ = x.shape
    heads, kvh, d = (cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    q, k, v, q_idx, k_idx, w = projections(p, x, positions, cfg, precision)
    block = min(QUERY_BLOCK, s)
    cols = jnp.arange(s, dtype=jnp.int32)

    @jax.checkpoint
    def rows(args):
        t0, qb, qib, wb, keep = args
        scores = index_scores(qib, k_idx, wb, precision)
        if keep is None:
            valid = cols[None, :] <= (
                t0 + jnp.arange(block, dtype=jnp.int32))[:, None]
            keep = selection(scores, valid, cfg["index_topk"])
        logits = mm("tkgd,skd->kgts",
                    qb.reshape(block, kvh, heads // kvh, d), k,
                    precision) / jnp.sqrt(F32(d))
        prob = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        out = mm("kgts,skd->tkgd", prob, v, precision)
        target = jax.lax.stop_gradient(jnp.mean(prob, axis=(0, 1)))
        mine = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        live = keep & (target > 0)
        kl = jnp.sum(jnp.where(
            live, target * (jnp.log(jnp.where(live, target, 1.0))
                            - jnp.where(keep, mine, 0.0)), 0.0))
        return out.reshape(block, heads * d), kl, keep

    n = s // block
    cut = lambda a: a.reshape((n, block) + a.shape[1:])  # noqa: E731
    out, kl, keep = jax.lax.map(
        rows, (jnp.arange(n, dtype=jnp.int32) * block, cut(q), cut(q_idx),
               cut(w), None if keep is None else cut(keep)))
    x = x + mm("sd,dh->sh", out.reshape(s, heads * d),
               p["self_attn.o_proj.weight"], precision)
    return x, jnp.sum(kl), keep.reshape(s, s)


def experts(p, x, cfg, precision, picks=None):
    """x [S, H] -> (x + the held experts' part, sum_t p[t, :] [E], tokens
    that picked each expert [E], the picks [S, k]). `picks` given: those
    experts are taken in place of the top k (their weights still this
    function's own probabilities)."""
    lo, hi = cfg["held_experts"]
    h2 = rms(x, p["post_attention_layernorm.weight"], cfg["rms_norm_eps"])
    prob = jax.nn.softmax(mm("sh,he->se", h2, p["mlp.router"], precision),
                          axis=-1)
    if picks is None:
        top, picks = jax.lax.top_k(prob, cfg["num_experts_per_tok"])
    else:
        top = jnp.take_along_axis(prob, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)

    def one(y, xs):
        e, wg, wu, wd = xs
        gate = jnp.sum(jnp.where(picks == e, top, 0.0), axis=-1)
        a = jax.nn.silu(mm("sh,hn->sn", h2, wg, precision)) \
            * mm("sh,hn->sn", h2, wu, precision)
        return y + gate[:, None] * mm("sn,nh->sh", a, wd, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(lo, hi), p["mlp.gate_proj"],
                         p["mlp.up_proj"], p["mlp.down_proj"]))
    picked = jnp.zeros((prob.shape[-1],), F32).at[picks.reshape(-1)].add(1.0)
    return x + y, jnp.sum(prob, 0), picked, picks


def layer(p, x, positions, cfg, precision, keep=None, picks=None):
    """One sequence through one layer -> (x, sum_t KL_t, sum_t p[t, :],
    tokens that picked each expert, the selection [S, S], the experts
    picked [S, k]); `keep` and `picks` given take the place of the
    layer's own top-k."""
    x, kl, keep = attention(p, x, positions, cfg, precision, keep)
    x, prob_sum, picked, picks = experts(p, x, cfg, precision, picks)
    return x, kl, prob_sum, picked, keep, picks


def own_picks(p, x, positions, cfg, precision="float32"):
    """(selection [S, S], experts [S, k]) the layer itself would pick at
    this input: what given picks are held against."""
    xa, _, keep = attention(p, x, positions, cfg, precision)
    return keep, experts(p, xa, cfg, precision)[3]


def head_loss_sum(outer, x, labels, cfg, precision):
    """Sum of the token losses of one sequence x [S, H]."""
    a = rms(x, outer["norm.weight"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(
        mm("sh,vh->sv", a, outer["lm_head"], precision), -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1))


def _freeze(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in cfg.items()))


@functools.partial(jax.jit, static_argnums=(5, 6))
def _layer_fwd(p, x, positions, keep, picks, cfg, precision):
    """Every sequence through one layer. `keep` [B, S, S] and `picks`
    [B, S, k] given (both or neither): the layer runs on them, and the
    two last results say which share of them is not the layer's own."""
    cfg = dict(cfg)

    def one(a):
        xs, pos, ks, ps = a
        out = layer(p, xs, pos, cfg, precision, ks, ps)
        if ks is None:
            return out + (F32(0), F32(0))
        mine_k, mine_p = own_picks(p, xs, pos, cfg, precision)
        key_miss = jnp.sum(ks & ~mine_k) / jnp.sum(ks)
        hit = jnp.any(ps[:, :, None] == mine_p[:, None, :], axis=-1)
        return out + (key_miss, 1.0 - jnp.mean(hit))

    return jax.lax.map(one, (x, jnp.moveaxis(positions, 1, 0), keep, picks))


@functools.partial(jax.jit, static_argnums=(8, 9))
def _layer_bwd(p, x, positions, keep, picks, dy, f_weight, kl_weight, cfg,
               precision):
    """(dp, dx) of one layer, one sequence at a time. The layer's share
    of the loss is kl_weight * sum KL + sum_e f_weight[e] * sum_t
    p[t, e]: f_weight holds the batch's picks, which have no gradient;
    `keep` [B, S, S] and `picks` [B, S, k] are the forward walk's."""
    cfg = dict(cfg)

    def row(acc, a):
        xs, pos, ks, ps, dys = a

        def f(pp, xx):
            y, kl, prob_sum, _, _, _ = layer(pp, xx, pos, cfg, precision,
                                             ks, ps)
            return y, kl_weight * kl + jnp.sum(f_weight * prob_sum)

        _, vjp = jax.vjp(f, p, xs)
        dp, dx = vjp((dys, jnp.ones((), F32)))
        return jax.tree.map(jnp.add, acc, dp), dx

    return jax.lax.scan(row, jax.tree.map(jnp.zeros_like, p),
                        (x, jnp.moveaxis(positions, 1, 0), keep, picks, dy))


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
    """(mean token loss, its gradient in `outer`, in x [B, S, H]), one
    sequence at a time."""
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


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _layer_scalars(kl, prob_sum, picked, keep, key_miss, pick_miss, scale,
                   tokens, coef, held):
    """What one layer's forward walk adds to a step's numbers: the share
    f [E] of the batch's tokens that picked each expert, the balance
    term, mean KL, pairs kept, pairs on held experts, and the worst
    sequence's two shares of given picks that are not the layer's own."""
    f = jnp.sum(picked, 0) / tokens
    return (f, coef * jnp.sum(f * jnp.sum(prob_sum, 0) / tokens),
            jnp.sum(kl) / tokens, jnp.sum(keep),
            jnp.sum(picked[:, held[0]:held[1]]), jnp.max(key_miss),
            jnp.max(pick_miss), f * scale)


@jax.jit
def _embed_grad(weight, ids, dx):
    """dx's rows added into their tokens' rows of a zero [V, H]: a
    one-hot product, a block of tokens at a time (the scatter-add it
    stands for takes the TPU compiler 7 s)."""
    flat, rows = ids.reshape(-1), dx.reshape(-1, dx.shape[-1])
    block = math.gcd(flat.size, 2048)
    vocab = jnp.arange(weight.shape[0], dtype=flat.dtype)[:, None]

    def add(acc, a):
        hot = (vocab == a[0][None, :]).astype(F32)
        return acc + jnp.einsum("vt,th->vh", hot, a[1],
                                precision=jax.lax.Precision.HIGHEST), None

    return jax.lax.scan(add, jnp.zeros_like(weight),
                        (flat.reshape(-1, block),
                         rows.reshape(-1, block, rows.shape[-1])))[0]


@jax.jit
def _sq_tree(tree):
    return jax.tree.map(lambda a: jnp.sum(jnp.square(a)), tree)


@jax.jit
def _sq_diff(a, b):
    return jax.tree.map(lambda x, y: jnp.sum(jnp.square(x - y)), a, b)


# -- AdamW ---------------------------------------------------------------

def adamw(p, g, m, v, t, hyper):
    lr, b1, b2, eps, wd = hyper
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    m_hat = m / (1 - b1 ** t)
    v_hat = v / (1 - b2 ** t)
    return p * (1 - lr * wd) - lr * m_hat / (jnp.sqrt(v_hat) + eps)


@functools.partial(jax.jit, static_argnums=(4,))
def _update(p, g, g1, t, hyper):
    """One AdamW step of every leaf of the dict `p`; the state before it
    is that of ONE earlier step whose gradient was `g1` (None: no
    earlier step)."""
    b1, b2 = hyper[1], hyper[2]
    out = {}
    for k in p:
        m = 0.0 * g[k] if g1 is None else (1 - b1) * g1[k]
        v = 0.0 * g[k] if g1 is None else (1 - b2) * g1[k] ** 2
        out[k] = adamw(p[k], g[k], m, v, t, hyper)
    return out


class RefTrainer:
    """Three losses and two AdamW updates of the whole model.

    `outer` holds embed_tokens.weight, norm.weight, lm_head [V, H];
    `layers` is a list of per-layer dicts of LAYER_LEAVES (the experts'
    leaves hold the held experts only, cfg["held_experts"] = [lo, hi]).
    After `run`, `losses` has three entries (each the sum of the three
    `parts` of its step), `grad_norms` the per-leaf norm of the first
    gradient, `counts` the first step's kept and routed pairs, `picks`
    the first step's (selections, experts) per layer, `miss` how far the
    `given` picks are from the reference's own, and
    `delta_norms(outer0, layers0)` the per-leaf norm of the change after
    the two updates (layer leaves over all layers together).
    `probe(tree, layer)` is handed every dict of first gradients, a
    layer's with its index, the outer leaves' with None.
    Whatever is more than a few scalars runs in a jitted function of a
    whole dict: a run that starts with no compiled code pays for every
    program, however small.
    """

    def __init__(self, outer, layers, cfg, hyper, precision="float32",
                 probe=None, given=None):
        self.outer, self.layers = dict(outer), [dict(p) for p in layers]
        self.cfg, self.precision = _freeze(cfg), precision
        self.hyper = tuple(float(x) for x in hyper)   # lr b1 b2 eps wd
        self.losses, self.parts, self.grad_norms = [], [], {}
        self.counts = None
        # `given` = (selection bool [L, B, S, S], experts int [L, B, S, k]):
        # the FIRST step runs on these picks in place of its own top-k
        # (its gradient is then the gradient at those picks), and `miss`
        # is the largest share, over the layers, of the given keys and of
        # the given experts that are not the reference's own
        self.given, self.miss, self.picks = given, None, None
        self.probe = probe or (lambda tree, layer: None)
        self._g1 = None

    def _forward(self, ids, positions, want_grads=True, given=None):
        """-> (x_L, per layer (input, selection, experts), per layer the
        balance term's weight on sum_t p[t, :] [E], the two auxiliary
        terms, (kept, routed))."""
        cfg = dict(self.cfg)
        tokens, n = ids.size, len(self.layers)
        x = self.outer["embed_tokens.weight"][ids]
        xs, weights, scalars = [], [], []
        coef = cfg["router_aux_loss_coef"] * cfg["num_experts"]
        for i, p in enumerate(self.layers):
            x_in = x
            ks, ps = (None, None) if given is None else (
                jnp.asarray(given[0][i], bool),
                jnp.asarray(given[1][i], jnp.int32))
            x, kl, prob_sum, picked, keep, picks, km, pm = _layer_fwd(
                p, x, positions, ks, ps, self.cfg, self.precision)
            out = _layer_scalars(kl, prob_sum, picked, keep, km, pm,
                                 F32(coef / tokens / n), tokens, coef,
                                 tuple(cfg["held_experts"]))
            xs.append((x_in, keep, picks) if want_grads else None)
            weights.append(out[-1])
            scalars.append(out[1:-1])
        balance, index_loss, kept, routed, km, pm = (
            [float(v) for v in col] for col in zip(*scalars))
        if given is not None:
            self.miss = {"key_pick_miss": max(km),
                         "expert_pick_miss": max(pm)}
        return x, xs, weights, (sum(balance) / n, sum(index_loss) / n), \
            (int(sum(kept)), int(sum(routed)))

    def _note(self, lm, parts):
        self.parts.append((float(lm), float(parts[0]), float(parts[1])))
        self.losses.append(sum(self.parts[-1]))

    def _step(self, ids, labels, positions, t):
        tokens, n = ids.size, len(self.layers)
        first = self._g1 is None
        x, xs, weights, parts, counts = self._forward(
            ids, positions, given=self.given if first else None)
        lm, d_outer, dy = _head_grads(self.outer, x, labels, self.cfg,
                                      self.precision)
        self._note(lm, parts)
        if first:
            self.counts = {"kept_keys": counts[0], "routed_pairs": counts[1]}
            self.picks = ([a[1] for a in xs], [a[2] for a in xs])
        g1 = {"layers": [None] * n} if first else self._g1
        sq = []
        for i in reversed(range(n)):
            dp, dy = _layer_bwd(
                self.layers[i], xs[i][0], positions, xs[i][1], xs[i][2], dy,
                weights[i], F32(1.0 / tokens / n), self.cfg, self.precision)
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
        `positions` int [3, B, S], three equal rows 0..S-1 when None."""
        b, s = batches[0][0].shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                         (3, b, s))
        positions = jnp.asarray(positions, jnp.int32)
        with jax.default_matmul_precision("highest"):
            for t, (ids, labels) in enumerate(batches[:2], start=1):
                self._step(jnp.asarray(ids, jnp.int32),
                           jnp.asarray(labels, jnp.int32), positions, t)
            ids, labels = batches[2]
            x, _, _, parts, _ = self._forward(jnp.asarray(ids, jnp.int32),
                                              positions, want_grads=False)
            self._note(_head_loss(self.outer, x,
                                  jnp.asarray(labels, jnp.int32), self.cfg,
                                  self.precision), parts)
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


def compile_ahead(outer, layer, cfg, batch, seq, precision="float32"):
    """Lower and compile, executing nothing, the large programs that
    `RefTrainer.run` calls for these shapes: both forward walks (given
    picks, and its own), the backward walk, the head with and without
    its gradient. `outer` and `layer` map leaf names to shapes. `run`
    then finds them compiled and compiles none of them again: for a
    caller that has a minute of compiling of its own to wait for
    meanwhile, on another thread."""
    def spec(shape, dtype=F32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    frozen, c = _freeze(cfg), dict(cfg)
    outer = {k: spec(v) for k, v in outer.items()}
    p = {k: spec(v) for k, v in layer.items()}
    x = spec((batch, seq, c["hidden_size"]))
    ids = spec((batch, seq), jnp.int32)
    positions = spec((3, batch, seq), jnp.int32)
    keep = spec((batch, seq, seq), bool)
    picks = spec((batch, seq, c["num_experts_per_tok"]), jnp.int32)
    with jax.default_matmul_precision("highest"):
        for given in ((keep, picks), (None, None)):
            _layer_fwd.lower(p, x, positions, *given, frozen,
                             precision).compile()
        _layer_bwd.lower(p, x, positions, keep, picks, x,
                         spec((c["num_experts"],)), spec(()), frozen,
                         precision).compile()
        _head_grads.lower(outer, x, ids, frozen, precision).compile()
        _head_loss.lower(outer, x, ids, frozen, precision).compile()


def loss_and_grads(outer, layers, cfg, ids, labels, positions=None,
                   precision="float32", given=None):
    """(loss, (lm, balance, L_I), grads of every leaf) of one batch: the
    first half-step of `RefTrainer`, for tests. grads = {"outer": {...},
    "layers": [{...}]}."""
    t = RefTrainer(outer, layers, cfg, (0.0, 0.9, 0.95, 1e-8, 0.0), precision,
                   given=given)
    b, s = ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                     (3, b, s))
    with jax.default_matmul_precision("highest"):
        t._step(jnp.asarray(ids, jnp.int32), jnp.asarray(labels, jnp.int32),
                jnp.asarray(positions, jnp.int32), 1)
    return t.losses[0], t.parts[0], t._g1
