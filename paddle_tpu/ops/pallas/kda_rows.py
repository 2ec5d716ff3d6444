"""The head-wise row work round Kimi Delta Attention's scan (`kda.py`), on
FLAT [b, s, heads 128] tables: what a KDA layer does to one head's 128
channels of one token, before the scan and after it.

  `kda_inputs`      q = unit(q~) 128^-1/2, k = unit(k~), the decay
                    a = lower_bound sigmoid(exp(A_log) (f + dt_bias)),
                    beta = sigmoid(beta's logit), beta k, beta v: the five
                    operands of `kda.kda_flat`
  `kda_gated_norm`  RMSNorm_128(o) gn sigmoid(gate's logit)

A row of such a table is a token's 32 heads side by side, and column block h
of 128 lanes IS head h: a BlockSpec cuts [rows, 128] blocks out of the flat
table, so no [b, s, heads, 128] table exists in the XLA program, in either
type. (XLA tiles a [.., 32, 128] table (32, 128) and a [.., 4096] row
(8, 128): every reshape between the two is a copy of the table, and a head's
scalar times a head's channels becomes a broadcast table in HBM. PERF.md
section 6, PR 43.) A head's gate is picked inside the kernel from the row
block's [rows, heads] table, a one-hot lane select and a row sum.

Four kernels, grid (row blocks, heads), the heads innermost and in order: a
row block's logits are fetched once, their sigmoids made at its first head
(all heads' in one pass, kept in VMEM scratch) and the logits' cotangents
closed at its last. A forward and a pull-back of each entry. float32
inside; q, k, beta k, beta v and the gated norm rounded to the tables' type
once, where the `jnp` form rounds them (beta k from the ROUNDED k, the k the
recurrence's other products read); `a` float32. The pull-backs recompute a
row's norms from the saved inputs and give the parameters' cotangents as one
partial sum a (row block, head), which XLA adds up.

Where no kernel runs (CPU, a head width other than 128) the `jnp` forms
`kda_inputs_xla` and `kda_gated_norm_xla` are the path: the same
expressions (`_inputs_math`, `_gated_math`: the kernels' forward bodies call
them too) on [b, s, heads, d] tables, differentiated by JAX.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import routing
from .flash_attention import _LANES, _Z, pl, pltpu

__all__ = ["kda_inputs", "kda_inputs_xla", "kda_gated_norm",
           "kda_gated_norm_xla", "supports"]

F32 = jnp.float32
UNIT_EPS = 1e-6     # inside the root of q's and k's L2 norms
# rows of a grid step's block, the most: a layer's six passes took 11.76 |
# 10.98 | 10.74 ms at 512 | 1,024 | 2,048 on a v5e (PERF.md section 6, PR 43)
ROWS = 2048
SLAB = 128          # rows worked at a time inside it: what stays in vregs
# the pull-back of `kda_inputs` holds sixteen blocks of 2,048 rows twice over
# (20 MiB) where the compiler's own limit is 16
VMEM_LIMIT = 64 * 2 ** 20


def supports(shape, heads, dtype) -> bool:
    """Whether the kernels take [b, s, width] tables of `heads` heads."""
    b, s, width = shape
    return (dtype in (jnp.float32, jnp.bfloat16) and width == heads * _LANES
            and (b * s) % 16 == 0)


# -- one head's channels last, in jnp (XLA and the kernels' bodies alike) ----

def _unit(x32):
    return x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), -1, keepdims=True)
                               + UNIT_EPS)


def _rms(x, w, eps):       # models/keye_vl2.py `_rms` (the model's o_norm)
    x32 = x.astype(F32)
    out = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                              + eps)
    return (out * w.astype(F32)).astype(x.dtype)


def _inputs_math(q, k, v, f, beta, scale, bias, lower_bound):
    """q, k, v [.., d] (one type), f [.., d] float32, beta [.., 1] float32,
    scale = exp(A_log) and bias = dt_bias float32, a channel each -> q, k,
    beta k, beta v in q's type and a float32."""
    op = q.dtype
    q = (_unit(q.astype(F32)) * q.shape[-1] ** -0.5).astype(op)
    k = _unit(k.astype(F32)).astype(op)
    a = lower_bound * jax.nn.sigmoid(scale * (f + bias))
    return (q, k, (k.astype(F32) * beta).astype(op),
            (v.astype(F32) * beta).astype(op), a)


def _gated_math(o, gate, gn, eps):
    """o [.., d], gate [.., 1] float32, gn [d] -> in o's type."""
    return (_rms(o, gn, eps).astype(F32) * gate).astype(o.dtype)


def _sigmoid(logits):
    return jax.nn.sigmoid(logits.astype(F32))


def _channel_rows(a_log, dt_bias, d):
    """-> (exp(A_log), a head's value on each of its channels; dt_bias),
    float32 [heads d]."""
    return (jnp.repeat(jnp.exp(a_log.astype(F32)), d), dt_bias.astype(F32))


def kda_inputs_xla(qc, kc, vc, f, beta_logits, a_log, dt_bias, *,
                   lower_bound):
    """`kda_inputs` by XLA alone (module docstring)."""
    b, s, width = qc.shape
    heads = beta_logits.shape[-1]
    cut = (b, s, heads, width // heads)
    scale, bias = _channel_rows(a_log, dt_bias, cut[-1])
    out = _inputs_math(
        qc.reshape(cut), kc.reshape(cut), vc.reshape(cut), f.reshape(cut),
        _sigmoid(beta_logits)[..., None], scale.reshape(cut[2:]),
        bias.reshape(cut[2:]), lower_bound)
    return tuple(x.reshape(b, s, width) for x in out)


def kda_gated_norm_xla(o, gate_logits, gn, *, eps):
    """`kda_gated_norm` by XLA alone (module docstring)."""
    b, s, width = o.shape
    heads = gate_logits.shape[-1]
    return _gated_math(o.reshape(b, s, heads, width // heads),
                       _sigmoid(gate_logits)[..., None], gn,
                       eps).reshape(b, s, width)


# -- the kernels --------------------------------------------------------------

def _column(block, h):
    """Column h of a float32 [rows, heads] block, [rows, 1]: the other lanes
    are exact zeros in the sum."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == h, block, 0.0), axis=1, keepdims=True)


def _set_column(ref, rows, h, col):
    """Write col [rows, 1] as column h of ref[rows, :]; the block stays in
    VMEM over the grid's heads, and every head writes its own column."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (col.shape[0], ref.shape[1]),
                                    1)
    ref[rows, :] = jnp.where(lane == h, col, ref[rows, :])


def _slabs(block_rows, body, init=()):
    """body(rows of one slab, carry) over a block's slabs, in a loop (the
    body's code once, its temporaries a slab's)."""
    slab = SLAB if block_rows % SLAB == 0 else block_rows

    def one(i, carry):
        return body(pl.ds(pl.multiple_of(i * slab, slab), slab), carry)

    return jax.lax.fori_loop(0, block_rows // slab, one, init)


def _gates(logit_ref, gate_ref):
    """At a row block's first head: the sigmoid of its [rows, heads] logits,
    all heads' at once, into scratch for the block's other heads. (A head's
    own column is a vreg every eight rows with one lane in use: its sigmoid
    a head was two fifths of a gated norm's instructions.)"""
    @pl.when(pl.program_id(1) == 0)
    def _first():
        def slab(rows, carry):
            gate_ref[rows, :] = _sigmoid(logit_ref[rows, :])
            return carry

        _slabs(gate_ref.shape[0], slab)


def _logit_cotangents(dlogit_ref, gate_ref):
    """At a row block's last head: the gates' cotangents, a column a head in
    `dlogit_ref`, through the sigmoid."""
    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _last():
        def slab(rows, carry):
            gate = gate_ref[rows, :]
            dlogit_ref[rows, :] = dlogit_ref[rows, :] * gate * (1.0 - gate)
            return carry

        _slabs(gate_ref.shape[0], slab)


def _unit_pull_back(x32, g):
    """The pull-back of `_unit` at x32 along g, and 1 / |x|."""
    r = jax.lax.rsqrt(jnp.sum(jnp.square(x32), -1, keepdims=True) + UNIT_EPS)
    return r * g - x32 * (r * r * r * jnp.sum(g * x32, -1, keepdims=True)), r


def _inputs_fwd_kernel(q_ref, k_ref, v_ref, f_ref, bl_ref, scale_ref,
                       bias_ref, qo_ref, ko_ref, kb_ref, vb_ref, a_ref,
                       beta_ref, *, lower_bound):
    h = pl.program_id(1)
    scale, bias = scale_ref[...], bias_ref[...]
    _gates(bl_ref, beta_ref)

    def slab(rows, carry):
        out = _inputs_math(q_ref[rows, :], k_ref[rows, :], v_ref[rows, :],
                           f_ref[rows, :], _column(beta_ref[rows, :], h),
                           scale, bias, lower_bound)
        for ref, x in zip((qo_ref, ko_ref, kb_ref, vb_ref, a_ref), out):
            ref[rows, :] = x
        return carry

    _slabs(q_ref.shape[0], slab)


def _inputs_bwd_kernel(dq_ref, dk_ref, dkb_ref, dvb_ref, da_ref, q_ref, k_ref,
                       v_ref, f_ref, bl_ref, scale_ref, bias_ref, dqc_ref,
                       dkc_ref, dvc_ref, df_ref, dbl_ref, dscale_ref,
                       dbias_ref, beta_ref, *, lower_bound):
    h = pl.program_id(1)
    scale, bias = scale_ref[...], bias_ref[...]
    op = q_ref.dtype
    _gates(bl_ref, beta_ref)

    def slab(rows, carry):
        dscale, dbias = carry
        beta = _column(beta_ref[rows, :], h)
        dqc, _ = _unit_pull_back(
            q_ref[rows, :].astype(F32),
            dq_ref[rows, :].astype(F32) * q_ref.shape[1] ** -0.5)
        dqc_ref[rows, :] = dqc.astype(op)
        k32 = k_ref[rows, :].astype(F32)
        dkb = dkb_ref[rows, :].astype(F32)
        dkc, r = _unit_pull_back(k32, dk_ref[rows, :].astype(F32)
                                 + dkb * beta)
        dkc_ref[rows, :] = dkc.astype(op)
        dvb = dvb_ref[rows, :].astype(F32)
        dvc_ref[rows, :] = (dvb * beta).astype(op)
        # beta multiplied the ROUNDED k (`_inputs_math`)
        _set_column(dbl_ref, rows, h, jnp.sum(
            dkb * (k32 * r).astype(op).astype(F32)
            + dvb * v_ref[rows, :].astype(F32), -1, keepdims=True))
        shifted = f_ref[rows, :] + bias
        s = jax.nn.sigmoid(scale * shifted)
        dz = da_ref[rows, :] * (lower_bound * s * (1.0 - s))
        df = dz * scale
        df_ref[rows, :] = df
        return (dscale + jnp.sum(dz * shifted, 0, keepdims=True),
                dbias + jnp.sum(df, 0, keepdims=True))

    zero = jnp.zeros((1, _LANES), F32)
    dscale_ref[...], dbias_ref[...] = _slabs(q_ref.shape[0], slab,
                                             (zero, zero))
    _logit_cotangents(dbl_ref, beta_ref)


def _gated_fwd_kernel(o_ref, gl_ref, gn_ref, out_ref, gate_ref, *, eps):
    h = pl.program_id(1)
    gn = gn_ref[...]
    _gates(gl_ref, gate_ref)

    def slab(rows, carry):
        out_ref[rows, :] = _gated_math(
            o_ref[rows, :], _column(gate_ref[rows, :], h), gn, eps)
        return carry

    _slabs(o_ref.shape[0], slab)


def _gated_bwd_kernel(dout_ref, o_ref, gl_ref, gn_ref, do_ref, dgl_ref,
                      dgn_ref, gate_ref, *, eps):
    h = pl.program_id(1)
    w = gn_ref[...].astype(F32)
    op = o_ref.dtype
    _gates(gl_ref, gate_ref)

    def slab(rows, dgn):
        x32 = o_ref[rows, :].astype(F32)
        r = jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
        unit = x32 * r
        dout = dout_ref[rows, :].astype(F32)
        # the gate multiplied the ROUNDED norm (`_gated_math`)
        _set_column(dgl_ref, rows, h, jnp.sum(
            dout * (unit * w).astype(op).astype(F32), -1, keepdims=True))
        dn = dout * _column(gate_ref[rows, :], h)
        t = dn * w
        do_ref[rows, :] = (r * t - x32 * (r * r * r * jnp.mean(
            t * x32, -1, keepdims=True))).astype(op)
        return dgn + jnp.sum(dn * unit, 0, keepdims=True)

    dgn_ref[...] = _slabs(o_ref.shape[0], slab, jnp.zeros((1, _LANES), F32))
    _logit_cotangents(dgl_ref, gate_ref)


def _call(kernel, name, n, heads, interpret, ins, outs):
    """`kernel` over the grid (row blocks, heads), the heads one after
    another with a row block's gates in VMEM scratch from its first head on.
    `ins` and `outs` are (array or its shape-and-type, kind of table):
    "wide" [n, heads 128], cut into [rows, 128] blocks; "logits" [n, heads],
    a row block whole; "channel" [1, heads 128], a head's [1, 128]; "gain"
    [1, 128], whole; "sums" [row blocks, 1, heads 128], the [1, 128] of a
    (row block, head)."""
    rows = next(r for r in (2048, 1024, 512, 256, 128, 64, 32, 16)
                if r <= ROWS and n % r == 0)
    spec = {
        "wide": pl.BlockSpec((rows, _LANES), lambda i, h: (i, h)),
        "logits": pl.BlockSpec((rows, heads), lambda i, h: (i, _Z)),
        "channel": pl.BlockSpec((1, _LANES), lambda i, h: (_Z, h)),
        "gain": pl.BlockSpec((1, _LANES), lambda i, h: (_Z, _Z)),
        "sums": pl.BlockSpec((None, 1, _LANES), lambda i, h: (i, _Z, h))}
    shape = {"wide": (n, heads * _LANES), "logits": (n, heads),
             "sums": (n // rows, 1, heads * _LANES)}
    return routing.pallas_call(
        kernel, name=name, grid=(n // rows, heads),
        in_specs=[spec[kind] for _, kind in ins],
        out_specs=[spec[kind] for _, kind in outs],
        out_shape=[jax.ShapeDtypeStruct(shape[kind], dtype)
                   for dtype, kind in outs],
        scratch_shapes=[pltpu.VMEM((rows, heads), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*(x.reshape(shape.get(kind, (1, -1))) for x, kind in ins))


# jitted, all four: a model's layers call them with the same shapes, so they
# share one trace and one lowering of each kernel's body (`kda.kda_fwd`)
@functools.partial(jax.jit, static_argnames=("lower_bound", "interpret"))
def kda_inputs_fwd(qc, kc, vc, f, beta_logits, scale, bias, lower_bound,
                   interpret=False):
    """qc, kc, vc [b, s, H 128] (one type), f [b, s, H 128] float32,
    beta_logits [b, s, H], scale, bias [H 128] float32 -> (q, k, beta k,
    beta v in qc's type, a float32), [b, s, H 128] each."""
    b, s, _ = qc.shape
    out = _call(
        functools.partial(_inputs_fwd_kernel, lower_bound=lower_bound),
        "kda_inputs_fwd", b * s, beta_logits.shape[-1], interpret,
        [(x, "wide") for x in (qc, kc, vc, f)]
        + [(beta_logits, "logits"), (scale, "channel"), (bias, "channel")],
        [(qc.dtype, "wide")] * 4 + [(F32, "wide")])
    return tuple(x.reshape(qc.shape) for x in out)


@functools.partial(jax.jit, static_argnames=("lower_bound", "interpret"))
def kda_inputs_bwd(cotangents, qc, kc, vc, f, beta_logits, scale, bias,
                   lower_bound, interpret=False):
    """The pull-back of `kda_inputs_fwd` along (dq, dk, d(beta k),
    d(beta v) in qc's type, da float32) -> its seven operands' cotangents."""
    b, s, _ = qc.shape
    dqc, dkc, dvc, df, dbl, dscale, dbias = _call(
        functools.partial(_inputs_bwd_kernel, lower_bound=lower_bound),
        "kda_inputs_bwd", b * s, beta_logits.shape[-1], interpret,
        [(x, "wide") for x in (*cotangents, qc, kc, vc, f)]
        + [(beta_logits, "logits"), (scale, "channel"), (bias, "channel")],
        [(qc.dtype, "wide")] * 3 + [(F32, "wide"), (F32, "logits"),
                                    (F32, "sums"), (F32, "sums")])
    return (*(x.reshape(qc.shape) for x in (dqc, dkc, dvc, df)),
            dbl.reshape(beta_logits.shape).astype(beta_logits.dtype),
            jnp.sum(dscale, (0, 1)), jnp.sum(dbias, (0, 1)))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def kda_gated_norm_fwd(o, gate_logits, gn, eps, interpret=False):
    """o [b, s, H 128], gate_logits [b, s, H], gn [128] -> o's like."""
    b, s, _ = o.shape
    out, = _call(
        functools.partial(_gated_fwd_kernel, eps=eps), "kda_gated_norm_fwd",
        b * s, gate_logits.shape[-1], interpret,
        [(o, "wide"), (gate_logits, "logits"), (gn, "gain")],
        [(o.dtype, "wide")])
    return out.reshape(o.shape)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def kda_gated_norm_bwd(dout, o, gate_logits, gn, eps, interpret=False):
    """The pull-back of `kda_gated_norm_fwd` along dout (o's type) -> (do,
    dgate_logits, dgn)."""
    b, s, _ = o.shape
    do, dgl, dgn = _call(
        functools.partial(_gated_bwd_kernel, eps=eps), "kda_gated_norm_bwd",
        b * s, gate_logits.shape[-1], interpret,
        [(dout, "wide"), (o, "wide"), (gate_logits, "logits"), (gn, "gain")],
        [(o.dtype, "wide"), (F32, "logits"), (F32, "sums")])
    return (do.reshape(o.shape),
            dgl.reshape(gate_logits.shape).astype(gate_logits.dtype),
            jnp.sum(dgn.reshape(-1, _LANES), 0).astype(gn.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _inputs(qc, kc, vc, f, beta_logits, scale, bias, lower_bound, interpret):
    return kda_inputs_fwd(qc, kc, vc, f, beta_logits, scale, bias,
                          lower_bound, interpret)


def _inputs_fwd(*args):
    return kda_inputs_fwd(*args), args[:7]


def _inputs_bwd(lower_bound, interpret, res, cotangents):
    return kda_inputs_bwd(cotangents, *res, lower_bound, interpret)


_inputs.defvjp(_inputs_fwd, _inputs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gated_norm(o, gate_logits, gn, eps, interpret):
    return kda_gated_norm_fwd(o, gate_logits, gn, eps, interpret)


def _gated_norm_fwd(o, gate_logits, gn, eps, interpret):
    return kda_gated_norm_fwd(o, gate_logits, gn, eps, interpret), (
        o, gate_logits, gn)


def _gated_norm_bwd(eps, interpret, res, dout):
    return kda_gated_norm_bwd(dout, *res, eps, interpret)


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def kda_inputs(qc, kc, vc, f, beta_logits, a_log, dt_bias, *, lower_bound,
               interpret=None, use_kernel=None):
    """The five operands of `kda.kda_flat` from a KDA layer's convolved
    projections, differentiable in all seven operands.

    qc, kc, vc [b, s, H d] in the model's type; f [b, s, H d] float32 (the
    decay's logits); beta_logits [b, s, H]; a_log [H]; dt_bias [H d] ->
    (q, k, beta k, beta v in qc's type, a float32), [b, s, H d] each. On a
    TPU at d = 128 by the two kernels; else by `kda_inputs_xla`."""
    heads = beta_logits.shape[-1]
    use_kernel, interpret = routing.route(
        "kda_inputs", supports(qc.shape, heads, qc.dtype),
        (qc.shape, heads, str(qc.dtype)), interpret, use_kernel)
    if not use_kernel:
        return kda_inputs_xla(qc, kc, vc, f, beta_logits, a_log, dt_bias,
                              lower_bound=lower_bound)
    scale, bias = _channel_rows(a_log, dt_bias, _LANES)
    return _inputs(qc, kc, vc, f.astype(F32), beta_logits, scale, bias,
                   float(lower_bound), interpret)


def kda_gated_norm(o, gate_logits, gn, *, eps, interpret=None,
                   use_kernel=None):
    """RMSNorm over a head's channels of o [b, s, H d] times gn [d] (rounded
    to o's type), times sigmoid(gate_logits [b, s, H]) a head -> o's like,
    differentiable in all three. Kernels and fallback as `kda_inputs`."""
    heads = gate_logits.shape[-1]
    use_kernel, interpret = routing.route(
        "kda_gated_norm", supports(o.shape, heads, o.dtype),
        (o.shape, heads, str(o.dtype)), interpret, use_kernel)
    if not use_kernel:
        return kda_gated_norm_xla(o, gate_logits, gn, eps=eps)
    return _gated_norm(o, gate_logits, gn, float(eps), interpret)
