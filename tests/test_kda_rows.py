"""`ops/pallas/kda_rows.py` (a KDA layer's head-wise row work on column blocks
of flat [b, s, heads 128] rows) against the `jnp` expressions the model held
before it (`models/ling3.py` `_kda` at PR 42: `_unit`, `rms`,
`kda._operands`, on [b, s, heads, 128] tables), written out here: the four
kernels in interpret mode, values and every cotangent, and the three entries
chained against that composition through `kda()`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.models.decoder_parts import rms
from paddle_tpu.ops.pallas import kda as K
from paddle_tpu.ops.pallas import kda_rows as R
from paddle_tpu.ops.pallas import routing

F32 = jnp.float32
LOWER, EPS, B = -5.0, 1e-6, 2
INPUTS = ("q", "k", "beta k", "beta v", "a", "dq~", "dk~", "dv~", "df",
          "dbeta", "dA_log", "ddt_bias")
GATED = ("out", "do", "dgate", "dgn")
# the largest error against the cotangent's largest entry: float32 differs
# by a sum's order; at bfloat16 the `jnp` form rounds each cotangent that
# passes a rounded table to bfloat16 on its way back, the kernels do not
TOL = {jnp.float32: 5e-6, jnp.bfloat16: 2e-2}


def _unit(x32):
    return x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), -1, keepdims=True)
                               + 1e-6)


def old_gate(heads, qc, kc, vc, f, beta_logits, a_log, dt_bias):
    """`_kda`'s `kda/gate` scope as it stood -> `kda()`'s five operands."""
    b, s, width = qc.shape
    d = width // heads

    def cut(v):
        return v.reshape(b, s, heads, d)

    q = (_unit(cut(qc).astype(F32)) * d ** -0.5).astype(qc.dtype)
    k = _unit(cut(kc).astype(F32)).astype(qc.dtype)
    a = cut(LOWER * jax.nn.sigmoid(
        jnp.repeat(jnp.exp(a_log.astype(F32)), d)
        * (f + dt_bias.astype(F32))))
    return q, k, cut(vc), a, jax.nn.sigmoid(beta_logits.astype(F32))


def old_inputs(heads):
    """That scope and `kda._operands` behind it, flat rows in and out."""
    def f(qc, *rest):
        return tuple(x.reshape(qc.shape) for x in K._operands(
            *old_gate(heads, qc, *rest), 16))
    return f


def old_gated_norm(heads):
    """`_kda`'s `kda/gate_norm` scope as it stood, flat rows in and out."""
    def f(o, gate_logits, gn):
        b, s, width = o.shape
        o4 = o.reshape(b, s, heads, width // heads)
        out = rms(o4, gn, EPS).astype(F32) \
            * jax.nn.sigmoid(gate_logits.astype(F32))[..., None]
        return out.astype(o.dtype).reshape(b, s, width)
    return f


def draw(seed, seq, heads, d, dtype):
    """The seven operands of `kda_inputs`, then o, gate's logits and gn."""
    rng = np.random.default_rng(seed)
    width = heads * d

    def normal(*shape, shift=0.0, scale=1.0):
        return shift + scale * rng.standard_normal(shape)

    return ([jnp.asarray(normal(B, seq, width), dtype),
             jnp.asarray(normal(B, seq, width, shift=0.3), dtype),
             jnp.asarray(normal(B, seq, width), dtype),
             jnp.asarray(normal(B, seq, width), F32),
             jnp.asarray(normal(B, seq, heads), dtype),
             jnp.asarray(np.log(rng.uniform(1, 16, heads)), dtype),
             jnp.asarray(normal(width, scale=0.3), dtype)],
            [jnp.asarray(normal(B, seq, width), dtype),
             jnp.asarray(normal(B, seq, heads), dtype),
             jnp.asarray(normal(d, shift=1.0, scale=0.1), dtype)])


def pulled_back(f, args, seed=5):
    """f's outputs, then its operands' cotangents along seeded normals."""
    out, pull = jax.vjp(f, *args)
    rng = np.random.default_rng(seed)
    cts = jax.tree.map(lambda o: jnp.asarray(rng.standard_normal(o.shape),
                                             o.dtype), out)
    return (*(out if isinstance(out, tuple) else (out,)), *pull(cts))


def worst(names, got, want):
    assert len(got) == len(want) == len(names)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
    return {n: float(jnp.max(jnp.abs(g.astype(F32) - w.astype(F32)))
                     / jnp.max(jnp.abs(w.astype(F32))))
            for n, g, w in zip(names, got, want)}


CASES = pytest.mark.parametrize("seq,heads,dtype", [
    (128, 2, jnp.float32), (192, 4, jnp.float32),
    (128, 4, jnp.bfloat16), (192, 2, jnp.bfloat16)],
    ids=lambda v: str(getattr(v, "__name__", v)))


@CASES
def test_kda_inputs_kernels_match_the_jnp_expressions_and_all_cotangents(
        seq, heads, dtype):
    # 256 and 384 rows: one block of 256, three of 128; a block's gates are
    # made at its first head and its logits' cotangents closed at its last
    args, _ = draw(0, seq, heads, 128, dtype)
    want = pulled_back(old_inputs(heads), args)
    got = pulled_back(lambda *a: R.kda_inputs(*a, lower_bound=LOWER,
                                              interpret=True), args)
    errors = worst(INPUTS, got, want)
    assert max(errors.values()) < TOL[dtype], errors
    # the five operands are the same roundings of the same float32 numbers
    assert all(errors[n] == 0.0 for n in INPUTS[:5]), errors


@CASES
def test_kda_gated_norm_kernels_match_the_jnp_expressions_and_all_cotangents(
        seq, heads, dtype):
    _, args = draw(1, seq, heads, 128, dtype)
    want = pulled_back(old_gated_norm(heads), args)
    got = pulled_back(lambda *a: R.kda_gated_norm(*a, eps=EPS,
                                                  interpret=True), args)
    errors = worst(GATED, got, want)
    assert max(errors.values()) < TOL[dtype], errors
    assert errors["out"] == 0.0


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_where_no_kernel_runs_the_jnp_forms_are_the_old_expressions_exactly(
        d, dtype):
    ins, gated = draw(2, 64, 2, d, dtype)
    for old, new, args in (
            (old_inputs(2), lambda *a: R.kda_inputs(*a, lower_bound=LOWER),
             ins),
            (old_gated_norm(2), lambda *a: R.kda_gated_norm(*a, eps=EPS),
             gated)):
        for g, w in zip(pulled_back(new, args), pulled_back(old, args)):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g.astype(F32)),
                                          np.asarray(w.astype(F32)))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("seq,heads", [(128, 2), (192, 4)])
def test_the_three_entries_chained_match_the_old_composition_through_kda(
        seq, heads, dtype, tol):
    """conv's outputs -> `kda/out`'s input, every kernel interpreted on both
    sides: the scan's kernels see the same five operands, so what differs is
    a sum's order and, at bfloat16, the cotangents' roundings on the way."""
    ins, (_, gate_logits, gn) = draw(3, seq, heads, 128, dtype)
    ins[3] = ins[3] - 2.0       # a decay that spreads over (-5, 0)
    args = (*ins, gate_logits, gn)

    def new(qc, kc, vc, f, bl, a_log, dt_bias, gl, gn):
        q, k, kb, vb, a = R.kda_inputs(qc, kc, vc, f, bl, a_log, dt_bias,
                                       lower_bound=LOWER, interpret=True)
        o = K.kda_flat(q, k, kb, vb, a, heads, chunk=64, interpret=True)
        return R.kda_gated_norm(o, gl, gn, eps=EPS, interpret=True)

    def old(*v):
        o = K.kda(*old_gate(heads, *v[:7]), chunk=64, interpret=True)
        return old_gated_norm(heads)(o.reshape(v[0].shape), *v[7:])

    names = ("out", "dq~", "dk~", "dv~", "df", "dbeta", "dA_log",
             "ddt_bias", "dgate", "dgn")
    errors = worst(names, pulled_back(new, args), pulled_back(old, args))
    assert max(errors.values()) < tol, errors
    assert errors["out"] == 0.0


def test_kda_flat_is_kda_on_the_operands_and_refuses_what_kda_refuses():
    q, k, v, a, beta = (jnp.asarray(x) for x in np.random.default_rng(
        4).uniform(-1, 0, (5, 1, 128, 2, 128)).astype(np.float32))
    beta = -beta[..., 0]
    flat = [x.reshape(1, 128, 256) for x in K._operands(q, k, v, a, beta, 64)]
    for interpret in (None, True):      # XLA's driver, then the kernels
        np.testing.assert_array_equal(
            np.asarray(K.kda_flat(*flat, 2, chunk=64, interpret=interpret)),
            np.asarray(K.kda(q, k, v, a, beta, chunk=64, interpret=interpret)
                       ).reshape(1, 128, 256))
    with pytest.raises(ValueError, match="multiple of chunk"):
        K.kda_flat(*flat, 2, chunk=48)


def test_what_the_row_kernels_do_not_take_is_refused_by_name_or_counted():
    ins, gated = draw(5, 64, 2, 64, jnp.float32)
    assert not R.supports(ins[0].shape, 2, jnp.float32)        # width 64
    assert not R.supports((1, 24, 256), 2, jnp.float32)        # 24 rows
    assert not R.supports((2, 64, 256), 2, jnp.float16)
    assert R.supports((2, 8192, 4096), 32, jnp.bfloat16)
    with pytest.raises(ValueError, match="kda_inputs kernel does not"):
        R.kda_inputs(*ins, lower_bound=LOWER, use_kernel=True)
    with pytest.raises(ValueError, match="kda_gated_norm kernel does not"):
        R.kda_gated_norm(*gated, eps=EPS, use_kernel=True)
    key = ("kda_gated_norm", (gated[0].shape, 2, "float32"))
    before = routing.xla_fallbacks[key]
    R.kda_gated_norm(*gated, eps=EPS, interpret=True)   # asked, not taken
    assert routing.xla_fallbacks[key] == before + 1
    for name in ("kda_inputs_fwd", "kda_inputs_bwd", "kda_gated_norm_fwd",
                 "kda_gated_norm_bwd"):
        # `kda_scan_roofline` finds the scan's kernels by these substrings
        assert "kda_fwd" not in name and "kda_bwd" not in name
        assert getattr(R, name).__name__ == name
