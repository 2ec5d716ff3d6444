"""`ops/pallas/kda.py` (the chunked gated delta rule with a decay a channel)
against the token-by-token recurrence of the plain reference
(benchmark/reference/ling3.py `kda_recurrence`): `kda_xla` and the two
kernels in interpret mode, values and all five cotangents."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.ops.pallas import kda as K

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmark"))
from reference import ling3 as ref  # noqa: E402

NAMES = ("o", "dq", "dk", "dv", "da", "dbeta")


def operands(seed, b, seq, heads, d, lo, hi, dtype=jnp.float32):
    """q, k as they enter the recurrence (unit rows that lean the same way,
    as rows that left a silu do; q scaled), v, a in (lo, hi), beta."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((b, seq, heads, d))) * d ** -0.5
    k = unit(rng.standard_normal((b, seq, heads, d)) + 0.5)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(rng.standard_normal((b, seq, heads, d)), dtype),
            jnp.asarray(rng.uniform(lo, hi, (b, seq, heads, d)), jnp.float32),
            jnp.asarray(rng.uniform(0.05, 0.95, (b, seq, heads)),
                        jnp.float32))


def pulled_back(f, args, seed=9):
    do = jnp.asarray(np.random.default_rng(seed).standard_normal(
        args[2].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        o, pull = jax.vjp(f, *args)
        return (o,) + pull(do.astype(o.dtype))


def worst(got, want):
    return {n: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                     / jnp.max(jnp.abs(w)))
            for n, g, w in zip(NAMES, got, want)}


PATHS = {"xla": lambda *a, **kw: K.kda_xla(*a, **kw),
         "kernels-interpreted": lambda *a, **kw: K.kda(*a, interpret=True,
                                                       **kw)}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("lo,hi", [(-5.0, -4.5), (-0.5, 0.0), (-5.0, 0.0)],
                         ids=["fast-decay", "slow-decay", "whole-range"])
def test_kda_matches_the_recurrence_forward_and_all_five_cotangents(
        path, lo, hi):
    # three chunks of 64; two grid steps' worth for the kernels (a block of
    # two chunks, then one of one would not divide: 192 = 3 blocks of 1)
    args = operands(0, 1, 192, 2, 128, lo, hi)
    want = pulled_back(ref.kda_recurrence, args)
    got = pulled_back(PATHS[path], args)
    assert max(worst(got, want).values()) < 5e-4, worst(got, want)


@pytest.mark.parametrize("chunk,seq", [(16, 64), (32, 128), (64, 512)])
def test_kda_kernels_at_other_chunks_and_blocks_of_several(chunk, seq):
    args = operands(1, 2, seq, 1, 128, -5.0, 0.0)
    want = pulled_back(ref.kda_recurrence, args)
    got = pulled_back(lambda *a: K.kda(*a, chunk=chunk, interpret=True), args)
    assert max(worst(got, want).values()) < 5e-4, worst(got, want)
    assert K._block(seq, chunk) == 4 * chunk


@pytest.mark.parametrize("seq,per,systems", [
    (64, 1, [1]), (128, 2, [2]), (192, 1, [1]), (512, 4, [2, 2]),
    (1024, 4, [2, 2])])
def test_kda_kernels_at_grid_steps_of_one_two_and_four_chunks(seq, per,
                                                              systems):
    """A grid step of one chunk runs the [64, 64] system, one of two or four
    chunks its pairs as [128, 128] systems; 512 and 1024 are two and four
    grid steps of four chunks (the state and dS through scratch, the pair
    loop run again)."""
    assert K._block(seq, 64) == per * 64
    assert K._systems(per * 64, 64) == systems
    args = operands(5, 1, seq, 2, 128, -5.0, 0.0)
    want = pulled_back(ref.kda_recurrence, args)
    got = pulled_back(lambda *a: K.kda(*a, interpret=True), args)
    assert max(worst(got, want).values()) < 5e-4, worst(got, want)


def test_at_the_cells_geometry_a_grid_step_pairs_its_chunks():
    b, seq, heads, d = 2, 8192, 32, 128
    assert K.supports((b, seq, heads, d), (b, seq, heads, d), 64,
                      jnp.bfloat16)
    block = K._block(seq, 64)
    assert block == 256 and K._systems(block, 64) == [2, 2]


def test_kda_with_bfloat16_operands_stays_near_the_recurrence():
    args = operands(2, 1, 128, 2, 128, -5.0, 0.0, jnp.bfloat16)
    want = pulled_back(ref.kda_recurrence,
                       tuple(a.astype(jnp.float32) for a in args))
    for path in PATHS.values():
        assert max(worst(pulled_back(path, args), want).values()) < 3e-2


def test_kda_interpreted_runs_the_kernels_not_the_xla_path(monkeypatch):
    monkeypatch.setattr(K, "kda_xla", None)
    args = operands(3, 1, 64, 1, 128, -5.0, 0.0)
    assert K.kda(*args, interpret=True).shape == args[2].shape


def test_the_triangular_inverse_is_exact_where_plain_doubling_is_not():
    """Rows that all lean one way: I + N has entries near 1 below the
    diagonal, N's powers grow as binomials and the two-level inverse keeps
    float32's digits."""
    n = 64
    a = np.tril(np.full((n, n), 0.9, np.float32), -1)
    got = np.asarray(K._tri_inverse(jnp.asarray(a)))
    want = np.linalg.inv(np.eye(n) + a.astype(np.float64))
    np.testing.assert_allclose(got, want, atol=5e-4)
    small = np.asarray(K._tri_inverse(jnp.asarray(a[:16, :16])))
    np.testing.assert_allclose(small, want[:16, :16], atol=5e-4)
    plain, power = np.eye(n, dtype=np.float32) - a, a
    for _ in range(5):          # (I - N)(I + N^2) .. (I + N^32) in float32
        power = power @ power
        plain = plain @ (np.eye(n, dtype=np.float32) + power)
    assert np.abs(plain - want).max() > 1e3


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_the_inverse_of_a_pair_as_one_system_is_the_two_chunks_own(chunk):
    """Two chunks as ONE block-diagonal [2 C, 2 C] system, rows that lean
    one way: the two inverses come back side by side, each that chunk's own
    to float32's rounding, and nothing of one chunk reaches the other's (the
    blocks beside the diagonal are exact zeros, not small numbers)."""
    rng = np.random.default_rng(chunk)
    blocks = [np.tril(np.full((chunk, chunk), 0.9, np.float32)
                      + 0.05 * rng.standard_normal((chunk, chunk)).astype(
                          np.float32), -1) for _ in range(2)]

    def paired(first, second):
        pair = np.zeros((2 * chunk, 2 * chunk), np.float32)
        pair[:chunk, :chunk], pair[chunk:, chunk:] = first, second
        return np.asarray(K._tri_inverse(jnp.asarray(pair), chunk))

    got = paired(*blocks)
    assert got.shape == (chunk, 2 * chunk)
    for i, a in enumerate(blocks):
        at = slice(i * chunk, (i + 1) * chunk)
        own = np.asarray(K._tri_inverse(jnp.asarray(a)))
        want = np.linalg.inv(np.eye(chunk) + a.astype(np.float64))
        # float32's rounding of the powers on the way (entries near 1e3),
        # as far as either lies from the float64 inverse
        np.testing.assert_allclose(got[:, at], own, atol=1e-4)
        np.testing.assert_allclose(got[:, at], want, atol=5e-4)
    other = paired(blocks[0], 0 * blocks[1])
    np.testing.assert_array_equal(other[:, :chunk], got[:, :chunk])
    np.testing.assert_array_equal(other[:, chunk:], np.eye(chunk))


def test_what_the_kernels_do_not_take_goes_to_xla_or_is_refused_by_name():
    assert K.supports((2, 8192, 32, 128), (2, 8192, 32, 128), 64,
                      jnp.bfloat16)
    assert not K.supports((1, 64, 2, 64), (1, 64, 2, 64), 64, jnp.float32)
    assert not K.supports((1, 64, 2, 128), (1, 64, 2, 128), 64, jnp.float16)
    args = operands(4, 1, 96, 1, 128, -5.0, 0.0)
    with pytest.raises(ValueError, match="not a multiple of chunk"):
        K.kda(*args)
    with pytest.raises(ValueError, match="does not support"):
        K.kda(*operands(4, 1, 64, 1, 64, -5.0, 0.0), use_kernel=True)
