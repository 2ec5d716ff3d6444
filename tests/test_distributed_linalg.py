"""paddle.linalg.distributed — SUMMA / blocked factorizations /
eigensolvers on the 8-device host mesh (ISSUE 9 tentpole).

Contracts under test (ISSUE acceptance):
  * every op matches the single-device jnp.linalg reference at fp32
    tol <= 1e-4 (most are ~1e-6 on these sizes);
  * non-square and non-divisible global shapes work (internal padding);
  * the compiled per-device program of every op contains NO buffer the
    size of a full global matrix (panels move, matrices don't), checked
    over the optimized HLO with the per-axis collective census from
    tools/hlo_overlap.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.linalg import distributed as dla
from paddle_tpu.linalg.distributed import probe

TOL = 1e-4


@pytest.fixture(scope="module")
def grid():
    return dla.build_grid(devices=jax.devices("cpu")[:8])


@pytest.fixture(scope="module")
def grid2x2():
    return dla.build_grid(2, 2, devices=jax.devices("cpu")[:8])


def _rand(*shape, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) * scale).astype(np.float32)


class TestGrid:
    def test_default_factors_all_devices(self, grid):
        r, c = dla.grid_shape(grid)
        assert r * c == 8 and grid.axis_names == ("rows", "cols")

    def test_square_subset(self):
        g = dla.build_grid(square=True, devices=jax.devices("cpu")[:8])
        assert dla.grid_shape(g) == (2, 2)

    def test_too_many_devices_raises(self):
        with pytest.raises(ValueError, match="needs"):
            dla.build_grid(16, 16, devices=jax.devices("cpu")[:8])

    def test_block_cyclic_permutation_roundtrip(self):
        idx = dla.block_cyclic_permutation(24, 2, 4)
        inv = np.empty_like(idx)
        inv[idx] = np.arange(24)
        x = np.arange(24)
        np.testing.assert_array_equal(x[idx][inv], x)
        # blocks of 4, alternating owners 0,1,0,1,... -> owner-0 blocks
        # first (0, 2, 4), then owner-1 (1, 3, 5)
        np.testing.assert_array_equal(idx[:4], np.arange(0, 4))
        np.testing.assert_array_equal(idx[4:8], np.arange(8, 12))


class TestSUMMA:
    def test_parity_divisible(self, grid):
        a, b = _rand(64, 48, seed=1), _rand(48, 32, seed=2)
        got = np.asarray(dla.matmul(a, b, grid=grid))
        np.testing.assert_allclose(got, a @ b, atol=TOL)

    def test_parity_non_divisible_non_square(self, grid):
        a, b = _rand(37, 53, seed=3), _rand(53, 29, seed=4)
        got = np.asarray(dla.matmul(a, b, grid=grid))
        np.testing.assert_allclose(got, a @ b, atol=TOL)

    def test_more_panels(self, grid):
        a, b = _rand(32, 64, seed=5), _rand(64, 16, seed=6)
        got = np.asarray(dla.matmul(a, b, grid=grid, panels=16))
        np.testing.assert_allclose(got, a @ b, atol=TOL)

    def test_block_cyclic_layout(self, grid2x2):
        a, b = _rand(40, 24, seed=7), _rand(24, 36, seed=8)
        got = np.asarray(dla.matmul(a, b, grid=grid2x2, block_size=4))
        np.testing.assert_allclose(got, a @ b, atol=TOL)

    def test_block_cyclic_needs_square_grid(self, grid):
        with pytest.raises(ValueError, match="square grid"):
            dla.matmul(_rand(8, 8), _rand(8, 8), grid=grid,
                       block_size=2)

    def test_tensor_in_tensor_out(self, grid):
        a = paddle.to_tensor(_rand(16, 24, seed=9))
        b = paddle.to_tensor(_rand(24, 8, seed=10))
        out = dla.matmul(a, b, grid=grid)
        assert hasattr(out, "_data")
        np.testing.assert_allclose(
            np.asarray(out._data),
            np.asarray(a._data) @ np.asarray(b._data), atol=TOL)

    def test_inner_dim_mismatch_raises(self, grid):
        with pytest.raises(ValueError, match="inner dims"):
            dla.matmul(_rand(8, 9), _rand(8, 9), grid=grid)

    def test_compiled_callable_reused(self, grid):
        from paddle_tpu.linalg.distributed import _grid as G

        a, b = _rand(64, 48, seed=1), _rand(48, 32, seed=2)
        dla.matmul(a, b, grid=grid)
        n = len(G._jit_cache)
        dla.matmul(a + 1, b, grid=grid)      # same signature
        assert len(G._jit_cache) == n


class TestCholesky:
    def _spd(self, n, seed=0):
        x = _rand(n, n, seed=seed)
        return x @ x.T + n * np.eye(n, dtype=np.float32)

    def test_parity(self, grid2x2):
        spd = self._spd(32, seed=11)
        got = np.asarray(dla.cholesky(spd, grid=grid2x2))
        np.testing.assert_allclose(got, np.linalg.cholesky(spd),
                                   atol=TOL)

    def test_parity_non_divisible(self, grid2x2):
        spd = self._spd(37, seed=12)
        got = np.asarray(dla.cholesky(spd, grid=grid2x2))
        np.testing.assert_allclose(got, np.linalg.cholesky(spd),
                                   atol=TOL)

    def test_upper(self, grid2x2):
        spd = self._spd(16, seed=13)
        got = np.asarray(dla.cholesky(spd, upper=True, grid=grid2x2))
        np.testing.assert_allclose(got, np.linalg.cholesky(spd).T,
                                   atol=TOL)

    def test_rect_grid_rejected(self, grid):
        with pytest.raises(ValueError, match="square grid"):
            dla.cholesky(self._spd(16), grid=grid)

    def test_non_square_matrix_rejected(self, grid2x2):
        with pytest.raises(ValueError, match="square matrix"):
            dla.cholesky(_rand(8, 9), grid=grid2x2)


class TestQR:
    def _check(self, a, grid):
        q, r = dla.qr(a, grid=grid)
        q, r = np.asarray(q), np.asarray(r)
        m, n = a.shape
        np.testing.assert_allclose(q @ r, a, atol=TOL)
        np.testing.assert_allclose(q.T @ q, np.eye(n), atol=TOL)
        assert np.abs(np.tril(r, -1)).max() < TOL
        # sign-canonical parity vs the reference R (QR is unique up to
        # per-column sign for full-rank A)
        r_ref = np.linalg.qr(a, mode="reduced")[1]
        s, s_ref = np.sign(np.diag(r)), np.sign(np.diag(r_ref))
        np.testing.assert_allclose(r * s[:, None],
                                   r_ref * s_ref[:, None], atol=TOL)

    def test_parity_divisible(self, grid):
        self._check(_rand(128, 16, seed=14), grid)

    def test_parity_non_divisible(self, grid):
        self._check(_rand(101, 13, seed=15), grid)

    def test_wide_rejected(self, grid):
        with pytest.raises(ValueError, match="tall"):
            dla.qr(_rand(8, 16), grid=grid)

    def test_full_mode_rejected(self, grid):
        with pytest.raises(NotImplementedError, match="reduced"):
            dla.qr(_rand(32, 4), mode="complete", grid=grid)


class TestEigsh:
    def _sym_with_spectrum(self, n, lam, seed=0):
        rng = np.random.default_rng(seed)
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        a = (q * lam) @ q.T
        return (0.5 * (a + a.T)).astype(np.float32)

    def test_topk_parity(self, grid):
        # spectral gap λ5/λ4 ~ 0.01 -> ~6-iter convergence; 25 iters is
        # ample and keeps the unrolled-program compile cheap
        lam = np.array([10.0, 8.0, 6.0, 4.5]
                       + list(0.05 * np.random.default_rng(1).random(44)))
        a = self._sym_with_spectrum(48, lam, seed=16)
        w, v = dla.eigsh(a, k=4, iters=25, grid=grid)
        w, v = np.asarray(w), np.asarray(v)
        ref = np.sort(np.linalg.eigvalsh(a))[::-1][:4]
        np.testing.assert_allclose(w, ref, atol=TOL)
        # eigenvector residual ||Av - λv||
        assert np.abs(a @ v - v * w[None, :]).max() < TOL

    def test_non_divisible_n(self, grid):
        lam = np.array([5.0, 3.0] + [0.05] * 41)
        a = self._sym_with_spectrum(43, lam, seed=17)
        w, _ = dla.eigsh(a, k=2, iters=25, grid=grid)
        ref = np.sort(np.linalg.eigvalsh(a))[::-1][:2]
        np.testing.assert_allclose(np.asarray(w), ref, atol=TOL)

    def test_power_iteration(self, grid):
        lam = np.array([7.0] + [0.5] * 31)
        a = self._sym_with_spectrum(32, lam, seed=18)
        ev, vec = dla.power_iteration(a, iters=20, grid=grid)
        assert abs(float(ev) - 7.0) < TOL
        vec = np.asarray(vec)
        assert np.abs(a @ vec - float(ev) * vec).max() < TOL


class TestHLOReceipts:
    """The no-full-gather contract, on the compiled per-device HLO."""

    def test_summa_receipt(self, grid):
        low = dla.summa_lowered(64, 64, 64, grid=grid)
        v = probe.collective_receipt(low, grid, full_elems=64 * 64,
                                     what="matmul operand")
        assert v["no_full_matrix"]
        # a panel of each operand is all-reduced over exactly ONE mesh
        # axis, and nothing else moves: no gather, nothing over the
        # flattened grid. How many all-reduce ops carry the 4 + 4 panels
        # is the compiler's choice (this XLA combines an axis's four
        # into one tuple all-reduce), so the count is not asserted.
        pa = v["per_axis_counts"]
        assert set(pa) == {"rows", "cols"}, pa
        assert all(set(kinds) == {"all-reduce"} for kinds in pa.values()), pa
        assert set(v["counts"]) == {"all-reduce"}, v["counts"]

    def test_cholesky_receipt(self, grid2x2):
        low = dla.cholesky_lowered(32, grid=grid2x2)
        v = probe.collective_receipt(low, grid2x2, full_elems=32 * 32,
                                     what="cholesky input")
        assert v["no_full_matrix"]
        # rows-axis panel all_gathers (XLA DCEs the final iteration's —
        # its trailing update is empty) + the diagonal-block broadcasts
        assert v["per_axis_counts"]["rows"]["all-gather"] >= 1
        assert v["per_axis_counts"]["rows"]["all-reduce"] >= 2

    def test_qr_receipt(self, grid):
        # m large so the [w*n, n] R-stack stays well under m*n
        low = dla.qr_lowered(1024, 16, grid=grid)
        v = probe.collective_receipt(low, grid, full_elems=1024 * 16,
                                     what="qr input")
        assert v["no_full_matrix"]
        # TSQR: exactly ONE gather, over the flattened grid
        assert v["counts"] == {"all-gather": 1}
        assert v["per_axis_counts"]["rows+cols"]["all-gather"] == 1

    def test_eigsh_receipt(self, grid):
        low = dla.eigsh_lowered(64, k=4, iters=8, grid=grid)
        v = probe.collective_receipt(low, grid, full_elems=64 * 64,
                                     what="eigsh input")
        assert v["no_full_matrix"]
        # one cols psum + one rows gather per matvec (iters + 1
        # Rayleigh step)
        assert v["per_axis_counts"]["cols"]["all-reduce"] == 9
        assert v["per_axis_counts"]["rows"]["all-gather"] == 9

    def test_assert_no_full_matrix_flags_dense(self):
        # self-check: the probe actually fires on a full-size buffer
        text = "%p = f32[64,64] parameter(0)"
        with pytest.raises(AssertionError, match="materializes"):
            probe.assert_no_full_matrix(text, 64 * 64)


class TestNamespace:
    def test_paddle_linalg_surface(self):
        assert paddle.linalg.distributed is dla
        # the reference linalg surface rides along
        x = paddle.to_tensor(np.eye(3, dtype=np.float32))
        np.testing.assert_allclose(
            np.asarray(paddle.linalg.inv(x)._data), np.eye(3))
