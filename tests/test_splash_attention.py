"""Splash training attention (ops/pallas/splash_attention.py): kernel
(interpret mode) vs XLA fallback vs dense reference — forward + custom
backward — across causal/non-causal, GQA, and packed-sequence segment
masks; plus the F.scaled_dot_product_attention routing surface."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import splash_attention as sa

HP = jax.lax.Precision.HIGHEST


def _ref(q, k, v, causal, scale, seg=None):
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    grp = h // kvh
    qg = q.reshape(b, sq, kvh, grp, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   precision=HP).astype(jnp.float32) * scale
    mask = jnp.ones((b, sq, sq), bool)
    if causal:
        mask = mask & jnp.tril(jnp.ones((sq, sq), bool))[None]
    if seg is not None:
        mask = mask & (seg[:, :, None] == seg[:, None, :])
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                     precision=HP)
    return out.reshape(b, sq, h, d)


def _rand(b, s, h, kvh, d, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda hh: jnp.asarray(  # noqa: E731
        rng.standard_normal((b, s, hh, d)) * 0.5, dtype)
    return mk(h), mk(kvh), mk(kvh)


def _segments(b, s, docs, seed=0):
    rng = np.random.default_rng(seed)
    bounds = np.sort(rng.integers(1, s, docs - 1))
    return jnp.asarray(np.broadcast_to(
        np.searchsorted(bounds, np.arange(s), side="right"),
        (b, s)).copy(), jnp.int32)


class TestSplashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("h,kvh", [(2, 2), (4, 2)])
    def test_forward_and_grads_match_dense(self, causal, h, kvh):
        q, k, v = _rand(2, 256, h, kvh, 32)
        scale = 1.0 / 32 ** 0.5
        out = sa.splash_attention(q, k, v, causal=causal, scale=scale,
                                  interpret=True)
        want = _ref(q, k, v, causal, scale)
        assert float(jnp.max(jnp.abs(out - want))) < 3e-5

        def loss_k(q, k, v):
            return jnp.sum(jnp.sin(sa.splash_attention(
                q, k, v, causal=causal, scale=scale, interpret=True)))

        def loss_r(q, k, v):
            return jnp.sum(jnp.sin(_ref(q, k, v, causal, scale)))

        gk = jax.grad(loss_k, (0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            assert float(jnp.max(jnp.abs(a - b))) < 5e-4

    @pytest.mark.parametrize("h,kvh", [(2, 2), (2, 1)])
    def test_segment_mask_matches_dense(self, h, kvh):
        q, k, v = _rand(2, 256, h, kvh, 32, seed=3)
        seg = _segments(2, 256, 3, seed=3)
        scale = 0.2
        out = sa.splash_attention(q, k, v, causal=True, scale=scale,
                                  segment_ids=seg, interpret=True)
        want = _ref(q, k, v, True, scale, seg=seg)
        assert float(jnp.max(jnp.abs(out - want))) < 3e-5

        def loss_k(q, k, v):
            return jnp.sum(jnp.sin(sa.splash_attention(
                q, k, v, causal=True, scale=scale, segment_ids=seg,
                interpret=True)))

        def loss_r(q, k, v):
            return jnp.sum(jnp.sin(_ref(q, k, v, True, scale, seg=seg)))

        gk = jax.grad(loss_k, (0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            assert float(jnp.max(jnp.abs(a - b))) < 5e-4

    def test_segments_equal_per_document_attention(self):
        """The packed-sequence contract: one splash call over packed
        docs == each document attended separately (out AND grads)."""
        b, s, h, d = 1, 256, 2, 32
        lens = [96, 64, 96]
        q, k, v = _rand(b, s, h, h, d, seed=4)
        seg = jnp.asarray(np.repeat(np.arange(len(lens)), lens)[None],
                          jnp.int32)

        def packed(q, k, v):
            return sa.splash_attention(q, k, v, causal=True,
                                       segment_ids=seg, interpret=True)

        def perdoc(q, k, v):
            outs, off = [], 0
            for ln in lens:
                sl = slice(off, off + ln)
                outs.append(_ref(q[:, sl], k[:, sl], v[:, sl], True,
                                 1.0 / d ** 0.5))
                off += ln
            return jnp.concatenate(outs, axis=1)

        assert float(jnp.max(jnp.abs(
            packed(q, k, v) - perdoc(q, k, v)))) < 3e-5
        gk = jax.grad(lambda *a: jnp.sum(jnp.sin(packed(*a))),
                      (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(jnp.sin(perdoc(*a))),
                      (0, 1, 2))(q, k, v)
        for a, bb in zip(gk, gr):
            assert float(jnp.max(jnp.abs(a - bb))) < 5e-4

    def test_xla_fallback_matches_kernel(self):
        q, k, v = _rand(2, 256, 2, 2, 32, seed=5)
        seg = _segments(2, 256, 2, seed=5)
        out_k = sa.splash_attention(q, k, v, causal=True,
                                    segment_ids=seg, interpret=True)
        out_x = sa.splash_attention(q, k, v, causal=True,
                                    segment_ids=seg, use_kernel=False)
        assert float(jnp.max(jnp.abs(out_k - out_x))) < 3e-5

    def test_bf16(self):
        q, k, v = _rand(1, 256, 2, 2, 32, dtype=jnp.bfloat16, seed=6)
        out = sa.splash_attention(q, k, v, causal=True, interpret=True)
        want = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), True, 1.0 / 32 ** 0.5)
        assert out.dtype == jnp.bfloat16
        assert float(jnp.max(jnp.abs(
            out.astype(jnp.float32) - want))) < 3e-2

    def test_supports_gate(self):
        assert sa.supports((2, 1024, 8, 64), 8, jnp.bfloat16)
        assert sa.supports((2, 256, 8, 64), 4, jnp.float32)     # GQA
        assert not sa.supports((2, 1021, 8, 64), 8, jnp.float32)
        assert not sa.supports((2, 256, 8, 64), 3, jnp.float32)
        assert not sa.supports((2, 256, 8, 512), 8, jnp.float32)
        assert not sa.supports((2, 256, 8, 64), 8, jnp.int8)


# -- causal-only work (PR 28): strips on the diagonal tile, no compare below
# it, nothing fetched above it -------------------------------------------

# (seq, block_q, block_k): which tiling of the kernels a case runs
GEOMETRY = {
    # one square tile: the strips, forward and (group 1) backward
    "one_tile": (256, 256, 256),
    # a grid of tiles: below / on / above the diagonal, k-side maps held
    "4x4_tiles": (512, 128, 128),
    # block_q = 2 block_k, what `_REVISIT_MIN` halves a backward to: the
    # diagonal crosses two tiles a q row, masked by position
    "bk_halved": (512, 256, 128),
    # several q tiles against one k tile: the per-row backward calls
    "row_loop": (256, 128, 256),
}


def _selection(b, s, keep, seed):
    """int8 [b, s, s]: each key kept with probability `keep`, and the
    diagonal, so that no causal row is empty."""
    rng = np.random.default_rng(seed)
    sel = (rng.random((b, s, s)) < keep) | np.eye(s, dtype=bool)[None]
    return sel.astype(np.int8)


def _grads(fn, q, k, v):
    def run(q, k, v):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + tuple(vjp(jnp.cos(out)))

    # one program: an interpreted backward is a kernel call a q tile
    return jax.jit(run)(q, k, v)


def _parity_cases():
    """masks x group x head_dim x geometry. A group of 8 over a grid of
    tiles is 16-32 interpreted backward calls (~10 s): there the two
    masks go together, and apart only on the one-tile geometry."""
    import itertools

    return [c for c in itertools.product(
        ["plain", "segments", "selection", "both"], [1, 8], [64, 128],
        list(GEOMETRY))
        if c[1] == 1 or c[3] == "one_tile" or c[0] == "both"]


def _assert_parity(q, k, v, atol=(3e-5, 5e-4), **kw):
    """Forward, dQ, dK, dV of the interpreted kernel against the dense
    XLA path with the same masks."""
    blocks = {n: kw.pop(n) for n in ("block_q", "block_k") if n in kw}
    got = _grads(lambda q, k, v: sa.splash_attention(
        q, k, v, interpret=True, **blocks, **kw), q, k, v)
    want = _grads(lambda q, k, v: sa.splash_attention_xla(
        q, k, v, **kw), q, k, v)
    for name, a, b, tol in zip(("out", "dq", "dk", "dv"), got, want,
                               (atol[0],) + (atol[1],) * 3):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b))) < tol, name
    return got


class TestCausalOnlyWork:
    @pytest.mark.parametrize("masks,group,d,geometry", _parity_cases())
    def test_matches_dense(self, masks, group, d, geometry):
        s, bq, bk = GEOMETRY[geometry]
        q, k, v = _rand(1, s, group, 1, d, seed=11)
        kw = {}
        if masks in ("segments", "both"):
            kw["segment_ids"] = _segments(1, s, 3, seed=12)
        if masks in ("selection", "both"):
            kw["selection"] = jnp.asarray(_selection(1, s, 0.3, seed=13))
        _assert_parity(q, k, v, causal=True, block_q=bq, block_k=bk, **kw)

    @pytest.mark.parametrize("geometry", ["one_tile", "4x4_tiles"])
    def test_empty_row_is_zero_out_and_zero_grad(self, geometry):
        s, bq, bk = GEOMETRY[geometry]
        q, k, v = _rand(1, s, 2, 1, 64, seed=14)
        sel = _selection(1, s, 0.3, seed=15)
        sel[:, 5, :] = 0
        sel[:, s - 1, :] = 0
        out, dq, _, _ = _assert_parity(
            q, k, v, causal=True, selection=jnp.asarray(sel), block_q=bq,
            block_k=bk)
        for row in (5, s - 1):
            assert float(jnp.max(jnp.abs(out[:, row]))) == 0.0
            assert float(jnp.max(jnp.abs(dq[:, row]))) == 0.0

    @pytest.mark.parametrize("group", [1, 2])
    def test_selection_empties_a_diagonal_sub_tile(self, group):
        """Rows 128..255 of one 256-tile keep no key of their own strip's
        square on the diagonal: what is left of their softmax sits in the
        part of the strip that takes no causal compare."""
        s = 256
        assert sa._strips(s, s, True, 1) == 2
        q, k, v = _rand(1, s, group, 1, 64, seed=16)
        sel = _selection(1, s, 0.3, seed=17)
        sel[:, 128:, 128:] = 0
        _assert_parity(q, k, v, causal=True, selection=jnp.asarray(sel))
        sel[:, :128, :128] = 0         # and the first strip is all empty
        out = _assert_parity(q, k, v, causal=True,
                             selection=jnp.asarray(sel))[0]
        assert float(jnp.max(jnp.abs(out[:, :128]))) == 0.0

    def test_bf16_strips(self):
        q, k, v = _rand(1, 512, 2, 2, 64, dtype=jnp.bfloat16, seed=18)
        assert sa._strips(512, 512, True, 1) == 2
        _assert_parity(q, k, v, atol=(2e-2, 6e-2), causal=True)

    # the non-causal kernel is the one every earlier tree compiled: these
    # are sha256 of its (out, dq, dk, dv) bytes on this input, taken from
    # the tree before PR 28 (interpret mode, this container's CPU)
    NONCAUSAL_BITS = {"0.9.0": {
        "plain": "2041cdf2b88b4d73e63cb525865a523abb98c85599cfe38a2969703276ae47bf",
        "segments": "b677b6a782c9bbd07f42e051494f7a72f02a83a6ad552eb085b692db1f65ba7e",
    }}

    @pytest.mark.parametrize("masks", ["plain", "segments"])
    def test_noncausal_is_bit_identical_to_the_parent(self, masks):
        import hashlib

        golden = self.NONCAUSAL_BITS.get(jax.__version__)
        if golden is None:
            pytest.skip(f"no parent bits recorded for jax {jax.__version__}")
        q, k, v = _rand(1, 256, 2, 1, 32, seed=19)
        kw = ({"segment_ids": _segments(1, 256, 3, seed=20)}
              if masks == "segments" else {})
        assert sa._strips(128, 128, False, 2) == 1
        got = _grads(lambda q, k, v: sa.splash_attention(
            q, k, v, causal=False, interpret=True, block_q=128,
            block_k=128, **kw), q, k, v)
        digest = hashlib.sha256(b"".join(
            np.asarray(a).tobytes() for a in got)).hexdigest()
        assert digest == golden[masks]


def _eqn_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (list, tuple)) else [value]:
            item = getattr(item, "jaxpr", item)
            if hasattr(item, "eqns"):
                yield item


def _kernel_equations(fn, *args):
    """Equations in the body of every pallas_call `fn` traces (nested
    `pl.when` bodies included), in call order. Nothing is lowered."""
    def count(jaxpr):
        return len(jaxpr.eqns) + sum(
            count(sub) for eqn in jaxpr.eqns for sub in _eqn_jaxprs(eqn))

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(count(eqn.params["jaxpr"]))
            else:
                for sub in _eqn_jaxprs(eqn):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


class TestSetUpBudget:
    """What refused PR 27: the causal-only kernels were faster in every
    cell, and their text cost the Keye cell 6.2 s before its first step
    (each of its 18 call sites traces and lowers the kernel body). The
    work the kernels form and the size of what they trace are pinned."""

    @pytest.mark.parametrize("seq,ceiling", [(1024, 1.5), (8192, 1.13)])
    def test_computed_pairs_over_required(self, seq, ceiling):
        required = seq * (seq + 1) // 2
        assert sa.computed_pairs(seq, 1024, 1024, causal=False) == seq * seq
        share = sa.computed_pairs(seq, 1024, 1024) / required
        assert 1.0 <= share <= ceiling, share

    def test_computed_pairs_by_hand(self):
        # one 256-tile in two strips: 128 x 128 + 128 x 256
        assert sa.computed_pairs(256) == 128 * 128 + 128 * 256
        # 2 x 2 tiles of 128: the two on the diagonal whole, one below
        assert sa.computed_pairs(256, 128, 128) == 3 * 128 * 128
        # block_q = 2 block_k: both tiles of a q row are crossed
        assert sa.computed_pairs(512, 256, 128) == (2 + 4) * 256 * 128

    # (forward, backward) equations of the kernels at the benchmark's
    # shapes. The tree before PR 28 traced (107, 87) without a selection
    # and (114, 94) with one at either shape; PR 28 traces (83, 92) and
    # (111, 106) at seq 1024, two strips, and (82, 77) and (100, 84) at
    # seq 8192, one body as before. PR 27's walked tiles were refused for
    # what their text cost the Keye cell's 18 call sites.
    @pytest.mark.parametrize("shape,selection,ceiling", [
        ((8, 1024, 32, 32, 64), False, (90, 100)),
        ((8, 1024, 32, 32, 64), True, (118, 112)),
        ((4, 8192, 32, 4, 128), False, (88, 82)),
        ((4, 8192, 32, 4, 128), True, (106, 90)),
    ])
    def test_traced_kernel_bodies_stay_small(self, shape, selection,
                                             ceiling):
        b, s, h, kvh, d = shape
        spec = jax.ShapeDtypeStruct
        args = [spec((b, s, h, d), jnp.bfloat16),
                spec((b, s, kvh, d), jnp.bfloat16),
                spec((b, s, kvh, d), jnp.bfloat16)]
        if selection:
            args.append(spec((b, s, s), jnp.int8))

        def fwd_and_bwd(q, k, v, *sel):
            out, vjp = jax.vjp(lambda q, k, v: sa.splash_attention(
                q, k, v, causal=True, selection=sel[0] if sel else None,
                use_kernel=True, interpret=False), q, k, v)
            return vjp(out)

        from paddle_tpu.utils import flags
        selfcheck = flags.get_flag("FLAGS_pallas_alias_selfcheck")
        flags.set_flags({"FLAGS_pallas_alias_selfcheck": False})
        try:
            fwd, bwd = _kernel_equations(fwd_and_bwd, *args)
        finally:
            flags.set_flags({"FLAGS_pallas_alias_selfcheck": selfcheck})
        assert fwd <= ceiling[0] and bwd <= ceiling[1], (fwd, bwd)


# -- a sliding window (PR 35): the band's grid, its two edges, the planes of
# the backward's accumulators ----------------------------------------------

def _dense_window(q, k, v, window, seg=None):
    """Dense masked softmax attention of keys 0 <= t - s < window, in
    plain jnp: neither of the module's two paths."""
    b, s, h, d = q.shape
    grp = h // k.shape[2]
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    mask = ((ahead >= 0) & (ahead < window))[None]
    if seg is not None:
        mask = mask & (seg[:, :, None] == seg[:, None, :])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, grp, 2),
                        precision=HP) / d ** 0.5
    prob = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", prob, jnp.repeat(v, grp, 2),
                      precision=HP)


class TestSlidingWindow:
    # tiles of 128 in a sequence of 512: a window smaller than the tile,
    # equal to it, no multiple of it, wider than the sequence, and of one
    @pytest.mark.parametrize("window", [64, 128, 200, 384, 600, 1])
    @pytest.mark.parametrize("group,masks", [(1, "plain"), (4, "plain"),
                                             (2, "segments")])
    def test_kernel_xla_and_dense_agree(self, window, group, masks):
        q, k, v = _rand(1, 512, group, 1, 64, seed=21)
        seg = _segments(1, 512, 3, seed=22) if masks == "segments" else None
        got = _assert_parity(q, k, v, causal=True, window=window,
                             segment_ids=seg, block_q=128, block_k=128)
        want = _grads(lambda q, k, v: _dense_window(q, k, v, window, seg),
                      q, k, v)
        for a, b in zip(got, want):
            assert float(jnp.max(jnp.abs(a - b))) < 5e-4

    def test_two_batch_rows_and_kv_heads(self):
        q, k, v = _rand(2, 256, 4, 2, 32, seed=23)
        _assert_parity(q, k, v, causal=True, window=100, block_q=128,
                       block_k=128)

    def test_a_window_of_the_whole_sequence_is_causal_attention(self):
        q, k, v = _rand(1, 256, 2, 1, 64, seed=24)
        got = _grads(lambda q, k, v: sa.splash_attention(
            q, k, v, window=256, interpret=True, block_q=128, block_k=128),
            q, k, v)
        want = _grads(lambda q, k, v: sa.splash_attention(
            q, k, v, interpret=True, block_q=128, block_k=128), q, k, v)
        for a, b in zip(got, want):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-5

    @pytest.mark.parametrize("masks", ["plain", "segments"])
    def test_no_window_is_bit_equal_to_the_call_without_the_argument(
            self, masks):
        q, k, v = _rand(1, 512, 2, 1, 64, seed=25)
        kw = ({"segment_ids": _segments(1, 512, 3, seed=26)}
              if masks == "segments" else {})
        for blocks in ({}, {"block_q": 128, "block_k": 128}):
            a = _grads(lambda q, k, v: sa.splash_attention(
                q, k, v, interpret=True, window=None, **blocks, **kw),
                q, k, v)
            b = _grads(lambda q, k, v: sa.splash_attention(
                q, k, v, interpret=True, **blocks, **kw), q, k, v)
            for x, y in zip(a, b):
                assert np.asarray(x).tobytes() == np.asarray(y).tobytes()

    def test_no_window_traces_the_kernels_the_parent_traced(self):
        """`window=None` adds no keyword to the kernels' partials and no
        equation to their bodies (the three cells' programs must not
        change)."""
        q, k, v = (jax.ShapeDtypeStruct((1, 512, 2, 64), jnp.float32),) * 3

        def run(window):
            return str(jax.make_jaxpr(lambda q, k, v: jax.vjp(
                lambda q, k, v: sa.splash_attention(
                    q, k, v, interpret=True, block_q=128, block_k=128,
                    **window), q, k, v)[1](q))(q, k, v))

        assert run({"window": None}) == run({})
        assert "window" not in run({})

    def test_computed_pairs_by_hand(self):
        tile = 1024 * 1024
        # seq 8192 on 1024-key tiles: q tile 0 meets one k tile, the seven
        # after it two each, 15 of the causal grid's 36
        assert sa.computed_pairs(8192, 1024, 1024, window=1024) == 15 * tile
        assert sa.computed_pairs(8192, 1024, 1024) == 36 * tile
        # on 512-key tiles 1 + 2 + 14 x 3 = 45 of 136
        assert sa.computed_pairs(8192, 512, 512, window=1024) == 45 * 512 ** 2
        assert sa.computed_pairs(8192, 512, 512) == 136 * 512 ** 2
        # the band itself: sum_t min(t + 1, 1024)
        band = 1024 * 1025 // 2 + (8192 - 1024) * 1024
        assert band == 7_864_832
        assert sa.computed_pairs(8192, 1024, 1024, window=1024) / band < 2.01
        # a window of one key: the diagonal tiles alone; of one more than a
        # tile: three tiles a row; wider than the sequence: the causal grid
        assert sa.computed_pairs(512, 128, 128, window=1) == 4 * 128 ** 2
        assert sa.computed_pairs(512, 128, 128, window=130) == 9 * 128 ** 2
        assert sa.computed_pairs(512, 128, 128, window=129) == 7 * 128 ** 2
        assert sa.computed_pairs(512, 128, 128, window=4096) == \
            sa.computed_pairs(512, 128, 128)
        # the caller's rule for a windowed call's tile
        assert sa.computed_pairs(8192, window=1024) == sa.computed_pairs(
            8192, *(sa._window_block(8192),) * 2, window=1024)

    def test_the_grid_is_the_bands(self):
        """A windowed call's grid has `_band_steps` steps along k, not the
        causal grid's with steps skipped; the backward's accumulators one
        plane a step."""
        q, k, v = (jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.float32),) * 3
        grids = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    grids.append(tuple(eqn.params["grid_mapping"].grid))
                for sub in _eqn_jaxprs(eqn):
                    walk(sub)

        from paddle_tpu.utils import flags
        selfcheck = flags.get_flag("FLAGS_pallas_alias_selfcheck")
        flags.set_flags({"FLAGS_pallas_alias_selfcheck": False})
        try:
            walk(jax.make_jaxpr(lambda q, k, v: jax.vjp(
                lambda q, k, v: sa.splash_attention(
                    q, k, v, window=256, block_q=128, block_k=128,
                    use_kernel=True, interpret=False), q, k, v)[1](q))(
                        q, k, v).jaxpr)
        finally:
            flags.set_flags({"FLAGS_pallas_alias_selfcheck": selfcheck})
        assert sa._band_steps(256, 128, 8) == 3
        assert grids == [(2, 8, 3), (2, 8, 3)]      # (b kv heads, q tiles, band)

    @pytest.mark.parametrize("window,block,nqs", [
        (1024, 1024, 8), (1024, 512, 16), (200, 128, 4), (1, 128, 4),
        (600, 128, 4)])
    def test_a_plane_meets_a_k_block_once_a_head(self, window, block, nqs):
        """What the chip's self-check refused in this PR's first kernel:
        bands begun at tile 0 put the first q tiles' step 0 on ONE block
        of one plane of the backward's accumulators, a grid step or two
        apart. Ended on the q tile's own tile, no (plane, block) is met
        twice by a head, and every tile met lies in the band."""
        steps = sa._band_steps(window, block, nqs)
        seen = set()
        for i in range(nqs):
            met = [sa._band_tile(i, nqs, steps, j) for j in range(steps)]
            assert met[-1] == i and met == sorted(met)
            for j, t in enumerate(met):
                if t >= 0:
                    assert (j, t) not in seen
                    seen.add((j, t))
                    assert (i - t - 1) * block + 1 < window or t == i
        # and every tile the band touches is met
        for i in range(nqs):
            first = max(0, (i * block - (window - 1)) // block)
            assert {t for j in range(steps) if (t := sa._band_tile(
                i, nqs, steps, j)) >= 0} >= set(range(first, i + 1))

    def test_a_window_takes_causal_attention_and_no_selection(self):
        q, k, v = _rand(1, 256, 2, 1, 32)
        with pytest.raises(ValueError, match="window"):
            sa.splash_attention(q, k, v, window=64, interpret=True,
                                selection=jnp.ones((1, 256, 256), jnp.int8))
        with pytest.raises(ValueError, match="window"):
            sa.splash_attention(q, k, v, window=64, causal=False,
                                interpret=True)
        with pytest.raises(ValueError, match="window"):
            sa.splash_attention(q, k, v, window=0, interpret=True)


class TestFunctionalRouting:
    def test_sdpa_segments_route_to_splash(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        rng = np.random.default_rng(7)
        qn = rng.standard_normal((1, 256, 2, 32)).astype(np.float32)
        seg = _segments(1, 256, 2, seed=7)
        q = paddle.to_tensor(qn)
        out = F.scaled_dot_product_attention(
            q, q, q, is_causal=True, segment_ids=paddle.to_tensor(
                np.asarray(seg)))
        want = _ref(jnp.asarray(qn), jnp.asarray(qn), jnp.asarray(qn),
                    True, 1.0 / 32 ** 0.5, seg=seg)
        np.testing.assert_allclose(np.asarray(out._data),
                                   np.asarray(want), atol=3e-5)

    def test_sdpa_segments_with_dropout_use_dense_mask(self):
        """Dropout forces the dense segment-mask path (splash has no
        dropout plumbing) — output rows still never cross a segment."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        rng = np.random.default_rng(8)
        s = 64   # any length: the dense path has no tiling constraint
        qn = rng.standard_normal((1, s, 2, 16)).astype(np.float32)
        vn = np.zeros((1, s, 2, 16), np.float32)
        vn[0, :32] = 1.0    # doc 0's values are 1, doc 1's are 0
        seg = jnp.asarray(np.repeat([0, 1], s // 2)[None], jnp.int32)
        q = paddle.to_tensor(qn)
        v = paddle.to_tensor(vn)
        out = F.scaled_dot_product_attention(
            q, q, v, is_causal=True, dropout_p=0.5, training=True,
            segment_ids=paddle.to_tensor(np.asarray(seg)))
        o = np.asarray(out._data)
        # doc-1 queries can only see doc-1 keys, whose values are all 0
        assert np.abs(o[0, 32:]).max() == 0.0

    def test_segment_context_threads_through_model(self):
        """GPTModel.forward publishes segment_ids to every attention
        layer: packed forward == per-document forward."""
        import paddle_tpu as paddle
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_attention_heads=2,
                        max_position_embeddings=32)
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        m.eval()
        rng = np.random.default_rng(9)
        ids = rng.integers(0, 97, (1, 32))
        seg = np.repeat([0, 1], 16)[None]
        packed = m(paddle.to_tensor(ids, dtype="int64"),
                   segment_ids=paddle.to_tensor(seg, dtype="int32"))
        parts = []
        for sl in (slice(0, 16), slice(16, 32)):
            # per-doc forward at positions matching the packed layout
            pos = paddle.to_tensor(np.arange(32)[None, sl],
                                   dtype="int64")
            parts.append(np.asarray(m(
                paddle.to_tensor(ids[:, sl], dtype="int64"),
                position_ids=pos)._data))
        want = np.concatenate(parts, axis=1)
        np.testing.assert_allclose(np.asarray(packed._data), want,
                                   atol=2e-4)

    def test_sdpa_rejects_mask_plus_segments(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        q = paddle.to_tensor(np.zeros((1, 16, 2, 8), np.float32))
        mask = paddle.to_tensor(np.zeros((1, 1, 16, 16), np.float32))
        seg = paddle.to_tensor(np.zeros((1, 16), np.int32))
        with pytest.raises(ValueError, match="segment_ids"):
            F.scaled_dot_product_attention(q, q, q, attn_mask=mask,
                                           segment_ids=seg)
