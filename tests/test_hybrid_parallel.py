"""Full hybrid parallelism (ISSUE 8): dp×mp Megatron tensor sharding +
the dp×pp ring pipeline over the sharded fused scan, planner-picked
layouts. Runs on the conftest 8-virtual-CPU-device host mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as popt
from paddle_tpu.distributed import env as denv
from paddle_tpu.jit import (
    PipelineScanTrainStep, ShardedFusedScanTrainStep, TrainStep,
    select_train_step,
)
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
)

TINY = dict(vocab_size=96, hidden_size=32, num_layers=4,
            num_attention_heads=2, max_position_embeddings=16,
            hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
N_DEV = 8
LOSS_TOL = 5e-4          # the sharded-scan parity bar (test_sharded_scan.py)
PARAM_REL_TOL = 5e-3
PARAM_ABS = 5e-4


@pytest.fixture(autouse=True)
def _clean_mesh():
    denv.reset()
    yield
    denv.reset()


def _devs(n=N_DEV):
    devs = jax.devices("cpu")[:n]
    if len(devs) < n:
        pytest.skip(f"needs {n} virtual cpu devices")
    return devs


def _batch(bs=N_DEV, seq=12, vocab=96, seed=0):
    rng = np.random.default_rng(seed)
    return (paddle.to_tensor(rng.integers(0, vocab, (bs, seq)),
                             dtype="int64"),
            paddle.to_tensor(rng.integers(0, vocab, (bs, seq)),
                             dtype="int64"))


def _build(step_kind, mesh=None, clip=True, steps=3, lr=1e-2,
           cfg_over=None, **kw):
    cfg = GPTConfig(**{**TINY, **(cfg_over or {})}, scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = popt.AdamW(learning_rate=lr, parameters=model.parameters(),
                     grad_clip=(nn.ClipGradByGlobalNorm(0.05) if clip
                                else None))
    if step_kind == "eager":
        step = TrainStep(model, lambda m, a, b: crit(m(a), b), opt)
    elif step_kind == "pipe":
        step = PipelineScanTrainStep(model, opt, criterion=crit,
                                     mesh=mesh, **kw)
    else:
        step = ShardedFusedScanTrainStep(model, opt, criterion=crit,
                                         mesh=mesh, **kw)
    ids, labels = _batch(vocab=cfg.vocab_size)
    losses = [float(step(ids, labels)) for _ in range(steps)]
    return losses, model, step


def _param_rel(m1, m2):
    """Worst allclose-style violation over all params: |a-b| measured
    against rtol*|a| + atol (atol 5e-5 — Adam's sqrt(v) amplifies
    float-noise-level grad differences on near-zero params into large
    RELATIVE drift that says nothing about parity)."""
    worst = 0.0
    for (_, p1), (_, p2) in zip(m1.named_parameters(),
                                m2.named_parameters()):
        a = np.asarray(p1._data, np.float32)
        b = np.asarray(p2._data, np.float32)
        denom = PARAM_REL_TOL * np.abs(a) + 5e-5
        worst = max(worst, float(np.max(np.abs(a - b) / denom)))
    return worst * PARAM_REL_TOL   # scaled so the threshold reads as rtol


def _ldiff(a, b):
    return max(abs(x - y) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# dp×mp: Megatron tensor sharding inside the scan
# ---------------------------------------------------------------------------

def test_dpmp_parity_vs_dp_only_and_eager():
    """dp4×mp2 loss/param trajectories match the dp-only sharded scan
    and the eager TrainStep within LOSS_TOL / PARAM_TOL, with the
    global-norm clip ACTIVE (acceptance bar of ISSUE 8)."""
    devs = _devs()
    from jax.sharding import Mesh

    mesh_dp = Mesh(np.asarray(devs), ("sharding",))
    denv.set_mesh(mesh_dp)
    eager, m_e, _ = _build("eager")
    noclip, _, _ = _build("eager", clip=False)
    assert _ldiff(eager, noclip) > 10 * LOSS_TOL   # clip not inert
    dp_only, m_dp, _ = _build("sharded", mesh=mesh_dp, axis="sharding")

    mesh_mp = Mesh(np.asarray(devs).reshape(4, 2), ("dp", "mp"))
    denv.set_mesh(mesh_mp)
    dpmp, m_mp, step = _build("sharded", mesh=mesh_mp, axis="dp",
                              mp_axis="mp")
    assert step._axes == ("dp", "mp") and step._degree == 8
    assert _ldiff(dpmp, eager) < LOSS_TOL
    assert _ldiff(dpmp, dp_only) < LOSS_TOL
    assert _param_rel(m_e, m_mp) < PARAM_REL_TOL
    assert _param_rel(m_dp, m_mp) < PARAM_REL_TOL
    # optimizer state sharded 1/(dp*mp) on live shapes
    opt_flat = step._opt._accumulators["moment1"]["__scan_shard_s0__"]
    assert len(opt_flat.addressable_shards) == 8
    assert opt_flat.addressable_shards[0].data.shape[-1] * 8 \
        == opt_flat.shape[-1]


def test_dpmp_untied_vocab_parallel_head():
    """tie_word_embeddings=False routes the separate [H, V] lm_head
    through the vocab-parallel sharded CE (transposed row shard)."""
    devs = _devs()
    from jax.sharding import Mesh

    over = dict(tie_word_embeddings=False)
    mesh_dp = Mesh(np.asarray(devs), ("sharding",))
    denv.set_mesh(mesh_dp)
    eager, m_e, _ = _build("eager", cfg_over=over)
    mesh_mp = Mesh(np.asarray(devs).reshape(4, 2), ("dp", "mp"))
    denv.set_mesh(mesh_mp)
    dpmp, m_mp, _ = _build("sharded", mesh=mesh_mp, axis="dp",
                           mp_axis="mp", cfg_over=over)
    assert _ldiff(dpmp, eager) < LOSS_TOL
    assert _param_rel(m_e, m_mp) < PARAM_REL_TOL


def test_sharded_fused_ce_matches_full_fused_ce():
    """The vocab-parallel sharded fused CE == the full vocab-tiled CE,
    losses and BOTH grads — including the padded-tile case where padded
    columns alias the next rank's global vocab ids (the regression that
    motivated the in-kernel valid mask)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.ops.pallas.fused_cross_entropy import (
        fused_cross_entropy, sharded_fused_cross_entropy,
    )

    devs = _devs(4)
    mesh = Mesh(np.asarray(devs), ("mp",))
    rng = np.random.default_rng(0)
    N, H, V, MP = 24, 16, 96, 4          # vloc=24 pads to the 128 tile
    h = jnp.asarray(rng.standard_normal((N, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, H)) * 0.1, jnp.float32)
    lbl = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32).at[3].set(
        -100)
    vloc = V // MP

    def run(h, w, lbl):
        def body(h, w, lbl):
            r = jax.lax.axis_index("mp")
            wl = jax.lax.dynamic_slice_in_dim(w, r * vloc, vloc, 0)

            def f(h, wl):
                losses = sharded_fused_cross_entropy(h, wl, lbl,
                                                     r * vloc, "mp")
                m = (lbl != -100).astype(jnp.float32)
                return jnp.sum(losses * m) / jnp.maximum(jnp.sum(m),
                                                         1.0)

            loss, vjpf = jax.vjp(f, h, wl)
            dh, dwl = vjpf(jnp.float32(1.0))
            dh_sum = jax.lax.psum(dh, "mp") / MP
            dw_full = jax.lax.psum(jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(w), dwl, r * vloc, 0), "mp") / MP
            return loss, dh_sum, dw_full

        return jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                             out_specs=(P(), P(), P()),
                             check_vma=False)(h, w, lbl)

    loss_s, dh_s, dw_s = jax.jit(run)(h, w, lbl)

    def ref(h, w, lbl):
        losses = fused_cross_entropy(h, w, lbl)
        m = (lbl != -100).astype(jnp.float32)
        return jnp.sum(losses * m) / jnp.maximum(jnp.sum(m), 1.0)

    loss_r, (dh_r, dw_r) = jax.value_and_grad(ref, (0, 1))(h, w, lbl)
    assert abs(float(loss_s) - float(loss_r)) < 1e-6
    assert float(jnp.max(jnp.abs(dh_s - dh_r))) < 1e-6
    assert float(jnp.max(jnp.abs(dw_s - dw_r))) < 1e-6


def test_mp_hlo_grads_reduced_in_scan_no_full_gather():
    """HLO receipt for the acceptance criterion: the dp×mp program's
    grad reduce-scatters run over the FLATTENED dp+mp product (the mp
    assembly rides the data-parallel scatter — no separate mp grad
    all-reduce/gather), the in-block mp psums are all-reduces on the mp
    axis alone, and every all-gather is the update scan's param gather
    over dp+mp — there is NO mp-only or unclassified gather that a
    full-gradient assembly would show."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "hlo_overlap", os.path.join(root, "tools", "hlo_overlap.py"))
    hlo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(hlo)

    devs = _devs()
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devs).reshape(4, 2), ("dp", "mp"))
    denv.set_mesh(mesh)
    cfg = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-2, parameters=model.parameters(),
                     grad_clip=nn.ClipGradByGlobalNorm(0.05))
    step = ShardedFusedScanTrainStep(model, opt,
                                     criterion=GPTPretrainingCriterion(),
                                     mesh=mesh, axis="dp", mp_axis="mp")
    step.ensure_built()
    state = step._extract_state()
    ids = jnp.zeros((8, 12), jnp.int32)
    text = step._jitted.lower(state, jnp.float32(1e-2), ids, ids,
                              None).compile().as_text()
    v = hlo.analyze(text, axis_degrees={"dp": 4, "mp": 2})
    per = v["per_axis_counts"]
    # a layer's two row-parallel psums (attention out, mlp fc2) are
    # all-reduces on the mp axis ALONE. A scan body holds them once
    # however many layers run it, and how many bodies the compiler keeps
    # (forward, recompute, backward; unrolled or rolled) is its choice,
    # so not a count per layer
    assert per.get("mp", {}).get("all-reduce", 0) >= 2, per
    assert set(per.get("mp", {})) == {"all-reduce"}, per
    assert per.get("dp+mp", {}).get("reduce-scatter", 0) >= 1, per
    # no grad traffic outside the classified patterns, and no gathers
    # anywhere but the flattened dp+mp param gather
    assert "other" not in per, per
    for label, kinds in per.items():
        if label != "dp+mp":
            assert "all-gather" not in kinds, per
    assert v["counts"].get("reduce-scatter", 0) == per["dp+mp"][
        "reduce-scatter"]


def test_mp_rejects_attention_dropout_and_custom_criterion():
    devs = _devs()
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devs).reshape(4, 2), ("dp", "mp"))
    denv.set_mesh(mesh)
    cfg = GPTConfig(**{**TINY, "attention_dropout_prob": 0.1},
                    scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-2, parameters=model.parameters())
    with pytest.raises(ValueError, match="attention dropout"):
        ShardedFusedScanTrainStep(model, opt, mesh=mesh, axis="dp",
                                  mp_axis="mp")
    cfg2 = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    model2 = GPTForCausalLM(cfg2)
    opt2 = popt.AdamW(learning_rate=1e-2,
                      parameters=model2.parameters())
    with pytest.raises(ValueError, match="vocab-parallel"):
        ShardedFusedScanTrainStep(model2, opt2, mesh=mesh, axis="dp",
                                  mp_axis="mp",
                                  criterion=lambda a, b: a.sum())


# ---------------------------------------------------------------------------
# dp×pp: the ring pipeline schedule
# ---------------------------------------------------------------------------

def test_pipeline_parity_dp2pp2():
    """dp2×pp2 ring pipeline matches the eager TrainStep and the
    dp-only sharded scan within LOSS_TOL / PARAM_TOL."""
    devs = _devs()
    from jax.sharding import Mesh

    mesh_dp = Mesh(np.asarray(devs), ("sharding",))
    denv.set_mesh(mesh_dp)
    eager, m_e, _ = _build("eager")
    mesh_pp = denv.build_mesh({"dp": 2, "pp": 2}, devices=devs[:4])
    denv.set_mesh(mesh_pp)
    pp, m_pp, step = _build("pipe", mesh=mesh_pp, num_micro=2)
    assert set(step._axes) == {"dp", "pp"}
    assert _ldiff(pp, eager) < LOSS_TOL
    assert _param_rel(m_e, m_pp) < PARAM_REL_TOL
    stats = step.schedule_stats()
    assert stats["pp"] == 2 and stats["virtual_stages_per_rank"] == 2
    assert stats["bubble_ratio"] == pytest.approx(1 / 3)


def test_pipeline_microbatch_grads_match_accumulated_single_stage():
    """The ring schedule's micro-batched gradient == the sequential
    single-stage accumulation of the same micro-batches (the
    TrainStep(accum_steps=k) contract): the degree-1 pp ring IS that
    accumulation loop. The LOSS is bit-identical; gradients agree to
    float-ulp level (<= 1e-7 — XLA fuses the ring and the sequential
    program differently, so last-ulp equality across the two compiled
    programs is not guaranteed; the schedule itself contributes exact
    zeros for bubble ticks and exact ppermute transport)."""
    ids, labels = _batch()

    def probe(pp, ndev):
        cfg = GPTConfig(**TINY, scan_layers=True)
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=model.parameters())
        mesh = denv.build_mesh({"dp": ndev // pp, "pp": pp},
                               devices=_devs(ndev))
        denv.set_mesh(mesh)
        step = PipelineScanTrainStep(model, opt,
                                     criterion=GPTPretrainingCriterion(),
                                     mesh=mesh, num_micro=4)
        loss, G, o = step.grads_probe(ids, labels)
        return (float(loss), [np.asarray(g) for g in G],
                [np.asarray(g) for g in o])

    l_ring, G_ring, o_ring = probe(2, 2)     # dp1×pp2 ring
    l_seq, G_seq, o_seq = probe(1, 1)        # dp1×pp1: sequential accum
    assert l_ring == l_seq                   # bit-identical loss
    for a, b in zip(G_ring + o_ring, G_seq + o_seq):
        assert float(np.max(np.abs(a - b))) <= 1e-7


def test_pipeline_rejects_bad_configs():
    devs = _devs()
    mesh = denv.build_mesh({"dp": 2, "pp": 2}, devices=devs[:4])
    denv.set_mesh(mesh)
    cfg = GPTConfig(**{**TINY, "hidden_dropout_prob": 0.1},
                    scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-2, parameters=model.parameters())
    # dropout under pp is LEGAL since ISSUE 11 (per-(micro, stage) PRNG
    # offsets) — construction must succeed; the determinism/grad tests
    # live in tests/test_sharded_storage.py
    PipelineScanTrainStep(model, opt, mesh=mesh, num_micro=2)
    mesh3 = denv.build_mesh({"dp": 2, "pp": 3}, devices=devs[:6])
    denv.set_mesh(mesh3)
    cfg2 = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    model2 = GPTForCausalLM(cfg2)
    opt2 = popt.AdamW(learning_rate=1e-2,
                      parameters=model2.parameters())
    with pytest.raises(ValueError, match="divisible by pp"):
        PipelineScanTrainStep(model2, opt2, mesh=mesh3, num_micro=2)


@pytest.mark.slow
def test_full_3d_hybrid_dp_mp_pp_parity():
    """The composition: dp2×mp2×pp2 (all three axes live) still matches
    the eager trajectory — the mp block slicing rides chunk_apply inside
    the pp ring, and grads scatter over the flattened 3-axis product."""
    devs = _devs()
    from jax.sharding import Mesh

    mesh_dp = Mesh(np.asarray(devs), ("sharding",))
    denv.set_mesh(mesh_dp)
    eager, m_e, _ = _build("eager")
    mesh = denv.build_mesh({"dp": 2, "mp": 2, "pp": 2}, devices=devs)
    denv.set_mesh(mesh)
    tri, m_t, step = _build("pipe", mesh=mesh, axis="dp", mp_axis="mp",
                            pp_axis="pp", num_micro=2)
    assert step._degree == 8 and len(step._axes) == 3
    assert _ldiff(tri, eager) < LOSS_TOL
    assert _param_rel(m_e, m_t) < PARAM_REL_TOL


# ---------------------------------------------------------------------------
# compile discipline
# ---------------------------------------------------------------------------

def test_one_compile_per_mesh_signature():
    """Repeated steps on one mesh signature reuse ONE executable for
    both hybrid classes (the retrace probes of the acceptance bar)."""
    devs = _devs()
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devs).reshape(4, 2), ("dp", "mp"))
    denv.set_mesh(mesh)
    _, _, step = _build("sharded", mesh=mesh, axis="dp", mp_axis="mp",
                        steps=3)
    assert step._jitted._cache_size() == 1
    mesh_pp = denv.build_mesh({"dp": 2, "pp": 2}, devices=devs[:4])
    denv.set_mesh(mesh_pp)
    _, _, pstep = _build("pipe", mesh=mesh_pp, num_micro=2, steps=3)
    assert pstep._jitted._cache_size() == 1


# ---------------------------------------------------------------------------
# planner-picked layouts
# ---------------------------------------------------------------------------

def _spec(vocab=96, batch=8):
    from paddle_tpu.distributed.auto_tuner import spec_of_model

    cfg = GPTConfig(**{**TINY, "vocab_size": vocab}, scan_layers=True)
    return spec_of_model(cfg, global_batch=batch, seq_len=12)


def test_planner_picks_pruned_feasible_layout():
    """pick_layout returns a feasible (pruning-clean) layout covering
    all devices, ranked by the calibrated cost model — and prefers pure
    dp when collectives are expensive relative to compute (the host-
    mesh regime), mp when intra-chip links are effectively free."""
    from paddle_tpu.distributed.auto_tuner import pick_layout
    from paddle_tpu.distributed.auto_tuner.prune import prune_candidates

    slow_links = {"coll_lat_us": 500.0, "ici_gbps": 1e9,
                  "pp_tick_ms": 1.0, "peak_flops": 1e12}
    dec = pick_layout(_spec(), 8, backend=slow_links, env={})
    c = dec["candidate"]
    assert c.degree == 8 and c.pruned_reason is None
    assert prune_candidates([c], _spec(), 16.0)[0].pruned_reason is None
    assert dec["source"] == "planner" and len(dec["ranking"]) >= 3
    assert (c.dp, c.mp, c.pp) == (8, 1, 1)

    fast_links = {"coll_lat_us": 0.1, "ici_gbps": 4e11,
                  "pp_tick_ms": 1e-4, "peak_flops": 1e12}
    # a model too big per-chip forces splitting; with free links the
    # planner should reach for model parallelism, and the pick must
    # still be feasible under the HBM estimate it was pruned with
    big = _spec(vocab=96, batch=32)
    big.params = int(4e9)
    dec2 = pick_layout(big, 8, hbm_gb=16.0, backend=fast_links, env={})
    c2 = dec2["candidate"]
    assert c2.pruned_reason is None and c2.degree == 8
    assert c2.mp > 1 or c2.pp > 1 or c2.sharding_stage >= 1
    assert c2.estimated_mem_gb <= 16.0


def test_planner_env_override_and_infeasible_rejection():
    from paddle_tpu.distributed.auto_tuner import pick_layout
    from paddle_tpu.distributed.auto_tuner.select import LAYOUT_ENV

    dec = pick_layout(_spec(), 8, backend={"peak_flops": 1e12},
                      env={LAYOUT_ENV: "dp=4,mp=2"})
    c = dec["candidate"]
    assert (c.dp, c.mp, c.pp) == (4, 2, 1) and dec["source"] == "env"
    # infeasible forced layout fails loudly: 96 heads%5 etc — use mp=5
    with pytest.raises(ValueError, match="infeasible"):
        pick_layout(_spec(), 10, backend={},
                    env={LAYOUT_ENV: "dp=2,mp=5"})


def test_select_train_step_dispatch_and_auto():
    """Explicit meshes dispatch by active axes; auto=True plans, builds
    the mesh, and returns a runnable step carrying the decision."""
    devs = _devs()
    mesh_mp = denv.build_mesh({"dp": 4, "mp": 2}, devices=devs)
    denv.set_mesh(mesh_mp)
    cfg = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-2, parameters=model.parameters())
    crit = GPTPretrainingCriterion()
    step = select_train_step(model, opt, criterion=crit, mesh=mesh_mp)
    assert isinstance(step, ShardedFusedScanTrainStep)
    assert step._axes == ("dp", "mp")

    denv.reset()
    mesh_pp = denv.build_mesh({"dp": 2, "pp": 2}, devices=devs[:4])
    denv.set_mesh(mesh_pp)
    paddle.seed(0)
    model2 = GPTForCausalLM(cfg)
    opt2 = popt.AdamW(learning_rate=1e-2,
                      parameters=model2.parameters())
    step2 = select_train_step(model2, opt2, criterion=crit,
                              mesh=mesh_pp, num_micro=2)
    assert isinstance(step2, PipelineScanTrainStep)

    denv.reset()
    paddle.seed(0)
    model3 = GPTForCausalLM(cfg)
    opt3 = popt.AdamW(learning_rate=1e-2,
                      parameters=model3.parameters())
    step3 = select_train_step(model3, opt3, criterion=crit, auto=True,
                              global_batch=8)
    assert step3.layout_decision["candidate"].degree >= 1
    ids, labels = _batch()
    assert np.isfinite(float(step3(ids, labels)))


# ---------------------------------------------------------------------------
# fleet end-to-end wiring
# ---------------------------------------------------------------------------

def test_fleet_hybrid_end_to_end():
    """fleet.init(strategy) with mp_degree / pp_degree > 1 reaches the
    hybrid steps through distributed_model(...).train_step(...)."""
    import paddle_tpu.distributed.fleet as fleet

    _devs()
    ids, labels = _batch()
    crit = GPTPretrainingCriterion()
    cfg = GPTConfig(**TINY, scan_layers=True)

    strat = fleet.DistributedStrategy()
    strat.hybrid_configs.update({"dp_degree": 4, "mp_degree": 2})
    fleet.init(is_collective=True, strategy=strat)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-2, parameters=model.parameters())
    dm = fleet.distributed_model(model)
    step = dm.train_step(opt, criterion=crit)
    assert isinstance(step, ShardedFusedScanTrainStep)
    assert step._axes == ("dp", "mp")
    assert np.isfinite(float(step(ids, labels)))

    denv.reset()
    strat2 = fleet.DistributedStrategy()
    strat2.hybrid_configs.update({"dp_degree": 2, "pp_degree": 2})
    strat2.pipeline_configs = {"accumulate_steps": 2}
    fleet.init(is_collective=True, strategy=strat2)
    paddle.seed(0)
    model2 = GPTForCausalLM(cfg)
    opt2 = popt.AdamW(learning_rate=1e-2,
                      parameters=model2.parameters())
    dm2 = fleet.distributed_model(model2)
    assert type(dm2).__name__ == "HybridParallel"
    step2 = dm2.train_step(opt2, criterion=crit)
    assert isinstance(step2, PipelineScanTrainStep)
    assert step2._num_micro == 2          # strategy accumulate_steps
    assert np.isfinite(float(step2(ids, labels)))
