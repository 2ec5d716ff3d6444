"""Sharded parameter storage (ISSUE 11, jit/sharded_scan.py): params
stored as 1/N flat bucket shards, all-gathered on use inside the scans
(double-buffered prefetch), written back as shards by the update scan —
plus the quantized multi-axis collective legs, dropout under pp, and
the resharding checkpoint restore. Runs on the conftest
8-virtual-CPU-device host mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as popt
from paddle_tpu.distributed import env as denv
from paddle_tpu.jit import FusedScanTrainStep, ShardedFusedScanTrainStep
from paddle_tpu.jit.pipeline_step import PipelineScanTrainStep
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
)

TINY = dict(vocab_size=92, hidden_size=36, num_layers=2,
            num_attention_heads=2, max_position_embeddings=16,
            hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
N_DEV = 8


@pytest.fixture
def mesh():
    devs = jax.devices("cpu")[:N_DEV]
    if len(devs) < N_DEV:
        pytest.skip(f"needs {N_DEV} virtual cpu devices")
    from jax.sharding import Mesh

    denv.reset()
    m = Mesh(np.asarray(devs), ("sharding",))
    denv.set_mesh(m)
    yield m
    denv.reset()


def _batch(bs=N_DEV, seq=12, vocab=92, seed=0):
    rng = np.random.default_rng(seed)
    return (paddle.to_tensor(rng.integers(0, vocab, (bs, seq)),
                             dtype="int64"),
            paddle.to_tensor(rng.integers(0, vocab, (bs, seq)),
                             dtype="int64"))


def _build(mesh, storage, steps=3, lr=1e-2, clip=True, cfg_over=None,
           **kw):
    cfg = GPTConfig(**{**TINY, **(cfg_over or {})}, scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(
        learning_rate=lr, parameters=model.parameters(),
        grad_clip=nn.ClipGradByGlobalNorm(0.05) if clip else None)
    step = ShardedFusedScanTrainStep(
        model, opt, criterion=GPTPretrainingCriterion(), mesh=mesh,
        axis="sharding", param_storage=storage, **kw)
    ids, labels = _batch(vocab=cfg.vocab_size)
    losses = [float(step(ids, labels)) for _ in range(steps)]
    return losses, model, opt, step


def test_bit_parity_dp_sharded_vs_replicated(mesh):
    """The acceptance core: same mesh, same seed — the sharded-storage
    step's losses AND final params are bit-identical to the replicated
    step (shards hold exactly the bytes the stacks would)."""
    rep, m_rep, _, _ = _build(mesh, "replicated")
    sh, m_sh, _, st = _build(mesh, "sharded")
    assert rep == sh
    for (n1, p1), (_, p2) in zip(m_rep.named_parameters(),
                                 m_sh.named_parameters()):
        assert np.array_equal(np.asarray(p1._data),
                              np.asarray(p2._data)), n1
    assert st._jitted._cache_size() == 1


def test_param_shards_live_one_over_n(mesh):
    """1/N param-shard shape asserts on LIVE addressable shards, and
    no full-sized trainable `_data` resident between steps (the lazy
    sentinel is in the slot until someone reads)."""
    from paddle_tpu.jit.sharded_scan import _STALE, _data_slot

    _, model, _, step = _build(mesh, "sharded", steps=2)
    for grp in ("s", "o"):
        for arr in step._param_shards[grp]:
            shards = arr.addressable_shards
            assert len(shards) == N_DEV
            assert shards[0].data.shape[-1] * N_DEV == arr.shape[-1]
    slot = _data_slot()
    stale = [slot.__get__(p) is _STALE
             for _, p in model.named_parameters() if p.trainable]
    assert all(stale)            # nothing materialized between steps
    # a read gathers the real values back (lazy materialization)
    w = model.gpt.wte.weight
    assert np.isfinite(np.asarray(w._data)).all()
    assert tuple(w._data.shape) == tuple(w.shape)


def test_external_write_repacks_into_shards(mesh):
    """`p._data = ...` between steps (checkpoint restore, test poking)
    must flow back into the authoritative shards at the next step."""
    _, model, _, step = _build(mesh, "sharded", steps=1)
    w = model.gpt.wte.weight
    marked = w._data.at[3].set(7.0)
    w._data = marked
    assert step._dirty_param_buckets      # write marked the bucket
    ids, labels = _batch()
    float(step(ids, labels))              # repack + train
    # the update consumed the written value: row 3 moved FROM 7.0
    # (trained), not from the stale pre-write value
    row = np.asarray(w._data)[3]
    assert not np.array_equal(row, np.asarray(marked)[3])
    assert np.abs(row - 7.0).max() < 1.0  # one step of lr=1e-2 drift


def test_rebuild_step_on_same_model_takes_over_shards(mesh):
    """Rebuilding a train step on the same model (new optimizer,
    phase-2 fine-tune) must work: the new step materializes current
    values from the old step's shards and takes over storage — review
    finding on the original hard error."""
    _, model, _, step1 = _build(mesh, "sharded", steps=2)
    w_after = np.asarray(model.gpt.wte.weight._data).copy()
    del step1
    opt2 = popt.AdamW(learning_rate=1e-2,
                      parameters=model.parameters())
    step2 = ShardedFusedScanTrainStep(
        model, opt2, criterion=GPTPretrainingCriterion(), mesh=mesh,
        axis="sharding", param_storage="sharded")
    step2.ensure_built()
    # the takeover packed the step1-TRAINED values, not stale initials
    assert np.array_equal(np.asarray(model.gpt.wte.weight._data),
                          w_after)
    ids, labels = _batch()
    assert np.isfinite(float(step2(ids, labels)))
    # jitted pack/gather helpers are cached, not rebuilt per call
    _ = model.gpt.wte.weight._data
    g1 = step2._gather_jit
    float(step2(ids, labels))
    _ = model.gpt.wte.weight._data
    assert step2._gather_jit is g1


def test_layer_chunk_unroll_and_segments_parity(mesh):
    """Gather-on-use composes with layer_chunk/scan_unroll (the
    double-buffer indexes chunks, not layers) and with packed-sequence
    segment ids."""
    base, _, _, _ = _build(mesh, "sharded")
    var, _, _, _ = _build(mesh, "sharded", layer_chunk=2, scan_unroll=2)
    np.testing.assert_allclose(base, var, rtol=2e-6, atol=1e-7)
    ids, labels = _batch()
    seg = paddle.to_tensor(
        np.repeat([[0] * 6 + [1] * 6], N_DEV, 0), dtype="int32")

    def seg_run(storage):
        cfg = GPTConfig(**TINY, scan_layers=True)
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=model.parameters())
        step = ShardedFusedScanTrainStep(model, opt, mesh=mesh,
                                         axis="sharding",
                                         param_storage=storage)
        return [float(step(ids, labels, segment_ids=seg))
                for _ in range(2)]

    assert seg_run("sharded") == seg_run("replicated")


def test_checkpoint_reshard_restore_different_mesh(mesh, tmp_path):
    """dp8-saved checkpoint restores onto a dp4 step — different mesh
    shape AND different flat pad length (h=36 per-layer numel pads to
    different multiples of 8 vs 4) — and the resumed trajectory matches
    an uninterrupted dp8 run within cross-mesh fp tolerance."""
    from jax.sharding import Mesh
    from paddle_tpu.distributed.checkpoint.manager import (
        CheckpointManager,
    )

    devs = jax.devices("cpu")[:N_DEV]
    ids, labels = _batch()

    def build(nd, seed=0):
        cfg = GPTConfig(**TINY, scan_layers=True)
        paddle.seed(seed)
        model = GPTForCausalLM(cfg)
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=model.parameters(),
                         grad_clip=nn.ClipGradByGlobalNorm(0.05))
        m = Mesh(np.asarray(devs[:nd]), ("sharding",))
        denv.set_mesh(m)
        step = ShardedFusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(), mesh=m,
            axis="sharding", param_storage="sharded")
        return model, opt, step

    model, opt, step = build(8)
    assert step._s_assign.buckets[0].numel % 8 == 0
    straight = [float(step(ids, labels)) for _ in range(4)]
    model, opt, step = build(8)
    part1 = [float(step(ids, labels)) for _ in range(2)]
    CheckpointManager(str(tmp_path / "ck"), model=model,
                      optimizer=opt).save(1)
    model2, opt2, step2 = build(4, seed=99)
    # the dp4 layout really does have a different padded flat length
    assert step2._s_assign.buckets[0].numel != \
        step._s_assign.buckets[0].numel
    step2.ensure_built()
    mgr2 = CheckpointManager(str(tmp_path / "ck"), model=model2,
                             optimizer=opt2)
    assert mgr2.restore_or_init() == 1
    part2 = [float(step2(ids, labels)) for _ in range(2)]
    assert max(abs(a - b)
               for a, b in zip(straight, part1 + part2)) <= 5e-4


def test_pp_dropout_deterministic_and_applied():
    devs = jax.devices("cpu")[:4]
    if len(devs) < 4:
        pytest.skip("needs 4 virtual cpu devices")
    denv.reset()
    mesh = denv.build_mesh({"dp": 2, "pp": 2}, devices=devs)
    denv.set_mesh(mesh)
    ids, labels = _batch(bs=4)

    def run(p):
        cfg = GPTConfig(**{**TINY, "hidden_dropout_prob": p},
                        scan_layers=True)
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=model.parameters())
        step = PipelineScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(), mesh=mesh,
            axis="dp", pp_axis="pp", num_micro=2)
        return [float(step(ids, labels)) for _ in range(2)]

    a, b, base = run(0.1), run(0.1), run(0.0)
    assert a == b                    # deterministic across builds
    assert a != base                 # masks actually applied
    assert np.isfinite(a).all()
    denv.reset()


def test_pp_dropout_bwd_matches_jax_grad():
    """The per-(micro, stage) offset scheme's strong consistency check
    (mirror of the fused-scan dropout test): on the degenerate pp=1
    ring with num_micro=2, moment1 after step 1 must equal
    (1-beta1) * jax.grad of a pure forward that draws the SAME
    per-micro masks via the step's own offset helpers."""
    devs = jax.devices("cpu")[:1]
    denv.reset()
    mesh = denv.build_mesh({"dp": 1, "pp": 1}, devices=devs)
    denv.set_mesh(mesh)
    cfg = GPTConfig(**{**TINY, "hidden_dropout_prob": 0.2},
                    scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-3,
                     parameters=model.parameters())
    step = PipelineScanTrainStep(model, opt,
                                 criterion=GPTPretrainingCriterion(),
                                 mesh=mesh, axis="dp", pp_axis="pp",
                                 num_micro=2,
                                 param_storage="replicated")
    step.ensure_built()
    state = step._extract_state()
    sp0 = [jnp.array(d) for d in state["s"]["p"]]
    op0 = [jnp.array(d) for d in state["o"]["p"]]
    ids, labels = _batch(bs=4)
    ids_d, lab_d = ids._data, labels._data
    seq = ids_d.shape[1]
    pos = jnp.arange(seq, dtype=ids_d.dtype)[None, :]
    L = cfg.num_layers
    M = 2
    mb = 4 // M
    t32 = jnp.int32(1)
    from paddle_tpu.jit.fused_scan_step import _RNG_SLOTS

    # the step's offset formula with dp_rank=0 (dp degree 1), written
    # out host-side (axis_index is only bound inside the shard_map)
    nr = step._rng_nranks          # dp * M
    n_slots = L + 1

    def off(layer, m):
        return ((t32 * n_slots + layer) * nr + m) * _RNG_SLOTS

    def pure_loss(sp):
        x = step._embed_fn(op0, ids_d, pos, rng_off=off(L, 0))
        outs = []
        for m in range(M):
            h = x[m * mb:(m + 1) * mb]
            for i in range(L):
                h = step._block_fn([a[i] for a in sp], h,
                                   rng_off=off(i, m))
            outs.append(h)
        return step._head_fn(op0, jnp.concatenate(outs, 0), lab_d)

    grads = jax.jit(jax.grad(pure_loss))(sp0)
    loss = step(ids, labels)
    assert np.isfinite(float(loss))
    # moment1 lives as flat 1/N bucket shards; unpack per entry
    for bkt in step._s_assign.buckets:
        flat = np.asarray(
            opt._accumulators["moment1"][f"__scan_shard_s{bkt.index}__"],
            np.float32)
        for e in bkt.entries:
            m1 = flat[:, e.offset:e.offset + e.numel].reshape(
                (L,) + tuple(e.shape))
            want = 0.1 * np.asarray(grads[e.key], np.float32)
            np.testing.assert_allclose(m1, want, rtol=2e-4, atol=1e-7,
                                       err_msg=str(e.key))
    denv.reset()


def test_quantized_multiaxis_scatter_and_gather(mesh):
    """The flattened-axis-tuple int8 wire format (scatter + the new
    gather leg) holds the comm_quant rel-err bound — and the gather leg
    is exact-inverse-shaped (gather(scatter_shape) round trip)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.distributed.collective import (
        comm_quant_multiaxis_selftest,
    )
    from paddle_tpu.jit.sharded_scan import gather_flat

    devs = jax.devices("cpu")[:N_DEV]
    m2 = Mesh(np.asarray(devs).reshape(4, 2), ("dp", "mp"))
    denv.set_mesh(m2)
    for qf in ("int8", "bf16"):
        rep = comm_quant_multiaxis_selftest(qformat=qf, mesh=m2,
                                            axes=("dp", "mp"))
        assert rep["pass"], rep
    # gather_flat(quant=) vs exact on the tuple axes
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 32 * 3)), jnp.float32)

    def both(v):
        return (gather_flat(v, ("dp", "mp"), axis=1),
                gather_flat(v, ("dp", "mp"), axis=1, quant="int8"))

    exact, quant = jax.jit(jax.shard_map(
        both, mesh=m2, in_specs=(P(None, ("dp", "mp")),),
        out_specs=(P(), P()), check_vma=False))(
            jnp.tile(x, (1, 8)))
    rel = float(jnp.linalg.norm(quant - exact)
                / jnp.maximum(jnp.linalg.norm(exact), 1e-30))
    assert rel < 1e-2, rel


def test_quantized_param_gather_trains(mesh):
    """FLAGS_comm_quant engages the compressed param-gather leg on the
    sharded-storage step (lossy, opt-in): training stays finite and
    lands near the exact trajectory."""
    exact, _, _, _ = _build(mesh, "sharded", clip=False)
    qloss, _, _, _ = _build(mesh, "sharded", clip=False,
                            comm_quant="int8")
    assert np.isfinite(qloss).all()
    assert qloss != exact                       # actually compressed
    assert max(abs(a - b) for a, b in zip(exact, qloss)) < 0.1


def test_planner_ep_grid_and_rules():
    from paddle_tpu.distributed.auto_tuner.prune import prune_candidates
    from paddle_tpu.distributed.auto_tuner.search import grid_candidates
    from paddle_tpu.distributed.auto_tuner.tuner import ModelSpec

    spec = ModelSpec(params=10_000_000, num_layers=4, hidden_size=64,
                     num_heads=2, vocab_size=128, seq_len=64,
                     global_batch=32, num_experts=4)
    cands = grid_candidates(8, sharding_stages=(1,), max_micro=8,
                            global_batch=32, num_experts=4)
    assert any(c.ep > 1 for c in cands)        # ep is searched
    pruned = prune_candidates(
        [c for c in cands if c.degree == 8], spec, hbm_gb=16.0)
    live = [c for c in pruned if c.pruned_reason is None]
    assert any(c.ep == 2 and c.dp == 4 for c in live)
    # mp×ep / pp×ep / oversized ep are pruned with reasons
    assert all(not (c.ep > 1 and (c.mp > 1 or c.pp > 1))
               for c in live)
    assert all(c.ep <= 4 for c in live)        # experts % ep
    # dense model: every ep>1 candidate pruned
    dense = ModelSpec(params=10_000_000, num_layers=4, hidden_size=64,
                      num_heads=2, vocab_size=128, seq_len=64,
                      global_batch=32)
    pruned_d = prune_candidates(
        [c for c in cands if c.degree == 8], dense, hbm_gb=16.0)
    assert all(c.pruned_reason for c in pruned_d if c.ep > 1)


def test_armed_step_crash_fires_before_dispatch(mesh):
    """`train.step.crash` raises at the step boundary, BEFORE the compiled
    step is dispatched: no donated buffer is half-consumed, and the same
    step object goes on to the uninterrupted run's losses."""
    from paddle_tpu.observability import faults

    ids, labels = _batch()

    def build():
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(**TINY, scan_layers=True))
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=model.parameters())
        return ShardedFusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(), mesh=mesh,
            axis="sharding")

    step = build()
    straight = [float(step(ids, labels)) for _ in range(3)]
    step = build()
    got = [float(step(ids, labels))]
    inj = faults.install(0)
    inj.arm("train.step.crash", message="armed by the test")
    try:
        with pytest.raises(faults.FaultError):
            step(ids, labels)
    finally:
        faults.reset()
    got += [float(step(ids, labels)) for _ in range(2)]
    assert got == straight


def test_planner_sharded_storage_memory_and_gather_term():
    from paddle_tpu.distributed.auto_tuner.tuner import (
        Candidate, ModelSpec, estimate_memory_gb, estimate_step_ms,
    )

    base = dict(params=1_300_000_000, num_layers=24, hidden_size=2048,
                num_heads=16, vocab_size=50304, seq_len=2048,
                global_batch=64)
    rep = ModelSpec(**base, sharded_param_storage=False)
    sh = ModelSpec(**base, sharded_param_storage=True)
    c = Candidate(dp=8, sharding_stage=1, micro_batch=1)
    # sharded storage frees the replicated param bytes...
    assert estimate_memory_gb(sh, c) < estimate_memory_gb(rep, c)
    # ...and pays a gather-traffic term in step time
    assert estimate_step_ms(sh, c) > estimate_step_ms(rep, c)


def test_hlo_no_full_param_buffer_receipt():
    """Compiled-HLO receipt: the sharded-storage probe program holds no
    buffer the size of the parameter set, nor of even ONE stacked
    [L, ...] leaf (the replicated layout's storage unit: at most ~a layer
    chunk's gathered params are live across chunk boundaries); its
    largest buffer is strictly below the replicated program's; and every
    all-gather is the param gather over the mesh's axis, nothing
    unclassified."""
    from paddle_tpu.jit.sharded_scan import build_probe_lowered
    from paddle_tpu.linalg.distributed.probe import max_buffer_elems
    from paddle_tpu.observability.hlo_costs import load_hlo_overlap

    denv.reset()
    # the probe model's parameter accounting (build_probe_lowered's config)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                    num_attention_heads=2, max_position_embeddings=32,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    scan_layers=True)
    paddle.seed(0)
    trainable = [(n, p) for n, p in
                 GPTForCausalLM(cfg).named_parameters() if p.trainable]
    total_elems = sum(int(np.prod(p.shape)) for _, p in trainable)
    largest_stacked = max(
        int(np.prod(p.shape)) for n, p in trainable
        if "blocks__" in n and p.ndim >= 1
        and p.shape[0] == cfg.num_layers)

    text = {storage: build_probe_lowered(
        n_devices=N_DEV, scan_unroll=2,
        param_storage=storage).compile().as_text()
        for storage in ("sharded", "replicated")}
    peak = {k: max_buffer_elems(t) for k, t in text.items()}
    assert peak["sharded"] < largest_stacked < total_elems, peak
    assert peak["sharded"] < peak["replicated"], peak
    per_axis = load_hlo_overlap().analyze(
        text["sharded"],
        axis_degrees={"sharding": N_DEV})["per_axis_counts"]
    assert per_axis["sharding"]["all-gather"] >= 1, per_axis
    assert set(per_axis) == {"sharding"}, per_axis


def test_bit_parity_hybrid_meshes():
    """dp4×mp2 and dp2×pp2 sharded-vs-replicated storage parity."""
    from jax.sharding import Mesh

    devs = jax.devices("cpu")[:N_DEV]
    ids, labels = _batch()

    def run(kind, storage):
        cfg = GPTConfig(**TINY, scan_layers=True)
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=model.parameters(),
                         grad_clip=nn.ClipGradByGlobalNorm(0.05))
        crit = GPTPretrainingCriterion()
        if kind == "dpmp":
            m2 = Mesh(np.asarray(devs).reshape(4, 2), ("dp", "mp"))
            denv.set_mesh(m2)
            step = ShardedFusedScanTrainStep(
                model, opt, criterion=crit, mesh=m2, axis="dp",
                mp_axis="mp", param_storage=storage)
        else:
            m2 = denv.build_mesh({"dp": 2, "pp": 2}, devices=devs[:4])
            denv.set_mesh(m2)
            step = PipelineScanTrainStep(
                model, opt, criterion=crit, mesh=m2, axis="dp",
                pp_axis="pp", num_micro=2, param_storage=storage)
        return [float(step(ids, labels)) for _ in range(3)]

    for kind in ("dpmp", "dppp"):
        assert run(kind, "sharded") == run(kind, "replicated"), kind
    denv.reset()
