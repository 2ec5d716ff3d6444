"""In-graph training-numerics observatory (ISSUE 15,
observability/numerics.py + the four jit step paths): per-chunk grad
sq-norm parity vs eager per-layer grads on the same model (fused +
sharded + pipeline), injected NaN at layer k attributed to chunk(k) on
all three scan paths, update-ratio sanity vs the actual Adam step,
EWMA spike detector behavior, norm-reduction dedup (no duplicate norm
all-reduce in the sharded HLO), and the /numericsz endpoint."""
import json
import math
import os
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as popt
from paddle_tpu.distributed import env as denv
from paddle_tpu.jit import (
    FusedScanTrainStep, ShardedFusedScanTrainStep, TrainStep,
)
from paddle_tpu.jit.pipeline_step import PipelineScanTrainStep
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
)
from paddle_tpu.observability import numerics as onum

TINY = dict(vocab_size=96, hidden_size=32, num_layers=4,
            num_attention_heads=2, max_position_embeddings=16,
            hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
N_DEV = 8
L = TINY["num_layers"]


@pytest.fixture
def mesh():
    devs = jax.devices("cpu")[:N_DEV]
    if len(devs) < N_DEV:
        pytest.skip(f"needs {N_DEV} virtual cpu devices")
    denv.reset()
    m = denv.build_mesh({"sharding": N_DEV})
    denv.set_mesh(m)
    yield m
    denv.reset()


@pytest.fixture
def mesh_pp():
    devs = jax.devices("cpu")[:N_DEV]
    if len(devs) < N_DEV:
        pytest.skip(f"needs {N_DEV} virtual cpu devices")
    denv.reset()
    m = denv.build_mesh({"dp": 2, "pp": 2})
    denv.set_mesh(m)
    yield m
    denv.reset()


def _batch(bs=8, seq=12, vocab=96, seed=0):
    rng = np.random.default_rng(seed)
    return (paddle.to_tensor(rng.integers(0, vocab, (bs, seq)),
                             dtype="int64"),
            paddle.to_tensor(rng.integers(0, vocab, (bs, seq)),
                             dtype="int64"))


def _model_opt(seed=0, clip=True):
    cfg = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(
        learning_rate=1e-3, parameters=model.parameters(),
        grad_clip=nn.ClipGradByGlobalNorm(1.0) if clip else None)
    return model, opt


def _eager_chunk_grad_sq(ids, labels, seed=0):
    """Reference per-chunk grad sq-norms from the EAGER tape on an
    identical model: backward through the scan-layers forward, then
    per-layer slices of every stacked leaf's grad + the outer group."""
    model, _ = _model_opt(seed=seed, clip=False)
    crit = GPTPretrainingCriterion()
    loss = crit(model(ids), labels)
    loss.backward()
    per_chunk = np.zeros(L)
    for name, p in model.named_parameters():
        if p.grad is None or not p.trainable:
            continue
        g = np.asarray(p.grad._data, np.float64)
        if "blocks__" in name:           # stacked [L, ...] leaf
            for k in range(L):
                per_chunk[k] += float((g[k] ** 2).sum())
        # outer group handled separately below
    outer = 0.0
    for name, p in model.named_parameters():
        if p.grad is None or "blocks__" in name or not p.trainable:
            continue
        g = np.asarray(p.grad._data, np.float64)
        outer += float((g ** 2).sum())
    return per_chunk, outer, float(loss)


class TestChunkGradParity:
    """Monitor grad rows == eager per-layer jax.grad norms (the same
    model/batch), on all three scan paths."""

    def _check(self, step, ids, labels, tol=1e-4):
        ref, ref_outer, _ = _eager_chunk_grad_sq(ids, labels)
        step(ids, labels)
        mon = step._numerics
        rows = mon.latest_rows()
        assert len(rows) == L + 1
        for k in range(L):
            got = rows[k]["grad_norm"] ** 2
            assert abs(got - ref[k]) <= tol * max(ref[k], 1e-6), (
                k, got, ref[k])
        got_outer = rows[L]["grad_norm"] ** 2
        assert abs(got_outer - ref_outer) <= tol * ref_outer
        # the global gauge equals the root of the row sum
        s = mon.summary()
        assert math.isclose(
            s["grad_norm"],
            math.sqrt(sum(r["grad_norm"] ** 2 for r in rows)),
            rel_tol=1e-6)

    def test_fused(self):
        model, opt = _model_opt(clip=False)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion())
        self._check(step, *_batch())

    def test_fused_with_clip_shares_reduction(self):
        # clipping on: the monitor reads the clip pre-pass's terms —
        # values must be identical to the eager reference regardless
        model, opt = _model_opt(clip=True)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion())
        self._check(step, *_batch())

    def test_sharded(self, mesh):
        model, opt = _model_opt(clip=True)
        step = ShardedFusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(),
            mesh=mesh, axis="sharding")
        self._check(step, *_batch())

    def test_pipeline(self, mesh_pp):
        model, opt = _model_opt(clip=True)
        step = PipelineScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(),
            mesh=mesh_pp, axis="dp", pp_axis="pp", num_micro=2)
        self._check(step, *_batch())


class TestActivationRms:
    def test_rms_matches_forward(self):
        # chunk c's act RMS == RMS of the hidden state after layer c,
        # computed eagerly via the step's own pure per-block function
        # on a twin model (verifies the stats index the right chunk
        # and the RMS math; the grad-parity tests cover independence)
        ids, labels = _batch()
        model, opt = _model_opt(clip=False)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion())
        ref_model, ref_opt = _model_opt(clip=False)
        ref = FusedScanTrainStep(
            ref_model, ref_opt, criterion=GPTPretrainingCriterion(),
            numerics=False)
        pos = jnp.arange(ids.shape[1], dtype=ids._data.dtype)[None, :]
        x = ref._embed_fn([p._data for _, p in ref._o_params],
                          ids._data, pos)
        refs = []
        for k in range(L):
            x = ref._block_fn([p._data[k] for p in ref._s_params], x)
            arr = np.asarray(x, np.float64)
            refs.append(float(np.sqrt((arr ** 2).mean())))
        step(ids, labels)
        rows = step._numerics.latest_rows()
        for k in range(L):
            assert abs(rows[k]["act_rms"] - refs[k]) <= 1e-4 * refs[k]


class TestUpdateRatio:
    def test_ratio_matches_actual_adam_step(self):
        # ‖Δw‖/‖w‖ per chunk == the ratio computed from param
        # snapshots around one real Adam step (the hand-computable
        # ground truth — Δw IS the Adam update)
        ids, labels = _batch()
        model, opt = _model_opt(clip=False)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion())
        stacked = [(n, np.asarray(p._data, np.float64))
                   for n, p in model.named_parameters()
                   if "blocks__" in n and p.trainable]
        step(ids, labels)
        rows = step._numerics.latest_rows()
        after = {n: np.asarray(p._data, np.float64)
                 for n, p in model.named_parameters()}
        for k in range(L):
            upd_sq = sum(float(((after[n][k] - b[k]) ** 2).sum())
                         for n, b in stacked)
            p_sq = sum(float((b[k] ** 2).sum()) for n, b in stacked)
            want = math.sqrt(upd_sq) / math.sqrt(p_sq)
            got = rows[k]["update_ratio"]
            assert abs(got - want) <= 1e-3 * max(want, 1e-9), (
                k, got, want)


class TestFusedBlockAgainstState:
    """ISSUE 25 moved how the fused scan step's monitor reads (the sums
    leave one barrier with the slot's new value; DECISIONS §21), not
    what it reports: every row of the [chunks + 1, NFIELDS] block
    against sums taken outside the step — parameter and update
    sq-norms from the state before and after, gradient sq-norms from
    the eager tape, the activation count from the batch."""

    @staticmethod
    def _snapshot(model):
        return {n: np.asarray(p._data, np.float64)
                for n, p in model.named_parameters() if p.trainable}

    @staticmethod
    def _sq(before, after, layer_chunk):
        """(param_sq, upd_sq): [chunks + 1] each, the outer group last."""
        chunks = L // layer_chunk
        p_sq, u_sq = np.zeros(chunks + 1), np.zeros(chunks + 1)
        for n, b in before.items():
            d = after[n] - b
            if "blocks__" in n:
                for c in range(chunks):
                    rows = slice(c * layer_chunk, (c + 1) * layer_chunk)
                    p_sq[c] += (b[rows] ** 2).sum()
                    u_sq[c] += (d[rows] ** 2).sum()
            else:
                p_sq[-1] += (b ** 2).sum()
                u_sq[-1] += (d ** 2).sum()
        return p_sq, u_sq

    @staticmethod
    def _block(step):
        # the device block of the step just taken, before any flush
        return onum.NumericsMonitor._fold(step._numerics._pending[-1][1])

    @pytest.mark.parametrize("layer_chunk", [1, 2])
    @pytest.mark.parametrize("clip", [False, True])
    @pytest.mark.parametrize("guard", [False, True])
    def test_rows(self, layer_chunk, clip, guard):
        ids, labels = _batch()
        model, opt = _model_opt(clip=clip)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(),
            layer_chunk=layer_chunk, guard_nonfinite=guard or None)
        chunks = L // layer_chunk
        blocks = []
        for _ in range(2):      # the second step has moments behind it
            before = self._snapshot(model)
            step(ids, labels)
            blocks.append(self._block(step))
            after = self._snapshot(model)
            assert blocks[-1].shape == (chunks + 1, onum.NFIELDS)
            p_sq, u_sq = self._sq(before, after, layer_chunk)
            np.testing.assert_allclose(blocks[-1][:, onum.F_PARAM_SQ],
                                       p_sq, rtol=1e-5)
            np.testing.assert_allclose(blocks[-1][:, onum.F_UPD_SQ],
                                       u_sq, rtol=1e-4)
        # the eager reference holds the seeded weights: the first step
        g_layer, g_outer, _ = _eager_chunk_grad_sq(ids, labels)
        block = blocks[0]
        g_sq = np.append(g_layer.reshape(chunks, layer_chunk).sum(1),
                         g_outer)
        np.testing.assert_allclose(block[:, onum.F_GRAD_SQ], g_sq,
                                   rtol=1e-4)
        n_act = ids.shape[0] * ids.shape[1] * TINY["hidden_size"]
        assert list(block[:chunks, onum.F_ACT_N]) == [n_act] * chunks
        assert np.all(block[:chunks, onum.F_ACT_SQ] > 0)
        for f in (onum.F_GRAD_BAD, onum.F_ACT_ORIGIN,
                  onum.F_GRAD_ORIGIN):
            assert not block[:, f].any()

    @pytest.mark.parametrize("layer_chunk", [1, 2])
    def test_skipped_step_reports_no_update(self, layer_chunk):
        # guard on, one chunk's gradient poisoned: the step is skipped,
        # every row's update sq-norm is exactly 0 and the clean chunks'
        # parameter sq-norms are still the state's
        ids, labels = _batch()
        model, opt = _model_opt(clip=True)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(),
            layer_chunk=layer_chunk, guard_nonfinite=True)
        step(ids, labels)
        bad_layer = 2
        p = step._s_params[0]
        p._data = p._data.at[bad_layer].set(jnp.float32("nan"))
        before = self._snapshot(model)
        step(ids, labels)
        block = self._block(step)
        after = self._snapshot(model)
        for n, b in before.items():
            assert np.array_equal(b, after[n], equal_nan=True), n
        assert not block[:, onum.F_UPD_SQ].any()
        p_sq, _ = self._sq(before, after, layer_chunk)
        clean = [c for c in range(L // layer_chunk)
                 if c != bad_layer // layer_chunk] + [-1]
        np.testing.assert_allclose(block[clean, onum.F_PARAM_SQ],
                                   p_sq[clean], rtol=1e-5)
        assert block[:, onum.F_GRAD_BAD].any()


class TestNanProvenance:
    """NaN injected into layer k's params -> first_bad_chunk == k on
    every scan path (activation origin: the poisoned layer's output is
    the first non-finite tensor given a finite input)."""

    BAD = 2

    def _poison_and_check(self, step, tmp_path):
        os.environ["PADDLE_FLIGHT_DIR"] = str(tmp_path)
        try:
            ids, labels = _batch()
            step(ids, labels)
            assert step._numerics.summary()["finite"] is True
            p = step._s_params[0]
            p._data = p._data.at[self.BAD].set(jnp.float32("nan"))
            step(ids, labels)
            s = step._numerics.summary()
            assert s["finite"] is False
            assert s["first_bad_chunk"] == self.BAD
            prov = step._numerics.provenance()
            assert prov["origin"] == "activation"
            assert prov["label"].startswith(f"chunk{self.BAD}")
            # flight recorder got the event + wrote a dump with the
            # recent per-layer ring
            from paddle_tpu.observability import recorder

            evs = [e for e in recorder().snapshot()
                   if e.get("kind") == "nan_provenance"
                   and e.get("monitor") == type(step).__name__]
            assert evs and evs[-1]["first_bad_chunk"] == self.BAD
            dumps = [f for f in os.listdir(tmp_path)
                     if f.startswith("crash_")]
            assert dumps
        finally:
            os.environ.pop("PADDLE_FLIGHT_DIR", None)

    def test_fused(self, tmp_path):
        model, opt = _model_opt(clip=True)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion())
        self._poison_and_check(step, tmp_path)

    def test_sharded(self, mesh, tmp_path):
        model, opt = _model_opt(clip=True)
        step = ShardedFusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(),
            mesh=mesh, axis="sharding")
        self._poison_and_check(step, tmp_path)

    def test_pipeline(self, mesh_pp, tmp_path):
        model, opt = _model_opt(clip=True)
        step = PipelineScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(),
            mesh=mesh_pp, axis="dp", pp_axis="pp", num_micro=2)
        self._poison_and_check(step, tmp_path)

    def test_guard_interplay_fused(self, tmp_path):
        # with the non-finite guard bound, the poisoned step is
        # SKIPPED (clean layers bit-identical) AND attributed
        model, opt = _model_opt(clip=True)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(),
            guard_nonfinite=True)
        ids, labels = _batch()
        step(ids, labels)
        p = step._s_params[0]
        before = np.asarray(p._data)
        p._data = p._data.at[self.BAD].set(jnp.float32("nan"))
        step(ids, labels)
        assert step._numerics.summary()["first_bad_chunk"] == self.BAD
        after = np.asarray(p._data)
        ok = [i for i in range(L) if i != self.BAD]
        assert np.array_equal(before[ok], after[ok])
        assert int(np.asarray(jnp.asarray(step._guard._skipped))) == 1


class TestSpikeDetector:
    def _mk(self, rows=3, warmup=5):
        return onum.NumericsMonitor("t", rows, warmup=warmup,
                                    ewma_alpha=0.2, z_threshold=8.0)

    @staticmethod
    def _stats(grad_norms):
        rows = np.zeros((len(grad_norms), onum.NFIELDS), np.float32)
        rows[:, onum.F_GRAD_SQ] = np.square(grad_norms)
        rows[:, onum.F_PARAM_SQ] = 1.0
        return jnp.asarray(rows)

    def test_fires_on_100x_spike_silent_on_clean(self):
        from paddle_tpu.observability import registry

        mon = self._mk()
        ctr = registry().counter("numerics.anomaly.count")
        base = ctr.value
        rng = np.random.default_rng(0)
        for i in range(20):     # clean: ~1% jitter around 1.0
            mon.on_step(self._stats(1.0 + 0.01 * rng.standard_normal(3)),
                        step=i)
        mon.flush()
        assert ctr.value == base, "spike detector fired on clean run"
        mon.on_step(self._stats(np.array([1.0, 100.0, 1.0])), step=20)
        mon.flush()
        assert ctr.value > base
        ev = mon.anomalies()[-1]
        assert ev["chunk"] == 1 and ev["z"] > 8.0

    def test_warmup_gates(self):
        mon = self._mk(warmup=10)
        for i in range(3):
            mon.on_step(self._stats([1.0, 1.0, 1.0]), step=i)
        mon.flush()
        mon.on_step(self._stats([1.0, 500.0, 1.0]), step=3)
        mon.flush()
        assert not mon.anomalies()     # still warming up

    def test_nonfinite_steps_do_not_poison_ewma(self):
        mon = self._mk(warmup=2)
        for i in range(6):
            mon.on_step(self._stats([1.0, 1.0, 1.0]), step=i)
        bad = np.zeros((3, onum.NFIELDS), np.float32)
        bad[:, onum.F_GRAD_SQ] = np.float32("nan")
        bad[1, onum.F_GRAD_BAD] = 1.0
        mon.on_step(jnp.asarray(bad), step=6)
        mon.on_step(self._stats([1.0, 1.0, 1.0]), step=7)
        mon.flush()
        assert np.isfinite(mon._ewma_mean).all()


class TestProvenanceRules:
    def test_forward_origin_wins(self):
        mon = onum.NumericsMonitor("t", 4)
        rows = np.zeros((4, onum.NFIELDS), np.float32)
        rows[:, onum.F_GRAD_SQ] = np.float32("nan")
        rows[:3, onum.F_GRAD_BAD] = 1.0       # contaminated backward
        rows[2, onum.F_ACT_ORIGIN] = 1.0      # true forward origin
        mon.on_step(jnp.asarray(rows))
        s = mon.summary()
        assert s["first_bad_chunk"] == 2
        assert mon.provenance()["origin"] == "activation"

    def test_backward_contamination_picks_highest(self):
        # grads bad in chunks 0..2 (NaN flowed toward layer 0): the
        # origin is the bad chunk CLOSEST to the loss
        mon = onum.NumericsMonitor("t", 4)
        rows = np.zeros((4, onum.NFIELDS), np.float32)
        rows[:3, onum.F_GRAD_BAD] = 1.0
        mon.on_step(jnp.asarray(rows))
        assert mon.summary()["first_bad_chunk"] == 2
        assert mon.provenance()["origin"] == "grad_nonfinite"


class TestNoDuplicateNormAllReduce:
    def test_census_identical_monitor_on_off(self, mesh):
        # ISSUE 15 dedup satellite: with ClipGradByGlobalNorm active,
        # enabling the monitor adds NO collective to the compiled
        # sharded step (the grad-norm stats ride the clip's reductions
        # and the stats block leaves shard_map as stacked partials)
        from paddle_tpu.observability.hlo_costs import load_hlo_overlap

        mod = load_hlo_overlap()
        ids, labels = _batch()
        counts = {}
        for on in (False, True):
            model, opt = _model_opt(clip=True)
            step = ShardedFusedScanTrainStep(
                model, opt, criterion=GPTPretrainingCriterion(),
                mesh=mesh, axis="sharding", numerics=on)
            step.ensure_built()
            state = step._extract_state()
            with step._step_guard():
                text = step._jitted.lower(
                    state, jnp.float32(1e-3), ids._data, labels._data,
                    None).as_text()
            counts[on] = dict(mod.analyze(
                text, axis_degrees={"sharding": N_DEV})["counts"])
        assert counts[True] == counts[False]


class TestTrainStepRows:
    def test_per_param_rows(self):
        paddle.seed(0)
        m = nn.Linear(16, 8)
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=m.parameters())
        step = TrainStep(m, lambda mm, a, b: ((mm(a) - b) ** 2).mean(),
                         opt)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(4, 16).astype(np.float32))
        y = paddle.to_tensor(np.random.RandomState(1)
                             .randn(4, 8).astype(np.float32))
        before = [np.asarray(p._data, np.float64)
                  for p in m.parameters()]
        step(x, y)
        after = [np.asarray(p._data, np.float64)
                 for p in m.parameters()]
        rows = step._numerics.latest_rows()
        assert len(rows) == len(before)
        for r, b, a in zip(rows, before, after):
            p_norm = math.sqrt(float((b ** 2).sum()))
            if p_norm == 0.0:          # zero-init bias: ratio pins 0
                assert r["update_ratio"] == 0.0
                continue
            want = math.sqrt(float(((a - b) ** 2).sum())) / p_norm
            assert abs(r["update_ratio"] - want) <= 1e-3 * want

    def test_opt_out(self):
        paddle.seed(0)
        m = nn.Linear(4, 4)
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=m.parameters())
        step = TrainStep(m, lambda mm, a, b: ((mm(a) - b) ** 2).mean(),
                         opt, numerics=False)
        x = paddle.to_tensor(np.zeros((2, 4), np.float32))
        step(x, x)
        assert step._numerics is None


class TestEndpointAndGauges:
    def test_numericsz_endpoint(self):
        from paddle_tpu.observability import DebugServer

        model, opt = _model_opt(clip=True)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion())
        ids, labels = _batch()
        step(ids, labels)
        with DebugServer() as srv:
            body = urllib.request.urlopen(f"{srv.url}/numericsz",
                                          timeout=10).read()
        payload = json.loads(body)
        mine = [m for m in payload["monitors"]
                if m.get("name") == "FusedScanTrainStep"
                and m.get("per_chunk")]
        assert mine
        m = mine[-1]
        assert len(m["per_chunk"]) == L + 1
        assert m["summary"]["finite"] is True

    def test_lazy_gauges(self):
        from paddle_tpu.observability import registry

        model, opt = _model_opt(clip=True)
        step = FusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion())
        ids, labels = _batch()
        step(ids, labels)
        reg = registry()
        gn = reg.gauge("numerics.global_grad_norm").value
        assert gn is not None and gn > 0
        assert reg.gauge("numerics.finite_frac").value == 1.0
        assert reg.gauge("numerics.first_bad_chunk").value == -1


class TestFitSurfacing:
    def test_fit_logs_carry_telemetry(self):
        # ISSUE 15 satellite: fit's log-boundary records surface loss
        # scale / guard skips / grad norm from the lazy gauges
        from paddle_tpu.hapi import Model
        from paddle_tpu.observability import registry

        reg = registry()
        gauges = [reg.gauge(n) for n in
                  ("train.loss_scale", "train.guard_skipped_steps",
                   "numerics.global_grad_norm")]
        # the lazy fns are registered ONCE per process (guard/monitor
        # registration is idempotent) — save and restore them, a
        # reset() here would kill them for every later consumer
        saved = [(g._fn, g._value) for g in gauges]
        gauges[0].set(2.0 ** 12)
        gauges[1].set(3)
        gauges[2].set(0.75)
        try:
            paddle.seed(0)
            net = nn.Linear(4, 2)
            model = Model(net)
            model.prepare(
                optimizer=popt.AdamW(learning_rate=1e-2,
                                     parameters=net.parameters()),
                loss=nn.MSELoss())
            seen = []

            from paddle_tpu.hapi.callbacks import Callback

            class Capture(Callback):
                def on_train_batch_end(self, step, logs=None):
                    seen.append(dict(logs or {}))

            data = [(np.zeros((2, 4), np.float32),
                     np.zeros((2, 2), np.float32))] * 3
            model.fit(data, epochs=1, verbose=0,
                      callbacks=[Capture()])
            assert seen
            last = seen[-1]
            assert last["loss_scale"] == 2.0 ** 12
            assert last["guard_skips"] == 3.0
            assert last["grad_norm"] == 0.75
        finally:
            for g, (fn, value) in zip(gauges, saved):
                if fn is not None:
                    g.set_fn(fn)
                elif value is not None:
                    g.set(value)
                else:
                    g.reset()
