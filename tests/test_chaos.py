"""Chaos-hardening tests (ISSUE 19).

Fast, fully scripted lanes against the process-global FaultInjector
and the self-healing fleet: trigger grammar (at/every/prob/times/
match), seeded determinism, log-vs-hits accounting, corrupt hand-off
blobs rejected by crc32 before allocation, per-request deadlines,
brown-out shedding below the healthy-capacity watermark, replica-kill
re-dispatch with bit-exact token parity, and hung-join accounting at
stop(). The randomized multi-seed churn sweep is marked ``slow``
(tier-1 runs only the deterministic lanes). The dp8 -> dp4 elastic
resume is in tests/test_sharded_storage.py; no test measures an MTTR.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import faults
from paddle_tpu.observability.faults import FaultError, FaultInjector
from paddle_tpu.serving import FleetRouter, ServingEngine
from paddle_tpu.serving.request import FinishReason, RequestState


@pytest.fixture(autouse=True)
def _quiet_faults():
    yield
    faults.reset()


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=96,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


KW = dict(max_slots=4, max_len=96, page_size=8, chunk_size=16,
          prefill_batch=2)


def _pin_sessions(target, others, n):
    from paddle_tpu.serving.router import rendezvous_score

    out, i = [], 0
    while len(out) < n:
        s = f"chaos{i}"
        i += 1
        if all(rendezvous_score(s, target) > rendezvous_score(s, o)
               for o in others):
            out.append(s)
    return out


def _fired(inj, point):
    """Firing count for one point (``hits`` counts every PROBE)."""
    return sum(1 for e in inj.log if e["point"] == point)


# ---------------------------------------------------------------------------
# FaultInjector trigger grammar
# ---------------------------------------------------------------------------

class TestFaultInjector:
    POINT = "serving.step.raise"

    def test_unknown_point_rejected(self):
        inj = FaultInjector()
        with pytest.raises(ValueError, match="unknown fault point"):
            inj.arm("serving.step.tpyo")

    def test_quiet_fast_path(self):
        faults.reset()
        assert faults.active() is None
        assert faults.fire(self.POINT) is None
        assert not faults.should_fire(self.POINT)
        assert faults.maybe_delay("serving.step.stuck") == 0.0
        faults.maybe_raise(self.POINT)   # no injector -> no raise

    def test_at_fires_on_exactly_the_nth_hit(self):
        inj = FaultInjector()
        inj.arm(self.POINT, at=3, times=None)
        fired = [inj.fire(self.POINT, {}) is not None
                 for _ in range(5)]
        assert fired == [False, False, True, False, False]
        assert inj.hits[self.POINT] == 5          # every probe counted
        assert _fired(inj, self.POINT) == 1       # one firing logged

    def test_at_accepts_a_set_of_hits(self):
        inj = FaultInjector()
        inj.arm(self.POINT, at=(2, 4), times=None)
        fired = [inj.fire(self.POINT, {}) is not None
                 for _ in range(5)]
        assert fired == [False, True, False, True, False]

    def test_every_kth_hit(self):
        inj = FaultInjector()
        inj.arm(self.POINT, every=2, times=None)
        fired = [inj.fire(self.POINT, {}) is not None
                 for _ in range(6)]
        assert fired == [False, True, False, True, False, True]

    def test_times_bounds_total_fires(self):
        inj = FaultInjector()
        inj.arm(self.POINT, every=1, times=2)
        fired = [inj.fire(self.POINT, {}) is not None
                 for _ in range(5)]
        assert fired == [True, True, False, False, False]

    def test_one_shot_default(self):
        inj = FaultInjector()
        inj.arm(self.POINT)
        assert inj.fire(self.POINT, {}) is not None
        assert inj.fire(self.POINT, {}) is None

    def test_prob_is_seed_deterministic(self):
        def schedule(seed):
            inj = FaultInjector(seed=seed)
            inj.arm(self.POINT, prob=0.5, times=None)
            return [inj.fire(self.POINT, {}) is not None
                    for _ in range(64)]

        a, b = schedule(7), schedule(7)
        assert a == b                      # same seed -> same schedule
        assert 0 < sum(a) < 64             # and it is genuinely random
        assert schedule(8) != a

    def test_match_restricts_to_context(self):
        inj = FaultInjector()
        spec = inj.arm(self.POINT, at=1, match={"engine": "d0"})
        assert inj.fire(self.POINT, {"engine": "d1"}) is None
        assert spec.seen == 0              # non-matching hits don't count
        assert inj.fire(self.POINT, {"engine": "d0"}) is spec
        assert inj.log[-1]["engine"] == "d0"

    def test_maybe_raise_and_delay(self):
        inj = faults.install(0)
        inj.arm(self.POINT, message="boom")
        with pytest.raises(FaultError, match="boom"):
            faults.maybe_raise(self.POINT)
        inj.arm("serving.step.stuck", delay_s=0.001)
        t0 = time.perf_counter()
        assert faults.maybe_delay("serving.step.stuck") == 0.001
        assert time.perf_counter() - t0 >= 0.001

    def test_summary_and_register(self):
        inj = FaultInjector(seed=3)
        inj.arm(self.POINT, at=1)
        inj.fire(self.POINT, {"engine": "d0"})
        s = inj.summary()
        assert s["seed"] == 3
        assert s["hits"] == {self.POINT: 1}
        assert s["fired"][0]["point"] == self.POINT
        assert s["armed"][0]["fired"] == 1
        p = faults.register("serving.step.raise", "idempotent")
        assert p in faults.FAULT_POINTS

    def test_flip_byte_is_a_single_bit(self):
        inj = FaultInjector(seed=1)
        buf = np.zeros(32, np.uint8)
        idx = inj.flip_byte(buf)
        assert buf[idx] == 0x01 and buf.sum() == 1
        inj.flip_byte(buf, index=idx)      # flip back
        assert buf.sum() == 0


# ---------------------------------------------------------------------------
# corrupt hand-off blobs die at the crc32 gate, before allocation
# ---------------------------------------------------------------------------

class TestCorruptBlob:
    def _cache(self):
        from paddle_tpu.inference.kv_cache import PagedKVCache

        return PagedKVCache(num_layers=2, num_kv_heads=2, head_dim=4,
                            num_pages=17, page_size=8, max_slots=4,
                            pages_per_seq=6)

    def test_flip_rejected_before_allocation(self):
        src = self._cache()
        slot = src.allocate(21)
        src._host("seq_lens")[slot] = 21
        blob = src.export_slot(slot)

        inj = faults.install(0)
        inj.arm("kv.handoff.corrupt")
        assert faults.corrupt_blob("kv.handoff.corrupt", blob)
        assert _fired(inj, "kv.handoff.corrupt") == 1

        dst = self._cache()
        free_before = len(dst._free_pages)
        with pytest.raises(ValueError, match="corrupt"):
            dst.import_slot(blob)
        assert len(dst._free_pages) == free_before   # nothing allocated

    def test_quiet_point_leaves_blob_alone(self):
        src = self._cache()
        slot = src.allocate(13)
        src._host("seq_lens")[slot] = 13
        blob = src.export_slot(slot)
        assert not faults.corrupt_blob("kv.handoff.corrupt", blob)
        dst = self._cache()
        assert dst.import_slot(blob) >= 0


# ---------------------------------------------------------------------------
# serving lanes (tiny model; deterministic scripted faults)
# ---------------------------------------------------------------------------

def _engine_clean(eng):
    lk = eng.leak_check()
    assert (lk["free_pages"] == lk["total_pages"]
            and lk["free_slots"] == lk["total_slots"]
            and lk["resident_slot_pages"] == 0
            and lk["leased_slots"] == 0), lk


class TestDeadline:
    def test_queue_expiry_frees_everything(self, model):
        eng = ServingEngine(model, **KW)
        h = eng.submit(np.arange(1, 9, dtype=np.int32), 8, seed=1,
                       deadline_s=0.0)
        eng.run()
        assert h.done
        assert h.finish_reason is FinishReason.DEADLINE_EXCEEDED
        assert len(h.output_tokens) == 0
        _engine_clean(eng)


class TestBrownout:
    def test_sheds_below_watermark_keeps_priority(self, model):
        fleet = FleetRouter(
            model=model, decode_replicas=2, engine_kw=KW, seed=7,
            watchdog={},
            brownout=dict(watermark=0.75, priority_floor=1))
        # deterministic death: no stepping needed — an error-flagged
        # replica is DEAD on the next watchdog tick
        fleet._by_name["d0"].error = RuntimeError("chaos: d0 died")
        assert fleet._watchdog_tick()
        assert fleet.recoveries and \
            fleet.recoveries[0]["cause"] == "error"
        assert fleet._brownout_active()

        shed = fleet.submit(np.arange(1, 7, dtype=np.int32), 4,
                            seed=1, priority=0)
        assert shed.done and shed.state is RequestState.FAILED
        assert shed.finish_reason is FinishReason.SHED
        assert len(shed.output_tokens) == 0

        kept = fleet.submit(np.arange(1, 7, dtype=np.int32), 3,
                            seed=2, priority=1)
        fleet.run()
        assert kept.done and len(kept.output_tokens) == 3
        assert kept.finish_reason is not FinishReason.SHED
        lk = fleet.leak_check()
        assert lk["clean"], lk


class TestKillRedispatch:
    def test_replica_kill_streams_bit_identical(self, model):
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 64, (int(rng.integers(4, 11)),))
                   .astype(np.int32) for _ in range(3)]
        budgets = [int(rng.integers(4, 7)) for _ in range(3)]

        ref_eng = ServingEngine(model, **KW)
        rhs = [ref_eng.submit(p, b, seed=100 + i)
               for i, (p, b) in enumerate(zip(prompts, budgets))]
        ref_eng.run()
        ref = [list(h.output_tokens) for h in rhs]

        inj = faults.install(0)
        inj.arm("serving.step.raise", at=3, match={"engine": "d0"},
                message="chaos: kill d0")
        fleet = FleetRouter(model=model, decode_replicas=2,
                            engine_kw=KW, seed=7, watchdog={})
        sessions = _pin_sessions("d0", ["d1"], 2)
        fhs = [fleet.submit(p, b, seed=100 + i,
                            session=(sessions[i] if i < 2 else None))
               for i, (p, b) in enumerate(zip(prompts, budgets))]
        fleet.run()

        assert [list(h.output_tokens) for h in fhs] == ref, \
            "replica kill changed a token stream"
        assert all(h.done for h in fhs)
        assert _fired(inj, "serving.step.raise") == 1
        recs = fleet.recoveries
        assert len(recs) == 1 and recs[0]["replica"] == "d0"
        assert recs[0]["cause"] == "error"
        assert recs[0]["safe_harvest"] is True
        snap = fleet.metrics_snapshot()
        assert snap["quarantined_replicas"] == ["d0"], snap
        lk = fleet.leak_check()
        assert lk["clean"], lk


class TestHungJoin:
    def test_hung_thread_recorded_and_strict_raises(self, model):
        # no warmup on purpose: the wedge must land on the FIRST
        # worked step, before anything compiles — stop() then hits a
        # replica sleeping through its join timeout
        inj = faults.install(0)
        inj.arm("serving.step.stuck", at=1, match={"engine": "d0"},
                delay_s=0.6)
        fleet = FleetRouter(model=model, decode_replicas=2,
                            engine_kw=KW, seed=7, threaded=True,
                            join_timeout_s=0.05)
        fleet.start()
        try:
            session = _pin_sessions("d0", ["d1"], 1)[0]
            fleet.submit(np.ones((8,), np.int32), 2, seed=1,
                         session=session)
            time.sleep(0.15)           # let d0 enter the wedge
            out = fleet.stop()
            assert out["hung_replicas"] == ["d0"], out
            assert any(e["action"] == "replica_hung"
                       for e in fleet.events), fleet.events
            assert fleet.metrics_snapshot()["hung_replicas"] == ["d0"]
            with pytest.raises(RuntimeError):
                fleet.stop(strict=True)
        finally:
            for r in (list(fleet._replicas) + list(fleet._retired)
                      + list(fleet._quarantined)):
                if r.thread is not None:
                    r.thread.join(5.0)


# ---------------------------------------------------------------------------
# randomized multi-seed churn (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_churn_multi_seed_exactly_once(model, seed):
    """Seeded-random kills under load: whatever fires, every stream
    stays bit-identical to the fault-free single engine (zero
    duplicated, zero lost tokens) and the fleet leaks nothing.
    ``times=2`` over 3 replicas guarantees a survivor; quarantined
    replicas never step again, so both firings land on live prey."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 64, (int(rng.integers(4, 24)),))
               .astype(np.int32) for _ in range(8)]
    budgets = [int(rng.integers(4, 10)) for _ in range(8)]

    ref_eng = ServingEngine(model, **KW)
    rhs = [ref_eng.submit(p, b, seed=1000 + i)
           for i, (p, b) in enumerate(zip(prompts, budgets))]
    ref_eng.run()
    ref = [list(h.output_tokens) for h in rhs]
    _engine_clean(ref_eng)

    inj = faults.install(seed)
    inj.arm("serving.step.raise", prob=0.08, times=2,
            message=f"chaos churn seed={seed}")
    fleet = FleetRouter(model=model, decode_replicas=3, engine_kw=KW,
                        seed=seed, watchdog={})
    fhs = [fleet.submit(p, b, seed=1000 + i)
           for i, (p, b) in enumerate(zip(prompts, budgets))]
    fleet.run()

    assert [list(h.output_tokens) for h in fhs] == ref, \
        f"seed {seed}: churn changed a token stream"
    assert all(h.done for h in fhs)
    assert len(fleet.recoveries) == _fired(inj, "serving.step.raise")
    lk = fleet.leak_check()
    assert lk["clean"], lk
