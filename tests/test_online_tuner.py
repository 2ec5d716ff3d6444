"""Online knob tuner safety envelope (ISSUE 17): every move is one
bounded step, hysteresis demands consecutive agreeing intervals,
cooldown holds after a move, the chunk cap only walks the engine's
compiled bucket ladder, the retrace-triggering knob (decode_burst)
actuates ONLY through the safe-boundary rebuild hook and never under
speculative decoding, and every decision is recorded with provenance.

These run against a FakeEngine so the control law is tested exhaustively
in milliseconds; the last test closes the loop on a real engine (token
parity through the tuner's moves).
"""
import pytest

from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.serving.tuner import OnlineTuner, TunerLimits


class FakeScheduler:
    def __init__(self, wm=2):
        self.admit_watermark = wm

    def _watermark(self):
        return self.admit_watermark


class FakeMetrics:
    def __init__(self):
        self.registry = MetricsRegistry()
        self.queue_depth = 0
        self.preemptions = 0


class FakeSLO:
    def __init__(self):
        self.ttft = 0.0
        self.itl = 0.0

    def snapshot(self):
        return {
            "ttft_p95": {"metric": "ttft_s", "burn_rate": self.ttft},
            "itl_p95": {"metric": "itl_s", "burn_rate": self.itl},
        }


class FakeCache:
    free_page_count = 8


class FakeEngine:
    """Just the surface OnlineTuner reads/actuates."""

    def __init__(self, chunk_size=64, chunk_buckets=(16, 32, 64),
                 decode_burst=1, prefill_chunks=1):
        self.metrics = FakeMetrics()
        self.slo = FakeSLO()
        self.scheduler = FakeScheduler()
        self.cache = FakeCache()
        self.chunk_buckets = tuple(chunk_buckets)
        self.chunk_size = chunk_size
        self.max_slots = 4
        self.decode_burst = decode_burst
        self.prefill_chunks_per_step = prefill_chunks
        self.spec_step = None
        self.rebuilds = []          # every safe-boundary rebuild

    def set_decode_burst(self, k):
        self.rebuilds.append(int(k))
        self.decode_burst = int(k)


def mk(eng=None, **kw):
    eng = eng or FakeEngine()
    kw.setdefault("interval", 1)
    kw.setdefault("hysteresis", 2)
    kw.setdefault("cooldown", 0)
    return eng, OnlineTuner(eng, **kw)


class TestControlLaw:
    def test_quiet_signals_never_move(self):
        eng, t = mk()
        for _ in range(20):
            eng.metrics.queue_depth = 1     # not calm, not pressured
            assert t.evaluate() is None
        assert t.decisions == [] and t.evaluations == 20

    def test_hysteresis_needs_consecutive_agreement(self):
        eng, t = mk(hysteresis=3)
        eng.slo.ttft = 2.0
        assert t.evaluate() is None
        assert t.evaluate() is None
        rec = t.evaluate()                  # third agreeing interval
        assert rec and rec["knob"] == "prefill_chunks_per_step"
        assert rec["from"] == 1 and rec["to"] == 2

    def test_competing_signals_reset_each_other(self):
        eng, t = mk(hysteresis=2)
        for _ in range(4):                  # alternate ttft / itl burn
            eng.slo.ttft, eng.slo.itl = 2.0, 0.0
            assert t.evaluate() is None
            eng.slo.ttft, eng.slo.itl = 0.0, 2.0
            assert t.evaluate() is None
        assert t.decisions == []            # two half-streaks, no move

    def test_cooldown_holds_after_a_move(self):
        eng, t = mk(hysteresis=1, cooldown=2)
        eng.slo.ttft = 2.0
        assert t.evaluate() is not None     # move
        assert t.evaluate() is None         # hold 1
        assert t.evaluate() is None         # hold 2
        assert t.evaluate() is not None     # free again
        assert len(t.decisions) == 2

    def test_on_step_evaluates_every_interval(self):
        eng, t = mk(interval=4, hysteresis=1)
        eng.slo.ttft = 2.0
        moves = [t.on_step() for _ in range(8)]
        assert t.evaluations == 2
        assert sum(m is not None for m in moves) == 2

    def test_every_move_is_one_bounded_step(self):
        eng, t = mk(hysteresis=1)
        eng.slo.ttft = 2.0
        eng.metrics.queue_depth = 99
        for _ in range(50):
            t.evaluate()
        lad = t.limits.chunk_ladder
        for d in t.decisions:
            if d["knob"] == "chunk_size":   # adjacent rungs only
                i, j = lad.index(d["from"]), lad.index(d["to"])
                assert abs(i - j) == 1
            else:
                assert abs(d["to"] - d["from"]) == 1
        # and the bounds held under sustained pressure
        assert eng.prefill_chunks_per_step <= t.limits.max_prefill_chunks
        assert eng.chunk_size in lad
        assert eng.scheduler.admit_watermark >= t.limits.min_watermark


class TestChunkLadder:
    def test_chunk_moves_stay_on_compiled_buckets(self):
        eng = FakeEngine(chunk_size=16)
        eng, t = mk(eng, hysteresis=1,
                    limits=TunerLimits(eng, max_prefill_chunks=1))
        eng.slo.ttft = 2.0
        seen = [eng.chunk_size]
        for _ in range(20):
            t.evaluate()
            seen.append(eng.chunk_size)
        assert set(seen) <= set(eng.chunk_buckets)
        assert eng.chunk_size == 64         # walked 16 -> 32 -> 64

    def test_off_ladder_value_never_proposed(self):
        eng = FakeEngine(chunk_size=64)     # already at the top rung
        eng, t = mk(eng, hysteresis=1,
                    limits=TunerLimits(eng, max_prefill_chunks=1))
        eng.slo.ttft = 2.0
        eng.cache.free_page_count = 0       # block the watermark fallback
        for _ in range(10):
            t.evaluate()
        assert all(d["knob"] != "chunk_size" for d in t.decisions)


class TestDecodeBurst:
    def _itl_pressure(self, eng):
        eng.slo.itl = 2.0
        eng.metrics.queue_depth = 0

    def test_itl_burn_raises_burst_via_safe_boundary_rebuild(self):
        eng, t = mk(hysteresis=1)
        self._itl_pressure(eng)
        for _ in range(3):
            t.evaluate()
        # ONLY through set_decode_burst (the rebuild hook), one step up
        assert eng.rebuilds == [2, 3, 4]
        assert [d["knob"] for d in t.decisions] == ["decode_burst"] * 3

    def test_burst_blocked_under_speculative_decoding(self):
        eng, t = mk(hysteresis=1)
        eng.spec_step = object()            # spec unrolls its own k
        self._itl_pressure(eng)
        for _ in range(5):
            t.evaluate()
        assert eng.rebuilds == []
        assert all(d["knob"] != "decode_burst" for d in t.decisions)

    def test_tune_decode_burst_false_is_host_only(self):
        eng, t = mk(hysteresis=1, tune_decode_burst=False)
        self._itl_pressure(eng)
        for _ in range(5):
            t.evaluate()
        assert eng.rebuilds == []

    def test_calm_drifts_burst_back_down(self):
        eng = FakeEngine(decode_burst=3)
        eng, t = mk(eng, hysteresis=2)
        for _ in range(6):                  # burns 0, queue empty
            t.evaluate()
        assert eng.decode_burst == 1        # 3 -> 2 -> 1, then floor
        assert eng.rebuilds == [2, 1]


class TestWatermark:
    def test_preemption_churn_raises_watermark(self):
        eng, t = mk(hysteresis=2)
        for _ in range(4):
            eng.metrics.preemptions += 1    # churn every interval
            t.evaluate()
        assert eng.scheduler.admit_watermark == 4      # 2 -> 3 -> 4
        assert all(d["knob"] == "admit_watermark" and
                   d["to"] == d["from"] + 1 for d in t.decisions)

    def test_deep_queue_with_slack_admits_sooner(self):
        eng = FakeEngine(chunk_size=64)
        eng, t = mk(eng, hysteresis=1,
                    limits=TunerLimits(eng, max_prefill_chunks=1))
        eng.metrics.queue_depth = 99        # ttft path, ladder at top
        for _ in range(5):
            t.evaluate()
        drops = [d for d in t.decisions if d["knob"] == "admit_watermark"]
        assert drops and all(d["to"] == d["from"] - 1 for d in drops)
        assert eng.scheduler.admit_watermark >= t.limits.min_watermark


class TestProvenance:
    def test_decisions_carry_reason_signals_and_gauges(self):
        eng, t = mk(hysteresis=1)
        eng.slo.ttft = 2.0
        rec = t.evaluate()
        assert set(rec) == {"knob", "from", "to", "reason", "signals",
                            "step"}
        assert "ttft" in rec["reason"]
        assert rec["signals"]["ttft_burn"] == 2.0
        reg = eng.metrics.registry
        assert reg.gauge("tuner.moves").value == len(t.decisions) == 1
        assert reg.gauge("tuner.prefill_chunks_per_step").value == 2

    def test_decisions_ring_is_bounded(self):
        eng, t = mk(hysteresis=1)
        for i in range(300):                # alternate churn up forever
            eng.metrics.preemptions += 1
            t.limits.max_watermark = 10**9
            t.evaluate()
        assert len(t.decisions) <= 256


class TestEngineDefaultOff:
    def test_engine_without_tuner_has_no_controller(self):
        # tuner OFF is the default: the engine ctor leaves .tuner None
        # and step() never calls on_step — PR-16 behavior verbatim.
        import inspect

        from paddle_tpu.serving.engine import ServingEngine

        sig = inspect.signature(ServingEngine.__init__)
        assert sig.parameters["tuner"].default is False


def test_real_engine_keeps_token_parity_through_tuner_moves():
    """Every knob shapes the schedule, never the numerics: a real engine
    with the tuner on emits, for each request, the tokens plain
    `generate` emits; each decision is one bounded step on a known knob;
    no page leaks."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2,
        num_attention_heads=4, max_position_embeddings=96,
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0))
    m.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 64, (n,)).astype(np.int32)
               for n in (5, 11, 19, 8, 14, 26, 7, 12)]
    eng = ServingEngine(
        m, max_slots=3, max_len=64, page_size=8, chunk_size=8, tuner=True,
        tuner_kw={"interval": 4, "hysteresis": 2, "cooldown": 1}).warmup()
    hs = [eng.submit(p, 6 + (i % 3) * 3) for i, p in enumerate(prompts)]
    eng.run(max_steps=5000)
    for h in hs:
        ref = m.generate(np.asarray(h.request.prompt)[None],
                         max_new_tokens=h.request.max_new_tokens,
                         use_cache="paged")
        assert np.asarray(ref._data)[0].tolist() == h.output_tokens, \
            f"rid {h.request.rid} diverged"
    assert eng.tuner.evaluations > 0
    for d in eng.tuner.decisions:
        assert d["knob"] in ("admit_watermark", "prefill_chunks_per_step",
                             "chunk_size", "decode_burst"), d
        if d["knob"] != "chunk_size":
            assert abs(d["to"] - d["from"]) == 1, d
    leaks = eng.leak_check()
    assert leaks["free_pages"] == leaks["total_pages"], leaks
