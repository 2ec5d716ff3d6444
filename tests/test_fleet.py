"""Disaggregated multi-replica serving fleet tests (ISSUE 18).

KV hand-off blob invariants (bit-parity round-trips on fp32 and int8
pools, pool conservation, no stale page-table aliasing, geometry/quant
validation before allocation, warmable migration buckets), rendezvous
+ P2C routing properties, merged-sample fleet percentiles vs the
averaged-p99 fallacy, deterministic per-request traffic seeding (the
1-vs-N replay property), host-ring LRU byte-cap behavior, and
abort/drain hygiene: zero leaked pages/slots/spans across fleet churn.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=96,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 64, (int(rng.integers(4, 28)),))
            .astype(np.int32) for i in range(n)]


# ---------------------------------------------------------------------------
# export_slot / import_slot blob invariants (kv_cache)
# ---------------------------------------------------------------------------

class TestHandoffBlob:
    def _cache(self, quant=None, num_pages=17, max_slots=4,
               pages_per_seq=6, page_size=8):
        from paddle_tpu.inference.kv_cache import PagedKVCache

        return PagedKVCache(num_layers=2, num_kv_heads=2, head_dim=4,
                            num_pages=num_pages, page_size=page_size,
                            max_slots=max_slots,
                            pages_per_seq=pages_per_seq, quant=quant)

    def _fill(self, cache, seed):
        """Distinct random content in every pool element so a gather
        from the wrong page can never pass a bit-compare."""
        rng = np.random.default_rng(seed)

        def rnd(a):
            if a.dtype == jnp.int8:
                return jnp.asarray(rng.integers(
                    -127, 128, a.shape).astype(np.int8))
            return jnp.asarray(
                rng.standard_normal(a.shape).astype(a.dtype))

        cache.k_layers = [rnd(a) for a in cache.k_layers]
        cache.v_layers = [rnd(a) for a in cache.v_layers]
        if cache.quant == "int8":
            cache.k_scales = [rnd(a) for a in cache.k_scales]
            cache.v_scales = [rnd(a) for a in cache.v_scales]

    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_round_trip_bit_parity(self, quant):
        src = self._cache(quant=quant)
        self._fill(src, seed=1)
        slot = src.allocate(21)            # 3 pages
        src._host("seq_lens")[slot] = 21
        blob = src.export_slot(slot)
        assert blob["seq_len"] == 21 and blob["pages"] == 3

        dst = self._cache(quant=quant)
        slot2 = dst.import_slot(blob)
        blob2 = dst.export_slot(slot2)
        for key in (("k", "v") if quant is None else
                    ("k", "v", "k_scales", "v_scales")):
            for a, b in zip(blob[key], blob2[key]):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(a, b)

    def test_pool_conservation_through_moves(self):
        c = self._cache()
        self._fill(c, seed=2)
        slots = [c.allocate(17) for _ in range(3)]
        for s in slots:
            c._host("seq_lens")[s] = 17
        blobs = [c.export_slot(s) for s in slots]
        for s in slots:
            c.free(s)
        landed = [c.import_slot(b) for b in blobs]
        ps = c.pool_stats()
        assert ps["used_pages"] + ps["free_pages"] == ps["total_pages"]
        assert ps["used_pages"] == 3 * 3      # 3 slots x 3 pages
        for s in landed:
            c.free(s)
        ps = c.pool_stats()
        assert ps["used_pages"] == 0 and ps["slot_pages"] == {}
        assert ps["free_pages"] == ps["total_pages"]

    def test_import_never_aliases_neighbour_pages(self):
        """Landing a blob must not disturb a resident neighbour: its
        page-table row and its re-exported bits stay identical."""
        c = self._cache()
        self._fill(c, seed=3)
        resident = c.allocate(30)          # 4 pages
        c._host("seq_lens")[resident] = 30
        before_tbl = c.page_tables[resident].copy()
        before = c.export_slot(resident)

        donor = self._cache()
        self._fill(donor, seed=4)
        d = donor.allocate(21)
        donor._host("seq_lens")[d] = 21
        c.import_slot(donor.export_slot(d))

        np.testing.assert_array_equal(c.page_tables[resident],
                                      before_tbl)
        after = c.export_slot(resident)
        for key in ("k", "v"):
            for a, b in zip(before[key], after[key]):
                np.testing.assert_array_equal(a, b)

    def test_geometry_and_quant_mismatch_raise_before_alloc(self):
        src = self._cache()
        self._fill(src, seed=5)
        s = src.allocate(21)
        src._host("seq_lens")[s] = 21
        blob = src.export_slot(s)

        other_geom = self._cache(page_size=4, num_pages=33,
                                 pages_per_seq=12)
        with pytest.raises(ValueError):
            other_geom.import_slot(blob)
        other_quant = self._cache(quant="int8")
        with pytest.raises(ValueError):
            other_quant.import_slot(blob)
        # rejected imports allocated nothing
        for c in (other_geom, other_quant):
            ps = c.pool_stats()
            assert ps["used_pages"] == 0 and ps["slot_pages"] == {}

    def test_migration_buckets_cover_reachable_widths(self):
        """Every page count one slot can hold maps to a bucket the
        warmup can actually exercise (an allocatable seq_len exists) —
        the property that keeps hand-offs compile-free mid-stream."""
        for kw in (dict(), dict(num_pages=225, pages_per_seq=28),
                   dict(num_pages=9, pages_per_seq=8)):
            c = self._cache(**kw)
            buckets = c.migration_buckets()
            cap = min(c.num_pages - 1, c.pages_per_seq)
            assert buckets[-1] == cap
            for n in range(1, cap + 1):
                w = c.migration_bucket(n)
                assert w >= n and w in buckets, (n, w, buckets)
            for w in buckets:
                lo = w // 2
                n = next((n for n in range(w, lo, -1)
                          if c.can_allocate((n - 1) * c.page_size + 1)),
                         None)
                assert n is not None, (w, buckets)


# ---------------------------------------------------------------------------
# routing: rendezvous affinity + P2C
# ---------------------------------------------------------------------------

class TestReplicaRouter:
    def test_affinity_remaps_only_lost_replicas_sessions(self):
        from paddle_tpu.serving.router import ReplicaRouter

        names = [f"d{i}" for i in range(4)]
        r = ReplicaRouter(names, seed=0)
        sessions = [f"s{i}" for i in range(200)]
        before = {s: r.pick(lambda _: 0, session=s) for s in sessions}
        r.remove("d2")
        after = {s: r.pick(lambda _: 0, session=s) for s in sessions}
        moved = [s for s in sessions if before[s] != after[s]]
        # EXACTLY the sessions that lived on the removed replica move
        assert set(moved) == {s for s in sessions
                              if before[s] == "d2"}
        # and that is ~1/N of them (loose statistical band)
        assert 0.10 <= len(moved) / len(sessions) <= 0.42

        # adding a replica only pulls sessions ONTO the newcomer
        r2 = ReplicaRouter(names, seed=0)
        r2.add("d4")
        grown = {s: r2.pick(lambda _: 0, session=s) for s in sessions}
        for s in sessions:
            if grown[s] != before[s]:
                assert grown[s] == "d4", (s, before[s], grown[s])

    def test_p2c_prefers_shorter_queue(self):
        from paddle_tpu.serving.router import ReplicaRouter

        r = ReplicaRouter(["a", "b"], seed=1)
        load = {"a": 10, "b": 1}
        for _ in range(50):
            assert r.pick(lambda n: load[n]) == "b"
        # and under many replicas the hottest one is rarely picked
        r = ReplicaRouter(["a", "b", "c", "d"], seed=2)
        load = {"a": 100, "b": 1, "c": 1, "d": 1}
        picks = [r.pick(lambda n: load[n]) for _ in range(200)]
        assert picks.count("a") == 0

    def test_p2c_seeded_replay(self):
        from paddle_tpu.serving.router import ReplicaRouter

        load = dict(a=3, b=1, c=2, d=5)
        r1 = ReplicaRouter(list(load), seed=7)
        r2 = ReplicaRouter(list(load), seed=7)
        assert [r1.pick(load.get) for _ in range(64)] == \
            [r2.pick(load.get) for _ in range(64)]


# ---------------------------------------------------------------------------
# fleet percentiles: merged samples, never averaged p99s
# ---------------------------------------------------------------------------

class TestMergedPercentiles:
    def _hist(self, name, samples, window=4096):
        from paddle_tpu.observability import MetricsRegistry

        h = MetricsRegistry().histogram(name, window=window)
        h.extend(samples)
        return h

    def test_slow_minority_tail_survives_merge(self):
        """One slow replica's tail must dominate the fleet p99 even
        when a fast replica has 99x the traffic — averaging per-replica
        p99s would halve it."""
        from paddle_tpu.observability import merge_histograms

        fast = self._hist("fast", [0.001] * 990)
        slow = self._hist("slow", [1.0] * 30)
        merged = merge_histograms([fast, slow], name="fleet")
        avg_of_p99 = (fast.percentile(99) + slow.percentile(99)) / 2
        assert merged.percentile(99) == pytest.approx(1.0)
        assert avg_of_p99 == pytest.approx(0.5005, rel=1e-2)

    def test_tiny_outlier_replica_does_not_inflate(self):
        """Opposite skew: 10 slow samples in 10_000 are NOT the fleet
        p99, but averaging per-replica p99s says 0.5s."""
        from paddle_tpu.observability import merge_histograms

        fast = self._hist("fast", [0.001] * 9990, window=16384)
        slow = self._hist("slow", [1.0] * 10)
        merged = merge_histograms([fast, slow], name="fleet",
                                  window=16384)
        assert merged.percentile(99) == pytest.approx(0.001)
        assert merged.percentile(50) == pytest.approx(0.001)

    def test_merge_folds_lifetime_counts(self):
        from paddle_tpu.observability import merge_histograms

        a = self._hist("a", [1.0, 2.0, 3.0])
        b = self._hist("b", [4.0])
        m = merge_histograms([a, b])
        snap = m.snapshot()
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# per-request seeds: the same requests give the same tokens on 1 and 2
# replicas
# ---------------------------------------------------------------------------

class TestRequestSeeding:
    def test_one_vs_two_replica_streams_identical(self, model):
        """The property per-request seeds exist for: the SAME ten
        requests served by 1 and by 2 replicas yield bit-identical
        tokens per request, sampled, whatever the router did."""
        from paddle_tpu.serving import FleetRouter

        kw = dict(max_slots=4, max_len=64, page_size=8, chunk_size=16,
                  do_sample=True, temperature=0.9, top_k=8)
        rng = np.random.default_rng(13)
        requests = [
            (rng.integers(1, 64, (int(rng.integers(4, 21)),))
             .astype(np.int32),
             int(rng.integers(4, 13)), int(rng.integers(0, 2**31 - 1)))
            for _ in range(10)]

        def serve(n):
            fleet = FleetRouter(model=model, decode_replicas=n,
                                engine_kw=kw, seed=3)
            hs = [fleet.submit(prompt, budget, seed=seed)
                  for prompt, budget, seed in requests]
            fleet.run()
            lk = fleet.leak_check()
            assert lk["clean"], lk
            return [list(h.output_tokens) for h in hs]

        assert serve(1) == serve(2)


# ---------------------------------------------------------------------------
# host ring: byte-capped LRU parking lot
# ---------------------------------------------------------------------------

class TestHostKVRing:
    def _blob(self, nbytes):
        return {"nbytes": int(nbytes)}

    def test_lru_drop_on_overflow(self):
        from paddle_tpu.serving import HostKVRing

        ring = HostKVRing(capacity_mb=1.0)     # 1 MiB
        kb512 = 512 * 1024
        ring.put(1, self._blob(kb512), 7)
        ring.put(2, self._blob(kb512), 8)
        assert len(ring) == 2 and ring.bytes == 2 * kb512
        ring.put(3, self._blob(kb512), 9)      # overflows: rid 1 drops
        stats = ring.stats()
        assert stats["drops"] == 1 and len(ring) == 2
        assert ring.take(1) is None
        blob, tok = ring.take(3)
        assert tok == 9
        assert ring.bytes == kb512

    def test_put_same_rid_replaces_not_double_counts(self):
        from paddle_tpu.serving import HostKVRing

        ring = HostKVRing(capacity_mb=1.0)
        ring.put(1, self._blob(1000), 1)
        ring.put(1, self._blob(2000), 2)
        assert ring.bytes == 2000 and len(ring) == 1
        blob, tok = ring.take(1)
        assert blob["nbytes"] == 2000 and tok == 2
        assert ring.bytes == 0

    def test_oversized_blob_never_wedges(self):
        from paddle_tpu.serving import HostKVRing

        ring = HostKVRing(capacity_mb=0.001)    # ~1 KB
        ring.put(1, self._blob(10_000), 1)      # larger than the cap
        assert len(ring) == 0 and ring.bytes == 0
        assert ring.stats()["drops"] == 1


# ---------------------------------------------------------------------------
# abort/drain hygiene across fleet churn
# ---------------------------------------------------------------------------

class TestFleetChurnHygiene:
    def test_abort_then_drain_no_orphans_no_leaks(self, model):
        from paddle_tpu.serving import FleetRouter

        kw = dict(max_slots=3, max_len=64, page_size=8, chunk_size=8)
        fleet = FleetRouter(model=model, decode_replicas=2,
                            prefill_replicas=1, engine_kw=kw, seed=5)
        hs = [fleet.submit(p, 8, seed=40 + i)
              for i, p in enumerate(_prompts(6, seed=6))]
        for _ in range(6):
            fleet.step()
        # mid-flight abort on every replica: residents re-queue, then
        # the drain must close every span and return every page
        for r in fleet._replicas:
            r.engine.scheduler.abort_all()
        fleet.run()
        assert all(h.done for h in hs)
        lk = fleet.leak_check()
        assert lk["clean"], lk
        for name, rep in lk["replicas"].items():
            assert rep["open_spans"] == 0, (name, rep)
            assert rep["orphan_spans"] == 0, (name, rep)
            assert rep["pending_imports"] == 0, (name, rep)

    def test_disagg_handoff_leaves_prefill_clean(self, model):
        from paddle_tpu.serving import FleetRouter

        kw = dict(max_slots=3, max_len=64, page_size=8, chunk_size=8)
        fleet = FleetRouter(model=model, decode_replicas=1,
                            prefill_replicas=1, engine_kw=kw)
        hs = [fleet.submit(p, 6, seed=i)
              for i, p in enumerate(_prompts(5, seed=9))]
        fleet.run()
        assert all(h.done for h in hs)
        snap = fleet.metrics_snapshot()
        assert snap["replicas"]["d0"]["prefill_chunks"] == 0
        assert snap["replicas"]["p0"]["prefill_chunks"] > 0
        lk = fleet.leak_check()
        assert lk["clean"], lk
