"""Device-memory observability (ISSUE 14): compiled-step HBM profiles
on every jitted step path, live-buffer attribution that sums to the
`jax.live_arrays()` total, the sharded-vs-replicated storage receipt,
OOM forensics through the flight recorder, `/memz`, page-pool stats,
and the zero-retrace guarantee of the instrumentation itself."""
import gc
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu import observability as obs
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
)

TINY = dict(vocab_size=96, hidden_size=32, num_layers=4,
            num_attention_heads=2, max_position_embeddings=16,
            hidden_dropout_prob=0.0, attention_dropout_prob=0.0)


def _batch(rows=8, seed=0):
    rng = np.random.default_rng(seed)
    ids = paddle.to_tensor(
        rng.integers(0, TINY["vocab_size"], (rows, 16)), dtype="int64")
    labels = paddle.to_tensor(
        rng.integers(0, TINY["vocab_size"], (rows, 16)), dtype="int64")
    return ids, labels


def _fused_step(seed=0):
    cfg = GPTConfig(**TINY, scan_layers=True)
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    return GPTPretrainingCriterion(), model, opt


def _assert_profile_sane(prof):
    s = prof.summary()
    assert s["peak_bytes"] and s["peak_bytes"] > 0, s
    # the arg+out+temp-alias identity is exact only when the peak was
    # DERIVED from those stats; a jaxlib-reported scheduled peak may
    # sit below the sum (not all temps live at once)
    if s["peak_source"] == "derived":
        assert s["peak_bytes"] == (s["argument_bytes"]
                                   + s["output_bytes"] + s["temp_bytes"]
                                   - (s["alias_bytes"] or 0)), s
    else:
        assert s["peak_source"] == "reported", s
        assert s["peak_bytes"] <= (s["argument_bytes"]
                                   + s["output_bytes"]
                                   + s["temp_bytes"]), s
    assert prof.top_buffers, "no buffers parsed from the compiled HLO"
    sizes = [b["bytes"] for b in prof.top_buffers]
    assert sizes == sorted(sizes, reverse=True), sizes
    assert prof.largest_buffer_bytes == sizes[0]
    for b in prof.top_buffers:
        assert b["bytes"] > 0 and b["count"] >= 1
        assert b["dtype"] and b["shape"].startswith("[")
        assert b["op"], b
    return s


class TestHloBufferParse:
    def test_parse_shapes_ops_and_provenance(self):
        text = (
            'ENTRY %main (p0: f32[8,16]) -> f32[8,16] {\n'
            '  %p0 = f32[8,16]{1,0} parameter(0), '
            'metadata={op_name="x"}\n'
            '  %big = bf16[128,256]{1,0} dot(f32[8,16]{1,0} %p0), '
            'metadata={op_name="jit(step)/dot_general"}\n'
            '  ROOT %t = (f32[8,16]{1,0}, s32[4]{0}) tuple(%p0, %p0)\n'
            '}\n')
        bufs = obs.parse_hlo_buffers(text, top_k=None)
        by_op = {b["op"]: b for b in bufs}
        assert by_op["dot"]["bytes"] == 128 * 256 * 2
        assert by_op["dot"]["op_name"] == "jit(step)/dot_general"
        assert by_op["parameter"]["bytes"] == 8 * 16 * 4
        # tuple result: one buffer PER element
        assert by_op["tuple"]["dtype"] in ("f32", "s32")
        assert sum(b["count"] for b in bufs
                   if b["name"] == "t") == 2
        assert bufs[0]["bytes"] == max(b["bytes"] for b in bufs)

    def test_duplicate_buffers_collapse_with_count(self):
        line = ('  %a.1 = f32[64]{0} add(f32[64]{0} %x, f32[64]{0} %y), '
                'metadata={op_name="jit(f)/add"}\n')
        bufs = obs.parse_hlo_buffers("x = 1\n" + line * 5, top_k=None)
        assert len(bufs) == 1 and bufs[0]["count"] == 5

    def test_operand_shapes_are_not_result_buffers(self):
        text = '  %d = f32[2,2]{1,0} dot(f32[999,999]{1,0} %huge)\n'
        bufs = obs.parse_hlo_buffers(text, top_k=None)
        assert len(bufs) == 1 and bufs[0]["bytes"] == 16

    def test_dtype_widths(self):
        from paddle_tpu.observability.memory import _dtype_bytes

        assert _dtype_bytes("f32") == 4 and _dtype_bytes("bf16") == 2
        assert _dtype_bytes("pred") == 1 and _dtype_bytes("s64") == 8
        assert _dtype_bytes("u8") == 1


class TestCompiledProfiles:
    def test_eager_train_step_profile(self):
        from paddle_tpu.jit import TrainStep

        crit, _, _ = _fused_step()
        cfg = GPTConfig(**TINY, scan_layers=False)
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        opt = popt.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = TrainStep(m, lambda mm, a, b: crit(mm(a), b), opt)
        ids, labels = _batch()
        with pytest.raises(RuntimeError, match="built step"):
            step.memory_profile(ids, labels)
        step(ids, labels)
        prof = step.memory_profile(ids, labels)
        s = _assert_profile_sane(prof)
        # params + opt state dominate the arguments
        n_param_bytes = sum(int(np.prod(p.shape)) * 4
                            for p in m.parameters())
        assert s["argument_bytes"] >= 3 * n_param_bytes
        # gauges published under the step-class name
        g = obs.registry().get("mem.compiled.TrainStep.peak_bytes")
        assert g is not None and g.value == s["peak_bytes"]

    def test_fused_scan_profile_and_zero_retrace(self):
        from paddle_tpu.jit import FusedScanTrainStep

        crit, model, opt = _fused_step()
        step = FusedScanTrainStep(model, opt, criterion=crit)
        ids, labels = _batch()
        step(ids, labels)
        prof = step.memory_profile(ids, labels)
        _assert_profile_sane(prof)
        # the AOT profile must not add executables or sentinel events
        step(ids, labels)
        st = step.retrace_stats()
        assert st["signatures"] == 1 and st["unexpected"] == 0, st
        if hasattr(step._jitted, "_cache_size"):
            assert step._jitted._cache_size() == 1

    def test_sharded_scan_profile(self):
        import jax
        from jax.sharding import Mesh

        from paddle_tpu.distributed import env as denv
        from paddle_tpu.jit import ShardedFusedScanTrainStep

        crit, model, opt = _fused_step()
        mesh = Mesh(np.asarray(jax.devices("cpu")[:8]), ("sharding",))
        denv.set_mesh(mesh)
        step = ShardedFusedScanTrainStep(model, opt, criterion=crit,
                                         mesh=mesh, axis="sharding")
        ids, labels = _batch()
        step(ids, labels)
        prof = step.memory_profile(ids, labels)
        _assert_profile_sane(prof)
        # sharded storage: a scrape-time owner walk must not gather
        from paddle_tpu.jit.sharded_scan import _STALE, _data_slot

        rep = obs.live_registry().report(publish=False)
        assert rep["owners"].get("params.scan_shards", 0) > 0, \
            rep["owners"]
        slot = _data_slot()
        assert all(slot.__get__(p) is _STALE for _, p in step._s_train)
        step(ids, labels)
        assert step.retrace_stats()["signatures"] == 1

    def test_pipeline_scan_profile(self):
        import jax

        from paddle_tpu.distributed import env as denv
        from paddle_tpu.jit.pipeline_step import PipelineScanTrainStep

        crit, model, opt = _fused_step()
        mesh = denv.build_mesh({"dp": 2, "pp": 2},
                               devices=jax.devices("cpu")[:4])
        denv.set_mesh(mesh)
        step = PipelineScanTrainStep(model, opt, criterion=crit,
                                     mesh=mesh, axis="dp",
                                     pp_axis="pp", num_micro=2)
        ids, labels = _batch(rows=4)    # local batch 2 = num_micro
        step(ids, labels)
        prof = step.memory_profile(ids, labels)
        _assert_profile_sane(prof)

    def test_decode_and_serving_step_profiles(self):
        from paddle_tpu.jit.decode_step import GenerationEngine
        from paddle_tpu.serving import ServingEngine

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4,
                        max_position_embeddings=64,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        for kind in ("dense", "paged"):
            eng = GenerationEngine(m, kind=kind, batch=2, max_len=32)
            eng.generate(np.ones((2, 4), np.int64), 2)
            tc = eng.decode_step.trace_count
            prof = eng.memory_profile()
            _assert_profile_sane(prof)
            # a profile is AOT analysis on a FRESH jit copy: the live
            # decode executable and its trace counter are untouched
            assert eng.decode_step.trace_count == tc
        srv = ServingEngine(m, max_slots=2, max_len=32, page_size=8,
                            chunk_size=8)
        srv.submit(np.ones((4,), np.int32), 3)
        srv.run(max_steps=500)
        prof = srv.memory_profile()
        _assert_profile_sane(prof)
        g = obs.registry().get("mem.compiled.ServeDecodeStep.peak_bytes")
        assert g is not None and g.value == prof.peak_bytes


class TestLiveAttribution:
    def test_owners_sum_to_live_total(self):
        from paddle_tpu.jit import FusedScanTrainStep

        crit, model, opt = _fused_step(seed=3)
        step = FusedScanTrainStep(model, opt, criterion=crit)
        ids, labels = _batch()
        step(ids, labels)
        rep = obs.live_buffer_report()
        assert (sum(rep["owners"].values()) + rep["untagged_bytes"]
                == rep["total_bytes"]), rep
        n_param_bytes = sum(int(np.prod(p.shape)) * 4
                            for p in model.parameters())
        assert rep["owners"]["params"] >= n_param_bytes
        assert rep["owners"]["opt_state"] >= 2 * n_param_bytes
        # gauges land on scrape
        assert obs.registry().get("mem.live.total_bytes").value \
            == rep["total_bytes"]
        assert obs.registry().get("mem.live.params").value \
            == rep["owners"]["params"]

    def test_replication_counts_device_resident_bytes(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.observability.memory import device_bytes

        mesh = Mesh(np.asarray(jax.devices("cpu")[:8]), ("dp",))
        sharded = jax.device_put(
            jnp.zeros((8, 4), jnp.float32), NamedSharding(mesh, P("dp")))
        replicated = jax.device_put(
            jnp.zeros((8, 4), jnp.float32), NamedSharding(mesh, P()))
        assert device_bytes(sharded) == 8 * 4 * 4
        assert device_bytes(replicated) == 8 * 4 * 4 * 8

    def test_dead_producer_drops_out(self):
        import jax.numpy as jnp

        class Owner:
            def __init__(self):
                self.arrs = [jnp.ones((64,), jnp.float32)]

            def _mem_owners(self):
                return {"ephemeral_owner": self.arrs}

        o = Owner()
        obs.live_registry().track(o)
        obs.live_registry().track(o)        # idempotent
        rep = obs.live_registry().report(publish=False)
        assert rep["owners"].get("ephemeral_owner") == 256, rep
        del o
        gc.collect()
        rep = obs.live_registry().report(publish=False)
        assert "ephemeral_owner" not in rep["owners"]

    def test_vanished_owner_gauge_zeroed(self):
        import jax.numpy as jnp

        class Owner:
            def __init__(self):
                self.arrs = [jnp.ones((64,), jnp.float32)]

            def _mem_owners(self):
                return {"vanishing_owner": self.arrs}

        o = Owner()
        obs.live_registry().track(o)
        obs.live_buffer_report()
        g = obs.registry().get("mem.live.vanishing_owner")
        assert g is not None and g.value == 256
        del o
        gc.collect()
        obs.live_buffer_report()
        # phantom bytes must not survive on the scrape surface
        assert g.value == 0

    def test_prefetch_ring_tagged(self):
        from paddle_tpu.io.device_prefetcher import DevicePrefetcher

        batches = [(np.ones((4, 16), np.int64),
                    np.ones((4, 16), np.int64)) for _ in range(4)]
        pf = DevicePrefetcher(iter(batches), depth=2, to_tensor=False)
        try:
            next(iter(pf))
            import time

            deadline = time.time() + 5
            rep = obs.live_registry().report(publish=False)
            while ("prefetch_ring" not in rep["owners"]
                   and time.time() < deadline):
                time.sleep(0.02)    # producer thread fills the ring
                rep = obs.live_registry().report(publish=False)
            assert rep["owners"].get("prefetch_ring", 0) > 0, \
                rep["owners"]
        finally:
            pf.close()


class TestStorageReceipt:
    def test_sharded_vs_replicated_profile_delta(self):
        # the PR-11 receipt through the ONE profile implementation:
        # probe HLO max buffer 49,984 elems (sharded) vs 65,536
        # (replicated)
        from paddle_tpu.jit.sharded_scan import build_probe_lowered
        from paddle_tpu.observability.memory import (
            CompiledMemoryProfile,
        )

        profs = {}
        for storage in ("replicated", "sharded"):
            lowered = build_probe_lowered(param_storage=storage)
            profs[storage] = CompiledMemoryProfile.from_lowered(lowered)
        s, r = profs["sharded"], profs["replicated"]
        assert s.peak_bytes < r.peak_bytes
        assert s.top_buffers[0]["elems"] == 49984, s.top_buffers[0]
        assert r.top_buffers[0]["elems"] == 65536, r.top_buffers[0]


class TestOomForensics:
    def test_is_oom_error(self):
        assert obs.is_oom_error(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 1 bytes"))
        assert obs.is_oom_error(RuntimeError("Resource exhausted"))
        assert not obs.is_oom_error(ValueError("shape mismatch"))
        assert not obs.is_oom_error(KeyboardInterrupt())

    def test_synthetic_oom_dumps_and_reraises(self, tmp_path,
                                              monkeypatch):
        from paddle_tpu.jit import FusedScanTrainStep

        monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path))
        crit, model, opt = _fused_step(seed=5)
        step = FusedScanTrainStep(model, opt, criterion=crit)
        ids, labels = _batch()
        step(ids, labels)

        class Boom:
            def __init__(self, orig):
                self.orig = orig

            def __call__(self, *a, **k):
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory while trying "
                    "to allocate 17179869184 bytes")

            def lower(self, *a, **k):
                return self.orig.lower(*a, **k)

        orig = step._jitted
        step._jitted = Boom(orig)
        try:
            with pytest.raises(RuntimeError,
                               match="RESOURCE_EXHAUSTED"):
                step(ids, labels)
        finally:
            step._jitted = orig
        dump = obs.last_oom_report()
        assert dump["step"] == "FusedScanTrainStep"
        assert dump["live"]["total_bytes"] > 0
        assert dump["compiled"]["peak_bytes"] > 0
        assert dump["compiled"]["top_buffers"]
        assert dump["dump_path"] and \
            dump["dump_path"].startswith(str(tmp_path))
        with open(dump["dump_path"]) as f:
            disk = json.load(f)
        ev = [e for e in disk["events"] if e.get("kind") == "oom"]
        assert ev and ev[-1]["compiled_peak_bytes"] == \
            dump["compiled"]["peak_bytes"]
        assert ev[-1]["top_buffers"]
        # counted, step still healthy at one executable
        assert obs.registry().get("mem.oom.count").value >= 1
        step(ids, labels)
        if hasattr(step._jitted, "_cache_size"):
            assert step._jitted._cache_size() == 1

    def test_non_oom_errors_do_not_dump(self, monkeypatch):
        from paddle_tpu.observability import memory as M

        calls = []
        monkeypatch.setattr(M, "dump_oom",
                            lambda *a, **k: calls.append(1))
        with pytest.raises(ValueError):
            with M.oom_guard(step="x"):
                raise ValueError("not an oom")
        assert not calls


class TestMemz:
    def test_global_memz_endpoint(self):
        import urllib.request

        from urllib.error import HTTPError

        with obs.DebugServer(port=0) as srv:
            body = json.load(urllib.request.urlopen(
                f"{srv.url}/memz", timeout=5))
            try:
                listing = json.load(urllib.request.urlopen(
                    f"{srv.url}/nope", timeout=5))
            except HTTPError as e:
                assert e.code == 404
                listing = json.load(e)
        assert body["live"]["total_bytes"] > 0
        assert isinstance(body["compiled"], dict)
        assert "memz" in listing["endpoints"]

    def test_engine_memz_includes_pool(self):
        import urllib.request

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4,
                        max_position_embeddings=64,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        from paddle_tpu.serving import ServingEngine

        eng = ServingEngine(m, max_slots=2, max_len=32, page_size=8,
                            chunk_size=8)
        port = eng.start_debug_server()
        try:
            body = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/memz", timeout=5))
        finally:
            eng.stop_debug_server()
        assert body["pool"]["total_pages"] == eng.num_pages - 1
        assert body["pool"]["used_pages"] == 0
        # pool gauges ride the engine scrape too
        assert "serving_kv_free_pages" in eng.metrics_text()


class TestPoolStats:
    def _cache(self, num_pages=17, page_size=8, max_slots=4):
        from paddle_tpu.inference.kv_cache import PagedKVCache

        return PagedKVCache(1, 2, 8, num_pages=num_pages,
                            page_size=page_size, max_slots=max_slots,
                            pages_per_seq=8)

    def test_invariants_and_per_slot_counts(self):
        c = self._cache()
        st = c.pool_stats()
        assert st["total_pages"] == 16 and st["trash_pages"] == 1
        assert st["used_pages"] == 0 and st["fragmentation"] == 0.0
        s0 = c.allocate(20)          # 3 pages
        s1 = c.allocate(9)           # 2 pages
        st = c.pool_stats()
        assert st["slot_pages"] == {s0: 3, s1: 2}
        assert st["used_pages"] == 5
        assert st["used_pages"] + st["free_pages"] == st["total_pages"]
        assert st["occupancy"] == round(5 / 16, 4)

    def test_fragmentation_tracks_free_contiguity(self):
        c = self._cache()
        s0 = c.allocate(24)          # pages
        s1 = c.allocate(24)
        assert c.pool_stats()["fragmentation"] == 0.0
        c.free(s0)                   # hole before s1's pages
        st = c.pool_stats()
        assert st["fragmentation"] > 0.0
        assert st["max_contiguous_free"] < st["free_pages"]
        c.free(s1)
        st = c.pool_stats()
        assert st["fragmentation"] == 0.0
        assert st["max_contiguous_free"] == st["free_pages"] \
            == st["total_pages"]

    def test_kv_pools_tagged_for_live_attribution(self):
        c = self._cache()
        rep = obs.live_registry().report(publish=False)
        want = sum(a.nbytes for a in c.k_layers + c.v_layers)
        assert rep["owners"].get("kv_pages", 0) >= want
