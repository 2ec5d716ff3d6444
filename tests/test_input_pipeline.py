"""Input pipeline: DevicePrefetcher (ISSUE 5) + DataLoader worker
lifecycle + sharded sampler determinism.

The prefetcher stages host batches onto device on a background thread
(sharding-aware device_put into a depth-K ring). The safety bundle the
acceptance criteria demand — bit-identical training sync vs prefetched,
zero added retraces, no rewrite-in-flight under buffer reuse — is
asserted here on the library surface. What the prefetcher buys is a
number of the benchmark (`input_stall` in its train cells), not of a test.
"""
import gc
import multiprocessing as mp
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as popt
from paddle_tpu.io import (
    DataLoader, Dataset, DevicePrefetcher, DistributedBatchSampler,
)


class RangeVec(Dataset):
    def __init__(self, n=32, dim=4):
        self.n, self.dim = n, dim

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return (np.full((self.dim,), i, dtype=np.float32),
                np.int64(i))


def _np_batches(n, shape=(4, 3), seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal(shape).astype(np.float32),
             rng.integers(0, 10, (shape[0],), dtype=np.int64))
            for _ in range(n)]


class TestDevicePrefetcher:
    def test_stream_values_and_order(self):
        batches = _np_batches(6)
        got = list(DevicePrefetcher(iter(batches), depth=2))
        assert len(got) == 6
        for (wx, wy), (gx, gy) in zip(batches, got):
            assert isinstance(gx, paddle.Tensor)
            np.testing.assert_array_equal(wx, gx.numpy())
            np.testing.assert_array_equal(wy, gy.numpy())

    def test_wraps_dataloader_epochs(self):
        loader = DataLoader(RangeVec(12), batch_size=3, shuffle=False)
        pf = DevicePrefetcher(loader, depth=2)
        for _ in range(2):  # re-iterable source => multi-epoch prefetcher
            got = [x.numpy() for x, _ in pf]
            assert len(got) == 4
            np.testing.assert_array_equal(
                np.concatenate(got)[:, 0], np.arange(12, dtype=np.float32))

    def test_default_collate_loader_not_mutated(self):
        # the prefetcher iterates a numpy-collating CLONE of a
        # default-collate DataLoader (the in-loader to_tensor is the
        # synchronous transfer this layer hides) — the user's loader
        # object must keep its own collate behavior
        loader = DataLoader(RangeVec(8), batch_size=4, shuffle=False)
        before = (loader.collate_fn, loader._user_collate)
        got = list(DevicePrefetcher(loader, depth=2))
        assert (loader.collate_fn, loader._user_collate) == before
        assert len(got) == 2 and isinstance(got[0][0], paddle.Tensor)
        x, _ = next(iter(loader))  # plain iteration still collates itself
        assert isinstance(x, paddle.Tensor)

    def test_non_array_leaves_pass_through(self):
        src = [{"x": np.ones((2, 2), np.float32), "tag": "a", "k": 3}]
        (got,) = list(DevicePrefetcher(iter(src), depth=1))
        assert got["tag"] == "a" and got["k"] == 3
        np.testing.assert_array_equal(got["x"].numpy(), np.ones((2, 2)))

    def test_error_propagates_to_consumer(self):
        def bad():
            yield (np.zeros((2,), np.float32),)
            raise RuntimeError("loader boom")

        pf = DevicePrefetcher(bad(), depth=2)
        it = iter(pf)
        next(it)
        with pytest.raises(RuntimeError, match="loader boom"):
            next(it)

    def test_close_mid_epoch_joins_producer(self):
        def slow():
            for i in range(100):
                time.sleep(0.01)
                yield (np.full((2,), i, np.float32),)

        pf = DevicePrefetcher(slow(), depth=2)
        it = iter(pf)
        next(it)
        ep = pf._epoch
        pf.close()
        assert not ep._thread.is_alive()
        # closed => a fresh iteration starts a fresh epoch
        got = next(iter(DevicePrefetcher(slow(), depth=2)))
        np.testing.assert_array_equal(got[0].numpy(), np.zeros((2,)))

    def test_stats_api(self):
        pf = DevicePrefetcher(iter(_np_batches(5)), depth=2)
        list(pf)
        s = pf.get_stats()
        assert s["batches"] == 5 and s["depth"] == 2
        assert s["input_stall_ms"]["count"] == 5
        assert s["h2d_ms"]["count"] == 5
        assert len(s["per_step_input_stall_ms"]) == 5
        assert s["h2d_ms"]["mean"] is not None
        pf.reset_stats()
        assert pf.get_stats()["batches"] == 0

    # -- safety proofs (acceptance criteria) ---------------------------
    def test_no_rewrite_in_flight(self):
        """A staged buffer can never change under a consumer: the host
        loader reuses ONE mutable buffer, and a batch held across later
        stages (> ring depth) keeps its original values."""
        buf = np.zeros((4, 2), np.float32)

        def reusing():
            for i in range(8):
                buf[:] = i
                yield (buf,)

        pf = DevicePrefetcher(reusing(), depth=2, to_tensor=False)
        it = iter(pf)
        held = next(it)[0]
        rest = [b[0] for b in it]
        assert float(np.asarray(held).mean()) == 0.0
        for i, b in enumerate(rest, start=1):
            assert float(np.asarray(b).mean()) == float(i)

    def test_zero_added_retraces(self):
        import jax

        traces = []

        @jax.jit
        def f(x):
            traces.append(1)
            return (x * 2.0).sum()

        batches = [(np.ones((4, 3), np.float32) * i,) for i in range(6)]
        # warm up the executable with a plain to_tensor batch, then feed
        # the prefetched stream — placement must match, so no retrace
        f(paddle.to_tensor(batches[0][0])._data).block_until_ready()
        assert len(traces) == 1
        for (x,) in DevicePrefetcher(iter(batches), depth=3):
            f(x._data).block_until_ready()
        assert len(traces) == 1

    def test_training_bit_identical_sync_vs_prefetched(self):
        def build():
            paddle.seed(11)
            m = nn.Sequential(nn.Linear(6, 8), nn.GELU(), nn.Linear(8, 2))
            opt = popt.AdamW(learning_rate=1e-2,
                             parameters=m.parameters())
            from paddle_tpu.jit import TrainStep

            crit = nn.CrossEntropyLoss()
            return m, TrainStep(m, lambda mm, a, b: crit(mm(a), b), opt)

        batches = [(x, y) for x, y in
                   ((np.random.default_rng(e).standard_normal(
                       (4, 6)).astype(np.float32),
                     np.random.default_rng(e + 50).integers(
                         0, 2, (4,), dtype=np.int64))
                    for e in range(8))]

        m_a, step_a = build()
        for x, y in batches:
            step_a(paddle.to_tensor(x), paddle.to_tensor(y, dtype="int64"))
        want = [np.asarray(p._data).tobytes() for p in m_a.parameters()]

        m_b, step_b = build()
        for x, y in step_b.prefetch(iter(batches), depth=3):
            step_b(x, y)
        got = [np.asarray(p._data).tobytes() for p in m_b.parameters()]
        assert want == got

    # -- sharded staging -----------------------------------------------
    def test_sharded_staging_1_over_n(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from paddle_tpu.distributed import env as denv

        mesh = denv.build_mesh({"dp": 8})
        src = [(np.arange(16 * 3, dtype=np.float32).reshape(16, 3),
                np.float32(1.5))]
        pf = DevicePrefetcher(iter(src), depth=1, mesh=mesh,
                              to_tensor=False)
        x, scalar = next(iter(pf))
        shards = x.addressable_shards
        assert len(shards) == 8
        for s in shards:
            assert s.data.shape == (2, 3)  # 1/N rows per device
            np.testing.assert_array_equal(
                np.asarray(s.data), np.asarray(x)[s.index])
        # rank-0 leaves (scalar) replicate instead of sharding
        assert float(scalar) == 1.5
        pf.close()

    def test_data_sharding_helper(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from jax.sharding import PartitionSpec
        from paddle_tpu.distributed import env as denv

        mesh = denv.build_mesh({"dp": 8})
        sh = denv.data_sharding(mesh=mesh)
        assert sh.spec == PartitionSpec("dp")
        assert denv.data_sharding(mesh=mesh, axis=None).mesh is mesh


class TestHapiPrefetch:
    def test_fit_prefetch_matches_plain_fit(self):
        ds = RangeVec(24, dim=6)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(6, 3)

            def forward(self, x):
                return self.fc(x)

        def fit(prefetch):
            paddle.seed(5)
            model = paddle.Model(Net())
            model.prepare(
                popt.Adam(learning_rate=1e-3,
                          parameters=model.network.parameters()),
                nn.CrossEntropyLoss())
            model.fit(ds, epochs=2, batch_size=4, shuffle=False,
                      verbose=0, prefetch=prefetch)
            stats = getattr(model, "input_pipeline_stats", None)
            return ([np.asarray(p._data).tobytes()
                     for p in model.network.parameters()], stats)

        plain, _ = fit(False)
        pre, stats = fit(True)
        assert plain == pre
        assert stats is not None and stats["batches"] == 12
        assert stats["input_stall_ms"]["count"] == 12


class TestWorkerLifecycle:
    def _leaked_shm(self):
        d = "/dev/shm"
        if not os.path.isdir(d):
            return []
        return [f for f in os.listdir(d) if f.startswith("pt_dl_")]

    def _assert_no_children(self, before, timeout=15.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            extra = [p for p in mp.active_children() if p not in before]
            if not extra:
                return
            time.sleep(0.1)
        raise AssertionError(f"orphaned workers: {extra}")

    def test_consumer_break_mid_epoch_no_orphans(self):
        before = set(mp.active_children())
        loader = DataLoader(RangeVec(64), batch_size=2, num_workers=2)
        it = iter(loader)
        next(it)
        next(it)
        it.close()  # the iterator finally must shut the pool down
        self._assert_no_children(before)
        assert self._leaked_shm() == []

    def test_consumer_raises_mid_epoch_no_orphans(self):
        before = set(mp.active_children())
        loader = DataLoader(RangeVec(64), batch_size=2, num_workers=2)
        with pytest.raises(ValueError, match="consumer boom"):
            for i, _ in enumerate(loader):
                if i == 1:
                    raise ValueError("consumer boom")
        gc.collect()  # the abandoned generator finalizes -> pool.shutdown
        self._assert_no_children(before)
        assert self._leaked_shm() == []

    def test_pool_shutdown_idempotent(self):
        from paddle_tpu.io import numpy_collate_fn
        from paddle_tpu.io.worker import WorkerPool

        pool = WorkerPool(RangeVec(8), numpy_collate_fn, 2,
                          use_shared_memory=True, seed=0)
        pool.submit(0, [0, 1])
        pool.next_batch(timeout_s=60)
        pool.shutdown()
        pool.shutdown()  # second call is a no-op, not a crash
        assert self._leaked_shm() == []

    def test_prefetcher_over_multiprocess_loader_abandoned(self):
        before = set(mp.active_children())
        loader = DataLoader(RangeVec(64), batch_size=2, num_workers=2)
        pf = DevicePrefetcher(loader, depth=2)
        it = iter(pf)
        next(it)
        pf.close()
        gc.collect()
        self._assert_no_children(before)
        assert self._leaked_shm() == []


class TestDistributedSamplerDeterminism:
    def test_disjoint_shards_union_to_global_shuffle(self):
        n, ranks = 64, 4
        ds = RangeVec(n)
        per_rank = []
        for r in range(ranks):
            s = DistributedBatchSampler(ds, batch_size=4,
                                        num_replicas=ranks, rank=r,
                                        shuffle=True)
            s.set_epoch(3)
            per_rank.append([i for b in s for i in b])
        flat = [i for idxs in per_rank for i in idxs]
        # disjoint (n divisible by ranks -> no padding duplicates)...
        assert len(flat) == n and len(set(flat)) == n
        # ...and the union is exactly the one global epoch-3 permutation
        want = np.random.RandomState(3).permutation(n)
        strided = [[int(v) for v in want[r::ranks]] for r in range(ranks)]
        assert per_rank == strided

    def test_same_epoch_same_order_across_constructions(self):
        ds = RangeVec(32)

        def draw():
            s = DistributedBatchSampler(ds, batch_size=4, num_replicas=4,
                                        rank=1, shuffle=True)
            s.set_epoch(7)
            return [tuple(b) for b in s]

        assert draw() == draw()

    def test_epoch_changes_order(self):
        ds = RangeVec(32)
        s = DistributedBatchSampler(ds, batch_size=4, num_replicas=4,
                                    rank=0, shuffle=True)
        s.set_epoch(0)
        a = [tuple(b) for b in s]
        s.set_epoch(1)
        b = [tuple(b) for b in s]
        assert a != b

    def test_padding_covers_every_sample(self):
        n, ranks = 30, 4  # not divisible: pads to 32 with duplicates
        ds = RangeVec(n)
        flat = []
        for r in range(ranks):
            s = DistributedBatchSampler(ds, batch_size=4,
                                        num_replicas=ranks, rank=r,
                                        shuffle=True)
            s.set_epoch(0)
            flat += [i for b in s for i in b]
        assert len(flat) == 32
        assert set(flat) == set(range(n))  # every sample seen >= once
