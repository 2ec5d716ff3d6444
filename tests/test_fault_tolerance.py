"""Fault-tolerant training (ISSUE 4): crash-safe checkpointing + the
in-graph non-finite step guard.

Reference test strategy: the reference trusts the filesystem and skips
bad steps host-side (check_finite_and_unscale + GradScaler); here the
acceptance bar is adversarial — SIGKILL at randomized points during
save, flipped bytes on disk, NaN injected at a specific step on every
compiled path — and recovery must be exact (checksum-verified restore,
bit-identical state pass-through).
"""
import glob
import os
import pickle
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as popt
from paddle_tpu.amp import GradScaler
from paddle_tpu.distributed import env as denv
from paddle_tpu.distributed.checkpoint import (
    CheckpointError, CheckpointManager, load_state_dict, save_state_dict,
    verify_checkpoint,
)
from paddle_tpu.jit import (
    FusedScanTrainStep, ShardedFusedScanTrainStep, TrainStep,
)
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
)

from ft_victim import victim_state

TINY = dict(vocab_size=96, hidden_size=32, num_layers=2,
            num_attention_heads=2, max_position_embeddings=16,
            hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
N_DEV = 8


def _batch(bs=8, seq=12, vocab=96, seed=0):
    rng = np.random.default_rng(seed)
    return (paddle.to_tensor(rng.integers(0, vocab, (bs, seq)),
                             dtype="int64"),
            paddle.to_tensor(rng.integers(0, vocab, (bs, seq)),
                             dtype="int64"))


def _fresh_params():
    """Reset the global auto-name counter: a resume rebuilds the model
    in a fresh process where names restart at param_0 — in-process
    rebuild rehearsals must line the optimizer state keys up the same
    way."""
    import itertools

    import paddle_tpu.nn.layer.layers as _layers

    _layers._param_counter = itertools.count()


def _gpt(seed=0, lr=1e-2, scan=True, **cfg_over):
    _fresh_params()
    cfg = GPTConfig(**{**TINY, **cfg_over}, scan_layers=scan)
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=lr, parameters=model.parameters())
    return model, opt


def _state_snapshot(step):
    st = step._extract_state()
    return jax.tree_util.tree_map(
        lambda a: np.asarray(a).copy() if isinstance(a, jax.Array)
        else a, st)


def _assert_trees_equal(before, after, skip=("guard",), msg=""):
    fb, _ = jax.tree_util.tree_flatten_with_path(before)
    fa, _ = jax.tree_util.tree_flatten_with_path(after)
    assert len(fb) == len(fa)
    for (pb, vb), (_, va) in zip(fb, fa):
        name = jax.tree_util.keystr(pb)
        if any(s in name for s in skip):
            continue
        if isinstance(vb, np.ndarray):
            assert np.array_equal(vb, va, equal_nan=True), \
                f"{msg}: {name} changed on a bad step"


# ---------------------------------------------------------------------------
# framework/io.py: crash-safe paddle.save
# ---------------------------------------------------------------------------

class TestAtomicSave:
    def test_no_temp_residue_and_round_trip(self, tmp_path):
        p = str(tmp_path / "m.pdparams")
        paddle.save({"w": paddle.to_tensor(np.arange(6.0))}, p)
        assert os.listdir(str(tmp_path)) == ["m.pdparams"]
        got = paddle.load(p)
        np.testing.assert_array_equal(np.asarray(got["w"]._data),
                                      np.arange(6.0))

    def test_failed_replace_preserves_old_file(self, tmp_path,
                                               monkeypatch):
        """A crash at the commit point leaves the OLD file intact and
        readable — never a truncated pickle."""
        p = str(tmp_path / "m.pdparams")
        paddle.save({"v": 1}, p)

        def boom(src, dst):
            raise OSError("simulated crash at commit")

        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError):
            paddle.save({"v": 2}, p)
        monkeypatch.undo()
        assert paddle.load(p) == {"v": 1}
        assert os.listdir(str(tmp_path)) == ["m.pdparams"]  # tmp cleaned

    def test_unpicklable_leaves_no_file(self, tmp_path):
        p = str(tmp_path / "x.pdparams")
        with pytest.raises(Exception):
            paddle.save({"bad": lambda: None}, p)
        assert not os.path.exists(p)


# ---------------------------------------------------------------------------
# load_state_dict: clear CheckpointError on corruption
# ---------------------------------------------------------------------------

class TestCheckpointErrors:
    def _save_one(self, tmp_path):
        path = str(tmp_path / "ckpt")
        sd = {"w": paddle.Tensor(jnp.arange(16.0).reshape(4, 4))}
        save_state_dict(sd, path)
        return path

    def _tgt(self):
        return {"w": paddle.Tensor(jnp.zeros((4, 4)))}

    def test_truncated_chunk_names_file(self, tmp_path):
        path = self._save_one(tmp_path)
        chunk = glob.glob(os.path.join(path, "*_0.distcp"))[0]
        raw = open(chunk, "rb").read()
        open(chunk, "wb").write(raw[:len(raw) // 2])
        with pytest.raises(CheckpointError, match="0_0.distcp"):
            load_state_dict(self._tgt(), path)

    def test_flipped_byte_names_file(self, tmp_path):
        path = self._save_one(tmp_path)
        chunk = glob.glob(os.path.join(path, "*_0.distcp"))[0]
        raw = bytearray(open(chunk, "rb").read())
        raw[-8] ^= 0x10
        open(chunk, "wb").write(bytes(raw))
        with pytest.raises(CheckpointError, match="checksum"):
            load_state_dict(self._tgt(), path)
        with pytest.raises(CheckpointError):
            verify_checkpoint(path)

    def test_missing_tensor_names_key_and_file(self, tmp_path):
        """Manifest/chunk disagreement surfaces the TENSOR KEY, not a
        bare KeyError from _ChunkReader."""
        path = self._save_one(tmp_path)
        chunk = glob.glob(os.path.join(path, "*_0.distcp"))[0]
        payload = pickle.load(open(chunk, "rb"))
        payload.clear()                      # drop every chunk
        raw = pickle.dumps(payload)
        open(chunk, "wb").write(raw)
        # keep the checksum consistent so the KEY error path is reached
        import zlib

        meta = pickle.load(open(os.path.join(path, "0.metadata"), "rb"))
        meta.file_checksums[os.path.basename(chunk)] = (
            zlib.crc32(raw), len(raw))
        open(os.path.join(path, "0.metadata"), "wb").write(
            pickle.dumps(meta))
        with pytest.raises(CheckpointError, match="'w'"):
            load_state_dict(self._tgt(), path)

    def test_corrupt_manifest(self, tmp_path):
        path = self._save_one(tmp_path)
        open(os.path.join(path, "0.metadata"), "wb").write(b"garbage")
        with pytest.raises(CheckpointError, match="manifest"):
            load_state_dict(self._tgt(), path)

    def test_missing_manifest_is_not_a_checkpoint(self, tmp_path):
        path = self._save_one(tmp_path)
        os.remove(os.path.join(path, "0.metadata"))
        with pytest.raises(CheckpointError, match="manifest"):
            verify_checkpoint(path)


# ---------------------------------------------------------------------------
# CheckpointManager: atomic commit under SIGKILL, retention, async
# ---------------------------------------------------------------------------

class TestCheckpointManager:
    def test_kill_dash_nine_randomized(self, tmp_path):
        """Acceptance: SIGKILL at randomized points during save, >= 20
        trials — restore_or_init always recovers a complete, checksum-
        verified checkpoint at a step the victim actually committed.
        Victims run in parallel batches to amortize interpreter
        startup."""
        trials, batch = 20, 5
        rng = np.random.default_rng(7)
        here = os.path.dirname(os.path.abspath(__file__))
        repo = os.path.dirname(here)
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = os.pathsep.join(
            [repo] + [p for p in env.get("PYTHONPATH", "").split(
                os.pathsep) if p])
        mid_save = 0
        done = 0
        while done < trials:
            n = min(batch, trials - done)
            victims = []
            for i in range(n):
                root = str(tmp_path / f"t{done + i}")
                child = subprocess.Popen(
                    [sys.executable, os.path.join(here, "ft_victim.py"),
                     root],
                    stdout=subprocess.PIPE, text=True, env=env,
                    cwd=repo)
                victims.append((root, child))
            for root, child in victims:
                first = child.stdout.readline()     # >=1 commit each
                assert first.startswith("committed"), first
            time.sleep(float(rng.uniform(0.0, 0.3)))
            for _, child in victims:
                child.send_signal(signal.SIGKILL)
            for root, child in victims:
                child.wait()
                confirmed = [int(ln.split()[1]) for ln in
                             child.stdout.read().split("\n")
                             if ln.startswith("committed")]
                if any(".tmp_" in nme for nme in os.listdir(root)):
                    mid_save += 1
                extra = victim_state(0)
                mgr = CheckpointManager(root, extra_state=extra)
                got = mgr.restore_or_init()
                assert got is not None, f"{root}: nothing restorable"
                verify_checkpoint(os.path.join(root, f"step_{got}"))
                if confirmed:
                    assert got >= max(confirmed), (got, confirmed)
                want = victim_state(got)
                assert extra["step_scalar"] == got
                for k in ("w0", "w1"):
                    assert np.array_equal(np.asarray(extra[k]), want[k])
            done += n
        # the point of randomized timing: a healthy share of kills must
        # actually land mid-save (tmp dir present), not between saves
        assert mid_save >= 2, f"only {mid_save} kills landed mid-save"

    def test_retention_and_orphan_gc(self, tmp_path):
        extra = {"w": np.arange(8.0, dtype=np.float32)}
        root = str(tmp_path / "ck")
        mgr = CheckpointManager(root, extra_state=extra, max_to_keep=2)
        # an orphaned tmp dir from a "crashed" previous process
        orphan = os.path.join(root, "step_9.tmp_deadbeef")
        os.makedirs(orphan)
        for s in range(4):
            mgr.save(s)
        assert mgr.all_steps() == [2, 3]
        assert not os.path.exists(orphan)
        assert not any(".tmp_" in n for n in os.listdir(root))

    def test_async_error_propagates_to_next_save(self, tmp_path,
                                                 monkeypatch):
        import paddle_tpu.distributed.checkpoint.manager as mgr_mod

        extra = {"w": np.arange(4.0, dtype=np.float32)}
        mgr = CheckpointManager(str(tmp_path / "ck"), extra_state=extra,
                                async_save=True)

        def boom(*a, **k):
            raise OSError("disk on fire")

        monkeypatch.setattr(mgr_mod, "save_state_dict", boom)
        mgr.save(0)                  # background failure, silent here
        with pytest.raises(CheckpointError, match="disk on fire"):
            mgr.wait()
        monkeypatch.undo()
        mgr.save(1)                  # manager is usable again
        mgr.wait()
        assert mgr.all_steps() == [1]

    def test_restore_falls_back_past_corrupt(self, tmp_path):
        extra = {"w": np.arange(8.0, dtype=np.float32), "step_tag": 0}
        root = str(tmp_path / "ck")
        mgr = CheckpointManager(root, extra_state=extra, max_to_keep=5)
        for s in range(3):
            extra["step_tag"] = s
            extra["w"] = np.full(8, float(s), np.float32)
            mgr.save(s)
        # corrupt the newest TWO: restore must land on step 0
        for s in (1, 2):
            chunk = glob.glob(os.path.join(root, f"step_{s}",
                                           "*_0.distcp"))[0]
            raw = bytearray(open(chunk, "rb").read())
            raw[10] ^= 0xFF
            open(chunk, "wb").write(bytes(raw))
        tgt = {"w": np.zeros(8, np.float32), "step_tag": -1}
        mgr2 = CheckpointManager(root, extra_state=tgt)
        assert mgr2.restore_or_init() == 0
        assert tgt["step_tag"] == 0
        np.testing.assert_array_equal(np.asarray(tgt["w"]),
                                      np.zeros(8, np.float32))

    def test_armed_chunk_flip_is_caught_and_restore_falls_back(
            self, tmp_path):
        """The manager's own ``ckpt.chunk.flip`` fault point (one flipped
        byte in a chunk it has just written, before commit): manifest
        verification refuses that step and restore lands on the previous
        one with its payload intact."""
        from paddle_tpu.observability import faults

        root = str(tmp_path / "ck")
        inj = faults.install(0)
        # the manager asks the point once per save: fire on the SECOND,
        # so step 0 stays whole as the step to fall back to
        inj.arm("ckpt.chunk.flip", at=2)
        try:
            extra = victim_state(0)
            mgr = CheckpointManager(root, extra_state=extra)
            for step in (0, 1):
                extra.clear()
                extra.update(victim_state(step))
                mgr.save(step)
            assert inj.hits.get("ckpt.chunk.flip", 0) >= 2, inj.hits
        finally:
            faults.reset()
        with pytest.raises(CheckpointError):
            verify_checkpoint(os.path.join(root, "step_1"))
        tgt = victim_state(1)
        assert CheckpointManager(
            root, extra_state=tgt).restore_or_init() == 0
        assert tgt["step_scalar"] == 0
        np.testing.assert_array_equal(np.asarray(tgt["w0"]),
                                      victim_state(0)["w0"])

    def test_restore_key_mismatch_raises_not_silent(self, tmp_path):
        """A template/checkpoint key mismatch is NOT corruption: older
        checkpoints have the same keys, so falling back would silently
        restart the run (or silently drop saved optimizer state). It
        must raise a clear CheckpointError instead."""
        extra = {"w": np.arange(8.0, dtype=np.float32), "m": 1.0}
        root = str(tmp_path / "ck")
        CheckpointManager(root, extra_state=extra).save(0)
        # template missing a key the checkpoint has (e.g. restoring
        # before the optimizer accumulators exist)
        tgt = {"w": np.zeros(8, np.float32)}
        with pytest.raises(CheckpointError, match="not in template"):
            CheckpointManager(root, extra_state=tgt).restore_or_init()
        # template with a key the checkpoint lacks (model changed)
        tgt2 = {"w": np.zeros(8, np.float32), "m": 0.0, "new": 5.0}
        with pytest.raises(CheckpointError, match="not in checkpoint"):
            CheckpointManager(root, extra_state=tgt2).restore_or_init()

    def test_negative_step_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "ck"),
                                extra_state={"w": np.zeros(2)})
        with pytest.raises(ValueError, match=">= 0"):
            mgr.save(-1)

    def test_sigterm_preemption_final_save(self, tmp_path):
        """SIGTERM triggers one final synchronous save before chaining
        to the previous handler (the Cloud-TPU preemption contract)."""
        extra = {"w": np.arange(4.0, dtype=np.float32)}
        mgr = CheckpointManager(str(tmp_path / "ck"), extra_state=extra)
        chained = []
        prev = signal.signal(signal.SIGTERM,
                             lambda *a: chained.append(a[0]))
        try:
            mgr.install_preemption_handler(get_step=lambda: 41)
            extra["w"] = np.full(4, 7.0, np.float32)
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(0.05)
            assert mgr.all_steps() == [41]
            assert chained == [signal.SIGTERM]   # previous handler ran
        finally:
            mgr.uninstall_preemption_handler()
            signal.signal(signal.SIGTERM, prev)
        tgt = {"w": np.zeros(4, np.float32)}
        mgr2 = CheckpointManager(str(tmp_path / "ck"), extra_state=tgt)
        assert mgr2.restore_or_init() == 41
        np.testing.assert_array_equal(np.asarray(tgt["w"]),
                                      np.full(4, 7.0, np.float32))

    def test_scaler_state_round_trips(self, tmp_path):
        """Satellite: GradScaler.state_dict round-trips through
        CheckpointManager."""
        sc = GradScaler(init_loss_scaling=2.0 ** 9)
        sc._good_steps, sc._bad_steps = 5, 1
        mgr = CheckpointManager(str(tmp_path / "ck"), scaler=sc)
        mgr.save(0)
        sc2 = GradScaler(init_loss_scaling=2.0 ** 15)
        mgr2 = CheckpointManager(str(tmp_path / "ck"), scaler=sc2)
        assert mgr2.restore_or_init() == 0
        assert float(sc2._scale) == 2.0 ** 9
        assert int(sc2._good_steps) == 5 and int(sc2._bad_steps) == 1

    def test_trainstep_save_restore_continue_bit_identical(self,
                                                           tmp_path):
        """Generic TrainStep state (params/opt/rng) through the manager:
        continuation equals the uninterrupted run bit for bit."""

        def build():
            _fresh_params()
            paddle.seed(3)
            m = nn.Linear(8, 4)
            opt = popt.AdamW(learning_rate=1e-2,
                             parameters=m.parameters())
            step = TrainStep(m, lambda mm, a, b: ((mm(a) - b) ** 2)
                             .mean(), opt)
            rng = np.random.default_rng(0)
            x = paddle.to_tensor(
                rng.standard_normal((4, 8)).astype(np.float32))
            y = paddle.to_tensor(
                rng.standard_normal((4, 4)).astype(np.float32))
            return m, opt, step, x, y

        m, opt, step, x, y = build()
        straight = [float(step(x, y)) for _ in range(5)]

        m, opt, step, x, y = build()
        part1 = [float(step(x, y)) for _ in range(3)]
        mgr = CheckpointManager(str(tmp_path / "ck"), model=m,
                                optimizer=opt)
        mgr.save(2)
        m2, opt2, step2, x, y = build()
        step2._warmup_accumulators()
        mgr2 = CheckpointManager(str(tmp_path / "ck"), model=m2,
                                 optimizer=opt2)
        assert mgr2.restore_or_init() == 2
        part2 = [float(step2(x, y)) for _ in range(2)]
        assert straight == part1 + part2

    def test_fused_scan_async_save_restore_continue_bit_identical(
            self, tmp_path):
        """FusedScanTrainStep through an ASYNC save: save at step 2,
        restore into a model and optimizer built from another seed,
        continue — the losses equal the uninterrupted run's bit for
        bit."""
        ids, labels = _batch(bs=4)

        def build(seed=0):
            model, opt = _gpt(seed=seed)
            return model, opt, FusedScanTrainStep(
                model, opt, criterion=GPTPretrainingCriterion())

        _, _, step = build()
        straight = [float(step(ids, labels)) for _ in range(5)]

        model, opt, step = build()
        mgr = CheckpointManager(str(tmp_path / "ck"), model=model,
                                optimizer=opt, async_save=True)
        part1 = [float(step(ids, labels)) for _ in range(3)]
        mgr.save(2)
        mgr.wait()
        assert {"snapshot_s", "blocked_s", "io_s"} <= set(mgr.last_timings)

        model2, opt2, step2 = build(seed=123)
        step2.ensure_built()            # optimizer state slots exist
        assert CheckpointManager(
            str(tmp_path / "ck"), model=model2,
            optimizer=opt2).restore_or_init() == 2
        part2 = [float(step2(ids, labels)) for _ in range(2)]
        assert straight == part1 + part2

    def test_no_retrace_after_restore(self, tmp_path):
        """Restored params come back device-committed while fresh
        guard/rng scalars start uncommitted; jit keys committed and
        uncommitted arguments differently, so without the
        _commit_uncommitted canonicalization the second resumed step
        compiles one extra executable."""

        def build():
            _fresh_params()
            paddle.seed(3)
            m = nn.Linear(8, 4)
            opt = popt.AdamW(learning_rate=1e-2,
                             parameters=m.parameters())
            step = TrainStep(m, lambda mm, a, b: ((mm(a) - b) ** 2)
                             .mean(), opt, scaler=GradScaler())
            rng = np.random.default_rng(0)
            x = paddle.to_tensor(
                rng.standard_normal((4, 8)).astype(np.float32))
            y = paddle.to_tensor(
                rng.standard_normal((4, 4)).astype(np.float32))
            return m, opt, step, x, y

        m, opt, step, x, y = build()
        for _ in range(2):
            step(x, y)
        CheckpointManager(str(tmp_path / "ck"), model=m,
                          optimizer=opt).save(1)

        m2, opt2, step2, x, y = build()
        step2._warmup_accumulators()
        mgr = CheckpointManager(str(tmp_path / "ck"), model=m2,
                                optimizer=opt2)
        assert mgr.restore_or_init() == 1
        for _ in range(3):
            step2(x, y)
        assert step2._jitted._cache_size() == 1

    def test_no_retrace_after_restore_fused_scan(self, tmp_path):
        """Same committed/uncommitted canonicalization on the fused-scan
        step (it has no mesh branch to do it for free)."""
        ids, labels = _batch(bs=4)

        def build():
            model, opt = _gpt()
            step = FusedScanTrainStep(model, opt,
                                      criterion=GPTPretrainingCriterion(),
                                      scaler=GradScaler())
            return model, opt, step

        model, opt, step = build()
        for _ in range(2):
            step(ids, labels)
        CheckpointManager(str(tmp_path / "ck"), model=model,
                          optimizer=opt).save(1)

        model2, opt2, step2 = build()
        step2.ensure_built()
        mgr = CheckpointManager(str(tmp_path / "ck"), model=model2,
                                optimizer=opt2)
        assert mgr.restore_or_init() == 1
        for _ in range(3):
            step2(ids, labels)
        if hasattr(step2._jitted, "_cache_size"):
            assert step2._jitted._cache_size() == 1


# ---------------------------------------------------------------------------
# in-graph non-finite guard: TrainStep
# ---------------------------------------------------------------------------

class TestGuardTrainStep:
    def _build(self, scaler=None, guard=None):
        _fresh_params()
        paddle.seed(0)
        m = nn.Linear(8, 4)
        opt = popt.AdamW(learning_rate=1e-2, parameters=m.parameters())
        step = TrainStep(m, lambda mm, a, b: ((mm(a) - b) ** 2).mean(),
                         opt, scaler=scaler, guard_nonfinite=guard)
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.standard_normal((4, 8))
                             .astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((4, 4))
                             .astype(np.float32))
        return m, opt, step, x, y

    def test_nan_step_bit_identical_and_scale_halves(self):
        sc = GradScaler(init_loss_scaling=2.0 ** 10,
                        incr_every_n_steps=100)
        m, opt, step, x, y = self._build(scaler=sc)
        for _ in range(2):
            step(x, y)
        before = _state_snapshot(step)
        xbad = paddle.to_tensor(np.full((4, 8), np.nan, np.float32))
        lbad = step(xbad, y)
        assert not np.isfinite(float(lbad))
        after = _state_snapshot(step)
        _assert_trees_equal(before, after, msg="TrainStep")
        assert float(sc._scale) == 2.0 ** 9          # halved
        assert bool(sc._found_inf)
        assert int(np.asarray(after["opt"]["step"])) == \
            int(np.asarray(before["opt"]["step"]))
        # recovery: the very next good step trains
        l = step(x, y)
        assert np.isfinite(float(l))
        assert not np.array_equal(np.asarray(m.weight._data),
                                  before["params"][0])

    def test_no_retrace_and_no_host_transfer(self):
        """Acceptance probes: one executable across good AND bad steps,
        and the guarded program contains no host transfer ops."""
        sc = GradScaler(init_loss_scaling=2.0 ** 10)
        m, opt, step, x, y = self._build(scaler=sc)
        step(x, y)
        xbad = paddle.to_tensor(np.full((4, 8), np.nan, np.float32))
        step(xbad, y)
        step(x, y)
        if hasattr(step._jitted, "_cache_size"):
            assert step._jitted._cache_size() == 1
        # guard state stays on device between steps — zero added syncs
        assert isinstance(sc._scale, jax.Array)
        assert isinstance(sc._found_inf, jax.Array)
        state = step._extract_state()
        lr = jnp.float32(1e-2)
        text = step._jitted.lower(
            state, lr, [x._data, y._data]).as_text()
        for op in ("infeed", "outfeed", "send(", "recv(",
                   "host_callback"):
            assert op not in text, f"host transfer {op!r} in step HLO"

    def test_scale_grows_after_n_good_steps(self):
        sc = GradScaler(init_loss_scaling=2.0 ** 4, incr_ratio=2.0,
                        incr_every_n_steps=3)
        m, opt, step, x, y = self._build(scaler=sc)
        for _ in range(3):
            step(x, y)
        assert float(sc._scale) == 2.0 ** 5
        assert int(sc._good_steps) == 0

    def test_guard_without_scaler_gates_only(self):
        m, opt, step, x, y = self._build(guard=True)
        step(x, y)
        before = _state_snapshot(step)
        xbad = paddle.to_tensor(np.full((4, 8), np.inf, np.float32))
        step(xbad, y)
        _assert_trees_equal(before, _state_snapshot(step),
                            msg="guard_nonfinite")

    def test_guarded_matches_unguarded_on_good_steps(self):
        """The guard must be a no-op on finite steps: same trajectory as
        an unguarded run. (ULP-level tolerance: guarded and unguarded
        are different XLA programs, and XLA may reassociate ops
        differently between them — within one program the bad-step
        pass-through IS bit-exact, asserted above.)"""
        m1, _, s1, x, y = self._build()
        a = [float(s1(x, y)) for _ in range(3)]
        m2, _, s2, x, y = self._build(guard=True)
        b = [float(s2(x, y)) for _ in range(3)]
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        for (n, p1), (_, p2) in zip(m1.named_parameters(),
                                    m2.named_parameters()):
            np.testing.assert_allclose(np.asarray(p1._data),
                                       np.asarray(p2._data),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=n)


# ---------------------------------------------------------------------------
# in-graph non-finite guard: fused scan + sharded scan
# ---------------------------------------------------------------------------

def _poison_wte(model, row=5):
    w = model.gpt.wte.weight
    w._data = w._data.at[row].set(jnp.nan)
    return row


class _GuardScanMixin:
    def _run_nan_injection(self, step, model, sc, ids, labels,
                           wte_index):
        path, flat_range = (wte_index if isinstance(wte_index, tuple)
                            else (wte_index, None))
        for _ in range(2):
            step(ids, labels)
        before = _state_snapshot(step)
        row = _poison_wte(model)
        lbad = step(ids, labels)
        assert not np.isfinite(float(lbad))
        after = _state_snapshot(step)
        fb, _ = jax.tree_util.tree_flatten_with_path(before)
        fa, _ = jax.tree_util.tree_flatten_with_path(after)
        for (pb, vb), (_, va) in zip(fb, fa):
            name = jax.tree_util.keystr(pb)
            if "guard" in name:
                continue
            if not isinstance(vb, np.ndarray):
                continue
            if name == path:
                if flat_range is None:
                    mask = np.ones(vb.shape[0], bool)
                    mask[row] = False
                    assert np.array_equal(vb[mask], va[mask]), name
                else:
                    # sharded param storage: the poisoned wte row lives
                    # at its flat-bucket offset range inside the o fp
                    # shard array; everything outside it must pass
                    # through bit-identical on the bad step
                    lo, hi = flat_range(row)
                    mask = np.ones(vb.shape[-1], bool)
                    mask[lo:hi] = False
                    assert np.array_equal(vb[..., mask],
                                          va[..., mask]), name
            else:
                assert np.array_equal(vb, va, equal_nan=True), \
                    f"{name} changed on a bad step"
        assert float(sc._scale) == 2.0 ** 10 * 0.5
        assert int(np.asarray(after["step"])) == \
            int(np.asarray(before["step"]))
        # heal the poisoned row and keep training with the same
        # executable
        w = model.gpt.wte.weight
        w._data = w._data.at[row].set(0.01)
        l = step(ids, labels)
        assert np.isfinite(float(l))

    def _wte_state_index(self, step, model):
        """Locator of the wte weight's leaf in _extract_state: the
        plain state path for per-leaf storage, or (fp-bucket path,
        row -> flat range fn) when the step stores params as 1/N flat
        bucket shards (ISSUE 11)."""
        wte = model.gpt.wte.weight
        for j, (_, p) in enumerate(step._o_params):
            if p is wte:
                if getattr(step, "_param_storage", None) == "sharded":
                    bkt, e = step._o_assign.bucket_of(j)
                    h = int(wte.shape[1])
                    return (f"['o']['fp'][{bkt.index}]",
                            lambda row, off=e.offset, h=h:
                            (off + row * h, off + (row + 1) * h))
                return f"['o']['p'][{j}]"
        raise AssertionError("wte not in outer params")


class TestGuardFusedScan(_GuardScanMixin):
    def _build(self, clip=None):
        model, opt = _gpt()
        if clip is not None:
            opt._grad_clip = clip
        sc = GradScaler(init_loss_scaling=2.0 ** 10,
                        incr_every_n_steps=100)
        step = FusedScanTrainStep(model, opt,
                                  criterion=GPTPretrainingCriterion(),
                                  scaler=sc)
        ids, labels = _batch(bs=4)
        return model, opt, sc, step, ids, labels

    def test_nan_injection_no_clip(self):
        model, opt, sc, step, ids, labels = self._build()
        self._run_nan_injection(step, model, sc, ids, labels,
                                self._wte_state_index(step, model))
        if hasattr(step._jitted, "_cache_size"):
            assert step._jitted._cache_size() == 1   # no added retrace

    def test_nan_injection_rides_the_clip_norm_pass(self):
        model, opt, sc, step, ids, labels = self._build(
            clip=nn.ClipGradByGlobalNorm(0.5))
        self._run_nan_injection(step, model, sc, ids, labels,
                                self._wte_state_index(step, model))

    def test_guarded_matches_unguarded_good_steps(self):
        model1, opt1 = _gpt()
        s1 = FusedScanTrainStep(model1, opt1,
                                criterion=GPTPretrainingCriterion())
        ids, labels = _batch(bs=4)
        a = [float(s1(ids, labels)) for _ in range(3)]
        model2, opt2 = _gpt()
        s2 = FusedScanTrainStep(model2, opt2,
                                criterion=GPTPretrainingCriterion(),
                                guard_nonfinite=True)
        b = [float(s2(ids, labels)) for _ in range(3)]
        # ULP tolerance: different XLA programs (see TrainStep note)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    def test_scaled_run_matches_unscaled(self):
        """Loss scaling must be numerically invisible in fp32: scaled
        cotangent + in-graph unscale == plain run (tight tolerance)."""
        model1, opt1 = _gpt()
        s1 = FusedScanTrainStep(model1, opt1,
                                criterion=GPTPretrainingCriterion())
        ids, labels = _batch(bs=4)
        a = [float(s1(ids, labels)) for _ in range(3)]
        model2, opt2 = _gpt()
        sc = GradScaler(init_loss_scaling=2.0 ** 8,
                        incr_every_n_steps=100)
        s2 = FusedScanTrainStep(model2, opt2,
                                criterion=GPTPretrainingCriterion(),
                                scaler=sc)
        b = [float(s2(ids, labels)) for _ in range(3)]
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)


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


class TestGuardShardedScan(_GuardScanMixin):
    def test_nan_injection_sharded(self, mesh):
        model, opt = _gpt()
        sc = GradScaler(init_loss_scaling=2.0 ** 10,
                        incr_every_n_steps=100)
        step = ShardedFusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(), mesh=mesh,
            axis="sharding", scaler=sc)
        ids, labels = _batch(bs=N_DEV)
        self._run_nan_injection(step, model, sc, ids, labels,
                                self._wte_state_index(step, model))
        if hasattr(step._jitted, "_cache_size"):
            assert step._jitted._cache_size() == 1

    def test_nan_injection_sharded_with_clip(self, mesh):
        model, opt = _gpt()
        opt._grad_clip = nn.ClipGradByGlobalNorm(0.5)
        sc = GradScaler(init_loss_scaling=2.0 ** 10,
                        incr_every_n_steps=100)
        step = ShardedFusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(), mesh=mesh,
            axis="sharding", scaler=sc)
        ids, labels = _batch(bs=N_DEV)
        self._run_nan_injection(step, model, sc, ids, labels,
                                self._wte_state_index(step, model))


# ---------------------------------------------------------------------------
# sharded round trip: save under dp=8, restore, continue bit-identical
# ---------------------------------------------------------------------------

class TestShardedRoundTrip:
    def _build(self, mesh):
        model, opt = _gpt(num_layers=2)
        step = ShardedFusedScanTrainStep(
            model, opt, criterion=GPTPretrainingCriterion(), mesh=mesh,
            axis="sharding")
        ids, labels = _batch(bs=N_DEV)
        return model, opt, step, ids, labels

    def test_save_restore_next_step_bit_identical(self, mesh, tmp_path):
        """Acceptance: save under the dp=8 host mesh (1/N
        __scan_shard_*__ state included), restore into a fresh
        model/optimizer, and the next-step loss is bit-identical to an
        uninterrupted run; async save blocks the loop only for the
        device->host snapshot."""
        model, opt, step, ids, labels = self._build(mesh)
        straight = [float(step(ids, labels)) for _ in range(4)]

        model, opt, step, ids, labels = self._build(mesh)
        part1 = [float(step(ids, labels)) for _ in range(2)]
        mgr = CheckpointManager(str(tmp_path / "ck"), model=model,
                                optimizer=opt, async_save=True)
        mgr.save(1)
        mgr.wait()
        timings = dict(mgr.last_timings)
        assert timings["blocked_s"] < timings["io_s"] + \
            timings["snapshot_s"] + 1.0   # sanity: did not block on IO

        # the 1/N shard structure must be ON DISK (8 chunks per flat
        # moment), not a gathered replica
        meta = verify_checkpoint(str(tmp_path / "ck" / "step_1"))
        flat_chunks = meta.state_dict_metadata[
            "optimizer.accumulators.moment1.__scan_shard_s0__"]
        assert len(flat_chunks) == N_DEV

        model2, opt2 = _gpt(seed=99, num_layers=2)
        step2 = ShardedFusedScanTrainStep(
            model2, opt2, criterion=GPTPretrainingCriterion(),
            mesh=mesh, axis="sharding")
        step2.ensure_built()            # sharded state slots exist
        mgr2 = CheckpointManager(str(tmp_path / "ck"), model=model2,
                                 optimizer=opt2)
        assert mgr2.restore_or_init() == 1
        # restored flat state keeps its 1/N live sharding
        flat = opt2._accumulators["moment1"]["__scan_shard_s0__"]
        shards = flat.addressable_shards
        assert len(shards) == N_DEV
        assert shards[0].data.shape[-1] * N_DEV == flat.shape[-1]
        part2 = [float(step2(ids, labels)) for _ in range(2)]
        assert straight == part1 + part2


# ---------------------------------------------------------------------------
# eager GradScaler: fused unscale, found_inf on device until decision
# ---------------------------------------------------------------------------

class TestEagerScalerFusedUnscale:
    def test_found_inf_stays_on_device_until_step(self):
        paddle.seed(0)
        m = nn.Linear(4, 2)
        opt = popt.SGD(learning_rate=0.1, parameters=m.parameters())
        sc = GradScaler(init_loss_scaling=4.0)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        loss = sc.scale(m(x).sum())
        loss.backward()
        sc.unscale_(opt)
        assert isinstance(sc._found_inf, jax.Array)   # NOT synced yet
        sc.step(opt)
        assert isinstance(sc._found_inf, bool)        # single readback
        sc.update()

    def test_unscale_divides_and_detects(self):
        paddle.seed(0)
        m = nn.Linear(4, 2)
        opt = popt.SGD(learning_rate=0.1, parameters=m.parameters())
        sc = GradScaler(init_loss_scaling=8.0)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        loss = sc.scale(m(x).sum())
        loss.backward()
        g_scaled = np.asarray(m.weight.grad._data).copy()
        sc.unscale_(opt)
        np.testing.assert_allclose(np.asarray(m.weight.grad._data),
                                   g_scaled / 8.0, rtol=1e-6)
        assert not bool(sc._found_inf)
        # inf grad detected by the fused reduction
        m.weight.grad._data = m.weight.grad._data.at[0, 0].set(jnp.inf)
        sc._opt_states.clear()
        sc.unscale_(opt)
        assert bool(sc._found_inf)
