"""Persistent AOT executable cache (ISSUE 17): key invalidation on
every axis the key policy names (source/HLO edit, FLAGS flip, jaxlib
bump, donation change, mesh shape), byte-identical rebuild HIT,
corrupted-entry self-eviction, the LRU size cap, cached-vs-fresh
bit-identity on a real train step, and the shared fingerprint
helpers the bench/sweep/calib hashes build on."""
import json
import os
import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.jit.compile_cache import (
    CachedJit, CompileCache, cache_key_components, cached_jit,
    digest_key, file_fingerprint, fingerprint, set_cache_dir,
    signature_fingerprint, source_fingerprint,
)


@pytest.fixture
def cache_dir(tmp_path):
    """Enable the persistent cache for one test, restore disabled."""
    d = str(tmp_path / "cc")
    set_cache_dir(d)
    try:
        yield d
    finally:
        set_cache_dir(None)


# ---------------------------------------------------------------------------
# shared fingerprint helpers (satellite: one hashing recipe)
# ---------------------------------------------------------------------------

class TestFingerprintHelpers:
    def test_fingerprint_deterministic_and_prefixed(self):
        a = fingerprint(["x", b"y"], prefix="hlo")
        assert a == fingerprint(["x", b"y"], prefix="hlo")
        assert a.startswith("hlo:") and len(a) == 4 + 16
        assert fingerprint("xy") == fingerprint(["x", "y"])
        assert fingerprint("xy") != fingerprint("yx")
        assert len(fingerprint("x", width=32)) == 32
        assert len(fingerprint("x", width=None)) == 64

    def test_source_fingerprint_tracks_code(self):
        def f(x):
            return x + 1

        def g(x):
            return x + 2

        assert source_fingerprint(f) == source_fingerprint(f)
        assert source_fingerprint(f) != source_fingerprint(g)
        # extra parts (e.g. a toolchain version) key in
        assert source_fingerprint(f, extra=("v1",)) != \
            source_fingerprint(f, extra=("v2",))
        # unsourceable objects degrade to qualname, never raise
        assert source_fingerprint(len).startswith("src:")

    def test_file_fingerprint(self, tmp_path):
        p = tmp_path / "a.py"
        p.write_text("one")
        h1 = file_fingerprint([str(p)])
        p.write_text("two")
        assert file_fingerprint([str(p)]) != h1
        # missing file contributes its path — stable, no raise
        assert file_fingerprint([str(tmp_path / "gone")]) == \
            file_fingerprint([str(tmp_path / "gone")])

    def test_signature_fingerprint_axes(self):
        x = jnp.arange(4.0)
        assert signature_fingerprint((x,)) == signature_fingerprint((x,))
        # dtype, shape and pytree structure all key in
        assert signature_fingerprint((x,)) != \
            signature_fingerprint((x.astype(jnp.int32),))
        assert signature_fingerprint((x,)) != \
            signature_fingerprint((jnp.arange(8.0),))
        assert signature_fingerprint((x,)) != \
            signature_fingerprint(({"a": x},))

    def test_calib_hash_rides_shared_helper(self):
        # the planner's invalidation hash is the shared recipe (bare
        # hex, code+jax-version keyed) — not a third sha256 variant
        from paddle_tpu.distributed.auto_tuner import select
        from paddle_tpu.distributed.auto_tuner import tuner as at

        want = source_fingerprint(at.calibrate_backend,
                                  at.estimate_step_ms,
                                  extra=(jax.__version__,), prefix=None)
        assert select._calib_hash() == want


# ---------------------------------------------------------------------------
# key policy: every axis invalidates, byte-identical rebuild hits
# ---------------------------------------------------------------------------

def _components(**over):
    base = {"sig": "s0", "hlo": "hlo:abc", "donate_argnums": (),
            "label": "T", "mesh": None}
    base.update(over)
    return cache_key_components(**base)


class TestKeyComponents:
    def test_stable(self):
        assert digest_key(_components()) == digest_key(_components())

    def test_each_axis_changes_key(self, monkeypatch):
        base = digest_key(_components())
        assert digest_key(_components(sig="s1")) != base
        assert digest_key(_components(hlo="hlo:def")) != base
        assert digest_key(_components(donate_argnums=(0,))) != base
        assert digest_key(_components(label="U")) != base
        assert digest_key(_components(mesh={"dp": 4})) != base
        assert digest_key(_components(mesh={"dp": 2, "mp": 2})) != \
            digest_key(_components(mesh={"dp": 4}))

    def test_jaxlib_bump_changes_key(self, monkeypatch):
        import jaxlib

        base = digest_key(_components())
        monkeypatch.setattr(jaxlib, "__version__", "99.99.99",
                            raising=False)
        assert digest_key(_components()) != base

    def test_flag_flip_changes_key(self):
        from paddle_tpu.utils import flags as _flags

        old = _flags.get_flag("FLAGS_numerics_monitor")
        base = digest_key(_components())
        try:
            _flags.set_flags({"FLAGS_numerics_monitor": not old})
            assert digest_key(_components()) != base
        finally:
            _flags.set_flags({"FLAGS_numerics_monitor": old})


# ---------------------------------------------------------------------------
# the store + CachedJit end to end
# ---------------------------------------------------------------------------

def _run_leg(script_path, cache_dir):
    """One cache 'leg' in a FRESH process (a warm start is by
    definition a new process; XLA:CPU also cannot reliably re-load an
    executable into the process that serialized it). Returns the JSON
    line the script prints."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env["PADDLE_TPU_COMPILE_CACHE"] = cache_dir or ""   # "" = disabled
    r = subprocess.run([sys.executable, str(script_path)], env=env,
                       capture_output=True, text=True, timeout=300)
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    assert r.returncode == 0 and line, (r.returncode, r.stderr[-800:])
    return json.loads(line)


_LAMBDA_LEG = """\
import json
import jax.numpy as jnp
from paddle_tpu.jit.compile_cache import cached_jit
f = cached_jit(lambda v: v * 2 + 1, label="t")
y = f(jnp.arange(8.0))
print(json.dumps({"hits": f.disk_hits, "misses": f.disk_misses,
                  "out": repr(float(y.sum()))}))
"""


class TestCachedJit:
    def test_miss_then_fresh_process_hits(self, cache_dir, tmp_path):
        # the same script byte-identically re-run in a fresh process:
        # first leg fills (MISS), second leg deserializes (HIT), same
        # numbers out
        script = tmp_path / "leg.py"
        script.write_text(_LAMBDA_LEG)
        cold = _run_leg(script, cache_dir)
        assert cold["misses"] == 1 and cold["hits"] == 0
        assert len(os.listdir(cache_dir)) == 2     # .bin + .json
        warm = _run_leg(script, cache_dir)
        assert warm["hits"] == 1 and warm["misses"] == 0
        assert warm["out"] == cold["out"]

    def test_source_edit_misses(self, cache_dir):
        x = jnp.arange(8.0)
        cached_jit(lambda v: v * 2, label="t")(x)
        f2 = cached_jit(lambda v: v * 2 + 1, label="t")   # edited body
        f2(x)
        assert f2.disk_misses == 1 and f2.disk_hits == 0

    def test_signature_change_misses(self, cache_dir):
        f = cached_jit(lambda v: v * 2, label="t")
        f(jnp.arange(8.0))
        f(jnp.arange(8))                          # dtype flip
        assert f.disk_misses == 2

    def test_donation_change_misses(self, cache_dir):
        x = jnp.arange(8.0)
        cached_jit(lambda v: v * 2, label="t")(x)
        f2 = cached_jit(lambda v: v * 2, donate_argnums=(0,),
                        label="t")
        f2(jnp.arange(8.0))
        assert f2.disk_misses == 1 and f2.disk_hits == 0

    def test_flag_flip_misses(self, cache_dir):
        from paddle_tpu.utils import flags as _flags

        x = jnp.arange(8.0)
        cached_jit(lambda v: v * 2, label="t")(x)
        old = _flags.get_flag("FLAGS_numerics_monitor")
        try:
            _flags.set_flags({"FLAGS_numerics_monitor": not old})
            f2 = cached_jit(lambda v: v * 2, label="t")
            f2(x)
            assert f2.disk_misses == 1 and f2.disk_hits == 0
        finally:
            _flags.set_flags({"FLAGS_numerics_monitor": old})

    def test_corrupted_entry_self_evicts_and_recovers(self, cache_dir):
        x = jnp.arange(8.0)
        f1 = cached_jit(lambda v: v * 3, label="t")
        y1 = f1(x)
        bin_path = next(os.path.join(cache_dir, n)
                        for n in os.listdir(cache_dir)
                        if n.endswith(".bin"))
        with open(bin_path, "wb") as fh:
            fh.write(b"garbage" * 10)
        f2 = cached_jit(lambda v: v * 3, label="t")
        y2 = f2(x)                    # falls back to a fresh compile
        assert f2.disk_misses == 1
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
        # the corrupt entry was evicted, then re-put by the recompile
        with open(bin_path, "rb") as fh:
            rec = pickle.load(fh)     # readable again
        assert set(rec) == {"payload", "in_tree", "out_tree",
                            "device_ids"}

    def test_disabled_cache_is_plain_jit(self, tmp_path):
        set_cache_dir(None)
        f = cached_jit(lambda v: v + 1, label="t")
        y = f(jnp.arange(4.0))
        np.testing.assert_array_equal(np.asarray(y),
                                      np.arange(4.0) + 1)
        assert f.disk_hits == 0 and f.disk_misses == 0

    def test_lower_and_cache_size_api(self, cache_dir):
        f = cached_jit(lambda v: v * 2, label="t")
        assert "stablehlo" in f.lower(jnp.arange(4.0)).as_text().lower()
        f(jnp.arange(4.0))
        assert f._cache_size() >= 1


class TestStoreInventory:
    def _fill(self, root, n, size=1000):
        c = CompileCache(root, max_bytes=10**9)
        for i in range(n):
            key = f"{i:032x}"
            with open(c._bin(key), "wb") as f:
                f.write(b"x" * size)
            with open(c._meta(key), "w") as f:
                json.dump({"key": key, "bytes": size, "hits": 0,
                           "last_used": float(i),
                           "components": {"label": f"L{i}"}}, f)
        return c

    def test_entries_and_stats(self, tmp_path):
        c = self._fill(str(tmp_path), 3)
        ents = c.entries()
        assert len(ents) == 3
        # most recently used first
        assert [e.meta["components"]["label"] for e in ents] == \
            ["L2", "L1", "L0"]
        st = c.stats()
        assert st["entries"] == 3 and st["bytes"] == 3000

    def test_evict_and_clear(self, tmp_path):
        c = self._fill(str(tmp_path), 3)
        assert c.evict(c.entries()[0].key)
        assert len(c.entries()) == 2
        assert not c.evict("0" * 32 + "nope")
        assert c.clear() == 2
        assert c.entries() == []

    def test_lru_cap_evicts_oldest(self, tmp_path):
        c = self._fill(str(tmp_path), 4, size=1000)
        c.max_bytes = 2500            # fits 2 of 4
        c._enforce_cap()
        left = {e.meta["components"]["label"] for e in c.entries()}
        assert left == {"L3", "L2"}   # LRU victims were L0, L1

    def test_cap_never_evicts_sole_entry(self, tmp_path):
        c = self._fill(str(tmp_path), 1, size=5000)
        c.max_bytes = 100
        c._enforce_cap()
        assert len(c.entries()) == 1


# ---------------------------------------------------------------------------
# bit-identity on a real train path (cold fill vs warm hit vs no cache)
# ---------------------------------------------------------------------------

_TRAIN_LEG = """\
import json
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
)

paddle.seed(0)
cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                num_attention_heads=4, max_position_embeddings=32,
                hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
model = GPTForCausalLM(cfg)
crit = GPTPretrainingCriterion()
opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
step = TrainStep(model, lambda m, i, l: crit(m(i), l), opt)
rng = np.random.default_rng(0)
ids = paddle.to_tensor(rng.integers(1, 128, (2, 32)), dtype="int64")
losses = [float(step(ids, ids)) for _ in range(2)]
psum = float(np.sum([np.asarray(p._data, np.float64).sum()
                     for p in model.parameters()]))
print(json.dumps({"losses": losses, "psum": psum,
                  "hits": step._jitted.disk_hits,
                  "misses": step._jitted.disk_misses,
                  "sentinel": step.retrace_stats()}))
"""


@pytest.mark.slow
class TestTrainStepBitIdentity:
    def test_cold_fill_and_warm_hit_match_uncached(self, tmp_path):
        # three FRESH PROCESSES running the same train script: no
        # cache, cold fill, warm hit — losses and the updated param
        # checksum must be bit-identical across all three (json float
        # round-trip is exact)
        script = tmp_path / "leg.py"
        script.write_text(_TRAIN_LEG)
        cc = str(tmp_path / "cc")
        base = _run_leg(script, None)
        assert base["hits"] == 0 and base["misses"] == 0
        cold = _run_leg(script, cc)
        assert cold["misses"] >= 1 and cold["hits"] == 0
        warm = _run_leg(script, cc)
        assert warm["hits"] >= 1
        assert warm["misses"] == 0, "unstable cache key across processes"
        assert base["losses"] == cold["losses"] == warm["losses"]
        assert base["psum"] == cold["psum"] == warm["psum"]
        # retrace sentinel strict-clean under the cache
        for leg in (cold, warm):
            s = leg["sentinel"]
            assert s["unexpected"] == 0 and s["signatures"] == 1
