"""What the chip cannot re-check every day (ISSUE 21): nothing hides the
device, one process per chip, one compile cache placed from outside,
and the entry scripts fail — not fall back — where there is no TPU."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import env as denv
from paddle_tpu.ops.pallas import fused_cross_entropy as fce
from paddle_tpu.ops.pallas import routing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_over=None, env_drop=(), timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in env_drop and k != "XLA_FLAGS"}   # one host device
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    env.update(env_over or {})
    r = subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=timeout)
    return r.returncode, r.stdout, r.stderr


def test_set_device_tpu_raises_without_tpu():
    import jax

    before = paddle.get_device()
    with pytest.raises(RuntimeError, match="no TPU"):
        paddle.set_device("tpu")
    with pytest.raises(RuntimeError, match="no TPU"):
        paddle.TPUPlace(0).jax_device()
    assert paddle.get_device() == before == "cpu:0"
    # set_device is real: new tensors and the kernel routing follow it
    try:
        paddle.set_device("cpu:1")
        assert jax.config.jax_default_device == jax.devices("cpu")[1]
        assert paddle.ones([2])._data.devices() == {jax.devices("cpu")[1]}
        assert not routing.on_tpu()
    finally:
        paddle.set_device("cpu")
        jax.config.update("jax_default_device", None)


def test_build_mesh_raises_rather_than_borrowing_devices():
    import jax

    with pytest.raises(ValueError, match="needs .* devices"):
        denv.build_mesh({"dp": 2 * len(jax.devices())})


def test_unsupported_kernel_geometry_is_visible():
    rng = np.random.default_rng(0)
    # float64 operands: the fused-CE kernels take 32-bit floats and
    # narrower (a vocabulary of 100 = no whole tile they take since PR 40)
    h = jnp.asarray(rng.standard_normal((8, 16)), jnp.float64)
    w = jnp.asarray(rng.standard_normal((100, 16)), jnp.float64)
    lbl = jnp.asarray(rng.integers(0, 100, (8,)), jnp.int32)
    key = ("fused_cross_entropy",
           ("vocab=100", "hidden=16", "float64"))
    n0 = routing.xla_fallbacks[key]
    # on the CPU with no interpret request the XLA path IS the path: quiet
    want = fce.fused_cross_entropy(h, w, lbl)
    assert routing.xla_fallbacks[key] == n0
    # asked for the kernel: the XLA path is taken and counted
    got = fce.fused_cross_entropy(h, w, lbl, interpret=True)
    assert routing.xla_fallbacks[key] == n0 + 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="does not support"):
        fce.fused_cross_entropy(h, w, lbl, use_kernel=True, interpret=True)


_CACHE_CHILD = """
import sys
import jax
import paddle_tpu, paddle_tpu.serving, paddle_tpu.jit
import paddle_tpu.distributed.launch
from jax._src import xla_bridge
assert not xla_bridge._backends, xla_bridge._backends   # no backend yet
from paddle_tpu.utils.compile_cache_dir import use_compile_cache
print("CACHE", use_compile_cache())
import paddle_tpu as paddle, paddle_tpu.nn as nn, paddle_tpu.optimizer as popt
from paddle_tpu.jit import TrainStep
m = nn.Linear(4, 3)
salt = float(sys.argv[1])          # a program no earlier run compiled
step = TrainStep(m, lambda mm, a: ((mm(a) - salt) ** 2).mean(),
                 popt.SGD(learning_rate=0.1, parameters=m.parameters()))
print("LOSS", float(step(paddle.ones([2, 4]))))
def placed_from_outside_witness(x):   # after the step: had the package
    return x * salt                   # moved the cache, this would follow
jax.jit(placed_from_outside_witness)(jax.numpy.ones(3)).block_until_ready()
"""
_KEEP_ALL = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_compile_cache_is_placed_from_outside(tmp_path):
    """A child given JAX_COMPILATION_CACHE_DIR runs one TrainStep
    through the helper: the entries land there and nowhere else (the
    other workers of a parallel run write `jit_step_fn-*` entries into
    <checkout>/.jax_cache meanwhile, so "nowhere else" is read off a
    function only the child compiles, after its step has run). The
    same child pins that importing the package (and .serving, .jit,
    .distributed.launch) initialises no backend — a launcher parent must
    leave the chip to its child. Without the variable the place is
    <checkout>/.jax_cache: this process (tests/conftest.py called the
    helper) is the witness."""
    import time

    import jax

    from paddle_tpu.utils.compile_cache_dir import use_compile_cache

    default = os.path.join(REPO, ".jax_cache")
    before = _entries(default)
    rc, out, err = _run(
        ["-c", _CACHE_CHILD, repr(time.time())],
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path), **_KEEP_ALL})
    assert rc == 0, err[-2000:]
    assert f"CACHE {tmp_path}" in out
    given = _entries(str(tmp_path))
    assert any(e.startswith("jit_step_fn-") for e in given), given
    assert any("placed_from_outside_witness" in e for e in given), given
    leaked = {e for e in _entries(default) - before
              if "placed_from_outside_witness" in e}
    assert not leaked, f"entries leaked into .jax_cache: {leaked}"

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR") or default
    assert use_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == placed
    before = _entries(placed)
    salt = time.time()
    jax.jit(lambda x: x * salt)(jnp.ones(3)).block_until_ready()
    assert _entries(placed) - before, f"no new entry in {placed}"


def test_chip_smoke_tiny_passes_and_plain_run_finds_no_tpu():
    rc, out, err = _run(["chip_smoke.py", "--tiny"], _KEEP_ALL)
    assert rc == 0, (out[-3000:], err[-2000:])
    last = out.strip().splitlines()[-1]
    assert '"ok": true' in last and '"tiny": true' in last \
        and '"platform": "cpu"' in last
    rc, out, err = _run(["chip_smoke.py"])
    assert rc != 0
    assert "no TPU" in out + err
    assert '"ok"' not in out


def test_benchmark_fails_on_cpu_rather_than_printing_a_metric():
    rc, out, err = _run(["benchmark/run.py", "--workload",
                         "gpt3-350m.train.8x1024", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert "benchmark: no TPU" in err
    assert "train_tok_s_chip" not in out and '"metrics"' not in out


def test_model_built_off_the_mesh_shards_without_touching_chip_0():
    """train4's set-up: the model is built on the host and the sharded
    step ships each device its 1/N. Same losses as a model built on the
    mesh's first device, and nothing is left where it was built."""
    import jax

    import paddle_tpu.optimizer as popt
    from paddle_tpu.jit import ShardedFusedScanTrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    devs = jax.devices()
    mesh = denv.build_mesh({"sharding": 4}, devices=devs[:4])
    ids = paddle.to_tensor(
        np.random.default_rng(0).integers(0, 96, (4, 16)), dtype="int64")

    def losses(build_on):
        try:
            paddle.set_device(build_on)
            paddle.seed(0)
            model = GPTForCausalLM(GPTConfig(
                vocab_size=96, hidden_size=32, num_layers=2,
                num_attention_heads=2, max_position_embeddings=16,
                scan_layers=True))
            opt = popt.AdamW(learning_rate=1e-2,
                             parameters=model.parameters())
        finally:
            paddle.set_device("cpu")
            jax.config.update("jax_default_device", None)
        step = ShardedFusedScanTrainStep(model, opt, mesh=mesh,
                                         axis="sharding")
        out = [float(step(ids, ids)) for _ in range(2)]
        return out, step

    on_mesh, _ = losses("cpu:0")
    off_mesh, step = losses("cpu:7")
    assert on_mesh == off_mesh
    for flat in step._param_shards["s"] + step._param_shards["o"]:
        assert {s.device for s in flat.addressable_shards} == set(devs[:4])
