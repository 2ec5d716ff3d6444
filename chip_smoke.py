#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls,
at the full width of gpt3-1.3b (hidden 2048, 24 layers, 32 heads of 64,
vocab 50,304), with seeded random weights:

  kernels  every Pallas kernel, COMPILED, against its own XLA reference
           on the chip at the geometry the two runs below use
  train    FusedScanTrainStep, batch 8 x 1024, 6 steps on one batch
  serve    ServingEngine (paged KV: bf16, int8, int4), greedy requests
  train4   ShardedFusedScanTrainStep through fleet on four chips — run
           when the machine has four (`--train4` demands it)

The top-level process imports neither jax nor paddle_tpu: a chip belongs
to one process, so each phase is a child that owns it for its lifetime.
Any phase that fails, or finds no TPU, makes the run exit non-zero with
no result line. On success the last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.

`--tiny` rehearses the same code on the CPU (2 layers, hidden 64, kernels
in interpret mode); its result line says `"tiny": true, "platform": "cpu"`
and is never a chip pass. Times and rates printed here are smoke output,
not benchmark results.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150        # the whole run, compilation included

FULL = dict(tiny=False, seq=1024, batch=8, steps=6, heads=32, hd=64,
            hidden=2048, vocab=50304, page=16, chunk=64, max_len=1024,
            slots=8, prompts=(17, 64, 200, 513, 700, 960), new=32,
            flash=(512, 2048), lens=(0, 1, 17, 64, 200, 513, 700, 1000),
            chunk_start=(0, 3, 64, 900), batch4=32)
TINY = dict(tiny=True, seq=128, batch=4, steps=6, heads=2, hd=32,
            hidden=64, vocab=512, page=16, chunk=16, max_len=64,
            slots=8, prompts=(5, 16, 20, 33, 40, 50), new=4,
            flash=(128,), lens=(0, 1, 5, 16, 33, 40, 50, 64),
            chunk_start=(0, 3, 16, 40), batch4=8)

# Tolerances, as max|got - want| / max|want| in fp32. bf16 carries 8
# mantissa bits (spacing 2^-8 = 4e-3 relative): a kernel whose operands,
# probabilities or outputs round to bf16 agrees with an fp32-internal
# reference to a few of those, so 2e-2. The fused-CE loss is fp32 over
# the same vocab tiles in the same order on both sides: 1e-3.
TOL_BF16 = 2e-2
TOL_CE_LOSS = 1e-3
# The index scores are float32 sums of the same exact bf16 products on
# both sides, the heads in another order.
TOL_INDEXER = 1e-4
# one chip vs four chips, same global batch: per-step loss, bf16 compute,
# different batch split and reduction order. tests/test_sharded_scan.py
# uses rtol = atol = 5e-4 in fp32 on the CPU; widened here for bf16.
TOL_LOSS_4CHIP = 2e-2


FAILED = []     # a failed check does not stop its phase: one chip call
                # should say everything that is wrong; the phase fails


def ok(cond, what):
    print(f"  {'ok ' if cond else 'BAD'} {what}", flush=True)
    if not cond:
        FAILED.append(what)


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def phase_kernels(z):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import fused_cross_entropy as fce
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.ops.pallas import splash_attention as sa

    rng = np.random.default_rng(0)
    f32, bf16 = jnp.float32, jnp.bfloat16
    kern = dict(use_kernel=True, interpret=z.tiny)   # never the XLA path

    def randn(shape, scale=0.5, dtype=bf16):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    def agree(name, got, want, tol):
        for part, g, w in zip(("out", "d0", "d1", "d2"), got, want):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            err = float(np.abs(g - w).max() / max(np.abs(w).max(), 1e-6))
            ok(np.isfinite(g).all() and err < tol,
               f"{name} {part}{list(g.shape)} rel err {err:.2e} < {tol}")

    def fwd_bwd(fn, args, wgt):
        """(out, grads) of sum(fn(*args) * wgt) — one jitted program."""
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out.astype(f32) * wgt), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, tuple(range(len(args))), has_aux=True))(*args)
        return (out,) + tuple(grads)

    # training attention: splash causal, then with segment ids; flash
    shape = (z.batch, z.seq, z.heads, z.hd)
    qkv = [randn(shape) for _ in range(3)]
    seg = jnp.asarray(np.broadcast_to(
        np.searchsorted([z.seq // 3, 2 * z.seq // 3 + 5],
                        np.arange(z.seq), side="right"),
        (z.batch, z.seq)), jnp.int32)
    wgt = randn(shape, 1.0, f32)
    for name, s in (("splash causal", None), ("splash segments", seg)):
        agree(f"{name} q{list(shape)}",
              fwd_bwd(lambda q, k, v: sa.splash_attention(
                  q, k, v, causal=True, segment_ids=s, **kern), qkv, wgt),
              fwd_bwd(lambda q, k, v: sa.splash_attention_xla(
                  q, k, v, causal=True, segment_ids=s), qkv, wgt),
              TOL_BF16)
    for s in z.flash:
        shape = (2, s, 4, 64)
        qkv = [randn(shape) for _ in range(3)]
        wgt = randn(shape, 1.0, f32)
        agree(f"flash causal q{list(shape)}",
              fwd_bwd(lambda q, k, v: fa.flash_attention(
                  q, k, v, causal=True, interpret=z.tiny), qkv, wgt),
              fwd_bwd(lambda q, k, v: sa.splash_attention_xla(
                  q, k, v, causal=True), qkv, wgt), TOL_BF16)

    # LM head + loss: fused CE, per-token loss and dh / dW
    n = z.batch * z.seq
    h, w = randn((n, z.hidden)), randn((z.vocab, z.hidden), 0.02)
    lbl = rng.integers(0, z.vocab, (n,))
    lbl[::97] = -100                                   # ignored rows
    lbl = jnp.asarray(lbl, jnp.int32)
    wgt = randn((n,), 1.0, f32)
    got = fwd_bwd(lambda h, w: fce.fused_cross_entropy(h, w, lbl, **kern),
                  (h, w), wgt)
    want = fwd_bwd(lambda h, w: fce.fused_cross_entropy(
        h, w, lbl, use_kernel=False), (h, w), wgt)
    name = f"fused CE n={n} hidden={z.hidden} vocab={z.vocab}"
    agree(name + " loss", got[:1], want[:1], TOL_CE_LOSS)
    agree(name + " grads", got, want, TOL_BF16)

    # the sparse indexer's scores of one query chunk and their pull-back
    # at Keye's widths: a first chunk (fifteen key tiles of sixteen have
    # no body), a middle one, the last
    from paddle_tpu.ops import sparse_attention as spa
    from paddle_tpu.ops.pallas import indexer_scores as isc

    t, s, j, d = (32, 256, 4, 16) if z.tiny else (512, 8192, 16, 64)
    qkw = [randn((t, j, d)), randn((s, d)), randn((t, j))]
    for t0 in (0, s // 2 - t, s - t):
        valid = jnp.arange(s)[None, :] <= t0 + jnp.arange(t)[:, None]
        wgt = jnp.where(valid, randn((t, s), 1.0, f32), 0.0)
        got = fwd_bwd(lambda q, k, w: jnp.where(
            valid, isc.causal_indexer_scores(
                q, k, w, jnp.int32(t0), interpret=z.tiny), 0.0), qkw, wgt)
        want = fwd_bwd(lambda q, k, w: jnp.where(
            valid, spa.indexer_scores(q, k, w), 0.0), qkw, wgt)
        name = f"indexer scores q{[t, j, d]} k{[s, d]} t0={t0}"
        agree(name, got[:1], want[:1], TOL_INDEXER)
        agree(name + " grads", got, want, TOL_BF16)
        ok(not np.asarray(got[2], np.float32)[t0 + t:].any(),
           name + ": dk_idx beyond the chunk's last query is exactly 0")

    # serving attention: ragged decode + chunk, bf16 / int8 / int4 pools
    b, nh, d, ps = z.slots, z.heads, z.hd, z.page
    pp = z.max_len // ps
    n_pages = 1 + b * pp
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                         .reshape(b, pp), jnp.int32)
    lens = jnp.asarray(z.lens, jnp.int32)              # [0] = empty slot
    start = jnp.asarray(z.chunk_start, jnp.int32)
    bc = len(z.chunk_start)
    q1, qc = randn((b, nh, d)), randn((bc, z.chunk, nh, d))
    for quant in (None, "int8", "int4"):
        if quant is None:
            pools, sc = [randn((nh, n_pages, ps, d)) for _ in "kv"], {}
        else:
            lo, hi, dt, dd = ((-127, 128, jnp.int8, d) if quant == "int8"
                              else (0, 256, jnp.uint8, d // 2))
            pools = [jnp.asarray(rng.integers(lo, hi, (nh, n_pages, ps, dd)),
                                 dt) for _ in "kv"]
            sc = {f"{x}_scales": jnp.asarray(rng.uniform(
                1e-3, 1e-2, (nh, n_pages, ps)), f32) for x in "kv"}
        agree(f"paged decode {quant or 'bf16'} heads={nh}x{d} page={ps} "
              f"lens={list(z.lens)}",
              (jax.jit(lambda q, k, v: pa.paged_attention(
                  q, k, v, tables, lens, **sc, **kern))(q1, *pools),),
              (jax.jit(lambda q, k, v: pa.paged_attention_xla(
                  q, k, v, tables, lens, **sc))(q1, *pools),), TOL_BF16)
        agree(f"paged chunk {quant or 'bf16'} c={z.chunk} "
              f"start={list(z.chunk_start)}",
              (jax.jit(lambda q, k, v: pa.paged_attention_chunk(
                  q, k, v, tables[:bc], start, **sc, **kern))(qc, *pools),),
              (jax.jit(lambda q, k, v: pa.paged_attention_chunk_xla(
                  q, k, v, tables[:bc], start, **sc))(qc, *pools),),
              TOL_BF16)


# ---------------------------------------------------------------------------
# phases: train, train4
# ---------------------------------------------------------------------------

def _gpt_config(z, seq, **kw):
    from paddle_tpu.models import GPTConfig, gpt_config

    if z.tiny:
        return GPTConfig(vocab_size=z.vocab, hidden_size=z.hidden,
                         num_layers=2, num_attention_heads=z.heads,
                         max_position_embeddings=seq, **kw)
    return gpt_config("gpt3-1.3b", max_position_embeddings=seq, **kw)


def _train_setup(z):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu.models import GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(_gpt_config(z, z.seq, scan_layers=True))
    opt = popt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                     moment_dtype="bfloat16")
    return model, opt


def _run_steps(z, step, batch, steps, t_build):
    """Feed one repeated seeded batch through step.prefetch; -> (losses,
    the last device batch). Set-up (build + compile + first step) is
    printed apart from the steady steps."""
    import numpy as np

    rng = np.random.default_rng(0)
    ids = rng.integers(0, z.vocab, (batch, z.seq), dtype=np.int64)
    labels = rng.integers(0, z.vocab, (batch, z.seq), dtype=np.int64)
    losses, times = [], []
    for a, b in step.prefetch(((ids, labels) for _ in range(steps))):
        t = time.perf_counter()
        losses.append(float(step(a, b)))
        times.append(time.perf_counter() - t)
    print(f"  set-up (build + compile + step 0): "
          f"{time.perf_counter() - t_build - sum(times[1:]):.1f} s; "
          f"steps 1..{steps - 1}: " + " ".join(f"{t:.3f}" for t in times[1:])
          + f" s = {batch * z.seq / min(times[1:]):.0f} tok/s at best "
          "(smoke output, not a benchmark)")
    print("  losses: " + " ".join(f"{v:.4f}" for v in losses))
    ok(all(np.isfinite(losses)), "every step's loss is finite")
    ok(losses[-1] < losses[0], f"loss fell: {losses[0]:.4f} -> "
       f"{losses[-1]:.4f}")
    ok(step._jitted._cache_size() == 1 and
       not step.retrace_stats()["unexpected"],
       "one executable, retrace sentinel clean")
    return losses, (a, b)


def _peaks(devices):
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    print("  peak_bytes_in_use per device: " + ", ".join(
        "not reported" if p is None else f"{p / 2**30:.2f} GiB"
        for p in out))
    return out


def phase_train(z):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.jit import FusedScanTrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.ops.pallas import routing

    paddle.set_device(z.platform)
    t0 = time.perf_counter()
    model, opt = _train_setup(z)
    step = FusedScanTrainStep(model, opt,
                              criterion=GPTPretrainingCriterion(),
                              fused_head=True, compute_dtype="bfloat16")
    losses, (ids, labels) = _run_steps(z, step, z.batch, z.steps, t0)
    state = step._extract_state()
    ok({d.platform for leaf in jax.tree_util.tree_leaves(state)
        for d in leaf.devices()} == {z.platform},
       f"every state leaf lives on a {z.platform} device")
    hlo = step._jitted.lower(state, jnp.float32(1e-4), ids._data,
                             labels._data, None).compile().as_text()
    kernels = routing.mosaic_kernels(hlo)
    print(f"  Mosaic calls in the compiled step: {dict(kernels)}")
    ok(z.tiny or {"splash_fwd", "splash_bwd", "fused_ce_fwd",
                  "fused_ce_bwd"} <= set(kernels),
       "splash fwd+bwd and fused-CE fwd+bwd run as Mosaic kernels"
       + (" (tiny: interpreted, not checked)" if z.tiny else ""))
    bad = routing.forbidden_shapes(hlo, z.batch, z.seq, z.vocab)
    ok(z.tiny or not bad,
       f"no [{z.batch * z.seq}, {z.vocab}] logits and no [{z.batch}, "
       f"{z.heads}, {z.seq}, {z.seq}] scores buffer {bad[:3]}"
       + (" (tiny: a score TILE is [seq, seq], not checked)"
          if z.tiny else ""))
    _peaks(jax.devices()[:1])
    print("RESULT " + json.dumps({"losses": losses}))


def phase_train4(z):
    import gc

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTPretrainingCriterion

    if len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke: train4 needs four chips, found "
                         f"{len(jax.devices())}")

    def run(hybrid, batch, steps):
        denv.reset()
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = hybrid
        fleet.init(is_collective=True, strategy=strategy)
        t0 = time.perf_counter()
        # built on the host: an eager 1.3b initialisation on the default
        # chip piles 5 GB on chip 0 before the first shard exists
        paddle.set_device("cpu")
        model, opt = _train_setup(z)
        paddle.set_device(z.platform)
        step = fleet.distributed_model(model).train_step(
            opt, criterion=GPTPretrainingCriterion(), fused_head=True,
            compute_dtype="bfloat16")
        print(f" {hybrid} global batch {batch}: {type(step).__name__}")
        losses, _ = _run_steps(z, step, batch, steps, t0)
        mesh_devs = list(step._mesh.devices.flat)
        ok(len(set(mesh_devs)) == 4 and
           {d.platform for d in mesh_devs} == {z.platform},
           f"mesh holds four distinct {z.platform} devices")
        shards = (step._param_shards["s"] + step._param_shards["o"]
                  + step._opt_state_arrays())
        ok(shards and all(
            len({s.device for s in a.addressable_shards}) == 4
            and a.addressable_shards[0].data.size * 4 == a.size
            for a in shards),
           f"{len(shards)} parameter/optimizer buffers, each 1/4 per chip")
        peaks = _peaks(mesh_devs)
        del step, model, opt
        gc.collect()
        return losses, peaks

    # peaks are per-process high-water marks: the big batch runs first
    _, peaks = run({"sharding_degree": 4}, z.batch4, 4)
    ok(z.tiny or max(peaks) <= 1.15 * min(peaks),
       "per-device peaks within 15 % of each other")
    losses, _ = run({"sharding_degree": 4}, z.batch, 4)
    if z.ref_losses:
        delta = np.abs(np.asarray(losses[:3]) - z.ref_losses[:3]).max()
        ok(delta < TOL_LOSS_4CHIP,
           f"first 3 losses at global batch {z.batch} agree with the "
           f"one-chip step: max delta {delta:.2e} < {TOL_LOSS_4CHIP}")
    else:
        ok(z.tiny, "one-chip losses from phase `train` to compare with "
           "(run --phases train,train4)")
    run({"dp_degree": 2, "mp_degree": 2}, z.batch4, 4)


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def phase_serve(z):
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.ops.pallas import routing
    from paddle_tpu.serving import ServingEngine

    paddle.set_device(z.platform)
    t0 = time.perf_counter()
    paddle.seed(0)
    model = GPTForCausalLM(_gpt_config(z, z.max_len, scan_layers=False))
    model.bfloat16()
    model.eval()
    print(f"  model build: {time.perf_counter() - t0:.1f} s")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, z.vocab, (n,)) for n in z.prompts]

    def serve(quant, prompts):
        t0 = time.perf_counter()
        eng = ServingEngine(model, max_slots=z.slots, max_len=z.max_len,
                            page_size=z.page, chunk_size=z.chunk,
                            cache_dtype=jnp.bfloat16, kv_quant=quant)
        eng.warmup()
        t1 = time.perf_counter()
        counts = eng.compile_counts()
        handles = []
        for p in prompts:        # submitted while the engine is stepping
            handles.append(eng.submit(p, z.new))
            eng.step()
        eng.run()
        dt = time.perf_counter() - t1
        tag = quant or "bf16"
        n_tok = len(prompts) * z.new
        print(f"  {tag}: warm-up ({len(eng.chunk_buckets) + 1} programs) "
              f"{t1 - t0:.1f} s; {len(prompts)} requests, {n_tok} tokens "
              f"in {dt:.2f} s = {n_tok / dt:.0f} tok/s "
              "(smoke output, not a benchmark)")
        for n, hd in zip((len(p) for p in prompts), handles):
            toks = hd.output_tokens
            ok(hd.done and len(toks) == z.new
               and all(0 <= t < z.vocab for t in toks),
               f"{tag}: prompt of {n} finished with {z.new} tokens in "
               "vocabulary range")
        leak = eng.leak_check()
        ok(leak["free_pages"] == leak["total_pages"]
           and leak["free_slots"] == leak["total_slots"]
           and not leak["resident_slot_pages"], f"{tag}: leak_check clean")
        ok(eng.compile_counts() == counts,
           f"{tag}: compile_counts unchanged from first to last request "
           f"{counts}")
        meta = {k: np.asarray(v) for k, v in eng._meta().items()}
        common = (eng._param_data(), eng._buffers, meta)
        B = eng.prefill_batch
        texts = {
            "decode": eng.decode_step.compiled_text(
                *common, eng._tokens, eng._seeds),
            "chunk-prefill": eng.prefill_step.compiled_text(
                *common, np.zeros((B, z.chunk), np.int32),
                np.full((B,), z.slots, np.int32), np.zeros((B,), np.int32),
                np.zeros((B,), np.int32), np.zeros((B,), np.uint32), [])}
        for name, want in (("decode", "paged_attention_decode"),
                           ("chunk-prefill", "paged_attention_chunk")):
            calls = routing.mosaic_kernels(texts[name])
            ok(z.tiny or calls[want] > 0,
               f"{tag}: {name} executable holds {calls[want]} {want} "
               "Mosaic calls" + (" (tiny: interpreted)" if z.tiny else ""))
        return eng, [list(hd.output_tokens) for hd in handles]

    eng, tokens = serve(None, prompts)
    again = eng.submit(prompts[0], z.new)
    eng.run()
    ok(list(again.output_tokens) == tokens[0],
       f"the {z.prompts[0]}-token request alone returns the same tokens")
    del eng
    for quant in ("int8", "int4"):
        serve(quant, prompts[1:3])


PHASES = {"kernels": phase_kernels, "train": phase_train,
          "serve": phase_serve, "train4": phase_train4}


# ---------------------------------------------------------------------------
# child and parent
# ---------------------------------------------------------------------------

def child(phase, tiny, ref_losses):
    import jax
    import jaxlib

    from paddle_tpu.utils import flags
    from paddle_tpu.utils.compile_cache_dir import use_compile_cache

    cache = use_compile_cache()
    d = jax.devices()[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices())}
    print(f"[{phase}{' --tiny' if tiny else ''}] jax {jax.__version__} "
          f"jaxlib {jaxlib.__version__} platform={dev['platform']} "
          f"device_kind={dev['kind']!r} count={dev['count']} "
          f"compile cache={cache}", flush=True)
    print("DEVICE " + json.dumps(dev), flush=True)
    if not tiny and d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: jax.devices() is "
                         f"{jax.devices()} (use --tiny to rehearse on CPU)")
    if tiny:
        flags.set_flags({"FLAGS_pallas_force_interpret": True,
                         "FLAGS_pallas_flash_min_seqlen": 128})
    z = SimpleNamespace(**(TINY if tiny else FULL), platform=d.platform,
                        ref_losses=ref_losses)
    t0 = time.perf_counter()
    PHASES[phase](z)
    if FAILED:
        raise SystemExit(f"chip_smoke: phase {phase} FAILED "
                         f"{len(FAILED)} check(s): {FAILED}")
    print(f"[{phase}] passed in {time.perf_counter() - t0:.1f} s",
          flush=True)


def run_phase(phase, tiny, ref_losses, timeout):
    """Run one phase as a child that owns the chip; -> (rc, device,
    result). Its output is passed through; it is killed, with whatever
    it started, at `timeout` or when this process is interrupted."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    env = dict(os.environ)
    if tiny:
        cmd.append("--tiny")
        env["JAX_PLATFORMS"] = "cpu"
        if phase == "train4":
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                "host_platform_device_count=4").strip()
    if ref_losses:
        cmd += ["--ref-losses", json.dumps(ref_losses)]
    proc = subprocess.Popen(cmd, env=env, cwd=HERE, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            start_new_session=True)

    def kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(timeout, kill)
    timer.start()
    device = result = None
    try:
        for line in proc.stdout:
            if line.startswith("DEVICE "):
                device = json.loads(line[7:])
            elif line.startswith("RESULT "):
                result = json.loads(line[7:])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        return proc.wait(), device, result
    finally:
        timer.cancel()
        kill()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU: 2 layers, hidden 64, "
                         "kernels interpreted — never a chip pass")
    ap.add_argument("--train4", action="store_true",
                    help="demand phase train4 (fails on fewer than 4 chips)")
    ap.add_argument("--phases", default="kernels,train,serve",
                    help="comma-separated subset, in order")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)          # child mode
    ap.add_argument("--ref-losses", default="",
                    help="JSON list: one-chip losses for train4 to compare "
                         "with when phase train is not run")
    args = ap.parse_args()
    if args.phase:
        return child(args.phase, args.tiny,
                     json.loads(args.ref_losses or "null"))

    # killed from outside (a time limit): unwind through run_phase's
    # `finally`, which stops the child that holds the chip
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t_start = time.monotonic()
    phases = [p for p in args.phases.split(",") if p]
    if args.train4 and "train4" not in phases:
        phases.append("train4")
    device, losses, done = None, json.loads(args.ref_losses or "null"), []
    while phases:
        phase = phases.pop(0)
        left = DEADLINE_S - (time.monotonic() - t_start)
        rc, dev, result = run_phase(phase, args.tiny, losses, max(left, 1))
        if rc != 0:
            print(f"chip_smoke: phase {phase} FAILED (exit code {rc})",
                  file=sys.stderr)
            return 1
        device = device or dev
        losses = (result or {}).get("losses", losses)
        done.append(phase)
        if (dev["count"] >= 4 and not args.tiny
                and "train4" not in phases + done):
            phases.append("train4")
    out = {"ok": True, "device": device}
    if args.tiny:
        out["tiny"] = True
    print(f"chip_smoke: phases {done} passed in "
          f"{time.monotonic() - t_start:.0f} s")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
