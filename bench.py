"""Training/decoding throughput lanes on one chip (rebuilt into cells by
ROADMAP A0; this file is the pre-A0 harness).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device",
...}. A run is live or it fails: there is no recorded number to fall
back on, a phase that raises ends the process non-zero, and without a
TPU whose peak is listed in `_PEAK_BF16_FLOPS` nothing is printed.
`BENCH_MODEL` / `BENCH_BS` / `BENCH_SEQ` / `BENCH_SECONDARY` select the
configuration; the lane switches are at the bottom of the file.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _setup_jax():
    import jax

    from paddle_tpu.utils.compile_cache_dir import use_compile_cache

    use_compile_cache()
    return jax


# Peak dense bf16 FLOP/s per chip, keyed by `jax.devices()[0].device_kind`.
# Source: Google Cloud TPU documentation, system architecture pages
# ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
_PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _device():
    """{"platform", "kind", "count"} of the default backend, as JAX
    reports it."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_flops():
    """Peak of the chip this process runs on; a CPU or an unlisted chip
    is an error — no MFU is ever computed against a guessed peak."""
    dev = _device()
    if dev["platform"] != "tpu":
        raise RuntimeError(f"no TPU: bench.py measures the chip, found "
                           f"{dev}")
    if dev["kind"] not in _PEAK_BF16_FLOPS:
        raise RuntimeError(
            f"device_kind {dev['kind']!r} has no entry in "
            f"_PEAK_BF16_FLOPS — add its published peak with the source")
    return _PEAK_BF16_FLOPS[dev["kind"]]


def _is_big(model_name):
    return any(s in model_name for s in ("1.3b", "2.7b", "6.7b", "13b"))


def run_config(model_name, batch, seq, steps, recompute, remat_policy,
               offload_masters, scan_unroll=None, layer_chunk=None):
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_config,
    )

    peak = _peak_flops()        # fails before any work when off-chip

    # fused-scan step (round 5): scan-over-layers with the AdamW update
    # fused INTO the reverse scan, so one layer's grad is live at a time —
    # this is what makes 1.3b both fit 16G (the plain scan path holds all
    # 24 layers' grads and OOMs, docs/DECISIONS.md §7) with an O(1-block)
    # program. Default ON for 1.3b+; the plain paths remain via
    # BENCH_FUSED_SCAN=0 (+BENCH_SCAN_LAYERS for the generic scan).
    big_model = _is_big(model_name)
    # fused-scan rejects master offload (in-scan update needs the masters
    # resident), so BENCH_OFFLOAD=1 suppresses the big-model default
    fused_scan = os.environ.get(
        "BENCH_FUSED_SCAN",
        "1" if big_model and not offload_masters else "0") == "1"
    scan_layers = (fused_scan
                   or os.environ.get("BENCH_SCAN_LAYERS", "0") == "1")
    cfg = gpt_config(model_name, max_position_embeddings=seq,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                     use_recompute=recompute and not fused_scan,
                     recompute_policy=remat_policy or None,
                     scan_layers=scan_layers)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    moment_dtype = ("bfloat16"
                    if os.environ.get("BENCH_BF16_MOMENTS", "1") == "1"
                    else None)
    crit = GPTPretrainingCriterion()
    if fused_scan:
        # fp32-STORED params + bf16 compute views inside the scan: the
        # param is its own master (2 bytes/param less HBM than the
        # bf16-params+fp32-masters layout — the difference between the
        # 15.3G measured-OOM peak and a fitting 13.4G at 1.3b,
        # tools/diag_fused_mem.py). Same math as AMP O2.
        opt = popt.AdamW(learning_rate=1e-4,
                         parameters=model.parameters(),
                         moment_dtype=moment_dtype)
    else:
        # bf16 params + fp32 master weights — the TPU-native AMP O2 layout
        model.bfloat16()
        opt = popt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                         multi_precision=True,
                         moment_dtype=moment_dtype,
                         offload_master_weights=offload_masters)

    # fused CE (vocab-tiled streaming kernel, ISSUE 7) defaults ON: the
    # [tokens, vocab] logits no longer exist in the head/loss path.
    # BENCH_FUSED_CE=0 restores the dense criterion path; on the
    # fused-scan step the head routing is BENCH_FUSED_HEAD (also ON).
    fused_ce = os.environ.get("BENCH_FUSED_CE", "1") == "1"
    fused_head = os.environ.get(
        "BENCH_FUSED_HEAD", "1" if fused_ce else "0") == "1"
    su = lc = None
    if fused_scan:
        from paddle_tpu.jit import FusedScanTrainStep

        # scan granularity: explicit arg > env > per-layer default
        su = (scan_unroll if scan_unroll is not None
              else int(os.environ.get("BENCH_SCAN_UNROLL", "0")))
        lc = (layer_chunk if layer_chunk is not None
              else int(os.environ.get("BENCH_LAYER_CHUNK", "0")))
        su, lc = su or 1, lc or 1
        step = FusedScanTrainStep(
            model, opt, criterion=crit,
            fused_head=fused_head,
            compute_dtype="bfloat16",
            layer_chunk=lc, scan_unroll=su)
    else:
        if fused_ce:
            # fused LM head (model.loss → fused_linear_cross_entropy):
            # vocab-tiled streaming CE by default (FLAGS_fused_ce), no
            # [tokens, vocab] logits in forward or backward
            def loss_fn(m, ids, labels):
                return m.loss(ids, labels)
        else:
            def loss_fn(m, ids, labels):
                return crit(m(ids), labels)
        step = TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)), dtype="int64")
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)), dtype="int64")

    # warmup/compile, reported apart from the measured steps
    tw = time.perf_counter()
    loss = step(ids, labels)
    _ = float(loss)
    cold_start_ms = round((time.perf_counter() - tw) * 1e3, 1)
    print(f"[bench] {model_name} fused_scan={fused_scan} warmup "
          f"{cold_start_ms / 1e3:.1f}s", file=sys.stderr)

    # measured loop feeds through the device prefetcher (ISSUE 5): each
    # step's batch is a REAL host->device transfer, staged on a background
    # thread while the previous step computes; input_stall_ms / h2d_ms
    # land in the record. The warmup above compiled against to_tensor
    # placement, so zero-retrace staging is exercised, not assumed.
    def host_batches():
        for _ in range(steps):
            yield (rng.integers(0, cfg.vocab_size, (batch, seq),
                                dtype=np.int64),
                   rng.integers(0, cfg.vocab_size, (batch, seq),
                                dtype=np.int64))

    # per-step timeline artifact (ISSUE 12): one JSONL record per
    # measured step under the ignored .bench_live/ — host_ms is the host-loop
    # dispatch interval (dispatch is async; the aggregate wall time
    # below is the throughput truth, the timeline shows its shape)
    from paddle_tpu.observability import JsonlSink, StepTimeline
    os.makedirs(_LIVE_DIR, exist_ok=True)
    tl_path = os.path.join(_LIVE_DIR, f"timeline_{model_name}.jsonl")
    open(tl_path, "w").close()          # fresh artifact per run
    tl = StepTimeline(sinks=[JsonlSink(tl_path)], lane="train")

    pf = step.prefetch(host_batches(), depth=2)
    t0 = time.perf_counter()
    t_prev = t0
    for i, (ids_b, labels_b) in enumerate(pf):
        loss = step(ids_b, labels_b)
        now = time.perf_counter()
        tl.record(step=i, host_ms=round((now - t_prev) * 1e3, 3))
        t_prev = now
    jax.block_until_ready(loss._data)
    dt = time.perf_counter() - t0
    tl.record(step=steps, wall_s=round(dt, 3),
              tok_s=round(batch * seq * steps / dt, 1))
    tl.close()
    pf_stats = pf.get_stats()

    tokens_per_sec = batch * seq * steps / dt

    # HLO-derived accounting (ISSUE 12): ask the COMPILER what the step
    # actually executes — cost-analysis flops (vs the analytic 6N
    # model) and the per-mesh-axis collective byte census. AOT
    # lower+compile of the already-compiled program: the persistent
    # compile cache makes this cheap.
    hlo_costs = None
    if os.environ.get("BENCH_COST_ANALYSIS", "1") == "1":
        t_ca = time.perf_counter()
        hlo_costs = step.cost_analysis(ids, labels)
        hlo_costs["lower_compile_s"] = round(
            time.perf_counter() - t_ca, 1)

    # device-memory receipt (ISSUE 14): compiled-step buffer-assignment
    # peak (AOT — same persistent-cache economics as cost_analysis) +
    # the live-buffer attribution of what is resident between steps.
    mem = None
    if os.environ.get("BENCH_MEM", "1") == "1":
        from paddle_tpu.observability.memory import live_buffer_report

        prof = step.memory_profile(ids, labels)
        mem = {"compiled": prof.summary(top_k=4),
               "live": live_buffer_report()}

    # MFU: model flops per token = 6N (fwd+bwd matmuls) + attention
    # 12*L*h*s (QK^T + PV, fwd+bwd, causal ~halves but count full per
    # PaLM-appendix convention); peak from _PEAK_BF16_FLOPS.
    # training-kernel routing actually in effect for this run (ISSUE 7
    # acceptance keys): fused_ce = the head/loss path streams vocab
    # tiles (no [tokens, vocab] logits); splash_attn = the splash
    # Pallas kernel serves the training attention on this chip/config
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import splash_attention as _splash
    from paddle_tpu.utils import flags as _flags

    ce_active = bool(_flags.get_flag("FLAGS_fused_ce")) and (
        fused_head if fused_scan else fused_ce)
    # mirror the FULL scaled_dot_product_attention routing gates (incl.
    # the min-seqlen threshold and no-dropout requirement), not just the
    # kernel capability — the record must only say true when the splash
    # kernel actually serves this run's attention
    splash_active = (
        _splash.kernel_active(
            (batch, seq, cfg.num_attention_heads,
             cfg.hidden_size // cfg.num_attention_heads),
            cfg.num_attention_heads, jnp.bfloat16)
        and seq >= int(_flags.get_flag("FLAGS_pallas_flash_min_seqlen"))
        and not cfg.attention_dropout_prob)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_token = (6 * n_params
                       + 12 * cfg.num_layers * cfg.hidden_size * seq)
    mfu = tokens_per_sec * flops_per_token / peak
    # cost-analysis MFU (ISSUE 12): same tok/s, flops-per-token taken
    # from compiled.cost_analysis() instead of the analytic 6N model
    mfu_ca = None
    if hlo_costs and hlo_costs.get("flops_per_step"):
        mfu_ca = round(tokens_per_sec * hlo_costs["flops_per_step"]
                       / (batch * seq) / peak, 4)
    # training-numerics receipt (ISSUE 15): the monitor's deferred
    # readback happens HERE, after the measured loop — finite_frac
    # gates absolutely in bench_compare (must stay 1.0), the grad norm
    # is informational drift only
    numerics = None
    mon = getattr(step, "_numerics", None)
    if mon is not None:
        ns = mon.summary()
        numerics = {
            "finite_frac": ns.get("finite_frac"),
            "global_grad_norm": ns.get("grad_norm"),
            "update_ratio_max": ns.get("update_ratio_max"),
            "first_bad_chunk": ns.get("first_bad_chunk"),
        }

    coll = (hlo_costs or {}).get("collectives") or {}
    return {
        "metric": f"{model_name}_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "device": _device(),
        "mfu": round(mfu, 4),
        "mfu_cost_analysis": mfu_ca,
        # trace+compile(or deserialize)-to-first-step wall (ISSUE 17):
        # the cold-start metric bench_compare gates round-over-round
        "cold_start_ms": cold_start_ms,
        "cost_analysis": (None if hlo_costs is None else {
            "flops_per_step": hlo_costs.get("flops_per_step"),
            "bytes_accessed_per_step": hlo_costs.get(
                "bytes_accessed_per_step"),
            "comm_bytes_per_step": coll.get("total_comm_bytes", 0),
            "comm_bytes_per_axis": coll.get("per_axis_bytes", {}),
            "lower_compile_s": hlo_costs.get("lower_compile_s"),
        }),
        "mem": mem,
        "numerics": numerics,
        "timeline": {"path": os.path.relpath(
            tl_path, os.path.dirname(os.path.abspath(__file__))),
            "steps": steps},
        "input_pipeline": {
            "input_stall_ms": pf_stats["input_stall_ms"]["mean"],
            "h2d_ms": pf_stats["h2d_ms"]["mean"],
            "depth": pf_stats["depth"],
        },
        "config": {"batch": batch, "seq": seq, "steps": steps,
                   "params": n_params, "recompute": cfg.use_recompute,
                   "remat_policy": remat_policy or None,
                   "offload_masters": (offload_masters
                                       and not fused_scan),
                   "scan_layers": scan_layers,
                   "fused_scan": fused_scan,
                   "scan_unroll": su if fused_scan else None,
                   "layer_chunk": lc if fused_scan else None,
                   "fused_ce": ce_active,
                   "splash_attn": splash_active},
    }


def run_scan_sweep(model_name=None, batch=None, seq=None, steps=None):
    """ISSUE 3: measured scan_unroll/layer_chunk sweep on the fused-scan
    path (the r5 per-layer-barrier note's target). One run_config per
    variant; returns the table + best. Nothing reads it back: a later
    run takes its granularity from BENCH_SCAN_UNROLL/BENCH_LAYER_CHUNK."""
    from paddle_tpu.models.gpt import GPT_CONFIGS

    model_name = model_name or os.environ.get("BENCH_MODEL", "gpt3-350m")
    batch = batch or int(os.environ.get("BENCH_BS", "8"))
    seq = seq or int(os.environ.get("BENCH_SEQ", "1024"))
    steps = steps or int(os.environ.get("BENCH_STEPS", "5"))
    big = _is_big(model_name)
    recompute = os.environ.get("BENCH_RECOMPUTE",
                               "1" if big else "0") == "1"
    n_layers = GPT_CONFIGS[model_name]["num_layers"]
    variants = [(u, 1) for u in (1, 2, 4, 8)]
    variants += [(1, c) for c in (2, 3) if n_layers % c == 0]
    _peak_flops()               # off-chip: fail before the first variant
    rows = []
    for u, c in variants:
        os.environ["BENCH_FUSED_SCAN"] = "1"
        try:
            r = run_config(model_name, batch, seq, steps, recompute, "",
                           False, scan_unroll=u, layer_chunk=c)
            rows.append({"scan_unroll": u, "layer_chunk": c,
                         "tok_s": r["value"], "mfu": r["mfu"]})
        except Exception as e:   # an OOM variant is a result of the sweep
            rows.append({"scan_unroll": u, "layer_chunk": c,
                         "error": f"{type(e).__name__}: {e}"[:200]})
        print(f"[sweep] {model_name} unroll={u} chunk={c} -> "
              f"{rows[-1]}", file=sys.stderr)
    ok = [r for r in rows if "tok_s" in r]
    if not ok:
        raise RuntimeError(f"every sweep variant failed: {rows}")
    best = max(ok, key=lambda r: r["tok_s"])
    return {
        "metric": f"{model_name}_scan_granularity_sweep",
        "unit": "tokens/s",
        "device": _device(),
        "config": {"batch": batch, "seq": seq, "steps": steps,
                   "recompute": recompute},
        "variants": rows,
        "best": {k: best[k] for k in ("scan_unroll", "layer_chunk")},
        "best_tok_s": best["tok_s"],
    }


def run_decode_config(model_name=None, prompt_len=None, new_tokens=None,
                      batches=(1, 8), int8_ab=True):
    """Inference/decode lane (ISSUE 2): prefill TTFT + steady-state
    decode tokens/s/chip through the compiled generation engine, paged
    vs dense A/B, and the int8 weight-only decode A/B that PERF.md
    measured 5x at the kernel level (bs1 4096x16384)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.decode_step import GenerationEngine
    from paddle_tpu.models import GPTForCausalLM, gpt_config

    _peak_flops()               # a chip lane: off-chip it fails, not runs
    model_name = model_name or os.environ.get("BENCH_DECODE_MODEL",
                                              "gpt3-125m")
    prompt_len = prompt_len or int(os.environ.get(
        "BENCH_DECODE_PROMPT", "128"))
    new_tokens = new_tokens or int(os.environ.get(
        "BENCH_DECODE_TOKENS", "64"))
    cfg = gpt_config(model_name,
                     max_position_embeddings=prompt_len + new_tokens)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    models = {"fp32": model}
    if int8_ab:
        from paddle_tpu.nn.quant import quantize_for_decode

        paddle.seed(0)
        models["int8"] = quantize_for_decode(GPTForCausalLM(cfg))
        models["int8"].eval()

    rng = np.random.default_rng(0)
    lanes = {}
    for bs in batches:
        ids = rng.integers(1, cfg.vocab_size, (bs, prompt_len))
        rec = {}
        for kind in ("dense", "paged"):
            for tag, m in models.items():
                if kind == "paged" and tag == "int8":
                    continue   # the cache A/B, not the weight A/B
                eng = GenerationEngine(
                    m, kind=kind, batch=bs,
                    max_len=prompt_len + new_tokens)
                t_cold = time.perf_counter()
                eng.generate(ids, 2)             # compile both steps
                cold_ms = round(
                    (time.perf_counter() - t_cold) * 1e3, 1)
                t0 = time.perf_counter()
                eng.generate(ids, 1)
                ttft = time.perf_counter() - t0  # prefill + 1 sample
                t0 = time.perf_counter()
                eng.generate(ids, new_tokens)
                total = time.perf_counter() - t0
                decode_s = max(total - ttft, 1e-9)
                name = kind if tag == "fp32" else f"{kind}_{tag}"
                rec[f"{name}_decode_tok_s_chip"] = round(
                    bs * (new_tokens - 1) / decode_s, 1)
                if tag == "fp32":
                    rec[f"{name}_prefill_ttft_ms"] = round(
                        ttft * 1e3, 2)
                    # compile(or cache-deserialize)-to-first-tokens
                    # (ISSUE 17): both step programs built here
                    rec[f"{name}_cold_start_ms"] = cold_ms
                    # compiled decode-step HBM peak (ISSUE 14): the
                    # AOT buffer-assignment receipt per cache shape
                    rec[f"{name}_mem"] = eng.memory_profile(
                        top_k=3).summary(top_k=1)
        lanes[f"bs{bs}"] = rec
    # live-buffer attribution (ISSUE 14): params vs KV pools vs
    # untagged, as resident at the end of the lane
    from paddle_tpu.observability.memory import live_buffer_report

    mem_live = live_buffer_report()
    return {
        "metric": f"{model_name}_decode_tokens_per_sec_per_chip",
        "unit": "tokens/s",
        "device": _device(),
        "config": {"model": model_name, "prompt_len": prompt_len,
                   "new_tokens": new_tokens,
                   "params": sum(int(np.prod(p.shape))
                                 for p in model.parameters())},
        "lanes": lanes,
        "mem_live": mem_live,
    }


def run_resnet_config(batch=None, steps=None):
    """BASELINE metric #2 lane: ResNet-50 training images/sec on one
    chip (the DP-scaling baseline's per-chip anchor)."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    _peak_flops()               # a chip lane: off-chip it fails, not runs
    batch = batch or int(os.environ.get("BENCH_RESNET_BS", "32"))
    steps = steps or int(os.environ.get("BENCH_RESNET_STEPS", "5"))
    paddle.seed(0)
    model = resnet50(num_classes=1000)
    crit = paddle.nn.CrossEntropyLoss()
    opt = popt.Momentum(learning_rate=0.1, momentum=0.9,
                        parameters=model.parameters())
    step = TrainStep(model, lambda m, x, y: crit(m(x), y), opt)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((batch, 3, 224, 224)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 1000, (batch,)), dtype="int64")
    tw = time.perf_counter()
    loss = step(x, y)
    _ = float(loss)
    print(f"[bench] resnet50 warmup {time.perf_counter() - tw:.1f}s",
          file=sys.stderr)

    # ISSUE 5: the input-pipeline-bound lane pulls real per-step host
    # batches through the device prefetcher — 19MB of images per step
    # generated + transferred on the producer thread under the previous
    # step's compute; stall/h2d land in the record
    def host_batches():
        for _ in range(steps):
            yield (rng.standard_normal((batch, 3, 224, 224))
                   .astype(np.float32),
                   rng.integers(0, 1000, (batch,), dtype=np.int64))

    pf = step.prefetch(host_batches(), depth=2)
    t0 = time.perf_counter()
    for xb, yb in pf:
        loss = step(xb, yb)
    jax.block_until_ready(loss._data)
    dt = time.perf_counter() - t0
    pf_stats = pf.get_stats()
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(batch * steps / dt, 1),
        "unit": "images/s",
        "vs_baseline": None,
        "device": _device(),
        "input_pipeline": {
            "input_stall_ms": pf_stats["input_stall_ms"]["mean"],
            "h2d_ms": pf_stats["h2d_ms"]["mean"],
            "depth": pf_stats["depth"],
        },
        "config": {"batch": batch, "steps": steps},
    }


def run_selftest():
    """Selftest lane: on-chip int8 weight-only matmul and pinned-host
    master-offload parity, plus the CPU probe children (one subprocess
    each, `JAX_PLATFORMS=cpu` — they never need the chip). Every check
    runs; any failure then fails the run. The Pallas kernels' on-chip
    numerics live in `chip_smoke.py` (phase `kernels`), not here."""
    import paddle_tpu as paddle
    results = {}

    def check(name, fn):
        try:
            fn()
            results[name] = "pass"
        except Exception as e:
            results[name] = f"FAIL: {type(e).__name__}: {e}"[:200]

    def int8_matmul():
        from paddle_tpu.nn.quant import (
            weight_only_linear, weight_quantize,
        )

        rng = np.random.default_rng(1)
        x = paddle.to_tensor(rng.standard_normal((8, 256))
                             .astype(np.float32)).astype("bfloat16")
        w = paddle.to_tensor((rng.standard_normal((256, 128)) * 0.1)
                             .astype(np.float32)).astype("bfloat16")
        qw, scale = weight_quantize(w, algo="weight_only_int8")
        got = np.asarray(weight_only_linear(x, qw, weight_scale=scale,
                                            weight_dtype="int8")._data,
                         np.float32)
        want = np.asarray((x @ w)._data, np.float32)
        denom = max(np.abs(want).max(), 1e-6)
        assert np.abs(got - want).max() / denom < 4e-2

    def offload_parity():
        import paddle_tpu.optimizer as popt
        from paddle_tpu.jit import TrainStep
        import paddle_tpu.nn as nn

        def train(off):
            paddle.seed(7)
            m = nn.Linear(32, 16)
            m.bfloat16()
            opt = popt.AdamW(learning_rate=0.01,
                             parameters=m.parameters(),
                             multi_precision=True,
                             offload_master_weights=off)
            step = TrainStep(m, lambda mm, a, b:
                             ((mm(a) - b) ** 2).mean(), opt)
            x = paddle.to_tensor(np.random.RandomState(0)
                                 .randn(4, 32).astype(np.float32)) \
                .astype("bfloat16")
            y = paddle.to_tensor(np.random.RandomState(1)
                                 .randn(4, 16).astype(np.float32)) \
                .astype("bfloat16")
            losses = [float(step(x, y)) for _ in range(3)]
            return losses, opt

        base, _ = train(False)
        off, opt = train(True)
        assert base == off, (base, off)
        kinds = {m._data.sharding.memory_kind if hasattr(m, "_data")
                 else m.sharding.memory_kind
                 for m in opt._master_weights.values()}
        assert kinds == {"pinned_host"}, kinds

    def bucketed_rs_parity():
        # host-mesh lane: must run under JAX_PLATFORMS=cpu with 8 virtual
        # devices, which the already-initialized (possibly TPU) backend of
        # this process cannot provide — so a CPU subprocess
        rec = _run_cpu_host_mesh_probe(multichip=False)
        lane = rec.get("bucketed_reduce_scatter_parity", {})
        assert lane.get("check") == "pass", lane
        results["bucketed_reduce_scatter_parity_detail"] = lane

    def decode_parity():
        # hermetic CPU lane: paged == dense == full-sequence forward
        # within fp32 tolerance + greedy eager==compiled, asserted in a
        # JAX_PLATFORMS=cpu subprocess so the record is chip-independent
        rec = _run_cpu_probe("paddle_tpu.inference.decode_selftest",
                             n_devices=1)
        assert rec.get("check") == "pass", rec
        results["decode_parity_detail"] = rec

    def sharded_scan_parity():
        # ISSUE 3: sharded fused-scan == single-device fused scan ==
        # eager TrainStep with ClipGradByGlobalNorm, on an 8-device
        # host mesh; 1/N opt-state sharding asserted on live shapes;
        # tolerances land in the record
        rec = _run_cpu_probe("paddle_tpu.jit.sharded_scan_selftest")
        lane = rec.get("sharded_scan_parity", {})
        assert lane.get("check") == "pass", lane
        results["sharded_scan_parity_detail"] = lane

    def hybrid_parallel():
        # ISSUE 8: full hybrid parallelism — dp4×mp2 (Megatron block
        # slicing + vocab-parallel sharded CE) and dp2×pp2 (ring
        # pipeline, micro-batch accumulation) both match the dp-only
        # sharded scan on the 8-device host mesh within the
        # sharded-scan tolerances, one compiled executable per mesh
        # signature, and the planner returns a pruning-clean layout
        rec = _run_cpu_probe("paddle_tpu.jit.hybrid_selftest",
                             timeout=900)
        lane = rec.get("hybrid_parallel", {})
        assert lane.get("check") == "pass", lane
        results["hybrid_parallel_detail"] = lane

    def fault_tolerance():
        # ISSUE 4: crash-safe checkpointing — victim subprocess
        # SIGKILLed mid-save resumes from the last committed step, a
        # flipped byte is caught by the manifest, save-restore-continue
        # is bit-identical, async save blocks only for the snapshot
        rec = _run_cpu_probe(
            "paddle_tpu.distributed.checkpoint.ft_selftest",
            extra_args=("--trials", "6"), n_devices=1)
        assert rec.get("check") == "pass", rec
        results["fault_tolerance_detail"] = rec

    def input_pipeline():
        # ISSUE 5: zero-stall input delivery — throttled sync-vs-prefetch
        # A/B (prefetched steady-state stall <= 10% of sync), training
        # bit-identical sync vs prefetched over a multi-epoch stream,
        # zero added retraces, donation-safe ring under host-buffer
        # reuse, 1/N sharded staging on an 8-device host mesh
        rec = _run_cpu_probe("paddle_tpu.io.input_pipeline_selftest")
        assert rec.get("check") == "pass", rec
        results["input_pipeline_detail"] = rec

    def training_kernels():
        # ISSUE 7: splash training attention + vocab-tiled fused CE —
        # interpret-mode kernels == XLA fallbacks == dense references
        # (fwd + bwd, causal/GQA/segment masks), segment attention ==
        # per-document dense attention, fused-scan step parity vs the
        # unfused path with the kernels engaged, compile_count == 1,
        # and the HLO probe: no [tokens, vocab] / [b, h, s, s] buffer
        # in the compiled train step
        rec = _run_cpu_probe("paddle_tpu.ops.pallas.training_selftest",
                             n_devices=1, timeout=900)
        assert rec.get("check") == "pass", rec
        results["training_kernels_detail"] = rec

    def distributed_linalg():
        # ISSUE 9: paddle.linalg.distributed — SUMMA matmul (incl.
        # non-divisible + block-cyclic), blocked Cholesky, TSQR QR and
        # the subspace-iteration eigensolver vs jnp.linalg on the
        # 8-device host mesh, plus the no-full-matrix HLO receipt per op
        rec = _run_cpu_probe("paddle_tpu.linalg.distributed.selftest")
        lane = rec.get("distributed_linalg", {})
        assert lane.get("check") == "pass", lane
        results["distributed_linalg_detail"] = lane

    def moe():
        # ISSUE 9: expert-parallel MoE — dp4×ep2 scan step == dp8
        # dense-equivalent routing <= 1e-5 over 4 steps, 1 compile per
        # signature, >= 2 ep-axis all-to-alls in the compiled HLO, and
        # exact aux-loss plumbing through the fused scan
        rec = _run_cpu_probe("paddle_tpu.jit.moe_selftest", timeout=900)
        lane = rec.get("moe", {})
        assert lane.get("check") == "pass", lane
        results["moe_detail"] = lane

    def sharded_storage():
        # ISSUE 11: sharded parameter storage — gather-on-use bit-parity
        # vs replicated storage on dp/dp×mp/dp×pp host meshes, live 1/N
        # param shards, the no-full-parameter-buffer HLO receipt with a
        # measured peak-buffer reduction, dp8->dp4 resharding restore,
        # quantized multi-axis scatter+gather legs, dropout under pp,
        # and the step-time A/B (all numbers land in the record)
        rec = _run_cpu_probe("paddle_tpu.jit.sharded_storage_selftest",
                             timeout=900)
        lane = rec.get("sharded_storage", {})
        assert lane.get("check") == "pass", lane
        results["sharded_storage_detail"] = lane

    def observability():
        # ISSUE 12: unified telemetry — measured registry/sentinel
        # overhead <= 1% of step time, the retrace sentinel attributes
        # a deliberately injected dtype flip (naming the leaf) on all
        # three train-step paths with strict mode raising, timeline
        # JSONL schema round-trips, Prometheus exposition parses, and
        # the instrumented steps stay at 1 executable with no host
        # transfer ops (the PR-4 probe pattern)
        rec = _run_cpu_probe("paddle_tpu.observability.selftest",
                             timeout=900)
        lane = rec.get("observability", {})
        assert lane.get("check") == "pass", lane
        results["observability_detail"] = lane

    def numerics():
        # ISSUE 15: in-graph training-numerics observatory — measured
        # monitor overhead <= 1% of step time on the gpt selftest
        # config, NaN injected at layer k attributed to chunk(k) on
        # FusedScan / ShardedFusedScan(dp8) / PipelineScan(dp2xpp2)
        # with a flight-recorder dump, zero added collectives in the
        # compiled sharded step (per-axis census identical monitor
        # on/off — the no-duplicate-norm-all-reduce probe), strict
        # retrace sentinel clean, spike detector fires on a 50x spike
        # and stays silent on clean runs, /numericsz content
        rec = _run_cpu_probe(
            "paddle_tpu.observability.numerics_selftest", timeout=900)
        lane = rec.get("numerics", {})
        assert lane.get("check") == "pass", lane
        results["numerics_detail"] = lane

    def memory_observability():
        # ISSUE 14: device-memory observability — compiled-step
        # buffer-assignment profiles on the train/decode step paths,
        # live-buffer attribution summing to jax.live_arrays() totals,
        # the sharded-vs-replicated param-storage peak delta receipt,
        # the synthetic-OOM flight-recorder dump, /memz, and the
        # measured hot-path overhead bound <= 1% of step time
        rec = _run_cpu_probe("paddle_tpu.observability.memory_selftest",
                             timeout=900)
        lane = rec.get("memory_observability", {})
        assert lane.get("check") == "pass", lane
        results["memory_observability_detail"] = lane

    def serving():
        # ISSUE 6: continuous-batching serving tier — Poisson arrivals
        # on a tiny model: per-request token parity vs generate(),
        # preempt-then-resume bit-parity on an oversubscribed page
        # pool, bounded TTFT under load via chunked prefill, zero
        # leaked pages/slots at drain, decode compile-count stable
        # under mid-flight admission, and the continuous-vs-static
        # batching A/B at 3 concurrency levels
        rec = _run_cpu_probe("paddle_tpu.serving.selftest",
                             n_devices=1, timeout=900)
        assert rec.get("check") == "pass", rec
        results["serving_detail"] = rec

    def spec_decode():
        # ISSUES 16/20: speculative decoding is LOSSLESS (greedy spec
        # == plain decode bit-identically on paged + int8 + int4 KV
        # with a mismatched weak draft; self-draft heads likewise with
        # zero draft params/pools), the strong-draft dispatch
        # arithmetic holds (accept 1.0 => ceil((n-1)/(k+1))
        # dispatches), the retrace sentinel stays strict-clean across
        # variable accept counts, serving parity + zero leaked pages,
        # and the pool-capacity receipts (int8 ~2x bf16; int4 >= 1.8x
        # int8, >= 3.5x bf16 at equal HBM)
        rec = _run_cpu_probe("paddle_tpu.inference.spec_decode_selftest",
                             n_devices=1, timeout=900)
        assert rec.get("check") == "pass", rec
        results["spec_decode_detail"] = rec

    def fleet():
        # ISSUE 18: disaggregated multi-replica serving fleet — token
        # parity across the prefill->decode KV page hand-off and
        # through host-ring evict/re-onload (sampled streams
        # bit-identical to one engine), 2-replica threaded scaling
        # >= 1.7x under emulated device occupancy, disaggregated chat
        # ITL p99 strictly better than unified under a prefill burst,
        # SLO-burn autoscale down/up with cold-start receipts, zero
        # page/slot/span leaks on every replica (live and retired),
        # strict-clean retrace sentinel fleet-wide
        rec = _run_cpu_probe("paddle_tpu.serving.fleet_selftest",
                             n_devices=1, timeout=900)
        assert rec.get("check") == "pass", rec
        results["fleet_detail"] = rec

    def chaos():
        # ISSUE 19: chaos-hardened self-healing fleet — scripted,
        # seeded fault injection end to end: replica kill mid-decode
        # and mid-hand-off with BIT-identical token streams after
        # re-dispatch (exactly-once), lease/ack losing zero pages,
        # corrupt blobs rejected pre-allocation, ring drops under
        # eviction, per-request deadlines, bounded in-place recovery,
        # brown-out shedding, stuck-replica watchdog with lockless
        # harvest, hung joins recorded; plus dp8 -> dp4 IN-PROCESS
        # elastic training resume within TOL["resume"]. MTTR recorded
        # for both tiers.
        rec = _run_cpu_probe("paddle_tpu.observability.chaos_selftest",
                             n_devices=1, timeout=900)
        assert rec.get("check") == "pass", rec
        results["chaos_detail"] = rec
        if rec.get("mttr_ms") is not None:
            results["chaos_mttr_ms"] = rec["mttr_ms"]
        if rec.get("mttr_stuck_ms") is not None:
            results["chaos_mttr_stuck_ms"] = rec["mttr_stuck_ms"]
        el = _run_cpu_probe("paddle_tpu.observability.chaos_selftest",
                            extra_args=("--elastic",), n_devices=8,
                            timeout=900)
        assert el.get("check") == "pass", el
        results["chaos_elastic_detail"] = el
        if el.get("mttr_train_ms") is not None:
            results["chaos_mttr_train_ms"] = el["mttr_train_ms"]

    def cold_start():
        # ISSUE 17: persistent AOT executable cache — hermetic
        # process-pair A/B on one shared cache dir: cold child compiles
        # + serializes, warm child deserializes. Gates warm first step
        # <= 0.5x cold, zero warm misses, bit-identical train losses /
        # params / decode tokens, strict-clean retrace sentinel.
        rec = _run_cpu_probe("paddle_tpu.jit.cold_start_selftest",
                             n_devices=1, timeout=900)
        assert rec.get("check") == "pass", rec
        results["cold_start_detail"] = rec

    check("cold_start", cold_start)
    check("int8_weight_only_matmul", int8_matmul)
    check("master_offload_parity_pinned_host", offload_parity)
    check("bucketed_reduce_scatter_parity", bucketed_rs_parity)
    check("decode_parity", decode_parity)
    check("sharded_scan_parity", sharded_scan_parity)
    check("hybrid_parallel", hybrid_parallel)
    check("fault_tolerance", fault_tolerance)
    check("input_pipeline", input_pipeline)
    check("serving", serving)
    check("fleet", fleet)
    check("spec_decode", spec_decode)
    check("observability", observability)
    check("numerics", numerics)
    check("memory_observability", memory_observability)
    check("training_kernels", training_kernels)
    check("distributed_linalg", distributed_linalg)
    check("moe", moe)
    check("sharded_storage", sharded_storage)
    check("chaos", chaos)
    failed = {k: v for k, v in results.items()
              if isinstance(v, str) and v.startswith("FAIL")}
    if failed:
        raise RuntimeError(f"selftest failed: {json.dumps(failed)}")
    return results


def _run_cpu_probe(module, extra_args=(), n_devices=8, timeout=600):
    """Run `python -m <module>` in a CPU subprocess (`JAX_PLATFORMS=cpu`,
    `n_devices` host devices) and return its JSON record. The child
    never touches the chip this process may hold."""
    import subprocess

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.abspath(__file__))]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    cmd = [sys.executable, "-m", module, *extra_args]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=timeout,
                       cwd=os.path.dirname(os.path.abspath(__file__)))
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    if r.returncode != 0 or line is None:
        raise RuntimeError(
            f"CPU probe {module} failed rc={r.returncode}: "
            f"{r.stderr[-500:]}")
    return json.loads(line)


def _run_cpu_host_mesh_probe(multichip=False, n_devices=8, timeout=600):
    return _run_cpu_probe(
        "paddle_tpu.distributed.comm_bucketer",
        extra_args=("--multichip",) if multichip else (),
        n_devices=n_devices, timeout=timeout)


_LIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".bench_live")      # ignored; timelines only


def _load_bench_compare():
    """tools/bench_compare.py by path (same loader pattern as
    hlo_costs.load_hlo_overlap)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "bench_compare.py")
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    _setup_jax()

    # opt-in debug/scrape server for the whole bench process (ISSUE
    # 13): /metrics /healthz /tracez /flightz on the global registry
    if os.environ.get("BENCH_DEBUG_PORT"):
        from paddle_tpu.observability import DebugServer

        port = DebugServer(
            port=int(os.environ["BENCH_DEBUG_PORT"])).start()
        print(f"[bench] debug server on 127.0.0.1:{port}",
              file=sys.stderr)

    model_name = os.environ.get("BENCH_MODEL", "gpt3-350m")
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    batch = int(os.environ.get("BENCH_BS", "8"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    # 1.3b on one 16G chip is capacity-bound: 13G param+optimizer state
    # (PERF.md), so remat is mandatory there but off for 350m-class
    big = _is_big(model_name)
    recompute = os.environ.get("BENCH_RECOMPUTE", "1" if big else "0") == "1"
    # 1.3b: FULL remat (the dots policy OOMs the 13G-state chip, PERF.md)
    remat_policy = os.environ.get("BENCH_REMAT_POLICY",
                                  "" if big else ("dots" if recompute
                                                  else ""))
    offload = os.environ.get("BENCH_OFFLOAD", "0") == "1"

    # every lane below runs live; one that raises ends the process
    # non-zero with nothing printed
    result = run_config(model_name, batch, seq, steps, recompute,
                        remat_policy, offload)

    if os.environ.get("BENCH_SELFTEST", "1") == "1":
        result["selftest"] = run_selftest()

    # inference/decode lane (ISSUE 2): compact bs1 record;
    # `python bench.py --decode` is the full bs1/bs8 A/B
    if os.environ.get("BENCH_DECODE", "1") == "1":
        result["decode"] = run_decode_config(batches=(1,))

    # ResNet-50 images/sec lane (BASELINE metric #2)
    if os.environ.get("BENCH_RESNET", "1") == "1":
        result["resnet50"] = run_resnet_config()

    secondary_name = os.environ.get("BENCH_SECONDARY",
                                    "gpt3-350m" if big else "")
    if secondary_name:
        # pinned historical config (BENCH_BS/BENCH_SEQ overrides apply
        # to the primary only)
        result["secondary"] = run_config(
            secondary_name, batch=8, seq=1024, steps=steps,
            recompute=False, remat_policy="", offload_masters=False)

    # opt-in regression gate (ISSUE 13): BENCH_COMPARE=1 diffs THIS run
    # against the newest recorded BENCH_r*.json in the checkout, if any,
    # with per-metric tolerances; the verdict table goes to stderr, the
    # verdict JSON into the record
    if os.environ.get("BENCH_COMPARE", "0") == "1":
        bc = _load_bench_compare()
        verdict = bc.compare_latest(
            os.path.dirname(os.path.abspath(__file__)), current=result)
        print(bc.render_table(verdict), file=sys.stderr)
        if len(verdict.get("rows", [])) > 40:
            verdict["rows"] = [r for r in verdict["rows"]
                               if r["verdict"] != "ok"]
        result["bench_compare"] = verdict

    print(json.dumps(result))


if __name__ == "__main__":
    if "--multichip" in sys.argv:
        # MULTICHIP lane: bucketed vs per-param stage-2 gradient sync on a
        # host-device-count mesh (collective counts by HLO inspection +
        # walltime), PLUS the sharded fused-scan parity probe and the
        # tools/hlo_overlap.py collective-overlap verdict (ISSUE 3) —
        # hermetic CPU subprocesses, one JSON line
        rec = _run_cpu_host_mesh_probe(multichip=True)
        rec["sharded_scan"] = _run_cpu_probe(
            "paddle_tpu.jit.sharded_scan_selftest",
            extra_args=("--multichip",))
        print(json.dumps(rec))
    elif "--hybrid" in sys.argv:
        # HYBRID lane (ISSUE 8): dp4×mp2 + dp2×pp2 parity vs the
        # dp-only sharded scan, compile-count probes, planner pick —
        # hermetic CPU subprocess, one JSON line (the probe already
        # prints under the "hybrid_parallel" key)
        print(json.dumps(_run_cpu_probe("paddle_tpu.jit.hybrid_selftest",
                                        timeout=900)))
    elif "--sweep" in sys.argv:
        # SWEEP lane: measured scan_unroll/layer_chunk A/B on the
        # fused-scan path (ISSUE 3)
        _setup_jax()
        print(json.dumps(run_scan_sweep()))
    elif "--decode" in sys.argv:
        # DECODE lane: prefill TTFT + decode tokens/s/chip at bs1/bs8,
        # paged vs dense A/B, int8 weight-only A/B — one JSON line.
        # BENCH_SPEC=1 (default) appends the speculative-decoding A/B
        # (hermetic CPU probe: strong draft by construction, accept
        # rate 1.0, tokens/s/user + int8-KV occupancy receipt)
        _setup_jax()
        rec = run_decode_config(batches=(1, 8))
        if os.environ.get("BENCH_SPEC", "1") == "1":
            rec["spec"] = _run_cpu_probe(
                "paddle_tpu.inference.spec_decode_selftest",
                extra_args=("--bench",), n_devices=1, timeout=900)
        print(json.dumps(rec))
    elif "--resnet" in sys.argv:
        _setup_jax()
        print(json.dumps(run_resnet_config()))
    elif "--input-pipeline" in sys.argv:
        # INPUT-PIPELINE lane (ISSUE 5): hermetic CPU throttled
        # sync-vs-prefetch A/B + bit-identity + retrace/donation proofs
        print(json.dumps(
            {"input_pipeline":
             _run_cpu_probe("paddle_tpu.io.input_pipeline_selftest")}))
    elif "--serve" in sys.argv:
        # SERVING lane (ISSUE 6): continuous-batching vs static
        # generate-and-wait on Poisson traffic at >= 3 concurrency
        # levels — p50/p99 TTFT, aggregate tok/s, preemption counters,
        # retrace-free decode proof. Hermetic CPU subprocess (the lane
        # measures the scheduler, not matmuls); BENCH_SERVE_MODEL /
        # BENCH_SERVE_USERS / BENCH_SERVE_RATE_PER_USER tune the load
        rec = {"serving": _run_cpu_probe("paddle_tpu.serving.selftest",
                                         extra_args=("--bench",),
                                         n_devices=1, timeout=900)}
        # BENCH_SPEC=1 (default): speculative serve A/B — tokens/s/user
        # plain vs spec vs spec+int8-KV at accept rate 1.0 by
        # construction, the >= 1.5x acceptance bar asserted in-probe
        if os.environ.get("BENCH_SPEC", "1") == "1":
            rec["spec"] = _run_cpu_probe(
                "paddle_tpu.inference.spec_decode_selftest",
                extra_args=("--bench",), n_devices=1, timeout=900)
        print(json.dumps(rec))
    elif "--fleet" in sys.argv:
        # FLEET lane (ISSUE 18): multi-replica serving — aggregate
        # fleet tok/s + merged-sample TTFT percentiles at 1/2/4
        # threaded replicas, the emulated-occupancy scaling ratio, the
        # disaggregation chat-ITL A/B, and one autoscale spawn with
        # its cold-start receipt. Hermetic CPU subprocess;
        # BENCH_FLEET_USERS / BENCH_FLEET_REQS_PER_USER tune the load
        print(json.dumps({"fleet": _run_cpu_probe(
            "paddle_tpu.serving.fleet_selftest",
            extra_args=("--bench",), n_devices=1, timeout=900)}))
    elif "--spec" in sys.argv:
        # SPEC-DECODE lane (ISSUES 16/20): correctness probe + serve
        # A/B (tokens/s/user plain vs speculative vs spec+int8-KV vs
        # spec+int4-KV, plus the self-draft A/B at constructed accept
        # 1.0, accept-rate/tokens-per-dispatch gauges, int8/int4 pool
        # receipts) — hermetic CPU subprocess, one JSON line
        print(json.dumps({
            "spec_probe": _run_cpu_probe(
                "paddle_tpu.inference.spec_decode_selftest",
                n_devices=1, timeout=900),
            "spec_bench": _run_cpu_probe(
                "paddle_tpu.inference.spec_decode_selftest",
                extra_args=("--bench",), n_devices=1, timeout=900),
        }))
    elif "--linalg" in sys.argv:
        # DISTRIBUTED-LINALG lane (ISSUE 9): SUMMA / blocked Cholesky /
        # TSQR / subspace-iteration parity vs jnp.linalg on the 8-dev
        # host mesh + the no-full-matrix collective receipts — hermetic
        # CPU subprocess, one JSON line
        print(json.dumps(_run_cpu_probe(
            "paddle_tpu.linalg.distributed.selftest")))
    elif "--moe" in sys.argv:
        # MOE lane (ISSUE 9): dp4×ep2 expert-parallel scan step vs the
        # dp8 dense-equivalent routing reference, compile-count probes,
        # ep all-to-all census, aux-loss plumbing — hermetic CPU
        # subprocess, one JSON line
        print(json.dumps(_run_cpu_probe("paddle_tpu.jit.moe_selftest",
                                        timeout=900)))
    elif "--param-storage" in sys.argv:
        # PARAM-STORAGE lane (ISSUE 11): sharded vs replicated
        # parameter storage — bit-parity on dp/dp×mp/dp×pp host meshes,
        # live 1/N param-shard shapes, peak-live-bytes HLO receipt,
        # dp8->dp4 resharding checkpoint restore, quantized multi-axis
        # scatter+gather rel-err, dropout-under-pp determinism, and the
        # min-of-reps step-time A/B — hermetic CPU subprocess
        print(json.dumps(_run_cpu_probe(
            "paddle_tpu.jit.sharded_storage_selftest", timeout=900)))
    elif "--memory" in sys.argv:
        # MEMORY lane (ISSUE 14): compiled-step HBM profiles on the
        # train/decode paths, live-buffer attribution vs
        # jax.live_arrays() totals, sharded-vs-replicated storage peak
        # delta, synthetic-OOM forensics dump, /memz, overhead bound —
        # hermetic CPU subprocess, one JSON line
        print(json.dumps(_run_cpu_probe(
            "paddle_tpu.observability.memory_selftest", timeout=900)))
    elif "--numerics" in sys.argv:
        # hermetic training-numerics lane (ISSUE 15): monitor overhead
        # bound, NaN provenance on all three scan paths, zero added
        # collectives, strict sentinel, spike detector, /numericsz
        print(json.dumps(_run_cpu_probe(
            "paddle_tpu.observability.numerics_selftest",
            timeout=900)))
    elif "--observability" in sys.argv:
        # OBSERVABILITY lane (ISSUE 12): registry overhead bound,
        # retrace-sentinel attribution of an injected dtype flip on all
        # three train-step paths (strict), timeline JSONL schema
        # round-trip, Prometheus scrape format, zero added
        # retraces/host transfers — hermetic CPU subprocess
        print(json.dumps(_run_cpu_probe(
            "paddle_tpu.observability.selftest", timeout=900)))
    elif "--training-kernels" in sys.argv:
        # TRAINING-KERNELS lane (ISSUE 7): splash attention + fused CE
        # interpret-mode parity (fwd+bwd, segment masks), scan-step
        # integration, HLO no-logits/no-scores probe — hermetic CPU
        print(json.dumps(
            {"training_kernels":
             _run_cpu_probe("paddle_tpu.ops.pallas.training_selftest",
                            n_devices=1, timeout=900)}))
    elif "--cold-start" in sys.argv:
        # COLD-START lane (ISSUE 17): hermetic process-pair A/B on one
        # shared compile-cache dir — cold child compiles+serializes,
        # warm child deserializes; gates warm <= 0.5x cold first step,
        # zero warm misses, bit-identical outputs, strict sentinel
        print(json.dumps({
            "cold_start": _run_cpu_probe(
                "paddle_tpu.jit.cold_start_selftest",
                n_devices=1, timeout=900)}))
    elif "--chaos" in sys.argv:
        # CHAOS lane (ISSUE 19): scripted deterministic fault injection
        # against the self-healing fleet (kill/corrupt/stuck/hung/
        # brown-out, exactly-once re-dispatch parity, MTTR) plus the
        # dp8 -> dp4 in-process elastic training resume — two hermetic
        # CPU subprocesses
        print(json.dumps({
            "chaos": _run_cpu_probe(
                "paddle_tpu.observability.chaos_selftest",
                n_devices=1, timeout=900),
            "chaos_elastic": _run_cpu_probe(
                "paddle_tpu.observability.chaos_selftest",
                extra_args=("--elastic",), n_devices=8, timeout=900)}))
    elif "--selftest" in sys.argv:
        _setup_jax()
        print(json.dumps({"selftest": run_selftest()}))
    else:
        main()
