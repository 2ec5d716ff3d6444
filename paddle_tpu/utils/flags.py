"""Env-flag registry.

Reference parity: paddle/common/flags.h:38-68 (PHI_DEFINE_EXPORTED_*) +
paddle.set_flags/get_flags (pybind global_value_getter_setter.cc). Flags are
overridable via environment variables of the same name.
"""
from __future__ import annotations

import os
import threading

_lock = threading.Lock()
_registry: dict[str, dict] = {}


def _coerce(value, default):
    if isinstance(default, bool):
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    if isinstance(default, int):
        return int(value)
    if isinstance(default, float):
        return float(value)
    return value


def define_flag(name: str, default, help_str: str = ""):
    """PHI_DEFINE_EXPORTED_* parity; env var overrides default at definition."""
    with _lock:
        env = os.environ.get(name)
        value = _coerce(env, default) if env is not None else default
        _registry[name] = {"value": value, "default": default, "help": help_str}
    return value


def get_flags(flags):
    single = isinstance(flags, str)
    names = [flags] if single else list(flags)
    out = {}
    for n in names:
        if n not in _registry:
            raise ValueError(f"unknown flag {n!r}")
        out[n] = _registry[n]["value"]
    return out


def set_flags(flags: dict):
    with _lock:
        for n, v in flags.items():
            if n not in _registry:
                # auto-register unknown flags (reference tolerates phase-in flags)
                _registry[n] = {"value": v, "default": v, "help": ""}
            else:
                _registry[n]["value"] = _coerce(v, _registry[n]["default"])


def get_flag(name: str):
    return _registry[name]["value"] if name in _registry else None


# -- core flag set (subset of paddle/common/flags.cc) ------------------------
define_flag("FLAGS_check_nan_inf", False, "sweep every op output for NaN/Inf")
define_flag("FLAGS_benchmark", False, "sync after each op for benchmarking")
define_flag("FLAGS_low_precision_op_list", 0, "collect AMP op stats")
define_flag("FLAGS_set_to_1d", True, "0-d to 1-d tensor compat")
define_flag("FLAGS_allocator_strategy", "auto_growth", "allocator strategy (XLA-managed on TPU)")
define_flag("FLAGS_init_allocated_mem", False, "")
define_flag("FLAGS_use_stream_safe_cuda_allocator", True, "no-op on TPU (PJRT-managed)")
define_flag("FLAGS_distributed_timeout_sec", 1800, "collective watchdog timeout")
define_flag("FLAGS_log_level", 0, "VLOG level")
define_flag("FLAGS_attention_fp32_scores", False,
            "store attention scores in fp32 instead of the input dtype "
            "(softmax math is fp32 either way); costs ~2x score-matrix "
            "HBM traffic")
define_flag("FLAGS_pallas_alias_selfcheck", True,
            "one-time per-config on-device check that the fused flash "
            "backward's aliased dK/dV HBM accumulation matches the "
            "hazard-free per-q-row path; fails loudly if a Mosaic "
            "pipeline-ordering change silently corrupts gradients")
define_flag("FLAGS_comm_bucket_mb", 25,
            "gradient-communication bucket size in MB: per-parameter "
            "grads coalesce into size-capped flat buckets and sync as ONE "
            "reduce_scatter/all_reduce per bucket (reference "
            "reducer.cc:484 EagerReducer group_size; 0 disables bucketing "
            "and restores the per-parameter collectives). DataParallel's "
            "explicit sync sizes its buckets from its comm_buffer_size "
            "constructor arg instead, honoring only the 0 kill-switch")
define_flag("FLAGS_comm_quant", "",
            "opt-in compressed gradient collectives on the explicit "
            "bucketed paths: 'int8' (EQuARX-style symmetric per-bucket "
            "scales on both the scatter and gather legs, ~4x less ICI "
            "bytes) or 'bf16' (~2x); '' (default) keeps full-precision "
            "payloads. Accumulation is fp32 in every mode")
define_flag("FLAGS_param_storage", "",
            "parameter storage format of the sharded fused-scan train "
            "steps: 'sharded' (default when empty — params live as 1/N "
            "flat bucket shards, gathered on use inside the scans with "
            "double-buffered prefetch, ~param_bytes/param less "
            "steady-state HBM per device) or 'replicated' (the pre-"
            "ISSUE-11 layout: full per-leaf stacks on every device, the "
            "bit-parity reference). Per-step override: "
            "ShardedFusedScanTrainStep(param_storage=...)")
define_flag("FLAGS_numerics_monitor", True,
            "in-graph training-numerics observatory (ISSUE 15): every "
            "compiled train step emits a fixed-shape per-layer-chunk "
            "stats block (grad/param sq-norms, update ratio, "
            "activation RMS, finite flags) consumed lazily by "
            "observability.numerics.NumericsMonitor — zero added "
            "collectives, one deferred host readback per logging "
            "boundary. Off removes the stats from the compiled "
            "programs entirely. Per-step override: numerics=True/False")
define_flag("FLAGS_splash_attn", True,
            "route training attention (causal/plain, no mask, no "
            "dropout) through the splash Pallas kernel "
            "(ops/pallas/splash_attention.py: tiled online-softmax "
            "fwd, stats-recompute bwd, GQA, segment IDs) on TPU when "
            "the geometry qualifies, and packed-sequence segment "
            "attention through it on every backend (XLA fallback off "
            "TPU). Off restores the round-3 flash/XLA routing.")
define_flag("FLAGS_pallas_force_interpret", False,
            "testing: route the splash-attention / fused-CE Pallas "
            "kernels in interpret mode even off-TPU, so CPU tests "
            "(tests/test_training_kernels.py, chip_smoke.py --tiny) "
            "exercise the kernel code paths instead of the XLA "
            "fallbacks")
define_flag("FLAGS_pallas_flash_min_seqlen", 1024,
            "min seq len to route scaled_dot_product_attention to the "
            "pallas flash kernel. Measured on v5e (h16 d64 bf16, fwd+bwd "
            "vs bf16-score XLA attention): the round-3 kernels (fused "
            "single-block path at <=1024; single-pass fused backward "
            "beyond) win from seq 1024 up (1.22x at 1024, 1.64x at 2048, "
            "1.17x at 4096, 2.5x at 8192 — PERF.md round-3 A/B), and from "
            "16384 the O(s^2) score matrix OOMs 16G HBM while the flash "
            "kernel trains. Below 1024 XLA's fused softmax is fine and "
            "the kernel is not plumbed for masks/dropout.")
