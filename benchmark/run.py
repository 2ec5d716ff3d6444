#!/usr/bin/env python3
"""One cell, once: build, warm, measure for --seconds, print one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by its name in BENCHMARK.json (see README.md).
Exits non-zero, with no result line, when JAX finds no TPU, fewer chips
than the cell asks for, or a `device_kind` without published peaks.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from harness import clock, device, load  # noqa: E402


def place_compile_cache():
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says, else
    <checkout>/.jax_cache (the program's own rule). Every program is
    kept, however quick it was to compile: a run is a new process, and
    the sub-second programs are most of a warm set-up."""
    import jax

    from paddle_tpu.utils.compile_cache_dir import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def open_cell(workload: str, allow_cpu: bool = False):
    """(cell, device, cache directory): what every entry point does first."""
    cell = load.cell(workload)
    cache = place_compile_cache()
    return cell, device.require_chips(cell["chips"], allow_cpu), cache


def layer_metrics(cell, ctx):
    out = {}
    for m in cell["per_layer"]:
        value = load.module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, allow_cpu=False):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = clock.process_start()

    cell, dev, cache = open_cell(args.workload, allow_cpu)
    print(f"benchmark: {args.workload} seed {args.seed} on {dev}; compile "
          f"cache {cache}", flush=True)
    ctx = {"cell": cell, "device": dev, "args": args,
           "trace_dir": os.path.join(HERE, ".trace", args.workload)}
    if args.trace:
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    runner = load.module("runners", cell["traffic"]["kind"])
    result = runner.run(cell, args, t_start, ctx)

    if args.trace:
        from harness import trace_reduce

        ctx["trace"] = trace_reduce.reduce_dir(ctx["trace_dir"],
                                               len(result["devices"]))
        print("end-to-end metrics of this traced run (for the tracing "
              f"overhead): {json.dumps(ctx['e2e'])}", flush=True)
        metrics = layer_metrics(cell, ctx)
    else:
        metrics = {m["name"]: {"value": float(ctx["e2e"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    dev = dict(dev, memory_peak_bytes=int(ctx["peak_bytes"]))
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": dev}
    if args.trace:
        t = ctx["trace"]
        dev.update(busy_s=t["busy_s"], window_s=t["window_s"])
        line["breakdown"] = {"device_ops": t["device_ops"][:10],
                             "idle_gaps": t["idle_gaps"][:10]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
