#!/usr/bin/env python3
"""Where a benchmark cell's set-up goes: one run of the cell's own runner
up to the window's first instant, with the phases timed.

    python3 tools/setup_phases.py --workload <cell> --seed <n> [--root <checkout>]

Runs `benchmark/run.py`'s start and the cell's runner unchanged (nothing
under benchmark/ is edited: the runner's own functions are wrapped from
outside) and stops where the measured window would begin, so a run costs
its set-up alone. Prints one JSON line:

* `setup_s` and the runner's marks (`imports`, `build`, `draw`,
  `weights`, `first_steps`: seconds each phase took);
* inside `first_steps`: each step call (the first one traces, lowers and
  compiles or loads the step), each wait for its loss, the readers
  between them, the splash and `moe_add_rows` self-checks, and
  `ahead.join()` where the runner compiles its reference on a thread;
* JAX's own compile events by thread (`tracing`, `jaxpr_to_mlir`,
  `backend_compile`, which is the cache load in a warm run), the largest
  programs by name;
* Pallas kernels by name: calls, seconds tracing the body (at bind) and
  seconds lowering it to Mosaic text (inside `jaxpr_to_mlir`);
* `step_text`, for a tape `TrainStep`: the Mosaic calls of the compiled
  step by kernel name, `routing.xla_fallbacks`, its widest float32
  buffers (read after the clock has stopped).

`--root` names another checkout of the repo (the parent, unpacked beside
this one) to run in this file's place. A time comes only from a chip run.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import threading


class _Done(Exception):
    pass


def step_text(step, batch):
    """Which kernels the tape step's compiled program holds (after the
    clock has stopped: the text is the compile cache's copy): Mosaic
    calls by kernel name, geometries that asked for a kernel and took the
    XLA path, and the widest float32 buffers of rank >= 3."""
    import re

    import jax.numpy as jnp
    from paddle_tpu.jit import train_step
    from paddle_tpu.ops.pallas import routing

    if type(step) is not train_step.TrainStep or not batch:
        return None
    text = step._jitted.lower(
        step._extract_state(), jnp.asarray(step._opt.get_lr(), jnp.float32),
        train_step._tree_data(list(batch))).compile().as_text()
    wide = set(re.findall(r"f32\[\d+(?:,\d+){2,}\]", text))
    return {"mosaic_kernels": dict(routing.mosaic_kernels(text)),
            "xla_fallbacks": {f"{k} {g}": n for (k, g), n
                              in routing.xla_fallbacks.items()},
            "widest_f32": sorted(wide, key=lambda shape: (math.prod(
                map(int, re.findall(r"\d+", shape[3:]))), shape))[-6:]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--rehearse", metavar="MANIFEST", help="a tiny manifest "
                    "under benchmark/tests/tiny, run on the CPU with the "
                    "kernels interpreted: the tool's own rehearsal")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    os.chdir(root)
    sys.path[:0] = [os.path.join(root, "benchmark"), root]

    import run as bench                      # benchmark/run.py
    from harness import clock, load

    now = clock.now
    t_start = clock.process_start()
    main_thread = threading.get_ident()
    inside = collections.OrderedDict()       # name -> seconds, in order met
    events = collections.defaultdict(float)  # (thread, event) -> seconds
    programs = collections.defaultdict(float)   # (thread, event, name)
    kernels = collections.defaultdict(lambda: [0, 0.0, 0.0])

    def add(name, dt):
        inside[name] = inside.get(name, 0.0) + dt

    def timed(fn, name):
        def wrapper(*a, **kw):
            t = now()
            try:
                return fn(*a, **kw)
            finally:
                add(name, now() - t)
        return wrapper

    if args.rehearse:
        tiny = os.path.join(root, "benchmark", "tests", "tiny")
        load.SEARCH.insert(0, tiny)
        load.MANIFEST[0] = os.path.join(tiny, args.rehearse)
        from paddle_tpu.utils import flags

        flags.set_flags({"FLAGS_pallas_force_interpret": True})
    cell, dev, cache = bench.open_cell(args.workload, bool(args.rehearse))
    marks = [("imports", now())]

    from jax import monitoring

    def on_duration(event, duration, **kw):
        who = ("main" if threading.get_ident() == main_thread
               else threading.current_thread().name)
        short = event.rsplit("/", 1)[-1].replace("_duration", "")
        events[(who, short)] += duration
        if "fun_name" in kw:
            programs[(who, short, str(kw["fun_name"]))] += duration

    monitoring.register_event_duration_secs_listener(on_duration)

    # Pallas kernels: the body's trace (at bind) and its lowering
    from jax._src.pallas import pallas_call as _pc
    from paddle_tpu.ops.pallas import routing

    real_call = routing.pallas_call

    def pallas_call(kernel, *a, **kw):
        run = real_call(kernel, *a, **kw)
        name = kw.get("name") or getattr(kernel, "__name__", "kernel")

        def traced(*operands):
            t = now()
            try:
                return run(*operands)
            finally:
                k = kernels[name]
                k[0] += 1
                k[1] += now() - t
        return traced

    routing.pallas_call = pallas_call
    backend = getattr(_pc, "mosaic_tpu_backend", None)
    if backend is not None:
        real_rule = backend.pallas_call_tpu_lowering_rule

        def rule(ctx, *nodes, **params):
            t = now()
            try:
                return real_rule(ctx, *nodes, **params)
            finally:
                kernels[params.get("name") or "kernel"][2] += now() - t

        backend.pallas_call_tpu_lowering_rule = rule

    from paddle_tpu.ops.pallas import splash_attention as splash

    if hasattr(splash, "_alias_selfcheck"):
        splash._alias_selfcheck = timed(splash._alias_selfcheck,
                                        "splash self-check")
    try:        # --root may name a checkout from before the kernel
        from paddle_tpu.ops.pallas import moe_rows
    except ImportError:
        moe_rows = None
    if moe_rows is not None:
        moe_rows._alias_selfcheck = timed(moe_rows._alias_selfcheck,
                                          "moe_add_rows self-check")

    runner = load.module("runners", cell["traffic"]["kind"])

    from harness import weights

    def marking(module, name, mark):
        fn = getattr(module, name, None)
        if fn is None:
            return

        def wrapper(*a, **kw):
            out = fn(*a, **kw)
            marks.append((mark, now()))
            return out
        setattr(module, name, wrapper)

    marking(runner, "build_model", "build")
    marking(runner, "load_weights", "draw")     # the Keye runner's
    marking(weights, "load_into", "draw")       # train_job's

    class Loss:
        """The step's result, its wait timed where the runner reads it."""

        def __init__(self, value, k):
            self.value, self.k = value, k

        def __float__(self):
            t = now()
            try:
                return float(self.value)
            finally:
                add(f"step {self.k} wait for the loss", now() - t)

    held = {}        # the step object and its last batch, for step_text()
    real_first = getattr(runner, "first_steps", None)
    if real_first is not None:
        def first_steps(cell, model, opt, step, *rest, **kw):
            marks.append(("weights", now()))
            calls = [0]

            def call(*a, **k):
                n = calls[0]
                calls[0] += 1
                held["batch"] = a
                t = now()
                out = step(*a, **k)
                add(f"step {n} call", now() - t)
                return Loss(out, n)

            t = now()
            out = real_first(cell, model, opt, call, *rest, **kw)
            add("first_steps() in all", now() - t)
            return out
        runner.first_steps = first_steps

    real_ahead = getattr(runner, "compile_reference_ahead", None)
    if real_ahead is not None:
        def ahead(*a, **kw):
            thread = real_ahead(*a, **kw)
            thread.join = timed(thread.join, "ahead.join()")
            return thread
        runner.compile_reference_ahead = ahead

    def stop(step=None, *a, **kw):
        held["step"] = step
        raise _Done
    # the first thing every runner does after its set-up
    runner.executables = stop

    ctx = {"cell": cell, "device": dev, "args": args,
           "trace_dir": os.path.join(root, "benchmark", ".trace", "phases")}
    args.seconds, args.trace = 0.0, 0
    try:
        runner.run(cell, args, t_start, ctx)
        raise SystemExit("setup_phases: the runner never reached its window")
    except _Done:
        t_end = now()

    marks.append(("first_steps", t_end))
    phases, before = collections.OrderedDict(), t_start
    for name, t in marks:
        phases[name] = round(t - before, 3)
        before = t
    if "first_steps() in all" in inside:
        # the self-check runs inside step 0's call, while it is traced
        inside["readers between the steps"] = inside[
            "first_steps() in all"] - sum(
                v for k, v in inside.items() if k.startswith("step "))
    top = sorted(programs.items(), key=lambda kv: -kv[1])[:12]
    line = {
        "workload": args.workload, "seed": args.seed, "root": root,
        "device": dev, "cache": cache, "setup_s": round(t_end - t_start, 3),
        "phases": phases,
        "inside_first_steps": {k: round(v, 3) for k, v in inside.items()},
        "jax_events": {f"{who}:{ev}": round(v, 3)
                       for (who, ev), v in sorted(events.items())},
        "largest_programs": [[who, ev, name, round(v, 3)]
                             for (who, ev, name), v in top],
        "pallas_kernels": {name: {"calls": n, "trace_s": round(tr, 3),
                                  "lower_s": round(lo, 3)}
                           for name, (n, tr, lo) in sorted(kernels.items())},
    }
    line["step_text"] = step_text(held.get("step"), held.get("batch"))
    print("setup_phases: " + json.dumps(line), flush=True)
    # the prefetcher's and the reference's threads are daemons of a run
    # that would go on; there is nothing to flush
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
