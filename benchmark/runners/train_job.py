"""Runner for `train_job` traffic: one compiled train step, fed a fresh
seeded batch every step through the step's own prefetcher.

Set-up builds ONE object — the program's step class with its state —
loads the seeded weights, drives it through its first three steps (which
compile or load the executable and are what `correct` is decided on), and
hands that same object to the measured window. The window ends with the
first step that completes at or after `--seconds` and the steps already
queued behind it; the rate is all its tokens over all its time. The loop
reads each step's loss `loss_lag` steps late (workloads/<cell>.json, 0
when absent), as a loop that logs its loss asynchronously does: the host
queues step k + lag while the device runs step k. The plain reference
follows the same three batches after the window, once the program's
state is freed.
"""
from __future__ import annotations

import collections
import gc
import math
import statistics

import numpy as np

from harness import check, clock, data, device, probe, weights
from reference import gpt as ref

TRACE_STEPS = 4          # steps inside the profiler's slice of a traced run
# a leaf whose reference gradient norm is under this share of the median
# leaf's has no gradient but rounding noise
ZERO_GRADIENT = 1e-3


# -- the program, through its normal entry points -------------------------

def build_model(cell):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    c, job = cell["config"], cell["traffic"]
    kind = cell["step"]
    if job["seq"] > c["max_position_embeddings"]:
        raise SystemExit("benchmark: the job's sequences are longer than "
                         "the configuration's positions")
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_layers"],
        num_attention_heads=c["num_attention_heads"],
        intermediate_size=c["intermediate_size"],
        max_position_embeddings=c["max_position_embeddings"],
        layer_norm_epsilon=c["layer_norm_epsilon"],
        tie_word_embeddings=True, scan_layers=kind != "tape"))
    if kind == "tape":
        model.bfloat16()        # bf16 parameters + fp32 masters (AMP O2)
    return model


def build_step(cell, model):
    """A fresh optimizer and step object around `model`."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu.models import GPTPretrainingCriterion

    o, kind = cell["optimizer"], cell["step"]
    opt = popt.AdamW(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters(), moment_dtype=o["moment_dtype"],
        multi_precision=kind == "tape")
    if kind == "fused_scan":
        from paddle_tpu.jit import FusedScanTrainStep

        step = FusedScanTrainStep(model, opt,
                                  criterion=GPTPretrainingCriterion(),
                                  fused_head=True, compute_dtype="bfloat16")
    elif kind == "tape":
        from paddle_tpu.jit import TrainStep

        step = TrainStep(model, lambda m, a, b: m.loss(a, b), opt)
    else:
        raise SystemExit(f"benchmark: unknown step kind {kind!r}")
    return opt, step


def executables(step) -> int:
    """Compiled programs the step holds plus unexpected retraces."""
    jitted = getattr(step, "_jitted", None)
    n = jitted._cache_size() if jitted is not None else 0
    unexpected = step.retrace_stats()["unexpected"]
    return n + (unexpected if isinstance(unexpected, int)
                else len(unexpected))


# -- reading the program's state ----------------------------------------

def grad_norms(cell, model, opt) -> dict:
    """Per-leaf norm, and signed sums (harness/probe.py), of the first
    gradient as the optimizer got it, from AdamW's first moment after
    one step: m1 = (1 - beta1) g. A leaf is a parameter, or one of the
    q | k | v slices of the qkv parameters (weights.leaf_parts)."""
    m1 = opt.opt_state_pytree()["accumulators"]["moment1"]
    sq, sums = {}, {}
    for leaf, layer, p in weights.program_leaves(model, cell["config"]):
        m, names = m1[p.name], weights.leaf_parts(leaf)
        size = m.size // len(names)
        for j, name in enumerate(names):
            q, s = probe.read(m, 0 if layer is None else layer * size, j,
                              len(names))
            sq[name] = sq.get(name, 0.0) + q
            sums[name] = sums.get(name, 0.0) + s
    scale = 1.0 - cell["optimizer"]["beta1"]
    return ({k: math.sqrt(v) / scale for k, v in sq.items()},
            {k: v / scale for k, v in sums.items()})


def delta_norms(cell, model, opt, seed) -> dict:
    """Per-leaf norm of (parameters now - seeded parameters), the fp32
    master where the step keeps one. The seeded leaf is drawn again
    inside the reading's own program, rounded through the type the
    program stores it in."""
    specs = weights.leaf_specs(cell["config"])
    masters = opt.opt_state_pytree()["master_weights"]
    now, dtype = {}, {}
    for leaf, _, p in weights.program_leaves(model, cell["config"]):
        master = masters.get(p.name)
        now.setdefault(leaf, []).append(p._data if master is None
                                        else master)
        dtype[leaf] = p._data.dtype
    out = {}
    for leaf, arrays in now.items():
        names = weights.leaf_parts(leaf)
        sq = weights.sq_delta_from_seed(specs, leaf, seed, arrays,
                                        dtype[leaf], len(names))
        out.update((n, math.sqrt(v)) for n, v in zip(names, sq))
    return out


def first_steps(cell, model, opt, step, feed, seed):
    """Steps 0..2 through the window's own call and feed -> the numbers
    the reference is compared with."""
    from jax.profiler import TraceAnnotation

    out = {"losses": []}
    for k in range(3):
        with TraceAnnotation("bench.prefetch_next"):
            ids, labels = next(feed)
        with TraceAnnotation("bench.step"):
            out["losses"].append(float(step(ids, labels)))
        if k == 0:
            import jax

            out["peak_step0"] = device.memory_peak_bytes(jax.local_devices())
            out["grad_norms"], out["grad_sums"] = grad_norms(
                cell, model, opt)
        if k == 1:
            out["delta_norms"] = delta_norms(cell, model, opt, seed)
    return out


# -- the reference's side ---------------------------------------------------

def reference_numbers(cell, seed, precision="float32"):
    """The same three batches through the plain reference (or, with a
    lower `precision`, the control)."""
    c, job, o = cell["config"], cell["traffic"], cell["optimizer"]
    specs, outer, layers = weights.reference_params(c, seed)
    stream = data.TokenStream(job, c["vocab_size"], seed)
    sums = {}

    def note(leaf, layer, g):
        s = probe.sums(g, 0 if layer is None else layer * g.size)
        sums[leaf] = sums.get(leaf, 0.0) + s

    trainer = ref.RefTrainer(
        outer, layers, c["num_attention_heads"], c["layer_norm_epsilon"],
        (o["lr"], o["beta1"], o["beta2"], o["epsilon"], o["weight_decay"]),
        precision=precision, probe=note)
    del outer, layers
    trainer.run([stream.batch_at(k) for k in range(3)])
    return {"losses": trainer.losses, "grad_norms": trainer.grad_norms,
            "grad_sums": sums,
            "delta_norms": trainer.delta_norms(
                lambda name: weights.draw_leaf(specs, name, seed))}


def compare(cell, got, want, verdict=None, tag=""):
    """The numbers `correct` is decided on, each beside its limit."""
    v = verdict or check.Verdict()
    lim = cell["limits"]
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], want["losses"]))
    g_gap, g_leaf = check.worst_leaf_gap(got["grad_norms"],
                                         want["grad_norms"])
    # AdamW steps a parameter whose gradient is only rounding noise (the
    # key bias: softmax does not see it) by +-lr in any precision, so
    # its change says nothing of the update and is left out
    floor = ZERO_GRADIENT * statistics.median(want["grad_norms"].values())
    noise = sorted(k for k, g in want["grad_norms"].items() if g < floor)
    d_gap, d_leaf = check.worst_leaf_gap(got["delta_norms"],
                                         want["delta_norms"], skip=noise)
    p_gap, p_leaf = probe.direction_gap(got["grad_sums"], want["grad_sums"],
                                        want["grad_norms"])
    v.at_most(tag + "loss_gap", loss_gap, lim["loss_gap"],
              f"losses {got['losses']} vs reference {want['losses']}")
    v.at_most(tag + "grad_norm_gap", g_gap, lim["grad_norm_gap"],
              f"worst leaf {g_leaf}")
    v.at_most(tag + "grad_direction_gap", p_gap, lim["grad_direction_gap"],
              f"worst leaf {p_leaf}")
    v.at_most(tag + "delta_norm_gap", d_gap, lim["delta_norm_gap"],
              f"worst leaf {d_leaf}; left out, the reference's gradient "
              f"being zero: {noise}")
    return v, {"loss_gap": loss_gap, "grad_norm_gap": g_gap,
               "grad_direction_gap": p_gap, "delta_norm_gap": d_gap}


# -- one run ------------------------------------------------------------------

def run(cell, args, t_start, ctx):
    import jax
    from jax.profiler import TraceAnnotation

    c, job = cell["config"], cell["traffic"]
    marks = {"imports": clock.now()}
    model = build_model(cell)
    marks["build"] = clock.now()
    weights.load_into(model, c, args.seed)
    marks["draw"] = clock.now()
    opt, step = build_step(cell, model)
    stream = data.TokenStream(job, c["vocab_size"], args.seed)
    feed = step.prefetch(stream)
    it = iter(feed)
    marks["weights"] = clock.now()
    got = first_steps(cell, model, opt, step, it, args.seed)
    marks["first_steps"] = clock.now()

    devices = jax.devices()[:1]
    feed.reset_stats()
    compiled_before = executables(step)
    tokens_per_step = job["batch"] * job["seq"]
    lag = cell.get("loss_lag", 0)
    losses, ends, queued, host = [], [], collections.deque(), [0.0]

    def drive(done):
        """Queue a step, then read the loss of the step `lag` before it,
        until `done()`; then read the losses still queued."""
        while not done():
            t_host = clock.now()
            with TraceAnnotation("bench.prefetch_next"):
                ids, labels = next(it)
            with TraceAnnotation("bench.step"):
                queued.append(step(ids, labels))
            host[0] += clock.now() - t_host
            if len(queued) > lag:
                losses.append(float(queued.popleft()))
                ends.append(clock.now())
        while queued:
            losses.append(float(queued.popleft()))
            ends.append(clock.now())

    t0 = clock.now()
    drive(lambda: bool(ends) and ends[-1] - t0 >= args.seconds)
    window = ends[-1] - t0
    steps, host_s = len(ends), host[0]
    stall = feed.get_stats()
    compiled_after = executables(step)
    if args.trace:
        # a steady slice right after the window, so that the window of a
        # traced run is the window of any other run
        jax.profiler.start_trace(ctx["trace_dir"])
        drive(lambda: len(ends) + len(queued) >= steps + TRACE_STEPS)
        jax.profiler.stop_trace()
        del losses[steps:], ends[steps:]
    peak = device.memory_peak_bytes(devices)
    state_platforms = {d.platform for p in model.parameters()
                       for d in p._data.devices()}
    feed.close()

    e2e = {"train_tok_s_chip": tokens_per_step * len(ends) / window
           / cell["chips"],
           "setup_s": t0 - t_start}
    ctx.update(
        e2e=e2e, window_s=window, steps=len(ends),
        tokens_per_step=tokens_per_step, peak_bytes=peak,
        counters={"input_stall_ms_total": stall["input_stall_ms"]["total"],
                  "input_batches": stall["batches"],
                  "host_queue_s": host_s,
                  "window_compiles": compiled_after - compiled_before})
    durations = np.diff([t0] + ends)
    print(f"train: {len(ends)} steps in {window:.3f} s window, losses read "
          f"{lag} late, host {1e3 * host_s / steps:.2f} ms a step queueing "
          f"(step s: min "
          f"{durations.min():.4f}, median {np.median(durations):.4f}, max "
          f"{durations.max():.4f}); losses "
          f"{losses[0]:.4f} .. {losses[-1]:.4f}; set-up split (s): start-up and imports "
          f"{marks['imports'] - t_start:.1f}, model build "
          f"{marks['build'] - marks['imports']:.1f}, seeded weights "
          f"{marks['draw'] - marks['build']:.1f}, optimizer and step object "
          f"{marks['weights'] - marks['draw']:.1f}, first three steps and "
          f"checks' readings {marks['first_steps'] - marks['weights']:.1f}, "
          f"input stall total {stall['input_stall_ms']['total']} ms; peak "
          f"bytes in use after step 0 {got['peak_step0']}, after the window "
          f"{peak}; memory_stats {devices[0].memory_stats()}", flush=True)

    # free the program, then let the reference follow the same batches
    del step, opt, model, feed, it, drive, queued
    gc.collect()
    t_ref = clock.now()
    want = reference_numbers(cell, args.seed)
    v, _ = compare(cell, got, want)
    print(f"reference: three losses and two updates in "
          f"{clock.now() - t_ref:.1f} s", flush=True)
    finite = [x for x in losses if np.isfinite(x)]
    v.require("window losses finite", len(finite) == len(losses))
    v.require("loss fell over the window", losses[-1] < losses[0],
              f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    v.require("no compilation inside the window",
              compiled_after == compiled_before,
              f"{compiled_before} -> {compiled_after}")
    v.require("state on the accelerator",
              state_platforms == {ctx["device"]["platform"]},
              str(state_platforms))
    return {"correct": v.correct, "attempted": len(losses),
            "failed": len(losses) - len(finite), "devices": devices}
