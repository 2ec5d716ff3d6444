"""Runner for `sparse_moe_train_job` traffic: the Keye-VL-2.0 decoder
(dropless experts, learned sparse attention) through the program's tape
`TrainStep`, fed a fresh seeded batch every step through the step's own
prefetcher.

The same run as runners/train_job.py, for another block: set-up builds
ONE step object with its state, loads the seeded weights
(harness/keye_weights.py), drives it through its first three steps (which
compile or load the executable and are what `correct` is decided on) and
hands that same object to the measured window; the window and the `ctx`
keys are train_job.py's, so the readers that do not depend on the block
serve this kind of cell unchanged. After the window the program is freed
and the plain reference (reference/keye_vl2.py) follows the same three
batches. Training's progress is read on batch 0, stepped once more after
the window: the job's fresh batches differ from each other by more than
a window's updates move the loss. Besides train_job.py's counters the
run keeps the program's own routing counters of the window's last step
(`KeyeVL2ForCausalLM.routing_counters()`), which the new readers use.

A run that starts with no compiled code has to end inside the check's
360 s, reference and trace analysis included (benchmark/
ADDING_A_BLOCK.md): hence the constant initializer at the build, the
readers of whole dicts, the reference compiled ahead on a thread, and a
traced slice of 2 steps without the host's events.
"""
from __future__ import annotations

import collections
import functools
import gc
import math
import statistics

import numpy as np

from harness import check, clock, data, device, keye_weights, probe
from reference import keye_vl2 as ref

# steps inside the profiler's slice of a traced run: two, the launch-gap
# reader's least; each is 3 s on the chip and 95,000 device operations to
# decode
TRACE_STEPS = 2
ZERO_GRADIENT = 1e-3     # as runners/train_job.py


# -- the program, through its normal entry points -------------------------

def build_model(cell):
    import paddle_tpu as paddle
    from paddle_tpu.models import KeyeVL2Config, KeyeVL2ForCausalLM
    from paddle_tpu.nn import initializer

    c, job = cell["config"], cell["traffic"]
    if job["seq"] > c["max_position_embeddings"]:
        raise SystemExit("benchmark: the job's sequences are longer than "
                         "the configuration's positions")
    if cell["step"] != "tape":
        raise SystemExit(f"benchmark: unknown step kind {cell['step']!r}")
    paddle.seed(0)
    # every parameter is re-drawn from --seed right after (load_weights),
    # so the model's own host-side draw (41 s at these sizes) is skipped,
    # as it is before a checkpoint is loaded
    initializer.set_global_initializer(initializer.Constant(0.0),
                                       initializer.Constant(0.0))
    try:
        model = KeyeVL2ForCausalLM(KeyeVL2Config(
            use_recompute=bool(cell["recompute"]),
            index_q_chunk=c["sa_config"]["q_chunk_size"],
            **cell.get("tiling", {}), **keye_weights.shapes(c)))
    finally:
        initializer.set_global_initializer(None)
    model.bfloat16()            # bf16 parameters + fp32 masters (AMP O2)
    # every step also keeps which keys and experts it picked (201 + 6 MB
    # of buffers at the cell's shapes): `correct` is decided given them
    model.record_picks(job["batch"], job["seq"])
    return model


def load_weights(model, cell, seed):
    keye_weights.load_into(model, cell["config"], seed)


def build_step(cell, model):
    """A fresh optimizer and step object around `model`."""
    import paddle_tpu.optimizer as popt
    from paddle_tpu.jit import TrainStep

    o = cell["optimizer"]
    opt = popt.AdamW(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters(), moment_dtype=o["moment_dtype"],
        multi_precision=True)
    return opt, TrainStep(model, lambda m, a, b: m.loss(a, b), opt)


def slice_options():
    """The traced slice records the device alone. The host's events are
    what harness/xplane.py names idle gaps by, one scan of them a gap,
    and this program leaves 90,000 gaps of 20 ns a step between its
    operations (the radix select's passes, the expert tiles): naming
    them took 32 s of a traced run for 3.6 ms of idle time in 5.9 s."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    return options


def executables(step) -> int:
    """Compiled programs the step holds plus unexpected retraces."""
    jitted = getattr(step, "_jitted", None)
    n = jitted._cache_size() if jitted is not None else 0
    unexpected = step.retrace_stats()["unexpected"]
    return n + (unexpected if isinstance(unexpected, int)
                else len(unexpected))


# -- reading the program's state ----------------------------------------

@functools.lru_cache(maxsize=None)
def _tree_reader():
    """jitted {leaf: [arrays]} -> {leaf: ([n] sums of squares, [n, K]
    signed sums)}: harness/probe.py's reading of every leaf in ONE
    program (a program a shape otherwise); array i of a leaf is its
    layer first + i, and continues the flat index where the layer
    before it ended."""
    import jax
    import jax.numpy as jnp

    def read(tree, first):
        out = {}
        for leaf, arrays in tree.items():
            # one loop over a leaf's layers, not a copy of the reading a
            # layer: the compiler's time follows the program's length
            out[leaf] = jax.lax.map(
                lambda a: probe._read(
                    a[1], (first + a[0]) * np.uint32(arrays[0].size), 0, 1,
                    probe.K),
                (jnp.arange(len(arrays), dtype=jnp.uint32),
                 jnp.stack(arrays)))
        return out

    return jax.jit(read)


def read_tree(tree, first=0):
    """{leaf: [arrays]} -> ({leaf: sum of squares}, {leaf: the K signed
    sums}) over all of a leaf's arrays, summed in float64; the arrays
    are the leaf's layers first, first + 1, ..."""
    got = {k: (np.asarray(sq, np.float64), np.asarray(sums, np.float64))
           for k, (sq, sums) in _tree_reader()(tree,
                                               np.uint32(first)).items()}
    return ({k: float(v[0].sum()) for k, v in got.items()},
            {k: v[1].sum(axis=0) for k, v in got.items()})


def by_leaf(cell, model, array_of) -> dict:
    """{leaf: [array_of(parameter) of its layers in order]}."""
    out = {}
    for leaf, _, p in keye_weights.program_leaves(model, cell["config"]):
        out.setdefault(leaf, []).append(array_of(p))
    return out


def grad_norms(cell, model, opt):
    """Per-leaf norm, and signed sums (harness/probe.py), of the first
    gradient as the optimizer got it, from AdamW's first moment after
    one step: m1 = (1 - beta1) g. Layer leaves over all layers."""
    m1 = opt.opt_state_pytree()["accumulators"]["moment1"]
    sq, sums = read_tree(by_leaf(cell, model, lambda p: m1[p.name]))
    scale = 1.0 - cell["optimizer"]["beta1"]
    return ({k: math.sqrt(v) / scale for k, v in sq.items()},
            {k: v / scale for k, v in sums.items()})


def delta_norms(cell, model, opt, seed) -> dict:
    """Per-leaf norm of (the fp32 masters now - the seeded parameters,
    rounded through the type the program stores them in)."""
    masters = opt.opt_state_pytree()["master_weights"]
    now = by_leaf(cell, model, lambda p: p._data
                  if masters.get(p.name) is None else masters[p.name])
    stored = {k: v[0].dtype
              for k, v in by_leaf(cell, model, lambda p: p._data).items()}
    return {k: math.sqrt(v) for k, v in keye_weights.sq_deltas(
        cell["config"], seed, now, stored).items()}


def first_steps(cell, model, opt, step, feed, seed):
    """Steps 0..2 through the window's own call and feed -> the numbers
    the reference is compared with."""
    import jax
    from jax.profiler import TraceAnnotation

    out = {"losses": []}
    for k in range(3):
        with TraceAnnotation("bench.prefetch_next"):
            ids, labels = next(feed)
        with TraceAnnotation("bench.step"):
            out["losses"].append(float(step(ids, labels)))
        if k == 0:
            out["peak_step0"] = device.memory_peak_bytes(jax.local_devices())
            out["counters"] = model.routing_counters()
            out["picks"] = model.picks()
            out["grad_norms"], out["grad_sums"] = grad_norms(
                cell, model, opt)
        if k == 1:
            out["delta_norms"] = delta_norms(cell, model, opt, seed)
    return out


# -- the reference's side ---------------------------------------------------

def reference_numbers(cell, seed, precision="float32", given=None,
                      export_picks=False):
    """The same three batches through the plain reference (or, with a
    lower `precision`, the control). `given` = (selections, experts) per
    layer: the first step runs on those picks in place of its own top-k,
    and `miss` says how far they are from its own. `export_picks`: the
    first step's own picks come back, for a run that is handed them."""
    c, job, o = cell["config"], cell["traffic"], cell["optimizer"]
    outer, layers = keye_weights.reference_params(c, seed)
    stream = data.TokenStream(job, c["vocab_size"], seed)
    sums = {}

    def note(tree, layer):
        prefix, first = ("", 0) if layer is None else ("layers.", layer)
        got = read_tree({prefix + k: [a] for k, a in tree.items()}, first)[1]
        for leaf, s in got.items():
            sums[leaf] = sums.get(leaf, 0.0) + s

    trainer = ref.RefTrainer(
        outer, layers, keye_weights.shapes(c),
        (o["lr"], o["beta1"], o["beta2"], o["epsilon"], o["weight_decay"]),
        precision=precision, probe=note, given=given)
    del outer, layers
    trainer.run([stream.batch_at(k) for k in range(3)])
    picks = None
    if export_picks:
        picks = tuple([np.asarray(a) for a in side]
                      for side in trainer.picks)
    trainer.picks = None
    return {"losses": trainer.losses, "parts": trainer.parts,
            "grad_norms": trainer.grad_norms, "grad_sums": sums,
            "counters": trainer.counts, "miss": trainer.miss,
            "picks": picks,
            "delta_norms": trainer.delta_norms(
                *keye_weights.reference_params(c, seed))}


def compile_reference_ahead(cell):
    """A started thread that compiles the reference's large programs
    for the cell's shapes (reference/keye_vl2.py `compile_ahead`),
    touching no device: started before the step's first call, it works
    while the main thread waits 70 s for the step's own program, and the
    reference after the window finds 80 s of compiling done. It prints
    what stops it; the run then compiles those programs when it reaches
    them."""
    import threading
    import traceback

    c, job = cell["config"], cell["traffic"]
    specs = keye_weights.leaf_specs(c)
    outer = {k: specs[k][0] for k in keye_weights.OUTER}
    layer = {k: specs["layers." + k][0][1:] for k in ref.LAYER_LEAVES}

    def work():
        try:
            ref.compile_ahead(outer, layer, keye_weights.shapes(c),
                              job["batch"], job["seq"])
        except Exception:
            traceback.print_exc()

    thread = threading.Thread(target=work, name="reference-compile",
                              daemon=True)
    thread.start()
    return thread


def compare(cell, got, want, verdict=None, tag=""):
    """The numbers `correct` is decided on, each beside its limit. In two
    parts, because a top-k is discontinuous: the share of `got`'s picks
    (keys, experts) that are not the reference's own, and every other
    number against the reference GIVEN those picks (`want` was computed
    with `given=got["picks"]`)."""
    v = verdict or check.Verdict()
    lim = cell["limits"]
    gaps = [abs(a - b) / abs(b)
            for a, b in zip(got["losses"], want["losses"])]
    # the first loss is the forward pass at the seeded weights alone;
    # the next two follow updates, which a program that stores bf16
    # parameters applies later than a float32 reference sees them
    loss0_gap, loss_gap = gaps[0], max(gaps)
    g_gap, g_leaf = check.worst_leaf_gap(got["grad_norms"],
                                         want["grad_norms"])
    floor = ZERO_GRADIENT * statistics.median(want["grad_norms"].values())
    noise = sorted(k for k, g in want["grad_norms"].items() if g < floor)
    d_gap, d_leaf = check.worst_leaf_gap(got["delta_norms"],
                                         want["delta_norms"], skip=noise)
    p_gap, p_leaf = probe.direction_gap(got["grad_sums"], want["grad_sums"],
                                        want["grad_norms"])
    routed = (abs(got["counters"]["routed_pairs"]
                  - want["counters"]["routed_pairs"])
              / want["counters"]["routed_pairs"])
    for name in ("key_pick_miss", "expert_pick_miss"):
        v.at_most(tag + name, want["miss"][name], lim[name],
                  "share of the picks that are not the reference's own, "
                  "worst layer")
    v.at_most(tag + "loss0_gap", loss0_gap, lim["loss0_gap"],
              "the first step's loss, before any update")
    v.at_most(tag + "loss_gap", loss_gap, lim["loss_gap"],
              f"losses {got['losses']} vs reference {want['losses']} "
              f"(lm, balance, L_I: {want.get('parts')})")
    v.at_most(tag + "grad_norm_gap", g_gap, lim["grad_norm_gap"],
              f"worst leaf {g_leaf}")
    v.at_most(tag + "grad_direction_gap", p_gap, lim["grad_direction_gap"],
              f"worst leaf {p_leaf}")
    v.at_most(tag + "delta_norm_gap", d_gap, lim["delta_norm_gap"],
              f"worst leaf {d_leaf}; left out, the reference's gradient "
              f"being zero: {noise}")
    v.at_most(tag + "routed_pairs_gap", routed, lim["routed_pairs_gap"],
              f"pairs on held experts {got['counters']['routed_pairs']} vs "
              f"{want['counters']['routed_pairs']}")
    v.require(tag + "kept keys are the exact top-k's count",
              got["counters"]["kept_keys"] == want["counters"]["kept_keys"],
              f"{got['counters']['kept_keys']} vs "
              f"{want['counters']['kept_keys']}")
    return v, {"key_pick_miss": want["miss"]["key_pick_miss"],
               "expert_pick_miss": want["miss"]["expert_pick_miss"],
               "loss0_gap": loss0_gap, "loss_gap": loss_gap,
               "grad_norm_gap": g_gap,
               "grad_direction_gap": p_gap, "delta_norm_gap": d_gap,
               "routed_pairs_gap": routed}


# -- one run ------------------------------------------------------------------

def run(cell, args, t_start, ctx):
    import jax
    from jax.profiler import TraceAnnotation

    c, job = cell["config"], cell["traffic"]
    marks = {"imports": clock.now()}
    model = build_model(cell)
    marks["build"] = clock.now()
    load_weights(model, cell, args.seed)
    marks["draw"] = clock.now()
    opt, step = build_step(cell, model)
    stream = data.TokenStream(job, c["vocab_size"], args.seed)
    feed = step.prefetch(stream)
    it = iter(feed)
    marks["weights"] = clock.now()
    ahead = compile_reference_ahead(cell)
    got = first_steps(cell, model, opt, step, it, args.seed)
    ahead.join()        # nothing compiles inside the window, on any thread
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
    routing = model.routing_counters()
    if args.trace:
        # a steady slice right after the window, so that the window of a
        # traced run is the window of any other run
        jax.profiler.start_trace(ctx["trace_dir"],
                                 profiler_options=slice_options())
        drive(lambda: len(ends) + len(queued) >= steps + TRACE_STEPS)
        jax.profiler.stop_trace()
        del losses[steps:], ends[steps:]
    # fresh batches differ by more than a few steps move the loss, so
    # progress is read where it can be seen: batch 0 once more, through
    # the same call, after everything that is timed or traced
    again = step.prefetch([stream.batch_at(0)])
    loss_again = float(step(*next(iter(again))))
    again.close()
    compiled_end = executables(step)
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
                  "window_compiles": compiled_after - compiled_before,
                  "routing": routing})
    durations = np.diff([t0] + ends)
    print(f"train: {len(ends)} steps in {window:.3f} s window, losses read "
          f"{lag} late, host {1e3 * host_s / steps:.2f} ms a step queueing "
          f"(step s: min {durations.min():.4f}, median "
          f"{np.median(durations):.4f}, max {durations.max():.4f}); losses "
          f"{losses[0]:.4f} .. {losses[-1]:.4f}; routing of step 0 "
          f"{got['counters']}, of the window's last step {routing}; "
          f"set-up split (s): start-up and imports "
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
    want = reference_numbers(cell, args.seed, given=got["picks"])
    v, _ = compare(cell, got, want)
    print(f"reference: three losses and two updates in "
          f"{clock.now() - t_ref:.1f} s", flush=True)
    finite = [x for x in losses if np.isfinite(x)]
    v.require("window losses finite", len(finite) == len(losses))
    v.require("the first batch's loss fell over the run",
              loss_again < got["losses"][0],
              f"{got['losses'][0]:.4f} at the seeded weights -> "
              f"{loss_again:.4f} after {len(losses) + 3} updates (the "
              f"window's own, on a fresh batch each: {losses[0]:.4f} .. "
              f"{losses[-1]:.4f})")
    v.require("no compilation inside the window",
              compiled_after == compiled_before == compiled_end,
              f"{compiled_before} -> {compiled_after} -> {compiled_end}")
    v.require("state on the accelerator",
              state_platforms == {ctx["device"]["platform"]},
              str(state_platforms))
    return {"correct": v.correct, "attempted": len(losses),
            "failed": len(losses) - len(finite), "devices": devices}
