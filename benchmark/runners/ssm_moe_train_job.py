"""Runner for `ssm_moe_train_job` traffic: the Nemotron-H decoder (Mamba-2
layers, an attention layer, sigmoid-routed relu^2 dropless experts beside
a shared expert) through the program's tape `TrainStep`, fed a fresh
seeded batch every step through the step's own prefetcher.

The same run as runners/sparse_moe_train_job.py, for another block: what
of that file does not name its block is imported from it (the optimizer
and step object, the traced slice's options, the count of executables,
the reader of whole dicts of leaves, and the loop, `run`); what names
the block (model, weights, reference, the comparison) is this file's.
The `ctx` keys are train_job.py's, so the readers that do not depend on
the block serve this kind of cell unchanged.
`correct` is decided in two parts, as there: the program keeps the
experts it picked, the reference's first step runs on them, and
`expert_pick_miss` says how far they are from the reference's own.
"""
from __future__ import annotations

import collections  # noqa: F401  (this and the next: the loop's names)
import gc  # noqa: F401
import math
import types

import numpy as np

from harness import clock, data, device  # noqa: F401
from harness import nemotron3_weights as weights
from reference import nemotron_h as ref
from runners import sparse_moe_train_job as keye_job
from runners.sparse_moe_train_job import (  # noqa: F401  (calibrate_block)
    TRACE_STEPS, ZERO_GRADIENT, build_step, executables, read_tree,
    slice_options)
from runners.window_moe_train_job import compare  # noqa: F401


# -- the program, through its normal entry points -------------------------

def build_model(cell):
    import paddle_tpu as paddle
    from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
    from paddle_tpu.nn import initializer

    c, job = cell["config"], cell["traffic"]
    if job["seq"] > c["max_position_embeddings"]:
        raise SystemExit("benchmark: the job's sequences are longer than "
                         "the configuration's positions")
    if cell["step"] != "tape":
        raise SystemExit(f"benchmark: unknown step kind {cell['step']!r}")
    paddle.seed(0)
    # every parameter is re-drawn from --seed right after (load_weights)
    initializer.set_global_initializer(initializer.Constant(0.0),
                                       initializer.Constant(0.0))
    try:
        model = NemotronHForCausalLM(NemotronHConfig(
            use_recompute=bool(cell["recompute"]),
            **cell.get("tiling", {}), **weights.shapes(c)))
    finally:
        initializer.set_global_initializer(None)
    model.bfloat16()            # bf16 parameters + fp32 masters (AMP O2)
    # every step also keeps which experts it picked (3 MB of buffer at
    # the cell's shapes): `correct` is decided given them
    model.record_picks(job["batch"], job["seq"])
    return model


def load_weights(model, cell, seed):
    weights.load_into(model, cell["config"], seed)


# -- reading the program's state ----------------------------------------

def by_leaf(cell, model, array_of) -> dict:
    """{leaf: [array_of(parameter) of its layers in order]}."""
    out = {}
    for leaf, _, p in weights.program_leaves(model, cell["config"]):
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
    return {k: math.sqrt(v) for k, v in weights.sq_deltas(
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
                      export_picks=False, zero_state=False, skip_d=False):
    """The same three batches through the plain reference (or, with a
    lower `precision`, the control). `given` = the experts per mixture
    layer: the first step runs on those picks in place of its own top-k,
    and `miss` says how far they are from its own. `export_picks`: the
    first step's own picks come back, for a run that is handed them.
    `zero_state` and `skip_d`: the reference of a wrong program
    (reference/nemotron_h.py)."""
    c, job, o = cell["config"], cell["traffic"], cell["optimizer"]
    outer, layers = weights.reference_params(c, seed)
    stream = data.TokenStream(job, c["vocab_size"], seed)
    kinds, sums = weights.kinds(c), {}

    def note(tree, layer):
        # a layer's leaves continue the flat index of its kind's leaf
        # where the kind's layer before it ended, as the program's do
        prefix, first = "", 0
        if layer is not None:
            prefix = ref.KIND_NAMES[kinds[layer]] + "."
            first = kinds[:layer].count(kinds[layer])
        got = read_tree({prefix + k: [a] for k, a in tree.items()}, first)[1]
        for leaf, s in got.items():
            sums[leaf] = sums.get(leaf, 0.0) + s

    trainer = ref.RefTrainer(
        outer, layers, weights.shapes(c),
        (o["lr"], o["beta1"], o["beta2"], o["epsilon"], o["weight_decay"]),
        precision=precision, probe=note, given=given, zero_state=zero_state,
        skip_d=skip_d)
    del outer, layers
    trainer.run([stream.batch_at(k) for k in range(3)])
    picks = (np.stack([np.asarray(a) for a in trainer.picks])
             if export_picks else None)
    trainer.picks = None
    return {"losses": trainer.losses, "parts": trainer.parts,
            "grad_norms": trainer.grad_norms, "grad_sums": sums,
            "counters": trainer.counts, "miss": trainer.miss,
            "picks": picks,
            "delta_norms": trainer.delta_norms(
                *weights.reference_params(c, seed))}


def compile_reference_ahead(cell):
    """A started thread that compiles the reference's large programs for
    the cell's shapes (reference/nemotron_h.py `compile_ahead`), touching
    no device, while the main thread waits for the step's own program. It
    prints what stops it; the run then compiles those programs when it
    reaches them."""
    import threading
    import traceback

    c, job, o = cell["config"], cell["traffic"], cell["optimizer"]
    hyper = (o["lr"], o["beta1"], o["beta2"], o["epsilon"], o["weight_decay"])
    specs = weights.leaf_specs(c)
    outer = {k: specs[k][0] for k in weights.OUTER}
    layers = {kind: {k: specs[ref.KIND_NAMES[kind] + "." + k][0][1:]
                     for k in ref.LEAVES[kind]}
              for kind in set(weights.kinds(c))}

    def work():
        import jax
        import jax.numpy as jnp

        try:
            ref.compile_ahead(outer, layers, weights.shapes(c),
                              job["batch"], job["seq"], hyper)
            # the reference's own weights (one float32 drawing program) and
            # its readers of a dict of gradients: a program a kind of
            # layer, and one for the outer leaves
            weights.compile_reference_drawer(c)
            trees = [{k: outer[k] for k in weights.OUTER}] + [
                {ref.KIND_NAMES[kind] + "." + k: v for k, v in leaves.items()}
                for kind, leaves in layers.items()]
            for tree in trees:
                keye_job._tree_reader().lower(
                    {k: [jax.ShapeDtypeStruct(tuple(v), jnp.float32)]
                     for k, v in tree.items()}, np.uint32(0)).compile()
        except Exception:
            traceback.print_exc()

    thread = threading.Thread(target=work, name="reference-compile",
                              daemon=True)
    thread.start()
    return thread


# -- one run ------------------------------------------------------------------

# The loop itself names no block: it calls `build_model`, `load_weights`,
# `build_step`, `first_steps`, `compile_reference_ahead`,
# `reference_numbers` and `compare` by name. The sparse_moe runner's,
# looking those names up in THIS module (joining the runner files' loops
# in place is a `benchmark` issue's: it edits files that exist).
run = types.FunctionType(keye_job.run.__code__, globals(), "run")
