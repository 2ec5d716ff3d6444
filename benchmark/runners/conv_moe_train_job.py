"""Runner for `conv_moe_train_job` traffic: LFM2-MoE's decoder (gated
short-convolution layers three to one with grouped-query attention, a leading
dense layer, sigmoid-routed SwiGLU dropless experts, a tied head) through the
program's tape `TrainStep`, fed a fresh seeded batch every step through the
step's own prefetcher.

The same run as runners/kda_moe_train_job.py, for another block: what names no
block is taken from the ssm_moe runner (the readers of the program's state,
`first_steps`, the thread that compiles the reference ahead) and the
sparse_moe runner (the loop, `run`), looking this module's names up; what names the block (model, weights, reference) is this file's. The
`ctx` keys are train_job.py's, so the readers that do not depend on the block
serve this kind of cell unchanged. `correct` is decided in two parts, as
there: the program keeps the experts it picked, the reference's first step
runs on them, and `expert_pick_miss` says how far they are from the
reference's own.
"""
from __future__ import annotations

import collections  # noqa: F401  (this and the next: the loop's names)
import gc  # noqa: F401
import math  # noqa: F401

import numpy as np  # noqa: F401

from harness import clock, data, device  # noqa: F401
from harness import lfm2_weights as weights
from reference import lfm2 as ref
from runners import sparse_moe_train_job as keye_job
from runners import ssm_moe_train_job as ssm_job
from runners.sparse_moe_train_job import (  # noqa: F401  (calibrate_block)
    TRACE_STEPS, ZERO_GRADIENT, build_step, executables, read_tree,
    slice_options)
from runners.window_moe_train_job import compare  # noqa: F401


# -- the program, through its normal entry points -------------------------

def build_model(cell):
    import paddle_tpu as paddle
    from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM
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
        model = Lfm2MoeForCausalLM(Lfm2MoeConfig(
            use_recompute=bool(cell["recompute"]),
            **cell.get("tiling", {}), **weights.shapes(c)))
    finally:
        initializer.set_global_initializer(None)
    model.bfloat16()            # bf16 parameters + fp32 masters (AMP O2)
    # every step also keeps which experts it picked (4 MB of buffer at the
    # cell's shapes): `correct` is decided given them
    model.record_picks(job["batch"], job["seq"])
    return model


def load_weights(model, cell, seed):
    weights.load_into(model, cell["config"], seed)


# -- the reference's side ---------------------------------------------------

def reference_numbers(cell, seed, precision="float32", given=None,
                      export_picks=False, **wrong):
    """The same three batches through the plain reference (or, with a lower
    `precision`, the control): ssm_moe_train_job.py `reference_numbers`.
    `wrong`: the reference of wrong programs, by name (reference/lfm2.py
    WRONG: `no_input_gate=True`, `late_tap=True`, `untied_head=True`)."""
    c, job, o = cell["config"], cell["traffic"], cell["optimizer"]
    outer, layers = weights.reference_params(c, seed)
    stream = data.TokenStream(job, c["vocab_size"], seed)
    kinds, sums = weights.kinds(c), {}

    def note(tree, layer):
        # a sub-layer's leaves continue the flat index of its kind's leaf
        # where the kind's sub-layer before it ended, as the program's do
        prefix, first = "", 0
        if layer is not None:
            prefix = kinds[layer] + "."
            first = kinds[:layer].count(kinds[layer])
        got = read_tree({prefix + k: [a] for k, a in tree.items()}, first)[1]
        for leaf, s in got.items():
            sums[leaf] = sums.get(leaf, 0.0) + s

    trainer = ref.RefTrainer(
        outer, layers, weights.shapes(c),
        (o["lr"], o["beta1"], o["beta2"], o["epsilon"], o["weight_decay"]),
        precision=precision, probe=note, given=given,
        wrong=tuple(k for k, v in wrong.items() if v))
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


# -- what names no block ------------------------------------------------------

by_leaf = weights.borrow(ssm_job.by_leaf, globals())
grad_norms = weights.borrow(ssm_job.grad_norms, globals())
delta_norms = weights.borrow(ssm_job.delta_norms, globals())
first_steps = weights.borrow(ssm_job.first_steps, globals())
compile_reference_ahead = weights.borrow(ssm_job.compile_reference_ahead,
                                         globals())
run = weights.borrow(keye_job.run, globals())
