"""AOT memory diagnosis of a fused-scan train step: lower+compile the
program and print the XLA buffer-assignment stats (argument/output/temp/
alias sizes, the peak they imply, and the top-K largest buffers with
HLO op provenance) WITHOUT executing — the way to see whether donation
aliased the state through the scan carries and where the peak lives,
without paying an on-chip OOM each probe.

Since ISSUE 14 this is a thin CLI over
``paddle_tpu.observability.memory.CompiledMemoryProfile`` — the ONE
buffer-assignment-parsing implementation, the same one
``step.memory_profile()`` and the bench ``mem`` records use.

Usage: python tools/diag_fused_mem.py [model] [batch]
Env:   SEQ=1024 FP32_STORE=1 FUSED_HEAD=0 LAYER_CHUNK=1 TOP_K=8
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    model_name = sys.argv[1] if len(sys.argv) > 1 else "gpt3-1.3b"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    seq = int(os.environ.get("SEQ", "1024"))
    top_k = int(os.environ.get("TOP_K", "8"))

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu.jit import FusedScanTrainStep
    from paddle_tpu.models import GPTForCausalLM, gpt_config
    from paddle_tpu.utils.compile_cache_dir import use_compile_cache

    use_compile_cache()
    cfg = gpt_config(model_name, max_position_embeddings=seq,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                     scan_layers=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    compute_dtype = None
    if os.environ.get("FP32_STORE", "1") == "1":
        compute_dtype = "bfloat16"      # fp32-stored params, bf16 compute
        opt = popt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                         moment_dtype="bfloat16")
    else:
        model.bfloat16()
        opt = popt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                         multi_precision=True, moment_dtype="bfloat16")
    step = FusedScanTrainStep(
        model, opt, fused_head=os.environ.get("FUSED_HEAD", "0") == "1",
        compute_dtype=compute_dtype,
        layer_chunk=int(os.environ.get("LAYER_CHUNK", "1")))
    step.ensure_built()

    import numpy as np

    ids = paddle.to_tensor(np.zeros((batch, seq), np.int32))
    labels = paddle.to_tensor(np.zeros((batch, seq), np.int32))
    prof = step.memory_profile(ids, labels, top_k=top_k, publish=False)
    print(f"model={model_name} batch={batch} seq={seq}")
    print(prof.render())


if __name__ == "__main__":
    main()
