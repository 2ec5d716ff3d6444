"""Model FLOP/s utilisation: required FLOPs per token (harness/flops.py)
times tokens per second per chip over the chip's bf16 peak."""
from harness import device, flops


def read(ctx):
    cell = ctx["cell"]
    need = flops.train_flops_per_token(cell["config"], cell["traffic"]["seq"])
    peak = device.peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * need * ctx["e2e"]["train_tok_s_chip"] / peak
