"""Model FLOP/s utilisation of the gated-convolution / attention + mixture
block: required FLOPs per token (harness/lfm2_flops.py: the convolution
layers' two products, taps and gates, the attention layers' projections and
causal pairs at 64, the dense layer, the router, the pairs the program's
counter says were routed to held experts at three products a row, the tied
head over the vocabulary slice) times tokens per second per chip over the
chip's bf16 peak."""
from harness import device, lfm2_flops, lfm2_weights


def read(ctx):
    cell = ctx["cell"]
    routing = ctx["counters"].get("routing")
    if not routing:
        return None
    mixtures = lfm2_weights.kinds(cell["config"]).count("moe")
    need = lfm2_flops.train_flops_per_token(
        cell["config"], cell["traffic"]["seq"],
        routing["routed_pairs"] / max(mixtures, 1) / ctx["tokens_per_step"])
    peak = device.peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * need * ctx["e2e"]["train_tok_s_chip"] / peak
