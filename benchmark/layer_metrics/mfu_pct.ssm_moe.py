"""Model FLOP/s utilisation of the state-space + mixture block: required
FLOPs per token (harness/nemotron3_flops.py: the Mamba layers'
projections, the scan, the attention layer's projections and causal
pairs, router and shared expert, the pairs the program's counter says
were routed to held experts at two products a row, the sliced head) times
tokens per second per chip over the chip's bf16 peak."""
from harness import device, nemotron3_flops, nemotron3_weights


def read(ctx):
    cell = ctx["cell"]
    routing = ctx["counters"].get("routing")
    if not routing:
        return None
    mixtures = nemotron3_weights.kinds(cell["config"]).count("E")
    need = nemotron3_flops.train_flops_per_token(
        cell["config"], cell["traffic"]["seq"],
        routing["routed_pairs"] / max(mixtures, 1) / ctx["tokens_per_step"])
    peak = device.peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * need * ctx["e2e"]["train_tok_s_chip"] / peak
