"""Model FLOP/s utilisation of the KDA / MLA + mixture block: required FLOPs
per token (harness/ling3_flops.py: the KDA layers' projections and chunked
delta rule, the MLA layer's projections and causal pairs at 192 / 128, the
dense layer, router and shared expert, the pairs the program's counter says
were routed to held experts at three products a row, the sliced head) times
tokens per second per chip over the chip's bf16 peak."""
from harness import device, ling3_flops, ling3_weights


def read(ctx):
    cell = ctx["cell"]
    routing = ctx["counters"].get("routing")
    if not routing:
        return None
    mixtures = ling3_weights.kinds(cell["config"]).count("moe")
    need = ling3_flops.train_flops_per_token(
        cell["config"], cell["traffic"]["seq"],
        routing["routed_pairs"] / max(mixtures, 1) / ctx["tokens_per_step"])
    peak = device.peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * need * ctx["e2e"]["train_tok_s_chip"] / peak
