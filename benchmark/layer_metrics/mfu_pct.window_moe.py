"""Model FLOP/s utilisation of the window + mixture block: required FLOPs
per token (harness/mellum2_flops.py: projections, the band's pairs in
sliding layers, causal pairs in full ones, the pairs the program's
counter says were routed to held experts, the sliced head) times tokens
per second per chip over the chip's bf16 peak."""
from harness import device, mellum2_flops


def read(ctx):
    cell = ctx["cell"]
    routing = ctx["counters"].get("routing")
    if not routing:
        return None
    need = mellum2_flops.train_flops_per_token(
        cell["config"], cell["traffic"]["seq"],
        routing["routed_pairs"] / cell["config"]["num_hidden_layers"]
        / ctx["tokens_per_step"])
    peak = device.peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * need * ctx["e2e"]["train_tok_s_chip"] / peak
