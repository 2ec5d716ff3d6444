"""Model FLOP/s utilisation of the sparse block: required FLOPs per token
(harness/keye_flops.py: selected pairs, all causal indexer pairs, the
pairs routed to held experts, the sliced head) times tokens per second
per chip over the chip's bf16 peak."""
from harness import device, keye_flops


def read(ctx):
    cell = ctx["cell"]
    routing = ctx["counters"].get("routing")
    if not routing:
        return None
    layers = cell["config"]["num_hidden_layers"]
    tokens = ctx["tokens_per_step"]
    need = keye_flops.train_flops_per_token(
        cell["config"], cell["traffic"]["seq"],
        routing["routed_pairs"] / layers / tokens,
        routing["kept_keys"] / layers / tokens)
    peak = device.peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * need * ctx["e2e"]["train_tok_s_chip"] / peak
