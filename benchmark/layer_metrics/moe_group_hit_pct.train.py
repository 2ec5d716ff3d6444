"""Share of (token, mixture layer) pairs with at least one pick inside the
group of experts that this chip's held experts lie in (the program's counter
`group_hit_tokens`): with `topk_group` of `n_group` groups kept a token the
expected share is topk_group / n_group (a kept group nearly always holds a
pick), which is what bounds the held experts' load from above."""
from harness import ling3_weights


def read(ctx):
    routing = ctx["counters"].get("routing") or {}
    if "group_hit_tokens" not in routing:
        return None
    mixtures = ling3_weights.kinds(ctx["cell"]["config"]).count("moe")
    return 100.0 * routing["group_hit_tokens"] / max(
        mixtures * ctx["tokens_per_step"], 1)
