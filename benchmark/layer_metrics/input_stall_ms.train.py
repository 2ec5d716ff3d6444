"""Mean time a step waited for its batch: the prefetcher's own counter."""


def read(ctx):
    c = ctx["counters"]
    return c["input_stall_ms_total"] / max(c["input_batches"], 1)
