"""Mean time the host took to fetch a batch and queue its step (the step
call's return, not the step's end): what a late-read loss hides."""


def read(ctx):
    return 1e3 * ctx["counters"]["host_queue_s"] / max(ctx["steps"], 1)
