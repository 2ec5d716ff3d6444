"""Median host time of one engine.step() that ran a decode (the
benchmark's own span around the call), over the window."""
from harness import stats


def read(ctx):
    ms = ctx["serve"]["decode_ms"]
    return stats.median(ms) if ms else None
