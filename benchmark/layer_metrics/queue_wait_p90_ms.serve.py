"""Due time to admission, 90th percentile over requests due in the window
(a request not admitted by the window's end enters at end - due)."""
from harness import stats


def read(ctx):
    waits = ctx["serve"]["qwait"]
    return stats.percentile(waits, 90) * 1e3 if waits else None
