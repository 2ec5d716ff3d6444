"""One clock for the whole run: `time.perf_counter`, with the instant the
process started (read from /proc, so interpreter start-up and imports are
inside `setup_s`)."""
from __future__ import annotations

import os
import time

now = time.perf_counter


def process_age_s() -> float:
    """Seconds since this process was started."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def process_start() -> float:
    """The process's start on the `now()` clock."""
    return now() - process_age_s()
