"""The comparison that decides `correct`: each number beside its limit."""
from __future__ import annotations

import statistics


def worst_leaf_gap(program: dict, reference: dict,
                   skip=()) -> tuple[float, str]:
    """max over leaves of |program norm - reference norm| / max(reference
    norm of that leaf, reference norm of the median leaf): the gap between
    the two norms, not the norm of a difference, and measured against the
    median leaf where a leaf's own norm is all but zero. Leaves named in
    `skip` are not compared (and not in the median)."""
    reference = {k: v for k, v in reference.items() if k not in skip}
    floor = statistics.median(reference.values())
    worst, name = 0.0, ""
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, floor, 1e-30)
        if not gap <= worst:            # NaN counts as worst
            worst, name = gap, leaf
    return worst, name


class Verdict:
    """Collects (number, limit) pairs; `correct` is their conjunction."""

    def __init__(self):
        self.rows = []

    def at_most(self, name: str, value: float, limit: float, note=""):
        ok = bool(value <= limit)       # NaN fails
        self.rows.append((name, value, limit, ok, note))
        print(f"check {'ok ' if ok else 'BAD'} {name} = {value:.6g} "
              f"(limit {limit:g}) {note}", flush=True)
        return ok

    def require(self, name: str, cond: bool, note=""):
        self.rows.append((name, float(not cond), 0.0, bool(cond), note))
        print(f"check {'ok ' if cond else 'BAD'} {name} {note}", flush=True)
        return bool(cond)

    @property
    def correct(self) -> bool:
        return all(r[3] for r in self.rows)
