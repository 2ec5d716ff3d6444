"""HLO-derived per-step accounting (ISSUE 12 tentpole part 4).

The analytic 6N-flops MFU the bench has always reported assumes the
model math; ``compiled.cost_analysis()`` asks the COMPILER what the
program actually executes. `cost_analysis_of` pulls flops /
bytes-accessed per step from the compiled executable and — via
tools/hlo_overlap.py's per-axis collective census extended with payload
bytes — the communication bytes per step per mesh axis, then publishes
everything into the metrics registry (``hlo.*`` gauges) so BENCH
records and Prometheus scrapes carry both MFU flavors and the comm
budget of every step program.
"""
from __future__ import annotations

import os

from .registry import registry as _registry

__all__ = ["load_hlo_overlap", "cost_analysis_of"]


def load_hlo_overlap():
    """tools/hlo_overlap.py by path (tools/ lives at the repo root,
    next to the paddle_tpu package — same loader the linalg probe
    uses)."""
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, "tools", "hlo_overlap.py")
    if os.path.exists(path):
        spec = importlib.util.spec_from_file_location("hlo_overlap", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    import tools.hlo_overlap as mod  # namespace-package fallback

    return mod


def _cost_dict(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def cost_analysis_of(jitted, *args, axis_degrees=None, **kw) -> dict:
    """AOT-lower + compile ``jitted`` for ``args`` and account for one
    step of the executable. With the persistent XLA compile cache warm
    (the jit call already compiled the same program) this is cheap; a
    cold compile is the price of the receipt.

    Returns {"flops_per_step", "bytes_accessed_per_step",
    "collectives": {counts, per_axis_counts?, per_axis_bytes?,
    total_comm_bytes}}; numbers are PER DEVICE (cost_analysis and the
    per-device HLO module both are). ``axis_degrees`` (ordered
    {axis: degree}, mesh order) labels the comm traffic per mesh axis.
    Publishes ``hlo.*`` gauges into the global registry. Fields missing
    on a backend are reported as None."""
    compiled = jitted.lower(*args, **kw).compile()
    out = {"flops_per_step": None, "bytes_accessed_per_step": None,
           "collectives": None}
    try:
        ca = _cost_dict(compiled)
        if "flops" in ca:
            out["flops_per_step"] = float(ca["flops"])
        if "bytes accessed" in ca:
            out["bytes_accessed_per_step"] = float(ca["bytes accessed"])
    except Exception as e:
        out["cost_analysis_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        verdict = load_hlo_overlap().analyze(compiled.as_text(),
                                             axis_degrees=axis_degrees)
        coll = {"counts": verdict.get("counts", {}),
                "total_comm_bytes": verdict.get("total_comm_bytes", 0)}
        for k in ("per_axis_counts", "per_axis_bytes"):
            if k in verdict:
                coll[k] = verdict[k]
        out["collectives"] = coll
    except Exception as e:
        out["collectives_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        reg = _registry()
        if out["flops_per_step"] is not None:
            reg.gauge("hlo.flops_per_step").set(out["flops_per_step"])
        if out["bytes_accessed_per_step"] is not None:
            reg.gauge("hlo.bytes_accessed_per_step").set(
                out["bytes_accessed_per_step"])
        coll = out.get("collectives") or {}
        reg.gauge("hlo.comm_bytes_per_step").set(
            coll.get("total_comm_bytes", 0))
        for axis, nbytes in (coll.get("per_axis_bytes") or {}).items():
            reg.gauge(f"hlo.comm_bytes_per_step.{axis}").set(nbytes)
    except Exception:
        pass
    return out
