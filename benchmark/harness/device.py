"""The device a run is on, and the published peaks it is measured against.

Peaks are keyed by `device_kind` as JAX reports it. A kind that is not in
the table is an error, never a default (copied from bench.py
`_PEAK_BF16_FLOPS`, with the memory bandwidth added).
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s per chip. JAX reports the chip as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


class NoChip(SystemExit):
    """No TPU, too few chips, or a chip without published peaks."""


def require_chips(chips: int, allow_cpu: bool = False) -> dict:
    """The `device` object of the result line, or exit non-zero.

    `allow_cpu` is for the rehearsal tests only: they call the runners
    directly and never reach a result that names a device metric.
    """
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" and not allow_cpu:
        raise NoChip(f"benchmark: no TPU: jax.devices() is {devs}")
    if len(devs) < chips:
        raise NoChip(f"benchmark: the cell needs {chips} chips, "
                     f"found {len(devs)}")
    if d.platform == "tpu" and d.device_kind not in PEAKS:
        raise NoChip(f"benchmark: no published peaks for device_kind "
                     f"{d.device_kind!r}; add it to harness/device.py "
                     "with its source")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peaks(kind: str) -> dict:
    return PEAKS[kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest of `devices`: the allocator's
    `peak_bytes_in_use` (arrays) plus `peak_bytes_reserved`, the space the
    TPU runtime sets aside for the executables' own temporaries, which
    `peak_bytes_in_use` leaves out (gpt3-1.3b train: 10.77 + 3.81 GB, the
    sum matching the compiler's 13.65 GB estimate; my chip run, PR 23).
    0 where the backend reports nothing, as the CPU rehearsal's does."""
    out = 0
    for d in devices:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0))
                  + int(stats.get("peak_bytes_reserved", 0)))
    return out
