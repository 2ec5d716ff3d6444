"""Victim of the SIGKILL test (test_fault_tolerance.py): run by path with
the repo on PYTHONPATH, it saves checkpoints 0, 1, 2, ... into
``sys.argv[1]`` forever, printing ``committed K`` after every commit,
until it is killed."""
import sys

import numpy as np

_VICTIM_ARRAY_KB = 192      # per-array payload: big enough that a save
_VICTIM_ARRAYS = 4          # takes ~ms, so random kills land mid-write


def victim_state(step: int):
    rng = np.random.default_rng(step)
    n = _VICTIM_ARRAY_KB * 1024 // 4
    return {f"w{i}": rng.standard_normal(n).astype(np.float32)
            for i in range(_VICTIM_ARRAYS)} | {"step_scalar": step}


def main(root: str):
    from paddle_tpu.distributed.checkpoint import CheckpointManager

    extra = victim_state(0)
    mgr = CheckpointManager(root, extra_state=extra, max_to_keep=3)
    step = 0
    while True:
        extra.clear()
        extra.update(victim_state(step))
        mgr.save(step)
        print(f"committed {step}", flush=True)
        step += 1


if __name__ == "__main__":
    main(sys.argv[1])
