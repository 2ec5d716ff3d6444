"""Run the tiny LFM2 cell end to end on the CPU: the same run.py,
runner and readers as the chip's cell, kernels interpreted. Never a chip
result.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_lfm2.py --workload lfm2-tiny.train --seed 1 --seconds 2 --trace 0
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None):
    import run
    from harness import load

    load.SEARCH.insert(0, os.path.join(HERE, "tiny"))
    load.MANIFEST[0] = os.path.join(HERE, "tiny",
                                    "BENCHMARK.lfm2-tiny.json")
    from paddle_tpu.utils import flags

    flags.set_flags({"FLAGS_pallas_force_interpret": True})
    return run.main(argv, allow_cpu=True)


if __name__ == "__main__":
    sys.exit(main())
