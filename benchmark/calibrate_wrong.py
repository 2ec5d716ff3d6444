#!/usr/bin/env python3
"""Hold a cell's program against the reference of WRONG programs, on the
chip, by hand: the program's first three steps on one seed, then the plain
reference given the program's picks, once as it is and once for every
`--wrong` (keyword arguments of the runner's `reference_numbers`: what the
block's reference can be told to compute wrongly). Each wrong reference has
to fail a limit of the cell; the sound one none.

    python3 benchmark/calibrate_wrong.py --workload <cell> --seed 7 \
        --wrong zero_state=1 --wrong skip_d=1

calibrate_block.py reads the sound program and the control; this reads
what a limit is held against. The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from harness import clock, data, load  # noqa: E402


def main(argv=None, allow_cpu=False):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--wrong", action="append", default=[],
                    metavar="KEY=VALUE[,KEY=VALUE]")
    args = ap.parse_args(argv)

    import run as bench_run

    cell, dev, _ = bench_run.open_cell(args.workload, allow_cpu)
    runner = load.module("runners", cell["traffic"]["kind"])
    c, job = cell["config"], cell["traffic"]
    model = runner.build_model(cell)
    runner.load_weights(model, cell, args.seed)
    opt, step = runner.build_step(cell, model)
    feed = step.prefetch(data.TokenStream(job, c["vocab_size"], args.seed))
    got = runner.first_steps(cell, model, opt, step, iter(feed), args.seed)
    feed.close()
    del model, opt, step, feed
    gc.collect()
    out = {"workload": args.workload, "seed": args.seed, "device": dev}
    for spec in [""] + args.wrong:
        wrong = {k: json.loads(v) for k, v in (
            item.split("=", 1) for item in spec.split(",") if item)}
        name = spec or "sound"
        t = clock.now()
        want = runner.reference_numbers(cell, args.seed,
                                        given=got.get("picks"), **wrong)
        verdict, gaps = runner.compare(cell, got, want, tag=name + " ")
        out[name] = dict(gaps, correct=verdict.correct)
        print(f"{name}: correct {verdict.correct} "
              f"({clock.now() - t:.1f} s)", flush=True)
    print("WRONG_PROGRAMS " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
