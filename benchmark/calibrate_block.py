#!/usr/bin/env python3
"""Read the numbers a training cell's limits are set from, for a cell
whose runner kind is not `train_job` (calibrate.py is that kind's).

    python3 benchmark/calibrate_block.py --workload <cell> --seeds 1,2,3 --controls 3

The runner is the one the cell's traffic names (`runners/<kind>.py`) and
brings `build_model`, `load_weights`, `build_step`, `first_steps`,
`reference_numbers(cell, seed, precision=, given=, export_picks=)` and
`compare`. As calibrate.py: one model build
serves every seed (each gets a fresh optimizer and step object); then,
the program freed, the plain reference on the same weights and batches,
and for the first `--controls` seeds the control (the reference in fp8).
Prints each number, then per number the largest sound reading and the
smallest control reading. The benchmark's own runs never call this.
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

CONTROLS = ("fp8",)      # the next precision below the cell's bf16 compute


def main(argv=None, allow_cpu=False):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    import run as bench_run

    cell, dev, _ = bench_run.open_cell(args.workload, allow_cpu)
    print(f"calibrate: {args.workload} on {dev}", flush=True)
    runner = load.module("runners", cell["traffic"]["kind"])
    c, job = cell["config"], cell["traffic"]
    t = clock.now()
    model = runner.build_model(cell)
    print(f"model build {clock.now() - t:.1f} s", flush=True)
    got = {}
    for seed in seeds:
        t = clock.now()
        runner.load_weights(model, cell, seed)
        opt, step = runner.build_step(cell, model)
        feed = step.prefetch(data.TokenStream(job, c["vocab_size"], seed))
        got[seed] = runner.first_steps(cell, model, opt, step, iter(feed),
                                       seed)
        feed.close()
        del opt, step, feed
        gc.collect()
        print(f"program seed {seed}: losses {got[seed]['losses']} "
              f"({clock.now() - t:.1f} s)", flush=True)
    del model
    gc.collect()

    sound, control = [], {p: [] for p in CONTROLS}
    for i, seed in enumerate(seeds):
        t = clock.now()
        want = runner.reference_numbers(cell, seed,
                                        given=got[seed].get("picks"))
        _, gaps = runner.compare(cell, got[seed], want, tag=f"seed {seed} ")
        sound.append(gaps)
        print(f"reference seed {seed}: {clock.now() - t:.1f} s", flush=True)
        if i < args.controls:
            for prec in control:
                t = clock.now()
                low = runner.reference_numbers(cell, seed, precision=prec,
                                               export_picks=True)
                # the reference given the CONTROL's picks, as a run's is
                # given the program's
                held = runner.reference_numbers(cell, seed,
                                                given=low["picks"])
                _, gaps = runner.compare(cell, low, held,
                                         tag=f"control {prec} seed {seed} ")
                control[prec].append(gaps)
                print(f"control {prec} seed {seed}: "
                      f"{clock.now() - t:.1f} s", flush=True)
    out = {"workload": args.workload, "seeds": seeds, "device": dev}
    for name in sound[0]:
        out[name] = {"sound_max": max(g[name] for g in sound),
                     "sound_all": [g[name] for g in sound]}
        for prec, rows in control.items():
            if rows:
                out[name][f"{prec}_min"] = min(g[name] for g in rows)
                out[name][f"{prec}_all"] = [g[name] for g in rows]
    print("CALIBRATION " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
