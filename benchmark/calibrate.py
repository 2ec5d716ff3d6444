#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, in one process.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 --controls 3

Training: for every seed the program's first three steps (one model build
serves all seeds; each gets a fresh optimizer and step object), then — the
program freed — the plain reference on the same weights and batches, and
for the first `--controls` seeds the control: the reference computed in
the next precision below the configuration's (fp8 for bf16 compute).
Serving: one engine; for every seed the weights are drawn again and the
cell's own schedule runs for `--seconds` after its ramp and drains; then —
the engine freed — the reference follows the same sample of finished
requests a run would, and for the control seeds the fp8 forward's first
choice takes the served token's place. Prints each number, then per
number the largest sound reading and the smallest control reading. The
benchmark's own runs never call this; PERF.md records what it printed on
the chip.
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

from harness import clock, data, load, traffic, weights  # noqa: E402

CONTROLS = ("fp8",)      # the next precision below the cells' bf16 compute


def train(cell, seeds, args):
    runner = load.module("runners", "train_job")
    c, job = cell["config"], cell["traffic"]
    t = clock.now()
    model = runner.build_model(cell)
    print(f"model build {clock.now() - t:.1f} s", flush=True)
    got = {}
    for seed in seeds:
        t = clock.now()
        weights.load_into(model, c, seed)
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
        want = runner.reference_numbers(cell, seed)
        _, gaps = runner.compare(cell, got[seed], want, tag=f"seed {seed} ")
        sound.append(gaps)
        print(f"reference seed {seed}: {clock.now() - t:.1f} s", flush=True)
        if i < args.controls:
            for prec in control:
                t = clock.now()
                low = runner.reference_numbers(cell, seed, precision=prec)
                _, gaps = runner.compare(cell, low, want,
                                         tag=f"control {prec} seed {seed} ")
                control[prec].append(gaps)
                print(f"control {prec} seed {seed}: "
                      f"{clock.now() - t:.1f} s", flush=True)
    return sound, control


def serve(cell, seeds, args):
    runner = load.module("runners", "open_loop")
    c, job, e = cell["config"], cell["traffic"], cell["engine"]
    model, eng, marks = runner.build_engine(cell, seeds[0])
    print(f"engine set-up {marks}", flush=True)
    samples = {}
    for seed in seeds:
        t = clock.now()
        weights.load_into(model, c, seed)
        sched = traffic.schedule(job, c["vocab_size"], seed,
                                 job["ramp_s"] + args.seconds)
        drive = runner.Drive(eng, sched, job["ramp_s"], args.seconds).run()
        eng.run()
        samples[seed] = runner.pick_sample(drive, seed)
        print(f"program seed {seed}: {len(drive.requests)} requests, "
              f"sample of {len(samples[seed])} with "
              f"{sum(len(s[1]) for s in samples[seed])} served tokens "
              f"({clock.now() - t:.1f} s)", flush=True)
        drive.eng = None
    del eng, model, drive
    gc.collect()

    sound, control = [], {p: [] for p in CONTROLS}
    for i, seed in enumerate(seeds):
        t = clock.now()
        worst, rows = runner.reference_gaps(cell, seed, samples[seed])
        sound.append({"served_logit_gap": worst})
        print(f"seed {seed} served_logit_gap = {worst:.6g} {rows} "
              f"({clock.now() - t:.1f} s)", flush=True)
        if i < args.controls:
            for prec in control:
                worst, rows = runner.reference_gaps(cell, seed, samples[seed],
                                                    precision=prec)
                control[prec].append({"served_logit_gap": worst})
                print(f"control {prec} seed {seed} served_logit_gap = "
                      f"{worst:.6g} {rows}", flush=True)
    return sound, control


def main(argv=None, allow_cpu=False):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="serving: the short window at the cell's own load")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    import run as bench_run

    cell, dev, _ = bench_run.open_cell(args.workload, allow_cpu)
    print(f"calibrate: {args.workload} on {dev}", flush=True)
    kind = cell["traffic"]["kind"]
    sound, control = (train if kind == "train_job" else serve)(
        cell, seeds, args)
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
