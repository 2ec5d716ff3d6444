#!/usr/bin/env python3
"""Find a serving cell's knee once: one engine, a few fixed rates.

    python3 benchmark/sweep.py --workload <cell> --rates 0.6,0.9,1.2 --seed 1 --seconds 50

For each rate the cell's own schedule (ramp, then window) runs at that
rate and the residents drain. A rate is sustained when the engine
delivers >= 95 % of the output tokens offered by the requests due in the
window and the waiting queue at the window's end is no longer than at
its middle. The knee is the highest sustained rate; the cell's traffic
file then fixes 0.8 x the knee. PERF.md keeps the table this prints.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from harness import load, stats, traffic  # noqa: E402


def main(argv=None, allow_cpu=False):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import run as bench_run

    cell, dev, _ = bench_run.open_cell(args.workload, allow_cpu)
    serve = load.module("runners", "open_loop")
    job, c = cell["traffic"], cell["config"]
    model, eng, marks = serve.build_engine(cell, args.seed)
    print(f"sweep: {args.workload} on {dev}; set-up {marks}", flush=True)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        sched = traffic.schedule(job, c["vocab_size"], args.seed + i,
                                 job["ramp_s"] + args.seconds, rate=rate)
        d = serve.Drive(eng, sched, job["ramp_s"], args.seconds).run()
        m = d.metrics()
        eng.run()
        steps = m["steps"]
        mid = steps[len(steps) // 2]["waiting"] if steps else 0
        row = {
            "rate_rps": rate, "due": len(m["due"]),
            "offered_tokens": m["offered_tokens"],
            "delivered_tokens": m["tokens"],
            "delivered_share": m["tokens"] / max(m["offered_tokens"], 1),
            "waiting_mid": mid, "waiting_end": d.waiting_end,
            "serve_tok_s": m["tokens"] / args.seconds,
            "ttft_p50_ms": stats.median(m["ttft"]) * 1e3,
            "ttft_p90_ms": stats.percentile(m["ttft"], 90) * 1e3,
            "itl_p50_ms": stats.median(m["gaps"]) * 1e3,
            "itl_p95_ms": stats.percentile(m["gaps"], 95) * 1e3,
            "occupancy": m["occupancy"],
            "engine_step_ms": (stats.median(m["decode_ms"])
                               if m["decode_ms"] else None),
        }
        row["sustained"] = bool(row["delivered_share"] >= 0.95
                                and row["waiting_end"] <= max(mid, 1))
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    ok = [r["rate_rps"] for r in rows if r["sustained"]]
    print("KNEE " + json.dumps({"knee_rps": max(ok) if ok else None,
                                "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
