#!/usr/bin/env python3
"""The static schedule of `ops/pallas/kda.py`'s two kernels, with no chip:
compiles `kda` forward + backward for a DESCRIBED v5e with libtpu's LLO dump
on, and counts the instruction bundles the compiler scheduled.

    JAX_PLATFORMS=cpu python3 tools/kda_bundles.py [--seq 8192 --chunk 64]

Prints one line `kda_bundles: {...}`: for `kda_fwd` and `kda_bwd` the bundles
of the whole kernel, of each loop inside a grid step (in program order; a
loop's bundles run once an iteration), the share of bundles that hold an op
of each unit, and the commonest ops. A bundle issues in a cycle unless it
stalls, so the counts say where a grid step's cycles go (PR 42: the parent's
forward body was 1,853 bundles a chunk and ran 2,191 cycles a chunk-head on
the chip) and which unit's ops fill them. A COUNT from the sandbox's
compiler, never a time: times come from tools/time_kda.py on the chip.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = {"kda_fwd": "jvp_kda_fwd_", "kda_bwd": "transpose_jvp_kda_bwd__"}
_BUNDLE = re.compile(r"\s*0x[0-9a-f]+\s+(?:[A-Z]{2})?\s*:\s*(>*)\s*\{(.*)")
_OP = re.compile(r"= ([vs][a-z0-9_.]+)")


def compile_with_dump(args, dump):
    """In a child: with the dump on libtpu aborts the process once the
    program's last kernel is written out (it looks for a report template
    this installation lacks), which is after the files read here."""
    code = f"""
import sys
sys.path.insert(0, {ROOT!r})
import jax, jax.numpy as jnp
jax.config.update("jax_enable_compilation_cache", False)
from jax.experimental import topologies
import paddle_tpu
from paddle_tpu.ops.pallas import kda as K, routing
routing.on_tpu = lambda: True
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
shape = ({args.batch}, {args.seq}, {args.heads}, 128)
wide = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)
def both(*a):
    y, pull = jax.vjp(lambda *x: K.kda(*x, chunk={args.chunk}), *a)
    return y, pull(y)
jax.jit(both).trace(
    wide, wide, wide, jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip),
    jax.ShapeDtypeStruct(shape[:3], jnp.float32, sharding=chip)).lower(
    lowering_platforms=("tpu",)).compile()
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               TPU_ACCELERATOR_TYPE="v5litepod-4",
               TPU_WORKER_HOSTNAMES="localhost",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                "--xla_jf_dump_llo_text=true")
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True)
    for stem in KERNELS.values():
        if not glob.glob(f"{dump}/*{stem}*final_bundles.txt"):
            raise SystemExit(f"kda_bundles: no schedule of {stem} was "
                             "written:\n" + done.stderr[-2000:])


def read(dump, stem):
    """-> the kernel's counts from its `final_bundles` and utilization
    files."""
    path, = [p for p in glob.glob(f"{dump}/*{stem}*final_bundles.txt")
             if "schedule-analysis" not in p]
    total, loops, ops = 0, [], collections.Counter()
    for line in open(path):
        m = _BUNDLE.match(line)
        if not m:
            continue
        total += 1
        if len(m.group(1)) < 2:         # '>' the grid's loop, '>>' one inside
            continue
        if loops and loops[-1][0] == total - 1:
            loops[-1] = [total, loops[-1][1] + 1]
        else:
            loops.append([total, 1])
        ops.update(re.sub(r"\.mxu\d", "", o) for o in _OP.findall(m.group(2)))
    use, = glob.glob(f"{dump}/*{stem}*final_hlo-static-per-bundle-"
                     "utilization.txt")
    lines = open(use).read().split("\n")
    units = [u.strip() for u in lines[1].split(",")]
    rows = [[int(x) for x in row.split()] for row in lines[4:] if row.strip()]
    return {"bundles": total,
            "loops": [n for _, n in loops if n > 40],
            "bundles_with_pct": {u: round(100 * sum(1 for r in rows if r[i])
                                          / len(rows), 1)
                                 for i, u in enumerate(units)},
            "ops": dict(ops.most_common(12))}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=64)
    args = ap.parse_args(argv)
    with tempfile.TemporaryDirectory() as dump:
        compile_with_dump(args, dump)
        out = {name: read(dump, stem) for name, stem in KERNELS.items()}
    print("kda_bundles: " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
