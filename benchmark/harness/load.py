"""Find a cell's files by the names in BENCHMARK.json."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# where cells' files are looked for, and which manifest names them; the
# rehearsal tests put their tiny copies in front
SEARCH = [BENCH_DIR]
MANIFEST = [os.path.join(ROOT, "BENCHMARK.json")]


def find(*parts) -> str | None:
    for base in SEARCH:
        path = os.path.join(base, *parts)
        if os.path.exists(path):
            return path
    return None


def read_json(*parts):
    path = find(*parts)
    if path is None:
        raise SystemExit(f"benchmark: {os.path.join(*parts)} is missing")
    with open(path) as f:
        return json.load(f)


def manifest():
    with open(MANIFEST[0]) as f:
        return json.load(f)


def cell(name: str, bench: dict | None = None) -> dict:
    """Everything one run needs: the workload file, its configuration and
    traffic, and the metrics BENCHMARK.json lists for it."""
    bench = bench or manifest()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = read_json("configs", os.path.basename(conf["file"]))
    out = dict(read_json("workloads", name + ".json"))
    out.update(name=name, chips=entry["chips"], config=config,
               config_name=entry["config"],
               traffic=read_json("traffic", entry["traffic"] + ".json"),
               traffic_name=entry["traffic"])

    def mine(metric):
        return name in metric.get("workloads", [name])

    out["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    out["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return out


def module(kind: str, name: str):
    """Import benchmark/<kind>/<name>.py (names may hold dots)."""
    path = find(kind, name + ".py")
    if path is None:
        raise SystemExit(f"benchmark: {kind}/{name}.py is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
