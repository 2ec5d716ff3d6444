"""The yardstick against hand-worked numbers: percentiles, FLOP counts,
each kernel's operations and bytes, the traffic generator, and the trace
reduction on a recorded four-step trace of gpt3-1.3b.train.8x1024 (one
v5e chip, PR 23). Run by hand: `pytest benchmark/tests`."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from harness import flops, load, stats, traffic  # noqa: E402

GPT13 = load.read_json("configs", "gpt3-1.3b.json")
GPT350 = load.read_json("configs", "gpt3-350m.json")


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90 and stats.percentile(v, 95) == 95
    assert stats.percentile([5, 1, 3], 50) == 3
    assert stats.percentile([7], 99) == 7
    assert stats.median([4, 1, 3, 2]) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_flops_by_hand():
    # 1.3b: 24 x (4 x 2048^2 + 2 x 2048 x 8192) + 50304 x 2048
    assert flops.matmul_params(GPT13) == 24 * 50331648 + 103022592
    assert flops.matmul_params(GPT350) == 24 * 12582912 + 51511296
    # attention per token: 12 x H x (S + 1) / 2 per layer... 6 H (S + 1)
    per_tok = 6 * 1310982144 + 24 * 6 * 2048 * 1025
    assert flops.train_flops_per_token(GPT13, 1024) == pytest.approx(per_tok)
    assert per_tok == pytest.approx(8.168e9, rel=1e-3)
    assert flops.train_flops_per_token(GPT350, 1024) == pytest.approx(
        6 * 353501184 + 24 * 6 * 1024 * 1025)


@pytest.mark.parametrize("name,args,ops,nbytes", [
    # 8 x 32 heads, 1024 x 1025 / 2 pairs, 4 x 64 FLOPs a pair
    ("splash_fwd", (8, 1024, 32, 64), 4 * 64 * 524800 * 256,
     4 * 8 * 1024 * 2048 * 2 + 256 * 1024 * 4),
    ("splash_bwd", (8, 1024, 32, 64), 10 * 64 * 524800 * 256,
     8 * 8 * 1024 * 2048 * 2 + 2 * 256 * 1024 * 4),
    ("splash_fwd", (8, 1024, 16, 64), 4 * 64 * 524800 * 128,
     4 * 8 * 1024 * 1024 * 2 + 128 * 1024 * 4),
    ("fused_ce_fwd", (8192, 2048, 50304), 2 * 8192 * 2048 * 50304,
     (8192 * 2048 + 50304 * 2048) * 2 + 8192 * 8),
    ("fused_ce_bwd", (8192, 1024, 50304), 6 * 8192 * 1024 * 50304,
     (2 * 8192 * 1024 + 50304 * 1024) * 2 + 50304 * 1024 * 4 + 8192 * 8),
    # 32 sequences, 6400 live tokens: K and V of 32 x 64 bf16 each
    ("paged_attention_decode", (6400, 32, 64, 2, 32), 4 * 64 * 32 * 6400,
     2 * 6400 * 2048 * 2 + 2 * 32 * 2048 * 2),
    # one window of 64 after 128 cached: 64 x 128 + 64 x 65 / 2 pairs
    ("paged_attention_chunk", ([128], 64, 32, 64), 4 * 64 * 32 * 10272,
     2 * 192 * 2048 * 2 + 2 * 64 * 2048 * 2),
])
def test_kernel_counts_by_hand(name, args, ops, nbytes):
    assert load.module("kernels", name).cost(*args) == (ops, nbytes)


def test_kernel_counts_from_cell():
    cell = load.cell("gpt3-1.3b.train.8x1024")
    assert load.module("kernels", "splash_fwd").from_cell(cell) == \
        load.module("kernels", "splash_fwd").cost(8, 1024, 32, 64)
    cell = load.cell("gpt3-350m.train.8x1024")
    assert load.module("kernels", "fused_ce_fwd").from_cell(cell) == \
        load.module("kernels", "fused_ce_fwd").cost(8192, 1024, 50304)


def test_every_kernel_and_metric_file_is_found():
    bench = load.manifest()
    for m in bench["per_layer"]:
        assert hasattr(load.module("layer_metrics", m["name"]), "read")
    for k in ("splash_fwd", "splash_bwd", "fused_ce_fwd", "fused_ce_bwd",
              "paged_attention_decode", "paged_attention_chunk"):
        assert hasattr(load.module("kernels", k), "cost")


def test_schedule_same_work_every_seed():
    job = load.read_json("traffic", "chat-steady.json")
    n = job["block"]
    horizon = 4 * n + 0.001                 # four blocks at 1 request/s
    a = traffic.schedule(job, 50304, 1, horizon, rate=1.0)
    b = traffic.schedule(job, 50304, 5_000_000_001, horizon, rate=1.0)
    assert len(a) == len(b) == 4 * n
    for blk in range(4):
        sl = slice(n * blk, n * blk + n)
        assert sorted(len(r[1]) for r in a[sl]) == \
            sorted(len(r[1]) for r in b[sl]) == \
            traffic.length_quantiles(job["prompt"], n)
        assert sorted(r[2] for r in a[sl]) == sorted(r[2] for r in b[sl])
    assert [len(r[1]) for r in a] != [len(r[1]) for r in b]
    assert a[n - 1][0] == pytest.approx(n) and b[-1][0] == pytest.approx(4 * n)
    again = traffic.schedule(job, 50304, 1, horizon, rate=1.0)
    assert all((x[1] == y[1]).all() and x[0] == y[0]
               for x, y in zip(a, again))
    assert all(16 <= len(r[1]) <= 896 and 8 <= r[2] <= 128
               and len(r[1]) + r[2] <= 1024 for r in a)


@pytest.fixture(scope="module")
def reduced():
    from harness import trace_reduce

    path = os.path.join(BENCH, "data", "train-1.3b-4steps.xplane.pb")
    return trace_reduce.reduce_profile(trace_reduce.load(path), 1)


def test_trace_busy_union(reduced):
    # four steps of 1.4453 s (the trace's own 'XLA Modules' line)
    assert reduced["window_s"] == pytest.approx(5.7948, abs=1e-3)
    assert reduced["busy_s"] == pytest.approx(5.7359, abs=1e-3)
    assert reduced["idle_share_worst"] == pytest.approx(0.01017, abs=1e-4)


def test_trace_kernel_time(reduced):
    from harness import trace_reduce

    # 24 layers x 4 steps, forward scan and the backward's recompute
    secs, n = trace_reduce.kernel_seconds(reduced, ["splash_fwd"])
    assert n == 192 and secs == pytest.approx(0.18676, abs=1e-4)
    secs, n = trace_reduce.kernel_seconds(reduced, ["fused_ce_bwd"])
    assert n == 4 and secs == pytest.approx(0.19741, abs=1e-4)
    assert trace_reduce.kernel_seconds(reduced, ["paged_attention"]) == (0, 0)


def test_trace_gap_attribution(reduced):
    # every idle gap of this trace lies inside the benchmark's step span
    assert reduced["idle_gaps"][0][0] == "bench.step"
    assert reduced["idle_gaps"][0][1] == pytest.approx(0.05891, abs=1e-4)
    top = dict((k, v) for k, v in reduced["device_ops"][:3])
    assert top["copy f32[24,1,2048,8192]"] == pytest.approx(0.9394, abs=1e-3)


def test_short_names():
    from harness.trace_reduce import short_name

    assert short_name("%copy.517 = f32[24,1,8192,2048]{3,2,1,0:T(8,128)} "
                      "copy(f32[24] %x)") == "copy f32[24,1,8192,2048]"
    assert short_name("%jvp_splash_fwd_.15 = (bf16[2]{0}, f32[2]{0}) "
                      "custom-call()") == "jvp_splash_fwd_ (tuple)"
    assert short_name("%while.12") == "while"


def test_interval_arithmetic():
    from harness import trace_reduce as tr

    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    ev = tr.self_times([(0, 10, "while"), (1, 4, "a"), (5, 9, "b")])
    assert ev[0][3] == 3 and not ev[0][4] and ev[1][4] and ev[2][4]
    assert tr.span_at([(0, 10, "outer"), (2, 5, "inner")], 3) == "inner"


def test_manifest_meets_the_contract():
    bench = load.manifest()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.1
                                    for m in e2e.values())
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(load.ROOT, c["file"])))
        assert cfg["hidden_size"] == cfg["num_attention_heads"] * cfg[
            "head_dim"]
        assert {"source", "assumed", "reduced"} <= set(cfg)
        assert cfg["reduced"] == c["reduced"]
