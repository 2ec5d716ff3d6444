"""The Nemotron-H cell rehearsed on the CPU at a tiny size: run.py, the
`ssm_moe_train_job` runner, the reference, the control, the references of
two wrong programs and a broken timed path, through the tiny manifest
`tiny/BENCHMARK.nemotron3-tiny.json`; every new reader returning a number
or None; the counts of harness/nemotron3_flops.py and kernels/ssd_scan_*.py
by hand. Run by hand with the other benchmark tests
(`JAX_PLATFORMS=cpu pytest benchmark/tests`); nothing here is a chip
result."""
import json
import os
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

PRELUDE = textwrap.dedent(f"""
    import os, sys
    sys.path.insert(0, {BENCH!r}); sys.path.insert(0, {ROOT!r})
    sys.path.insert(0, {HERE!r})
    import rehearse_nemotron3
""")
RUN = ('["--workload", "nemotron3-tiny.train", "--seed", "4000000007", '
       '"--seconds", "0.5", "--trace", "0"]')
CELL = "nemotron3-nano-30b-a3b.train.4x8192"


def child(code, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", PRELUDE + textwrap.dedent(
        code)], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=timeout)


def result(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_tiny_cell_runs_end_to_end_and_is_correct():
    proc = child(f"sys.exit(rehearse_nemotron3.main({RUN}))")
    line = result(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert "check ok  routed_pairs_gap" in proc.stdout
    assert "check ok  expert_pick_miss" in proc.stdout
    assert "'routed_pairs': " in proc.stdout


def test_a_broken_timed_path_is_not_correct():
    """The program's scan forgets D x on the timed path: the run that
    measured it must not come out `correct`."""
    proc = child(f"""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import ssd_scan as ssd
        whole = ssd.ssd_scan
        ssd.ssd_scan = lambda x, dt, A, B, C, D, **kw: whole(
            x, dt, A, B, C, jnp.zeros_like(D), **kw)
        sys.exit(rehearse_nemotron3.main({RUN}))
    """)
    assert result(proc)["correct"] is False
    assert "check BAD" in proc.stdout


def test_the_control_and_the_wrong_references_fail_a_limit():
    proc = child("""
        from harness import load
        load.SEARCH.insert(0, os.path.join(%r, "tiny"))
        load.MANIFEST[0] = os.path.join(%r, "tiny",
                                        "BENCHMARK.nemotron3-tiny.json")
        cell = load.cell("nemotron3-tiny.train")
        runner = load.module("runners", "ssm_moe_train_job")
        sound = runner.reference_numbers(cell, 11, export_picks=True)
        held = runner.reference_numbers(cell, 11, given=sound["picks"])
        print("SOUND", runner.compare(cell, sound, held)[0].correct)
        low = runner.reference_numbers(cell, 11, precision="fp8",
                                       export_picks=True)
        held = runner.reference_numbers(cell, 11, given=low["picks"])
        print("CONTROL", runner.compare(cell, low, held,
                                        tag="control ")[0].correct)
        # the reference of a program whose chunks start from a zero state,
        # and of one without D x, held against a sound run
        for name, wrong in (("ZERO_STATE", {"zero_state": True}),
                            ("NO_D", {"skip_d": True})):
            want = runner.reference_numbers(cell, 11, given=sound["picks"],
                                            **wrong)
            print(name, runner.compare(cell, sound, want,
                                       tag=name + " ")[0].correct)
    """ % (HERE, HERE))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "SOUND True" in proc.stdout
    for name in ("CONTROL", "ZERO_STATE", "NO_D"):
        assert name + " False" in proc.stdout, name


def test_every_new_reader_returns_a_number_or_none():
    """Untraced (a CPU trace has no device plane) the trace readers give
    None and do not raise; the counter readers give numbers."""
    from harness import load

    bench = load.manifest()
    cell = load.cell(CELL)
    names = [m["name"] for m in bench["per_layer"]
             if m.get("workloads") == [CELL]]
    assert sorted(names) == sorted([
        "mfu_pct.ssm_moe", "ssm_ms.train", "ssm_proj_ms.train",
        "ssm_conv_ms.train", "ssd_scan_ms.train", "ssm_gate_norm_ms.train",
        "moe_shared_ms.train", "ssd_scan_roofline"])
    ctx = {"cell": cell, "device": {"kind": "TPU v5 lite", "platform": "tpu"},
           "counters": {"routing": {"routed_pairs": 4 * 12288,
                                    "computed_rows": 4 * 14336,
                                    "max_load_over_mean": 1.1}},
           "tokens_per_step": 32768,
           "e2e": {"train_tok_s_chip": 30000.0}}
    for name in names:
        value = load.module("layer_metrics", name).read(ctx)
        if name == "mfu_pct.ssm_moe":
            assert 20 < value < 40, value
        else:
            assert value is None, name
    # a parent's counters (no routing): nothing, and no raise
    ctx["counters"] = {}
    assert load.module("layer_metrics", "mfu_pct.ssm_moe").read(ctx) is None


def test_required_flops_by_hand():
    from harness import nemotron3_flops

    cfg = json.load(open(os.path.join(
        BENCH, "configs", "nemotron3-nano-30b-a3b.json")))
    parts = nemotron3_flops.per_token(cfg, 8192, 0.375)
    w_in, w_out = 2688 * (4096 + 6144 + 64), 4096 * 2688
    assert parts["mamba"] == 6.0 * 4 * (w_in + w_out + 4 * 6144)
    chunk = 8 * 2 * 128 * 128 * 128 + 64 * (2 * 128 * 128 * 64
                                            + 4 * 128 * 128 * 64)
    assert parts["scan"] == 3.0 * 4 * chunk / 128
    assert parts["attention"] == 6.0 * (2 * 2688 * 4096 + 2 * 2688 * 256) \
        + 3.0 * 4 * 128 * 32 * (8192 * 8193 // 2) / 8192
    assert parts["mixture"] == 6.0 * 4 * (2688 * 128 + 2 * 2688 * 3712)
    assert parts["experts"] == 6.0 * 4 * 0.375 * 2 * 2688 * 1856
    assert parts["head"] == 6.0 * 2688 * 16384
    # ISSUE 39's arithmetic: 38.74 M a Mamba layer, 23.40 M the attention
    # layer; a step's scan 1.3 TFLOP, its causal pairs 7.7; the Mamba
    # layers 49 % of the products
    assert abs((w_in + w_out) / 1e6 - 38.71) < 0.01
    step = {k: v * 32768 / 1e12 for k, v in parts.items()}
    assert abs(step["scan"] - 1.34) < 0.01
    assert abs(3.0 * 4 * 128 * 32 * (8192 * 8193 // 2) * 4 / 1e12 - 6.6) < 0.1
    products = sum(step.values()) - step["scan"] \
        - 3.0 * 4 * 128 * 32 * (8192 * 8193 // 2) * 4 / 1e12
    assert 0.47 < step["mamba"] / products < 0.51
    assert 60 < products < 65


def test_kernel_costs_by_hand():
    from harness import load

    cell = load.cell(CELL)
    fwd = load.module("kernels", "ssd_scan_fwd")
    bwd = load.module("kernels", "ssd_scan_bwd")
    assert fwd.chunk_ops(4, 2, 3, 1, 5) == 2 * 16 * 5 + 2 * (2 * 16 * 3
                                                            + 4 * 4 * 5 * 3)
    ops, nbytes = fwd.cost(2, 8, 2, 3, 1, 5, 4)
    assert ops == 2 * 2 * fwd.chunk_ops(4, 2, 3, 1, 5)
    assert nbytes == (2 * 2 * 8 * 2 * 3 + 2 * 2 * 8 * 5) * 2 + 2 * 8 * 2 * 4
    assert bwd.cost(2, 8, 2, 3, 1, 5, 4) == (2 * ops, nbytes)
    assert fwd.layers(cell) == 4
    assert fwd.shapes(cell) == (4, 8192, 64, 64, 8, 128, 128)
    ops, nbytes = fwd.from_cell(cell)
    assert ops == 256 * (8 * 2 * 128 ** 3 + 64 * 6 * 128 * 128 * 64)
    assert nbytes == 2 * (2 * 4 * 8192 * 4096 + 2 * 4 * 8192 * 1024) \
        + 4 * 8192 * 64 * 4
    # the shared readers' count files read this cell unedited
    full = load.module("kernels", "full_attention")
    assert full.layers(cell) == 1
    assert full.from_cell(cell)[0] == 14 * 128 * 32 * 4 * (8192 * 8193 // 2)
    ce = load.module("kernels", "fused_ce_fwd")
    assert ce.from_cell(cell)[0] == 2 * 32768 * 2688 * 16384


def test_the_new_scopes_on_paths_as_the_profiler_writes_them():
    from harness import scope_tree
    from paddle_tpu.profiler import DEVICE_SCOPES

    node_of = scope_tree.finder(DEVICE_SCOPES)
    assert node_of("jit(step_fn)/forward/ssm/scan/pallas_call:") == "ssm/scan"
    assert node_of("jit(step_fn)/backward/transpose(jvp(ssm/project))/"
                   "dot_general") == "ssm/project"
    assert node_of("jit(step_fn)/backward/checkpoint/ssm/gate_norm/mul") == \
        "ssm/gate_norm"
    assert node_of("jit(step_fn)/forward/jvp(moe/shared)/dot_general") == \
        "moe/shared"
    assert node_of("jit(step_fn)/forward/my_ssm/scanner") is None
