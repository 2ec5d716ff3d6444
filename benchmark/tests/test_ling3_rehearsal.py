"""The Ling-3.0 cell rehearsed on the CPU at a tiny size: run.py, the
`kda_moe_train_job` runner, the reference, the control, the references of
four wrong programs and a broken timed path, through the tiny manifest
`tiny/BENCHMARK.ling3-tiny.json`; every new reader returning a number or
None; the counts of harness/ling3_flops.py and kernels/kda_*.py,
mla_attention.py by hand. Run by hand with the other benchmark tests
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
    import rehearse_ling3
""")
RUN = ('["--workload", "ling3-tiny.train", "--seed", "4000000007", '
       '"--seconds", "0.5", "--trace", "0"]')
CELL = "ling-3.0-flash-vl.train.2x8192"
NEW = ["mfu_pct.kda_moe", "kda_ms.train", "kda_proj_ms.train",
       "kda_conv_ms.train", "kda_gate_ms.train", "kda_scan_ms.train",
       "kda_gate_norm_ms.train", "mla_attn_ms.train", "mla_proj_ms.train",
       "kda_scan_roofline", "mla_attn_roofline", "moe_group_hit_pct.train"]


def child(code, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", PRELUDE + textwrap.dedent(
        code)], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=timeout)


def result(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_tiny_cell_runs_end_to_end_and_is_correct():
    proc = child(f"sys.exit(rehearse_ling3.main({RUN}))")
    line = result(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert "check ok  routed_pairs_gap" in proc.stdout
    assert "check ok  expert_pick_miss" in proc.stdout
    assert "'group_hit_tokens': " in proc.stdout


def test_a_broken_timed_path_is_not_correct():
    """The program's scan takes one decay a head (the channels' mean) on the
    timed path: the run that measured it must not come out `correct`."""
    proc = child(f"""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import kda as K
        whole = K.kda
        K.kda = lambda q, k, v, a, beta, **kw: whole(
            q, k, v, jnp.broadcast_to(a.mean(-1, keepdims=True), a.shape),
            beta, **kw)
        sys.exit(rehearse_ling3.main({RUN}))
    """)
    assert result(proc)["correct"] is False
    assert "check BAD" in proc.stdout


def test_the_control_and_the_wrong_references_fail_a_limit():
    proc = child("""
        from harness import load
        load.SEARCH.insert(0, os.path.join(%r, "tiny"))
        load.MANIFEST[0] = os.path.join(%r, "tiny",
                                        "BENCHMARK.ling3-tiny.json")
        cell = load.cell("ling3-tiny.train")
        runner = load.module("runners", "kda_moe_train_job")
        sound = runner.reference_numbers(cell, 11, export_picks=True)
        held = runner.reference_numbers(cell, 11, given=sound["picks"])
        print("SOUND", runner.compare(cell, sound, held)[0].correct)
        low = runner.reference_numbers(cell, 11, precision="fp8",
                                       export_picks=True)
        held = runner.reference_numbers(cell, 11, given=low["picks"])
        print("CONTROL", runner.compare(cell, low, held,
                                        tag="control ")[0].correct)
        # the references of the four wrong programs, held against a sound run
        for name in ("no_correction", "head_decay", "zero_state",
                     "no_group_limit"):
            want = runner.reference_numbers(cell, 11, given=sound["picks"],
                                            **{name: True})
            print(name.upper(), runner.compare(cell, sound, want,
                                               tag=name + " ")[0].correct)
    """ % (HERE, HERE))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "SOUND True" in proc.stdout
    for name in ("CONTROL", "NO_CORRECTION", "HEAD_DECAY", "ZERO_STATE",
                 "NO_GROUP_LIMIT"):
        assert name + " False" in proc.stdout, name


def test_every_new_reader_returns_a_number_or_none():
    """Untraced (a CPU trace has no device plane) the trace readers give
    None and do not raise; the counter readers give numbers."""
    from harness import load

    bench = load.manifest()
    cell = load.cell(CELL)
    # by name, not by `workloads == [CELL]`: a later block appends its cell
    names = [m["name"] for m in bench["per_layer"]
             if m["name"] in NEW and CELL in m["workloads"]]
    assert sorted(names) == sorted(NEW)
    ctx = {"cell": cell, "device": {"kind": "TPU v5 lite", "platform": "tpu"},
           "counters": {"routing": {"routed_pairs": 6 * 2048,
                                    "computed_rows": 6 * 4096,
                                    "max_load_over_mean": 1.2,
                                    "group_hit_tokens": 6 * 8000}},
           "tokens_per_step": 16384,
           "e2e": {"train_tok_s_chip": 20000.0}}
    for name in names:
        value = load.module("layer_metrics", name).read(ctx)
        if name == "mfu_pct.kda_moe":
            assert 25 < value < 45, value
        elif name == "moe_group_hit_pct.train":
            assert abs(value - 100 * 8000 / 16384) < 1e-9
        else:
            assert value is None, name
    # a parent's counters (no routing, or no group counter): nothing, and
    # no raise
    del ctx["counters"]["routing"]["group_hit_tokens"]
    assert load.module("layer_metrics",
                       "moe_group_hit_pct.train").read(ctx) is None
    ctx["counters"] = {}
    for name in ("mfu_pct.kda_moe", "moe_group_hit_pct.train"):
        assert load.module("layer_metrics", name).read(ctx) is None


def test_the_manifest_names_the_cell_in_every_shared_list():
    from harness import load

    bench = load.manifest()
    cell = load.cell(CELL)
    got = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= got and len(got) == 36
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_s_chip",
                                                      "setup_s"}
    entry = next(c for c in bench["configs"]
                 if c["name"] == "ling-3.0-flash-vl")
    assert entry["reduced"] == cell["config"]["reduced"]
    # every number of the published row the file does not list as reduced
    c = cell["config"]
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"]) == (
        2560, 32, 128)
    assert (c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"]) == (512, 128, 64, 128)
    assert (c["published"]["num_experts"], c["n_group"], c["topk_group"],
            c["num_experts_per_tok"]) == (512, 8, 4, 8)
    assert (c["moe_intermediate_size"], c["intermediate_size"]) == (768, 6144)
    assert c["held_experts"] == [0, 8] and c["vocab_size"] == 19648


def test_parameters_and_required_flops_by_hand():
    from harness import ling3_flops, ling3_weights

    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "ling-3.0-flash-vl.json")))
    assert ling3_weights.kinds(cfg) == (
        "kda", "dense") + ("kda", "moe") * 4 + ("mla", "moe", "kda", "moe")
    count = 0
    for shape, _, _ in ling3_weights.leaf_specs(cfg).values():
        n = 1
        for d in shape:
            n *= d
        count += n
    # ISSUE 41's 822.0 M parameters, and the six selection biases (buffers)
    assert abs((count - 6 * 512) / 1e6 - 822.0) < 0.1
    parts = ling3_flops.per_token(cfg, 8192, 0.125)
    kda = 4 * 2560 * 4096 + 2 * 2560 * 32 + 4096 * 2560 + 3 * 4 * 4096
    assert abs(kda / 1e6 - 52.65) < 0.01            # ISSUE 41's KDA mixer
    assert parts["kda"] == 6.0 * 6 * kda
    chunk = 2 * 64 * 64 * 128 + 64 * 64 * 128 + 6 * 64 * 128 * 128
    assert parts["scan"] == 3.0 * 6 * 32 * chunk / 64
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 2560 * 32 + 4096 * 2560
    assert abs(mla / 1e6 - 31.97) < 0.01            # ISSUE 41's MLA mixer
    assert parts["mla"] == 6.0 * mla \
        + 3.0 * 2 * 320 * 32 * (8192 * 8193 // 2) / 8192
    assert parts["dense"] == 6.0 * 3 * 2560 * 6144
    assert parts["mixture"] == 6.0 * 6 * (2560 * 512 + 3 * 2560 * 768)
    assert parts["experts"] == 6.0 * 6 * 0.125 * 3 * 2560 * 768
    assert parts["head"] == 6.0 * 2560 * 19648
    step = {k: v * 16384 / 1e12 for k, v in parts.items()}
    total = sum(step.values())
    # ISSUE 41 reckoned 55.6 TFLOP; this file's scan and pairs come to 53.7
    assert 53 < total < 56
    assert 0.55 < (step["kda"] + step["scan"]) / total < 0.62
    assert step["experts"] / total < 0.02
    assert 0.08 < step["head"] / total < 0.10


def test_kernel_costs_by_hand():
    from harness import load

    cell = load.cell(CELL)
    fwd = load.module("kernels", "kda_fwd")
    bwd = load.module("kernels", "kda_bwd")
    assert fwd.chunk_ops(4, 2, 3) == 2 * 16 * 2 + 16 * 3 + 6 * 4 * 2 * 3
    ops, nbytes = fwd.cost(2, 8, 5, 2, 3, 4)
    assert ops == 2 * 2 * 5 * fwd.chunk_ops(4, 2, 3)
    assert nbytes == 2 * 8 * 5 * ((4 + 6) * 2 + 8 + 4)
    assert bwd.cost(2, 8, 5, 2, 3, 4) == (2 * ops, nbytes)
    assert fwd.layers(cell) == 6
    assert fwd.shapes(cell) == (2, 8192, 32, 128, 128, 64)
    mla = load.module("kernels", "mla_attention")
    assert mla.layers(cell) == 1
    pairs = 2 * (8192 * 8193 // 2)
    ops, nbytes = mla.from_cell(cell)
    assert ops == 32 * pairs * (2 * 320 + 2 * (3 * 192 + 2 * 128))
    assert nbytes == 2 * 8192 * 32 * 2 * (640 + 1280)
    # ISSUE 41: the MLA layer's attention 4.8 TFLOP a step at equal widths'
    # count of 14 d; at 192 / 128 this file counts 2,304 a pair
    assert 4.7 < ops / 1e12 < 5.0
    # the shared readers' count files read this cell unedited
    ce = load.module("kernels", "fused_ce_fwd")
    assert ce.from_cell(cell)[0] == 2 * 16384 * 2560 * 19648


def test_the_new_scopes_on_paths_as_the_profiler_writes_them():
    from harness import scope_tree
    from paddle_tpu.profiler import DEVICE_SCOPES

    node_of = scope_tree.finder(DEVICE_SCOPES)
    assert node_of("jit(step_fn)/forward/kda/scan/pallas_call:") == "kda/scan"
    assert node_of("jit(step_fn)/backward/transpose(jvp(kda/project))/"
                   "dot_general") == "kda/project"
    assert node_of("jit(step_fn)/backward/checkpoint/kda/gate_norm/mul") == \
        "kda/gate_norm"
    assert node_of("jit(step_fn)/forward/jvp(mla_attention)/pallas_call") \
        == "mla_attention"
    assert node_of("jit(step_fn)/forward/jvp(mla/project)/dot_general") == \
        "mla/project"
    assert node_of("jit(step_fn)/forward/mlp/dot_general") == "mlp"
    assert node_of("jit(step_fn)/forward/my_kda/scanner") is None
