"""The LFM2 cell rehearsed on the CPU at a tiny size: run.py, the
`conv_moe_train_job` runner, the reference, the control, the references of
three wrong programs and a broken timed path, through the tiny manifest
`tiny/BENCHMARK.lfm2-tiny.json`; every new reader returning a number or
None; the counts of harness/lfm2_flops.py and kernels/gated_conv.py by hand.
Run by hand with the other benchmark tests (`JAX_PLATFORMS=cpu pytest
benchmark/tests`); nothing here is a chip result."""
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
    import rehearse_lfm2
""")
RUN = ('["--workload", "lfm2-tiny.train", "--seed", "4000000007", '
       '"--seconds", "0.5", "--trace", "0"]')
CELL = "lfm2-24b-a2b.train.4x8192"
NEW = ["mfu_pct.conv_moe", "conv_mixer_ms.train", "conv_proj_ms.train",
       "conv_gate_ms.train", "conv_out_ms.train", "gated_conv_roofline"]


def child(code, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", PRELUDE + textwrap.dedent(
        code)], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=timeout)


def result(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_tiny_cell_runs_end_to_end_and_is_correct():
    proc = child(f"sys.exit(rehearse_lfm2.main({RUN}))")
    line = result(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert "check ok  routed_pairs_gap" in proc.stdout
    assert "check ok  expert_pick_miss" in proc.stdout


def test_a_broken_timed_path_is_not_correct():
    """The program's operator convolves X alone on the timed path (no input
    gate): the run that measured it must not come out `correct`."""
    proc = child(f"""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import gated_conv as G
        whole = G.gated_conv

        def ungated(bcx, w, **kw):
            h = w.shape[1]
            return whole(jnp.concatenate(
                [jnp.ones_like(bcx[..., :h]), bcx[..., h:]], -1), w, **kw)

        G.gated_conv = ungated
        sys.exit(rehearse_lfm2.main({RUN}))
    """)
    assert result(proc)["correct"] is False
    assert "check BAD" in proc.stdout


def test_the_control_and_the_wrong_references_fail_a_limit():
    proc = child("""
        from harness import load
        load.SEARCH.insert(0, os.path.join(%r, "tiny"))
        load.MANIFEST[0] = os.path.join(%r, "tiny",
                                        "BENCHMARK.lfm2-tiny.json")
        cell = load.cell("lfm2-tiny.train")
        runner = load.module("runners", "conv_moe_train_job")
        sound = runner.reference_numbers(cell, 11, export_picks=True)
        held = runner.reference_numbers(cell, 11, given=sound["picks"])
        print("SOUND", runner.compare(cell, sound, held)[0].correct)
        low = runner.reference_numbers(cell, 11, precision="fp8",
                                       export_picks=True)
        held = runner.reference_numbers(cell, 11, given=low["picks"])
        print("CONTROL", runner.compare(cell, low, held,
                                        tag="control ")[0].correct)
        # the references of the three wrong programs, held against a sound run
        for name in ("no_input_gate", "late_tap", "untied_head"):
            want = runner.reference_numbers(cell, 11, given=sound["picks"],
                                            **{name: True})
            print(name.upper(), runner.compare(cell, sound, want,
                                               tag=name + " ")[0].correct)
    """ % (HERE, HERE))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "SOUND True" in proc.stdout
    for name in ("CONTROL", "NO_INPUT_GATE", "LATE_TAP", "UNTIED_HEAD"):
        assert name + " False" in proc.stdout, name


def test_every_new_reader_returns_a_number_or_none():
    """Untraced (a CPU trace has no device plane) the trace readers give
    None and do not raise; the counter reader gives a number."""
    from harness import load

    bench = load.manifest()
    cell = load.cell(CELL)
    names = [m["name"] for m in bench["per_layer"]
             if m["name"] in NEW and CELL in m["workloads"]]
    assert sorted(names) == sorted(NEW)
    ctx = {"cell": cell, "device": {"kind": "TPU v5 lite", "platform": "tpu"},
           "counters": {"routing": {"routed_pairs": 8 * 16384,
                                    "computed_rows": 8 * 18432,
                                    "max_load_over_mean": 1.2}},
           "tokens_per_step": 32768,
           "e2e": {"train_tok_s_chip": 36000.0}}
    for name in names:
        value = load.module("layer_metrics", name).read(ctx)
        if name == "mfu_pct.conv_moe":
            assert 25 < value < 45, value
        else:
            assert value is None, name
    # a parent's counters (no routing): nothing, and no raise
    ctx["counters"] = {}
    assert load.module("layer_metrics", "mfu_pct.conv_moe").read(ctx) is None


def test_the_manifest_names_the_cell_in_every_shared_list():
    from harness import load

    bench = load.manifest()
    cell = load.cell(CELL)
    got = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= got and len(got) == 32
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tok_s_chip",
                                                      "setup_s"}
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == cell["config"]["reduced"]
    assert entry["source"] == cell["config"]["source"]
    # every number of the published row the file does not list as reduced
    c = cell["config"]
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (2048, 32, 8, 64)
    assert (c["intermediate_size"], c["moe_intermediate_size"],
            c["published"]["num_experts"], c["num_experts_per_tok"]) == (
        11776, 1536, 64, 4)
    assert (c["conv_L_cache"], c["norm_eps"], c["conv_bias"],
            c["rope_parameters"]["rope_theta"]) == (3, 1e-5, False, 1000000)
    assert len(c["layer_types"]) == 40      # the published list, whole
    assert c["held_experts"] == [0, 8] and c["vocab_size"] == 8192


def test_parameters_and_required_flops_by_hand():
    from harness import lfm2_flops, lfm2_weights

    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "lfm2-24b-a2b.json")))
    assert lfm2_weights.kinds(cfg) == ("conv", "dense") + (
        ("attn", "moe") + ("conv", "moe") * 3) * 2
    count = 0
    for shape, _, _ in lfm2_weights.leaf_specs(cfg).values():
        n = 1
        for d in shape:
            n *= d
        count += n
    # ISSUE 45's 832.6 M parameters, and the eight selection biases (buffers)
    assert abs((count - 8 * 64) / 1e6 - 832.6) < 0.1
    parts = lfm2_flops.per_token(cfg, 8192, 0.5)
    conv = 3 * 2048 * 2048 + 2048 * 2048 + 3 * 2048 + 2048
    assert abs(conv / 1e6 - 16.78) < 0.01           # ISSUE 45's conv mixer
    assert parts["conv"] == 6.0 * 7 * conv
    attn = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048
    assert abs(attn / 1e6 - 10.49) < 0.01           # ISSUE 45's attention
    assert parts["attention"] == 6.0 * 2 * attn \
        + 3.0 * 2 * 128 * 32 * 2 * (8192 * 8193 // 2) / 8192
    assert parts["dense"] == 6.0 * 3 * 2048 * 11776
    assert parts["mixture"] == 6.0 * 8 * 2048 * 64
    assert parts["experts"] == 6.0 * 8 * 0.5 * 3 * 2048 * 1536
    assert parts["head"] == 6.0 * 2048 * 8192
    step = {k: v * 32768 / 1e12 for k, v in parts.items()}
    total = sum(step.values())
    assert 58 < total < 60                          # ISSUE 45: 59 TFLOP
    assert 0.38 < step["conv"] / total < 0.40
    assert 0.23 < step["dense"] / total < 0.25
    assert 0.17 < step["attention"] / total < 0.19
    assert 0.12 < step["experts"] / total < 0.14
    assert 0.05 < step["head"] / total < 0.06


def test_kernel_costs_by_hand():
    from harness import load

    cell = load.cell(CELL)
    conv = load.module("kernels", "gated_conv")
    cost = conv.cost(10, 4, 3)
    assert cost["fwd"] == {"ops": 40 * 7, "bytes": 4 * 40 * 2 + 48}
    assert cost["bwd"] == {"ops": 40 * 21, "bytes": 7 * 40 * 2 + 96}
    assert conv.layers(cell) == 7
    assert conv.shapes(cell) == (32768, 2048, 3)
    ops, nbytes = conv.from_cell(cell)
    # ISSUE 45: forward reads 3 bf16 tables and writes 1, 537 MB
    assert abs(nbytes / 1e6 - 537) < 1 and ops == 32768 * 2048 * 7
    assert conv.from_cell(cell, backward=True)[1] == 7 * 32768 * 2048 * 2 \
        + 8 * 3 * 2048
    # the shared readers' count files read this cell unedited
    ce = load.module("kernels", "fused_ce_fwd")
    assert ce.from_cell(cell)[0] == 2 * 32768 * 2048 * 8192
    full = load.module("kernels", "full_attention")
    assert full.layers(cell) == 2
    assert full.from_cell(cell)[0] == 14 * 64 * 32 * 4 * (8192 * 8193 // 2)


def test_the_new_scopes_on_paths_as_the_profiler_writes_them():
    from harness import scope_tree
    from paddle_tpu.profiler import DEVICE_SCOPES

    node_of = scope_tree.finder(DEVICE_SCOPES)
    assert node_of("jit(step_fn)/forward/conv/gate_conv/pallas_call:") == \
        "conv/gate_conv"
    assert node_of("jit(step_fn)/backward/transpose(jvp(conv/project))/"
                   "dot_general") == "conv/project"
    assert node_of("jit(step_fn)/backward/checkpoint/conv/out/add") == \
        "conv/out"
    assert node_of("jit(step_fn)/forward/kda/conv/mul") == "kda/conv"
    assert node_of("jit(step_fn)/forward/ssm/conv/mul") == "ssm/conv"
    assert node_of("jit(step_fn)/forward/my_conv/outer") is None
