"""The Keye-VL-2.0 cell rehearsed on the CPU at a tiny size: run.py, the
`sparse_moe_train_job` runner, the reference and the control, through the
tiny manifest `tiny/BENCHMARK.keye-tiny.json`; the counts of
harness/keye_flops.py and the two kernels' cost files by hand; the scope
reader on paths as the profiler writes them. Run by hand with the other
benchmark tests (`JAX_PLATFORMS=cpu pytest benchmark/tests`); nothing
here is a chip result."""
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
    import rehearse_keye
""")


def child(code, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", PRELUDE + textwrap.dedent(
        code)], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=timeout)


def test_the_tiny_cell_runs_end_to_end_and_is_correct():
    proc = child('sys.exit(rehearse_keye.main(["--workload", '
                 '"keye-tiny.train", "--seed", "4000000007", "--seconds", '
                 '"0.5", "--trace", "0"]))')
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert "check ok  routed_pairs_gap" in proc.stdout
    assert "check ok  expert_pick_miss" in proc.stdout


def test_the_control_and_a_broken_mixture_fail_a_limit():
    proc = child("""
        from harness import load
        load.SEARCH.insert(0, os.path.join(%r, "tiny"))
        load.MANIFEST[0] = os.path.join(%r, "tiny", "BENCHMARK.keye-tiny.json")
        cell = load.cell("keye-tiny.train")
        runner = load.module("runners", "sparse_moe_train_job")
        low = runner.reference_numbers(cell, 11, precision="fp8",
                                       export_picks=True)
        held = runner.reference_numbers(cell, 11, given=low["picks"])
        v, gaps = runner.compare(cell, low, held, tag="control ")
        print("CONTROL", v.correct)
        # a program that forgets to renormalise the picked weights: the
        # reference computed so, in the program's place
        import copy
        broken = copy.deepcopy(cell)
        broken["config"]["norm_topk_prob"] = False
        bad = runner.reference_numbers(broken, 11, export_picks=True)
        held = runner.reference_numbers(cell, 11, given=bad["picks"])
        v, gaps = runner.compare(cell, bad, held, tag="not renormalised ")
        print("DROPPED", v.correct)
    """ % (HERE, HERE))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "CONTROL False" in proc.stdout
    assert "DROPPED False" in proc.stdout


def test_required_flops_by_hand():
    from harness import keye_flops

    cfg = json.load(open(os.path.join(
        BENCH, "configs", "keye-vl2-30b-a3b.json")))
    assert keye_flops.selected_pairs(8192, 2048) == \
        2048 * 2049 // 2 + 6144 * 2048
    parts = keye_flops.per_token(cfg, 8192, 1.0)
    proj = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * (1024 + 64 + 16) \
        + 2048 * 128
    assert parts["projections"] == 6.0 * 6 * proj
    assert parts["experts"] == 6.0 * 6 * 3 * 2048 * 768
    assert parts["indexer"] == 3.0 * 6 * (2 * 16 * 64 + 32) * 8193 / 2
    assert parts["attention"] == 3.0 * 6 * 4 * 128 * 32 * (
        keye_flops.selected_pairs(8192, 2048) / 8192)
    assert parts["head"] == 6.0 * 2048 * 18992
    # ISSUE 26's arithmetic, per token and layer: ~128 / 28 / 25 MFLOP
    assert abs(parts["projections"] / 6 / 1e6 - 128) < 2
    assert abs(parts["experts"] / 6 / 1e6 - 28.3) < 0.1
    assert abs(parts["indexer"] / 6 / 1e6 - 25.6) < 0.1


def test_kernel_costs_by_hand():
    from harness import load

    att = load.module("kernels", "sparse_attention")
    ops, nbytes = att.cost(1, 8, 2, 1, 4, pairs=10)
    assert ops == 14 * 4 * 2 * 10
    assert nbytes == (2 * 64 + 2 * 32) * 2 + 64 + (4 * 64 + 4 * 32) * 2 \
        + 2 * 64 + 2 * 64
    moe = load.module("kernels", "moe_experts")
    ops, nbytes = moe.cost(rows=5, hidden=4, inner=3, held=2)
    assert ops == 9 * 2 * 4 * 3 * 5
    assert nbytes == 2 * 72 * 2 + 72 * 4 + 4 * 5 * 4 * 2


def test_attn_probs_costs_by_hand():
    from harness import load

    for name, extra in (("attn_probs_stats", 0), ("attn_probs_mean", 8 * 16 * 4)):
        ops, nbytes = load.module("kernels", name).cost(8, 16, 4, 2, 32)
        assert ops == 2 * 32 * 4 * 8 * 17 // 2
        assert nbytes == (4 * 8 * 32 + 2 * 16 * 32) * 2 + 8 * 16 \
            + 4 * 8 * 4 + extra


def test_scope_of_reads_plain_and_wrapped_scopes():
    from harness import scopes

    s = scopes.scope_of
    assert s("jit(step_fn)/forward/indexer/while/body/dot_general:") == \
        "indexer"
    assert s("jit(step_fn)/backward/transpose(jvp(checkpoint))/"
             "moe/experts/while/body/moe/route/gather") == "moe/route"
    assert s("jit(step_fn)/backward/transpose(jvp(sparse_attention))/"
             "pallas_call") == "sparse_attention"
    assert s("a/transpose(jvp(moe/experts))/dot_general") == "moe/experts"
    assert s("jit(step_fn)/forward/jvp()/convert_element_type:") is None
    assert s("jit(step_fn)/forward/reindexer/x") is None
