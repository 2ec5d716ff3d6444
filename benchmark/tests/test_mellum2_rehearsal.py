"""The Mellum 2 cell rehearsed on the CPU at a tiny size: run.py, the
`window_moe_train_job` runner, the reference, the control, a reference
with the wrong window and a broken timed path, through the tiny manifest
`tiny/BENCHMARK.mellum2-tiny.json`; the counts of harness/mellum2_flops.py
and the two scopes' cost files by hand; the scope reader on paths as the
profiler writes them. Run by hand with the other benchmark tests
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
    import rehearse_mellum2
""")
RUN = ('["--workload", "mellum2-tiny.train", "--seed", "4000000007", '
       '"--seconds", "0.5", "--trace", "0"]')


def child(code, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", PRELUDE + textwrap.dedent(
        code)], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=timeout)


def result(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_tiny_cell_runs_end_to_end_and_is_correct():
    proc = child(f"sys.exit(rehearse_mellum2.main({RUN}))")
    line = result(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert "check ok  routed_pairs_gap" in proc.stdout
    assert "check ok  expert_pick_miss" in proc.stdout


def test_a_broken_timed_path_is_not_correct():
    """The program's sliding layers attend over every earlier key (the
    window lost on the timed path): the run that measured it must not
    come out `correct`."""
    proc = child(f"""
        from paddle_tpu.ops.pallas import splash_attention as sp
        whole = sp.splash_attention
        sp.splash_attention = lambda *a, window=None, **kw: whole(*a, **kw)
        sys.exit(rehearse_mellum2.main({RUN}))
    """)
    assert result(proc)["correct"] is False
    assert "check BAD" in proc.stdout


def test_the_control_and_the_wrong_references_fail_a_limit():
    proc = child("""
        from harness import load
        load.SEARCH.insert(0, os.path.join(%r, "tiny"))
        load.MANIFEST[0] = os.path.join(%r, "tiny",
                                        "BENCHMARK.mellum2-tiny.json")
        cell = load.cell("mellum2-tiny.train")
        runner = load.module("runners", "window_moe_train_job")
        sound = runner.reference_numbers(cell, 11, export_picks=True)
        held = runner.reference_numbers(cell, 11, given=sound["picks"])
        print("SOUND", runner.compare(cell, sound, held)[0].correct)
        low = runner.reference_numbers(cell, 11, precision="fp8",
                                       export_picks=True)
        held = runner.reference_numbers(cell, 11, given=low["picks"])
        print("CONTROL", runner.compare(cell, low, held,
                                        tag="control ")[0].correct)
        # the reference of a program with twice the window, and of one
        # that turns the full layers by plain RoPE, held against a sound run
        for name, wrong in (("WINDOW", {"window": 32}),
                            ("ROPE", {"yarn": False})):
            want = runner.reference_numbers(cell, 11, given=sound["picks"],
                                            **wrong)
            print(name, runner.compare(cell, sound, want,
                                       tag=name + " ")[0].correct)
    """ % (HERE, HERE))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "SOUND True" in proc.stdout
    for name in ("CONTROL", "WINDOW", "ROPE"):
        assert name + " False" in proc.stdout, name


def test_required_flops_by_hand():
    from harness import mellum2_flops

    cfg = json.load(open(os.path.join(
        BENCH, "configs", "mellum2-12b-a2.5b.json")))
    band = 1024 * 1025 // 2 + 7168 * 1024
    assert mellum2_flops.band_pairs(8192, 1024) == band == 7_864_832
    assert mellum2_flops.band_pairs(512, 1024) == 512 * 513 // 2
    parts = mellum2_flops.per_token(cfg, 8192, 2.0)
    proj = 2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64
    assert parts["projections"] == 6.0 * 4 * proj
    assert parts["experts"] == 6.0 * 4 * 2.0 * 3 * 2304 * 896
    assert parts["attention"] == 3.0 * 4 * 128 * 32 * (
        3 * band + 8192 * 8193 // 2) / 8192
    assert parts["head"] == 6.0 * 2304 * 24576
    # ISSUE 35's arithmetic: attention 21.23 M parameters a layer, a
    # token's two held picks 12.4 M
    assert abs(proj / 1e6 - 21.38) < 0.01
    assert abs(parts["experts"] / 6 / 4 / 1e6 - 12.39) < 0.01


def test_scope_costs_by_hand():
    from harness import load

    cell = load.cell("mellum2-12b-a2.5b.train.4x8192")
    win = load.module("kernels", "window_attention")
    ops, nbytes = win.cost(1, 8, 2, 1, 4, pairs=10)
    assert ops == 14 * 4 * 2 * 10
    assert nbytes == (2 * 64 + 2 * 32) * 2 + 64 + (4 * 64 + 4 * 32) * 2 \
        + 2 * 64
    assert win.layers(cell) == 3
    assert win.from_cell(cell)[0] == 14 * 128 * 32 * 4 * 7_864_832
    full = load.module("kernels", "full_attention")
    assert full.layers(cell) == 1
    assert full.from_cell(cell)[0] == 14 * 128 * 32 * 4 * (8192 * 8193 // 2)


def test_the_attention_scopes_on_paths_as_the_profiler_writes_them():
    from harness import attention_scopes

    def scope(path):
        found = attention_scopes._FIND.findall(path.rstrip(":"))
        return found[-1] if found else None

    assert scope("jit(step_fn)/forward/window_attention/pallas_call:") == \
        "window_attention"
    assert scope("jit(step_fn)/backward/transpose(jvp(full_attention))/"
                 "pallas_call") == "full_attention"
    assert scope("jit(step_fn)/backward/checkpoint/window_attention/"
                 "splash_fwd") == "window_attention"
    # the mixture's scopes still win where they are the innermost
    assert scope("jit(step_fn)/forward/moe/experts/while/body/moe/route/"
                 "gather") == "moe/route"
    assert scope("jit(step_fn)/forward/my_window_attention_x/y") is None
