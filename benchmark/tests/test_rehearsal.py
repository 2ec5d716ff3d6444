"""Rehearsals on the CPU (kernels interpreted, tiny sizes): every kind of
cell end to end through run.py's own code, the result line against the
contract's keys, the control coming out as not correct, the timed path
broken underneath coming out as not correct, a run without a chip
failing with no result line, and a throw-away cell added as new files
only. Each case is a child process: a run owns its JAX state.

Run by hand: `pytest benchmark/tests` (about five minutes). Nothing here
is a chip result.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, "tiny")

PRELUDE = textwrap.dedent(f"""
    import os, sys
    sys.path.insert(0, {BENCH!r}); sys.path.insert(0, {ROOT!r})
    sys.path.insert(0, {HERE!r})
    import rehearse
""")


def child(code, devices=1, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, "-c", PRELUDE + textwrap.dedent(
        code)], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=timeout)


def result_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        line) <= {"correct", "attempted", "failed", "metrics", "device",
                  "breakdown"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    return line


def rehearse(workload, seed, seconds=2, devices=1, patch=""):
    call = (f'sys.exit(rehearse.main(["--workload", {workload!r}, "--seed", '
            f'"{seed}", "--seconds", "{seconds}", "--trace", "0"]))')
    return child(textwrap.dedent(patch) + "\n" + call, devices=devices)


@pytest.mark.parametrize("workload,devices,metric", [
    ("tiny.train.scan", 1, "train_tok_s_chip"),
    ("tiny.train.tape", 1, "train_tok_s_chip"),
    ("tiny.serve", 1, "ttft_p75_ms"),
])
def test_cell_end_to_end(workload, devices, metric):
    line = result_line(rehearse(workload, 5_000_000_003, devices=devices))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) >= {metric, "setup_s"}
    assert line["device"]["platform"] == "cpu"     # never a chip result
    assert line["device"]["count"] == devices


def test_no_chip_fails_without_a_result_line():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "gpt3-350m.train.8x1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_unlisted_device_kind_is_an_error():
    sys.path.insert(0, BENCH)
    from harness import device

    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_control_comes_out_not_correct():
    """The reference in the program's place, computed in fp8: at least one
    of the cell's numbers passes its limit (the tiny cell's own limits)."""
    proc = child("""
        from harness import load
        load.SEARCH.insert(0, os.path.join(%r))
        load.MANIFEST[0] = os.path.join(%r, "BENCHMARK.tiny.json")
        cell = load.cell("tiny.train.scan")
        train = load.module("runners", "train_job")
        want = train.reference_numbers(cell, 11)
        low = train.reference_numbers(cell, 11, precision="fp8")
        v, gaps = train.compare(cell, low, want, tag="control ")
        same, _ = train.compare(cell, want, want, tag="reference ")
        print("VERDICT", v.correct, same.correct)
    """ % (TINY, TINY))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == "VERDICT False True"


def test_broken_train_step_comes_out_not_correct():
    """The timed path broken underneath: the step trains on the first
    half of the batch twice (a part of the batch left out)."""
    line = result_line(rehearse("tiny.train.scan", 77, patch="""
import harness.load as L
_module = L.module
def _broken(real_build, cell, model):
    opt, step = real_build(cell, model)
    call = type(step).__call__
    def half(self, ids, labels, *a):
        import jax.numpy as jnp
        n = ids.shape[0] // 2
        dup = lambda t: type(t)._wrap(jnp.concatenate(
            [t._data[:n], t._data[:n]]))
        return call(self, dup(ids), dup(labels), *a)
    type(step).__call__ = half
    return opt, step
def patched(kind, name):
    mod = _module(kind, name)
    if (kind, name) == ("runners", "train_job"):
        real = mod.build_step
        mod.build_step = lambda cell, model: _broken(real, cell, model)
    return mod
L.module = patched
"""))
    assert line["correct"] is False


def test_altered_served_token_comes_out_not_correct():
    """A token altered where it is produced: every served token is the
    engine's plus one."""
    line = result_line(rehearse("tiny.serve", 78, patch="""
from paddle_tpu.serving.request import RequestHandle
_push = RequestHandle._push_token
RequestHandle._push_token = lambda self, tok, now: _push(
    self, (int(tok) + 1) % 512, now)
"""))
    assert line["correct"] is False


def test_a_cell_is_added_with_new_files_only(tmp_path):
    """A later PR's cell: a traffic file, a workload file, a reader and
    one entry each in the manifest — nothing that exists is edited."""
    for sub in ("traffic", "workloads", "layer_metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "traffic" / "pretrain-throwaway.json").write_text(json.dumps(
        dict(kind="train_job", batch=2, seq=128, zipf_alpha=1.0,
             zipf_shift=2.7, mean_document_tokens=32, why="throw-away")))
    base = json.load(open(os.path.join(
        TINY, "workloads", "tiny.train.scan.json")))
    (tmp_path / "workloads" / "tiny.throwaway.json").write_text(
        json.dumps(base))
    (tmp_path / "layer_metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n    return ctx['steps']\n")
    bench = json.load(open(os.path.join(TINY, "BENCHMARK.tiny.json")))
    bench["workloads"].append(dict(
        name="tiny.throwaway", config="tiny", traffic="pretrain-throwaway",
        chips=1, why="throw-away"))
    for m in bench["end_to_end"]:
        if m["name"] == "train_tok_s_chip":
            m["workloads"].append("tiny.throwaway")
    bench["per_layer"].append(dict(
        name="steps_in_window", unit="count", better="higher",
        source="program_counter", layer="train step",
        moves="train_tok_s_chip", workloads=["tiny.throwaway"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = child(f"""
        from harness import load
        import run
        load.SEARCH.insert(0, {TINY!r}); load.SEARCH.insert(0, {str(tmp_path)!r})
        load.MANIFEST[0] = {str(tmp_path / "BENCHMARK.json")!r}
        from paddle_tpu.utils import flags
        flags.set_flags({{"FLAGS_pallas_force_interpret": True,
                         "FLAGS_pallas_flash_min_seqlen": 128}})
        cell = load.cell("tiny.throwaway")
        assert [m["name"] for m in cell["per_layer"]] == ["steps_in_window"]
        print(run.layer_metrics(cell, {{"steps": 7}}))
        sys.exit(run.main(["--workload", "tiny.throwaway", "--seed", "3",
                           "--seconds", "1", "--trace", "0"], allow_cpu=True))
    """)
    line = result_line(proc)
    assert line["correct"] is True
    assert "steps_in_window" in proc.stdout
