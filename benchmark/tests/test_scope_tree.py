"""harness/scope_tree.py (PR 37) on a hand-made `XLA Ops` line: nesting,
an operation that names nothing inheriting from the event it is nested
in, names wrapped by a transformation, the innermost path winning, and
leaves + bare remainders + `unnamed` + the optimizer's remainder = the
line's self time; a fusion whose path is absent or its loop's read by the
instructions fused into it, from a hand-made HloProto in a hand-made
`/host:metadata` plane. On the same events harness/scopes.py and
harness/attention_scopes.py read what they read before the leaves
existed. The ten readers on a recorded GPT trace (a program that names
no node) and untraced return None, and the manifest lists each for the
two mixture cells. Run by hand: `pytest benchmark/tests`."""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

from harness import (attention_scopes, load, scope_tree, scopes,  # noqa: E402
                     trace_reduce, xplane)
from paddle_tpu.profiler import DEVICE_SCOPES  # noqa: E402

T350 = os.path.join(BENCH, "data", "train-350m-4steps.xplane.pb")
KEYE, MELLUM2 = "keye-vl2-30b-a3b.train.4x8192", \
    "mellum2-12b-a2.5b.train.4x8192"
NEW = {
    "moe_router_ms.train": ("moe/route/router", (KEYE, MELLUM2)),
    "moe_plan_ms.train": ("moe/route/plan", (KEYE, MELLUM2)),
    "moe_gather_ms.train": ("moe/route/gather", (KEYE, MELLUM2)),
    "moe_add_back_ms.train": ("moe/route/add_back", (KEYE, MELLUM2)),
    "indexer_select_ms.train": ("indexer/select", (KEYE,)),
    "indexer_loss_ms.train": ("indexer/loss", (KEYE,)),
    "indexer_loop_ms.train": ("indexer", (KEYE,)),
    "attn_proj_ms.train": ("attention/projections", (KEYE, MELLUM2)),
    "head_ms.train": ("head", (KEYE, MELLUM2)),
    "unnamed_ms.train": (scope_tree.UNNAMED, (KEYE, MELLUM2)),
}
F, B = "jit(step_fn)/forward/", "jit(step_fn)/backward/"

# (operation, start ns, end ns, tf_op); nested events lie inside their
# parent's interval. Self times by hand, in the comment of each line.
LINE = [
    ("%embed = f32[8]", 0, 10, F + "jvp(embed)/gather:"),              # 10
    ("%conv.1 = bf16[8]", 10, 40,
     F + "jvp(attention/projections)/dot_general:"),                   # 30
    ("%fusion.2 = f32[8]", 40, 60, F + "jvp(moe/route/router)/exp:"),  # 20
    ("%sort.3 = s32[8]", 60, 100,
     F + "jvp(moe/route/plan)/sort:"),                                 # 40
    # the forward loop: a `while` under the add-back leaf holding a
    # gather, a product, an add-back and a copy with no path at all
    ("%while.4 = (f32[8])", 100, 200,
     F + "moe/route/add_back/while:"),                 # 100 - 90 = 10
    ("%gather.5 = bf16[8]", 100, 120,
     F + "moe/route/add_back/while/body/moe/route/gather/gather:"),    # 20
    ("%conv.6 = f32[8]", 120, 170,
     F + "moe/route/add_back/while/body/moe/experts/dot_general:"),    # 50
    ("%moe_add_rows.7 = f32[8]", 170, 185, F + "moe/route/add_back/"
     "while/body/moe/route/add_back/moe_add_rows/pallas_call:"),       # 15
    ("%copy.8 = f32[8]", 185, 190, ""),        # 5, inherits the while's
    # an operation under the bare inner node, and one that names nothing
    # nested in it
    ("%fusion.9 = f32[8]", 200, 230, F + "moe/route/select_n:"),  # 30 - 8
    ("%bitcast.10 = f32[8]", 210, 218, ""),                             # 8
    # the indexer: the chunk loop bare, its leaves plain and wrapped, the
    # chunk body's paths from their own root
    ("%while.11 = (f32[8])", 230, 330,
     F + "jvp(indexer)/jvp()/while:"),                 # 100 - 95 = 5
    ("%ds.12 = f32[8]", 230, 240,
     F + "jvp(indexer)/jvp()/while/body/dynamic_slice:"),              # 10
    ("%scores.13 = f32[8]", 240, 260, "indexer/scores/dot_general:"),  # 20
    ("%select.14 = s32[8]", 260, 300, F + "jvp(indexer)/jvp()/while/body/"
     "closed_call/indexer/select/cumsum:"),                            # 40
    ("%target.15 = f32[8]", 300, 310, "indexer/target/pallas_call:"),  # 10
    ("%loss.16 = f32[8]", 310, 318, "indexer/loss/exp:"),               # 8
    ("%pull.17 = f32[8]", 318, 325, "indexer/scores/transpose("
     "indexer/scores)/jvp()/mul:"),                                     # 7
    ("%project.18 = bf16[8]", 330, 345,
     F + "jvp(indexer)/indexer/project/dot_general:"),                 # 15
    ("%splash.19 = bf16[8]", 345, 400,
     F + "jvp(sparse_attention)/splash_fwd/pallas_call:"),             # 55
    ("%splash.20 = bf16[8]", 400, 430,
     F + "jvp(window_attention)/splash_fwd/pallas_call:"),             # 30
    ("%ce.21 = f32[8]", 430, 470, F + "jvp(head/fused_ce_fwd)/pallas_call:"),
    # backward: wrapped names, a loop under the add-back
    ("%ce.22 = f32[8]", 470, 540,
     B + "transpose(jvp(head/fused_ce_bwd))/pallas_call:"),            # 70
    ("%while.23 = (f32[8])", 540, 600, B + "transpose(jvp(checkpoint))/"
     "moe/route/add_back/while:"),                     # 60 - 50 = 10
    ("%gather.24 = f32[8]", 540, 560, B + "transpose(jvp(checkpoint))/"
     "moe/route/add_back/while/body/moe/route/gather/gather:"),        # 20
    ("%conv.25 = f32[8]", 560, 590, B + "transpose(jvp(checkpoint))/"
     "moe/route/add_back/while/body/moe/experts/dot_general:"),        # 30
    ("%cast.26 = f32[8]", 600, 606,
     B + "transpose(jvp(moe/cast))/convert_element_type:"),             # 6
    ("%plan.27 = f32[8]", 606, 610,
     B + "transpose(jvp(moe/route/plan))/scatter-add:"),                # 4
    ("%fusion.28 = f32[8]", 610, 622,
     B + "transpose(jvp(full_attention))/splash_bwd/pallas_call:"),    # 12
    # no node: a sum of the loss terms; the optimizer's own; no path
    ("%add.29 = f32[]", 622, 625, B + "transpose(jvp())/add_any:"),     # 3
    ("%adamw.30 = f32[8]", 625, 650, "jit(step_fn)/optimizer/mul:"),   # 25
    ("%sums.31 = f32[8]", 650, 660,
     "jit(step_fn)/optimizer/numerics/reduce_sum:"),                   # 10
    ("%copy.32 = f32[8]", 660, 662, ""),                                # 2
]
OWN = {
    "embed": 10, "attention/projections": 30, "moe/route/router": 20,
    "moe/route/plan": 44, "moe/route/add_back": 10 + 15 + 5 + 10,
    "moe/route/gather": 40, "moe/experts": 80, "moe/route": 30,
    "indexer": 15, "indexer/scores": 27, "indexer/select": 40,
    "indexer/target": 10, "indexer/loss": 8, "indexer/project": 15,
    "sparse_attention": 55, "window_attention": 30, "full_attention": 12,
    "head": 110, "moe/cast": 6, scope_tree.UNNAMED: 5,
    scope_tree.OPTIMIZER: 35,
}


def events():
    out = [xplane.Event(name, start, end, {"tf_op": path})
           for name, start, end, path in LINE]
    return sorted(out, key=lambda e: (e.start, -e.end))


def plane_of(evs):
    """What `xplane.line_events` needs of a plane: the decoded line."""
    return types.SimpleNamespace(_decoded={trace_reduce.OPS_LINE: evs})


def test_the_walk_by_hand():
    own, lent, nameless = scope_tree.walk(events(), DEVICE_SCOPES)
    assert lent == {}
    got = {k: round(v * 1e9) for k, v in own.items()}
    assert got == {**dict.fromkeys(got, 0), **OWN}
    # every nanosecond of the line's self time is somewhere, once
    assert sum(got.values()) == max(e.end for e in events()) == 662
    # the unnamed operations by name, for the printed list
    assert {k: round(v * 1e9) for k, v in nameless.items()} == {
        ("add f32[]", "transpose(jvp())/add_any"): 3,
        ("copy f32[8]", ""): 2}


def test_a_node_with_its_leaves_is_what_the_older_walkers_read():
    evs = events()
    own, _, _ = scope_tree.walk(evs, DEVICE_SCOPES)
    old = scopes.scope_seconds(plane_of(evs))
    for name in scopes.SCOPES:
        assert old[name] == pytest.approx(
            sum(own[n] for n in scope_tree.under(DEVICE_SCOPES, name)))
    att = attention_scopes.scope_seconds(plane_of(evs))
    assert att == {"window_attention": pytest.approx(30e-9),
                   "full_attention": pytest.approx(12e-9)}
    # and without the leaves (the parent's paths: every leaf's name cut
    # back to its node) the older walkers read the same
    def cut(path):
        for leaf in DEVICE_SCOPES:
            for node in scopes.SCOPES:
                if leaf.startswith(node + "/"):
                    path = path.replace(leaf, node)
        return path

    parents = [e._replace(stats={"tf_op": cut(e.stats["tf_op"])})
               for e in evs]
    assert scopes.scope_seconds(plane_of(parents)) == old


# -- a fusion without a path of its own ---------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field number, int | bytes | str) pairs."""
    out = bytearray()
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return bytes(out)


def metadata_plane(program_id, computations):
    """`/host:metadata` as the profiler writes it: one event metadata a
    program, its HloProto the bytes of a stat. `computations`: {name:
    [op_name or None of each instruction]}."""
    module = _msg((1, "jit_step_fn"), *[
        (3, _msg((1, name), *[
            (2, _msg((1, f"i.{k}"), (2, "multiply"),
                     *([(7, _msg((1, "mul"), (2, path)))] if path else [])))
            for k, path in enumerate(paths)]))
        for name, paths in computations.items()])
    event_meta = _msg((1, program_id), (2, f"jit_step_fn({program_id})"),
                      (5, _msg((1, 1), (6, _msg((1, module))))))
    buf = _msg((2, "/host:metadata"),
               (4, _msg((1, program_id), (2, event_meta))),
               (5, _msg((1, 1), (2, _msg((1, 1), (2, "Hlo Proto"))))))
    return xplane.Plane(memoryview(buf), (0, len(buf)))


def test_a_fusion_without_its_own_path_reads_by_what_is_fused_into_it():
    pid = 14685498922084405142              # more than 63 bits, as they are
    fused = scope_tree.fused_paths([metadata_plane(pid, {
        "fused_computation.1": [None, F + "jvp(attention/projections)/mul",
                                F + "jvp(attention/projections)/sub", None],
        "fused_computation.2.clone": [
            None, F + "jvp(indexer)/jvp()/while/body/closed_call/"
            "indexer/select/jit(cumsum)/topk_mask"],
        "fused_computation.3": [F + "jvp(moe/route/gather)/gather",
                                F + "moe/experts/mul", F + "moe/experts/add"],
        "fused_computation.4": [None, "jit(step_fn)/optimizer/mul"],
    })])
    loop = F + "jvp(indexer)/jvp()/while/body/closed_call/while:"

    def event(name, start, end, path, called=None, program=pid):
        text = f"%{name} = f32[8] fusion(f32[8] %p), kind=kLoop, " \
            f"calls=%{called}" if called else f"%{name} = f32[8] copy(%p)"
        return xplane.Event(text, start, end,
                            {"tf_op": path, "program_id": str(program)})

    evs = [
        # no path at all: the rotary's two-output fusion
        event("subtract_convert_fusion.7", 0, 20, "", "fused_computation.1"),
        # the chunk loop, and in its body the radix select's fusion, which
        # the profiler lent the loop's path, beside a copy it lent it too
        event("while.1", 20, 120, F + "jvp(indexer)/jvp()/while:"),
        event("fusion.3570", 20, 90, loop, "fused_computation.2.clone"),
        event("copy.3139", 90, 100, loop),
        # a path of its own holds, whatever is fused into the operation
        event("fusion.5", 120, 130, F + "moe/route/add_back/select_n:",
              "fused_computation.3"),
        # no path, and most of the fused instructions say moe/experts
        event("fusion.6", 130, 140, "", "fused_computation.3"),
        # nothing fused into it names a node; another program; no HLO
        event("fusion.7", 140, 150, "", "fused_computation.4"),
        event("fusion.8", 150, 160, "", "fused_computation.1", program=7),
        event("fusion.9", 160, 170, "", "fused_computation.99"),
    ]
    assert fused(evs[0]) == (F + "jvp(attention/projections)/mul",
                             F + "jvp(attention/projections)/sub")
    assert fused(evs[3]) == fused(evs[7]) == fused(evs[8]) == ()
    own, lent, nameless = scope_tree.walk(evs, DEVICE_SCOPES, fused)
    got = {k: round(v * 1e9) for k, v in own.items() if v}
    assert got == {"attention/projections": 20, "indexer": 20 + 10,
                   "indexer/select": 70, "moe/route/add_back": 10,
                   "moe/experts": 10, scope_tree.UNNAMED: 30}
    assert {k: round(v * 1e9) for k, v in lent.items()} == {
        "attention/projections": 20, "indexer/select": 70, "moe/experts": 10}
    # without the program's HLO the first rules alone: the loop's path
    # holds the select's fusion, the pathless ones are unnamed
    plain, none, _ = scope_tree.walk(evs, DEVICE_SCOPES,
                                     scope_tree.fused_paths([]))
    assert none == {}
    assert {k: round(v * 1e9) for k, v in plain.items() if v} == {
        "indexer": 100, "moe/route/add_back": 10,
        scope_tree.UNNAMED: 20 + 10 + 30}
    assert plain == scope_tree.walk(evs, DEVICE_SCOPES)[0]


def test_the_vocabulary_is_the_programs():
    assert scope_tree.vocabulary() == DEVICE_SCOPES
    assert scope_tree.under(DEVICE_SCOPES, "moe/route") == [
        "moe/route", "moe/route/router", "moe/route/plan",
        "moe/route/gather", "moe/route/add_back"]
    assert scope_tree.under(DEVICE_SCOPES, "moe/experts") == ["moe/experts"]
    src = open(os.path.join(BENCH, "harness", "scope_tree.py")).read()
    for name in DEVICE_SCOPES:
        if "/" in name:
            assert f'"{name}"' not in src, name


def test_of_run_prints_the_tree_once_and_serves_every_reader(
        monkeypatch, capsys):
    evs = events()
    plane = plane_of(evs)
    plane.name = "/device:TPU:0"
    monkeypatch.setattr(xplane, "step_programs",
                        lambda p: ([], [object(), object()]))
    ctx = {"trace": {}, "xplane": [plane]}
    calls = []
    walk = scope_tree.walk
    monkeypatch.setattr(scope_tree, "walk",
                        lambda *a: calls.append(1) or walk(*a))
    for name, (node, _) in NEW.items():
        got = load.module("layer_metrics", name).read(ctx)
        assert got == pytest.approx(OWN[node] * 1e-6 / 2), name
    assert calls == [1]
    out = capsys.readouterr().out
    assert out.count("scope tree: device self time") == 1
    assert "moe/route/add_back" in out and "of it bare" in out
    assert "unnamed, largest operations" in out


def test_the_readers_find_nothing_where_nothing_is_named():
    gpt = {"xplane": xplane.planes(T350),
           "trace": trace_reduce.reduce_profile(trace_reduce.load(T350), 1)}
    for name in NEW:
        read = load.module("layer_metrics", name).read
        assert read({"trace": None}) is None
        assert read(gpt) is None
    assert "scope_tree" in gpt          # walked once, and found empty


def test_a_program_without_the_vocabulary_gives_no_tree(monkeypatch):
    monkeypatch.setattr(scope_tree, "vocabulary", lambda: None)
    ctx = {"trace": {}, "xplane": [plane_of(events())]}
    assert scope_tree.of_run(ctx) is None
    assert scope_tree.ms(ctx, "head") is None


def test_the_manifest_lists_each_reader_for_its_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, (node, cells) in NEW.items():
        m = listed[name]
        assert tuple(m["workloads"]) == cells
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms/step", "lower", "device_trace", "train_tok_s_chip")
        assert node == scope_tree.UNNAMED or node in DEVICE_SCOPES
    for cell in (KEYE, MELLUM2):
        names = [m["name"] for m in load.cell(cell)["per_layer"]]
        assert [n for n in NEW if cell in NEW[n][1]] == \
            [n for n in names if n in NEW]
