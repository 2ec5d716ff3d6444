"""HLO collective-overlap checker (ISSUE 3 CI/tooling satellite).

Extends the `-start(`/`-done(` counting of
paddle_tpu/distributed/comm_bucketer._COLLECTIVE_RE into a structural
checker over the COMPILED (scheduled) HLO: did XLA actually arrange the
program so collectives can run while compute proceeds?

Two modes, chosen by what the backend emits:

- **async** (TPU, GPU): collectives appear as `<kind>-start` /
  `<kind>-done` pairs. A pair "brackets compute" when >= 1 real compute
  instruction (fusion/dot/convolution/reduce/sort) is scheduled between
  the start and its done — the latency-hiding scheduler's visible
  receipt that the collective overlaps compute. We count pairs, and the
  interleave depth (max compute ops bracketed by one pair).

- **sync** (XLA:CPU — the hermetic host-mesh lane): collectives are
  single sync ops; the thunk runtime overlaps them internally but the
  HLO shows no start/done. Here the checker measures (a)
  `scheduled_interleaved`: collectives with >= 1 compute op scheduled
  between them and their first consumer (the module is
  `is_scheduled=true`, so order IS execution order), and (b)
  `overlap_potential`: collectives with >= 1 LATER compute op that is
  NOT transitively data-dependent on the collective's result — exactly
  the instructions an async scheduler may slide into the collective's
  shadow. The multichip lane records both so the CPU record is honest
  about being a proxy; the async numbers land when the same probe runs
  on a real chip.

Every collective also contributes its RESULT-shape payload bytes to a
per-kind and per-axis byte census (``bytes`` / ``total_comm_bytes`` /
``per_axis_bytes`` in the verdict) — the comm-bytes-per-step numbers
ISSUE 12 pipes into the metrics registry.

Per-axis classification covers every COLLECTIVE_KINDS entry — including
``all-to-all`` (both the single-operand and the tuple form XLA emits for
multi-array exchanges), so the MoE expert-parallel dispatch/combine get
the same per-axis HLO receipt the mp/pp paths have (ISSUE 9): a dp×ep
train step shows its all-to-alls under the ``ep`` label and its grad
scatter under ``dp+ep``.

Standalone:
    python tools/hlo_overlap.py <hlo_text_file> [--assert-overlap]
    python tools/hlo_overlap.py --probe [--assert-overlap]
`--probe` builds the sharded fused-scan train step on the host mesh
(requires JAX_PLATFORMS=cpu + xla_force_host_platform_device_count) and
analyzes its compiled HLO. The per-axis receipts of the other layouts
are tests: tests/test_moe.py (ep all-to-alls), tests/test_sharded_storage.py
(the param gather under both storage formats), tests/test_hybrid_parallel.py.
"""
from __future__ import annotations

import json
import re
import sys

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")
# "real compute" for bracketing purposes: ops that burn cycles, not
# layout/bookkeeping (bitcast, tuple, get-tuple-element, copy, ...)
COMPUTE_OPS = ("fusion", "dot", "convolution", "reduce",
               "reduce-window", "sort", "select-and-scatter", "scatter")

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*[^=]*?\s"
    r"(?P<op>[\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*->")
_REF_RE = re.compile(r"%([\w.\-]+)")
_SHAPE_RE = re.compile(
    r"(pred|bf16|f16|f32|f64|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|"
    r"c64|c128)\[([0-9,]*)\]")
_ITEMSIZE = {"pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
             "f32": 4, "s32": 4, "u32": 4,
             "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16}


def _result_bytes(line, op):
    """Payload bytes of an instruction's RESULT shape (the text between
    '=' and the op token; operand shapes inside the parens are excluded
    by construction). Sync collectives sum the tuple elements (the
    tuple form of all-to-all/all-reduce carries many REAL output
    arrays); async ``-start`` ops instead take the LARGEST element —
    their tuple is (aliased operand, output[, context scalars]), so a
    sum would double-count the payload."""
    rhs = line.split("=", 1)[1]
    cut = rhs.find(op + "(")
    if cut < 0:
        return 0
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(rhs[:cut]):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _ITEMSIZE[dtype])
    if not sizes:
        return 0
    if op.endswith("-start"):
        return int(max(sizes))
    return int(sum(sizes))
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[\d,{} ]*\})\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _parse_groups(line):
    """Replica groups of a collective instruction line, as a frozenset
    of frozensets of device ids — both the literal `{{0,1},{2,3}}` form
    and the iota `[groups,size]<=[dims]T(perm)` form — or None."""
    m = _GROUPS_RE.search(line)
    if m:
        groups = []
        for grp in re.findall(r"\{([\d, ]*)\}", m.group(1)):
            ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
            if ids:
                groups.append(frozenset(ids))
        return frozenset(groups) if groups else None
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        n_groups, size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        total = 1
        for d in dims:
            total *= d
        ids = list(range(total))
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            import itertools as _it

            arr = ids
            # reshape to dims, transpose by perm, flatten — pure python
            def strides(ds):
                s, out = 1, []
                for d in reversed(ds):
                    out.append(s)
                    s *= d
                return list(reversed(out))

            st = strides(dims)
            tdims = [dims[p] for p in perm]
            tst = [st[p] for p in perm]
            arr = []
            for coord in _it.product(*(range(d) for d in tdims)):
                arr.append(sum(c * s for c, s in zip(coord, tst)))
            ids = arr
        return frozenset(
            frozenset(ids[g * size:(g + 1) * size])
            for g in range(n_groups))
    return None


def expected_axis_groups(axis_degrees):
    """{axes_label: frozenset of replica groups} for every non-empty
    subset of mesh axes, devices numbered row-major over the given
    (ordered) axis -> degree mapping — the layout jax meshes lower to.
    Labels join subset axis names with '+' in mesh order."""
    import itertools as _it

    names = list(axis_degrees)
    degrees = [int(axis_degrees[n]) for n in names]
    out = {}
    for r in range(1, len(names) + 1):
        for subset in _it.combinations(range(len(names)), r):
            groups = {}
            for coord in _it.product(*(range(d) for d in degrees)):
                key = tuple(c for i, c in enumerate(coord)
                            if i not in subset)
                rank = 0
                for c, d in zip(coord, degrees):
                    rank = rank * d + c
                groups.setdefault(key, []).append(rank)
            label = "+".join(names[i] for i in subset)
            out[label] = frozenset(frozenset(g)
                                   for g in groups.values())
    return out


def parse_computations(text):
    """-> {computation_name: [(instr_name, op, [operand_names],
    replica_groups, result_bytes)]} in scheduled order (compiled
    modules print is_scheduled=true). result_bytes is only computed for
    collective ops (everything else reads 0) — it feeds the per-axis
    comm-bytes census (ISSUE 12)."""
    comps = {}
    cur = None
    for line in text.splitlines():
        if not line.startswith(" ") and _COMP_RE.match(line) \
                and line.rstrip().endswith("{"):
            cur = _COMP_RE.match(line).group("name")
            comps[cur] = []
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, op = m.group("name"), m.group("op")
        # operands: %refs after the '=' excluding the def itself; strip
        # metadata= / calls= tails conservatively (calls=%comp refs do
        # not collide with instruction names in practice)
        rhs = line.split("=", 1)[1]
        refs = [r for r in _REF_RE.findall(rhs) if r != name]
        nbytes = (_result_bytes(line, op)
                  if _collective_kind(op) is not None else 0)
        comps[cur].append((name, op, refs, _parse_groups(line), nbytes))
    return comps


def _is_compute(op):
    return op in COMPUTE_OPS


def _collective_kind(op):
    for k in COLLECTIVE_KINDS:
        if op == k or op == k + "-start":
            return k
    return None


def analyze(text, axis_degrees=None):
    """Structural overlap verdict over compiled HLO. `axis_degrees`
    (ordered {axis_name: degree}, MESH order) additionally classifies
    every collective's replica groups per mesh axis (or axis product)
    so dp vs mp vs flattened-dp×mp traffic is distinguishable in the
    multichip record (ISSUE 8 satellite)."""
    comps = parse_computations(text)
    async_pairs = []
    sync_colls = []
    counts = {k: 0 for k in COLLECTIVE_KINDS}
    byte_counts = {k: 0 for k in COLLECTIVE_KINDS}
    total_bytes = 0
    axis_expected = (expected_axis_groups(axis_degrees)
                     if axis_degrees else None)
    per_axis = {}
    per_axis_bytes = {}

    def classify(groups):
        if axis_expected is None or groups is None:
            return None
        for label, want in axis_expected.items():
            if groups == want:
                return label
        # single-group collectives over the whole mesh match the full
        # product label above; anything else is an unexpected pattern
        return "other"

    for cname, instrs in comps.items():
        for i, (name, op, refs, groups, nbytes) in enumerate(instrs):
            kind = _collective_kind(op)
            if kind is None:
                continue
            counts[kind] += 1
            byte_counts[kind] += nbytes
            total_bytes += nbytes
            label = classify(groups)
            if label is not None:
                per_axis.setdefault(label, {}).setdefault(kind, 0)
                per_axis[label][kind] += 1
                per_axis_bytes[label] = (per_axis_bytes.get(label, 0)
                                         + nbytes)
            if op.endswith("-start"):
                # find the matching -done consuming this value
                done_i = None
                for j in range(i + 1, len(instrs)):
                    n2, op2, refs2, _, _ = instrs[j]
                    if op2 == kind + "-done" and name in refs2:
                        done_i = j
                        break
                bracketed = 0
                if done_i is not None:
                    bracketed = sum(
                        1 for j in range(i + 1, done_i)
                        if _is_compute(instrs[j][1]))
                async_pairs.append({
                    "kind": kind, "computation": cname, "start": name,
                    "matched": done_i is not None,
                    "bracketed_compute": bracketed})
                continue
            # sync collective: scheduled window to first consumer +
            # overlap potential (later compute independent of the result)
            first_use = None
            dependent = {name}
            independent_after = 0
            window = 0
            for j in range(i + 1, len(instrs)):
                n2, op2, refs2, _, _ = instrs[j]
                if any(r in dependent for r in refs2):
                    dependent.add(n2)
                    if first_use is None:
                        first_use = j
                    continue
                if _is_compute(op2):
                    independent_after += 1
                    if first_use is None:
                        window += 1
            sync_colls.append({
                "kind": kind, "computation": cname, "name": name,
                "scheduled_window_compute": window,
                "independent_compute_after": independent_after})
    n_async_ok = sum(1 for p in async_pairs
                     if p["matched"] and p["bracketed_compute"] >= 1)
    scheduled = sum(1 for s in sync_colls
                    if s["scheduled_window_compute"] >= 1)
    potential = sum(1 for s in sync_colls
                    if s["independent_compute_after"] >= 1)
    depth = max(
        [p["bracketed_compute"] for p in async_pairs if p["matched"]]
        + [s["scheduled_window_compute"] for s in sync_colls]
        + [0])
    pot_depth = max(
        [s["independent_compute_after"] for s in sync_colls] + [0])
    return {
        "mode": "async" if async_pairs else "sync",
        "counts": {k: v for k, v in counts.items() if v},
        "bytes": {k: v for k, v in byte_counts.items() if v},
        "total_comm_bytes": total_bytes,
        **({"per_axis_counts": per_axis,
            "per_axis_bytes": per_axis_bytes} if axis_expected else {}),
        "async_pairs": len(async_pairs),
        "async_pairs_bracketing_compute": n_async_ok,
        "sync_collectives": len(sync_colls),
        "sync_scheduled_interleaved": scheduled,
        "sync_overlap_potential": potential,
        "interleave_depth": depth,
        "overlap_potential_depth": pot_depth,
        "overlap_ok": bool(n_async_ok >= 1 if async_pairs
                           else potential >= 1),
    }


def assert_overlap(verdict):
    """Raise unless the program shows overlap: >= 1 async pair
    bracketing compute (async backends), or >= 1 collective with
    independent later compute for the scheduler to hide it behind
    (sync/CPU proxy)."""
    if not verdict["overlap_ok"]:
        raise AssertionError(
            f"no collective/compute overlap in HLO: {verdict}")
    return verdict


def _build_probe_hlo():
    """Compile the sharded fused-scan step on the ambient host mesh and
    return its optimized HLO text (caller provides the cpu-forced env)."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from paddle_tpu.jit.sharded_scan import build_probe_lowered

    return build_probe_lowered().compile().as_text()


def main(argv):
    do_assert = "--assert-overlap" in argv
    argv = [a for a in argv if a != "--assert-overlap"]
    if "--probe" in argv:
        text = _build_probe_hlo()
    elif argv:
        with open(argv[0]) as f:
            text = f.read()
    else:
        text = sys.stdin.read()
    verdict = analyze(text)
    print(json.dumps(verdict))
    if do_assert:
        assert_overlap(verdict)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
