"""The train path's own spans and phases (ISSUE 24): `RecordEvent` spans
reach any `jax.profiler` trace and an in-memory ring, the step call is
split at its layer boundaries, full garbage collections are spans, and
the compiled steps name forward / backward / optimizer."""
import gc
import glob
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu import profiler
from paddle_tpu.jit import FusedScanTrainStep, TrainStep
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
)
from paddle_tpu.profiler import Profiler, RecordEvent, spans

CHILDREN = ("extract_state", "lr", "sentinel", "dispatch", "inject_state")
KINDS = ("tape", "scan")


def _build(kind):
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_attention_heads=2,
        intermediate_size=128, max_position_embeddings=32,
        tie_word_embeddings=True, scan_layers=kind == "scan"))
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    if kind == "tape":
        return TrainStep(model, lambda m, a, b: m.loss(a, b), opt)
    return FusedScanTrainStep(model, opt,
                              criterion=GPTPretrainingCriterion(),
                              fused_head=True)


def _ids():
    rng = np.random.default_rng(0)
    return paddle.to_tensor(rng.integers(0, 128, (2, 32)).astype("int64"))


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """Both steps, warmed, then three calls of each inside ONE
    `jax.profiler` trace with no paddle Profiler anywhere."""
    ids = _ids()
    steps = {k: _build(k) for k in KINDS}
    for step in steps.values():
        step(ids, ids)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    t_lo = time.perf_counter()
    jax.profiler.start_trace(trace_dir)
    try:
        for step in steps.values():
            for _ in range(3):
                float(step(ids, ids))
    finally:
        jax.profiler.stop_trace()
    t_hi = time.perf_counter()
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = []          # (line, name, start, end, stats) of our spans
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("paddle_tpu."):
                    events.append((i, e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return {"steps": steps, "ids": ids, "events": events,
            "window": (t_lo, t_hi)}


def _family(parents, others):
    """{parent index: names of the spans its interval encloses}."""
    out = {}
    for i, (line, _, s, e, _) in enumerate(parents):
        out[i] = sorted(n for ln, n, s2, e2, _ in others
                        if ln == line and s <= s2 and e2 <= e)
    return out


def test_trace_holds_the_step_spans_nested(run):
    events = run["events"]
    parents = [ev for ev in events if ev[1] == "paddle_tpu.step"]
    assert len(parents) == 6              # 2 steps x 3 calls
    kids = [ev for ev in events if ev[1].startswith("paddle_tpu.step.")]
    want = sorted(f"paddle_tpu.step.{c}" for c in CHILDREN)
    assert all(names == want for names in _family(parents, kids).values())


def test_trace_spans_carry_the_step_attribute(run):
    events = run["events"]
    seen = sorted(ev[4]["step"] for ev in events
                  if ev[1] == "paddle_tpu.step")
    assert seen == [1, 1, 2, 2, 3, 3]     # call 0 warmed up before the trace
    for _, name, _, _, stats in events:
        if name.startswith("paddle_tpu.step"):
            assert "step" in stats, name


@pytest.mark.parametrize("kind", KINDS)
def test_ring_holds_the_same_spans(run, kind):
    lo, hi = run["window"]
    parents = spans("paddle_tpu.step", lo, hi)
    assert len(parents) == 6
    assert [p.step for p in parents] == [1, 2, 3, 1, 2, 3]
    mine = parents[:3] if kind == KINDS[0] else parents[3:]
    for p in mine:
        inside = [s for c in CHILDREN
                  for s in spans(f"paddle_tpu.step.{c}", p.t0, p.t1)
                  if s.thread == p.thread and s.t1 <= p.t1]
        assert sorted(s.name.rsplit(".", 1)[1] for s in inside) \
            == sorted(CHILDREN)
        assert {s.step for s in inside} == {p.step}
        assert sum(s.t1 - s.t0 for s in inside) <= p.t1 - p.t0


def test_ring_is_bounded_per_name():
    for _ in range(profiler._RING + 76):
        with RecordEvent("test.bounded"):
            pass
    kept = spans("test.bounded")
    assert len(kept) == profiler._RING
    assert kept == sorted(kept, key=lambda s: s.t0)


def test_ring_is_cut_by_a_time_window():
    marks = []
    for i in range(5):
        marks.append(time.perf_counter())
        with RecordEvent("test.window", step=i):
            time.sleep(0.002)
    marks.append(time.perf_counter())
    assert [s.step for s in spans("test.window", marks[1], marks[4])] \
        == [1, 2, 3]
    assert [s.step for s in spans("test.window", lo=marks[3])][:2] == [3, 4]
    assert spans("test.window", marks[5]) == []
    everything = spans(lo=marks[0], hi=marks[5])
    assert [s.name for s in everything].count("test.window") == 5


def test_names_that_get_a_ring_are_capped():
    before = len(profiler._rings)
    for i in range(profiler._MAX_NAMES + 8):
        with RecordEvent(f"test.formatted#{i}"):
            pass
    assert len(profiler._rings) == max(before, profiler._MAX_NAMES)
    for name in [n for n in profiler._rings
                 if n.startswith("test.formatted#")]:
        del profiler._rings[name]


def test_full_collection_leaves_one_gc_span():
    t0 = time.perf_counter()
    gc.collect()
    found = spans("paddle_tpu.host.gc", t0, time.perf_counter())
    assert len(found) == 1 and found[0].t1 >= found[0].t0


def test_young_collections_leave_no_gc_span():
    t0 = time.perf_counter()
    gc.collect(0)
    gc.collect(1)
    assert spans("paddle_tpu.host.gc", t0, time.perf_counter()) == []


def _compiled_text(step, ids):
    lr = jnp.asarray(1e-3, jnp.float32)
    state = step._extract_state()
    if isinstance(step, FusedScanTrainStep):
        args = (state, lr, ids._data, ids._data, None)
    else:
        args = (state, lr, [ids._data, ids._data])
    return step._jitted.lower(*args).compile().as_text()


@pytest.mark.parametrize("kind", KINDS)
def test_compiled_step_names_its_phases(run, kind):
    step = run["steps"][kind]
    assert step._jitted._cache_size() == 1     # four calls, one executable
    assert not step.retrace_stats()["unexpected"]
    names = set(re.findall(r'op_name="([^"]*)"',
                           _compiled_text(step, run["ids"])))
    for phase in ("forward", "backward", "optimizer"):
        assert any(f"/{phase}/" in n for n in names), phase
    # a transposed operation runs in the backward pass: the phase that
    # counts is the one outside the brackets
    assert any(n.startswith("jit(step_fn)/backward/transpose(")
               for n in names)
    if kind == "scan":          # AdamW inside the backward scan
        assert any("/backward/while/body/" in n and "/optimizer/" in n
                   for n in names)


def test_prefetcher_spans_are_renamed(run):
    step, ids = run["steps"]["tape"], run["ids"]
    t0 = time.perf_counter()
    feed = step.prefetch([(ids, ids)] * 3)
    assert len(list(feed)) == 3
    feed.close()
    t1 = time.perf_counter()
    waits = spans("paddle_tpu.input.wait", t0, t1)
    h2d = spans("paddle_tpu.input.h2d", t0, t1)
    assert len(waits) >= 3 and len(h2d) == 3
    assert {s.thread for s in waits} == {threading.get_ident()}
    assert threading.get_ident() not in {s.thread for s in h2d}
    assert not spans("DevicePrefetcher.wait") and not spans("TrainStep")


def test_paddle_profiler_still_gets_the_spans(run):
    step, ids = run["steps"]["scan"], run["ids"]
    p = Profiler(on_trace_ready=lambda prof: None)
    p.start()
    step(ids, ids)
    res = p.stop()
    names = [e.name for e in res.events]
    assert names.count("paddle_tpu.step") == 1
    assert names.count("paddle_tpu.step.dispatch") == 1
