"""Request-scoped tracing, tail-latency forensics and the SLO/goodput
layer (ISSUE 13): span lifecycle/nesting, exemplar-ring bounds and
threshold selection, orphan detection after serving churn with
preemptions, chrome-trace merge shape, debug-server endpoints, SLO
burn-rate math against a hand-computed window, JsonlSink rotation,
and flight-recorder signal dumps."""
import json
import os
import signal
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models import GPTConfig, GPTForCausalLM


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=96,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(n, seed=0, lens=(5, 11, 19, 8, 14, 26)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 64, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


# ---------------------------------------------------------------------------
# Tracer / Span unit behavior
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_lifecycle_and_nesting(self):
        t = obs.Tracer(registry=obs.MetricsRegistry())
        root = t.begin("request", track="req1", rid=1)
        assert not root.closed and root.track == "req1"
        child = t.begin("prefill", parent=root, bucket=8)
        grand = t.begin("inner", parent=child)
        assert grand.track == "req1"            # inherited
        assert len(t.open_spans()) == 3
        t.end(grand)
        t.end(child, pages=2)
        assert child.attrs["pages"] == 2
        assert child.duration_s() >= 0
        t.end(root)
        assert root.closed and not t.open_spans()
        d = root.to_dict()
        assert d["name"] == "request" and d["attrs"]["rid"] == 1
        assert d["children"][0]["name"] == "prefill"
        assert d["children"][0]["children"][0]["name"] == "inner"
        assert root.find("inner")[0] is grand
        # double end is a no-op, not a corruption
        t1 = root.t1
        t.end(root)
        assert root.t1 == t1

    def test_ring_bound_newest_wins(self):
        t = obs.Tracer(capacity=4, registry=obs.MetricsRegistry())
        for i in range(10):
            t.end(t.begin("request", track=f"req{i}"))
        tr = t.traces()
        assert len(tr) == 4
        assert [x["track"] for x in tr] == ["req6", "req7", "req8",
                                            "req9"]
        assert t.find_trace("req9") is not None
        assert t.find_trace("req0") is None     # evicted
        assert t.completed_total == 10

    def test_max_children_cap_counts_drops(self):
        t = obs.Tracer(max_children=3, registry=obs.MetricsRegistry())
        root = t.begin("request", track="r")
        spans = [t.begin("c", parent=root) for _ in range(5)]
        for s in spans:
            t.end(s)
        t.end(root)
        assert len(root.children) == 3
        assert root.dropped_children == 2
        assert t.spans_dropped == 2
        assert root.to_dict()["dropped_children"] == 2

    def test_orphan_detection(self):
        t = obs.Tracer(registry=obs.MetricsRegistry())
        root = t.begin("request", track="r")
        leak = t.begin("decode", parent=root)
        assert t.orphans() == []                # root still open
        t.end(root)
        assert t.orphans() == [leak]            # outlived its trace
        t.end(leak)
        assert t.orphans() == []

    def test_disabled_tracer_is_noop(self):
        t = obs.Tracer(enabled=False, registry=obs.MetricsRegistry())
        s = t.begin("request", track="r")
        c = t.begin("child", parent=s)
        t.end(c)
        t.end(s)
        assert t.traces() == [] and not t.open_spans()
        assert t.spans_begun == 0

    def test_exemplar_ring_bounds(self):
        t = obs.Tracer(exemplar_capacity=2,
                       registry=obs.MetricsRegistry())
        roots = []
        for i in range(5):
            r = t.begin("request", track=f"req{i}")
            t.end(r)
            t.add_exemplar(r, "slow", rid=i)
            t.add_exemplar(r, "slow", rid=i)    # idempotent per root
        ex = t.exemplars()
        assert len(ex) == 2
        assert [e["rid"] for e in ex] == [3, 4]
        assert ex[0]["reason"] == "slow" and "trace" in ex[0]

    def test_clear_resets_everything(self):
        t = obs.Tracer(registry=obs.MetricsRegistry())
        r = t.begin("request", track="x")
        t.end(r)
        t.add_exemplar(r, "why")
        t.begin("request", track="y")           # left open
        t.clear()
        st = t.stats()
        assert st == {"open": 0, "completed": 0, "begun": 0,
                      "ended": 0, "dropped": 0, "exemplars": 0,
                      "ring": 0}

    def test_trace_gauges_lazy_on_registry(self):
        reg = obs.MetricsRegistry()
        t = obs.Tracer(registry=reg)
        t.begin("request", track="r")
        assert reg.gauge("trace.open_spans").value == 1
        assert reg.gauge("trace.orphans").value == 0


# ---------------------------------------------------------------------------
# chrome span merge (per-request tracks in the Profiler export)
# ---------------------------------------------------------------------------

class TestChromeMerge:
    def test_span_events_gated_on_profiler_and_merged(self):
        import paddle_tpu.profiler as profiler

        obs.drain_chrome_spans()                # start clean
        t = obs.Tracer(registry=obs.MetricsRegistry())
        # no profiler cycle active: nothing lands in the buffer
        t.end(t.begin("request", track="req_idle"))
        assert obs.drain_chrome_spans() == []

        prof = profiler.Profiler(
            scheduler=(0, 2), on_trace_ready=lambda p: None,
            timer_only=True)
        prof.start()
        root = t.begin("request", track="req42", rid=42)
        sp = t.begin("decode_burst", parent=root, k=4)
        t.end(sp)
        t.end(root)
        prof.step()
        prof.step()
        prof.stop()
        res = prof._last_result
        spans = res.request_spans
        names = [e["name"] for e in spans]
        assert "decode_burst" in names and "request" in names
        meta = [e for e in spans if e["ph"] == "M"]
        assert any(e["args"].get("name") == "req42" for e in meta)
        xs = [e for e in spans if e["ph"] == "X"]
        assert all(e["pid"] == 1 and "dur" in e for e in xs)
        burst = next(e for e in xs if e["name"] == "decode_burst")
        assert burst["args"]["k"] == 4
        # merged into the chrome trace next to counter tracks
        evts = res.chrome_trace()["traceEvents"]
        assert any(e.get("name") == "decode_burst" for e in evts)

        # a SECOND profiler cycle must get the track metadata again —
        # the first drain consumed it (review fix: cycles after the
        # first would otherwise render bare numeric tids)
        prof2 = profiler.Profiler(
            scheduler=(0, 2), on_trace_ready=lambda p: None,
            timer_only=True)
        prof2.start()
        t.end(t.begin("request", track="req42"))
        prof2.step()
        prof2.step()
        prof2.stop()
        spans2 = prof2._last_result.request_spans
        assert any(e["ph"] == "M"
                   and e["args"].get("name") == "req42"
                   for e in spans2), spans2


# ---------------------------------------------------------------------------
# SLO burn-rate math (hand-computed window)
# ---------------------------------------------------------------------------

class TestSLO:
    def test_burn_rate_hand_computed(self):
        clock = [100.0]
        reg = obs.MetricsRegistry()
        tr = obs.SLOTracker(registry=reg, clock=lambda: clock[0])
        tr.declare("ttft", "ttft_s", threshold=0.1, target=0.9,
                   window_s=60.0)
        # 20 samples, 5 violations -> good 15/20 = 0.75
        for i in range(20):
            tr.observe_metric("ttft_s", 0.2 if i % 4 == 0 else 0.05)
        st = tr.status("ttft")
        assert st["samples"] == 20 and st["bad"] == 5
        assert st["good_fraction"] == 0.75
        # burn = bad_frac / budget = 0.25 / 0.1 = 2.5
        assert st["burn_rate"] == 2.5
        assert st["breaching"] is True
        # gauges scrape the same numbers
        assert reg.gauge("slo.ttft.burn_rate").value == 2.5
        assert reg.gauge("slo.ttft.breaching").value is True
        # window rolls: 61s later the old samples age out
        clock[0] += 61.0
        tr.observe("ttft", 0.05)
        st = tr.status("ttft")
        assert st["samples"] == 1 and st["bad"] == 0
        assert st["burn_rate"] == 0.0 and st["breaching"] is False
        # lifetime totals survive the roll
        assert st["total_observed"] == 21 and st["total_bad"] == 5

    def test_empty_window_not_breaching(self):
        tr = obs.SLOTracker(registry=obs.MetricsRegistry())
        tr.declare("itl", "itl_s", threshold=0.05, target=0.99)
        st = tr.status("itl")
        assert st["burn_rate"] == 0.0 and st["breaching"] is False
        assert st["good_fraction"] == 1.0

    def test_declare_validation_and_redeclare(self):
        tr = obs.SLOTracker(registry=obs.MetricsRegistry())
        with pytest.raises(ValueError):
            tr.declare("x", "m", 1.0, target=1.0)
        with pytest.raises(ValueError):
            tr.declare("x", "m", 1.0, window_s=0)
        tr.declare("x", "m", 1.0)
        tr.observe_metric("m", 2.0)
        tr.declare("x", "m2", 1.0)              # replaces: new metric
        tr.observe_metric("m", 5.0)             # no longer routed
        assert tr.status("x")["samples"] == 0
        assert tr.names() == ["x"]


# ---------------------------------------------------------------------------
# serving integration: churn with preemptions -> complete, orphan-free
# traces + exemplar threshold selection
# ---------------------------------------------------------------------------

class TestServingTraces:
    def _churn(self, model, **kw):
        from paddle_tpu.serving import ServingEngine

        # 7 usable pages over 3 slots: the pool dries mid-churn, so
        # preemption/resume paths are exercised (asserted below)
        eng = ServingEngine(model, max_slots=3, max_len=48, page_size=8,
                            chunk_size=8, num_pages=8, do_sample=True,
                            **kw)
        handles = []
        for i, p in enumerate(_prompts(6)):
            handles.append(eng.submit(p, 8, seed=100 + i))
            eng.step()
        eng.run(max_steps=5000)
        return eng, handles

    def test_trace_completeness_under_preemption_churn(self, model):
        eng, handles = self._churn(model)
        assert eng.metrics.preemptions >= 1
        for h in handles:
            root = eng.request_trace(h.request.rid)
            assert root is not None and root.closed
            assert root.attrs["tokens"] == len(h.output_tokens)
            assert len(root.find("prefill_chunk")) >= 1
            assert len(root.find("decode_burst")) >= 1
            assert len(root.find("stream_deliver")) >= 1
            admits = root.find("admit")
            assert len(admits) == 1 + h.preemptions
            if h.preemptions:
                pre = root.find("preempt")
                assert len(pre) == h.preemptions
                assert all(p.attrs["reason"] in
                           ("pool_dry", "self_sacrifice")
                           for p in pre)
                assert all(p.attrs["pages_reclaimed"] >= 1
                           for p in pre)
                assert any(c.attrs.get("resume")
                           for c in root.find("prefill_chunk"))
            # queue_wait per admission, all closed
            qs = root.find("queue_wait")
            assert len(qs) == 1 + h.preemptions
            assert all(q.closed for q in qs)
        # zero orphan / open spans after drain + abort_all
        eng.scheduler.abort_all()
        assert eng.tracer.open_spans() == []
        assert eng.tracer.orphans() == []

    def test_prefill_chunk_annotations(self, model):
        eng, handles = self._churn(model)
        root = eng.request_trace(handles[2].request.rid)  # 19-tok prompt
        chunks = [c for c in root.find("prefill_chunk")
                  if not c.attrs.get("resume")]
        assert {c.attrs["bucket"] for c in chunks} <= {8}
        starts = sorted(c.attrs["start"] for c in chunks)
        assert starts[0] == 0 and len(starts) >= 3   # 19 tokens / 8
        for c in chunks:
            assert c.attrs["batch"] >= 1
            assert c.attrs["pages_held"] >= 1
            assert c.attrs["slot"] is not None

    def test_exemplar_threshold_selection(self, model):
        # low quantile + tiny min_samples: the slowest requests land in
        # the exemplar ring; quantile 99 with min_samples huge: nothing
        eng, _ = self._churn(model, exemplar_quantile=50.0,
                             exemplar_min_samples=4)
        slow = eng.slow_requests()
        assert slow
        for e in slow:
            assert e["reason"]
            assert e["trace"]["name"] == "request"
        eng2, _ = self._churn(model, exemplar_min_samples=10_000)
        assert eng2.slow_requests() == []

    def test_mid_flight_abort_then_drain_is_clean(self, model):
        from paddle_tpu.serving import ServingEngine

        eng = ServingEngine(model, max_slots=2, max_len=48, page_size=8,
                            chunk_size=8, num_pages=9)
        handles = [eng.submit(p, 6) for p in _prompts(3)]
        for _ in range(3):
            eng.step()
        aborted = eng.scheduler.abort_all()
        assert aborted
        assert eng.tracer.orphans() == []
        eng.run(max_steps=5000)
        assert all(h.done for h in handles)
        assert eng.tracer.open_spans() == []
        aborted_traces = [
            eng.request_trace(h.request.rid) for h in handles
            if any(s.attrs.get("reason") == "abort"
                   for s in (eng.request_trace(h.request.rid)
                             or obs.Span("", 0, None, None, 0, {})
                             ).find("preempt"))]
        assert aborted_traces, "abort left no preempt(abort) span"

    def test_failed_step_leaks_no_spans(self, model):
        # a raising compiled call (the _recover scenario) must not
        # leave its prefill/decode/stream spans open forever
        from paddle_tpu.serving import ServingEngine

        eng = ServingEngine(model, max_slots=2, max_len=48, page_size=8,
                            chunk_size=8)
        handles = [eng.submit(p, 6) for p in _prompts(3)]
        for _ in range(3):
            eng.step()              # some resident, decode-active
        real_decode = eng.decode_step

        def boom(*a):
            raise RuntimeError("injected step failure")

        eng.decode_step = boom
        with pytest.raises(RuntimeError, match="injected"):
            eng.step()
        # recovery requeued everyone; the only open spans are live
        # roots + their queue waits — zero orphans, zero leaked
        # decode/stream/prefill spans
        assert eng.tracer.orphans() == []
        open_names = {s.name for s in eng.tracer.open_spans()}
        assert open_names <= {"request", "queue_wait"}, open_names
        eng.decode_step = real_decode
        eng.run(max_steps=5000)
        assert all(h.done for h in handles)
        assert eng.tracer.open_spans() == []

    def test_trace_disabled_engine_still_serves(self, model):
        eng, handles = self._churn(model, trace=False)
        assert all(h.done for h in handles)
        assert eng.tracer.traces() == []
        assert eng.slow_requests() == []
        assert eng.request_trace(handles[0].request.rid) is None

    def test_warmup_clears_traces(self, model):
        from paddle_tpu.serving import ServingEngine

        eng = ServingEngine(model, max_slots=2, max_len=48, page_size=8,
                            chunk_size=8).warmup()
        assert eng.tracer.traces() == []
        assert eng.tracer.open_spans() == []

    def test_engine_slo_wiring(self, model):
        eng, handles = self._churn(
            model, slos=[("ttft", "ttft_s", 1e-9, 0.9),
                         ("itl", "itl_s", 1e9, 0.99)])
        st = eng.slo_status()
        # every finished request violated the absurd 1ns TTFT target
        assert st["ttft"]["samples"] == len(handles)
        assert st["ttft"]["breaching"] is True
        assert st["ttft"]["burn_rate"] > 1
        # and nobody violates a 1e9s ITL bound
        assert st["itl"]["bad"] == 0 and st["itl"]["breaching"] is False
        with pytest.raises(ValueError):
            eng.declare_slo("x", "not_a_metric", 1.0)


# ---------------------------------------------------------------------------
# debug server endpoints
# ---------------------------------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.headers.get("Content-Type"), r.read()


class TestDebugServer:
    def test_endpoints_against_static_registry(self):
        reg = obs.MetricsRegistry()
        reg.counter("c.total").inc(3)
        reg.gauge("g.depth").set(2)
        tracer = obs.Tracer(registry=reg)
        r = tracer.begin("request", track="req7", rid=7)
        tracer.end(r)
        tracer.add_exemplar(r, "slow")
        with obs.DebugServer(registry=reg, tracer=tracer) as srv:
            port = srv.port
            code, ctype, body = _get(port, "/metrics")
            assert code == 200 and ctype.startswith("text/plain")
            # the acceptance identity: /metrics IS registry.expose()
            assert body.decode() == reg.expose()
            code, _, body = _get(port, "/healthz")
            hz = json.loads(body)
            assert code == 200 and hz["status"] == "ok"
            assert hz["pid"] == os.getpid()
            code, _, body = _get(port, "/tracez")
            tz = json.loads(body)
            assert code == 200
            assert tz["traces"][-1]["track"] == "req7"
            assert len(tz["exemplars"]) == 1
            assert tz["open_spans"] == 0 and tz["orphans"] == 0
            code, _, body = _get(port, "/flightz")
            assert code == 200 and "events" in json.loads(body)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(port, "/nope")
            assert ei.value.code == 404
            assert "endpoints" in json.loads(ei.value.read())
        assert srv.port is None                  # stopped

    def test_engine_debug_server_and_sloz(self, model):
        from paddle_tpu.serving import ServingEngine

        eng = ServingEngine(model, max_slots=2, max_len=48, page_size=8,
                            chunk_size=8,
                            slos=[("ttft", "ttft_s", 0.25)])
        port = eng.start_debug_server()
        try:
            h = eng.submit(_prompts(1)[0], 4)
            eng.run()
            assert h.done
            code, _, body = _get(port, "/sloz")
            assert code == 200
            assert json.loads(body)["ttft"]["samples"] == 1
            code, _, body = _get(port, "/tracez?n=1")
            assert len(json.loads(body)["traces"]) == 1
            # /metrics matches the engine scrape minus the one
            # time-varying gauge (tok_s recomputes per call)
            _, _, body = _get(port, "/metrics")

            def strip(t):
                return [ln for ln in t.splitlines()
                        if "tok_s" not in ln]

            assert strip(body.decode()) == strip(eng.metrics_text())
        finally:
            eng.stop_debug_server()
        assert eng._debug_server is None

    def test_broken_provider_returns_500(self):
        def boom():
            raise RuntimeError("provider down")

        with obs.DebugServer(registry=obs.MetricsRegistry(),
                             extra={"boom": boom}) as srv:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.port, "/boom")
            assert ei.value.code == 500


# ---------------------------------------------------------------------------
# JsonlSink rotation
# ---------------------------------------------------------------------------

class TestJsonlRotation:
    def test_rotation_and_ordered_read(self, tmp_path):
        path = str(tmp_path / "tl.jsonl")
        sink = obs.JsonlSink(path, max_bytes=200, backups=3)
        tl = obs.StepTimeline(sinks=[sink], lane="rot")
        want = [tl.record(step=i, host_ms=float(i)) for i in range(30)]
        tl.close()
        assert os.path.exists(path + ".1")
        assert os.path.getsize(path) <= 200
        got = obs.read_jsonl(path)
        # bounded: the oldest segment(s) may be dropped, but what
        # remains is a contiguous in-order suffix of the stream
        assert 0 < len(got) <= 30
        assert got == want[-len(got):]
        # rotated segments ignored on request
        head_only = obs.read_jsonl(path, follow_rotated=False)
        assert head_only == want[-len(head_only):]
        assert len(head_only) < len(got)

    def test_no_cap_no_rotation(self, tmp_path):
        path = str(tmp_path / "tl.jsonl")
        tl = obs.StepTimeline(sinks=[obs.JsonlSink(path)], lane="rot2")
        want = [tl.record(step=i, x=1.0) for i in range(10)]
        tl.close()
        assert not os.path.exists(path + ".1")
        assert obs.read_jsonl(path) == want

    def test_stale_and_stray_segments_handled(self, tmp_path):
        path = str(tmp_path / "tl.jsonl")
        # stray/stale siblings from "an earlier run with a larger cap"
        with open(path + ".7", "w") as f:
            f.write('{"stale": 1}\n')
        with open(path + ".9", "w") as f:
            f.write("not json at all\n")
        sink = obs.JsonlSink(path, max_bytes=100, backups=2)
        sink({"live": 1})
        sink.close()
        # init pruned everything beyond the backups cap
        assert not os.path.exists(path + ".7")
        assert not os.path.exists(path + ".9")
        assert obs.read_jsonl(path) == [{"live": 1}]
        # a stray non-JSONL sibling inside the cap is skipped, not a
        # parse error; the main file still raises on corruption
        with open(path + ".1", "w") as f:
            f.write("garbage\n")
        assert obs.read_jsonl(path) == [{"live": 1}]
        with open(path, "a") as f:
            f.write("corrupt main\n")
        with pytest.raises(json.JSONDecodeError):
            obs.read_jsonl(path)

    def test_append_resumes_size_accounting(self, tmp_path):
        path = str(tmp_path / "tl.jsonl")
        s1 = obs.JsonlSink(path, max_bytes=50)
        s1({"a": 1})
        s1.close()
        s2 = obs.JsonlSink(path, max_bytes=50)
        for i in range(10):
            s2({"b": i})
        s2.close()
        assert os.path.exists(path + ".1")     # cap honored across


# ---------------------------------------------------------------------------
# flight recorder signal dump
# ---------------------------------------------------------------------------

class TestSignalDump:
    def test_sigusr2_dumps_without_dying(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path))
        chained = []
        signal.signal(signal.SIGUSR2, lambda s, f: chained.append(s))
        try:
            got = obs.install_signal_dump(signal.SIGUSR2)
            assert got == signal.SIGUSR2
            obs.recorder().note("pre_dump_marker", k=1)
            os.kill(os.getpid(), signal.SIGUSR2)
            deadline = time.time() + 10
            while obs.recorder().last_dump_path is None \
                    and time.time() < deadline:
                time.sleep(0.02)
            path = obs.recorder().last_dump_path
            assert path and os.path.exists(path)
            rec = json.loads(open(path).read())
            assert "signal" in rec["reason"]
            assert rec["threads"], "no thread stacks in dump"
            assert any("MainThread" in k for k in rec["threads"])
            assert any(e["kind"] == "pre_dump_marker"
                       for e in rec["events"])
            # chained to the pre-existing handler, process alive
            assert chained == [signal.SIGUSR2]
            # idempotent
            assert obs.install_signal_dump(signal.SIGUSR2) \
                == signal.SIGUSR2
        finally:
            from paddle_tpu.observability import flight_recorder as fr

            signal.signal(signal.SIGUSR2, signal.SIG_DFL)
            fr._signal_prev.pop(signal.SIGUSR2, None)

    def test_thread_stacks_surface(self):
        stacks = obs.thread_stacks()
        assert any("MainThread" in k for k in stacks)
        assert any("test_thread_stacks_surface" in v
                   for v in stacks.values())
