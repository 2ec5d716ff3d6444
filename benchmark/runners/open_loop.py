"""Runner for `open_loop` traffic: one ServingEngine under a seeded arrival
schedule at a fixed rate, latency from when each request was DUE.

One thread drives everything: submit what is due, `engine.step()`, repeat;
sleep only when the engine is idle. The schedule runs for `ramp_s` before
the window opens (set-up the traffic needs: the window starts with the
queue and the slots as they are mid-stream). After the window every page
and slot is accounted for, the engine is freed, and the plain reference
runs over a seeded sample of the requests the window finished.
"""
from __future__ import annotations

import gc
import re
import time

import numpy as np

from harness import check, clock, device, stats, traffic, weights
from reference import gpt as ref

TRACE_SECONDS = 5.0       # the profiler's slice of a traced run
CHECK_REQUESTS = 6        # finished requests the reference follows
TAIL = re.compile(r"^(ttft|itl)_p(\d+)_ms$")


def build_engine(cell, seed):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine

    c, e = cell["config"], cell["engine"]
    if e["max_len"] > c["max_position_embeddings"]:
        raise SystemExit("benchmark: the engine's max_len is beyond the "
                         "configuration's positions")
    t = clock.now()
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_layers"],
        num_attention_heads=c["num_attention_heads"],
        intermediate_size=c["intermediate_size"],
        max_position_embeddings=c["max_position_embeddings"],
        layer_norm_epsilon=c["layer_norm_epsilon"],
        tie_word_embeddings=True, scan_layers=False))
    model.bfloat16()
    model.eval()
    t_build = clock.now() - t
    weights.load_into(model, c, seed)
    eng = ServingEngine(
        model, max_slots=e["max_slots"], max_len=e["max_len"],
        page_size=e["page_size"], chunk_size=e["chunk_size"],
        cache_dtype=jnp.bfloat16, kv_quant=e.get("kv_quant"),
        clock=clock.now)
    t = clock.now()
    eng.warmup()
    return model, eng, {"build": t_build, "warmup": clock.now() - t}


class Drive:
    """One pass of a schedule through the engine; keeps what the metrics
    and the readers need."""

    def __init__(self, eng, schedule, ramp_s, seconds, trace_dir=None):
        self.eng, self.schedule = eng, schedule
        self.ramp_s, self.seconds = float(ramp_s), float(seconds)
        self.trace_dir = trace_dir
        self.requests = []     # dicts: due, handle, submit, admit, tokens
        self.steps = []        # dicts per engine.step()

    def _note_step(self, t1, t2, before, decode_before):
        rec = {"t1": t1, "t2": t2, "running": len(self.eng.scheduler.running),
               "waiting": len(self.eng.scheduler.waiting),
               "pages": self.eng.cache.pool_stats()["used_pages"],
               "decode": [], "chunks": []}
        for r in self._live:
            h = r["handle"]
            pos0, out0, state0 = before[id(r)]
            if r["admit"] is None and (h.slot is not None or h.done):
                r["admit"] = t1
            if h.prefill_pos > pos0:
                rec["chunks"].append((pos0, h.prefill_pos - pos0))
            if id(r) in decode_before and len(h.output_tokens) > out0:
                rec["decode"].append(r["prompt_len"] + out0)
        rec["ran_decode"] = bool(rec["decode"])
        self.steps.append(rec)

    def run(self):
        from jax.profiler import TraceAnnotation

        import jax
        from paddle_tpu.serving.request import RequestState

        eng, sched = self.eng, self.eng.scheduler
        t_begin = clock.now()
        self.t0 = t_begin + self.ramp_s
        self.t_end = self.t0 + self.seconds
        # a traced run goes on for TRACE_SECONDS after the window, the
        # schedule still arriving, and profiles only that slice
        t_stop = self.t_end + (TRACE_SECONDS if self.trace_dir else 0.0)
        nxt, late = 0, []
        tracing = False
        self._live = []
        self.trace_window = None
        while True:
            now = clock.now()
            if now >= t_stop:
                break
            if self.trace_dir and not tracing and now >= self.t_end:
                jax.profiler.start_trace(self.trace_dir)
                tracing, t_trace = True, now
            while nxt < len(self.schedule) \
                    and t_begin + self.schedule[nxt][0] <= now:
                due, ids, new = self.schedule[nxt]
                with TraceAnnotation("bench.submit"):
                    h = eng.submit(ids, new)
                t_sub = clock.now()
                late.append(t_sub - (t_begin + due))
                r = {"due": t_begin + due, "handle": h, "submit": t_sub,
                     "admit": None, "prompt_len": len(ids), "new": new,
                     "ids": ids}
                self.requests.append(r)
                self._live.append(r)
                nxt += 1
            before = {id(r): (r["handle"].prefill_pos,
                              len(r["handle"].output_tokens),
                              r["handle"].state) for r in self._live}
            decode_before = {id(r) for r in self._live
                             if r["handle"].state is RequestState.RUNNING}
            t1 = clock.now()
            with TraceAnnotation("bench.engine_step"):
                worked = eng.step()
            t2 = clock.now()
            if t1 < self.t_end:
                self.waiting_end = len(sched.waiting)
            if worked:
                self._note_step(t1, t2, before, decode_before)
                self._live = [r for r in self._live
                              if not r["handle"].done]
            else:
                wait = (t_begin + self.schedule[nxt][0] - clock.now()
                        if nxt < len(self.schedule) else 0.002)
                with TraceAnnotation("bench.idle_wait"):
                    time.sleep(min(max(wait, 0.0), 0.002))
        if tracing:
            self.trace_window = (t_trace, clock.now())
            jax.profiler.stop_trace()
        self.late = late
        return self

    # -- the window's numbers ----------------------------------------------
    def in_window(self, t):
        return self.t0 <= t < self.t_end

    def metrics(self):
        due = [r for r in self.requests if self.in_window(r["due"])]
        ttft, qwait, gaps, tokens = [], [], [], 0
        for r in self.requests:
            times = r["handle"]._token_times
            tokens += sum(1 for t in times if self.in_window(t))
            gaps += [b - a for a, b in zip(times, times[1:])
                     if self.in_window(b)]
        for r in due:
            times = r["handle"]._token_times
            first = times[0] if times and times[0] < self.t_end \
                else self.t_end
            ttft.append(first - r["due"])
            admit = r["admit"] if r["admit"] is not None else self.t_end
            qwait.append(max(admit - r["due"], 0.0))
        steps = [s for s in self.steps if self.in_window(s["t1"])]
        decode_ms = [(s["t2"] - s["t1"]) * 1e3 for s in steps
                     if s["ran_decode"]]
        offered = sum(r["new"] for r in due)
        return {
            "due": due, "ttft": ttft, "gaps": gaps, "tokens": tokens,
            "qwait": qwait, "steps": steps, "decode_ms": decode_ms,
            "offered_tokens": offered,
            "occupancy": (float(np.mean([s["running"] for s in steps]))
                          / self.eng.max_slots if steps else 0.0),
            "pages_peak": max((s["pages"] for s in steps), default=0),
            "pages_mean": (float(np.mean([s["pages"] for s in steps]))
                           if steps else 0.0),
        }


def end_to_end(cell, m, seconds):
    """The cell's end-to-end metrics by the names BENCHMARK.json gives
    them: `serve_tok_s`, and `ttft_p<q>_ms` / `itl_p<q>_ms` for whatever
    percentile q the cell's sample supports."""
    out = {}
    for metric in cell["end_to_end"]:
        name, tail = metric["name"], TAIL.match(metric["name"])
        if name == "serve_tok_s":
            out[name] = m["tokens"] / seconds
        elif tail:
            sample = m["ttft"] if tail.group(1) == "ttft" else m["gaps"]
            out[name] = stats.percentile(sample, int(tail.group(2))) * 1e3
        elif name != "setup_s":
            raise SystemExit(f"benchmark: the open_loop runner has no "
                             f"end-to-end metric {name!r}")
    return out


def pool_account(eng):
    """Pages and slots the window left: free + held by residents ==
    total, with nothing drained (a drain would cost every run the
    longest request's lifetime)."""
    pool, leak = eng.cache.pool_stats(), eng.leak_check()
    held = sum(pool["slot_pages"].values())
    residents = len(eng.scheduler.running)
    ok = (pool["free_pages"] + held == pool["total_pages"]
          and leak["free_slots"] + residents == leak["total_slots"]
          and leak["resident_slot_pages"] == residents)
    return {"ok": ok, "free_pages": pool["free_pages"], "held_pages": held,
            "total_pages": pool["total_pages"],
            "free_slots": leak["free_slots"], "residents": residents}


def reference_gaps(cell, seed, sample, precision=None):
    """Widest gap by which a served token's logit lies below the
    reference's best, over `sample` = [(prompt ids, served tokens)].
    With `precision`, the control: the token that precision's forward
    puts first takes the served token's place."""
    import jax
    import jax.numpy as jnp

    c, e = cell["config"], cell["engine"]
    _, outer, layers = weights.reference_params(c, seed)
    worst, rows = 0.0, []
    with jax.default_matmul_precision("highest"):
        for prompt, served in sample:
            n, m = len(prompt), len(served)
            ids = np.zeros((e["max_len"],), np.int32)   # causal: the
            ids[:n + m] = np.concatenate([prompt, served])  # tail is inert
            args = (outer, layers, jnp.asarray(ids),
                    c["num_attention_heads"], c["layer_norm_epsilon"])
            logits = ref.forward_logits(*args)[n - 1:n + m - 1]
            tok = jnp.asarray(served, jnp.int32)
            if precision is not None:
                low = ref.forward_logits(*args, precision=precision)
                tok = jnp.argmax(low[n - 1:n + m - 1], -1)
            gap = jnp.max(logits, -1) - jnp.take_along_axis(
                logits, tok[:, None], -1)[:, 0]
            rows.append((n, m, float(jnp.max(gap)),
                         int(jnp.sum(gap > 0))))
            worst = max(worst, rows[-1][2])
    return worst, rows


def pick_sample(drive, seed):
    """The longest finished request and CHECK_REQUESTS - 1 more, drawn
    from the seed among those that finished."""
    done = [r for r in drive.requests
            if r["handle"].done and r["handle"].output_tokens]
    if not done:
        return []
    done.sort(key=lambda r: -(r["prompt_len"] + len(
        r["handle"].output_tokens)))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    rest = list(rng.permutation(len(done) - 1)[:CHECK_REQUESTS - 1] + 1)
    return [(done[i]["ids"], list(done[i]["handle"].output_tokens))
            for i in [0] + rest]


def run(cell, args, t_start, ctx):
    import jax

    c, job, e = cell["config"], cell["traffic"], cell["engine"]
    model, eng, marks = build_engine(cell, args.seed)
    horizon = job["ramp_s"] + args.seconds + (
        TRACE_SECONDS if args.trace else 0.0)
    sched = traffic.schedule(job, c["vocab_size"], args.seed, horizon)
    counts0 = eng.compile_counts()
    drive = Drive(eng, sched, job["ramp_s"], args.seconds,
                  ctx["trace_dir"] if args.trace else None).run()
    counts1 = eng.compile_counts()
    m = drive.metrics()
    peak = device.memory_peak_bytes(jax.devices()[:1])
    e2e = dict(end_to_end(cell, m, args.seconds), setup_s=drive.t0 - t_start)
    pool = eng.cache.pool_stats()
    m["pool_fill"] = m["pages_peak"] / pool["total_pages"]
    ctx.update(e2e=e2e, window_s=args.seconds, peak_bytes=peak,
               serve=m, drive=drive, trace_window=drive.trace_window,
               counters={"window_compiles": sum(
                   counts1[k] - counts0[k] for k in (
                       "decode_executables", "prefill_executables"))})
    print(f"serve: {len(m['due'])} requests due, {len(m['ttft'])} TTFT "
          f"samples, {len(m['gaps'])} token gaps, {len(m['steps'])} engine "
          f"steps in the window; {m['tokens']} tokens delivered of "
          f"{m['offered_tokens']} offered by requests due; waiting at the "
          f"end {drive.waiting_end}; generator late by max "
          f"{max(drive.late) * 1e3:.1f} ms, mean "
          f"{np.mean(drive.late) * 1e3:.1f} ms; KV pool: peak "
          f"{m['pages_peak']} and mean {m['pages_mean']:.0f} of "
          f"{pool['total_pages']} pages held ({100 * m['pool_fill']:.1f} % "
          f"at the peak = {m['pages_peak'] * pool['page_bytes']} live bytes "
          f"of {pool['pool_bytes']} reserved); set-up split (s): build "
          f"{marks['build']:.1f}, warm-up {marks['warmup']:.1f}, ramp "
          f"{job['ramp_s']}", flush=True)

    # what the window left behind: pages and slots all accounted for (a
    # leaked page is neither free nor held by a resident), then free the
    # engine; the reference follows requests the window finished
    leak = pool_account(eng)
    sample = pick_sample(drive, args.seed)
    due = m["due"]
    short = [r for r in due if r["handle"].done
             and len(r["handle"].output_tokens) != r["new"]]
    in_vocab = all(0 <= tok < c["vocab_size"] for r in drive.requests
                   for tok in r["handle"].output_tokens)
    del eng, model, drive.eng
    gc.collect()

    v = check.Verdict()
    t = clock.now()
    worst, rows = reference_gaps(cell, args.seed, sample)
    print(f"reference: {len(sample)} requests, "
          f"{sum(r[1] for r in rows)} served tokens in "
          f"{clock.now() - t:.1f} s; (prompt, tokens, widest gap, tokens "
          f"off the reference's best) {rows}", flush=True)
    v.at_most("served_logit_gap", worst, cell["limits"]["served_logit_gap"],
              "widest gap of a served token below the reference's best")
    v.require("sample holds served tokens", bool(sample))
    v.require("every request due in the window finished whole", not short,
              f"{len(short)} short")
    v.require("tokens in vocabulary range", in_vocab)
    v.require("every page and slot free or held by a resident",
              leak["ok"], str(leak))
    v.require("no compilation inside the window",
              ctx["counters"]["window_compiles"] == 0,
              f"{counts0} -> {counts1}")
    return {"correct": v.correct, "attempted": len(due),
            "failed": len(short), "devices": jax.devices()[:1]}
