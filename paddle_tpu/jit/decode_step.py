"""Compiled, retrace-free generation: prefill/decode split over a KV cache.

The serving-side sibling of train_step.py: the eager dygraph decode step
(embedding, N cached-attention blocks, LM head, sampling) is traced ONCE
into a jitted function over a (params, cache-state) pytree and then
executed as one fused XLA program per generated token, with the big KV
buffers DONATED so steady-state decoding is allocation-free. Everything
that varies per step — the token ids, the write position, the RNG key —
is a traced input, so nothing retraces and nothing recompiles after the
first step (the `trace_count` probe asserts exactly that in tests).

Prefill is the separate compile: the prompt is padded to a length
BUCKET (powers-of-two by default) and run through the full causal
forward (the flash/SDPA path) once while every layer's K/V is written
into the cache. jax.jit's shape-keyed executable cache gives one
program per bucket; the true prompt length is a traced scalar/vector,
so any prompt inside a bucket reuses its program.

Cache state is threaded as TWO pytrees: the KV pool buffers (donated —
they are the HBM-dominant part and are consumed functionally every
step) and the small metadata (positions, page tables, seq_lens — NOT
donated, because the host-side continuous-batching bookkeeping reads
and rewrites page tables between steps and a donated buffer would be
dead by then).

Two cache shapes (inference/kv_cache.py): "dense" (aligned batch, one
dynamic_update_slice per layer per step) and "paged" (ragged seq_lens +
page-pool cache in the Ragged-Paged-Attention layout, slot allocate/
free continuous-batching bookkeeping on the host side).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

import time

from ..framework.autograd import no_grad
from ..framework.tensor import Tensor
from ..nn.functional.sampling import (
    sample_logits, sample_logits_per_slot, spec_accept_greedy,
    spec_accept_sampled, spec_draft_keys, truncated_probs,
)
from ..observability import RetraceSentinel
from ..observability import enabled as _obs_enabled
from ..observability import registry as _obs_registry
from .train_step import _tree_data, _tree_wrap

__all__ = ["GenerationEngine", "DecodeStep", "PrefillStep",
           "ChunkPrefillStep", "ServeDecodeStep", "SpecDecodeStep",
           "ServeSpecDecodeStep", "SelfDraftProposer",
           "DEFAULT_PREFILL_BUCKETS"]

DEFAULT_PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048)

# per cache kind, the state keys that are DONATED pool buffers (the
# rest is metadata); presence-filtered, so the int8 scale pools ride
# in the donated set exactly when the cache is quantized
_BUFFER_KEYS = {"dense": ("layers",),
                "paged": ("k_layers", "v_layers",
                          "k_scales", "v_scales")}


class SelfDraftProposer:
    """Draft-checkpoint-free proposer (self-speculative decoding,
    ISSUE 20): the TARGET model's own draft heads
    (``GPTConfig.num_draft_heads``) propose the k tokens from one
    target forward, so speculative decoding needs no second checkpoint
    and no draft KV pools. Engines accept ``draft_model="self"`` as
    sugar for wrapping their target model in this adapter.

    The adapter exists so the spec machinery keeps ONE seam: it quacks
    like a draft model (``.gpt``, ``.config``) but owns no parameters
    (the heads already ride the target's parameter list) and no cache
    (``is_self_draft`` makes the engines skip draft pools and draft
    param threading entirely)."""

    is_self_draft = True

    def __init__(self, model):
        if getattr(model, "draft_heads", None) is None:
            raise ValueError(
                "draft_model='self' needs a target built with "
                "GPTConfig.num_draft_heads > 0")
        self.model = model

    @property
    def gpt(self):
        return self.model.gpt

    @property
    def config(self):
        return self.model.config

    def parameters(self):
        return []


def _split_state(kind, state):
    buf_keys = [k for k in _BUFFER_KEYS[kind] if k in state]
    return ({k: state[k] for k in buf_keys},
            {k: v for k, v in state.items() if k not in buf_keys})


def refresh_serving_buffers(engine):
    """Import-slot safe boundary (ISSUE 18): re-split the cache state
    into the serving engine's threaded buffer dict after an
    out-of-band pool mutation (``PagedKVCache.import_slot`` — KV
    hand-off adoption or host-ring re-onload).

    Must run between engine steps, never inside one: the engine
    threads ``_buffers`` through each compiled call and commits the
    step's outputs back, so a pool rewritten behind its back would be
    silently overwritten by the next commit. ``.at[].set`` returns
    arrays with the donor pools' avals and placement, and the metadata
    stays host numpy, so the refreshed dispatch reuses the resident
    executable — the retrace sentinel stays strict-clean across
    imports by construction.
    """
    buffers, _ = _split_state("paged", _tree_data(engine.cache.state()))
    old = engine._buffers
    if isinstance(old, dict) and "draft" in old:
        buffers["draft"] = old["draft"]
    engine._buffers = buffers


class _Step:
    """Shared machinery: trace counting, jit/eager dispatch, donation."""

    # serving steps set this: the continuous-batching bookkeeping
    # rewrites SOME metadata leaves between calls (a freed slot pulls
    # seq_lens to host, an untouched step leaves it on device), and a
    # call-to-call varying numpy/device mix PER LEAF keys a fresh
    # executable per combination (measured: silent mid-serve
    # recompiles). Pinning every leaf to host numpy = one cache key;
    # the D2H is a few hundred bytes on arrays the serving loop reads
    # synchronously anyway. The GenerationEngine steps keep it off —
    # their meta leaves are already call-to-call consistent, and the
    # pull-down would serialize decode dispatch per token.
    _pin_meta_host = False
    # sentinel config (ISSUE 12): argument names for attribution, and
    # the args whose SHAPE legitimately varies (prefill length buckets
    # — one expected executable per bucket)
    _arg_names = ()
    _bucketed_args = ()

    def __init__(self, engine, donate_cache):
        self.engine = engine
        self._donate = donate_cache and engine.compiled
        self._jitted = None
        self.trace_count = 0   # traces when compiled, calls when eager
        self._sentinel = RetraceSentinel(type(self).__name__,
                                         bucketed=self._bucketed_args)
        # per-call DISPATCH time (enqueue, not device completion —
        # results stay async) on the PROCESS-GLOBAL registry, keyed by
        # step class: a whole-process view (concurrent engines share
        # one histogram, like the global serving.queue_depth mirror) —
        # per-request timing lives on the engine's trace spans. One
        # cached histogram object: ~1µs observe, no registry lookup.
        self._obs_on = _obs_enabled()
        self._dispatch_hist = (_obs_registry().histogram(
            f"jit.{type(self).__name__}.dispatch_ms")
            if self._obs_on else None)

    def _fn(self, *args):
        raise NotImplementedError

    def retrace_stats(self):
        """Sentinel receipt: distinct signatures (= expected compiles),
        cache hits, and attributed unexpected recompiles."""
        return self._sentinel.stats()

    def cache_size(self):
        """Number of compiled executables (jax.jit's cache), -1 when the
        runtime does not expose it."""
        if self._jitted is None:
            return 0
        try:
            return self._jitted._cache_size()
        except Exception:
            return -1

    def lowered_text(self, *args):
        """StableHLO/HLO text of the step for the given example args
        (compile-guard tests grep this for dynamic-update-slice).
        Traces a fresh copy — neither the live jit cache nor the
        trace_count probe is affected."""
        saved = self.trace_count
        try:
            return jax.jit(self._fn).lower(*args).as_text()
        finally:
            self.trace_count = saved

    def compiled_text(self, *args):
        """Optimized HLO text of the step compiled for the given example
        args with the real donation config (what `chip_smoke.py` greps
        for the Mosaic calls). A fresh jit copy, like `lowered_text`."""
        saved = self.trace_count
        try:
            return jax.jit(
                self._fn, donate_argnums=(1,) if self._donate else ()
            ).lower(*args).compile().as_text()
        finally:
            self.trace_count = saved

    def memory_profile(self, *args, top_k=8, publish=True):
        """Compiled-step HBM accounting (ISSUE 14): AOT buffer-
        assignment stats of this step program for the given example
        args — with the REAL donation config, so the KV pools show up
        as alias bytes, not double-counted temps. Traces a fresh jit
        copy (an AOT analysis must not perturb the live executable
        cache or the trace_count probe); publishes
        ``mem.compiled.<step>.*`` gauges."""
        from ..observability.memory import CompiledMemoryProfile

        saved = self.trace_count
        try:
            jitted = jax.jit(
                self._fn, donate_argnums=(1,) if self._donate else ())
            prof = CompiledMemoryProfile.from_jitted(jitted, *args,
                                                     top_k=top_k)
        finally:
            self.trace_count = saved
        if publish:
            prof.publish(name=type(self).__name__)
        return prof

    def _dispatch(self, args):
        """The guarded compiled call: a RESOURCE_EXHAUSTED here dumps
        compiled + live memory forensics through the flight recorder
        before re-raising (observability.memory; ISSUE 14)."""
        try:
            return self._jitted(*args)
        except Exception as e:
            from ..observability import memory as _mem

            if _mem.is_oom_error(e):
                _mem.dump_oom(
                    e, step=type(self).__name__,
                    profile=lambda: self.memory_profile(
                        *args, publish=False))
            raise

    def __call__(self, *args):
        if not self.engine.compiled:
            # eager: the paged metadata lives as host numpy between
            # steps and the step bodies index it with `.at[]` — lift
            # it to jax arrays (a no-op for leaves already on device)
            args = list(args)
            args[2] = {k: jnp.asarray(v) for k, v in args[2].items()}
            return self._fn(*args)
        if self._jitted is None:
            # persistent AOT cache (ISSUE 17): with
            # PADDLE_TPU_COMPILE_CACHE set, a warm replica's first
            # token deserializes the step executable; unset, this is
            # plain jax.jit
            from .compile_cache import cached_jit

            self._jitted = cached_jit(
                self._fn,
                donate_argnums=(1,) if self._donate else (),
                label=type(self).__name__)
        if self._pin_meta_host:
            args = list(args)
            args[2] = {k: np.asarray(v) for k, v in args[2].items()}
        # the exact post-pinning call args — a numpy/device mix drift
        # in the metadata (the PR-6 silent-recompile class) shows up
        # here as an attributed placement/kind change
        self._sentinel.observe(tuple(args), names=self._arg_names)
        if self._dispatch_hist is None:
            return self._dispatch(args)
        tc0 = self.trace_count
        t0 = time.perf_counter()
        out = self._dispatch(args)
        # a call that TRACED just paid compile time (minutes for big
        # models) — one such sample would permanently skew a histogram
        # whose steady-state entries are ~1ms, so only steady-state
        # dispatches are recorded
        if self.trace_count == tc0:
            self._dispatch_hist.observe(
                (time.perf_counter() - t0) * 1e3)
        return out

    # -- shared step body helpers ---------------------------------------
    def _enter(self, params, buffers, meta, dparams=None):
        """Bind traced params + cache state into the live model(s).

        When the engine carries a DRAFT model (speculative decoding)
        and the caller threads `dparams`, the draft's params and KV
        pools (nested under ``buffers["draft"]``) are bound too; the
        draft cache has no metadata of its own — its positions/tables
        are re-derived from the TARGET's metadata every step. A
        SELF-draft engine has no draft cache or params at all (the
        heads ride the target), so nothing extra binds."""
        eng = self.engine
        for p, d in zip(eng._params, params):
            p._data = d
        tgt = {k: v for k, v in buffers.items() if k != "draft"}
        eng.cache.load_state(_tree_wrap({**tgt, **meta}))
        self._draft_bound = (dparams is not None
                             and eng.draft_model is not None
                             and eng.draft_cache is not None)
        if self._draft_bound:
            for p, d in zip(eng._draft_params, dparams):
                p._data = d
            eng.draft_cache.load_state(
                _tree_wrap({**buffers["draft"], **meta}))

    def _exit_state(self):
        """Read back + split the cache state produced by the step."""
        eng = self.engine
        buffers, meta = _split_state(eng.kind,
                                     _tree_data(eng.cache.state()))
        if getattr(self, "_draft_bound", False):
            dbuf, _ = _split_state(
                eng.kind, _tree_data(eng.draft_cache.state()))
            buffers["draft"] = dbuf
        return buffers, meta

    def _sample(self, logits, key):
        eng = self.engine
        if eng.do_sample:
            key, sub = jax.random.split(key)
            ids = sample_logits(logits, key=sub,
                                temperature=eng.temperature,
                                top_k=eng.top_k, top_p=eng.top_p)
        else:
            ids = sample_logits(logits, key=None)
        return ids, key


class _BindCtx:
    """Snapshot the live params/cache for the duration of one trace and
    restore the concrete state after (a tracing error must not leave
    tracers bound in the model — same contract as TrainStep)."""

    def __init__(self, engine):
        self.engine = engine

    def __enter__(self):
        eng = self.engine
        self._saved_params = [p._data for p in eng._params]
        self._saved_cache = eng.cache.state()
        if getattr(eng, "draft_cache", None) is not None:
            self._saved_dparams = [p._data for p in eng._draft_params]
            self._saved_dcache = eng.draft_cache.state()
        else:
            self._saved_dparams = None
        return self

    def __exit__(self, *exc):
        eng = self.engine
        for p, d in zip(eng._params, self._saved_params):
            p._data = d
        eng.cache.load_state(self._saved_cache)
        if self._saved_dparams is not None:
            for p, d in zip(eng._draft_params, self._saved_dparams):
                p._data = d
            eng.draft_cache.load_state(self._saved_dcache)
        return False


class PrefillStep(_Step):
    """Bucketed prompt pass: write all layers' K/V, sample token 0."""

    _arg_names = ("params", "buffers", "meta", "ids", "lens",
                  "slot_ids", "key", "dparams")
    _bucketed_args = ("ids",)

    def _fn(self, params, buffers, meta, ids, lens, slot_ids, key,
            dparams=None):
        self.trace_count += 1
        eng = self.engine
        with no_grad(), _BindCtx(eng):
            self._enter(params, buffers, meta, dparams=dparams)
            cache = eng.cache
            b = ids.shape[0]
            lens_b = jnp.broadcast_to(lens.reshape(-1), (b,)) \
                .astype(jnp.int32)
            hidden = eng.model.gpt.prefill(
                Tensor._wrap(ids), cache,
                seq_lens=Tensor._wrap(lens_b),
                slot_ids=Tensor._wrap(slot_ids))
            if self._draft_bound:
                # prime the DRAFT cache over the same prompt/slots so
                # the first spec dispatch attends a complete context
                eng.draft_model.gpt.prefill(
                    Tensor._wrap(ids), eng.draft_cache,
                    seq_lens=Tensor._wrap(lens_b),
                    slot_ids=Tensor._wrap(slot_ids))
            # last VALID position per row (traced -> bucket-stable)
            last = jnp.take_along_axis(
                hidden._data, (lens_b - 1)[:, None, None]
                .astype(jnp.int32), axis=1)[:, 0]        # [b, h]
            logits = eng.model.head(Tensor._wrap(last))._data
            if cache.kind == "dense":
                cache.pos = Tensor._wrap(
                    lens.reshape(()).astype(jnp.int32))
            else:
                sl = _data_of(cache.seq_lens)
                cache.seq_lens = Tensor._wrap(
                    sl.at[slot_ids].set(lens_b))
            ids_next, key = self._sample(logits, key)
            new_buffers, new_meta = self._exit_state()
        return ids_next, logits, new_buffers, new_meta, key


class DecodeStep(_Step):
    """One-token cached decode step — compiled once, donated KV pools."""

    _arg_names = ("params", "buffers", "meta", "tokens", "key")

    def _fn(self, params, buffers, meta, tokens, key):
        self.trace_count += 1
        eng = self.engine
        with no_grad(), _BindCtx(eng):
            self._enter(params, buffers, meta)
            cache = eng.cache
            b = tokens.shape[0]
            if cache.kind == "dense":
                pos_ids = jnp.broadcast_to(
                    _data_of(cache.pos).reshape(1, 1),
                    (b, 1)).astype(jnp.int32)
            else:
                pos_ids = _data_of(cache.seq_lens)[:, None] \
                    .astype(jnp.int32)
            hidden = eng.model.gpt.decode_step(
                Tensor._wrap(tokens.reshape(b, 1)), cache,
                Tensor._wrap(pos_ids))
            logits = eng.model.head(hidden)._data[:, 0]   # [b, vocab]
            # advance the write positions
            if cache.kind == "dense":
                cache.pos = Tensor._wrap(_data_of(cache.pos) + 1)
            else:
                sl = _data_of(cache.seq_lens)
                act = _data_of(cache.active)
                cache.seq_lens = Tensor._wrap(
                    jnp.where(act, sl + 1, sl))
            ids_next, key = self._sample(logits, key)
            new_buffers, new_meta = self._exit_state()
        return ids_next, logits, new_buffers, new_meta, key


def _data_of(x):
    return x._data if isinstance(x, Tensor) else x


# ---------------------------------------------------------------------------
# serving-tier steps (paddle_tpu/serving): chunked prefill + per-slot RNG
# ---------------------------------------------------------------------------

class ChunkPrefillStep(_Step):
    """One bounded chunk of one prompt (continuous batching): write the
    chunk's K/V at positions [start, start+c) of its slot, attending
    over the context cached so far, and sample the prefill-complete
    token with the request's OWN RNG stream.

    Chunks are padded to a small set of chunk buckets, so jax.jit's
    shape-keyed cache holds one program per bucket and long prompts
    interleave with decode steps at a bounded per-chunk cost (TTFT for
    resident sequences stays bounded while a long prompt prefills).
    The sampled token is only meaningful when this was the final chunk
    — the host discards it otherwise. Paged cache only."""

    _pin_meta_host = True
    _arg_names = ("params", "buffers", "meta", "ids", "slot_ids",
                  "start", "lens_new", "seeds", "dparams")
    _bucketed_args = ("ids",)

    def _fn(self, params, buffers, meta, ids, slot_ids, start, lens_new,
            seeds, dparams=None):
        self.trace_count += 1
        eng = self.engine
        with no_grad(), _BindCtx(eng):
            self._enter(params, buffers, meta, dparams=dparams)
            cache = eng.cache
            hidden = eng.model.gpt.prefill_chunk(
                Tensor._wrap(ids), cache, Tensor._wrap(slot_ids),
                Tensor._wrap(start), Tensor._wrap(lens_new))
            if self._draft_bound:
                # mirror the chunk into the draft cache (same slots,
                # same positions) so spec decode starts with a fully
                # prefilled draft context
                eng.draft_model.gpt.prefill_chunk(
                    Tensor._wrap(ids), eng.draft_cache,
                    Tensor._wrap(slot_ids), Tensor._wrap(start),
                    Tensor._wrap(lens_new))
            # last VALID chunk position per row (traced, bucket-stable)
            last = jnp.take_along_axis(
                hidden._data,
                (lens_new - start - 1)[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]                             # [b, h]
            logits = eng.model.head(Tensor._wrap(last))._data
            sl = _data_of(cache.seq_lens)
            cache.seq_lens = Tensor._wrap(
                sl.at[slot_ids].set(lens_new))
            # sample position = total context length after this chunk —
            # identical to what the decode step would use at the same
            # context, which is what makes preempt-resume re-prefill
            # reproduce the original stream (exactly, wherever this
            # path's logits match the decode path's — bitwise on the
            # shared XLA fallback; kernel-level numerics on chip)
            ids_next = sample_logits_per_slot(
                logits, seeds, lens_new, temperature=eng.temperature,
                top_k=eng.top_k, top_p=eng.top_p,
                greedy=not eng.do_sample)
            new_buffers, new_meta = self._exit_state()
        return ids_next, logits, new_buffers, new_meta


class ServeDecodeStep(_Step):
    """`decode_burst` one-token decode steps over the full slot batch,
    fused into ONE compiled program: one dispatch + one host sync
    yields k tokens per slot (multi-step scheduling — the per-call
    host cost is what dominates a continuous-batching loop on small
    steps). Sampling uses PER-SLOT RNG streams: slot i samples with
    fold_in(PRNGKey(seeds[i]), ctx_len_i), so a request's tokens are
    bit-reproducible no matter which other sequences share the batch
    (admissions/retirements around it cannot perturb its stream).
    Inactive slots (free, or still chunk-prefilling) write to the
    trash page, attend nothing and keep their seq_lens — their sampled
    output is garbage the host discards. A slot whose request finishes
    mid-burst saturates its seq_len at the engine window and writes
    past its reserved pages onto the trash page — more host-discarded
    garbage."""

    _pin_meta_host = True
    _arg_names = ("params", "buffers", "meta", "tokens", "seeds")

    def _fn(self, params, buffers, meta, tokens, seeds):
        self.trace_count += 1
        eng = self.engine
        with no_grad(), _BindCtx(eng):
            self._enter(params, buffers, meta)
            cache = eng.cache
            b = tokens.shape[0]
            cur, toks = tokens, []
            # unrolled: burst length is a small engine constant, so
            # this stays one trace / one executable
            for _ in range(eng.decode_burst):
                pos_ids = _data_of(cache.seq_lens)[:, None] \
                    .astype(jnp.int32)
                hidden = eng.model.gpt.decode_step(
                    Tensor._wrap(jnp.reshape(cur, (b, 1))), cache,
                    Tensor._wrap(pos_ids))
                logits = eng.model.head(hidden)._data[:, 0]  # [b, v]
                sl = _data_of(cache.seq_lens)
                act = _data_of(cache.active)
                new_sl = jnp.where(act,
                                   jnp.minimum(sl + 1, eng.max_len), sl)
                cache.seq_lens = Tensor._wrap(new_sl)
                cur = sample_logits_per_slot(
                    logits, seeds, new_sl, temperature=eng.temperature,
                    top_k=eng.top_k, top_p=eng.top_p,
                    greedy=not eng.do_sample)
                toks.append(cur)
            new_buffers, new_meta = self._exit_state()
        return jnp.stack(toks), logits, new_buffers, new_meta


class SpecDecodeStep(_Step):
    """Speculative decoding inside ONE compiled program (ISSUE 16):
    the draft model proposes k tokens per slot, the target scores all
    k+1 positions in a single multi-token paged-attention call (the
    chunk-prefill machinery doubling as the verifier), and accept/
    rollback is traced slot bookkeeping — so one dispatch + one host
    sync yields BETWEEN 1 and k+1 tokens per slot at one target
    forward's cost.

    Structure of one dispatch, per slot, with pre-dispatch context
    length sl0 and incoming token t0 (sampled last dispatch, not yet
    cached — the same "last token is uncached" contract as the plain
    decode step):

    1. DRAFT: k+1 single-token decode iterations over the draft's own
       KV cache (same page tables / slot geometry as the target,
       draft-sized pools). Iteration j writes the j-th context token's
       K/V at sl0+j and proposes d_{j+1}; the final iteration only
       writes d_k's K/V — without it a full accept would leave a hole
       at sl0+k and the NEXT dispatch's draft would attend a torn
       context. Greedy engines take argmax; sampling engines draw from
       `truncated_probs` on the per-slot tag-3 stream
       (`spec_draft_keys`), recording q for the acceptance test.
    2. VERIFY: the target runs `prefill_chunk` over [t0, d_1..d_k] —
       ONE ragged multi-token attention call that also writes the
       target K/V for all k+1 rows (rows at/past the per-slot cap are
       trash-routed, so acceptance can never outrun reserved pages).
    3. ACCEPT/ROLLBACK: `spec_accept_greedy` (longest argmax-matching
       prefix — bit-identical to plain greedy decode) or
       `spec_accept_sampled` (rejection sampling with the residual
       correction — exactly target-distributed for ANY draft). The KV
       "rewind" on rejection is pure bookkeeping: seq_lens comes back
       as sl0 + accepted + 1 wait-free; stale rows beyond it are
       masked by every later attention and overwritten by the next
       dispatch's writes before they are ever read.

    Returns (tokens [b, k+1], counts [b], logits [b, k+1, vocab],
    buffers, meta): tokens[:counts] are the emitted tokens (accepted
    proposals then the correction/bonus token), counts is the per-slot
    yield (0 for slots whose cap is already met), logits row t is the
    target distribution the t-th emitted token came from. The host
    never learns WHY a token was emitted — only how many; variable
    yield is the whole scheduler-visible surface. All shapes are
    fixed by (batch, k), so steady state stays one executable.

    SELF-draft engines (``draft_model="self"``, ISSUE 20) replace
    step 1 with one TARGET decode step on t0 plus the target's k
    draft heads applied to h(t0) — same verify/accept machinery, no
    second checkpoint, no draft KV pools, still one executable."""

    _arg_names = ("params", "buffers", "meta", "dparams", "tokens",
                  "seeds", "caps")

    def _fn(self, params, buffers, meta, dparams, tokens, seeds, caps):
        self.trace_count += 1
        eng = self.engine
        kk = eng.spec_k
        with no_grad(), _BindCtx(eng):
            self._enter(params, buffers, meta, dparams=dparams)
            cache, dcache = eng.cache, eng.draft_cache
            b = tokens.shape[0]
            caps = jnp.minimum(jnp.asarray(caps).astype(jnp.int32),
                               eng.max_len)
            if eng.kind == "paged":
                sl0 = _data_of(cache.seq_lens).astype(jnp.int32)
                act = _data_of(cache.active)
                limit = cache.pages_per_seq * cache.page_size
            else:
                sl0 = jnp.broadcast_to(
                    jnp.reshape(_data_of(cache.pos), (-1,)),
                    (b,)).astype(jnp.int32)
                act = jnp.ones((b,), bool)
                limit = (dcache.max_len if dcache is not None
                         else cache.max_len)
            greedy = not eng.do_sample
            dmpe = eng.draft_model.config.max_position_embeddings
            cur = jnp.reshape(tokens, (b,)).astype(jnp.int32)
            prop, qprobs = [], []
            if getattr(eng.draft_model, "is_self_draft", False):
                # SELF-DRAFT propose (ISSUE 20): ONE target decode
                # step on the incoming token t0 yields h(t0); the k
                # draft heads then propose positions sl0+1..sl0+k from
                # h(t0) in one shot (head j looks j+1 ahead — not
                # sequential). The step writes t0's K/V at sl0 into
                # the TARGET cache; the verify chunk rewrites the same
                # bytes (the KV quantizers are deterministic, so the
                # double write is idempotent). No second model runs
                # and no draft pools exist.
                ok = act & (sl0 < jnp.minimum(caps, limit))
                if eng.kind == "paged":
                    cache.active = Tensor._wrap(ok)
                pos0 = jnp.minimum(sl0, dmpe - 1)[:, None]
                hidden = eng.model.gpt.decode_step(
                    Tensor._wrap(cur[:, None]), cache,
                    Tensor._wrap(pos0))
                if eng.kind == "paged":
                    cache.active = Tensor._wrap(act)
                heads = eng.model.draft_logits(hidden)._data[:, 0]
                for j in range(kk):           # [b, num_heads, vocab]
                    logits = heads[:, j]
                    if greedy:
                        nxt = jnp.argmax(logits.astype(jnp.float32),
                                         axis=-1).astype(jnp.int32)
                    else:
                        q = truncated_probs(logits, eng.temperature,
                                            eng.top_k, eng.top_p)
                        lq = jnp.where(q > 0,
                                       jnp.log(jnp.maximum(q, 1e-38)),
                                       -jnp.inf)
                        keys = spec_draft_keys(seeds, sl0, j)
                        nxt = jax.vmap(jax.random.categorical)(
                            keys, lq).astype(jnp.int32)
                        qprobs.append(q)
                    prop.append(nxt)
            else:
                for j in range(kk + 1):
                    dsl = sl0 + j
                    # overflow guard: near the window end the draft
                    # runs ahead of the target's reserved pages —
                    # deactivate those rows so their writes
                    # trash-route instead of clamping into the slot's
                    # last real page
                    ok = act & (dsl < limit)
                    if eng.kind == "paged":
                        dcache.seq_lens = Tensor._wrap(dsl)
                        dcache.active = Tensor._wrap(ok)
                    else:
                        dcache.pos = Tensor._wrap(dsl)
                    pos_ids = jnp.minimum(dsl, dmpe - 1)[:, None]
                    hidden = eng.draft_model.gpt.decode_step(
                        Tensor._wrap(cur[:, None]), dcache,
                        Tensor._wrap(pos_ids))
                    if j == kk:
                        break   # write-only iteration: d_k's K/V
                    logits = eng.draft_model.head(hidden)._data[:, 0]
                    if greedy:
                        nxt = jnp.argmax(logits.astype(jnp.float32),
                                         axis=-1).astype(jnp.int32)
                    else:
                        q = truncated_probs(logits, eng.temperature,
                                            eng.top_k, eng.top_p)
                        lq = jnp.where(q > 0,
                                       jnp.log(jnp.maximum(q, 1e-38)),
                                       -jnp.inf)
                        keys = spec_draft_keys(seeds, sl0, j)
                        nxt = jax.vmap(jax.random.categorical)(
                            keys, lq).astype(jnp.int32)
                        qprobs.append(q)
                    prop.append(nxt)
                    cur = nxt
            proposed = jnp.stack(prop, axis=1)               # [b, k]
            ver = jnp.concatenate(
                [jnp.reshape(tokens, (b, 1)).astype(jnp.int32),
                 proposed], axis=1)                          # [b, k+1]
            hidden = eng.model.gpt.prefill_chunk(
                Tensor._wrap(ver), cache,
                Tensor._wrap(jnp.arange(b, dtype=jnp.int32)),
                Tensor._wrap(sl0), Tensor._wrap(caps))
            logits_all = eng.model.head(hidden)._data   # [b, k+1, v]
            if greedy:
                a, nxt_tok = spec_accept_greedy(logits_all, proposed)
            else:
                tgt_p = truncated_probs(logits_all, eng.temperature,
                                        eng.top_k, eng.top_p)
                a, nxt_tok = spec_accept_sampled(
                    tgt_p, jnp.stack(qprobs, axis=1), proposed,
                    seeds, sl0)
            new_sl = jnp.where(act,
                               jnp.minimum(sl0 + 1 + a, caps), sl0)
            counts = (new_sl - sl0).astype(jnp.int32)
            toks = jnp.concatenate(
                [proposed, jnp.zeros((b, 1), jnp.int32)], axis=1)
            toks = toks.at[jnp.arange(b), a].set(nxt_tok)
            if eng.kind == "paged":
                cache.seq_lens = Tensor._wrap(new_sl)
            else:
                cache.pos = Tensor._wrap(new_sl)
            new_buffers, new_meta = self._exit_state()
        return toks, counts, logits_all, new_buffers, new_meta


class ServeSpecDecodeStep(SpecDecodeStep):
    """SpecDecodeStep under the serving loop's metadata contract: the
    continuous-batching bookkeeping rewrites page tables / active
    flags between calls, so every meta leaf is pinned to host numpy
    for one stable executable signature (see _Step._pin_meta_host).
    The scheduler sees only the variable per-slot token yield."""

    _pin_meta_host = True


class GenerationEngine:
    """Prefill + decode orchestration over one (model, cache) pair.

    Construction picks the cache shape; `generate()` runs prompt ->
    tokens end to end. The jitted steps live on the engine, so holding
    an engine (models cache them per signature, GPTForCausalLM.generate)
    means steady-state decoding never retraces or recompiles.
    """

    def __init__(self, model, kind="dense", batch=1, max_len=128,
                 do_sample=False, top_k=0, top_p=1.0, temperature=1.0,
                 compiled=True, cache_dtype=None, page_size=16,
                 prefill_buckets=DEFAULT_PREFILL_BUCKETS, donate=True,
                 draft_model=None, spec_k=4, kv_quant=None):
        cfg = model.config
        model.gpt._check_decodable()
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"max_len={max_len} exceeds max_position_embeddings="
                f"{cfg.max_position_embeddings}")
        self.model = model
        self.kind = kind
        self.batch = batch
        self.max_len = max_len
        self.do_sample = bool(do_sample)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.temperature = float(temperature)
        self.compiled = bool(compiled)
        # buckets must COVER max_len: a prompt between the largest
        # power-of-two bucket and max_len is within capacity and must
        # not fall through _bucket()
        buckets = tuple(sorted(bkt for bkt in prefill_buckets
                               if bkt <= max_len))
        if not buckets or buckets[-1] < max_len:
            buckets = buckets + (max_len,)
        self.prefill_buckets = buckets
        self._params = list(model.parameters())
        if kind not in ("dense", "paged"):
            raise ValueError(f"unknown cache kind {kind!r}")
        if kv_quant is not None and kind != "paged":
            raise ValueError(
                "kv_quant needs the paged cache (use_cache='paged')")
        self._cache_dtype = cache_dtype or jnp.float32
        self._page_size = page_size
        self.kv_quant = kv_quant
        # speculative decoding (ISSUE 16): a small draft model turns
        # the decode loop into draft-k/verify-once dispatches.
        # draft_model="self" (ISSUE 20) resolves to the target's own
        # draft heads — no second checkpoint, no draft KV pools.
        if isinstance(draft_model, str):
            if draft_model != "self":
                raise ValueError(
                    f"unknown draft_model {draft_model!r} (the only "
                    "string form is 'self')")
            draft_model = SelfDraftProposer(model)
        self.draft_model = draft_model
        self.spec_k = int(spec_k)
        self.cache = self._make_cache()
        if draft_model is not None:
            self_draft = getattr(draft_model, "is_self_draft", False)
            if self_draft:
                if self.spec_k > cfg.num_draft_heads:
                    raise ValueError(
                        f"spec_k={self.spec_k} exceeds the target's "
                        f"num_draft_heads={cfg.num_draft_heads}")
            else:
                draft_model.gpt._check_decodable()
                if draft_model.config.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        "draft model vocab_size "
                        f"{draft_model.config.vocab_size} != target "
                        f"{cfg.vocab_size} (proposals must be target "
                        "ids)")
            if self.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            self._draft_params = ([] if self_draft
                                  else list(draft_model.parameters()))
            self.draft_cache = (None if self_draft
                                else self._make_draft_cache())
            self.spec_step = SpecDecodeStep(self, donate_cache=donate)
        else:
            self._draft_params = []
            self.draft_cache = None
            self.spec_step = None
        self.prefill_step = PrefillStep(self, donate_cache=donate)
        self.decode_step = DecodeStep(self, donate_cache=donate)
        # live-buffer attribution (ISSUE 14): a decode-only process has
        # no train step to claim the model weights (the cache claims
        # its own pools)
        from ..observability.memory import live_registry

        live_registry().track(self)

    def _mem_owners(self):
        # shard-backed params (a sharded-storage train step sharing
        # this model) are skipped: reading them would GATHER on scrape,
        # and the owning step already claims the shards
        return {"params": [p._data for p in self._params
                           if not getattr(type(p), "_shard_backed",
                                          False)]}

    def _make_cache(self):
        """Fresh cache with this engine's geometry — also the recovery
        path when a failed generate leaves donated buffers dead."""
        from ..inference.kv_cache import DenseKVCache, PagedKVCache

        cfg = self.model.config
        nh = cfg.num_attention_heads
        hd = cfg.hidden_size // nh
        if self.kind == "dense":
            return DenseKVCache(cfg.num_layers, self.batch,
                                self.max_len, nh, hd,
                                dtype=self._cache_dtype)
        pages_per_seq = -(-self.max_len // self._page_size)
        return PagedKVCache(
            cfg.num_layers, nh, hd,
            num_pages=1 + self.batch * pages_per_seq,
            page_size=self._page_size, max_slots=self.batch,
            pages_per_seq=pages_per_seq, dtype=self._cache_dtype,
            quant=self.kv_quant)

    def _make_draft_cache(self):
        """Draft-model KV cache with the TARGET's slot/page geometry
        (shared page tables, draft-sized pools). The dense variant is
        oversized by spec_k+1 rows — the draft runs that far ahead of
        the target at the window end; the paged variant trash-routes
        its overrun instead (SpecDecodeStep's overflow guard). The
        draft stays un-quantized: its pools are small, and a noisy
        draft only costs accept rate while a noisy TARGET costs output
        quality."""
        from ..inference.kv_cache import DenseKVCache, PagedKVCache

        dcfg = self.draft_model.config
        nh = dcfg.num_attention_heads
        hd = dcfg.hidden_size // nh
        if self.kind == "dense":
            return DenseKVCache(dcfg.num_layers, self.batch,
                                self.max_len + self.spec_k + 1, nh, hd,
                                dtype=self._cache_dtype)
        return PagedKVCache(
            dcfg.num_layers, nh, hd,
            num_pages=self.cache.num_pages,
            page_size=self.cache.page_size,
            max_slots=self.cache.max_slots,
            pages_per_seq=self.cache.pages_per_seq,
            dtype=self._cache_dtype)

    # -- memory observability (ISSUE 14) ---------------------------------
    def memory_profile(self, top_k=8, publish=True):
        """Compiled decode-step memory profile for THIS engine's
        geometry (model params + KV pools + metadata at the live
        shapes) — see `_Step.memory_profile`."""
        buffers, meta = _split_state(self.kind,
                                     _tree_data(self.cache.state()))
        tok = jnp.zeros((self.batch,), jnp.int32)
        key = jax.random.PRNGKey(0)
        return self.decode_step.memory_profile(
            self._param_data(), buffers, meta, tok, key,
            top_k=top_k, publish=publish)

    # -- helpers ---------------------------------------------------------
    def _bucket(self, s):
        for bkt in self.prefill_buckets:
            if bkt >= s:
                return bkt
        raise ValueError(
            f"prompt length {s} exceeds the largest prefill bucket "
            f"{self.prefill_buckets[-1]} (max_len {self.max_len})")

    def _param_data(self):
        return [p._data for p in self._params]

    def _draft_param_data(self):
        return [p._data for p in self._draft_params]

    def generate(self, input_ids, max_new_tokens, seq_lens=None,
                 eos_token_id=None, seed=None, return_logits=False):
        """input_ids: [batch, prompt] int array (right-padded when
        `seq_lens` gives ragged true lengths — paged cache only).
        Returns int32 Tensor [batch, max_new_tokens] (plus the per-step
        logits [batch, max_new_tokens, vocab] when return_logits)."""
        ids = np.asarray(input_ids)
        b, s = ids.shape
        if b != self.batch:
            raise ValueError(f"engine batch {self.batch}, got {b}")
        if s + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {s} + {max_new_tokens} new tokens exceeds the "
                f"engine max_len {self.max_len}")
        cache = self.cache
        lens = (np.full((b,), s, np.int32) if seq_lens is None
                else np.asarray(seq_lens, np.int32).reshape(b))
        slots = list(range(b))
        if self.kind == "dense":
            if len(set(lens.tolist())) > 1:
                raise ValueError(
                    "the dense cache needs an aligned batch (one shared "
                    "prompt length); use use_cache='paged' for ragged "
                    "prompts")
            cache.pos = jnp.zeros((), jnp.int32)
            lens_in = jnp.asarray(lens[0], jnp.int32)
        else:
            # fresh slots for this batch (continuous-batching entry)
            for slot in list(cache._slot_pages):
                cache.free(slot)
            slots = [cache.allocate(int(L)) for L in lens]
            lens_in = jnp.asarray(lens, jnp.int32)
        slot_arr = jnp.asarray(slots, jnp.int32)

        bucket = self._bucket(s)
        if bucket > s:
            ids = np.concatenate(
                [ids, np.zeros((b, bucket - s), ids.dtype)], axis=1)
        if seed is None:
            # draw from the framework RNG stream (eager sampling
            # semantics): repeated sampled generates must differ
            from ..framework import random as _random

            key = _random.next_key()
        else:
            key = jax.random.PRNGKey(int(seed))
        buffers, meta = _split_state(self.kind,
                                     _tree_data(cache.state()))
        dp = self._draft_param_data()
        if self.draft_cache is not None:
            dbuf, _ = _split_state(self.kind,
                                   _tree_data(self.draft_cache.state()))
            buffers["draft"] = dbuf
        try:
            tok, logits, buffers, meta, key = self.prefill_step(
                self._param_data(), buffers, meta, jnp.asarray(ids),
                lens_in, slot_arr, key, dp)
            if self.draft_model is not None:
                out, logit_rows = self._spec_loop(
                    tok, logits, buffers, meta, dp, lens, slots,
                    int(max_new_tokens), key, return_logits)
                buffers, meta = self._spec_tail
            else:
                toks, logit_steps = [tok], [logits]
                cur = lens.copy()
                for _ in range(int(max_new_tokens) - 1):
                    if self.kind == "paged":
                        # grow page tables on demand (host
                        # bookkeeping; the device table is just a
                        # refreshed input, not a retrace)
                        for j, slot in enumerate(slots):
                            cache.reserve(slot, int(cur[j]) + 1)
                        meta["page_tables"] = cache.page_tables
                    tok, logits, buffers, meta, key = self.decode_step(
                        self._param_data(), buffers, meta, tok, key)
                    toks.append(tok)
                    if return_logits:
                        logit_steps.append(logits)
                    cur += 1
                out = np.stack([np.asarray(t) for t in toks], axis=1)
                logit_rows = ([np.asarray(lg, np.float32)
                               for lg in logit_steps]
                              if return_logits else None)
            dbuf = buffers.pop("draft", None)
            cache.load_state({**buffers, **meta})
            if dbuf is not None:
                self.draft_cache.load_state({**dbuf, **meta})
        except BaseException:
            # the steps DONATE the KV buffers, and the model keeps this
            # engine cached — an abort mid-loop would leave the cache
            # pointing at consumed buffers, so rebuild it pristine
            self.cache = self._make_cache()
            if self.draft_cache is not None:
                self.draft_cache = self._make_draft_cache()
            raise
        if self.kind == "paged":
            for slot in slots:
                cache.free(slot)
        if eos_token_id is not None:
            done = np.zeros((b,), bool)
            for t in range(out.shape[1]):
                out[done, t] = eos_token_id
                done |= out[:, t] == eos_token_id
        out_t = Tensor._wrap(jnp.asarray(out.astype(np.int32)))
        if return_logits:
            if self.draft_model is not None:
                logits_arr = np.stack(
                    [np.stack(rows, axis=0) for rows in logit_rows],
                    axis=0)
            else:
                logits_arr = np.stack(logit_rows, axis=1)
            return out_t, Tensor._wrap(jnp.asarray(logits_arr))
        return out_t

    def _spec_loop(self, tok, logits, buffers, meta, dp, lens, slots,
                   mnt, key, return_logits):
        """Host side of speculative generation: dispatch SpecDecodeStep
        until every row has `mnt` tokens, consuming the VARIABLE
        per-slot yield (1..spec_k+1 accepted-or-corrected tokens per
        dispatch; finished rows yield 0 via caps). Returns (out
        [b, mnt] np.int32, per-row logits lists); leaves the final
        (buffers, meta) in self._spec_tail for the caller."""
        cache = self.cache
        b = len(slots)
        tok_h = np.asarray(tok).astype(np.int32).reshape(b)
        outs = [[int(tok_h[i])] for i in range(b)]
        la0 = np.asarray(logits, np.float32)
        lrows = ([[la0[i]] for i in range(b)] if return_logits
                 else None)
        if self.do_sample:
            # per-slot streams for the spec accept/correct draws,
            # derived from the same key that seeded the prefill sample
            seeds = np.asarray(jax.random.randint(
                key, (b,), 0, np.iinfo(np.int32).max), np.uint32)
        else:
            seeds = np.zeros((b,), np.uint32)
        cur_tok = tok_h.copy()
        # invariant: cached context length = prompt + emitted - 1 (the
        # latest emitted token is never cached — it is the next
        # dispatch's verify row 0)
        sl_host = lens.astype(np.int64).copy()
        if self.kind == "dense":
            # pos must enter the step as a [b] vector from dispatch 1
            # (the step returns it as one — a scalar->vector flip
            # mid-loop would retrace)
            meta["pos"] = jnp.broadcast_to(
                jnp.reshape(jnp.asarray(meta["pos"], jnp.int32),
                            (-1,)), (b,))
        while min(len(o) for o in outs) < mnt:
            rem = np.array([mnt - len(o) for o in outs], np.int64)
            ahead = np.maximum(np.minimum(self.spec_k + 1, rem), 0)
            caps = (sl_host + ahead).astype(np.int32)
            if self.kind == "paged":
                for j, slot in enumerate(slots):
                    cache.reserve(slot, int(caps[j]))
                meta["page_tables"] = cache.page_tables
            toks_o, counts, logits_all, buffers, meta = self.spec_step(
                self._param_data(), buffers, meta, dp,
                np.asarray(cur_tok, np.int32), seeds, caps)
            counts_h = np.asarray(counts)
            toks_h = np.asarray(toks_o)
            la = (np.asarray(logits_all, np.float32)
                  if return_logits else None)
            for i in range(b):
                c = int(counts_h[i])
                for t in range(c):
                    outs[i].append(int(toks_h[i, t]))
                    if return_logits:
                        lrows[i].append(la[i, t])
                if c:
                    cur_tok[i] = toks_h[i, c - 1]
                sl_host[i] += c
        self._spec_tail = (buffers, meta)
        return np.stack([np.asarray(o, np.int32) for o in outs]), lrows
