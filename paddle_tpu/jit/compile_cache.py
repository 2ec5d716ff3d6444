"""Persistent AOT executable cache — instant cold start (ISSUE 17).

Every new replica, elastic-reshard resume, or bench run used to pay a
full retrace+compile before its first token/step. This module makes a
warm process reach its first dispatch by DESERIALIZING instead: each
jitted step path builds through `cached_jit`, which AOT-lowers
(`jax.jit(fn).lower(*args)`), fingerprints the program, and either
loads a previously serialized executable from a content-addressed
on-disk store or compiles once and serializes the result
(`jax.experimental.serialize_executable`).

Cache key policy (DECISIONS.md §23): an entry is addressed by the
sha256 of a canonical JSON over

- the retrace sentinel's abstract ARGUMENT SIGNATURE (the same
  per-leaf aval/sharding/placement machinery jax.jit keys its own
  executable cache on — `observability.sentinel._leaf_sig`),
- the LOWERED-HLO fingerprint (StableHLO text hash — source edits,
  flag-dependent graph changes and donation all land here),
- jax + jaxlib versions (serialized executables are toolchain-bound),
- backend platform / device kind / device count,
- the donation config (`donate_argnums`),
- compile-relevant FLAGS values (`_KEY_FLAGS`) + `jax_enable_x64`,
- the mesh axis layout of any sharded argument.

Anything that could change the compiled program MISSES; a
byte-identical rebuild HITS. A corrupted or undeserializable entry is
evicted and falls back to a fresh compile — the cache can slow a cold
start, never break a step.

The store is OFF unless `PADDLE_TPU_COMPILE_CACHE` names a directory
(or `set_cache_dir()` is called) — with it unset every wrapped site
delegates verbatim to `jax.jit`, so default behavior is bit-identical
to the pre-cache tree. `PADDLE_TPU_COMPILE_CACHE_MB` caps the store
(LRU by last use, default 512 MiB).

Metrics (process-global registry): `jit.cache.hit` / `jit.cache.miss`
counters, `jit.cache.deserialize_ms` / `jit.cache.compile_ms`
histograms, lazy `jit.cache.entries` / `jit.cache.bytes` gauges.

This module is also the ONE home for code fingerprinting: the store's
keys and the backend-calib invalidation hash
(`distributed/auto_tuner/select.py`) build on `fingerprint` /
`source_fingerprint` below instead of ad-hoc sha256 recipes.
"""
from __future__ import annotations

import hashlib
import inspect
import json
import logging
import os
import pickle
import threading
import time

__all__ = [
    "fingerprint", "source_fingerprint", "file_fingerprint",
    "signature_fingerprint", "CompileCache", "CacheEntry",
    "active_cache", "set_cache_dir", "cache_enabled", "cached_jit",
    "CachedJit", "CACHE_ENV", "CACHE_CAP_ENV",
]

logger = logging.getLogger("paddle_tpu.jit.compile_cache")

CACHE_ENV = "PADDLE_TPU_COMPILE_CACHE"
CACHE_CAP_ENV = "PADDLE_TPU_COMPILE_CACHE_MB"
_DEFAULT_CAP_MB = 512

# FLAGS that change what the step paths trace/compile. The lowered-HLO
# hash would catch most of these anyway; keying on them explicitly
# keeps the provenance record queryable (tools/compile_cache.py shows
# WHY two entries differ) and guards flags that alter runtime behavior
# without reshaping the HLO text.
_KEY_FLAGS = (
    "FLAGS_splash_attn", "FLAGS_attention_fp32_scores",
    "FLAGS_numerics_monitor",
    "FLAGS_pallas_force_interpret", "FLAGS_pallas_flash_min_seqlen",
    "FLAGS_comm_quant", "FLAGS_param_storage",
)


# -- shared fingerprint helpers (satellite: ONE hashing recipe) -----------

def fingerprint(parts, prefix=None, width=16):
    """sha256 over an ordered iterable of str/bytes parts, rendered as
    ``prefix:hex[:width]`` (bare hex without a prefix). Every code/HLO
    hash in the tree goes through here so the recipe cannot drift."""
    h = hashlib.sha256()
    if isinstance(parts, (str, bytes)):
        parts = (parts,)
    for p in parts:
        h.update(p if isinstance(p, bytes) else str(p).encode())
    hx = h.hexdigest()[: int(width)] if width else h.hexdigest()
    return f"{prefix}:{hx}" if prefix else hx


def source_fingerprint(*objs, extra=(), prefix="src", width=16):
    """Fingerprint the SOURCE of functions/classes/modules (plus any
    extra strings — e.g. a toolchain version). An unsourceable object
    degrades to its qualified name, never raises."""
    parts = []
    for obj in objs:
        try:
            parts.append(inspect.getsource(obj))
        except (OSError, TypeError):
            parts.append(f"{getattr(obj, '__module__', '?')}."
                         f"{getattr(obj, '__qualname__', repr(obj))}")
    parts.extend(extra)
    return fingerprint(parts, prefix=prefix, width=width)


def file_fingerprint(paths, extra=(), prefix="src", width=16):
    """Fingerprint file CONTENTS (bench's compile-path fallback hash).
    Missing files contribute their path only — stable, never raises."""
    parts = []
    for p in paths:
        try:
            with open(p, "rb") as f:
                parts.append(f.read())
        except OSError:
            parts.append(str(p))
    parts.extend(extra)
    return fingerprint(parts, prefix=prefix, width=width)


def signature_fingerprint(args, width=16):
    """Stable hash of the sentinel-style abstract signature of a call's
    args: pytree structure + per-leaf `_leaf_sig` (aval, sharding,
    committed-ness / numpy shape+dtype / python type)."""
    import jax

    from ..observability.sentinel import _leaf_sig

    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = [str(treedef)]
    parts.extend(repr(_leaf_sig(l)) for l in leaves)
    return fingerprint(parts, width=width)


def _relevant_flags():
    from ..utils import flags as _flags

    return {name: _flags.get_flag(name) for name in _KEY_FLAGS}


def _backend_descr():
    import jax

    try:
        devs = jax.devices()
    except Exception:
        return {"platform": "none", "device_kind": "none", "n_devices": 0}
    return {"platform": devs[0].platform,
            "device_kind": getattr(devs[0], "device_kind", "?"),
            "n_devices": len(devs)}


def _mesh_shape_of(args):
    """Axis layout {name: size} of the first NamedSharding mesh found
    among the argument leaves ({} for unsharded/single-device calls)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(args):
        sh = getattr(leaf, "sharding", None)
        mesh = getattr(sh, "mesh", None)
        if mesh is not None and getattr(mesh, "shape", None):
            return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    return {}


def cache_key_components(sig, hlo, donate_argnums, label, mesh=None):
    """The full, JSON-serializable key record. Stored verbatim in the
    entry's sidecar so the CLI can explain what any entry is bound to."""
    import jax

    import jaxlib

    comp = {
        "label": str(label),
        "signature": sig,
        "hlo": hlo,
        "jax_version": jax.__version__,
        "jaxlib_version": getattr(jaxlib, "__version__", "?"),
        "backend": _backend_descr(),
        "donate_argnums": sorted(int(i) for i in donate_argnums),
        "flags": _relevant_flags(),
        "x64": bool(jax.config.jax_enable_x64),
        "mesh": mesh or {},
    }
    return comp


def digest_key(components) -> str:
    return fingerprint(json.dumps(components, sort_keys=True), width=32)


# -- the on-disk store ----------------------------------------------------

class CacheEntry:
    __slots__ = ("key", "path", "meta")

    def __init__(self, key, path, meta):
        self.key = key
        self.path = path
        self.meta = meta


class CompileCache:
    """Content-addressed executable store: ``<key>.bin`` holds the
    pickled (payload, in_tree, out_tree) triple from
    `serialize_executable.serialize`; ``<key>.json`` the key
    components + size/hit accounting. All I/O is best-effort: the
    cache may decline to serve, it may never raise into a step."""

    def __init__(self, root, max_bytes=None, registry=None):
        self.root = os.path.abspath(root)
        if max_bytes is None:
            mb = os.environ.get(CACHE_CAP_ENV)
            max_bytes = int(float(mb) * (1 << 20)) if mb else \
                _DEFAULT_CAP_MB * (1 << 20)
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        os.makedirs(self.root, exist_ok=True)
        if registry is None:
            from ..observability import registry as _reg

            registry = _reg()
        self._registry = registry
        self._hit = registry.counter("jit.cache.hit")
        self._miss = registry.counter("jit.cache.miss")
        self._deser_ms = registry.histogram("jit.cache.deserialize_ms")
        self._compile_ms = registry.histogram("jit.cache.compile_ms")
        registry.gauge("jit.cache.entries").set_fn(
            lambda: len(self.entries()))
        registry.gauge("jit.cache.bytes").set_fn(self.total_bytes)

    # -- paths ----------------------------------------------------------
    def _bin(self, key):
        return os.path.join(self.root, f"{key}.bin")

    def _meta(self, key):
        return os.path.join(self.root, f"{key}.json")

    # -- store surface ---------------------------------------------------
    def get(self, key):
        """Deserialize+load the executable under ``key``; None on miss.
        A corrupt entry (unreadable pickle, undeserializable payload,
        truncation) self-evicts and reads as a miss."""
        path = self._bin(key)
        if not os.path.exists(path):
            self._miss.inc()
            return None
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                rec = pickle.load(f)
            import jax
            from jax.experimental import serialize_executable as _se

            # without its devices named, a program compiled for one
            # device loads as one shard on EVERY local device
            by_id = {d.id: d for d in jax.devices()}
            compiled = _se.deserialize_and_load(
                rec["payload"], rec["in_tree"], rec["out_tree"],
                execution_devices=[by_id[i] for i in rec["device_ids"]])
        except Exception as e:          # corrupt/stale: evict, recompile
            logger.warning("compile cache entry %s unusable (%s: %s) — "
                           "evicting, falling back to compile",
                           key[:12], type(e).__name__, e)
            self.evict(key)
            self._miss.inc()
            return None
        ms = (time.perf_counter() - t0) * 1e3
        self._hit.inc()
        self._deser_ms.observe(ms)
        self._touch(key, ms)
        return compiled

    def put(self, key, compiled, components, compile_ms=None):
        """Serialize ``compiled`` under ``key`` with its provenance
        sidecar; silently a no-op when serialization is unsupported."""
        try:
            from jax.experimental import serialize_executable as _se

            payload, in_tree, out_tree = _se.serialize(compiled)
            blob = pickle.dumps(
                {"payload": payload, "in_tree": in_tree,
                 "out_tree": out_tree,
                 "device_ids": [d.id for d in compiled
                                .runtime_executable().local_devices()]},
                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:
            logger.warning("compile cache: cannot serialize %s (%s: %s)",
                           components.get("label", "?"),
                           type(e).__name__, e)
            return False
        with self._lock:
            try:
                tmp = self._bin(key) + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._bin(key))
                meta = {"key": key, "components": components,
                        "bytes": len(blob), "hits": 0,
                        "compile_ms": round(compile_ms, 3)
                        if compile_ms is not None else None,
                        "created": time.time(),
                        "last_used": time.time()}
                mtmp = self._meta(key) + ".tmp"
                with open(mtmp, "w") as f:
                    json.dump(meta, f)
                os.replace(mtmp, self._meta(key))
            except OSError:
                return False
        if compile_ms is not None:
            self._compile_ms.observe(compile_ms)
        self._enforce_cap()
        return True

    def _touch(self, key, deserialize_ms=None):
        """Best-effort hit accounting + LRU timestamp on the sidecar."""
        try:
            with open(self._meta(key)) as f:
                meta = json.load(f)
            meta["hits"] = int(meta.get("hits", 0)) + 1
            meta["last_used"] = time.time()
            if deserialize_ms is not None:
                meta["deserialize_ms"] = round(deserialize_ms, 3)
            tmp = self._meta(key) + ".tmp"
            with open(tmp, "w") as f:
                json.dump(meta, f)
            os.replace(tmp, self._meta(key))
        except (OSError, ValueError):
            pass

    # -- inventory (the CLI surface) -------------------------------------
    def entries(self):
        """CacheEntry list, most recently used first. Entries whose
        sidecar is unreadable still appear (minimal meta) so `clear`
        and the cap can always account for them."""
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for name in names:
            if not name.endswith(".bin"):
                continue
            key = name[:-4]
            path = os.path.join(self.root, name)
            try:
                size = os.path.getsize(path)
            except OSError:
                continue
            meta = {"key": key, "bytes": size, "hits": 0,
                    "last_used": 0.0, "components": {}}
            try:
                with open(self._meta(key)) as f:
                    meta.update(json.load(f))
            except (OSError, ValueError):
                pass
            meta["bytes"] = size
            out.append(CacheEntry(key, path, meta))
        out.sort(key=lambda e: -float(e.meta.get("last_used") or 0))
        return out

    def total_bytes(self):
        return sum(e.meta["bytes"] for e in self.entries())

    def stats(self):
        entries = self.entries()
        return {
            "root": self.root,
            "entries": len(entries),
            "bytes": sum(e.meta["bytes"] for e in entries),
            "max_bytes": self.max_bytes,
            "hits": self._hit.value,
            "misses": self._miss.value,
            "disk_hits": sum(int(e.meta.get("hits", 0))
                             for e in entries),
        }

    def evict(self, key) -> bool:
        with self._lock:
            found = False
            for p in (self._bin(key), self._meta(key)):
                try:
                    os.remove(p)
                    found = True
                except OSError:
                    pass
            return found

    def clear(self) -> int:
        n = 0
        for e in self.entries():
            if self.evict(e.key):
                n += 1
        return n

    def _enforce_cap(self):
        """LRU eviction down to ``max_bytes`` (never evicts the single
        newest entry even if it alone exceeds the cap)."""
        entries = self.entries()
        total = sum(e.meta["bytes"] for e in entries)
        while total > self.max_bytes and len(entries) > 1:
            victim = entries.pop()          # least recently used
            self.evict(victim.key)
            total -= victim.meta["bytes"]


# -- process-wide activation ----------------------------------------------

_active = None
_active_lock = threading.Lock()
_active_resolved = False


def set_cache_dir(path):
    """Programmatically enable (path) / disable (None) the persistent
    cache for this process — overrides the environment."""
    global _active, _active_resolved
    with _active_lock:
        _active = CompileCache(path) if path else None
        _active_resolved = True
    return _active


def active_cache():
    """The process CompileCache, resolved once from
    ``PADDLE_TPU_COMPILE_CACHE`` (None = caching disabled, every
    `cached_jit` site delegates verbatim to `jax.jit`)."""
    global _active, _active_resolved
    if not _active_resolved:
        with _active_lock:
            if not _active_resolved:
                root = os.environ.get(CACHE_ENV, "").strip()
                try:
                    _active = CompileCache(root) if root else None
                except OSError as e:
                    logger.warning("compile cache disabled (%s: %s)",
                                   type(e).__name__, e)
                    _active = None
                _active_resolved = True
    return _active


def cache_enabled() -> bool:
    return active_cache() is not None


# -- the jit wrapper ------------------------------------------------------

class CachedJit:
    """Drop-in for ``jax.jit(fn, donate_argnums=...)`` on the step
    paths. With no active cache it IS jax.jit (same object dispatched,
    bit-identical behavior). With a cache, each new abstract signature
    AOT-lowers, keys the store, and either deserializes a prior
    executable or compiles-and-serializes — then dispatches the loaded
    executable directly. Tracing semantics are preserved: `lower`
    traces the wrapped fn exactly once per signature, so the steps'
    `trace_count` probes keep counting."""

    def __init__(self, fn, donate_argnums=(), label=None):
        self._fn = fn
        self._donate = tuple(donate_argnums)
        self.label = label or getattr(fn, "__name__", "fn")
        import jax

        self._jit = jax.jit(fn, donate_argnums=self._donate)
        self._compiled = {}     # signature fingerprint -> loaded exec
        self._sig_memo = {}     # hashable leaf-sig key -> fingerprint
        self._lock = threading.Lock()
        self.disk_hits = 0
        self.disk_misses = 0

    # jax.jit API the steps rely on ---------------------------------------
    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def eval_shape(self, *args, **kwargs):
        return self._jit.eval_shape(*args, **kwargs)

    def _cache_size(self):
        try:
            n = self._jit._cache_size()
        except Exception:
            n = 0
        return n + len(self._compiled)

    # ---------------------------------------------------------------------
    def __call__(self, *args):
        cache = active_cache()
        if cache is None:
            return self._jit(*args)
        sig = self._sig(args)
        ex = self._compiled.get(sig)
        if ex is None:
            with self._lock:
                ex = self._compiled.get(sig)
                if ex is None:
                    ex = self._aot(args, sig, cache)
                    self._compiled[sig] = ex
        return ex(*args)

    def _sig(self, args):
        """Per-call signature fingerprint, memoized on the sentinel-style
        hashable leaf-sig key so steady-state dispatch pays one dict
        probe instead of repr+sha256 over the whole state tree."""
        import jax

        from ..observability.sentinel import _leaf_sig

        leaves, treedef = jax.tree_util.tree_flatten(args)
        key = (treedef, tuple(_leaf_sig(l) for l in leaves))
        try:
            memo = self._sig_memo.get(key)
        except TypeError:               # unhashable sharding: no memo
            return signature_fingerprint(args)
        if memo is None:
            memo = signature_fingerprint(args)
            self._sig_memo[key] = memo
        return memo

    def _aot(self, args, sig, cache):
        lowered = self._jit.lower(*args)
        try:
            hlo = fingerprint(lowered.as_text(), prefix="hlo")
        except Exception:
            hlo = fingerprint(self.label, prefix="label")
        comp = cache_key_components(sig, hlo, self._donate, self.label,
                                    mesh=_mesh_shape_of(args))
        key = digest_key(comp)
        compiled = cache.get(key)
        if compiled is not None:
            self.disk_hits += 1
            return compiled
        self.disk_misses += 1
        t0 = time.perf_counter()
        compiled = lowered.compile()
        ms = (time.perf_counter() - t0) * 1e3
        cache.put(key, compiled, comp, compile_ms=ms)
        return compiled


def cached_jit(fn, donate_argnums=(), label=None):
    """The step-path entry point: ``self._jitted = cached_jit(step_fn,
    donate_argnums=..., label="TrainStep")``."""
    return CachedJit(fn, donate_argnums=donate_argnums, label=label)
