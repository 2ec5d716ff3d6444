"""Retrace sentinel: executable-cache-miss accounting with attribution.

The bug class PR 6 hit — a numpy/device-array metadata mix keying a
fresh executable per combination, silently recompiling mid-serve — was
only detectable by hand-written compile-count probes. The sentinel
turns it into one attributed log line: every jitted step path
(`TrainStep`, `FusedScanTrainStep` + its sharded/pipeline subclasses,
the decode/serve `_Step`s) calls ``observe(args)`` right before
dispatching its compiled callable. The sentinel derives the same
abstract signature jax.jit keys its executable cache on (pytree
structure + per-leaf shape/dtype/weak-type/placement/host-vs-device
kind) and:

- counts cache hits and misses per signature;
- on a NEW signature after the first, diffs the leaves against the
  closest previously-seen signature and reports exactly WHICH argument
  leaf changed (``state['guard']['scale']: dtype float32 -> float16``);
- classifies the miss as *expected* when every changed leaf is a
  declared bucketed/optional argument (prefill length buckets, the
  optional segment-id arg) — everything else is an **unexpected
  recompile**, logged, counted in the registry, noted in the flight
  recorder, and raised as ``RetraceError`` in strict mode (tests).

All the existing compile-count probes are expressible through the
sentinel: ``signatures`` is the trace count, ``calls`` the dispatch
count, ``unexpected`` must stay 0 on a clean run.
"""
from __future__ import annotations

import logging
import threading
import weakref

from .registry import registry as _registry

__all__ = ["RetraceSentinel", "RetraceError", "set_strict_retrace",
           "strict_retrace", "retrace_summary", "enabled"]

logger = logging.getLogger("paddle_tpu.observability")

_strict = False
_enabled_env = None


class RetraceError(RuntimeError):
    """An unexpected recompile under strict mode — the message names
    the offending argument leaf/leaves."""


def set_strict_retrace(on: bool):
    """Global strict toggle: any sentinel without an explicit
    ``strict=`` raises `RetraceError` on an unexpected recompile."""
    global _strict
    _strict = bool(on)


def strict_retrace() -> bool:
    return _strict


def enabled() -> bool:
    """Telemetry kill-switch: PADDLE_TPU_TELEMETRY=0 disables the
    per-step observe/record calls (instruments stay importable)."""
    global _enabled_env
    if _enabled_env is None:
        import os

        _enabled_env = os.environ.get("PADDLE_TPU_TELEMETRY", "1") != "0"
    return _enabled_env


# -- signatures -------------------------------------------------------------

_jax = None
_np = None


def _mods():
    global _jax, _np
    if _jax is None:
        import jax
        import numpy

        _jax, _np = jax, numpy
    return _jax, _np


def _leaf_sig(leaf):
    """Hashable signature of one leaf covering the fields jax.jit's
    cache key depends on. HOT PATH (runs per state leaf per step): for
    jax arrays the signature is the aval OBJECT itself (ShapedArray —
    hashable, carries shape+dtype+weak_type in one attribute read) plus
    sharding and committed-ness; field-level description only happens
    on the rare mismatch (`_describe`)."""
    jax, np = _mods()

    if isinstance(leaf, jax.Array):
        try:
            sh = leaf.sharding
        except Exception:
            sh = None
        return (leaf.aval, sh, getattr(leaf, "_committed", True))
    if isinstance(leaf, (np.ndarray, np.generic)):
        return ("np", np.shape(leaf), leaf.dtype)
    # python scalars trace as weak-typed values; anything else is a
    # static-by-structure leaf — key by type
    return ("py", type(leaf))


_FIELDS = ("kind", "shape", "dtype", "weak_type", "placement")


def _describe(sig):
    """Expand a leaf signature into named fields for attribution."""
    if sig[0] == "np":
        return {"kind": "np(host)", "shape": tuple(sig[1]),
                "dtype": str(sig[2]), "weak_type": False,
                "placement": "host"}
    if sig[0] == "py":
        return {"kind": "py", "shape": (), "dtype": sig[1].__name__,
                "weak_type": True, "placement": None}
    aval, sh, committed = sig
    return {"kind": "jax", "shape": tuple(aval.shape),
            "dtype": str(aval.dtype),
            "weak_type": bool(getattr(aval, "weak_type", False)),
            "placement": f"{sh}|committed={bool(committed)}"}


def _format_path(path, names=None):
    """Human-readable leaf path; the TOP-LEVEL tuple index is replaced
    by the caller-provided argument name."""
    from jax.tree_util import DictKey, FlattenedIndexKey, GetAttrKey, SequenceKey

    parts = []
    for i, k in enumerate(path):
        if isinstance(k, SequenceKey):
            if i == 0 and names is not None and k.idx < len(names):
                parts.append(names[k.idx])
            else:
                parts.append(f"[{k.idx}]")
        elif isinstance(k, DictKey):
            parts.append(f"[{k.key!r}]")
        elif isinstance(k, GetAttrKey):
            parts.append(f".{k.name}")
        elif isinstance(k, FlattenedIndexKey):
            parts.append(f"[{k.key}]")
        else:
            parts.append(str(k))
    out = ""
    for p in parts:
        if out and not p.startswith((".", "[")):
            out += "." + p
        else:
            out += p
    return out or "<root>"


_all_sentinels = []
_sentinel_lock = threading.Lock()


class RetraceSentinel:
    """Signature tracker for one jitted callable.

    Args:
      name: label for logs/metrics (``retrace.<name>.*`` in the
        registry).
      bucketed: argument names/paths whose SHAPE legitimately varies
        (prefill length buckets) — shape-only changes there are
        expected compiles.
      optional: argument names whose PRESENCE may vary (the optional
        segment-id arg: None and array each compile once, expected).
      strict: True/False, or None to follow the global
        `set_strict_retrace` toggle.
    """

    def __init__(self, name, bucketed=(), optional=(), strict=None,
                 registry=None):
        self.name = name
        self.bucketed = tuple(bucketed)
        self.optional = tuple(optional)
        self.strict = strict
        self._registry = registry if registry is not None else _registry()
        self._lock = threading.Lock()
        self._keys = {}          # signature key -> index
        # index -> {leaf path: leaf sig}: small strings/tuples only —
        # holding the args themselves would pin every model/state array
        # the step was ever called with
        self._pathmaps = []
        self.calls = 0
        self.hits = 0
        self.unexpected = 0
        self.events = []
        with _sentinel_lock:
            _all_sentinels.append(weakref.ref(self))

    # -- probe surface ---------------------------------------------------
    @property
    def signatures(self):
        """Distinct signatures seen = expected executable count."""
        return len(self._keys)

    def stats(self):
        return {"name": self.name, "calls": self.calls,
                "signatures": self.signatures, "hits": self.hits,
                "unexpected": self.unexpected,
                "events": list(self.events)}

    # -- the per-call check ----------------------------------------------
    def observe(self, args, names=None):
        """Record one dispatch of the watched callable with ``args``
        (any pytree; typically the exact tuple passed to the jitted
        function). Returns the retrace event dict for a new signature
        (None on a cache hit)."""
        if not enabled():
            return None
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(args)
        key = (treedef, tuple(_leaf_sig(l) for l in leaves))
        try:
            hash(key[1])
        except TypeError:      # unhashable sharding object: degrade
            key = (treedef, tuple(map(repr, key[1])))
        with self._lock:
            self.calls += 1
            if key in self._keys:
                self.hits += 1
                return None
            first = not self._keys
            self._keys[key] = len(self._keys)
        pathmap = {
            _format_path(p, names): _leaf_sig(l)
            for p, l in jax.tree_util.tree_flatten_with_path(args)[0]}
        with self._lock:
            self._pathmaps.append(pathmap)
        self._registry.gauge(f"retrace.{self.name}.signatures").set(
            self.signatures)
        if first:
            return None
        event = self._attribute(pathmap)
        if not event["expected"]:
            with self._lock:
                self.unexpected += 1
                self.events.append(event)
                del self.events[:-64]
            self._registry.counter(
                f"retrace.{self.name}.unexpected").inc()
            # a whole-state placement shift can touch hundreds of
            # leaves — log the first few, count the rest
            shown = event["changes"][:6]
            more = len(event["changes"]) - len(shown)
            msg = (f"unexpected recompile of {self.name} "
                   f"(signature #{self.signatures}): "
                   + "; ".join(shown)
                   + (f" (+{more} more changed leaves)" if more else ""))
            logger.warning(msg)
            try:
                from .flight_recorder import recorder

                recorder().note("retrace", name=self.name,
                                changes=event["changes"])
            except Exception:
                pass
            strict = self.strict if self.strict is not None else _strict
            if strict:
                # the dispatch is being REFUSED — unregister the bad
                # signature so a retry re-detects (and re-raises)
                # instead of counting as a cache hit and silently
                # compiling the drifted program
                with self._lock:
                    if self._keys.get(key) == len(self._keys) - 1:
                        del self._keys[key]
                        self._pathmaps.pop()
                self._registry.gauge(
                    f"retrace.{self.name}.signatures").set(
                    self.signatures)
                raise RetraceError(msg)
        else:
            with self._lock:
                self.events.append(event)
                del self.events[:-64]
        return event

    # -- attribution -----------------------------------------------------
    def _attribute(self, new_paths):
        """Diff the new signature against the closest seen one and name
        the changed leaves."""
        with self._lock:
            candidates = self._pathmaps[:-1]
        best = None
        for old_paths in candidates:
            diffs = self._diff(old_paths, new_paths)
            if best is None or len(diffs) < len(best):
                best = diffs
        diffs = best or []
        changes, expected = [], bool(diffs)
        for path, field, old, new in diffs:
            if len(changes) < 128:       # bound the stored event
                changes.append(f"{path}: {field} {old} -> {new}")
            head = path.split(".")[0].split("[")[0]
            if field == "presence" and head in self.optional:
                continue
            if field == "shape" and head in self.bucketed:
                continue
            expected = False
        return {"name": self.name, "signature_index": self.signatures,
                "changes": changes, "expected": expected}

    @staticmethod
    def _diff(old_paths, new_paths):
        diffs = []
        for path in sorted(set(old_paths) | set(new_paths)):
            o, n = old_paths.get(path), new_paths.get(path)
            if o is None or n is None:
                diffs.append((path, "presence",
                              "absent" if o is None else "present",
                              "present" if o is None else "absent"))
                continue
            if o == n:
                continue
            od, nd = _describe(o), _describe(n)
            before = len(diffs)
            for f in _FIELDS:
                if od[f] != nd[f]:
                    diffs.append((path, f, od[f], nd[f]))
            if len(diffs) == before:
                # signatures differ but every described field matches
                # (e.g. distinct-but-equivalent sharding objects)
                diffs.append((path, "placement",
                              repr(o)[:120], repr(n)[:120]))
        return diffs


def retrace_summary():
    """{sentinel name: stats} over every live sentinel — the one-call
    clean-run receipt (total unexpected must be 0)."""
    out, total = {}, 0
    with _sentinel_lock:
        refs = list(_all_sentinels)
    for ref in refs:
        s = ref()
        if s is None:
            continue
        st = s.stats()
        st.pop("events", None)
        # several instances may share a class name (one per engine)
        key = st["name"]
        if key in out:
            for f in ("calls", "signatures", "hits", "unexpected"):
                out[key][f] += st[f]
        else:
            out[key] = st
        total += st["unexpected"]
    return {"sentinels": out, "total_unexpected": total}
