"""Persistent compile-cache inspector (ISSUE 17 satellite).

Operator surface over `paddle_tpu.jit.compile_cache.CompileCache` — the
on-disk AOT executable store the step paths hit on warm start. Answers
the questions an operator actually asks: what is cached, WHY is an
entry keyed the way it is (full key provenance: signature, HLO hash,
toolchain versions, flags, donation, mesh), how big is the store, and
how do I trim it.

Usage::

    python tools/compile_cache.py list   [--dir DIR] [--json] [-v]
    python tools/compile_cache.py stats  [--dir DIR] [--json]
    python tools/compile_cache.py evict  KEYPREFIX [--dir DIR]
    python tools/compile_cache.py clear  [--dir DIR]
    python tools/compile_cache.py prune  [--dir DIR] [--max-mb MB]

``--dir`` defaults to ``$PADDLE_TPU_COMPILE_CACHE``. ``evict`` accepts
an unambiguous key prefix (keys are 32-hex). ``prune`` runs the same
LRU cap enforcement the store applies online (``--max-mb`` overrides
``$PADDLE_TPU_COMPILE_CACHE_MB``, default 512).

Exit codes: 0 ok / 1 usage or no cache dir / 3 evict target missing or
ambiguous.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from paddle_tpu.jit.compile_cache import (  # noqa: E402
    CACHE_CAP_ENV, CACHE_ENV, CompileCache,
)


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0


def _fmt_age(ts):
    if not ts:
        return "-"
    d = max(0.0, time.time() - float(ts))
    for lim, unit in ((60, "s"), (3600, "m"), (86400, "h")):
        if d < lim:
            return f"{d:.0f}{unit}" if unit == "s" else \
                f"{d / (lim / 60):.0f}{unit}"
    return f"{d / 86400:.1f}d"


def _provenance(comp):
    """One compact provenance string from the sidecar key components."""
    if not comp:
        return "(no sidecar)"
    backend = comp.get("backend", {})
    flags_on = sorted(k.replace("FLAGS_", "")
                      for k, v in (comp.get("flags") or {}).items() if v)
    bits = [
        f"sig={str(comp.get('signature', '?'))[:10]}",
        f"{comp.get('hlo', '?')}",
        f"jaxlib={comp.get('jaxlib_version', '?')}",
        f"{backend.get('platform', '?')}x{backend.get('n_devices', '?')}",
        f"donate={comp.get('donate_argnums', [])}",
    ]
    if comp.get("mesh"):
        bits.append("mesh=" + "x".join(
            f"{k}{v}" for k, v in comp["mesh"].items()))
    if flags_on:
        bits.append("flags=" + ",".join(flags_on))
    return " ".join(bits)


def render_list(cache, verbose=False):
    lines = []
    entries = cache.entries()
    if not entries:
        return [f"compile cache {cache.root}: empty"]
    lines.append(f"{'KEY':<14} {'LABEL':<24} {'SIZE':>9} {'HITS':>5} "
                 f"{'AGE':>6} {'USED':>6}  PROVENANCE")
    for e in entries:
        comp = e.meta.get("components") or {}
        lines.append(
            f"{e.key[:12]:<14} "
            f"{str(comp.get('label', '?'))[:24]:<24} "
            f"{_fmt_bytes(e.meta['bytes']):>9} "
            f"{int(e.meta.get('hits', 0)):>5} "
            f"{_fmt_age(e.meta.get('created')):>6} "
            f"{_fmt_age(e.meta.get('last_used')):>6}  "
            f"{_provenance(comp)}")
        if verbose:
            lines.append("    " + json.dumps(comp, sort_keys=True))
    return lines


def render_stats(cache):
    st = cache.stats()
    used = st["bytes"] / max(st["max_bytes"], 1) * 100.0
    return [
        f"compile cache {st['root']}",
        f"  entries      {st['entries']}",
        f"  size         {_fmt_bytes(st['bytes'])} / "
        f"{_fmt_bytes(st['max_bytes'])} cap ({used:.0f}%)",
        f"  proc hit/miss {st['hits']}/{st['misses']}",
        f"  lifetime hits {st['disk_hits']} (sidecar accounting)",
    ]


def _open_cache(args):
    root = args.dir or os.environ.get(CACHE_ENV, "").strip()
    if not root:
        print(f"no cache dir: pass --dir or set ${CACHE_ENV}",
              file=sys.stderr)
        return None
    max_bytes = None
    if getattr(args, "max_mb", None):
        max_bytes = int(args.max_mb * (1 << 20))
    return CompileCache(root, max_bytes=max_bytes)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="compile_cache", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("list", "stats", "clear"):
        p = sub.add_parser(name)
        p.add_argument("--dir", default=None)
        p.add_argument("--json", action="store_true")
        if name == "list":
            p.add_argument("-v", "--verbose", action="store_true")
    p = sub.add_parser("evict")
    p.add_argument("key")
    p.add_argument("--dir", default=None)
    p = sub.add_parser("prune")
    p.add_argument("--dir", default=None)
    p.add_argument("--max-mb", type=float, default=None,
                   help=f"cap override (default ${CACHE_CAP_ENV} or 512)")
    args = ap.parse_args(argv)

    cache = _open_cache(args)
    if cache is None:
        return 1

    if args.cmd == "list":
        if args.json:
            print(json.dumps([e.meta for e in cache.entries()],
                             indent=2, sort_keys=True))
        else:
            print("\n".join(render_list(cache, verbose=args.verbose)))
        return 0
    if args.cmd == "stats":
        if args.json:
            print(json.dumps(cache.stats(), indent=2, sort_keys=True))
        else:
            print("\n".join(render_stats(cache)))
        return 0
    if args.cmd == "evict":
        matches = [e for e in cache.entries()
                   if e.key.startswith(args.key)]
        if len(matches) != 1:
            print(f"evict {args.key!r}: "
                  f"{'no match' if not matches else 'ambiguous prefix'} "
                  f"({len(matches)} entries)", file=sys.stderr)
            return 3
        cache.evict(matches[0].key)
        print(f"evicted {matches[0].key}")
        return 0
    if args.cmd == "clear":
        n = cache.clear()
        print(f"cleared {n} entries from {cache.root}")
        return 0
    if args.cmd == "prune":
        before = {e.key for e in cache.entries()}
        cache._enforce_cap()
        gone = before - {e.key for e in cache.entries()}
        print(f"pruned {len(gone)} entries "
              f"(cap {_fmt_bytes(cache.max_bytes)}, now "
              f"{_fmt_bytes(cache.total_bytes())})")
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
