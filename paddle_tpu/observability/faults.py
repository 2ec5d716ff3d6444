"""Seeded-deterministic fault injection for the whole stack (ISSUE 19).

One process-global :class:`FaultInjector` owns every deliberate failure
the chaos lanes (and the pre-existing fault-tolerance lanes) inject:
replica step raises, stuck/slow steps, KV hand-off blob corruption,
host-ring drops, checkpoint chunk flips, straggler delays, victim
SIGKILLs. Production code declares *fault points* — named call sites
that ask "should I fail here?" — and test harnesses *arm* them with
scriptable triggers:

    from paddle_tpu.observability import faults

    inj = faults.install(seed=7)
    inj.arm("serving.step.raise", at=3, match={"engine": "d0"})
    inj.arm("kv.ring.drop", prob=0.25)
    ...
    faults.reset()

Trigger grammar (per armed spec):

* ``at=N`` (or a list of Ns) — fire on exactly the N-th matching hit
  after arming (1-based): the *scheduled* trigger.
* ``every=K`` — fire on every K-th matching hit.
* ``prob=p`` — fire with probability ``p`` per matching hit, drawn
  from the injector's seeded RNG: deterministic per (seed, hit order).
* neither — fire on the first matching hit (*one-shot*).
* ``times=N`` bounds total fires (default 1; ``times=None`` = forever).
* ``match={field: value}`` restricts to hits whose call-site context
  carries those fields (e.g. one replica out of a fleet).

Every firing is logged to the PR-12 flight recorder
(``fault_injected`` events) and counted on the process registry
(``faults.fired`` + ``faults.fired.<point>``), so a chaos run's black
box states exactly which faults fired, where, and in what order.

When nothing is installed every fault point is a single global-load +
``is None`` check — the production cost of the hooks is nil.
"""
from __future__ import annotations

import threading
import time

import numpy as np

__all__ = [
    "FAULT_POINTS", "FaultError", "FaultInjector", "FaultSpec",
    "active", "corrupt_blob", "corrupt_file", "fire", "install",
    "maybe_delay", "maybe_raise", "register", "reset", "should_fire",
]

# The registry of named fault points compiled into the stack. Arming an
# unknown point raises (typo safety); modules adding new points at
# import time use register().
FAULT_POINTS = {
    "serving.step.raise":
        "raise inside ServingEngine.step (replica crash; the engine's "
        "bounded-retry recovery, then the fleet watchdog, handle it)",
    "serving.step.stuck":
        "delay inside ServingEngine.step (wedged replica; the fleet "
        "watchdog's heartbeat goes stale)",
    "serving.decode.straggler":
        "delay before one decode dispatch (tail-latency straggler)",
    "kv.handoff.corrupt":
        "flip one byte in an exported KV hand-off blob (the adopter "
        "must reject it pre-allocation and re-let the lease)",
    "kv.ring.drop":
        "drop a HostKVRing.put blob (the victim falls back to "
        "resume-by-re-prefill)",
    "ckpt.chunk.flip":
        "flip one byte in a written checkpoint chunk before commit "
        "(manifest verification must catch it on restore)",
    "train.step.crash":
        "raise at a train-step boundary (elastic-resume rehearsal)",
    "train.step.straggler":
        "delay at a train-step boundary",
}


class FaultError(RuntimeError):
    """The exception an armed ``raise``-style fault point throws."""


def register(point: str, description: str = ""):
    """Declare an additional fault point name (idempotent)."""
    FAULT_POINTS.setdefault(point, description)
    return point


class FaultSpec:
    """One armed trigger on one fault point."""

    __slots__ = ("point", "at", "every", "prob", "times", "match",
                 "delay_s", "message", "seen", "fired")

    def __init__(self, point, at=None, every=None, prob=None, times=1,
                 match=None, delay_s=None, message=None):
        self.point = point
        self.at = (None if at is None
                   else frozenset([at] if isinstance(at, int) else at))
        self.every = None if every is None else int(every)
        self.prob = None if prob is None else float(prob)
        self.times = None if times is None else int(times)
        self.match = dict(match or {})
        self.delay_s = delay_s
        self.message = message
        self.seen = 0        # matching hits since arming
        self.fired = 0

    def matches(self, ctx: dict) -> bool:
        return all(ctx.get(k) == v for k, v in self.match.items())

    def make_exc(self) -> FaultError:
        return FaultError(self.message
                          or f"injected fault at {self.point!r}")


class FaultInjector:
    """Process-global, seeded-deterministic fault scheduler.

    Thread-safe: replica threads hit fault points concurrently; hit
    counting and RNG draws serialize under one lock, so a fixed
    (seed, workload) pair replays the identical fault schedule."""

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self.rng = np.random.default_rng(self.seed)
        self._lock = threading.Lock()
        self._specs: dict[str, list[FaultSpec]] = {}
        self.hits: dict[str, int] = {}
        self.log: list[dict] = []    # every firing, in order

    # -- arming -----------------------------------------------------------
    def arm(self, point: str, at=None, every=None, prob=None, times=1,
            match=None, delay_s=None, message=None) -> FaultSpec:
        if point not in FAULT_POINTS:
            raise ValueError(
                f"unknown fault point {point!r} — known: "
                f"{sorted(FAULT_POINTS)}")
        spec = FaultSpec(point, at=at, every=every, prob=prob,
                         times=times, match=match, delay_s=delay_s,
                         message=message)
        with self._lock:
            self._specs.setdefault(point, []).append(spec)
        return spec

    def disarm(self, point: str | None = None):
        with self._lock:
            if point is None:
                self._specs.clear()
            else:
                self._specs.pop(point, None)

    def armed(self, point: str | None = None) -> list:
        with self._lock:
            if point is not None:
                return list(self._specs.get(point, ()))
            return [s for specs in self._specs.values() for s in specs]

    # -- firing -----------------------------------------------------------
    def fire(self, point: str, ctx: dict) -> FaultSpec | None:
        """Called by fault points. Returns the spec that fired (at most
        one per hit), or None. Counts the hit either way."""
        with self._lock:
            self.hits[point] = self.hits.get(point, 0) + 1
            specs = self._specs.get(point)
            if not specs:
                return None
            for spec in specs:
                if (spec.times is not None
                        and spec.fired >= spec.times):
                    continue
                if not spec.matches(ctx):
                    continue
                spec.seen += 1
                if spec.at is not None:
                    hit = spec.seen in spec.at
                elif spec.every is not None:
                    hit = spec.seen % spec.every == 0
                elif spec.prob is not None:
                    hit = float(self.rng.random()) < spec.prob
                else:
                    hit = True
                if not hit:
                    continue
                spec.fired += 1
                ev = {"point": point, "hit": spec.seen,
                      "fired": spec.fired, **ctx}
                self.log.append(ev)
                self._note(ev)
                return spec
            return None

    @staticmethod
    def _note(ev: dict):
        """Flight-recorder + registry receipt of one firing. Never
        raises — a broken telemetry path must not change whether the
        fault itself fires."""
        try:
            from .flight_recorder import recorder
            from .registry import registry

            recorder().note("fault_injected", **ev)
            reg = registry()
            reg.counter("faults.fired").inc()
            reg.counter(f"faults.fired.{ev['point']}").inc()
        except Exception:
            pass

    # -- seeded services the harnesses share ------------------------------
    def uniform(self, lo: float, hi: float) -> float:
        """One seeded draw (e.g. the kill lane's SIGKILL delay)."""
        with self._lock:
            return float(self.rng.uniform(lo, hi))

    def pick_index(self, n: int) -> int:
        with self._lock:
            return int(self.rng.integers(0, max(1, int(n))))

    def flip_byte(self, buf, index: int | None = None) -> int:
        """Flip one byte of a writable uint8 view in place; returns the
        flipped offset. The single byte-flip implementation behind both
        the checkpoint chunk-flip and KV blob-corruption faults."""
        view = np.frombuffer(buf, np.uint8) if isinstance(
            buf, (bytes, bytearray)) else buf.view(np.uint8).reshape(-1)
        if index is None:
            index = self.pick_index(view.size)
        view[index] ^= 0x01
        return int(index)

    def summary(self) -> dict:
        with self._lock:
            return {
                "seed": self.seed,
                "hits": dict(self.hits),
                "fired": list(self.log),
                "armed": [{"point": s.point, "seen": s.seen,
                           "fired": s.fired} for specs in
                          self._specs.values() for s in specs],
            }


# -- process-global install / fast-path hooks -----------------------------
_injector: FaultInjector | None = None


def install(seed: int = 0) -> FaultInjector:
    """Install (replacing any previous) the process-global injector."""
    global _injector
    _injector = FaultInjector(seed=seed)
    return _injector


def reset():
    """Remove the process-global injector (all points go quiet)."""
    global _injector
    _injector = None


def active() -> FaultInjector | None:
    return _injector


def fire(point: str, **ctx) -> FaultSpec | None:
    """The generic fault-point hook: None when quiet, else the fired
    spec. One global load + None check when nothing is installed."""
    inj = _injector
    if inj is None:
        return None
    return inj.fire(point, ctx)


def should_fire(point: str, **ctx) -> bool:
    return fire(point, **ctx) is not None


def maybe_raise(point: str, **ctx):
    """Raise FaultError here if armed (the replica-crash points)."""
    spec = fire(point, **ctx)
    if spec is not None:
        raise spec.make_exc()


def maybe_delay(point: str, default_s: float = 0.05, **ctx) -> float:
    """Sleep here if armed (stuck-step / straggler points). Returns the
    injected delay (0.0 when quiet)."""
    spec = fire(point, **ctx)
    if spec is None:
        return 0.0
    d = float(spec.delay_s if spec.delay_s is not None else default_s)
    if d > 0:
        time.sleep(d)
    return d


def corrupt_blob(point: str, blob: dict, **ctx) -> bool:
    """Flip one seeded byte of a KV hand-off blob's payload if armed
    (after any checksum was computed, so the importer's CRC check must
    catch it). Returns True when the corruption was applied."""
    inj = _injector
    if inj is None:
        return False
    spec = inj.fire(point, ctx)
    if spec is None:
        return False
    for key in ("k", "v"):
        arrays = blob.get(key)
        if arrays:
            # force an owned, WRITABLE copy: device arrays surface as
            # read-only zero-copy numpy views
            a = np.array(arrays[0], copy=True)
            inj.flip_byte(a)
            arrays[0] = a
            return True
    return False


def corrupt_file(point: str, path: str, **ctx) -> bool:
    """Flip one seeded byte of a file in place if armed (the checkpoint
    chunk-flip fault). Returns True when applied."""
    inj = _injector
    if inj is None:
        return False
    spec = inj.fire(point, dict(ctx, path=path))
    if spec is None:
        return False
    with open(path, "rb") as f:
        raw = bytearray(f.read())
    if not raw:
        return False
    inj.flip_byte(raw)
    with open(path, "wb") as f:
        f.write(bytes(raw))
    return True
