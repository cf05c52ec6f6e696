"""Compile telemetry: jit cache-miss/recompile counting with
shape/dtype attribution, plus optional XLA cost-analysis accounting.

A GESP solver's serving story rests on "the jitted programs never
recompile after warmup" (serve/batcher.py's bucket ladder exists for
exactly this); this module is the instrument that PROVES it.  Every
whole-phase jitted program (`ops/batched._phase_fns`, the fused-solver
builders, the dist factor/solve closures) is wrapped in `watch()`: a
per-wrapper signature table detects the first call with a new
(shape, dtype, static-arg) signature — a jit cache miss — counts it
with full attribution, confirms against the jit's own `_cache_size()`
when available, and emits a `compile` trace event into the span
tracer.

With `SLU_OBS_COST=1` each miss additionally runs XLA cost analysis
(`fn.lower(...).compile().cost_analysis()`) and records the compiled
program's FLOP/byte counts per signature on the wrapper; the
factorize/solve paths hand the executed call's cost to the Stats
consumer through the thread-local `stamp_cost`/`take_cost` pair so
`Stats.ops_measured[phase]` adopts the right schedule's program per
execution — `Stats.gflops` then reports the program's own flop
accounting instead of the hand-counted `plan.factor_flops`.  Off by
default: the AOT lower+compile is an extra compilation per new
signature (the persistent compile cache usually dedupes the XLA
work, but tracing is re-paid).

Attribution caveats: a wrapper serving several signatures (e.g. the
solve program across nrhs buckets) keeps a cost PER SIGNATURE —
consumers read the executed call's program via `cost_of(*args)`;
the legacy `.cost` field holds the last miss and is only sound for
single-signature wrappers (the dist factor closures).
`snapshot()["cost_by_phase"]` keeps the last compiled program per
phase label process-wide.

The hit path costs one signature build (a few tuple allocations over
the argument list) and two dict reads — noise against the ms-scale
dispatches it wraps, and pinned by the SLU_OBS=0 overhead test.
"""

from __future__ import annotations

import threading
import time

from .. import flags
from . import tracer as _tracer


_EVENT_CAP = 1024


def _cost_enabled() -> bool:
    return flags.env_str("SLU_OBS_COST") == "1"


def _leaf_sig(a):
    """(shape, dtype) for an array-like, recursing into list/tuple
    containers (the packed-trisolve solve fn takes a pytree of panel
    arrays — repr() of a 200-array container would format every
    array's CONTENTS, tens of ms per call), repr for static
    scalars.

    Attribute-capable containers (trisolve.PackSet, an immutable
    tuple subclass) memoize their signature on themselves: rebuilding
    a ~200-leaf signature measured 0.65 ms per call, ~18% of a
    packed nrhs=1 solve.  Plain lists/tuples reject the setattr and
    stay un-memoized (they may be mutated between calls)."""
    shape = getattr(a, "shape", None)
    if shape is not None and hasattr(a, "dtype"):
        return (tuple(shape), str(a.dtype))
    if isinstance(a, (list, tuple)):
        memo = getattr(a, "_sig_cache", None)
        if memo is not None:
            return memo
        sig = tuple(_leaf_sig(x) for x in a)
        try:
            a._sig_cache = sig
        except (AttributeError, TypeError):
            pass
        return sig
    return repr(a)


def _sig_of(args, kwargs):
    """Hashable jit-call signature: (shape, dtype) for array-likes
    (containers recursed), repr for static scalars — the same
    partitioning jax's own cache keys on for our call sites."""
    parts = [_leaf_sig(a) for a in args]
    for k in sorted(kwargs):
        v = kwargs[k]
        shape = getattr(v, "shape", None)
        if shape is not None and hasattr(v, "dtype"):
            parts.append((k, tuple(shape), str(v.dtype)))
        else:
            # containers recurse like positional args (a keyword
            # pytree must not fall into the repr-the-contents trap)
            parts.append((k, _leaf_sig(v)))
    return tuple(parts)


def _sig_attrib(sig) -> dict:
    """Human/trace-readable shapes+dtypes split of a signature."""
    shapes, dtypes, static = [], [], []

    def walk(p, key=None):
        if isinstance(p, tuple) and len(p) == 2 \
                and isinstance(p[0], tuple) and isinstance(p[1], str):
            shapes.append(list(p[0]))
            dtypes.append(p[1])
        elif (isinstance(p, tuple) and len(p) == 3
              and isinstance(p[0], str)):
            shapes.append([p[0]] + list(p[1]))
            dtypes.append(p[2])
        elif isinstance(p, tuple):
            # container arg (the packed-panel pytree): flatten
            for q in p:
                walk(q)
        else:
            static.append(p if isinstance(p, str) else repr(p))

    for p in sig:
        walk(p)
    return {"shapes": shapes, "dtypes": dtypes, "static": static}


class _WatchedFn:
    """Callable proxy around a jitted function.  Unknown attributes
    (`lower`, `_cache_size`, `trace`, …) delegate to the wrapped jit,
    so HLO-inspection call sites (`measure_comm`, the pair-mode
    lowering tests, `solve_jit_cache_size`) work unchanged; extra
    attributes set on the proxy (`resid_fn`, `sel`, …) stick to it."""

    def __init__(self, fn, watch: "CompileWatch", phase: str,
                 cost_phase: str | None, donate=()):
        self._fn = fn
        self._watch = watch
        self._phase = phase
        self._cost_phase = cost_phase
        self._donate = tuple(donate)
        self._seen: dict = {}
        self._miss_lock = threading.Lock()
        # per-signature cost analyses (SLU_OBS_COST=1): one jit
        # wrapper compiles a PROGRAM PER SIGNATURE (the solve fn
        # across the nrhs bucket ladder), so the consumers must look
        # up the executed call's cost via cost_of(), not a shared
        # last-miss field — else a 1-wide solve adopts the 64-wide
        # program's flops
        self._cost_by_sig: dict = {}
        # last-missed-signature cost: adequate ONLY for wrappers with
        # a single live signature (the dist factor closures)
        self.cost: dict | None = None

    def __call__(self, *args, **kwargs):
        sig = _sig_of(args, kwargs)
        if sig in self._seen:           # GIL-atomic read: the hot path
            self._watch.calls += 1      # approximate under races — the
            return self._fn(*args, **kwargs)   # exact counter is misses
        with self._miss_lock:
            first = sig not in self._seen
            # claimed before the call so a racing thread on the same
            # new signature counts it exactly once
            self._seen[sig] = True
        if not first:
            self._watch.calls += 1
            return self._fn(*args, **kwargs)
        before = self._cache_size_safe()
        cost = None
        if self._cost_phase is not None and _cost_enabled():
            cost = self._cost_analysis(args, kwargs)
        t0 = time.perf_counter()
        try:
            out = self._fn(*args, **kwargs)
        except BaseException:
            # the claim must not survive a failed first call: the
            # retry that actually compiles still counts as the miss
            with self._miss_lock:
                self._seen.pop(sig, None)
            raise
        wall = time.perf_counter() - t0
        if cost:
            # this wrapper's program cost (per execution): the
            # attribution consumers (Stats.ops_measured via the
            # factorize/solve handles) read it per call via
            # cost_of(), so it must belong to THIS signature's
            # program, not the wrapper's last miss
            self._cost_by_sig[sig] = cost
            self.cost = cost
        self._watch.record_miss(
            phase=self._phase, sig=sig, wall_s=wall,
            cache_size=self._cache_size_safe(),
            cache_size_before=before, cost=cost,
            cost_phase=self._cost_phase, donated=self._donate)
        return out

    def cost_of(self, *args, **kwargs) -> dict | None:
        """The cost analysis of the program THESE arguments dispatch
        to (None until its miss ran under SLU_OBS_COST=1).  The empty
        check keeps the per-solve stamp at one attribute read when
        cost accounting is off — the flag's zero-cost-off contract."""
        if not self._cost_by_sig:
            return None
        return self._cost_by_sig.get(_sig_of(args, kwargs))

    def _cache_size_safe(self):
        try:
            return int(self._fn._cache_size())
        except Exception:
            return None

    def _cost_analysis(self, args, kwargs):
        try:
            compiled = self._fn.lower(*args, **kwargs).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if not isinstance(ca, dict):
                return None
            return {"flops": float(ca.get("flops", 0.0)),
                    "bytes": float(ca.get("bytes accessed", 0.0))}
        except Exception:
            return None

    def __getattr__(self, name):
        return getattr(self._fn, name)


class CompileWatch:
    """Process-wide jit compile counters (a Registry provider)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.calls = 0                  # hit-path calls, approximate
        self._misses_total = 0
        self._by_phase: dict[str, int] = {}
        self._events: list[dict] = []
        self._cost_by_phase: dict[str, dict] = {}

    def watch(self, phase: str, fn, cost_phase: str | None = None,
              donate=()) -> _WatchedFn:
        """Wrap a jitted callable; `phase` labels its miss events,
        `cost_phase` maps its cost analysis onto a Stats phase key
        ("FACT"/"SOLVE"/"FUSED")."""
        return _WatchedFn(fn, self, phase, cost_phase, donate)

    def record_miss(self, *, phase: str, sig, wall_s: float,
                    cache_size, cache_size_before, cost,
                    cost_phase, donated) -> None:
        attrib = _sig_attrib(sig)
        ev = dict(phase=phase, wall_s=round(wall_s, 6),
                  cache_size=cache_size, donated=list(donated),
                  **attrib)
        if cost:
            ev["cost"] = cost
        with self._lock:
            self._misses_total += 1
            self._by_phase[phase] = self._by_phase.get(phase, 0) + 1
            if len(self._events) < _EVENT_CAP:
                self._events.append(ev)
            if cost and cost_phase:
                self._cost_by_phase[cost_phase] = dict(cost)
        # a compile event in the same trace as the phase spans: the
        # wall here covers trace+compile+first run of the new
        # signature (the user-visible warmup cost of the miss)
        _tracer.complete(
            f"xla_compile:{phase}", wall_s, cat="compile",
            args={"phase": phase, "shapes": attrib["shapes"],
                  "dtypes": attrib["dtypes"],
                  "static": attrib["static"],
                  "donated": list(donated),
                  "cache_size": cache_size})

    # -- readers -------------------------------------------------------

    def misses(self, phase: str | None = None) -> int:
        with self._lock:
            if phase is None:
                return self._misses_total
            return self._by_phase.get(phase, 0)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "calls": self.calls,
                "misses": self._misses_total,
                "by_phase": dict(self._by_phase),
                "cost_by_phase": {k: dict(v) for k, v in
                                  self._cost_by_phase.items()},
                "recent": [dict(e) for e in self._events[-8:]],
            }


# the process-wide instance every watched jit reports into
COMPILE_WATCH = CompileWatch()


# thread-local hand-off of an executed program's cost between the
# backend call site (ops/batched.py, parallel closures) and the Stats
# consumer (models/gssvx.py).  The cost must NOT ride the shared LU
# handle: two threads solving through one cached factorization (the
# serve layer's whole design) would cross-attribute programs — thread
# B's 1-wide stamp read back by thread A's 64-wide solve.  The stamp
# and read happen on the same thread within one driver call, so a
# thread-local slot is exact.
_TLS = threading.local()


def stamp_cost(kind: str, cost: dict | str | None) -> None:
    """Record the just-dispatched program's cost ("factor"/"solve": a
    dict or None) for this thread's in-flight driver call; under
    "pack", where the miss path of `ops/trisolve.get_packs` was taken
    ("at_factor" / "at_solve")."""
    setattr(_TLS, kind, cost)


def take_cost(kind: str) -> dict | str | None:
    """Pop this thread's stamp.  Popping (not peeking) means a
    backend path that stamps nothing — host, staged, dist solve —
    reads None instead of a stale earlier program's numbers."""
    c = getattr(_TLS, kind, None)
    if c is not None:
        setattr(_TLS, kind, None)
    return c


def watch_jit(phase: str, fn, cost_phase: str | None = None,
              donate=()) -> _WatchedFn:
    return COMPILE_WATCH.watch(phase, fn, cost_phase, donate)
