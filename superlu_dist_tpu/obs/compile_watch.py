"""The start-up ledger: which jitted programs were new, when, and
where their start-up seconds went.

A GESP solver's serving story rests on "the jitted programs never
recompile after warmup" (serve/batcher.py's bucket ladder exists for
exactly this); this module is the instrument that PROVES it, and the
one record of what a start costs.  Every whole-phase jitted program
(`ops/batched._phase_fns`, the fused-solver builders, the dist
factor/solve closures) is wrapped in `watch()`: a per-wrapper
signature table detects the first call with a new (shape, dtype,
static-arg) signature — a jit cache miss — counts it with full
attribution, confirms against the jit's own `_cache_size()` when
available, and emits an `xla_compile:<phase>` event into the span
tracer.

The process-wide `COMPILE_WATCH` also listens to `jax.monitoring`
(registered once, below).  jax fires those events on the calling
thread and only when a program is new, so nothing here runs on a warm
call.  Every new program of the process leaves one row:

    name     jax's `fun_name`, `jit(...)` stripped
    watched  the `watch_jit` label, or None (set-up's eager
             operations, `build_schedule`'s bucket grid)
    t0       first instant of the row, on `time.perf_counter()`
    trace_s, lower_s   tracing to a jaxpr, lowering to StableHLO
    compile_s          a true backend compile
    load_s             the backend-compile span of a program the
                       persistent cache served (its retrieval)
    saved_s  jax's `compile_time_saved_sec` (can be negative)
    cache    "hit" | "miss" | "off" (no request reached the cache);
             None for a row that was traced and never compiled
    aot      what the exported-program store (resilience/aot.py) did
             for the program: "hit" (deserialized: `trace_s` and
             `lower_s` are then the wrapper's, not the program's),
             "miss" (exported and saved now), "refused" (an entry
             failed verification: quarantined, re-exported),
             "unexportable" (fell back to the plain jit) or "off"
             (the store is off, or the program is not one it wraps)
    thread   ident of the compiling thread
    spans    the (kind, start, end) intervals the seconds are the
             union of, so that a reader can union ACROSS rows

Seconds are unions of intervals: the traces nested in a program
(`matmul` inside `slu_factor`) fire their own events inside the outer
one and must not be summed.  The cache events carry no name; they are
tied to a program by thread and order (they fire inside its
backend-compile span).  An unwatched row closes at its backend-compile
event.  A watched miss opens the row before the call and closes it
after, so every event of that call, nested ones included, falls to it;
what its wall holds beyond the intervals is `first_call_other_s`
(argument hand-out and the dispatch; the execution is asynchronous and
is not in it).  jax stamps its spans with `time.time()`; they are
moved once onto `perf_counter`'s clock by the offset taken at
registration (the header says so).

The plan's and the schedule's phases (`record_phases`) share the
ledger, so one reader sees a start from the ordering to the last
cache load.  `ledger(since, until)` is the readers' view.

The hit path costs one signature build (a few tuple allocations over
the argument list) and two dict reads — noise against the ms-scale
dispatches it wraps, and pinned by the SLU_OBS=0 overhead test.
"""

from __future__ import annotations

import threading
import time

import jax

from . import tracer as _tracer


_EVENT_CAP = 1024
_ROW_CAP = 4096

# jax.monitoring's names for the three spans of a new program
_KIND = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_KINDS = ("trace", "lower", "compile", "load")
_FOLD_KEYS = ("count",) + tuple(k + "_s" for k in _KINDS)
_COLD = {"miss": 2, "off": 1, "hit": 0}     # the worst one names a row
_TOTAL = {"hit": "cache_hits", "miss": "cache_misses", "off": "cache_off"}


def merge(intervals) -> list:
    """(start, end) intervals with every overlap joined, in order."""
    out: list = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1] = (out[-1][0], t1)
        else:
            out.append((t0, t1))
    return out


def union_s(intervals) -> float:
    """Seconds covered by (start, end) intervals, overlaps once."""
    return sum(t1 - t0 for t0, t1 in merge(intervals))


def _seconds(spans) -> dict:
    """trace_s, lower_s, compile_s, load_s of a row's joined spans."""
    out = {k + "_s": 0.0 for k in _KINDS}
    for kind, a, b in spans:
        out[kind + "_s"] += b - a
    return out


def _other_s(wall_s: float, spans) -> float:
    """What a watched first call's wall holds beyond its spans."""
    return max(0.0, wall_s - union_s([(a, b) for _k, a, b in spans]))


def _bare(fun_name: str) -> str:
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class _Open:
    """The row a thread is writing: its spans so far, and what the
    cache said inside the backend-compile span that has not ended."""

    __slots__ = ("watched", "outer", "spans", "name", "name_s", "cache",
                 "saved_s", "request", "hit", "aot")

    def __init__(self, watched, outer=None):
        self.watched = watched
        self.outer = outer          # the row a nested watched miss hid
        self.spans: list = []       # (kind, start, end, traced name),
        self.name = None            # on time.time()'s clock
        self.name_s = -1.0          # seconds of the span that named it
        self.cache = None
        self.saved_s = 0.0
        self.request = self.hit = False
        self.aot = "off"


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
                 donate=()):
        self._fn = fn
        self._watch = watch
        self._phase = phase
        self._donate = tuple(donate)
        self._seen: dict = {}
        self._miss_lock = threading.Lock()

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
        row = self._watch.open_row(self._phase)
        t0 = time.perf_counter()
        try:
            out = self._fn(*args, **kwargs)
        except BaseException:
            # the claim must not survive a failed first call: the
            # retry that actually compiles still counts as the miss
            with self._miss_lock:
                self._seen.pop(sig, None)
            self._watch.close_row(row, t0, time.perf_counter() - t0)
            raise
        wall = time.perf_counter() - t0
        split = self._watch.close_row(row, t0, wall)
        self._watch.record_miss(
            phase=self._phase, sig=sig, wall_s=wall, split=split,
            cache_size=self._cache_size_safe(),
            cache_size_before=before, donated=self._donate)
        return out

    def _cache_size_safe(self):
        try:
            return int(self._fn._cache_size())
        except Exception:
            return None

    def __getattr__(self, name):
        return getattr(self._fn, name)


class CompileWatch:
    """Process-wide jit compile counters and the start-up ledger (a
    Registry provider)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.calls = 0                  # hit-path calls, approximate
        self._misses_total = 0
        self._by_phase: dict[str, int] = {}
        self._events: list[dict] = []
        # -- the ledger --
        self._open: dict[int, _Open] = {}     # thread ident -> its row
        self._rows: list[tuple] = []
        self._phases: list[tuple] = []        # (name, t0, seconds)
        self._folded: dict[str, list] = {}    # rows past _ROW_CAP
        self._header: dict | None = None
        self._to_perf = 0.0     # perf_counter() - time.time()
        self._self_s = 0.0      # the listeners' own seconds
        self._listener_calls = 0
        self._totals = dict.fromkeys(("programs", *_TOTAL.values()), 0)
        self._totals.update({k + "_s": 0.0 for k in _KINDS})

    def watch(self, phase: str, fn, donate=()) -> _WatchedFn:
        """Wrap a jitted callable; `phase` labels its miss events and
        its rows of the ledger."""
        return _WatchedFn(fn, self, phase, donate)

    # -- jax.monitoring ------------------------------------------------

    def listen(self) -> None:
        """Register the three listeners (the process-wide instance
        does, once, below).  jax calls them on the compiling thread
        and only for a program that is new."""
        self._to_perf = time.perf_counter() - time.time()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _mine(self) -> _Open:
        tid = threading.get_ident()
        row = self._open.get(tid)
        if row is None:
            row = self._open[tid] = _Open(None)
        return row

    def _on_event(self, event, **_kw) -> None:
        if event == _CACHE_REQUEST:
            self._mine().request = True
        elif event == _CACHE_HIT:
            self._mine().hit = True
        else:
            return
        self._listener_calls += 1

    def _on_duration(self, event, secs, **_kw) -> None:
        if event == _CACHE_SAVED:
            self._mine().saved_s += secs
            self._listener_calls += 1

    def _on_span(self, event, start, end, fun_name="", **_kw) -> None:
        kind = _KIND.get(event)
        if kind is None:
            return
        t_in = time.perf_counter()
        row = self._mine()
        spans = row.spans
        if kind == "trace":
            # a factor program fires some 27,000 of these.  The traces
            # nested in this one ended before it and lie inside it
            # (the outermost ends last): they are dropped here, so the
            # list stays short, and the last one left names a row that
            # never compiles
            while spans and spans[-1][0] == "trace" \
                    and spans[-1][1] >= start:
                spans.pop()
            spans.append(("trace", start, end, fun_name))
        else:
            if end - start > row.name_s:
                # a row takes the name of its longest lowering or
                # compile
                row.name, row.name_s = _bare(fun_name), end - start
            if kind == "compile":
                cfg = jax.config
                if not row.request \
                        or cfg.jax_compilation_cache_dir is None \
                        or not cfg.jax_enable_compilation_cache:
                    # jax asks its cache whenever caching is enabled,
                    # directory or not: without one nothing is there
                    cache = "off"
                else:
                    cache = "hit" if row.hit else "miss"
                if row.hit:
                    kind = "load"
                if row.cache is None or _COLD[cache] > _COLD[row.cache]:
                    row.cache = cache
                row.request = row.hit = False
            spans.append((kind, start, end, None))
            if kind != "lower" and row.watched is None:
                del self._open[threading.get_ident()]
                self._close(row)
        self._listener_calls += 1
        self._self_s += time.perf_counter() - t_in

    # -- rows ----------------------------------------------------------

    def open_row(self, watched: str) -> _Open:
        """A watched miss begins: until `close_row`, every event of
        this thread falls to its row.  What the thread had traced and
        never compiled (`eval_shape`, `lower()`) closes as a row of
        its own first."""
        tid = threading.get_ident()
        outer = self._open.get(tid)
        if outer is not None and outer.watched is None:
            self._close(outer)
            outer = None
        row = self._open[tid] = _Open(watched, outer)
        return row

    def stamp_aot(self, status: str) -> None:
        """`AotJit` resolved a new signature inside this thread's
        watched first call: the row it is writing says how."""
        row = self._open.get(threading.get_ident())
        if row is not None and row.watched is not None:
            row.aot = status

    def close_row(self, row: _Open, t0: float, wall_s: float) -> dict:
        t_in = time.perf_counter()
        tid = threading.get_ident()
        if row.outer is not None:
            self._open[tid] = row.outer
        else:
            self._open.pop(tid, None)
        split = self._close(row, t0, wall_s)
        self._self_s += time.perf_counter() - t_in
        return split

    def _close(self, row: _Open, t0=None, wall_s=None) -> dict:
        """Join the row's spans by kind (a factor program's trace
        holds thousands of nested ones) and append it.  Returns the
        split (seconds by kind, cache, first_call_other_s)."""
        by: dict[str, list] = {}
        off = self._to_perf         # onto perf_counter's clock, once
        traced = None
        for kind, a, b, fun_name in row.spans:
            by.setdefault(kind, []).append((a + off, b + off))
            traced = fun_name or traced
        spans = tuple((k, a, b) for k in _KINDS
                      for a, b in merge(by.get(k, ())))
        split = _seconds(spans)
        split["cache"] = row.cache
        split["saved_s"] = row.saved_s
        if t0 is None:
            t0 = min((a for _k, a, _b in spans), default=0.0)
        else:
            split["first_call_other_s"] = _other_s(wall_s, spans)
        name = row.name or traced or row.watched
        tup = (name, row.watched, t0, threading.get_ident(), row.cache,
               row.saved_s, wall_s, spans, row.aot)
        with self._lock:
            if self._header is None:
                self._header = self._make_header()
            tot = self._totals
            tot["programs"] += 1
            for k in _KINDS:
                tot[k + "_s"] += split[k + "_s"]
            if row.cache is not None:
                tot[_TOTAL[row.cache]] += 1
            if len(self._rows) < _ROW_CAP:
                self._rows.append(tup)
            else:
                f = self._folded.setdefault(name, [0] + [0.0] * 4)
                f[0] += 1
                for i, k in enumerate(_KINDS):
                    f[i + 1] += split[k + "_s"]
        return split

    def record_phases(self, t0: float, seconds: dict) -> None:
        """One phase record a name, at the END of a plan or schedule
        build (once a plan, never a step).  `seconds` is what the
        builder's `Stats.utime` gained, in the order the phases ran;
        `Stats.timer` keeps no start, so they are laid end to end from
        `t0`, the builder's own start: the sum is exact, a start is
        early by the untimed work before it."""
        with self._lock:
            if self._header is None:
                self._header = self._make_header()
            for name, s in seconds.items():
                if s > 0.0:
                    self._phases.append((name, t0, s))
                    t0 += s

    def _make_header(self) -> dict:
        cfg = jax.config
        return {
            "cache_dir": cfg.jax_compilation_cache_dir,
            "jax_persistent_cache_min_compile_time_secs":
                cfg.jax_persistent_cache_min_compile_time_secs,
            "jax_persistent_cache_min_entry_size_bytes":
                cfg.jax_persistent_cache_min_entry_size_bytes,
            "clock": "time.perf_counter(); jax's spans are "
                     "time.time() moved by the offset at registration",
            "clock_offset_s": self._to_perf,
            "row_cap": _ROW_CAP,
        }

    def record_miss(self, *, phase: str, sig, wall_s: float, split: dict,
                    cache_size, cache_size_before, donated) -> None:
        attrib = _sig_attrib(sig)
        ev = dict(phase=phase, wall_s=round(wall_s, 6),
                  cache_size=cache_size, donated=list(donated),
                  **attrib)
        with self._lock:
            self._misses_total += 1
            self._by_phase[phase] = self._by_phase.get(phase, 0) + 1
            if len(self._events) < _EVENT_CAP:
                self._events.append(ev)
        # a compile event in the same trace as the phase spans: the
        # wall here covers trace+compile+first dispatch of the new
        # signature (the user-visible warmup cost of the miss), and
        # the args say how it splits
        _tracer.complete(
            f"xla_compile:{phase}", wall_s, cat="compile",
            args={"phase": phase, "shapes": attrib["shapes"],
                  "dtypes": attrib["dtypes"],
                  "static": attrib["static"],
                  "donated": list(donated),
                  "cache_size": cache_size, **split})

    # -- readers -------------------------------------------------------

    def misses(self, phase: str | None = None) -> int:
        with self._lock:
            if phase is None:
                return self._misses_total
            return self._by_phase.get(phase, 0)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def ledger(self, since: float | None = None,
               until: float | None = None) -> dict:
        """Header, program rows and phases whose `t0` lies in
        [since, until) (perf_counter's clock; None is open)."""
        def inside(t0):
            return ((since is None or t0 >= since)
                    and (until is None or t0 < until))

        with self._lock:
            rows = [r for r in self._rows if inside(r[2])]
            phases = [p for p in self._phases if inside(p[1])]
            header = dict(self._header or {},
                          ledger_self_s=self._self_s,
                          listener_calls=self._listener_calls,
                          overflowed=bool(self._folded))
            folded = {name: dict(zip(_FOLD_KEYS, v))
                      for name, v in self._folded.items()}
        programs = []
        for (name, watched, t0, thread, cache, saved, wall, spans,
             aot) in rows:
            rec = {"name": name, "watched": watched, "t0": t0,
                   "thread": thread, "cache": cache, "aot": aot,
                   "saved_s": saved,
                   "spans": [list(s) for s in spans]}
            rec.update(_seconds(spans))
            if wall is not None:
                rec["wall_s"] = wall
                rec["first_call_other_s"] = _other_s(wall, spans)
            programs.append(rec)
        return {"header": header, "programs": programs,
                "phases": [{"name": n, "t0": t0, "seconds": s}
                           for n, t0, s in phases],
                "folded": folded}

    def snapshot(self) -> dict:
        from ..resilience import aot
        with self._lock:
            return {
                "calls": self.calls,
                "misses": self._misses_total,
                "by_phase": dict(self._by_phase),
                "startup": dict(self._totals,
                                ledger_self_s=self._self_s,
                                aot=aot.stats()),
            }


# the process-wide instance every watched jit reports into, and the
# one that listens to jax
COMPILE_WATCH = CompileWatch()
COMPILE_WATCH.listen()


# thread-local hand-off between a backend call site and the consumer
# of the same driver call: where `ops/trisolve.get_packs` took its
# miss, for the Stats of `models/gssvx.py`; what
# `ops/batched._staged_factor_run` dispatched, for `factorize_device`.
# A stamp must NOT ride the shared LU handle: two threads solving
# through one cached factorization (the serve layer's whole design)
# would read each other's.  The stamp and the read happen on the same
# thread within one driver call, so a thread-local slot is exact.
_TLS = threading.local()


def stamp_cost(kind: str, cost) -> None:
    """Record for this thread's in-flight driver call: kind "pack",
    where the miss path of `ops/trisolve.get_packs` was taken
    ("at_factor" / "at_solve"); kind "dispatch", the staged run's
    (programs dispatched, shapes of the members on the Pallas panel
    LU)."""
    setattr(_TLS, kind, cost)


def take_cost(kind: str):
    """Pop this thread's stamp.  Popping (not peeking) means a
    backend path that stamps nothing — host, staged, dist solve —
    reads None instead of a stale earlier call's."""
    c = getattr(_TLS, kind, None)
    if c is not None:
        setattr(_TLS, kind, None)
    return c


def watch_jit(phase: str, fn, donate=()) -> _WatchedFn:
    return COMPILE_WATCH.watch(phase, fn, donate)
