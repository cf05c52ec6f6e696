"""Thread-safe span tracer: nested phase spans → Chrome trace JSON.

The one telemetry spine for "where did this solve's time go": every
`Stats.timer` phase (EQUIL → … → FACT → SOLVE → REFINE), the serve
pipeline's queue/assemble/batch/solve stages, and the compile watcher's
jit-miss events all land here as trace events in the Chrome
trace-event format (`ph`/`ts`/`dur`/`pid`/`tid` — the schema Perfetto
and `chrome://tracing` load natively; `tools/trace_export.py` is the
export/validate CLI).

Gating contract (the near-zero-overhead-when-off requirement, pinned
by tests/test_obs_trace.py):

  * `SLU_OBS=1` enables the tracer; `SLU_OBS=0` force-disables it.
  * `SLU_TRACE=<path|1>` implies SLU_OBS and additionally exports the
    Chrome trace JSON at process exit (`1` → ./last.trace.json).
  * `SLU_TRACE_JSONL=<path>` implies SLU_OBS and write-through-appends
    one JSON event per line as spans close (the event log twin).

When disabled, `span()` returns a single reusable no-op context
manager — one module-global read, one `is_enabled()` probe and an
identity return per call, no allocation, no lock.  When enabled, a
span costs two `perf_counter_ns` reads, one small dict and one lock
acquisition at close.  The in-memory buffer is capped (`_EVENT_CAP`);
past it new events are counted as dropped instead of growing without
bound under sustained serve traffic.

Second sink, no flag: while a `jax.profiler` session is live
(`start_trace` … `stop_trace`, whoever started it), `span()` and
`instant()` also write `slu.<name>` into the profiler's own trace, on
the clock the device operations are recorded on, with `args` as the
event's stats.  The session is the switch.  `complete()` (a span
whose start predates the call) cannot be written there and stays with
the SLU_OBS tracer, as does everything `enabled()` gates.
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time

from jax.profiler import TraceAnnotation

from .. import flags


_EVENT_CAP = 262144


class _NullSpan:
    """Reusable, reentrant no-op context manager (the disabled path)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _BothSpan:
    """One call site, both sinks: the profiler's annotation outside,
    the SLU_OBS span inside."""

    __slots__ = ("_annotation", "_span")

    def __init__(self, annotation, span):
        self._annotation = annotation
        self._span = span

    def __enter__(self):
        self._annotation.__enter__()
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._annotation.__exit__(*exc)
        return False


class _Span:
    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_depth")

    def __init__(self, tracer, name, cat, args):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args

    def __enter__(self):
        tls = self._tracer._tls
        self._depth = getattr(tls, "depth", 0)
        tls.depth = self._depth + 1
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        tr = self._tracer
        tr._tls.depth = self._depth
        args = dict(self._args) if self._args else {}
        args["depth"] = self._depth
        tr._emit({
            "name": self._name,
            "cat": self._cat,
            "ph": "X",
            "ts": (self._t0 - tr._epoch_ns) // 1000,
            "dur": max(0, (t1 - self._t0) // 1000),
            "pid": tr._pid,
            "tid": threading.get_ident(),
            "args": args,
        })
        return False


class Tracer:
    """Collects trace events; exports Chrome trace JSON and/or a JSONL
    event log.  All mutation is behind one lock; span timing itself is
    lock-free (the lock is taken only to append the finished event)."""

    def __init__(self, jsonl_path: str | None = None) -> None:
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._dropped = 0
        self._tls = threading.local()
        self._pid = os.getpid()
        self._epoch_ns = time.perf_counter_ns()
        self._jsonl_path = jsonl_path
        self._jsonl_file = None
        self._jsonl_error: str | None = None

    # -- recording -----------------------------------------------------

    def span(self, name: str, cat: str = "phase", args: dict | None = None):
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "event",
                args: dict | None = None) -> None:
        self._emit({
            "name": name, "cat": cat, "ph": "i",
            "ts": self._now_us(), "pid": self._pid,
            "tid": threading.get_ident(), "s": "t",
            "args": dict(args) if args else {},
        })

    def complete(self, name: str, duration_s: float, cat: str = "phase",
                 args: dict | None = None) -> None:
        """Retrospective span ending now and lasting `duration_s` —
        for stages whose start predates the call site (e.g. the serve
        queue wait, stamped when the batch is assembled)."""
        dur_us = max(0, int(duration_s * 1e6))
        self._emit({
            "name": name, "cat": cat, "ph": "X",
            "ts": self._now_us() - dur_us, "dur": dur_us,
            "pid": self._pid, "tid": threading.get_ident(),
            "args": dict(args) if args else {},
        })

    def _now_us(self) -> int:
        return (time.perf_counter_ns() - self._epoch_ns) // 1000

    def _emit(self, ev: dict) -> None:
        with self._lock:
            # the JSONL sink is the UNBOUNDED streaming twin: it keeps
            # recording (and flushes per line, so a tail -f consumer
            # sees events as they close) even after the in-memory
            # buffer hits its cap.  A sink I/O failure (bad path,
            # disk full) DISABLES the sink instead of propagating:
            # observability must never throw into the numeric hot
            # path or kill the serve flusher thread
            if self._jsonl_path is not None:
                try:
                    if self._jsonl_file is None:
                        self._jsonl_file = open(self._jsonl_path, "a")
                    self._jsonl_file.write(json.dumps(ev) + "\n")
                    self._jsonl_file.flush()
                except Exception as e:
                    self._jsonl_path = None
                    self._jsonl_error = repr(e)
            if len(self._events) >= _EVENT_CAP:
                self._dropped += 1
                return
            self._events.append(ev)

    # -- reading / export ----------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def export_chrome(self, path: str) -> str:
        """Write the Chrome trace-event JSON (Perfetto-loadable)."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "superlu_dist_tpu.obs",
                          "dropped_events": dropped},
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path

    def close(self) -> None:
        with self._lock:
            # null the path too: a straggler span closing after close()
            # (the serve flusher mid-batch) must not resurrect the sink
            # by reopening a file nobody will ever close again
            self._jsonl_path = None
            if self._jsonl_file is not None:
                self._jsonl_file.close()
                self._jsonl_file = None

    def snapshot(self) -> dict:
        """Registry provider view: event counts + per-name wall."""
        # copy under the lock, aggregate outside it — the O(events)
        # walk must not stall _emit (every span-closing thread) while
        # a metrics dump runs
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
            jsonl_error = self._jsonl_error
        by_name: dict[str, dict] = {}
        for ev in events:
            if ev.get("ph") != "X":
                continue
            rec = by_name.setdefault(ev["name"],
                                     {"count": 0, "total_us": 0})
            rec["count"] += 1
            rec["total_us"] += ev.get("dur", 0)
        return {"events": len(events),
                "dropped": dropped,
                "jsonl_error": jsonl_error,
                "spans": by_name}


# --------------------------------------------------------------------
# module-level gate: the one pointer the hot path reads
# --------------------------------------------------------------------

_tracer: Tracer | None = None
_trace_path: str | None = None
_atexit_registered = False
_lock = threading.Lock()


def resolve_trace_path() -> str | None:
    v = flags.env_str("SLU_TRACE")
    if v in ("", "0"):
        return None
    return "last.trace.json" if v == "1" else v


def _env_enabled() -> bool:
    obs = flags.env_opt("SLU_OBS")
    if obs is not None:
        return obs not in ("", "0")
    return (resolve_trace_path() is not None
            or bool(flags.env_opt("SLU_TRACE_JSONL")))


def configure(enabled: bool | None = None,
              trace_path: str | None = None,
              jsonl_path: str | None = None) -> Tracer | None:
    """(Re)configure the global tracer.  With no arguments, re-reads
    the SLU_OBS / SLU_TRACE / SLU_TRACE_JSONL environment.  Returns
    the active tracer (None when disabled)."""
    global _tracer, _trace_path
    with _lock:
        if enabled is None:
            enabled = _env_enabled()
        if trace_path is None:
            trace_path = resolve_trace_path()
        if jsonl_path is None:
            jsonl_path = flags.env_opt("SLU_TRACE_JSONL") or None
        old = _tracer
        if old is not None:
            old.close()
        if not enabled:
            _tracer, _trace_path = None, None
            return None
        _tracer = Tracer(jsonl_path=jsonl_path)
        _trace_path = trace_path
        if trace_path is not None or jsonl_path is not None:
            # either sink needs the exit hook: the chrome export for
            # SLU_TRACE, the close() for a JSONL-only config
            _register_atexit()
        return _tracer


def _register_atexit() -> None:
    global _atexit_registered
    if not _atexit_registered:
        _atexit_registered = True
        atexit.register(_atexit_export)


def _atexit_export() -> None:
    t, path = _tracer, _trace_path
    if t is None:
        return
    try:
        if path is not None:
            t.export_chrome(path)
    except Exception as e:
        # never traceback at interpreter exit over a lost trace —
        # one stderr line is the most an export failure gets
        print(f"slu.obs: trace export to {path} failed: {e!r}",
              file=sys.stderr)
    finally:
        t.close()      # a JSONL-only config still needs the close


def enabled() -> bool:
    return _tracer is not None


def get_tracer() -> Tracer | None:
    return _tracer


def _annotation(name: str, args: dict | None):
    return (TraceAnnotation("slu." + name, **args) if args
            else TraceAnnotation("slu." + name))


def span(name: str, cat: str = "phase", args: dict | None = None):
    """The ONE hot-path entry: a context manager that is a shared
    no-op singleton when nothing is listening, the profiler's
    `slu.<name>` annotation while a profiler session is live, the
    SLU_OBS span when the tracer is on, and both when both."""
    t = _tracer
    if TraceAnnotation.is_enabled():
        a = _annotation(name, args)
        return a if t is None else _BothSpan(a, t.span(name, cat, args))
    if t is None:
        return NULL_SPAN
    return t.span(name, cat, args)


def instant(name: str, cat: str = "event", args: dict | None = None) -> None:
    if TraceAnnotation.is_enabled():
        # the profiler has no instant event: an empty span marks it
        with _annotation(name, args):
            pass
    t = _tracer
    if t is not None:
        t.instant(name, cat, args)


def complete(name: str, duration_s: float, cat: str = "phase",
             args: dict | None = None) -> None:
    t = _tracer
    if t is not None:
        t.complete(name, duration_s, cat, args)


def export_trace(path: str | None = None) -> str | None:
    """Export the Chrome trace now (default: the SLU_TRACE path)."""
    t = _tracer
    p = path or _trace_path
    if t is None or p is None:
        return None
    return t.export_chrome(p)


# resolve the env gate once at import; tests re-resolve via configure()
configure()
