"""Request-scoped flight recorder: one structured record per serve
request, from admission to outcome.

PR 3 built the process-global spine (spans, compile telemetry,
health); this module adds the PER-REQUEST story the serve layer was
missing: a p99 outlier, a `DegradedResult`, or a tier berr-guard
block can now be traced back to the request that produced it.  Every
`SolveService` request gets a monotonic request ID (rid) and a
`FlightRecord` that accumulates stage events as the request crosses
the pipeline:

  admit -> cache (hit / miss / pattern_hit / single_flight_wait /
  store_hit / retry / breaker_open / poisoned) -> tier/degraded
  routing -> queue (wait, batch id, bucket, occupancy) -> solve ->
  refine (berr, steps) -> outcome

plus every resilience event that touches it (retry attempts, breaker
state, degraded cover, flusher death, transparent resubmit).  Records
land in a bounded ring exported via `obs.snapshot()["flight"]` and,
with `SLU_FLIGHT_JSONL=<path>`, as one JSON line per retained record
(`tools/trace_export.py` renders those as per-request Perfetto
tracks, one pid per request).

Retention: the ring keeps every non-`ok` record (the traceability
contract: a failure is always one lookup away) and 1-in-`sample` of
the `ok` ones (`SLU_FLIGHT_SAMPLE`, default 1 = all, ring-bounded by
`SLU_FLIGHT_RING`).

Gating contract (the serve analog of the tracer's): `SLU_FLIGHT=1`
(or a programmatic `configure(enabled=True)`) turns the recorder on;
off, every entry point is ONE module-global pointer check — the serve
request path grows zero work (pinned by tests/test_flight.py).

Threading model: the submitting thread owns the record through
routing (a thread-local set by SolveService around `_route`); the
batcher's flusher thread appends the queue/solve/refine events
through the per-request handle it carried in, plus a thread-local
batch list (`batch_begin`/`batch_event`) so per-BATCH observations
(refine berr, tier-guard blocks) fan out to every request in the
dispatch.  Event appends are GIL-atomic list appends; retention and
the JSONL sink serialize on the recorder lock.
"""

from __future__ import annotations

import binascii
import collections
import itertools
import json
import os
import threading
import time

from .. import flags
from . import tracer as _tracer

# --------------------------------------------------------------------
# replica identity: pid + boot nonce
# --------------------------------------------------------------------
# rids are allocated by a per-process lock-free counter, so two
# REPLICAS of one service emit colliding rids into any shared sink
# (a fleet SLU_FLIGHT_JSONL, the drill's merged trace).  Every record
# therefore carries a replica id — pid plus a boot nonce, because
# pids recycle across restarts and a restarted replica's rid 1 must
# not alias its predecessor's.  (replica, rid) is the fleet-unique
# request id; tools/trace_export.py groups per-replica on it.

_REPLICA_ID: str | None = None
_replica_lock = threading.Lock()


def replica_id() -> str:
    """This process's replica id, minted once per boot:
    '<pid-hex>-<nonce>'.  Stable for the process lifetime; distinct
    across restarts even when the pid recycles."""
    global _REPLICA_ID
    if _REPLICA_ID is None:
        with _replica_lock:
            if _REPLICA_ID is None:
                nonce = binascii.hexlify(os.urandom(3)).decode()
                _REPLICA_ID = f"{os.getpid():x}-{nonce}"
    return _REPLICA_ID

# outcome -> the pipeline stage that failed it (the coarse map; the
# record's event list is the fine-grained story).  "ok" has no
# failing stage.
FAILED_STAGE = {
    "rejected": "admit",
    "miss_failfast": "cache",
    "poisoned": "factor",
    "degraded": "factor",       # the REFACTORIZATION failed; the
                                # degraded solve itself succeeded
    "flusher_dead": "batch",
    "stale_rejected": "solve",  # the stream berr guard withheld the
                                # result (stale-factor drift)
    "deadline": "queue",
    "serve_error": "serve",
    "error": "serve",
}


class FlightRecord:
    """One request's structured trajectory.  Event appends are
    lock-free (GIL-atomic); finish() is routed through the recorder
    for retention and is idempotent."""

    __slots__ = ("rid", "t0_ns", "t0_us", "meta", "events", "outcome",
                 "error", "failed_stage", "e2e_us", "_recorder",
                 "_done")

    def __init__(self, rid: int, recorder: "FlightRecorder",
                 meta: dict | None = None) -> None:
        self.rid = rid
        self._recorder = recorder
        self.t0_ns = time.perf_counter_ns()
        # epoch-relative so flight events and tracer spans share one
        # timeline (the recorder adopts the live tracer's epoch)
        self.t0_us = (self.t0_ns - recorder.epoch_ns) // 1000
        self.meta = dict(meta) if meta else {}
        self.events: list[dict] = []
        self.outcome: str | None = None
        self.error: str | None = None
        self.failed_stage: str | None = None
        self.e2e_us: int | None = None
        self._done = False

    def event(self, stage: str, **fields) -> None:
        # the kwargs dict IS the event (one dict per event, no copy)
        fields["stage"] = stage
        fields["t_us"] = (time.perf_counter_ns() - self.t0_ns) // 1000
        self.events.append(fields)

    def annotate(self, **meta) -> None:
        """Late meta (n, dtype, pattern — known only after routing)."""
        self.meta.update(meta)

    def finish(self, outcome: str, error: BaseException | str | None
               = None, stage: str | None = None,
               e2e_s: float | None = None) -> None:
        self._recorder.finish(self, outcome, error=error, stage=stage,
                              e2e_s=e2e_s)

    def to_dict(self) -> dict:
        return {"rid": self.rid, "replica": replica_id(),
                "t0_us": self.t0_us,
                "e2e_us": self.e2e_us, "outcome": self.outcome,
                "error": self.error,
                "failed_stage": self.failed_stage,
                "meta": dict(self.meta),
                "events": [dict(e) for e in self.events]}


class FlightRecorder:
    """Bounded ring of per-request records + the JSONL sink (a
    Registry provider)."""

    def __init__(self, ring: int = 256, sample: int = 1,
                 jsonl_path: str | None = None) -> None:
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self.sample = max(1, int(sample))
        # lock-free id allocation (itertools.count.__next__ is
        # GIL-atomic): start() runs on EVERY submitting thread and
        # must not serialize them on the recorder lock — measured as
        # the dominant flight-on cost under concurrency 16 before
        # this; the lock now guards only finish-time retention
        self._rid = itertools.count(1)
        self._batch = itertools.count(1)
        self._fin = itertools.count(1)
        self._ret = itertools.count(1)
        self._outcome_counters: dict = {}
        self.started = 0       # highest rid issued (atomic store)
        self.finished = 0
        self.retained = 0
        self.by_outcome: dict[str, int] = {}
        self._jsonl_path = jsonl_path
        self._jsonl_file = None
        self._jsonl_error: str | None = None
        t = _tracer.get_tracer()
        # share the tracer's timeline when one is live, so a flight
        # record's t0_us lands where its spans do in the merged view
        self.epoch_ns = (t._epoch_ns if t is not None
                         else time.perf_counter_ns())

    # -- request lifecycle --------------------------------------------

    def start(self, **meta) -> FlightRecord:
        rid = next(self._rid)
        self.started = rid          # dense rids: last issued == count
        return FlightRecord(rid, self, meta=meta or None)

    def next_batch_id(self) -> int:
        return next(self._batch)

    def finish(self, rec: FlightRecord, outcome: str,
               error: BaseException | str | None = None,
               stage: str | None = None,
               e2e_s: float | None = None) -> None:
        """`e2e_s` is the caller-stamped latency (the service's
        done-callback stamps it so deferred finalization does not
        inflate it); None = stamp now.

        LOCK-FREE on the common path: finalizations drain on every
        submitting thread concurrently, and serializing them on the
        recorder lock measurably cut serve throughput.  Each record
        is finished by exactly one thread (the deque hands it out
        once; sync aborts never register the callback), deque.append
        and dict.setdefault are GIL-atomic, and the counters are
        monotonic gauges — only the JSONL sink still takes the lock
        (shared file handle)."""
        if rec._done:
            return
        rec._done = True
        rec.outcome = outcome
        if error is not None:
            rec.error = (error if isinstance(error, str)
                         else f"{type(error).__name__}: {error}")
        rec.failed_stage = (stage if stage is not None
                            else FAILED_STAGE.get(outcome))
        rec.e2e_us = (int(e2e_s * 1e6) if e2e_s is not None else
                      (time.perf_counter_ns() - rec.t0_ns) // 1000)
        self.finished = next(self._fin)
        c = self._outcome_counters.get(outcome)
        if c is None:
            c = self._outcome_counters.setdefault(
                outcome, itertools.count(1))
        self.by_outcome[outcome] = next(c)
        if outcome != "ok" or (rec.rid - 1) % self.sample == 0:
            self.retained = next(self._ret)
            self._ring.append(rec)
            if self._jsonl_path is not None:
                with self._lock:
                    self._write_jsonl(rec)
        # span/trace linkage: the merged Perfetto view gets one
        # retrospective per-request span carrying the rid (only when
        # BOTH the tracer and the recorder are on; guarded so the
        # tracer-off path builds no args)
        if _tracer.get_tracer() is not None:
            _tracer.complete(f"request.{outcome}", rec.e2e_us / 1e6,
                             cat="flight",
                             args={"rid": rec.rid,
                                   "failed_stage": rec.failed_stage})

    def _write_jsonl(self, rec: FlightRecord) -> None:
        # self-disabling on I/O error, like the tracer's sink:
        # observability must never throw into the serve path
        if self._jsonl_path is None:
            return
        try:
            if self._jsonl_file is None:
                self._jsonl_file = open(self._jsonl_path, "a")
            self._jsonl_file.write(json.dumps(rec.to_dict()) + "\n")
            self._jsonl_file.flush()
        except Exception as e:
            self._jsonl_path = None
            self._jsonl_error = repr(e)

    def close(self) -> None:
        with self._lock:
            self._jsonl_path = None
            if self._jsonl_file is not None:
                self._jsonl_file.close()
                self._jsonl_file = None

    # -- readers -------------------------------------------------------
    # every reader first runs the registered drain hooks: services
    # DEFER per-request finalization off their flusher threads, so a
    # read outside the request flow must flush it to see the tail

    def records(self) -> list[dict]:
        run_drain_hooks()
        with self._lock:
            return [r.to_dict() for r in self._ring]

    def lookup(self, rid: int) -> dict | None:
        run_drain_hooks()
        with self._lock:
            for r in reversed(self._ring):
                if r.rid == rid:
                    return r.to_dict()
        return None

    def snapshot(self) -> dict:
        run_drain_hooks()
        with self._lock:
            recs = [r.to_dict() for r in self._ring]
            return {"enabled": True,
                    "replica": replica_id(),
                    "started": self.started,
                    "finished": self.finished,
                    "retained": self.retained,
                    "ring": len(recs),
                    "sample": self.sample,
                    "by_outcome": dict(self.by_outcome),
                    "jsonl_error": self._jsonl_error,
                    "records": recs}


# --------------------------------------------------------------------
# module-level gate: the one pointer the serve request path reads
# --------------------------------------------------------------------

_recorder: FlightRecorder | None = None
_tls = threading.local()
_lock = threading.Lock()
# weakly-held callables that flush deferred finalizations (each
# SolveService registers its _drain_observability); run by recorder
# and SLO readers so out-of-band snapshots see completed requests
_drain_hooks: list = []


def register_drain_hook(method) -> None:
    """Register a bound method (held weakly) to run before
    flight/SLO reads.  Dead references self-clean."""
    import weakref
    with _lock:
        _drain_hooks.append(weakref.WeakMethod(method))


def run_drain_hooks() -> None:
    if not _drain_hooks:
        return
    with _lock:
        hooks = list(_drain_hooks)
    for ref in hooks:
        fn = ref()
        if fn is None:
            with _lock:
                try:
                    _drain_hooks.remove(ref)
                except ValueError:
                    pass
            continue
        try:
            fn()
        except Exception:
            pass           # observability reads must never throw


def _env_enabled() -> bool:
    v = flags.env_opt("SLU_FLIGHT")
    if v is not None:
        return v not in ("", "0")
    # a JSONL sink path implies the recorder, like SLU_TRACE_JSONL
    return bool(flags.env_opt("SLU_FLIGHT_JSONL"))


def configure(enabled: bool | None = None, ring: int | None = None,
              sample: int | None = None,
              jsonl_path: str | None = None) -> FlightRecorder | None:
    """(Re)configure the global recorder.  With no arguments, re-reads
    SLU_FLIGHT / SLU_FLIGHT_RING / SLU_FLIGHT_SAMPLE /
    SLU_FLIGHT_JSONL.  Returns the active recorder (None when off)."""
    global _recorder
    from .registry import REGISTRY
    with _lock:
        if enabled is None:
            enabled = _env_enabled()
        if ring is None:
            ring = flags.env_int("SLU_FLIGHT_RING", 256)
        if sample is None:
            sample = flags.env_int("SLU_FLIGHT_SAMPLE", 1)
        if jsonl_path is None:
            jsonl_path = flags.env_opt("SLU_FLIGHT_JSONL") or None
        old = _recorder
        if old is not None:
            old.close()
            REGISTRY.unregister("flight", old)
        if not enabled:
            _recorder = None
            return None
        _recorder = FlightRecorder(ring=ring, sample=sample,
                                   jsonl_path=jsonl_path)
        REGISTRY.register("flight", _recorder)
        return _recorder


def enabled() -> bool:
    return _recorder is not None


def get_recorder() -> FlightRecorder | None:
    return _recorder


def start(**meta) -> FlightRecord | None:
    """New per-request record, or None when the recorder is off (the
    ONE flag check the off-path pays)."""
    r = _recorder
    if r is None:
        return None
    return r.start(**meta)


def set_current(rec: FlightRecord | None) -> None:
    """Bind `rec` as the submitting thread's current record so code
    that cannot carry a handle (factor cache, breaker, retry) can
    reach it via current()."""
    if _recorder is not None or getattr(_tls, "rec", None) is not None:
        _tls.rec = rec


def current() -> FlightRecord | None:
    if _recorder is None:
        return None
    return getattr(_tls, "rec", None)


def event(stage: str, **fields) -> None:
    """Append a stage event to the submitting thread's current record
    (no-op when off or unbound) — the factor cache / resilience hook."""
    rec = current()
    if rec is not None:
        rec.event(stage, **fields)


def next_batch_id() -> int | None:
    r = _recorder
    return r.next_batch_id() if r is not None else None


def batch_begin(records) -> None:
    """Bind the flusher thread's active dispatch: per-batch
    observations (refine berr, guard blocks) fan out to every
    request's record via batch_event()."""
    if _recorder is not None:
        _tls.batch = [r for r in records if r is not None]


def batch_event(stage: str, **fields) -> None:
    if _recorder is None:
        return
    for rec in getattr(_tls, "batch", ()) or ():
        rec.event(stage, **fields)


def batch_end() -> None:
    if getattr(_tls, "batch", None):
        _tls.batch = ()


def snapshot() -> dict:
    r = _recorder
    return r.snapshot() if r is not None else {"enabled": False}


# resolve the env gate once at import; tests reconfigure explicitly
configure()
