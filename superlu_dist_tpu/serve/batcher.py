"""RHS micro-batching: coalesce concurrent solves into one dispatch.

The triangular-solve path is a chain of O(#groups) small dispatches
whose cost is nearly flat in nrhs — pre-round chip record, not
re-measured: 59 ms at
nrhs=1 vs 8.3 ms/rhs at nrhs=64, a 7× amortization.  This is the
inference-server continuous-batching shape applied to RHS vectors:
concurrent `submit(b)` calls against one factorization are gathered
into a single `solve(lu, B)` with B's column count padded up a fixed
bucket ladder, so after one warmup pass per bucket the jitted solver
never sees a new shape and never recompiles.

Flush policy: a batch is dispatched when the widest bucket fills, or
when the oldest pending request has lingered `max_linger_s` — the
classic latency/occupancy knob.  Deadlines are enforced at both ends:
a request already past its deadline when assembly starts is dropped
from the batch (its slot is not wasted), and a request whose solve
lands after its deadline gets DeadlineExceeded instead of the result
(never a success after the deadline).

Padding columns are zeros; a zero RHS is exact under the triangular
sweeps and contributes berr=0 to refinement, so padded work never
perturbs the convergence loop of real columns.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future

import numpy as np

from .. import obs
from ..models.gssvx import LUFactorization, solve, solve_rhs_dtype
from ..obs import flight
from ..resilience import chaos
from .errors import DeadlineExceeded, FlusherDead, ServeError
from .metrics import Metrics

# nrhs bucket ladder: the only column counts the jitted solver ever
# sees.  Small enough that warmup is 5 compiles; log-spaced so padding
# waste is bounded by ~2x (amortization already beats that at 8).
BUCKET_LADDER = (1, 8, 16, 32, 64)

# flush this far ahead of the earliest pending deadline so the solve
# has a chance to land inside it
_DEADLINE_FLUSH_MARGIN_S = 0.001


def _trisolve_arm(lu) -> str:
    """The solve arm serving this dispatch (ops/trisolve.active_arm);
    import deferred so the batcher never pays an ops import on the
    module path.  A
    mesh-resident handle (dist backend, ISSUE 17) is its own arm —
    its dispatch granularity is the shard_map'd whole-phase sweep,
    not any single-device trisolve variant."""
    if getattr(lu, "backend", None) == "dist":
        return "dist"
    from ..ops.trisolve import active_arm
    return active_arm()


def _mesh_leg(lu) -> str | None:
    """Mesh-shape label for flight records ("2x2x2"); None for
    single-device handles, so the leg costs nothing off-mesh."""
    if getattr(lu, "backend", None) != "dist":
        return None
    m = lu.device_lu.mesh
    return "x".join(str(int(m.shape[a])) for a in m.axis_names)


def bucket_for(nrhs: int, ladder=BUCKET_LADDER) -> int:
    """Smallest ladder bucket ≥ nrhs (callers cap nrhs at ladder[-1])."""
    for b in ladder:
        if nrhs <= b:
            return b
    return ladder[-1]


class _Request:
    __slots__ = ("b", "deadline", "future", "t_submit", "flight")

    def __init__(self, b, deadline):
        self.b = b
        self.deadline = deadline          # absolute monotonic time or None
        self.future: Future = Future()
        self.t_submit = time.monotonic()
        # the submitting thread's flight record (None when the
        # recorder is off — one pointer check): the flusher thread
        # appends this request's queue/solve/refine events through it
        self.flight = flight.current()


class MicroBatcher:
    """Per-factorization batching queue with a background flusher.

    One MicroBatcher serves one LUFactorization handle (the service
    keeps one per hot cache key).  `solve_fn(lu, B) -> X` is
    injectable for tests; the default is the full models/gssvx.py
    solve (refinement included, per the handle's options).
    """

    def __init__(self, lu: LUFactorization,
                 max_linger_s: float = 0.002,
                 ladder=BUCKET_LADDER,
                 metrics: Metrics | None = None,
                 solve_fn=None,
                 dtype=None,
                 cast_rhs: bool = False) -> None:
        self.lu = lu
        self.max_linger_s = max_linger_s
        self.ladder = tuple(sorted(ladder))
        self.metrics = metrics or Metrics()
        self._solve_fn = solve_fn or solve
        # the ONE dtype every batch is assembled in — the residual's
        # and the answer's dtype must not depend on batch composition.
        # Default: the shared gssvx.solve_rhs_dtype rule (f64 against
        # f32 factors; complex factors promote to c128).  It is the
        # HOST side's dtype: solve() casts each sweep's operand to the
        # factor's precision itself and refines against this batch
        # unrounded, so the batch must NOT be cast down here.
        # submit() rejects an RHS that would promote past it — unless
        # `cast_rhs` (the variant carries an EXPLICIT
        # Options.solve_dtype, whose whole point is downcasting
        # client buffers).
        self.dtype = (np.dtype(dtype) if dtype is not None
                      else solve_rhs_dtype(lu))
        self.cast_rhs = cast_rhs
        # mesh residency label, resolved once (the handle's mesh is
        # immutable for the batcher's lifetime): rides every combined
        # queue flight event so p99 attribution can split mesh vs
        # single-device dispatches
        self._mesh_leg = _mesh_leg(lu)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending: list[_Request] = []
        self._closed = False
        # set to the fatal exception if the flusher thread ever dies;
        # submits then fail fast with FlusherDead instead of queueing
        # into a thread that will never flush them
        self._dead: BaseException | None = None
        # the batch popped off _pending but not yet resolved — the
        # death handler must fail these too (they are invisible to
        # _pending once claimed)
        self._inflight_batch: list[_Request] = []
        self.batches_dispatched = 0
        self._flusher = threading.Thread(target=self._run,
                                         name="slu-serve-flusher",
                                         daemon=True)
        self._flusher.start()

    @property
    def dead(self) -> BaseException | None:
        """The exception that killed the flusher thread, or None while
        it is healthy — the service's replace-dead-batcher probe."""
        return self._dead

    # -- client side ---------------------------------------------------

    def submit(self, b: np.ndarray, deadline: float | None = None) -> Future:
        """Enqueue one RHS vector (n,); resolves to x (n,).  `deadline`
        is absolute `time.monotonic()` time."""
        b = np.asarray(b)
        if b.ndim != 1 or b.shape[0] != self.lu.n:
            raise ValueError(
                f"rhs must be ({self.lu.n},); got {b.shape}")
        if self.cast_rhs:
            # the variant's solve_dtype pin: the pinned dtype wins
            # over the client buffer's (models/gssvx.solve performs
            # the same cast; doing it here keeps the batch assembly
            # single-dtype)
            b = b.astype(self.dtype, copy=False)
        elif np.promote_types(b.dtype, self.dtype) != self.dtype:
            raise ValueError(
                f"rhs dtype {b.dtype} would promote the batch past "
                f"{self.dtype} and change the compiled program; "
                "prefactor the matrix with a matching factor_dtype "
                "(or solve it unbatched)")
        req = _Request(b, deadline)
        with self._cond:
            if self._closed:
                # ServeError so the service can map a retired batcher
                # (concurrent eviction) to its cold-key contract
                raise ServeError("batcher is closed")
            if self._dead is not None or not self._flusher.is_alive():
                # watchdog: a dead flusher means this queue will never
                # drain — fail fast instead of hanging the caller (the
                # service replaces the batcher on the next request)
                raise FlusherDead(
                    f"flusher thread is dead "
                    f"({self._dead!r}); resubmit")
            self._pending.append(req)
            self._cond.notify()
        return req.future

    def warmup(self, dtype=None) -> None:
        """Compile every ladder bucket with a zero solve so live
        traffic never triggers a jit recompile: the padded shapes in
        self.dtype are the ONLY (shape, dtype) signatures this
        batcher's dispatches ever produce."""
        dt = np.dtype(dtype) if dtype is not None else self.dtype
        # a solve_fn may expose a metrics-free twin for warmup (the
        # service's merged variant does: synthetic zero solves must
        # not pollute the berr/latency histograms)
        fn = getattr(self._solve_fn, "warmup_fn", self._solve_fn)
        for k in self.ladder:
            fn(self.lu, np.zeros((self.lu.n, k), dtype=dt))

    def close(self, flush: bool = True) -> None:
        with self._cond:
            self._closed = True
            if not flush:
                pending, self._pending = self._pending, []
                for r in pending:
                    r.future.cancel()
            self._cond.notify()
        if threading.current_thread() is not self._flusher:
            # a dead batcher may be retired FROM its own flusher
            # thread (the containment handler's future callbacks run
            # there, and one of them may rebuild the batcher via the
            # service); a self-join would raise — the thread is
            # exiting anyway
            self._flusher.join()

    # -- flusher -------------------------------------------------------

    def _run(self) -> None:
        # containment wrapper: the loop body must never be able to
        # strand queued futures by dying silently.  Any escape —
        # a genuine bug outside _dispatch's own solve try, or the
        # chaos flusher_raise site — fails every pending AND claimed
        # request with an explicit FlusherDead, so callers get an
        # error, never a hang (tests/test_resilience.py gates on
        # exactly this).
        try:
            self._run_loop()
        except BaseException as e:   # noqa: BLE001 — containment
            self._flusher_died(e)

    def _flusher_died(self, e: BaseException) -> None:
        with self._cond:
            self._dead = e
            victims = self._pending + self._inflight_batch
            self._pending = []
            self._inflight_batch = []
            self._cond.notify_all()
        self.metrics.inc("batcher.flusher_died")
        obs.instant("serve.flusher_died", cat="serve",
                    args={"error": repr(e), "stranded": len(victims)})
        err = FlusherDead(f"flusher thread died: {e!r}")
        err.__cause__ = e
        for r in victims:
            if r.flight is not None:
                r.flight.event("flusher_died", error=repr(e))
            # a claimed request is already running (the handshake
            # below then raises and is swallowed); a queued one needs
            # it first.  Either way the future must RESOLVE.
            try:
                r.future.set_running_or_notify_cancel()
            except RuntimeError:
                pass
            try:
                r.future.set_exception(err)
            except Exception:
                pass    # already resolved (cancelled / late race)

    def _run_loop(self) -> None:
        max_bucket = self.ladder[-1]
        while True:
            with self._cond:
                # the flusher with nothing to feed the device: no
                # request yet, or lingering for the bucket to fill
                with obs.span("serve.wait", cat="serve"):
                    while not self._pending and not self._closed:
                        self._cond.wait()
                    if not self._pending and self._closed:
                        return
                    # linger until the widest bucket fills or the
                    # oldest request has waited max_linger_s.  A
                    # pending deadline that cannot outlast the linger
                    # window forfeits it: flush IMMEDIATELY, so the
                    # solve gets the whole remaining budget instead of
                    # being dispatched at (or dropped after) the
                    # deadline — tight-deadline traffic trades batch
                    # occupancy for latency by construction
                    flush_at = (self._pending[0].t_submit
                                + self.max_linger_s)
                    while (len(self._pending) < max_bucket
                           and not self._closed):
                        tight = any(
                            r.deadline is not None
                            and r.deadline - _DEADLINE_FLUSH_MARGIN_S
                            < flush_at
                            for r in self._pending)
                        if tight:
                            break
                        remaining = flush_at - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cond.wait(timeout=remaining)
                batch = self._pending[:max_bucket]
                del self._pending[:len(batch)]
                # claimed but unresolved: visible to _flusher_died
                self._inflight_batch = batch
            # chaos site: the flusher dies holding a claimed batch —
            # the worst-placed crash; containment must fail these
            # futures explicitly (no-op when chaos is off)
            chaos.maybe_raise("flusher_raise",
                              f"flusher killed holding {len(batch)} "
                              "requests")
            self._dispatch(batch)
            with self._cond:
                self._inflight_batch = []

    def _dispatch(self, batch: list[_Request]) -> None:
        now = time.monotonic()
        live: list[_Request] = []
        for r in batch:
            if not r.future.set_running_or_notify_cancel():
                continue                      # caller cancelled in queue
            if r.deadline is not None and now > r.deadline:
                self.metrics.inc("batcher.deadline_dropped")
                if r.flight is not None:
                    r.flight.event(
                        "queue.deadline_dropped",
                        wait_us=int((now - r.t_submit) * 1e6))
                r.future.set_exception(DeadlineExceeded(
                    "deadline passed while queued"))
                continue
            self.metrics.observe("serve.queue_wait_s", now - r.t_submit)
            # retrospective trace span: the wait started at submit
            # time on the caller's thread; the event lands on the
            # flusher's tid ending now
            obs.complete("serve.queue", now - r.t_submit, cat="serve")
            live.append(r)
        if not live:
            return
        k = bucket_for(len(live), self.ladder)
        # per-request flight linkage: one recorder-global batch id
        # ties the records dispatched together (None when off).  The
        # queue/solve observations are folded into ONE event per
        # request, appended after the solve — this loop runs on the
        # flusher thread, the serve throughput bottleneck.
        bid = flight.next_batch_id()
        # one span a batch on the flusher's thread, parent of its
        # three stages: assemble, batch_solve, fanout
        with obs.span("serve.batch", cat="serve",
                      args={"batch": (bid if bid is not None
                                      else self.batches_dispatched),
                            "live": len(live), "bucket": k}):
            self._serve_batch(live, now, k, bid)

    def _serve_batch(self, live: list[_Request], now: float, k: int,
                     bid) -> None:
        t0 = time.monotonic()
        with obs.span("serve.assemble", cat="serve",
                      args={"batch": len(live), "nrhs": k}):
            B = np.zeros((self.lu.n, k), dtype=self.dtype)
            for j, r in enumerate(live):
                B[:, j] = r.b
        self.metrics.observe("serve.batch_assembly_s",
                             time.monotonic() - t0)
        self.metrics.observe("serve.batch_occupancy", len(live) / k)
        self.metrics.inc("batcher.requests_solved", len(live))
        t1 = time.monotonic()
        # chaos site: artificial dispatch latency (deadline storms)
        chaos.maybe_sleep("latency")
        # bind the dispatch's records so per-BATCH observations made
        # inside solve_fn (refine berr, tier/degraded guard blocks)
        # fan out to every request served by it
        flight.batch_begin([r.flight for r in live])
        try:
            with obs.span("serve.batch_solve", cat="serve",
                          args={"nrhs": k,
                                "occupancy": len(live) / k}):
                X = self._solve_fn(self.lu, B)
        except BaseException as e:
            flight.batch_event("solve.error", error=repr(e))
            for r in live:
                r.future.set_exception(e)
            return
        finally:
            flight.batch_end()
        solve_s = time.monotonic() - t1
        self.metrics.observe("serve.device_solve_s", solve_s)
        self.batches_dispatched += 1
        done = time.monotonic()
        solve_us = int(solve_s * 1e6)
        occ = round(len(live) / k, 4) if bid is not None else 0.0
        # which trisolve arm served this batch (resolved per dispatch
        # — a mid-run SLU_TRISOLVE flip must not mislabel exemplars):
        # p99 latency attribution in obs/flight.py needs to know
        # whether the merged lsum kernel or the legacy sweep ran
        arm = _trisolve_arm(self.lu) if bid is not None else None
        with obs.span("serve.fanout", cat="serve"):
            for j, r in enumerate(live):
                if r.flight is not None:
                    r.flight.event(
                        "queue", wait_us=int((now - r.t_submit) * 1e6),
                        batch=bid, bucket=k, occupancy=occ,
                        solve_us=solve_us, arm=arm,
                        mesh=self._mesh_leg)
                if r.deadline is not None and done > r.deadline:
                    # the work is done, but a missed deadline must
                    # never read as success — the caller already
                    # moved on
                    self.metrics.inc("batcher.deadline_missed")
                    r.future.set_exception(DeadlineExceeded(
                        "solved after deadline"))
                else:
                    r.future.set_result(np.array(X[:, j]))
