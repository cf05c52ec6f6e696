"""Pipelined refactorization: background factor of step k+1 while
solves ride step k.

PR 5 built the mechanism as a FAILURE path: degraded-mode serving
solves on a stale factor with refinement against the fresh matrix
behind a berr guard, when a refactorization *failed*.  This module
promotes it to the steady-state serving mode for matrix STREAMS —
sequences of systems with a fixed pattern and drifting values (the
reference's `SamePattern_SameRowPerm` rung, ROADMAP item 4): a
`StreamHandle` keeps ONE resident generation of factors
(stream/swap.py), serves every solve through it immediately
(refinement against the live values closes the drift gap, df64
residual for sub-f64 factors — the PR 4/PR 5 machinery), and pays the
factorization as a CONTAINED background
task whose cadence the measured berr drift sets (stream/cadence.py).
The compute/communication-overlap discipline of the HPL-exascale
pipelining work (PAPERS.md, arxiv 2304.10397), applied to the
factorization itself.

Containment contract (the robustness headline):

  * the background worker factors through the factor cache's full
    resilient path — per-key breaker, bounded retry, finite-
    validation gate, store write-through, fleet single-flight — so a
    `FactorPoisoned`, retry exhaustion, breaker-open or chaos raise
    degrades to CONTINUED stale-factor serving, never an outage;
  * the worker thread itself is contained like the batcher's flusher
    (serve/batcher.py `_run`): any escape marks it dead, solves keep
    riding the resident generation, and the next refactor request
    restarts the worker (counted, observable);
  * a result is NEVER served past the berr guard: a stale solve
    whose refined berr leaves the accuracy class fails typed
    (`StaleFactorError`), blocks those values from further stale
    serving, and requests an urgent refactorization;
  * `kill -9` at ANY instant of the swap is safe: the durable store
    published the new generation at factorization time (write-through
    precedes the in-memory swap by construction), so a restarted
    process primes warm from whichever generation the store last
    published — the `swap_kill` chaos site fires exactly between
    validation and the in-memory assignment, and
    tests/test_stream.py gates the restart at
    factorizations == 0.

Front-door integration: stream solves ride the REAL service plumbing
— `SolveService.submit`'s admission control, flight recorder and SLO
accounting — via its `_router` seam; this module provides only the
routing (resident-generation lookup, stale-vs-fresh dispatch, the
guard).  Every solve's flight record carries the factor generation
and staleness (`stream.route`).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time

import numpy as np

from .. import flags, obs
from ..models.gssvx import (_ESC_BERR_SLACK, LUFactorization,
                            solve as _solve)
from ..obs import flight
from ..options import Options
from ..resilience import chaos
from ..serve.errors import (FactorMissError, FactorPoisoned,
                            ServeError, StaleFactorError)
from ..serve.factor_cache import CacheKey, matrix_key
from ..sparse import CSRMatrix
from .cadence import Cadence
from .swap import Generation, ResidentSwap


@dataclasses.dataclass
class StreamConfig:
    """Policy knobs of one matrix stream (the streaming analog of
    ServeConfig)."""

    # background refactor pipeline; False = the pinned arm (solves
    # ride generation 1 forever, refinement-only — the drift drill's
    # overlap baseline)
    background: bool = True
    # probe solve before publish: one refined solve on the fresh
    # generation (builds its PackSet, warms the nrhs=1 program, and
    # refuses a factorization whose solve path is broken even though
    # its factors are finite).  SLU_STREAM_PROBE=0 skips.
    probe: bool = dataclasses.field(
        default_factory=lambda: bool(flags.env_int("SLU_STREAM_PROBE",
                                                   1)))
    # cadence overrides (None = the flag-gateway stream defaults)
    trip_frac: float | None = None
    interval_scale: float | None = None
    max_lag: int | None = None
    # restart a dead worker on the next refactor request (the
    # service's replace-dead-batcher discipline)
    restart_worker: bool = True


class StreamHandle:
    """One matrix stream: fixed pattern + factor options, drifting
    values.  Built by `SolveService.stream()`.

    Lock order (audited by tools/slulint over stream/): the handle
    condition (`_cond`) is the INNERMOST stream lock and is never
    held across a service/cache/solve call — live-state snapshots are
    taken under it, everything expensive runs outside it.
    """

    # stale-serving wrapper handles kept per (generation, values):
    # drift means one live value set at a time, so a handful covers
    # the steady state plus scipy-compat solves against named older
    # systems
    _STALE_HANDLES = 8

    def __init__(self, service, a: CSRMatrix,
                 options: Options | None = None,
                 config: StreamConfig | None = None) -> None:
        self.service = service
        self.options = options or Options()
        self.config = config or StreamConfig()
        self.metrics = service.metrics
        self.swap = ResidentSwap()
        limit = _ESC_BERR_SLACK * float(
            np.finfo(np.dtype(self.options.refine_dtype)).eps)
        self.cadence = Cadence(
            limit,
            trip_frac=self.config.trip_frac,
            interval_scale=self.config.interval_scale,
            max_lag=self.config.max_lag,
            fleet=service.cache.fleet is not None)
        self._cond = threading.Condition()
        self._closed = False
        self._worker: threading.Thread | None = None
        self._worker_dead: BaseException | None = None
        # latest refactor request: (key, matrix, step, trigger) — the
        # worker always takes the NEWEST pending values (factoring an
        # already-superseded step would waste a factorization)
        self._want: tuple | None = None
        self._gen_count = 0
        self._step = 0
        # values sigs whose stale refinement breached the berr guard,
        # tagged with the GENERATION the breach was measured against:
        # refused typed only while that generation is still resident
        # (a fresher generation shrinks the drift distance, so a
        # breach recorded against gen k never blocks serving off gen
        # k+1 — even when the breach lands concurrently with the
        # swap)
        self._blocked_values: dict[str, int] = {}
        # generations whose soft trip already fired a health
        # escalation (one stream_drift event per generation)
        self._escalated_gens: set[int] = set()
        # THIS handle's figures (under _cond): the stream.* metrics
        # counters are service-wide and a status() reading them would
        # misattribute a sibling stream's refactors/breaches
        self._hcounts = {"refactors": 0, "refactor_failures": 0,
                         "guard_breaches": 0}
        # stale-serving handles, one per (generation, live values):
        # the refine-against-live wrapper around the resident factors
        # is shared by every request on that pair (its refine_cache
        # with it) instead of being rebuilt per solve
        self._stale_handles: "collections.OrderedDict[tuple, object]"\
            = collections.OrderedDict()

        # synchronous prime: generation 1.  Store read-through makes
        # a restarted process's prime a warm adopt (factorizations ==
        # 0 — the drift drill's restart gate); fleet single-flight
        # makes a pool's prime one factorization total.
        key = matrix_key(a, self.options)
        t0 = time.monotonic()
        lu = service.cache.get_or_factorize(a, self.options, key=key)
        # the prime wall seeds the cadence's cost estimate, a
        # PER-PATTERN figure; later refactor walls
        # refine it by EWMA.  A warm store adopt under-estimates —
        # the first real refactor corrects it.
        self.cadence.note_swap(time.monotonic() - t0)
        # condition baseline (numerics/): under SLU_COND_ESTIMATE the
        # serve factor path cached an rcond on the handle; generation
        # 1's estimate is the stream's drift baseline
        self.cadence.note_rcond(getattr(lu, "rcond", None))
        self._gen_count = 1
        self.swap.publish(Generation(gen=1, key=key, lu=lu, a=a,
                                     step=0))
        self._pattern_key = key.pattern_key
        self._live: tuple = (key, a, 0)
        if self.config.background:
            self._start_worker()

    # -- operator surface ---------------------------------------------

    def update(self, a_new: CSRMatrix,
               key: CacheKey | None = None) -> CacheKey:
        """Step the stream: `a_new` is the live value set from now on
        (same pattern — a different structure is a different stream).
        Returns immediately; the cadence decides when the background
        refactorization starts.  `key` skips the O(nnz) fingerprint
        when the caller already computed `matrix_key(a_new,
        h.options)` (the scipy-compat hot path)."""
        # chaos site (drill-only): deterministic value-skew toward
        # rank deficiency — the hardening-problem fault the
        # rcond-drift trigger exists for.  Off-path cost: one pointer
        # check.  A skewed matrix is a NEW value set, so the key is
        # recomputed from it.
        a_skew = chaos.maybe_skew_singular("near_singular", a_new)
        if a_skew is not a_new:
            a_new, key = a_skew, None
        if key is None:
            key = matrix_key(a_new, self.options)
        if key.pattern_key != self._pattern_key:
            raise ValueError(
                "stream update changed the sparsity pattern (or the "
                "factor options); a new pattern is a new stream — "
                "open one via SolveService.stream()")
        with self._cond:
            if self._closed:
                raise ServeError("stream is closed")
            self._step += 1
            self._live = (key, a_new, self._step)
        self.metrics.inc("stream.updates")
        self._maybe_refactor()
        return key

    def submit(self, b: np.ndarray, deadline_s: float | None = None,
               against: tuple | None = None,
               options: Options | None = None):
        """Admit one solve against the LIVE values (or an explicit
        `against=(key, matrix)` — the scipy-compat path, which must
        refine against the system its caller named even after the
        stream stepped on).  `options` overrides SOLVE-time knobs
        (trans, refinement) for this request; factor knobs stay the
        stream's.  Rides the service front door: admission control,
        flight record, SLO accounting."""
        tk = self._ticket(against)
        return self.service.submit(
            None, b, options, deadline_s,
            _router=functools.partial(self._route_stream, tk))

    def solve(self, b: np.ndarray, deadline_s: float | None = None,
              info: dict | None = None,
              against: tuple | None = None,
              options: Options | None = None) -> np.ndarray:
        """Blocking submit (deadline-respecting), like
        SolveService.solve."""
        tk = self._ticket(against)
        return self.service.solve(
            None, b, options, deadline_s, info=info,
            _router=functools.partial(self._route_stream, tk))

    def grad_solve(self, b: np.ndarray, xbar=None, trans=None):
        """Differentiable solve + adjoint pull on the RESIDENT
        generation (autodiff.vjp_solve): the gradient rides the
        generation's factors at ITS linearization point — `g.a`, the
        matrix those factors came from, not the drifted live values,
        because the grad of a stale generation is the grad of the
        system it actually solves.  Returns (GradResult, gen) so the
        caller can pin which generation the cotangents belong to
        across a concurrent swap; FactorMissError when nothing is
        resident (closed or never primed)."""
        from ..autodiff import vjp_solve
        g = self.swap.current
        if g is None:
            raise FactorMissError(
                "stream has no resident generation to differentiate "
                "through")
        res = vjp_solve(g.lu, b, xbar=xbar, A_values=g.a.data,
                        trans=trans)
        return res, g.gen

    def refactor_now(self) -> None:
        """Force a background refactorization of the live values
        (cadence bypassed) — the operator's manual lever.  Works on a
        pinned stream (background=False) too: the manual request
        starts a worker for it; only the CADENCE stays off."""
        with self._cond:
            live = self._live
        key, a, step = live
        g = self.swap.current
        if g is not None and g.values == key.values:
            return
        self._request(key, a, step, "manual")

    def status(self) -> dict:
        g = self.swap.current
        with self._cond:
            live = self._live
            dead = self._worker_dead
            worker = self._worker
            blocked = len(self._blocked_values)
            counts = dict(self._hcounts)
        lag = (live[2] - g.step) if g is not None else 0
        return {
            "gen": g.gen if g is not None else 0,
            "gen_step": g.step if g is not None else None,
            "live_step": live[2],
            "lag": lag,
            "fresh": g is not None and g.values == live[0].values,
            "staleness_s": (round(g.staleness_s(), 3)
                            if g is not None else None),
            "swaps": self.swap.swaps,
            "worker_alive": worker is not None and worker.is_alive(),
            "worker_dead": repr(dead) if dead is not None else None,
            "blocked_values": blocked,
            "cadence": self.cadence.snapshot(),
            "refactors": counts["refactors"],
            "refactor_failures": counts["refactor_failures"],
            "guard_breaches": counts["guard_breaches"],
            # the resident generation's device-memory watermark pair
            # (obs/memory.py): what the live factors cost to hold
            "mem_watermarks": (dict(g.lu.stats.mem_watermarks)
                               if g is not None and g.lu.stats
                               is not None
                               and g.lu.stats.mem_watermarks
                               else None),
        }

    def close(self) -> None:
        with self._cond:
            if self._closed:
                return
            self._closed = True
            worker = self._worker
            self._want = None
            self._cond.notify_all()
        if worker is not None \
                and threading.current_thread() is not worker:
            worker.join(timeout=30.0)
        self.service._discard_stream(self)

    # -- routing (the service _router seam) ---------------------------

    def _ticket(self, against: tuple | None) -> tuple:
        # an explicit `against` (the scipy-compat StreamLU) names a
        # FIXED system: it stays solvable on a closed handle — the
        # resident generation is frozen with it, so its berr cannot
        # drift and the guard's resubmit contract never arises.  The
        # LIVE path refuses instead: a closed stream can never swap,
        # so continued drift would end in a StaleFactorError whose
        # "resubmit" promise no worker honors.
        if against is not None:
            key, a = against
            return (key, a, None)
        with self._cond:
            if self._closed:
                raise ServeError("stream is closed")
            return self._live

    def _route_stream(self, tk: tuple, _a, b, options, deadline_s,
                      t0: float | None = None):
        key, a, step = tk
        req_opts = options if options is not None else self.options
        deadline_s = (deadline_s if deadline_s is not None
                      else self.service.config.default_deadline_s)
        deadline = ((t0 if t0 is not None else time.monotonic())
                    + deadline_s if deadline_s is not None else None)
        g = self.swap.current
        rec = flight.current()
        fresh = g.values == key.values
        # one routing event per solve: the generation served from,
        # its staleness, and how many steps the live values are ahead
        # — the satellite contract ("every solve stamped")
        if rec is not None:
            rec.event("stream.route", gen=g.gen, fresh=fresh,
                      staleness_ms=int(g.staleness_s() * 1e3),
                      lag=(step - g.step
                           if step is not None and g.step is not None
                           else None))
        self.service._note_route(rec, g.lu, served="stream")
        if fresh:
            self.metrics.inc("stream.fresh_solves")
            mb = self._batcher_for(g, g.lu, req_opts)
            return mb.submit(b, deadline=deadline)
        with self._cond:
            bgen = self._blocked_values.get(key.values)
        if bgen is not None and bgen >= g.gen:
            # these values already breached the guard off this (or an
            # older) generation; an urgent refactor is in flight —
            # fail typed instead of re-burning a doomed refinement
            self.metrics.inc("stream.blocked_rejects")
            raise StaleFactorError(
                "values blocked: stale-factor refinement left the "
                "accuracy class for this value set; awaiting the "
                "next generation (resubmit)")
        self.metrics.inc("stream.stale_solves")
        # the degraded-mode solve semantics as the steady state:
        # refinement mandatory, df64 residual for sub-f64 real
        # factors, refined against the LIVE matrix (the stale factors
        # are the preconditioner) — but the result is NOT stamped
        # DegradedResult: this is the designed serving mode behind
        # the same guard, not a failure fallback
        d_opts = self.service._degraded_options(a, g.lu, req_opts)
        handle = self._stale_handle(g, a, key)
        mb = self._batcher_for(
            g, handle, d_opts,
            on_berr=self._guard(key, g.gen, d_opts),
            # per-(generation, live values) variant: each drifted
            # value set refines against ITS matrix and cannot share
            # a batch with another's (the degraded-path discipline)
            variant=("stream", key.values))
        fut = mb.submit(b, deadline=deadline)
        self._maybe_refactor()
        return fut

    def _stale_handle(self, g: Generation, a: CSRMatrix,
                      key: CacheKey) -> LUFactorization:
        """The refine-against-live wrapper around generation `g` for
        live value set `key.values`, shared (refine_cache included)
        by every stale solve on that pair — the per-request
        construction would be pure allocation churn on the designed
        steady-state path."""
        hk = (g.gen, key.values)
        with self._cond:
            handle = self._stale_handles.get(hk)
            if handle is not None:
                self._stale_handles.move_to_end(hk)
                return handle
        from ..serve.service import refine_wrapper
        built = refine_wrapper(g.lu, a)
        with self._cond:
            handle = self._stale_handles.setdefault(hk, built)
            self._stale_handles.move_to_end(hk)
            while len(self._stale_handles) > self._STALE_HANDLES:
                self._stale_handles.popitem(last=False)
        return handle

    def _batcher_for(self, g: Generation, handle, opts,
                     **kw) -> "object":
        """service._batcher_for, with the stream's residency story:
        the Generation holds its factors alive even if the SHARED
        cache LRU-evicted the key under other traffic, so an evicted
        resident generation is re-published and retried once instead
        of failing every solve until the next drift-driven
        refactorization (a fresh-but-evicted stream would otherwise
        never recover — nothing re-factors unchanged values)."""
        try:
            return self.service._batcher_for(g.key, handle, opts,
                                             **kw)
        except FactorMissError:
            self.metrics.inc("stream.resident_reputs")
            self.service.cache.put(g.key, g.lu)
            return self.service._batcher_for(g.key, handle, opts,
                                             **kw)

    def _guard(self, key: CacheKey, gen: int, d_opts: Options):
        """Per-dispatch berr watchdog for stale stream traffic.  Hard
        breach (past the 64·eps class): the batch FAILS typed —
        no result is ever served past the guard — the values block,
        and an urgent refactorization is requested.  Soft trip (past
        the cadence's escalation threshold): one `stream_drift`
        health escalation per generation and a refactor request; the
        result still serves (it is inside the accuracy class)."""
        limit = self.cadence.guard_limit
        trip = self.cadence.trip

        def on_berr(berr: float) -> None:
            self.cadence.note_berr(berr)
            if not (berr <= limit) or not np.isfinite(berr):
                flight.batch_event("stream.berr_block",
                                   berr=float(berr))
                self.metrics.inc("stream.guard_breaches")
                with self._cond:
                    self._blocked_values[key.values] = gen
                    self._hcounts["guard_breaches"] += 1
                obs.HEALTH.record_escalation(
                    berr=float(berr),
                    factor_dtype=d_opts.factor_dtype,
                    refine_dtype=d_opts.refine_dtype,
                    to_dtype=d_opts.refine_dtype,
                    trigger="stream_berr")
                self._urgent_refactor()
                raise StaleFactorError(
                    f"stale-factor refinement berr {berr:.2e} left "
                    f"the {limit:.2e} accuracy class; result "
                    "withheld, refactorization requested — resubmit")
            if berr >= trip:
                with self._cond:
                    first = gen not in self._escalated_gens
                    self._escalated_gens.add(gen)
                if first:
                    self.metrics.inc("stream.drift_escalations")
                    obs.HEALTH.record_escalation(
                        berr=float(berr),
                        factor_dtype=d_opts.factor_dtype,
                        refine_dtype=d_opts.refine_dtype,
                        to_dtype=d_opts.refine_dtype,
                        trigger="stream_drift")
                # soft trip is still INSIDE the accuracy class, so the
                # request goes through the cadence (min interval
                # included) — a berr plateau just past trip must not
                # drive back-to-back factorizations at 100% duty; only
                # a hard breach above earns the urgent bypass
                self._maybe_refactor()

        return on_berr

    # -- cadence -> worker --------------------------------------------

    def _maybe_refactor(self) -> None:
        if not self.config.background:
            return
        with self._cond:
            if self._closed:
                return
            key, a, step = self._live
        g = self.swap.current
        if g is None or g.values == key.values:
            return
        lag = max(1, step - (g.step or 0))
        trigger = self.cadence.due(lag=lag)
        if trigger is None:
            return
        self._request(key, a, step, trigger)

    def _urgent_refactor(self) -> None:
        """Guard-driven request: bypasses the cadence (min interval
        included) — the accuracy class is at stake, not economics."""
        if not self.config.background:
            return
        with self._cond:
            if self._closed:
                return
            key, a, step = self._live
        g = self.swap.current
        if g is not None and g.values == key.values:
            return
        self._request(key, a, step, "berr_trip")

    def _request(self, key, a, step, trigger) -> None:
        with self._cond:
            if self._closed:
                return
            if self._worker_dead is not None:
                if not self.config.restart_worker:
                    return
                # the replace-dead-batcher discipline: the worker is
                # a contained component, its death is a recorded
                # fault, and the stream recovers on the next request
                self.metrics.inc("stream.worker_restarts")
                self._worker_dead = None
                self._start_worker_locked()
            elif self._worker is None:
                # a pinned stream (background=False) has no worker
                # until the operator's manual refactor_now() asks for
                # one — the cadence paths stay gated on background,
                # so this never turns the pinned arm into the
                # pipelined one by itself
                self._start_worker_locked()
            self._want = (key, a, step, trigger)
            self._cond.notify()

    # -- the contained background worker ------------------------------

    def _start_worker(self) -> None:
        with self._cond:
            self._start_worker_locked()

    def _start_worker_locked(self) -> None:
        t = threading.Thread(target=self._run,
                             name="slu-stream-refactor", daemon=True)
        self._worker = t
        t.start()

    def _run(self) -> None:
        # containment wrapper (the serve/batcher.py flusher
        # discipline): nothing the loop body does may silently end
        # background refactorization — an escape marks the worker
        # dead, serving continues on the resident generation, and
        # the next request restarts the worker
        try:
            self._run_loop()
        except BaseException as e:     # noqa: BLE001 — containment
            with self._cond:
                self._worker_dead = e
            self.metrics.inc("stream.worker_died")
            obs.instant("stream.worker_died", cat="stream",
                        args={"error": repr(e)})

    def _run_loop(self) -> None:
        while True:
            with self._cond:
                while self._want is None and not self._closed:
                    self._cond.wait()
                if self._closed:
                    return
                want, self._want = self._want, None
            try:
                self._refactor_once(*want)
            except Exception as e:
                # FactorPoisoned / breaker-open / retry exhaustion /
                # chaos raise: the refactorization failed, the stale
                # generation keeps serving, the cadence re-trips on
                # the next berr sample.  Never an outage.
                self.metrics.inc("stream.refactor_failures")
                with self._cond:
                    self._hcounts["refactor_failures"] += 1
                obs.instant("stream.refactor_failed", cat="stream",
                            args={"error": f"{type(e).__name__}: {e}",
                                  "trigger": want[3]})

    def _quarantine_generation(self, key: CacheKey) -> None:
        """Undo a probe-refused generation's publications: drop the
        in-memory cache entry and quarantine the durable store entry
        (the store's bits-rotted-or-writer-lied lane) so NOTHING
        adopts the factors the probe rejected."""
        cache = self.service.cache
        cache.evict(key)
        store = cache.store
        if store is not None:
            store.quarantine(store.path_for(key),
                             reason="stream probe refused")

    def _refactor_once(self, key: CacheKey, a: CSRMatrix, step: int,
                       trigger: str) -> None:
        # a request queued WHILE the worker was factoring these very
        # values (every stale solve re-requests until the swap lands)
        # is already satisfied — factoring it again would publish a
        # duplicate generation: cache-hit "refactor", extra probe,
        # stale-handle caches cleared, swap counters inflated
        g = self.swap.current
        if g is not None and g.values == key.values:
            return
        # chaos sites for the background pipeline specifically (the
        # foreground factor path keeps its own factor_raise site):
        # refactor_slow models a long factorization the solves must
        # ride through; refactor_raise a background failure
        chaos.maybe_sleep("refactor_slow")
        chaos.maybe_raise(
            "refactor_raise",
            f"background refactorization killed (step {step})")
        self.cadence.note_refactor_start()
        self.metrics.inc("stream.refactors")
        with self._cond:
            self._hcounts["refactors"] += 1
        obs.instant("stream.refactor", cat="stream",
                    args={"step": step, "trigger": trigger})
        t0 = time.monotonic()
        # the cache's FULL resilient path: pattern-tier plan reuse
        # (numeric-only SamePattern_SameRowPerm refactorization),
        # breaker gate, bounded retry, finite validation, store
        # write-through, fleet single-flight — one leader per pool
        lu = self.service.cache.get_or_factorize(a, self.options,
                                                 key=key)
        wall = time.monotonic() - t0
        if self.config.probe:
            # probe pass: builds the generation's PackSet, warms the
            # nrhs=1 program, and proves the SOLVE path end to end
            # before any live request can route to these factors
            xp = _solve(lu, np.ones(a.n, dtype=np.float64))
            if not np.all(np.isfinite(np.asarray(xp))):
                # write-through PRECEDED validation, so the refused
                # factors are already durable and cache-resident —
                # evict + quarantine them, or a restart/fleet sibling
                # primes warm from exactly what the probe rejected
                # and a same-process retry cache-hits it forever
                self._quarantine_generation(key)
                raise FactorPoisoned(
                    "probe solve on the fresh generation produced "
                    "non-finite results; generation not published")
        # MID-SWAP kill window: the durable store already holds this
        # generation (write-through above); the in-memory publication
        # has not happened.  A kill -9 here is exactly the crash the
        # restart drill proves safe (boot warm from the store).
        chaos.maybe_sigkill("swap_kill")
        with self._cond:
            self._gen_count += 1
            gen_no = self._gen_count
            # every recorded block was measured against a previously
            # RESIDENT generation (strictly below gen_no), so none
            # survives publication — the route check's `bgen >=
            # g.gen` already ignores them; this bounds the map
            self._blocked_values.clear()
            # old-generation stale wrappers are unreachable once the
            # swap publishes (solves route off the new resident)
            self._stale_handles.clear()
        g = self.swap.publish(Generation(gen=gen_no, key=key, lu=lu,
                                         a=a, step=step))
        self.cadence.note_swap(wall)
        # the fresh generation's condition estimate (when the serve
        # factor path computed one) feeds the rcond-drift trigger
        self.cadence.note_rcond(getattr(lu, "rcond", None))
        self.metrics.inc("stream.swaps")
        mem = (lu.stats.mem_watermarks
               if lu.stats is not None else None) or {}
        obs.instant("stream.swap", cat="stream",
                    args={"gen": g.gen, "step": step,
                          "trigger": trigger,
                          "wall_s": round(wall, 3),
                          "peak_bytes":
                          mem.get("peak_bytes_measured")})
