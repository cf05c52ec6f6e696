"""The solve-service front door.

`SolveService` turns the batch-shaped solver into a multi-tenant
request/response service: requests name a matrix (by value or by a
precomputed cache key), the service resolves factors through the LRU
factor cache (single-flight on misses), routes the RHS into the
per-key micro-batcher, and enforces the two service-level contracts a
caller can rely on:

  * admission control — at most `max_queue_depth` requests in flight;
    request N+1 gets an immediate ServeRejected instead of unbounded
    queueing (explicit pushback is the only honest overload signal);
  * deadlines — a request carries an absolute deadline; it is dropped
    from batch assembly once passed, and a solve that lands late
    raises DeadlineExceeded rather than returning a stale success.

Cold keys follow `miss_policy`: "factor" pays the factorization once
(single-flight, so a thundering herd on one key does one
factorization's worth of work); "failfast" raises FactorMissError so
interactive traffic never blocks minutes behind a cold tenant
(errors.factor_cost_hint) — the operator prefactors keys out of band via
`prefactor()`.

Failure containment (resilience/): factorization failures are retried
(bounded backoff), repeatedly-failing keys are circuit-broken
(FactorPoisoned, one immediate error instead of a factorization-length
retry per request), dead batcher flushers fail their futures with
FlusherDead and are replaced on the next request — and when a
refactorization fails while a stale same-pattern factorization is
resident, DEGRADED MODE solves through the stale factors with
refinement against the fresh matrix behind the standard berr guard,
returning a `DegradedResult`-stamped answer instead of an outage.

Everything is observable through a shared Metrics registry.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import threading
import time
from concurrent.futures import Future

import numpy as np

from .. import flags
from ..models.gssvx import LUFactorization, solve
from ..obs import COMPILE_WATCH, flight, slo
from ..obs import registry as obs_registry
from ..options import Options, merge_solve_options, solve_options_key
from ..resilience import breaker as breaker_defaults
from ..resilience.breaker import CircuitBreaker
from ..resilience.retry import RetryPolicy
from ..resilience.store import FactorStore
from ..sparse import CSRMatrix
from .batcher import BUCKET_LADDER, MicroBatcher
from .errors import (DeadlineExceeded, DegradedResult, FactorMissError,
                     FactorPoisoned, FlusherDead, InvalidInputError,
                     ServeError, ServeRejected, SingularMatrixError,
                     StaleFactorError, StructurallySingularError,
                     TenantThrottled, factor_cost_hint)
from .factor_cache import CacheKey, FactorCache, matrix_key
from .metrics import Metrics


def _merged_solve_fn(options: Options, metrics: Metrics | None = None,
                     on_berr=None):
    """Batch solver honoring the request's SOLVE-TIME knobs: the
    gssvx FACTORED-rung merge, applied per dispatch.  The replace copy
    shares the handle's refine_cache container, so refinement
    operands build once across all variants.

    Per-dispatch berr is exported to the `serve.berr` histogram: the
    serve path never re-factors (no gssvx escalation rung), so a
    pattern-tier refactorization whose inherited scaling serves the
    new values poorly shows up HERE, not as an exception — alert on
    this histogram."""
    from ..options import IterRefine
    from ..utils.stats import Stats

    def raw(lu: LUFactorization, B):
        merged = merge_solve_options(lu.effective_options, options)
        st = Stats()
        x = solve(dataclasses.replace(lu, options=merged), B, stats=st)
        return x, st, merged

    def fn(lu: LUFactorization, B):
        x, st, merged = raw(lu, B)
        # perturbation/condition stamp (numerics/): a solve that rode
        # tiny-pivot-replaced factors — or an ill-conditioned key
        # under SLU_COND_POLICY=stamp — is labeled PerturbedResult.
        # The batcher's per-request column slices inherit the stamp
        # (PerturbedResult.__array_finalize__).  Cost when clean: two
        # getattr, nothing else.
        led = getattr(lu, "ledger", None)
        rc = getattr(lu, "rcond", None)
        if (led is not None and led.perturbed) or rc is not None:
            from ..numerics.ledger import stamp_perturbed
            from ..numerics.policy import ConditionPolicy
            pol = ConditionPolicy.from_env()
            ill = (pol.mode == "stamp" and pol.classify(
                rc, merged.refine_dtype) == "ill")
            if (led is not None and led.perturbed) or ill:
                x = stamp_perturbed(x, ledger=led, rcond=rc)
                flight.batch_event(
                    "perturbed",
                    tiny_pivots=(int(led.count) if led is not None
                                 else 0),
                    rcond=(float(rc) if rc is not None else None))
                if metrics is not None:
                    metrics.inc("serve.perturbed_served")
        if merged.iter_refine != IterRefine.NOREFINE:
            # per-request linkage: the batcher bound this dispatch's
            # flight records before calling us (batch_begin), so the
            # batch-level berr fans out to every request it served
            flight.batch_event("refine", berr=float(st.berr),
                               steps=int(st.refine_steps or 0))
            if metrics is not None:
                metrics.observe("serve.berr", float(st.berr))
                if st.refine_steps:
                    metrics.observe("serve.refine_steps",
                                    float(st.refine_steps))
            if on_berr is not None:
                # dtype-tier accuracy guard (SolveService._tier_guard):
                # a tier-served dispatch whose refined berr missed the
                # sold accuracy class reports here
                on_berr(float(st.berr))
        return x

    # warmup path: same compiled programs, no metrics — five
    # synthetic berr=0 samples per prefactor would dilute the very
    # histogram operators alert on
    fn.warmup_fn = lambda lu, B: raw(lu, B)[0]
    return fn


def refine_wrapper(lu: "LUFactorization", a: CSRMatrix
                   ) -> "LUFactorization":
    """Stale factors as the preconditioner for a FRESH matrix: the
    live values attached, with a private refine cache + lock so the
    wrapper's refinement state never mixes with the resident
    handle's.  Shared by the degraded fallback and the stream's
    steady-state stale serving — the reset-per-wrapper invariants
    live HERE, once."""
    return dataclasses.replace(lu, a=a, refine_cache={},
                               cache_lock=threading.Lock())


def _mark_degraded(fut: Future) -> Future:
    """A future resolving to the same outcome as `fut`, with a
    successful result re-viewed as DegradedResult — the stamp a caller
    checks with isinstance (loadgen counts it as its own status)."""
    out: Future = Future()

    def _done(f: Future) -> None:
        if f.cancelled():
            out.cancel()
            out.set_running_or_notify_cancel()
            return
        e = f.exception()
        if e is not None:
            out.set_exception(e)
        else:
            out.set_result(np.asarray(f.result()).view(DegradedResult))

    fut.add_done_callback(_done)
    return out


def _mesh_from_env():
    """The serve mesh from SLU_SERVE_MESH/SLU_MESH_SHAPE (flags.py),
    or None (single-device serving, the default).  SLU_SERVE_MESH=1
    turns mesh residency on; SLU_MESH_SHAPE names the grid ("2x2x2",
    "8"; default: all local devices on one flat axis).  Resolved once
    per ServeConfig construction — building a Mesh touches the device
    client, so the off path must stay one env read."""
    if not flags.env_int("SLU_SERVE_MESH", 0):
        return None
    import jax
    from ..parallel.grid import make_solver_mesh
    shape = flags.env_str("SLU_MESH_SHAPE", "").strip()
    if shape:
        dims = [int(d) for d in shape.lower().split("x")]
    else:
        dims = [len(jax.devices())]
    dims = (dims + [1, 1])[:3]
    return make_solver_mesh(*dims).mesh


@dataclasses.dataclass
class ServeConfig:
    """Service policy knobs (the serving analog of Options)."""

    max_queue_depth: int = 256          # admission cap, requests in flight
    default_deadline_s: float | None = None   # per-request default
    miss_policy: str = "factor"         # "factor" | "failfast"
    max_linger_s: float = 0.002         # batcher flush timer
    ladder: tuple = BUCKET_LADDER
    capacity_bytes: int | None = None   # factor-cache byte bound
    backend: str = "auto"
    # cap on live (key, solve-options) batcher variants — each owns a
    # flusher thread; least-recently-used variants retire past the cap
    max_batchers: int = 64
    # dtype-TIER serving (precision/policy.py; SLU_PREC_TIERS=1 flips
    # the default): a cold high-precision request whose matrix is
    # resident at a LOWER ladder rung is served from those factors
    # through doubleword-residual refinement instead of paying a cold
    # full-precision factorization — the psgssvx_d2 economics as a
    # cache policy.  A tier-served solve whose berr misses the sold
    # accuracy class blocks the tier mapping for that key (health
    # event `tier_berr`), so subsequent requests re-key to a genuine
    # full-precision factorization.
    dtype_tiers: bool = dataclasses.field(
        default_factory=lambda: bool(flags.env_int("SLU_PREC_TIERS",
                                                   0)))
    # --- resilience (resilience/) ---
    # durable factor store directory; None falls through to the
    # cache's own SLU_FT_STORE env default
    store_dir: str | None = None
    # extra factorization attempts after the first (bounded
    # exponential backoff + deterministic jitter); 0 = no retry
    factor_retries: int = 0
    retry_base_s: float = 0.05
    # per-key circuit breaker: this many lead-factorization failures
    # open the circuit for cooldown_s (then one half-open probe);
    # 0 disables.  Defaults route through flags.py
    # (SLU_BREAKER_THRESHOLD / SLU_BREAKER_COOLDOWN_S)
    breaker_threshold: int = dataclasses.field(
        default_factory=breaker_defaults.default_threshold)
    breaker_cooldown_s: float = dataclasses.field(
        default_factory=breaker_defaults.default_cooldown_s)
    # degraded-mode serving: when a refactorization fails (or the key
    # is circuit-broken) but a stale same-pattern factorization is
    # resident, solve through it with refinement against the FRESH
    # matrix behind the berr guard and stamp the result DegradedResult
    # — instead of returning an outage
    degraded: bool = True
    # --- fleet (fleet/) ---
    # cross-process single-flight over the shared store (requires
    # store_dir / SLU_FT_STORE): a cold key factors exactly once
    # across every replica process sharing the store; followers
    # adopt the published entry.  SLU_FLEET=1 flips the default.
    fleet: bool = dataclasses.field(
        default_factory=lambda: bool(flags.env_int("SLU_FLEET", 0)))
    # --- device-mesh residency (ISSUE 17) ---
    # jax.sharding.Mesh the replica's factorizations shard over: the
    # cache factors through the dist backend (grid=mesh) and every
    # keyed request is stamped with Options.mesh_shape, so mesh and
    # single-device entries can never serve each other's requests.
    # None = single-device serving; default from SLU_SERVE_MESH /
    # SLU_MESH_SHAPE.
    mesh: object | None = dataclasses.field(
        default_factory=_mesh_from_env)
    # multi-tenant QoS gate (fleet/policy.py QosGate, duck-typed:
    # anything with admit(tenant)): consulted at the front door for
    # requests carrying a tenant= label; a refusal raises
    # TenantThrottled — typed shed, never rerouted.  None = no gate,
    # tenant labels pass through unexamined.
    qos: object | None = None


_BLAS_LIMITED = False
_blas_limit_lock = threading.Lock()


def _ensure_blas_limit() -> None:
    """Pin the host BLAS pool for the serving process (once,
    process-wide, first SolveService applies it).  A multi-threaded
    OpenBLAS pool is the wrong shape for concurrent small solves: its
    spin-wait barriers let ONE caller monopolize every core, so a
    background factorization's host BLAS calls stall the whole solve
    path — measured as the stream drill's overlap A/B failing at
    1.45x p99 until this pin (1.05x after; the pinned arm's own p99
    variance collapses too).  `SLU_SERVE_BLAS_THREADS` sizes it (1
    default, 0 = leave the pool alone); degrades to a no-op without
    threadpoolctl."""
    global _BLAS_LIMITED
    with _blas_limit_lock:
        if _BLAS_LIMITED:
            return
        _BLAS_LIMITED = True
    n = flags.env_int("SLU_SERVE_BLAS_THREADS", 1)
    if n <= 0:
        return
    try:
        import threadpoolctl
        threadpoolctl.threadpool_limits(limits=n, user_api="blas")
    except Exception:       # noqa: BLE001 — optional dependency
        pass


class _CacheObsProvider:
    """Registry shim over a FactorCache: its stats() counters plus
    the breaker's by_state, in JSON-safe form — the "cache" leg of
    the export snapshot (obs/export.py) that obs/aggregate.py sums
    into the fleet view."""

    def __init__(self, cache: FactorCache) -> None:
        self._cache = cache

    def snapshot(self) -> dict:
        out = dict(self._cache.stats())
        br = self._cache.breaker
        out["breaker_by_state"] = (br.snapshot()["by_state"]
                                   if br is not None else {})
        return out


class SolveService:
    def __init__(self, config: ServeConfig | None = None,
                 metrics: Metrics | None = None,
                 cache: FactorCache | None = None) -> None:
        self.config = config or ServeConfig()
        _ensure_blas_limit()
        if self.config.miss_policy not in ("factor", "failfast"):
            raise ValueError(
                f"unknown miss_policy {self.config.miss_policy!r}")
        self.metrics = metrics or Metrics()
        # the service's metrics ARE the registry's "serve" surface:
        # obs.snapshot() / obs.dump_text() expose them next to phase
        # stats, compile misses and the health monitors
        self.metrics.register_obs("serve")
        # `is not None`, not truthiness: an EMPTY FactorCache has
        # len()==0 and would be silently replaced
        if cache is not None:
            self.cache = cache
        else:
            cfg = self.config
            store = (FactorStore(cfg.store_dir, metrics=self.metrics)
                     if cfg.store_dir else None)
            self.cache = FactorCache(
                capacity_bytes=cfg.capacity_bytes,
                backend=cfg.backend, metrics=self.metrics,
                store=store, mesh=cfg.mesh,
                # True = coordinator over whatever store the cache
                # resolves (store_dir OR SLU_FT_STORE); False = an
                # explicit opt-out SLU_FLEET=1 must not override
                fleet=bool(cfg.fleet),
                breaker=(CircuitBreaker(
                    threshold=cfg.breaker_threshold,
                    cooldown_s=cfg.breaker_cooldown_s,
                    metrics=self.metrics)
                    if cfg.breaker_threshold > 0 else None),
                retry=(RetryPolicy(attempts=1 + cfg.factor_retries,
                                   base_s=cfg.retry_base_s)
                       if cfg.factor_retries > 0 else None))
        if self.cache.on_evict is None:
            # an evicted key's batchers must die with it, or their
            # flusher threads pin the factors the byte bound claims to
            # have released
            self.cache.on_evict = self._on_evict
        self._lock = threading.Lock()
        # keyed by (CacheKey, solve-time option values): requests
        # differing in trans/refinement share the FACTORS but cannot
        # share a batch — each variant batches (and warms) separately.
        # LRU-ordered and capped (config.max_batchers): every variant
        # owns a flusher thread, and an unbounded option sweep must
        # not grow threads for the process lifetime
        self._batchers: "collections.OrderedDict[tuple, MicroBatcher]" \
            = collections.OrderedDict()
        # options each key was prefactored with: keyed submits that
        # omit options get the PREFACTORED solve semantics (and its
        # warmed batcher variant), not silently-different defaults
        self._prefactor_opts: dict[CacheKey, Options] = {}
        # requested keys whose dtype-tier serving missed the sold
        # accuracy class: never tier-serve them again (the "re-key" —
        # their next request factors at the requested precision)
        self._tier_blocked: set[CacheKey] = set()
        # requested keys whose DEGRADED serving missed the accuracy
        # class: stale factors are a useless preconditioner for these
        # values — subsequent failures surface as errors, not as
        # berr-failing degraded answers
        self._degraded_blocked: set[CacheKey] = set()
        # open matrix streams (stream/pipeline.py StreamHandle),
        # closed with the service
        self._streams: list = []
        self._inflight = 0
        self._closed = False
        # request-scoped observability scratch (the SLO key computed
        # during routing, read back by submit on the same thread)
        self._tls = threading.local()
        # deferred flight/SLO finalizations: the done-callback runs on
        # the batcher's FLUSHER thread — the serve throughput
        # bottleneck — so it only stamps the latency and enqueues;
        # submitting threads (and close/obs_snapshot/recorder reads,
        # via the flight drain hook) drain.  Keeps the flight-on
        # flusher cost to ~a few dict appends per request (the
        # --flight-ab <=5% overhead budget).
        self._pending_fin: collections.deque = collections.deque()
        flight.register_drain_hook(self._drain_observability)
        # the cache's counters become the registry's "cache" surface —
        # what the export plane (obs/export.py) ships off-process and
        # obs/aggregate.py sums fleet-wide.  Last-wins like "serve".
        self._cache_obs = _CacheObsProvider(self.cache)
        obs_registry.REGISTRY.register("cache", self._cache_obs)
        # serve-layer factor coalescer (serve/coalescer.py): cold
        # same-pattern keys merge into one batch-engine factorization
        # when SLU_BATCH_COALESCE=1 — one env read at construction,
        # zero per-request overhead when off
        from .coalescer import FactorCoalescer, coalesce_enabled
        self._coalescer = (FactorCoalescer(self.cache,
                                           metrics=self.metrics)
                           if coalesce_enabled() else None)

    def _resident_for(self, a, options, key, deadline=None):
        """The cold-factor acquisition choke point: the factor
        coalescer (same-pattern keys batch through batch/engine.py,
        SLU_BATCH_COALESCE=1) or the cache's single-flight
        get_or_factorize.  Either way the caller gets an ordinary
        resident LUFactorization."""
        if self._coalescer is not None:
            return self._coalescer.submit(a, options, key=key,
                                          deadline=deadline)
        return self.cache.get_or_factorize(a, options, key=key,
                                           deadline=deadline)

    # -- operator surface ---------------------------------------------

    def _stamp_mesh(self, options: Options) -> Options:
        """Stamp the replica's mesh shape onto the request's options
        (Options.mesh_shape, a FACTOR_KEY_FIELDS leg) so every key
        this service creates names the residency it serves from —
        mesh-factored entries are a MISS for single-device requests
        and vice versa, across the cache, the durable store
        (entry_name hashes the options) and the fleet routing key.
        An explicit caller-set mesh_shape wins (tests pinning
        cross-residency misses rely on that)."""
        mesh = self.config.mesh
        if mesh is None or options.mesh_shape is not None:
            return options
        return options.replace(mesh_shape=tuple(
            int(mesh.shape[a]) for a in mesh.axis_names))

    def prefactor(self, a: CSRMatrix, options: Options | None = None
                  ) -> CacheKey:
        """Warm a key out of band: factorize (single-flight), then
        compile every ladder bucket for the requested solve options so
        first live traffic on this key runs recompile-free.  Returns
        the key for keyed submits."""
        with self._lock:
            if self._closed:
                raise ServeError("service is closed")
        t0 = time.perf_counter()
        options = self._stamp_mesh(options or Options())
        key = matrix_key(a, options)
        lu = self._resident_for(a, options, key)
        with self._lock:
            self._prefactor_opts[key] = options
        self._batcher_for(key, lu, options).warmup()
        # the start-up ledger's one record of a key's warm-up (its
        # plan, schedule and programs write their own inside it)
        COMPILE_WATCH.record_phases(
            t0, {"PREFACTOR": time.perf_counter() - t0})
        return key

    def grad_solve(self, a: CSRMatrix | CacheKey, b: np.ndarray,
                   xbar=None, options: Options | None = None,
                   A_values=None, trans=None):
        """Differentiable solve + adjoint pull against the factor
        cache (autodiff.vjp_solve): solve op(A)x = b on the resident
        factors, then pull the loss direction `xbar` (default ones)
        back through the custom VJP — ZERO new factorizations when
        the key is warm.  `a` may be a CacheKey from prefactor()
        (fail-fast FactorMissError when no longer resident — grad
        never pays an implicit factorization on a keyed request) or
        the matrix itself (resolved through the cache like solve()'s
        factor policy).  Returns an autodiff.GradResult; the flight
        record carries per-leg `grad.fwd` / `grad.adj` events and
        errors map through the same outcome taxonomy as solves."""
        from ..autodiff import vjp_solve
        with self._lock:
            if self._closed:
                raise ServeError("service is closed")
        rec = flight.start(kind="grad")
        t0 = time.monotonic()
        try:
            self._validate_request(a, b)
            if isinstance(a, CacheKey):
                key = a
                self.cache.note_demand(key)
                lu = self.cache.get(key)
                if lu is None:
                    self.metrics.inc("serve.miss_failfast")
                    raise FactorMissError(
                        "keyed grad_solve for a key no longer "
                        "resident; prefactor() it again")
            else:
                options = self._stamp_mesh(options or Options())
                key = matrix_key(a, options)
                self.cache.note_demand(key)
                lu = self._resident_for(a, options, key)
                if A_values is None:
                    A_values = a.data
            self._note_route(rec, lu, served="grad")
            flight.set_current(rec)
            try:
                res = vjp_solve(lu, b, xbar=xbar, A_values=A_values,
                                trans=trans)
            finally:
                flight.set_current(None)
        except BaseException as e:
            self.metrics.inc("serve.grad_errors")
            self._abort_request(rec, t0, e)
            raise
        self.metrics.inc("serve.grad_solves")
        if rec is not None:
            rec.finish("ok", e2e_s=time.monotonic() - t0)
        return res

    def stream(self, a: CSRMatrix, options: Options | None = None,
               config=None):
        """Open a matrix STREAM on `a`'s pattern (stream/pipeline.py):
        fixed structure, drifting values.  The returned StreamHandle
        primes synchronously (store read-through makes a restarted
        replica's prime warm), then serves every solve off the
        resident generation — stale generations with fresh-matrix
        refinement behind the berr guard — while a contained
        background worker refactors on the drift cadence and
        publishes via the atomic resident swap.  `config` is a
        stream.StreamConfig."""
        with self._lock:
            if self._closed:
                raise ServeError("service is closed")
        from ..stream.pipeline import StreamHandle
        if options is not None or self.config.mesh is not None:
            options = self._stamp_mesh(options or Options())
        h = StreamHandle(self, a, options, config)
        with self._lock:
            # close() may have drained _streams while the prime
            # factorization ran; an append now would leave the handle
            # (and its background worker) untracked forever
            closed = self._closed
            if not closed:
                self._streams.append(h)
        if closed:
            h.close()
            raise ServeError("service is closed")
        return h

    def _discard_stream(self, h) -> None:
        """StreamHandle.close() deregisters itself here — a closed
        stream left in _streams would pin its generations' factors
        until service close (unbounded under pattern churn, e.g. the
        scipy-compat pool's LRU retirement)."""
        with self._lock:
            try:
                self._streams.remove(h)
            except ValueError:
                pass

    def close(self) -> None:
        with self._lock:
            self._closed = True
            batchers = list(self._batchers.values())
            self._batchers.clear()
            streams = list(self._streams)
            self._streams.clear()
        if self._coalescer is not None:
            self._coalescer.close()
        for s in streams:
            s.close()
        for b in batchers:
            b.close()
        self._drain_observability()
        self.metrics.unregister_obs("serve")
        obs_registry.REGISTRY.unregister("cache", self._cache_obs)

    def drain_observability(self) -> None:
        """Flush deferred flight/SLO finalizations NOW — call before
        reading the flight ring or SLO windows outside the request
        flow (run_load does, after its workers join)."""
        self._drain_observability()

    def obs_snapshot(self) -> dict:
        """The unified observability snapshot (obs.Registry): serve
        metrics + phase stats + compile misses + health monitors."""
        from .. import obs
        self._drain_observability()
        return obs.snapshot()

    def dump_metrics_text(self) -> str:
        """Flat Prometheus-style text dump of the same registry."""
        from .. import obs
        self._drain_observability()
        return obs.dump_text()

    # -- request path --------------------------------------------------

    def submit(self, a: CSRMatrix | CacheKey, b: np.ndarray,
               options: Options | None = None,
               deadline_s: float | None = None,
               _t0: float | None = None,
               _router=None,
               tenant: str | None = None) -> Future:
        """Admit one solve request; resolves to x.  `a` may be the
        matrix itself or a CacheKey from prefactor() (keyed submits
        skip fingerprint hashing on the hot path).  `_t0` is the
        deadline base (solve() passes its own entry time so the
        blocking wait and the batcher enforce the SAME absolute
        deadline — a result landing in the skew window must not read
        'ok' on a future whose caller already timed out).  `_router`
        (package-internal: stream/pipeline.py) replaces the cache
        routing step with the caller's own — admission control,
        flight lifecycle and SLO accounting stay the service's.

        With the flight recorder on (obs/flight.py, SLU_FLIGHT) the
        request gets a monotonic request ID — exposed as
        `future.request_id`, attached to synchronously-raised serve
        errors as `e.request_id` — and a FlightRecord tracing it
        through cache, batcher, solve and every resilience event.
        Off, this path pays one module-global pointer check."""
        rec = flight.start()       # None when the recorder is off
        t0 = _t0 if _t0 is not None else time.monotonic()
        observed = rec is not None or slo.enabled()
        if observed:
            self._tls.slo_key = None
            if self._pending_fin:
                self._drain_observability()
        try:
            # front-door validation (numerics/): malformed or poisoned
            # inputs are refused typed BEFORE admission — they must
            # never consume a queue slot, a batcher dispatch, or (for
            # a cold CSRMatrix) a factorization
            self._validate_request(a, b)
            # multi-tenant QoS (fleet/policy.py): the gate refuses
            # BEFORE a queue slot is consumed — a shed tenant's
            # request must cost the service nothing but this check
            if self.config.qos is not None:
                try:
                    self.config.qos.admit(tenant)
                except TenantThrottled:
                    self.metrics.inc("serve.shed")
                    raise
            with self._lock:
                if self._closed:
                    raise ServeError("service is closed")
                if self._inflight >= self.config.max_queue_depth:
                    self.metrics.inc("serve.rejected")
                    raise ServeRejected(
                        f"queue depth {self._inflight} at cap "
                        f"{self.config.max_queue_depth}")
                self._inflight += 1
        except BaseException as e:
            self._abort_request(rec, t0, e)
            raise
        if rec is not None:
            rec.event("admit", inflight=self._inflight,
                      deadline_s=deadline_s)
        flight.set_current(rec)
        try:
            route = _router if _router is not None else self._route
            future = route(a, b, options, deadline_s, t0=t0)
        except BaseException as e:
            with self._lock:
                self._inflight -= 1
            self._abort_request(rec, t0, e)
            raise
        finally:
            if rec is not None:
                flight.set_current(None)
        if observed:
            skey = getattr(self._tls, "slo_key", None)
            if rec is not None:
                future.request_id = rec.rid
            # ONE combined callback, and it does almost nothing: it
            # runs on the flusher thread (the serve throughput
            # bottleneck), so it stamps the e2e latency and defers
            # the flight/SLO finalization to a submitting thread
            future.add_done_callback(
                lambda f: (self._release(f),
                           self._pending_fin.append(
                               (f, rec, time.monotonic() - t0,
                                skey))))
        else:
            future.add_done_callback(self._release)
        return future

    def solve(self, a: CSRMatrix | CacheKey, b: np.ndarray,
              options: Options | None = None,
              deadline_s: float | None = None,
              info: dict | None = None,
              _router=None,
              tenant: str | None = None) -> np.ndarray:
        """Blocking submit; respects the deadline while waiting.
        Pass `info={}` to receive out-of-band request metadata —
        currently `info['request_id']`, the flight-recorder rid (None
        when the recorder is off) — without changing the return
        type."""
        deadline_s = (deadline_s if deadline_s is not None
                      else self.config.default_deadline_s)
        t0 = time.monotonic()
        try:
            future = self.submit(a, b, options, deadline_s, _t0=t0,
                                 _router=_router, tenant=tenant)
        except BaseException as e:
            if info is not None:
                info["request_id"] = getattr(e, "request_id", None)
            raise
        if info is not None:
            info["request_id"] = getattr(future, "request_id", None)
        timeout = None
        if deadline_s is not None:
            timeout = max(0.0, t0 + deadline_s - time.monotonic())
        try:
            x = future.result(timeout=timeout)
        except concurrent.futures.TimeoutError:
            self.metrics.inc("serve.deadline_missed")
            raise DeadlineExceeded(
                f"no result within {deadline_s:.3f}s") from None
        self.metrics.observe("serve.e2e_latency_s",
                             time.monotonic() - t0)
        return x

    # -- internals -----------------------------------------------------

    @staticmethod
    def _validate_request(a, b) -> None:
        """Typed front-door input validation.  A CSRMatrix submit gets
        the full driver gate (dimensions + finite A and b); a keyed
        submit — where n is not known until cache lookup — still gets
        the finite/non-empty b checks."""
        if isinstance(a, CSRMatrix):
            from ..models.gssvx import _validate_system
            _validate_system(a, b)
            return
        bb = np.asarray(b)
        if bb.size == 0 or bb.ndim not in (1, 2):
            raise InvalidInputError(
                f"right-hand side has shape {bb.shape}")
        if not bool(np.isfinite(bb).all()):
            raise InvalidInputError("non-finite entries in b")

    def _release(self, _future) -> None:
        with self._lock:
            self._inflight -= 1

    # -- request-scoped observability (obs/flight.py, obs/slo.py) ------

    @staticmethod
    def _outcome_of(e: BaseException | None) -> str:
        """Exception -> the loadgen/flight outcome taxonomy (order
        matters: every serve error derives from ServeError)."""
        if e is None:
            return "ok"
        for cls, name in ((TenantThrottled, "shed"),
                          # TenantThrottled SUBCLASSES ServeRejected:
                          # the shed must match first or it reads as a
                          # full queue in every ledger
                          (ServeRejected, "rejected"),
                          (DeadlineExceeded, "deadline"),
                          (FactorPoisoned, "poisoned"),
                          (FlusherDead, "flusher_dead"),
                          (FactorMissError, "miss_failfast"),
                          (StaleFactorError, "stale_rejected"),
                          (ServeError, "serve_error"),
                          # numerical-trust refusals (numerics/):
                          # typed, and each its own loadgen status —
                          # a singular matrix is not a serve fault
                          (InvalidInputError, "invalid_input"),
                          (StructurallySingularError,
                           "structurally_singular"),
                          (SingularMatrixError, "singular")):
            if isinstance(e, cls):
                return name
        return "error"

    def _note_route(self, rec, lu: LUFactorization,
                    served: str = "direct") -> None:
        """Stamp routing facts known only once factors are resolved:
        the SLO accounting key (n-bucket, dtype tier) and the flight
        meta.  No-op unless the request is observed.  The (key, tier)
        pair is cached on the handle — np.dtype+format per request is
        measurable at micro-batch QPS."""
        if rec is None and not slo.enabled():
            return
        cached = getattr(lu, "_slo_leg", None)
        if cached is None:
            tier = np.dtype(lu.effective_options.factor_dtype).name
            cached = (slo.slo_key(lu.n, tier), tier)
            try:
                object.__setattr__(lu, "_slo_leg", cached)
            except Exception:
                pass               # frozen/slotted handle: recompute
        self._tls.slo_key = cached[0]
        if rec is not None:
            rec.annotate(n=lu.n, tier=cached[1], served=served)

    def _abort_request(self, rec, t0: float,
                       e: BaseException) -> None:
        """Synchronous-raise bookkeeping: finish the flight record,
        feed the SLO engine, and attach the rid to the exception so
        blocking callers can still correlate."""
        outcome = self._outcome_of(e)
        rid = None
        if rec is not None:
            rec.finish(outcome, error=e)
            rid = rec.rid
            try:
                e.request_id = rid
            except Exception:
                pass
        slo.observe(getattr(self._tls, "slo_key", None) or "unrouted",
                    time.monotonic() - t0, ok=False, rid=rid)

    def _drain_observability(self) -> None:
        """Finalize deferred flight/SLO completions (thread-safe:
        deque.popleft is atomic; a record finishes at most once)."""
        dq = self._pending_fin
        while dq:
            try:
                fut, rec, lat, skey = dq.popleft()
            except IndexError:
                break
            self._finish_request(fut, rec, lat, skey)

    def _finish_request(self, fut: Future, rec, lat: float,
                        skey: str | None) -> None:
        """Close the loop on an admitted request; `lat` is the e2e
        latency stamped by the done-callback."""
        if fut.cancelled():
            outcome, e = "cancelled", None
        else:
            e = fut.exception()
            if e is None:
                outcome = ("degraded"
                           if isinstance(fut.result(), DegradedResult)
                           else "ok")
            else:
                outcome = self._outcome_of(e)
        if rec is not None:
            rec.finish(outcome, error=e, e2e_s=lat)
        # degraded counts as SERVED for availability: it is a
        # berr-guarded answer, the honest alternative to an outage
        slo.observe(skey or "unrouted", lat,
                    ok=outcome in ("ok", "degraded"),
                    rid=rec.rid if rec is not None else None)

    def _route(self, a, b, options, deadline_s,
               t0: float | None = None) -> Future:
        deadline_s = (deadline_s if deadline_s is not None
                      else self.config.default_deadline_s)
        # deadline base = the caller's submit entry time, so the
        # batcher's late-solve check and solve()'s blocking wait agree
        deadline = ((t0 if t0 is not None else time.monotonic())
                    + deadline_s if deadline_s is not None else None)
        rec = flight.current()
        if isinstance(a, CacheKey):
            key = a
            # demand ledger BEFORE the lookup: fail-fast misses are
            # exactly the demand the fleet controller's prefactor
            # policy exists to serve
            self.cache.note_demand(key)
            # get(), not peek(): keyed submits ARE the hot path, and
            # the recorded hit rate must reflect them
            lu = self.cache.get(key)
            if lu is None:
                raise FactorMissError(
                    "keyed submit for a key no longer resident; "
                    "prefactor() it again")
            self._note_route(rec, lu)
            if options is None:
                # a keyed submit without options means "as
                # prefactored" — same solve semantics, same warmed
                # batcher variant (a default-Options fallback here
                # would hit an UNWARMED variant and recompile inline)
                with self._lock:
                    options = self._prefactor_opts.get(key)
        else:
            options = self._stamp_mesh(options or Options())
            key = matrix_key(a, options)
            self.cache.note_demand(key)
            resident = self.cache.peek(key, touch=False) is not None
            if not resident and self.config.dtype_tiers:
                tiered = self._tier_lookup(a, options or Options(),
                                           key)
                if tiered is not None:
                    t_key, t_lu, t_opts = tiered
                    self.metrics.inc("serve.dtype_tier_hits")
                    self._note_route(rec, t_lu, served="tier")
                    if rec is not None:
                        rec.event(
                            "tier.hit",
                            rung=np.dtype(t_opts.factor_dtype).name)
                    mb = self._batcher_for(
                        t_key, t_lu, t_opts,
                        on_berr=self._tier_guard(
                            key, t_key, t_opts, t_lu),
                        variant=("tier",))
                    try:
                        return mb.submit(b, deadline=deadline)
                    except ServeError:
                        raise FactorMissError(
                            "tier factors evicted concurrently; "
                            "resubmit to re-factor") from None
            if not resident and self.config.miss_policy == "failfast":
                self.metrics.inc("serve.miss_failfast")
                raise FactorMissError(
                    f"cold key under failfast policy (pattern "
                    f"{key.pattern[:12]}; inline factorization costs "
                    f"{factor_cost_hint()})")
            # "factor" policy: pay it here, once — concurrent misses
            # on this key coalesce into the leader's factorization.
            # Followers respect the request deadline while waiting;
            # the leader runs to completion (see get_or_factorize)
            try:
                lu = self._resident_for(a, options, key,
                                        deadline=deadline)
            except (DeadlineExceeded, ServeRejected):
                raise           # economics, not faults — never degrade
            except Exception as factor_err:
                # DEGRADED MODE: the factorization failed (raised, NaN
                # factors, circuit-broken).  If a stale same-pattern
                # factorization is resident, serve through it with
                # refinement against the FRESH matrix — an answer
                # stamped DegradedResult beats an outage; the berr
                # guard keeps it honest
                fut = self._try_degraded(a, key, options or Options(),
                                         b, deadline, factor_err)
                if fut is not None:
                    return fut
                raise
            self._note_route(rec, lu)
        try:
            return self._submit_resilient(key, lu, options or Options(),
                                          b, deadline)
        except FlusherDead:
            raise       # lightning struck twice: explicit, not a miss
        except ServeError:
            # the batcher was retired by a concurrent eviction between
            # lookup and submit; the factors are gone — same contract
            # as a cold keyed submit
            raise FactorMissError(
                "factors evicted concurrently; resubmit (or "
                "prefactor) to re-factor") from None

    def _submit_resilient(self, key: CacheKey, lu: LUFactorization,
                          options: Options, b, deadline) -> Future:
        """Submit into the key's batcher with ONE transparent resubmit
        if the flusher dies under the request: the factors are still
        resident (a flusher death is a thread fault, not an eviction),
        _batcher_for replaces the dead batcher, and the caller sees
        FlusherDead only when the replacement dies too.  Covers both
        the synchronous raise (submit into a just-died batcher) and
        the asynchronous one (the request was claimed by the batch the
        flusher died holding)."""
        # carried explicitly: the async resubmit runs on the dying
        # flusher's thread, where no thread-local current record is
        # bound — without this the resubmitted leg would vanish from
        # the request's flight record
        f_rec = flight.current()

        def submit_once() -> Future:
            flight.set_current(f_rec)
            try:
                return self._batcher_for(key, lu, options).submit(
                    b, deadline=deadline)
            finally:
                if f_rec is not None:
                    flight.set_current(None)

        # ONE retry total, shared between the synchronous raise and
        # the async relay — a request never runs more than twice
        retry_left = 1
        try:
            fut = submit_once()
        except FlusherDead:
            retry_left = 0
            fut = submit_once()
        out: Future = Future()

        def relay(f: Future, retry_left: int) -> None:
            # runs on the resolving thread (normally the flusher; on
            # death, the dying flusher's containment handler — which
            # holds no locks by then, so re-entering _batcher_for to
            # build the replacement is safe)
            if f.cancelled():
                out.cancel()
                return
            e = f.exception()
            if e is None:
                out.set_result(f.result())
            elif isinstance(e, FlusherDead) and retry_left:
                if deadline is not None \
                        and time.monotonic() > deadline:
                    # the resubmit would land late by construction
                    out.set_exception(DeadlineExceeded(
                        "deadline passed during flusher recovery"))
                    return
                self.metrics.inc("serve.flusher_resubmits")
                if f_rec is not None:
                    f_rec.event("resubmit")
                try:
                    f2 = submit_once()
                except BaseException as e2:
                    out.set_exception(e2)
                    return
                f2.add_done_callback(lambda g: relay(g, 0))
            else:
                out.set_exception(e)

        fut.add_done_callback(lambda f: relay(f, retry_left))
        return out

    def _tier_lookup(self, a: CSRMatrix, options: Options,
                     key: CacheKey):
        """A resident LOWER-precision factorization of this matrix
        able to serve the request's accuracy class through
        doubleword-residual refinement (precision/policy.lower_rungs,
        finest resident rung wins).  Returns (tier key, handle, solve
        options) or None.  The solve options keep the request's
        refine_dtype — the accuracy being sold — and switch only the
        residual strategy, so the berr the guard below checks is
        measured against the promised class."""
        from ..options import IterRefine
        from ..precision.policy import lower_rungs
        if options.iter_refine == IterRefine.NOREFINE:
            return None           # nothing recovers the precision gap
        if np.issubdtype(np.dtype(a.dtype), np.complexfloating) \
                or np.dtype(options.factor_dtype).kind == "c":
            return None           # df64 pairs are real machinery
        with self._lock:
            if key in self._tier_blocked:
                return None
        hit = self.cache.resident_lower_tier(
            a, options, lower_rungs(options.factor_dtype), key=key)
        if hit is None:
            return None
        t_key, t_lu, d = hit
        t_opts = options.replace(
            factor_dtype=d,
            residual_mode="doubleword",
            iter_refine=IterRefine.SLU_DOUBLE)
        return t_key, t_lu, t_opts

    def _tier_guard(self, requested_key: CacheKey, t_key: CacheKey,
                    t_opts: Options, t_lu: LUFactorization | None = None):
        """Per-dispatch berr watchdog for tier-served traffic: berr
        above the sold accuracy class (the gssvx escalation gate,
        64·eps(refine_dtype)) blocks the tier mapping — a health
        `tier_berr` escalation event, a serve.tier_escalations tick,
        and every subsequent request for `requested_key` re-keys to a
        genuine full-precision factorization."""
        from .. import obs
        from ..models.gssvx import _ESC_BERR_SLACK
        from ..numerics.policy import ConditionPolicy
        # ill-conditioned keys get a TIGHTER accuracy guard (slack /
        # SLU_COND_SLACK_DIV): high-kappa systems are exactly where a
        # berr sitting just under the generic 64-eps gate can still
        # hide a large forward error
        slack = ConditionPolicy.from_env().berr_slack(
            _ESC_BERR_SLACK, getattr(t_lu, "rcond", None),
            t_opts.refine_dtype)
        limit = slack * float(
            np.finfo(np.dtype(t_opts.refine_dtype)).eps)

        def on_berr(berr: float) -> None:
            if berr <= limit and np.isfinite(berr):
                return
            flight.batch_event("tier.berr_block", berr=float(berr))
            with self._lock:
                already = requested_key in self._tier_blocked
                self._tier_blocked.add(requested_key)
            if already:
                return
            self.metrics.inc("serve.tier_escalations")
            obs.HEALTH.record_escalation(
                berr=berr, factor_dtype=t_opts.factor_dtype,
                refine_dtype=t_opts.refine_dtype,
                to_dtype=t_opts.refine_dtype, trigger="tier_berr")

        return on_berr

    # -- degraded mode (resilience pillar 4) ---------------------------

    def _try_degraded(self, a: CSRMatrix, key: CacheKey,
                      options: Options, b, deadline,
                      cause: BaseException):
        """A future serving `b` off resident stale same-pattern
        factors, or None when degraded mode cannot apply (disabled,
        berr-blocked key, nothing resident).  The handle is a replace
        copy carrying the FRESH matrix, so iterative refinement
        computes residuals against the values actually being solved —
        stale factors act as the preconditioner (ROADMAP item 4b's
        staleness-tolerant mode, applied as a failure fallback)."""
        if not self.config.degraded or not isinstance(a, CSRMatrix):
            return None
        with self._lock:
            if key in self._degraded_blocked:
                return None
        stale = self.cache.resident_stale(key)
        if stale is None:
            return None
        s_key, s_lu = stale
        d_opts = self._degraded_options(a, s_lu, options)
        handle = refine_wrapper(s_lu, a)
        try:
            mb = self._batcher_for(
                s_key, handle, d_opts,
                on_berr=self._degraded_guard(key, d_opts, s_lu),
                # per-(requested values) variant: each drifted value
                # set refines against ITS matrix and must not share a
                # batch (or a handle) with another's
                variant=("degraded", key.values))
            fut = mb.submit(b, deadline=deadline)
        except ServeError:
            return None     # stale factors evicted under us: no cover
        self.metrics.inc("serve.degraded_served")
        rec = flight.current()
        self._note_route(rec, s_lu, served="degraded")
        if rec is not None:
            rec.event("degraded.cover",
                      cause=f"{type(cause).__name__}: {cause}",
                      stale_values=s_key.values[:12])
        from .. import obs
        obs.instant("serve.degraded", cat="serve",
                    args={"pattern": key.pattern[:12],
                          "cause": type(cause).__name__})
        return _mark_degraded(fut)

    @staticmethod
    def _degraded_options(a: CSRMatrix, s_lu: LUFactorization,
                          options: Options) -> Options:
        """Degraded solve semantics: refinement is MANDATORY (it is
        what closes the stale-factor gap), and sub-f64 real factors
        ride the doubleword residual so the recovered precision
        matches the f64 class the berr guard checks.  f64-class or
        complex factors keep their native residual (doubleword is
        real-only machinery, and over f64 factors it is rejected by
        the precision policy)."""
        from ..options import IterRefine
        d = options
        if d.iter_refine == IterRefine.NOREFINE:
            d = d.replace(iter_refine=IterRefine.SLU_DOUBLE)
        f_dt = np.dtype(s_lu.effective_options.factor_dtype)
        if (f_dt.kind != "c"
                and not np.issubdtype(np.dtype(a.dtype),
                                      np.complexfloating)
                and np.finfo(f_dt).eps > np.finfo(np.float64).eps):
            d = d.replace(residual_mode="doubleword",
                          iter_refine=IterRefine.SLU_DOUBLE)
        return d

    def _degraded_guard(self, requested_key: CacheKey,
                        d_opts: Options,
                        lu: LUFactorization | None = None):
        """berr watchdog for degraded dispatches — the same accuracy
        class the tier guard enforces (64·eps(refine_dtype)): a
        degraded answer whose refinement could not close the
        stale-factor gap blocks the key from further degraded serving
        (subsequent failures surface as errors) and fires a
        `degraded_berr` health escalation."""
        from .. import obs
        from ..models.gssvx import _ESC_BERR_SLACK
        from ..numerics.policy import ConditionPolicy
        # same condition-aware tightening as the tier guard: degraded
        # serving of an ill-conditioned key has the least margin of
        # any path in the service
        slack = ConditionPolicy.from_env().berr_slack(
            _ESC_BERR_SLACK, getattr(lu, "rcond", None),
            d_opts.refine_dtype)
        limit = slack * float(
            np.finfo(np.dtype(d_opts.refine_dtype)).eps)

        def on_berr(berr: float) -> None:
            if berr <= limit and np.isfinite(berr):
                return
            flight.batch_event("degraded.berr_block",
                               berr=float(berr))
            with self._lock:
                already = requested_key in self._degraded_blocked
                self._degraded_blocked.add(requested_key)
            if already:
                return
            self.metrics.inc("serve.degraded_escalations")
            obs.HEALTH.record_escalation(
                berr=berr, factor_dtype=d_opts.factor_dtype,
                refine_dtype=d_opts.refine_dtype,
                to_dtype=d_opts.refine_dtype,
                trigger="degraded_berr")

        return on_berr

    def _batcher_for(self, key: CacheKey, lu: LUFactorization,
                     options: Options,
                     on_berr=None, variant: tuple = ()
                     ) -> MicroBatcher:
        """One MicroBatcher per (cache key, solve-time options).  Its
        solve_fn merges the request's solve knobs onto the shared
        handle (the gssvx FACTORED rung's merge) so the leader's
        factorization-time knobs never leak into other callers'
        solves — and requests with different trans/refinement never
        land in the same batch."""
        # guarded traffic (tier / degraded) gets its OWN variant leg:
        # its solve_fn carries a berr guard (and, degraded, its own
        # handle), and sharing a batcher created unguarded by direct
        # traffic with the same solve options would silently drop the
        # guard (and the re-key / block contract with it)
        if on_berr is not None and not variant:
            variant = ("guarded",)
        bkey = (key,) + solve_options_key(options) + tuple(variant)
        retired = []
        with self._lock:
            if self._closed:
                # close() may race a submit that already passed
                # admission; never resurrect a batcher on a closed
                # service
                raise ServeError("service is closed")
            mb = self._batchers.get(bkey)
            if mb is not None and mb.dead is not None:
                # a dead flusher already failed its futures
                # (FlusherDead); replace the batcher so the key
                # recovers instead of erroring forever
                self.metrics.inc("serve.batcher_replaced")
                retired.append(self._batchers.pop(bkey))
                mb = None
            if mb is not None:
                self._batchers.move_to_end(bkey)
            else:
                # residency check under the service lock: _on_evict
                # (which also takes this lock, strictly AFTER the
                # cache entry is gone) either sees the batcher we
                # insert here and retires it, or we see the eviction
                # and refuse — no orphan batcher can pin evicted
                # factors
                if self.cache.peek(key, touch=False) is None:
                    raise FactorMissError(
                        "factors evicted concurrently; resubmit to "
                        "re-factor")
                # assembly dtype from the MERGED options — the dtype
                # the dispatch's solve() refines and answers in (its
                # sweeps take the factor's precision whatever this
                # is).  An explicit request solve_dtype both re-types
                # the batch and downcasts client buffers (cast_rhs)
                # instead of tripping the promote-past rejection
                merged = merge_solve_options(lu.effective_options,
                                             options)
                from ..models.gssvx import solve_rhs_dtype
                mdtype = solve_rhs_dtype(
                    dataclasses.replace(lu, options=merged))
                mb = self._batchers[bkey] = MicroBatcher(
                    lu, max_linger_s=self.config.max_linger_s,
                    ladder=self.config.ladder, metrics=self.metrics,
                    dtype=mdtype,
                    cast_rhs=merged.solve_dtype is not None,
                    solve_fn=_merged_solve_fn(options, self.metrics,
                                              on_berr=on_berr))
                while len(self._batchers) > self.config.max_batchers:
                    _, old = self._batchers.popitem(last=False)
                    retired.append(old)
        for old in retired:
            old.close(flush=True)
        return mb

    def _on_evict(self, key: CacheKey, _lu) -> None:
        """Factor-cache eviction hook: retire every batcher variant of
        the evicted key (flush first — queued requests still hold the
        handle and complete; new traffic re-factors)."""
        with self._lock:
            victims = [bk for bk in self._batchers if bk[0] == key]
            batchers = [self._batchers.pop(bk) for bk in victims]
            self._prefactor_opts.pop(key, None)
        for mb in batchers:
            mb.close(flush=True)


def solve_jit_cache_size(lu: LUFactorization) -> int:
    """Number of compiled entries in the jitted solve program serving
    this handle — the recompile pin for the zero-recompiles-after-
    warmup contract (tests assert it is flat across a load run).
    Returns -1 when the handle has no single jitted solve program
    (host backend; a staged handle under the legacy sweep, a program
    a group each way)."""
    if lu.backend == "dist" and lu.device_lu is not None:
        # mesh replica (ISSUE 17): the handle dispatches through the
        # plan-level dist solve cache — sum every compiled signature
        # across its arms (merged / rhs-sharded), so a
        # ladder-induced recompile on ANY arm moves this probe
        from ..parallel.factor_dist import dist_solve_cache_size
        return dist_solve_cache_size(lu.device_lu)
    if lu.backend != "jax" or lu.device_lu is None:
        return -1
    from ..ops import batched, trisolve
    d = lu.device_lu
    if trisolve.sweeps_packed():
        # the merged arm dispatches the packed solve program
        # (trisolve.solve_packed), not _phase_fns', for a DeviceLU
        # and a StagedLU alike — probe that one
        return trisolve.solve_packed_cache_size(d)
    if isinstance(d, batched.StagedLU):
        return -1
    _, solve_fn = batched._phase_fns(
        d.schedule, d.dtype, batched._thresh_for(lu.plan, d.dtype),
        pair=batched._lu_is_pair(d))
    try:
        return int(solve_fn._cache_size())
    except AttributeError:
        return -1
