"""LRU factor cache with single-flight factorization.

A pre-round chip record (not re-measured) has the economics this
module exploits: one
n=27k factorization costs ~477 s while a held-factor solve costs 59 ms
(8.3 ms/rhs at nrhs=64).  A service must therefore keep
`LUFactorization` handles resident and amortize them across every
caller that presents the same matrix — and must never pay the same
factorization twice because two requests raced on a cold key.

Keys.  A matrix is fingerprinted in two tiers:

  pattern key = sha1(m, n, indptr, indices)            — the symbolics
  full key    = pattern key + sha1(values) + options.factor_key()
                + the EFFECTIVE factor dtype

The options leg is `Options.factor_key()` (options.py
FACTOR_KEY_FIELDS): exactly the factorization-describing knobs.
Solve-time knobs (trans, refinement) are merged per request by the
FACTORED rung in models/gssvx.py and must not split entries.  The
dtype in the key is `effective_factor_dtype` — a complex matrix with a
real factor_dtype promotes, and the key must name the factors actually
stored.

Pattern tier.  On a full-key miss whose PATTERN key hits, the cached
`FactorPlan` is reused and only the numeric phase runs — the
`SamePattern_SameRowPerm` rung (SRC/superlu_defs.h:589-593): perms,
scalings and the whole symbolic plan carry over, new values stream
through `plan.scaled_values`.  That is the PDE-app refactorization
path (same mesh, new coefficients) at plan-free cost.  Accuracy note:
refinement runs per solve and its berr is exported to the
`serve.berr` histogram, but the serve path never re-factors (no
gssvx escalation rung) — values the inherited scaling serves poorly
surface as an elevated berr there, and the remedy is a fresh
full-key factorization (new Options or explicit prefactor), not a
silent retry.

Single-flight.  N concurrent misses on one key elect one leader that
factors; the rest block on the flight and share the result (the
standard groupcache discipline).  Counters expose hits / misses /
pattern_hits / evictions / single_flight_waits / bytes_resident.

Capacity is a byte bound over `query_space(lu)["held_bytes"]` —
factors dominate (the n=27k f32 example holds ~GBs); plans ride along
uncounted in the pattern tier with a separate entry bound.

Resilience tier (resilience/).  With a FactorStore attached
(`SLU_FT_STORE=dir`) every fresh factorization is written through to
disk (atomic rename + checksum) and every full-key miss reads through
it — a `kill -9`'d replica boots warm, and corrupted entries are
quarantined, never served.  The lead factorization is wrapped in a
per-key circuit breaker and a bounded retry policy, and NaN/Inf
factors raise FactorPoisoned instead of entering the cache.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import threading
import time
from typing import Callable, Optional

import numpy as np

from ..models.gssvx import (LUFactorization, effective_factor_dtype,
                            factorize, factors_finite, query_space)
from ..obs import flight
from ..options import Options
from ..plan.plan import plan_factorization
from ..resilience import chaos
from ..resilience.store import store_from_env
from ..sparse import CSRMatrix
from .errors import DeadlineExceeded, FactorPoisoned
from .metrics import Metrics


def pattern_fingerprint(a: CSRMatrix) -> str:
    """Symbolic identity: shape + CSR structure, values excluded."""
    h = hashlib.sha1()
    h.update(f"{a.m}x{a.n}".encode())
    h.update(np.ascontiguousarray(a.indptr).tobytes())
    h.update(np.ascontiguousarray(a.indices).tobytes())
    return h.hexdigest()


def values_fingerprint(a: CSRMatrix) -> str:
    return hashlib.sha1(np.ascontiguousarray(a.data).tobytes()).hexdigest()


@dataclasses.dataclass(frozen=True)
class CacheKey:
    pattern: str
    values: str
    options: tuple

    @property
    def pattern_key(self) -> tuple:
        # plan reuse is only sound when the plan-shaping options match
        # too, so the pattern tier keys on (structure, options) and
        # drops only the values leg
        return (self.pattern, self.options)


def matrix_key(a: CSRMatrix, options: Options | None = None) -> CacheKey:
    options = options or Options()
    eff_dtype = effective_factor_dtype(a.dtype, options.factor_dtype).name
    return CacheKey(pattern=pattern_fingerprint(a),
                    values=values_fingerprint(a),
                    options=options.factor_key() + (eff_dtype,))


class _Flight:
    """One in-progress factorization; followers wait on the event."""

    __slots__ = ("event", "lu", "error")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.lu: Optional[LUFactorization] = None
        self.error: Optional[BaseException] = None


@dataclasses.dataclass
class _Entry:
    lu: LUFactorization
    nbytes: int


class FactorCache:
    """Thread-safe LRU of LUFactorization handles + a plan tier.

    `factorize_fn(a, options, plan)` is injectable for tests (count
    invocations, simulate slow factorizations); the default runs the
    real pipeline via models/gssvx.py.
    """

    def __init__(self, capacity_bytes: int | None = None,
                 max_plans: int = 64,
                 backend: str = "auto",
                 metrics: Metrics | None = None,
                 factorize_fn: Callable | None = None,
                 on_evict: Callable | None = None,
                 store=None,
                 breaker=None,
                 retry=None,
                 fleet=None,
                 validate_factors: bool = True,
                 mesh=None) -> None:
        self.capacity_bytes = capacity_bytes
        self.max_plans = max_plans
        self.backend = backend
        # device-mesh residency (ISSUE 17): with a mesh attached every
        # factorization this cache leads runs through the dist backend
        # (grid=mesh) and the resident handles are DistLU-backed —
        # factor once across the mesh, solve from all chips.  The
        # service stamps Options.mesh_shape on every keyed request, so
        # mesh and single-device entries can never serve each other.
        self.mesh = mesh
        self.metrics = metrics or Metrics()
        self._factorize_fn = factorize_fn or self._default_factorize
        # durable persistence tier (resilience/store.py): read-through
        # on full-key misses, write-through on fresh factorizations —
        # a restarted replica boots warm.  Default from SLU_FT_STORE.
        self.store = store if store is not None \
            else store_from_env(metrics=self.metrics)
        if self.store is not None and self.store._metrics is None:
            # adopt an explicitly-passed store into this cache's
            # metrics so its saves/hits/quarantines are observable
            self.store._metrics = self.metrics
        if self.store is not None and mesh is not None:
            # hand the mesh to the store so persisted dist entries can
            # rebuild onto it (kind="dist" round-trip); a store with
            # no mesh refuses those entries typed instead
            self.store.mesh = mesh
        # per-key circuit breaker + bounded retry (resilience/): the
        # containment pair around _acquire_factors.  Both default off
        # for direct cache users; SolveService wires them from
        # ServeConfig.
        self.breaker = breaker
        self.retry = retry
        # fleet-wide single-flight (fleet/lease.py): with a shared
        # store, a cold key elects ONE leader across all replica
        # PROCESSES — followers adopt the published entry instead of
        # stampeding the factorization.  True = REQUESTED (a
        # coordinator over whatever store resolved, ServeConfig.fleet
        # or explicit store alike); None defaults from SLU_FLEET=1;
        # False is an EXPLICIT opt-out the env must not override
        # (ServeConfig(fleet=False) under SLU_FLEET=1); explicit
        # coordinators (tests) pass through.  Either way there is
        # nothing to coordinate without a store.
        if self.store is not None:
            if fleet is True:
                from ..fleet.lease import FleetCoordinator
                fleet = FleetCoordinator(self.store.root,
                                         metrics=self.metrics)
            elif fleet is None:
                from ..fleet.lease import coordinator_from_env
                fleet = coordinator_from_env(self.store.root,
                                             metrics=self.metrics)
        self.fleet = fleet if not isinstance(fleet, bool) else None
        if self.fleet is not None and self.fleet._metrics is None:
            self.fleet._metrics = self.metrics
        # finite-validation gate: NaN/Inf factors raise FactorPoisoned
        # instead of entering the cache (GESP has no runtime pivoting
        # to catch them later — they would solve to silent garbage).
        # One O(factor bytes) host pass per factorization, noise next
        # to the factorization itself.
        self.validate_factors = validate_factors
        # on_evict(key, lu) fires AFTER the cache lock is released for
        # every LRU eviction — the service uses it to drop the evicted
        # key's batchers, so eviction actually releases the factors
        # instead of leaving them pinned by a flusher thread
        self.on_evict = on_evict
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[CacheKey, _Entry]" = \
            collections.OrderedDict()
        self._plans: "collections.OrderedDict[tuple, object]" = \
            collections.OrderedDict()
        self._inflight: dict[CacheKey, _Flight] = {}
        self.bytes_resident = 0
        # demand ledger (ISSUE 16): per-key request counts noted by
        # the service on EVERY routed request — hit, inline miss, and
        # fail-fast miss alike — so the fleet controller can see which
        # PATTERNS are hot before they are resident and prefactor them
        # at their ring homes.  Bounded recency-ordered dict: the cold
        # tail falls off, the hot head is what policy reads.
        self._popularity: "collections.OrderedDict[CacheKey, int]" = \
            collections.OrderedDict()
        self._popularity_cap = 256

    # -- introspection -------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        m = self.metrics
        with self._lock:
            resident = self.bytes_resident
            entries = len(self._entries)
            plans = len(self._plans)
        hits = m.counter("factor_cache.hits")
        misses = m.counter("factor_cache.misses")
        total = hits + misses
        return {
            "entries": entries,
            "plans": plans,
            "bytes_resident": resident,
            "hits": hits,
            "misses": misses,
            "pattern_hits": m.counter("factor_cache.pattern_hits"),
            "evictions": m.counter("factor_cache.evictions"),
            "single_flight_waits":
                m.counter("factor_cache.single_flight_waits"),
            "factorizations": m.counter("factor_cache.factorizations"),
            "hit_rate": (hits / total) if total else 0.0,
            # resilience tier (resilience/store.py, breaker.py)
            "store_hits": m.counter("factor_cache.store_hits"),
            "store_saves": m.counter("factor_store.saves"),
            "store_quarantined": m.counter("factor_store.quarantined"),
            "factor_retries": m.counter("factor_cache.factor_retries"),
            "breaker_rejected":
                m.counter("factor_cache.breaker_rejected"),
            # fleet tier (fleet/lease.py): cross-process single-flight
            "fleet_adopted": m.counter("factor_cache.fleet_adopted"),
            "fleet_leads": m.counter("fleet.lead"),
            "fleet_waits": m.counter("fleet.waits"),
            "fleet_steals": m.counter("fleet.steals"),
        }

    # -- demand ledger (ISSUE 16) --------------------------------------

    def note_demand(self, key: CacheKey) -> None:
        """Record one request's demand for `key` (hit or miss — the
        service calls this on every routed request).  Feeds
        `popularity()`, the fleet controller's prefactor signal."""
        with self._lock:
            self._popularity[key] = self._popularity.get(key, 0) + 1
            self._popularity.move_to_end(key)
            while len(self._popularity) > self._popularity_cap:
                self._popularity.popitem(last=False)

    def popularity(self, top: int = 16) -> list[dict]:
        """The hottest keys by demand count, hottest first.  Each
        entry: {"key": CacheKey, "count": int, "resident": bool} —
        `resident` lets policy skip keys already factored, so the
        prefactor loop only spends on genuinely cold demand."""
        with self._lock:
            ranked = sorted(self._popularity.items(),
                            key=lambda kv: kv[1], reverse=True)[:top]
            return [{"key": k, "count": c,
                     "resident": k in self._entries}
                    for k, c in ranked]

    # -- core ----------------------------------------------------------

    def peek(self, key: CacheKey,
             touch: bool = True) -> Optional[LUFactorization]:
        """Lookup without hit/miss accounting (policy probes, keyed
        submits).  touch=False also leaves the LRU order alone."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                return None
            if touch:
                self._entries.move_to_end(key)
            return ent.lu

    def resident_lower_tier(self, a: CSRMatrix, options: Options,
                            rungs,
                            key: CacheKey | None = None
                            ) -> Optional[tuple]:
        """Dtype-TIER probe (precision/policy.py): the first RESIDENT
        sibling of (a, options) among `rungs` — coarser factor dtypes,
        probed in the given order (pass precision.lower_rungs's
        finest-first order so an fp32 resident beats a bf16 one).
        Returns (tier key, handle, rung dtype) or None.  Pass the
        request's already-computed `key` to skip re-hashing the
        matrix: only the OPTIONS leg varies across rungs, so the
        pattern/values sha1 legs (milliseconds at production nnz) are
        reused on this hot path.  Probes touch the LRU position (a
        tier hit IS a use of those factors) but not the hit/miss
        counters — the tier decision is the service's, not a cache
        miss."""
        for d in rungs:
            t_opts = options.replace(factor_dtype=d)
            if key is not None:
                eff = effective_factor_dtype(a.dtype, d).name
                t_key = CacheKey(pattern=key.pattern,
                                 values=key.values,
                                 options=t_opts.factor_key() + (eff,))
            else:
                t_key = matrix_key(a, t_opts)
            t_lu = self.peek(t_key)
            if t_lu is not None:
                return t_key, t_lu, d
        return None

    def evict(self, key: CacheKey) -> Optional[LUFactorization]:
        """Explicitly drop `key`'s resident factors (a probe-refused
        stream generation, operator invalidation).  Fires on_evict
        like a capacity eviction so dependent batchers retire; the
        pattern-tier plan stays (the NEXT factorization of this
        pattern reuses it legitimately).  Returns the evicted handle
        or None."""
        with self._lock:
            e = self._entries.pop(key, None)
            if e is None:
                return None
            self.bytes_resident -= e.nbytes
            self.metrics.inc("factor_cache.evictions")
        if self.on_evict is not None:
            self.on_evict(key, e.lu)
        return e.lu

    def get(self, key: CacheKey) -> Optional[LUFactorization]:
        """Plain lookup (counts a hit/miss, refreshes LRU position)."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                self.metrics.inc("factor_cache.hits")
                flight.event("cache.hit")
                return ent.lu
        self.metrics.inc("factor_cache.misses")
        flight.event("cache.miss")
        return None

    def get_or_factorize(self, a: CSRMatrix,
                         options: Options | None = None,
                         key: CacheKey | None = None,
                         deadline: float | None = None
                         ) -> LUFactorization:
        """Return resident factors for (a, options), factoring at most
        once per key across all concurrent callers.

        `deadline` (absolute time.monotonic()) bounds how long a
        FOLLOWER waits on another caller's in-flight factorization
        (DeadlineExceeded on expiry).  The leader deliberately ignores
        it: its factorization is useful to every future caller of the
        key, so abandoning it at the deadline would waste the work —
        callers that cannot afford to lead use miss_policy='failfast'."""
        options = options or Options()
        key = key or matrix_key(a, options)
        while True:
            with self._lock:
                ent = self._entries.get(key)
                if ent is not None:
                    self._entries.move_to_end(key)
                    self.metrics.inc("factor_cache.hits")
                    flight.event("cache.hit")
                    return ent.lu
                fl = self._inflight.get(key)
                if fl is None:
                    fl = self._inflight[key] = _Flight()
                    leader = True
                else:
                    leader = False
            if not leader:
                self.metrics.inc("factor_cache.single_flight_waits")
                flight.event("cache.single_flight_wait")
                t_wait = time.monotonic()
                timeout = (None if deadline is None
                           else max(0.0, deadline - time.monotonic()))
                if not fl.event.wait(timeout):
                    raise DeadlineExceeded(
                        "deadline passed waiting on another caller's "
                        "in-flight factorization")
                flight.event(
                    "cache.single_flight_done",
                    waited_us=int((time.monotonic() - t_wait) * 1e6),
                    ok=fl.error is None)
                if fl.error is not None:
                    raise fl.error
                if fl.lu is not None:
                    return fl.lu
                continue  # leader aborted without result; re-elect
            return self._lead_factorization(a, options, key, fl)

    def _lead_factorization(self, a, options, key, fl):
        # CONTAINMENT CONTRACT (pinned by tests/test_resilience.py):
        # whatever _acquire_factors raises is (a) recorded on the
        # flight so every waiting follower wakes with the SAME
        # exception, and (b) the in-flight entry is removed in the
        # finally — so the N+1-th request elects a fresh leader and
        # retries cleanly instead of hanging on a dead flight or
        # finding a permanently-poisoned key slot.
        self.metrics.inc("factor_cache.misses")
        flight.event("cache.miss_lead")
        try:
            lu = self._acquire_factors(a, options, key)
            self.put(key, lu)
            fl.lu = lu
            return lu
        except BaseException as e:
            fl.error = e
            raise
        finally:
            with self._lock:
                self._inflight.pop(key, None)
            fl.event.set()

    def _acquire_factors(self, a, options, key) -> LUFactorization:
        """Factors for a confirmed miss: breaker gate → store
        read-through → fleet single-flight (one leader across all
        replica processes; followers adopt) → factorize (bounded
        retry, chaos sites, finite validation) → store
        write-through."""
        if self.breaker is not None and not self.breaker.allow(key):
            self.metrics.inc("factor_cache.breaker_rejected")
            raise FactorPoisoned(
                f"key circuit-broken ({self.breaker.state(key)}): "
                "its factorization failed repeatedly; retry after "
                "the cooldown")
        if self.store is not None:
            lu = self._verified_store_load(key)
            if lu is not None:
                self.metrics.inc("factor_cache.store_hits")
                if self.breaker is not None:
                    # a verified store hit resolves the key (and
                    # releases a half-open probe admitted above)
                    self.breaker.record_success(key)
                return lu
        if self.fleet is not None and self.store is not None:
            from ..resilience.store import entry_name
            lu, role = self.fleet.factor_once(
                entry_name(key),
                # cheap existence prefilter: the verified (and
                # counter-ticking) load only on presence, so a
                # follower's poll loop doesn't inflate miss counters
                probe=lambda: (self._verified_store_load(key)
                               if self.store.contains(key) else None),
                work=lambda: self._factor_locally(a, options, key))
            if role == "adopt":
                # another replica published; this one rode the wait.
                # Same bookkeeping as a store hit: the key resolved
                # without this process paying a factorization
                self.metrics.inc("factor_cache.fleet_adopted")
                self.metrics.inc("factor_cache.store_hits")
                if self.breaker is not None:
                    self.breaker.record_success(key)
            return lu
        return self._factor_locally(a, options, key)

    def _verified_store_load(self, key):
        """The ONE verified-store-read policy (shared by the
        read-through and the fleet adopt probe, which must clear
        identical checks): a finite handle, or None.  The store
        itself verifies frame digest / checksum / layout and
        quarantines corrupt entries; the extra finite gate here
        covers pre-validation writers and pluggable store backends
        whose load path may not re-validate."""
        lu = self.store.load(key)
        if lu is None or factors_finite(lu):
            return lu
        self.store.quarantine(self.store.path_for(key),
                              reason="non-finite on load")
        return None

    def _factor_locally(self, a, options, key) -> LUFactorization:
        """The in-process factorization path (pattern-tier plan
        reuse, bounded retry, chaos sites, finite validation, store
        write-through) — the fleet leader's `work`, and the whole
        story when no coordinator is attached."""
        plan = None
        with self._lock:
            plan = self._plans.get(key.pattern_key)
            if plan is not None:
                self._plans.move_to_end(key.pattern_key)
        if plan is not None:
            self.metrics.inc("factor_cache.pattern_hits")
        delays = list(self.retry.delays()) if self.retry is not None \
            else []
        attempt = 0
        while True:
            try:
                chaos.maybe_raise("factor_raise",
                                  f"factorization killed (pattern "
                                  f"{key.pattern[:12]})")
                self.metrics.inc("factor_cache.factorizations")
                lu = self._factorize_fn(a, options, plan)
                chaos.maybe_poison_factors("factor_nan", lu)
                if self.validate_factors and not factors_finite(lu):
                    raise FactorPoisoned(
                        "factorization produced non-finite factors "
                        "(overflow/NaN at this dtype); not cached, "
                        "not served")
                break
            except DeadlineExceeded:
                raise                      # deadlines are not faults
            except Exception:
                if attempt >= len(delays):
                    # breaker counts REQUESTS that failed (retries
                    # exhausted), not every attempt — one request's
                    # own retry ladder must not open the circuit
                    if self.breaker is not None:
                        self.breaker.record_failure(key)
                    raise
                self.metrics.inc("factor_cache.factor_retries")
                time.sleep(delays[attempt])
                attempt += 1
        if self.breaker is not None:
            self.breaker.record_success(key)
        lu = self._condition_check(a, options, lu, plan)
        if self.store is not None:
            try:
                self.store.save(key, lu)
            except Exception:
                # persistence is an availability feature; its failure
                # (disk full, perms) must not fail the request that
                # just paid a real factorization
                self.metrics.inc("factor_store.save_errors")
        return lu

    def _condition_check(self, a, options, lu, plan):
        """Eager condition gate on the serve factorization path
        (SLU_COND_ESTIMATE=1, numerics/): estimate rcond off the
        fresh factors — a handful of refinement-free packed-trisolve
        dispatches, zero extra factorizations — refuse a numerically
        singular key typed (SingularMatrixError, never cached, never
        a garbage solve), and climb ONE precision rung before the
        first serve when the key classifies ill-conditioned.  Off (the
        default) this is one env read per factorization."""
        from ..numerics.gscon import ensure_rcond
        from ..numerics.policy import ConditionPolicy, \
            cond_estimate_enabled
        if not cond_estimate_enabled():
            return lu
        opts = options if options is not None else \
            lu.effective_options
        policy = ConditionPolicy.from_env()
        rcond = ensure_rcond(lu)
        cls = policy.classify(rcond, opts.refine_dtype)
        if cls == "ill" and getattr(opts, "escalate", False):
            from ..precision.policy import next_factor_dtype
            cur = lu.effective_options.factor_dtype
            nxt = next_factor_dtype(cur, ceiling=opts.refine_dtype)
            if nxt is not None:
                from .. import obs
                self.metrics.inc("factor_cache.cond_escalations")
                obs.HEALTH.record_escalation(
                    berr=0.0, factor_dtype=cur,
                    refine_dtype=opts.refine_dtype, to_dtype=nxt,
                    trigger="ill_conditioned")
                lu = self._factorize_fn(
                    a, opts.replace(factor_dtype=nxt), plan)
                ensure_rcond(lu)
        # floor refusal comes AFTER the rung climb: the higher-rung
        # estimate is the honest one
        policy.enforce(lu.rcond, opts.refine_dtype,
                       where=" (serve factor path)")
        return lu

    def resident_stale(self, key: CacheKey
                       ) -> Optional[tuple]:
        """Most-recently-used RESIDENT entry sharing `key`'s pattern
        key (same structure and factor options, different values) —
        the degraded-mode fallback when `key` itself cannot be
        factored: its factors are a stale-but-structurally-identical
        preconditioner the service refines against the fresh values
        (service.py).  Returns (stale key, handle) or None.  Does not
        touch LRU order or hit/miss counters — a degraded probe is a
        policy question, not a use."""
        with self._lock:
            for ek in reversed(self._entries):
                if ek != key and ek.pattern_key == key.pattern_key:
                    return ek, self._entries[ek].lu
        return None

    def _default_factorize(self, a, options, plan):
        if plan is None:
            plan = plan_factorization(a, options)
        if self.mesh is not None:
            return factorize(a, options, plan=plan, backend="dist",
                             grid=self.mesh)
        return factorize(a, options, plan=plan, backend=self.backend)

    def put(self, key: CacheKey, lu: LUFactorization) -> None:
        """Insert factors (and their plan into the pattern tier),
        evicting least-recently-used entries past the byte bound."""
        try:
            nbytes = int(query_space(lu)["held_bytes"])
        except Exception:
            nbytes = int(getattr(lu.stats, "lu_bytes", 0) or 0)
        evicted: list[tuple[CacheKey, _Entry]] = []
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self.bytes_resident -= old.nbytes
            self._entries[key] = _Entry(lu=lu, nbytes=nbytes)
            self.bytes_resident += nbytes
            self._plans[key.pattern_key] = lu.plan
            self._plans.move_to_end(key.pattern_key)
            while len(self._plans) > self.max_plans:
                self._plans.popitem(last=False)
            if self.capacity_bytes is not None:
                # never evict the entry just inserted: an oversized
                # single factorization stays resident (the service has
                # nothing cheaper to serve it from)
                while (self.bytes_resident > self.capacity_bytes
                       and len(self._entries) > 1):
                    ek, ee = self._entries.popitem(last=False)
                    self.bytes_resident -= ee.nbytes
                    self.metrics.inc("factor_cache.evictions")
                    evicted.append((ek, ee))
        if self.on_evict is not None:
            for ek, ee in evicted:
                self.on_evict(ek, ee.lu)
