"""resilience/: durable factor store (roundtrip, corruption →
quarantine, crash-restart warm boot), chaos determinism, circuit
breaker cycle, retry bounds, flusher-death containment, and
degraded-mode serving with its berr guard — the failure-model pins
behind DESIGN.md §14."""

import dataclasses
import threading
import time

import numpy as np
import pytest

from superlu_dist_tpu import Options
from superlu_dist_tpu.models.gssvx import (factor_arrays, factorize,
                                           factors_finite, solve)
from superlu_dist_tpu.resilience import (ChaosError, CircuitBreaker,
                                         FactorStore, RetryPolicy,
                                         chaos)
from superlu_dist_tpu.serve import (DegradedResult, FactorCache,
                                    FactorPoisoned, FlusherDead,
                                    ServeConfig, SolveService,
                                    factor_cost_hint, matrix_key)
from superlu_dist_tpu.utils.testmat import laplacian_2d, laplacian_3d


@pytest.fixture(autouse=True)
def _no_leaked_chaos():
    """Chaos must never leak across tests (it is process-global)."""
    chaos.uninstall()
    yield
    chaos.uninstall()


def _drift(a, factor):
    return dataclasses.replace(a, data=a.data * factor)


# --------------------------------------------------------------------
# durable store
# --------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["host", "jax"])
def test_store_roundtrip_solves_identically(tmp_path, backend):
    a = laplacian_2d(6)
    key = matrix_key(a, Options())
    store = FactorStore(str(tmp_path))
    lu = factorize(a, Options(), backend=backend)
    assert store.save(key, lu) is not None
    lu2 = store.load(key)
    assert lu2 is not None and lu2.backend == lu.backend
    b = np.ones(a.n)
    np.testing.assert_allclose(solve(lu2, b), solve(lu, b), rtol=1e-12)
    # the persisted arrays are byte-identical to the live factors
    for x, y in zip(factor_arrays(lu), factor_arrays(lu2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_store_bit_flip_quarantines_never_serves(tmp_path):
    """A flipped bit ANYWHERE in a persisted entry (factor arrays,
    plan, matrix, framing) must quarantine it — sweep positions across
    the file."""
    import random
    a = laplacian_2d(5)
    key = matrix_key(a, Options())
    store = FactorStore(str(tmp_path))
    lu = factorize(a, Options(), backend="host")
    path = store.save(key, lu)
    pristine = open(path, "rb").read()
    rng = random.Random(0)
    for trial in range(8):
        open(path, "wb").write(pristine)
        data = bytearray(pristine)
        i = rng.randrange(len(data))
        data[i] ^= 1 << rng.randrange(8)
        open(path, "wb").write(bytes(data))
        assert store.load(key) is None, f"flip at byte {i} served"
        # quarantined, not deleted: evidence survives
        assert store.quarantined()
        # a re-save replaces the entry and serves again
        store.save(key, lu)
        assert store.load(key) is not None


def test_store_skips_unpicklable_plan_caches(tmp_path):
    """A plan that has been factorized on device carries jitted
    closures (_batched_schedules); persistence must still work —
    FactorPlan.__getstate__ strips them."""
    a = laplacian_2d(6)
    lu = factorize(a, Options(), backend="jax")   # attaches schedules
    assert getattr(lu.plan, "_batched_schedules", None)
    store = FactorStore(str(tmp_path))
    key = matrix_key(a, Options())
    store.save(key, lu)
    lu2 = store.load(key)
    assert lu2 is not None
    # the reloaded plan rebuilds its schedule lazily and solves
    np.testing.assert_allclose(solve(lu2, np.ones(a.n)),
                               solve(lu, np.ones(a.n)), rtol=1e-12)


def test_crash_restart_boots_warm(tmp_path):
    """The restart gate: factor → simulate crash (drop the cache,
    keep the store dir) → a NEW FactorCache serves the key warm with
    ZERO new factorizations off a checksum-verified load."""
    a = laplacian_3d(5)
    opts = Options()
    key = matrix_key(a, opts)
    cache1 = FactorCache(backend="host",
                         store=FactorStore(str(tmp_path)))
    lu1 = cache1.get_or_factorize(a, opts)
    assert cache1.stats()["factorizations"] == 1
    x1 = solve(lu1, np.ones(a.n))
    del cache1, lu1                                  # the crash

    cache2 = FactorCache(backend="host",
                         store=FactorStore(str(tmp_path)))
    lu2 = cache2.get_or_factorize(a, opts, key=key)
    st = cache2.stats()
    assert st["factorizations"] == 0, "restart paid a factorization"
    assert st["store_hits"] == 1
    assert st["store_quarantined"] == 0              # verified clean
    assert cache2.peek(key) is lu2                   # resident now
    np.testing.assert_allclose(solve(lu2, np.ones(a.n)), x1,
                               rtol=1e-12)


def test_warm_boot_preloads_store(tmp_path):
    a = laplacian_2d(5)
    a2 = _drift(a, 2.0)
    store = FactorStore(str(tmp_path))
    for m in (a, a2):
        store.save(matrix_key(m, Options()),
                   factorize(m, Options(), backend="host"))
    cache = FactorCache(backend="host", store=store)
    assert store.warm_boot(cache) == 2
    assert cache.peek(matrix_key(a, Options())) is not None
    assert cache.peek(matrix_key(a2, Options())) is not None


def test_store_write_through_on_cache_factorization(tmp_path):
    cache = FactorCache(backend="host",
                        store=FactorStore(str(tmp_path)))
    a = laplacian_2d(5)
    cache.get_or_factorize(a, Options())
    assert cache.store.contains(matrix_key(a, Options()))
    assert cache.stats()["store_saves"] == 1


# --------------------------------------------------------------------
# chaos layer
# --------------------------------------------------------------------

def test_chaos_spec_is_deterministic_and_validated():
    with pytest.raises(ValueError, match="unknown chaos site"):
        chaos.ChaosPolicy("definitely_not_a_site=1")
    p1 = chaos.ChaosPolicy("factor_raise=0.5,latency=0.3:0.01", seed=7)
    p2 = chaos.ChaosPolicy("factor_raise=0.5,latency=0.3:0.01", seed=7)
    seq1 = [p1.should("factor_raise") for _ in range(64)]
    seq2 = [p2.should("factor_raise") for _ in range(64)]
    assert seq1 == seq2 and any(seq1) and not all(seq1)
    assert p1.param("latency", 0) == pytest.approx(0.01)
    assert p1.fired()["factor_raise"] == sum(seq1)


def test_chaos_off_is_inert():
    assert chaos.active() is None
    assert not chaos.should("factor_raise")
    chaos.maybe_raise("factor_raise", "must not fire")
    data = b"payload"
    assert chaos.maybe_flip_bit("store_flip", data) == data


def test_chaos_store_flip_quarantines(tmp_path):
    a = laplacian_2d(5)
    key = matrix_key(a, Options())
    store = FactorStore(str(tmp_path))
    store.save(key, factorize(a, Options(), backend="host"))
    chaos.install("store_flip=1", seed=0)
    assert store.load(key) is None
    chaos.uninstall()
    assert store.quarantined()


def test_chaos_nan_factors_are_contained(tmp_path):
    """factor_nan poisoning must surface as FactorPoisoned — never a
    cached entry, never a persisted entry, never a served factor."""
    cache = FactorCache(backend="host",
                        store=FactorStore(str(tmp_path)))
    a = laplacian_2d(5)
    key = matrix_key(a, Options())
    chaos.install("factor_nan=1", seed=0)
    with pytest.raises(FactorPoisoned, match="non-finite"):
        cache.get_or_factorize(a, Options())
    chaos.uninstall()
    assert cache.peek(key, touch=False) is None
    assert not cache.store.contains(key)
    # clean retry heals
    lu = cache.get_or_factorize(a, Options())
    assert factors_finite(lu)


# --------------------------------------------------------------------
# circuit breaker / retry
# --------------------------------------------------------------------

def test_breaker_open_half_open_close_cycle():
    t = [0.0]
    br = CircuitBreaker(threshold=3, cooldown_s=5.0,
                        clock=lambda: t[0])
    k = "key"
    for _ in range(2):
        assert br.allow(k)
        br.record_failure(k)
    assert br.state(k) == "closed"          # below threshold
    br.record_failure(k)
    assert br.state(k) == "open"
    assert not br.allow(k)                  # cooldown running
    t[0] = 4.9
    assert not br.allow(k)
    t[0] = 5.1
    assert br.allow(k)                      # the half-open probe
    assert br.state(k) == "half_open"
    assert not br.allow(k)                  # only ONE probe
    br.record_failure(k)                    # probe failed: re-open
    assert br.state(k) == "open"
    assert not br.allow(k)
    t[0] = 10.3
    assert br.allow(k)
    br.record_success(k)                    # probe succeeded: closed
    assert br.state(k) == "closed"
    assert br.allow(k)


def test_retry_delays_bounded_and_deterministic():
    p = RetryPolicy(attempts=5, base_s=0.1, max_s=0.5, jitter=0.5,
                    seed=3)
    d1, d2 = list(p.delays()), list(p.delays())
    assert d1 == d2 and len(d1) == 4
    for i, d in enumerate(d1):
        base = min(0.5, 0.1 * 2 ** i)
        assert base <= d <= base * 1.5
    assert list(RetryPolicy(attempts=1).delays()) == []


def test_cache_retries_transient_failures():
    a = laplacian_2d(5)
    calls = [0]
    real = FactorCache(backend="host")._default_factorize

    def flaky(a_, o_, p_):
        calls[0] += 1
        if calls[0] == 1:
            raise RuntimeError("transient")
        return real(a_, o_, p_)

    cache = FactorCache(backend="host", factorize_fn=flaky,
                        retry=RetryPolicy(attempts=2, base_s=0.0,
                                          jitter=0.0))
    lu = cache.get_or_factorize(a, Options())
    assert calls[0] == 2 and lu is not None
    assert cache.stats()["factor_retries"] == 1


def test_breaker_quarantines_repeatedly_failing_key():
    """A poisoned key costs one immediate FactorPoisoned per request
    while open — not a factorization attempt each time — and the
    half-open probe re-admits one real attempt after the cooldown."""
    a = laplacian_2d(5)
    attempts = [0]

    def always_fails(a_, o_, p_):
        attempts[0] += 1
        raise RuntimeError("hard failure")

    t = [0.0]
    cache = FactorCache(
        backend="host", factorize_fn=always_fails,
        breaker=CircuitBreaker(threshold=2, cooldown_s=30.0,
                               clock=lambda: t[0]))
    for _ in range(2):
        with pytest.raises(RuntimeError, match="hard failure"):
            cache.get_or_factorize(a, Options())
    n_real = attempts[0]
    # circuit open: requests fail fast without touching factorize
    for _ in range(5):
        with pytest.raises(FactorPoisoned, match="circuit-broken"):
            cache.get_or_factorize(a, Options())
    assert attempts[0] == n_real
    assert cache.stats()["breaker_rejected"] == 5
    # cooldown over: exactly one half-open probe reaches factorize
    t[0] = 31.0
    with pytest.raises(RuntimeError, match="hard failure"):
        cache.get_or_factorize(a, Options())
    assert attempts[0] == n_real + 1


def test_breaker_leaked_probe_self_releases():
    """A half-open probe whose caller never reports back (died, took
    a path that neither succeeded nor failed) must not permanently
    circuit-break the key: after another cooldown a new probe is
    admitted."""
    t = [0.0]
    br = CircuitBreaker(threshold=1, cooldown_s=5.0,
                        clock=lambda: t[0])
    br.record_failure("k")
    t[0] = 6.0
    assert br.allow("k")            # probe admitted ... and leaked
    assert not br.allow("k")
    t[0] = 11.5                     # a full cooldown later
    assert br.allow("k"), "leaked probe permanently broke the key"


def test_breaker_defaults_route_through_flags(monkeypatch):
    """SLU_BREAKER_THRESHOLD / SLU_BREAKER_COOLDOWN_S set the fleet-
    wide constructor defaults; explicit arguments still win."""
    monkeypatch.setenv("SLU_BREAKER_THRESHOLD", "7")
    monkeypatch.setenv("SLU_BREAKER_COOLDOWN_S", "2.5")
    br = CircuitBreaker()
    assert br.threshold == 7
    assert br.cooldown_s == 2.5
    br = CircuitBreaker(threshold=1, cooldown_s=60.0)
    assert br.threshold == 1 and br.cooldown_s == 60.0
    monkeypatch.delenv("SLU_BREAKER_THRESHOLD")
    monkeypatch.delenv("SLU_BREAKER_COOLDOWN_S")
    br = CircuitBreaker()
    assert br.threshold == 3 and br.cooldown_s == 30.0


def test_breaker_half_open_admits_exactly_one_concurrent_probe():
    """N threads hammer allow() the instant the cooldown elapses: the
    half-open state must admit exactly ONE probe — a thundering herd
    on a just-cooled key is precisely what half-open exists to stop."""
    t = [0.0]
    br = CircuitBreaker(threshold=1, cooldown_s=5.0,
                        clock=lambda: t[0])
    br.record_failure("k")
    assert br.state("k") == "open"
    t[0] = 6.0
    admitted = []
    barrier = threading.Barrier(8)

    def race():
        barrier.wait()
        if br.allow("k"):
            admitted.append(1)

    ts = [threading.Thread(target=race) for _ in range(8)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert len(admitted) == 1
    assert br.state("k") == "half_open"
    # the probe reports success: the circuit closes for everyone
    br.record_success("k")
    assert all(br.allow("k") for _ in range(8))


def test_breaker_snapshot_counts_by_state():
    t = [0.0]
    br = CircuitBreaker(threshold=1, cooldown_s=5.0,
                        clock=lambda: t[0])
    assert br.snapshot() == {"tracked": 0, "by_state": {}}
    br.record_failure("a")                  # open
    br.record_failure("b")                  # open
    br.allow("c")                           # untracked: closed
    t[0] = 6.0
    assert br.allow("a")                    # half-open probe
    snap = br.snapshot()
    assert snap["tracked"] == 2
    assert snap["by_state"] == {"open": 1, "half_open": 1}


def test_store_hit_closes_open_circuit(tmp_path):
    """The half-open probe resolving via the store read-through is a
    SUCCESS: the circuit closes instead of leaking the probe."""
    a = laplacian_2d(5)
    key = matrix_key(a, Options())
    store = FactorStore(str(tmp_path))
    store.save(key, factorize(a, Options(), backend="host"))
    t = [0.0]
    br = CircuitBreaker(threshold=1, cooldown_s=5.0,
                        clock=lambda: t[0])
    cache = FactorCache(backend="host", store=store, breaker=br,
                        factorize_fn=lambda *_: (_ for _ in ()).throw(
                            RuntimeError("never reached")))
    br.record_failure(key)
    assert br.state(key) == "open"
    t[0] = 6.0
    lu = cache.get_or_factorize(a, Options())   # probe → store hit
    assert lu is not None
    assert br.state(key) == "closed"
    assert cache.stats()["store_hits"] == 1


# --------------------------------------------------------------------
# single-flight failure audit (satellite 1)
# --------------------------------------------------------------------

def test_lead_failure_wakes_all_followers_then_next_retry_succeeds():
    """N followers behind a failing lead ALL get the lead's exception;
    the in-flight entry is cleared, so the N+1-th request elects a
    fresh leader and succeeds."""
    a = laplacian_3d(5)
    calls = [0]
    gate = threading.Event()
    real = FactorCache(backend="host")._default_factorize

    def fails_first(a_, o_, p_):
        calls[0] += 1
        if calls[0] == 1:
            gate.wait(5)            # hold the flight so followers pile up
            raise ChaosError("injected lead failure")
        return real(a_, o_, p_)

    cache = FactorCache(backend="host", factorize_fn=fails_first)
    n = 6
    outcomes = [None] * n
    started = threading.Barrier(n + 1)

    def hit(i):
        started.wait()
        try:
            cache.get_or_factorize(a, Options())
            outcomes[i] = "ok"
        except ChaosError:
            outcomes[i] = "error"

    threads = [threading.Thread(target=hit, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    started.wait()                 # all workers racing on the key
    time.sleep(0.2)                # followers parked on the flight
    gate.set()
    for t in threads:
        t.join(10)
    assert outcomes == ["error"] * n, outcomes
    assert calls[0] == 1, "followers must share the lead's failure"
    # the key slot is clean: the next request re-attempts and succeeds
    lu = cache.get_or_factorize(a, Options())
    assert lu is not None and calls[0] == 2


# --------------------------------------------------------------------
# flusher death containment (satellite 2)
# --------------------------------------------------------------------

def test_flusher_death_fails_futures_never_hangs():
    """A flusher killed holding a claimed batch fails every queued
    and claimed future with FlusherDead — bounded wait, no hang."""
    from superlu_dist_tpu.serve import MicroBatcher
    a = laplacian_2d(6)
    lu = factorize(a, Options(), backend="host")
    chaos.install("flusher_raise=1", seed=0)
    mb = MicroBatcher(lu, max_linger_s=0.01)
    futs = []
    for _ in range(3):
        try:
            futs.append(mb.submit(np.ones(a.n)))
        except FlusherDead:
            break                   # already-dead watchdog: also fine
    assert futs, "first submit must be accepted"
    for f in futs:
        with pytest.raises(FlusherDead):
            f.result(timeout=10)    # resolves, never hangs
    chaos.uninstall()
    # dead batcher fails fast on subsequent submits
    with pytest.raises(FlusherDead):
        mb.submit(np.ones(a.n))
    assert mb.dead is not None
    mb.close()


def test_service_replaces_dead_batcher_and_resubmits():
    """ONE flusher death under load is invisible to callers: the
    queued request fails with FlusherDead internally, the relay
    resubmits it against a replacement batcher, and the caller gets
    the solution.  (Under sustained chaos — every replacement dying
    too — the second death surfaces as an explicit FlusherDead, which
    the chaos gate counts as a typed outcome.)"""
    a = laplacian_2d(6)
    # long linger: the request stays QUEUED while we kill the flusher
    svc = SolveService(ServeConfig(backend="host", max_linger_s=0.5))
    key = svc.prefactor(a, Options())
    x0 = np.asarray(svc.solve(key, np.ones(a.n)))
    mb = next(iter(svc._batchers.values()))
    fut = svc.submit(key, np.ones(a.n))
    # deterministic single death: drive the containment handler the
    # way a crashed _run_loop would
    mb._flusher_died(RuntimeError("injected flusher crash"))
    x = fut.result(timeout=30)
    np.testing.assert_allclose(x, x0, rtol=1e-12)
    assert svc.metrics.counter("batcher.flusher_died") >= 1
    assert svc.metrics.counter("serve.flusher_resubmits") == 1
    assert svc.metrics.counter("serve.batcher_replaced") == 1
    svc.close()


# --------------------------------------------------------------------
# degraded-mode serving (pillar 4)
# --------------------------------------------------------------------

def test_degraded_serves_stale_factors_with_refinement():
    a = laplacian_2d(6)
    a2 = _drift(a, 1.0 + 1e-8)
    svc = SolveService(ServeConfig(backend="host"))
    svc.prefactor(a, Options())
    chaos.install("factor_raise=1", seed=0)
    x = svc.solve(a2, np.ones(a.n))
    chaos.uninstall()
    assert isinstance(x, DegradedResult)
    assert svc.metrics.counter("serve.degraded_served") == 1
    # refined against the FRESH matrix: full-accuracy answer
    xd = np.linalg.solve(a2.to_scipy().toarray(), np.ones(a.n))
    np.testing.assert_allclose(np.asarray(x), xd, rtol=1e-9)
    # healthy traffic is never stamped
    assert not isinstance(svc.solve(a, np.ones(a.n)), DegradedResult)
    svc.close()


def test_degraded_berr_guard_blocks_bad_cover():
    """The berr guard: a degraded serve whose refinement cannot reach
    the sold accuracy class blocks the key — subsequent failures
    surface as errors, never as berr-failing 'answers'."""
    a = laplacian_2d(6)
    # values FAR from the stale factors: refinement on the stale
    # preconditioner cannot contract to eps-class in 8 steps
    a2 = _drift(a, 50.0)
    key2 = matrix_key(a2, Options())
    svc = SolveService(ServeConfig(backend="host"))
    svc.prefactor(a, Options())
    guard = svc._degraded_guard(key2, Options())
    guard(1e-3)                     # a berr far above 64·eps(f64)
    assert key2 in svc._degraded_blocked
    assert svc.metrics.counter("serve.degraded_escalations") == 1
    # blocked: the degraded path refuses, the original failure
    # propagates as an explicit error
    chaos.install("factor_raise=1", seed=0)
    with pytest.raises(ChaosError):
        svc.solve(a2, np.ones(a.n))
    chaos.uninstall()
    assert svc.metrics.counter("serve.degraded_served") == 0
    svc.close()


def test_degraded_end_to_end_guard_fires_on_genuinely_bad_cover():
    """End-to-end version: serve a WILDLY drifted matrix degraded
    once; the dispatch-level berr guard must fire and block the key
    (the result of that first serve is stamped degraded — the caller
    was told — and the block prevents a second one)."""
    a = laplacian_2d(6)
    a2 = _drift(a, 50.0)
    svc = SolveService(ServeConfig(backend="host"))
    svc.prefactor(a, Options())
    chaos.install("factor_raise=1", seed=0)
    x = svc.solve(a2, np.ones(a.n))
    chaos.uninstall()
    assert isinstance(x, DegradedResult)
    assert matrix_key(a2, Options()) in svc._degraded_blocked
    assert svc.metrics.counter("serve.degraded_escalations") == 1
    svc.close()


def test_degraded_disabled_propagates_failure():
    a = laplacian_2d(6)
    a2 = _drift(a, 1.0 + 1e-8)
    svc = SolveService(ServeConfig(backend="host", degraded=False))
    svc.prefactor(a, Options())
    chaos.install("factor_raise=1", seed=0)
    with pytest.raises(ChaosError):
        svc.solve(a2, np.ones(a.n))
    chaos.uninstall()
    svc.close()


# --------------------------------------------------------------------
# satellites: docs figure centralization
# --------------------------------------------------------------------

def test_factor_cost_hint_reads_measured_trajectory():
    """The refusal text quotes no figure: the program reads no bench
    record (it once quoted the last line of a latency file)."""
    assert factor_cost_hint() == "minutes at production scale"
