"""serve/service.py: admission control, deadline semantics, miss
policies, and the small deterministic tier-1 load test (concurrency
8, tiny matrix) with the zero-recompile pin."""

import threading
import time

import numpy as np
import pytest

from superlu_dist_tpu import Options
from superlu_dist_tpu.serve import (DeadlineExceeded, FactorMissError,
                                    Metrics, ServeConfig, ServeRejected,
                                    SolveService, run_load,
                                    solve_jit_cache_size)
from superlu_dist_tpu.serve.factor_cache import FactorCache
from superlu_dist_tpu.utils.testmat import laplacian_2d, laplacian_3d


def _service(**kw):
    kw.setdefault("backend", "host")
    cfg = ServeConfig(**kw)
    m = Metrics()
    return SolveService(cfg, metrics=m)


def test_basic_solve_through_service():
    svc = _service()
    a = laplacian_2d(6)
    b = np.ones(a.n)
    x = svc.solve(a, b)
    np.testing.assert_allclose(
        x, np.linalg.solve(a.to_scipy().toarray(), b), rtol=1e-10)
    # second call is a cache hit
    svc.solve(a, 2 * b)
    assert svc.cache.stats()["hits"] >= 1
    svc.close()


def test_prefactor_and_keyed_submit():
    svc = _service()
    a = laplacian_2d(6)
    key = svc.prefactor(a, Options())
    # warmup's five zero solves must NOT pollute the berr histogram
    # operators alert on
    assert svc.metrics.histogram("serve.berr")["count"] == 0
    x = svc.solve(key, np.ones(a.n))
    assert np.all(np.isfinite(x))
    assert svc.metrics.histogram("serve.berr")["count"] == 1
    svc.close()


def test_admission_control_rejects_over_capacity_burst():
    """An over-capacity burst yields EXPLICIT rejections (no silent
    queueing, no hang) and in-flight never exceeds the cap."""
    svc = _service(max_queue_depth=4, max_linger_s=0.05)
    a = laplacian_2d(6)
    svc.prefactor(a, Options())
    release = threading.Event()
    orig = svc._batchers[next(iter(svc._batchers))]._solve_fn

    def gated_solve(lu, B):
        release.wait(5)
        return orig(lu, B)

    for mb in svc._batchers.values():
        mb._solve_fn = gated_solve

    futures, rejected = [], 0
    for i in range(12):
        try:
            futures.append(svc.submit(a, np.ones(a.n)))
        except ServeRejected:
            rejected += 1
    assert rejected == 12 - 4
    assert svc.metrics.counter("serve.rejected") == rejected
    release.set()
    for f in futures:
        assert np.all(np.isfinite(f.result(timeout=30)))
    # slots drain: new traffic is admitted again
    assert np.all(np.isfinite(svc.solve(a, np.ones(a.n))))
    svc.close()


def test_deadline_missed_never_succeeds():
    svc = _service(max_linger_s=0.0)
    a = laplacian_2d(6)
    svc.prefactor(a, Options())

    def slow_solve(lu, B):
        time.sleep(0.2)
        from superlu_dist_tpu import solve
        return solve(lu, B)

    for mb in svc._batchers.values():
        mb._solve_fn = slow_solve
    with pytest.raises(DeadlineExceeded):
        svc.solve(a, np.ones(a.n), deadline_s=0.05)
    assert (svc.metrics.counter("serve.deadline_missed")
            + svc.metrics.counter("batcher.deadline_missed")) >= 1
    svc.close()


def test_failfast_policy_on_cold_key():
    svc = _service(miss_policy="failfast")
    a = laplacian_2d(6)
    with pytest.raises(FactorMissError):
        svc.solve(a, np.ones(a.n))
    assert svc.metrics.counter("serve.miss_failfast") == 1
    # prefactor() is the sanctioned warm path; then it serves
    svc.prefactor(a, Options())
    assert np.all(np.isfinite(svc.solve(a, np.ones(a.n))))
    svc.close()


def test_factor_policy_pays_once_under_concurrency():
    a = laplacian_2d(7)
    n_factor = [0]
    real = FactorCache(backend="host")._default_factorize

    def counting(a_, o_, p_):
        n_factor[0] += 1
        time.sleep(0.05)
        return real(a_, o_, p_)

    m = Metrics()
    cache = FactorCache(backend="host", metrics=m,
                        factorize_fn=counting)
    svc = SolveService(ServeConfig(backend="host"), metrics=m,
                       cache=cache)
    barrier = threading.Barrier(6)
    errs = []

    def hit():
        barrier.wait()
        try:
            svc.solve(a, np.ones(a.n))
        except Exception as e:          # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=hit) for _ in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert n_factor[0] == 1
    svc.close()


def test_per_request_solve_options_honored():
    """trans/refinement are PER-REQUEST: callers sharing one cached
    factorization must each get solves under their own solve-time
    knobs (the factor-cache key deliberately ignores them)."""
    import scipy.sparse as sp
    from superlu_dist_tpu import Trans
    from superlu_dist_tpu.sparse import csr_from_scipy
    rng = np.random.default_rng(0)
    n = 30
    dense = np.eye(n) * 4 + sp.random(n, n, 0.2, random_state=3).toarray()
    a = csr_from_scipy(sp.csr_matrix(dense))
    svc = _service()
    b = rng.standard_normal(n)
    x_plain = svc.solve(a, b)
    x_trans = svc.solve(a, b, options=Options(trans=Trans.TRANS))
    np.testing.assert_allclose(x_plain, np.linalg.solve(dense, b),
                               rtol=1e-9)
    np.testing.assert_allclose(x_trans, np.linalg.solve(dense.T, b),
                               rtol=1e-9)
    # one factorization served both variants, via two batchers
    assert svc.cache.stats()["factorizations"] == 1
    assert len(svc._batchers) == 2
    svc.close()


def test_eviction_retires_batchers():
    """LRU eviction must drop the evicted key's batchers too —
    otherwise their flusher threads pin the factors the byte bound
    claims to have released."""
    mats = [laplacian_2d(5), laplacian_2d(6), laplacian_2d(7)]
    probe = SolveService(ServeConfig(backend="host"))
    for m in mats:
        probe.solve(m, np.ones(m.n))
    full = probe.cache.stats()["bytes_resident"]
    probe.close()

    svc = _service(capacity_bytes=int(full * 0.8))
    for m in mats:
        svc.solve(m, np.ones(m.n))
    assert svc.cache.stats()["evictions"] >= 1
    live_keys = {bk[0] for bk in svc._batchers}
    resident = {k for k in live_keys if svc.cache.peek(k, touch=False)}
    assert live_keys == resident, "batcher survives its evicted key"
    # evicted key still serves (re-factors through the normal path)
    assert np.all(np.isfinite(svc.solve(mats[0], np.ones(mats[0].n))))
    svc.close()


def test_rhs_dtype_past_batch_dtype_rejected():
    svc = _service()
    a = laplacian_2d(6)
    svc.prefactor(a, Options())
    with pytest.raises(ValueError, match="promote the batch"):
        svc.solve(a, np.ones(a.n, dtype=np.complex128))
    svc.close()


def test_invalid_miss_policy_rejected():
    with pytest.raises(ValueError, match="miss_policy"):
        SolveService(ServeConfig(miss_policy="drop"))


def test_closed_service_refuses():
    svc = _service()
    svc.close()
    from superlu_dist_tpu.serve import ServeError
    with pytest.raises(ServeError):
        svc.submit(laplacian_2d(5), np.ones(25))


def test_tier1_load_batched_and_recompile_free():
    """The deterministic tier-1 serve test: concurrency 8 on a tiny
    3D Laplacian through the REAL jax backend.  Pins (a) micro-batches
    actually form, (b) every request succeeds, (c) zero jit recompiles
    after ladder warmup, (d) the metrics surface is populated."""
    a = laplacian_3d(5)           # n=125, compiles in seconds on CPU
    svc = SolveService(ServeConfig(backend="jax", max_linger_s=0.01,
                                   max_queue_depth=512))
    key = svc.prefactor(a, Options())
    lu = svc.cache.peek(key)
    jit_before = solve_jit_cache_size(lu)
    report = run_load(svc, [key], requests=64, concurrency=8, seed=7)
    jit_after = solve_jit_cache_size(lu)
    m = svc.metrics
    occ = m.histogram("serve.batch_occupancy")
    svc.close()

    assert report["by_status"] == {"ok": 64}
    # 8 closed-loop workers against one key must coalesce: fewer
    # dispatches than requests (i.e. mean occupancy of the 1-bucket
    # alone can't explain the count)
    assert occ["count"] < 64
    assert report["solves_per_s"] > 0
    assert report["p95_ms"] >= report["p50_ms"]
    if jit_before >= 0:
        assert jit_after == jit_before, "jit recompiled under load"
    # per-stage surface
    snap = m.snapshot()
    for h in ("serve.queue_wait_s", "serve.device_solve_s",
              "serve.batch_occupancy"):
        assert snap["histograms"][h]["count"] > 0
    # keyed submits count as cache hits (they ARE the hot path): one
    # prefactor miss vs 64 keyed hits
    assert svc.cache.stats()["hit_rate"] > 0.9
