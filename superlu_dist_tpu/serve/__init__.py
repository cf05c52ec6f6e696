"""serve/ — the solve-service layer.

Turns the batch-shaped solver (factor once, solve once) into a
multi-tenant service: an LRU factor cache with single-flight
factorization (factor_cache.py), RHS micro-batching over a fixed
nrhs bucket ladder so the jitted solver never recompiles after warmup
(batcher.py), a front door with admission control and per-request
deadlines (service.py), structured metrics (metrics.py), and a
seeded closed-loop load generator (loadgen.py).

Failure containment rides the sibling resilience/ package: the
durable factor store (ServeConfig.store_dir / SLU_FT_STORE), per-key
circuit breaker + bounded retry around cold factorizations, explicit
FlusherDead futures when a batcher thread dies, and degraded-mode
serving off stale factors (DegradedResult) — exercised by
tests/test_resilience.py.

Quickstart:

    from superlu_dist_tpu.serve import ServeConfig, SolveService
    svc = SolveService(ServeConfig(max_queue_depth=64))
    key = svc.prefactor(a, Options(factor_dtype="float32"))
    x = svc.solve(key, b, deadline_s=0.5)       # batched under load
"""

from .batcher import BUCKET_LADDER, MicroBatcher, bucket_for
from .coalescer import FactorCoalescer, coalesce_enabled
from .errors import (DeadlineExceeded, DegradedResult, FactorMissError,
                     FactorPoisoned, FlusherDead, ServeError,
                     ServeRejected, StaleFactorError, factor_cost_hint)
from .factor_cache import (CacheKey, FactorCache, matrix_key,
                           pattern_fingerprint, values_fingerprint)
from .loadgen import run_load, run_stream_load
from .metrics import Counter, Histogram, Metrics
from .service import ServeConfig, SolveService, solve_jit_cache_size

__all__ = [
    "BUCKET_LADDER",
    "CacheKey",
    "Counter",
    "DeadlineExceeded",
    "DegradedResult",
    "FactorCache",
    "FactorCoalescer",
    "FactorMissError",
    "FactorPoisoned",
    "FlusherDead",
    "Histogram",
    "Metrics",
    "MicroBatcher",
    "ServeConfig",
    "ServeError",
    "ServeRejected",
    "SolveService",
    "StaleFactorError",
    "bucket_for",
    "coalesce_enabled",
    "factor_cost_hint",
    "matrix_key",
    "pattern_fingerprint",
    "run_load",
    "run_stream_load",
    "solve_jit_cache_size",
    "values_fingerprint",
]
