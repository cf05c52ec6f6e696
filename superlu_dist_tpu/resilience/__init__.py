"""resilience/ — crash-safety, fault injection and failure containment.

The serve layer (serve/) made factorizations shared, cached state; this
package makes losing or corrupting that state an ENGINEERED-AROUND
event instead of an outage:

  * `store`   — durable factor store: atomic-rename persistence of
    LUFactorization + FactorPlan with an ABFT-lite checksum and a
    format version; corrupt entries are quarantined, never served.
    Wired into FactorCache as a write-through/read-through tier
    (`SLU_FT_STORE=dir`), so a kill -9'd replica boots warm.
  * `chaos`   — deterministic, seedable fault injection (`SLU_CHAOS`):
    factorization raises, NaN factors, persisted-entry bit flips,
    flusher-thread death, artificial latency.  Every site is a no-op
    pointer check when off.
  * `breaker` — per-key circuit breaker: a key whose factorization
    fails repeatedly costs one immediate error per request during the
    cooldown (open → half-open probe → closed), not a full
    factorization attempt each time.
  * `retry`   — bounded exponential backoff + deterministic jitter for
    transiently-failed factorizations.

Consumed by serve/factor_cache.py (store, breaker, retry, factor
validation), serve/batcher.py (flusher chaos + latency) and
serve/service.py (degraded-mode serving).  Driven end to end by
tests/test_resilience.py, which gates on zero hangs and zero
silent wrong answers.
"""

from .breaker import CircuitBreaker
from .chaos import (SITES, ChaosError, ChaosPolicy, active, install,
                    install_from_env, uninstall)
from .retry import RetryPolicy
from .store import (FORMAT_VERSION, FactorStore, StoreCorrupt,
                    checksum_arrays, entry_name, store_from_env)

__all__ = [
    "FORMAT_VERSION",
    "ChaosError",
    "ChaosPolicy",
    "CircuitBreaker",
    "FactorStore",
    "RetryPolicy",
    "SITES",
    "StoreCorrupt",
    "active",
    "checksum_arrays",
    "entry_name",
    "install",
    "install_from_env",
    "store_from_env",
    "uninstall",
]
