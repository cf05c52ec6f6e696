"""Failure vocabulary of the solve service.

Every way a request can fail without being a solver bug is an explicit
exception type, so callers (and the load generator's status taxonomy)
can tell capacity pushback from deadline economics from cold-cache
policy from contained faults.  All derive from ServeError for blanket
handling.  The one NON-error in this module is `DegradedResult`: the
marker type stamped on solutions served through degraded mode
(service.py) — still a correct answer behind the berr guard, but one
the caller deserves to know came off stale factors.
"""

from __future__ import annotations

import numpy as np

# the numerical-trust taxonomy (numerics/errors.py) re-exported here
# so service callers import ONE failure vocabulary; numerics/ sits
# below serve/ and imports nothing back, so this is cycle-free
from ..numerics.errors import (  # noqa: F401 — re-exports
    InvalidInputError,
    NumericalError,
    SingularMatrixError,
    StructurallySingularError,
)
from ..numerics.ledger import PerturbedResult  # noqa: F401 — re-export


class ServeError(RuntimeError):
    """Base class for service-level request failures."""


class ServeRejected(ServeError):
    """Admission control refused the request: the queue-depth cap was
    reached.  Explicit pushback beats unbounded queueing — the caller
    should shed or retry with backoff."""


class TenantThrottled(ServeRejected):
    """Multi-tenant QoS shed (fleet/policy.py QosGate): the tenant's
    admission tokens ran dry, or the fleet controller ordered a
    weighted shed for this tenant under SLO burn.  A subclass of
    ServeRejected on purpose — the same deadline-economics taxonomy
    applies (never rerouted along the ring, the caller backs off) —
    but its own type so a shed is distinguishable from a full queue
    in every status ledger."""


class DeadlineExceeded(ServeError):
    """The request's deadline passed before a result was delivered.
    A solve that COMPLETED after its deadline also raises this: a
    deadline-missed request must never return a result marked
    successful."""


class FactorMissError(ServeError):
    """Factor-cache miss under the fail-fast policy: this service is
    configured not to pay a factorization inline (they cost minutes at
    production scale, `factor_cost_hint()`); prefactor() the key or
    use miss_policy='factor'."""


class FactorPoisoned(ServeError):
    """The key's factorization cannot be served: it produced
    non-finite (NaN/Inf) factors — which GESP would otherwise turn
    into silently-wrong solves, there being no runtime pivoting to
    trip on them — or it failed repeatedly and the per-key circuit
    breaker is open (resilience/breaker.py).  Costs the caller one
    immediate error, never a factorization-length retry."""


class FlusherDead(ServeError):
    """A micro-batcher's flusher thread died (crashed mid-flight or
    was chaos-killed); its queued futures were failed with this
    instead of hanging forever, and the service replaces the batcher
    on the next request for the key."""


class StaleFactorError(ServeError):
    """A STREAMING solve's stale-factor refinement could not reach
    the sold accuracy class: the live values have drifted past what
    the resident generation's factors can cover, the berr guard
    refused the result (never served past the guard), and an urgent
    background refactorization was requested (stream/pipeline.py).
    The caller should resubmit — the next generation covers the
    drift — or treat it as the bounded-staleness contract firing."""


class DegradedResult(np.ndarray):
    """Marker subclass stamped on solutions served in DEGRADED mode:
    a refactorization failed (or the key is circuit-broken) and the
    service solved through resident stale/pattern-tier factors with
    refinement against the fresh matrix, behind the standard berr
    guard.  Numerically a normal ndarray (`isinstance(x,
    DegradedResult)` is the stamp; `np.asarray(x)` strips it) — the
    honest alternative to an outage, never a silent substitute for a
    healthy solve."""


def factor_cost_hint() -> str:
    """Human-readable cold-factorization cost for error messages,
    kept in one place so the refusals that quote it agree."""
    return "minutes at production scale"
