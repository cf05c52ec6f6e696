"""fleet/ — the coordination layer that turns N independent replica
processes into one resilient pool.

The single-replica pieces exist: a durable verified factor store
(resilience/store.py), per-key breaker + degraded mode + single-flight
(serve/), flight rids + SLOs (obs/).  At fleet scale they compose
badly by default: a cold pattern arriving at N replicas triggers N
factorizations (the 477 s × N stampede — the scaled-up version of the
bug in-process single-flight already kills), residency is accidental
(whichever replica happened to factor holds the bytes), and a dead
replica's traffic errors instead of riding the warm copies its
neighbours already hold.  This package closes those three gaps:

  * `lease.py` — CROSS-PROCESS single-flight over the shared store:
    a cold key elects one leader fleet-wide via an O_EXCL lease file
    (hard-linked into place with its full content, so a lease is
    never read torn), the leader heartbeats while it factors and
    publishes through the store's atomic rename, followers poll with
    backoff and ADOPT the verified published entry, and a dead
    leader's expired lease is STOLEN through an exclusive rename.
    Every wait/adopt/steal step
    lands on the request's flight record.
  * `router.py` — consistent-hash key routing: residency is
    deliberate, warm traffic lands where the factor lives, and the
    ring hands back an ordered failover list instead of one target.
  * `pool.py` — the replica pool: route → serve → typed failover.  A
    routed-to replica that is down or whose key is circuit-broken
    fails over along the ring (flight `route.failover`), and the last
    resort is the degraded stale-factor path (PR 5) — a
    DegradedResult beats an outage, and an untyped error is never the
    answer.

Held by tests/test_fleet.py: zero lost/hung requests, warm takeover
with zero survivor factorizations for published keys, and exactly one
fleet-wide factorization per cold key.

ISSUE 16 adds the ELASTIC layer on the same substrate:

  * `policy.py` — signals in, typed actions out: SLO-burn-driven
    autoscale with hysteresis + cooldown, popularity-driven
    prefactor of hot-but-cold keys at their ring homes, weighted
    multi-tenant shed (QosGate, refusing typed with TenantThrottled).
  * `scaler.py` — durable membership (`<name>.member` files beside
    the store), the arc-move receipt for every ring change, and the
    retire protocol: drain → demote → release-leases → stop.
  * `controller.py` — the gather → decide → actuate loop tying them
    together; any one actuation may fail, the loop never does.

Held by tests/test_fleet_controller.py: zero lost requests, every
shed typed, policy prefactor at exactly one factorization per cold
key, and zero takeover factorizations.
"""

from .controller import FleetController, signals_from
from .lease import FleetCoordinator, LeaseInfo
from .policy import (FleetPolicy, FleetSignals, PolicyConfig, QosGate,
                     weighted_shed)
from .pool import ReplicaPool
from .router import HashRing
from .scaler import MembershipDirectory, ReplicaScaler, arc_moves

__all__ = [
    "FleetController",
    "FleetCoordinator",
    "FleetPolicy",
    "FleetSignals",
    "HashRing",
    "LeaseInfo",
    "MembershipDirectory",
    "PolicyConfig",
    "QosGate",
    "ReplicaPool",
    "ReplicaScaler",
    "arc_moves",
    "signals_from",
    "weighted_shed",
]
