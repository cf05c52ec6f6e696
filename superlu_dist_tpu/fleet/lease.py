"""Cross-process single-flight: lease files + heartbeat + steal.

The in-process factor cache already guarantees one factorization per
key per PROCESS (serve/factor_cache.py's `_Flight`).  A fleet of N
replicas sharing one warm store still stampedes: N concurrent misses
on one cold pattern are N *processes*, and a threading.Event cannot
reach across them.  The measured economics make that the single worst
failure of scale the serve layer has — 477 s of factorization per
replica (pre-round chip record, not re-measured) for work one replica could have done
for everyone.

This module is the cross-process analog of `_Flight`, built on the
only coordination substrate the shared store already requires — its
filesystem — with three primitives, each atomic on POSIX:

  acquire   the leader creates `<key>.lease` by HARD-LINKING a fully
            written temp file into place (link(2) fails with EEXIST
            if the lease exists).  Unlike O_CREAT|O_EXCL + write, the
            lease appears with its complete JSON body — no reader
            ever sees a torn lease.
  heartbeat the leader rewrites the lease (atomic replace) every
            ttl/4 while it factors, after re-reading it to confirm
            it still owns it; ownership lost (a steal it raced)
            stops the beat — the old leader finishes its work and
            publishes harmlessly (same verified bytes, atomic
            replace), but never knowingly re-asserts the lease.
            The read-then-replace pair is NOT atomic (a filesystem
            has no compare-and-swap): a beat that passed its
            ownership read, stalled across a steal, and then wrote,
            wins the lease back from the stealer — the stealer's own
            next beat sees the foreign owner and demotes.  The cost
            is bounded, not hidden: at most one duplicate
            factorization, and at most one extra TTL of delay if the
            re-asserted leader then dies (its fresh-stamped lease
            ages out and is stolen again).  That is the split-brain
            discipline this module actually provides: two processes
            may briefly both FACTOR (wasted work, bounded by one TTL
            misjudgment), but publication is idempotent and a key is
            never blocked longer than one TTL past its last
            heartbeat.
  steal     a follower that finds the lease older than its TTL
            renames it to a unique `.stale-<nonce>` name.  rename(2)
            on a named source succeeds for exactly ONE caller — the
            winner acquires fresh, every loser re-enters the wait
            loop.  No unlink race, no double-leader.

Followers poll the published entry with exponential backoff (cheap
`contains` probe first; the verified `load` only on presence) and
ADOPT it — `factorizations == 0` on the adopting replica is the
fleet drill's warm-takeover gate.  Acquisition is double-checked: a
fresh leader re-probes UNDER the lease before factoring, because its
own missed probe may be stale by the time the acquire lands (the
previous leader published and released in the gap — stalling there
must cost an adopt, never a duplicate factorization; caught by the
contended three-way race in tests/test_fleet.py).

TTL sizing: a lease must outlive the factorization it guards, or
healthy leaders get robbed mid-factor.  Default is 120 s;
`SLU_FLEET_TTL_S` overrides outright (tests shrink it to seconds).
The heartbeat
refreshes the lease's OWN recorded ttl window, so a steal judgment
never depends on the judging replica's configuration matching the
leader's.

Every step lands on the requesting thread's flight record
(obs/flight.py): `fleet.lead`, `fleet.wait`, `fleet.adopt`,
`fleet.steal` — a follower's 60 s wall is one rid lookup from the
leader it waited on.
"""

from __future__ import annotations

import binascii
import dataclasses
import json
import os
import threading
import time

from .. import flags
from ..obs import flight
from ..resilience import chaos
from ..utils.io import atomic_write_bytes

LEASE_SUFFIX = ".lease"

_TTL_DEFAULT_S = 120.0


@dataclasses.dataclass(frozen=True)
class LeaseInfo:
    """One parsed lease file."""

    replica: str
    pid: int
    ts: float          # epoch seconds of the last heartbeat
    ttl_s: float
    key: str

    def age_s(self, now: float | None = None) -> float:
        return (time.time() if now is None else now) - self.ts

    def expired(self, now: float | None = None) -> bool:
        return self.age_s(now) > self.ttl_s


def default_ttl_s() -> float:
    """`SLU_FLEET_TTL_S` override, else the default (see module
    docstring)."""
    override = flags.env_float("SLU_FLEET_TTL_S", 0.0)
    return override if override > 0 else _TTL_DEFAULT_S


class FleetCoordinator:
    """Fleet-wide single-flight over a shared directory.

    `factor_once(name, probe, work)` is the whole API surface the
    factor cache needs: `probe()` returns the published value or
    None (a verified store load), `work()` computes AND publishes it
    (the cache's local factorization + write-through).  Exactly one
    replica runs `work` per key per publication; everyone else
    adopts `probe`'s result.

    Thread-safe: concurrent keys coordinate independently (the lease
    path is per-key); concurrent callers on ONE key inside one
    process should already be collapsed by the in-process
    single-flight above this layer, but nothing here breaks if they
    are not — the lease simply treats them as extra followers.
    """

    def __init__(self, root: str, ttl_s: float | None = None,
                 poll_s: float | None = None, metrics=None,
                 replica: str | None = None) -> None:
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self.ttl_s = float(ttl_s) if ttl_s else default_ttl_s()
        self.poll_s = (float(poll_s) if poll_s
                       else flags.env_float("SLU_FLEET_POLL_S", 0.05))
        # ownership identity: the process's replica id PLUS a
        # per-coordinator nonce — two coordinators in one process
        # (tests, embedded multi-tenant setups) must not alias each
        # other's lease ownership through the shared process id
        self.replica = replica or (
            flight.replica_id() + "-"
            + binascii.hexlify(os.urandom(2)).decode())
        self._metrics = metrics
        # heartbeat registry: key name -> (stop event, thread); the
        # leader of each in-flight key owns one beat thread
        self._hb_lock = threading.Lock()
        self._beats: dict[str, tuple] = {}

    def _inc(self, name: str) -> None:
        if self._metrics is not None:
            self._metrics.inc(name)

    # -- lease file primitives ----------------------------------------

    def lease_path(self, name: str) -> str:
        return os.path.join(self.root, name + LEASE_SUFFIX)

    def _lease_body(self, name: str) -> bytes:
        return json.dumps({
            "replica": self.replica, "pid": os.getpid(),
            "ts": time.time(), "ttl_s": self.ttl_s,
            "key": name}).encode()

    def try_acquire(self, name: str) -> bool:
        """Create the lease iff absent — atomically WITH its content
        (hard-link of a fully written temp file; see module
        docstring).  True = this process is now the leader."""
        path = self.lease_path(name)
        tmp = (path + f".claim-{os.getpid():x}-"
               + binascii.hexlify(os.urandom(3)).decode())
        with open(tmp, "wb") as f:
            f.write(self._lease_body(name))
            f.flush()
            os.fsync(f.fileno())
        try:
            os.link(tmp, path)
            return True
        except FileExistsError:
            return False
        finally:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def read_lease(self, name: str) -> LeaseInfo | None:
        """The current lease, or None (absent / vanished
        concurrently).  A lease whose JSON cannot be read falls back
        to the file's mtime with the coordinator's TTL — it can still
        be judged expired and stolen."""
        path = self.lease_path(name)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return None
        try:
            d = json.loads(raw)
            return LeaseInfo(replica=str(d["replica"]),
                             pid=int(d.get("pid", 0)),
                             ts=float(d["ts"]),
                             ttl_s=float(d.get("ttl_s", self.ttl_s)),
                             key=str(d.get("key", name)))
        except (ValueError, KeyError, TypeError):
            try:
                ts = os.stat(path).st_mtime
            except OSError:
                return None
            return LeaseInfo(replica="?", pid=0, ts=ts,
                             ttl_s=self.ttl_s, key=name)

    def try_steal(self, name: str) -> bool:
        """Bury an expired lease: rename it to a unique stale name.
        rename(2) succeeds for exactly one of N racing stealers —
        the winner may then acquire; every loser re-enters the wait
        loop (and typically finds the winner's fresh lease)."""
        path = self.lease_path(name)
        stale = (path + ".stale-"
                 + binascii.hexlify(os.urandom(4)).decode())
        try:
            os.rename(path, stale)
        except OSError:
            return False               # someone else got there first
        try:
            os.unlink(stale)
        except OSError:
            pass
        self._inc("fleet.steals")
        return True

    def release(self, name: str) -> None:
        """Drop the lease IF still ours (a steal may have replaced it
        with another leader's — never unlink that one)."""
        self._stop_heartbeat(name)
        cur = self.read_lease(name)
        if cur is not None and cur.replica == self.replica:
            try:
                os.unlink(self.lease_path(name))
            except OSError:
                pass

    def release_all(self) -> None:
        """Release every lease this coordinator still owns — the
        retire protocol's last step (fleet/scaler.py: drain → demote
        → release-leases).  A retiring replica that exits holding
        leases forces its successors through the TTL-expiry + steal
        path; releasing hands the keys over immediately.  Scans the
        lease DIRECTORY, not just the heartbeat registry — a lease
        acquired but not yet (or no longer) heartbeating is still
        ours to hand back."""
        with self._hb_lock:
            held = set(self._beats)
        try:
            for fn in os.listdir(self.root):
                if fn.endswith(LEASE_SUFFIX):
                    held.add(fn[:-len(LEASE_SUFFIX)])
        except OSError:
            pass
        for name in held:
            self.release(name)      # no-op unless the lease is OURS

    # -- heartbeat -----------------------------------------------------

    def _start_heartbeat(self, name: str,
                         rec=None) -> None:
        """`rec` is the LEADING request's flight record: the beat
        runs on its own thread, where the thread-local current record
        is unbound, so lease-loss must be stamped through the handle
        captured at lead time or it would vanish from every trace."""
        interval = min(5.0, max(0.05, self.ttl_s / 4.0))
        stop = threading.Event()

        def beat() -> None:
            while not stop.wait(interval):
                cur = self.read_lease(name)
                if cur is None or cur.replica != self.replica:
                    # stolen out from under us (a TTL misjudgment):
                    # stop asserting ownership — the work in flight
                    # finishes and publishes idempotently, but the
                    # lease now belongs to the stealer
                    self._inc("fleet.lease_lost")
                    if rec is not None:
                        rec.event("fleet.lease_lost", key=name[:12])
                    return
                try:
                    atomic_write_bytes(self.lease_path(name),
                                       self._lease_body(name))
                except OSError:
                    return             # store dir gone: nothing to own

        t = threading.Thread(target=beat, name=f"fleet-hb-{name[:8]}",
                             daemon=True)
        with self._hb_lock:
            self._beats[name] = (stop, t)
        t.start()

    def _stop_heartbeat(self, name: str) -> None:
        with self._hb_lock:
            ent = self._beats.pop(name, None)
        if ent is None:
            return
        stop, t = ent
        stop.set()
        # a heartbeat thread never calls release/factor_once, so this
        # join cannot be a self-join; the guard keeps that invariant
        # checkable if someone ever routes a callback through it
        if threading.current_thread() is not t:
            t.join(timeout=10.0)

    # -- the single-flight ---------------------------------------------

    def factor_once(self, name: str, probe, work):
        """Return `(value, role)` where role is 'lead' (this replica
        ran `work`), 'adopt' (another replica published; `probe`
        returned it), or 'steal-lead' (this replica buried a dead
        leader's lease, then ran `work`).

        The follower wait is UNBOUNDED by caller deadline, exactly
        like the in-process leader path: the published factorization
        is useful to every future caller, and the steal path bounds
        the wait against leader death — a follower waits at most one
        TTL past the last heartbeat before the lease is stolen (by
        it or a peer) and the work restarts."""
        stole = False
        t0 = time.monotonic()
        backoff = self.poll_s
        waiting_logged = False
        while True:
            # adopt first: if the entry is already published there is
            # nothing to lead (the verified-hit fast path)
            val = probe()
            if val is not None:
                self._inc("fleet.adopted")
                if waiting_logged or stole:
                    flight.event(
                        "fleet.adopt", key=name[:12],
                        waited_us=int((time.monotonic() - t0) * 1e6))
                return val, "adopt"
            if self.try_acquire(name):
                self._start_heartbeat(name, rec=flight.current())
                try:
                    # double-check UNDER the lease: a caller that
                    # stalled between its missed probe and this
                    # acquire (the previous leader published and
                    # released in the gap) must adopt, never
                    # re-factor a verified published entry
                    val = probe()
                    if val is not None:
                        self._inc("fleet.adopted")
                        flight.event(
                            "fleet.adopt", key=name[:12],
                            waited_us=int((time.monotonic() - t0)
                                          * 1e6))
                        return val, "adopt"
                    role = "steal-lead" if stole else "lead"
                    self._inc("fleet.lead")
                    flight.event("fleet.lead", key=name[:12],
                                 ttl_s=self.ttl_s, stolen=stole)
                    return work(), role
                finally:
                    self.release(name)
            # follower: someone else holds the lease
            if not waiting_logged:
                waiting_logged = True
                self._inc("fleet.waits")
                flight.event("fleet.wait", key=name[:12])
            lease = self.read_lease(name)
            if lease is not None:
                # chaos site: treat a fresh lease as expired — forces
                # the steal path without needing a real leader death
                if lease.expired() or chaos.should("lease_steal"):
                    if self.try_steal(name):
                        stole = True
                        flight.event("fleet.steal", key=name[:12],
                                     age_s=round(lease.age_s(), 3),
                                     dead_replica=lease.replica)
                        continue       # immediately re-try acquire
            else:
                # lease vanished without a publication (leader failed
                # and released, or its steal corpse was buried):
                # loop straight back to probe-then-acquire
                continue
            time.sleep(backoff)
            backoff = min(backoff * 1.5, max(self.poll_s, 1.0))


def coordinator_from_env(store_root: str,
                         metrics=None) -> FleetCoordinator | None:
    """The `SLU_FLEET=1` hookup used by FactorCache: fleet
    single-flight over the store's own directory."""
    if not flags.env_int("SLU_FLEET", 0):
        return None
    return FleetCoordinator(store_root, metrics=metrics)
