"""Refine-until-degraded cadence: WHEN to pay a refactorization.

Under value drift the stale resident factors are a preconditioner
whose quality decays — each solve's refined berr creeps up as the
live values move away from the factored ones.  The hard line is the
berr guard (the 64·eps accuracy class the serve layer already
enforces on tier/degraded traffic): a result is NEVER served past it.
Everything below that line is an economics question — a
factorization costs seconds to minutes while a
stale refined solve costs milliseconds, so the right schedule rides
the stale factors as long as refinement honestly covers the drift and
starts the next factorization early enough that it LANDS before the
guard would trip.

This controller turns the measured berr trajectory into that
schedule.  Three triggers, checked cheapest-first:

  berr_trip   the last refined berr crossed `trip_frac` x the guard
              limit — the escalation threshold (obs.HEALTH records
              it, trigger="stream_drift").  Refactor now.
  drift       a linear fit over the trajectory since the last swap
              predicts the trip level will be reached within one
              factorization wall — refactor NOW so the swap beats
              the breach (the lookahead is what makes the background
              pipeline overlap instead of chase).
  lag         the live values are `max_lag` steps past the resident
              generation (optional; drift in berr is the primary
              signal, but a bounded-staleness policy can insist).

plus `rcond_drift` (between berr_trip and drift, SLU_COND_ESTIMATE
only): the estimated rcond of the newest generation has fallen
SLU_STREAM_RCOND_DRIFT x below the stream's first-generation
baseline — the PROBLEM is hardening toward singularity, which berr
alone can miss right up to the cliff (numerics/, ISSUE 15).

plus a MIN INTERVAL between refactor starts — `interval_scale` x the
factorization cost — bounding the background duty cycle so a noisy
berr series cannot turn the pipeline into a hot loop of 477 s
factorizations.  The cost estimate is this handle's own measured
refactor walls (EWMA).

Fleet coupling: with a coordinator
attached, the background refactorization itself goes through the
fleet single-flight (one leader factors a drifted key, every other
replica adopts the published entry — once per pool, not N times), and
a small deterministic per-replica phase jitter keeps N replicas from
probing the lease at the same instant.
"""

from __future__ import annotations

import threading
import time

from .. import flags
from ..obs import flight

# factorization-cost estimate before the first wall is measured;
# deliberately small — the first real refactor replaces it
_COST_FALLBACK_S = 1.0
# trajectory points kept / used by the drift fit
_TRAJ_CAP = 32
_FIT_POINTS = 8


def _defaults() -> dict:
    return {
        "trip_frac": flags.env_float("SLU_STREAM_TRIP", 0.25),
        "interval_scale": flags.env_float("SLU_STREAM_INTERVAL_SCALE",
                                          1.0),
        "max_lag": flags.env_int("SLU_STREAM_MAX_LAG", 0),
        "rcond_drift": flags.env_float("SLU_STREAM_RCOND_DRIFT",
                                       100.0),
    }


class Cadence:
    """Per-stream refactor scheduler.  Thread-safe: berr samples land
    from batcher flusher threads, `due()` runs on update/solve
    threads, swap notes on the pipeline worker."""

    def __init__(self, guard_limit: float,
                 trip_frac: float | None = None,
                 interval_scale: float | None = None,
                 max_lag: int | None = None,
                 fleet: bool = False) -> None:
        d = _defaults()
        self.guard_limit = float(guard_limit)
        self.trip_frac = (d["trip_frac"] if trip_frac is None
                          else float(trip_frac))
        self.interval_scale = (d["interval_scale"]
                               if interval_scale is None
                               else float(interval_scale))
        self.max_lag = d["max_lag"] if max_lag is None else int(max_lag)
        self.trip = self.trip_frac * self.guard_limit
        # conditioning drift (numerics/, ISSUE 15): refactor when the
        # live values' estimated rcond has fallen `rcond_drift`x below
        # the generation-0 baseline — berr measures how well refinement
        # covers the drift, rcond measures how much the PROBLEM itself
        # has hardened; a matrix drifting toward singularity can keep
        # berr low right up to the cliff
        self.rcond_drift = d["rcond_drift"]
        self._lock = threading.Lock()
        self._traj: list[tuple[float, float]] = []   # (mono, berr)
        self._rcond0: float | None = None    # baseline at last swap
        self._rcond_last: float | None = None
        self._last_start: float | None = None
        self._measured_wall_s: float | None = None   # EWMA
        # deterministic per-replica phase jitter (fleet only): spreads
        # N replicas' refactor starts over a quarter interval so lease
        # probes stagger instead of stampeding at the same instant
        self._jitter_frac = 0.0
        if fleet:
            rid = flight.replica_id()
            self._jitter_frac = 0.25 * (
                sum(rid.encode()) % 256) / 256.0

    # -- inputs --------------------------------------------------------

    def note_berr(self, berr: float,
                  now: float | None = None) -> None:
        """One refined solve's berr against the current resident
        generation (the stream guard feeds this per dispatch)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            self._traj.append((now, float(berr)))
            del self._traj[:-_TRAJ_CAP]

    def note_rcond(self, rcond: float | None) -> None:
        """One generation's condition estimate (the pipeline feeds
        this at prime and after each swap, when SLU_COND_ESTIMATE has
        populated the handle).  The first estimate after a swap is the
        new baseline; later estimates are compared against it."""
        if rcond is None:
            return
        with self._lock:
            if self._rcond0 is None:
                self._rcond0 = float(rcond)
            self._rcond_last = float(rcond)

    def note_refactor_start(self, now: float | None = None) -> None:
        with self._lock:
            self._last_start = (time.monotonic() if now is None
                                else now)

    def note_swap(self, wall_s: float | None = None) -> None:
        """A new generation published: the trajectory restarts (its
        berr series described the OLD factors) and the measured
        refactor wall updates the cost estimate (EWMA, so one noisy
        wall does not whipsaw the schedule)."""
        with self._lock:
            self._traj.clear()
            if wall_s is not None:
                w = float(wall_s)
                self._measured_wall_s = (
                    w if self._measured_wall_s is None
                    else 0.5 * self._measured_wall_s + 0.5 * w)

    # -- the schedule --------------------------------------------------

    def cost_s(self) -> float:
        """Estimated wall of the next refactorization: this stream's
        own measured walls (EWMA — the pipeline seeds it with the
        prime factorization and updates it per refactor), else
        the fallback."""
        with self._lock:
            if self._measured_wall_s is not None:
                return self._measured_wall_s
        return _COST_FALLBACK_S

    def min_interval_s(self) -> float:
        base = self.interval_scale * self.cost_s()
        return base * (1.0 + self._jitter_frac)

    def due(self, lag: int = 0,
            now: float | None = None) -> str | None:
        """Should a refactorization start now?  Returns the trigger
        name ('berr_trip' | 'rcond_drift' | 'drift' | 'lag') or None.
        `lag` is how many steps the live values are past the resident
        generation (0 = fresh: nothing to do)."""
        if lag <= 0:
            return None
        now = time.monotonic() if now is None else now
        # snapshot under the lock, decide outside it: cost_s()/
        # min_interval_s() take the same (non-reentrant) lock
        with self._lock:
            last_start = self._last_start
            traj = list(self._traj)
            rc0, rc_last = self._rcond0, self._rcond_last
        if (last_start is not None
                and now - last_start < self.min_interval_s()):
            return None
        if self.max_lag and lag >= self.max_lag:
            return "lag"
        if not traj:
            return None
        if traj[-1][1] >= self.trip:
            return "berr_trip"
        if (rc0 is not None and rc_last is not None
                and self.rcond_drift > 1.0
                and rc_last <= rc0 / self.rcond_drift):
            # the problem itself has hardened rcond_drift x since the
            # stream's first generation: refactor eagerly — refinement
            # against stale factors has less margin per unit of value
            # drift the closer the matrix sits to singular
            return "rcond_drift"
        slope = self._slope(traj)
        if slope > 0.0:
            # lookahead: will berr reach the trip level before a
            # factorization started NOW could land?
            t_to_trip = (self.trip - traj[-1][1]) / slope
            if t_to_trip <= self.cost_s():
                return "drift"
        return None

    @staticmethod
    def _slope(traj) -> float:
        """d(berr)/dt over the last few points (least squares)."""
        pts = traj[-_FIT_POINTS:]
        if len(pts) < 2:
            return 0.0
        t0 = pts[0][0]
        xs = [t - t0 for t, _ in pts]
        ys = [b for _, b in pts]
        n = len(pts)
        mx = sum(xs) / n
        my = sum(ys) / n
        den = sum((x - mx) ** 2 for x in xs)
        if den <= 0.0:
            return 0.0
        return sum((x - mx) * (y - my)
                   for x, y in zip(xs, ys)) / den

    def snapshot(self) -> dict:
        with self._lock:
            traj = list(self._traj)
            last_start = self._last_start
            wall = self._measured_wall_s
            rc0, rc_last = self._rcond0, self._rcond_last
        return {
            "trip": self.trip,
            "rcond_drift": self.rcond_drift,
            "rcond0": rc0,
            "rcond_last": rc_last,
            "guard_limit": self.guard_limit,
            "trip_frac": self.trip_frac,
            "interval_scale": self.interval_scale,
            "max_lag": self.max_lag,
            "cost_s": round(self.cost_s(), 4),
            "measured_wall_s": (round(wall, 4)
                                if wall is not None else None),
            "last_berr": traj[-1][1] if traj else None,
            "berr_slope_per_s": self._slope(traj),
            "points": len(traj),
            "since_last_start_s": (
                round(time.monotonic() - last_start, 3)
                if last_start is not None else None),
        }
