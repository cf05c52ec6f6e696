"""Device-memory accounting (ISSUE 19 leg c).

The n>=1e6 spill tier (HBM/host/store paging) cannot be designed
against a system that never says where the bytes went.  This module
makes every factorization's memory footprint a recorded, falsifiable
pair:

  * `plan_bytes_predicted` — the analytic bytes model from the
    schedule's slab extents (per-device factor flats L/U/Li/Ui plus
    the replicated update slab), always available, computed from a
    handful of integers the schedule already carries.
  * `peak_bytes_measured` — live/peak bytes from jax
    `device.memory_stats()` where the platform provides them
    (SLU_OBS_MEM=1; TPU yes, CPU usually no), summed over addressable
    devices.  When the probe is unavailable the measured figure falls
    back to the analytic prediction and the record says so
    (`source: "analytic"`), so a consumer can always distinguish a
    measurement from a model.

Watermarks ride `Stats.mem_watermarks`, the health monitor's
per-factorization ring (obs/health.py `mem=`), and the `MEMWATCH`
registry provider — so `obs.snapshot()` (and with it the export
plane, obs/export.py) carries the fleet's memory story.

Cost discipline: with SLU_OBS_MEM unset the per-factorization cost is
a few attribute reads and integer multiplies (the analytic model);
the device probe — one runtime API call per device — only runs when
explicitly enabled.  Nothing here ever throws into the factorize
path.
"""

from __future__ import annotations

import collections
import threading

import numpy as np

from .. import flags

# documented slack on the analytic model (DESIGN.md §25): the model
# counts factor slabs + the update slab only, so a MEASURED peak may
# legitimately exceed it (XLA temporaries, RHS buffers) — but the
# model over-predicting the measured peak by more than this factor
# means the slab extents are wrong, which is what the test pins.
PREDICTION_SLACK = 8.0


def _analytic_bytes(lu) -> int:
    """Per-device bytes of the factor storage predicted from the
    SCHEDULE, before any numeric work ran: the four flat slabs plus
    the (replicated) extend-add update slab.  Host-backend handles
    (no schedule slabs) fall back to 2x lu_nnz entries — L+U plus
    their inverse panels."""
    itemsize = np.dtype(
        getattr(lu.effective_options, "factor_dtype", "float64")
    ).itemsize
    dev = getattr(lu, "device_lu", None)
    sched = getattr(dev, "schedule", None) if dev is not None else None
    if sched is not None and hasattr(sched, "L_total"):
        flats = (int(sched.L_total) + int(sched.U_total)
                 + int(sched.Li_total) + int(sched.Ui_total))
        upd = int(sched.upd_total) + int(getattr(sched, "upd_pad", 1))
        return (flats + upd) * itemsize
    return 2 * int(lu.plan.lu_nnz()) * itemsize


def schedule_bytes_predicted(schedule, dtype) -> int:
    """The same analytic model from a bare BatchedSchedule (for
    callers that have no handle yet: the prediction at plan time)."""
    itemsize = np.dtype(dtype).itemsize
    flats = (int(schedule.L_total) + int(schedule.U_total)
             + int(schedule.Li_total) + int(schedule.Ui_total))
    upd = int(schedule.upd_total) + int(getattr(schedule, "upd_pad", 1))
    return (flats + upd) * itemsize


def device_memory_stats() -> dict | None:
    """Summed live/peak bytes over addressable devices, or None when
    no device reports them (CPU backends typically return nothing).
    Never raises — this runs on the factorize path."""
    try:
        import jax
        devices = jax.devices()
    except Exception:       # noqa: BLE001 — probe, never a crash
        return None
    live = peak = 0
    seen = False
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:   # noqa: BLE001 — per-device containment
            continue
        if not ms:
            continue
        b = int(ms.get("bytes_in_use", 0))
        live += b
        peak += int(ms.get("peak_bytes_in_use", b))
        seen = True
    return {"live": live, "peak": peak} if seen else None


class MemoryWatch:
    """Per-phase device-memory watermarks (a Registry provider):
    last watermark per phase + a bounded ring of per-factorization
    records."""

    def __init__(self, recent_cap: int = 64) -> None:
        self._lock = threading.Lock()
        self.factorizations = 0
        self._by_phase: dict = {}
        self._recent = collections.deque(maxlen=recent_cap)

    def record(self, phase: str, rec: dict) -> None:
        with self._lock:
            self.factorizations += 1
            self._by_phase[phase] = dict(rec)
            self._recent.append(dict(rec, phase=phase))

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "probe_enabled": probe_enabled(),
                "factorizations": self.factorizations,
                "by_phase": {p: dict(r)
                             for p, r in self._by_phase.items()},
                "last": (dict(self._recent[-1])
                         if self._recent else None),
            }


MEMWATCH = MemoryWatch()

_lock = threading.Lock()
_probe: bool | None = None


def configure(probe: bool | None = None) -> None:
    """Re-resolve the SLU_OBS_MEM gate (tests reconfigure
    explicitly; import-time call picks up the environment)."""
    global _probe
    with _lock:
        if probe is None:
            probe = flags.env_str("SLU_OBS_MEM") == "1"
        _probe = bool(probe)


def probe_enabled() -> bool:
    return bool(_probe)


def watermarks(lu, phase: str = "FACT") -> dict:
    """One factorization's watermark record: the predicted/measured
    byte pair, recorded on MEMWATCH and returned for the caller to
    attach to Stats/health/flight.  Analytic-only when the live probe
    is off or unavailable."""
    pred = _analytic_bytes(lu)
    rec = {
        "plan_bytes_predicted": int(pred),
        "peak_bytes_measured": int(pred),
        "live_bytes_measured": None,
        "source": "analytic",
    }
    if _probe:
        ms = device_memory_stats()
        if ms is not None:
            rec["peak_bytes_measured"] = int(ms["peak"])
            rec["live_bytes_measured"] = int(ms["live"])
            rec["source"] = "measured"
    MEMWATCH.record(phase, rec)
    return rec


configure()
