"""Numerical-health monitors for the GESP runtime contract.

Static pivoting means NOBODY pivots at runtime: a drifting value set
served through a cached factorization can only be caught by
*watching* the runtime numerics — tiny-pivot replacement counts,
pivot-growth estimates, the berr/ferr trajectory of every refinement
loop, and precision-escalation events (the psgssvx_d2 safety net
firing).  The reference surfaces the first of these once per
factorization in PStatPrint (RefineSteps/Berr, SRC/util.c:331); a
multi-tenant service needs them as a monitored time series, which is
what this module provides (a Registry provider; the serve layer's
berr histogram in serve/metrics.py is the percentile view of the same
signal).

Recording is always on: each hook is one lock plus a few scalar
writes per solve (noise against a device dispatch), so the monitors
work regardless of SLU_OBS.  Only the optional pivot-growth estimate
is gated behind the tracer being enabled — it walks diag(U) to the
host (O(n) + a device transfer), which is real money on the solve hot
path.
"""

from __future__ import annotations

import collections
import threading

import numpy as np

from . import tracer as _tracer


class HealthMonitor:
    """Aggregated numerical-health counters + a bounded ring of
    per-solve records (a Registry provider)."""

    def __init__(self, recent_cap: int = 64) -> None:
        self._lock = threading.Lock()
        self.factorizations = 0
        self.solves = 0
        self.tiny_pivots_total = 0
        self.escalations = 0
        self.refine_steps_total = 0
        self.stalled_refines = 0        # loops that quit on stall
        self.last_berr = 0.0
        self.last_pivot_growth = 0.0
        self._recent = collections.deque(maxlen=recent_cap)
        # precision-rung promotions: {trigger: count} + a bounded ring
        # of {from_dtype, to_dtype, trigger, berr} events
        self.escalations_by_trigger: dict = {}
        self._esc_recent = collections.deque(maxlen=recent_cap)
        # numerical-trust layer (numerics/, ISSUE 15): per-
        # factorization perturbation ledgers + rcond estimates
        self.perturbed_factorizations = 0
        self.pivot_growth_unavailable = 0   # probe couldn't run
        self.last_rcond: float | None = None
        self.rcond_estimates = 0
        self._factor_recent = collections.deque(maxlen=recent_cap)

    # -- recording hooks ----------------------------------------------

    def record_factor(self, *, tiny_pivots: int = 0,
                      pivot_growth: float | None = None,
                      dtype: str = "",
                      perturbation: dict | None = None,
                      mem: dict | None = None,
                      flops: dict | None = None,
                      extend_add: dict | None = None,
                      complex_lowering: str | None = None,
                      gesp: dict | None = None,
                      pack: str = "none",
                      route: dict | None = None) -> dict:
        """One factorization's numerical outcome.  `perturbation` is
        the tiny-pivot ledger dict (numerics/ledger.to_dict()) when
        GESP replaced any pivots; it rides the per-factorization ring
        so snapshot() exposes WHERE and how much, not just a lifetime
        count.  `mem` is the device-memory watermark record
        (obs/memory.py) — every factorization carries one.  `flops`
        is its {useful, executed} flop count (Stats.factor_flops,
        Stats.factor_flops_executed), `extend_add` its extend-add
        elements by lane (Stats.ea_elements), `complex_lowering` how
        a complex factorization was lowered and where ("pair",
        "native", or "cpu" for a gated placement:
        Stats.complex_lowering; None for a real one), `gesp` the
        plan's static-pivoting facts (plan/plan.gesp_facts;
        Stats.gesp), `pack` where its solve mirror was dispatched
        ("at_factor", or "none" so far: Stats.packs), `route` which
        route it took and what it dispatched (`dispatch` "staged" or
        "program", `segments`, `groups`, `pallas_buckets`,
        `pallas_shapes`: ops/batched._route; its keys lie flat in
        the record, absent where the backend has no such route).
        Returns the ring's record, for `record_pack`."""
        with self._lock:
            self.factorizations += 1
            self.tiny_pivots_total += int(tiny_pivots)
            if pivot_growth is not None:
                self.last_pivot_growth = float(pivot_growth)
            if perturbation is not None:
                self.perturbed_factorizations += 1
            rec = {
                "tiny_pivots": int(tiny_pivots),
                "dtype": dtype,
                "pivot_growth": (float(pivot_growth)
                                 if pivot_growth is not None else None),
                "perturbation": (dict(perturbation)
                                 if perturbation is not None else None),
                "mem": dict(mem) if mem is not None else None,
                "flops": dict(flops) if flops is not None else None,
                "extend_add": ({k: dict(v) for k, v in extend_add.items()}
                               if extend_add else None),
                "complex_lowering": complex_lowering,
                "gesp": dict(gesp) if gesp else None,
                "pack": pack,
                **(route or {}),
            }
            self._factor_recent.append(rec)
        if tiny_pivots:
            _tracer.instant("health.tiny_pivots", cat="health",
                            args={"count": int(tiny_pivots),
                                  "dtype": dtype})
        return rec

    def record_pack(self, rec: dict | None, where: str) -> None:
        """A factorization's pack was dispatched after its record was
        written (by its first solve, "at_solve"): correct the record
        `record_factor` returned."""
        if rec is None:
            return
        with self._lock:
            rec["pack"] = where

    def record_pivot_growth_unavailable(self, *,
                                        dtype: str = "") -> None:
        """The pivot-growth probe could not run (mesh-bound factors
        with no addressable diagonal, or a transfer failure).  Until
        ISSUE 15 this was a SILENT None — the monitor showed the
        previous factorization's growth figure as if it were current.
        Now it is a counted health event."""
        with self._lock:
            self.pivot_growth_unavailable += 1
        _tracer.instant("health.pivot_growth_unavailable",
                        cat="health", args={"dtype": dtype})

    def record_rcond(self, rcond: float | None) -> None:
        """One Hager-Higham condition estimate (numerics/gscon.py)."""
        if rcond is None:
            return
        with self._lock:
            self.rcond_estimates += 1
            self.last_rcond = float(rcond)
        _tracer.instant("health.rcond", cat="health",
                        args={"rcond": float(rcond)})

    def record_refine(self, *, berr: float, steps: int,
                      berr_trajectory=(), ferr_trajectory=(),
                      converged: bool = True,
                      stalled: bool = False,
                      sweeps: dict | None = None,
                      complex_lowering: str | None = None,
                      sweep_segments: int | None = None,
                      sweep_arm: str | None = None,
                      sweep_syncs: int | None = None,
                      members: int | None = None,
                      members_stalled: int | None = None) -> None:
        """One refinement loop's outcome.  `ferr_trajectory` is the
        per-step forward-error estimate ‖δ‖/‖x‖ (the correction-norm
        proxy for pdgsrfs' FERR output).  `sweeps` counts the solve's
        triangular sweeps (x0's and the corrections') by operand
        dtype, `complex_lowering` says how they were lowered and
        where (as record_factor's), `sweep_segments` how many
        programs each of them dispatched (ops/batched.sweep_programs:
        1 under the merged trisolve arm on either handle form, a
        program a group each way for a staged handle under the legacy
        sweep; 1 on a mesh; None on the host oracle).  On a mesh
        `sweep_arm` names that program (`merged` or `rhs_sharded`:
        parallel/factor_dist.solve_arm) and
        `sweep_syncs` counts its all-reduces.  A batched solve
        (batch/engine.batch_solve) leaves ONE record: `berr` and
        `steps` are the largest of its `members`, `members_stalled`
        counts those that stalled (the two keys are absent from a
        one-system record), and `sweep_arm` is the batched sweep's
        (`vmap`, member-parallel, or `scan`).  `stalled` means the loop
        quit because berr stopped halving — NOT that it merely ran
        out of step budget while still improving; only the former
        raises the alarm event."""
        with self._lock:
            self.solves += 1
            self.refine_steps_total += int(steps)
            self.last_berr = float(berr)
            if stalled:
                self.stalled_refines += 1
            rec = {
                "berr": float(berr), "steps": int(steps),
                "berr_trajectory": [float(b) for b in berr_trajectory],
                "ferr_trajectory": [float(f) for f in ferr_trajectory],
                "converged": bool(converged),
                "stalled": bool(stalled),
                "sweeps": dict(sweeps or {}),
                "complex_lowering": complex_lowering,
                "sweep_segments": sweep_segments,
                "sweep_arm": sweep_arm,
                "sweep_syncs": sweep_syncs,
            }
            if members is not None:
                rec.update(members=int(members),
                           members_stalled=int(members_stalled or 0))
            self._recent.append(rec)
        if stalled:
            _tracer.instant("health.refine_stalled", cat="health",
                            args={"berr": float(berr),
                                  "steps": int(steps)})

    def record_escalation(self, *, berr: float, factor_dtype: str,
                          refine_dtype: str,
                          to_dtype: str | None = None,
                          trigger: str = "berr_plateau") -> None:
        """One precision-rung promotion — the loudest health event
        there is: a low-precision factor failed its refinement
        contract and the driver (gssvx ladder / serve dtype tier) is
        re-factoring one rung up.  `to_dtype` is the rung being
        promoted to (None: legacy callers, implies refine_dtype);
        `trigger` names the signal that fired
        (precision/policy.classify_trigger: berr_plateau |
        refine_stalled | pivot_growth | nonfinite | tier_berr).  The
        recent ring + per-trigger counters surface in snapshot() and
        the registry's dump_text()."""
        to_dtype = to_dtype or refine_dtype
        with self._lock:
            self.escalations += 1
            self.escalations_by_trigger[trigger] = \
                self.escalations_by_trigger.get(trigger, 0) + 1
            self._esc_recent.append({
                "from_dtype": factor_dtype, "to_dtype": to_dtype,
                "trigger": trigger, "berr": float(berr),
            })
        _tracer.instant("health.escalation", cat="health",
                        args={"berr": float(berr),
                              "factor_dtype": factor_dtype,
                              "refine_dtype": refine_dtype,
                              "to_dtype": to_dtype,
                              "trigger": trigger})

    # -- readers -------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            last = self._recent[-1] if self._recent else None
            return {
                "factorizations": self.factorizations,
                "solves": self.solves,
                "tiny_pivots_total": self.tiny_pivots_total,
                "escalations": self.escalations,
                "refine_steps_total": self.refine_steps_total,
                "stalled_refines": self.stalled_refines,
                "last_berr": self.last_berr,
                "last_pivot_growth": self.last_pivot_growth,
                "last_solve": dict(last) if last else None,
                "recent_solves": [dict(e) for e in self._recent],
                "perturbed_factorizations":
                    self.perturbed_factorizations,
                "pivot_growth_unavailable":
                    self.pivot_growth_unavailable,
                "last_rcond": self.last_rcond,
                "rcond_estimates": self.rcond_estimates,
                "factor_events":
                    [dict(e) for e in self._factor_recent],
                "last_factor": (dict(self._factor_recent[-1])
                                if self._factor_recent else None),
                # {trigger: count} flattens into dump_text lines
                # (slu_health_escalations_by_trigger_<t>); the event
                # ring is the structured view
                "escalations_by_trigger":
                    dict(self.escalations_by_trigger),
                "escalation_events":
                    [dict(e) for e in self._esc_recent],
                "last_escalation": (dict(self._esc_recent[-1])
                                    if self._esc_recent else None),
            }

    def summary(self) -> str:
        """One line for Stats.report()."""
        with self._lock:
            s = (f"berr {self.last_berr:.2e}, "
                 f"tiny pivots {self.tiny_pivots_total}, "
                 f"escalations {self.escalations}, "
                 f"stalled refines {self.stalled_refines}")
            if self.last_pivot_growth:
                s += f", pivot growth {self.last_pivot_growth:.2e}"
            if self.pivot_growth_unavailable:
                s += (", pivot growth unavailable "
                      f"{self.pivot_growth_unavailable}x")
            if self.last_rcond is not None:
                s += f", rcond {self.last_rcond:.2e}"
            return s


def pivot_growth(lu) -> float | None:
    """Cheap pivot-growth estimate for a GESP factorization:
    max|diag(U)| / max|A_scaled| (diag-only — a lower bound on the
    classic max|U|/max|A|, but free of any full-factor transfer).
    A large value flags the amplification static pivoting cannot
    bound; compare against 1/eps of the factor dtype.  Returns None
    instead of raising when the factors can't be probed (e.g. a
    mesh-sharded U spanning non-addressable devices) — this runs on
    the factorize path, and observability never throws into it.  The
    None is no longer SILENT: it is counted as a
    `pivot_growth_unavailable` health event, so a monitor showing a
    stale last_pivot_growth figure is distinguishable from one whose
    probe is actually running."""
    try:
        from ..models.gssvx import get_diag_u
        du = np.abs(np.asarray(get_diag_u(lu)))
        anorm = float(getattr(lu.plan, "anorm", 0.0)) or 1.0
        return float(du.max() / anorm) if du.size else 0.0
    except Exception:
        HEALTH.record_pivot_growth_unavailable(
            dtype=str(getattr(getattr(lu, "effective_options", None),
                              "factor_dtype", "")))
        return None


# the process-wide monitor every numeric path reports into
HEALTH = HealthMonitor()
