"""Execution statistics and per-phase timing.

Analog of SuperLUStat_t (SRC/util_dist.h:101-123), the PhaseType keys
(SRC/superlu_enum_consts.h:66-90) and PStatPrint (SRC/util.c:331).  On
TPU the timers bracket `jax.block_until_ready` so device work is
attributed to the right phase (SURVEY.md §5.1).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict

from .. import obs


# Phase keys mirroring PhaseType (SRC/superlu_enum_consts.h:66-90).
# FACT_ESC is this build's addition: the precision-escalation rerun
# (a second factorization at refine precision) reports separately so
# FACT's GFLOP/s never blends two differently-precisioned runs.
PHASES = (
    "EQUIL", "ROWPERM", "COLPERM", "ETREE", "SYMBFACT", "GATHER",
    "DIST", "FACT", "FACT_ESC", "SOLVE", "REFINE", "SPMV",
)


_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_HLO_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")


def hlo_collective_stats(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Measured collective inventory of a compiled XLA module: count
    and result bytes per collective kind, parsed from the
    post-optimization HLO (`compiled.as_text()`).  This is the
    ground-truth cross-check for the schedule's *predicted* traffic
    (BatchedSchedule.comm_summary — the SCT_t measured-counters
    contract, SRC/util_dist.h:194-317, realized as
    compiled-artifact inspection instead of runtime probes: under XLA
    the program IS the message schedule)."""
    import re
    out: Dict[str, Dict[str, int]] = {}
    # Sync form:   %ag  = f32[8,128]{1,0} all-gather(...)
    # Async pair:  %ags = (f32[1,128], f32[8,128]) all-gather-start(...)
    #              %agd = f32[8,128]{1,0} all-gather-done(...)
    # The -start tuple mixes operand and result shapes (it would
    # double-count local+global), so async collectives are counted at
    # their -done op, whose result IS the collective's output; -start
    # is skipped.  CPU emits the sync form, TPU the async pair — both
    # land on the same numbers this way.
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    op_re = re.compile(
        r"= ([^=]*?) (" + "|".join(_HLO_COLLECTIVES) + r")(-done)?\(")
    for m in op_re.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in shape_re.findall(shapes):
            if dt not in _HLO_DTYPE_BYTES:
                continue
            elems = 1
            for d in dims.split(","):
                if d:
                    elems *= int(d)
            nbytes += _HLO_DTYPE_BYTES[dt] * elems
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += nbytes
    return out


@dataclasses.dataclass
class Stats:
    utime: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {p: 0.0 for p in PHASES})
    ops: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {p: 0.0 for p in PHASES})
    tiny_pivots: int = 0
    refine_steps: int = 0
    berr: float = 0.0
    # last refinement loop quit on a genuine stall (berr stopped
    # halving short of eps — models/refine.py); the escalation
    # ladder's trigger classification reads it
    refine_stalled: bool = False
    # triangular sweeps under this Stats by operand dtype (x0's and
    # every refinement correction's; models/gssvx.solve casts each to
    # the factor's precision, precision/policy.sweep_operand_dtype,
    # and counts it here: accumulates over solves like refine_steps)
    sweeps: Dict[str, int] = dataclasses.field(default_factory=dict)
    # precision escalations: low-precision factor failed refinement,
    # refactored at refine_dtype (gssvx _should_escalate)
    escalations: int = 0
    # memory accounting (dQuerySpace_dist analog, SRC/superlu_ddefs.h:616)
    lu_nnz: int = 0
    lu_bytes: int = 0
    workspace_bytes: int = 0
    # flops of the LAST factorization under this Stats: the plan's
    # useful count (plan/frontal.front_flops over the fronts' (w, r))
    # and what the schedule executes (the same formula over every
    # scheduled slot at its bucket shape, padding slots included)
    factor_flops: float = 0.0
    factor_flops_executed: float = 0.0
    # extend-add elements of the LAST factorization by lane
    # (BatchedSchedule.ea_elements: element / row / block, each
    # {padded, real}, the row lane also {children, turns}); empty on
    # the host backend
    ea_elements: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    # collective traffic: predicted from the schedule (comm_summary)
    # and measured from the compiled HLO (hlo_collective_stats) — the
    # SCT_print3D comm-volume contract
    comm_predicted: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    comm_measured: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    # per-factorization detail (ISSUE 15): one {tiny_pivots, dtype}
    # record per factorize() under this Stats, so a multi-factor run
    # (escalation ladder, SamePattern refresh) shows WHICH
    # factorization perturbed, not just a blended total
    factor_events: list = dataclasses.field(default_factory=list)
    # device-memory watermarks of the LAST factorization under this
    # Stats (obs/memory.py, ISSUE 19): the plan_bytes_predicted /
    # peak_bytes_measured pair that makes the spill-tier design
    # falsifiable; per-factorization copies ride factor_events
    mem_watermarks: Dict[str, object] = dataclasses.field(
        default_factory=dict)
    # condition estimate of the LAST factorization served through this
    # run (numerics/gscon.ensure_rcond), None when not estimated
    rcond: float | None = None
    # phase -> backend, for phases that did NOT run on the default
    # backend (utils/platform.complex_device_gate places complex
    # programs on "cpu" when the default backend is a TPU); empty when
    # everything ran where jax put it
    placement: Dict[str, str] = dataclasses.field(default_factory=dict)
    # phase -> how a COMPLEX factorization or solve was lowered and
    # where: "pair" (real/imaginary planes, an all-real program: what
    # a TPU runs), "native", or "cpu" for a gated placement
    # (utils/platform.complex_lowering, complex_device_gate); empty
    # for a real system.  Each factorization's and each refined
    # solve's value rides the health ring (`complex_lowering`)
    complex_lowering: Dict[str, str] = dataclasses.field(
        default_factory=dict)
    # where `ops/trisolve.get_packs` took its miss path under this
    # Stats: a factorization dispatches its own pack under the merged
    # sweep ("at_factor", ops/batched.factorize_device); "at_solve"
    # counts handles that reached their first solve without one.  The
    # health ring's factor records carry each one's as `pack`
    packs: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"at_factor": 0, "at_solve": 0})
    # the plan's GESP facts (plan/plan.gesp_facts: rows_moved, n,
    # equed, row_scale_min/max, col_scale_min/max, zero_diagonal),
    # stamped by the plan build and by every factorization on the
    # plan; the health ring's factor records carry them as `gesp`
    gesp: Dict[str, object] = dataclasses.field(default_factory=dict)
    # which route the LAST factorization under this Stats took on the
    # one-device jax backend and what it dispatched
    # (ops/batched._route: `dispatch` "staged" or "program",
    # `segments`, `groups`, `pallas_buckets`, `pallas_shapes`), and
    # `sweep_segments`, the programs each sweep of the last solve
    # dispatched; empty on the host oracle.  On a process grid the
    # route is the mesh's (parallel/factor_dist._mesh_route:
    # `devices`, `coop_groups`, `comm_bytes`) and the last solve adds
    # `sweep_arm` (merged | rhs_sharded), `sweep_segments`
    # 1 and `sweep_syncs`, the all-reduces a sweep.  The health
    # ring's factor and solve records carry the same keys
    dispatch: Dict[str, object] = dataclasses.field(default_factory=dict)
    # the LAST batched solve's outcome member by member
    # (batch/engine.batch_solve): `berr`, `refine_steps` and `stalled`
    # as (B,) arrays, and `missed`, the indices of the members with a
    # zero pivot or a berr outside the contract's 64 eps; `passes`,
    # a (members live, members swept) a refinement pass: all of them
    # while more than the straggler rung are live, the rung from
    # there; `berr`, `refine_steps` and `refine_stalled` above hold
    # the worst member's.  `dispatch` gains `batch_members`, `batch_sweep_arm`
    # (vmap | scan) and `batch_residual` (host, or None unrefined)
    batch: Dict[str, object] = dataclasses.field(default_factory=dict)

    @contextlib.contextmanager
    def timer(self, phase: str):
        # every phase wall doubles as an obs trace span (the Chrome
        # trace and the report come from the SAME brackets, so they
        # cannot disagree); obs.span is a shared no-op when tracing
        # is off
        t0 = time.perf_counter()
        try:
            with obs.span(phase, cat="phase"):
                yield
        finally:
            self.utime[phase] = self.utime.get(phase, 0.0) + (
                time.perf_counter() - t0)

    def add_ops(self, phase: str, flops: float) -> None:
        self.ops[phase] = self.ops.get(phase, 0.0) + flops

    def note_factor_event(self, *, tiny_pivots: int = 0,
                          dtype: str = "",
                          mem: dict | None = None) -> None:
        """One factorization's per-run record (called from
        models/gssvx.factorize).  `mem` is the obs/memory.py
        watermark record — every factorization event carries one."""
        self.factor_events.append({"tiny_pivots": int(tiny_pivots),
                                   "dtype": str(dtype),
                                   "mem": (dict(mem)
                                           if mem is not None else None)})

    def note_pack(self, where: str) -> None:
        """One taken miss of the pack ("at_factor" / "at_solve");
        "none" (no pack dispatched) counts nothing."""
        if where in self.packs:
            self.packs[where] += 1

    def gflops(self, phase: str) -> float:
        t = self.utime.get(phase, 0.0)
        if t <= 0:
            return 0.0
        return self.ops.get(phase, 0.0) / t / 1e9

    def snapshot(self) -> dict:
        """JSON-ready view for the obs.Registry (the serve
        Metrics.snapshot analog for per-run phase stats)."""
        return {
            "utime": {p: t for p, t in self.utime.items() if t},
            "ops": {p: v for p, v in self.ops.items() if v},
            "tiny_pivots": self.tiny_pivots,
            "refine_steps": self.refine_steps,
            "berr": self.berr,
            "refine_stalled": self.refine_stalled,
            "sweeps": dict(self.sweeps),
            "packs": dict(self.packs),
            "complex_lowering": dict(self.complex_lowering),
            "gesp": dict(self.gesp),
            "dispatch": dict(self.dispatch),
            "escalations": self.escalations,
            "lu_nnz": self.lu_nnz,
            "lu_bytes": self.lu_bytes,
            "factor_flops": self.factor_flops,
            "factor_flops_executed": self.factor_flops_executed,
            "ea_elements": {k: dict(v)
                            for k, v in self.ea_elements.items()},
            "comm_predicted": dict(self.comm_predicted),
            "factor_events": [dict(e) for e in self.factor_events],
            "mem_watermarks": dict(self.mem_watermarks),
            "rcond": self.rcond,
        }

    def report(self) -> str:
        """PStatPrint-style report (SRC/util.c:331)."""
        lines = ["** Phase breakdown **"]
        for p in PHASES:
            t = self.utime.get(p, 0.0)
            if t == 0.0 and self.ops.get(p, 0.0) == 0.0:
                continue
            line = f"  {p:<10s} {t * 1e3:10.2f} ms"
            if self.ops.get(p, 0.0) > 0:
                line += f"  {self.gflops(p):8.2f} GF/s"
            lines.append(line)
        lines.append(f"  tiny pivots replaced: {self.tiny_pivots}")
        if len(self.factor_events) > 1 or any(
                e["tiny_pivots"] for e in self.factor_events):
            # per-factorization breakdown: which run perturbed
            per = ", ".join(
                f"#{i} {e['dtype'] or '?'}: {e['tiny_pivots']}"
                for i, e in enumerate(self.factor_events))
            lines.append(f"    per factorization:  {per}")
        if self.gesp:
            g = self.gesp
            lines.append(
                f"  static pivoting:      {g['rows_moved']} of {g['n']} "
                f"rows moved, {g['zero_diagonal']} zero diagonals, "
                f"equed {g['equed']} (rows {g['row_scale_min']:.3g}.."
                f"{g['row_scale_max']:.3g}, cols "
                f"{g['col_scale_min']:.3g}..{g['col_scale_max']:.3g})")
        lines.append(f"  refinement steps:     {self.refine_steps}")
        if self.sweeps:
            lines.append("  sweeps by operand:    " + ", ".join(
                f"{k} {v}" for k, v in sorted(self.sweeps.items())))
        if any(self.packs.values()):
            lines.append(
                f"  packs dispatched:     {self.packs['at_factor']} at "
                f"factor, {self.packs['at_solve']} at solve")
        if "dispatch" in self.dispatch:
            d = self.dispatch
            line = (f"  dispatch:             {d['dispatch']}, "
                    f"{d['segments']} programs a factorization "
                    f"({d['groups']} groups, {d['pallas_buckets']} on "
                    "the Pallas panel LU)")
            if "sweep_segments" in d:
                line += f", {d['sweep_segments']} a sweep"
            lines.append(line)
        if "sweep_arm" in self.dispatch:
            d = self.dispatch
            lines.append(
                f"  mesh sweep:           {d['sweep_arm']}, "
                f"{d['sweep_segments']} program a sweep, "
                f"{d['sweep_syncs']} all-reduces")
        if "batch_members" in self.dispatch:
            d = self.dispatch
            line = (f"  batched solve:        {d['batch_members']} "
                    f"members, {d['batch_sweep_arm']} sweep")
            if self.batch:
                line += (f", residual on the {d['batch_residual']}, "
                         f"{len(self.batch['missed'])} missed")
            lines.append(line)
        if self.rcond is not None:
            lines.append(f"  estimated rcond:      {self.rcond:.2e}")
        if self.placement:
            placed = ", ".join(f"{p} on {b}" for p, b in
                               sorted(self.placement.items()))
            lines.append(f"  placed off-default:   {placed}")
        if self.complex_lowering:
            lines.append("  complex lowering:     " + ", ".join(
                f"{p} {how}" for p, how in
                sorted(self.complex_lowering.items())))
        # process-wide compile + health telemetry (obs/): the jit
        # caches and the health monitor are process-scoped like the
        # compile caches themselves, so the report shows the process
        # counters alongside this run's walls
        cw = obs.COMPILE_WATCH.snapshot()
        by = ", ".join(f"{k}={v}" for k, v in
                       sorted(cw["by_phase"].items()))
        lines.append(f"  jit compiles:         {cw['misses']} miss"
                     + (f" ({by})" if by else ""))
        su = cw["startup"]
        lines.append(
            f"  start-up (process):   {su['programs']} new programs, "
            f"trace {su['trace_s']:.2f} s, lower {su['lower_s']:.2f} s, "
            f"compile {su['compile_s']:.2f} s, cache load "
            f"{su['load_s']:.2f} s ({su['cache_hits']} hit, "
            f"{su['cache_misses']} miss, {su['cache_off']} off); "
            f"exported store {su['aot']['hits']} hit, "
            f"{su['aot']['misses']} miss, {su['aot']['rejected']} "
            f"refused, {su['aot']['unexportable']} unexportable")
        lines.append(f"  health: {obs.HEALTH.summary()}")
        if self.escalations:
            lines.append(
                f"  precision escalations: {self.escalations}")
        if self.lu_nnz:
            lines.append(
                f"  nnz(L+U): {self.lu_nnz}  LU bytes: {self.lu_bytes}")
        if self.factor_flops_executed:
            share = 100.0 * self.factor_flops / self.factor_flops_executed
            lines.append(
                f"  factor flops: {self.factor_flops:.4g} useful, "
                f"{self.factor_flops_executed:.4g} executed "
                f"({share:.1f} %)")
        if self.ea_elements:
            lines.append("  extend-add elements (padded / real): " + ", ".join(
                f"{k} {v['padded']:.4g} / {v['real']:.4g}"
                for k, v in self.ea_elements.items()))
            row = self.ea_elements.get("row", {})
            if row.get("turns"):
                lines.append(
                    f"  row lane: {row['children']} children in "
                    f"{row['turns']} loop turns")
        if self.comm_predicted:
            lines.append("** Collective traffic (predicted) **")
            for k, v in self.comm_predicted.items():
                lines.append(f"  {k:<24s} {v}")
        if self.comm_measured:
            lines.append("** Collective traffic (measured, compiled HLO) **")
            for phase, kinds in self.comm_measured.items():
                for k, v in kinds.items():
                    if isinstance(v, dict):
                        lines.append(f"  {phase}/{k:<18s} "
                                     f"count {v['count']:<5d} "
                                     f"bytes {v['bytes']}")
                    else:
                        # scalar mesh stamps (measure_comm "MESH"):
                        # n_devices, per-boundary bytes, arm
                        lines.append(f"  {phase}/{k:<18s} {v}")
        return "\n".join(lines)
