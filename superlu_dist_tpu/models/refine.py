"""Iterative refinement (pdgsrfs analog, SRC/pdgsrfs.c:124).

Classic Wilkinson loop: r = b − A·x (accumulated in refine_dtype, the
psgsrfs_d2 mixed-precision strategy when the factorization ran in a
lower precision, SRC/psgsrfs_d2.c:229), solve A·δ = r with the existing
factorization IN THE FACTOR'S PRECISION (the caller's `solve_factored`
casts r after `to_factor_rhs`: precision/policy.sweep_operand_dtype,
the one rule this loop and the fused device loop share), x += δ in
refine_dtype, until the componentwise backward error `berr` stops
improving (same stopping rule family as the reference: stop when
berr <= eps or improvement < 2×; "eps" is the accumulator's
`precision/policy.refine_eps`: eps for a real one, sqrt(2)·eps for a
complex one, whose answers' berr stands astride eps itself).

This is the HOST loop (scipy CSR residuals — already scatter-free).
The fused device solver runs the same decisions on device with the
padded-ELL residual SpMV (`ops/spmv.py`; scatter-free by
construction, `SLU_SPMV_LAYOUT` selects) inside one XLA while_loop —
`ops/batched.make_fused_solver` mirrors this loop's semantics and the
two must not diverge."""

from __future__ import annotations

import numpy as np

from .. import obs


def _refine_dtype(opts, a_dtype):
    """The accumulator dtype per the resolved residual mode
    (precision/policy.resolve_residual_mode — ONE resolution shared
    with the fused device solver): PLAIN accumulates in the working
    (factor) precision, FP64 in refine_dtype (f64 by default) — the
    psgsrfs vs psgsrfs_d2 distinction.  DOUBLEWORD on this HOST loop
    accumulates in native float64: the df64 fp32-pair kernels exist to
    avoid fp64 *emulation* on accelerators (precision/doubleword.py),
    and on a CPU with hardware fp64 the native accumulator is both
    faster and a few bits tighter — same contract (residual carries
    ≥2× factor precision), better lowering for the backend.  A complex
    system promotes the accumulator to the matching complex dtype
    (the mode names the *precision*, the matrix decides realness — the
    reference's z twin files hard-code doublecomplex here)."""
    from ..precision.policy import ResidualMode, resolve_residual_mode
    mode = resolve_residual_mode(opts)
    if mode == ResidualMode.PLAIN.value:
        base = np.dtype(opts.factor_dtype)
    elif mode == ResidualMode.DOUBLEWORD.value:
        base = np.dtype(np.float64)
    else:
        base = np.dtype(opts.refine_dtype)
    if np.issubdtype(np.dtype(a_dtype), np.complexfloating):
        # lift realness only — promote_types(f32, c64)=c64 keeps the
        # working precision, unlike promoting with a_dtype directly
        base = np.promote_types(base, np.complex64)
    return base


def _operands(lu, sys_dtype):
    """A and |A| in refine precision, cached on the factorization
    handle (the FACTORED rung exists for repeated solves; rebuilding
    these per solve would be an O(nnz) tax on every call)."""
    rdt = _refine_dtype(lu.effective_options, sys_dtype)
    # store A in the real precision of rdt when A itself is real:
    # numpy promotion in `b - A @ x` gives the identical complex
    # residual without doubling the cached matrix or the SpMV cost
    adt = rdt
    if (not np.issubdtype(lu.a.dtype, np.complexfloating)
            and np.issubdtype(rdt, np.complexfloating)):
        adt = np.dtype(np.dtype(rdt).char.lower())  # c->f of same width
    # the cache is a SHARED container mutated in place (never
    # reassigned): dataclasses.replace handle copies — the
    # FACTORED/CONJ rungs, the serve layer's per-request option
    # merges — all see one build.  One entry PER operand dtype
    # (bounded by the handful of refine precisions), inserted fully
    # formed under the handle lock, so a lock-free fast-path reader
    # never sees a torn (asp, abs_a) pair and alternating-dtype
    # callers sharing one handle never thrash rebuilds
    cache = lu.refine_cache   # dataclass default_factory guarantees
    ent = cache.get(adt)      # the container exists on every handle
    if ent is None:
        with lu.cache_lock:
            ent = cache.get(adt)
            if ent is None:
                asp = lu.a.to_scipy().astype(adt)
                ent = {"asp": asp, "abs_a": abs(asp)}
                cache[adt] = ent    # atomic insert of a complete entry
    return ent["asp"], ent["abs_a"]


def iterative_refine(lu, b, x, solve_factored, to_factor_rhs,
                     from_factor_sol, trans: bool = False,
                     sweeps: dict | None = None,
                     lowering: str | None = None,
                     sweep_segments: int | None = None,
                     sweep_mesh: dict | None = None):
    """`sweeps` is the caller's live count of this solve's sweeps by
    operand dtype (x0's included; `solve_factored` adds to it): it
    rides the health ring's record next to `steps`, and so do
    `lowering`, the complex lowering those sweeps ran under
    (Stats.complex_lowering; None for a real system),
    `sweep_segments`, the programs each of them dispatched, and on a
    mesh `sweep_mesh`: `sweep_arm` and `sweep_syncs`
    (parallel/factor_dist.solve_arm, solve_syncs)."""
    from ..precision.policy import refine_eps
    opts = lu.effective_options
    # the system's realness is set by matrix AND rhs: a real matrix
    # with a complex b still needs a complex accumulator
    sys_dtype = np.promote_types(lu.a.dtype, b.dtype)
    rdt = _refine_dtype(opts, sys_dtype)
    eps = refine_eps(rdt)
    asp, abs_a = _operands(lu, sys_dtype)
    if trans:
        asp = asp.T
        abs_a = abs_a.T
    xk = x.astype(rdt)
    bk = b.astype(rdt)

    def berr_of(r, xv):
        # componentwise backward error: max_i |r_i| / (|A||x| + |b|)_i
        denom = abs_a @ np.abs(xv) + np.abs(bk)
        denom = np.where(denom == 0.0, 1.0, denom)
        return float(np.max(np.abs(r) / denom))

    def residual(xv):
        # the host residual and its berr: two sparse products
        with obs.span("refine.residual", cat="refine"):
            rv = bk - asp @ xv
            return rv, berr_of(rv, xv)

    r, berr = residual(xk)
    steps = 0
    # health trajectories (obs/health.py): the berr path of the loop
    # and the forward-error proxy ‖δ‖/‖x‖ per step — the runtime
    # numerics watch the GESP contract demands (a drifting value set
    # against cached factors shows up HERE first)
    berr_traj = [berr]
    ferr_traj = []
    track_ferr = obs.enabled()
    stalled = False
    for _ in range(opts.max_refine_steps):
        if berr <= eps:
            break
        with obs.span("REFINE_STEP", args={"berr": berr}):
            d = from_factor_sol(solve_factored(lu, to_factor_rhs(r)))
            x_new = xk + d
            r_new, berr_new = residual(x_new)
        steps += 1
        berr_traj.append(berr_new)
        if track_ferr:
            # two full-array host norms — only worth paying when
            # observability is on (berr above is free: the loop's own
            # control variable)
            xn = float(np.linalg.norm(x_new))
            ferr_traj.append(
                float(np.linalg.norm(d)) / xn if xn else 0.0)
        if not np.isfinite(berr_new) or berr_new >= berr * 0.5:
            stalled = True
            if berr_new < berr:
                xk, berr = x_new, berr_new
            break
        xk, r, berr = x_new, r_new, berr_new
    # the numerics alarm is "berr stopped halving SHORT of eps" —
    # neither a loop that ran out of step budget while still
    # improving, nor one whose last halving landed at machine
    # precision (berr can't halve below eps), is a stall
    converged = bool(berr <= eps)
    stalled = stalled and not converged
    obs.HEALTH.record_refine(berr=berr, steps=steps,
                             berr_trajectory=berr_traj,
                             ferr_trajectory=ferr_traj,
                             converged=converged,
                             stalled=stalled, sweeps=sweeps,
                             complex_lowering=lowering,
                             sweep_segments=sweep_segments,
                             **(sweep_mesh or {}))
    # `stalled` rides back to the driver: the escalation ladder
    # (gssvx) labels its health event with the signal that fired
    # (precision/policy.classify_trigger), and "the loop quit because
    # berr stopped halving" is that signal's ground truth
    return xk, berr, steps, stalled
