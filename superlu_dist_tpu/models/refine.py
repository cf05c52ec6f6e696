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


class BatchResidual:
    """r_m = b_m - A_m x_m and the componentwise backward error of B
    systems on ONE pattern, on the host in the refine dtype: the
    residual of `batch/engine.batch_solve`'s loop.  It is the host's
    because a TPU's float64 is two float32 words (some 2^-47: the
    compiler's own lowering), which stands at the guarantee's edge,
    where this arithmetic is native.  One pass over the values in the
    native library, members over threads
    (`utils/native.batch_residual`); without the library the twin,
    two block-diagonal scipy products (`twin`: the oracle the tests
    hold the kernel to, bitwise in r).

    Built once a (plan, trans): the pattern's CSR form over the
    plan's COO order (`src` maps a sorted entry to its place in a
    member's value array; None where the order is the array's)."""

    def __init__(self, plan, trans: bool = False):
        rows, cols = ((plan.coo_cols, plan.coo_rows) if trans
                      else (plan.coo_rows, plan.coo_cols))
        order = np.lexsort((cols, rows)).astype(np.int64)
        self.n = int(plan.n)
        self.indptr = np.searchsorted(
            rows[order], np.arange(self.n + 1)).astype(np.int64)
        self.indices = np.ascontiguousarray(cols[order], np.int64)
        self.src = (None if np.array_equal(order,
                                           np.arange(len(order)))
                    else order)
        self._block = {}        # members -> block-diagonal pattern

    def __call__(self, vals, x, b, out=None):
        """vals (B, nnz), x and b (B, n, nrhs), one dtype: (r, berr);
        r in `out` where the caller keeps a buffer for it."""
        from ..utils import native
        with obs.span("refine.residual", cat="refine"):
            if native.available():
                return native.batch_residual(
                    self.indptr, self.indices, self.src, vals, x, b,
                    out=out)
            r, berr = self.twin(vals, x, b)
            if out is not None:
                out[...] = r
                r = out
            return r, berr

    def twin(self, vals, x, b):
        import scipy.sparse as sp
        B, n, nrhs = x.shape
        nnz = len(self.indices)
        if B not in self._block:
            self._block = {B: (
                np.concatenate([[0], (self.indptr[1:][None, :] + nnz
                                      * np.arange(B)[:, None]).ravel()]),
                (self.indices[None, :]
                 + n * np.arange(B)[:, None]).ravel())}
        indptr, indices = self._block[B]
        data = (vals if self.src is None else vals[:, self.src]).ravel()
        a = sp.csr_matrix((data, indices, indptr), shape=(B * n, B * n))
        xf, bf = x.reshape(B * n, nrhs), b.reshape(B * n, nrhs)
        r = bf - a @ xf
        denom = abs(a) @ np.abs(xf) + np.abs(bf)
        denom[denom == 0.0] = 1.0
        q = (np.abs(r) / denom).reshape(B, n * nrhs)
        return r.reshape(x.shape), np.max(q, axis=1)
