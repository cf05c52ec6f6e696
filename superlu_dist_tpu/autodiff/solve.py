"""The differentiable sparse solve: custom VJP over resident factors.

Forward leg: the handle's merged packed trisolve (ops/trisolve.sweep)
inside the same permute/scale embedding algebra solve() uses
(models/gssvx.perm_scale_vectors), expressed as pure gathers so the
whole program traces — plus `SLU_AD_REFINE` refinement steps against
the TRACED value vector (a scatter-free padded-ELL residual, the
ops/spmv layout), which is what makes the primal genuinely depend on
`A_values` while still riding the resident factors.

Backward leg (custom VJP): the implicit-function adjoint of the EXACT
solve fixed point — NOT the unrolled derivative of the refinement
iteration.  JAX's complex vjp convention is v ↦ Jᵀv on the
holomorphic part (NO conjugation — vjp of z ↦ c·z returns c·v, not
conj(c)·v; grad adds the conj at the real-loss boundary), so for
x = A⁻¹b:

    μ       = A⁻ᵀ v            (the resident TRANS sweep, unconjugated
                                even for complex)
    ct_b    = μ
    ct_vals[s] = −μ[r_s]·x[c_s]         summed over RHS columns,

with (r_s, c_s) = plan.coo order slot s — one gather per side, zero
scatters, pinned by the `autodiff.adjoint_solve` HLO contract.  TRANS
swaps the sweep direction and the row/column roles; CONJ (x = A⁻ᴴb,
anti-holomorphic in A) is one overall conjugation around the TRANS
formulas; see DESIGN.md §24 for the derivations.

Both legs dispatch through cached compile-watched jits (phases
"grad_fwd" / "adjoint"), so `jit(grad(f))` recompiles nothing on a
second same-signature call and `jax.grad` performs ZERO new
factorizations — the `autodiff.reuses_resident` contract.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags, obs
from ..obs import flight
from ..numerics.errors import InvalidInputError
from ..numerics.ledger import strip_result_markers
from ..ops.spmv import ell_cols_from_src, ell_from_csr, ell_spmv
from ..ops.trisolve import get_packs, get_trisolve, resident_sweep
from ..options import Trans

_CTX_LOCK = threading.Lock()


def _ell_plane(rows: np.ndarray, cols: np.ndarray, n: int):
    """Padded-ELL planes of the pattern (rows, cols) whose value
    gather indexes the ORIGINAL slot order: (src, ell_cols) with
    src[i, k] ∈ [0, nnz] the original slot of row i's k-th entry
    (pad → nnz, the extended-with-one-zero convention of
    ops/spmv.DeviceSpMV) and ell_cols the matching column plane
    (pad → n, the clamp-and-kill sentinel).  Built once per context
    for A and once for Aᵀ (rows/cols swapped), so the refinement
    residual of every trans lane is a pure gather over the traced
    value vector."""
    nnz = len(rows)
    order = np.argsort(rows, kind="stable").astype(np.int64)
    cols_sorted = np.asarray(cols, dtype=np.int64)[order]
    counts = np.bincount(np.asarray(rows, dtype=np.int64)[order],
                         minlength=n)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    src_sorted, _w = ell_from_csr(indptr, cols_sorted, nnz=nnz)
    # src_sorted indexes the row-sorted slot order; compose back to
    # original slots (order_ext[nnz] = nnz keeps the pad sentinel)
    order_ext = np.concatenate([order, np.asarray([nnz], np.int64)])
    src = order_ext[src_sorted]
    idt = np.int32 if max(n, nnz) < 2**31 - 1 else np.int64
    return (jnp.asarray(src.astype(idt)),
            jnp.asarray(ell_cols_from_src(src_sorted, cols_sorted,
                                          n).astype(idt)))


def _plane_spmv(plane, vals, x):
    """y = P(vals)·x for a pattern plane: extend the traced value
    vector with one zero so pad slots contribute exactly 0, gather
    the band, ride ops/spmv.ell_spmv (gather + einsum, no scatter)."""
    src, ecols = plane
    ve = jnp.concatenate([vals, jnp.zeros((1,), vals.dtype)])
    return ell_spmv(ecols, ve[src], x)


@dataclasses.dataclass
class GradResult:
    """One vjp_solve answer: the (refined) forward solution plus the
    cotangents of the loss direction `xbar` — ct_b aligned with b,
    ct_vals aligned with A_values (plan.coo slot order == a.data)."""
    x: object
    ct_vals: object
    ct_b: object
    trans: Trans


class GradContext:
    """Prepared differentiable-solve machinery for ONE resident
    factorization: the trisolve schedule + packs, the embedding
    permutation/scale vectors of both sweep directions, the pattern
    index planes, and the per-lane cached jitted legs and custom-VJP
    callables.  Built once per handle via grad_context() — every
    jax.grad / jax.vjp / vmap composition reuses the same compiled
    programs (the zero-recompile pin in tests/test_autodiff.py)."""

    def __init__(self, lu):
        from ..models.gssvx import perm_scale_vectors
        from ..ops.batched import _lu_is_pair
        dlu = lu.device_lu
        plan = lu.plan
        self.n = int(plan.n)
        self.ts = get_trisolve(dlu.schedule)
        self.packs = get_packs(dlu)
        self.pair = _lu_is_pair(dlu)
        self.fdtype = np.dtype(dlu.dtype)
        idt = np.int32 if self.n < 2**31 - 1 else np.int64
        embed = {}
        for trans_leg, lane in ((False, Trans.NOTRANS),
                                (True, Trans.TRANS)):
            isc, iperm, operm, osc = perm_scale_vectors(plan, lane)
            embed[trans_leg] = (jnp.asarray(isc),
                                jnp.asarray(iperm.astype(idt)),
                                jnp.asarray(np.asarray(operm)
                                            .astype(idt)),
                                jnp.asarray(osc))
        self._embed = embed
        rows = np.asarray(plan.coo_rows)
        cols = np.asarray(plan.coo_cols)
        self.coo_rows = jnp.asarray(rows.astype(idt))
        self.coo_cols = jnp.asarray(cols.astype(idt))
        self.plane_a = _ell_plane(rows, cols, self.n)
        self.plane_t = _ell_plane(cols, rows, self.n)
        self.refine_steps = max(0, flags.env_int("SLU_AD_REFINE", 1))
        self.use_jit = flags.env_str("SLU_AD_JIT", "1").strip() != "0"
        self._legs: dict = {}
        self._vjps: dict = {}
        # reentrant: diff_fn's critical section builds the legs
        self._lock = threading.RLock()

    # -- traced programs ----------------------------------------------

    def _resident(self, packs, v, trans_leg: bool):
        """One resident sweep in the embedding algebra, all gathers:
        x = out_scale·y[out_perm], y = M-solve((in_scale·v)[in_perm])."""
        isc, iperm, operm, osc = self._embed[trans_leg]
        sdt = v.real.dtype
        bf = (v * isc.astype(sdt)[:, None])[iperm]
        y = resident_sweep(self.ts, packs, bf, self.fdtype, trans_leg,
                           pair=self.pair)
        return y[operm] * osc.astype(y.real.dtype)[:, None]

    def _fwd_trace(self, packs, vals, b2, lane: Trans):
        if lane == Trans.CONJ:
            # x = A⁻ᴴb = conj(A⁻ᵀ·conj(b)); Aᴴ·x = Aᵀ-plane(conj vals)
            def sol(v):
                return jnp.conj(self._resident(packs, jnp.conj(v),
                                               True))

            def op(x):
                return _plane_spmv(self.plane_t, jnp.conj(vals), x)
        elif lane == Trans.TRANS:
            def sol(v):
                return self._resident(packs, v, True)

            def op(x):
                return _plane_spmv(self.plane_t, vals, x)
        else:
            def sol(v):
                return self._resident(packs, v, False)

            def op(x):
                return _plane_spmv(self.plane_a, vals, x)
        x = sol(b2)
        for _ in range(self.refine_steps):
            x = x + sol(b2 - op(x))
        return x

    def _adj_trace(self, packs, xbar, x, lane: Trans):
        """Implicit-function cotangents at the exact-solve fixed
        point (module docstring table); one resident sweep + two
        pattern gathers, no scatter, no new factorization."""
        def slots(left, right):
            # ct_vals[s] = −Σ_j left[·_s, j]·right[·_s, j] — JAX's
            # Jᵀv convention carries no conjugation on the
            # holomorphic part (module docstring)
            return -(left * right).sum(axis=-1)

        if lane == Trans.TRANS:
            # x = A⁻ᵀb:  ct_b = A⁻¹v;  ct[s] = −μ[c]·x[r]
            mu = self._resident(packs, xbar, False)
            ct_vals = slots(mu[self.coo_cols], x[self.coo_rows])
        elif lane == Trans.CONJ:
            # x = A⁻ᴴb (anti-holomorphic in A): one conjugation
            # around TRANS — ct_b = conj(A⁻¹·conj(v));
            # ct[s] = conj(−ct_b[c]·x[r]).  Real dtypes degenerate
            # to the TRANS lane exactly (conj is the identity).
            mu = jnp.conj(self._resident(packs, jnp.conj(xbar),
                                         False))
            ct_vals = jnp.conj(slots(mu[self.coo_cols],
                                     x[self.coo_rows]))
        else:
            # x = A⁻¹b:  μ = A⁻ᵀv;  ct[s] = −μ[r]·x[c]
            mu = self._resident(packs, xbar, True)
            ct_vals = slots(mu[self.coo_rows], x[self.coo_cols])
        return ct_vals, mu

    # -- cached compiled legs -----------------------------------------

    def leg_fns(self, lane: Trans):
        """(forward, adjoint) compile-watched jits for one trans lane
        — positional-only, packs as an argument (the trisolve packed
        discipline), obs phases 'grad_fwd' / 'adjoint' so the
        zero-recompile and contract gates see them."""
        fns = self._legs.get(lane)
        if fns is not None:
            return fns
        with self._lock:
            fns = self._legs.get(lane)
            if fns is None:
                def fwd_fn(packs, vals, b2, _lane=lane):
                    return self._fwd_trace(packs, vals, b2, _lane)

                def adj_fn(packs, xbar, x, _lane=lane):
                    return self._adj_trace(packs, xbar, x, _lane)

                fns = self._legs[lane] = (
                    obs.watch_jit("grad_fwd", jax.jit(fwd_fn)),
                    obs.watch_jit("adjoint", jax.jit(adj_fn)))
        return fns

    def diff_fn(self, lane: Trans):
        """The custom-VJP callable f(vals, b2) -> x2 for one lane —
        cached so repeated sparse_solve calls hand jax the SAME
        function object (outer jit caches stay warm)."""
        f = self._vjps.get(lane)
        if f is not None:
            return f
        with self._lock:
            f = self._vjps.get(lane)
            if f is None:
                f = self._vjps[lane] = self._make_vjp(lane)
        return f

    def _make_vjp(self, lane: Trans):
        fwd_leg, adj_leg = self.leg_fns(lane)
        use_jit = self.use_jit

        def run_fwd(vals, b2):
            if use_jit:
                return fwd_leg(self.packs, vals, b2)
            return self._fwd_trace(self.packs, vals, b2, lane)

        @jax.custom_vjp
        def sparse_solve_lane(vals, b2):
            return run_fwd(vals, b2)

        def fwd_rule(vals, b2):
            x = run_fwd(vals, b2)
            # vals/b ride the residuals only for their dtypes: the
            # pattern is static, so the adjoint needs x alone
            return x, (x, vals, b2)

        def bwd_rule(res, xbar):
            x, vals, b2 = res
            if use_jit:
                ct_vals, ct_b = adj_leg(self.packs, xbar, x)
            else:
                ct_vals, ct_b = self._adj_trace(self.packs, xbar, x,
                                                lane)
            return (_cast_cotangent(ct_vals, vals.dtype),
                    _cast_cotangent(ct_b, b2.dtype))

        sparse_solve_lane.defvjp(fwd_rule, bwd_rule)
        return sparse_solve_lane


def _cast_cotangent(ct, primal_dtype):
    """custom_vjp requires cotangent dtype == primal dtype; the legs
    compute at the promoted solve dtype, so a real primal under a
    complex loss keeps the real part (JAX's R-inner-product
    convention) and precision rounds down to the primal's."""
    pdt = np.dtype(primal_dtype)
    if (not jnp.issubdtype(pdt, jnp.complexfloating)
            and jnp.issubdtype(ct.dtype, jnp.complexfloating)):
        ct = ct.real
    return ct.astype(pdt)


def grad_context(lu) -> GradContext:
    """The handle's cached GradContext (built on first use; keyed by
    the SLU_AD_* knobs).  Requires resident jax-backend factors —
    host/dist handles raise the typed InvalidInputError taxonomy, the
    same failure model as solves (DESIGN.md §24)."""
    if getattr(lu, "backend", None) != "jax" \
            or getattr(lu, "device_lu", None) is None:
        raise InvalidInputError(
            "sparse_solve differentiates through resident device "
            f"factors; this handle's backend is "
            f"{getattr(lu, 'backend', None)!r} (factorize with "
            "backend='jax')")
    key = (max(0, flags.env_int("SLU_AD_REFINE", 1)),
           flags.env_str("SLU_AD_JIT", "1").strip() != "0")
    dlu = lu.device_lu
    with _CTX_LOCK:
        cache = getattr(dlu, "_ad_ctx", None)
        if cache is None:
            cache = dlu._ad_ctx = {}
        ctx = cache.get(key)
        if ctx is None:
            ctx = cache[key] = GradContext(lu)
    return ctx


def _lane_of(lu, trans) -> Trans:
    if trans is None:
        trans = lu.effective_options.trans
    return Trans(trans)


def sparse_solve(A_values, b, lu, *, trans: Trans | None = None):
    """Differentiable x = op(A)⁻¹·b riding the resident factorization
    `lu` (op = identity / transpose / conjugate-transpose per
    `trans`, default the handle's Options.trans).

    `A_values` is the matrix value vector in `a.data` order (the
    plan.coo slot order); the primal is the SLU_AD_REFINE-step
    refined solution, the VJP is the exact-fixed-point adjoint on the
    SAME factors — `jax.grad`/`jax.vjp`/`jax.vmap` compose, zero new
    factorizations.  PerturbedResult/DegradedResult markers are
    stripped off the inputs and re-stamped on the PRIMAL output only
    (never on tracers or cotangents)."""
    ctx = grad_context(lu)
    lane = _lane_of(lu, trans)
    vals = jnp.asarray(strip_result_markers(A_values))
    bv = strip_result_markers(b)
    squeeze = getattr(bv, "ndim", 2) == 1
    b2 = jnp.asarray(bv)
    if squeeze:
        b2 = b2[:, None]
    x = ctx.diff_fn(lane)(vals, b2)
    if squeeze:
        x = x[:, 0]
    return _restamp_primal(x, lu)


def vjp_solve(lu, b, xbar=None, A_values=None,
              trans: Trans | None = None) -> GradResult:
    """One forward + one adjoint leg on the resident handle: solve
    op(A)x = b, then pull the loss direction `xbar` (default: ones —
    d(sum x)/d·) back through the custom VJP.  `A_values` defaults to
    the handle's own matrix values (the linearization point the
    factors came from).  The serve/stream grad entries ride this."""
    ctx = grad_context(lu)
    lane = _lane_of(lu, trans)
    if A_values is None:
        if getattr(lu, "a", None) is None:
            raise InvalidInputError(
                "vjp_solve needs A_values: this handle kept no "
                "matrix (factorized with keep_a=False?)")
        A_values = lu.a.data
    vals = jnp.asarray(strip_result_markers(A_values))
    bv = strip_result_markers(b)
    squeeze = getattr(bv, "ndim", 2) == 1
    b2 = jnp.asarray(bv)
    if squeeze:
        b2 = b2[:, None]
    t0 = time.monotonic()
    x, pull = jax.vjp(ctx.diff_fn(lane), vals, b2)
    jax.block_until_ready(x)
    flight.event("grad.fwd", s=round(time.monotonic() - t0, 6))
    if xbar is None:
        xb2 = jnp.ones_like(x)
    else:
        xb2 = jnp.asarray(strip_result_markers(xbar)).astype(x.dtype)
        if xb2.ndim == 1:
            xb2 = xb2[:, None]
    t1 = time.monotonic()
    ct_vals, ct_b = pull(xb2)
    jax.block_until_ready(ct_vals)
    flight.event("grad.adj", s=round(time.monotonic() - t1, 6))
    if squeeze:
        x, ct_b = x[:, 0], ct_b[:, 0]
    return GradResult(x=_restamp_primal(x, lu), ct_vals=ct_vals,
                      ct_b=ct_b, trans=lane)


def _restamp_primal(x, lu):
    """Re-stamp the perturbation marker on a concrete primal output
    when the factors carry a perturbed ledger — tracers flow through
    untouched (a stamped tracer would poison vmap/grad), and
    cotangents are never stamped (they answer a different question
    than 'which factors did this solution ride')."""
    if isinstance(x, jax.core.Tracer):
        return x
    led = getattr(lu, "ledger", None)
    if led is not None and getattr(led, "perturbed", False):
        from ..numerics.ledger import stamp_perturbed
        return stamp_perturbed(np.asarray(x), ledger=led,
                               rcond=getattr(lu, "rcond", None))
    return x


# --------------------------------------------------------------------
# HLO contract registry declarations (tools/slulint/contracts.py)
# --------------------------------------------------------------------

def _contract_build_adjoint_solve():
    from ..models.gssvx import factorize
    from ..options import Options
    from ..utils.testmat import laplacian_3d
    a = laplacian_3d(8)
    lu = factorize(a, Options(factor_dtype="float32"), backend="jax")
    ctx = grad_context(lu)
    _fwd, adj = ctx.leg_fns(Trans.NOTRANS)
    z = jnp.zeros((a.n, 1), jnp.float32)
    return adj, (ctx.packs, z, z), {}


def _contract_check_reuses_resident():
    from ..models.gssvx import factorize
    from ..options import Options
    from ..utils.testmat import laplacian_3d
    a = laplacian_3d(6)
    lu = factorize(a, Options(factor_dtype="float64"), backend="jax")
    vals = jnp.asarray(a.data)
    b = jnp.ones((a.n,), vals.dtype)
    before = obs.HEALTH.factorizations
    jax.grad(lambda v, bb: sparse_solve(v, bb, lu).sum(),
             argnums=(0, 1))(vals, b)
    after = obs.HEALTH.factorizations
    return (after == before,
            f"jax.grad ran {after - before} factorization(s) against "
            "a resident handle")


HLO_CONTRACTS = [
    {"name": "autodiff.adjoint_solve",
     "phase": "adjoint",
     "env": {"SLU_TRISOLVE": "merged"},
     "contracts": ("no_scatter", "no_host_callback"),
     "build": _contract_build_adjoint_solve,
     "note": "the backward leg of grad-through-solve is ONE resident "
             "transpose sweep plus pattern gathers — a scatter or "
             "host callback here means d/dA stopped being the "
             "gather-only −x·λᵀ restriction (peer of "
             "gscon.estimator_solve)"},
    {"name": "autodiff.reuses_resident",
     "phase": "adjoint",
     "env": {"SLU_TRISOLVE": "merged"},
     "check": _contract_check_reuses_resident,
     "note": "jax.grad of sparse_solve must perform ZERO new "
             "factorizations — the adjoint rides the same resident "
             "factors as the forward solve (the ISSUE-18 tentpole "
             "pin)"},
]
