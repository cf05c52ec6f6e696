"""Expert driver: the pdgssvx analog (SRC/pdgssvx.c:506).

`gssvx(options, A, B)` runs the full pipeline — equilibrate, static
pivoting row perm, fill-reducing col perm, symbolic plan, numeric
factorization, triangular solves, iterative refinement — and returns X
plus statistics.  `factorize`/`solve` expose the two halves for the
Fact reuse ladder (SamePattern / SamePattern_SameRowPerm / FACTORED,
SRC/superlu_defs.h:577-598):

    plan = plan_factorization(A, opts)        # once per pattern
    lu   = factorize(A, plan=plan)            # per value set
    x    = solve(lu, b)                       # per right-hand side

Backends: "jax" (bucketed level-batched device execution, the TPU
path; what backend="auto" means without a grid), "dist" (the same on a
process grid) and "host" (numpy reference multifrontal, the test
oracle — only ever by explicit request).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import numpy as np

from .. import obs
from ..options import ColPerm, Fact, IterRefine, Options, Trans
from ..plan.plan import FactorPlan, plan_factorization
from ..sparse import CSRMatrix
from ..utils.stats import Stats
from ..ops import ref_multifrontal


@dataclasses.dataclass
class LUFactorization:
    """Factorization handle: plan + numeric factors (LUstruct analog,
    SRC/superlu_ddefs.h:266-271)."""
    plan: FactorPlan
    backend: str
    host_lu: Optional[object] = None      # ops.ref_multifrontal.HostLU
    device_lu: Optional[object] = None    # ops.batched.DeviceLU
    a: Optional[CSRMatrix] = None         # kept for refinement residuals
    stats: Optional[Stats] = None
    options: Optional[Options] = None     # effective numeric options
    # cached refinement operands (rebuilt per factorization, reused
    # across the many solves the FACTORED rung is for).  A shared
    # MUTABLE container, populated in place (models/refine.py
    # _operands): dataclasses.replace copies — the FACTORED/CONJ
    # rungs and the serve layer's per-request option merges — all see
    # one build, instead of each copy rebuilding its own O(nnz)
    # operands
    refine_cache: dict = dataclasses.field(default_factory=dict,
                                           repr=False, compare=False)
    # guards the lazy operand-cache build above; replace copies carry
    # the SAME lock object, so handle copies serialize against each
    # other
    cache_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)
    # numerical-trust fields (numerics/): the Hager-Higham rcond
    # estimate (None until numerics.gscon.ensure_rcond caches it —
    # replace copies carry a computed value forward) and the
    # tiny-pivot perturbation ledger factorize() stamps
    rcond: Optional[float] = None
    ledger: Optional[object] = None   # numerics.ledger.PerturbationLedger
    # this factorization's record in the health ring (obs/health.py),
    # kept so a solve that takes the pack's miss can say so there
    factor_record: Optional[dict] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n(self) -> int:
        return self.plan.n

    @property
    def effective_options(self) -> Options:
        return self.options or self.plan.options


def effective_factor_dtype(a_dtype, factor_dtype) -> np.dtype:
    """A complex system forces a complex factor dtype of matching
    precision (the reference's z drivers hard-code doublecomplex; a
    silent cast would truncate imaginary parts)."""
    fdt = np.dtype(factor_dtype)
    if np.issubdtype(np.dtype(a_dtype), np.complexfloating) \
            and fdt.kind != "c":
        fdt = np.promote_types(fdt, np.complex64)
    return fdt


def factorize(a: CSRMatrix, options: Options | None = None,
              plan: FactorPlan | None = None,
              stats: Stats | None = None,
              backend: str = "auto",
              user_perm_r: np.ndarray | None = None,
              user_perm_c: np.ndarray | None = None,
              grid=None, _phase: str = "FACT") -> LUFactorization:
    # caller's options win (numeric knobs may differ from the cached
    # plan's); fall back to the plan's when none are given
    if options is None:
        options = plan.options if plan is not None else Options()
    stats = stats if stats is not None else Stats()
    if plan is None:
        plan = plan_factorization(a, options, stats=stats,
                                  user_perm_r=user_perm_r,
                                  user_perm_c=user_perm_c)
    fdt = effective_factor_dtype(a.dtype, options.factor_dtype)
    if fdt.name != options.factor_dtype:
        options = options.replace(factor_dtype=fdt.name)
    if backend == "auto":
        backend = "dist" if grid is not None else "jax"
    elif backend != "dist" and grid is not None:
        raise ValueError(
            f"backend={backend!r} conflicts with grid=; pass "
            "backend='dist' (or 'auto') for mesh execution")

    from ..utils.platform import complex_device_gate
    # drop any stale stamp from a direct ops-layer call the driver
    # never read (the host path below stamps nothing)
    obs.take_cost("pack")
    # complex on a TPU: the one-device jax backend and the process
    # grid take the pair lowering and stay on the chips
    # (utils/platform.complex_lowering; a mesh is judged by its own
    # devices); the host oracle has no pair storage and keeps the
    # gated placement
    mesh = getattr(grid, "mesh", grid) if backend == "dist" else None
    with complex_device_gate(np.dtype(options.factor_dtype),
                             pair_capable=(backend in ("jax", "dist")),
                             stats=stats, phase=_phase, mesh=mesh), \
            stats.timer(_phase):
        # the host's share of a refactorization before any dispatch:
        # Dr·A·Dc in the plan's order (the backends' cast of it to the
        # factor dtype closes a span of the same name)
        with obs.span("fact.scale", cat="fact"):
            scaled = plan.scaled_values(a)
        if backend == "host":
            host_lu = ref_multifrontal.factorize_host(
                plan, scaled, dtype=np.dtype(options.factor_dtype))
            stats.tiny_pivots += host_lu.tiny_pivots
            lu = LUFactorization(plan=plan, backend="host",
                                 host_lu=host_lu, a=a, stats=stats)
        elif backend == "jax":
            from ..ops import batched
            device_lu = batched.factorize_device(
                plan, scaled, dtype=np.dtype(options.factor_dtype))
            stats.tiny_pivots += int(device_lu.tiny_pivots)
            lu = LUFactorization(plan=plan, backend="jax",
                                 device_lu=device_lu, a=a, stats=stats)
        elif backend == "dist":
            # mesh-sharded factors (pdgssvx on a process grid); `grid`
            # is a parallel.grid.Grid/Grid3D or a jax Mesh
            from ..parallel import factor_dist
            if grid is None:
                raise ValueError("backend='dist' requires grid=")
            dist_lu = factor_dist.dist_factor_fn(
                plan, mesh, np.dtype(options.factor_dtype))(scaled)
            stats.tiny_pivots += dist_lu.tiny_pivots
            stats.comm_predicted = dist_lu.schedule.comm_summary(
                np.dtype(options.factor_dtype))
            lu = LUFactorization(plan=plan, backend="dist",
                                 device_lu=dist_lu, a=a, stats=stats)
        else:
            raise ValueError(f"unknown backend {backend!r}")
    lu.options = options
    stats.add_ops(_phase, plan.factor_flops)
    # useful against executed flops of THIS factorization: the plan's
    # count, and the same formula over the schedule's bucket-padded
    # slots (the host oracle pads nothing)
    sched = getattr(lu.device_lu, "schedule", None)
    stats.factor_flops = plan.factor_flops
    stats.factor_flops_executed = (sched.executed_flops if sched
                                   else plan.factor_flops)
    stats.ea_elements = sched.ea_elements if sched else {}
    stats.gesp = dict(getattr(plan, "gesp", None) or {})
    # which route it took and what it dispatched (the one-device jax
    # backend's handles say: ops/batched._route), or on a process grid
    # the devices, cooperative groups and predicted collective bytes
    # (parallel/factor_dist._mesh_route)
    route = getattr(lu.device_lu, "route", None)
    stats.dispatch.update(route or {})
    # where this factorization's solve mirror was dispatched: by
    # `factorize_device` under the merged sweep ("at_factor"), else
    # not yet ("none": a later solve that packs corrects the ring's
    # record, solve() below)
    pack = obs.take_cost("pack") or "none"
    stats.note_pack(pack)
    stats.lu_nnz = plan.lu_nnz()
    stats.lu_bytes = stats.lu_nnz * np.dtype(options.factor_dtype).itemsize
    # numerical-health watch (obs/health.py): GESP never pivots at
    # runtime, so every factorization reports its tiny-pivot
    # replacements — and, when tracing is on (the estimate walks
    # diag(U) to the host), a pivot-growth estimate.  The perturbation
    # ledger (numerics/ledger.py) makes the replacements first-class:
    # count, original-column locations and injected magnitude ride
    # the handle, the health ring and (via the serve layer) flight
    # records and result stamps.  Free on a clean factorization — the
    # O(n) diagonal gather only runs when the device counter is
    # nonzero.
    from ..numerics.ledger import build_ledger
    src = lu.host_lu if lu.backend == "host" else lu.device_lu
    lu.ledger = build_ledger(lu)
    # device-memory watermarks (obs/memory.py, ISSUE 19): the
    # predicted/measured byte pair of THIS factorization rides the
    # Stats, the health ring, and the MEMWATCH registry provider —
    # analytic slab-extent bytes always, live device.memory_stats()
    # under SLU_OBS_MEM=1
    from ..obs import memory as obs_memory
    mem = obs_memory.watermarks(lu, phase=_phase)
    stats.mem_watermarks = mem
    lu.factor_record = obs.HEALTH.record_factor(
        tiny_pivots=int(getattr(src, "tiny_pivots", 0)),
        pivot_growth=(obs.pivot_growth(lu) if obs.enabled() else None),
        dtype=options.factor_dtype,
        perturbation=(lu.ledger.to_dict() if lu.ledger.perturbed
                      else None),
        mem=mem,
        flops={"useful": stats.factor_flops,
               "executed": stats.factor_flops_executed},
        extend_add=stats.ea_elements,
        complex_lowering=stats.complex_lowering.get(_phase),
        gesp=stats.gesp, pack=pack, route=route)
    stats.note_factor_event(tiny_pivots=int(getattr(src, "tiny_pivots",
                                                    0)),
                            dtype=options.factor_dtype,
                            mem=mem)
    return lu


def _dist_sweep(lu: LUFactorization, b_factor_order: np.ndarray,
                trans: bool):
    """The mesh sweep under the spans `ops/batched` gives the
    one-device sweep: rhs to the devices → answer on the host."""
    from ..parallel import factor_dist
    with obs.span("solve.sweep", cat="solve",
                  args={"nrhs": (b_factor_order.shape[1]
                                 if b_factor_order.ndim == 2 else 1),
                        "trans": int(trans)}):
        X = factor_dist.dist_solve(lu.device_lu, b_factor_order,
                                   trans=trans)
        with obs.span("solve.fetch", cat="solve"):
            return np.asarray(X)


def _solve_factored(lu: LUFactorization, b_factor_order: np.ndarray):
    """Triangular solves in factor ordering/scaling."""
    if lu.backend == "host":
        return ref_multifrontal.solve_host(lu.host_lu, b_factor_order)
    if lu.backend == "dist":
        return _dist_sweep(lu, b_factor_order, trans=False)
    from ..ops import batched
    return batched.solve_device(lu.device_lu, b_factor_order)


def _solve_factored_trans(lu: LUFactorization, b_factor_order: np.ndarray):
    """Mᵀ·y = b in factor ordering (forward Uᵀ, backward Lᵀ)."""
    if lu.backend == "host":
        return ref_multifrontal.solve_host_trans(lu.host_lu,
                                                 b_factor_order)
    if lu.backend == "dist":
        return _dist_sweep(lu, b_factor_order, trans=True)
    from ..ops import batched
    return batched.solve_device_trans(lu.device_lu, b_factor_order)


def solve(lu: LUFactorization, b: np.ndarray,
          stats: Stats | None = None) -> np.ndarray:
    """Solve A·x = b for one or many right-hand sides (b: (n,) or
    (n, nrhs)).  Applies scalings/permutations, the factored solves,
    and iterative refinement per options (pdgstrs + pdgsrfs analog,
    SRC/pdgstrs.c:1035, SRC/pdgsrfs.c:124)."""
    plan = lu.plan
    stats = stats or lu.stats or Stats()
    options = lu.effective_options
    b = np.asarray(b)
    if b.shape[0] != plan.n:
        raise ValueError(
            f"b has {b.shape[0]} rows but the matrix is {plan.n}×{plan.n}")
    squeeze = b.ndim == 1
    bb = b[:, None] if squeeze else b
    if options.solve_dtype is not None:
        # PrecisionPolicy.solve_dtype: an explicit pin that downcasts
        # the CLIENT's buffer (an fp32 service pipeline stays fp32
        # end to end: residual and answer are then to the rounded b).
        # It is not the sweeps' operand dtype, which follows the
        # factors below whatever is pinned here.  Realness is the
        # system's, precision is the policy's.
        sdt = np.dtype(options.solve_dtype)
        if np.issubdtype(bb.dtype, np.complexfloating):
            sdt = np.promote_types(sdt, np.complex64)
        bb = bb.astype(sdt)

    if options.trans == Trans.CONJ:
        # (Aᴴ)⁻¹·b = conj((Aᵀ)⁻¹·conj(b)) — run the TRANS pipeline
        # (refinement included) on the conjugated system
        merged = options.replace(trans=Trans.TRANS)
        # the replace copy shares refine_cache, so operands the inner
        # solve builds are kept for the FACTORED rung automatically
        lu_t = dataclasses.replace(lu, options=merged)
        x = solve(lu_t, np.conj(bb), stats=stats)
        x = np.conj(x)
        return x[:, 0] if squeeze else x

    if options.trans == Trans.NOTRANS:
        # M = Pf_r·Dr·A·Dc·Pf_cᵀ:  b' = Pf_r·Dr·b ; x = Dc·Pf_cᵀ·y
        def to_factor_rhs(v):
            scaled = v * plan.row_scale[:, None]
            out = np.empty_like(scaled)
            out[plan.final_row] = scaled
            return out

        def from_factor_sol(y):
            out = y[plan.final_col]
            return out * plan.col_scale[:, None]

        solver = _solve_factored
    else:
        # Aᵀ = Dr⁻¹... algebra: (Aᵀ)⁻¹ = Dr·Pf_rᵀ·M⁻ᵀ·Pf_c·Dc, so the
        # roles of (row perm, row scale) and (col perm, col scale) swap
        # around the Mᵀ solve (the pdgssvx TRANS contract)
        def to_factor_rhs(v):
            scaled = v * plan.col_scale[:, None]
            out = np.empty_like(scaled)
            out[plan.final_col] = scaled
            return out

        def from_factor_sol(y):
            out = y[plan.final_row]
            return out * plan.row_scale[:, None]

        solver = _solve_factored_trans

    from ..precision.policy import sweep_operand_dtype
    from ..utils.platform import complex_device_gate
    factor_dt = np.dtype(lu.effective_options.factor_dtype)
    sweeps: dict = {}       # this solve's sweeps by operand dtype
    # a handle keeps the storage it was made with: pair-stored factors
    # sweep on the default backend (all-real programs), natively
    # stored ones cannot take the pair lowering and are gated on a TPU
    stored = "native"
    sweep_segments = None   # programs a sweep dispatches
    sweep_mesh = {}         # on a mesh: which program, its all-reduces
    if lu.backend in ("jax", "dist"):
        from ..ops.batched import _lu_is_pair, sweep_programs
        stored = "pair" if _lu_is_pair(lu.device_lu) else "native"
    if lu.backend == "jax":
        sweep_segments = sweep_programs(lu.device_lu)
    elif lu.backend == "dist":
        from ..parallel import factor_dist
        arm = factor_dist.solve_arm(lu.device_lu, bb.shape[1])
        sweep_segments = 1
        sweep_mesh = {"sweep_arm": arm,
                      "sweep_syncs": factor_dist.solve_syncs(
                          lu.device_lu, arm)}
    if sweep_segments is not None:
        stats.dispatch.update(getattr(lu.device_lu, "route", None) or {},
                              sweep_segments=sweep_segments,
                              **sweep_mesh)

    def sweep(lu_, v):
        # every triangular sweep — x0's and each refinement
        # correction's — takes its operand in the FACTOR's precision
        # (psgsrfs_d2: residual in double, correction in single; the
        # one rule the fused device loop shares).  The cast comes
        # AFTER to_factor_rhs, so scaling and permutation run in the
        # caller's / the refine dtype, and the residual, berr and
        # x += δ stay there against the unrounded b.
        op = v.astype(sweep_operand_dtype(factor_dt, v.dtype),
                      copy=False)
        for count in (sweeps, stats.sweeps):
            count[op.dtype.name] = count.get(op.dtype.name, 0) + 1
        return solver(lu_, op)

    with complex_device_gate(factor_dt, bb.dtype,
                             pair_capable=(stored == "pair"),
                             stats=stats, phase="SOLVE",
                             mesh=getattr(lu.device_lu, "mesh", None)):
        obs.take_cost("pack")   # drop any stale unread stamp
        with stats.timer("SOLVE"):
            x = from_factor_sol(sweep(lu, to_factor_rhs(bb)))
        # a handle that came without its packs took the miss in the
        # sweep above (ops/trisolve.get_packs); every later sweep hits
        pack = obs.take_cost("pack")
        if pack:
            stats.note_pack(pack)
            obs.HEALTH.record_pack(lu.factor_record, pack)

        if options.iter_refine != IterRefine.NOREFINE and lu.a is not None:
            from .refine import iterative_refine
            with stats.timer("REFINE"):
                x, berr, steps, stalled = iterative_refine(
                    lu, bb, x, sweep, to_factor_rhs, from_factor_sol,
                    trans=(options.trans == Trans.TRANS),
                    sweeps=sweeps,
                    lowering=stats.complex_lowering.get("SOLVE"),
                    sweep_segments=sweep_segments,
                    sweep_mesh=sweep_mesh)
            stats.berr = berr
            stats.refine_steps += steps
            stats.refine_stalled = stalled

    return x[:, 0] if squeeze else x


def perm_scale_vectors(plan: FactorPlan, trans: Trans):
    """The four vectors of solve()'s embedding algebra for one trans
    lane, as plain numpy arrays: (in_scale, in_perm, out_perm,
    out_scale) such that

        x = out_scale · y[out_perm],   y = M_solve( (in_scale · b)[in_perm] )

    with M = Pf_r·Dr·A·Dc·Pf_cᵀ (NOTRANS) or its transpose swap
    (TRANS; CONJ callers conjugate around the TRANS lane).  `in_perm`
    is the argsort inverse of the scatter solve() uses
    (`out[final_row] = scaled` ⇔ `out = scaled[argsort(final_row)]`),
    which is what makes the same algebra expressible as pure gathers
    inside a jax trace — the autodiff fwd/adjoint legs
    (superlu_dist_tpu/autodiff/solve.py) are built on exactly this."""
    if trans == Trans.TRANS:
        return (plan.col_scale, np.argsort(plan.final_col),
                plan.final_row, plan.row_scale)
    if trans == Trans.CONJ:
        raise ValueError("CONJ has no direct embedding lane; "
                         "conjugate around TRANS (see solve())")
    return (plan.row_scale, np.argsort(plan.final_row),
            plan.final_col, plan.col_scale)


def solve_rhs_dtype(lu: LUFactorization) -> np.dtype:
    """The HOST-side dtype of a solve fed plain float64 right-hand
    sides: the one dtype the serve micro-batcher assembles a batch in
    and warm_solve's zero block has (what a float64 RHS promotes to
    against the factors; an explicit Options.solve_dtype, the pin
    that downcasts client buffers, replaces the float64).  It is NOT
    the compiled sweep program's operand dtype: solve() casts every
    sweep's operand to the factor's precision
    (precision/policy.sweep_operand_dtype) and keeps residual and
    answer in the refine dtype against the batch as assembled here —
    casting the batch itself would answer a rounded b."""
    opts = lu.effective_options
    rhs = (np.dtype(opts.solve_dtype) if opts.solve_dtype is not None
           else np.dtype(np.float64))
    return np.promote_types(np.dtype(opts.factor_dtype), rhs)


def warm_solve(lu: LUFactorization, nrhs_widths=(1,),
               dtype=None) -> None:
    """Pre-compile the jitted solve programs for the given RHS widths
    with zero solves (a zero RHS is exact under the sweeps, and a
    (n, k) zero block traces the identical program live traffic
    uses).  Standalone users' analog of the serve micro-batcher's
    warmup (serve/batcher.py), which applies the same
    solve_rhs_dtype rule through its per-variant solve_fn."""
    dt = np.dtype(dtype) if dtype is not None else solve_rhs_dtype(lu)
    for k in nrhs_widths:
        solve(lu, np.zeros((lu.n, int(k)), dtype=dt))


def get_diag_u(lu: LUFactorization) -> np.ndarray:
    """Diagonal of U in FACTOR column order (pdGetDiagU analog,
    SRC/pdGetDiagU.c).  diag(U)[final_col[j]] is original column j's
    pivot."""
    plan = lu.plan
    fp = plan.frontal
    xsup = fp.sym.part.xsup
    out = np.empty(plan.n, dtype=np.dtype(
        lu.effective_options.factor_dtype))
    if lu.backend == "host":
        for s in range(fp.nsuper):
            w = int(fp.w[s])
            hu = lu.host_lu.U[s]
            out[int(xsup[s]):int(xsup[s]) + w] = np.diagonal(hu[:w, :w])
        return out
    sched = lu.device_lu.schedule

    def _gather_decode(flat, idx):
        # device-side gather of just the diagonal entries: only O(n)
        # scalars cross to the host, never the full U slab (the
        # tracing-gated health.pivot_growth hook calls this per
        # factorization, so the slab transfer would be real money).
        # Pair-stored factors ((2, N) real planes) decode to complex
        # after the gather.
        import jax.numpy as jnp
        flat = jnp.asarray(flat)
        if flat.ndim == 2:
            picked = np.asarray(jnp.take(flat, idx, axis=1))
            return picked[0] + 1j * picked[1]
        return np.asarray(jnp.take(flat, idx))

    def _diag_idx(groups, base_of):
        # flat indices of diag(U) + their destination columns; a
        # (wb, mb) row-major panel's diagonal is base + i*(mb+1)
        idx, dst = [], []
        for g in groups:
            for bg, s in zip(g.sup_pos, g.sup_ids):
                w = int(fp.w[s])
                base = base_of(g, int(bg))
                idx.append(base + np.arange(w) * (g.mb + 1))
                dst.append(int(xsup[s]) + np.arange(w))
        return (np.concatenate(idx) if idx else np.empty(0, np.int64),
                np.concatenate(dst) if dst else np.empty(0, np.int64))

    panels = getattr(lu.device_lu, "panels", None)
    if panels is not None:
        # staged factors: per-group local U flats, offset 0
        # (staged is single-device, so bg is the local block index)
        for g, p in zip(sched.groups, panels):
            idx, dst = _diag_idx([g], lambda g, b: b * g.wb * g.mb)
            if idx.size:
                out[dst] = _gather_decode(p[1], idx)
        return out
    U_flat = lu.device_lu.U_flat
    # dist flats are the ndev-concatenated device-major slabs; the
    # single-device case is ndev=1 of the same layout
    n_elems = (U_flat.shape[1] if getattr(U_flat, "ndim", 1) == 2
               else U_flat.size)
    U_total = n_elems // sched.ndev

    def _base(g, bg):
        d, b = divmod(bg, g.n_loc)
        return d * U_total + g.U_off + b * g.wb * g.mb

    idx, dst = _diag_idx(sched.groups, _base)
    if idx.size:
        out[dst] = _gather_decode(U_flat, idx)
    return out


def factor_arrays(lu: LUFactorization) -> list:
    """The numeric factor payload as HOST arrays in a deterministic
    order — the ABFT-lite surface the resilience layer checksums,
    validates and persists (resilience/store.py).  Host panels come
    back as the live numpy objects; device flats cross to the host
    (an O(factor bytes) transfer — callers are the once-per-
    factorization save/validate paths, never a solve).  The dist
    backend's factors are mesh-bound and raise."""
    if lu.backend == "host":
        h = lu.host_lu
        return [np.asarray(p)
                for side in (h.L, h.U, h.Linv, h.Uinv) for p in side]
    if lu.backend == "dist":
        raise ValueError(
            "dist-backend factors are sharded over a live mesh and "
            "have no host-array form; persist the single-device "
            "factorization instead")
    d = lu.device_lu
    if hasattr(d, "panels"):          # StagedLU: per-group local flats
        return [np.asarray(a) for p in d.panels for a in p]
    return [np.asarray(d.L_flat), np.asarray(d.U_flat),
            np.asarray(d.Li_flat), np.asarray(d.Ui_flat)]


def factors_finite(lu: LUFactorization) -> bool:
    """True when every factor entry is finite — the containment gate
    between a factorization and any cache/store/serve surface: a
    NaN/Inf-poisoned factor produces silently-wrong solves under GESP
    (no runtime pivoting to catch it), so the serve layer refuses to
    admit one (serve/factor_cache.py raises FactorPoisoned)."""
    try:
        arrays = factor_arrays(lu)
    except ValueError:
        return True     # mesh-bound factors: nothing to probe here
    return all(bool(np.isfinite(a).all()) for a in arrays)


def query_space(lu: LUFactorization) -> dict:
    """LU storage accounting (dQuerySpace_dist analog,
    SRC/superlu_ddefs.h:616): true nnz(L+U) and the bytes actually
    held (padded slabs on device, unpadded panels on host)."""
    itemsize = np.dtype(lu.effective_options.factor_dtype).itemsize
    nnz = lu.plan.lu_nnz()
    if lu.backend == "host":
        held = sum(p.nbytes for s in (lu.host_lu.L, lu.host_lu.U,
                                      lu.host_lu.Linv, lu.host_lu.Uinv)
                   for p in s)
    else:
        d = lu.device_lu
        if hasattr(d, "held_bytes"):
            held = d.held_bytes()
        else:
            # nbytes counts pair storage ((2, N) real planes, same
            # bytes as N complex) and native storage identically
            held = (d.L_flat.nbytes + d.U_flat.nbytes
                    + d.Li_flat.nbytes + d.Ui_flat.nbytes)
    return {"lu_nnz": nnz, "lu_bytes": nnz * itemsize,
            "held_bytes": int(held)}


def gssvx(options: Options | None, a: CSRMatrix, b: np.ndarray,
          stats: Stats | None = None, backend: str = "auto",
          lu: LUFactorization | None = None,
          user_perm_r: np.ndarray | None = None,
          user_perm_c: np.ndarray | None = None,
          grid=None):
    """One-call driver.  Returns (x, lu, stats).  Pass `lu` with
    options.fact=FACTORED to reuse a prior factorization, or with
    options.fact=SAME_PATTERN* to re-factor new values reusing the
    plan.  user_perm_r/user_perm_c feed RowPerm.MY_PERMR /
    ColPerm.MY_PERMC."""
    options = options or Options()
    stats = stats if stats is not None else Stats()
    # front-door validation (numerics/): a poisoned or malformed
    # system is refused with a typed error BEFORE a factorization
    # burns — until this gate only factor OUTPUT had a finite check
    # (factors_finite), so NaN inputs cost a full factorization to
    # detect.  O(nnz + n·nrhs) host scans, once per driver call.
    _validate_system(a, b)
    # this run's phase stats become the registry's "stats" surface
    # (last-solve-wins — the PStatPrint cardinality); the root span
    # makes every numeric-phase span a CHILD in the exported trace
    obs.REGISTRY.register("stats", stats)
    with obs.span("gssvx", cat="driver",
                  args={"n": a.n, "fact": options.fact.name}):
        return _gssvx_impl(options, a, b, stats, backend, lu,
                           user_perm_r, user_perm_c, grid)


def _validate_system(a, b) -> None:
    """Typed front-door rejection of malformed systems (numerics/
    errors.InvalidInputError — a ValueError, so pre-existing callers
    catching ValueError keep working)."""
    from ..numerics.errors import InvalidInputError
    n = int(getattr(a, "n", 0))
    if n == 0:
        raise InvalidInputError("empty system: A is 0x0")
    b = np.asarray(b)
    if b.ndim not in (1, 2) or b.shape[0] != n:
        raise InvalidInputError(
            f"b has shape {b.shape} but the matrix is {n}x{n}")
    if b.size == 0:
        raise InvalidInputError("empty right-hand side: b has 0 "
                                "columns")
    vals = getattr(a, "data", None)
    if vals is not None and not bool(np.isfinite(vals).all()):
        raise InvalidInputError(
            "non-finite entries in A: a NaN/Inf value would poison "
            "the factors (GESP has no runtime pivoting to catch it); "
            "refused before paying a factorization")
    if not bool(np.isfinite(b).all()):
        raise InvalidInputError("non-finite entries in b")


def _condition_gate(options, a, lu, stats, backend, grid):
    """Eager condition estimation + policy enforcement after a
    factorization (SLU_COND_ESTIMATE=1): estimate rcond off the
    resident factors, refuse numerically singular systems with typed
    SingularMatrixError, and climb the precision ladder one rung
    BEFORE the first serve when the key classifies ill-conditioned —
    precision buys back digits exactly when kappa eats them, and
    paying the rung up-front beats discovering it via a stalled
    refinement later.  Terminates at the ladder ceiling like the berr
    ladder below."""
    from ..numerics.gscon import ensure_rcond
    from ..numerics.policy import ConditionPolicy, cond_estimate_enabled
    if not cond_estimate_enabled():
        return lu
    from ..precision.policy import next_factor_dtype
    policy = ConditionPolicy.from_env()
    while True:
        rcond = ensure_rcond(lu)
        stats.rcond = rcond
        cls = policy.enforce(rcond, options.refine_dtype)
        if (cls != "ill" or options.fact == Fact.FACTORED
                or not options.escalate):
            return lu
        cur = lu.effective_options.factor_dtype
        nxt = next_factor_dtype(cur, ceiling=options.refine_dtype)
        if nxt is None:
            return lu
        stats.escalations += 1
        obs.HEALTH.record_escalation(
            berr=stats.berr, factor_dtype=cur,
            refine_dtype=options.refine_dtype,
            to_dtype=nxt, trigger="ill_conditioned")
        lu = factorize(a, options.replace(factor_dtype=nxt),
                       plan=lu.plan, stats=stats, backend=backend,
                       grid=grid, _phase="FACT_ESC")


def _stamp_result(x, lu, options):
    """Label solutions that rode perturbed or ill-conditioned factors
    (numerics/ledger.PerturbedResult): zero-copy view stamp, applied
    only on the rare dishonest-to-hide paths — a clean
    well-conditioned solve returns a plain ndarray."""
    led = getattr(lu, "ledger", None)
    rcond = getattr(lu, "rcond", None)
    ill = False
    if rcond is not None:
        from ..numerics.policy import ConditionPolicy
        policy = ConditionPolicy.from_env()
        ill = (policy.mode == "stamp"
               and policy.classify(rcond,
                                   options.refine_dtype) == "ill")
    if (led is not None and led.perturbed) or ill:
        from ..numerics.ledger import stamp_perturbed
        return stamp_perturbed(x, ledger=led, rcond=rcond)
    return x


def _gssvx_impl(options, a, b, stats, backend, lu,
                user_perm_r, user_perm_c, grid):
    if options.fact in (Fact.FACTORED, Fact.SAME_PATTERN,
                        Fact.SAME_PATTERN_SAME_ROWPERM) and lu is None:
        raise ValueError(f"options.fact={options.fact.name} requires "
                         "an existing lu")
    if options.fact == Fact.FACTORED and lu is not None:
        # a FACTORED reuse must be consistent with the stored factors:
        # a grid request against a non-dist handle (or a different
        # mesh) would silently be ignored otherwise
        if grid is not None:
            mesh = getattr(grid, "mesh", grid)
            if lu.backend != "dist":
                raise ValueError(
                    "Fact.FACTORED with grid= requires factors from "
                    f"the dist backend; this handle is {lu.backend!r}")
            if lu.device_lu.mesh != mesh:
                raise ValueError(
                    "Fact.FACTORED grid mesh differs from the mesh "
                    "the factors are sharded over")
    if options.fact == Fact.FACTORED:
        # honor the caller's SOLVE-time knobs on the reused handle;
        # factorization-describing knobs (factor_dtype, equil,
        # col_perm, ...) must keep describing the stored factors.
        # The replace copy shares the caller handle's refine_cache
        # container, so operands built here serve later reuses too.
        from ..options import merge_solve_options
        lu = dataclasses.replace(
            lu, options=merge_solve_options(lu.effective_options,
                                            options))
    elif (lu is not None and options.fact == Fact.SAME_PATTERN):
        # reuse only the fill-reducing column permutation (the
        # expensive ordering); recompute equilibration, row perm and
        # the symbolic plan for the new values — the reference's
        # SamePattern semantics (perm_c + etree reuse,
        # SRC/superlu_defs.h:584-588)
        opts2 = options.replace(col_perm=ColPerm.MY_PERMC)
        plan = plan_factorization(a, opts2, stats=stats,
                                  user_perm_c=lu.plan.perm_c)
        lu = factorize(a, opts2, plan=plan, stats=stats, backend=backend,
                       grid=grid)
    elif (lu is not None
          and options.fact == Fact.SAME_PATTERN_SAME_ROWPERM):
        # reuse perms, scalings and the whole symbolic plan; refresh
        # numeric values only
        lu = factorize(a, options, plan=lu.plan, stats=stats,
                       backend=backend, grid=grid)
    else:
        lu = factorize(a, options, stats=stats, backend=backend,
                       user_perm_r=user_perm_r, user_perm_c=user_perm_c,
                       grid=grid)
    # condition gate BEFORE the first solve: refuse numerically
    # singular factors (typed, never a garbage solve) and pre-climb
    # the ladder for ill-conditioned keys under SLU_COND_ESTIMATE=1
    lu = _condition_gate(options, a, lu, stats, backend, grid)
    x = solve(lu, b, stats=stats)
    # Precision-escalation LADDER (precision/policy.py): when a
    # low-precision factor fails its refinement contract
    # (cond(A)·eps_factor ≥ 1: berr stagnates far above the
    # refine-precision class), re-factor at the NEXT rung up —
    # bf16 → fp32 → refine_dtype — instead of jumping straight to the
    # top: on an accelerator the middle rung (fp32 + extended-
    # precision residual) is full-rate MXU arithmetic while the top
    # rung is emulated, and most bf16 failures are rescued one rung
    # up.  This is the safety net the psgssvx_d2 strategy (SURVEY.md
    # §2.6, psgssvx_d2.c:516) leaves to the caller, automatic here
    # because GESP has no mid-factor pivoting to fall back on.  The
    # plan is value-identical across rungs, so it is reused outright;
    # each promotion is a health event labeled with the signal that
    # fired (berr plateau / refine stall / pivot growth / overflow).
    # Terminates: eps(factor) strictly decreases toward the
    # refine_dtype ceiling, where _escalation_core returns False.
    from ..precision.policy import next_factor_dtype
    while True:
        trigger = _escalation_trigger(options, lu, stats)
        if trigger is None:
            break
        cur = lu.effective_options.factor_dtype
        nxt = next_factor_dtype(cur, ceiling=options.refine_dtype)
        if nxt is None:
            break
        stats.escalations += 1
        obs.HEALTH.record_escalation(
            berr=stats.berr, factor_dtype=cur,
            refine_dtype=options.refine_dtype,
            to_dtype=nxt, trigger=trigger)
        opts2 = options.replace(factor_dtype=nxt)
        # the rerun reports under FACT_ESC so FACT's GFLOP/s never
        # blends two differently-precisioned factorizations
        lu = factorize(a, opts2, plan=lu.plan, stats=stats,
                       backend=backend, grid=grid, _phase="FACT_ESC")
        x = solve(lu, b, stats=stats)
    # re-gate after any berr-driven escalation: the rcond of the
    # ESCALATED handle is the one the policy (and the stamp) must
    # describe; free when no escalation ran (rcond already cached)
    lu2 = _condition_gate(options, a, lu, stats, backend, grid)
    if lu2 is not lu:
        lu = lu2
        x = solve(lu, b, stats=stats)
    return _stamp_result(x, lu, options), lu, stats


def _escalation_trigger(options: Options, lu: LUFactorization,
                        stats: Stats):
    """None when the refinement contract held; otherwise the
    health-signal label (precision/policy.classify_trigger) justifying
    one ladder rung up.  The pivot-growth probe walks diag(U) to the
    host (O(n) + a transfer) — paid only once the berr gate has
    already decided to escalate, never on the happy path."""
    if not _should_escalate(options, lu, stats):
        return None
    import jax.numpy as jnp
    from ..precision.policy import classify_trigger
    f_eps = float(jnp.finfo(jnp.dtype(
        lu.effective_options.factor_dtype)).eps)
    return classify_trigger(stats.berr,
                            stalled=stats.refine_stalled,
                            pivot_growth=obs.pivot_growth(lu),
                            factor_eps=f_eps)


def _should_escalate(options: Options, lu: LUFactorization,
                     stats: Stats) -> bool:
    if options.fact == Fact.FACTORED:
        # solve-only rung: never silently re-pay a factorization on a
        # reused handle (and the escalated handle would be discarded
        # by a caller looping over their original lu anyway)
        return False
    # the dtype of the factors actually used, not the caller's field
    # (they differ on reuse rungs)
    return _escalation_core(options,
                            lu.effective_options.factor_dtype, stats)


def _should_escalate_fused(options: Options, stats: Stats) -> bool:
    """Escalation test for the fused one-program path (pddrive
    --fused), which always factors fresh at options.factor_dtype."""
    return _escalation_core(options, options.factor_dtype, stats)


# refinement-contract class boundary: converged means berr within a
# few bits of eps(refine_dtype) — the reference's pdgsrfs stops at
# berr ≈ eps (SRC/pdgsrfs.c:124) and refine.py's own loop runs until
# berr ≤ eps or the gain stalls, so a healthy factor lands at
# eps-class and a stalled one sits ORDERS above it.  64 = 6 bits of
# slack for slow-but-genuine convergence (berr is a max over
# components; rounding noise scales with row density).  The round-3
# sqrt(r_eps) gate (~1.5e-8 for f64) wrongly classified factors
# stalling at 1e-8..1e-13 as converged; those are exactly the
# cond·eps_f32 ≈ 1 marginal cases an f64 refactor rescues.
_ESC_BERR_SLACK = 64.0


def _escalation_core(options: Options, factor_dtype: str,
                     stats: Stats) -> bool:
    if not options.escalate:
        return False
    if options.iter_refine == IterRefine.NOREFINE:
        return False
    import jax.numpy as jnp   # jnp.finfo understands bfloat16
    f_eps = float(jnp.finfo(jnp.dtype(factor_dtype)).eps)
    r_eps = float(jnp.finfo(jnp.dtype(options.refine_dtype)).eps)
    if f_eps <= r_eps:            # nothing higher to escalate to
        return False
    # NaN/Inf berr (overflowed low-precision factor) must escalate —
    # write the test as "not converged" so non-finite falls through
    return not (stats.berr <= _ESC_BERR_SLACK * r_eps)
