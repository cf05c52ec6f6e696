"""Distributed level-synchronous multifrontal factorization + solve.

The TPU-native re-design of the reference's distributed numeric phase:
where pdgstrf (SRC/pdgstrf.c:1108) drives 2D block-cyclic panels with
MPI point-to-point and pdgstrf3d (SRC/pdgstrf3d.c:292) adds Z-axis
subtree replication with pairwise ancestor reductions
(dreduceAncestors3d, SRC/pd3dcomm.c:704), this build shards every
elimination-tree level's bucketed front batch across the mesh and
expresses the cross-process dataflow as XLA collectives inside ONE
compiled program:

  * front batches: block-partitioned over the mesh axes
    (ops/batched.build_schedule(plan, ndev) — the same builder as the
    single-device path, so the oracle and the distributed path cannot
    diverge);
  * Schur/update propagation: `all_gather` of the level's update slab
    (device-major contiguous layout makes the gather exactly the
    reference's gather of ancestor contributions);
  * triangular solve sweeps: device-local updates reconciled by a
    psum-of-diffs only at static sync points — groups whose fronts
    have cross-device descendants (forward) or ancestors (backward).
    Zone-affine subtree interiors sweep with ZERO collectives (the
    C_Tree bcast/reduce forest of pdgstrs, SRC/pdgstrs.c:2133,
    collapsed to one reduction per zone boundary);
  * factor panels stay device-resident and device-sharded (the
    dLocalLU_t distribution, SRC/superlu_ddefs.h:97-263) — `DistLU`
    persists them across solves, the distributed FACTORED rung.

The per-group bodies are literally ops.batched's `_factor_group_impl` /
`_fwd_group_impl` / `_bwd_group_impl` with a mesh axis — one
implementation serves all execution modes by construction, and the
`_factor_loop`/`_solve_loop` helpers below are the single source of
the group iteration shared by the fused step and the split
factor/solve pair.

Everything is shard_map'd over the mesh, so the same program runs on 1
device (degenerate), an 8-device CPU mesh (tests), or a TPU pod slice
(ICI collectives).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs
from ..plan.plan import FactorPlan
from ..utils.compat import shard_map as _shard_map
from ..ops.batched import (_bwd_group_impl, _bwd_group_T_impl, _dec,
                           _enc, _factor_group_impl,
                           _flat_axis_index, _fwd_group_impl,
                           _fwd_group_T_impl, _hi_prec, _lu_is_pair,
                           _pair_decode_sol, _pair_encode_rhs,
                           _pair_encode_vals, _real_dtype,
                           _solve_view, _thresh_for, get_schedule,
                           psum_exact)
from ..utils.platform import complex_lowering


def _resolve_axis(mesh: Mesh, axis):
    if axis is None:
        axis = tuple(mesh.axis_names)
    if isinstance(axis, (list, tuple)):
        axis = tuple(axis)
        ndev = int(np.prod([mesh.shape[a] for a in axis]))
    else:
        ndev = mesh.shape[axis]
    return axis, ndev


def _regroup(dsched, idx_flat, per):
    """Flat shard_map operand list -> per-group tuples, leading
    device-block dim stripped.  Items may be pytrees (the ea-block
    tuples), hence the tree_map."""
    it = iter(idx_flat)
    return [tuple(jax.tree_util.tree_map(lambda a: a[0], next(it))
                  for _ in range(per))
            for _ in dsched.groups]


@_hi_prec
def _factor_loop(dsched, vals, thresh_np, dtype, per_group, axis,
                 pair: bool = False):
    """Shared factorization group loop (runs inside shard_map).  In
    pair mode (complex on stacked real/imag planes,
    batched._factor_group_impl_pair) `vals` arrives host-encoded as
    (2, nnz) real planes and every slab carries the leading plane
    axis — the compiled program contains no complex ops."""
    rdt = _real_dtype(dtype)
    thresh = jnp.asarray(thresh_np, dtype=rdt)
    if pair:
        sdt, lead = rdt, (2,)
        vals = jnp.concatenate(
            [vals.astype(rdt), jnp.zeros((2, 1), rdt)], axis=1)
    else:
        sdt, lead = dtype, ()
        vals = jnp.concatenate([vals.astype(dtype),
                                jnp.zeros(1, dtype)])
    upd_buf = jnp.zeros(lead + (dsched.upd_total + dsched.upd_pad,),
                        sdt)
    L_flat = jnp.zeros(lead + (dsched.L_total,), sdt)
    U_flat = jnp.zeros(lead + (dsched.U_total,), sdt)
    Li_flat = jnp.zeros(lead + (dsched.Li_total,), sdt)
    Ui_flat = jnp.zeros(lead + (dsched.Ui_total,), sdt)
    tiny = jnp.zeros((), jnp.int32)
    nzero = jnp.zeros((), jnp.int32)
    for g, idx in zip(dsched.groups, per_group):
        a_src, a_dst, one_dst, ea_blocks, pos_idx = idx[:5]
        (upd_buf, L_flat, U_flat, Li_flat, Ui_flat, tiny,
         nzero) = _factor_group_impl(
            vals, upd_buf, L_flat, U_flat, Li_flat, Ui_flat, tiny,
            nzero, thresh, a_src, a_dst, one_dst, ea_blocks,
            jnp.int32(g.upd_off_global), jnp.int32(g.L_off),
            jnp.int32(g.U_off), jnp.int32(g.Li_off),
            jnp.int32(g.Ui_off), mb=g.mb, wb=g.wb, n_pad=g.n_loc,
            ea_meta=g.ea_meta, eb_meta=g.eb_meta,
            axis=axis, gather=g.needs_gather, coop=g.coop,
            ndev=dsched.ndev, pos_idx=pos_idx, cp=g.cp, tp=g.tp,
            pair=pair)
    return (L_flat, U_flat, Li_flat, Ui_flat, tiny, nzero)


@_hi_prec
def _solve_loop(dsched, flats, b, dtype, per_group, axis,
                trans: bool, pair: bool = False):
    """Shared triangular-sweep loop (runs inside shard_map).
    `per_group` entries are (col_idx, struct_idx) pairs.

    Axis mode runs every group's updates DEVICE-LOCALLY (the impls'
    axis=None branch) and reconciles X by one psum-of-diffs only at
    the schedule's static sync points (GroupSpec.fwd_sync/bwd_sync):
    zone-affine subtree interiors sweep with zero collectives, the
    pdgstrs C_Tree forest (SRC/pdgstrs.c:2133) collapsed to one
    reduction per zone boundary."""
    # complex factors sweep on stacked real/imag planes
    # (batched._solve_view): the SWEEP BODY — per-group panel
    # dynamic-slice, extraction, einsum — becomes complex-free; the
    # one-time whole-array real/imag extraction remains in the
    # program prologue.  Complex per-panel slicing is where XLA:CPU's
    # threaded runtime raced (rare nondeterministic NaN, caught by
    # tests/test_coop.py::test_complex_dist_solve_deterministic).
    # Follow-up if the prologue ever misbehaves or the O(nnz) restack
    # per solve shows up in profiles: materialize this storage once
    # at factor time in DistLU.
    L_flat, U_flat, Li_flat, Ui_flat = (
        _solve_view(f) for f in flats)

    # merged trisolve arm (ops/trisolve.py, SLU_TRISOLVE): the
    # single-device sweep re-expressed over the lsum gather/update
    # layout — packed panels, dense update buffers, no scatters,
    # the same arithmetic in the same order.  The packing slices here are
    # loop-invariant inside the fused solvers' refinement while_loop,
    # so XLA hoists them and the repeated sweeps pay only the lsum
    # dataflow.  Axis mode below is the replicated-X psum sweep: what
    # make_dist_step's fused program and make_dist_solve run.  A
    # mesh handle's narrow-rhs sweep is make_dist_solve_merged, not
    # this loop.
    if axis is None:
        from ..ops import trisolve
        if trisolve.trisolve_mode() == "merged":
            ts = trisolve.get_trisolve(dsched)
            packs = trisolve.pack_panels(
                ts, (L_flat, U_flat, Li_flat, Ui_flat))
            return trisolve.sweep(ts, packs, b, dtype, trans,
                                  pair=pair)
    n = dsched.n
    if pair:
        # pair-stored factors: flats are already (2, N) planes and b
        # arrives real-view encoded (n, 2R) from the host — the whole
        # program is complex-free, including the prologue/epilogue
        # (on the gated platform even the one-time extraction would
        # reintroduce the broken lowering)
        cplx = True
        X = jnp.zeros((n + 1, b.shape[1]), b.dtype)
        X = X.at[:n, :].set(b)
    else:
        xdt = jnp.promote_types(dtype, b.dtype)
        cplx = bool(jnp.issubdtype(xdt, jnp.complexfloating))
        X = jnp.zeros((n + 1, b.shape[1]), xdt)
        X = X.at[:n, :].set(b.astype(xdt))
        # complex systems sweep on the real-view storage (see the
        # codec note at batched._dec): gathers/scatters/psums stay
        # real
        X = _enc(X, cplx)
    Xs = X                       # last reconciled snapshot (axis mode)

    @jax.named_scope("slu.lsum")
    def sync(X, Xs):
        Xn = Xs + jax.lax.psum(X - Xs, axis)
        return Xn, Xn

    if not trans:
        fwd_fn, fwd_flats = _fwd_group_impl, (L_flat, Li_flat)
        bwd_fn, bwd_flats = _bwd_group_impl, (U_flat, Ui_flat)
        fwd_offs = lambda g: (jnp.int32(g.L_off), jnp.int32(g.Li_off))
        bwd_offs = lambda g: (jnp.int32(g.U_off), jnp.int32(g.Ui_off))
    else:
        fwd_fn, fwd_flats = _fwd_group_T_impl, (U_flat, Ui_flat)
        bwd_fn, bwd_flats = _bwd_group_T_impl, (L_flat, Li_flat)
        fwd_offs = lambda g: (jnp.int32(g.U_off), jnp.int32(g.Ui_off))
        bwd_offs = lambda g: (jnp.int32(g.L_off), jnp.int32(g.Li_off))

    for g, (ci, si) in zip(dsched.groups, per_group):
        if axis is not None and g.fwd_sync:
            X, Xs = sync(X, Xs)
        X = fwd_fn(X, *fwd_flats, ci, si, *fwd_offs(g),
                   mb=g.mb, wb=g.wb, n_pad=g.n_loc, cplx=cplx)
    if axis is not None:
        X, Xs = sync(X, Xs)      # complete forward solution
    for g, (ci, si) in zip(reversed(dsched.groups),
                           reversed(per_group)):
        if axis is not None and g.bwd_sync:
            X, Xs = sync(X, Xs)
        X = bwd_fn(X, *bwd_flats, ci, si, *bwd_offs(g),
                   mb=g.mb, wb=g.wb, n_pad=g.n_loc, cplx=cplx)
    if axis is not None:
        X, _ = sync(X, Xs)       # replicate the final solution
    if pair:
        return X[:n]             # still encoded; host decodes
    return _dec(X, cplx)[:n]


def _group_operands(dsched, fields):
    """Once-only thunk of the flat operand tuple for the given
    GroupSpec.dev positions.  The builders call it inside their
    programs' traces: the operands are constants of the trace, and a
    program served from the exported store (resilience/aot.py)
    uploads none of them."""
    return functools.cache(lambda: tuple(
        t[i] for t in (g.dev(squeeze=False) for g in dsched.groups)
        for i in fields))


def _vals_partition(dsched, nnz):
    """Distributed numeric input (the NRformat_loc contract,
    supermatrix.h:176-188): each device receives only the slice of A's
    values its own groups assemble, not the whole array.  Every
    original entry is extend-added into exactly one front, so the
    per-device reference sets are disjoint except for replicated coop
    fronts — total shipped ≈ nnz + coop shares, vs nnz × ndev for the
    replicated input this replaces (the round-3 `in_specs=(P(),)`
    ceiling; pddistribute.c:66 dReDistribute_A is the reference's
    equivalent one-time redistribution).

    Returns (sel, a_src_loc): `sel` (ndev, Lsel) global value indices
    per device (pad slots repeat index 0 — never referenced), and per
    group the (ndev, La) remap of its a_src into the device-local
    slice, sentinel → Lsel (the appended zero slot, matching
    _factor_loop's `concatenate([vals, 0])`)."""
    ndev = dsched.ndev
    refs = [[] for _ in range(ndev)]
    for g in dsched.groups:
        a = np.asarray(g.a_src)
        for d in range(ndev):
            v = a[d].ravel()
            refs[d].append(v[v < nnz])
    sels = [np.unique(np.concatenate(r)) if r else
            np.zeros(0, np.int64) for r in refs]
    lsel = max(max((s.size for s in sels), default=0), 1)
    sel = np.zeros((ndev, lsel), dtype=np.int64)
    for d, s in enumerate(sels):
        sel[d, :s.size] = s
    sdt = np.int32 if lsel < 2**31 - 1 else np.int64
    a_src_loc = []
    for g in dsched.groups:
        a = np.asarray(g.a_src)
        out = np.full(a.shape, lsel, dtype=sdt)
        for d in range(ndev):
            v = a[d]
            m = v < nnz
            out[d][m] = np.searchsorted(sels[d], v[m])
        a_src_loc.append(out)
    return sel, a_src_loc


def _factor_operands(plan, dsched, per, sharded_in):
    """(sel, idx_args) for a factor-group loop: group operand
    positions 0..per-1 as a once-only thunk (`_group_operands`).
    With per-device value slices (`sharded_in`) position 0 (a_src) is
    replaced by its local-slice remap and `sel` is the slices' global
    indices, which every call needs on the host; else `sel` is None."""
    if not sharded_in:
        return None, _group_operands(dsched, range(per))
    sel, a_src_loc = _vals_partition(dsched, len(plan.coo_rows))

    def build():
        with jax.ensure_compile_time_eval():    # called under a trace
            a_src = [jnp.asarray(a) for a in a_src_loc]
        group_idx = (g.dev(squeeze=False, with_a_src=False)
                     for g in dsched.groups)
        return tuple(
            a_src[gi] if i == 0 else t[i]
            for gi, t in enumerate(group_idx) for i in range(per))

    return sel, functools.cache(build)


# Which numeric input a mesh program takes.  Real systems and
# PAIR-lowered complex systems (real/imaginary planes, what complex
# takes on a TPU mesh: utils/platform.complex_lowering) get the
# sharded input: each device its own slice of the values, a pair
# program both planes of it.  NATIVE complex (an XLA:CPU mesh without
# the tests' hook) keeps the ROUND-3 replicated-vals program shape:
# the XLA:CPU forced-multi-device client's per-process complex
# miscompile lottery (lottery_util docstring) turned out to be acutely
# sensitive to the assembly program's shape — measured per-draw clean
# rates on the coop-complex body: replicated vals 4/5 (the documented
# ~1-in-5 loss), sharded complex operands 2/5, sharded real/imag-plane
# operands under complex arithmetic 0/6.  Every variation re-rolls
# unknown odds, so the policy is: pin the best-measured shape for
# native complex on this client.  A pair program holds no complex
# operation at all, is outside that lottery like every all-real
# program (which has never drawn a loss), and shards as the real path
# does.


def _pair(dtype, mesh) -> bool:
    """Complex in pair storage on this mesh?  THE rule's answer
    (utils/platform.complex_lowering), judged on the mesh's devices."""
    return complex_lowering(dtype, mesh) == "pair"


def _shard_vals(dtype, pair: bool = False) -> bool:
    return pair or np.dtype(dtype).kind != "c"


def _pair_spec(axis, pair: bool):
    """A flat's PartitionSpec: the element axis shards over the mesh,
    under the leading plane axis of pair storage."""
    return P(None, axis) if pair else P(axis)


def _host_vals(vals, sel, dtype, pair: bool):
    """The host's one-time redistribution of the numeric input
    (dReDistribute_A analog, pddistribute.c:66): each device's slice
    of the values, (ndev, Lsel) — in pair storage both planes of it,
    (ndev, 2, Lsel), encoded here on the host (a complex→real
    extraction inside the program would be a complex operation)."""
    if not pair:
        return np.asarray(vals)[sel]
    with obs.span("pair.encode", cat="fact"):
        return np.moveaxis(_pair_encode_vals(vals, dtype)[:, sel], 0, 1)


def encode_rhs(bb: np.ndarray, blocks: int = 1) -> np.ndarray:
    """The sweeps' real-view encoding of a complex right-hand side, on
    the host (`ops/batched._pair_encode_rhs`: real and imaginary
    halves side by side along the rhs axis, (n, R) -> (n, 2R)).  With
    `blocks` > 1 (the rhs-sharded sweep, R a multiple of it) each of
    the `blocks` column slices is encoded by itself, so that a
    device's slice of the columns holds both halves of its own
    right-hand sides."""
    n = bb.shape[0]
    return _pair_encode_rhs(bb.reshape(n, blocks, -1)).reshape(n, -1)


def decode_sol(X: np.ndarray, xdt, blocks: int = 1) -> np.ndarray:
    """Invert `encode_rhs` on the solved X (host side)."""
    n = X.shape[0]
    return _pair_decode_sol(X.reshape(n, blocks, -1), xdt).reshape(n, -1)


def _aot_wrap_dist(name: str, jfn, dsched, mesh, axis, dtype,
                   trans: bool, pair: bool = False):
    """AOT-wrap a shard_map'd dist solve program (resilience/aot.py,
    ISSUE 17) — fingerprint carries the mesh legs (shape + axis +
    device kinds) on top of the schedule layout, so a cold process
    deserializes the export only for the IDENTICAL mesh and refuses
    typed (AotMismatch) otherwise.  Natively complex lanes are never
    wrapped (the platform-gate note at batched._phase_fns); a pair
    program is all-real and is wrapped like a real one.  An
    unexportable shard_map falls back to the plain jit inside
    AotJit."""
    if np.dtype(dtype).kind == "c" and not pair:
        return jfn
    from ..resilience import aot
    return aot.wrap_jit(
        name, jfn,
        aot.schedule_fingerprint(
            dsched, dtype,
            extra=(name, bool(trans), bool(pair))
            + aot.mesh_fingerprint_legs(mesh, axis)))


def make_dist_step(plan: FactorPlan, mesh: Mesh, dtype=np.float64,
                   axis=None):
    """Build the fused distributed factor+solve step:
    `step(vals, b) -> x`, shard_map'd over `mesh` and jitted as one
    program.  `axis` is a mesh axis name or tuple (default: ALL axes —
    the 3D (r,c,z) grid flattens onto one front partition).  `vals` in
    plan COO order; `b` (n, nrhs) in factor ordering."""
    axis, ndev = _resolve_axis(mesh, axis)
    dsched = get_schedule(plan, ndev)
    dtype = np.dtype(dtype)
    thresh_np = _thresh_for(plan, dtype)

    pair = _pair(dtype, mesh)
    sharded_in = _shard_vals(dtype, pair)
    sel, idx_args = _factor_operands(plan, dsched, 7, sharded_in)
    vspec = P(axis) if sharded_in else P()
    idx_specs = (P(axis),) * (7 * len(dsched.groups))

    def body(vals, b, *idx_flat):
        per_group = _regroup(dsched, idx_flat, 7)
        flats = _factor_loop(dsched,
                             vals[0] if sharded_in else vals,
                             thresh_np, dtype, per_group, axis,
                             pair=pair)[:4]
        solve_idx = [(t[5], t[6]) for t in per_group]
        return _solve_loop(dsched, flats, b, dtype, solve_idx, axis,
                           trans=False, pair=pair)

    mapped = _shard_map(
        body, mesh=mesh, in_specs=(vspec, P()) + idx_specs,
        out_specs=P(), check_vma=False)

    jitted = obs.watch_jit(
        "dist_step",
        jax.jit(lambda vsel, b: mapped(vsel, b, *idx_args())))
    vshard = jax.sharding.NamedSharding(mesh, P(axis))

    def step(vals, b):
        # host-side one-time redistribution (dReDistribute_A analog):
        # each device's jit operand is its own value slice, committed
        # to its shard — never the whole array.  Native complex keeps
        # the replicated round-3 shape (_shard_vals note); in pair
        # storage the host encodes values and right-hand side and
        # decodes the answer, which then comes back as a host array.
        if not sharded_in:
            return jitted(jnp.asarray(vals), b)
        vv = jax.device_put(_host_vals(vals, sel, dtype, pair), vshard)
        if not pair:
            return jitted(vv, b)
        bb = np.asarray(b)
        xdt = np.promote_types(dtype, bb.dtype)
        return decode_sol(np.asarray(jitted(vv, encode_rhs(
            bb.astype(xdt)))), xdt)

    step.jitted = jitted
    step.sel = sel
    return step, dsched


# --------------------------------------------------------------------
# split factor / solve: persistent device-sharded factors — the
# distributed FACTORED reuse rung (LUstruct persisting across pdgstrs
# calls, SRC/superlu_defs.h:577-598)
# --------------------------------------------------------------------

@dataclasses.dataclass
class DistLU:
    """Factor slabs sharded over the mesh (dLocalLU_t analog: each
    device holds its front partition's panels; flats are the
    ndev-concatenated global arrays, device-major).  In pair storage
    (complex on a TPU mesh) each flat is (2, ndev * total) real
    planes, the element axis sharded the same way."""
    plan: FactorPlan
    mesh: Mesh
    axis: object
    dtype: np.dtype
    schedule: object       # ops.batched.BatchedSchedule for ndev
    L_flat: jnp.ndarray    # (ndev * L_total_local,), sharded on axis
    U_flat: jnp.ndarray
    Li_flat: jnp.ndarray
    Ui_flat: jnp.ndarray
    tiny_pivots: int
    # what the mesh factorization ran on, for `Stats.dispatch` and the
    # health ring's `last_factor` (`_mesh_route`)
    route: dict | None = None


def _mesh_route(dsched, dtype) -> dict:
    """A mesh factorization's record: the devices it ran on, its
    cooperative tree-top groups, and the schedule's predicted
    collective bytes a factorization and a one-column solve
    (`comm_summary` at the factor dtype's width, which is the planes'
    in pair storage)."""
    return {"devices": int(dsched.ndev),
            "coop_groups": sum(1 for g in dsched.groups if g.coop),
            "comm_bytes": dsched.comm_summary(dtype)}


def make_dist_factor(plan: FactorPlan, mesh: Mesh, dtype=np.float64,
                     axis=None):
    """Build `factor(vals) -> DistLU` with mesh-sharded factor slabs.
    `vals` in plan COO order, already scaled (plan.scaled_values)."""
    axis, ndev = _resolve_axis(mesh, axis)
    dsched = get_schedule(plan, ndev)
    dtype = np.dtype(dtype)
    thresh_np = _thresh_for(plan, dtype)

    pair = _pair(dtype, mesh)
    sharded_in = _shard_vals(dtype, pair)
    sel, idx_args = _factor_operands(plan, dsched, 5, sharded_in)
    vspec = P(axis) if sharded_in else P()
    idx_specs = (P(axis),) * (5 * len(dsched.groups))
    fspec = _pair_spec(axis, pair)

    def body(vals, *idx_flat):
        per_group = _regroup(dsched, idx_flat, 5)
        L, U, Li, Ui, tiny, nzero = _factor_loop(
            dsched, vals[0] if sharded_in else vals, thresh_np,
            dtype, per_group, axis, pair=pair)
        return (L, U, Li, Ui, jax.lax.psum(tiny, axis),
                jax.lax.psum(nzero, axis))

    mapped = _shard_map(
        body, mesh=mesh, in_specs=(vspec,) + idx_specs,
        out_specs=(fspec, fspec, fspec, fspec, P(), P()),
        check_vma=False)
    # AOT persistence (resilience/aot.py, ISSUE 17): the shard_map'd
    # whole-phase factor exports like the single-device phase programs
    # — the fingerprint gains the mesh legs (shape + axis + device
    # kinds) so a mesh reshape refuses typed instead of dispatching a
    # program compiled for a different collective topology.  Natively
    # complex lanes skip AOT (the platform-gate note at
    # batched._phase_fns), a pair program is all-real and exports;
    # an unexportable shard_map falls back to the plain jit inside
    # AotJit — never a dispatch break.
    from ..resilience import aot
    # named for the profiler and for the persistent-cache key (the
    # batched._phase_fns note)
    @jax.jit
    def slu_dist_factor(vsel):
        return mapped(vsel, *idx_args())

    factor_fn = slu_dist_factor
    if sharded_in:
        factor_fn = aot.wrap_jit(
            "dist_factor", factor_fn,
            aot.schedule_fingerprint(
                dsched, dtype,
                extra=("dist_factor", bool(pair))
                + aot.mesh_fingerprint_legs(mesh, axis)))
    jitted = obs.watch_jit("dist_factor", factor_fn)
    vshard = jax.sharding.NamedSharding(mesh, P(axis))
    route = _mesh_route(dsched, dtype)

    def factor(vals) -> DistLU:
        # host-side one-time redistribution (dReDistribute_A analog,
        # pddistribute.c:66): ship each device ONLY its slice,
        # committed to its shard (`_host_vals`: both planes of it in
        # pair storage).  Native complex keeps the replicated round-3
        # shape (_shard_vals note).
        vv = (jax.device_put(_host_vals(vals, sel, dtype, pair), vshard)
              if sharded_in else jnp.asarray(vals))
        L, U, Li, Ui, tiny, nzero = jitted(vv)
        if int(nzero) > 0:
            raise ZeroDivisionError(
                f"{int(nzero)} exactly-zero pivot(s); matrix singular")
        return DistLU(plan=plan, mesh=mesh, axis=axis, dtype=dtype,
                      schedule=dsched, L_flat=L, U_flat=U, Li_flat=Li,
                      Ui_flat=Ui, tiny_pivots=int(tiny), route=route)

    factor.jitted = jitted  # exposed for HLO inspection (measure_comm)
    factor.sel = sel        # per-device value-slice indices
    factor.pair = pair
    return factor


def dist_factor_fn(plan: FactorPlan, mesh: Mesh, dtype):
    """`make_dist_factor`'s closure for this mesh, dtype and lowering,
    cached on the PLAN: what `factorize(grid=)` runs and what
    `measure_comm` lowers."""
    cache = getattr(plan, "_dist_factor_fns", None)
    if cache is None:
        cache = plan._dist_factor_fns = {}
    dtype = np.dtype(dtype)
    key = (mesh, dtype.str, _pair(dtype, mesh))
    if key not in cache:
        cache[key] = make_dist_factor(plan, mesh, dtype=dtype)
    return cache[key]


def make_dist_solve_merged(plan: FactorPlan, mesh: Mesh,
                           dtype=np.float64, axis=None,
                           trans: bool = False, pair: bool = False):
    """Row-partitioned merged mesh trisolve: what a narrow-rhs sweep
    on a mesh is (`solve_arm`).  One solve spans devices over the lsum
    layout (ops/trisolve.py): each
    device sweeps its own front partition — the rows its fronts own
    — writing y/update blocks DENSELY into its device-major slices
    of the global Y/UPD/XF slot spaces, and the cross-device dataflow
    is an all-reduce at the merged segments' static sync points: the
    reference's C_Tree lsum reduction (SRC/pdgstrs.c:2133) collapsed
    to one all-reduce per segment boundary instead of one per
    supernode.  Interior segments (zone-affine subtrees) sweep with
    ZERO collectives.

    A sync point reconciles only what was written since the last one
    (`trisolve.mesh_sync_ranges`: slot bases grow in group order, so
    that is one contiguous range of UPD going forward and of XF going
    backward), so a sweep all-reduces each slot at most once,
    u_total + y_total slots in all, whatever the number of
    boundaries.

    Matching contract: every dense slot is written exactly once by
    exactly one device, every other device holds an exact zero
    there, and the all-reduce of the raw range is v + 0 + 0·…, so
    the mesh execution does the arithmetic of the sequential
    execution of the same layout on one device (`mesh_oracle_solve`;
    tests/test_trisolve.py holds the two to 4·eps·max|x|: separately
    compiled programs do not agree bit for bit)."""
    axis, ndev = _resolve_axis(mesh, axis)
    dsched = get_schedule(plan, ndev)
    from ..ops import trisolve as tsv
    ts = tsv.get_trisolve(dsched)
    dtype = np.dtype(dtype)
    n = dsched.n

    idx_args = functools.cache(lambda: tuple(
        a for gs in ts.groups for a in gs.dev(squeeze=False)))
    idx_specs = (P(axis),) * (3 * len(ts.groups))

    def body(L_flat, U_flat, Li_flat, Ui_flat, b, *idx_flat):
        flats = tuple(_solve_view(f)
                      for f in (L_flat, U_flat, Li_flat, Ui_flat))
        packs = tsv.pack_flats(ts, flats)
        it = iter(idx_flat)
        per_group = [tuple(next(it)[0] for _ in range(3))
                     for _ in ts.groups]
        di = _flat_axis_index(axis)
        if pair:
            # (2, N) plane flats; b arrives encoded from the host
            cplx, B0 = True, b
        else:
            xdt = jnp.promote_types(dtype, b.dtype)
            cplx = bool(jnp.issubdtype(xdt, jnp.complexfloating))
            B0 = _enc(b.astype(xdt), cplx)
        R = B0.shape[-1]
        rdt = B0.dtype
        B, UPD, Y = tsv.init_lsum_buffers(ts, B0)
        fwd_rng, bwd_rng, last_rng = tsv.mesh_sync_ranges(ts)

        def dev_meta(i):
            g = dsched.groups[i]
            gs = ts.groups[i]
            return g, tsv._Meta(
                trim=gs.trim, rtrim=gs.rtrim, J=gs.J,
                y_off=gs.y_off + di * gs.trim * g.wb,
                u_off=gs.u_off + di * gs.trim * gs.rtrim)

        @jax.named_scope("slu.lsum")
        def sync(buf, rng):
            # the slots written since the last sync point: one device
            # wrote each, the others hold zeros there
            if rng is None:
                return buf
            lo, hi = rng
            red = psum_exact(jax.lax.slice_in_dim(buf, lo, hi), axis)
            return jax.lax.dynamic_update_slice_in_dim(buf, red, lo, 0)

        state = (B, UPD, Y)
        for seg, rng in zip(ts.segments, fwd_rng):
            B_, UPD_, Y_ = state
            state = (B_, sync(UPD_, rng), Y_)
            for i in seg:
                g, gsd = dev_meta(i)
                state = tsv._fwd_member(state, g, gsd, packs[i],
                                        per_group[i], cplx, trans)
        _, _, Y = state
        XF = jnp.zeros((ts.y_total + 1, R), rdt)
        for seg, rng in zip(reversed(ts.segments), reversed(bwd_rng)):
            XF = sync(XF, rng)
            for i in reversed(seg):
                g, gsd = dev_meta(i)
                XF = tsv._bwd_member(XF, Y, g, gsd, packs[i],
                                     per_group[i], cplx, trans)
        XF = sync(XF, last_rng)   # replicate the rest of the solution
        x = XF[jnp.asarray(ts.final_idx)]
        return x if pair else _dec(x, cplx)   # pair: host decodes

    mapped = _shard_map(
        _hi_prec(body), mesh=mesh,
        in_specs=(_pair_spec(axis, pair),) * 4 + (P(),) + idx_specs,
        out_specs=P(), check_vma=False)

    @jax.jit
    def slu_dist_solve_merged(L_flat, U_flat, Li_flat, Ui_flat, b):
        return mapped(L_flat, U_flat, Li_flat, Ui_flat, b, *idx_args())

    solve = _aot_wrap_dist("dist_solve_merged", slu_dist_solve_merged,
                           dsched, mesh, axis, dtype, trans, pair)
    return obs.watch_jit("dist_solve_merged", solve)


def mesh_oracle_solve(dlu: DistLU, b_factor_order,
                      trans: bool = False):
    """Sequential one-device execution of a DistLU's merged mesh
    layout (either storage; `b` in the caller's dtype, complex against
    pair-stored factors): per group, each device's member step runs
    in device order with EXACTLY the per-device operand shapes the
    shard_map'd solve uses (XLA:CPU lowers a batch-2t GEMV differently
    from two batch-t GEMVs, so shape identity is required for bit
    identity).  Every dense slot is written once by one device, and
    consumers gather cross-device slots only after the mesh's sync
    points would have replicated them (v + 0 + 0 + ... = v, exact),
    so this sequential execution IS the mesh execution's arithmetic —
    the oracle, no collectives, no shard_map."""
    from ..ops import trisolve as tsv
    from ..ops.batched import _dec, _enc
    dsched = dlu.schedule
    ndev = dsched.ndev
    ts = tsv.get_trisolve(dsched)
    flats = [np.asarray(f) for f in (dlu.L_flat, dlu.U_flat,
                                     dlu.Li_flat, dlu.Ui_flat)]

    def dev_pack(g, gs, d):
        def cut(flat, off, shape, keep=lambda p: p):
            if flat.ndim == 2:      # pair storage: a plane at a time
                return tuple(cut(p, off, shape, keep) for p in flat)
            per = shape[0] * shape[1]
            v = flat.reshape(ndev, -1)[d, off:off + gs.trim * per]
            return jnp.asarray(keep(v.reshape((gs.trim,) + shape)))

        return (cut(flats[2], g.Li_off, (g.wb, g.wb)),
                cut(flats[0], g.L_off, (g.mb, g.wb),
                    lambda p: p[:, g.wb:, :]),
                cut(flats[3], g.Ui_off, (g.wb, g.wb)),
                cut(flats[1], g.U_off, (g.wb, g.mb),
                    lambda p: p[:, :, g.wb:]))

    def dev_meta(g, gs, d):
        return tsv._Meta(trim=gs.trim, rtrim=gs.rtrim, J=gs.J,
                         y_off=gs.y_off + d * gs.trim * g.wb,
                         u_off=gs.u_off + d * gs.trim * gs.rtrim)

    def dev_idx(gs, d):
        return (jnp.asarray(gs.b_idx[d]),
                jnp.asarray(gs.u_gidx[d]),
                jnp.asarray(gs.xs_idx[d]))

    b = jnp.asarray(b_factor_order)
    xdt = jnp.promote_types(dlu.dtype, b.dtype)
    cplx = bool(jnp.issubdtype(xdt, jnp.complexfloating))
    B0 = _enc(b.astype(xdt), cplx)
    R = B0.shape[-1]
    rdt = B0.dtype
    state = tsv.init_lsum_buffers(ts, B0)
    with jax.default_matmul_precision("float32"):
        for g, gs in zip(dsched.groups, ts.groups):
            for d in range(ndev):
                state = tsv._fwd_member(
                    state, g, dev_meta(g, gs, d), dev_pack(g, gs, d),
                    dev_idx(gs, d), cplx, trans)
        _, _, Y = state
        XF = jnp.zeros((ts.y_total + 1, R), rdt)
        for g, gs in zip(reversed(dsched.groups),
                         list(reversed(ts.groups))):
            for d in range(ndev):
                XF = tsv._bwd_member(
                    XF, Y, g, dev_meta(g, gs, d), dev_pack(g, gs, d),
                    dev_idx(gs, d), cplx, trans)
    x = XF[jnp.asarray(ts.final_idx)]
    return np.asarray(_dec(x, cplx))


def make_dist_solve(plan: FactorPlan, mesh: Mesh, dtype=np.float64,
                    axis=None, trans: bool = False, pair: bool = False):
    """Build `solve(L, U, Li, Ui, b) -> x` against persistent sharded
    factors.  b (n, nrhs) in factor ordering; against pair-stored
    factors b is the host's encoding (`encode_rhs`) and so is x."""
    axis, ndev = _resolve_axis(mesh, axis)
    dsched = get_schedule(plan, ndev)
    dtype = np.dtype(dtype)

    idx_args = _group_operands(dsched, (5, 6))
    idx_specs = (P(axis),) * (2 * len(dsched.groups))

    def body(L_flat, U_flat, Li_flat, Ui_flat, b, *idx_flat):
        per_group = _regroup(dsched, idx_flat, 2)
        return _solve_loop(dsched, (L_flat, U_flat, Li_flat, Ui_flat),
                           b, dtype, per_group, axis, trans=trans,
                           pair=pair)

    mapped = _shard_map(
        body, mesh=mesh,
        in_specs=(_pair_spec(axis, pair),) * 4 + (P(),) + idx_specs,
        out_specs=P(), check_vma=False)

    @jax.jit
    def slu_dist_solve(L_flat, U_flat, Li_flat, Ui_flat, b):
        return mapped(L_flat, U_flat, Li_flat, Ui_flat, b, *idx_args())

    solve = _aot_wrap_dist("dist_solve", slu_dist_solve, dsched, mesh,
                           axis, dtype, trans, pair)
    return obs.watch_jit("dist_solve", solve)


def make_dist_solve_rhs_sharded(plan: FactorPlan, mesh: Mesh,
                                dtype=np.float64, axis=None,
                                trans: bool = False,
                                pair: bool = False):
    """Many-RHS distributed solve: shard X by RHS COLUMNS instead of
    replicating it.  Each device all_gathers the factor slabs ONCE
    (device-major concatenation IS the global layout) and then sweeps
    ALL fronts over its own column slice with ZERO collectives — the
    many-RHS counterpart of pdgstrs's mrhs lsum kernels
    (SRC/pdgstrs_lsum.c dlsum_fmod_inv_gpu_mrhs; baseline config #5,
    ldoor nrhs=64).

    Traffic trade vs the replicated-X sweep (`make_dist_solve`): one
    lu_bytes-sized gather per solve instead of solve_syncs × n × nrhs
    words of psum — the gather amortizes over RHS columns, so this
    wins when nrhs is large (dist_solve auto-selects at
    nrhs ≥ 2·ndev).  `b` (n, nrhs) in factor ordering; nrhs is padded
    to a multiple of ndev internally.  Against pair-stored factors `b`
    is the host's encoding in `ndev` column blocks
    (`encode_rhs(bb, blocks=ndev)`), so that each device's slice holds
    both halves of its own right-hand sides."""
    axis, ndev = _resolve_axis(mesh, axis)
    dsched = get_schedule(plan, ndev)
    dtype = np.dtype(dtype)
    n = dsched.n

    # per-group index tensors over ALL devices' fronts, device-major —
    # matching the row order of the gathered slabs
    # (constants of the trace, made inside it: `_group_operands`)
    @functools.cache
    def g_idx():
        def rows(a):
            a = np.asarray(a, dtype=np.int32)
            return jnp.asarray(a.reshape(ndev * a.shape[1], a.shape[-1]))
        with jax.ensure_compile_time_eval():
            return [(rows(g.col_idx), rows(g.struct_idx))
                    for g in dsched.groups]

    def body(L_flat, U_flat, Li_flat, Ui_flat, b):
        flats = [_solve_view(jax.lax.all_gather(
            f, axis, axis=f.ndim - 1, tiled=True))
            for f in (L_flat, U_flat, Li_flat, Ui_flat)]
        L, U, Li, Ui = flats

        def gsl(flat, off: int, size: int):
            """Group slab across ALL devices, offset-0 contiguous
            (device-major), in either solve storage."""
            if flat.ndim == 2:          # (2, ndev*total) real view
                return (flat.reshape(2, ndev, -1)[:, :, off:off + size]
                        .reshape(2, ndev * size))
            return (flat.reshape(ndev, -1)[:, off:off + size]
                    .reshape(ndev * size))

        if pair:
            cplx = True
            X = jnp.zeros((n + 1, b.shape[1]), b.dtype).at[:n].set(b)
        else:
            xdt = jnp.promote_types(dtype, b.dtype)
            cplx = bool(jnp.issubdtype(xdt, jnp.complexfloating))
            X = jnp.zeros((n + 1, b.shape[1]), xdt)
            X = X.at[:n, :].set(b.astype(xdt))
            X = _enc(X, cplx)
        z = jnp.int32(0)

        if not trans:
            fwd_fn, fwd_src = _fwd_group_impl, (L, Li)
            bwd_fn, bwd_src = _bwd_group_impl, (U, Ui)
            fwd_off = lambda g: ((g.L_off, g.mb * g.wb),
                                 (g.Li_off, g.wb * g.wb))
            bwd_off = lambda g: ((g.U_off, g.wb * g.mb),
                                 (g.Ui_off, g.wb * g.wb))
        else:
            fwd_fn, fwd_src = _fwd_group_T_impl, (U, Ui)
            bwd_fn, bwd_src = _bwd_group_T_impl, (L, Li)
            fwd_off = lambda g: ((g.U_off, g.wb * g.mb),
                                 (g.Ui_off, g.wb * g.wb))
            bwd_off = lambda g: ((g.L_off, g.mb * g.wb),
                                 (g.Li_off, g.wb * g.wb))

        idx = g_idx()
        for g, (ci, si) in zip(dsched.groups, idx):
            (o1, s1), (o2, s2) = fwd_off(g)
            X = fwd_fn(X, gsl(fwd_src[0], o1, g.n_loc * s1),
                       gsl(fwd_src[1], o2, g.n_loc * s2), ci, si,
                       z, z, mb=g.mb, wb=g.wb,
                       n_pad=ndev * g.n_loc, cplx=cplx)
        for g, (ci, si) in zip(reversed(dsched.groups),
                               reversed(idx)):
            (o1, s1), (o2, s2) = bwd_off(g)
            X = bwd_fn(X, gsl(bwd_src[0], o1, g.n_loc * s1),
                       gsl(bwd_src[1], o2, g.n_loc * s2), ci, si,
                       z, z, mb=g.mb, wb=g.wb,
                       n_pad=ndev * g.n_loc, cplx=cplx)
        return X[:n] if pair else _dec(X, cplx)[:n]

    mapped = _shard_map(
        _hi_prec(body), mesh=mesh,
        in_specs=(_pair_spec(axis, pair),) * 4 + (P(None, axis),),
        out_specs=P(None, axis), check_vma=False)
    jitted = obs.watch_jit(
        "dist_solve_rhs_sharded",
        _aot_wrap_dist("dist_solve_rhs_sharded", jax.jit(mapped),
                       dsched, mesh, axis, dtype, trans, pair))

    def solve(L_flat, U_flat, Li_flat, Ui_flat, b):
        r = b.shape[1]
        pad = (-r) % ndev
        if pad:
            b = jnp.concatenate(
                [b, jnp.zeros((b.shape[0], pad), b.dtype)], axis=1)
        x = jitted(L_flat, U_flat, Li_flat, Ui_flat, b)
        return x[:, :r] if pad else x

    solve.jitted = jitted   # exposed for HLO inspection (tests)
    return solve


def measure_comm(dlu: DistLU, nrhs: int = 1) -> dict:
    """Measured collective inventory of the compiled distributed
    factor and solve programs (per-phase counts + bytes from the
    post-optimization HLO) — the runtime-measured side of the
    SCT_print3D contract; compare against
    `dlu.schedule.comm_summary(dlu.dtype, nrhs)`.  Reuses the plan's
    cached factor/solve closures (the ones gssvx/dist_solve built), so
    programs that already executed are lowering+cache-hit, not
    recompiled."""
    from ..utils.stats import hlo_collective_stats
    plan = dlu.plan
    factor = dist_factor_fn(plan, dlu.mesh, dlu.dtype)
    _, ndev = _resolve_axis(dlu.mesh, dlu.axis)
    # measure the solve program dist_solve actually runs at this nrhs
    arm = solve_arm(dlu, nrhs)
    sharded_rhs = arm == "rhs_sharded"
    solve = _solve_fn(dlu, False, arm)
    # lower with the dtype production traced with: factor consumes
    # plan.scaled_values(a) — f64 for real systems, c128 for complex —
    # NOT the factor dtype (the cast happens inside the program); a
    # mismatched aval here would force a pointless full recompile
    pair = _lu_is_pair(dlu)
    rdt = _real_dtype(dlu.dtype)
    if factor.sel is None:      # native complex: replicated round-3
        vals = jnp.zeros(len(plan.coo_rows), np.complex128)
    elif pair:                  # both planes of each device's slice
        nd, lsel = factor.sel.shape
        vals = jnp.zeros((nd, 2, lsel), rdt)
    else:
        vals = jnp.zeros(factor.sel.shape, np.float64)
    out = {}
    txt = factor.jitted.lower(vals).compile().as_text()
    out["FACT"] = hlo_collective_stats(txt)
    if sharded_rhs:
        # the wrapper pads nrhs to a ndev multiple before its jit
        pad_r = nrhs + (-nrhs) % ndev
        lowerable = solve.jitted
    else:
        pad_r = nrhs
        lowerable = solve
    # a pair solve takes the host's encoding: two real columns a rhs
    b = (jnp.zeros((dlu.schedule.n, 2 * pad_r), rdt) if pair
         else jnp.zeros((dlu.schedule.n, pad_r), dlu.dtype))
    txt = lowerable.lower(dlu.L_flat, dlu.U_flat, dlu.Li_flat,
                          dlu.Ui_flat, b).compile().as_text()
    out["SOLVE"] = hlo_collective_stats(txt)
    # mesh stamps (ISSUE 17 satellite): scalar legs that let a caller
    # hold PER-DEVICE and PER-BOUNDARY ceilings, not just totals — a
    # mesh twice the size must not get twice the collective allowance.
    syncs = solve_syncs(dlu, arm)
    psum_b = int(out["SOLVE"].get("all-reduce", {}).get("bytes", 0))
    out["MESH"] = {
        "n_devices": int(ndev),
        "mesh_shape": "x".join(str(int(dlu.mesh.shape[a]))
                               for a in dlu.mesh.axis_names),
        "axis_names": ",".join(str(a) for a in dlu.mesh.axis_names),
        "solve_syncs": syncs,
        "solve_psum_bytes_per_boundary": (psum_b // syncs if syncs
                                          else 0),
        "solve_arm": arm,
    }
    return out


def dist_solve_cache_size(dlu: DistLU) -> int:
    """Compiled-signature count across every dist solve program built
    for this handle's plan — the mesh replica's analog of
    trisolve.solve_packed_cache_size, and the probe the serve layer's
    zero-recompile pin reads (serve/service.py solve_jit_cache_size).
    -1 when no solve program exists yet."""
    cache = getattr(dlu.plan, "_dist_solve_fns", None)
    if not cache:
        return -1
    total = 0
    for fn in cache.values():
        j = getattr(fn, "jitted", fn)
        try:
            total += int(j._cache_size())
        except AttributeError:
            return -1
    return total


def _rhs_sharded_auto(nrhs: int, ndev: int) -> bool:
    """Pick the rhs-sharded sweep when the column slice amortizes the
    one-time factor gather (nrhs ≥ 2·ndev).  SLU_RHS_SHARDED=1/0
    forces."""
    from ..flags import env_str
    v = env_str("SLU_RHS_SHARDED", "auto").strip().lower()
    if v in ("1", "true", "on"):
        return True
    if v in ("0", "false", "off"):
        return False
    return nrhs >= 2 * ndev


def solve_arm(dlu: DistLU, nrhs: int) -> str:
    """Which program a sweep of `nrhs` columns is on this handle's
    mesh.  Many columns amortize one gather of the factors
    (`rhs_sharded`, `_rhs_sharded_auto`); a narrow sweep is `merged`,
    the row-partitioned lsum program."""
    _, ndev = _resolve_axis(dlu.mesh, dlu.axis)
    return "rhs_sharded" if _rhs_sharded_auto(nrhs, ndev) else "merged"


def solve_syncs(dlu: DistLU, arm: str) -> int:
    """All-reduces one sweep of that arm compiles to: a boundary of
    the merged segments each (`trisolve.mesh_sync_count`), none
    where the columns are sharded (one all-gather of the factors
    instead)."""
    if arm == "rhs_sharded":
        return 0
    from ..ops import trisolve as tsv
    return tsv.mesh_sync_count(tsv.get_trisolve(dlu.schedule))


_SOLVE_MAKERS = {"rhs_sharded": make_dist_solve_rhs_sharded,
                 "merged": make_dist_solve_merged}


def _solve_fn(dlu: DistLU, trans: bool, arm: str):
    """The compiled solve of one arm (`solve_arm`) for this handle's
    mesh, dtype and storage, cached on the PLAN so SamePattern
    re-factorizations reuse it across handles."""
    plan = dlu.plan
    cache = getattr(plan, "_dist_solve_fns", None)
    if cache is None:
        cache = plan._dist_solve_fns = {}
    pair = _lu_is_pair(dlu)
    key = (dlu.mesh, dlu.dtype.str, dlu.axis, trans,
           arm == "rhs_sharded", arm == "merged", pair)
    if key not in cache:
        cache[key] = _SOLVE_MAKERS[arm](
            plan, dlu.mesh, dtype=dlu.dtype, axis=dlu.axis,
            trans=trans, pair=pair)
    return cache[key]


def dist_solve(dlu: DistLU, b_factor_order, trans: bool = False):
    """Solve against a DistLU.  Compiled solves are cached on the PLAN
    keyed (mesh, dtype, trans, arm, storage), so SamePattern
    re-factorizations reuse them across handles.  `solve_arm` picks
    the program: many-RHS solves the rhs-sharded sweep
    (make_dist_solve_rhs_sharded), narrow ones the merged one
    (make_dist_solve_merged).
    Against pair-stored factors the host encodes the right-hand side
    and decodes the answer (spans `slu.pair.encode` /
    `slu.pair.decode`), and the answer is a host array."""
    nrhs = int(b_factor_order.shape[1]) \
        if getattr(b_factor_order, "ndim", 1) == 2 else 1
    _, ndev = _resolve_axis(dlu.mesh, dlu.axis)
    arm = solve_arm(dlu, nrhs)
    sharded_rhs = arm == "rhs_sharded"
    solve = _solve_fn(dlu, trans, arm)
    flats = (dlu.L_flat, dlu.U_flat, dlu.Li_flat, dlu.Ui_flat)
    if not _lu_is_pair(dlu):
        return solve(*flats, b_factor_order)
    bb = np.asarray(b_factor_order)
    squeeze = bb.ndim == 1
    bb = bb[:, None] if squeeze else bb
    xdt = np.promote_types(dlu.dtype, bb.dtype)
    blocks = ndev if sharded_rhs else 1
    with obs.span("pair.encode", cat="solve"):
        pad = (-nrhs) % blocks
        if pad:
            bb = np.concatenate(
                [bb, np.zeros((bb.shape[0], pad), bb.dtype)], axis=1)
        b_in = encode_rhs(bb.astype(xdt), blocks)
    X = solve(*flats, b_in)
    with obs.span("solve.fetch", cat="solve"):
        X = np.asarray(X)
    with obs.span("pair.decode", cat="solve"):
        x = decode_sol(X, xdt, blocks)[:, :nrhs]
    return x[:, 0] if squeeze else x


# --------------------------------------------------------------------
# slulint HLO contracts (tools/slulint/contracts.py): the mesh solve
# program's compiled shape, statically checkable because the task
# graph is fixed before numerics run
# --------------------------------------------------------------------

_CONTRACT_MEMO: dict = {}


def _contract_dlu():
    """A 2-device CPU mesh + a small factored DistLU — the
    representative signature the mesh-solve contracts lower at.
    Memoized: both entries share one factorization.  Returns None
    when no 2-device mesh is possible (backend already initialized
    single-device) — the contracts then report skipped-ok; the test
    env (8 forced host devices) asserts them for real."""
    if "dlu" in _CONTRACT_MEMO:
        return _CONTRACT_MEMO["dlu"]
    from ..utils.compat import set_cpu_devices
    set_cpu_devices(2)
    if len(jax.devices()) < 2:
        _CONTRACT_MEMO["dlu"] = None
        return None
    from ..options import Options
    from ..plan.plan import plan_factorization
    from ..utils.testmat import laplacian_2d
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("z",))
    a = laplacian_2d(8)
    plan = plan_factorization(a, Options())
    dlu = make_dist_factor(plan, mesh)(plan.scaled_values(a))
    _CONTRACT_MEMO["dlu"] = dlu
    return dlu


def _contract_build_mesh_solve():
    dlu = _contract_dlu()
    if dlu is None:
        raise RuntimeError("no 2-device CPU mesh available")
    solve = make_dist_solve_merged(dlu.plan, dlu.mesh,
                                   dtype=dlu.dtype, axis=dlu.axis)
    b = np.zeros((dlu.schedule.n, 4), dlu.dtype)
    return solve, (np.asarray(dlu.L_flat), np.asarray(dlu.U_flat),
                   np.asarray(dlu.Li_flat), np.asarray(dlu.Ui_flat),
                   b), {}


def _contract_psum_per_boundary():
    """Exactly ONE psum per merged-segment sync boundary (fwd + bwd
    + the final replicate) in the COMPILED mesh solve — the collapsed
    C_Tree lsum-reduction discipline (make_dist_solve_merged): a
    refactor that reintroduces per-supernode reductions multiplies
    the count and trips this before it prices a single request."""
    dlu = _contract_dlu()
    if dlu is None:
        return True, "skipped: no 2-device CPU mesh"
    from ..ops import trisolve as tsv
    from ..utils.stats import hlo_collective_stats
    fn, args, _ = _contract_build_mesh_solve()
    compiled = fn.lower(*args).compile()
    got = hlo_collective_stats(compiled.as_text()).get(
        "all-reduce", {}).get("count", 0)
    want = tsv.mesh_sync_count(tsv.get_trisolve(dlu.schedule))
    return got == want, (f"{got} all-reduce(s) compiled for {want} "
                         "segment boundaries")


def _contract_skip():
    """Truthy reason when the mesh contracts cannot be judged here
    (the backend initialized single-device before the checker could
    provision a host complement)."""
    return (None if _contract_dlu() is not None
            else "no 2-device mesh available")


HLO_CONTRACTS = (
    {"name": "dist.solve_merged",
     "phase": "dist_solve_merged",
     "contracts": ("no_scatter", "no_host_callback"),
     "build": _contract_build_mesh_solve,
     "skip": _contract_skip,
     "note": "the merged mesh trisolve writes y/update blocks "
             "DENSELY into device-major slices — a scatter in the "
             "lowering means the dense-slot discipline broke"},
    {"name": "dist.solve_psum_per_boundary",
     "phase": "dist_solve_merged",
     "check": _contract_psum_per_boundary,
     "note": "one all-reduce per merged segment boundary, none "
             "per supernode"},
)
