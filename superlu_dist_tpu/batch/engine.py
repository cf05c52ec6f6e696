"""The vmapped factor/solve engine: B same-pattern systems, one trace.

`batch_factorize` vmaps the level-merged donated-buffer factor
segments (ops/batched._staged_factor_segment's member bodies) over a
leading batch axis: one schedule, one compile per (segment, B), B
value sets streaming through one donated (B, upd) extend-add buffer.
`batch_solve` batches the packed lsum trisolve (ops/trisolve.sweep
over the PR 7 PackSet layout) over batched B/UPD/XF buffers — by
default as one lax.scan program over the member axis (see
_solve_arm: XLA:CPU's batch-collapsed dot kernels reassociate at
batch-dim 1, so the vmap-dense solve arm drifts 1-2 ulp on trim==1
groups; scan keeps every lane's ops at exact per-sample shapes).
Both legs are pinned bitwise equal to per-sample execution at fp64
(tests/test_batch.py).

The Pallas panel LU is force-disabled under the batch traces
(`force_xla=True` through _factor_group_impl): a pallas_call's
batching rule is not a path we certify.  The XLA lowering is the pinned
arm; a certified batched-Pallas arm is future work (GPU arm, ROADMAP
item 2).
"""

from __future__ import annotations

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..options import Options, Trans
from ..ops.batched import (StagedLU, _factor_group_impl, _real_dtype,
                           _thresh_for, factor_seg_metas,
                           get_factor_segments, get_schedule)
from ..ops import trisolve
from ..plan.plan import FactorPlan
from ..utils.stats import Stats
from .plan_share import batch_scaled_values

__all__ = ["BatchedLU", "batch_factorize", "batch_solve",
           "batch_solve_factor", "member_factorization"]


def _xla_metas(metas: tuple) -> tuple:
    """Normalize a factor_seg_metas tuple for the batch arm: the
    Pallas promotion leg is forced False so one canonical static key
    serves the vmapped program everywhere (and the member bodies
    route through the XLA panel-LU regardless of platform)."""
    return tuple((mb, wb, n_loc, ea_meta, eb_meta, False)
                 for (mb, wb, n_loc, ea_meta, eb_meta, _p) in metas)


@functools.partial(jax.jit, static_argnames=("metas",),
                   donate_argnums=(0,))
def _batched_factor_segment_jit(upd_buf, vals, thresh, a_srcs, a_dsts,
                                one_dsts, ea_blockss, upd_offs, *,
                                metas):
    """One merged factor segment vmapped over the batch: `upd_buf`
    (B, upd_total+pad) is donated and streams through the segment
    chain in place B-wide; `vals` is (B, nnz+1).  The member body is
    _staged_factor_segment's, verbatim, with force_xla=True — the
    static `metas` key is the SAME factor_seg_metas product the
    unbatched arm's dispatch/warmup share (Pallas leg normalized by
    _xla_metas), so the program set is warmable per B rung exactly
    like the unbatched arm's."""
    def member(upd_buf, vals):
        dtype = upd_buf.dtype
        z32 = jnp.zeros((), jnp.int32)
        panels = []
        tiny = nzero = z32
        with jax.default_matmul_precision("float32"):
            for ((mb, wb, n_pad, ea_meta, eb_meta, _p), a_src,
                 a_dst, one_dst, ea_blocks, upd_off) in zip(
                     metas, a_srcs, a_dsts, one_dsts, ea_blockss,
                     upd_offs):
                (upd_buf, L, U, Li, Ui, t, z) = _factor_group_impl(
                    vals, upd_buf,
                    jnp.zeros(n_pad * mb * wb, dtype),
                    jnp.zeros(n_pad * wb * mb, dtype),
                    jnp.zeros(n_pad * wb * wb, dtype),
                    jnp.zeros(n_pad * wb * wb, dtype),
                    z32, z32, thresh, a_src, a_dst, one_dst,
                    ea_blocks, upd_off, z32, z32, z32, z32,
                    mb=mb, wb=wb, n_pad=n_pad, ea_meta=ea_meta,
                    eb_meta=eb_meta, pair=False, force_xla=True)
                panels.append((L, U, Li, Ui))
                tiny = tiny + t
                nzero = nzero + z
        return upd_buf, tuple(panels), tiny, nzero

    return jax.vmap(member)(upd_buf, vals)


# the compile-watch proxy the zero-recompiles-after-warmup gate probes
# (phase "batch_factor"; the serve coalescer dispatches through it)
_batched_factor_segment = obs.watch_jit(
    "batch_factor", _batched_factor_segment_jit,
    donate=(0,))


@functools.partial(jax.jit, static_argnames=("dtype_str",))
def _batch_vals_ext(v, dtype_str: str):
    dtype = np.dtype(dtype_str)
    return jnp.concatenate(
        [v.astype(dtype), jnp.zeros((v.shape[0], 1), dtype)], axis=1)


@dataclasses.dataclass
class BatchedLU:
    """B same-plan factorizations in batched per-group panels: each
    panel flat carries a leading B axis over the StagedLU layout.
    `member(i)` slices an ordinary StagedLU back out — downstream
    layers (serve cache, store, fleet) never learn the factors were
    born batched."""
    plan: FactorPlan
    schedule: object            # ops.batched.BatchedSchedule
    dtype: np.dtype
    b: int
    panels: list                # per group (L, U, Li, Ui), leading B
    tiny: np.ndarray            # (B,) tiny-pivot replacement counts
    nzero: np.ndarray           # (B,) exact-zero pivot counts

    def ok_mask(self) -> np.ndarray:
        """True where the member factorized cleanly (no exact-zero
        pivot) — the masked-member semantics: a singular sibling
        refuses per-index, it never poisons this lane."""
        return np.asarray(self.nzero) == 0

    def member_status(self) -> list:
        return ["ok" if ok else "singular" for ok in self.ok_mask()]

    def member(self, i: int) -> StagedLU:
        """Member i as an ordinary StagedLU (the per-sample handle
        every existing consumer speaks).  Raises the per-sample typed
        refusal for a singular member — factorize_device's exact
        semantics, indexed."""
        i = int(i)
        nz = int(np.asarray(self.nzero)[i])
        if nz > 0:
            raise ZeroDivisionError(
                f"batch member {i}: factorization hit {nz} "
                "exactly-zero pivot(s); the matrix is singular "
                "(enable replace_tiny_pivot to perturb instead)")
        panels = [tuple(a[i] for a in p) for p in self.panels]
        return StagedLU(plan=self.plan, schedule=self.schedule,
                        dtype=self.dtype, panels=panels,
                        tiny_pivots=int(np.asarray(self.tiny)[i]))

    def held_bytes(self) -> int:
        return sum(int(a.nbytes) for p in self.panels for a in p)


def batch_factorize(plan: FactorPlan, values: np.ndarray,
                    dtype=np.float64,
                    scaled: bool = False) -> BatchedLU:
    """Numeric factorization of B same-pattern value sets against one
    plan: `values` is (B, nnz) in the plan's COO order (raw values by
    default; `scaled=True` skips the Dr·A·Dc refresh for callers that
    pre-scaled).  Returns a BatchedLU; per-member singularity reports
    through `nzero`/`member_status()` instead of raising — a singular
    member must not poison its siblings (callers refuse per index)."""
    dtype = np.dtype(dtype)
    if dtype.kind == "c":
        raise NotImplementedError(
            "batch_factorize is real-dtype only: the complex lanes "
            "keep the per-group pair dispatch (ops/batched.py) — "
            "factor members sequentially instead")
    values = np.asarray(values)
    if values.ndim != 2:
        raise ValueError(f"values must be (B, nnz); got {values.shape}")
    B = int(values.shape[0])
    if B < 1:
        raise ValueError("empty batch")
    sched = get_schedule(plan, 1)
    svals = np.asarray(values) if scaled else batch_scaled_values(
        plan, values)
    vals_ext = _batch_vals_ext(jnp.asarray(svals), dtype.str)
    thresh = jnp.asarray(_thresh_for(plan, dtype),
                         dtype=_real_dtype(dtype))
    upd_buf = jnp.zeros((B, sched.upd_total + sched.upd_pad), dtype)
    panels = []
    tiny = nzero = jnp.zeros((B,), jnp.int32)
    for seg in get_factor_segments(sched):
        ops = [sched.groups[i].dev(squeeze=True)[:4] for i in seg]
        (upd_buf, pseg, t, z) = _batched_factor_segment(
            upd_buf, vals_ext, thresh,
            tuple(o[0] for o in ops), tuple(o[1] for o in ops),
            tuple(o[2] for o in ops), tuple(o[3] for o in ops),
            tuple(jnp.asarray(sched.groups[i].upd_off_global,
                              jnp.int64) for i in seg),
            metas=_xla_metas(factor_seg_metas(sched, seg, dtype)))
        panels.extend(pseg)
        tiny = tiny + t
        nzero = nzero + z
    del upd_buf
    return BatchedLU(plan=plan, schedule=sched, dtype=dtype, b=B,
                     panels=[tuple(p) for p in panels],
                     tiny=np.asarray(tiny), nzero=np.asarray(nzero))


def per_sample_factorize(plan: FactorPlan, values: np.ndarray,
                         dtype=np.float64,
                         scaled: bool = False) -> StagedLU:
    """ONE value set factorized unbatched under the SHARED plan — the
    per-sample execution the bitwise contract pins batch_factorize
    against.  Note
    this is NOT models.gssvx.factorize on the member matrix: planning
    re-equilibrates from the member's values, so an independently
    planned factorization legitimately differs in roundoff the moment
    a row/column norm crosses a scale binade.  Plan sharing is the
    batching contract (plan_share.py) — the per-sample arm shares it
    too.  Raises factorize_device's typed ZeroDivisionError on an
    exactly-zero pivot."""
    from ..ops.batched import _staged_factor_run
    dtype = np.dtype(dtype)
    values = np.asarray(values).reshape(-1)
    sched = get_schedule(plan, 1)
    sv = values if scaled else batch_scaled_values(
        plan, values[None, :])[0]
    panels, tiny, nzero = _staged_factor_run(
        sched, np.asarray(sv), _thresh_for(plan, dtype), dtype)
    nz = int(np.asarray(nzero))
    if nz > 0:
        raise ZeroDivisionError(
            f"factorization hit {nz} exactly-zero pivot(s); the "
            "matrix is singular (enable replace_tiny_pivot to "
            "perturb instead)")
    return StagedLU(plan=plan, schedule=sched, dtype=dtype,
                    panels=[tuple(p) for p in panels],
                    tiny_pivots=int(np.asarray(tiny)))


# --------------------------------------------------------------------
# batched packed trisolve
# --------------------------------------------------------------------

_solve_fns_lock = threading.Lock()


def _solve_arm() -> str:
    """The batched-solve lowering arm: "scan" (default — one program,
    lax.scan over the member axis, every lane's ops at exact
    per-sample shapes, which is what makes the bitwise pin hold) or
    "vmap" (the MXU-dense arm: one batched dot per group).  Measured
    on XLA:CPU (tests/test_batch.py's pin): a dot_general whose batch
    dims are all 1 collapses to a plain dot with a DIFFERENT
    reduction order than the batched kernel, so the vmapped sweep
    drifts 1-2 ulp from per-sample execution on groups with trim==1 —
    scan is the arm the bitwise contract is pinned on; vmap stays
    available for dense-batch exploration on accelerators."""
    from .. import flags
    arm = flags.env_str("SLU_BATCH_SOLVE_MODE", "scan").strip().lower()
    return arm if arm in ("scan", "vmap") else "scan"


def _batch_solve_fns(sched, dtype):
    """Cached watched jits for the batched packed sweep on one
    (schedule, dtype): (notrans, trans), each `fn(panels, b)` with
    panels the B-leading per-group pytree and b (B, n, nrhs).  The
    member body is _solve_packed_fn's sweep verbatim (pack inside the
    member lane, where tracers are unbatched-shaped, so
    pack_panels_staged's pair discrimination stays valid)."""
    key = ("batch_solve", np.dtype(dtype).str, _solve_arm(),
           trisolve.merge_cells_limit(), trisolve.seg_cells_limit())
    cache = getattr(sched, "_batch_solve_fns", None)
    if cache is not None:
        fns = cache.get(key)
        if fns is not None:
            return fns
    with _solve_fns_lock:
        cache = getattr(sched, "_batch_solve_fns", None)
        if cache is None:
            cache = sched._batch_solve_fns = {}
        if key in cache:
            return cache[key]
        ts = trisolve.get_trisolve(sched)
        dt = np.dtype(dtype)
        arm = _solve_arm()

        def mk(trans):
            def member(p, bb):
                packs = trisolve.pack_panels_staged(ts, p)
                return trisolve.sweep(ts, packs, bb, dt, trans)

            @jax.jit
            def fn(panels, b):
                with jax.default_matmul_precision("float32"):
                    if arm == "vmap":
                        return jax.vmap(member)(panels, b)
                    _, ys = jax.lax.scan(
                        lambda c, px: (c, member(*px)), 0,
                        (panels, b))
                    return ys
            return obs.watch_jit("batch_solve", fn)

        cache[key] = (mk(False), mk(True))
        return cache[key]


def batch_solve_factor(blu: BatchedLU, bf, trans: bool = False):
    """Batched triangular solves in factor ordering: `bf` is
    (B, n, nrhs), returns (B, n, nrhs) — the _solve_device_common
    inner leg, B-wide.  Every lane is bitwise the per-sample packed
    sweep."""
    bf = np.asarray(bf)
    if bf.ndim != 3 or bf.shape[0] != blu.b or bf.shape[1] != blu.plan.n:
        raise ValueError(
            f"bf must be (B={blu.b}, n={blu.plan.n}, nrhs); got "
            f"{bf.shape}")
    xdt = np.promote_types(blu.dtype, bf.dtype)
    fns = _batch_solve_fns(blu.schedule, blu.dtype)
    fn = fns[1] if trans else fns[0]
    panels = tuple(tuple(p) for p in blu.panels)
    return fn(panels, jnp.asarray(bf.astype(xdt)))


def batch_solve(blu: BatchedLU, b, trans: bool = False) -> np.ndarray:
    """Full-system batched solve A_i·x_i = b_i: `b` is (B, n) or
    (B, n, nrhs); returns the matching shape.  The scaling/permutation
    embedding is models.gssvx.solve's algebra applied per lane
    (elementwise ops broadcast over the leading axis bitwise
    unchanged), so each lane equals the per-sample gssvx solve with
    refinement off."""
    from ..models.gssvx import perm_scale_vectors
    plan = blu.plan
    b = np.asarray(b)
    squeeze = b.ndim == 2
    bb = b[:, :, None] if squeeze else b
    if bb.shape[0] != blu.b or bb.shape[1] != plan.n:
        raise ValueError(
            f"b must be (B={blu.b}, n={plan.n}[, nrhs]); got {b.shape}")
    t = Trans.TRANS if trans else Trans.NOTRANS
    in_scale, in_perm, out_perm, out_scale = perm_scale_vectors(plan, t)
    bf = (bb * in_scale[None, :, None])[:, in_perm, :]
    y = np.asarray(batch_solve_factor(blu, bf, trans=trans))
    x = y[:, out_perm, :] * out_scale[None, :, None]
    return x[:, :, 0] if squeeze else x


# --------------------------------------------------------------------
# fan-out: batched members as ordinary residents
# --------------------------------------------------------------------

def member_factorization(blu: BatchedLU, i: int, a=None,
                         options: Options | None = None,
                         stats: Stats | None = None):
    """Member i as an ordinary LUFactorization resident — the exact
    handle models.gssvx.factorize builds, with the same post-steps
    (options pin, flop/byte accounting, perturbation ledger, memory
    watermarks, health ring) so the serve cache, store, fleet and
    flight layers cannot tell it was born batched.  Raises the typed
    per-member refusal for a singular member (the masked-member
    contract: one bad lane never blocks its siblings' fan-out)."""
    from ..models.gssvx import LUFactorization, effective_factor_dtype
    from ..numerics.ledger import build_ledger
    from ..obs import memory as obs_memory
    plan = blu.plan
    options = options or plan.options or Options()
    fdt = effective_factor_dtype(
        a.dtype if a is not None else blu.dtype, blu.dtype)
    if fdt.name != options.factor_dtype:
        options = options.replace(factor_dtype=fdt.name)
    stats = stats if stats is not None else Stats()
    slu = blu.member(i)         # raises the typed refusal if singular
    stats.tiny_pivots += int(slu.tiny_pivots)
    lu = LUFactorization(plan=plan, backend="jax", device_lu=slu,
                         a=a, stats=stats)
    lu.options = options
    stats.add_ops("FACT", plan.factor_flops)
    stats.lu_nnz = plan.lu_nnz()
    stats.lu_bytes = stats.lu_nnz * np.dtype(
        options.factor_dtype).itemsize
    lu.ledger = build_ledger(lu)
    mem = obs_memory.watermarks(lu, phase="FACT")
    stats.mem_watermarks = mem
    obs.HEALTH.record_factor(
        tiny_pivots=int(slu.tiny_pivots),
        pivot_growth=(obs.pivot_growth(lu) if obs.enabled() else None),
        dtype=options.factor_dtype,
        perturbation=(lu.ledger.to_dict() if lu.ledger.perturbed
                      else None),
        mem=mem)
    stats.note_factor_event(tiny_pivots=int(slu.tiny_pivots),
                            dtype=options.factor_dtype, mem=mem)
    return lu


# --------------------------------------------------------------------
# HLO contract registry declarations (tools/slulint/contracts.py)
# --------------------------------------------------------------------

_contract_state: dict = {}


def _contract_fixture():
    """Shared (a, plan, sched) for the two contract builders: one
    symbolic plan serves both lowerings (check_all runs them
    back-to-back in tier-1, and planning is the dominant build
    cost)."""
    if "fix" not in _contract_state:
        from ..utils.testmat import laplacian_3d
        from .plan_share import shared_plan
        a = laplacian_3d(6)     # n=216: a real multi-segment
        plan = shared_plan(a, Options(factor_dtype="float32"))
        _contract_state["fix"] = (a, plan, get_schedule(plan, 1))
    return _contract_state["fix"]


def _contract_build_factor_segment():
    """Lower the vmapped factor segment at a representative (B=4)
    signature: donation and the sorted/unique assembly-scatter
    promise must survive jax.vmap lowering (a batching rule that
    re-materialized the donated buffer or dropped the scatter hints
    would silently double the engine's memory/scatter cost)."""
    a, plan, sched = _contract_fixture()
    dtype = np.dtype(np.float32)
    seg = get_factor_segments(sched)[0]
    ops = [sched.groups[i].dev(squeeze=True)[:4] for i in seg]
    B = 4
    svals = batch_scaled_values(plan, np.tile(a.data, (B, 1)))
    vals_ext = _batch_vals_ext(jnp.asarray(svals), dtype.str)
    upd_buf = jnp.zeros((B, sched.upd_total + sched.upd_pad), dtype)
    thresh = jnp.asarray(_thresh_for(plan, dtype), dtype=dtype)
    args = (upd_buf, vals_ext, thresh,
            tuple(o[0] for o in ops), tuple(o[1] for o in ops),
            tuple(o[2] for o in ops), tuple(o[3] for o in ops),
            tuple(jnp.asarray(sched.groups[i].upd_off_global,
                              jnp.int64) for i in seg))
    kwargs = {"metas": _xla_metas(factor_seg_metas(sched, seg, dtype))}
    return _batched_factor_segment, args, kwargs


def _contract_build_trisolve():
    """Lower the vmapped packed sweep at B=4, nrhs=1: the batched
    solve program must stay scatter-free under vmap exactly like its
    per-sample twin (trisolve's no_scatter contract) — vmap batching
    of dynamic_update_slice must not lower back to scatter.  Panel
    operands are jax.eval_shape avals of the factor chain (lowering
    needs shapes, not numerics), so this build traces the factor
    segments without ever compiling or running them."""
    a, plan, sched = _contract_fixture()
    dtype = np.dtype(np.float32)
    B = 4

    def factor_panels(vals):
        vals_ext = _batch_vals_ext(vals, dtype.str)
        thresh = jnp.asarray(_thresh_for(plan, dtype), dtype=dtype)
        upd_buf = jnp.zeros((B, sched.upd_total + sched.upd_pad),
                            dtype)
        panels = []
        for seg in get_factor_segments(sched):
            ops = [sched.groups[i].dev(squeeze=True)[:4] for i in seg]
            upd_buf, pseg, _t, _z = _batched_factor_segment(
                upd_buf, vals_ext, thresh,
                tuple(o[0] for o in ops), tuple(o[1] for o in ops),
                tuple(o[2] for o in ops), tuple(o[3] for o in ops),
                tuple(jnp.asarray(sched.groups[i].upd_off_global,
                                  jnp.int64) for i in seg),
                metas=_xla_metas(factor_seg_metas(sched, seg, dtype)))
            panels.extend(pseg)
        return tuple(tuple(p) for p in panels)

    panels = jax.eval_shape(
        factor_panels,
        jax.ShapeDtypeStruct((B, a.data.size), np.float64))
    fn = _batch_solve_fns(sched, dtype)[0]
    b_aval = jax.ShapeDtypeStruct((B, plan.n, 1), np.float32)
    return fn, (panels, b_aval), {}


HLO_CONTRACTS = (
    {"name": "batch.factor_segment",
     "phase": "batch_factor",
     "env": {},
     "contracts": ("donation_honored", "assembly_scatter_promised",
                   "no_host_callback"),
     "build": _contract_build_factor_segment,
     "note": "the vmapped merged factor segment: donation of the "
             "(B, upd) extend-add buffer and the sorted/unique "
             "scatter promises must survive jax.vmap lowering — the "
             "engine's memory story is B·upd_total resident, not "
             "2B·upd_total"},
    {"name": "batch.trisolve",
     "phase": "batch_solve",
     "env": {"SLU_TRISOLVE": "merged"},
     "contracts": ("no_scatter", "no_host_callback"),
     "build": _contract_build_trisolve,
     "note": "the vmapped packed lsum sweep stays scatter-free under "
             "vmap: batched dynamic_update_slice must lower as "
             "(batched) DUS, never as scatter — the serve "
             "coalescer's solve leg prices like the per-sample hot "
             "path, B-wide"},
)
