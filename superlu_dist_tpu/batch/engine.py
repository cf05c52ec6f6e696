"""The vmapped factor/solve engine: B same-pattern systems, one trace.

`batch_factorize` vmaps the level-merged donated-buffer factor
segments (ops/batched._staged_factor_segment's member bodies) over a
leading batch axis: one schedule, one compile per (segment, B), B
value sets streaming through one donated (B, upd) extend-add buffer.
The host casts the values once; Dr·A·Dc runs in the program's
prologue.  `batch_solve` batches the packed lsum trisolve
(ops/trisolve.sweep over the PR 7 PackSet layout, packed B-wide when
the handle is born) over batched B/UPD/XF buffers: member-parallel
under jax.vmap on an accelerator, one lax.scan program over the
member axis on XLA:CPU (see _solve_arm: XLA:CPU's batch-collapsed dot
kernels reassociate at batch-dim 1, so the vmap-dense solve arm
drifts 1-2 ulp there on trim==1 groups; scan keeps every lane's ops
at exact per-sample shapes).  On XLA:CPU both legs are pinned bitwise
equal to per-sample execution at fp64 (tests/test_batch.py).  Under
the handle's options `batch_solve` refines every member to the
one-system guarantee: the residual of all members is the host's, in
the refine dtype, one pass over the values in the native library
(models/refine.BatchResidual: a TPU's float64 is two float32 words
and cannot hold the guarantee), the stopping rule is pdgsrfs's on
each member's own berr (tests/test_batch_refine.py).

The Pallas panel LU is force-disabled under the batch traces
(`force_xla=True` through _factor_group_impl): a pallas_call's
batching rule is not a path we certify.  The XLA lowering is the pinned
arm; a certified batched-Pallas arm is future work (GPU arm, ROADMAP
item 2).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..options import IterRefine, Options, Trans
from ..ops.batched import (StagedLU, _factor_group_impl, _real_dtype,
                           _thresh_for, factor_seg_metas,
                           get_factor_segments, get_schedule)
from ..models.refine import _refine_dtype
from ..ops import trisolve
from ..plan.plan import FactorPlan
from ..precision.policy import sweep_operand_dtype
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


@functools.partial(jax.jit, static_argnames=("metas", "scaled"),
                   donate_argnums=(0,))
def _batched_factor_segment_jit(upd_buf, vals, row_scale, col_scale,
                                thresh, a_srcs, a_dsts, one_dsts,
                                ea_blockss, upd_offs, *, metas,
                                scaled=False):
    """One merged factor segment vmapped over the batch: `upd_buf`
    (B, upd_total+pad) is donated and streams through the segment
    chain in place B-wide; `vals` is (B, nnz) in the factor dtype, as
    the host cast it.  The prologue applies Dr·A·Dc in that dtype
    (`row_scale` / `col_scale` are the plan's scalings gathered to the
    COO order; row first, THEN column: plan.scaled_values' order, so
    a float64 batch is bitwise `plan_share.batch_scaled_values`) and
    appends the zero a pad slot reads; `scaled` skips the product for
    a caller that pre-scaled.  The member body is
    _staged_factor_segment's, verbatim, with force_xla=True — the
    static `metas` key is the SAME factor_seg_metas product the
    unbatched arm's dispatch/warmup share (Pallas leg normalized by
    _xla_metas), so the program set is warmable per B rung exactly
    like the unbatched arm's."""
    def member(upd_buf, vals):
        dtype = upd_buf.dtype
        z32 = jnp.zeros((), jnp.int32)
        with jax.named_scope("slu.batch.scale"):
            if not scaled:
                vals = (vals * row_scale) * col_scale
            vals = jnp.concatenate([vals, jnp.zeros((1,), dtype)])
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


def _coo_scales(plan: FactorPlan, sched, dtype):
    """The plan's row and column scalings gathered to its COO order,
    on the device in the factor dtype: the factor program's prologue
    multiplies by them."""
    def build():
        return (jnp.asarray(plan.row_scale[plan.coo_rows], dtype),
                jnp.asarray(plan.col_scale[plan.coo_cols], dtype))
    return trisolve._sched_fn(
        sched, ("batch_scales", np.dtype(dtype).str), build)


def _segment_operands(plan: FactorPlan, sched, seg, dtype):
    """What one factor segment takes beside the donated buffer and the
    values: its positional operands (the scalings, the tiny-pivot
    threshold, the groups' index arrays) and its static `metas`."""
    ops = [sched.groups[i].dev(squeeze=True)[:4] for i in seg]
    args = (*_coo_scales(plan, sched, dtype),
            jnp.asarray(_thresh_for(plan, dtype),
                        dtype=_real_dtype(dtype)),
            tuple(o[0] for o in ops), tuple(o[1] for o in ops),
            tuple(o[2] for o in ops), tuple(o[3] for o in ops),
            tuple(jnp.asarray(sched.groups[i].upd_off_global,
                              jnp.int64) for i in seg))
    return args, _xla_metas(factor_seg_metas(sched, seg, dtype))


# a block of the staging cast: small enough that the allocator hands
# a freed one back (glibc keeps blocks up to 32 MiB in its heap)
_STAGE_BLOCK_BYTES = 16 << 20


def _stage(values: np.ndarray, dtype) -> jax.Array:
    """The caller's (B, nnz) values on the device in the factor
    dtype: cast by the host, in row blocks.  A cast of the whole stack
    is a fresh array the kernel pages in anew every call (70 MB at
    2,048 x 8,554 float32: most of the cast's seconds); a block is
    memory the process already has."""
    rows = max(1, _STAGE_BLOCK_BYTES
               // (values.shape[1] * np.dtype(dtype).itemsize))
    if values.dtype == dtype or rows >= len(values):
        return jnp.asarray(values.astype(dtype, copy=False))
    return jnp.concatenate([
        jnp.asarray(values[i:i + rows].astype(dtype))
        for i in range(0, len(values), rows)])


def _factor_run(plan: FactorPlan, sched, vals, dtype,
                scaled: bool = False):
    """The factor segments in order on a (B, nnz) stack in the factor
    dtype: (panels, tiny, nzero), nothing waited for."""
    B = vals.shape[0]
    upd_buf = jnp.zeros((B, sched.upd_total + sched.upd_pad), dtype)
    panels = []
    tiny = nzero = jnp.zeros((B,), jnp.int32)
    for seg in get_factor_segments(sched):
        args, metas = _segment_operands(plan, sched, seg, dtype)
        upd_buf, pseg, t, z = _batched_factor_segment(
            upd_buf, vals, *args, metas=metas, scaled=bool(scaled))
        panels.extend(tuple(p) for p in pseg)
        tiny = tiny + t
        nzero = nzero + z
    return panels, tiny, nzero


@dataclasses.dataclass
class BatchedLU:
    """B same-plan factorizations in batched per-group panels: each
    panel flat carries a leading B axis over the StagedLU layout.
    `member(i)` slices an ordinary StagedLU back out — downstream
    layers (serve cache, store, fleet) never learn the factors were
    born batched.  The handle holds its solve mirror from birth
    (`packs`: the lsum layout of every member, dispatched on the
    factor program's output futures), the caller's `options`, and
    for the refinement residual the caller's UNSCALED `values`, as a
    one-system handle keeps `lu.a`."""
    plan: FactorPlan
    schedule: object            # ops.batched.BatchedSchedule
    dtype: np.dtype
    b: int
    panels: list                # per group (L, U, Li, Ui), leading B
    tiny: np.ndarray            # (B,) tiny-pivot replacement counts
    nzero: np.ndarray           # (B,) exact-zero pivot counts
    packs: object = None        # trisolve.PackSet, leading B
    options: Optional[Options] = None
    values: Optional[np.ndarray] = None     # (B, nnz), unscaled
    # the values cast to a refine dtype that is not their own
    refine_cache: dict = dataclasses.field(default_factory=dict,
                                           repr=False, compare=False)

    @property
    def effective_options(self) -> Options:
        """The caller's options; a handle made without any solves
        unrefined (what `batch_solve` did before it refined)."""
        if self.options is not None:
            return self.options
        return (self.plan.options or Options()).replace(
            iter_refine=IterRefine.NOREFINE,
            factor_dtype=self.dtype.name)

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
        """Device bytes of the factors and of the solve mirror."""
        return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(
            (self.panels, self.packs)))


def _refines(blu: BatchedLU) -> bool:
    """Does a solve on this handle refine?  Its options say, and it
    needs the unscaled values (a one-system handle's `lu.a`)."""
    return (blu.effective_options.iter_refine != IterRefine.NOREFINE
            and blu.values is not None)


def batch_factorize(plan: FactorPlan, values: np.ndarray,
                    dtype=np.float64, scaled: bool = False,
                    options: Options | None = None) -> BatchedLU:
    """Numeric factorization of B same-pattern value sets against one
    plan: `values` is (B, nnz) in the plan's COO order (raw values by
    default; `scaled=True` skips the Dr·A·Dc product for callers that
    pre-scaled).  `options` are the caller's, as `factorize` takes
    them: their `factor_dtype` is the factors' (and `dtype` is not
    read), and the handle keeps them for `batch_solve`.  The host
    casts the values to the factor dtype once (`slu.batch.stage`);
    the scaling runs in the factor program's prologue in that dtype
    (`batch_scaled_values` is the float64 oracle: equal bitwise for
    float64 factors, to a few roundings of a narrower factor dtype).
    Returns a BatchedLU; per-member singularity reports through
    `nzero`/`member_status()` instead of raising — a singular member
    must not poison its siblings (callers refuse per index)."""
    dtype = np.dtype(options.factor_dtype if options is not None
                     else dtype)
    if dtype.kind == "c":
        raise NotImplementedError(
            "batch_factorize is real-dtype only: the complex lanes "
            "keep the per-group pair dispatch (ops/batched.py) — "
            "factor members sequentially instead")
    values = np.asarray(values)
    if values.ndim != 2 or values.shape[1] != len(plan.coo_rows):
        raise ValueError(
            f"values must be (B, nnz={len(plan.coo_rows)}); got "
            f"{values.shape}")
    B = int(values.shape[0])
    if B < 1:
        raise ValueError("empty batch")
    sched = get_schedule(plan, 1)
    with obs.span("FACT", args={"B": B}):
        with obs.span("batch.stage", cat="fact"):
            vals = _stage(values, dtype)
        panels, tiny, nzero = _factor_run(plan, sched, vals, dtype,
                                          scaled)
        # the solve mirror, dispatched on the factor program's output
        # futures BEFORE the blocking reads of the counts below
        # (factorize_device's order), so the host hands out its
        # buffers while the chip factors
        with obs.span("solve.pack", cat="solve",
                      args={"groups": len(sched.groups),
                            "programs": 1, "at": "factor"}):
            packs = _batch_pack_fn(sched)(tuple(panels))
        blu = BatchedLU(plan=plan, schedule=sched, dtype=dtype, b=B,
                        panels=panels, tiny=np.asarray(tiny),
                        nzero=np.asarray(nzero), packs=packs,
                        options=options,
                        values=None if scaled else values)
    obs.HEALTH.record_factor(
        tiny_pivots=int(blu.tiny.sum()), dtype=dtype.name,
        flops={"useful": B * plan.factor_flops,
               "executed": B * sched.executed_flops},
        extend_add=sched.ea_elements,
        gesp=dict(getattr(plan, "gesp", None) or {}),
        pack="at_factor",
        route={"dispatch": "batch", "groups": len(sched.groups),
               "segments": len(get_factor_segments(sched)),
               "batch_members": B})
    return blu


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

def _solve_arm(backend: str | None = None) -> str:
    """The batched sweep's lowering, by where it runs.  On XLA:CPU
    "scan": one program, lax.scan over the member axis, every lane's
    ops at exact per-sample shapes, which is what makes the bitwise
    pin hold there (a dot_general whose batch dims are all 1
    collapses to a plain dot with a DIFFERENT reduction order than
    the batched kernel, so the member-parallel sweep drifts 1-2 ulp
    from per-sample execution on groups with trim==1:
    tests/test_batch_refine.py states the tolerance).  On every other
    backend "vmap", the member-parallel arm: one batched dot a group,
    B members wide, where a scan would run B sweeps one after
    another."""
    return ("scan" if (backend or jax.default_backend()) == "cpu"
            else "vmap")


class _Work:
    """The refinement loop's host buffers at one (B, n, nrhs): the
    trial answer, the residual and its trial, the sweep's operand in
    the factor's precision.  Kept between solves because new memory
    of this size is paged in by the kernel every time it is asked for
    (16 MB an array at 2,048 x 992 float64, some 4 us a page on the
    chip's host: more than the arithmetic on it)."""

    def __init__(self, shape, rdt, odt):
        self.x = np.empty(shape, rdt)
        self.r = (np.empty(shape, rdt), np.empty(shape, rdt))
        self.op = np.empty(shape, odt)


def _straggler_rung(members: int) -> int:
    """The width of a straggler pass: once no more than a 128th of
    the batch is still refining, a pass sweeps those members alone at
    this one width (fixed shapes: one more program, compiled with the
    others), so the batch's last members cost it a sweep of the rung
    and their own residual, not a pass of all.  Why a 128th: members
    that ask a pass more than the rest are about one in a thousand
    (one to three of 2,048 on the collision-operator batch), and the
    rung's cost is the gather of its members' packs.  0 for a batch
    too small to have one."""
    return members // 128


def _batch_pack_fn(sched):
    """The pack program, B-wide: `trisolve.pack_panels_staged` under
    vmap (pure slices and reshapes: a member's packs are bitwise the
    per-sample pack's)."""
    def build():
        ts = trisolve.get_trisolve(sched)

        @jax.jit
        def slu_batch_pack(panels):
            return trisolve.PackSet(jax.vmap(
                lambda p: tuple(trisolve.pack_panels_staged(ts, p))
            )(panels))

        return obs.watch_jit("batch_solve", slu_batch_pack)

    return trisolve._sched_fn(
        sched, ("batch_pack", trisolve.merge_cells_limit(),
                trisolve.seg_cells_limit()), build)


def _batch_solve_fns(sched, dtype, arm: str | None = None):
    """Cached watched jits for the batched packed sweep on one
    (schedule, dtype, arm), for NOTRANS and TRANS: each a triple
    `(raw, full, some)`.  `raw(packs, bf)` sweeps `bf` (B, n, nrhs)
    given in factor ordering; `full(packs, b, in_scale, in_perm,
    out_perm, out_scale)` is models.gssvx.solve's embedding around
    it, on the device (`perm_scale_vectors`: scale, permute, sweep,
    permute and scale the answer back, all in the dtype `b` comes in,
    which the host has cast to the factor's precision: elementwise
    products and gathers, for float64 factors per lane bitwise the
    host's); `some(packs, sel, b, ...)` is `full` on the members
    `sel` alone (`b` is (len(sel), n, nrhs); their packs are gathered
    on the device: a lane is bitwise its lane of `full`).
    The member body is `trisolve.sweep` verbatim.
    `arm` is `_solve_arm()`'s unless a test names the other."""
    arm = arm or _solve_arm()
    dt = np.dtype(dtype)

    def build():
        ts = trisolve.get_trisolve(sched)

        def mk(trans):
            def sweeps(packs, bf):
                def member(p, bb):
                    return trisolve.sweep(ts, p, bb, dt, trans)

                with jax.default_matmul_precision("float32"):
                    if arm == "vmap":
                        return jax.vmap(member)(packs, bf)
                    _, ys = jax.lax.scan(
                        lambda c, px: (c, member(*px)), 0,
                        (packs, bf))
                    return ys

            def full(packs, b, in_scale, in_perm, out_perm,
                     out_scale):
                bf = jnp.take(b * in_scale.astype(b.dtype)[None, :, None],
                              in_perm, axis=1)
                y = sweeps(packs, bf)
                return (jnp.take(y, out_perm, axis=1)
                        * out_scale.astype(y.dtype)[None, :, None])

            def some(packs, sel, b, *vecs):
                return full(jax.tree_util.tree_map(
                    lambda a: jnp.take(a, sel, axis=0), packs), b, *vecs)

            return tuple(obs.watch_jit("batch_solve", jax.jit(f))
                         for f in (sweeps, full, some))

        return (mk(False), mk(True))

    return trisolve._sched_fn(
        sched, ("batch_solve", dt.str, arm,
                trisolve.merge_cells_limit(),
                trisolve.seg_cells_limit()), build)


def batch_solve_factor(blu: BatchedLU, bf, trans: bool = False):
    """Batched triangular solves in factor ordering: `bf` is
    (B, n, nrhs), returns (B, n, nrhs) — the _solve_device_common
    inner leg, B-wide.  Every lane is bitwise the per-sample packed
    sweep on XLA:CPU (`_solve_arm`)."""
    bf = np.asarray(bf)
    if bf.ndim != 3 or bf.shape[0] != blu.b or bf.shape[1] != blu.plan.n:
        raise ValueError(
            f"bf must be (B={blu.b}, n={blu.plan.n}, nrhs); got "
            f"{bf.shape}")
    xdt = np.promote_types(blu.dtype, bf.dtype)
    raw = _batch_solve_fns(blu.schedule, blu.dtype)[int(trans)][0]
    return raw(blu.packs, jnp.asarray(bf.astype(xdt)))


def _embedding(blu: BatchedLU, trans: bool):
    """`perm_scale_vectors` of the plan as device arrays."""
    from ..models.gssvx import perm_scale_vectors

    def build():
        return tuple(jnp.asarray(v) for v in perm_scale_vectors(
            blu.plan, Trans.TRANS if trans else Trans.NOTRANS))
    return trisolve._sched_fn(
        blu.schedule, ("batch_embedding", bool(trans)), build)


def _residual(blu: BatchedLU, trans: bool):
    """The host residual of the plan's pattern (of its transpose),
    built once a schedule: `models/refine.BatchResidual`."""
    from ..models.refine import BatchResidual
    return trisolve._sched_fn(
        blu.schedule, ("batch_residual", bool(trans)),
        lambda: BatchResidual(blu.plan, trans))


def batch_solve(blu: BatchedLU, b, trans: bool = False,
                stats: Stats | None = None) -> np.ndarray:
    """Full-system batched solve A_i·x_i = b_i: `b` is (B, n) or
    (B, n, nrhs); returns the matching shape.  The scaling/permutation
    embedding is models.gssvx.solve's algebra applied per lane, and so
    is the refinement: under the handle's options (`iter_refine`,
    `refine_dtype`, `max_refine_steps`) every member is refined by
    pdgsrfs's rule on its own berr (stop at berr <= eps or when a
    pass gains less than half), passes run while any member is live
    (of all members while many are, of the live alone once they fit
    `_straggler_rung`), and the answer comes back once, in the
    refine dtype.  `stats` takes the per-member outcome
    (`Stats.batch`: `berr`, `refine_steps`, `stalled`, `missed`, the
    indices of the members that have a zero pivot or ended outside
    the refinement contract's class, 64 eps: their siblings are
    untouched; and `passes`, each pass's live members and width).
    Refinement off (a handle made without options, or NOREFINE):
    each lane equals the per-sample gssvx solve with refinement
    off."""
    plan = blu.plan
    opts = blu.effective_options
    stats = stats if stats is not None else Stats()
    b = np.asarray(b)
    squeeze = b.ndim == 2
    bb = b[:, :, None] if squeeze else b
    if bb.shape[0] != blu.b or bb.shape[1] != plan.n:
        raise ValueError(
            f"b must be (B={blu.b}, n={plan.n}[, nrhs]); got {b.shape}")
    trans = bool(trans) or opts.trans != Trans.NOTRANS
    arm = _solve_arm()
    _, full, some = _batch_solve_fns(blu.schedule, blu.dtype,
                                     arm)[int(trans)]
    vecs = _embedding(blu, trans)
    refine = _refines(blu)
    stats.dispatch.update(batch_members=blu.b, batch_sweep_arm=arm,
                          batch_residual="host" if refine else None)
    sweeps = {}

    def sweep(v, rows=None, into=None):
        # every sweep's operand in the FACTOR's precision (gssvx.solve's
        # rule), cast by the host (`into` a kept buffer, where the
        # caller has one): half the bytes cross, and a TPU never
        # sees a float64.  `rows`: the members of `v`, where they are
        # not all (a straggler pass, at the rung's width)
        odt = sweep_operand_dtype(blu.dtype, v.dtype)
        if into is None or odt == v.dtype:
            op = v.astype(odt, copy=False)
        else:
            op = into
            np.copyto(op, v, casting="same_kind")
        for count in (sweeps, stats.sweeps):
            count[op.dtype.name] = count.get(op.dtype.name, 0) + 1
        with obs.span("solve.sweep", cat="solve",
                      args={"nrhs": bb.shape[2], "trans": int(trans),
                            "B": len(op)}):
            if rows is None:
                y = full(blu.packs, jnp.asarray(op), *vecs)
            else:
                y = some(blu.packs, jnp.asarray(rows, jnp.int32),
                         jnp.asarray(op), *vecs)
            with obs.span("solve.fetch", cat="solve"):
                return np.asarray(y)

    def answer(x):
        return x[:, :, 0] if squeeze else x

    if not refine:
        with stats.timer("SOLVE"):
            return answer(np.array(sweep(bb), dtype=np.promote_types(
                blu.dtype, bb.dtype)))

    from ..models.gssvx import _ESC_BERR_SLACK
    from ..precision.policy import refine_eps
    rdt = _refine_dtype(opts, np.promote_types(blu.values.dtype,
                                               bb.dtype))
    eps = refine_eps(rdt)
    residual = _residual(blu, trans)
    vals = blu.values
    if vals.dtype != rdt:       # a narrower accumulator: cast once
        vals = blu.refine_cache.setdefault(
            np.dtype(rdt).str, vals.astype(rdt))
    bk = np.ascontiguousarray(bb, dtype=rdt)
    odt = np.dtype(sweep_operand_dtype(blu.dtype, rdt))
    rung = _straggler_rung(blu.b)
    if rung:
        # the rung's program exists before a pass needs it: compiled,
        # and run once on zeros, at the first solve of these shapes
        trisolve._sched_fn(
            blu.schedule,
            ("batch_rung", blu.dtype.str, odt.str, arm, trans, blu.b,
             rung, bb.shape[2]),
            lambda: jax.block_until_ready(some(
                blu.packs, jnp.zeros(rung, jnp.int32),
                jnp.zeros((rung,) + bb.shape[1:], odt), *vecs))
            is not None)
    # the loop's host buffers, kept on the schedule between solves (a
    # concurrent solve finds the pool empty and makes its own): the
    # answer alone is new memory a solve
    pool = trisolve._sched_fn(
        blu.schedule, ("batch_work", bk.shape, np.dtype(rdt).str,
                       odt.str), list)
    work = pool.pop() if pool else _Work(bk.shape, rdt, odt)
    x, x_new, (r, r_new) = np.empty(bk.shape, rdt), work.x, work.r
    with stats.timer("SOLVE"):
        np.copyto(x, sweep(bk, into=work.op))
    with stats.timer("REFINE"):
        # models/refine.iterative_refine, member by member: pdgsrfs's
        # rule on each member's own berr; a member that is not live
        # keeps its answer.  A pass sweeps all members (fixed shapes)
        # while more than the rung are live, and the live alone, at
        # the rung's width, from there: what a straggler costs the
        # batch is a sweep of the rung and its own residual
        r, berr = residual(vals, x, bk, out=r)
        live = berr > eps
        steps = np.zeros(blu.b, np.int64)
        stalled = np.zeros(blu.b, bool)
        passes = []
        while live.any() and steps.max() < opts.max_refine_steps:
            rows = np.flatnonzero(live)
            few = len(rows) <= rung
            passes.append((len(rows), rung if few else blu.b))
            with obs.span("REFINE_STEP",
                          args={"berr": float(np.max(berr[rows])),
                                "live": len(rows)}):
                if few:
                    sel = np.resize(rows, rung)     # padded by repeats
                    x_few = x[rows] + sweep(r[sel], sel)[:len(rows)]
                    r_few, berr_new = residual(vals[rows], x_few,
                                               bk[rows])
                else:
                    np.add(x, sweep(r, into=work.op), out=x_new)
                    r_new, berr_new = residual(vals, x_new, bk,
                                               out=r_new)
            steps[rows] += 1
            if few:
                stall = ~(berr_new < 0.5 * berr[rows])  # NaN stalls
                take = ~stall | (berr_new < berr[rows])
                at = rows[take]
                x[at], r[at], berr[at] = (x_few[take], r_few[take],
                                          berr_new[take])
                stalled[rows] |= stall
                live[rows] = ~stall & (berr[rows] > eps)
                continue
            stall = live & ~(berr_new < 0.5 * berr)
            take = live & (~stall | (berr_new < berr))
            if take.all():
                x, x_new, r, r_new = x_new, x, r_new, r
                berr = berr_new
            else:
                x[take], r[take] = x_new[take], r_new[take]
                berr[take] = berr_new[take]
            stalled |= stall
            live = live & ~stall & (berr > eps)
    work.x, work.r = x_new, (r, r_new)
    pool.append(work)
    x = answer(x)
    stalled &= ~(berr <= eps)
    # who missed: a zero pivot, or a berr outside the refinement
    # contract's class (gssvx's escalation guard: 64 eps; NaN too)
    missed = np.flatnonzero(~blu.ok_mask()
                            | ~(berr <= _ESC_BERR_SLACK * eps))
    stats.batch = {"berr": berr, "refine_steps": steps,
                   "stalled": stalled, "missed": missed,
                   "passes": passes}
    stats.berr = float(np.max(berr))
    stats.refine_steps += int(steps.max())
    stats.refine_stalled = bool(stalled.any())
    obs.HEALTH.record_refine(
        berr=stats.berr, steps=int(steps.max()),
        converged=bool((berr <= eps).all()),
        stalled=stats.refine_stalled, sweeps=sweeps,
        sweep_segments=1, sweep_arm=arm, members=blu.b,
        members_stalled=int(stalled.sum()))
    return x


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
    B = 4
    vals = jnp.asarray(np.tile(a.data, (B, 1)), dtype)
    upd_buf = jnp.zeros((B, sched.upd_total + sched.upd_pad), dtype)
    args, metas = _segment_operands(plan, sched, seg, dtype)
    return (_batched_factor_segment, (upd_buf, vals, *args),
            {"metas": metas})


def _contract_build_trisolve():
    """Lower the batched packed sweep at B=4, nrhs=1, on XLA:CPU's
    arm (the scan over members): the batched solve program must stay
    scatter-free exactly like its per-sample twin (trisolve's
    no_scatter contract).  The member-parallel arm cannot be held to
    it at this level: jax's batching rule writes every
    dynamic_update_slice under vmap as a scatter of one index, which
    the TPU compiler folds back (the compiled program of the cell
    xgc_coll992_b2048.bstep holds none: PERF.md section 6, PR 48).
    Pack operands are jax.eval_shape avals of the factor chain and
    the pack program (lowering needs shapes, not numerics), so this
    build traces the factor segments without ever compiling or
    running them."""
    a, plan, sched = _contract_fixture()
    dtype = np.dtype(np.float32)
    B = 4

    def factor_packs(vals):
        panels, _t, _z = _factor_run(plan, sched, vals, dtype)
        return _batch_pack_fn(sched)(tuple(panels))

    packs = jax.eval_shape(
        factor_packs,
        jax.ShapeDtypeStruct((B, a.data.size), np.float32))
    fn = _batch_solve_fns(sched, dtype)[0][0]
    b_aval = jax.ShapeDtypeStruct((B, plan.n, 1), np.float32)
    return fn, (packs, b_aval), {}


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
     "note": "the batched packed lsum sweep (XLA:CPU's arm, the scan "
             "over members) stays scatter-free: the batched solve "
             "leg prices like the per-sample hot path, B-wide"},
)
