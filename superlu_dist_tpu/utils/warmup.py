"""Parallel compile warmup for staged execution.

Staged mode (ops/batched.py, `SLU_STAGED`) bounds compile by building
one cached program per distinct group signature — but a cold start
still compiles them SEQUENTIALLY, in dispatch order, on one core
(measured: ~13 min at the k=64 3D Laplacian on a 1-core host).  XLA
releases the GIL during compilation, so a thread pool compiles
signatures concurrently on multi-core hosts.  What is warmed is what a
staged handle dispatches: the factor segments (or groups), and for
the sweeps the ONE packed solve program of the merged arm
(`jit_slu_solve_packed`, as on every other handle) or the legacy
arm's program a group each way.  The warmed programs are
reused at two levels, both verified by tests/test_warmup.py:

- SAME process: `.lower().compile()` populates the in-memory pjit
  executable cache, so the subsequent dispatch reuses the executables
  directly (no persistent-cache read, no deserialization).
- LATER process: the artifacts land in the PERSISTENT compilation
  cache (placed by utils/cache.place_compile_cache —
  chip_smoke.py and the test conftest call it) and a fresh process's dispatch hits that
  cache instead of the compiler (measured 38/38 signature hits):
  prime the cache once, dispatch fast in every later process that is
  handed the same cache directory.

This is the analog of the reference's one-time symbolic/setup phases
being separable from the numeric phase: plan once, warm once, then
every `SamePattern` refactorization is dispatch-only.

Usage:
    plan = plan_factorization(a, opts)
    report = warmup_staged(plan, dtype="float32", nrhs=1)
    # ... factorize/solve as usual; compiles are now cache hits
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

# Trace/lower under a lock; compile in parallel.  Concurrent .lower()
# calls race on jax's GLOBAL inner-jit trace cache: two threads
# tracing different outer signatures that both call the same inner
# jit (_where, diagonal, ... inside the group bodies) can each trace
# it, and the loser embeds an equal-but-NOT-IDENTICAL sub-jaxpr
# object in its outer jaxpr.  The per-module lowering cache dedupes
# by object identity, so the raced module lowers DUPLICATE private
# helper funcs (observed: 6 extra @_where_N) and shifts every
# subsequent symbol number — same semantics, different serialized
# bytes, DIFFERENT persistent-cache key than the sequential dispatch
# computes (the 1-of-38 intermittent warm-key mismatch de-flaked in
# PR 5 and chased here).  Lowering is GIL-bound Python anyway; the
# multi-core win of this module is XLA compilation, which releases
# the GIL — serializing the lower phase costs nothing measurable and
# makes warm keys deterministic.
_LOWER_LOCK = threading.Lock()


def staged_signatures(sched, dtype="float32"):
    """The distinct (static-args + operand-aval) signatures of the
    staged factor programs and of the sweep programs a staged
    handle's solve dispatches — what the jit executable cache
    is actually keyed by.  Returns (factor_sigs, sweep_sigs) dicts
    mapping signature -> a representative GroupSpec (or a segment
    index under the merged factor arm).  Under the merged trisolve
    arm a sweep is ONE program whatever the handle's form
    (`trisolve.solve_packed`), so `sweep_sigs` holds its one key, the
    operand avals of the pack in group order.  `dtype` is the FACTOR
    dtype the dispatch will use: complex factorizations keep the
    per-group dispatch (batched._staged_factor_run), so their factor
    keys stay per-group even when the merged arm is on."""
    import jax

    def aval(x):
        # shape/dtype only — no np.asarray, which would copy every
        # device index array to the host just to read metadata
        return (tuple(x.shape), str(x.dtype))

    def ea_avals_of(ea_blocks):
        return tuple(jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(
                aval, ea_blocks, is_leaf=lambda x: hasattr(x, "dtype"))))

    fsigs, ssigs = {}, {}
    for g in sched.groups:
        a_src, a_dst, one_dst, ea_blocks, _pos, ci, si = \
            g.dev(squeeze=True)
        fkey = (g.mb, g.wb, g.n_loc, g.ea_meta, g.eb_meta,
                aval(a_src), aval(a_dst), aval(one_dst),
                ea_avals_of(ea_blocks))
        fsigs.setdefault(fkey, g)
        skey = (g.mb, g.wb, g.n_loc, aval(ci), aval(si))
        ssigs.setdefault(skey, g)
    from ..ops import batched as B
    if B.factor_merge_on() and np.dtype(dtype).kind != "c":
        # the level-merged factor arm dispatches one program per
        # SEGMENT (batched._staged_factor_segment) — warm THOSE, not
        # the legacy per-group factor programs.  The static half of
        # the key is the shared factor_seg_metas definition (pallas
        # promotion included, resolved for float32 — uniform across a
        # warmup pass like the sweeps' cplx leg); the operand half is
        # the member avals in order.
        fsigs = {}
        for seg_i, seg in enumerate(B.get_factor_segments(sched)):
            opnd = tuple(
                (aval(t[0]), aval(t[1]), aval(t[2]),
                 ea_avals_of(t[3]))
                for t in (sched.groups[i].dev(squeeze=True)[:4]
                          for i in seg))
            fsigs.setdefault(
                (B.factor_seg_metas(sched, seg, np.float32), opnd),
                seg_i)
    from ..ops import trisolve as T
    if T.sweeps_packed():
        # the merged arm dispatches ONE program a sweep
        # (trisolve.solve_packed → jit_slu_solve_packed) — warm THAT,
        # not the legacy per-group sweep programs
        ssigs = {tuple(_pack_shapes(sched, T.get_trisolve(sched))):
                 None}
    return fsigs, ssigs


def _pack_shapes(sched, ts):
    """The (Li, L21, Ui, U12) shapes of every group's pack, in group
    order: what `trisolve.pack_panels_staged` cuts, dead lanes
    dropped."""
    for g, gs in zip(sched.groups, ts.groups):
        rb = g.mb - g.wb
        yield ((gs.trim, g.wb, g.wb), (gs.trim, rb, g.wb),
               (gs.trim, g.wb, g.wb), (gs.trim, g.wb, rb))


def warmup_staged(plan, dtype="float32", nrhs: int = 1,
                  rhs_dtype="float64", workers: Optional[int] = None,
                  trans: bool = False, force: bool = False) -> dict:
    """AOT-compile every distinct program a staged run of `plan`
    dispatches, concurrently.  Covers the factor groups and the solve
    sweep for `rhs_dtype` right-hand sides of width `nrhs` (default
    float64, the gssvx flow: the sweep operand carries the FACTOR's
    precision whatever the rhs's,
    precision/policy.sweep_operand_dtype; only the rhs's realness
    reaches the program).

    Returns {"factor_programs", "sweep_programs", "workers", "secs"}:
    `sweep_programs` is 1 under the merged trisolve arm (the packed
    solve program, N or T by `trans`), two a distinct group signature
    under the legacy one.
    """
    import os
    import warnings

    import jax

    from .. import flags
    from ..ops import batched as B

    dtype = np.dtype(dtype)
    rdt = B._real_dtype(dtype)
    sched = B.get_schedule(plan, 1)
    if not force and not B.staged_enabled(sched):
        # the run would take the fused one-program path; compiling
        # per-group programs would be pure waste
        warnings.warn(
            "warmup_staged: staged execution is inactive for this "
            f"schedule ({len(sched.groups)} groups; see SLU_STAGED) — "
            "nothing to warm.  Pass force=True to compile anyway.",
            stacklevel=2)
        return {"factor_programs": 0, "sweep_programs": 0,
                "workers": 0, "secs": 0.0, "staged_inactive": True}
    if not (jax.config.jax_compilation_cache_dir
            or flags.env_opt("JAX_COMPILATION_CACHE_DIR")):
        # AOT compiles land ONLY in the persistent cache; without one
        # the real dispatch recompiles everything and the warmup was
        # pure cost
        warnings.warn(
            "warmup_staged: no persistent compilation cache is "
            "configured (jax_compilation_cache_dir) — the warmed "
            "programs cannot be reused by the subsequent dispatch.",
            stacklevel=2)
    fsigs, ssigs = staged_signatures(sched, dtype)
    workers = workers or min(8, os.cpu_count() or 1)

    def sds(x):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)

    def compile_factor(item):
        (mb, wb, n_pad, ea_meta, eb_meta, *_), g = item
        a_src, a_dst, one_dst, ea_blocks = g.dev(squeeze=True)[:4]
        with _LOWER_LOCK:
            lowered = B._staged_factor_group.lower(
                jax.ShapeDtypeStruct(
                    (sched.upd_total + sched.upd_pad,), dtype),
                jax.ShapeDtypeStruct((len(plan.coo_rows) + 1,), dtype),
                jax.ShapeDtypeStruct((), rdt),
                sds(a_src), sds(a_dst), sds(one_dst),
                jax.tree_util.tree_map(sds, ea_blocks),
                jax.ShapeDtypeStruct((), np.int64),
                mb=mb, wb=wb, n_pad=n_pad, ea_meta=ea_meta,
                eb_meta=eb_meta)
        lowered.compile()

    # merged-factor-arm warmup: one program per merged SEGMENT
    # (batched._staged_factor_segment), operands mirrored exactly —
    # member operand avals in schedule order, metas from the shared
    # factor_seg_metas definition resolved at the WARM dtype (the
    # pallas-promotion leg is dtype-dependent)
    merged_factor = B.factor_merge_on() and dtype.kind != "c"

    def compile_factor_seg(item):
        _key, seg_i = item
        seg = B.get_factor_segments(sched)[seg_i]
        ops = [sched.groups[i].dev(squeeze=True)[:4] for i in seg]
        with _LOWER_LOCK:
            lowered = B._staged_factor_segment.lower(
                jax.ShapeDtypeStruct(
                    (sched.upd_total + sched.upd_pad,), dtype),
                jax.ShapeDtypeStruct((len(plan.coo_rows) + 1,), dtype),
                jax.ShapeDtypeStruct((), rdt),
                tuple(sds(o[0]) for o in ops),
                tuple(sds(o[1]) for o in ops),
                tuple(sds(o[2]) for o in ops),
                tuple(jax.tree_util.tree_map(sds, o[3]) for o in ops),
                tuple(jax.ShapeDtypeStruct((), np.int64) for _ in seg),
                metas=B.factor_seg_metas(sched, seg, dtype),
                pair=False)
        lowered.compile()

    # X carries the sweep operand's dtype (the factor's precision, the
    # rule gssvx.solve dispatches by) and is real-encoded for complex
    # systems (real/imag halves along the rhs axis — ops/batched._enc)
    from ..precision.policy import sweep_operand_dtype
    pdt = sweep_operand_dtype(dtype, rhs_dtype)
    x_cplx = pdt.kind == "c"
    xdt = B._real_dtype(pdt)
    r_hat = 2 * nrhs if x_cplx else nrhs
    kinds = ("fwdT", "bwdT") if trans else ("fwd", "bwd")

    def compile_sweep(item):
        (mb, wb, n_pad, ci_a, si_a), g = item
        for kind in kinds:
            with _LOWER_LOCK:
                lowered = B._staged_sweep_group.lower(
                    jax.ShapeDtypeStruct((sched.n + 1, r_hat), xdt),
                    jax.ShapeDtypeStruct((n_pad * mb * wb,), dtype),
                    jax.ShapeDtypeStruct((n_pad * wb * wb,), dtype),
                    jax.ShapeDtypeStruct(ci_a[0], np.dtype(ci_a[1])),
                    jax.ShapeDtypeStruct(si_a[0], np.dtype(si_a[1])),
                    mb=mb, wb=wb, n_pad=n_pad, cplx=x_cplx,
                    kind=kind)
            lowered.compile()

    # merged-arm sweep warmup: the one packed solve program
    # (trisolve.solve_packed), through the callable the dispatch
    # calls: where the exported-program store is on
    # (resilience/aot.py) that resolves the signature as a call does
    # and compiles the exported module's program, so this process's
    # dispatch and a later process's both find it.  The operand is
    # what `_solve_device_common` hands in: (n, nrhs) in the sweep
    # operand's dtype, the codec inside the program
    from ..ops import trisolve as T
    merged = T.sweeps_packed()

    def compile_packed(item):
        shapes, _ = item
        fn = T._solve_packed_fn(sched, dtype, False)[int(trans)]
        packs = T.PackSet(
            tuple(jax.ShapeDtypeStruct(s, dtype) for s in grp)
            for grp in shapes)
        b = jax.ShapeDtypeStruct((sched.n, nrhs), pdt)
        compile_at = getattr(fn, "warm", None) or (
            lambda *avals: fn.lower(*avals).compile())
        # one program, after the factor's: no compile to overlap
        with _LOWER_LOCK:
            compile_at(packs, b)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=workers) as ex:
        list(ex.map(compile_factor_seg if merged_factor
                    else compile_factor, fsigs.items()))
        list(ex.map(compile_packed if merged else compile_sweep,
                    ssigs.items()))
    return {"factor_programs": len(fsigs),
            "sweep_programs": len(ssigs) * (1 if merged else 2),
            "workers": workers,
            "secs": round(time.perf_counter() - t0, 2)}
