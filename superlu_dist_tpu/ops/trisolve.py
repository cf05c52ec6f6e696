"""Communication-avoiding blocked trisolve — the lsum solve layout.

The legacy sweep (`parallel/factor_dist._solve_loop`) walks the
factor schedule group by group mutating one (n+1, R) solution array:
per group it dynamic-slices its panels out of the factor flats,
gathers X rows, runs the two panel einsums, and SCATTER-ADDS the
off-diagonal update back into X.  At small nrhs that program is
latency-bound, not FLOP-bound (pre-round chip record, not
re-measured: 59 ms/rhs at
nrhs=1 vs 8.3 ms/rhs at nrhs=64 on TPU v5; the same-box CPU
decomposition in DESIGN.md §16 measured the scatter-adds and
per-solve panel re-slicing at ~40% of the nrhs=1 wall with the
einsums pinned at the single-thread GEMV rate).

This module rebuilds the solve path around the reference's lsum/fmod
dataflow (SRC/pdgstrs_lsum.c, dlsum_fmod_inv_gpu_mrhs in
SRC/pdgstrs_lsum_cuda.cu) re-expressed for a batched static schedule —
the communication-avoiding TRSM restructuring of arxiv 1612.01855
applied to the data movement rather than the arithmetic:

  * **packed solve panels** — Li / L21 / Ui / U12 are sliced out of
    the factor flats ONCE per factorization (dead padded lanes
    dropped) and cached on the handle, so the hot FACTORED solve
    never re-materializes panel bytes;
  * **lsum gather/update layout** — off-diagonal updates are written
    DENSELY into a flat lsum buffer (one dynamic_update_slice per
    group) and consumers subtract their contributions through a
    precomputed gather, one J-step chain replaying the legacy
    scatter-add application order, so the compiled program contains
    NO scatter at all and agrees with the legacy sweep to
    4·eps·max|x| (pinned in tests/test_trisolve.py; the two are
    separately compiled programs, so not bit for bit);
  * **level-merged segments** — consecutive small groups (the deep
    narrow chain tail that dominates nrhs=1 wall time) coalesce into
    single dispatch segments: the staged fused solver's sweeps
    (`staged_sweeps`) dispatch one program per SEGMENT instead of
    per group, and the mesh trisolve reconciles once per segment
    boundary instead of per group.

Every execution mode threads through here: the whole-phase solve jit
(`ops/batched._phase_fns` → `_solve_loop`), the packed FACTORED fast
path (`solve_packed`: ONE program a sweep, what `models/gssvx.solve`
and the serve micro-batcher dispatch on a DeviceLU and on a StagedLU
alike — the staged rule is the factor program's, the sweep's program
is chosen by the arm), the staged fused solver's per-segment
dispatch, the fused solvers' in-program sweeps, transpose solves, the
complex pair-plane lane, and the row-partitioned mesh trisolve
(`parallel/factor_dist.make_dist_solve_merged`: what a narrow-rhs
sweep on a process grid is).

Flags (see flags.py): SLU_TRISOLVE selects the one-device arm
(auto|merged|legacy; auto = merged), SLU_TRISOLVE_MERGE_CELLS /
SLU_TRISOLVE_SEG_CELLS bound the segment cost model.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags


# --------------------------------------------------------------------
# flags
# --------------------------------------------------------------------

def trisolve_mode() -> str:
    """Active trisolve arm: 'merged' (the lsum/packed formulation) or
    'legacy' (the historical scatter-add level sweep).  SLU_TRISOLVE
    ∈ {auto, merged, legacy}; auto resolves to merged — the merged
    arm does the legacy arm's arithmetic in its order, so the flag
    exists for A/B pricing and rollback, not correctness."""
    v = flags.env_str("SLU_TRISOLVE", "auto").strip().lower()
    if v in ("legacy", "0", "off"):
        return "legacy"
    return "merged"


def sweeps_packed() -> bool:
    """Do solves sweep the lsum layout (the merged arm)?  THE rule
    for who packs: `factorize_device` dispatches a factorization's
    pack where this holds and `_solve_device_common` takes the packs
    where it holds, so the two sites cannot diverge.  A mesh does not
    ask: its narrow-rhs sweep is the row-partitioned merged program
    (`parallel/factor_dist.solve_arm`)."""
    return trisolve_mode() == "merged"


def merge_cells_limit() -> int:
    """A group whose panel-cell count (trim · mb · wb) is below this
    joins a merged dispatch segment (SLU_TRISOLVE_MERGE_CELLS,
    default 65536 ≈ a 256 kB f32 panel batch): small enough that its
    einsums are dispatch-dominated, the regime merging exists for.
    Groups above it stand alone — their einsums are real work and
    chaining them into one dispatch buys nothing."""
    try:
        return max(0, flags.env_int("SLU_TRISOLVE_MERGE_CELLS", 65536))
    except ValueError:
        return 65536


def seg_cells_limit() -> int:
    """Total panel-cell budget of one merged segment
    (SLU_TRISOLVE_SEG_CELLS, default 1048576): bounds the per-segment
    staged program size so segment compiles stay in the per-group
    compile class."""
    try:
        return max(1, flags.env_int("SLU_TRISOLVE_SEG_CELLS", 1048576))
    except ValueError:
        return 1048576


def active_arm() -> str:
    """One-token description of the solve arm serving dispatches —
    stamped onto serve flight-recorder queue events and bench records
    so p99 exemplars attribute latency to the right kernel.  Every
    one-device handle, staged or not, dispatches the program this
    names (`ops/batched._solve_device_common`)."""
    return trisolve_mode()


# --------------------------------------------------------------------
# the lsum solve schedule
# --------------------------------------------------------------------

@dataclasses.dataclass
class GroupSolve:
    """One factor group's solve-time layout.  Index arrays are
    stacked (ndev, ...) like GroupSpec's; `trim` is the einsum batch
    actually used (dead padded lanes dropped on the single-device
    path, full n_loc on a mesh where shapes must stay uniform across
    devices)."""
    gi: int                 # index into sched.groups
    trim: int
    # forward update-row extent (currently the full rb: an output-dim
    # live-row trim measured as NOT bit-stable on XLA:CPU — see the
    # builder note; the field stays so an extent-stable backend can
    # adopt the trim without relayering)
    rtrim: int
    J: int                  # contributor-gather chain depth
    y_off: int              # this group's slot base in Y/XF (global)
    u_off: int              # this group's slot base in UPD (global)
    b_idx: np.ndarray       # (ndev, trim, wb) rows of B, pad -> n
    u_gidx: np.ndarray      # (ndev, J, trim, wb) UPD slots, pad -> u_total
    xs_idx: np.ndarray      # (ndev, trim, rb) XF slots, pad -> y_total
    _dev: Optional[dict] = None

    def dev(self, squeeze: bool):
        if self._dev is None:
            self._dev = {}
        if squeeze not in self._dev:
            # eager even when first called under a trace (the fused
            # paths build their index constants mid-trace): a traced
            # constant cached here would leak its tracer into the
            # next program
            with jax.ensure_compile_time_eval():
                arrs = (jnp.asarray(self.b_idx),
                        jnp.asarray(self.u_gidx),
                        jnp.asarray(self.xs_idx))
                if squeeze:
                    arrs = tuple(np.asarray(a)[0] for a in (
                        self.b_idx, self.u_gidx, self.xs_idx))
                    arrs = tuple(jnp.asarray(a) for a in arrs)
            self._dev[squeeze] = arrs
        return self._dev[squeeze]


@dataclasses.dataclass
class TrisolveSchedule:
    """The precomputed lsum gather/update layout for one
    BatchedSchedule: dense slot spaces for the forward outputs (Y,
    reused by the backward sweep's XF), the off-diagonal update
    buffer (UPD), per-group contributor gathers, and the merged
    dispatch segments."""
    sched: object                    # ops.batched.BatchedSchedule
    groups: List[GroupSolve]         # parallel to sched.groups
    segments: List[List[int]]        # group indices per segment
    y_total: int                     # Y/XF slots (+1 sentinel)
    u_total: int                     # UPD slots (+1 sentinel)
    final_idx: np.ndarray            # (n,) row -> XF slot
    # per-segment sync requirements (mesh): reconcile UPD before the
    # segment (fwd) / XF before its backward visit (bwd)
    seg_fwd_sync: List[bool] = dataclasses.field(default_factory=list)
    seg_bwd_sync: List[bool] = dataclasses.field(default_factory=list)


def _idt(maxval: int):
    return np.int32 if maxval < 2**31 - 1 else np.int64


def build_trisolve(sched) -> TrisolveSchedule:
    """Build the lsum layout from a BatchedSchedule.

    Order contract: the merged sweep applies the arithmetic
    of the legacy sweep — gathers and dense writes are data movement,
    the einsums run on identical per-front operands (dropping dead
    lanes does not change a kept lane's GEMV), and the
    contributor-subtract chain replays the legacy scatter-add
    application order (groups in program order; within a group, the
    update tensor's row-major iteration order — the order XLA applies
    duplicate scatter indices in).  The compiler still contracts and
    orders each program's multiply-adds for itself: the arms agree to
    4·eps·max|x|, not bit for bit (tests/test_trisolve.py)."""
    ndev = sched.ndev
    n = sched.n
    groups = sched.groups

    y_total = u_total = 0
    metas = []
    for g in groups:
        # single-device lanes are packed [0, n_true) by construction
        # (build_schedule fills per_dev_s[0] before appending dummy
        # fronts); a mesh keeps every lane so shapes stay uniform
        trim = g.n_true if ndev == 1 else g.n_loc
        trim = max(1, min(trim, g.n_loc))
        rb = g.mb - g.wb
        # NOTE a live-row trim of the forward update einsum (output
        # rows only) was measured to break bit parity on XLA:CPU —
        # the backend selects a different dot kernel (different
        # K-reduction blocking) by OUTPUT extent, so even an
        # output-dim trim changes the bits of rows kept.  rtrim
        # therefore stays at the full rb; the field remains so a
        # backend where kernel selection is extent-stable can adopt
        # the trim without relayering.
        rt = rb
        metas.append((trim, rb, rt, y_total, u_total))
        y_total += ndev * trim * g.wb
        u_total += ndev * trim * rt

    # ---- production side, vectorized: every struct-row update's
    # (row, UPD slot) pair in legacy application order ----
    prod_rows, prod_slots = [], []
    for g, (trim, rb, rt, y_off, u_off) in zip(groups, metas):
        if rt == 0:
            continue
        si = np.asarray(g.struct_idx)[:, :trim, :rt]     # (ndev, t, rt)
        base = (u_off
                + (np.arange(ndev)[:, None, None] * trim * rt)
                + (np.arange(trim)[None, :, None] * rt)
                + np.arange(rt)[None, None, :])
        keep = si < n
        prod_rows.append(si[keep].ravel())
        prod_slots.append(base[keep].ravel())
    if prod_rows:
        prod_rows = np.concatenate(prod_rows)
        prod_slots = np.concatenate(prod_slots)
    else:
        prod_rows = np.zeros(0, np.int64)
        prod_slots = np.zeros(0, np.int64)

    # per-row contribution table in arrival order: slot_table[r, j] is
    # the j-th contribution's UPD slot (sentinel u_total otherwise)
    counts = np.bincount(prod_rows, minlength=n)
    Jmax = int(counts.max()) if counts.size else 0
    order = np.argsort(prod_rows, kind="stable")
    sorted_rows = prod_rows[order]
    first = np.searchsorted(sorted_rows, np.arange(n))
    rank = np.arange(len(sorted_rows)) - first[sorted_rows]
    slot_table = np.full((n + 1, max(Jmax, 1)), u_total,
                         dtype=np.int64)
    slot_table[sorted_rows, rank] = prod_slots[order]

    # ---- per-group consumer layouts ----
    gsolves: List[GroupSolve] = []
    slot_of = np.full(n + 1, y_total, dtype=np.int64)
    for gi, (g, (trim, rb, rt, y_off, u_off)) in enumerate(
            zip(groups, metas)):
        ci = np.asarray(g.col_idx)[:, :trim, :]          # (ndev, t, wb)
        live = ci[ci < n]
        J = int(counts[live].max()) if live.size else 0
        if J > 0:
            # (ndev, t, wb, J) -> (ndev, J, t, wb)
            u_gidx = slot_table[np.minimum(ci, n), :J]
            u_gidx = np.moveaxis(u_gidx, -1, 1)
        else:
            u_gidx = np.zeros((ndev, 0, trim, g.wb), dtype=np.int64)
        ybase = (y_off
                 + (np.arange(ndev)[:, None, None] * trim * g.wb)
                 + (np.arange(trim)[None, :, None] * g.wb)
                 + np.arange(g.wb)[None, None, :])
        keep = ci < n
        slot_of[ci[keep]] = ybase[keep]
        gsolves.append(GroupSolve(
            gi=gi, trim=trim, rtrim=rt, J=J, y_off=y_off,
            u_off=u_off,
            b_idx=ci.astype(_idt(n + 1)),
            u_gidx=u_gidx.astype(_idt(u_total + 1)),
            xs_idx=np.zeros((ndev, trim, rb), dtype=np.int64)))

    # backward consumption: struct rows -> owner XF slots
    for g, gs in zip(groups, gsolves):
        si = np.asarray(g.struct_idx)[:, :gs.trim, :]
        gs.xs_idx = slot_of[np.minimum(si, n)].astype(
            _idt(y_total + 1))
    final_idx = slot_of[:n].astype(_idt(y_total + 1))

    # ---- merged dispatch segments (the level-merge pass): chains of
    # small consecutive groups fold into one dispatch/sync unit.  On
    # a mesh, a group needing a forward sync must START its segment
    # (UPD reconciled before its gathers) and one needing a backward
    # sync must END it (XF reconciled before its backward visit —
    # segments run reversed there). ----
    cells = merge_cells_limit()
    seg_cap = seg_cells_limit()
    segments: List[List[int]] = []
    cur: List[int] = []
    cur_cells = 0
    for g, gs in zip(groups, gsolves):
        c = gs.trim * g.mb * g.wb
        small = c <= cells
        brk_before = (not small) or (ndev > 1 and g.fwd_sync)
        if cur and (brk_before or cur_cells + c > seg_cap):
            segments.append(cur)
            cur, cur_cells = [], 0
        cur.append(gs.gi)
        cur_cells += c
        if (not small) or (ndev > 1 and g.bwd_sync):
            segments.append(cur)
            cur, cur_cells = [], 0
    if cur:
        segments.append(cur)

    seg_fwd = [bool(ndev > 1 and any(groups[i].fwd_sync for i in s))
               for s in segments]
    seg_bwd = [bool(ndev > 1 and any(groups[i].bwd_sync for i in s))
               for s in segments]
    return TrisolveSchedule(sched=sched, groups=gsolves,
                            segments=segments, y_total=y_total,
                            u_total=u_total, final_idx=final_idx,
                            seg_fwd_sync=seg_fwd, seg_bwd_sync=seg_bwd)


@jax.tree_util.register_pytree_node_class
class PackSet(tuple):
    """Immutable container for the per-group packed panels: a tuple
    subclass (so compile_watch's signature walker recurses it) that
    accepts attributes (so the per-call jit signature memoizes on the
    object — `_sig_cache`, see obs/compile_watch._leaf_sig) and is
    registered as a pytree (tuple SUBCLASSES are jax leaves by
    default)."""

    def tree_flatten(self):
        return tuple(self), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children)


# PackSet appears in the packed solve program's argument pytree, so
# jax.export must know how to serialize its (empty) auxdata for the
# AOT-persistence leg (resilience/aot.py) — without this, exporting
# the packed solve raises on the unregistered custom node
try:
    from jax import export as _jax_export
    _jax_export.register_pytree_node_serialization(
        PackSet, serialized_name="superlu_dist_tpu.trisolve.PackSet",
        serialize_auxdata=lambda aux: b"",
        deserialize_auxdata=lambda b: None)
except Exception:                   # noqa: BLE001 — older jax or a
    pass                            # re-registration; AOT then skips


# reentrant: _solve_packed_fn/get_packs build the layout
# (get_trisolve) while already holding the lock
_build_lock = threading.RLock()


def get_trisolve(sched) -> TrisolveSchedule:
    """Cached lsum layout for a schedule (keyed by the segmenting
    knobs so a mid-process flag change takes effect — the
    get_schedule precedent)."""
    key = (merge_cells_limit(), seg_cells_limit())
    cache = getattr(sched, "_trisolve", None)
    if cache is not None and key in cache:
        return cache[key]
    with _build_lock:
        cache = getattr(sched, "_trisolve", None)
        if cache is None:
            cache = sched._trisolve = {}
        if key not in cache:
            cache[key] = build_trisolve(sched)
        return cache[key]


def mesh_sync_ranges(ts: TrisolveSchedule):
    """What each sync point of the row-partitioned mesh sweep
    (`parallel/factor_dist.make_dist_solve_merged`) all-reduces.
    Slot bases grow in group order and every slot is written once, so
    what was written since the last sync point is ONE contiguous slot
    range: of UPD before a forward segment that needs its operands
    reconciled, of XF before a backward visit that does (the segments
    walked in reverse), and the XF range still open at the end, which
    the final replicate covers.  Returns (fwd, bwd, last): a (lo, hi)
    range or None a segment (None: no sync there, or nothing written
    since the last one), and the last range.  The ranges are disjoint,
    so a sweep all-reduces at most u_total + y_total slots."""
    first = [ts.groups[seg[0]] for seg in ts.segments]
    fwd, lo = [], 0
    for gs, need in zip(first, ts.seg_fwd_sync):
        rng = (lo, gs.u_off) if need and gs.u_off > lo else None
        if rng:
            lo = gs.u_off
        fwd.append(rng)
    bwd, hi = [None] * len(first), ts.y_total
    for s in reversed(range(len(first))):
        # written since the last sync: the groups after segment s
        nxt = first[s + 1].y_off if s + 1 < len(first) else ts.y_total
        if ts.seg_bwd_sync[s] and hi > nxt:
            bwd[s], hi = (nxt, hi), nxt
    return fwd, bwd, (0, hi)


def mesh_sync_count(ts: TrisolveSchedule) -> int:
    """All-reduces one row-partitioned mesh sweep compiles to: one a
    range of `mesh_sync_ranges`."""
    fwd, bwd, _ = mesh_sync_ranges(ts)
    return sum(r is not None for r in fwd + bwd) + 1


def _sched_fn(sched, key, build):
    """The watched program cached on a schedule under `key`, built
    once (`build()`) under the lock; the hit path takes none."""
    cache = getattr(sched, "_trisolve_fns", None)
    if cache is not None:
        fn = cache.get(key)
        if fn is not None:
            return fn
    with _build_lock:
        cache = getattr(sched, "_trisolve_fns", None)
        if cache is None:
            cache = sched._trisolve_fns = {}
        if key not in cache:
            cache[key] = build()
        return cache[key]


# --------------------------------------------------------------------
# packed solve panels
# --------------------------------------------------------------------

def _trim_pack(g, gs, Lp, Up, Li, Ui):
    """One group's (Li, L21, Ui, U12) from its four full panels: the
    dead padded lanes beyond `trim` dropped, the diagonal blocks cut
    off L and U.  Panels in either storage form (`_psub`)."""
    from .batched import _psub
    t, wb = gs.trim, g.wb
    return (_psub(Li, lambda p: p[:t]),
            _psub(Lp, lambda p: p[:t, wb:, :]),         # L21
            _psub(Ui, lambda p: p[:t]),
            _psub(Up, lambda p: p[:t, :, wb:]))         # U12


def pack_panels(ts: TrisolveSchedule, flats):
    """Slice the four solve operand families — Li, L21, Ui, U12 — out
    of the factor flats, dead lanes dropped, as a per-group list.
    Traceable: the fused and mesh programs call it in their own
    traces (where XLA hoists it out of the refinement while_loop).
    The packed FACTORED path does not come through here: its one pack
    program a factorization (`pack_device`) cuts the flats into
    per-group flats and runs `pack_panels_staged` on them, to the
    same leaves.  Pair-stored (2, N) flats pack to (Ar, Ai) tuples —
    the `_mm_enc` operand form."""
    from .batched import _slice_panel
    L_flat, U_flat, Li_flat, Ui_flat = flats
    return [_trim_pack(
        g, gs,
        _slice_panel(L_flat, g.L_off, g.n_loc * g.mb * g.wb,
                     (g.n_loc, g.mb, g.wb)),
        _slice_panel(U_flat, g.U_off, g.n_loc * g.wb * g.mb,
                     (g.n_loc, g.wb, g.mb)),
        _slice_panel(Li_flat, g.Li_off, g.n_loc * g.wb * g.wb,
                     (g.n_loc, g.wb, g.wb)),
        _slice_panel(Ui_flat, g.Ui_off, g.n_loc * g.wb * g.wb,
                     (g.n_loc, g.wb, g.wb)))
        for g, gs in zip(ts.sched.groups, ts.groups)]


def pack_panels_staged(ts: TrisolveSchedule, panels):
    """pack_panels for per-group local flats (offset 0): a StagedLU's
    panels, or a DeviceLU's flats cut by `_group_flats`.  The body of
    the pack program (`_pack_fn`) for both handle forms."""

    def view(flat, shape):
        if isinstance(flat, tuple):            # a plane pair, cut 1-D
            return tuple(p.reshape(shape) for p in flat)
        if getattr(flat, "ndim", 1) == 2:      # (2, N) pair planes
            P = flat.reshape((2,) + shape)
            return (P[0], P[1])
        return flat.reshape(shape)

    return [_trim_pack(g, gs,
                       view(L, (g.n_loc, g.mb, g.wb)),
                       view(U, (g.n_loc, g.wb, g.mb)),
                       view(Li, (g.n_loc, g.wb, g.wb)),
                       view(Ui, (g.n_loc, g.wb, g.wb)))
            for g, gs, (L, U, Li, Ui) in zip(ts.sched.groups,
                                             ts.groups, panels)]


def _group_flats(sched, flats):
    """A DeviceLU's four flats cut at the groups' offsets into the
    per-group local flats a StagedLU holds (its `panels`; the flats
    ARE those concatenated in group order).  Pair-stored (2, N) flats
    are first split into their two 1-D planes, once a flat, and each
    group's cut is the pair of its planes' cuts: a plane then packs
    exactly as a real flat does.  Cutting (2, size) windows out of
    the (2, N) flat instead is strided on the TPU at every offset
    that is not tile-aligned (ex11 at n=262,144, 91 groups, compiled
    for a v5e: 54 s and 187 MB of code that way, 18 s and 50 MB this
    way; the real pack of the same schedule 16 s and 24 MB)."""

    def cut(flat, off, size):
        if isinstance(flat, tuple):
            return tuple(cut(p, off, size) for p in flat)
        return jax.lax.slice_in_dim(flat, off, off + size, axis=0)

    L, U, Li, Ui = ((f[0], f[1]) if f.ndim == 2 else f for f in flats)
    return [(cut(L, g.L_off, g.n_loc * g.mb * g.wb),
             cut(U, g.U_off, g.n_loc * g.wb * g.mb),
             cut(Li, g.Li_off, g.n_loc * g.wb * g.wb),
             cut(Ui, g.Ui_off, g.n_loc * g.wb * g.wb))
            for g in sched.groups]


def pack_flats(ts: TrisolveSchedule, flats):
    """pack_panels by way of the per-group local flats: the four
    factor flats are first cut at the groups' offsets
    (`_group_flats`), then packed as a StagedLU's panels are.  The
    body of the pack program for a DeviceLU's flats, and of the mesh
    sweep for a device's slice of a DistLU's.  The barrier after the
    cut is load-bearing on the TPU: without it the compiler moves a
    panel's reshape before its static slice wherever the offset is
    not tile-aligned, i.e. reshapes the WHOLE flat to (N/wb, wb),
    padded 16-fold at wb=8, once a group (n=27,000, v5e: 75 s of
    compile, 220 MB of code and 1.1 GB of scratch, against 2 s,
    11 MB and none with it)."""
    return pack_panels_staged(ts, jax.lax.optimization_barrier(
        _group_flats(ts.sched, flats)))


def _pack_fn(sched):
    """Cached watched jit of the pack for one schedule: `fn(store)`
    -> PackSet, where `store` is a DeviceLU's four factor flats (1-D,
    or (2, N) pair planes) or a StagedLU's per-group panels; jax keys
    the trace on the store's structure and avals.  Lives beside the
    packed solve programs on the schedule, so every refactorization
    on a held plan dispatches the program its first one compiled.
    One body for both forms (`pack_flats`)."""
    from .. import obs

    def build():
        ts = get_trisolve(sched)

        # named for the profiler (the batched._phase_fns note)
        @jax.jit
        def slu_pack(store):
            if hasattr(store[0], "ndim"):       # four flats, not panels
                return PackSet(pack_flats(ts, store))
            return PackSet(pack_panels_staged(ts, store))

        return obs.watch_jit("pack", slu_pack)

    return _sched_fn(sched, ("pack", merge_cells_limit(),
                             seg_cells_limit()), build)


def pack_device(sched, store) -> PackSet:
    """The packed solve panels of one factorization in ONE device
    program: `store` is a DeviceLU's four flats or a StagedLU's
    panels.  The flats are not donated (get_diag_u, the legacy sweep,
    gscon and autodiff still read them)."""
    return _pack_fn(sched)(store)


def get_packs(device_lu, at: str = "solve"):
    """Per-handle packed panels, built by one device program
    (`jit_slu_pack`) and cached on the handle — the solve-optimized
    mirror of the factor slabs (the reference keeps dedicated lsum
    solve structures the same way; costs one extra ~factor-sized HBM
    residency, see DESIGN.md §16).  Under the merged arm
    `factorize_device` takes the miss (`at="factor"`) on the factor
    program's output futures, before it blocks on the pivot counts,
    so the host hands out the pack's buffers while the chip factors
    and every solve finds a hit; a handle that reaches its first
    solve without packs (the arm or a cell limit changed since)
    takes the miss there.  The miss stamps where it was taken
    (`at_factor` / `at_solve`) for this thread's in-flight driver
    call: `Stats.packs`, the health ring's `pack`."""
    key = (merge_cells_limit(), seg_cells_limit())
    ent = getattr(device_lu, "_trisolve_packs", None)
    if ent is not None and ent[0] == key:
        return ent[1]
    from .. import obs
    with _build_lock:
        ent = getattr(device_lu, "_trisolve_packs", None)
        if ent is not None and ent[0] == key:
            return ent[1]
        store = getattr(device_lu, "panels", None)
        if store is None:
            store = (device_lu.L_flat, device_lu.U_flat,
                     device_lu.Li_flat, device_lu.Ui_flat)
        # the miss path only: a hit opens no span
        with obs.span("solve.pack", cat="solve",
                      args={"groups": len(device_lu.schedule.groups),
                            "programs": 1, "at": at}):
            packs = pack_device(device_lu.schedule, store)
        device_lu._trisolve_packs = (key, packs)
        obs.stamp_cost("pack", "at_" + at)
        return packs


# --------------------------------------------------------------------
# the merged sweep bodies
# --------------------------------------------------------------------

# chains at or below this unroll as explicit subtract ops; above it
# they fold in a fori_loop (one compiled op).  Module-level so tests
# can bisect the two lowerings.
_CHAIN_UNROLL = 4


@jax.named_scope("slu.lsum")
def chain_subtract(xb, UPD, u_gidx, J: int):
    """The contributor-subtract chain: ONE gather of all J planes,
    then the sequential fold — the subtraction ORDER is the
    contract (it replays the legacy scatter-add application order);
    long chains fold in a fori_loop (one compiled op instead of J —
    the deep-root-chain tail).  Shared by the XLA member body and the
    Pallas lsum member so the order contract has ONE definition."""
    if J <= 0:
        return xb
    xg = UPD[u_gidx]                                # (J, t, wb, R)
    if J > _CHAIN_UNROLL:
        return jax.lax.fori_loop(
            0, J, lambda j, acc: acc - xg[j], xb)
    for j in range(J):
        xb = xb - xg[j]
    return xb


def init_lsum_buffers(ts: "TrisolveSchedule", B0):
    """(B, UPD, Y) dense buffers for one sweep: B is the encoded RHS
    with the sentinel row appended, UPD/Y zero-initialized with their
    sentinel slots.  Row n and the UPD/XF sentinels are EXACT 0.0 —
    load-bearing for the order contract (x − 0 is bit-exact) — and
    the concatenate keeps the program scatter-free.  One definition
    serves the fused sweep, the staged dispatcher, the mesh body and
    its oracle."""
    R = B0.shape[-1]
    rdt = B0.dtype
    B = jnp.concatenate([B0, jnp.zeros((1, R), rdt)])
    UPD = jnp.zeros((ts.u_total + 1, R), rdt)
    Y = jnp.zeros((ts.y_total + 1, R), rdt)
    return B, UPD, Y


def _mm(sub, A, xe, cplx):
    from .batched import _mm_enc
    return _mm_enc(sub, A, xe, cplx)


@jax.named_scope("slu.fwd")
def _fwd_member(state, g, gs, pack, idx, cplx, trans):
    """One group's forward lsum step on the dense buffers.  State is
    (B, UPD, Y): xb = B[cols] minus the contributor chain (replayed
    in the legacy scatter-add order), the panel solve, then the
    off-diagonal lsum update written densely.  `trans` swaps the L
    panels for the Uᵀ pair over the SAME layout (Mᵀ = Uᵀ·Lᵀ)."""
    from .batched import _psub
    B, UPD, Y = state
    b_idx, u_gidx, _ = idx
    xb = chain_subtract(B[b_idx], UPD, u_gidx, gs.J)
    if trans:
        _, _, Ui_p, U12_p = pack
        y = _mm("nwv,nwr->nvr", Ui_p, xb, cplx)      # Uiᵀ·xb
    else:
        Li_p, L21_p, _, _ = pack
        y = _mm("nvw,nwr->nvr", Li_p, xb, cplx)
    yo = jnp.asarray(gs.y_off)
    zc = jnp.zeros((), yo.dtype)
    Y = jax.lax.dynamic_update_slice(
        Y, y.reshape(-1, y.shape[-1]), (yo, zc))
    if gs.rtrim > 0:
        rt = gs.rtrim
        if trans:
            # fwdT's s axis comes from U12 COLUMNS (non-contiguous
            # slice, a copy — trans-solve only); output-dim trim is
            # bit-neutral for the rows kept
            upd = _mm("nws,nwr->nsr",
                      _psub(U12_p, lambda p: p[:, :, :rt]), y, cplx)
        else:
            # contiguous row-prefix view of L21 — zero-copy; the
            # dead padded rows below rtrim are never computed
            upd = _mm("nsw,nwr->nsr",
                      _psub(L21_p, lambda p: p[:, :rt, :]), y, cplx)
        uo = jnp.asarray(gs.u_off)
        UPD = jax.lax.dynamic_update_slice(
            UPD, upd.reshape(-1, upd.shape[-1]),
            (uo, jnp.zeros((), uo.dtype)))
    return B, UPD, Y


@jax.named_scope("slu.bwd")
def _bwd_member(XF, Y, g, gs, pack, idx, cplx, trans):
    """One group's backward step: xb from this group's own dense Y
    block, ancestor rows gathered from XF slots, the solution written
    densely back to the same slot base."""
    _, _, xs_idx = idx
    R = Y.shape[-1]
    yo = jnp.asarray(gs.y_off)
    zc = jnp.zeros((), yo.dtype)
    xb = jax.lax.dynamic_slice(
        Y, (yo, zc),
        (gs.trim * g.wb, R)).reshape(gs.trim, g.wb, R)
    if trans:
        Li_p, L21_p, _, _ = pack
        if g.mb > g.wb:
            xs = XF[xs_idx]
            xb = xb - _mm("nsw,nsr->nwr", L21_p, xs, cplx)
        x1 = _mm("nwv,nwr->nvr", Li_p, xb, cplx)     # Liᵀ·rhs
    else:
        _, _, Ui_p, U12_p = pack
        if g.mb > g.wb:
            xs = XF[xs_idx]
            xb = xb - _mm("nws,nsr->nwr", U12_p, xs, cplx)
        x1 = _mm("nvw,nwr->nvr", Ui_p, xb, cplx)
    return jax.lax.dynamic_update_slice(
        XF, x1.reshape(-1, R), (yo, zc))


def sweep(ts: TrisolveSchedule, packs, b, dtype, trans: bool,
          pair: bool = False, per_group_idx=None):
    """The full merged triangular solve inside one trace: b (n, nrhs)
    in factor ordering -> x (n, nrhs).  Complex systems ride the same
    real-view codec as the legacy sweep (`_enc`/`_dec`); pair mode
    takes pre-encoded b and returns encoded, exactly like
    `_solve_loop`."""
    from .batched import _dec, _enc
    sched = ts.sched
    n = sched.n
    if pair:
        cplx = True
        B0 = b
    else:
        xdt = jnp.promote_types(dtype, b.dtype)
        cplx = bool(jnp.issubdtype(xdt, jnp.complexfloating))
        B0 = _enc(b.astype(xdt), cplx)
    R = B0.shape[-1]
    rdt = B0.dtype
    B, UPD, Y = init_lsum_buffers(ts, B0)
    if per_group_idx is None:
        per_group_idx = [gs.dev(squeeze=True) for gs in ts.groups]

    state = (B, UPD, Y)
    for g, gs, pack, idx in zip(sched.groups, ts.groups, packs,
                                per_group_idx):
        state = _fwd_member(state, g, gs, pack, idx, cplx, trans)
    _, _, Y = state
    XF = jnp.zeros((ts.y_total + 1, R), rdt)
    for g, gs, pack, idx in zip(reversed(sched.groups),
                                reversed(ts.groups),
                                list(reversed(packs)),
                                list(reversed(per_group_idx))):
        XF = _bwd_member(XF, Y, g, gs, pack, idx, cplx, trans)
    x = XF[jnp.asarray(ts.final_idx)]
    if pair:
        return x
    return _dec(x, cplx)


def resident_sweep(ts: TrisolveSchedule, packs, b, dtype,
                   trans: bool, pair: bool = False):
    """Pair-codec-aware merged sweep: takes/returns the caller's
    complex b even for pair-stored factors (sweep's `pair=True`
    contract is pre-encoded real-view planes).  The embedding entry
    point the autodiff VJP legs ride (autodiff/solve.py) — both the
    forward and the adjoint (trans=True) leg of a differentiable
    solve are ONE call here against the same (ts, packs)."""
    if pair:
        from .batched import _dec, _enc
        return _dec(sweep(ts, packs, _enc(jnp.asarray(b), True),
                          dtype, trans, pair=True), True)
    return sweep(ts, packs, b, dtype, trans, pair=False)


# --------------------------------------------------------------------
# packed FACTORED fast path (what the serve hot path dispatches)
# --------------------------------------------------------------------

def _packed_key(dtype, pair: bool):
    return ("packed", np.dtype(dtype).str, bool(pair),
            merge_cells_limit(), seg_cells_limit())


def _solve_packed_fn(sched, dtype, pair: bool):
    """Cached watched jit over the packed sweep for one (schedule,
    dtype, pair): `fn(packs, b, trans)`.  Peer of
    `ops/batched._phase_fns`' solve program — same obs counter name
    ('solve'), so the serve zero-recompile gate and the per-signature
    cost attribution see one unified solve surface."""
    from .. import obs

    def build():
        ts = get_trisolve(sched)
        dt = np.dtype(dtype)

        # TWO positional-only jits instead of one with a static
        # `trans` kwarg: a static_argnames keyword call drops jax to
        # the slow python dispatch path — measured ~ms per call
        # against this fn's ~200-operand pack pytree, real money at
        # the nrhs=1 solve scale.  Where a compile cache is kept the
        # jit is AOT-wrapped (resilience/aot.py): per call signature
        # the program deserializes from the persistent export instead
        # of re-tracing — the serve hot path's cold-boot lever — with
        # the compile-watch proxy outermost as always.
        from ..resilience import aot

        def mk(trans):
            # named for the profiler and for the persistent-cache
            # key (the batched._phase_fns note)
            @jax.jit
            def slu_solve_packed(packs, b):
                with jax.default_matmul_precision("float32"):
                    return sweep(ts, packs, b, dt, trans,
                                 pair=pair)
            wrapped = slu_solve_packed
            if pair or dt.kind != "c":
                # natively complex lanes skip AOT: the complex-on-TPU
                # gate executes them on the host CPU under a TPU
                # default backend, and an export records one platform
                # (the batched._phase_fns note)
                wrapped = aot.wrap_jit(
                    f"solve_packed.{'T' if trans else 'N'}",
                    slu_solve_packed,
                    aot.schedule_fingerprint(
                        sched, dt, extra=("packed", bool(pair))))
            return obs.watch_jit("solve", wrapped)

        return mk(False), mk(True)

    return _sched_fn(sched, _packed_key(dtype, pair), build)


def solve_packed(lu, bb, trans: bool):
    """The packed merged solve against a DeviceLU/StagedLU handle:
    panels pre-sliced once per factorization, zero scatters, zero
    per-solve panel materialization.  `bb` (n, nrhs) in factor
    ordering, dtype-resolved by the caller (and pair-encoded when the
    handle stores pair planes).  Returns the device solution (pair:
    still encoded — `_solve_device_common` decodes)."""
    from .batched import _lu_is_pair
    pair = _lu_is_pair(lu)
    packs = get_packs(lu)
    fns = _solve_packed_fn(lu.schedule, lu.dtype, pair)
    fn = fns[1] if trans else fns[0]
    bj = jnp.asarray(bb)
    return fn(packs, bj)


def solve_packed_cache_size(lu) -> int:
    """Compiled-signature count of the packed solve program serving
    this handle (the zero-recompile pin's probe when the merged arm
    is active); -1 when no packed program exists yet."""
    from .batched import _lu_is_pair
    cache = getattr(lu.schedule, "_trisolve_fns", None)
    if not cache:
        return -1
    fns = cache.get(_packed_key(lu.dtype, _lu_is_pair(lu)))
    if fns is None:
        return -1
    try:
        return sum(int(f._cache_size()) for f in fns)
    except AttributeError:
        return -1


# --------------------------------------------------------------------
# staged per-segment dispatch
# --------------------------------------------------------------------

class _Meta:
    """Attribute bag standing in for (GroupSpec, GroupSolve) inside
    the staged segment jits — only the static fields the member
    bodies read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def seg_metas(ts: TrisolveSchedule, members, cplx: bool) -> tuple:
    """The static meta tuple of one staged segment's members, in the
    given order — THE single definition of the segment jit's static
    key, shared by the dispatch site (staged_sweeps) and the HLO
    contract's builder below."""
    sched = ts.sched
    return tuple(
        (sched.groups[i].wb, sched.groups[i].mb,
         ts.groups[i].trim, ts.groups[i].rtrim, ts.groups[i].J,
         ts.groups[i].y_off, ts.groups[i].u_off, cplx)
        for i in members)


@functools.partial(jax.jit, static_argnames=("metas", "trans"),
                   donate_argnums=(1, 2))
def _staged_fwd_segment(B, UPD, Y, packs, idxs, *, metas,
                        trans: bool):
    """One merged segment of the staged forward sweep as a single
    program: `metas` is a static tuple of (wb, mb, trim, J, y_off,
    u_off, cplx) per member, so a segment signature compiles once and
    is shared by every factorization with the same layout.  UPD/Y are
    donated — they stream through the segment chain in place (the
    staged-factor precedent); B is read-only and passes through."""
    state = (B, UPD, Y)
    with jax.default_matmul_precision("float32"):
        for meta, pack, idx in zip(metas, packs, idxs):
            wb, mb, trim, rtrim, J, y_off, u_off, cplx = meta
            g = _Meta(wb=wb, mb=mb)
            gs = _Meta(trim=trim, rtrim=rtrim, J=J, y_off=y_off,
                       u_off=u_off)
            state = _fwd_member(state, g, gs, pack, idx, cplx, trans)
    return state[1], state[2]


@functools.partial(jax.jit, static_argnames=("metas", "trans"),
                   donate_argnums=(0,))
def _staged_bwd_segment(XF, Y, packs, idxs, *, metas, trans: bool):
    with jax.default_matmul_precision("float32"):
        for meta, pack, idx in zip(metas, packs, idxs):
            wb, mb, trim, rtrim, J, y_off, u_off, cplx = meta
            g = _Meta(wb=wb, mb=mb)
            gs = _Meta(trim=trim, rtrim=rtrim, J=J, y_off=y_off,
                       u_off=u_off)
            XF = _bwd_member(XF, Y, g, gs, pack, idx, cplx, trans)
    return XF


@functools.partial(jax.jit, static_argnames=("cplx",))
def _final_gather(XF, final_idx, cplx: bool):
    from .batched import _dec
    return _dec(XF[final_idx], cplx)


def staged_sweeps(ts: TrisolveSchedule, packs, bf, dtype,
                  trans: bool, pair: bool = False):
    """The merged solve as ONE dispatch per merged segment instead of
    one per group, on caller-held packs: what the staged fused solver
    (`ops/batched.make_fused_solver(staged=True)`) sweeps with.  A
    handle's FACTORED solve does not come through here: it dispatches
    the one packed program (`solve_packed`) whatever its form."""
    from .batched import _enc
    sched = ts.sched
    n = sched.n
    dtype = np.dtype(dtype)
    if pair:
        cplx = True
        B0 = jnp.asarray(bf)
    else:
        xdt = jnp.promote_types(dtype, bf.dtype)
        cplx = bool(jnp.issubdtype(xdt, jnp.complexfloating))
        B0 = _enc(jnp.asarray(bf).astype(xdt), cplx)
    R = B0.shape[-1]
    rdt = B0.dtype
    B, UPD, Y = init_lsum_buffers(ts, B0)

    def seg_args(seg, rev=False):
        idx = list(reversed(seg)) if rev else seg
        metas = seg_metas(ts, idx, cplx)
        pk = tuple(packs[i] for i in idx)
        ix = tuple(ts.groups[i].dev(squeeze=True) for i in idx)
        return metas, pk, ix

    for seg in ts.segments:
        metas, pk, ix = seg_args(seg)
        UPD, Y = _staged_fwd_segment(B, UPD, Y, pk, ix,
                                     metas=metas, trans=trans)
    del B, UPD
    XF = jnp.zeros((ts.y_total + 1, R), rdt)
    for seg in reversed(ts.segments):
        metas, pk, ix = seg_args(seg, rev=True)
        XF = _staged_bwd_segment(XF, Y, pk, ix, metas=metas,
                                 trans=trans)
    return _final_gather(XF, jnp.asarray(ts.final_idx),
                         cplx and not pair)


# --------------------------------------------------------------------
# HLO contract registry declarations (tools/slulint/contracts.py)
# --------------------------------------------------------------------
#
# The merged trisolve's structural guarantees, declared next to the
# code that earns them and checked by `python -m tools.slulint` (and
# tests/test_slulint.py) by lowering at a representative signature.
# tests/test_trisolve.py's former inline HLO regex pin is now a
# one-line registry assertion against these entries.

def _contract_build_packed_solve():
    import jax.numpy as jnp

    from .. import factorize
    from ..options import Options
    from ..utils.testmat import laplacian_3d
    a = laplacian_3d(8)
    lu = factorize(a, Options(factor_dtype="float32"), backend="jax")
    d = lu.device_lu
    fn = _solve_packed_fn(d.schedule, d.dtype, False)[0]
    return fn, (get_packs(d), jnp.zeros((a.n, 1), jnp.float32)), {}


def _contract_build_pack():
    from .. import factorize
    from ..options import Options
    from ..utils.testmat import laplacian_3d
    lu = factorize(laplacian_3d(8), Options(factor_dtype="float32"),
                   backend="jax")
    d = lu.device_lu
    flats = (d.L_flat, d.U_flat, d.Li_flat, d.Ui_flat)
    return _pack_fn(d.schedule), (flats,), {}


def _contract_build_staged_fwd_segment():
    import jax.numpy as jnp

    from .. import factorize
    from ..options import Options
    from ..utils.testmat import laplacian_3d
    a = laplacian_3d(8)
    lu = factorize(a, Options(factor_dtype="float32"), backend="jax")
    d = lu.device_lu                    # StagedLU under SLU_STAGED=1
    ts = get_trisolve(d.schedule)
    packs = get_packs(d)
    B, UPD, Y = init_lsum_buffers(ts, jnp.zeros((a.n, 1), jnp.float32))
    seg = ts.segments[0]
    metas = seg_metas(ts, seg, False)
    pk = tuple(packs[i] for i in seg)
    ix = tuple(ts.groups[i].dev(squeeze=True) for i in seg)
    return (_staged_fwd_segment, (B, UPD, Y, pk, ix),
            dict(metas=metas, trans=False))


HLO_CONTRACTS = (
    {"name": "trisolve.packed_solve",
     "phase": "solve",
     "env": {"SLU_TRISOLVE": "merged"},
     "contracts": ("no_scatter", "no_host_callback"),
     "build": _contract_build_packed_solve,
     "note": "the legacy sweep's scatter-adds were the slowest op "
             "class at nrhs=1 (PR 7); the packed lsum layout must "
             "stay scatter-free"},
    {"name": "trisolve.pack",
     "phase": "pack",
     "env": {"SLU_TRISOLVE": "merged"},
     "contracts": ("no_scatter", "no_host_callback"),
     "build": _contract_build_pack,
     "note": "one program of static slices a factorization: a "
             "scatter or a host round trip here is paid by every "
             "step of a time-stepper"},
    {"name": "trisolve.staged_fwd_segment",
     "phase": "solve",
     "env": {"SLU_TRISOLVE": "merged", "SLU_STAGED": "1"},
     "contracts": ("donation_honored", "no_scatter",
                   "no_host_callback"),
     "build": _contract_build_staged_fwd_segment,
     "note": "UPD/Y stream through the segment chain in place; a "
             "dropped donation doubles the staged solve's buffer "
             "traffic silently"},
)
