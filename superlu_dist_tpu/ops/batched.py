"""Level-batched bucketed multifrontal execution (the TPU numeric core).

This is the device engine replacing the reference's pdgstrf hot loop
(SRC/pdgstrf.c:1108) and tree factorization
(SRC/dtreeFactorization.c:265): the supernodal etree is executed
level-synchronously from the leaves (SURVEY.md §7 "level-synchronous
execution"); within a level, all fronts with the same padded bucket
shape (wb, mb) batch into one vmapped kernel invocation:

    scatter-assemble A entries + identity padding + child updates
    → batched blocked partial LU (ops/dense_lu.py, MXU)
    → slab writes of L/U panels + diag-block inverses
    → update matrices into a flat extend-add buffer

All indices are precomputed on the host once per pattern
(BatchedSchedule, cached on the FactorPlan — the SamePattern rung) and
padded to bucketed lengths/counts so the jit cache is keyed by a small
bounded set of shapes.  The flat `_dat/_offset` slab layout mirrors
the reference's GPU LU mirrors (SRC/superlu_ddefs.h:99-132), the right
model for HBM-resident factors.

ONE schedule builder serves both execution modes: `build_schedule(plan,
ndev)` block-partitions every level/bucket group's fronts across `ndev`
devices (ndev=1 → the single-device path; ndev>1 → the shard_map path
in parallel/factor_dist.py, where the update-slab layout is
device-major so ancestor propagation is a single tiled all_gather —
the TPU form of dreduceAncestors3d, SRC/pd3dcomm.c:704).

The triangular solve walks the same schedule forwards then backwards
with the diag-inverse GEMM formulation (DiagInv=YES,
SRC/pdgssvx.c:1436-1447): x1 = inv(L11)·b1, then scatter-add of
L21·x1 — the lsum/fmod dataflow of SRC/pdgstrs_lsum.c as batched
matmuls instead of message-driven GEMVs.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags, obs
from ..plan.plan import FactorPlan
from .dense_lu import (partial_lu_panels_batch, unit_lower_inverse,
                       upper_inverse)


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


def _next_bucket(x: int) -> int:
    """Next length in the {2^k, 1.5·2^k} grid — ≤33% padding waste
    while keeping the distinct-shape set logarithmic (the jit cache
    key set for the unfused path; the fused program inlines every
    group anyway, so finer quantization costs nothing there)."""
    if x <= 1:
        return 1
    p = 1 << (x - 1).bit_length()      # next pow2 ≥ x
    mid = p // 2 + p // 4              # 1.5·(p/2), the grid midpoint
    return mid if x <= mid else p


def _pad_idx(arr: np.ndarray, fill: int) -> np.ndarray:
    """Pad an index array to the next {2^k, 1.5·2^k} length (≤33%
    scatter-index overhead; padded entries carry drop/zero indices)."""
    n = max(len(arr), 1)
    target = _next_bucket(n)
    out = np.full(target, fill, dtype=np.int64)
    out[:len(arr)] = arr
    return out


def _pad_pos(pos: np.ndarray, w: int, wb: int) -> np.ndarray:
    """Unpadded front position -> padded front position (pivot block
    padded from w to wb shifts the struct rows up by wb-w)."""
    return np.where(pos < w, pos, pos + (wb - w))


def _inverse_positions(pos: np.ndarray, extent: int,
                       absent: int) -> np.ndarray:
    """(..., L) positions (each row distinct where < extent; sentinels
    ≥ extent) -> (..., extent) inverse maps: position -> its index in
    the row, `absent` where no entry of the row sits there."""
    inv = np.full(pos.shape[:-1] + (extent + 1,), absent, dtype=np.int64)
    np.put_along_axis(
        inv, np.minimum(pos, extent),
        np.broadcast_to(np.arange(pos.shape[-1]), pos.shape), axis=-1)
    return inv[..., :extent]


@dataclasses.dataclass
class GroupSpec:
    """One (level, bucket) batch of fronts, block-partitioned over
    `ndev` devices.  All index arrays are stacked (ndev, ...)."""
    level: int
    mb: int
    wb: int
    n_loc: int                 # fronts per device (padded)
    n_true: int                # true front count across devices
    sup_ids: np.ndarray
    sup_pos: np.ndarray        # linear slot d*n_loc+b per sup_ids entry
                               # (zone placement reorders fronts, so
                               # position in sup_ids ≠ slot)
    a_src: np.ndarray          # (ndev, La) into vals (+ zero slot)
    a_dst: np.ndarray          # (ndev, La) local-front linear indices
    one_dst: np.ndarray        # (ndev, Lo)
    # Extend-add in OUTER-PRODUCT form: child updates are rc×rc blocks
    # whose scatter indices factor as (pos_i, pos_j) outer sums, so the
    # host ships only O(rc) positions per child and the rc² flat
    # indices are computed on device at gather/scatter time.  (The
    # materialized-index formulation hit 2.6e9 int64 entries at the
    # k=64 3D Laplacian — 21 GB host, 10 GB device — and dominated
    # schedule build time.)  Children are bucketed by padded rc; each
    # block is (src_off, stride, dst_base, pos) stacked (ndev, K[, rc_b])
    # with meta (rc_b, K, C): K padded child count, C fori_loop chunk.
    # per-bucket (src_off, stride, dst_base, pos_row, pos_col); for
    # ordinary groups pos_col IS pos_row (same array) — they diverge
    # only for sharded-coop parents, whose destination columns are
    # owned-slot indices instead of front positions.  A ROW-lane
    # bucket's src_off is the child's slot in its wave's slab (ea_meta)
    ea_hosts: tuple
    # per-bucket statics (rc_b, tc_b, K, C): C children a chunk on the
    # element lane; C == 0 marks a ROW-lane bucket (_ea_row_lane,
    # _ea_add_rows), whose records lie wave after wave (_ea_waves: no
    # two records of one wave share a parent front) and whose meta
    # carries a fifth entry, its waves as (W, Wc, src) triples: W
    # records read from one child group's slab (src = (voff, nslots,
    # rbc, stride): nslots blocks of (rbc, stride) from slab offset
    # voff, the slots the loop's records span), of which Wc move a
    # loop turn (K = Σ W)
    ea_meta: tuple
    col_idx: np.ndarray        # (ndev, n_loc, wb) global cols, pad -> n
    struct_idx: np.ndarray     # (ndev, n_loc, mb-wb) pad -> n
    upd_off_global: int        # start of this group's global slab
    L_off: int                 # per-device local flat offsets
    U_off: int
    Li_off: int
    Ui_off: int
    # BLOCK-COPY extend-add lane (the scatter-free fast path): children
    # whose position vector decomposes into a few long contiguous runs
    # move as 2-D dynamic_slice → dynamic_update_slice block copies
    # instead of element gather/scatter (pre-round chip record, not
    # re-measured: the element fusions run at 50–200 MB/s; contiguous
    # copies run at HBM rate).
    # Per bucket key (li, lj, st): (so, dr, dc, w) stacked (ndev, K) —
    # source flat offset, dest block row/col in the (n_pad·mb, ncols)
    # front view, and a 0/1 mask killing K-padding records.
    eb_hosts: tuple = ()
    eb_meta: tuple = ()        # per-bucket (li, lj, st, K) statics
    # False when every front's parent lives on the same device (zone-
    # affine placement): the update slab then skips its all_gather and
    # each device writes only its local slice — the gather-free
    # subforest interior of the 3D algorithm (SRC/pdgstrf3d.c:292)
    needs_gather: bool = True
    # True for tree-top groups factored cooperatively: the front is
    # replicated on every device (identical assembly indices) and the
    # trailing GEMM is column-sharded (ops/coop_lu.py) — the TPU analog
    # of the reference's 2D block-cyclic panel distribution
    coop: bool = False
    # sharded-coop layout (ops/coop_sharded.py; engaged when cp > 0):
    # each device holds only its block-cyclic-owned columns of every
    # front — slots [0, tp) owned trailing columns, [tp, cp) owned
    # panel columns; pos_of_slot (ndev, n_loc, cp) maps slot → padded
    # front position (sentinel mb for padding slots)
    cp: int = 0
    tp: int = 0
    pos_of_slot: Optional[np.ndarray] = None
    # solve-sweep sync points (axis mode): X is reconciled by psum only
    # BEFORE groups that read rows other devices may have written —
    # fwd: some front has a cross-device descendant; bwd: a cross-
    # device ancestor.  Zone-affine interiors then run sweep steps
    # with zero collectives (the C_Tree forest of pdgstrs collapsed
    # further: one reduction per zone boundary, not per supernode)
    fwd_sync: bool = True
    bwd_sync: bool = True
    _dev: Optional[dict] = None  # lazy device-array cache, keyed by squeeze

    def dev(self, squeeze: bool, with_a_src: bool = True):
        """Device copies of the index arrays (cached per key).
        squeeze=True drops the leading ndev=1 axis for the
        single-device path.  Position 3 is the extend-add pytree: a
        pair (elem_buckets, block_buckets) — element-gather buckets
        (per-bucket 5-tuples) and block-copy buckets (per-bucket
        4-tuples, eb_hosts).  with_a_src=False leaves position 0
        as None — for callers that substitute a remapped a_src
        (factor_dist._factor_operands), so the global array is
        never uploaded or cached."""
        if self._dev is None:
            self._dev = {}
        key = (squeeze, with_a_src)
        if key not in self._dev:
            ncols = self.cp if self.cp > 0 else self.mb
            f_loc = self.n_loc * self.mb * ncols
            fdt = np.int32 if f_loc < 2**31 - 1 else np.int64
            sdt = (np.int32 if int(self.a_src.max(initial=0)) < 2**31 - 1
                   else np.int64)

            def put(a, dt):
                # cast and squeeze in numpy, one transfer a leaf:
                # `jnp.asarray(a, dtype=dt)[0]` is three one-operation
                # programs a leaf, ~500 a schedule, each compiled on a
                # cold start (ROADMAP S1 a).  Eager even when first
                # called under a trace (the builders call this from
                # inside their programs' traces, so that a program
                # served from the exported store builds none of it):
                # a traced constant cached here would leak its tracer
                a = np.asarray(a, dtype=dt)
                with jax.ensure_compile_time_eval():
                    return jnp.asarray(a[0] if squeeze else a)

            eblocks = []
            for (rc_b, tc_b, _, C, *_), (so, st, db, pr, pc) in zip(
                    self.ea_meta, self.ea_hosts):
                span = (int(so.max(initial=0))
                        + int(st.max(initial=0)) * rc_b + tc_b)
                edt = np.int32 if span < 2**31 - 1 else np.int64
                if C == 0:
                    # row lane: the positions ship as their inverse
                    # maps (front row -> child row, owned column ->
                    # child column), the destination as the front's
                    # index in the group (_ea_add_rows)
                    pr = _inverse_positions(pr, self.mb, rc_b)
                    pc = _inverse_positions(pc, ncols, tc_b)
                    db = db // (self.mb * ncols)
                prd = put(pr, np.int32)
                # squeezed leaves are each their own array, as when
                # the squeeze ran a leaf on the device: one shared
                # array would be one constant of the traced program
                # where it has had two
                eblocks.append((put(so, edt), put(st, edt),
                                put(db, np.int32 if C == 0 else fdt),
                                prd,
                                prd if pc is pr and not squeeze
                                else put(pc, np.int32)))
            bblocks = []
            for (li, lj, st, K), (so, dr, dc, w) in zip(
                    self.eb_meta, self.eb_hosts):
                # dynamic_slice offsets need no gather-wrap dtype
                # promotion, but must hold the largest start value
                bdt = (np.int32
                       if int(so.max(initial=0)) + li * st < 2**31 - 1
                       else np.int64)
                bblocks.append((put(so, bdt), put(dr, np.int32),
                                put(dc, np.int32), put(w, np.int32)))
            pos = (self.pos_of_slot if self.pos_of_slot is not None
                   else np.zeros((self.a_src.shape[0], 1, 1),
                                 dtype=np.int32))
            self._dev[key] = (
                put(self.a_src, sdt) if with_a_src else None,
                put(self.a_dst, fdt),
                put(self.one_dst, fdt),
                (tuple(eblocks), tuple(bblocks)),
                put(pos, np.int32),
                put(self.col_idx, np.int32),
                put(self.struct_idx, np.int32),
            )
        return self._dev[key]


@dataclasses.dataclass
class BatchedSchedule:
    groups: List[GroupSpec]    # execution order, levels ascending
    ndev: int
    n: int
    upd_total: int             # replicated update-buffer size (global)
    L_total: int               # per-device flat sizes
    U_total: int
    Li_total: int
    Ui_total: int
    sup_dev: np.ndarray = None  # front -> device placement
    # tail padding of the update slab (in elements): the block-copy
    # extend-add lane reads each (li, lj) sub-block as one (li·st)
    # dynamic_slice whose final row over-reads up to st−lj elements
    # past the child slab; the pad guarantees the slice never clamps
    # (a clamped dynamic_slice silently SHIFTS its window).  1 when
    # the lane does not reach past the slab (the legacy +1 sentinel
    # slot).  (The row lane reads whole slots of a child group's slab
    # and never leaves it.)
    upd_pad: int = 1

    @functools.cached_property
    def executed_flops(self) -> float:
        """plan/frontal.front_flops over every scheduled slot at its
        bucket shape (wb, mb - wb), padding slots included: what the
        factor program runs, against the plan's useful
        `factor_flops` (ROADMAP S3: executed vs useful, always).  A
        cooperative group's fronts are shared by the devices and
        count once."""
        from ..plan.frontal import front_flops
        return float(sum(
            g.n_loc * (1 if g.coop else self.ndev)
            * front_flops(g.wb, g.mb - g.wb) for g in self.groups))

    @functools.cached_property
    def ea_elements(self) -> dict:
        """Extend-add elements a factorization moves, by lane
        (`element`, `row`, `block`) and over all devices: `padded` is
        what the program touches at its bucket shapes (padding records
        included), `real` the children's own entries (Σ rc·tc of the
        plan; a column no device owns is nobody's).  The row lane also
        counts its `children` (real records) and the loop `turns` its
        programs run to move them, a wave of children a turn
        (`_ea_wave_runs`)."""
        out = {k: {"padded": 0, "real": 0}
               for k in ("element", "row", "block")}
        out["row"].update(children=0, turns=0)
        for g in self.groups:
            ncols = g.cp if g.cp > 0 else g.mb
            for (rc_b, tc_b, K, C, *row), (_, _, _, pr, pc) in zip(
                    g.ea_meta, g.ea_hosts):
                lane = out["row" if C == 0 else "element"]
                lane["padded"] += pr.shape[0] * K * rc_b * tc_b
                lane["real"] += int(((pr < g.mb).sum(-1)
                                     * (pc < ncols).sum(-1)).sum())
                if C == 0:
                    lane["children"] += int((pr < g.mb).any(-1).sum())
                    lane["turns"] += pr.shape[0] * sum(
                        t for _, t, _ in _ea_wave_runs(row[0]))
            for (li, lj, _, K), (_, _, _, w) in zip(g.eb_meta,
                                                    g.eb_hosts):
                out["block"]["padded"] += w.shape[0] * K * li * lj
                out["block"]["real"] += int(w.sum()) * li * lj
        return out

    def comm_summary(self, dtype=np.float64, nrhs: int = 1) -> dict:
        """Static per-step collective traffic (the SCT_t comm-volume
        counters, SRC/util_dist.h:194-317, computed from the schedule
        instead of measured): words moved by factor all_gathers, coop
        panel/trailing psums, and solve sync psums.

        Counting conventions: each coop panel psum counts as ONE
        collective here, but complex factor dtypes execute it as TWO
        real all-reduces (psum_exact splits real/imag) — the *byte*
        totals coincide, the collective count understates by 2x for
        c64/c128.  The coop trailing recombination is an all_gather of
        disjoint column slices (coop_gather_bytes), separate from the
        update-slab all_gathers (factor_allgather_bytes).
        solve_sync_bytes is sized by the caller-passed dtype; the sweep
        actually moves the real-view-encoded X, which is again
        byte-identical for complex."""
        it = np.dtype(dtype).itemsize
        gather_b = sum(g.n_loc * self.ndev * (g.mb - g.wb) ** 2 * it
                       for g in self.groups
                       if g.needs_gather and g.mb > g.wb)
        coop_psum_b = coop_gather_b = 0
        for g in self.groups:
            if g.coop and g.cp > 0:
                # sharded coop (ops/coop_sharded.py): panel psums
                # total mb·wb words + the (wb, mb) U-stripe psum;
                # the trailing Schur slice stays device-local, so
                # there is NO recombination gather at all
                coop_psum_b += g.n_loc * it * 2 * g.wb * g.mb
            elif g.coop:
                # legacy replicated coop (SLU_COOP_SHARDED=0): panel
                # psums total mb·wb words; the trailing all_gather
                # moves each device's padded (mb, cb) column slice
                cb = -(-g.mb // self.ndev)
                coop_psum_b += g.n_loc * it * g.wb * g.mb
                # the kernel gathers whenever wb < mbp (= cb·ndev):
                # column PADDING alone triggers it even at mb == wb
                if g.wb < cb * self.ndev:
                    coop_gather_b += (g.n_loc * it
                                      * g.mb * cb * self.ndev)
        syncs = (sum(1 for g in self.groups if g.fwd_sync)
                 + sum(1 for g in self.groups if g.bwd_sync) + 2)
        return {
            "factor_allgather_bytes": int(gather_b),
            "coop_psum_bytes": int(coop_psum_b),
            "coop_gather_bytes": int(coop_gather_b),
            "solve_syncs": int(syncs) if self.ndev > 1 else 0,
            "solve_sync_bytes": (int(syncs * (self.n + 1) * nrhs * it)
                                 if self.ndev > 1 else 0),
        }


def _zone_assignment(fp, ndev: int) -> np.ndarray:
    """Subtree-affine device zones — the greedy load-balanced forest
    partition of the 3D algorithm (getGreedyLoadBalForests,
    SRC/supernodalForest.c:794): split the supernodal etree into
    ≥ 4·ndev maximal subtrees, bin-pack them onto devices by subtree
    flops, leave the shared ancestors above the cut at zone −1.
    Fronts inside a zone extend-add only device-locally, so their
    groups skip the update-slab all_gather."""
    from ..plan.etree import subtree_sizes
    from ..plan.frontal import front_flops
    ns = fp.nsuper
    zone = np.full(ns, -1, dtype=np.int64)
    if ns == 0:
        return zone
    if ndev <= 1:
        zone[:] = 0
        return zone
    sparent = fp.sym.part.sparent
    ft = front_flops(fp.w, fp.r)
    size = subtree_sizes(sparent)
    for s in range(ns):           # ascending = children before parents
        p = sparent[s]
        if p >= 0:
            ft[p] += ft[s]
    import heapq
    heap = [(-float(ft[s]), int(s))
            for s in np.flatnonzero(sparent == -1)]
    heapq.heapify(heap)
    fixed: list = []
    children = fp.sym.children
    while heap and len(heap) + len(fixed) < 4 * ndev:
        _, s = heapq.heappop(heap)
        ch = children[s]
        if len(ch) == 0:
            fixed.append(s)       # indivisible leaf subtree
        else:
            for c in ch:          # s itself becomes a shared ancestor
                heapq.heappush(heap, (-float(ft[c]), int(c)))
    cands = fixed + [s for _, s in heap]
    loads = np.zeros(ndev)
    for s in sorted(cands, key=lambda t: -ft[t]):
        d = int(np.argmin(loads))
        loads[d] += ft[s]
        # postorder contiguity: subtree of s = [s - size + 1, s]
        zone[s - size[s] + 1:s + 1] = d
    return zone


def _ea_block_on() -> bool:
    """Block-copy extend-add lane (SLU_EA_BLOCK, default ON): children
    whose extend-add position maps are a few long contiguous runs move
    as dynamic_slice/dynamic_update_slice 2-D block copies instead of
    element gather/scatter — the answer to the 50–200 MB/s
    slab↔GEMM-buffer fusions of a pre-round chip record, not
    re-measured.  =0 restores the pure
    element formulation for A/B."""
    return flags.env_str("SLU_EA_BLOCK", "1").strip().lower() \
        not in ("0", "false", "off")


def _ea_block_min_run() -> int:
    """Minimum contiguous-run length for the block lane
    (SLU_EA_BLOCK_MIN_RUN, default 8): shorter runs stay on the
    element path, where per-copy dispatch would dominate."""
    try:
            return max(2, flags.env_int("SLU_EA_BLOCK_MIN_RUN", 8))
    except ValueError:
        return 8


def _contig_runs(pos) -> list:
    """Maximal runs of consecutive (+1-stepping) values in `pos`:
    [(start_index, length), ...] covering the whole vector."""
    pos = np.asarray(pos)
    if len(pos) == 0:
        return []
    brk = np.flatnonzero(np.diff(pos) != 1)
    starts = np.concatenate([[0], brk + 1])
    ends = np.concatenate([brk + 1, [len(pos)]])
    return [(int(s), int(e - s)) for s, e in zip(starts, ends)]


def _plan_child_blocks(ps_row, min_run: int | None = None,
                       max_runs: int = 4):
    """Block-copy eligibility of one child's extend-add position
    vector: the run list [(i0, len)] when EVERY maximal run is ≥
    min_run and there are ≤ max_runs of them (the rc×rc update then
    moves as nruns² contiguous 2-D block copies), else None (the
    child stays on the element-gather path — the ragged remainder)."""
    if min_run is None:
        min_run = _ea_block_min_run()
    runs = _contig_runs(ps_row)
    if not runs or len(runs) > max_runs:
        return None
    if any(ln < min_run for _, ln in runs):
        return None
    return runs


# Row lane of the extend-add (`_ea_add_rows`): front entries one child
# moves by whole rows in the time the element lane's serialized scatter
# moves ONE entry.  Measured on a v5e over thirteen bucket shapes
# (PERF.md §6, PR 29): the element lane costs 17 ns an entry alone and
# 31 ns inside the factor program, whatever the shape; a row-lane child
# costs about 7 µs plus 0.05 ns an entry of its parent front (mb·ncols),
# whatever its own size.  340–600 by those readings; 256 leaves the
# break-even cases on the element lane.
_EA_ROW_GAIN = 256
# the row lane's fixed cost a child (one loop turn of a handful of
# device operations), in front entries at the rate above
_EA_ROW_FIXED = 1 << 17


def _ea_row_lane(rc_b: int, tc_b: int, mb: int, ncols: int) -> bool:
    """Whether a child bucket of padded shape (rc_b, tc_b) under fronts
    of (mb, ncols) rides the row lane: by the bucket's and the front's
    shapes alone, so `ea_meta` (a static key of every factor program)
    says which lane a bucket rides."""
    return rc_b * tc_b * _EA_ROW_GAIN >= mb * ncols + _EA_ROW_FIXED


# front entries one row-lane loop turn may move (W·mb·ncols, the size
# of each of the turn's few transients): a wave wider than this is cut
# into chunks, as the element lane bounds its chunks with C
_EA_WAVE_ENTRIES = 1 << 23


def _ea_wave_chunk(W: int, mb: int, ncols: int) -> int:
    """Records of a wave of W (on the size grid) that move in one loop
    turn under fronts of (mb, ncols): all of them where the turn's
    transients fit `_EA_WAVE_ENTRIES`, else the largest power of two
    that fits and divides W."""
    cap = max(1, _EA_WAVE_ENTRIES // (mb * ncols))
    if W <= cap:
        return W
    c = 1 << (cap.bit_length() - 1)
    while W % c:
        c //= 2
    return c


def _ea_wave_runs(waves: tuple) -> list:
    """A bucket's loops, [(Wc, turns, src), ...]: its waves' turns in
    order, adjacent waves of one chunk width and source together."""
    runs: list = []
    for W, Wc, src in waves:
        if runs and runs[-1][0] == Wc and runs[-1][2] == src:
            runs[-1] = (Wc, runs[-1][1] + W // Wc, src)
        else:
            runs.append((Wc, W // Wc, src))
    return runs


def _ea_waves(per_d: list, mb: int, ncols: int):
    """A row-lane bucket's records, a list a device in front order,
    re-ordered into WAVES: the j-th record of every parent front that
    has one, in the records' order, cut by the records' SOURCE, the
    child group whose slab holds them, so that a wave's children are
    slots of one array.  No two records of a wave share a parent, a
    wave's fronts ascend, and a parent's records keep their order,
    wave after wave.  Each wave is padded (None) to a width on the
    size grid, the largest over the devices.  A source is
    `(voff, nslots, rbc, stride)`: nslots blocks of (rbc, stride) from
    slab offset voff, the part of the child group's slab that the
    loop's records span and no more (`_ea_wave_runs`: a loop reads its
    source whole, so what it reads is what its records need, not what
    the child group wrote); rec[7] becomes that part.  Returns the
    lists and the bucket's static ((W, Wc, src), ...)
    (`_ea_wave_chunk`)."""
    by_wave = []
    for recs in per_d:
        nth: dict = {}
        wv: list = []
        for rec in recs:
            j = nth.get(rec[3], 0)          # rec[3]: the front's base
            nth[rec[3]] = j + 1
            if j == len(wv):
                wv.append({})
            wv[j].setdefault((rec[7], rec[2]), []).append(rec)
        by_wave.append(wv)
    out = [[] for _ in per_d]
    waves = []
    for j in range(max(len(wv) for wv in by_wave)):
        at = [wv[j] if j < len(wv) else {} for wv in by_wave]
        for src in sorted(set().union(*at)):
            W = _next_bucket(max(len(w.get(src, ())) for w in at))
            waves.append((W, _ea_wave_chunk(W, mb, ncols), src))
            for o, w in zip(out, at):
                recs = w.get(src, [])
                o += recs + [None] * (W - len(recs))
    # a loop's source: the slots its records span, over the devices
    base = n = 0
    for Wc, turns, ((_, _, rbc), stride) in _ea_wave_runs(waves):
        span = range(base, base + Wc * turns)
        offs = [o[i][1] for o in out for i in span if o[i] is not None]
        blk = rbc * stride
        part = (min(offs), max(offs) - min(offs) + blk, rbc)
        for o in out:
            for i in span:
                if o[i] is not None:
                    o[i] = o[i][:7] + (part,)
        while base < span.stop:
            waves[n] = (waves[n][0], Wc,
                        (part[0], part[1] // blk, rbc, stride))
            base += waves[n][0]
            n += 1
    return out, tuple(waves)


def _coop_mb_min() -> int:
    """Minimum padded front size for cooperative (column-sharded)
    factorization; SLU_COOP_MB overrides, 0 disables."""
    try:
            return flags.env_int("SLU_COOP_MB", 256)
    except (TypeError, ValueError):
        return 256


def _coop_sharded_on() -> bool:
    """Sharded coop chain (ops/coop_sharded.py) vs the legacy
    replicated scheme (ops/coop_lu.py).  Default ON — the replicated
    scheme's recombination gather was measured at ~64% of step traffic
    at 16 devices (tests/test_coop16.py); SLU_COOP_SHARDED=0 restores
    it for A/B."""
    return flags.env_str("SLU_COOP_SHARDED", "1").strip().lower() \
        not in ("0", "false", "off")


def _coop_solve_rotate() -> bool:
    """Rotate coop fronts' solve/diag-U ownership across devices
    (owner = supernode id % ndev; slot rotation would never leave
    device 0 — tree-top groups hold ONE front) instead of pinning
    device 0 (SLU_COOP_SOLVE_ROTATE=1).  Balances per-device MEANINGFUL solve
    flops — the analog of pdgstrs distributing trisolve over the grid
    per supernode (SRC/pdgstrs.c:1463,2133) — but buys NO wall-clock
    on SPMD lockstep (every device executes identical-shaped sweep
    einsums either way; sentinel masking only decides which results
    are kept) and COSTS backward-sweep X-psums: the coop chain's bwd
    interior is sync-free exactly because ownership never changes
    between parent and child, while the fwd interior pays a psum per
    coop level regardless (cross_desc is transitive from the
    distributed subtrees below).  Default OFF by that cost model —
    tests/test_coop16.py pins both designs' sync counts and the flop
    balance this flag restores."""
    return flags.env_str("SLU_COOP_SOLVE_ROTATE", "0") \
        .strip().lower() in ("1", "true", "on")


def _coop_block() -> int:
    """Block size B of the global-column block-cyclic ownership map
    owner(g) = (g // B) % ndev (SRC/superlu_defs.h:357-382 analog).
    B=1 (pure cyclic) maximizes balance on the arbitrary struct-column
    subsets fronts carry; SLU_COOP_B overrides."""
    try:
            return max(1, flags.env_int("SLU_COOP_B", 1))
    except (TypeError, ValueError):
        return 1


def build_schedule(plan: FactorPlan, ndev: int = 1) -> BatchedSchedule:
    t_build0 = time.perf_counter()
    fp = plan.frontal
    part = fp.sym.part
    xsup = part.xsup
    n = plan.n
    nnz = len(plan.coo_rows)
    zone = _zone_assignment(fp, ndev)
    sparent = part.sparent
    sup_dev = np.zeros(fp.nsuper, dtype=np.int64)
    coop_sup = np.zeros(fp.nsuper, dtype=bool)
    coop_min = _coop_mb_min()

    block_on = _ea_block_on()
    blk_min_run = _ea_block_min_run()
    max_blk_stride = 0           # sizes the upd-slab tail pad

    sup_upd_off = np.full(fp.nsuper, -1, dtype=np.int64)
    groups: List[GroupSpec] = []
    L_cur = U_cur = Li_cur = Ui_cur = 0

    # liveness-based update-slab allocator: a group's slab is dead
    # once every front in it has been consumed by its parent's
    # extend-add, so slab address space is reused via a first-fit
    # free list (the difference between O(sum of all slabs) and
    # O(live working set) HBM for 3D-mesh problems, whose rb² update
    # matrices dominate memory)
    holes: List[tuple] = []          # (offset, size), disjoint, sorted
    upd_peak = 0
    group_alloc: dict = {}           # group idx -> (offset, size)
    remaining: dict = {}             # group idx -> unconsumed fronts
    group_of_sup: dict = {}          # front -> group idx

    # sharded-coop bookkeeping (ops/coop_sharded.py): block-cyclic
    # ownership on GLOBAL column ids makes coop→coop extend-adds
    # device-local (DESIGN.md §5 successor design)
    sh_mode = _coop_sharded_on()
    cyc_B = _coop_block()
    rotate = _coop_solve_rotate()
    sharded_sup = np.zeros(fp.nsuper, dtype=bool)
    sup_slab_stride = np.zeros(fp.nsuper, dtype=np.int64)  # slab cols
    sharded_trail: dict = {}   # front -> [per-d array of struct idx]

    def _owner(gids):
        return (np.asarray(gids, dtype=np.int64) // cyc_B) % ndev

    def _free(gi: int):
        off, size = group_alloc[gi]
        if size == 0:
            return
        holes.append((off, size))
        holes.sort()
        merged = [holes[0]]
        for o, s in holes[1:]:       # coalesce adjacent holes
            po, ps = merged[-1]
            if po + ps == o:
                merged[-1] = (po, ps + s)
            else:
                merged.append((o, s))
        holes[:] = merged

    def _alloc(size: int) -> int:
        nonlocal upd_peak
        if size == 0:
            return 0
        for i, (o, s) in enumerate(holes):
            if s >= size:
                if s == size:
                    holes.pop(i)
                else:
                    holes[i] = (o + size, s - size)
                return o
        # reclaim the tail hole if it touches the peak
        if holes and holes[-1][0] + holes[-1][1] == upd_peak:
            o, s = holes.pop()
            upd_peak = o + size
            return o
        o = upd_peak
        upd_peak += size
        return o

    for lv, sups in enumerate(fp.level_supernodes):
        by_bucket = {}
        for s in sups:
            by_bucket.setdefault((int(fp.wb[s]), int(fp.mb[s])),
                                 []).append(int(s))
        for (wb, mb), slist in sorted(by_bucket.items()):
            N = len(slist)
            rb = mb - wb

            # tree-top groups with fewer fronts than half the devices
            # factor cooperatively: every device participates in every
            # front, with the trailing GEMM column-sharded
            # (ops/coop_sharded.py; legacy replicated ops/coop_lu.py)
            # — the 2D-block-cyclic-panel analog that removes the
            # one-device-factors-the-root Amdahl cap.  In sharded mode
            # coop is FORCED on any group whose fronts consume a
            # sharded child slab (the slab is device-local, so only a
            # sharded parent can assemble it without a gather); the
            # chain therefore runs coop all the way to the root.
            has_coop_child = sh_mode and any(
                sharded_sup[int(c)]
                for s in slist for c in fp.sym.children[s]
                if fp.r[int(c)] > 0)
            coop = (ndev > 1 and coop_min > 0
                    and ((mb >= coop_min and 2 * N <= ndev)
                         or has_coop_child))
            sharded = coop and sh_mode
            if coop:
                per_dev_s = [list(slist) for _ in range(ndev)]
                maxc = N
                coop_sup[slist] = True
            else:
                # zone-affine placement: fronts stick to their
                # subtree's device so interior extend-adds stay
                # device-local; shared ancestors (zone −1) go to the
                # least-loaded device.  A 2× padding guard falls back
                # to round-robin (which then forces the gather) when
                # zones are too skewed here.
                per_dev_s = [[] for _ in range(ndev)]
                shared = []
                for s in slist:
                    z = zone[s]
                    if 0 <= z < ndev:
                        per_dev_s[z].append(s)
                    else:
                        shared.append(s)
                for s in shared:
                    d = min(range(ndev),
                            key=lambda t: len(per_dev_s[t]))
                    per_dev_s[d].append(s)
                maxc = max(len(v) for v in per_dev_s)
                if maxc > 2 * (-(-N // ndev)):
                    # skewed zones would blow padding; round-robin
                    # instead (needs_gather is settled exactly in the
                    # post-pass below, from ACTUAL placements)
                    per_dev_s = [list(slist[d::ndev])
                                 for d in range(ndev)]
                    maxc = max(len(v) for v in per_dev_s)

            # pad per-device count to the {2^k, 1.5·2^k} grid
            n_loc = _next_bucket(maxc)
            n_tot = n_loc * ndev

            # sharded-coop ownership layout: per front, per device,
            # the owned columns under owner(g) = (g // B) % ndev on
            # GLOBAL column ids (panel columns are contiguous from
            # xsup; trailing columns are the struct set; padding panel
            # columns w..wb get virtual ids continuing the run so
            # every slot has exactly one owner)
            tp = cp = 0
            pos_of_slot = None
            if sharded:
                trail_lists, panel_lists = [], []
                max_t = max_p = 0
                for s in slist:
                    r = int(fp.r[s])
                    own_p = _owner(xsup[s] + np.arange(wb))
                    own_t = (_owner(fp.sym.struct[s]) if r
                             else np.empty(0, np.int64))
                    tl = [np.flatnonzero(own_t == d)
                          for d in range(ndev)]
                    pl = [np.flatnonzero(own_p == d)
                          for d in range(ndev)]
                    max_t = max([max_t] + [len(v) for v in tl])
                    max_p = max([max_p] + [len(v) for v in pl])
                    trail_lists.append(tl)
                    panel_lists.append(pl)
                dummy_panel = [np.flatnonzero(_owner(np.arange(wb))
                                              == d)
                               for d in range(ndev)]
                if n_loc > N:
                    max_p = max([max_p]
                                + [len(v) for v in dummy_panel])
                tp = _next_bucket(max_t) if max_t else 0
                cp = tp + _next_bucket(max_p)
                pos_of_slot = np.full((ndev, n_loc, cp), mb,
                                      dtype=np.int64)
            ncols = cp if sharded else mb
            f_loc = n_loc * mb * ncols

            # consume child slabs (each front is extend-added exactly
            # once, here); fully-consumed groups free their slab for
            # reuse — overlap with this group's own slab is safe
            # because the assembly reads happen before the slab write
            # within one functional step
            for s in slist:
                for c in fp.sym.children[s]:
                    if fp.r[c] > 0:
                        gc = group_of_sup[c]
                        remaining[gc] -= 1
                        if remaining[gc] == 0:
                            _free(gc)
            # sharded coop groups keep only the device-local owned
            # trailing slice (rb × tp) per front; legacy coop groups
            # keep ONE (owner-slot) replicated copy; ordinary groups a
            # device-major global fan-out
            slab_sz = (n_loc * rb * tp if sharded
                       else (n_loc if coop else n_tot) * rb * rb)
            upd_off = _alloc(slab_sz)

            sup_pos = np.empty(len(slist), dtype=np.int64)
            pos_of = {s: i for i, s in enumerate(slist)}
            per_dev = {k: [[] for _ in range(ndev)]
                       for k in ("a_src", "a_dst", "one")}
            # extend-add child records, outer-product form: per child
            # only (rc, slab offset, slab stride, front base, positions)
            child_recs = [[] for _ in range(ndev)]
            # block-copy records (li, lj, st, src_off, dst_row, dst_col)
            blk_recs = [[] for _ in range(ndev)]
            col_idx = np.full((ndev, n_loc, wb), n, dtype=np.int64)
            struct_idx = np.full((ndev, n_loc, rb), n, dtype=np.int64)

            for d in range(ndev):
                for b, s in enumerate(per_dev_s[d]):
                    bg = d * n_loc + b
                    w = int(fp.w[s]); r = int(fp.r[s])
                    base = b * mb * ncols
                    lr = _pad_pos(fp.a_lr[s], w, wb)
                    lc = _pad_pos(fp.a_lc[s], w, wb)
                    if sharded:
                        # position → owned slot map for (d, front):
                        # slots [0, tp) trailing, [tp, cp) panel
                        fi = pos_of[s]
                        tl = trail_lists[fi][d]
                        pl = panel_lists[fi][d]
                        sl_arr = np.full(mb + 1, -1, dtype=np.int64)
                        sl_arr[wb + tl] = np.arange(len(tl))
                        sl_arr[pl] = tp + np.arange(len(pl))
                        pos_of_slot[d, b, :len(tl)] = wb + tl
                        pos_of_slot[d, b, tp:tp + len(pl)] = pl
                        slt = sl_arr[lc]
                        keep = slt >= 0
                        per_dev["a_src"][d].append(fp.a_src[s][keep])
                        per_dev["a_dst"][d].append(
                            base + lr[keep] * ncols + slt[keep])
                        if wb > w:
                            t = np.arange(w, wb)
                            ts = sl_arr[t]
                            k2 = ts >= 0
                            per_dev["one"][d].append(
                                base + t[k2] * ncols + ts[k2])
                    else:
                        per_dev["a_src"][d].append(fp.a_src[s])
                        per_dev["a_dst"][d].append(base + lr * mb + lc)
                        if wb > w:
                            t = np.arange(w, wb)
                            per_dev["one"][d].append(base + t * mb + t)
                    for c in fp.sym.children[s]:
                        rc = int(fp.r[c])
                        if rc == 0:
                            continue
                        rbc = int(fp.mb[c] - fp.wb[c])
                        coff = sup_upd_off[c]
                        assert coff >= 0, "child scheduled after parent"
                        ps_row = _pad_pos(fp.ea_map[c], w, wb)
                        # the child's group's slab, blocks of
                        # (rbc, slab stride) a slot: where the row
                        # lane reads it (_ea_waves)
                        cslab = group_alloc[group_of_sup[int(c)]] + (rbc,)
                        if not sharded:
                            # slab columns ARE front positions: pos_col
                            # aliases pos_row (a sharded child under a
                            # non-sharded parent cannot occur — coop is
                            # forced up the chain)
                            assert not sharded_sup[int(c)]
                            runs = (_plan_child_blocks(
                                        ps_row, min_run=blk_min_run)
                                    if block_on else None)
                            if runs is not None:
                                # run × run sub-blocks of the rc×rc
                                # update move as contiguous 2-D copies
                                # (slab rows are vector-index order at
                                # stride rbc; dest rows/cols are the
                                # run's front positions)
                                max_blk_stride = max(max_blk_stride,
                                                     int(rbc))
                                for (i0, li) in runs:
                                    for (j0, lj) in runs:
                                        blk_recs[d].append(
                                            (li, lj, int(rbc),
                                             int(coff) + i0 * rbc + j0,
                                             base // ncols
                                             + int(ps_row[i0]),
                                             int(ps_row[j0])))
                            else:
                                child_recs[d].append(
                                    (rc, int(coff), rbc, base,
                                     ps_row, ps_row, rc, cslab))
                        elif sharded_sup[int(c)]:
                            # device-local child slice (rbc, tp_c):
                            # owned columns align with this device's
                            # owned parent columns BY CONSTRUCTION
                            # (same global column id)
                            jl = sharded_trail[int(c)][d]
                            pcl = sl_arr[ps_row[jl]]
                            assert (pcl >= 0).all(), \
                                "sharded coop ownership misaligned"
                            child_recs[d].append(
                                (rc, int(coff),
                                 int(sup_slab_stride[int(c)]), base,
                                 ps_row, pcl, len(jl), cslab))
                        else:
                            # replicated (gathered) child slab, full
                            # square: this device extend-adds only the
                            # columns it owns; unowned → sentinel
                            pcl = sl_arr[ps_row]
                            pcl = np.where(pcl < 0, ncols, pcl)
                            child_recs[d].append(
                                (rc, int(coff), rbc, base,
                                 ps_row, pcl, rc, cslab))
                    if coop and d != (int(s) % ndev if rotate else 0):
                        # coop fronts: factor work is shared, but
                        # ownership (slab slot, solve updates, diag-U
                        # extraction) belongs to ONE device — solve
                        # indices stay dummies off-owner so the psum of
                        # sweep deltas counts each front once.  Owner
                        # is device 0 (default) or rotated by supernode
                        # id (_coop_solve_rotate cost model; id, not
                        # slot — tree-top groups hold ONE front, so a
                        # slot rotation would never leave device 0).
                        continue
                    col_idx[d, b, :w] = np.arange(xsup[s], xsup[s] + w)
                    struct_idx[d, b, :r] = fp.sym.struct[s]
                    # global update slab is device-major contiguous so an
                    # all_gather of local slabs reproduces it exactly
                    # (coop slabs: single owner-slot copy, bg = b)
                    sup_upd_off[s] = upd_off + (b if coop else bg) \
                        * rb * (tp if sharded else rb)
                    sup_dev[s] = d
                    sup_pos[pos_of[s]] = bg
            if sharded:
                for fi, s in enumerate(slist):
                    sharded_sup[s] = True
                    sup_slab_stride[s] = tp
                    sharded_trail[int(s)] = trail_lists[fi]
            # dummy fronts (including wholly idle devices): identity
            # pivot block so the padded LU is well-defined
            for d in range(ndev):
                for b in range(len(per_dev_s[d]), n_loc):
                    if sharded:
                        dp = dummy_panel[d]
                        pos_of_slot[d, b, tp:tp + len(dp)] = dp
                        per_dev["one"][d].append(
                            b * mb * ncols + dp * ncols
                            + tp + np.arange(len(dp)))
                    else:
                        t = np.arange(wb)
                        per_dev["one"][d].append(
                            b * mb * mb + t * mb + t)

            # bucket the child records by (padded rc, padded source
            # cols); K aligned across devices and rounded to the chunk
            # size when chunked.  The chunk cap bounds the per-chunk
            # transient gather/scatter tensors (~16 MB int32).
            # A bucket at or over the size test (_ea_row_lane) rides
            # the ROW lane: a wave of children of distinct parents a
            # loop turn, moved by whole rows (C = 0 in its meta; its
            # records lie wave after wave, _ea_waves, and K is the
            # waves' widths together).  A wave's children are read as
            # slots of one child group's slab, so a wave holds one
            # such source, and the bucket's waves (width, chunk and
            # source each: static) join its meta.
            by_rc: dict = {}
            for d in range(ndev):
                for rec in child_recs[d]:
                    key = (_next_bucket(rec[0]), _next_bucket(rec[6]))
                    by_rc.setdefault(
                        key, [[] for _ in range(ndev)])[d].append(rec)
            ea_hosts, ea_meta = [], []
            for (rc_b, tc_b) in sorted(by_rc):
                per_d = by_rc[(rc_b, tc_b)]
                K = _next_bucket(max(len(v) for v in per_d))
                C = max(1, (1 << 22) // (rc_b * tc_b))
                waves = ()
                if _ea_row_lane(rc_b, tc_b, mb, ncols):
                    C = 0
                    per_d, waves = _ea_waves(per_d, mb, ncols)
                    K = len(per_d[0])
                elif K > C:
                    K = -(-K // C) * C
                else:
                    C = K
                so = np.zeros((ndev, K), dtype=np.int64)
                st = np.zeros((ndev, K), dtype=np.int64)
                db = np.zeros((ndev, K), dtype=np.int64)
                # row pos == mb / col pos == ncols are the padding
                # sentinels (dropped on device)
                pr = np.full((ndev, K, rc_b), mb, dtype=np.int64)
                pc = (pr if not sharded else
                      np.full((ndev, K, tc_b), ncols, dtype=np.int64))
                for d in range(ndev):
                    npad = 0
                    for i, rec in enumerate(per_d[d]):
                        if rec is None:
                            # a wave's padding record (every position
                            # absent): a front of its own past the
                            # group's, so that a wave's fronts stay
                            # distinct and ascending; its write is
                            # dropped on device
                            db[d, i] = (n_loc + npad) * mb * ncols
                            npad += 1
                            continue
                        rc, coff, stride, base, ps_row, ps_col, tc = \
                            rec[:7]
                        if C:
                            so[d, i] = coff
                        else:
                            # row lane: the child's slot in its wave's
                            # slab (a padding record reads slot 0)
                            voff, _, rbc = rec[7]
                            so[d, i] = (coff - voff) // (rbc * stride)
                        st[d, i] = stride
                        db[d, i] = base
                        pr[d, i, :rc] = ps_row
                        if sharded:
                            pc[d, i, :tc] = ps_col
                    # the element lane's K-padding records repeat the
                    # LAST real dst_base: their positions are
                    # all-sentinel (dropped) so db is semantically dead
                    # there, but the Pallas scatter engine's
                    # output-block schedule requires db monotone per
                    # device (a 0 would revisit front 0 out of order
                    # and overwrite its accumulated delta)
                    nreal = len(per_d[d])
                    if C and 0 < nreal < K:
                        db[d, nreal:] = db[d, nreal - 1]
                ea_hosts.append((so, st, db, pr, pc))
                ea_meta.append((rc_b, tc_b, K, C)
                               + ((waves,) if C == 0 else ()))

            # bucket the block-copy records by exact (li, lj, stride):
            # every record in a bucket shares its slice shapes, so one
            # fori_loop of uniform dynamic_slice copies serves the
            # bucket; K pads to the size grid with masked no-ops
            by_blk: dict = {}
            for d in range(ndev):
                for rec in blk_recs[d]:
                    by_blk.setdefault(
                        rec[:3], [[] for _ in range(ndev)])[d].append(rec)
            eb_hosts, eb_meta = [], []
            for (bli, blj, bst) in sorted(by_blk):
                per_d = by_blk[(bli, blj, bst)]
                K = _next_bucket(max(len(v) for v in per_d))
                so = np.zeros((ndev, K), dtype=np.int64)
                dr = np.zeros((ndev, K), dtype=np.int64)
                dc = np.zeros((ndev, K), dtype=np.int64)
                wm = np.zeros((ndev, K), dtype=np.int64)
                for d in range(ndev):
                    for i, (_, _, _, soff, drow,
                            dcol) in enumerate(per_d[d]):
                        so[d, i] = soff
                        dr[d, i] = drow
                        dc[d, i] = dcol
                        wm[d, i] = 1
                eb_hosts.append((so, dr, dc, wm))
                eb_meta.append((bli, blj, bst, K))

            def stack(key, fill, distinct_pad=False):
                """distinct_pad gives every padding slot its own
                out-of-bounds destination (f_loc + i): the scatter can
                then be promised unique_indices (a parallel lowering on
                TPU) without the repeated-fill duplicates breaking the
                promise."""
                cat = [np.concatenate(v) if v else
                       np.empty(0, dtype=np.int64)
                       for v in per_dev[key]]
                maxlen = max(len(c) for c in cat)
                padded = []
                for c in cat:
                    p = _pad_idx(np.concatenate(
                        [c, np.full(maxlen - len(c), fill,
                                    dtype=np.int64)]), fill)
                    if distinct_pad:
                        bad = np.flatnonzero(p == fill)
                        p[bad] = fill + np.arange(len(bad))
                    padded.append(p)
                return np.stack(padded)

            groups.append(GroupSpec(
                level=lv, mb=mb, wb=wb, n_loc=n_loc, n_true=N,
                sup_ids=np.asarray(slist, dtype=np.int64),
                sup_pos=sup_pos,
                a_src=stack("a_src", nnz),
                a_dst=stack("a_dst", f_loc, distinct_pad=True),
                one_dst=stack("one", f_loc, distinct_pad=True),
                ea_hosts=tuple(ea_hosts), ea_meta=tuple(ea_meta),
                eb_hosts=tuple(eb_hosts), eb_meta=tuple(eb_meta),
                col_idx=col_idx, struct_idx=struct_idx,
                upd_off_global=upd_off,
                L_off=L_cur, U_off=U_cur, Li_off=Li_cur, Ui_off=Ui_cur,
                coop=coop, cp=cp, tp=tp, pos_of_slot=pos_of_slot))
            gi = len(groups) - 1
            group_alloc[gi] = (upd_off, slab_sz)
            for s in slist:
                group_of_sup[s] = gi
            nread = sum(1 for s in slist if fp.r[s] > 0)
            remaining[gi] = nread
            if nread == 0:
                _free(gi)
            L_cur += n_loc * mb * wb
            U_cur += n_loc * wb * mb
            Li_cur += n_loc * wb * wb
            Ui_cur += n_loc * wb * wb

    # Sort the A-assembly (dst, src) pairs by destination (free on the
    # host, adds commute): the device scatter can then carry the
    # indices_are_sorted promise, the parallel-friendly lowering.
    # (Extend-add indices are device-computed per block now — no host
    # pairs to sort; their scatter runs without ordering promises.)
    for g in groups:
        for d in range(g.a_dst.shape[0]):
            o = np.argsort(g.a_dst[d], kind="stable")
            g.a_dst[d] = g.a_dst[d][o]
            g.a_src[d] = g.a_src[d][o]

    # gather post-pass, from ACTUAL placements (parents are always
    # scheduled after their children, so sup_dev is complete here): a
    # group's slab may skip its all_gather exactly when every consumer
    # of every front in it lives on the producing device.  Zones only
    # GUIDE placement; this decision never assumes they were honored.
    # Coop groups never gather (every device already holds the full
    # owner-slot slab locally); their CHILDREN always must (the coop
    # parent's replicated assembly reads every child slab everywhere).
    for g in groups:
        if g.coop:
            g.needs_gather = False
            continue
        g.needs_gather = ndev > 1 and any(
            fp.r[int(s)] > 0
            and (coop_sup[int(sparent[int(s)])]
                 or sup_dev[int(sparent[int(s)])] != sup_dev[int(s)])
            for s in g.sup_ids)

    # solve-sync post-pass: a sweep step must see a replicated X only
    # when other devices may have written rows it reads.  fwd reads
    # X[cols(s)], accumulated by s's DESCENDANTS; bwd reads
    # X[struct(s)] ⊆ ancestor columns, set by s's ANCESTORS.  Coop
    # fronts run their solve updates on their OWNER device (sup_dev:
    # 0 pinned, or id-rotated under SLU_COOP_SOLVE_ROTATE), so the
    # same device comparison covers them either way — rotation simply
    # makes parent/child owner changes visible here and buys the bwd
    # interior syncs its docstring costs out.
    if ndev > 1:
        ns = fp.nsuper
        cross_desc = np.zeros(ns, dtype=bool)
        anc_cross = np.zeros(ns, dtype=bool)
        for s in range(ns):            # postorder: children first
            p = int(sparent[s])
            if p >= 0 and (cross_desc[s] or sup_dev[s] != sup_dev[p]):
                cross_desc[p] = True
        for s in range(ns - 1, -1, -1):  # parents first
            p = int(sparent[s])
            if p >= 0:
                anc_cross[s] = bool(anc_cross[p]
                                    or sup_dev[p] != sup_dev[s])
        for g in groups:
            g.fwd_sync = bool(any(cross_desc[int(s)]
                                  for s in g.sup_ids))
            g.bwd_sync = bool(any(anc_cross[int(s)]
                                  for s in g.sup_ids))

    sched = BatchedSchedule(groups=groups, ndev=ndev, n=n,
                            upd_total=upd_peak,
                            L_total=L_cur, U_total=U_cur,
                            Li_total=Li_cur, Ui_total=Ui_cur,
                            sup_dev=sup_dev,
                            upd_pad=1 + max_blk_stride)
    # once a schedule, never a step (get_schedule caches it)
    obs.COMPILE_WATCH.record_phases(
        t_build0, {"SCHEDULE": time.perf_counter() - t_build0})
    return sched


def get_schedule(plan: FactorPlan, ndev: int = 1) -> BatchedSchedule:
    cache = getattr(plan, "_batched_schedules", None)
    if cache is None:
        cache = plan._batched_schedules = {}
    # the coop knobs participate in the key so a mid-process
    # SLU_COOP_* change takes effect instead of hitting a stale entry
    key = (ndev, (_coop_mb_min(), _coop_sharded_on(), _coop_block(),
                  _coop_solve_rotate())
           if ndev > 1 else 0,
           (_ea_block_min_run() if _ea_block_on() else None),
           _EA_ROW_GAIN, _EA_WAVE_ENTRIES)
    if key not in cache:
        cache[key] = build_schedule(plan, ndev)
    return cache[key]


def _thresh_for(plan: FactorPlan, dtype: np.dtype) -> float:
    if not plan.options.replace_tiny_pivot:
        return 0.0
    rdt = np.dtype(dtype.char.lower()) if dtype.kind == "c" else dtype
    # jnp.finfo also understands the ml_dtypes families (bfloat16)
    eps = float(jnp.finfo(rdt).eps)
    return float(np.sqrt(eps) * plan.anorm)


def _real_dtype(dtype: np.dtype):
    return np.dtype(dtype.char.lower()) if dtype.kind == "c" else dtype


def _pair_mode(dtype) -> bool:
    """Factor complex systems on stacked real/imag planes
    (ops/pair_lu, _factor_group_impl_pair) instead of native complex
    storage: what utils/platform.complex_lowering gives `dtype` (a
    complex dtype on a TPU default backend; the tests' hook on
    XLA:CPU)."""
    from ..utils.platform import complex_lowering
    return complex_lowering(dtype) == "pair"


def _pair_encode_vals(scaled_vals, dtype) -> np.ndarray:
    """Host-side complex→plane encoding of the numeric input: the
    device program must receive real operands (a complex→real
    extraction inside the program would reintroduce the broken
    lowering this mode exists to avoid)."""
    rdt = _real_dtype(np.dtype(dtype))
    v = np.asarray(scaled_vals).astype(np.dtype(dtype))
    return np.stack([v.real.astype(rdt), v.imag.astype(rdt)])


def _pair_encode_rhs(bb: np.ndarray) -> np.ndarray:
    """Host-side rhs encoding for the sweeps' real-view codec: real
    and imaginary halves concatenated along the rhs axis (_enc's
    layout, produced outside the program)."""
    return np.concatenate([bb.real, bb.imag], axis=-1)


def _pair_decode_sol(X: np.ndarray, xdt) -> np.ndarray:
    """Invert _pair_encode_rhs on the solved X (host side)."""
    h = X.shape[-1] // 2
    return (X[..., :h] + 1j * X[..., h:]).astype(xdt)


# --------------------------------------------------------------------
# per-group bodies — ONE implementation serves the single-device jit
# path (axis=None) and the shard_map distributed path (axis='z'): the
# only differences are the all_gather propagating the update slab and
# the psum-of-deltas solve updates, so keeping a single body guarantees
# the oracle and the distributed path cannot diverge.
# --------------------------------------------------------------------

def _hi_prec(fn):
    """Trace `fn` under full-f32 matmul precision.

    TPU MXU matmuls on float32 inputs default to single-pass bfloat16
    (~8e-3 relative error), which destroys the f32 factor as an
    iterative-refinement preconditioner: convergence needs
    cond(A)·eps_factor < 1 (SRC/psgssvx_d2.c strategy).  CPU ignores
    the setting, f64 is unaffected, so this pins TPU semantics to what
    the numerics require.  Measured on-chip: the 6-pass f32 mode is not
    slower than 3-pass for this workload (it is latency-, not
    MXU-bound), so use full float32."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)
    return wrapped


def _flat_axis_index(axis):
    """Row-major flattened index over a (possibly tuple) mesh axis —
    matches all_gather's tiled concatenation order."""
    return jax.lax.axis_index(axis)


def psum_exact(x, axis):
    """psum that splits complex operands into real/imag all-reduces.

    Complex all-reduce has shown run-to-run nondeterminism (wrong
    values/NaN) on the XLA:CPU threaded runtime; the split is bitwise
    equivalent and deterministic (pinned by
    tests/test_coop.py::test_complex_dist_solve_deterministic)."""
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        return (jax.lax.psum(x.real, axis)
                + 1j * jax.lax.psum(x.imag, axis)).astype(x.dtype)
    return jax.lax.psum(x, axis)


# Kernel scopes (`jax.named_scope`, trace-time only): ONE fixed
# vocabulary across ops/ and parallel/factor_dist.py, so a device
# operation in a profiler trace names the code it came from —
# slu.assemble (values into fronts), slu.extend_add, slu.partial_lu,
# slu.tri_inverse, slu.schur, slu.store (panels into the flats),
# slu.fwd, slu.bwd, slu.lsum (the contributor chain), slu.resid
# (device SpMV); on a mesh also slu.dist.gather (the level's update
# slab gathered to every device) and slu.coop.psum / slu.coop.gather
# (the cooperative tree-top LU's collectives, ops/coop_*.py).  The
# innermost scope is the operation's kernel.

@jax.named_scope("slu.extend_add")
def _ea_add(F, upd_buf, ea_blocks, ea_meta, *, mb: int, n_pad: int,
            ncols: int = 0):
    """Extend-add of child update blocks into the flat front batch F.
    Outer-product form: per child only its O(rc) position vectors ship
    from the host; the rc·tc flat indices are iota arithmetic on
    device.  Children are bucketed by padded (rc, tc); buckets with
    many children run as a fori_loop over C-child chunks so the
    transient index/update tensors stay bounded (~tens of MB) instead
    of materializing a whole leaf level at once.  A bucket at or over
    the size test (meta C == 0) takes the row lane, `_ea_add_rows`, in
    its place in the bucket order: its positions arrive as inverse
    maps, its destinations as front indices, its records wave after
    wave (a wave of children of distinct parents a loop turn), and no
    per-entry index is built.

    `ncols` is the front's column count (mb for the square layout;
    cp for sharded-coop owned-column slices, whose destination column
    index is an owned SLOT from the separate pos_col vector)."""
    if not ncols:
        ncols = mb
    f_loc = n_pad * mb * ncols

    for (rc_b, tc_b, K, C, *row), (so, st, db, pr, pc) in zip(
            ea_meta, ea_blocks):
        so = so.reshape(-1)
        st = st.reshape(-1)
        db = db.reshape(-1)
        pr = pr.reshape(-1, pr.shape[-1])
        pc = pc.reshape(-1, pc.shape[-1])
        if upd_buf.size > np.iinfo(np.dtype(so.dtype)).max:
            # audikw_1-class slabs pass 2^31 elements: jax's gather
            # must represent the ARRAY SIZE in the index dtype (wrap
            # normalization), so a >2 GiB-element upd_buf needs int64
            # source indices even when this group's own span is small
            # (and the clamp arithmetic of the row lane's dynamic_slice
            # must not wrap)
            so = so.astype(jnp.int64)
            st = st.astype(jnp.int64)
        if C == 0:
            F = _ea_add_rows(F, upd_buf, so, db, pr, pc, rc_b=rc_b,
                             tc_b=tc_b, waves=row[0], mb=mb,
                             n_pad=n_pad, ncols=ncols)
            continue

        def add_chunk(Ff, so, st, db, pr, pc):
            ai = jnp.arange(rc_b, dtype=so.dtype)
            aj = jnp.arange(tc_b, dtype=so.dtype)
            src = (so[:, None, None]
                   + ai[None, :, None] * st[:, None, None]
                   + aj[None, None, :]).reshape(-1)
            upd = upd_buf[src]
            pi = pr[:, :, None].astype(db.dtype)
            pj = pc[:, None, :].astype(db.dtype)
            dst = db[:, None, None] + pi * ncols + pj
            # row pos == mb / col pos == ncols are padding sentinels
            # (real positions are strictly smaller); route those lanes
            # out of bounds so mode="drop" kills them
            dst = jnp.where((pi >= mb) | (pj >= ncols),
                            jnp.asarray(f_loc, db.dtype), dst)
            return Ff.at[dst.reshape(-1)].add(upd, mode="drop")

        if K <= C:
            F = add_chunk(F, so, st, db, pr, pc)
        else:
            def body(i, Ff):
                s0 = i * C
                return add_chunk(
                    Ff,
                    jax.lax.dynamic_slice_in_dim(so, s0, C, 0),
                    jax.lax.dynamic_slice_in_dim(st, s0, C, 0),
                    jax.lax.dynamic_slice_in_dim(db, s0, C, 0),
                    jax.lax.dynamic_slice_in_dim(pr, s0, C, 0),
                    jax.lax.dynamic_slice_in_dim(pc, s0, C, 0))
            F = jax.lax.fori_loop(0, K // C, body, F)
    return F


def _ea_add_rows(F, upd_buf, slot, fr, inv_r, inv_c, *, rc_b: int,
                 tc_b: int, waves: tuple, mb: int, n_pad: int,
                 ncols: int):
    """Row lane of `_ea_add` (meta C == 0): a WAVE of children a loop
    turn, moved by whole rows — no index per matrix entry exists here.
    The bucket's records lie wave after wave (`_ea_waves`): the
    children of one wave have distinct parents and one source, so a
    turn moves Wc of them at once (`waves`: static (W, Wc, src)
    triples, W records of which Wc a turn; adjacent waves of one Wc
    and source share a loop), and a parent's children stay in their
    order, wave after wave, so every front entry takes the addends it
    took one child a turn, in that order.  The children are READ as
    what they are, slots of their group's slab (src = (voff, nslots,
    rbc, stride): the part of the slab that the loop's records span,
    from voff, viewed as nslots blocks of
    (rbc, stride), of which the turn gathers those at `slot`), each
    PULLED into its parent's shape by two whole-row gathers through
    the inverse position maps the host ships (`inv_r`: front row ->
    child row, `inv_c`: front column -> child column; each points
    where the child has none at an appended zero row) with a transpose
    between, and ADDED densely to their fronts `fr` of the
    (n_pad, mb, ncols) view: the turn's fronts are gathered, added to
    and set back.  A wave's padding record (every position absent, a
    front past the group's) adds zeros to nothing.

    A turn of ONE child (a wave of one, or a front so large that the
    chunk is one) updates its front in place, as the lane did before
    it had waves: the child's block a `dynamic_slice` of the slab, its
    front sliced, added to and written back with a
    `dynamic_update_slice`, where the batched form gathers and sets
    copies of its fronts (three fronts of scratch under a front of
    6,144 rows by the TPU compiler's report,
    tests/test_pack_program.py; priced alone on a v5e, PERF.md §6,
    PR 46: the batched turn of one at 1.0 to 1.2 times this one)."""
    F3 = F.reshape(n_pad, mb, ncols)

    def pull(blk, inv_r, inv_c):
        """One child's (rbc, stride) block -> its (mb, ncols) addend."""
        blk = blk[:rc_b, :tc_b]
        # a slab block smaller than the bucket holds the child whole
        blk = jnp.pad(blk, ((0, rc_b - blk.shape[0]),
                            (0, tc_b - blk.shape[1])))
        tall = jnp.concatenate([blk, jnp.zeros((1, tc_b), blk.dtype)]) \
            .at[inv_r].get(mode="promise_in_bounds")   # (mb, tc_b)
        wide = jnp.concatenate([tall.T, jnp.zeros((1, mb), blk.dtype)]) \
            .at[inv_c].get(mode="promise_in_bounds")   # (ncols, mb)
        return wide.T

    def add_one(F3, src, slot, fr, inv_r, inv_c):
        voff, _, rbc, stride = src
        blk = jax.lax.dynamic_slice(
            upd_buf, (voff + slot[0] * (rbc * stride),), (rbc * stride,))
        add = pull(blk.reshape(rbc, stride), inv_r[0], inv_c[0])
        cur = jax.lax.dynamic_index_in_dim(F3, fr[0], 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(F3, cur + add, fr[0], 0)

    def add_wave(F3, slab, slot, fr, inv_r, inv_c):
        add = jax.vmap(pull)(
            slab.at[slot].get(mode="promise_in_bounds"), inv_r, inv_c)
        cur = F3.at[fr].get(mode="clip", unique_indices=True,
                            indices_are_sorted=True)
        return F3.at[fr].set(cur + add, mode="drop",
                             unique_indices=True, indices_are_sorted=True)

    base = 0
    for Wc, turns, src in _ea_wave_runs(waves):
        if Wc == 1:
            add, arg = add_one, src
        else:
            voff, nslots, rbc, stride = src
            add, arg = add_wave, jax.lax.slice_in_dim(
                upd_buf, voff, voff + nslots * rbc * stride
            ).reshape(nslots, rbc, stride)

        def turn(t, F3, base=base, Wc=Wc, add=add, arg=arg):
            return add(F3, arg, *(
                jax.lax.dynamic_slice_in_dim(a, base + t * Wc, Wc, 0)
                for a in (slot, fr, inv_r, inv_c)))

        F3 = turn(0, F3) if turns == 1 else jax.lax.fori_loop(
            0, turns, turn, F3)
        base += Wc * turns
    return F3.reshape(-1)


@jax.named_scope("slu.extend_add")
def _ea_add_blocks(F, upd_buf, eb_blocks, eb_meta, *, mb: int,
                   n_pad: int, ncols: int = 0):
    """Block-copy extend-add lane (GroupSpec.eb_hosts): each record is
    one contiguous (li, lj) sub-block of a child update, moved as a
    dynamic_slice read (li·st flat elements reshaped to rows, over-read
    tail discarded; BatchedSchedule.upd_pad guarantees no clamp) and a
    read-add-dynamic_update_slice write into the (n_pad·mb, ncols)
    front view.  Sequential within a bucket (fori_loop), so overlapping
    destination blocks accumulate correctly; `w` masks K-padding
    records to no-ops (their in-bounds dst gets +0)."""
    if not eb_meta:
        return F
    if not ncols:
        ncols = mb
    F2 = F.reshape(n_pad * mb, ncols)
    for (li, lj, st, K), (so, dr, dc, w) in zip(eb_meta, eb_blocks):
        if upd_buf.size > np.iinfo(np.dtype(so.dtype)).max:
            # >2^31-element slabs: the clamp arithmetic of
            # dynamic_slice must not wrap in the index dtype (same
            # audikw-class guard as _ea_add's gather promotion)
            so = so.astype(jnp.int64)

        def copy_one(i, F2, so=so, dr=dr, dc=dc, w=w,
                     li=li, lj=lj, st=st):
            src = jax.lax.dynamic_slice(upd_buf, (so[i],), (li * st,))
            blk = src.reshape(li, st)[:, :lj]
            mask = w[i].astype(F2.dtype)
            cur = jax.lax.dynamic_slice(F2, (dr[i], dc[i]), (li, lj))
            return jax.lax.dynamic_update_slice(
                F2, cur + mask * blk, (dr[i], dc[i]))

        if K == 1:
            F2 = copy_one(0, F2)
        else:
            F2 = jax.lax.fori_loop(0, K, copy_one, F2)
    return F2.reshape(-1)


def _factor_group_impl(vals, upd_buf, L_flat, U_flat, Li_flat, Ui_flat,
                       tiny, nzero, thresh, a_src, a_dst, one_dst,
                       ea_blocks, upd_off, L_off, U_off, Li_off,
                       Ui_off, *, mb: int, wb: int, n_pad: int,
                       ea_meta: tuple = (), eb_meta: tuple = (),
                       axis: Optional[str] = None,
                       gather: bool = True, coop: bool = False,
                       ndev: int = 1, pos_idx=None, cp: int = 0,
                       tp: int = 0, pair: bool = False,
                       pallas_diag: bool = False,
                       force_xla: bool = False):
    if pair:
        return _factor_group_impl_pair(
            vals, upd_buf, L_flat, U_flat, Li_flat, Ui_flat, tiny,
            nzero, thresh, a_src, a_dst, one_dst, ea_blocks, upd_off,
            L_off, U_off, Li_off, Ui_off, mb=mb, wb=wb, n_pad=n_pad,
            ea_meta=ea_meta, eb_meta=eb_meta, axis=axis, gather=gather,
            coop=coop, pos_idx=pos_idx, cp=cp, tp=tp)
    dtype = L_flat.dtype
    one = jnp.ones((), dtype)
    sharded = coop and axis is not None and cp > 0
    ncols = cp if sharded else mb
    # position 3 carries both extend-add lanes: element-gather buckets
    # and contiguous block-copy buckets (GroupSpec.dev docstring)
    elem_blocks, blk_blocks = ea_blocks
    with jax.named_scope("slu.assemble"):
        F = jnp.zeros(n_pad * mb * ncols, dtype)
        # a_dst/one_dst carry DISTINCT out-of-bounds padding, so the
        # unique-indices promise holds; add-scatter index pairs are
        # dst-sorted by the schedule builder, so they also promise
        # indices_are_sorted — both enable parallel scatter lowerings
        F = F.at[a_dst].add(vals[a_src], mode="drop",
                            unique_indices=True,
                            indices_are_sorted=True)
        F = F.at[one_dst].set(one, mode="drop", unique_indices=True)
    F = _ea_add(F, upd_buf, elem_blocks, ea_meta, mb=mb, n_pad=n_pad,
                ncols=ncols)
    F = _ea_add_blocks(F, upd_buf, blk_blocks, eb_meta, mb=mb,
                       n_pad=n_pad, ncols=ncols)
    F = F.reshape(n_pad, mb, ncols)

    if sharded:
        # sharded coop chain (ops/coop_sharded.py): each device holds
        # only its block-cyclic-owned columns; panels replicate off
        # psums, the Schur slice stays device-local (no recombination
        # gather).  Counters replicate — owner device counts them.
        from .coop_sharded import coop_sharded_lu_batch
        with jax.named_scope("slu.partial_lu"):
            Lsrc, Usrc, slab, tiny_g, nzero_g = coop_sharded_lu_batch(
                F, pos_idx, thresh, wb=wb, cp=cp, tp=tp, axis=axis)
        upd_src = slab
        on_owner = (_flat_axis_index(axis) == 0).astype(jnp.int32)
        tiny_g = tiny_g * on_owner
        nzero_g = nzero_g * on_owner
    elif coop and axis is not None:
        # legacy replicated tree-top fronts (SLU_COOP_SHARDED=0):
        # cooperative column-sharded LU over the full replicated
        # front; counters replicate, so take them from the owner only
        from .coop_lu import coop_partial_lu_batch
        with jax.named_scope("slu.partial_lu"):
            F, tiny_g, nzero_g = coop_partial_lu_batch(
                F, thresh, wb=wb, ndev=ndev, axis=axis)
        on_owner = (_flat_axis_index(axis) == 0).astype(jnp.int32)
        tiny_g = tiny_g * on_owner
        nzero_g = nzero_g * on_owner
        Lsrc, Usrc, upd_src = F[:, :, :wb], F[:, :wb, :], F[:, wb:, wb:]
    else:
        # pallas_diag=True is the merged-factor-segment promotion of
        # the Pallas panel-LU kernel (ops/pallas_lu.merged_eligible):
        # the caller resolved eligibility per member bucket, so this
        # call routes through the kernel unconditionally-if-available.
        # force_xla: the batch engine (batch/engine.py) traces this
        # body under jax.vmap, where a pallas_call's batching rule is
        # not a path we certify, so it pins the panel LU to XLA
        Lsrc, Usrc, upd_src, tiny_g, nzero_g = partial_lu_panels_batch(
            F, thresh, wb=wb,
            pallas=(False if force_xla
                    else True if pallas_diag else None))

    with jax.named_scope("slu.store"):
        rows = jnp.arange(mb)[:, None]
        colsw = jnp.arange(wb)[None, :]
        Lpanel = jnp.where(rows > colsw, Lsrc,
                           jnp.where(rows == colsw, one, 0))
        Upanel = jnp.where(colsw.T <= jnp.arange(mb)[None, :], Usrc, 0)
    Li = unit_lower_inverse(Lpanel[:, :wb, :])
    Ui = upper_inverse(Upanel[:, :, :wb])
    with jax.named_scope("slu.store"):
        L_flat = jax.lax.dynamic_update_slice(L_flat, Lpanel.reshape(-1),
                                              (L_off,))
        U_flat = jax.lax.dynamic_update_slice(U_flat, Upanel.reshape(-1),
                                              (U_off,))
        Li_flat = jax.lax.dynamic_update_slice(Li_flat, Li.reshape(-1),
                                               (Li_off,))
        Ui_flat = jax.lax.dynamic_update_slice(Ui_flat, Ui.reshape(-1),
                                               (Ui_off,))
        if mb > wb and (not sharded or tp > 0):
            upd = upd_src.reshape(-1)
            if axis is not None and coop:
                # coop content at the single owner-slot offset: sharded —
                # each device writes its OWN (rb, tp) owned-column slice
                # (device-varying, consumed device-locally by the sharded
                # parent); legacy replicated — every device writes the
                # SAME full square, so consumers read it locally either
                # way and no gather is ever needed
                off = upd_off
            elif axis is not None and gather:
                # ancestor propagation: the reference's dreduceAncestors3d /
                # Z-axis panel exchange becomes one tiled all_gather along
                # the mesh axis — device-major local slabs concatenate into
                # exactly the global slab layout
                with jax.named_scope("slu.dist.gather"):
                    upd = jax.lax.all_gather(upd, axis, tiled=True)
                off = upd_off
            elif axis is not None:
                # gather-free subforest interior (zone-affine placement):
                # every consumer of this slab lives on this device, so
                # each device writes only its own device-major slice and
                # no ICI traffic happens (dsparseTreeFactor's layer-local
                # phase, SRC/pdgstrf3d.c:292-322)
                off = upd_off + _flat_axis_index(axis) * upd.size
            else:
                off = upd_off
            upd_buf = jax.lax.dynamic_update_slice(upd_buf, upd, (off,))
    return (upd_buf, L_flat, U_flat, Li_flat, Ui_flat,
            tiny + tiny_g, nzero + nzero_g)


def _factor_group_impl_pair(vals, upd_buf, L_flat, U_flat, Li_flat,
                            Ui_flat, tiny, nzero, thresh, a_src,
                            a_dst, one_dst, ea_blocks, upd_off, L_off,
                            U_off, Li_off, Ui_off, *, mb: int,
                            wb: int, n_pad: int, ea_meta: tuple = (),
                            eb_meta: tuple = (),
                            axis: Optional[str] = None,
                            gather: bool = True, coop: bool = False,
                            pos_idx=None, cp: int = 0, tp: int = 0):
    """_factor_group_impl on stacked real/imag planes (ops/pair_lu):
    the complex-factorization body for platforms whose native complex
    lowering is broken (utils/platform.py gate).  Every flat is
    (2, N) REAL — exactly the solve-storage layout _solve_view
    produces — so the factor's outputs feed the existing sweeps with
    no re-encoding.  Assembly and extend-add are structural
    (plane-wise, vmapped over the plane axis, which preserves the
    scatter uniqueness/sortedness promises per plane); only the dense
    kernels carry pair arithmetic.  On a mesh (`axis`) the level's
    update slab is gathered plane-wise (device-major along the element
    axis of each plane, the real path's layout twice), and tree-top
    `coop` groups run the sharded cooperative chain in pair arithmetic
    (ops/coop_sharded.coop_sharded_lu_pair_batch)."""
    from .pair_lu import (partial_lu_pair_batch, unit_lower_inverse_pair,
                          upper_inverse_pair)
    rdt = L_flat.dtype
    sharded = coop and axis is not None and cp > 0
    if coop and axis is not None and not sharded:
        raise NotImplementedError(
            "the legacy replicated cooperative LU (SLU_COOP_SHARDED=0, "
            "ops/coop_lu.py) has no pair arithmetic; complex on a TPU "
            "mesh runs the sharded chain (ops/coop_sharded.py)")
    ncols = cp if sharded else mb
    one_pl = jnp.stack([jnp.ones((), rdt), jnp.zeros((), rdt)])

    @jax.named_scope("slu.assemble")
    def assemble(f, v, o):
        f = f.at[a_dst].add(v[a_src], mode="drop",
                            unique_indices=True,
                            indices_are_sorted=True)
        return f.at[one_dst].set(o, mode="drop", unique_indices=True)

    elem_blocks, blk_blocks = ea_blocks
    F = jax.vmap(assemble)(jnp.zeros((2, n_pad * mb * ncols), rdt),
                           vals, one_pl)
    F = jax.vmap(lambda f, u: _ea_add(
        f, u, elem_blocks, ea_meta, mb=mb, n_pad=n_pad,
        ncols=ncols))(F, upd_buf)
    F = jax.vmap(lambda f, u: _ea_add_blocks(
        f, u, blk_blocks, eb_meta, mb=mb, n_pad=n_pad,
        ncols=ncols))(F, upd_buf)
    F = F.reshape(2, n_pad, mb, ncols)
    if sharded:
        # counters replicate off the psums: the owner device counts
        from .coop_sharded import coop_sharded_lu_pair_batch
        with jax.named_scope("slu.partial_lu"):
            Lsrc, Usrc, upd_src, tiny_g, nzero_g = \
                coop_sharded_lu_pair_batch(F, pos_idx, thresh, wb=wb,
                                           cp=cp, tp=tp, axis=axis)
        on_owner = (_flat_axis_index(axis) == 0).astype(jnp.int32)
        tiny_g = tiny_g * on_owner
        nzero_g = nzero_g * on_owner
    else:
        with jax.named_scope("slu.partial_lu"):
            F, tiny_g, nzero_g = partial_lu_pair_batch(F, thresh, wb=wb)
        Lsrc, Usrc = F[:, :, :, :wb], F[:, :, :wb, :]
        upd_src = None      # F's trailing block, sliced where stored

    with jax.named_scope("slu.store"):
        rows = jnp.arange(mb)[:, None]
        colsw = jnp.arange(wb)[None, :]
        Lpanel = jnp.where(rows > colsw, Lsrc, 0)
        Lpanel = Lpanel.at[0].add(             # unit diagonal, plane 0
            jnp.where(rows == colsw, jnp.ones((), rdt), 0))
        Upanel = jnp.where(colsw.T <= jnp.arange(mb)[None, :], Usrc, 0)
    with jax.named_scope("slu.tri_inverse"):
        Li = unit_lower_inverse_pair(Lpanel[:, :, :wb, :])
        Ui = upper_inverse_pair(Upanel[:, :, :, :wb])

    with jax.named_scope("slu.store"):
        z = jnp.zeros((), jnp.int32)
        L_flat = jax.lax.dynamic_update_slice(
            L_flat, Lpanel.reshape(2, -1), (z, L_off))
        U_flat = jax.lax.dynamic_update_slice(
            U_flat, Upanel.reshape(2, -1), (z, U_off))
        Li_flat = jax.lax.dynamic_update_slice(
            Li_flat, Li.reshape(2, -1), (z, Li_off))
        Ui_flat = jax.lax.dynamic_update_slice(
            Ui_flat, Ui.reshape(2, -1), (z, Ui_off))
        if mb > wb and (not sharded or tp > 0):
            if upd_src is None:
                upd_src = F[:, :, wb:, wb:]
            upd = upd_src.reshape(2, -1)
            off = upd_off
            # the three mesh cases of _factor_group_impl, per plane
            if axis is not None and not coop:
                if gather:
                    with jax.named_scope("slu.dist.gather"):
                        upd = jax.lax.all_gather(upd, axis, axis=1,
                                                 tiled=True)
                else:
                    off = upd_off + _flat_axis_index(axis) * upd.shape[1]
            upd_buf = jax.lax.dynamic_update_slice(
                upd_buf, upd,
                (jnp.zeros((), getattr(off, "dtype", jnp.int32)), off))
    return (upd_buf, L_flat, U_flat, Li_flat, Ui_flat,
            tiny + tiny_g, nzero + nzero_g)




# Sweep storage codec: when the system is complex, X is carried as a
# REAL array with real/imag planes concatenated along the rhs axis,
# and the sweep matmuls contract the panel's real and imaginary parts
# against that encoding separately — the triangular sweeps execute NO
# complex arithmetic at all.  Complex gather/scatter in this sweep
# pattern has shown a per-process miscompile lottery on the
# forced-multi-device XLA:CPU client (stable wrong single elements;
# see tests/test_coop.py::test_complex_dist_solve_deterministic), and
# complex einsums in the transpose sweep showed the same
# order-dependent lottery under the full-suite compile mix (round-1
# test_trans_complex flake) — so both are kept out of the sweeps
# entirely.  Cost is nil: a complex matmul IS four real matmuls; this
# just writes them explicitly.  The factor path keeps complex storage
# (its ops have never misbehaved).

def _dec(xb, cplx: bool):
    if not cplx:
        return xb
    h = xb.shape[-1] // 2
    return jax.lax.complex(xb[..., :h], xb[..., h:])


def _enc(y, cplx: bool):
    if not cplx:
        return y
    return jnp.concatenate([y.real, y.imag], axis=-1)


def _mm_enc(sub: str, A, xe, cplx: bool):
    """einsum(sub, A, x) where x is real-view encoded (real/imag
    halves concatenated along the last axis); returns the encoded
    product.  Real A (real factor, complex rhs) contracts both halves
    in one einsum; complex A splits into real/imag contractions:
    (Ar + i·Ai)(xr + i·xi) = (Ar·xr − Ai·xi) + i·(Ar·xi + Ai·xr).
    A may also arrive pre-split as an (Ar, Ai) pair (the all-real
    solve storage, _solve_view) — then the program contains no
    complex extraction at all."""
    if isinstance(A, tuple):
        Ar, Ai = A
    elif not cplx or not jnp.issubdtype(A.dtype, jnp.complexfloating):
        return jnp.einsum(sub, A, xe)
    else:
        Ar, Ai = A.real, A.imag
    h = xe.shape[-1] // 2
    er = jnp.einsum(sub, Ar, xe)
    ei = jnp.einsum(sub, Ai, xe)
    return jnp.concatenate([er[..., :h] - ei[..., h:],
                            er[..., h:] + ei[..., :h]], axis=-1)


def _solve_view(flat):
    """Solve-storage view of a factor flat: a complex flat becomes a
    (2, N) stacked real/imag REAL array.  Used by the distributed
    solve loop so its compiled program contains no complex ops at all
    — complex dynamic-slice/real-extraction were the last complex
    family left in that program, and XLA:CPU's threaded runtime has
    produced rare nondeterministic NaN there (the
    test_complex_dist_solve_deterministic canary)."""
    if jnp.issubdtype(flat.dtype, jnp.complexfloating):
        return jnp.stack([flat.real, flat.imag])
    return flat


def _slice_panel(flat, off, size: int, shape: tuple):
    """dynamic_slice + reshape of one group's panel from a factor
    flat, handling both storages: a 1-D flat yields the panel array; a
    (2, N) stacked real/imag flat yields an (Ar, Ai) pair for
    _mm_enc.  `off` may be a traced jnp scalar (the in-program sweep)
    or a host int (`trisolve.pack_panels`) — the plane index matches
    its dtype either way (dynamic_slice requires uniform index
    dtypes)."""
    if flat.ndim == 2:
        off = jnp.asarray(off)
        P = jax.lax.dynamic_slice(
            flat, (jnp.zeros((), off.dtype), off),
            (2, size)).reshape((2,) + shape)
        return (P[0], P[1])
    return jax.lax.dynamic_slice(flat, (off,), (size,)).reshape(shape)


def _psub(P, fn):
    """Apply a slicing fn to a panel in either storage form."""
    return tuple(fn(p) for p in P) if isinstance(P, tuple) else fn(P)


@jax.named_scope("slu.fwd")
def _fwd_group_impl(X, L_flat, Li_flat, col_idx, struct_idx, L_off,
                    Li_off, *, mb: int, wb: int, n_pad: int,
                    cplx: bool = False):
    """Device-local sweep step: in distributed mode each device runs
    this on its own X copy (dummy indices elsewhere) and _solve_loop
    reconciles by psum-of-diffs at its static sync points."""
    xb = X[col_idx]                                     # (Np, wb, R̂)
    Li = _slice_panel(Li_flat, Li_off, n_pad * wb * wb,
                      (n_pad, wb, wb))
    y = _mm_enc("nvw,nwr->nvr", Li, xb, cplx)           # Li @ xb
    X = X.at[col_idx].set(y)
    if mb > wb:
        Lp = _slice_panel(L_flat, L_off, n_pad * mb * wb,
                          (n_pad, mb, wb))
        X = X.at[struct_idx].add(
            -_mm_enc("nsw,nwr->nsr",
                     _psub(Lp, lambda p: p[:, wb:, :]), y, cplx))
    return X




@jax.named_scope("slu.bwd")
def _bwd_group_impl(X, U_flat, Ui_flat, col_idx, struct_idx, U_off,
                    Ui_off, *, mb: int, wb: int, n_pad: int,
                    cplx: bool = False):
    xb = X[col_idx]
    if mb > wb:
        Up = _slice_panel(U_flat, U_off, n_pad * wb * mb,
                          (n_pad, wb, mb))
        xs = X[struct_idx]
        rhs = xb - _mm_enc("nws,nsr->nwr",
                           _psub(Up, lambda p: p[:, :, wb:]), xs, cplx)
    else:
        rhs = xb
    Ui = _slice_panel(Ui_flat, Ui_off, n_pad * wb * wb,
                      (n_pad, wb, wb))
    x1 = _mm_enc("nvw,nwr->nvr", Ui, rhs, cplx)
    return X.at[col_idx].set(x1)




# transpose sweeps: Mᵀ = Uᵀ·Lᵀ — forward on lower-triangular Uᵀ,
# backward on unit-upper Lᵀ, same schedule/groups, panels transposed
# on the fly (einsum-transpose is free on the MXU)

@jax.named_scope("slu.fwd")
def _fwd_group_T_impl(X, U_flat, Ui_flat, col_idx, struct_idx, U_off,
                      Ui_off, *, mb: int, wb: int, n_pad: int,
                      cplx: bool = False):
    xb = X[col_idx]
    Ui = _slice_panel(Ui_flat, Ui_off, n_pad * wb * wb,
                      (n_pad, wb, wb))
    y = _mm_enc("nwv,nwr->nvr", Ui, xb, cplx)       # Uiᵀ @ xb
    X = X.at[col_idx].set(y)
    if mb > wb:
        Up = _slice_panel(U_flat, U_off, n_pad * wb * mb,
                          (n_pad, wb, mb))
        X = X.at[struct_idx].add(
            -_mm_enc("nws,nwr->nsr",
                     _psub(Up, lambda p: p[:, :, wb:]), y, cplx))
    return X




@jax.named_scope("slu.bwd")
def _bwd_group_T_impl(X, L_flat, Li_flat, col_idx, struct_idx, L_off,
                      Li_off, *, mb: int, wb: int, n_pad: int,
                      cplx: bool = False):
    xb = X[col_idx]
    if mb > wb:
        Lp = _slice_panel(L_flat, L_off, n_pad * mb * wb,
                          (n_pad, mb, wb))
        xs = X[struct_idx]
        rhs = xb - _mm_enc("nsw,nsr->nwr",
                           _psub(Lp, lambda p: p[:, wb:, :]), xs, cplx)
    else:
        rhs = xb
    Li = _slice_panel(Li_flat, Li_off, n_pad * wb * wb,
                      (n_pad, wb, wb))
    x1 = _mm_enc("nwv,nwr->nvr", Li, rhs, cplx)     # Liᵀ @ rhs
    return X.at[col_idx].set(x1)




# --------------------------------------------------------------------
# staged execution: one small jitted program PER GROUP instead of one
# giant fused program.  XLA compile time is superlinear in program
# size (measured: the 143-group k=64 fused program needs ~29 min on
# this 1-core host; its groups compiled separately total minutes), so
# past a group-count threshold the fused formulation loses more wall
# clock to the compiler than it saves in dispatch.  The staged mode
# trades ~one dispatch per group (µs) for bounded compiles: the
# per-group jits are cached by shape signature (mb, wb, n_pad, index
# lengths, ea_meta) and hit the persistent compilation cache across
# runs.  Buffers stream through the groups by DONATION (verified
# in-place on CPU and TPU), so no slab copies happen at dispatch
# boundaries.  This is the audikw_1-scale path: the reference's
# pdgstrf loop is O(nsupers) runtime and O(1) code size
# (SRC/pdgstrf.c:1108); staged execution restores that asymptotic for
# the compile while keeping every group body identical to the fused
# path (_factor_group_impl / _fwd_group_impl / _bwd_group_impl).
# --------------------------------------------------------------------

def staged_enabled(sched) -> bool:
    """Use per-group staged execution?  SLU_STAGED=1 forces on, =0
    forces off; default: on past SLU_STAGED_MIN_GROUPS groups (the
    regime where one fused program out-compiles its own runtime)."""
    v = flags.env_str("SLU_STAGED", "auto").strip().lower()
    if v in ("1", "true", "on"):
        return True
    if v in ("0", "false", "off"):
        return False
    try:
            thresh = flags.env_int("SLU_STAGED_MIN_GROUPS", 96)
    except ValueError:
        thresh = 96
    return len(sched.groups) > thresh


# --------------------------------------------------------------------
# level-merged factor segments (ISSUE 12): the PR 7 trisolve merge
# discipline (SLU_TRISOLVE_MERGE_CELLS) applied to the factor sweep.
# The staged factor dispatch pays ~one Python dispatch per group; the
# deep narrow chain tail of an elimination tree is hundreds of SMALL
# groups whose device bodies are µs-scale, so the sweep is
# dispatch-latency-bound exactly like the nrhs=1 solve was.  Chains
# of small consecutive groups coalesce into ONE donated-buffer
# dispatch unit (`_staged_factor_segment`): the extend-add slab
# streams through the segment in place, the member bodies are
# literally `_factor_group_impl` in schedule order — so the merged
# sweep is bitwise-identical to the per-group dispatch by
# construction (pinned at fp64 in tests/test_factor_merge.py) — and
# the per-segment programs are warmed/persisted exactly like the
# solve segments (utils/warmup.staged_signatures).
# --------------------------------------------------------------------

FACTOR_MERGE_CELLS_DEFAULT = 65536


def factor_merge_cells() -> int:
    """A factor group whose front-cell count (n_loc · mb · ncols) is
    at or below this joins a merged staged dispatch segment
    (SLU_FACTOR_MERGE_CELLS, default 65536 — the trisolve merge
    bound's sibling): small enough that the group body is
    dispatch-dominated.  0 restores the legacy per-group staged
    dispatch (the A/B arm)."""
    try:
        return max(0, flags.env_int("SLU_FACTOR_MERGE_CELLS",
                                    FACTOR_MERGE_CELLS_DEFAULT))
    except ValueError:
        return FACTOR_MERGE_CELLS_DEFAULT


def factor_seg_cells() -> int:
    """Total front-cell budget of one merged factor segment
    (SLU_FACTOR_SEG_CELLS, default 1048576): bounds per-segment
    program size so segment compiles stay in the per-group compile
    class (the SLU_TRISOLVE_SEG_CELLS sibling)."""
    try:
        return max(1, flags.env_int("SLU_FACTOR_SEG_CELLS", 1048576))
    except ValueError:
        return 1048576


def factor_merge_on() -> bool:
    return factor_merge_cells() > 0


def compute_factor_segments(sched, cells: int | None = None,
                            cap: int | None = None) -> list:
    """Group indices per merged dispatch segment, in schedule order
    (the trisolve segment pass, build_trisolve, applied to the factor
    sweep's cost model): groups at or below the `cells` bound chain
    into the open segment until `cap`; a large group stands alone —
    its LU/GEMM body is real work and chaining it buys nothing."""
    cells = factor_merge_cells() if cells is None else cells
    cap = factor_seg_cells() if cap is None else cap
    segments: list = []
    cur: list = []
    cur_cells = 0
    for gi, g in enumerate(sched.groups):
        ncols = g.cp if g.cp > 0 else g.mb
        c = g.n_loc * g.mb * ncols
        small = c <= cells
        if cur and ((not small) or cur_cells + c > cap):
            segments.append(cur)
            cur, cur_cells = [], 0
        cur.append(gi)
        cur_cells += c
        if not small:
            segments.append(cur)
            cur, cur_cells = [], 0
    if cur:
        segments.append(cur)
    return segments


def get_factor_segments(sched) -> list:
    """Cached factor segments for a schedule, keyed by the merge
    knobs (a mid-process flag change rebuilds instead of hitting a
    stale layout)."""
    cache = getattr(sched, "_factor_segments", None)
    if cache is None:
        cache = sched._factor_segments = {}
    key = (factor_merge_cells(), factor_seg_cells())
    if key not in cache:
        cache[key] = compute_factor_segments(sched)
    return cache[key]


def factor_seg_metas(sched, members, dtype) -> tuple:
    """The static meta tuple of one merged factor segment's members,
    in schedule order — THE single definition of the segment jit's
    static key, shared by the dispatch site (_staged_factor_run) and
    the AOT warmup (utils/warmup.py): a drift between the two would
    turn warmed programs into dead compiles (the trisolve seg_metas
    contract).  The last leg is the per-member Pallas panel-LU
    promotion decision (ops/pallas_lu.merged_eligible) — it shapes
    the program, so it keys the cache."""
    from . import pallas_lu
    dtype = np.dtype(dtype)
    rdt = _real_dtype(dtype)
    return tuple(
        (sched.groups[i].mb, sched.groups[i].wb,
         sched.groups[i].n_loc, sched.groups[i].ea_meta,
         sched.groups[i].eb_meta,
         bool(pallas_lu.merged_eligible(
             sched.groups[i].wb, sched.groups[i].mb, rdt)))
        for i in members)


def factor_arm(sched=None, dtype=None) -> str:
    """One-token description of the factor-sweep arm —
    legacy|merged|merged+pallas — stamped onto chip_smoke.py's
    records (the trisolve active_arm sibling).
    With a (schedule, dtype) the "+pallas" suffix is claimed only
    when some merged segment member actually routes through the
    kernel; without one it falls back to the env resolution.
    Complex dtypes always report "legacy": their staged dispatch
    stays per-group (see _staged_factor_run — claiming merged there
    would be exactly the misattribution the arm field exists to
    prevent)."""
    if not factor_merge_on():
        return "legacy"
    from . import pallas_lu
    if dtype is not None and np.dtype(dtype).kind == "c":
        return "legacy"
    if sched is not None and dtype is not None:
        rdt = _real_dtype(np.dtype(dtype))
        if any(pallas_lu.merged_eligible(sched.groups[i].wb,
                                         sched.groups[i].mb, rdt)
               for seg in get_factor_segments(sched) for i in seg):
            return "merged+pallas"
        return "merged"
    # schedule-less fallback mirrors pallas_lu.merged_eligible's
    # resolution (unset == "auto" -> kernel on real TPU): the arm the
    # serve layer reports must agree with the arm records are stamped
    # with, or TTL hints chase the wrong history
    flag = flags.env_str("SLU_TPU_PALLAS", "auto").strip().lower()
    if flag == "1" or (flag not in ("0", "false", "off")
                       and jax.default_backend() == "tpu"):
        return "merged+pallas"
    return "merged"


@functools.partial(jax.jit,
                   static_argnames=("mb", "wb", "n_pad", "ea_meta",
                                    "eb_meta", "pair"),
                   donate_argnums=(0,))
def _staged_factor_group(upd_buf, vals, thresh, a_src, a_dst, one_dst,
                         ea_blocks, upd_off, *, mb: int, wb: int,
                         n_pad: int, ea_meta: tuple,
                         eb_meta: tuple = (), pair: bool = False):
    """One factor group as its own program: group-LOCAL panel outputs
    (offset 0 into exact-size flats) instead of writes into the global
    slabs; `upd_buf` is donated so the extend-add buffer streams
    through the group sequence in place."""
    dtype = upd_buf.dtype
    lead = (2,) if pair else ()
    z32 = jnp.zeros((), jnp.int32)
    with jax.default_matmul_precision("float32"):
        return _factor_group_impl(
            vals, upd_buf,
            jnp.zeros(lead + (n_pad * mb * wb,), dtype),
            jnp.zeros(lead + (n_pad * wb * mb,), dtype),
            jnp.zeros(lead + (n_pad * wb * wb,), dtype),
            jnp.zeros(lead + (n_pad * wb * wb,), dtype),
            z32, z32, thresh, a_src, a_dst, one_dst, ea_blocks,
            upd_off, z32, z32, z32, z32,
            mb=mb, wb=wb, n_pad=n_pad, ea_meta=ea_meta,
            eb_meta=eb_meta, pair=pair)


@functools.partial(jax.jit, static_argnames=("metas", "pair"),
                   donate_argnums=(0,))
def _staged_factor_segment(upd_buf, vals, thresh, a_srcs, a_dsts,
                           one_dsts, ea_blockss, upd_offs, *, metas,
                           pair: bool = False):
    """One merged factor segment as a single program: `metas` is the
    static tuple from factor_seg_metas — (mb, wb, n_pad, ea_meta,
    eb_meta, use_pallas) per member — so a segment signature compiles
    once and is shared by every factorization with the same layout.
    `upd_buf` is donated and streams through the whole segment chain
    in place (the _staged_factor_group discipline, now amortized over
    the members); the member bodies run in exactly the order and with
    exactly the operands of the per-group dispatch, so results are
    bitwise-identical to it."""
    dtype = upd_buf.dtype
    lead = (2,) if pair else ()
    z32 = jnp.zeros((), jnp.int32)
    panels = []
    tiny = nzero = z32
    with jax.default_matmul_precision("float32"):
        for ((mb, wb, n_pad, ea_meta, eb_meta, use_pallas), a_src,
             a_dst, one_dst, ea_blocks, upd_off) in zip(
                 metas, a_srcs, a_dsts, one_dsts, ea_blockss,
                 upd_offs):
            (upd_buf, L, U, Li, Ui, t, z) = _factor_group_impl(
                vals, upd_buf,
                jnp.zeros(lead + (n_pad * mb * wb,), dtype),
                jnp.zeros(lead + (n_pad * wb * mb,), dtype),
                jnp.zeros(lead + (n_pad * wb * wb,), dtype),
                jnp.zeros(lead + (n_pad * wb * wb,), dtype),
                z32, z32, thresh, a_src, a_dst, one_dst, ea_blocks,
                upd_off, z32, z32, z32, z32,
                mb=mb, wb=wb, n_pad=n_pad, ea_meta=ea_meta,
                eb_meta=eb_meta, pair=pair,
                pallas_diag=use_pallas)
            panels.append((L, U, Li, Ui))
            tiny = tiny + t
            nzero = nzero + z
    return upd_buf, tuple(panels), tiny, nzero


@functools.partial(jax.jit,
                   static_argnames=("mb", "wb", "n_pad", "cplx",
                                    "kind"),
                   donate_argnums=(0,))
def _staged_sweep_group(X, pflat, iflat, col_idx, struct_idx, *,
                        mb: int, wb: int, n_pad: int, cplx: bool,
                        kind: str):
    """One triangular-sweep group step (X donated; panels group-local,
    offsets 0).  kind ∈ {fwd, bwd, fwdT, bwdT}."""
    fn = {"fwd": _fwd_group_impl, "bwd": _bwd_group_impl,
          "fwdT": _fwd_group_T_impl, "bwdT": _bwd_group_T_impl}[kind]
    z32 = jnp.zeros((), jnp.int32)
    with jax.default_matmul_precision("float32"):
        return fn(X, pflat, iflat, col_idx, struct_idx, z32, z32,
                  mb=mb, wb=wb, n_pad=n_pad, cplx=cplx)


@functools.partial(jax.jit, static_argnames=("dtype_str",))
def _vals_ext(v, dtype_str: str):
    dtype = np.dtype(dtype_str)
    return jnp.concatenate([v.astype(dtype), jnp.zeros(1, dtype)])


@functools.partial(jax.jit, static_argnames=("dtype_str",))
def _vals_ext_pair(v, dtype_str: str):
    dtype = np.dtype(dtype_str)
    return jnp.concatenate([v.astype(dtype), jnp.zeros((2, 1), dtype)],
                           axis=1)


def _pallas_shapes(sched, dtype, picked) -> list:
    """[n_loc, mb, wb] of the groups of one factorization whose panel
    LU runs as the Pallas kernel: those whose segment meta says so
    (`picked`, the last leg of `factor_seg_metas` in group order), or,
    where SLU_TPU_PALLAS=1 routes every call (`dense_lu._use_pallas`
    with no override; never a complex or pair-stored front), every
    usable bucket."""
    from . import pallas_lu
    if pallas_lu.enabled(dtype):
        picked = (pallas_lu.usable(g.mb, dtype) for g in sched.groups)
    return [[g.n_loc, g.mb, g.wb]
            for g, p in zip(sched.groups, picked) if p]


def _staged_factor_run(sched, vals, thresh_np, dtype,
                       pair: bool = False):
    """Python-dispatched group loop: returns (panels, tiny, nzero)
    where panels[i] = (L, U, Li, Ui) group-local flats for group i and
    the counters are device scalars (no per-group host sync — the
    dispatch loop must stay ahead of device execution).  In pair mode
    `vals` arrives host-encoded as (2, nnz) real planes and every
    buffer carries the leading plane axis.

    Two host spans name the run's halves: `slu.fact.dispatch` around
    the loop of donated-buffer dispatches, `slu.fact.wait` around the
    one blocking read of the counters (the chip is still working
    through the queued segments then; what is left of the device's
    idle time inside a factorization lies under the first).  What was
    dispatched (programs, and the shapes of the members that took the
    Pallas panel LU) is stamped for this thread's `factorize_device`
    (`obs.stamp_cost("dispatch", ...)`)."""
    dtype = np.dtype(dtype)
    rdt = _real_dtype(dtype)
    if pair:
        vals_ext = _vals_ext_pair(vals, rdt.str)
        upd_buf = jnp.zeros((2, sched.upd_total + sched.upd_pad), rdt)
    else:
        vals_ext = _vals_ext(vals, dtype.str)
        upd_buf = jnp.zeros(sched.upd_total + sched.upd_pad, dtype)
    thresh = jnp.asarray(thresh_np, dtype=rdt)
    panels = []
    tiny = nzero = jnp.zeros((), jnp.int32)
    if factor_merge_on() and not pair and dtype.kind != "c":
        # level-merged arm: one dispatch per SEGMENT (every segment,
        # singletons included, so the dispatched program set is
        # exactly what warmup_staged compiled); panels flatten back
        # to the per-group list every consumer expects.  REAL dtypes
        # only, and the ground is an XLA:CPU one: complex multiplies
        # re-associate when XLA:CPU fuses across group boundaries
        # (measured there: ~1e-17 element drift vs the per-group
        # dispatch; the same program-shape-sensitive complex lowering
        # XLA:CPU is documented for above, _mm_enc), so complex/pair
        # lanes keep the proven per-group dispatch and the bitwise
        # contract stays exact where it is pinned (real fp64, the PR 7
        # bar).  Whether the merged arm would hold for pair planes on
        # a TPU has not been tried: the one cell on the staged path,
        # `lap3d_k48.step`, is real (PERF.md section 4)
        with obs.span("fact.dispatch", cat="fact"):
            for seg in get_factor_segments(sched):
                ops = [sched.groups[i].dev(squeeze=True)[:4]
                       for i in seg]
                (upd_buf, pseg, t, z) = _staged_factor_segment(
                    upd_buf, vals_ext, thresh,
                    tuple(o[0] for o in ops), tuple(o[1] for o in ops),
                    tuple(o[2] for o in ops), tuple(o[3] for o in ops),
                    tuple(jnp.asarray(sched.groups[i].upd_off_global,
                                      jnp.int64) for i in seg),
                    metas=factor_seg_metas(sched, seg, dtype),
                    pair=pair)
                panels.extend(pseg)
                tiny = tiny + t
                nzero = nzero + z
        del upd_buf
        segs = get_factor_segments(sched)
        obs.stamp_cost("dispatch", (len(segs), _pallas_shapes(
            sched, dtype, (m[-1] for seg in segs for m in
                           factor_seg_metas(sched, seg, dtype)))))
        with obs.span("fact.wait", cat="fact"):
            return panels, int(tiny), int(nzero)
    with obs.span("fact.dispatch", cat="fact"):
        for g in sched.groups:
            a_src, a_dst, one_dst, ea_blocks = g.dev(squeeze=True)[:4]
            (upd_buf, L, U, Li, Ui, t, z) = _staged_factor_group(
                upd_buf, vals_ext, thresh, a_src, a_dst, one_dst,
                ea_blocks, jnp.asarray(g.upd_off_global, jnp.int64),
                mb=g.mb, wb=g.wb, n_pad=g.n_loc, ea_meta=g.ea_meta,
                eb_meta=g.eb_meta, pair=pair)
            panels.append((L, U, Li, Ui))
            tiny = tiny + t
            nzero = nzero + z
    del upd_buf
    obs.stamp_cost("dispatch", (len(sched.groups),
                                _pallas_shapes(sched, dtype, ())))
    with obs.span("fact.wait", cat="fact"):
        return panels, int(tiny), int(nzero)


def _staged_sweeps(sched, panels, bf, dtype, trans: bool,
                   pair: bool = False, packs=None):
    """Forward+backward sweeps over the staged panels.  `bf` is the
    RHS in factor ordering, shape (n, nrhs); returns X[:n].  In pair
    mode (plane-stored panels) `bf` arrives already real-view encoded
    (n, 2·nrhs) and the result returns encoded — the caller decodes on
    the host, so the program stays complex-free.

    Under the merged trisolve arm (SLU_TRISOLVE, ops/trisolve.py)
    the per-group dispatch chain collapses to one dispatch per merged
    SEGMENT over the lsum layout — the same arithmetic, a
    fraction of the Python/dispatch overhead at small nrhs.  `packs`
    lets a caller that solves repeatedly against one panel set (the
    staged fused solver's refinement loop) pre-pack once.  Callers:
    the staged fused solver under either arm, and a `StagedLU`'s
    FACTORED solve under the legacy arm (under the merged one that
    solve is one packed program, `_solve_device_common`)."""
    from . import trisolve
    if trisolve.trisolve_mode() == "merged":
        ts = trisolve.get_trisolve(sched)
        if packs is None:
            packs = trisolve.pack_device(sched, panels)
        return trisolve.staged_sweeps(ts, packs, bf, dtype, trans,
                                      pair=pair)
    dtype = np.dtype(dtype)
    n = sched.n
    if pair:
        cplx = True
        X = jnp.zeros((n + 1, bf.shape[1]), bf.dtype)
        X = X.at[:n, :].set(bf)
    else:
        xdt = jnp.promote_types(dtype, bf.dtype)
        cplx = bool(jnp.issubdtype(xdt, jnp.complexfloating))
        X = jnp.zeros((n + 1, bf.shape[1]), xdt)
        X = X.at[:n, :].set(bf.astype(xdt))
        X = _enc_jit(X, cplx)
    # trans solves Mᵀ = Uᵀ·Lᵀ: forward on Uᵀ panels, backward on Lᵀ
    fidx, fiidx = (1, 3) if trans else (0, 2)   # U,Ui / L,Li
    bidx, biidx = (0, 2) if trans else (1, 3)
    fkind, bkind = ("fwdT", "bwdT") if trans else ("fwd", "bwd")
    for g, p in zip(sched.groups, panels):
        ci, si = g.dev(squeeze=True)[5:7]
        X = _staged_sweep_group(X, p[fidx], p[fiidx], ci, si,
                                mb=g.mb, wb=g.wb, n_pad=g.n_loc,
                                cplx=cplx, kind=fkind)
    for g, p in zip(reversed(sched.groups), reversed(panels)):
        ci, si = g.dev(squeeze=True)[5:7]
        X = _staged_sweep_group(X, p[bidx], p[biidx], ci, si,
                                mb=g.mb, wb=g.wb, n_pad=g.n_loc,
                                cplx=cplx, kind=bkind)
    if pair:
        return X[:sched.n]          # still encoded; host decodes
    return _dec_jit(X, cplx)[:sched.n]


@functools.partial(jax.jit, static_argnames=("cplx",))
def _enc_jit(X, cplx):
    return _enc(X, cplx)


@functools.partial(jax.jit, static_argnames=("cplx",))
def _dec_jit(X, cplx):
    return _dec(X, cplx)


# --------------------------------------------------------------------
# single-device driver API
# --------------------------------------------------------------------

@dataclasses.dataclass
class DeviceLU:
    """Flat device factor storage (dLocalLU_t analog; the slab layout
    follows the reference's GPU flattened mirrors)."""
    plan: FactorPlan
    schedule: BatchedSchedule
    dtype: np.dtype
    L_flat: jnp.ndarray
    U_flat: jnp.ndarray
    Li_flat: jnp.ndarray
    Ui_flat: jnp.ndarray
    tiny_pivots: int
    # what `factorize_device` dispatched for this handle (`_route`)
    route: dict | None = None


@dataclasses.dataclass
class StagedLU:
    """Device factor storage in per-group panels (staged execution).
    Group-local flats concatenated in group order ARE the DeviceLU
    slab layout (offsets are cumulative in group order), so consumers
    that need the global view (get_diag_u) walk `panels` directly."""
    plan: FactorPlan
    schedule: BatchedSchedule
    dtype: np.dtype
    panels: list               # per group (L, U, Li, Ui) local flats
    tiny_pivots: int
    # what `factorize_device` dispatched for this handle (`_route`);
    # None for a handle built elsewhere (the batch engine, a restore)
    route: dict | None = None

    def held_bytes(self) -> int:
        # pair-stored panels are real arrays of 2× the element count;
        # nbytes counts either storage correctly
        return sum(int(a.nbytes) for p in self.panels for a in p)


def _lu_is_pair(lu) -> bool:
    """Factors stored as stacked real/imag planes?  (2, N) flats /
    panels discriminate from the native 1-D flat storage."""
    if isinstance(lu, StagedLU):
        return bool(lu.panels) and lu.panels[0][0].ndim == 2
    return lu.L_flat.ndim == 2


# serializes whole-phase jit-wrapper construction across threads (the
# wrappers are cheap; the point is ONE wrapper object per key so the
# underlying jit cache dedupes compiles)
_phase_fns_lock = threading.Lock()


def _phase_fns(sched, dtype, thresh_np, pair=None):
    """Cached whole-phase jitted programs for a (schedule, dtype):
    factor, solve and transpose-solve each compile ONCE and run as a
    single dispatch (vs one dispatch per group).  Backed by
    factor_dist's shared _factor_loop/_solve_loop so every execution
    mode runs the same group-loop code.

    `pair` selects plane storage (default: the env-resolved
    _pair_mode).  Solve-time callers pass the HANDLE's actual storage
    (_lu_is_pair) so a factorization held across an env change still
    gets a program matching its flats.

    Guarded by a module lock: the serve layer's first concurrent
    solves on a fresh schedule would otherwise each build their OWN
    jit wrapper (last-wins dict write) and trace/compile the same
    program once per racing thread."""
    if pair is None:
        pair = _pair_mode(dtype)
    from . import trisolve
    # the trisolve arm shapes the solve program (_solve_loop routes
    # through the merged lsum sweep), so it keys the cache — a
    # mid-process SLU_TRISOLVE change builds fresh programs instead
    # of hitting a stale arm
    key = (np.dtype(dtype).str, float(thresh_np), pair,
           trisolve.trisolve_mode(), trisolve.merge_cells_limit(),
           trisolve.seg_cells_limit())
    # lock-free hit path: entries are inserted fully formed under the
    # lock, and dict reads are GIL-atomic — hot solve dispatches never
    # contend on the module lock
    cache = getattr(sched, "_phase_fns", None)
    if cache is not None:
        fns = cache.get(key)
        if fns is not None:
            return fns
    with _phase_fns_lock:
        cache = getattr(sched, "_phase_fns", None)
        if cache is None:
            cache = sched._phase_fns = {}
        if key in cache:
            return cache[key]
        from ..parallel.factor_dist import _factor_loop, _solve_loop
        dtype = np.dtype(dtype)

        # the index constants of both traces, built once and only
        # when one of them is traced: a process whose programs all
        # come from the exported store (resilience/aot.py) never
        # uploads them
        per_group = functools.cache(
            lambda: [g.dev(squeeze=True) for g in sched.groups])

        # the programs' names are what a profiler trace calls them
        # (`jit_slu_factor`), and part of their persistent-cache key,
        # which the kernels' scope names are not: a rename is what
        # makes a cache written before the scopes existed miss once
        @jax.jit
        def slu_factor(vals):
            return _factor_loop(sched, vals, thresh_np, dtype,
                                per_group(), None, pair=pair)

        @functools.partial(jax.jit, static_argnames=("trans",))
        def slu_solve(L, U, Li, Ui, b, trans=False):
            return _solve_loop(sched, (L, U, Li, Ui), b, dtype,
                               [(t[5], t[6]) for t in per_group()],
                               None, trans=trans, pair=pair)

        # compile telemetry (obs/compile_watch.py): each whole-phase
        # program reports its jit cache misses with shape/dtype
        # attribution — the recompile counter the
        # zero-recompiles-after-warmup contract is pinned on.  The proxies
        # delegate lower()/_cache_size() to the jits underneath.
        # Where a compile cache is kept the factor program is
        # AOT-wrapped (resilience/aot.py): a fresh process
        # deserializes the persisted export instead of re-tracing the
        # whole-phase factor.  The solve twin keeps its plain jit
        # here (static `trans` leg; the serve hot path's solve program
        # is the packed one, AOT-wrapped in
        # trisolve._solve_packed_fn) and rides the compilation-cache
        # leg.
        # A pair-stored program is all-real and runs on the default
        # backend, so it is wrapped like a real one.  Natively complex
        # programs are not: the complex-on-TPU platform gate
        # (utils/platform.py) executes them on the host CPU while the
        # default backend stays TPU, and an export records ONE
        # platform — the gated dispatch would be refused at call time.
        from ..resilience import aot
        factor_w = slu_factor
        if pair or dtype.kind != "c":
            factor_w = aot.wrap_jit(
                "phase_factor", slu_factor,
                aot.schedule_fingerprint(
                    sched, dtype,
                    extra=("phase_factor", bool(pair),
                           float(thresh_np))))
        cache[key] = (
            obs.watch_jit("factor", factor_w),
            obs.watch_jit("solve", slu_solve))
        return cache[key]


def _route(sched, dispatch: str, segments: int, pallas: list) -> dict:
    """Which route one factorization took and what it dispatched, as
    the health ring's `last_factor` and `Stats.dispatch` carry it:
    `dispatch` "staged" (a program a segment, `staged_enabled`) or
    "program" (the one `jit_slu_factor`), `segments` the factor
    programs dispatched, `groups` the schedule's, `pallas_buckets` the
    groups whose panel LU ran as the Pallas kernel and
    `pallas_shapes` their [n_loc, mb, wb] (`_pallas_shapes`)."""
    return {"dispatch": dispatch, "segments": int(segments),
            "groups": len(sched.groups), "pallas_buckets": len(pallas),
            "pallas_shapes": pallas}


def sweep_programs(lu) -> int:
    """Programs one triangular solve on this handle dispatches: the
    one solve program (`jit_slu_solve_packed` under the merged arm,
    whatever the handle's form), or, for a `StagedLU` under the legacy
    sweep, a program a group each way."""
    from . import trisolve
    if trisolve.sweeps_packed() or not isinstance(lu, StagedLU):
        return 1
    return 2 * len(lu.schedule.groups)


def factorize_device(plan: FactorPlan, scaled_vals: np.ndarray,
                     dtype=np.float64):
    from . import trisolve
    sched = get_schedule(plan, 1)
    dtype = np.dtype(dtype)
    pair = _pair_mode(dtype)
    if staged_enabled(sched):
        # the staged run casts on the device (`_vals_ext`): what is
        # left of the one-program branch's span is the pair encode
        with obs.span("fact.scale", cat="fact"):
            vin = (_pair_encode_vals(scaled_vals, dtype) if pair
                   else np.asarray(scaled_vals))
        panels, tiny, nzero = _staged_factor_run(
            sched, jnp.asarray(vin),
            _thresh_for(plan, dtype), dtype, pair=pair)
        lu = StagedLU(plan=plan, schedule=sched, dtype=dtype,
                      panels=panels, tiny_pivots=tiny,
                      route=_route(sched, "staged",
                                   *obs.take_cost("dispatch")))
    else:
        factor_fn, _ = _phase_fns(sched, dtype,
                                  _thresh_for(plan, dtype), pair=pair)
        with obs.span("fact.scale", cat="fact"):
            vin = (_pair_encode_vals(scaled_vals, dtype) if pair
                   else scaled_vals.astype(dtype))
        vj = jnp.asarray(vin)
        (L_flat, U_flat, Li_flat, Ui_flat, tiny,
         nzero) = factor_fn(vj)
        # `tiny` and `nzero` are still the running program's futures
        lu = DeviceLU(plan=plan, schedule=sched, dtype=dtype,
                      L_flat=L_flat, U_flat=U_flat,
                      Li_flat=Li_flat, Ui_flat=Ui_flat,
                      tiny_pivots=tiny,
                      route=_route(sched, "program", 1,
                                   _pallas_shapes(sched, dtype, ())))
    # a factorization under the merged sweep hands back a handle whose
    # packs are in flight: `jit_slu_pack` is dispatched on the factor
    # program's output futures BEFORE the blocking reads below, so the
    # host hands out its buffers while the chip factors.  The staged
    # run has blocked on its counts already (`slu.fact.wait`), so its
    # pack hides nothing: on `lap3d_k48.step` the chip idles some 21 ms
    # a step under `slu.solve.pack` (PERF.md section 5)
    if trisolve.sweeps_packed():
        trisolve.get_packs(lu, at="factor")
    nzero = int(nzero)
    lu.tiny_pivots = int(lu.tiny_pivots)
    if nzero > 0:
        # nothing of the dropped handle stays within a caller's reach
        obs.take_cost("pack")
        # reference semantics: U(i,i) == 0 with ReplaceTinyPivot=NO is
        # the info=i singularity signal (SRC/pdgstrf.c header); the
        # host backend raises for the same input
        raise ZeroDivisionError(
            f"factorization hit {nzero} exactly-zero pivot(s); "
            "the matrix is singular (enable replace_tiny_pivot to "
            "perturb instead)")
    return lu


def _solve_device_common(lu, b: np.ndarray, trans: bool):
    squeeze = b.ndim == 1
    bb = b[:, None] if squeeze else b
    # promote rather than cast: a complex rhs against a real factor
    # must stay complex (matmuls promote; matches the host backend)
    xdt = np.promote_types(lu.dtype, bb.dtype)
    # pair-stored factors (complex planes, _pair_mode): the rhs is
    # real-view encoded on the host so the compiled sweep contains no
    # complex ops at all (the whole point of the storage)
    pair = _lu_is_pair(lu)
    if pair:
        # the host's plane encode (and decode, below) of a sweep: a
        # leaf span of its own, outside `slu.solve.sweep`
        with obs.span("solve.codec", cat="solve"):
            bin_ = _pair_encode_rhs(bb.astype(xdt))
    else:
        bin_ = bb.astype(xdt)
    from . import trisolve
    merged = trisolve.sweeps_packed()
    # merged: the handle-cached packed panels, so repeated FACTORED
    # solves skip the per-solve re-slice.  A hit since
    # `factorize_device` packs; taken BEFORE the sweep span opens
    # all the same: a handle made under another arm or cell limit
    # packs here (`slu.solve.pack`), and that is not sweep time
    if merged:
        trisolve.get_packs(lu)
    with obs.span("solve.sweep", cat="solve",
                  args={"nrhs": bb.shape[1], "trans": int(trans)}):
        if merged:
            # the packed FACTORED fast path (ops/trisolve.py): panels
            # pre-sliced once per factorization, lsum layout instead
            # of scatter-adds — the serve hot path's program, ONE
            # dispatch a sweep for a DeviceLU and a StagedLU alike
            # (the pack's body and the sweep's member bodies are the
            # same for both forms; the staged rule is the factor
            # program's).  Cost attribution happens inside
            # solve_packed (same thread-local hand-off as below; its
            # own get_packs is a hit by now).
            X = trisolve.solve_packed(lu, bin_, trans)
        elif isinstance(lu, StagedLU):
            # the legacy sweep on per-group panels: a program a group
            # each way
            X = _staged_sweeps(lu.schedule, lu.panels,
                               jnp.asarray(bin_), lu.dtype, trans,
                               pair=pair)
        else:
            _, solve_fn = _phase_fns(lu.schedule, lu.dtype,
                                     _thresh_for(lu.plan, lu.dtype),
                                     pair=pair)
            bj = jnp.asarray(bin_)
            # `trans` passed POSITIONALLY: a static_argnames keyword
            # call drops jax to the slow python dispatch path (the PR
            # 7 lesson, enforced by slulint's static-kwarg rule)
            X = solve_fn(lu.L_flat, lu.U_flat, lu.Li_flat,
                         lu.Ui_flat, bj, trans)
        with obs.span("solve.fetch", cat="solve"):
            out = np.asarray(X)
    if pair:
        with obs.span("solve.codec", cat="solve"):
            out = _pair_decode_sol(out, xdt)
    return out[:, 0] if squeeze else out


def solve_device(lu: DeviceLU, b: np.ndarray) -> np.ndarray:
    """b in factor ordering, (n,) or (n, nrhs); returns same shape."""
    return _solve_device_common(lu, b, trans=False)


def solve_device_trans(lu: DeviceLU, b: np.ndarray) -> np.ndarray:
    """Solve Mᵀ·x = b (factor ordering): forward with Uᵀ, backward
    with Lᵀ over the same group schedule."""
    return _solve_device_common(lu, b, trans=True)


# --------------------------------------------------------------------
# fused whole-pipeline step (one XLA program)
# --------------------------------------------------------------------

def make_fused_step(plan: FactorPlan, dtype=np.float64):
    """Build `step(vals, b) -> x`: the ENTIRE numeric phase — assemble,
    level-batched factorization, forward+backward trisolve — traced as
    one jittable function.  This is the maximal-fusion formulation the
    static-pivoting design exists to enable (SURVEY.md §7: after
    preprocessing the numeric phase is a fixed DAG), and the function
    the driver compile-checks (`__graft_entry__.entry`).

    `vals` are the scaled values in plan COO order; `b` is the RHS in
    factor ordering, shape (n, nrhs)."""
    sched = get_schedule(plan, 1)
    dtype = np.dtype(dtype)
    thresh_np = _thresh_for(plan, dtype)

    @_hi_prec
    def step(vals, b):
        thresh = jnp.asarray(thresh_np, dtype=_real_dtype(dtype))
        vals = jnp.concatenate(
            [vals.astype(dtype), jnp.zeros(1, dtype)])
        upd_buf = jnp.zeros(sched.upd_total + sched.upd_pad, dtype)
        L_flat = jnp.zeros(sched.L_total, dtype)
        U_flat = jnp.zeros(sched.U_total, dtype)
        Li_flat = jnp.zeros(sched.Li_total, dtype)
        Ui_flat = jnp.zeros(sched.Ui_total, dtype)
        tiny = jnp.zeros((), jnp.int32)
        nzero = jnp.zeros((), jnp.int32)
        for g in sched.groups:
            a_src, a_dst, one_dst, ea_blocks = \
                g.dev(squeeze=True)[:4]
            (upd_buf, L_flat, U_flat, Li_flat, Ui_flat, tiny,
             nzero) = _factor_group_impl(
                    vals, upd_buf, L_flat, U_flat, Li_flat, Ui_flat,
                    tiny, nzero, thresh, a_src, a_dst, one_dst,
                    ea_blocks, jnp.int32(g.upd_off_global),
                    jnp.int32(g.L_off), jnp.int32(g.U_off),
                    jnp.int32(g.Li_off), jnp.int32(g.Ui_off),
                    mb=g.mb, wb=g.wb, n_pad=g.n_loc,
                    ea_meta=g.ea_meta, eb_meta=g.eb_meta)
        # the triangular sweeps ride the shared _solve_loop (which
        # routes through the merged lsum trisolve when that arm is
        # active), so this fused step and every other consumer cannot
        # diverge; promote-not-cast rhs semantics live there too
        from ..parallel.factor_dist import _solve_loop
        pairs = [(g.dev(squeeze=True)[5], g.dev(squeeze=True)[6])
                 for g in sched.groups]
        return _solve_loop(sched, (L_flat, U_flat, Li_flat, Ui_flat),
                           b, dtype, pairs, None, trans=False)

    return step


# --------------------------------------------------------------------
# fused whole-driver solver: factor + solve + device-side refinement
# --------------------------------------------------------------------

def make_fused_solver(plan: FactorPlan, dtype=np.float32,
                      refine_dtype=None,
                      max_steps: Optional[int] = None,
                      mesh=None, axis=None,
                      staged: Optional[bool] = None,
                      residual_mode: str = "auto"):
    """Build `step(vals, b) -> (x, berr, steps, tiny, nzero)`: the
    ENTIRE pdgssvx numeric pipeline as ONE XLA program — scale +
    assemble + level-batched factorization in `dtype`, trisolve, then
    iterative refinement with `refine_dtype` residual accumulation
    entirely on device (pdgsrfs + pdgsmv, SRC/pdgsrfs.c:124,
    SRC/pdgsmv.c; the mixed-precision strategy of psgssvx_d2,
    SRC/psgssvx_d2.c:516).

    `vals` are the UNSCALED matrix values in plan COO order and `b` is
    the RHS in the ORIGINAL ordering, shape (n, nrhs) — scaling and
    permutation gathers happen in-program, so one dispatch serves the
    SamePattern production loop.

    With `mesh` given the SAME program runs shard_map'd over the mesh:
    fronts partition across devices, ancestor updates ride all_gather,
    sweeps psum — multi-chip time-to-solution as one compiled step
    (the pdgssvx3d-with-refinement contract).

    `staged` (single-device only): None = auto (staged_enabled); True
    forces per-group staged dispatch, False forces the one-program
    formulation.  The staged step is a PYTHON function (host-driven
    refinement loop, per-group programs) — it is NOT traceable, so
    wrap-in-jit/vmap callers must pass staged=False.  staged=True
    with mesh= is an error (mesh execution is always fused)."""
    if staged and mesh is not None:
        raise ValueError("staged=True is single-device only; mesh "
                         "execution always uses the fused program")
    from .spmv import (coo_spmv, ell_cols_from_src, ell_from_csr,
                       ell_spmv, spmv_layout)

    from ..options import IterRefine

    if mesh is not None:
        from ..parallel.factor_dist import _resolve_axis
        axis, ndev = _resolve_axis(mesh, axis)
    else:
        axis, ndev = None, 1
    sched = get_schedule(plan, ndev)
    dtype = np.dtype(dtype)
    # pair mode (complex on stacked real/imag planes, ops/pair_lu):
    # the whole fused pipeline — scale, assemble, factor, sweeps,
    # SpMV residual, berr, while_loop — compiles complex-free; the
    # public step wrapper encodes/decodes on the host.  Single-device
    # only: a mesh keeps the replicated native formulation here, and
    # where the rule gives that mesh the pair lowering (a TPU mesh;
    # pair storage on a mesh is parallel/factor_dist's split
    # factor/solve, what `factorize(grid=)` runs) this solver refuses
    # before a native complex program reaches the TPU's compiler.
    pair = mesh is None and _pair_mode(dtype)
    if mesh is not None and dtype.kind == "c":
        from ..utils.platform import complex_lowering
        if complex_lowering(dtype, mesh) == "pair":
            raise NotImplementedError(
                "the fused mesh solver has no pair storage; a complex "
                "system on this mesh runs through factorize(grid=) / "
                "solve (parallel/factor_dist)")
    # ---- residual-accumulation mode (precision/policy.py): "plain"
    # (working precision), "fp64" (native refine_dtype — exact on CPU,
    # EMULATED on TPU), or "doubleword" (two-float fp32 df64 pairs,
    # precision/doubleword.py — zero f64 ops in the lowered program;
    # the psgsrfs_d2 residual re-expressed in MXU-native arithmetic).
    # "auto" resolves through the plan's Options so this function and
    # models/refine.py cannot disagree on what a policy means. ----
    from ..precision.policy import refine_eps, resolve_residual_mode
    mode = (residual_mode if residual_mode != "auto"
            else resolve_residual_mode(plan.options))
    if mode not in ("plain", "doubleword", "fp64"):
        raise ValueError(f"unknown residual_mode {mode!r}; expected "
                         "auto|plain|doubleword|fp64")
    # doubleword also requires a factor dtype COARSER than the df64
    # class: an f64 factor under a doubleword policy (the escalation
    # ladder's top rung) would have its values rounded to fp32 pairs
    # and its refinement capped at DF64_EPS — a silent no-op rung —
    # so the top rung accumulates natively instead (exactly
    # ladder_policies' PLAIN-at-target contract)
    _dw_unsupported = (mesh is not None or pair
                       or np.dtype(dtype).kind == "c"
                       or (np.dtype(dtype).kind == "f"
                           and np.dtype(dtype).itemsize >= 8))
    if mode == "doubleword" and _dw_unsupported:
        if residual_mode == "doubleword":
            raise ValueError(
                "residual_mode='doubleword' is the single-device REAL "
                "fused path for LOW-precision factors (df64 fp32 "
                "pairs); complex systems ride pair storage, mesh "
                "execution accumulates in refine_dtype, and an "
                "f64-class factor gains nothing from fp32 pairs — "
                "use residual_mode='fp64' there")
        # a policy default reaching an unsupported formulation
        # degrades to native accumulation (same accuracy class or
        # better) instead of throwing into the driver
        mode = "fp64"
    if mode == "doubleword":
        # staged interaction, decided HERE because rdt shapes every
        # operand built below: the df64 loop lives INSIDE the fused
        # program (its while-loop state is the fp32 pair), so an
        # explicitly requested doubleword residual pins the
        # one-program formulation, while a policy default meeting the
        # staged compile-boundedness compromise degrades to native
        # accumulation (the staged host loop's residual jits are
        # per-group-sized anyway)
        if staged:
            if residual_mode == "doubleword":
                raise ValueError(
                    "residual_mode='doubleword' requires the fused "
                    "one-program formulation; pass staged=False")
            mode = "fp64"
        elif staged is None and mesh is None and staged_enabled(sched):
            if residual_mode == "doubleword":
                staged = False
            else:
                mode = "fp64"
    doubleword = mode == "doubleword"
    if refine_dtype is None:
        # honor the plan's refinement contract (models/refine.py):
        # plain accumulates in the working precision, otherwise in
        # options.refine_dtype
        if mode == "plain":
            refine_dtype = dtype
        else:
            refine_dtype = plan.options.refine_dtype
    rdt = np.dtype(refine_dtype)
    if doubleword:
        # every rdt-typed operand below (scales, pre/post gathers, x0)
        # becomes the df64 HI-PLANE dtype; the accuracy target is
        # DF64_EPS (~2^-44), not eps(rdt) — the compiled program never
        # contains an f64 buffer (HLO-pinned, tests/test_doubleword)
        rdt = np.dtype(np.float32)
    if dtype.kind == "c" and rdt.kind != "c":
        # complex system: the accumulator keeps its precision but must
        # be complex (mirror models/refine._refine_dtype)
        rdt = np.promote_types(rdt, np.complex64)
    if max_steps is None:
        if plan.options.iter_refine == IterRefine.NOREFINE:
            max_steps = 0
        else:
            max_steps = int(plan.options.max_refine_steps)
    thresh_np = _thresh_for(plan, dtype)
    n = plan.n

    # refinement must run on the UNSCALED system (b - A·x in original
    # ordering); precompute the permutation gathers host-side
    inv_final_row = np.empty(n, dtype=np.int64)
    inv_final_row[plan.final_row] = np.arange(n)

    idt = jnp.int32 if n < 2**31 - 1 else jnp.int64
    # single source for the equilibration product: the replicated
    # constant (single-device) and the per-slice operand (mesh) must
    # never diverge
    scale_fac_np = np.asarray(plan.row_scale[plan.coo_rows]
                              * plan.col_scale[plan.coo_cols])
    ops = dict(
        scale_fac=jnp.asarray(scale_fac_np),
        row_scale=jnp.asarray(plan.row_scale.astype(
            _real_dtype(rdt))),
        col_scale=jnp.asarray(plan.col_scale.astype(
            _real_dtype(rdt))),
        final_col=jnp.asarray(plan.final_col, dtype=idt),
        inv_final_row=jnp.asarray(inv_final_row, dtype=idt),
        coo_rows=jnp.asarray(plan.coo_rows, dtype=idt),
        coo_cols=jnp.asarray(plan.coo_cols, dtype=idt),
    )

    # ---- residual-SpMV layout: padded ELL by default — per-row
    # gather of a fixed band + row-sum, so the jitted refinement
    # residual lowers with ZERO scatter ops (the COO scatter-add ran
    # at ~600 MB/s on v5e, ~140 ms/step over the IR iterations;
    # pre-round chip record, not re-measured).  plan COO order IS CSR
    # row-major order (sparse.CSRMatrix.to_coo), so row boundaries
    # reconstruct from the row ids; SLU_SPMV_LAYOUT=coo restores the
    # scatter formulation for A/B ----
    nnz_a = len(plan.coo_rows)
    _rc_counts = np.bincount(np.asarray(plan.coo_rows), minlength=n)
    _indptr_a = np.concatenate([[0], np.cumsum(_rc_counts)])
    ell_src_np, ell_w = ell_from_csr(_indptr_a, plan.coo_cols,
                                     nnz=nnz_a)
    layout = spmv_layout(nnz_a, n, ell_w)
    if doubleword and layout != "ell":
        if flags.env_str("SLU_SPMV_LAYOUT",
                         "auto").strip().lower() != "coo":
            # the df64 COO lane's scatter-add cannot carry a
            # compensated sum (its row accumulation stays fp32-class,
            # precision/doubleword.df64_coo_spmv) — for a doubleword
            # residual, precision outranks the pad-waste heuristic, so
            # auto forces ELL; only an EXPLICIT SLU_SPMV_LAYOUT=coo
            # keeps the degraded lane (and the loop then simply stops
            # on stall above the df64 target)
            layout = "ell"
    if layout == "ell":
        sdt_e = jnp.int32 if nnz_a < 2**31 - 1 else jnp.int64
        ops["ell_src"] = jnp.asarray(ell_src_np, dtype=sdt_e)
        ops["ell_cols"] = jnp.asarray(
            ell_cols_from_src(ell_src_np, plan.coo_cols, n), dtype=idt)

    # ---- shared numerics pieces: ONE definition serves the fused
    # trace and the staged host loop, so the two cannot diverge ----

    rrdt = _real_dtype(rdt)
    # the sweeps' operand dtype, by the ONE rule the host loop
    # (models/gssvx.solve) states too; pair mode sweeps its real planes
    from ..precision.policy import sweep_operand_dtype
    sweep_dt = sweep_operand_dtype(dtype, rdt)
    if pair:
        sweep_dt = _real_dtype(sweep_dt)

    def _scale_impl(vals):
        # real scale factors: plane-wise in pair mode ((2, nnz)
        # broadcasts against (nnz,)), so one definition serves both
        return vals * ops["scale_fac"]

    def _pre_impl(r):
        """original-order residual -> factor-order sweep RHS (factor
        precision, like the reference's psgsrfs).  Pair mode: r is
        real-view encoded (n, 2R) and the real row scales apply to
        both halves identically, so the same gather/scale works —
        only the target dtype changes to the factor PLANE dtype."""
        return ((r * ops["row_scale"][:, None])
                [ops["inv_final_row"]]).astype(sweep_dt)

    def _post_impl(y):
        """factor-order sweep output -> original-order correction."""
        return (y[ops["final_col"]].astype(rrdt if pair else rdt)
                * ops["col_scale"][:, None])

    def _combine_resid(b, ax, den_a):
        """(residual, componentwise berr) from the SpMV pair — shared
        by the replicated and the chunked+psum'd formulations."""
        r = b - ax
        denom = den_a + jnp.abs(b)
        denom = jnp.where(denom == 0, 1, denom)
        return r, jnp.max(jnp.abs(r) / denom)

    def _ell_plane(v):
        """Runtime values -> padded ELL value plane (pad slots hit the
        appended zero).  Loop-invariant in the refinement while_loop —
        XLA's invariant code motion hoists it out of the body."""
        return jnp.concatenate(
            [v, jnp.zeros(1, v.dtype)])[ops["ell_src"]]

    def _resid_berr_impl(vals_r, abs_vals, b, xv):
        if pair:
            # pair SpMV: A and x in plane form — the product is four
            # real SpMVs (pdgsmv's z twin through representation
            # change); berr uses true complex moduli
            h = xv.shape[1] // 2
            xr, xi = xv[:, :h], xv[:, h:]

            if layout == "ell":
                er, ei = _ell_plane(vals_r[0]), _ell_plane(vals_r[1])
                ea = _ell_plane(abs_vals)

                def spr(ev, x):
                    return ell_spmv(ops["ell_cols"], ev, x)

                ax = jnp.concatenate(
                    [spr(er, xr) - spr(ei, xi),
                     spr(er, xi) + spr(ei, xr)], axis=1)
                den = spr(ea, jnp.sqrt(xr * xr + xi * xi))
            else:
                def sp(v, x):
                    return coo_spmv(ops["coo_rows"], ops["coo_cols"],
                                    v, x, n)

                ax = jnp.concatenate(
                    [sp(vals_r[0], xr) - sp(vals_r[1], xi),
                     sp(vals_r[0], xi) + sp(vals_r[1], xr)], axis=1)
                den = sp(abs_vals, jnp.sqrt(xr * xr + xi * xi))
            r = b - ax
            rmod = jnp.sqrt(r[:, :h] ** 2 + r[:, h:] ** 2)
            bmod = jnp.sqrt(b[:, :h] ** 2 + b[:, h:] ** 2)
            denom = den + bmod
            denom = jnp.where(denom == 0, 1, denom)
            return r, jnp.max(rmod / denom)
        if layout == "ell":
            ax = ell_spmv(ops["ell_cols"], _ell_plane(vals_r), xv)
            den = ell_spmv(ops["ell_cols"], _ell_plane(abs_vals),
                           jnp.abs(xv))
            return _combine_resid(b, ax, den)
        ax = coo_spmv(ops["coo_rows"], ops["coo_cols"], vals_r, xv, n)
        den = coo_spmv(ops["coo_rows"], ops["coo_cols"],
                       abs_vals, jnp.abs(xv), n)
        return _combine_resid(b, ax, den)

    def _abs_impl(vals_r):
        """|A| for the berr denominator: complex modulus in pair
        mode (plane-wise abs would understate it)."""
        if pair:
            return jnp.sqrt(vals_r[0] * vals_r[0]
                            + vals_r[1] * vals_r[1])
        return jnp.abs(vals_r)

    def _resid_fn(vals, b, x):
        """Introspection/test surface: the refinement residual+berr
        exactly as the step's loop body computes it (jittable; the
        HLO no-scatter contract in ELL mode is pinned on this)."""
        vals_r = vals.astype(rrdt if pair else rdt)
        return _resid_berr_impl(vals_r, _abs_impl(vals_r),
                                b.astype(rrdt if pair else rdt), x)

    def _factor(scaled_vals, per_group):
        # the group-loop drivers are factor_dist's — ONE implementation
        # serves the fused solver, the split dist pair, and the dist
        # step, so the paths cannot diverge
        from ..parallel.factor_dist import _factor_loop
        out = _factor_loop(sched, scaled_vals, thresh_np, dtype,
                           per_group, axis, pair=pair)
        return list(out[:4]), out[4], out[5]

    def _solve_once(flats, r, per_group):
        """r (original order, rdt) -> correction (original order, rdt)."""
        from ..parallel.factor_dist import _solve_loop
        solve_idx = [(t[5], t[6]) for t in per_group]
        y = _solve_loop(sched, tuple(flats), _pre_impl(r), dtype,
                        solve_idx, axis, trans=False, pair=pair)
        return _post_impl(y)

    def _wrap_pair(step_fn):
        """Public contract adapter for pair mode: callers pass
        complex vals/b and receive complex x; the encode/decode is
        host-side numpy so the compiled program never sees a complex
        buffer (on the gated platform even a transfer-only complex
        device array is off-limits)."""
        if not pair:
            return step_fn

        def step(vals, b):
            v = np.asarray(vals)
            vp = np.stack([v.real, v.imag]).astype(
                _real_dtype(np.promote_types(v.dtype, dtype)))
            bb = np.asarray(b).astype(rdt)
            benc = np.concatenate([bb.real, bb.imag], axis=1)
            x, berr, steps, tiny, nzero = step_fn(
                jnp.asarray(vp), jnp.asarray(benc))
            x = np.asarray(x)
            h = bb.shape[1]
            xc = (x[:, :h] + 1j * x[:, h:]).astype(rdt)
            return xc, berr, steps, tiny, nzero

        step._core = step_fn      # encoded-operand core (tests lower
        return step               # it to pin the complex-free HLO)

    def step_body(scaled, resid_berr, b, per_group):
        """Shared numeric pipeline: factor the scaled values, then the
        solve+refinement loop.  `scaled` are the (device-local) scaled
        assembly values, `resid_berr(xv) -> (r, berr)` the caller's
        residual formulation (replicated SpMV single-device, chunked +
        psum on a mesh), `b` already in rdt."""
        flats, tiny, nzero = _factor(scaled, per_group)
        if axis is not None:
            tiny = jax.lax.psum(tiny, axis)
            nzero = jax.lax.psum(nzero, axis)

        if max_steps <= 0:
            x = _solve_once(flats, b, per_group)
            _, berr = resid_berr(x)
            return x, berr, jnp.zeros((), jnp.int32), tiny, nzero

        eps = refine_eps(rdt)

        # The sweeps are traced ONCE, inside the loop body: iteration 0
        # IS the base solve (x=0, r=b), iterations 1.. are refinement —
        # halves the compiled program vs solve-then-loop.
        def cond(state):
            _, _, berr, _, stop = state
            return jnp.logical_and(jnp.logical_not(stop), berr > eps)

        def body(state):
            x, r, berr, steps, _ = state
            d = _solve_once(flats, r, per_group)
            x_new = x + d
            r_new, berr_new = resid_berr(x_new)
            # the base solve (iteration 0) is kept unconditionally —
            # the reference returns the unrefined solution even when
            # refinement cannot improve it (non-finite berr included)
            first = steps == 0
            improved = berr_new < berr * 0.5
            better = jnp.logical_or(first, berr_new < berr)
            x = jnp.where(better, x_new, x)
            r = jnp.where(better, r_new, r)
            berr = jnp.where(better, berr_new, berr)
            stop = jnp.logical_or(
                jnp.logical_and(jnp.logical_not(first),
                                jnp.logical_not(improved)),
                steps + 1 >= max_steps + 1)
            return x, r, berr, steps + 1, stop

        x0 = jnp.zeros((n, b.shape[1]), rrdt if pair else rdt)
        inf = jnp.asarray(np.inf, _real_dtype(rdt))
        x, _, berr, steps, _ = jax.lax.while_loop(
            cond, body,
            (x0, b, inf, jnp.zeros((), jnp.int32),
             jnp.zeros((), jnp.bool_)))
        # steps counts loop iterations; the first is the base solve
        return x, berr, jnp.maximum(steps - 1, 0), tiny, nzero

    if staged is None:
        staged = staged_enabled(sched)
    if mesh is None and staged:
        # staged whole-pipeline step: identical contract and identical
        # numerics policy (same group bodies, same refinement loop
        # logic), but the factor/sweep groups dispatch as per-group
        # programs and the refinement loop runs on the host — compile
        # stays bounded at audikw_1 scale (see staged_enabled)
        eps = refine_eps(rdt)

        _scale = jax.jit(_scale_impl)
        _pre = jax.jit(_pre_impl)
        _post = jax.jit(_post_impl)
        _resid_berr = jax.jit(_resid_berr_impl)
        _axpy = jax.jit(lambda x, d: x + d)

        def step(vals, b):
            from . import trisolve
            vals = jnp.asarray(vals)
            panels, tiny, nzero = _staged_factor_run(
                sched, _scale(vals), thresh_np, dtype, pair=pair)
            vals_r = vals.astype(rrdt if pair else rdt)
            abs_vals = _abs_impl(vals_r)
            b = jnp.asarray(b).astype(rrdt if pair else rdt)
            # pack the solve panels once per factorization so the
            # refinement loop's repeated sweeps skip the re-slice
            packs = (trisolve.pack_device(sched, panels)
                     if trisolve.trisolve_mode() == "merged"
                     else None)

            def solve_once(r):
                y = _staged_sweeps(sched, panels, _pre(r), dtype,
                                   trans=False, pair=pair,
                                   packs=packs)
                return _post(y)

            t32 = jnp.asarray(tiny, jnp.int32)
            z32 = jnp.asarray(nzero, jnp.int32)
            if max_steps <= 0:
                x = solve_once(b)
                _, berr = _resid_berr(vals_r, abs_vals, b, x)
                return x, berr, jnp.zeros((), jnp.int32), t32, z32

            # host mirror of the fused while_loop (same decisions)
            x = jnp.zeros((n, b.shape[1]), rrdt if pair else rdt)
            r, berr = b, np.inf
            steps, stop = 0, False
            while not stop and berr > eps:
                d = solve_once(r)
                x_new = _axpy(x, d)
                r_new, berr_new = _resid_berr(vals_r, abs_vals, b,
                                              x_new)
                berr_new_f = float(berr_new)
                first = steps == 0
                improved = berr_new_f < berr * 0.5
                if first or berr_new_f < berr:
                    x, r, berr = x_new, r_new, berr_new_f
                stop = ((not first and not improved)
                        or steps + 1 >= max_steps + 1)
                steps += 1
            return (x, jnp.asarray(berr, _real_dtype(rdt)),
                    jnp.asarray(max(steps - 1, 0), jnp.int32),
                    t32, z32)

        step = _wrap_pair(step)
        step.resid_fn = _resid_fn
        step.spmv_layout = layout
        step.residual_mode = mode
        return step

    if mesh is None and doubleword:
        # ---- doubleword (df64) refinement: the psgssvx_d2 inner-
        # outer scheme with the fp64 residual replaced by two-float
        # fp32 pairs (precision/doubleword.py).  The public wrapper
        # splits A's values and b into exact (hi, lo) fp32 planes on
        # the HOST (split_f64 — the pair-mode _wrap_pair precedent),
        # so the compiled program never sees an f64 buffer: factor
        # and sweeps run in `dtype` exactly as the plain path, the
        # residual r = b − A·x runs in df64 over the scatter-free ELL
        # band, and the solution accumulates as an fp32 pair carrying
        # ~48 bits.  Convergence target: DF64_EPS (2^-44), the df64
        # analog of the reference's berr ≈ eps stopping class. ----
        from ..precision.doubleword import (DF64_EPS, df_add, df_add_f,
                                            df64_coo_spmv,
                                            df64_ell_spmv, join_f64,
                                            split_f64)
        per_group_const = [g.dev(squeeze=True) for g in sched.groups]
        scale32 = jnp.asarray(scale_fac_np.astype(np.float32))

        def _resid_berr_df(vals_hi, vals_lo, abs_vals, bh, bl, xh, xl):
            """df64 residual + componentwise berr.  The berr
            numerator reads the hi plane only: rh carries the true
            residual to full fp32 RELATIVE precision (the
            cancellation already happened in df64), and the
            denominator |A||x|+|b| needs no cancellation protection
            at all."""
            if layout == "ell":
                axh, axl = df64_ell_spmv(
                    ops["ell_cols"], _ell_plane(vals_hi),
                    _ell_plane(vals_lo), xh, xl)
                den = ell_spmv(ops["ell_cols"], _ell_plane(abs_vals),
                               jnp.abs(xh))
            else:
                # explicit SLU_SPMV_LAYOUT=coo: the degraded lane
                # (row sums stay fp32-class; see df64_coo_spmv)
                axh, axl = df64_coo_spmv(
                    ops["coo_rows"], ops["coo_cols"], vals_hi,
                    vals_lo, xh, xl, n)
                den = coo_spmv(ops["coo_rows"], ops["coo_cols"],
                               abs_vals, jnp.abs(xh), n)
            rh, rl = df_add((bh, bl), (-axh, -axl))
            denom = den + jnp.abs(bh)
            denom = jnp.where(denom == 0, 1, denom)
            return (rh, rl), jnp.max(jnp.abs(rh) / denom)

        def _core(vals_hi, vals_lo, bh, bl):
            # both planes contribute to the scaled factor values: one
            # fp32 rounding instead of the two a hi-only product pays
            scaled = vals_hi * scale32 + vals_lo * scale32
            flats, tiny, nzero = _factor(scaled, per_group_const)
            abs_vals = jnp.abs(vals_hi)

            def resid_berr(xh, xl):
                return _resid_berr_df(vals_hi, vals_lo, abs_vals,
                                      bh, bl, xh, xl)

            if max_steps <= 0:
                x = _solve_once(flats, bh, per_group_const)
                _, berr = resid_berr(x, jnp.zeros_like(x))
                return (x, jnp.zeros_like(x), berr,
                        jnp.zeros((), jnp.int32), tiny, nzero)

            # same decision structure as the plain step_body loop
            # (iteration 0 IS the base solve), with the solution and
            # residual carried as df64 pairs; the sweep RHS is the hi
            # plane — the correction δ only ever needs fp32 accuracy
            def cond(state):
                _, _, _, berr, _, stop = state
                return jnp.logical_and(jnp.logical_not(stop),
                                       berr > DF64_EPS)

            def body(state):
                xh, xl, r32, berr, steps, _ = state
                d = _solve_once(flats, r32, per_group_const)
                nh, nl = df_add_f((xh, xl), d)
                (rh, rl), berr_new = resid_berr(nh, nl)
                first = steps == 0
                improved = berr_new < berr * 0.5
                better = jnp.logical_or(first, berr_new < berr)
                xh = jnp.where(better, nh, xh)
                xl = jnp.where(better, nl, xl)
                r32 = jnp.where(better, rh + rl, r32)
                berr = jnp.where(better, berr_new, berr)
                stop = jnp.logical_or(
                    jnp.logical_and(jnp.logical_not(first),
                                    jnp.logical_not(improved)),
                    steps + 1 >= max_steps + 1)
                return xh, xl, r32, berr, steps + 1, stop

            x0 = jnp.zeros((n, bh.shape[1]), jnp.float32)
            xh, xl, _, berr, steps, _ = jax.lax.while_loop(
                cond, body,
                (x0, jnp.zeros_like(x0), bh + bl,
                 jnp.asarray(np.inf, jnp.float32),
                 jnp.zeros((), jnp.int32), jnp.zeros((), jnp.bool_)))
            return (xh, xl, berr, jnp.maximum(steps - 1, 0), tiny,
                    nzero)

        core = obs.watch_jit("fused_step_dw", jax.jit(_core))

        def step(vals, b):
            vh, vl = split_f64(np.asarray(vals))
            bh, bl = split_f64(np.asarray(b))
            xh, xl, berr, steps, tiny, nzero = core(
                jnp.asarray(vh), jnp.asarray(vl),
                jnp.asarray(bh), jnp.asarray(bl))
            # recombine to float64 on the HOST — the program's own
            # arithmetic never touched f64 (pinned by lowering _core)
            x = join_f64(np.asarray(xh), np.asarray(xl))
            return x, berr, steps, tiny, nzero

        step._core = core         # f64-free jitted core (HLO pin)
        step.resid_fn_df = _resid_berr_df   # introspection/test hook
        step.spmv_layout = layout
        step.residual_mode = "doubleword"
        return step

    if mesh is None:
        per_group_const = [g.dev(squeeze=True) for g in sched.groups]

        @jax.jit
        def step(vals, b):
            b_r = b.astype(rrdt if pair else rdt)
            vals_r = vals.astype(rrdt if pair else rdt)
            abs_vals = _abs_impl(vals_r)

            def resid_berr(xv):
                return _resid_berr_impl(vals_r, abs_vals, b_r, xv)

            return step_body(_scale_impl(vals), resid_berr, b_r,
                             per_group_const)

        step = _wrap_pair(obs.watch_jit("fused_step", step))
        step.resid_fn = _resid_fn
        step.spmv_layout = layout
        step.residual_mode = mode
        return step

    # mesh execution: group index arrays enter as sharded operands,
    # and so does the NUMERIC INPUT (NRformat_loc, supermatrix.h:
    # 176-188): the assembly consumes per-device value slices
    # (factor_dist._vals_partition) and the refinement SpMV consumes
    # contiguous per-device nnz chunks, partial products psum'd — no
    # device ever holds the whole value array or the whole COO index
    # pair, replacing the round-3 replicated operands AND the
    # nnz-sized closure constants this branch used to bake into every
    # device's program.
    from jax.sharding import PartitionSpec as P

    from ..parallel.factor_dist import (_factor_operands,
                                        _group_operands, _regroup,
                                        _shard_vals)
    from ..utils.compat import shard_map as _shard_map

    idx_specs = (P(axis),) * (7 * len(sched.groups))
    if not _shard_vals(dtype):
        # complex: keep the round-3 replicated formulation — the
        # XLA:CPU multi-device complex lottery is acutely sensitive
        # to the assembly program's shape and the replicated variant
        # is the best-measured one (factor_dist._shard_vals note)
        idx_args = _group_operands(sched, range(7))

        def mapped_body_c(vals, b, *idx_flat):
            b_r = b.astype(rdt)
            vals_r = vals.astype(rdt)
            abs_vals = jnp.abs(vals_r)

            def resid_berr(xv):
                return _resid_berr_impl(vals_r, abs_vals, b_r, xv)

            return step_body(_scale_impl(vals), resid_berr, b_r,
                             _regroup(sched, idx_flat, 7))

        mapped_c = _shard_map(
            mapped_body_c, mesh=mesh,
            in_specs=(P(), P()) + idx_specs,
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False)

        jitted_c = obs.watch_jit(
            "fused_step_mesh",
            jax.jit(lambda vals, b: mapped_c(vals, b, *idx_args())))

        def step_c(vals, b):
            return jitted_c(vals, b)

        step_c.sel = None
        return step_c

    nnz = len(plan.coo_rows)
    sel, idx_args = _factor_operands(plan, sched, 7, True)
    # committed device placement: these enter the jit as ARGUMENTS
    # already sharded P(axis) — closed-over jnp arrays would be baked
    # into the lowered program as whole replicated constants, exactly
    # the footprint this branch exists to remove
    from jax.sharding import NamedSharding
    row_shard = NamedSharding(mesh, P(axis))
    scale_sel = jax.device_put(scale_fac_np[sel], row_shard)
    cdt = np.int64 if n >= 2**31 - 1 else np.int32
    if layout == "ell":
        # scatter-free mesh residual: ROW-partitioned padded ELL.
        # CSR rows are contiguous in plan COO order, so a row split is
        # a contiguous value-slice split; each device computes its own
        # row block y-slice (pure gather + rowsum), places it at its
        # row offset with ONE dynamic_update_slice, and the psum
        # assembles the full vector — no scatter anywhere.
        rchunk = -(-n // ndev)
        vmax = max(int((_indptr_a[min(n, (d + 1) * rchunk)]
                        - _indptr_a[min(n, d * rchunk)]))
                   for d in range(ndev))
        vmax = max(vmax, 1)
        vsel_r = np.zeros((ndev, vmax), dtype=np.int64)
        esl = np.full((ndev, rchunk, ell_w), vmax, dtype=np.int64)
        ecl = np.full((ndev, rchunk, ell_w), n, dtype=np.int64)
        for d in range(ndev):
            r0 = min(n, d * rchunk)
            r1 = min(n, (d + 1) * rchunk)
            v0, v1 = int(_indptr_a[r0]), int(_indptr_a[r1])
            vsel_r[d, :v1 - v0] = np.arange(v0, v1)
            loc = ell_src_np[r0:r1]           # global src, pad → nnz
            esl[d, :r1 - r0] = np.where(loc < nnz, loc - v0, vmax)
            ecl[d, :r1 - r0] = ell_cols_from_src(
                loc, plan.coo_cols, n)
        es_c = jax.device_put(
            esl.astype(np.int64 if vmax >= 2**31 - 1 else np.int32),
            row_shard)
        ec_c = jax.device_put(ecl.astype(cdt), row_shard)
        vpad_host = vsel_r
    else:
        # contiguous nnz chunks for the COO residual SpMV; pad
        # entries carry index n — coo_spmv's drop sentinel
        chunk = -(-nnz // ndev)
        pad = ndev * chunk - nnz
        rows_c = jax.device_put(
            np.pad(np.asarray(plan.coo_rows), (0, pad),
                   constant_values=n)
            .reshape(ndev, chunk).astype(cdt), row_shard)
        cols_c = jax.device_put(
            np.pad(np.asarray(plan.coo_cols), (0, pad),
                   constant_values=n)
            .reshape(ndev, chunk).astype(cdt), row_shard)
        es_c, ec_c = rows_c, cols_c           # positional slot reuse

    def mapped_body(vals_sel, ssel, vals_chunk, rc, cc, b, *idx_flat):
        # every per-device array arrives as an OPERAND with P(axis)
        # (a closure constant would be replicated whole on every
        # device, defeating the sharding)
        b_r = b.astype(rdt)
        vr = vals_chunk[0].astype(rdt)
        av = jnp.abs(vr)

        if layout == "ell":
            def resid_berr(xv):
                ve = jnp.concatenate([vr, jnp.zeros(1, vr.dtype)])
                ae = jnp.abs(ve)
                yl = ell_spmv(cc[0], ve[rc[0]], xv)
                dl = ell_spmv(cc[0], ae[rc[0]], jnp.abs(xv))
                di = _flat_axis_index(axis)
                zfull = jnp.zeros((rchunk * ndev, xv.shape[1]),
                                  yl.dtype)
                z0 = jnp.zeros((), di.dtype)
                ax = jax.lax.psum(jax.lax.dynamic_update_slice(
                    zfull, yl, (di * rchunk, z0)), axis)[:n]
                den = jax.lax.psum(jax.lax.dynamic_update_slice(
                    zfull, dl, (di * rchunk, z0)), axis)[:n]
                return _combine_resid(b_r, ax, den)
        else:
            def resid_berr(xv):
                ax = jax.lax.psum(
                    coo_spmv(rc[0], cc[0], vr, xv, n), axis)
                den = jax.lax.psum(
                    coo_spmv(rc[0], cc[0], av, jnp.abs(xv), n), axis)
                return _combine_resid(b_r, ax, den)

        return step_body(vals_sel[0] * ssel[0], resid_berr, b_r,
                         _regroup(sched, idx_flat, 7))

    mapped = _shard_map(
        mapped_body, mesh=mesh,
        in_specs=(P(axis),) * 5 + (P(),) + idx_specs,
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False)

    jitted = obs.watch_jit(
        "fused_step_mesh",
        jax.jit(lambda vsel, ssel, vchunk, rc, cc, b: mapped(
            vsel, ssel, vchunk, rc, cc, b, *idx_args())))

    def step(vals, b):
        # host-side one-time redistribution per call (dReDistribute_A
        # analog): each device receives only its slice/chunk.  O(nnz)
        # host work per SamePattern refactorization — the cost of a
        # host-global input API feeding a distributed program.
        v = np.asarray(vals)
        if layout == "ell":
            vchunk = v[vpad_host]
        else:
            vchunk = np.pad(v, (0, pad)).reshape(ndev, chunk)
        return jitted(jax.device_put(v[sel], row_shard), scale_sel,
                      jax.device_put(vchunk, row_shard),
                      es_c, ec_c, b)

    step.sel = sel
    step.spmv_layout = layout
    step.residual_mode = mode
    return step


# --------------------------------------------------------------------
# HLO contract registry declarations (tools/slulint/contracts.py)
# --------------------------------------------------------------------
#
# The merged factor segments' structural guarantees (ISSUE 12),
# declared next to the code that earns them.  Donation is the
# load-bearing one: the extend-add slab must stream through a
# segment's member chain IN PLACE — a dropped donation silently
# doubles the staged factor's slab traffic.  A factor program can
# never be scatter-free (the A-assembly writes nnz values into the
# front batch, and the ragged extend-add remainder accumulates by
# scatter-add by design), so the scatter contract here pins the PR 1
# promise discipline instead: the assembly scatters must keep their
# sorted+unique parallel-lowering promises through the merged
# segment lowering (DESIGN.md §19 records the no_scatter deviation).

def _contract_build_factor_segment():
    import jax

    from ..options import Options
    from ..plan.plan import plan_factorization
    from ..utils.testmat import laplacian_3d
    a = laplacian_3d(6)             # 7 groups -> one 7-member segment
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    sched = get_schedule(plan, 1)
    segs = get_factor_segments(sched)
    seg = next((s for s in segs if len(s) > 1), segs[0])
    dtype = np.dtype(np.float32)
    ops = [sched.groups[i].dev(squeeze=True)[:4] for i in seg]

    def sds(x):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)

    args = (
        jnp.zeros(sched.upd_total + sched.upd_pad, dtype),
        jnp.zeros(len(plan.coo_rows) + 1, dtype),
        jnp.zeros((), dtype),
        tuple(o[0] for o in ops), tuple(o[1] for o in ops),
        tuple(o[2] for o in ops), tuple(o[3] for o in ops),
        tuple(jnp.asarray(sched.groups[i].upd_off_global, jnp.int64)
              for i in seg),
    )
    return (_staged_factor_segment, args,
            dict(metas=factor_seg_metas(sched, seg, dtype),
                 pair=False))


HLO_CONTRACTS = (
    {"name": "factor.staged_segment",
     "phase": "factor",
     "env": {"SLU_FACTOR_MERGE_CELLS": "65536", "SLU_STAGED": "1"},
     "contracts": ("donation_honored", "assembly_scatter_promised",
                   "no_host_callback"),
     "build": _contract_build_factor_segment,
     "note": "the extend-add slab streams through the merged factor "
             "segment's member chain in place, and the A-assembly "
             "scatters keep their sorted+unique parallel-lowering "
             "promises (a factor program cannot be scatter-free — "
             "DESIGN.md §19)"},
)
