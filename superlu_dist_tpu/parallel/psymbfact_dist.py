"""Distributed planning over NRformat_loc row slices.

The genuinely-distributed half of the psymbfact slot (SURVEY row 17).
`parallel/multihost.py` ships a finished plan host-to-host;
this module COMPUTES the plan from distributed input — each process
holds only its contiguous row block of A (the NRformat_loc contract,
reference supermatrix.h:176-188) and the stages communicate the way
the reference's preprocessing does:

  * structure (indptr/indices) is allgathered once — every process
    then holds the full PATTERN, but numeric values never leave their
    owner, with one documented exception below.  Pattern bytes are
    the ordering/etree/symbfact working set; value bytes (the term
    that dominates at fp64) stay distributed, matching the memory
    split of dReDistribute_A (pddistribute.c:66);
  * equilibration is computed by partial reduction — each process
    reduces its own rows, O(n) scale vectors ride the wire, never
    O(nnz) values (pdgsequ's MPI_Allreduce, SRC/pdgsequ.c);
  * MC64/HWPM row permutation gathers values to process 0 ONLY,
    exactly as the reference's dldperm_dist does (pdgssvx.c:943:
    process 0 runs the serial matching on the gathered matrix and
    broadcasts perm_r);
  * column ordering runs on process 0 and is broadcast — threaded ND
    may tie-break differently per invocation, and the SPMD contract
    requires bit-identical schedules (multihost.py module docstring).
    EXCEPTION: ColPerm.PARMETIS with P > 1 runs the DISTRIBUTED
    multilevel ND instead (parallel/ordering_dist.py — per-rank
    O(nnz/P + n) ordering wire, deterministic single-owner blocks,
    identical perm on every rank by construction);
  * symbolic factorization is domain-distributed: the supernodal
    etree is cut by plan/psymbfact.py, each process computes its
    owned domains' struct lists, and one allgather of per-domain
    structs (boundary roots included) completes every process's view
    — the symbfact_dist exchange (psymbfact.c:440).

Every process returns the same FactorPlan bit-for-bit; pinned by
tests/test_psymbfact_dist.py against plan_factorization on the
assembled matrix.

The transport is abstracted behind PlanComm so the algorithm is
testable with P virtual processes in one process (ThreadComm in the
tests) and rides `jax.experimental.multihost_utils` in a real
multi-host job (JaxProcessComm) — the same split the reference gets
from MPI communicators.
"""

from __future__ import annotations

import pickle
import time
from typing import List

import numpy as np
import scipy.sparse as sp

from ..options import ColPerm, Options, RowPerm
from ..sparse import CSRMatrix
from ..utils.stats import Stats
from ..plan import colperm as colperm_mod
from ..plan import equilibrate, rowperm
from ..plan.plan import FactorPlan, ledger_phases, plan_from_perms
from ..plan.psymbfact import (complete_from_domains, domain_symbfact,
                              partition_domains)


class LocalComm:
    """The one-process group: every collective is the identity."""
    nproc = 1
    rank = 0

    def allgather(self, payload: bytes) -> List[bytes]:
        return [payload]

    def gather0(self, payload: bytes) -> List[bytes] | None:
        return [payload]

    def bcast(self, payload: bytes | None) -> bytes:
        assert payload is not None
        return payload

    def alltoall(self, payloads: List[bytes]) -> List[bytes]:
        return [payloads[0]]


class JaxProcessComm:
    """PlanComm over the JAX process group (multihost_utils) — the
    real multi-host transport.  gather0 is implemented with the only
    primitive the process group offers (allgather) and non-root sides
    discard; a transport with a true rooted gather (MPI_Gatherv) can
    do better, which is why it is a separate protocol method."""

    def __init__(self):
        import jax
        self.nproc = jax.process_count()
        self.rank = jax.process_index()

    def allgather(self, payload: bytes) -> List[bytes]:
        from jax.experimental import multihost_utils
        n = np.array([len(payload)], np.int64)
        lens = multihost_utils.process_allgather(n)[:, 0]
        buf = np.zeros(int(lens.max()), np.uint8)
        buf[:len(payload)] = np.frombuffer(payload, np.uint8)
        out = multihost_utils.process_allgather(buf)
        return [out[p, :int(lens[p])].tobytes()
                for p in range(self.nproc)]

    def gather0(self, payload: bytes) -> List[bytes] | None:
        parts = self.allgather(payload)
        return parts if self.rank == 0 else None

    def bcast(self, payload: bytes | None) -> bytes:
        from .multihost import _broadcast_bytes
        return _broadcast_bytes(payload if self.rank == 0 else b"",
                                self.rank == 0)

    def alltoall(self, payloads: List[bytes]) -> List[bytes]:
        # transport limitation: the process group offers allgather
        # only, so the exchange ships every pairwise payload to every
        # rank and each keeps its own column — RETAINED memory is the
        # per-rank share (the algorithmic claim), transient wire is
        # O(total).  An MPI_Alltoallv transport slots in here.
        parts = self.allgather(pickle.dumps(payloads))
        return [pickle.loads(p)[self.rank] for p in parts]


class ThreadComm:
    """P barrier-synchronized virtual processes in ONE process — the
    certification transport (tests, __graft_entry__ dryrun).  One
    instance per rank, sharing slots/barrier state: the collectives
    have real allgather/bcast/alltoall semantics (every rank
    deposits, barrier, every rank reads), so ordering bugs and
    one-sided raises deadlock or fail loudly instead of passing
    vacuously.  `spy` records every payload that crossed a
    collective, for no-values/wire-accounting assertions."""

    def __init__(self, nproc, rank, shared):
        self.nproc = nproc
        self.rank = rank
        self._s = shared

    @staticmethod
    def make_group(nproc, timeout=60):
        # timeout: deadlock breaker only.  Raise it for scale tests —
        # P CPU-bound ranks timeshare the host, so the first barrier
        # arrival legitimately waits ~(P-1)x one rank's phase time.
        import threading
        shared = {
            "slots": [None] * nproc,
            "barrier": threading.Barrier(nproc, timeout=timeout),
            "spy": [],
            "lock": threading.Lock(),
        }
        return [ThreadComm(nproc, r, shared) for r in range(nproc)]

    def _exchange(self, payload):
        s = self._s
        s["slots"][self.rank] = payload
        with s["lock"]:
            s["spy"].append((self.rank, payload))
        s["barrier"].wait()
        out = list(s["slots"])
        s["barrier"].wait()  # all read before any rank reuses slots
        return out

    def allgather(self, payload):
        return self._exchange(payload)

    def gather0(self, payload):
        out = self._exchange(payload)
        return out if self.rank == 0 else None

    def bcast(self, payload):
        out = self._exchange(payload if self.rank == 0 else b"")
        return out[0]

    def alltoall(self, payloads):
        # true pairwise exchange: rank r receives payloads[r] from
        # every rank (the spy records the full per-rank send list, so
        # wire-accounting tests can sum the real sent bytes)
        out = self._exchange(list(payloads))
        return [out[r][self.rank] for r in range(self.nproc)]


def run_spmd(comms, fn):
    """Run fn(rank_comm, rank) on every rank of a ThreadComm group;
    returns (results, errors) per rank.  No barrier.abort() on
    failure: aborting races with ranks still draining the same
    barrier generation (CPython Barrier semantics) and corrupts THEIR
    error into BrokenBarrierError; a genuinely one-sided death is
    broken by the barrier's configured timeout instead (make_group's
    `timeout`)."""
    import threading
    results = [None] * len(comms)
    errors = [None] * len(comms)

    def work(r):
        try:
            results[r] = fn(comms[r], r)
        except Exception as e:  # noqa: BLE001 — surfaced to caller
            errors[r] = e

    threads = [threading.Thread(target=work, args=(r,))
               for r in range(len(comms))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, errors


def default_comm():
    import jax
    return JaxProcessComm() if jax.process_count() > 1 else LocalComm()


def _dumps(*arrays) -> bytes:
    return pickle.dumps(arrays, protocol=pickle.HIGHEST_PROTOCOL)


def _loads(payload: bytes):
    return pickle.loads(payload)


def _bcast0(comm, make, what="distributed plan stage"):
    """Run `make` on rank 0, broadcast the result; a rank-0 exception
    is shipped and re-raised EVERYWHERE (multihost's framing — a
    one-sided raise would deadlock the other ranks in the next
    collective)."""
    from .multihost import _frame_err, _frame_ok, _unframe
    blob = None
    if comm.rank == 0:
        try:
            blob = _frame_ok(_dumps(make()))
        except Exception as e:
            blob = _frame_err(e)
    return _loads(_unframe(comm.bcast(blob), what))[0]


def _equilibrate_dist(comm, fst_row, m_loc, m,
                      rows_loc, indices_loc, data_loc):
    """gsequ by partial reduction: O(n) vectors on the wire, O(nnz)
    values never.  Bit-identical to equilibrate.gsequ on the
    assembled matrix: per-row maxima are exact locally (each row has
    one owner); column maxima are an elementwise max of per-process
    partials (float max is associative); the cnd/amax scalars are
    derived from the full vectors every rank then holds.  `rows_loc`
    is the caller's CSR row expansion (LOCAL labels)."""
    absv = np.abs(np.asarray(data_loc))
    rmax_loc = np.zeros(m_loc)
    np.maximum.at(rmax_loc, rows_loc, absv)
    amax_loc = absv.max() if len(absv) else 0.0

    parts = [_loads(p) for p in comm.allgather(
        _dumps(np.int64(fst_row), rmax_loc, np.float64(amax_loc)))]
    rmax = np.zeros(m)
    amax = 0.0
    for fst, rm, am in parts:
        rmax[int(fst):int(fst) + len(rm)] = rm
        amax = max(amax, float(am))
    if np.any(rmax == 0.0):
        raise ValueError("matrix has an empty row; singular")
    r = 1.0 / rmax

    cmax_loc = np.zeros(m)
    np.maximum.at(cmax_loc, np.asarray(indices_loc, np.int64),
                  absv * r[fst_row + rows_loc])
    cparts = [_loads(p)[0] for p in comm.allgather(_dumps(cmax_loc))]
    cmax = np.maximum.reduce(cparts)
    if np.any(cmax == 0.0):
        raise ValueError("matrix has an empty column; singular")
    c = 1.0 / cmax

    smlnum = np.finfo(np.float64).tiny
    rowcnd = max(r.min() / r.max(), smlnum) if m else 1.0
    colcnd = max(c.min() / c.max(), smlnum) if m else 1.0
    return r, c, rowcnd, colcnd, amax


def scaled_values_local(plan: FactorPlan, data_loc, fst_row: int,
                        indptr_loc) -> np.ndarray:
    """The row-slice counterpart of FactorPlan.scaled_values: scale a
    local value block in place in the plan's (global CSR) COO order.
    A row slice occupies the contiguous COO range
    [indptr[fst_row], indptr[fst_row + m_loc]), so the scaled slice
    feeds parallel/factor_dist._vals_partition directly."""
    m_loc = len(np.asarray(indptr_loc)) - 1
    rows_loc = fst_row + np.repeat(
        np.arange(m_loc, dtype=np.int64),
        np.diff(np.asarray(indptr_loc, np.int64)))
    # the plan's COO is the CSR expansion: recover this slice's columns
    # from the plan's global pattern
    lo = int(np.searchsorted(plan.coo_rows, fst_row, side="left"))
    hi = int(np.searchsorted(plan.coo_rows, fst_row + m_loc, side="left"))
    cols = plan.coo_cols[lo:hi]
    if hi - lo != len(np.asarray(data_loc)):
        raise ValueError(
            f"value slice has {len(np.asarray(data_loc))} entries; the "
            f"plan's rows [{fst_row}, {fst_row + m_loc}) hold {hi - lo}")
    return (np.asarray(data_loc) * plan.row_scale[rows_loc]
            * plan.col_scale[cols])


def plan_factorization_dist(fst_row: int, indptr_loc, indices_loc,
                            data_loc, m: int,
                            options: Options | None = None,
                            comm=None, stats: Stats | None = None
                            ) -> FactorPlan:
    """plan_factorization computed FROM row-sliced input.  Every
    process passes its contiguous row block [fst_row, fst_row + m_loc)
    in local CSR form and receives the identical FactorPlan.

    The output is bit-identical to
    `plan_factorization(assembled A, options)` — the decomposition
    regroups the same stage arithmetic (see _equilibrate_dist and
    plan/psymbfact.py for the two stages whose data flow actually
    changes); divergence would be a bug and is pinned by test.
    EXCEPTION: ColPerm.PARMETIS with P > 1 runs the distributed
    multilevel ND (parallel/ordering_dist.py) — a DIFFERENT ordering
    of the same quality class, exactly as the reference's
    get_perm_c_parmetis differs from get_perm_c(METIS); all ranks
    still return one identical plan (pinned by test).

    options.autotune is honored the same way plan_factorization
    honors it (bucket refit from the finished plan — deterministic,
    so every rank recomputes it identically with no extra wire
    traffic).  user_perm_r/user_perm_c are deliberately not in this
    signature: MY_PERMR/MY_PERMC callers already hold a global object
    (their permutation), so the host-global path serves them."""
    options = options or Options()
    if options.row_perm == RowPerm.MY_PERMR \
            or options.col_perm == ColPerm.MY_PERMC:
        raise ValueError(
            "MY_PERMR/MY_PERMC are not supported on the distributed "
            "plan path (this signature carries no user permutation); "
            "use plan_factorization on the assembled matrix")
    stats = stats if stats is not None else Stats()
    t_plan0 = time.perf_counter()
    u0 = dict(stats.utime)
    comm = comm if comm is not None else default_comm()
    indptr_loc = np.asarray(indptr_loc, dtype=np.int64)
    indices_loc = np.asarray(indices_loc, dtype=np.int64)
    data_loc = np.asarray(data_loc)
    m_loc = len(indptr_loc) - 1
    rows_loc = np.repeat(np.arange(m_loc, dtype=np.int64),
                         np.diff(indptr_loc))
    if len(indices_loc) != len(data_loc):
        raise ValueError(f"{len(indices_loc)} indices vs "
                         f"{len(data_loc)} values")
    n = m

    # [structure allgather] — the one O(nnz) pattern collective;
    # values are NOT in this payload (asserted by test).  Timed under
    # its own key so host-vs-dist stage comparisons don't blame the
    # frontal build ("DIST") for communication.
    from .multihost import _assemble_structure
    with stats.timer("GATHER"):
        parts = [_loads(p) for p in comm.allgather(
            _dumps(np.int64(fst_row), indptr_loc, indices_loc))]
        indptr, indices, _ = _assemble_structure(
            [(int(f), ip, ix) for f, ip, ix in parts], m)
    coo_rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(indptr))
    coo_cols = indices.copy()

    # [Equil] (pdgsequ partial-reduction analog)
    with stats.timer("EQUIL"):
        if options.equil:
            r, c, rowcnd, colcnd, amax = _equilibrate_dist(
                comm, fst_row, m_loc, m,
                rows_loc, indices_loc, data_loc)
            import types
            equed, r_eff, c_eff = equilibrate.laqgs(
                types.SimpleNamespace(m=m, n=n), r, c,
                rowcnd, colcnd, amax)
        else:
            equed = "N"
            r_eff = np.ones(n)
            c_eff = np.ones(n)
    scaled_loc = (data_loc * r_eff[fst_row + rows_loc]
                  * c_eff[indices_loc])
    anorm_loc = float(np.max(np.abs(scaled_loc))) if len(scaled_loc) \
        else 0.0
    anorm = max(float(_loads(p)[0])
                for p in comm.allgather(_dumps(np.float64(anorm_loc))))
    if int(indptr[-1]) == 0:
        anorm = 1.0  # empty-pattern convention of plan_factorization

    # [RowPerm] — the ONE stage that moves values, to process 0 only,
    # and only when the mode needs a weighted matching (the reference
    # gathers A to process 0 for dldperm_dist the same way,
    # pdgssvx.c:943); NOROWPERM ships nothing
    with stats.timer("ROWPERM"):
        if options.row_perm == RowPerm.NOROWPERM:
            perm_r = np.arange(m, dtype=np.int64)
        else:
            gathered = comm.gather0(_dumps(np.int64(fst_row),
                                           scaled_loc))
            def run_rowperm():
                parts = [_loads(p) for p in gathered]
                # dtype from ALL parts: rank 0's slice may be empty
                # (legal NRformat_loc) and default-float while others
                # carry complex values
                vdt = np.result_type(*(sv.dtype for _, sv in parts))
                vals = np.empty(int(indptr[-1]), dtype=vdt)
                for f, sv in parts:
                    f = int(f)
                    vals[indptr[f]:indptr[f] + len(sv)] = sv
                a_scaled = CSRMatrix(m, n, indptr, indices, vals)
                return rowperm.get_perm_r(a_scaled, options.row_perm,
                                          None)
            perm_r = _bcast0(comm, run_rowperm)

    # [ColPerm] on pattern(Pr·A).  ColPerm.PARMETIS with P > 1 runs
    # the DISTRIBUTED multilevel ND (parallel/ordering_dist.py — the
    # get_perm_c_parmetis slot: ordering computed from row-sliced
    # pattern, work spread across ranks, O(n) collectives only);
    # every other mode runs on process 0 and broadcasts (threaded ND
    # tie-break determinism; get_perm_c is pattern-only, so ones
    # stand in for the values process 0 does not hold)
    with stats.timer("COLPERM"):
        if options.col_perm == ColPerm.PARMETIS and comm.nproc > 1:
            from .ordering_dist import colperm_dist
            perm_c = colperm_dist(
                comm, perm_r[fst_row + rows_loc], indices_loc, n,
                nd_threads=options.nd_threads)
        else:
            def run_colperm():
                a_rp = sp.coo_matrix(
                    (np.ones(len(coo_rows)),
                     (perm_r[coo_rows], coo_cols)), shape=(n, n)).tocsr()
                return colperm_mod.get_perm_c(
                    CSRMatrix(n, n, a_rp.indptr.astype(np.int64),
                              a_rp.indices.astype(np.int64), a_rp.data),
                    options.col_perm, None,
                    nd_threads=options.nd_threads)
            perm_c = _bcast0(comm, run_colperm)

    # [Etree → Symbfact → frontal → plan] — the shared back half
    # (plan.plan_from_perms): every stage there is deterministic from
    # (pattern, perms), so every rank computes it identically; only
    # the symbfact wave communicates, via the substituted
    # domain-distributed pass (psymbfact.c:424-477: compute owned
    # domains locally, allgather per-domain structs, everyone runs
    # the small top wave)
    def dist_symbfact(b_indptr, b_indices, part):
        dp = partition_domains(part, comm.nproc)
        mine = []
        for d in dp.owned(comm.rank):
            lo, hi = (int(v) for v in dp.domains[d])
            mine.append((d, domain_symbfact(
                b_indptr, b_indices, part, lo, hi,
                threads=max(1, options.symb_threads))))
        struct: List = [None] * part.nsuper
        for p in comm.allgather(_dumps(mine)):
            for d, dstruct in _loads(p)[0]:
                lo, hi = (int(v) for v in dp.domains[d])
                struct[lo:hi + 1] = dstruct
        return complete_from_domains(b_indptr, b_indices, part, dp,
                                     struct)

    ledger_phases(t_plan0, u0, stats,
                  ("GATHER", "EQUIL", "ROWPERM", "COLPERM"))
    return plan_from_perms(n, options, stats, equed, r_eff, c_eff,
                           perm_r, perm_c, coo_rows, coo_cols, anorm,
                           symbfact_fn=dist_symbfact)
