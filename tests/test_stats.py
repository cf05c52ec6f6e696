"""Measured stats parity — the PStatPrint / SCT_print3D contract
(SRC/util.c:331, SRC/util_dist.h:194-317): per-phase device
wall-clock, predicted vs HLO-measured collective volumes, and the
report format pinned."""

import numpy as np
import scipy.sparse as sp

from superlu_dist_tpu import Options, gssvx
from superlu_dist_tpu.parallel.factor_dist import measure_comm
from superlu_dist_tpu.parallel.grid import make_solver_mesh
from superlu_dist_tpu.sparse import csr_from_scipy
from superlu_dist_tpu.utils.stats import Stats, hlo_collective_stats


def _testmat(m=40):
    t = sp.diags([-1.0, 2.4, -1.1], [-1, 0, 1], shape=(m, m))
    return csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())


def test_hlo_collective_stats_parses_shapes():
    txt = """
  %ag.1 = f32[8,128]{1,0} all-gather(f32[1,128]{1,0} %p), dims={0}
  %ar = (f64[9]{0}, f64[9]{0}) all-reduce-start(f64[9]{0} %x)
  %ard = f64[9]{0} all-reduce-done(%ar)
  %cp = u32[4]{0} collective-permute(u32[4]{0} %y)
"""
    out = hlo_collective_stats(txt)
    assert out["all-gather"] == {"count": 1, "bytes": 8 * 128 * 4}
    # async pairs are counted at -done (its result is the collective's
    # output); -start's operand/result tuple would double count
    assert out["all-reduce"]["count"] == 1
    assert out["all-reduce"]["bytes"] == 9 * 8
    assert out["collective-permute"] == {"count": 1, "bytes": 16}


def test_phase_walls_and_report_pinned():
    """Every numeric phase carries positive device wall-clock and the
    report prints the pinned PStatPrint-style keys."""
    a = _testmat()
    rng = np.random.default_rng(0)
    xtrue = rng.standard_normal(a.n)
    stats = Stats()
    x, lu, stats = gssvx(Options(factor_dtype="float32"), a,
                         a.to_scipy() @ xtrue, stats=stats)
    for phase in ("EQUIL", "ROWPERM", "COLPERM", "SYMBFACT", "FACT",
                  "SOLVE", "REFINE"):
        assert stats.utime[phase] > 0.0, phase
    rep = stats.report()
    for key in ("** Phase breakdown **", "FACT", "SOLVE", "REFINE",
                "GF/s", "tiny pivots replaced", "refinement steps",
                "nnz(L+U)",
                # the obs/ extension of the pinned contract: compile
                # counters and the numerical-health summary ride in
                # the same report (PR 4)
                "jit compiles:", "health: berr"):
        assert key in rep, key
    assert stats.gflops("FACT") > 0.0
    # the report's snapshot twin feeds the obs.Registry
    snap = stats.snapshot()
    assert snap["utime"]["FACT"] > 0.0
    assert snap["refine_steps"] == stats.refine_steps


def test_measured_comm_matches_prediction():
    """The schedule's predicted collective traffic (comm_summary) must
    agree with the compiled HLO's actual collectives: all-gather bytes
    exactly; solve all-reduce count == predicted sync count."""
    a = _testmat()
    rng = np.random.default_rng(1)
    xtrue = rng.standard_normal((a.n, 2))
    g = make_solver_mesh(2, 2, 2)
    stats = Stats()
    x, lu, stats = gssvx(Options(), a, a.to_scipy() @ xtrue,
                         stats=stats, grid=g)
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-10
    pred = stats.comm_predicted
    assert pred, "dist factorize must record the prediction"
    meas = measure_comm(lu.device_lu, nrhs=2)
    # factor path: every update-slab all_gather is predicted
    ag = meas["FACT"].get("all-gather", {"count": 0, "bytes": 0})
    assert ag["bytes"] == pred["factor_allgather_bytes"], (ag, pred)
    # solve path: two columns on eight devices sweep the trisolve
    # arm's program, the row-partitioned merged one: one all-reduce a
    # sync point of ITS count (the replicated sweep, which the
    # schedule's prediction describes, reconciles once more: after
    # the forward sweep), and each slot all-reduced at most once
    ar = meas["SOLVE"].get("all-reduce", {"count": 0, "bytes": 0})
    stamp = meas["MESH"]
    assert stamp["solve_arm"] == "merged"
    assert ar["count"] == stamp["solve_syncs"] == pred["solve_syncs"] - 1
    from superlu_dist_tpu.ops import trisolve
    ts = trisolve.get_trisolve(lu.device_lu.schedule)
    assert ar["bytes"] <= (ts.u_total + ts.y_total) * 2 * 8
    assert stats.dispatch["sweep_arm"] == "merged"
    assert stats.dispatch["sweep_syncs"] == ar["count"]
    # report renders both sections
    stats.comm_measured = meas
    rep = stats.report()
    assert "Collective traffic (predicted)" in rep
    assert "Collective traffic (measured, compiled HLO)" in rep
