"""The saddle point through the normal path (`plan_factorization` ->
`factorize(plan=...)` -> `solve`): the staggered-grid Stokes matrix of
the configuration `stokes2d_sinker` (benchmark/configs/gen_stokes2d.py,
numpy and scipy only), whose pressure block is exactly zero, so that
the static-pivoting permutation moves two thirds of the rows,
equilibration scales both sides and refinement carries the answer.
Also the plan's GESP counters (`Stats.gesp`, the health ring's
`gesp`), the leaf span `slu.fact.scale`, and the edge of the
guarantee at viscosity contrasts 1e4 and 1e6."""

import contextlib
import importlib.util
import os

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

import superlu_dist_tpu as slu
from superlu_dist_tpu import obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EPS = float(np.finfo(np.float64).eps)
SEED = 2147483659


def _gen():
    spec = importlib.util.spec_from_file_location(
        "gen_stokes2d", os.path.join(ROOT, "benchmark", "configs",
                                     "gen_stokes2d.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


GEN = _gen()
OPTS = slu.Options(factor_dtype="float32", refine_dtype="float64",
                   iter_refine=slu.IterRefine.SLU_DOUBLE)


def value_sets(a0, count):
    """One held pattern, `count` value sets: rows rescaled by
    U(0.5, 1.5), and a manufactured solution each."""
    rng = np.random.default_rng(SEED)
    out = []
    for _ in range(count):
        a = sp.diags(rng.uniform(0.5, 1.5, a0.shape[0])) @ a0
        a = a.tocsr()
        a.sort_indices()
        xtrue = rng.standard_normal(a0.shape[0])
        out.append((a, xtrue, a @ xtrue))
    return out


def berr_of(a, x, b):
    denom = abs(a) @ np.abs(x) + np.abs(b)
    denom[denom == 0.0] = 1.0
    return float(np.max(np.abs(b - a @ x) / denom))


def relerr_of(x, xtrue):
    return float(np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue))


def refactor_and_solve(plan, a, b):
    st = slu.Stats()
    lu = slu.factorize(slu.csr_from_scipy(a), OPTS, plan=plan, stats=st)
    x = np.asarray(slu.solve(lu, b, stats=st))
    return x, st


@pytest.mark.parametrize("N", [12, 16])
def test_normal_path_against_scipy_float64(N):
    a0 = GEN.generate(N)
    n = GEN.size(N)
    plan = slu.plan_factorization(slu.csr_from_scipy(a0), OPTS)
    # the pressure rows (all but the pin) each trade places with a
    # velocity row: two thirds of the matrix moves, and both sides
    # are scaled
    moved = int(np.count_nonzero(plan.perm_r != np.arange(n)))
    assert moved == 2 * (N * N - 1)
    assert 0.6 < moved / n < 0.7
    assert plan.equed == "B"
    assert sorted(plan.perm_r) == list(range(n))
    for a, xtrue, b in value_sets(a0, 2):
        x, st = refactor_and_solve(plan, a, b)
        assert x.dtype == np.float64
        assert berr_of(a, x, b) <= 64 * EPS
        assert relerr_of(x, xtrue) < 1e-9
        xref = spla.splu(a.tocsc()).solve(b)
        assert relerr_of(x, xref) < 1e-9
        assert st.berr <= 64 * EPS and st.tiny_pivots == 0
        assert 2 <= st.refine_steps <= 5
        assert st.sweeps == {"float32": 1 + st.refine_steps}


def test_gesp_counters_ride_stats_and_the_ring():
    N = 12
    a0 = GEN.generate(N)
    n = GEN.size(N)
    st = slu.Stats()
    plan = slu.plan_factorization(slu.csr_from_scipy(a0), OPTS, stats=st)
    want = {"rows_moved": 2 * (N * N - 1), "n": n, "equed": "B",
            "zero_diagonal": N * N - 1}
    for where in (plan.gesp, st.gesp):
        assert {k: where[k] for k in want} == want
        assert where["row_scale_min"] == plan.row_scale.min() < 1e-3
        assert where["row_scale_max"] == plan.row_scale.max()
        assert where["col_scale_min"] == plan.col_scale.min()
        assert where["col_scale_max"] == plan.col_scale.max() > 10.0
    # a factorization on the held plan carries them to its own Stats
    # and to the health ring, beside tiny_pivots and perturbation
    (a, _, b), = value_sets(a0, 1)
    _, st2 = refactor_and_solve(plan, a, b)
    assert st2.gesp == plan.gesp and st2.snapshot()["gesp"] == plan.gesp
    last = obs.HEALTH.snapshot()["last_factor"]
    assert last["gesp"] == plan.gesp
    assert last["tiny_pivots"] == 0 and last["perturbation"] is None
    report = st2.report()
    assert f"{2 * (N * N - 1)} of {n} rows moved" in report
    assert f"{N * N - 1} zero diagonals" in report and "equed B" in report


def test_a_laplacian_plan_moves_no_row():
    t = sp.diags([-1.0, 2.2, -1.05], [-1, 0, 1], shape=(12, 12))
    a = sp.kronsum(t, t, format="csr")
    st = slu.Stats()
    plan = slu.plan_factorization(slu.csr_from_scipy(a),
                                  slu.Options(), stats=st)
    assert st.gesp == plan.gesp == {
        "rows_moved": 0, "n": 144, "equed": "N",
        "row_scale_min": 1.0, "row_scale_max": 1.0,
        "col_scale_min": 1.0, "col_scale_max": 1.0, "zero_diagonal": 0}
    lu = slu.factorize(slu.csr_from_scipy(a), plan=plan)
    assert obs.HEALTH.snapshot()["last_factor"]["gesp"] == plan.gesp
    assert lu.stats.gesp["rows_moved"] == 0


def test_fact_scale_is_a_leaf_inside_fact(monkeypatch):
    """The host's value preparation of a refactorization (Dr·A·Dc in
    the plan's order, the cast to the factor dtype) runs inside
    `FACT`'s timer, under spans named `fact.scale` that hold no other
    span."""
    a0 = GEN.generate(8)
    plan = slu.plan_factorization(slu.csr_from_scipy(a0), OPTS)
    log = []
    real_span = obs.span

    def spying(name, **kw):
        inner = real_span(name, **kw)

        @contextlib.contextmanager
        def both():
            log.append(("in", name))
            with inner:
                yield
            log.append(("out", name))
        return both()

    monkeypatch.setattr(obs, "span", spying)
    (a, _, b), = value_sets(a0, 1)
    slu.factorize(slu.csr_from_scipy(a), OPTS, plan=plan)
    names = [n for _, n in log]
    assert names[0] == names[-1] == "FACT"
    at = [i for i, e in enumerate(log) if e == ("in", "fact.scale")]
    assert len(at) == 2                     # the scaling, then the cast
    for i in at:
        assert log[i + 1] == ("out", "fact.scale")      # a leaf
    assert at[0] == 1                       # FACT's first work


# -- the edge of the guarantee ----------------------------------------

def at_contrast(eta2, N=16):
    a0 = GEN.generate(N, 1.0, eta2)
    plan = slu.plan_factorization(slu.csr_from_scipy(a0), OPTS)
    (a, xtrue, b), = value_sets(a0, 1)
    x, st = refactor_and_solve(plan, a, b)
    return a, xtrue, b, x, st, obs.HEALTH.snapshot()["recent_solves"][-1]


def test_contrast_1e4_still_meets_the_limits():
    """A hundred times the source's contrast: refinement takes more
    passes (5 here, 3 at the source's 1:100) and still arrives."""
    a, xtrue, b, x, st, rec = at_contrast(1e4)
    assert berr_of(a, x, b) <= 64 * EPS and st.berr <= 64 * EPS
    assert relerr_of(x, xtrue) < 1e-9
    assert 4 <= st.refine_steps <= 7
    assert st.escalations == 0 and rec["steps"] == st.refine_steps
    assert rec["berr"] <= 64 * EPS


def test_contrast_1e6_fails_in_the_open():
    """At 1:1e6 float32 factors cannot carry the answer, and the
    reused-handle path does not escalate (`factorize(plan=...)` +
    `solve` never refactors): the answer misses the guarantee, and
    the program says so where a caller can read it: `Stats.berr` above
    64 eps, the health ring's refine record `converged: False` and
    stalled, no escalation counted.  A float64 factor of the same
    matrix arrives."""
    a, xtrue, b, x, st, rec = at_contrast(1e6)
    assert berr_of(a, x, b) > 64 * EPS
    assert st.berr > 64 * EPS and st.berr == pytest.approx(
        berr_of(a, x, b), rel=0.5)
    assert st.escalations == 0
    assert rec["converged"] is False and rec["stalled"] is True
    assert rec["berr"] == st.berr
    assert rec["berr_trajectory"][-1] > 64 * EPS
    assert obs.HEALTH.snapshot()["stalled_refines"] >= 1
    f64 = OPTS.replace(factor_dtype="float64")
    x64, lu, st64 = slu.gssvx(f64, slu.csr_from_scipy(a), b)
    assert berr_of(a, np.asarray(x64), b) <= 64 * EPS
    assert relerr_of(np.asarray(x64), xtrue) < 1e-6
