"""numerics/: condition estimation (gscon), the perturbation ledger,
typed singularity refusals, front-door validation, the hard-matrix
gauntlet's tier-1 subset, the near_singular chaos site, and the
cadence rcond-drift trigger — the defense-in-depth pins behind
DESIGN.md §21."""

import dataclasses

import numpy as np
import pytest
import scipy.sparse as sp

from superlu_dist_tpu import Options, obs
from superlu_dist_tpu.models.gssvx import factorize, gssvx, solve
from superlu_dist_tpu.numerics import (InvalidInputError,
                                       NumericalError,
                                       PerturbationLedger,
                                       PerturbedResult,
                                       SingularMatrixError,
                                       StructurallySingularError,
                                       estimate_rcond, one_norm,
                                       stamp_perturbed)
from superlu_dist_tpu.numerics.gauntlet import classify, corpus
from superlu_dist_tpu.numerics.policy import ConditionPolicy
from superlu_dist_tpu.resilience import chaos
from superlu_dist_tpu.serve import Metrics, ServeConfig, SolveService
from superlu_dist_tpu.sparse import csr_from_scipy
from superlu_dist_tpu.stream.cadence import Cadence
from superlu_dist_tpu.utils.stats import Stats
from superlu_dist_tpu.utils.testmat import laplacian_2d


@pytest.fixture(autouse=True)
def _no_leaked_chaos():
    chaos.uninstall()
    yield
    chaos.uninstall()


def _scaled(sp_mat, scale):
    return csr_from_scipy((sp.diags(scale) @ sp_mat).tocsr())


# --------------------------------------------------------------------
# gscon: the one-norm condition estimator
# --------------------------------------------------------------------

@pytest.mark.parametrize("dec", [0, 4, 8])
def test_rcond_tracks_true_condition_number(dec):
    """Hager–Higham vs the dense oracle, order of magnitude, across
    a kappa ladder (row-scaled Laplacian)."""
    lap = laplacian_2d(6).to_scipy()
    n = lap.shape[0]
    a = _scaled(lap, np.logspace(0.0, float(dec), n))
    lu = factorize(a, Options(), backend="host")
    est = estimate_rcond(lu)
    true = 1.0 / np.linalg.cond(a.to_scipy().toarray(), 1)
    assert est > 0.0
    # a one-norm estimator is a lower bound on ||A^-1||_1 in exact
    # arithmetic, so est >= true up to roundoff; order of magnitude
    # is the contract the serving policy needs
    assert true / 10.0 <= est <= true * 10.0


def test_rcond_estimate_adds_zero_factorizations():
    """The estimator rides the resident trisolve: a handful of
    solves, never a new factorization."""
    a = laplacian_2d(6)
    lu = factorize(a, Options(), backend="host")
    before = obs.HEALTH.factorizations
    est = estimate_rcond(lu)
    assert est > 0.0
    assert obs.HEALTH.factorizations == before


def test_ensure_rcond_caches_on_handle():
    from superlu_dist_tpu.numerics.gscon import ensure_rcond
    a = laplacian_2d(5)
    lu = factorize(a, Options(), backend="host")
    r1 = ensure_rcond(lu)
    assert lu.rcond == r1
    # second call reads the field (same object, no re-estimate drift)
    assert ensure_rcond(lu) == r1
    assert obs.HEALTH.last_rcond is not None


def test_one_norm_matches_dense():
    a = laplacian_2d(5)
    assert one_norm(a) == pytest.approx(
        np.abs(a.to_scipy().toarray()).sum(axis=0).max())


def test_gscon_estimator_solve_contract():
    """The estimator's compiled program is scatter-free (rides the
    merged packed trisolve) — the registry entry slulint checks."""
    from tools.slulint.contracts import assert_contract
    assert_contract("gscon.estimator_solve")


# --------------------------------------------------------------------
# typed singularity: plan-time structure, factor-time rcond floor
# --------------------------------------------------------------------

def test_structurally_singular_empty_column_is_typed():
    lap = laplacian_2d(5).to_scipy().tolil(copy=True)
    lap[:, 3] = 0.0
    a = csr_from_scipy(lap.tocsr())
    with pytest.raises(StructurallySingularError) as ei:
        gssvx(None, a, np.ones(a.n), backend="host")
    assert 3 in ei.value.empty_cols


def test_structurally_singular_empty_row_is_typed():
    lap = laplacian_2d(5).to_scipy().tolil(copy=True)
    lap[7, :] = 0.0
    a = csr_from_scipy(lap.tocsr())
    with pytest.raises(StructurallySingularError) as ei:
        gssvx(None, a, np.ones(a.n), backend="host")
    assert 7 in ei.value.empty_rows
    assert isinstance(ei.value, NumericalError)


def test_singular_to_working_precision_refused_any_mode(monkeypatch):
    """rcond below the floor (here ~1e-300: wild +-1e150 scaling) is
    a SingularMatrixError even in the default stamp mode — never a
    garbage solve."""
    monkeypatch.setenv("SLU_COND_ESTIMATE", "1")
    lap = laplacian_2d(6).to_scipy()
    n = lap.shape[0]
    scale = np.where(np.arange(n) % 2 == 0, 1e150, 1e-150)
    a = _scaled(lap, scale)
    with pytest.raises(SingularMatrixError) as ei:
        gssvx(None, a, np.ones(n), backend="host")
    assert ei.value.rcond is not None and ei.value.rcond < 1e-30


def test_refuse_mode_rejects_ill_conditioned(monkeypatch):
    """policy=refuse turns an ill-classified key (duplicated rows:
    GESP regularizes them to rcond ~1e-9, under sqrt(eps)) into a
    typed refusal instead of a stamped answer."""
    monkeypatch.setenv("SLU_COND_ESTIMATE", "1")
    monkeypatch.setenv("SLU_COND_POLICY", "refuse")
    dense = np.asarray(laplacian_2d(6).to_scipy().todense())
    dense[5, :] = dense[4, :]
    a = csr_from_scipy(sp.csr_matrix(dense))
    with pytest.raises(SingularMatrixError):
        gssvx(None, a, np.ones(a.n), backend="host")


def test_stamp_mode_serves_perturbed_result(monkeypatch):
    """Default stamp mode: duplicated rows factor ANYWAY (tiny-pivot
    replacement regularizes), but the answer carries the label — the
    ledger and the rcond ride the result."""
    monkeypatch.setenv("SLU_COND_ESTIMATE", "1")
    dense = np.asarray(laplacian_2d(6).to_scipy().todense())
    dense[5, :] = dense[4, :]
    a = csr_from_scipy(sp.csr_matrix(dense))
    x, lu, stats = gssvx(None, a, np.ones(a.n), backend="host")
    assert isinstance(x, PerturbedResult)
    assert lu.ledger is not None and lu.ledger.perturbed
    assert x.ledger.count >= 1
    assert x.rcond is not None and x.rcond < 1e-7
    assert stats.rcond == x.rcond


# --------------------------------------------------------------------
# the perturbation ledger
# --------------------------------------------------------------------

def test_ledger_counts_and_locates_tiny_pivots():
    dense = np.asarray(laplacian_2d(6).to_scipy().todense())
    dense[5, :] = dense[4, :]
    a = csr_from_scipy(sp.csr_matrix(dense))
    lu = factorize(a, Options(), backend="host")
    led = lu.ledger
    assert isinstance(led, PerturbationLedger)
    assert led.perturbed and led.count >= 1
    assert led.threshold > 0.0
    assert led.locations and len(led.locations) <= 32
    assert led.total_magnitude > 0.0
    d = led.to_dict()
    assert d["count"] == led.count and "threshold" in d


def test_ledger_clean_factorization_is_unperturbed():
    lu = factorize(laplacian_2d(6), Options(), backend="host")
    assert lu.ledger is not None and not lu.ledger.perturbed
    assert lu.ledger.count == 0


def test_perturbed_result_stamp_survives_views():
    """__array_finalize__: the serve micro-batcher slices columns out
    of a batched result — the stamp must ride the view."""
    led = PerturbationLedger(count=2, threshold=1e-8,
                             locations=(1, 3), truncated=False,
                             total_magnitude=2e-8)
    x = stamp_perturbed(np.ones((4, 2)), ledger=led, rcond=1e-9)
    col = x[:, 0]
    assert isinstance(col, PerturbedResult)
    assert col.ledger is led and col.rcond == 1e-9
    # np.asarray strips the subclass (oracle-side consumers see a
    # plain array)
    assert type(np.asarray(x)) is np.ndarray or \
        isinstance(np.asarray(x), PerturbedResult)


# --------------------------------------------------------------------
# front-door validation (driver and service)
# --------------------------------------------------------------------

def test_gssvx_rejects_nonfinite_a():
    lap = laplacian_2d(5).to_scipy().astype(np.float64)
    lap.data = lap.data.copy()
    lap.data[0] = np.nan
    a = csr_from_scipy(lap)
    with pytest.raises(InvalidInputError):
        gssvx(None, a, np.ones(a.n), backend="host")


def test_gssvx_rejects_nonfinite_b():
    a = laplacian_2d(5)
    b = np.ones(a.n)
    b[2] = np.inf
    with pytest.raises(InvalidInputError):
        gssvx(None, a, b, backend="host")


def test_gssvx_rejects_malformed_shapes():
    a = laplacian_2d(5)
    with pytest.raises(InvalidInputError):
        gssvx(None, a, np.ones(a.n + 1), backend="host")
    with pytest.raises(InvalidInputError):
        gssvx(None, a, np.zeros((a.n, 0)), backend="host")


def test_service_rejects_poisoned_request():
    svc = SolveService(ServeConfig(backend="host"), metrics=Metrics())
    try:
        a = laplacian_2d(5)
        b = np.ones(a.n)
        b[0] = np.nan
        with pytest.raises(InvalidInputError):
            svc.solve(a, b)
        # a clean request on the same service still works
        x = svc.solve(a, np.ones(a.n))
        assert np.all(np.isfinite(x))
    finally:
        svc.close()


def test_outcome_taxonomy_covers_numerics():
    f = SolveService._outcome_of
    assert f(InvalidInputError("x")) == "invalid_input"
    assert f(StructurallySingularError("x")) == "structurally_singular"
    assert f(SingularMatrixError("x")) == "singular"
    assert f(None) == "ok"


# --------------------------------------------------------------------
# condition policy thresholds
# --------------------------------------------------------------------

def test_condition_policy_classification():
    pol = ConditionPolicy()
    eps = float(np.finfo(np.float64).eps)
    assert pol.classify(None, "float64") == "ok"
    assert pol.classify(0.5, "float64") == "ok"
    assert pol.classify(np.sqrt(eps) / 2, "float64") == "ill"
    assert pol.classify(eps / 2, "float64") == "singular"
    with pytest.raises(SingularMatrixError):
        pol.enforce(eps / 2, "float64")


def test_condition_policy_berr_slack_tightens_for_ill_keys():
    pol = ConditionPolicy(slack_div=8.0)
    base = 64.0
    assert pol.berr_slack(base, None, "float64") == base
    assert pol.berr_slack(base, 0.5, "float64") == base
    assert pol.berr_slack(base, 1e-12, "float64") == base / 8.0


# --------------------------------------------------------------------
# the gauntlet (tier-1 subset vs the scipy oracle)
# --------------------------------------------------------------------

def test_gauntlet_subset_has_no_silent_wrong(monkeypatch):
    """One case per family class, classified under the live policy:
    the gate invariants (zero silent_wrong, zero untyped) hold on the
    tier-1 subset of the 14-case corpus."""
    monkeypatch.setenv("SLU_COND_ESTIMATE", "1")
    want = {"kappa_base": {"accurate"},
            "zero_row": {"refused_typed"},
            "nan_poisoned_a": {"refused_typed"},
            "dim_mismatch": {"refused_typed"},
            "duplicated_rows": {"stamped", "refused_typed"}}
    cases = {c["name"]: c for c in corpus()}

    def run(a, b):
        x, _, _ = gssvx(None, a, b, backend="host")
        return x

    for name, allowed in want.items():
        rec = classify(cases[name], run)
        assert rec["outcome"] in allowed, (name, rec)


def test_gauntlet_accurate_matches_oracle():
    """The kappa_base answer agrees with the dense oracle — the berr
    classifier isn't grading on a curve."""
    case = next(c for c in corpus() if c["name"] == "kappa_base")
    x, _, _ = gssvx(None, case["a"], case["b"], backend="host")
    ref = np.linalg.solve(case["a"].to_scipy().toarray(),
                          np.asarray(case["b"]))
    np.testing.assert_allclose(np.asarray(x).ravel(), ref.ravel(),
                               rtol=1e-8)


# --------------------------------------------------------------------
# near_singular chaos site
# --------------------------------------------------------------------

def test_chaos_near_singular_deterministic_and_inert():
    a = laplacian_2d(5)
    # off: the SAME object comes back (zero-copy hot path)
    assert chaos.maybe_skew_singular("near_singular", a) is a
    chaos.install("near_singular=1:0.5", seed=11)
    s1 = chaos.maybe_skew_singular("near_singular", a)
    assert s1 is not a
    np.testing.assert_allclose(
        np.asarray(s1.data),
        0.5 * np.asarray(a.data) + 0.5 * np.asarray(a.data).mean())
    chaos.uninstall()
    chaos.install("near_singular=1:0.5", seed=11)
    s2 = chaos.maybe_skew_singular("near_singular", a)
    np.testing.assert_array_equal(np.asarray(s1.data),
                                  np.asarray(s2.data))


def test_chaos_near_singular_full_skew_is_structural():
    """s=1 collapses every value to the mean — rank-1, and the plan
    still accepts the structure (values are nonzero), so the typed
    refusal comes from the CONDITION floor, not the structure check."""
    chaos.install("near_singular=1:1.0", seed=0)
    a = laplacian_2d(5)
    s = chaos.maybe_skew_singular("near_singular", a)
    v = np.asarray(s.data)
    assert np.allclose(v, v[0])


# --------------------------------------------------------------------
# observability: health events, per-factorization stats
# --------------------------------------------------------------------

def test_pivot_growth_unavailable_is_counted():
    from superlu_dist_tpu.obs.health import pivot_growth
    before = obs.HEALTH.pivot_growth_unavailable

    class _Broken:
        pass

    assert pivot_growth(_Broken()) is None
    assert obs.HEALTH.pivot_growth_unavailable == before + 1
    assert "pivot growth unavailable" in obs.HEALTH.summary()


def test_health_records_perturbation_and_rcond():
    before = obs.HEALTH.perturbed_factorizations
    dense = np.asarray(laplacian_2d(6).to_scipy().todense())
    dense[5, :] = dense[4, :]
    a = csr_from_scipy(sp.csr_matrix(dense))
    factorize(a, Options(), backend="host")
    snap = obs.HEALTH.snapshot()
    assert snap["perturbed_factorizations"] == before + 1
    last = snap["last_factor"]
    assert last["tiny_pivots"] >= 1
    assert last["perturbation"]["count"] >= 1


def test_stats_reports_per_factorization_tiny_pivots():
    s = Stats()
    s.note_factor_event(tiny_pivots=0, dtype="float32")
    s.note_factor_event(tiny_pivots=3, dtype="float64")
    s.rcond = 1.5e-9
    rep = s.report()
    assert "per factorization" in rep
    assert "float64: 3" in rep
    assert "estimated rcond" in rep
    snap = s.snapshot()
    assert snap["factor_events"][-1]["tiny_pivots"] == 3
    assert snap["rcond"] == 1.5e-9


# --------------------------------------------------------------------
# cadence: the rcond-drift trigger
# --------------------------------------------------------------------

def test_cadence_rcond_drift_trigger():
    c = Cadence(guard_limit=1e-9)
    c.note_berr(0.0, now=0.0)           # berr says everything is fine
    c.note_rcond(1e-2)                  # generation-0 baseline
    c.note_rcond(1e-6)                  # 10^4 x harder than baseline
    assert c.due(lag=1, now=100.0) == "rcond_drift"
    snap = c.snapshot()
    assert snap["rcond0"] == 1e-2 and snap["rcond_last"] == 1e-6


def test_cadence_no_trigger_without_drift():
    c = Cadence(guard_limit=1e-9)
    c.note_berr(0.0, now=0.0)
    c.note_rcond(1e-2)
    c.note_rcond(0.9e-2)                # within the 100x band
    assert c.due(lag=1, now=100.0) is None
    c2 = Cadence(guard_limit=1e-9)      # no estimates at all: inert
    c2.note_berr(0.0, now=0.0)
    assert c2.due(lag=1, now=100.0) is None
