"""PrecisionPolicy, the escalation ladder, and serve dtype tiers
(precision/policy.py + models/gssvx ladder walk + serve/service.py;
ISSUE 5 acceptance pins).

The three acceptance criteria live here:
  * fp32 factor + doubleword residual lands within 10× of the
    all-fp64 baseline berr on the tier-1 matrix family;
  * the health-driven ladder promotes an ill-conditioned matrix to
    the next rung EXACTLY once (and records from/to/trigger);
  * (the zero-f64 HLO pin is in tests/test_doubleword.py.)
"""

import numpy as np
import pytest
import scipy.sparse as sp

from superlu_dist_tpu import (Options, PrecisionPolicy, ResidualMode,
                              YesNo, gssvx)
from superlu_dist_tpu.options import (SOLVE_TIME_FIELDS,
                                      solve_options_key)
from superlu_dist_tpu.precision import policy as pp
from superlu_dist_tpu.sparse import csr_from_scipy
from superlu_dist_tpu.utils.testmat import laplacian_2d, laplacian_3d


def _illcond(n=40, spread=10, seed=0):
    """cond = 10^spread via SVD synthesis (test_escalate.py's
    family): cond·eps_f32 >> 1 while cond·eps_f64 < 1."""
    rng = np.random.default_rng(seed)
    u, _ = np.linalg.qr(rng.standard_normal((n, n)))
    v, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.logspace(0, -spread, n)
    return csr_from_scipy(sp.csr_matrix(u @ np.diag(s) @ v.T))


# -- the policy object ------------------------------------------------

def test_policy_apply_and_roundtrip():
    pol = PrecisionPolicy(factor_dtype="float32",
                          residual=ResidualMode.DOUBLEWORD,
                          target_dtype="float64")
    opts = pol.apply()
    assert opts.factor_dtype == "float32"
    assert opts.residual_mode == "doubleword"
    assert opts.refine_dtype == "float64"
    back = PrecisionPolicy.from_options(opts)
    assert back.factor_dtype == "float32"
    assert back.residual == ResidualMode.DOUBLEWORD
    # residual also accepts the raw string
    assert PrecisionPolicy(residual="fp64").residual == ResidualMode.FP64
    with pytest.raises(TypeError):
        PrecisionPolicy(factor_dtype="floaty128")


def test_residual_mode_is_a_solve_time_field():
    """The batcher-variant / FACTORED-merge contract: residual_mode
    and solve_dtype ride SOLVE_TIME_FIELDS, so two requests differing
    only there share factors but never a batch."""
    assert "residual_mode" in SOLVE_TIME_FIELDS
    assert "solve_dtype" in SOLVE_TIME_FIELDS
    a = Options(residual_mode="doubleword")
    b = Options(residual_mode="fp64")
    assert solve_options_key(a) != solve_options_key(b)
    # factor_key is UNCHANGED by solve-side policy legs
    assert a.factor_key() == b.factor_key()


def test_resolve_residual_mode_auto_matches_legacy():
    from superlu_dist_tpu.options import IterRefine
    assert pp.resolve_residual_mode(
        Options(iter_refine=IterRefine.SLU_SINGLE)) == "plain"
    assert pp.resolve_residual_mode(
        Options(iter_refine=IterRefine.SLU_DOUBLE)) == "fp64"
    assert pp.resolve_residual_mode(
        Options(residual_mode="doubleword")) == "doubleword"
    with pytest.raises(ValueError, match="unknown residual_mode"):
        pp.resolve_residual_mode(Options(residual_mode="bogus"))


# -- the ladder -------------------------------------------------------

def test_ladder_and_next_rung():
    assert pp.ladder() == ("bfloat16", "float32", "float64")
    assert pp.next_factor_dtype("bfloat16") == "float32"
    assert pp.next_factor_dtype("float32") == "float64"
    assert pp.next_factor_dtype("float64") is None
    # ceiling: never climb past the accuracy class being sold
    assert pp.next_factor_dtype("bfloat16",
                                ceiling="float32") == "float32"
    assert pp.next_factor_dtype("float32", ceiling="float32") is None
    # a non-ladder dtype still climbs by eps comparison
    assert pp.next_factor_dtype("float16") == "float32"
    assert pp.lower_rungs("float64") == ("float32", "bfloat16")


def test_ladder_env_override(monkeypatch):
    monkeypatch.setenv("SLU_PREC_LADDER", "float64, float32")
    assert pp.ladder() == ("float32", "float64")
    assert pp.next_factor_dtype("float32") == "float64"


def test_ladder_policies_shape():
    pols = pp.ladder_policies("float64")
    assert [p.factor_dtype for p in pols] == ["bfloat16", "float32",
                                              "float64"]
    assert pols[0].residual == ResidualMode.DOUBLEWORD
    assert pols[1].residual == ResidualMode.DOUBLEWORD
    assert pols[2].residual == ResidualMode.PLAIN


def test_classify_trigger_ordering():
    assert pp.classify_trigger(float("nan")) == "nonfinite"
    assert pp.classify_trigger(1e-3, stalled=True) == "refine_stalled"
    assert pp.classify_trigger(
        1e-3, stalled=True, pivot_growth=1e9,
        factor_eps=1.2e-7) == "pivot_growth"
    assert pp.classify_trigger(1e-3) == "berr_plateau"


# -- acceptance: 10× berr on the tier-1 matrix family ----------------

@pytest.mark.parametrize("mk", [lambda: laplacian_2d(12),
                                lambda: laplacian_3d(6)],
                         ids=["lap2d", "lap3d"])
def test_fp32_doubleword_policy_within_10x_of_f64(mk):
    a = mk()
    rng = np.random.default_rng(1)
    xtrue = rng.standard_normal(a.n)
    b = a.to_scipy() @ xtrue
    pol = PrecisionPolicy(factor_dtype="float32",
                          residual=ResidualMode.DOUBLEWORD)
    x, lu, st = gssvx(pol.apply(), a, b)
    x64, lu64, st64 = gssvx(Options(), a, b)
    assert st.escalations == 0          # the contract held at fp32
    assert st.berr <= 10 * max(st64.berr, np.finfo(np.float64).eps)
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    assert relerr < 1e-12


# -- acceptance: the ladder promotes exactly once --------------------

def test_ladder_promotes_illconditioned_exactly_once():
    from superlu_dist_tpu import obs
    a = _illcond(spread=10)
    rng = np.random.default_rng(2)
    b = a.to_scipy() @ rng.standard_normal(a.n)
    esc_before = obs.HEALTH.snapshot()["escalations"]
    pol = PrecisionPolicy(factor_dtype="float32",
                          residual=ResidualMode.DOUBLEWORD)
    x, lu, st = gssvx(pol.apply(), a, b)
    assert st.escalations == 1          # exactly one rung climbed
    assert lu.effective_options.factor_dtype == "float64"
    assert st.berr < np.sqrt(np.finfo(np.float64).eps)
    h = obs.HEALTH.snapshot()
    assert h["escalations"] == esc_before + 1
    ev = h["last_escalation"]
    assert ev["from_dtype"] == "float32"
    assert ev["to_dtype"] == "float64"
    assert ev["trigger"] in ("berr_plateau", "refine_stalled",
                             "pivot_growth")
    # the per-trigger counter surfaces in the flat text dump
    assert "slu_health_escalations_by_trigger_" in obs.dump_text()


def test_bf16_climbs_one_rung_at_a_time():
    """Ladder semantics: a failing bf16 factor promotes THROUGH fp32,
    never jumping straight to fp64 — the health event ring records
    every hop in order.  (On this dense SVD family the device
    backend's fp32 rung also hits its documented tiny-pivot floor,
    test_escalate.py's cond(U11) note, so the walk lands at fp64 in
    two recorded steps — which is exactly the one-rung-at-a-time
    contract under test.)"""
    from superlu_dist_tpu import obs
    a = _illcond(spread=4, seed=3)
    rng = np.random.default_rng(4)
    b = a.to_scipy() @ rng.standard_normal(a.n)
    opts = Options(factor_dtype="bfloat16", max_refine_steps=16)
    x, lu, st = gssvx(opts, a, b)
    assert st.escalations >= 1
    events = obs.HEALTH.snapshot()["escalation_events"]
    hops = [(e["from_dtype"], e["to_dtype"])
            for e in events[-st.escalations:]]
    assert hops[0] == ("bfloat16", "float32")
    if st.escalations > 1:
        assert hops[1] == ("float32", "float64")
    assert st.berr < 64 * np.finfo(np.float64).eps


def test_escalation_disabled_still_respected():
    a = _illcond(spread=10, seed=5)
    rng = np.random.default_rng(6)
    b = a.to_scipy() @ rng.standard_normal(a.n)
    pol = PrecisionPolicy(factor_dtype="float32",
                          residual=ResidualMode.DOUBLEWORD)
    x, lu, st = gssvx(pol.apply().replace(escalate=YesNo.NO), a, b)
    assert st.escalations == 0
    assert lu.effective_options.factor_dtype == "float32"


# -- solve_dtype ------------------------------------------------------

def test_solve_dtype_pins_sweep_rhs_dtype():
    from superlu_dist_tpu.models.gssvx import (factorize,
                                               solve_rhs_dtype)
    a = laplacian_2d(8)
    lu = factorize(a, Options(factor_dtype="float32",
                              solve_dtype="float32"))
    assert solve_rhs_dtype(lu) == np.dtype(np.float32)
    lu64 = factorize(a, Options(factor_dtype="float32"))
    assert solve_rhs_dtype(lu64) == np.dtype(np.float64)


def test_solve_dtype_end_to_end_fp32_pipeline():
    from superlu_dist_tpu import solve
    from superlu_dist_tpu.models.gssvx import factorize
    a = laplacian_2d(8)
    rng = np.random.default_rng(7)
    xtrue = rng.standard_normal(a.n)
    b = a.to_scipy() @ xtrue
    lu = factorize(a, Options(factor_dtype="float32",
                              solve_dtype="float32"))
    x = solve(lu, b)
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    # the RHS was truncated to fp32 by policy: fp32-class accuracy
    # is the contract (refinement recovers against the CAST b)
    assert relerr < 1e-4
    assert np.all(np.isfinite(x))


# -- serve dtype tiers ------------------------------------------------

def _serve(dtype_tiers=True, **kw):
    from superlu_dist_tpu.serve import ServeConfig, SolveService
    return SolveService(ServeConfig(dtype_tiers=dtype_tiers, **kw))


def test_tier_serves_f64_request_from_f32_factors():
    svc = _serve()
    try:
        a = laplacian_3d(5)
        svc.prefactor(a, Options(factor_dtype="float32"))
        rng = np.random.default_rng(8)
        xtrue = rng.standard_normal(a.n)
        b = a.to_scipy() @ xtrue
        before = svc.cache.stats()["factorizations"]
        x = svc.solve(a, b, Options(factor_dtype="float64"))
        assert svc.metrics.counter("serve.dtype_tier_hits") == 1
        assert svc.cache.stats()["factorizations"] == before
        relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
        assert relerr < 1e-12           # f64-class through the tier
    finally:
        svc.close()


def test_tier_guard_blocks_and_rekeys_on_berr_miss():
    from superlu_dist_tpu import obs
    svc = _serve()
    try:
        a = _illcond(spread=10, seed=9)
        svc.prefactor(a, Options(factor_dtype="float32"))
        rng = np.random.default_rng(10)
        b = a.to_scipy() @ rng.standard_normal(a.n)
        svc.solve(a, b, Options(factor_dtype="float64"))
        assert svc.metrics.counter("serve.tier_escalations") == 1
        assert obs.HEALTH.snapshot()["last_escalation"]["trigger"] \
            == "tier_berr"
        # re-key: the next identical request factors at f64 honestly
        before = svc.cache.stats()["factorizations"]
        svc.solve(a, b, Options(factor_dtype="float64"))
        assert svc.cache.stats()["factorizations"] == before + 1
        assert svc.metrics.counter("serve.dtype_tier_hits") == 1
    finally:
        svc.close()


def test_tier_skipped_for_norefine_and_when_disabled():
    from superlu_dist_tpu.options import IterRefine
    from superlu_dist_tpu.serve.errors import FactorMissError
    svc = _serve(miss_policy="failfast")
    try:
        a = laplacian_3d(4)
        svc.prefactor(a, Options(factor_dtype="float32"))
        b = np.ones(a.n)
        # NOREFINE cannot recover the precision gap: no tier, and
        # failfast then rejects the cold f64 key
        with pytest.raises(FactorMissError):
            svc.solve(a, b, Options(factor_dtype="float64",
                                    iter_refine=IterRefine.NOREFINE))
        assert svc.metrics.counter("serve.dtype_tier_hits") == 0
    finally:
        svc.close()
    svc2 = _serve(dtype_tiers=False, miss_policy="failfast")
    try:
        a = laplacian_3d(4)
        svc2.prefactor(a, Options(factor_dtype="float32"))
        with pytest.raises(FactorMissError):
            svc2.solve(a, np.ones(a.n),
                       Options(factor_dtype="float64"))
        assert svc2.metrics.counter("serve.dtype_tier_hits") == 0
    finally:
        svc2.close()


def test_tier_cache_probe_order():
    """resident_lower_tier probes finest-first: with BOTH f32 and
    bf16 resident, the f32 sibling wins."""
    from superlu_dist_tpu.serve.factor_cache import (FactorCache,
                                                     matrix_key)
    a = laplacian_3d(4)
    cache = FactorCache()
    o32 = Options(factor_dtype="float32")
    obf = Options(factor_dtype="bfloat16")
    lu32 = cache.get_or_factorize(a, o32)
    lubf = cache.get_or_factorize(a, obf)
    hit = cache.resident_lower_tier(
        a, Options(factor_dtype="float64"),
        pp.lower_rungs("float64"))
    assert hit is not None
    t_key, t_lu, d = hit
    assert d == "float32" and t_lu is lu32


# -- the refinement loops' stopping threshold -------------------------

@pytest.mark.parametrize("dtype,real,factor", [
    ("float32", "float32", 1.0), ("float64", "float64", 1.0),
    ("complex64", "float32", 2.0 ** 0.5),
    ("complex128", "float64", 2.0 ** 0.5)])
def test_refine_eps_by_accumulator(dtype, real, factor):
    """A real accumulator stops at its eps (the reference's class); a
    complex one at sqrt(2) times the eps of its planes (a complex
    product rounds by sqrt(2)·gamma_2, Higham Lemma 3.5)."""
    assert pp.refine_eps(dtype) == pytest.approx(
        factor * float(np.finfo(real).eps), rel=1e-15)
    assert pp.refine_eps(np.dtype(dtype)) == pp.refine_eps(dtype)


def _helmholtz(n, sigma1=100.0, sigma2=10.0):
    """PETSc ex11's matrix (complex Helmholtz, five-point, -norandom),
    built from its equation."""
    h2 = 1.0 / ((n + 1) * (n + 1))
    t = sp.diags([-1.0, 0.0, -1.0], [-1, 0, 1], shape=(n, n))
    a = sp.kronsum(t, t, format="csr").astype(np.complex128)
    return (a + (4.0 - sigma1 * h2 + 1j * sigma2 * h2)
            * sp.identity(n * n, format="csr")).tocsr()


def test_complex_refinement_takes_no_pass_from_the_floor():
    """The host loop on a complex128 accumulator with complex64
    corrections (scipy's single-precision LU in the factorization's
    place: the loop's rule is what is under test, not a kernel).  On
    ex11 at -n 128 the berr of a refined answer stands at 0.9-1.3
    eps(float64), astride eps: under the real threshold a value set
    whose floor read 1.1 eps paid one more sweep, which gained
    nothing, and one whose floor read 0.95 eps did not.  Under
    refine_eps every value set stops on the pass that reaches the
    floor: no pass starts from a berr at or under the threshold, the
    answers stand inside it, and at least one of them stands above
    eps(float64), where the real threshold would have gone on."""
    import threading
    import types

    import scipy.sparse.linalg as spla

    from superlu_dist_tpu.models.refine import iterative_refine
    eps = float(np.finfo(np.float64).eps)
    thresh = pp.refine_eps("complex128")
    a0 = _helmholtz(128)
    rng = np.random.default_rng(2147483648)
    rows = np.diff(a0.indptr)
    finals = []
    for _ in range(4):
        a = a0.copy()
        a.data = a0.data * np.repeat(rng.uniform(0.5, 1.5, a0.shape[0]),
                                     rows)
        xtrue = (rng.standard_normal(a.shape[0])
                 + 1j * rng.standard_normal(a.shape[0]))
        b = a @ xtrue
        lu32 = spla.splu(sp.csc_matrix(a.astype(np.complex64)))
        handle = types.SimpleNamespace(
            a=csr_from_scipy(a), refine_cache={},
            cache_lock=threading.Lock(),
            effective_options=Options(factor_dtype="complex64",
                                      refine_dtype="complex128"))
        x0 = lu32.solve(b.astype(np.complex64)).astype(np.complex128)
        x, berr, steps, stalled = iterative_refine(
            handle, b, x0, lambda lu, r: lu32.solve(r),
            lambda r: r.astype(np.complex64),
            lambda d: d.astype(np.complex128))
        from superlu_dist_tpu import obs
        traj = obs.HEALTH.snapshot()["recent_solves"][-1][
            "berr_trajectory"]
        assert len(traj) == steps + 1 and traj[-1] == berr
        assert all(t > thresh for t in traj[:-1]), traj
        assert berr <= thresh and not stalled, traj
        assert np.linalg.norm(x - xtrue) < 1e-9 * np.linalg.norm(xtrue)
        finals.append(berr)
    assert max(finals) > eps, finals
