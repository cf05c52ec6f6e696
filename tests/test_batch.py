"""batch/: vmapped numeric factorization over the shared plan
(ISSUE 20).

The bitwise contract — batch_factorize/batch_solve equal the
SHARED-PLAN per-sample execution (per_sample_factorize, NOT an
independent factorize(), which re-equilibrates from the member's own
values) at fp64, factor panels and full-system solves, NOTRANS and
TRANS; batched Hager-Higham rcond parity; the B-ladder zero-recompile
pin; the masked-member failure model in both replace_tiny_pivot modes
(plus a gauntlet singular case riding a batch); the serve-tier factor
coalescer's fan-back/containment; and the loadgen batch lane.  The
two batch HLO contracts (batch.factor_segment / batch.trisolve) are
registered in CONTRACT_MODULES and lower in test_slulint's
check_all pass."""

import dataclasses
import importlib
import threading

import numpy as np
import pytest

from superlu_dist_tpu import obs
from superlu_dist_tpu.batch import (batch_factorize, batch_solve,
                                    bucket_for_batch,
                                    member_factorization, pad_values,
                                    per_sample_factorize, shared_plan,
                                    warmup_batch)
from superlu_dist_tpu.numerics import gscon
from superlu_dist_tpu.options import IterRefine, Options, Trans, YesNo
from superlu_dist_tpu.sparse import CSRMatrix
from superlu_dist_tpu.utils.stats import Stats
from superlu_dist_tpu.utils.testmat import (laplacian_2d, laplacian_3d,
                                            random_unsymmetric)

gssvx = importlib.import_module("superlu_dist_tpu.models.gssvx")

NOREFINE = Options(iter_refine=IterRefine.NOREFINE)


def _member_matrix(a, vals_i):
    return CSRMatrix(a.m, a.n, a.indptr, a.indices, vals_i)


def _oracle_lu(plan, a, vals_i):
    """The per-sample execution the bitwise contract names: the
    member factorized UNBATCHED under the SHARED plan, wrapped in an
    ordinary solve handle (refinement off — the raw trisolve is the
    object under comparison)."""
    lu = gssvx.LUFactorization(
        plan=plan, backend="jax",
        device_lu=per_sample_factorize(plan, vals_i),
        a=_member_matrix(a, vals_i), stats=Stats())
    lu.options = NOREFINE
    return lu


def _mk_case(a):
    rng = np.random.default_rng(7)
    B = 3
    vals = np.stack([a.data * (1.0 + 0.05 * rng.standard_normal(
        a.data.shape)) for _ in range(B)])
    vals[0] = a.data            # the template's own values ride too
    plan = shared_plan(a)
    blu = batch_factorize(plan, vals)
    return a, plan, vals, blu


@pytest.fixture(scope="module")
def case_rand():
    return _mk_case(random_unsymmetric(128, density=0.05, seed=1))


@pytest.fixture(scope="module")
def case_lap():
    # n=216 keeps the second pattern class cheap here
    return _mk_case(laplacian_3d(6))


@pytest.fixture(params=[
    "rand128",
    # the second elimination-tree shape rides the slow tier: tier-1
    # keeps the rand128 + gauntlet pattern pins
    pytest.param("lap216", marks=pytest.mark.slow)])
def batch_case(request):
    """(a, plan, vals[B,nnz], blu) per test shape — built once."""
    return request.getfixturevalue(
        "case_rand" if request.param == "rand128" else "case_lap")


# --------------------------------------------------------------------
# the bitwise contract: batched == shared-plan per-sample execution
# --------------------------------------------------------------------

def test_factor_bitwise_equals_per_sample(batch_case):
    a, plan, vals, blu = batch_case
    assert blu.ok_mask().all()
    for i in range(vals.shape[0]):
        ref = per_sample_factorize(plan, vals[i])
        got = blu.member(i)
        for pg, pr in zip(got.panels, ref.panels):
            for x, y in zip(pg, pr):
                assert np.array_equal(np.asarray(x), np.asarray(y))


def test_solve_bitwise_full_system_notrans_and_trans(batch_case):
    a, plan, vals, blu = batch_case
    B = vals.shape[0]
    rng = np.random.default_rng(11)
    bb = rng.standard_normal((B, a.n, 2))
    x = np.asarray(batch_solve(blu, bb))
    xt = np.asarray(batch_solve(blu, bb, trans=True))
    for i in range(B):
        lu = _oracle_lu(plan, a, vals[i])
        assert np.array_equal(np.asarray(gssvx.solve(lu, bb[i])), x[i])
        lut = dataclasses.replace(
            lu, options=NOREFINE.replace(trans=Trans.TRANS))
        assert np.array_equal(np.asarray(gssvx.solve(lut, bb[i])),
                              xt[i])
        # and the batched solution actually solves the member system
        r = np.max(np.abs(_member_matrix(a, vals[i]).to_scipy()
                          @ x[i] - bb[i]))
        assert r < 1e-8


def test_rcond_batch_matches_sequential_estimator(case_rand):
    a, plan, vals, blu = case_rand
    anorms = [gscon.one_norm(_member_matrix(a, vals[i]))
              for i in range(vals.shape[0])]
    rc = gscon.estimate_rcond_batch(blu, anorms)
    for i in range(vals.shape[0]):
        lu = member_factorization(blu, i, a=_member_matrix(a, vals[i]),
                                  options=NOREFINE)
        assert gscon.estimate_rcond(lu, anorm=anorms[i]) == rc[i]
        assert 0.0 < rc[i] <= 1.0


# --------------------------------------------------------------------
# B-ladder economics: warm every rung once, then zero recompiles
# --------------------------------------------------------------------

def test_ladder_zero_recompiles_after_warmup(case_rand):
    a, plan, _vals, _blu = case_rand
    ladder = (1, 4)
    assert warmup_batch(plan, a.data, ladder=ladder) == len(ladder)
    m0f = obs.COMPILE_WATCH.misses("batch_factor")
    m0s = obs.COMPILE_WATCH.misses("batch_solve")
    for bsz in (1, 3, 4):        # 3→4 exercises the pad-up path
        rung = bucket_for_batch(bsz, ladder)
        vals = np.stack([a.data * (1 + 0.01 * k) for k in range(bsz)])
        blu = batch_factorize(plan, pad_values(vals, rung))
        x = np.asarray(batch_solve(blu, np.ones((rung, a.n))))[:bsz]
        assert np.all(np.isfinite(x))
    assert obs.COMPILE_WATCH.misses("batch_factor") == m0f
    assert obs.COMPILE_WATCH.misses("batch_solve") == m0s


# --------------------------------------------------------------------
# masked members: one bad matrix never poisons its siblings
# --------------------------------------------------------------------

@pytest.fixture(scope="module")
def rand_no_plan():
    """rand128 planned with tiny-pivot replacement OFF — the typed-
    refusal mode."""
    a = random_unsymmetric(128, density=0.05, seed=1)
    return a, shared_plan(a, Options(replace_tiny_pivot=YesNo.NO))


def test_masked_member_typed_refusal_siblings_clean(rand_no_plan):
    a, plan = rand_no_plan
    vals = np.stack([a.data, np.zeros_like(a.data), 2.0 * a.data])
    blu = batch_factorize(plan, vals)
    assert blu.ok_mask().tolist() == [True, False, True]
    with pytest.raises(ZeroDivisionError, match="member 1"):
        blu.member(1)
    # healthy siblings factor AND serve normally
    for i in (0, 2):
        lu = member_factorization(blu, i,
                                  a=_member_matrix(a, vals[i]))
        assert np.all(np.isfinite(np.asarray(
            gssvx.solve(lu, np.ones(a.n)))))


def test_masked_member_perturbation_ledger_default_mode(case_rand):
    """Default replace_tiny_pivot=YES: the singular member is
    PERTURBED (GESP's tiny-pivot substitution) and its handle says so
    via the perturbation ledger — never a silent plain result."""
    a, plan, _vals, _blu = case_rand
    # B=3 on purpose: reuses the factor program case_rand compiled
    vals = np.stack([a.data, np.zeros_like(a.data), a.data])
    blu = batch_factorize(plan, vals)
    assert blu.ok_mask().tolist() == [True, True, True]
    lu1 = member_factorization(blu, 1, a=_member_matrix(a, vals[1]))
    assert lu1.ledger is not None and lu1.ledger.perturbed
    lu0 = member_factorization(blu, 0, a=a)
    assert lu0.ledger is None or not lu0.ledger.perturbed


def test_gauntlet_singular_member_masked_in_batch():
    """The gauntlet's duplicated_rows case (numerically singular,
    full structure) rides a batch next to a healthy perturbation of
    itself: its outcome is TYPED (refusal or a perturbation-stamped
    handle — the test_numerics acceptance set), and the healthy
    sibling factors bitwise-clean."""
    from superlu_dist_tpu.numerics.gauntlet import corpus
    case = next(c for c in corpus() if c["name"] == "duplicated_rows")
    a = case["a"]
    rng = np.random.default_rng(3)
    fixed = a.data * (1.0 + 0.05 * rng.standard_normal(a.data.shape))
    vals = np.stack([a.data, fixed])
    plan = shared_plan(a, Options(replace_tiny_pivot=YesNo.NO))
    blu = batch_factorize(plan, vals)
    if blu.ok_mask()[0]:
        # exact duplication survived elimination rounding: the member
        # must still carry its (near-)singularity in-band via rcond
        lu0 = member_factorization(blu, 0, a=a)
        rc = gscon.estimate_rcond(lu0, anorm=gscon.one_norm(a))
        assert rc < 1e-12
    else:
        with pytest.raises(ZeroDivisionError):
            blu.member(0)
    # the de-duplicated sibling is healthy and bitwise per-sample
    assert blu.ok_mask()[1]
    ref = per_sample_factorize(plan, vals[1])
    for pg, pr in zip(blu.member(1).panels, ref.panels):
        for x, y in zip(pg, pr):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_per_sample_factorize_typed_refusal(rand_no_plan):
    a, plan = rand_no_plan
    with pytest.raises(ZeroDivisionError):
        per_sample_factorize(plan, np.zeros_like(a.data))


# --------------------------------------------------------------------
# serve-tier factor coalescer: fan-back, containment, typed refusal
# --------------------------------------------------------------------

BOPTS = Options(factor_dtype="float64", replace_tiny_pivot=YesNo.NO)


def _coalesced_service(monkeypatch, window_ms="50"):
    monkeypatch.setenv("SLU_BATCH_COALESCE", "1")
    monkeypatch.setenv("SLU_BATCH_WINDOW_MS", window_ms)
    from superlu_dist_tpu.serve import (Metrics, ServeConfig,
                                        SolveService)
    svc = SolveService(ServeConfig(), metrics=Metrics())
    assert svc._coalescer is not None
    return svc


def _burst(svc, mats, options):
    """Submit every matrix concurrently (all inside one coalesce
    window) and collect per-index outcomes."""
    out = [None] * len(mats)

    def work(i):
        try:
            svc.prefactor(mats[i], options)
            out[i] = "ok"
        except ZeroDivisionError:
            out[i] = "refused"
        except Exception as e:            # pragma: no cover
            out[i] = f"unexpected:{e!r}"

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(len(mats))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    return out


def test_coalescer_merges_cold_keys_and_fans_back(monkeypatch):
    svc = _coalesced_service(monkeypatch)
    try:
        a = laplacian_2d(6)
        mats = [_member_matrix(a, a.data * (1.0 + 0.01 * i))
                for i in range(3)]
        assert _burst(svc, mats, BOPTS) == ["ok", "ok", "ok"]
        assert svc.metrics.counter("serve.batch_flushes") >= 1
        assert svc.metrics.counter("serve.batch_fanned_back") == 3
        # fanned-back members are ORDINARY residents: keyed solves
        # hit the cache, no refactorization
        f0 = svc.metrics.counter("serve.factorizations")
        for m in mats:
            x = svc.solve(m, np.ones(a.n), options=BOPTS)
            r = np.max(np.abs(m.to_scipy() @ np.asarray(x) - 1.0))
            assert r < 1e-8
        assert svc.metrics.counter("serve.factorizations") == f0
    finally:
        svc.close()


def test_coalescer_member_refusal_does_not_poison_siblings(
        monkeypatch):
    svc = _coalesced_service(monkeypatch)
    try:
        a = laplacian_2d(6)
        mats = [_member_matrix(a, a.data),
                _member_matrix(a, np.zeros_like(a.data)),
                _member_matrix(a, 2.0 * a.data)]
        assert _burst(svc, mats, BOPTS) == ["ok", "refused", "ok"]
        assert svc.metrics.counter("serve.batch_member_refused") >= 1
        assert svc.metrics.counter("serve.batch_flush_errors") == 0
    finally:
        svc.close()


def test_loadgen_batch_lane_typed_outcomes(monkeypatch):
    svc = _coalesced_service(monkeypatch)
    try:
        from superlu_dist_tpu.serve import run_load
        a = laplacian_2d(6)
        res = run_load(svc, [a], requests=8, concurrency=4,
                       hot_fraction=1.0, seed=2, batch_fraction=1.0,
                       batch_singular_fraction=0.25,
                       batch_options=BOPTS)
        by = res["by_status"]
        assert set(by) <= {"batch_ok", "batch_member_refused"}
        assert by.get("batch_ok", 0) >= 1
        assert sum(by.values()) == 8
    finally:
        svc.close()
