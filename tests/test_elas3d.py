"""The finite-element deployment (benchmark configuration `elas3d_q1`,
PETSc ex56's Q1 elasticity matrix) at sizes the CPU can check: the
generator is elasticity, the normal path (plan_factorization ->
factorize(plan=...) -> solve) answers to the stated accuracy on it,
the configuration's controls do not, and the flop counters of
`Stats` agree with the benchmark's own count."""

import importlib.util
import os

import numpy as np
import pytest
import scipy.sparse.linalg as spla

import superlu_dist_tpu as slu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EPS = float(np.finfo(np.float64).eps)


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gen = _load("gen_elas3d", "configs", "gen_elas3d.py")
roofline = _load("bench_roofline", "roofline.py")


def rigid_body_modes(ne):
    """Three translations and three rotations of the (ne+1)^3 nodes,
    interleaved as the matrix's unknowns are: (n, 6)."""
    nn = ne + 1
    z, y, x = np.meshgrid(*[np.arange(nn) / ne] * 3, indexing="ij")
    x, y, z = x.ravel(), y.ravel(), z.ravel()
    o, e = np.zeros_like(x), np.ones_like(x)
    modes = [(e, o, o), (o, e, o), (o, o, e),
             (-y, x, o), (o, -z, y), (z, o, -x)]
    return np.stack([np.stack(m, axis=1).ravel() for m in modes], axis=1)


# -- the generator is elasticity --------------------------------------

def test_element_stiffness_symmetric_six_zero_eigenvalues():
    ke = gen.element_stiffness(1.0 / 5)
    assert np.allclose(ke, ke.T, rtol=0, atol=1e-15)
    w = np.linalg.eigvalsh((ke + ke.T) / 2)
    assert np.sum(np.abs(w) < 1e-12) == 6
    assert w[6] > 1e-3 and w[0] > -1e-12


@pytest.mark.parametrize("ne", [2, 4])
def test_unclamped_assembly_annihilates_rigid_body_modes(ne):
    a = gen.assemble(ne)
    assert np.abs(a @ rigid_body_modes(ne)).max() < 1e-12


def test_clamped_matrix_is_symmetric_positive_definite():
    a = gen.generate(3).toarray()
    # symmetric to the rounding of the assembly's sums
    assert np.allclose(a, a.T, rtol=0, atol=1e-15)
    assert np.linalg.eigvalsh(a).min() > 1e-3


@pytest.mark.parametrize("ne", [1, 3, 5])
def test_size_is_three_unknowns_a_node(ne):
    a = gen.generate(ne)
    assert a.shape == (3 * (ne + 1) ** 3,) * 2
    assert a.has_sorted_indices


def test_interior_row_stores_81_entries_and_clamped_rows_stay():
    ne, nn = 5, 6
    a = gen.generate(ne)
    counts = np.diff(a.indptr)
    node = 2 + nn * (2 + nn * 2)          # 27 unclamped neighbours
    assert list(counts[3 * node:3 * node + 3]) == [81, 81, 81]
    assert counts.max() == 81
    # the assembled pattern is kept: cancelled entries are stored
    assert np.sum(a.data == 0.0) > 0
    fixed = gen.clamped_dofs(ne)
    assert len(fixed) == 3 * nn * nn
    sub = a[fixed]
    assert np.array_equal(sub @ np.ones(a.shape[0]), np.ones(len(fixed)))
    assert np.array_equal(np.diff(sub.indptr), counts[fixed])


# -- the normal path on it --------------------------------------------

OPTIONS = dict(factor_dtype="float32", refine_dtype="float64",
               iter_refine=slu.IterRefine.SLU_DOUBLE)


def drifted(a0, seed, count=2):
    """Value sets on a0's pattern as the cell's drift makes them:
    rows rescaled by U(0.5, 1.5)."""
    rng = np.random.default_rng(seed)
    rows = np.diff(a0.indptr)
    out = []
    for _ in range(count):
        a = a0.copy()
        a.data = a0.data * np.repeat(
            rng.uniform(0.5, 1.5, a0.shape[0]), rows)
        out.append(a)
    return out


def errors(a, b, xtrue, x):
    denom = abs(a) @ np.abs(x) + np.abs(b)
    berr = float(np.max(np.abs(b - a @ x) / denom))
    rel = float(np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue))
    return berr, rel


def agreement(x, ref):
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


@pytest.fixture(scope="module", params=[3, 5])
def held(request):
    """One plan held over two drifted value sets, and the answers of
    the stated options on each."""
    a0 = gen.generate(request.param)
    opts = slu.Options(**OPTIONS)
    plan = slu.plan_factorization(slu.csr_from_scipy(a0), opts)
    rng = np.random.default_rng(request.param)
    steps = []
    for a in drifted(a0, 7 + request.param):
        xtrue = rng.standard_normal(a.shape[0])
        b = a @ xtrue
        lu = slu.factorize(slu.csr_from_scipy(a), opts, plan=plan)
        steps.append((a, b, xtrue, lu, np.asarray(slu.solve(lu, b))))
    return plan, steps


def test_refined_answers_meet_the_guarantees(held):
    _, steps = held
    for a, b, xtrue, _, x in steps:
        berr, rel = errors(a, b, xtrue, x)
        assert berr <= 64 * EPS and rel < 1e-9


def test_answers_agree_with_dense_and_sparse_references(held):
    _, steps = held
    for a, b, _, _, x in steps:
        assert agreement(x, np.linalg.solve(a.toarray(), b)) < 1e-9
        assert agreement(x, spla.splu(a.tocsc()).solve(b)) < 1e-9


@pytest.mark.parametrize("control", [
    {"refine_dtype": "float32"},
    {"iter_refine": slu.IterRefine.NOREFINE}], ids=["refine_float32",
                                                    "no_refine"])
def test_controls_miss_the_limits(held, control):
    """The configuration's controls (a lower precision in the
    program's place) miss every limit, on the same factors."""
    _, steps = held
    for a, b, xtrue, lu, _ in steps:
        low = slu.Options(**dict(OPTIONS, **control))
        x = np.asarray(slu.solve(
            slu.factorize(slu.csr_from_scipy(a), low, plan=lu.plan), b))
        berr, rel = errors(a, b, xtrue, x)
        assert berr > 64 * EPS and rel >= 1e-9
        assert agreement(x, spla.splu(a.tocsc()).solve(b)) >= 1e-9


def test_flop_counters_match_the_benchmarks_count(held):
    plan, steps = held
    stats = steps[-1][3].stats
    f = plan.frontal
    assert stats.factor_flops == pytest.approx(
        roofline.factor_flops(f.w, f.r), rel=1e-12)
    # every front at its bucket shape, and the groups' padding slots
    assert stats.factor_flops_executed >= (1 - 1e-12) * \
        roofline.factor_flops(f.wb, f.mb - f.wb)
    assert stats.factor_flops_executed >= stats.factor_flops
    assert "executed" in stats.report()
    last = slu.obs.HEALTH.snapshot()["last_factor"]["flops"]
    assert last["executed"] >= last["useful"] > 0


def test_host_oracle_executes_what_is_useful():
    a = slu.csr_from_scipy(gen.generate(2))
    lu = slu.factorize(a, slu.Options(), backend="host")
    assert lu.stats.factor_flops_executed == lu.stats.factor_flops > 0
