"""batch/: the refined batched solve (ISSUE 48).

`batch_solve` under a handle's options refines every member to the
guarantee the one-system `solve` gives, by pdgsrfs's rule on the
member's own berr: against LAPACK's banded solver in float64 and
against `solve` member by member, on the 32 x 31 nine-point grid of
the collision-operator deployment (B = 8 and a rung of 1).  A singular
member and an ill-conditioned one are named by index with their
siblings at the guarantee; the factor program's own scaling against
the float64 oracle `batch_scaled_values`; the sweep's arm by where it
runs; zero recompiles over a ring turn after warm-up; the
member-parallel arm within 2 ulp of the scan on XLA:CPU; the handle's
`held_bytes` with its packs."""

import numpy as np
import pytest
import scipy.linalg
import scipy.sparse as sp

import superlu_dist_tpu as slu
from superlu_dist_tpu import obs
from superlu_dist_tpu.batch import (batch_scaled_values,
                                    member_factorization, shared_plan)
from superlu_dist_tpu.batch import engine
from superlu_dist_tpu.options import IterRefine, Options, YesNo
from superlu_dist_tpu.sparse import CSRMatrix

NPAR, NPERP = 32, 31
N = NPAR * NPERP
EPS = float(np.finfo(np.float64).eps)
OPTS = Options(factor_dtype="float32", refine_dtype="float64",
               iter_refine=IterRefine.SLU_DOUBLE)


def band(m):
    return sp.diags([np.ones(m - 1), np.ones(m), np.ones(m - 1)],
                    [-1, 0, 1])


def pattern():
    p = sp.kron(band(NPERP), band(NPAR), format="csr")
    p.sort_indices()
    return p


def members(count, seed=0, shift=1.0):
    """`count` nonsymmetric nine-point value sets: a zero-row-sum
    diffusion stencil with a seeded drift, plus `shift` on the
    diagonal (cond ~ 16 / shift)."""
    p = pattern()
    rows = np.repeat(np.arange(N), np.diff(p.indptr))
    diag = rows == p.indices
    rng = np.random.default_rng(seed)
    out = np.empty((count, p.nnz))
    for m in range(count):
        off = -(1.0 + 0.4 * rng.random(p.nnz))
        off[diag] = 0.0
        v = off.copy()
        v[diag] = -np.bincount(rows, weights=off, minlength=N)
        v[diag] += shift
        out[m] = v * (1.0 + 0.2 * m)
    return p, out


def csr(p, v):
    return CSRMatrix(N, N, p.indptr.astype(np.int64),
                     p.indices.astype(np.int64), np.asarray(v))


def banded(p, v, b):
    bw = NPAR + 1
    rows = np.repeat(np.arange(N), np.diff(p.indptr))
    ab = np.zeros((2 * bw + 1, N))
    ab[bw + rows - p.indices, p.indices] = v
    return scipy.linalg.solve_banded((bw, bw), ab, b)


def berr_of(p, v, b, x):
    a = sp.csr_matrix((v, p.indices, p.indptr), shape=(N, N))
    return np.max(np.abs(b - a @ x) / (abs(a) @ np.abs(x) + np.abs(b)))


@pytest.fixture(scope="module")
def case():
    p, vals = members(8)
    plan = shared_plan(csr(p, vals.mean(axis=0)), OPTS)
    rng = np.random.default_rng(5)
    xtrue = rng.standard_normal((8, N))
    b = np.stack([sp.csr_matrix((vals[m], p.indices, p.indptr),
                                shape=(N, N)) @ xtrue[m]
                  for m in range(8)])
    return p, vals, plan, xtrue, b


def test_entry_points_are_at_the_package_root():
    assert slu.batch_factorize is engine.batch_factorize
    assert slu.batch_solve is engine.batch_solve
    assert slu.BatchedLU is engine.BatchedLU


@pytest.mark.parametrize("count", [8, 1])
def test_refined_to_the_guarantee_against_banded_and_solve(case, count):
    p, vals, plan, xtrue, b = case
    vals, xtrue, b = vals[:count], xtrue[:count], b[:count]
    blu = slu.batch_factorize(plan, vals, options=OPTS)
    assert blu.dtype == np.float32 and blu.effective_options is OPTS
    assert blu.values is vals        # the caller's, unscaled, no copy
    st = slu.Stats()
    x = slu.batch_solve(blu, b, stats=st)
    assert x.shape == (count, N) and x.dtype == np.float64
    out = st.batch
    assert out["berr"].shape == out["refine_steps"].shape == (count,)
    # a member may stop astride eps (stalled there): none missed
    assert out["missed"].size == 0
    assert st.dispatch["batch_members"] == count
    assert st.dispatch["batch_sweep_arm"] == "scan"
    assert st.dispatch["batch_residual"] == "host"
    assert st.berr == out["berr"].max() <= 2 * EPS
    assert st.refine_steps == out["refine_steps"].max()
    # the ring holds ONE record a batched solve
    rec = obs.HEALTH.snapshot()["recent_solves"][-1]
    assert rec["members"] == count
    assert rec["members_stalled"] == int(out["stalled"].sum())
    assert rec["sweep_arm"] == "scan" and rec["berr"] <= 2 * EPS
    assert rec["sweeps"] == {"float32": 1 + rec["steps"]}
    for m in range(count):
        xref = banded(p, vals[m], b[m])
        assert np.linalg.norm(x[m] - xref) < 1e-11 * np.linalg.norm(xref)
        assert berr_of(p, vals[m], b[m], x[m]) <= 2 * EPS
        # the one-system path on the same member of the same plan
        lu = member_factorization(blu, m, a=csr(p, vals[m]),
                                  options=OPTS)
        s1 = slu.Stats()
        x1 = slu.solve(lu, b[m], stats=s1)
        assert np.linalg.norm(x[m] - x1) < 1e-12 * np.linalg.norm(x1)
        # the same berr class (a loop may stop astride eps)
        assert s1.berr <= 2 * EPS and out["berr"][m] <= 2 * EPS
        assert abs(int(out["refine_steps"][m]) - s1.refine_steps) <= 1
    # two columns a member ride the same loop
    x2 = slu.batch_solve(blu, np.stack([b, 2.0 * b], axis=2))
    assert x2.shape == (count, N, 2)
    assert np.allclose(x2[:, :, 0], x, rtol=0, atol=1e-11)
    assert np.allclose(x2[:, :, 1], 2.0 * x, rtol=0, atol=1e-11)


def test_norefine_and_the_controls_precision(case):
    p, vals, plan, xtrue, b = case
    raw = slu.batch_solve(slu.batch_factorize(
        plan, vals, options=OPTS.replace(
            iter_refine=IterRefine.NOREFINE)), b)
    # a handle made without options solves unrefined, as it always did
    bare = slu.batch_solve(slu.batch_factorize(
        plan, vals, dtype=np.float32), b)
    assert np.array_equal(raw, bare)
    err = np.linalg.norm(raw - xtrue) / np.linalg.norm(xtrue)
    assert 1e-8 < err < 1e-4                 # float32 factors, bare
    st = slu.Stats()
    x32 = slu.batch_solve(slu.batch_factorize(
        plan, vals, options=OPTS.replace(refine_dtype="float32")), b,
        stats=st)
    assert x32.dtype == np.float32
    assert berr_of(p, vals[0], b[0], x32[0].astype(np.float64)) > 1e-9


def test_a_singular_and_an_ill_conditioned_member_are_named(case):
    p, vals, plan, xtrue, b = case
    opts = OPTS.replace(replace_tiny_pivot=YesNo.NO)
    plan = shared_plan(csr(p, vals.mean(axis=0)), opts)
    vals = vals.copy()
    vals[2] = 0.0                            # singular
    # the diffusion stencil with next to nothing on the diagonal:
    # cond ~ 1e10, float32 factors cannot carry a correction
    vals[5] = members(1, seed=9, shift=1e-9)[1][0]
    b = b.copy()
    b[5] = sp.csr_matrix((vals[5], p.indices, p.indptr),
                         shape=(N, N)) @ xtrue[5]
    blu = slu.batch_factorize(plan, vals, options=opts)
    assert blu.ok_mask().tolist() == [True, True, False] + [True] * 5
    assert blu.member_status()[2] == "singular"
    st = slu.Stats()
    x = slu.batch_solve(blu, b, stats=st)
    assert st.batch["missed"].tolist() == [2, 5]
    assert st.batch["stalled"][5] and st.batch["berr"][5] > 64 * EPS
    assert st.refine_stalled
    rec = obs.HEALTH.snapshot()["recent_solves"][-1]
    assert rec["members"] == 8 and rec["members_stalled"] >= 1
    # the siblings are untouched: at the guarantee, every one
    for m in (0, 1, 3, 4, 6, 7):
        assert st.batch["berr"][m] <= 2 * EPS
        assert berr_of(p, vals[m], b[m], x[m]) <= 2 * EPS


@pytest.mark.parametrize("dtype,ulps", [(np.float64, 0), (np.float32, 4)])
def test_the_factor_programs_scaling_against_the_oracle(case, dtype,
                                                        ulps):
    """Dr A Dc in the factor program's prologue: bitwise the float64
    oracle for float64 factors; for float32 ones the cast comes first,
    so a value differs from the oracle's cast by the roundings of two
    scale vectors and two products (4 ulp stated, 2 seen)."""
    p, vals, plan, xtrue, b = case
    sched = engine.get_schedule(plan, 1)
    import jax
    import jax.numpy as jnp
    rs, cs = engine._coo_scales(plan, sched, dtype)
    got = np.asarray(jax.jit(lambda v: (v * rs) * cs)(
        jnp.asarray(vals.astype(dtype))))
    want = batch_scaled_values(plan, vals).astype(dtype)
    if ulps == 0:
        assert np.array_equal(got, want)
    else:
        assert np.max(np.abs(got - want) / np.abs(want)) \
            <= ulps * np.finfo(dtype).eps
    # and through the program: pre-scaled values with scaled=True give
    # the factors of raw values scaled inside (float64: to the bit)
    inside = slu.batch_factorize(plan, vals[:2], dtype=dtype)
    outside = slu.batch_factorize(plan, want[:2], dtype=dtype,
                                  scaled=True)
    for pi, po in zip(inside.panels, outside.panels):
        for x, y in zip(pi, po):
            x, y = np.asarray(x), np.asarray(y)
            if ulps == 0:
                assert np.array_equal(x, y)
            else:
                assert np.allclose(x, y, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("trans", [False, True])
@pytest.mark.parametrize("nrhs", [1, 3])
def test_the_native_residual_is_the_scipy_twins(case, trans, nrhs):
    """One pass over the values in the native library against two
    block-diagonal scipy products: r to the bit (a row sums in the
    pattern's order in both), berr to the bit, in float64 and in
    float32; a member with a NaN reads berr NaN and no other does."""
    from superlu_dist_tpu.models.refine import BatchResidual
    from superlu_dist_tpu.utils import native
    if not native.available():
        pytest.skip("no native host library")
    p, vals, plan, xtrue, b = case
    res = BatchResidual(plan, trans)
    assert (res.src is None) == (not trans)
    rng = np.random.default_rng(3)
    x = rng.standard_normal((8, N, nrhs))
    bb = rng.standard_normal((8, N, nrhs))
    for dt in (np.float64, np.float32):
        args = [a.astype(dt) for a in (vals, x, bb)]
        r, berr = res(*args)
        r2, berr2 = res.twin(*args)
        assert r.dtype == berr.dtype == dt and berr.shape == (8,)
        assert np.array_equal(r, r2) and np.array_equal(berr, berr2)
    a5 = sp.csr_matrix((vals[5], p.indices, p.indptr), shape=(N, N))
    a5 = a5.T if trans else a5
    assert np.allclose(r[5], bb[5] - a5 @ x[5], rtol=0, atol=1e-3)
    x[2, 7, 0] = np.nan
    _, berr = res(vals, x, bb)
    assert np.flatnonzero(np.isnan(berr)).tolist() == [2]


def test_the_sweeps_arm_is_decided_by_where_it_runs(monkeypatch):
    assert engine._solve_arm("cpu") == "scan"
    for name in ("tpu", "gpu", "METAL", "anything"):
        assert engine._solve_arm(name) == "vmap"
    import jax
    assert engine._solve_arm() == "scan"          # tests run on XLA:CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert engine._solve_arm() == "vmap"


def test_member_parallel_arm_within_two_ulp_of_the_scan(case):
    """XLA:CPU's batch-collapsed dots reassociate, so the
    member-parallel sweep drifts from the per-sample one there: within
    2 ulp of the answer's largest entry, member by member."""
    p, vals, plan, xtrue, b = case
    import jax.numpy as jnp
    blu = slu.batch_factorize(plan, vals, dtype=np.float64)
    bf = jnp.asarray(b[:, :, None])
    y = {arm: np.asarray(engine._batch_solve_fns(
        blu.schedule, blu.dtype, arm)[0][0](blu.packs, bf))
        for arm in ("scan", "vmap")}
    scale = np.abs(y["scan"]).max(axis=(1, 2), keepdims=True)
    assert np.max(np.abs(y["vmap"] - y["scan"]) / scale) <= 2 * EPS


def test_zero_recompiles_over_a_ring_turn(case):
    p, vals, plan, xtrue, b = case
    ring = [vals * (1.0 + 0.02 * k) for k in range(4)]
    for k in range(2):                       # warm-up
        slu.batch_solve(slu.batch_factorize(plan, ring[k],
                                            options=OPTS), b)
    m0 = {ph: obs.COMPILE_WATCH.misses(ph)
          for ph in ("batch_factor", "batch_solve")}
    kept = []
    for k in range(4):
        st = slu.Stats()
        x = slu.batch_solve(slu.batch_factorize(plan, ring[k],
                                                options=OPTS), b,
                            stats=st)
        assert st.berr <= 2 * EPS
        kept.append((x, x.copy()))
    assert {ph: obs.COMPILE_WATCH.misses(ph) for ph in m0} == m0
    # an answer is the caller's: the loop's kept buffers are not it
    assert all(np.array_equal(x, was) for x, was in kept)


def test_a_straggler_costs_the_batch_a_sweep_of_the_rung(case,
                                                       monkeypatch):
    """Once no more than the rung's members are live a pass sweeps
    them alone, at the rung's width: the same answers, berr and pass
    counts as passes of all (bitwise on XLA:CPU's arm), and the
    rung's program is compiled at the first solve, before a pass
    needs it."""
    p, vals, plan, xtrue, b = case
    plan = shared_plan(csr(p, vals.mean(axis=0)), OPTS)   # own cache
    assert engine._straggler_rung(2048) == 16
    assert engine._straggler_rung(8) == 0
    # a member of cond ~ 1e5: one pass more than any sibling
    slow = vals.copy()
    slow[3] = members(1, seed=9, shift=1e-4)[1][0]
    bs = b.copy()
    bs[3] = sp.csr_matrix((slow[3], p.indices, p.indptr),
                          shape=(N, N)) @ xtrue[3]
    out = {}
    for rung in (0, 2):
        monkeypatch.setattr(engine, "_straggler_rung", lambda m: rung)
        if rung:                # warm-up takes no straggler pass
            st = slu.Stats()
            slu.batch_solve(slu.batch_factorize(plan, vals,
                                                options=OPTS), b,
                            stats=st)
            assert {w for _, w in st.batch["passes"]} == {8}
            m0 = obs.COMPILE_WATCH.misses("batch_solve")
        st = slu.Stats()
        x = slu.batch_solve(slu.batch_factorize(plan, slow,
                                                options=OPTS), bs,
                            stats=st)
        out[rung] = (x, st.batch, dict(st.sweeps))
    assert obs.COMPILE_WATCH.misses("batch_solve") == m0
    (x0, b0, s0), (x2, b2, s2) = out[0], out[2]
    assert [w for _, w in b0["passes"]] == [8] * len(b0["passes"])
    widths = [w for _, w in b2["passes"]]
    assert widths[0] == 8 and widths[-1] == 2 and b2["passes"][-1][0] == 1
    assert [n for n, _ in b2["passes"]] == [n for n, _ in b0["passes"]]
    assert b2["refine_steps"][3] == b2["refine_steps"].max() > \
        np.delete(b2["refine_steps"], 3).max()
    np.testing.assert_array_equal(x2, x0)
    for key in ("berr", "refine_steps", "stalled", "missed"):
        np.testing.assert_array_equal(b2[key], b0[key])
    assert s2 == s0 and b2["berr"].max() <= 2 * EPS


def test_held_bytes_counts_the_packs(case):
    p, vals, plan, xtrue, b = case
    import jax
    blu = slu.batch_factorize(plan, vals, options=OPTS)
    panels = sum(int(a.nbytes) for q in blu.panels for a in q)
    packs = sum(int(a.nbytes)
                for a in jax.tree_util.tree_leaves(blu.packs))
    assert packs > 0 and blu.held_bytes() == panels + packs
