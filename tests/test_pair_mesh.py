"""Pair storage on the mesh: a complex system on a process grid runs
as real and imaginary planes sharded over the devices
(parallel/factor_dist with `pair`, ops/batched._factor_group_impl_pair
under a mesh axis, the cooperative tree-top LU of ops/coop_sharded in
pair arithmetic), through `factorize(grid=)` / `solve`, the entry
points every other path uses.  That is what a TPU mesh runs by
`utils/platform.complex_lowering`; `SLU_COMPLEX_PAIR=1` forces it on
the XLA:CPU mesh of the tests.

The system is PETSc ex11's Helmholtz matrix (the benchmark's own
generator) at -n 8 to -n 16 on four of the eight forced host devices
as a 2x2x1 grid; the oracle is numpy / scipy in complex128 on seeded
values, nothing of the program.  Tolerances:

  * BERR_MAX = 64 eps(float64) on the componentwise backward error and
    ERR_MAX = 1e-9 on the error against the manufactured solution and
    against scipy's LU: the limits the benchmark's complex
    configurations state.  A refined answer reads some 2e-16 and 1e-15
    here; complex64 refinement reads 1e-7 on both and fails each.
  * PLANES_TOL = 200 eps(float32) on the factors against the
    one-device pair factorization: the same arithmetic in another
    order (per-device slabs, the cooperative chain's panels of 64
    against blocks of 32) agrees to float32 rounding times the modest
    growth of this matrix, and to nothing tighter.
"""

import importlib.util
import os

import numpy as np
import pytest
import scipy.sparse.linalg as spla

import jax
import jax.numpy as jnp

from superlu_dist_tpu import (Options, Stats, csr_from_scipy, factorize,
                              get_diag_u, gssvx, obs,
                              plan_factorization, solve)
from superlu_dist_tpu.ops import batched
from superlu_dist_tpu.options import IterRefine, Trans
from superlu_dist_tpu.parallel import factor_dist
from superlu_dist_tpu.parallel.grid import make_solver_mesh
from superlu_dist_tpu.utils import platform as plat

EPS64 = float(np.finfo(np.float64).eps)
EPS32 = float(np.finfo(np.float32).eps)
BERR_MAX = 64 * EPS64
ERR_MAX = 1e-9
PLANES_TOL = 200 * EPS32

OPTS = Options(factor_dtype="complex64", refine_dtype="complex128",
               iter_refine=IterRefine.SLU_DOUBLE)


def _ex11(n):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "gen_helm2d.py")
    spec = importlib.util.spec_from_file_location("gen_helm2d", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    return gen.generate(n=n, sigma1=100.0, sigma2_imag=10.0)


def _rescaled(A, rng):
    """A second value set on A's pattern: rows rescaled by U(0.5, 1.5),
    the benchmark's value drift."""
    B = A.copy()
    B.data = B.data * np.repeat(rng.uniform(0.5, 1.5, A.shape[0]),
                                np.diff(A.indptr))
    return B


def _system(A, rng, nrhs=None):
    shape = (A.shape[0],) if nrhs is None else (A.shape[0], nrhs)
    xtrue = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    return xtrue, A @ xtrue


def _berr(A, x, b):
    denom = abs(A) @ np.abs(x) + np.abs(b)
    return float(np.max(np.abs(b - A @ x) / denom))


def _relerr(x, ref):
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def _judge(A, x, b, xtrue):
    """The three comparisons of the benchmark's complex cells."""
    x = np.asarray(x)
    assert x.dtype == np.complex128 and np.isfinite(x).all()
    xs = spla.splu(A.tocsc().astype(np.complex128)).solve(b)
    return {"berr": _berr(A, x, b), "relerr": _relerr(x, xtrue),
            "vs_splu": _relerr(x, xs)}


def _holds(scores):
    return (scores["berr"] <= BERR_MAX and scores["relerr"] <= ERR_MAX
            and scores["vs_splu"] <= ERR_MAX)


@pytest.fixture(autouse=True)
def _pair_on(monkeypatch):
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")


@pytest.fixture
def force_coop(monkeypatch):
    monkeypatch.setenv("SLU_COOP_MB", "16")


@pytest.fixture(scope="module")
def grid():
    return make_solver_mesh(2, 2, 1, devices=jax.devices()[:4])


class Case:
    """One factorization on the grid and what the tests ask of it."""

    def __init__(self, n, grid, coop):
        mp = pytest.MonkeyPatch()
        mp.setenv("SLU_COMPLEX_PAIR", "1")
        if coop:
            mp.setenv("SLU_COOP_MB", "16")
        try:
            self.A = _ex11(n)
            self.a = csr_from_scipy(self.A)
            self.plan = plan_factorization(self.a, OPTS)
            self.sched = batched.get_schedule(self.plan, 4)
            self.stats = Stats()
            self.lu = factorize(self.a, OPTS, plan=self.plan, grid=grid,
                                stats=self.stats)
            self.record = obs.HEALTH.snapshot()["last_factor"]
        finally:
            mp.undo()


@pytest.fixture(scope="module", params=[False, True],
                ids=["sliced", "coop"])
def case(request, grid):
    return Case(12, grid, request.param)


# -- the rule ---------------------------------------------------------

class _Dev:
    def __init__(self, platform):
        self.platform = platform


class _Mesh:
    def __init__(self, *platforms):
        self.devices = np.array([_Dev(p) for p in platforms])


@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
def test_a_tpu_mesh_takes_the_pair_lowering(dtype, monkeypatch):
    """THE rule judges a mesh by its own devices: pair on a TPU mesh
    whatever the default backend is, native on a CPU mesh, the two
    hooks as on one device; a real dtype is native everywhere."""
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "0")
    monkeypatch.setenv("SLU_COMPLEX_TPU", "0")
    tpu, cpu = _Mesh("tpu", "tpu"), _Mesh("cpu", "cpu")
    assert jax.default_backend() == "cpu"
    assert plat.complex_lowering(dtype, tpu) == "pair"
    assert plat.complex_lowering(dtype, cpu) == "native"
    assert plat.complex_lowering(np.float32, tpu) == "native"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert plat.complex_lowering(dtype) == "pair"
    assert plat.complex_lowering(dtype, cpu) == "native"
    assert not plat.complex_needs_cpu(dtype)
    monkeypatch.setenv("SLU_COMPLEX_TPU", "1")
    assert plat.complex_lowering(dtype, tpu) == "native"
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    assert plat.complex_lowering(dtype, cpu) == "pair"
    assert not hasattr(plat, "complex_mesh_blocked")


def test_the_gate_records_the_meshs_lowering(monkeypatch):
    """`complex_device_gate(mesh=)` writes the mesh's lowering on the
    Stats and places nothing on the host CPU."""
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "0")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mesh, want in ((_Mesh("tpu"), "pair"), (_Mesh("cpu"), "native")):
        st = Stats()
        with plat.complex_device_gate(np.complex64, stats=st,
                                      phase="FACT", mesh=mesh) as on:
            assert on is False
        assert st.complex_lowering == {"FACT": want}
        assert st.placement == {}


def test_what_has_no_pair_storage_refuses_on_a_tpu_mesh(grid,
                                                        monkeypatch):
    """The fused mesh solver and the legacy replicated cooperative LU
    raise where the rule gives the mesh the pair lowering, before a
    native complex program reaches the TPU's compiler."""
    a = csr_from_scipy(_ex11(8))
    plan = plan_factorization(a, OPTS)
    with pytest.raises(NotImplementedError, match="no pair storage"):
        batched.make_fused_solver(plan, dtype="complex64",
                                  mesh=grid.mesh)
    monkeypatch.setenv("SLU_COOP_MB", "16")
    monkeypatch.setenv("SLU_COOP_SHARDED", "0")
    with pytest.raises(NotImplementedError, match="no pair arithmetic"):
        factorize(a, OPTS, grid=grid)


# -- factor and refined solve ----------------------------------------

def test_the_handle_holds_sharded_planes(case, grid):
    d = case.lu.device_lu
    assert isinstance(d, factor_dist.DistLU) and batched._lu_is_pair(d)
    per = {"L_flat": case.sched.L_total, "U_flat": case.sched.U_total,
           "Li_flat": case.sched.Li_total,
           "Ui_flat": case.sched.Ui_total}
    for name, total in per.items():
        flat = getattr(d, name)
        assert flat.dtype == jnp.float32
        assert flat.shape == (2, 4 * total)
        # the element axis is sharded over the grid, planes together
        shards = flat.addressable_shards
        assert len(shards) == 4
        assert {s.data.shape for s in shards} == {(2, total)}


def test_stats_and_the_ring_say_pair_on_the_mesh(case):
    assert case.stats.complex_lowering == {"FACT": "pair"}
    assert case.stats.placement == {}
    assert case.record["complex_lowering"] == "pair"
    st = Stats()
    rng = np.random.default_rng(5)
    _, b = _system(case.A, rng)
    solve(case.lu, b, stats=st)
    assert st.complex_lowering == {"SOLVE": "pair"}
    assert st.placement == {}
    last = obs.HEALTH.snapshot()["recent_solves"][-1]
    assert last["complex_lowering"] == "pair"


def test_last_factor_carries_the_mesh(case):
    """devices, cooperative groups and the schedule's predicted
    collective bytes at the planes' width (complex64: 8 bytes an
    entry, as two float32 planes)."""
    coop = sum(1 for g in case.sched.groups if g.coop)
    assert case.record["devices"] == 4
    assert case.record["coop_groups"] == coop
    assert case.record["comm_bytes"] == case.sched.comm_summary(
        np.dtype(np.complex64))
    assert case.stats.dispatch["devices"] == 4
    assert "dispatch" not in case.record     # the one-device routes' key


def test_coop_engages_when_forced(case, request):
    coop = [g for g in case.sched.groups if g.coop]
    if "coop" in request.node.callspec.id:
        assert coop and all(g.cp > 0 for g in coop)
        assert case.record["comm_bytes"]["coop_psum_bytes"] > 0
    else:
        assert not coop


@pytest.mark.parametrize("seed", [0, 1])
def test_refined_solve_holds_the_cells_limits(case, seed):
    rng = np.random.default_rng(seed)
    xtrue, b = _system(case.A, rng)
    st = Stats()
    x = solve(case.lu, b, stats=st)
    scores = _judge(case.A, x, b, xtrue)
    assert _holds(scores), scores
    assert st.refine_steps >= 1 and st.berr <= BERR_MAX


@pytest.mark.parametrize("control", [
    {"refine_dtype": "complex64"}, {"iter_refine": IterRefine.NOREFINE}],
    ids=["refine_complex64", "no_refine"])
def test_the_controls_fail_the_limits(case, grid, control):
    """complex64 refinement and no refinement leave the answer at
    float32's accuracy: the limits above are tight enough to tell."""
    rng = np.random.default_rng(2)
    xtrue, b = _system(case.A, rng)
    lu = factorize(case.a, OPTS.replace(**control), plan=case.plan,
                   grid=grid)
    x = np.asarray(solve(lu, b)).astype(np.complex128)
    scores = _judge(case.A, x, b, xtrue)
    assert scores["berr"] > BERR_MAX and scores["relerr"] > ERR_MAX


@pytest.mark.parametrize("trans", [Trans.TRANS, Trans.CONJ],
                         ids=["trans", "conj"])
def test_solve_trans_on_the_mesh(case, grid, trans):
    rng = np.random.default_rng(3)
    At = case.A.T.tocsr() if trans == Trans.TRANS \
        else case.A.conj().T.tocsr()
    xtrue, b = _system(At, rng)
    lu = factorize(case.a, OPTS.replace(trans=trans), plan=case.plan,
                   grid=grid)
    scores = _judge(At, solve(lu, b), b, xtrue)
    assert _holds(scores), scores


def test_a_second_value_set_on_the_held_plan(case, grid):
    """SamePattern_SameRowPerm: new values refactor through the
    programs the first set compiled."""
    rng = np.random.default_rng(4)
    B = _rescaled(case.A, rng)
    xtrue, b = _system(B, rng)
    factor = factor_dist.dist_factor_fn(case.plan, grid.mesh,
                                        np.dtype(np.complex64))
    before = factor.jitted._cache_size()
    lu = factorize(csr_from_scipy(B), OPTS, plan=case.plan, grid=grid)
    assert factor.jitted._cache_size() == before
    scores = _judge(B, solve(lu, b), b, xtrue)
    assert _holds(scores), scores


@pytest.mark.parametrize("arm, env", [
    ("rhs_sharded", None), ("merged", None), ("merged", "legacy")])
def test_every_solve_arm_takes_planes(case, arm, env, monkeypatch):
    """Eight right-hand sides pick the rhs-sharded sweep (each
    device's column block encoded by itself); two columns the
    row-partitioned merged sweep, with no variable set and under
    SLU_TRISOLVE=legacy alike: that variable selects the one-device
    sweep and does not reach a mesh."""
    nrhs = 8 if arm == "rhs_sharded" else 2
    monkeypatch.delenv("SLU_TRISOLVE", raising=False)
    if env:
        monkeypatch.setenv("SLU_TRISOLVE", env)
    case.plan._dist_solve_fns = {}
    rng = np.random.default_rng(6)
    xtrue, b = _system(case.A, rng, nrhs=nrhs)
    x = np.asarray(solve(case.lu, b))
    assert x.shape == (case.a.n, nrhs)
    for j in range(nrhs):
        assert _berr(case.A, x[:, j], b[:, j]) <= BERR_MAX
    assert _relerr(x, xtrue) <= ERR_MAX
    built = {k[4:] for k in case.plan._dist_solve_fns}
    assert built == {{"rhs_sharded": (True, False, True),
                      "merged": (False, True, True)}[arm]}


def test_gssvx_on_the_grid(grid, force_coop):
    A = _ex11(16)
    rng = np.random.default_rng(7)
    xtrue, b = _system(A, rng)
    x, lu, st = gssvx(OPTS, csr_from_scipy(A), b, grid=grid)
    assert lu.backend == "dist" and batched._lu_is_pair(lu.device_lu)
    assert st.complex_lowering == {"FACT": "pair", "SOLVE": "pair"}
    assert _holds(_judge(A, x, b, xtrue))


def test_the_fused_dist_step_in_pair_storage(grid, force_coop):
    """`make_dist_step` (factor and sweeps in one program) encodes and
    decodes on the host too; unrefined, so held to float32."""
    A = _ex11(8)
    a = csr_from_scipy(A)
    plan = plan_factorization(a, OPTS)
    rng = np.random.default_rng(8)
    xtrue, b = _system(A, rng, nrhs=2)
    step, _ = factor_dist.make_dist_step(plan, grid.mesh,
                                         dtype=np.complex64)
    bf = (b * plan.row_scale[:, None])[np.argsort(plan.final_row)]
    y = step(plan.scaled_values(a), bf)
    x = y[plan.final_col] * plan.col_scale[:, None]
    assert np.iscomplexobj(x) and _relerr(x, xtrue) < 1e-4


# -- against the one-device pair path ---------------------------------

def test_the_planes_equal_the_one_device_result(case):
    """diag(U) through both storages' layouts, and an unrefined sweep:
    equal to float32 rounding."""
    lu1 = factorize(case.a, OPTS, plan=case.plan)
    assert batched._lu_is_pair(lu1.device_lu)
    d4, d1 = get_diag_u(case.lu), get_diag_u(lu1)
    assert np.max(np.abs(d4 - d1) / np.abs(d1)) < PLANES_TOL
    rng = np.random.default_rng(9)
    _, b = _system(case.A, rng)
    raw = OPTS.replace(iter_refine=IterRefine.NOREFINE)
    x4 = solve(factorize(case.a, raw, plan=case.plan,
                         grid=make_solver_mesh(
                             2, 2, 1, devices=jax.devices()[:4])), b)
    x1 = solve(factorize(case.a, raw, plan=case.plan), b)
    assert _relerr(np.asarray(x4), np.asarray(x1)) < PLANES_TOL


# -- the programs -----------------------------------------------------

def _lowered(case, grid, debug=False):
    d = case.lu.device_lu
    factor = factor_dist.dist_factor_fn(case.plan, grid.mesh, d.dtype)
    nd, lsel = factor.sel.shape
    ftxt = factor.jitted.lower(
        jnp.zeros((nd, 2, lsel), jnp.float32)).as_text(debug_info=debug)
    flats = (d.L_flat, d.U_flat, d.Li_flat, d.Ui_flat)
    # the handle's narrow sweep, and the replicated-X sweep the fused
    # mesh step shares (`make_dist_solve`)
    b = jnp.zeros((case.a.n, 2), jnp.float32)
    stxt = [fn.lower(*flats, b).as_text()
            for trans in (False, True)
            for fn in (factor_dist._solve_fn(d, trans, "merged"),
                       factor_dist.make_dist_solve(
                           case.plan, grid.mesh, dtype=d.dtype,
                           axis=d.axis, trans=trans, pair=True))]
    return ftxt, stxt


def test_no_complex_operation_in_the_lowered_mesh_programs(case, grid):
    ftxt, stxt = _lowered(case, grid)
    for txt in [ftxt] + stxt:
        assert "complex<" not in txt
        assert "c64" not in txt and "c128" not in txt
    # and they are mesh programs: the collectives are in the text
    assert "all_gather" in ftxt or "all-gather" in ftxt
    assert all("all_reduce" in t or "all-reduce" in t for t in stxt)


def test_the_mesh_bodies_carry_the_scopes(case, grid, request):
    """The one-chip kernels' scopes, `slu.dist.gather` on the slab's
    gather, and `slu.coop.psum` in the cooperative chain."""
    ftxt, _ = _lowered(case, grid, debug=True)
    want = ["slu.assemble", "slu.extend_add", "slu.partial_lu",
            "slu.tri_inverse", "slu.schur", "slu.store",
            "slu.dist.gather"]
    if "coop" in request.node.callspec.id:
        want.append("slu.coop.psum")
    for scope in want:
        assert scope in ftxt, scope


def test_the_real_mesh_path_carries_the_collective_scopes(grid,
                                                          monkeypatch):
    """The same scope names on a real system's mesh programs, legacy
    replicated chain included (`slu.coop.gather` is its recombination
    gather; the sharded chain has none)."""
    from superlu_dist_tpu.utils.testmat import laplacian_2d
    a = laplacian_2d(12)
    monkeypatch.setenv("SLU_COOP_MB", "16")
    for sharded, want in (("1", ("slu.dist.gather", "slu.coop.psum")),
                          ("0", ("slu.coop.psum", "slu.coop.gather"))):
        monkeypatch.setenv("SLU_COOP_SHARDED", sharded)
        plan = plan_factorization(a, Options())
        factor = factor_dist.make_dist_factor(plan, grid.mesh)
        txt = factor.jitted.lower(jnp.zeros(
            factor.sel.shape, jnp.float64)).as_text(debug_info=True)
        for scope in want:
            assert scope in txt, (sharded, scope)


def test_the_codec_spans_at_the_dist_entry_points(case, monkeypatch):
    """`pair.encode` around the values' planes at a factorization and
    around each sweep's right-hand side, `pair.decode` around each
    sweep's answer (`slu.pair.encode` / `slu.pair.decode` in a
    profiler's trace)."""
    seen = []
    real_span = obs.span

    def spying(name, **kw):
        seen.append(name)
        return real_span(name, **kw)

    monkeypatch.setattr(factor_dist.obs, "span", spying)
    lu = factorize(case.a, OPTS, plan=case.plan,
                   grid=make_solver_mesh(2, 2, 1,
                                         devices=jax.devices()[:4]))
    assert seen.count("pair.encode") == 1
    assert "pair.decode" not in seen
    del seen[:]
    st = Stats()
    rng = np.random.default_rng(10)
    solve(lu, _system(case.A, rng)[1], stats=st)
    sweeps = sum(st.sweeps.values())
    assert sweeps >= 2
    assert seen.count("pair.encode") == sweeps
    assert seen.count("pair.decode") == sweeps
    mine = [n for n in seen if n.startswith(("pair.", "solve.fetch"))]
    assert mine[:3] == ["pair.encode", "solve.fetch", "pair.decode"]


def test_measure_comm_lowers_the_pair_programs(case):
    """The measured collective inventory reads a pair handle: the
    factor's gathers or psums, the sweep's all-reduces."""
    out = factor_dist.measure_comm(case.lu.device_lu)
    assert out["MESH"]["n_devices"] == 4
    assert out["SOLVE"]["all-reduce"]["count"] > 0
    fact = out["FACT"]
    assert sum(v.get("count", 0) for v in fact.values()) > 0
