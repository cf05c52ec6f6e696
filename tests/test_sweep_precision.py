"""The sweeps' operand is in the factor's precision (ISSUE 31, S4
piece 1; psgsrfs_d2's scheme, SRC/psgsrfs_d2.c:229): `solve` casts
x0's right-hand side and every refinement correction's residual to the
factor dtype after `to_factor_rhs`, and keeps residual, berr and
x += d in the refine dtype against the caller's UNROUNDED b.

  (a) f32 factors, f64 b: the program a sweep dispatches holds no f64,
      and the answer meets berr <= 64 eps(f64) against the unrounded b
      (host / jax / dist, NOTRANS / TRANS, nrhs 1 and 8);
  (b) the trap: a b that f32 rounding moves by more than 1e-9 still
      comes back to relerr < 1e-9;
  (c) refinement takes at most one pass more than with the old
      (promoted, f64) operand, kept here as a test helper only;
  (d) the host loop and make_fused_solver take the operand dtype from
      the one helper, and agree;
  (e) SolveService, ladder (1, 8): f64 requests, no compile after
      warm-up, answers at the f64 class;
  (f) the counter: sweeps by operand dtype in Stats and the health
      ring.
"""

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import superlu_dist_tpu as slu
from superlu_dist_tpu import Options, obs
from superlu_dist_tpu.models.gssvx import solve_rhs_dtype
from superlu_dist_tpu.ops import batched, ref_multifrontal, trisolve
from superlu_dist_tpu.options import Trans, YesNo
from superlu_dist_tpu.parallel import factor_dist
from superlu_dist_tpu.precision import policy as pp
from superlu_dist_tpu.sparse import csr_from_scipy
from superlu_dist_tpu.utils.testmat import helmholtz_2d, laplacian_3d
from test_precision_policy import _illcond
from tools.slulint.contracts import has_f64

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EPS = float(np.finfo(np.float64).eps)
F32 = Options(factor_dtype="float32", refine_dtype="float64")


def _gen(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "configs", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _berr(asp, x, b):
    """Componentwise backward error in float64, nothing of the
    program (benchmark/reference.py's formula)."""
    r = b - asp @ x
    den = abs(asp) @ np.abs(x) + np.abs(b)
    return float(np.max(np.abs(r) / np.where(den == 0, 1.0, den)))


def _old_operand(monkeypatch):
    """The operand rule before ISSUE 31 (promote the factor dtype with
    the right-hand side's): a test helper, not a path of the
    program."""
    monkeypatch.setattr(pp, "sweep_operand_dtype",
                        lambda f, o: np.promote_types(np.dtype(f), o))


# -- (a) no f64 in the dispatched program ------------------------------

def _spy(monkeypatch, module, name, seen):
    orig = getattr(module, name)

    def spy(*args, **kw):
        seen.append((name, args))
        return orig(*args, **kw)

    monkeypatch.setattr(module, name, spy)


@pytest.mark.parametrize("nrhs", [1, 8])
@pytest.mark.parametrize("trans", [Trans.NOTRANS, Trans.TRANS],
                         ids=["notrans", "trans"])
@pytest.mark.parametrize("backend", ["host", "jax", "dist"])
def test_f32_factors_f64_rhs_sweep_program_has_no_f64(
        monkeypatch, backend, trans, nrhs):
    a = laplacian_3d(5)
    asp = a.to_scipy()
    if trans == Trans.TRANS:
        # make it unsymmetric so TRANS is a different system
        asp = (asp + sp.diags([0.3], [1], shape=asp.shape)).tocsr()
        a = csr_from_scipy(asp)
    rng = np.random.default_rng(11)
    b = rng.standard_normal((a.n, nrhs))
    grid = slu.make_solver_mesh(2, 2, 1) if backend == "dist" else None
    lu = slu.factorize(a, F32.replace(trans=trans), backend=backend,
                       grid=grid)
    seen = []
    _spy(monkeypatch, trisolve, "solve_packed", seen)
    _spy(monkeypatch, factor_dist, "dist_solve", seen)
    _spy(monkeypatch, ref_multifrontal, "solve_host", seen)
    _spy(monkeypatch, ref_multifrontal, "solve_host_trans", seen)
    st = slu.Stats()
    x = slu.solve(lu, b, stats=st)
    assert x.dtype == np.float64
    sys_ = asp.T if trans == Trans.TRANS else asp
    assert _berr(sys_.tocsr(), x, b) <= 64 * EPS
    assert len(seen) == 1 + st.refine_steps and st.refine_steps >= 1
    for name, args in seen:
        operand = args[1]
        assert operand.dtype == np.float32, (name, operand.dtype)
    name, args = seen[-1]
    if backend == "jax":
        d = lu.device_lu
        fn = trisolve._solve_packed_fn(d.schedule, d.dtype, False)[
            1 if trans == Trans.TRANS else 0]
        txt = fn.lower(trisolve.get_packs(d),
                       jnp.asarray(args[1])).as_text()
        assert not has_f64(txt)
    elif backend == "dist":
        dlu = lu.device_lu
        fns = [f for k, f in lu.plan._dist_solve_fns.items()
               if k[3] == (trans == Trans.TRANS)]
        assert len(fns) == 1
        fn = getattr(fns[0], "jitted", fns[0])
        txt = fn.lower(dlu.L_flat, dlu.U_flat, dlu.Li_flat,
                       dlu.Ui_flat, args[1]).as_text()
        assert not has_f64(txt)


def test_old_operand_program_does_hold_f64(monkeypatch):
    """The pin above has teeth: under the old rule the same solve
    lowers a program with f64 in it."""
    _old_operand(monkeypatch)
    a = laplacian_3d(5)
    lu = slu.factorize(a, F32, backend="jax")
    seen = []
    _spy(monkeypatch, trisolve, "solve_packed", seen)
    slu.solve(lu, np.ones(a.n))
    d = lu.device_lu
    fn = trisolve._solve_packed_fn(d.schedule, d.dtype, False)[0]
    txt = fn.lower(trisolve.get_packs(d),
                   jnp.asarray(seen[-1][1][1])).as_text()
    assert seen[-1][1][1].dtype == np.float64 and has_f64(txt)


# -- (b) the rounded-b trap -------------------------------------------

@pytest.mark.parametrize("backend", ["host", "jax"])
def test_answer_is_to_the_unrounded_rhs(backend):
    a = laplacian_3d(5)
    asp = a.to_scipy()
    rng = np.random.default_rng(5)
    xtrue = rng.standard_normal(a.n) * (1.0 + 1e-4 / 3.0)
    b = asp @ xtrue
    moved = (np.linalg.norm(b.astype(np.float32).astype(np.float64) - b)
             / np.linalg.norm(b))
    assert moved > 1e-9            # rounding b would show
    lu = slu.factorize(a, F32, backend=backend)
    x = slu.solve(lu, b)
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-9
    assert _berr(asp, x, b) <= 64 * EPS
    # the pin that DOES round b (Options.solve_dtype) is the control:
    # its answer is to the rounded right-hand side
    lu32 = slu.factorize(a, F32.replace(solve_dtype="float32"),
                         backend=backend)
    x32 = slu.solve(lu32, b)
    assert np.linalg.norm(x32 - xtrue) / np.linalg.norm(xtrue) > 1e-9


# -- (c) passes against the old operand --------------------------------

def _matrices():
    yield "lap3d_k6", csr_from_scipy(
        _gen("gen_lap3d").generate(k=6).tocsr())
    yield "elas3d_ne3", csr_from_scipy(
        _gen("gen_elas3d").generate(ne=3).tocsr())
    yield "illcond_1e4", _illcond(spread=4, seed=3)


@pytest.mark.parametrize("which,backend", [
    ("lap3d_k6", "jax"), ("elas3d_ne3", "jax"),
    # on the host oracle: the device backend's f32 factors of this
    # dense family sit at their tiny-pivot floor (test_escalate.py's
    # note) and no operand refines them
    ("illcond_1e4", "host")])
def test_passes_no_more_than_one_above_old_operand(monkeypatch, which,
                                                   backend):
    a = dict(_matrices())[which]
    asp = a.to_scipy()
    rng = np.random.default_rng(2)
    b = asp @ rng.standard_normal(a.n)
    opts = F32.replace(escalate=YesNo.NO, max_refine_steps=16)
    lu = slu.factorize(a, opts, backend=backend)
    new = slu.Stats()
    x = slu.solve(lu, b, stats=new)
    _old_operand(monkeypatch)
    old = slu.Stats()
    x_old = slu.solve(lu, b, stats=old)
    assert set(new.sweeps) == {"float32"}
    assert set(old.sweeps) == {"float64"}
    assert 1 <= new.refine_steps <= old.refine_steps + 1, (
        new.refine_steps, old.refine_steps)
    assert new.berr <= 64 * EPS and old.berr <= 64 * EPS
    # and it ends in the same class
    assert _berr(asp, x, b) <= max(64 * EPS, 4 * _berr(asp, x_old, b))


# -- (d) one rule, two loops -------------------------------------------

@pytest.mark.parametrize("kind", ["float32", "bfloat16", "complex64",
                                  "pair"])
def test_host_loop_and_fused_solver_agree_on_operand(monkeypatch, kind):
    fdt = "complex64" if kind == "pair" else kind
    if kind == "pair":
        monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    cplx = np.dtype(fdt).kind == "c"
    a = helmholtz_2d(5) if cplx else laplacian_3d(4)
    calls = []
    orig = pp.sweep_operand_dtype

    def rule(f, o):
        calls.append((np.dtype(f), np.dtype(o), orig(f, o)))
        return calls[-1][2]

    monkeypatch.setattr(pp, "sweep_operand_dtype", rule)
    opts = Options(factor_dtype=fdt,
                   refine_dtype="float64", escalate=YesNo.NO)
    # the device loop states it when it is built
    plan = slu.plan_factorization(a, opts)
    batched.make_fused_solver(plan, dtype=np.dtype(fdt), staged=False)
    assert len(calls) == 1
    fused = calls.pop()
    # the host loop states it at every sweep
    lu = slu.factorize(a, opts, plan=plan, backend="jax")
    assert batched._lu_is_pair(lu.device_lu) == (kind == "pair")
    seen = []
    _spy(monkeypatch, batched, "solve_device", seen)
    rng = np.random.default_rng(1)
    b = rng.standard_normal(a.n) + (1j * rng.standard_normal(a.n)
                                    if cplx else 0.0)
    st = slu.Stats()
    slu.solve(lu, b, stats=st)
    assert len(calls) == len(seen) == 1 + st.refine_steps
    want = np.dtype(fdt)
    assert fused[0] == want and fused[2] == want
    for (f, _o, got), (_n, args) in zip(calls, seen):
        assert f == want and got == want
        assert args[1].dtype == want       # what the device was handed
    assert st.sweeps == {want.name: len(seen)}


@pytest.mark.parametrize("factor,operand,want", [
    ("float32", "float64", "float32"),
    ("float32", "complex128", "complex64"),
    ("bfloat16", "float64", "bfloat16"),
    ("bfloat16", "complex128", "complex64"),
    ("float64", "float32", "float64"),
    ("float64", "complex64", "complex128"),
    ("complex64", "float64", "complex64"),
    ("complex128", "complex64", "complex128"),
])
def test_sweep_operand_dtype_rule(factor, operand, want):
    assert pp.sweep_operand_dtype(factor, operand) == np.dtype(want)


def test_complex_rhs_on_real_f32_factors():
    """Realness is the system's: a complex b on real f32 factors
    sweeps in complex64 and answers in complex128."""
    a = laplacian_3d(4)
    asp = a.to_scipy()
    rng = np.random.default_rng(8)
    xtrue = rng.standard_normal(a.n) + 1j * rng.standard_normal(a.n)
    lu = slu.factorize(a, F32, backend="host")
    st = slu.Stats()
    x = slu.solve(lu, asp @ xtrue, stats=st)
    assert x.dtype == np.complex128 and set(st.sweeps) == {"complex64"}
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-12


# -- (e) the service ---------------------------------------------------

def test_service_f64_requests_f32_sweeps_no_compile_after_warmup():
    from superlu_dist_tpu.serve import (ServeConfig, SolveService,
                                        solve_jit_cache_size)
    a = laplacian_3d(5)
    asp = a.to_scipy()
    svc = SolveService(ServeConfig(backend="jax", ladder=(1, 8),
                                   max_linger_s=0.01))
    key = svc.prefactor(a, F32)
    lu = svc.cache.peek(key)
    mb = next(iter(svc._batchers.values()))
    assert mb.dtype == np.float64          # batches assemble in f64
    assert solve_rhs_dtype(lu) == np.float64
    before = solve_jit_cache_size(lu)
    misses = obs.COMPILE_WATCH.snapshot()["misses"]
    rng = np.random.default_rng(3)
    xs = rng.standard_normal((12, a.n))
    futs = [svc.submit(key, asp @ x) for x in xs]
    got = [f.result(timeout=120) for f in futs]
    one = svc.solve(key, asp @ xs[0])          # a width-1 batch too
    svc.close()
    assert solve_jit_cache_size(lu) == before
    assert obs.COMPILE_WATCH.snapshot()["misses"] == misses
    assert before == 2                        # widths 1 and 8, f32 only
    for x, xt in zip(got + [one], list(xs) + [xs[0]]):
        assert x.dtype == np.float64
        assert _berr(asp, x, asp @ xt) <= 64 * EPS
        assert np.linalg.norm(x - xt) / np.linalg.norm(xt) < 1e-9


# -- (f) the counter ---------------------------------------------------

def test_sweeps_by_dtype_in_stats_and_health_ring():
    a = laplacian_3d(4)
    lu = slu.factorize(a, F32, backend="jax")
    st = slu.Stats()
    slu.solve(lu, np.ones(a.n), stats=st)
    assert st.sweeps == {"float32": 1 + st.refine_steps}
    assert f"sweeps by operand:    float32 {1 + st.refine_steps}" \
        in st.report()
    assert st.snapshot()["sweeps"] == st.sweeps
    snap = obs.HEALTH.snapshot()
    last = snap["last_solve"]
    assert last["sweeps"] == st.sweeps and last["steps"] == st.refine_steps
    assert len(last["berr_trajectory"]) == 1 + st.refine_steps
    assert snap["recent_solves"][-1] == last
    # a second solve under the same Stats accumulates, as refine_steps
    slu.solve(lu, np.arange(a.n, dtype=np.float64), stats=st)
    assert st.sweeps == {"float32": 2 + st.refine_steps}
    # f64 factors keep f64; an unrefined solve is counted too
    lu64 = slu.factorize(a, Options(iter_refine=slu.IterRefine.NOREFINE),
                         backend="host")
    st64 = slu.Stats()
    slu.solve(lu64, np.ones(a.n), stats=st64)
    assert st64.sweeps == {"float64": 1}
