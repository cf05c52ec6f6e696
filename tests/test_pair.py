"""Real-pair complex lowering (ops/pair_lu +
batched._factor_group_impl_pair): the complex factor/solve compiled as
an ALL-REAL program — the lowering detour for the TPU, whose
compiler aborts on native complex128 (utils/platform.py gate;
tools/complex_probe.py).  Oracle: the native
complex kernels (same math, complex storage) and scipy splu — the
pzgstrf/pzgstrs parity contract (SRC/pzgstrf2.c, SRC/pzgstrs.c)
reached through representation change instead of dtype twins.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from superlu_dist_tpu import Options, gssvx, get_diag_u, query_space
from superlu_dist_tpu.options import Trans
from superlu_dist_tpu.ops import dense_lu, pair_lu
from superlu_dist_tpu.utils.testmat import helmholtz_2d, manufactured_rhs


@pytest.fixture(autouse=True)
def _pair_on(monkeypatch):
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")


@pytest.fixture(scope="module")
def problem():
    a = helmholtz_2d(10)
    xtrue, b = manufactured_rhs(a)
    return a, xtrue, b


def _rand_fronts(rng, N, mb):
    F = (rng.standard_normal((N, mb, mb))
         + 1j * rng.standard_normal((N, mb, mb))).astype(np.complex128)
    F += np.eye(mb) * mb
    return F


@pytest.mark.parametrize("mb,wb", [(8, 8), (48, 32), (96, 64)])
def test_partial_lu_pair_matches_complex_oracle(mb, wb):
    rng = np.random.default_rng(0)
    F = _rand_fronts(rng, 3, mb)
    Fc, tc, zc = dense_lu.partial_lu_batch(
        jnp.asarray(F), jnp.asarray(0.0), wb=wb)
    Fp, tp, zp = pair_lu.partial_lu_pair_batch(
        pair_lu.encode(jnp.asarray(F)), jnp.asarray(0.0), wb=wb)
    Fpd = np.asarray(pair_lu.decode(Fp))
    scale = np.max(np.abs(np.asarray(Fc)))
    assert np.max(np.abs(np.asarray(Fc) - Fpd)) / scale < 1e-13
    assert int(tc) == int(tp) and int(zc) == int(zp)


def test_tri_inverse_pair_matches_complex_oracle():
    rng = np.random.default_rng(1)
    w = 64
    L = np.tril(rng.standard_normal((2, w, w))
                + 1j * rng.standard_normal((2, w, w)), -1) + np.eye(w)
    Li_c = np.asarray(dense_lu.unit_lower_inverse(jnp.asarray(L)))
    Li_p = np.asarray(pair_lu.decode(
        pair_lu.unit_lower_inverse_pair(pair_lu.encode(jnp.asarray(L)))))
    assert np.max(np.abs(Li_c - Li_p)) / np.max(np.abs(Li_c)) < 1e-12
    U = np.triu(rng.standard_normal((2, w, w))
                + 1j * rng.standard_normal((2, w, w)), 1) + 3 * np.eye(w)
    Ui_c = np.asarray(dense_lu.upper_inverse(jnp.asarray(U)))
    Ui_p = np.asarray(pair_lu.decode(
        pair_lu.upper_inverse_pair(pair_lu.encode(jnp.asarray(U)))))
    assert np.max(np.abs(Ui_c - Ui_p)) / np.max(np.abs(Ui_c)) < 1e-12


def test_tiny_and_zero_pivot_parity():
    """GESP tiny-pivot replacement (complex unit direction) and the
    exact-zero count match the native complex kernel bit-for-bit."""
    F = np.zeros((1, 4, 4), np.complex128)
    F[0] = np.eye(4)
    F[0, 2, 2] = 1e-20 + 1e-21j
    Fc, tc, _ = dense_lu.partial_lu_batch(
        jnp.asarray(F), jnp.asarray(1e-10), wb=4, nb=4)
    Fp, tp, _ = pair_lu.partial_lu_pair_batch(
        pair_lu.encode(jnp.asarray(F)), jnp.asarray(1e-10), wb=4, nb=4)
    assert int(tc) == int(tp) == 1
    np.testing.assert_allclose(
        np.asarray(pair_lu.decode(Fp))[0, 2, 2],
        np.asarray(Fc)[0, 2, 2], rtol=0, atol=0)
    Fz = np.eye(4, dtype=np.complex128)[None].copy()
    Fz[0, 1, 1] = 0
    _, _, zc = dense_lu.partial_lu_batch(
        jnp.asarray(Fz), jnp.asarray(0.0), wb=4, nb=4)
    _, _, zp = pair_lu.partial_lu_pair_batch(
        pair_lu.encode(jnp.asarray(Fz)), jnp.asarray(0.0), wb=4, nb=4)
    assert int(zc) == int(zp) == 1


def _relres(a, x, b):
    return np.linalg.norm(a.to_scipy() @ x - b) / np.linalg.norm(b)


def test_gssvx_pair_end_to_end(problem):
    """The c128 user path with pair storage: accuracy matches the
    native-complex path's contract, the handle really holds planes,
    and accounting (diag U, space query) reads them correctly."""
    from superlu_dist_tpu.ops.batched import _lu_is_pair
    a, xtrue, b = problem
    opts = Options(factor_dtype="complex128", refine_dtype="complex128")
    x, lu, stats = gssvx(opts, a, b, backend="jax")
    assert _lu_is_pair(lu.device_lu)
    assert np.asarray(x).dtype == np.complex128
    assert _relres(a, np.asarray(x), b) < 1e-12
    np.testing.assert_allclose(np.asarray(x), xtrue, rtol=1e-8)
    # diag U parity with the host oracle
    xh, luh, _ = gssvx(opts, a, b, backend="host")
    np.testing.assert_allclose(get_diag_u(lu), get_diag_u(luh),
                               rtol=1e-10)
    q = query_space(lu)
    # (2, N) real planes hold the same bytes as N complex entries
    assert q["held_bytes"] >= q["lu_bytes"]


@pytest.mark.parametrize("trans", [Trans.TRANS, Trans.CONJ])
def test_gssvx_pair_trans_conj(problem, trans):
    a, xtrue, b = problem
    asp = a.to_scipy()
    bt = (asp.T @ xtrue if trans == Trans.TRANS
          else asp.conj().T @ xtrue)
    opts = Options(factor_dtype="complex128",
                   refine_dtype="complex128", trans=trans)
    x, _, _ = gssvx(opts, a, bt, backend="jax")
    np.testing.assert_allclose(np.asarray(x), xtrue, rtol=1e-8)


def test_gssvx_pair_staged(problem, monkeypatch):
    monkeypatch.setenv("SLU_STAGED", "1")
    from superlu_dist_tpu.ops.batched import _lu_is_pair
    a, xtrue, b = problem
    opts = Options(factor_dtype="complex128", refine_dtype="complex128")
    x, lu, _ = gssvx(opts, a, b, backend="jax")
    assert _lu_is_pair(lu.device_lu)
    np.testing.assert_allclose(np.asarray(x), xtrue, rtol=1e-8)
    xh, luh, _ = gssvx(opts, a, b, backend="host")
    np.testing.assert_allclose(get_diag_u(lu), get_diag_u(luh),
                               rtol=1e-10)


def test_gssvx_pair_c64_mixed_precision(problem):
    """c64 pair factor + c128 refinement reaches c128 accuracy — the
    complex psgssvx_d2 strategy through plane storage (f32 planes on
    the MXU, the TPU production mode for complex)."""
    a, xtrue, b = problem
    opts = Options(factor_dtype="complex64", refine_dtype="complex128")
    x, lu, stats = gssvx(opts, a, b, backend="jax")
    from superlu_dist_tpu.ops.batched import _lu_is_pair
    assert _lu_is_pair(lu.device_lu)
    assert _relres(a, np.asarray(x), b) < 1e-12
    assert stats.refine_steps >= 1


def test_pair_multi_rhs(problem):
    a, xtrue, b = problem
    rng = np.random.default_rng(7)
    X = (rng.standard_normal((a.n, 5))
         + 1j * rng.standard_normal((a.n, 5)))
    B = a.to_scipy() @ X
    opts = Options(factor_dtype="complex128", refine_dtype="complex128")
    x, _, _ = gssvx(opts, a, B, backend="jax")
    np.testing.assert_allclose(np.asarray(x), X, rtol=1e-8)


def test_pair_singular_raises(problem):
    """An exactly-zero pivot with replacement disabled raises the
    info>0 singularity analog through the pair path too."""
    import scipy.sparse as sp
    from superlu_dist_tpu import csr_from_scipy
    from superlu_dist_tpu.options import RowPerm
    n = 12
    d = np.ones(n, np.complex128)
    d[7] = 0.0
    # store the zero pivot EXPLICITLY (diags().tocsr() drops it, and a
    # pattern-empty row/column is now refused typed at plan time —
    # this test's teeth are the pair FACTOR path's zero division)
    idx = np.arange(n)
    A = sp.csr_matrix((d, (idx, idx)), shape=(n, n))
    a = csr_from_scipy(A)
    opts = Options(factor_dtype="complex128", replace_tiny_pivot=False,
                   equil=False, row_perm=RowPerm.NOROWPERM)
    with pytest.raises(ZeroDivisionError):
        gssvx(opts, a, np.ones(n, np.complex128), backend="jax")


def test_pair_gate_interaction(monkeypatch):
    """SLU_COMPLEX_PAIR=1 is the tests' hook: it forces the pair
    lowering on a backend that would run native (this one), and pair
    programs are all-real, so they are never CPU-gated
    (utils/platform.complex_lowering, complex_needs_cpu).  On a TPU
    it decides nothing: pair is what runs there with it unset or 0."""
    from superlu_dist_tpu.utils import platform as plat
    monkeypatch.setenv("SLU_COMPLEX_TPU", "0")
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    assert plat.complex_lowering(np.complex64) == "pair"
    # pair enabled → never CPU-gated, whatever the backend
    assert plat.complex_needs_cpu(np.complex128) is False
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "0")
    assert plat.complex_lowering(np.complex128) == "native"
    monkeypatch.setattr("jax.default_backend", lambda: "tpu")
    assert plat.complex_lowering(np.complex128) == "pair"
    assert plat.complex_needs_cpu(np.complex128) is False
    # real dtypes are never pair, never gated
    assert plat.complex_lowering(np.float64) == "native"
    assert plat.complex_needs_cpu(np.float64) is False


def test_fused_solver_pair(problem):
    """The whole fused pipeline (scale + assemble + factor + sweeps +
    SpMV residual + berr + while_loop refinement) in pair mode: c128
    to full accuracy, c64 factor + c128 refinement to the
    mixed-precision contract, and the jitted core complex-free."""
    import jax.numpy as jnp
    from superlu_dist_tpu.ops.batched import make_fused_solver
    from superlu_dist_tpu.plan.plan import plan_factorization
    a, xtrue, b = problem
    plan = plan_factorization(a, Options(factor_dtype="complex128",
                                         refine_dtype="complex128"))
    step = make_fused_solver(plan, dtype="complex128")
    x, berr, steps, tiny, nzero = step(a.data, b[:, None])
    assert np.asarray(x).dtype == np.complex128
    np.testing.assert_allclose(np.asarray(x)[:, 0], xtrue, rtol=1e-8)
    assert float(berr) < 1e-14
    # encoded-operand core compiles with NO complex HLO at all
    nnz = len(plan.coo_rows)
    txt = step._core.lower(
        jnp.zeros((2, nnz), jnp.float64),
        jnp.zeros((plan.n, 2), jnp.float64)).as_text()
    assert "c128" not in txt and "c64" not in txt
    # mixed precision: c64 planes on the factor, c128 accumulator
    plan2 = plan_factorization(a, Options(factor_dtype="complex64",
                                          refine_dtype="complex128"))
    step2 = make_fused_solver(plan2, dtype="complex64")
    x2, _, st2, _, _ = step2(a.data, b[:, None])
    np.testing.assert_allclose(np.asarray(x2)[:, 0], xtrue, rtol=1e-8)
    assert int(st2) >= 1
    # staged variant, same contract
    step3 = make_fused_solver(plan, dtype="complex128", staged=True)
    x3, _, _, _, _ = step3(a.data, b[:, None])
    np.testing.assert_allclose(np.asarray(x3)[:, 0], xtrue, rtol=1e-8)


def test_pair_handle_survives_env_change(problem, monkeypatch):
    """A factorization handle outlives the env var that selected its
    storage: solve derives pair-ness from the flats themselves
    (_lu_is_pair → _phase_fns pair=), so the FACTORED-reuse pattern
    keeps working after SLU_COMPLEX_PAIR flips either way."""
    from superlu_dist_tpu import Fact, factorize, solve
    a, xtrue, b = problem
    opts = Options(factor_dtype="complex128", refine_dtype="complex128")
    lu_pair = factorize(a, opts, backend="jax")       # pair storage
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "0")
    lu_native = factorize(a, opts, backend="jax")     # native storage
    x = solve(lu_pair, b)                             # env now says 0
    np.testing.assert_allclose(np.asarray(x), xtrue, rtol=1e-8)
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    x2 = solve(lu_native, b)                          # env now says 1
    np.testing.assert_allclose(np.asarray(x2), xtrue, rtol=1e-8)


def test_fused_gate_ignores_pair(monkeypatch):
    """A caller without pair storage (pair_capable=False) is gated on
    a TPU whatever the rule or the hook say: else the lift would
    route a native-complex program into the measured TPU compile
    abort.  The gate then engages, warns and records the placement."""
    from superlu_dist_tpu import Stats
    from superlu_dist_tpu.utils import platform as plat
    monkeypatch.setenv("SLU_COMPLEX_TPU", "0")
    monkeypatch.setattr(
        "jax.default_backend", lambda: "tpu")
    for hook in ("1", "0"):
        monkeypatch.setenv("SLU_COMPLEX_PAIR", hook)
        assert plat.complex_needs_cpu(np.complex128) is False
        assert plat.complex_needs_cpu(np.complex128,
                                      pair_capable=False) is True
    st = Stats()
    with pytest.warns(plat.ComplexPlacementWarning):
        with plat.complex_device_gate(np.complex128, pair_capable=False,
                                      stats=st, phase="FACT") as on:
            assert on is True
    assert st.placement == {"FACT": "cpu"}
    assert st.complex_lowering == {"FACT": "cpu"}


def test_pair_program_is_complex_free(problem):
    """The certification property: the compiled pair factor program
    contains no complex-typed HLO at all (on the gated platform any
    complex op would reintroduce the wedge)."""
    from superlu_dist_tpu.ops import batched
    from superlu_dist_tpu.plan.plan import plan_factorization
    a, _, _ = problem
    opts = Options(factor_dtype="complex128")
    plan = plan_factorization(a, opts)
    sched = batched.get_schedule(plan, 1)
    cdt = np.dtype(np.complex128)
    factor_fn, solve_fn = batched._phase_fns(
        sched, cdt, batched._thresh_for(plan, cdt))
    vals = batched._pair_encode_vals(plan.scaled_values(a), np.complex128)
    txt = factor_fn.lower(jnp.asarray(vals)).as_text()
    assert "c128" not in txt and "c64" not in txt
    # solve program too: pre-encoded rhs in, encoded solution out
    flats = tuple(jnp.zeros((2, t), jnp.float64)
                  for t in (sched.L_total, sched.U_total,
                            sched.Li_total, sched.Ui_total))
    bb = np.zeros((plan.n, 2), np.float64)
    txt2 = solve_fn.lower(*flats, jnp.asarray(bb),
                          trans=False).as_text()
    assert "c128" not in txt2 and "c64" not in txt2
