"""The one rule that says how a complex program is lowered
(utils/platform.complex_lowering), what records it (Stats, the health
ring), and the kernel scopes and the host span the pair lowering runs
under.  The backend is this host's CPU; a TPU default backend is
simulated by patching jax.default_backend, as tests/test_complex_gate
does."""

import itertools
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from superlu_dist_tpu import (Options, Stats, factorize, obs,
                              plan_factorization, solve)
from superlu_dist_tpu.utils import platform as plat
from superlu_dist_tpu.utils.testmat import helmholtz_2d, manufactured_rhs

REAL = (np.float32, np.float64)
CPLX = (np.complex64, np.complex128)
ENVS = list(itertools.product((None, "0", "1"), (None, "0", "1")))


def _expected(dtype, backend, pair_env, tpu_env):
    """The truth table, written out: realness, then the tests' hook,
    then the native override, then the backend."""
    if dtype in REAL:
        return "native"
    if pair_env == "1":
        return "pair"
    if tpu_env == "1":
        return "native"
    return "pair" if backend == "tpu" else "native"


@pytest.mark.parametrize("backend", ["cpu", "tpu", "gpu"])
@pytest.mark.parametrize("dtype", REAL + CPLX)
def test_truth_table(dtype, backend, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    for pair_env, tpu_env in ENVS:
        for name, val in (("SLU_COMPLEX_PAIR", pair_env),
                          ("SLU_COMPLEX_TPU", tpu_env)):
            if val is None:
                monkeypatch.delenv(name, raising=False)
            else:
                monkeypatch.setenv(name, val)
        want = _expected(dtype, backend, pair_env, tpu_env)
        where = (dtype.__name__, backend, pair_env, tpu_env)
        assert plat.complex_lowering(dtype) == want, where
        # its callers follow it
        from superlu_dist_tpu.ops.batched import _pair_mode
        assert _pair_mode(dtype) == (want == "pair"), where
        # a program leaves the chip only where it is complex, the
        # backend is a TPU, native is not forced, and pair is not
        # to be had
        on_tpu = (dtype in CPLX and backend == "tpu"
                  and tpu_env != "1")
        assert plat.complex_needs_cpu(dtype) is False, where
        assert plat.complex_needs_cpu(dtype, pair_capable=False) \
            is on_tpu, where


def test_the_hook_decides_nothing_on_a_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("SLU_COMPLEX_TPU", raising=False)
    for val in (None, "0", "1", "yes"):
        if val is None:
            monkeypatch.delenv("SLU_COMPLEX_PAIR", raising=False)
        else:
            monkeypatch.setenv("SLU_COMPLEX_PAIR", val)
        assert plat.complex_lowering(np.complex64) == "pair"


@pytest.fixture(scope="module")
def problem():
    a = helmholtz_2d(10)
    xtrue, b = manufactured_rhs(a)
    return a, xtrue, b


@pytest.mark.parametrize("backend,hook,want", [
    ("cpu", "0", "native"), ("cpu", "1", "pair"), ("tpu", "0", "pair")])
def test_stats_and_the_ring_say_which_lowering_ran(
        problem, backend, hook, want, monkeypatch):
    monkeypatch.setenv("SLU_COMPLEX_PAIR", hook)
    monkeypatch.delenv("SLU_COMPLEX_TPU", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    a, xtrue, b = problem
    opts = Options(factor_dtype="complex64", refine_dtype="complex128")
    plan = plan_factorization(a, opts)
    st = Stats()
    lu = factorize(a, opts, plan=plan, stats=st)
    assert st.complex_lowering == {"FACT": want}
    x = solve(lu, b, stats=st)
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-10
    assert st.complex_lowering == {"FACT": want, "SOLVE": want}
    assert st.placement == {}
    assert st.snapshot()["complex_lowering"] == st.complex_lowering
    assert f"complex lowering:     FACT {want}, SOLVE {want}" \
        in st.report()
    snap = obs.HEALTH.snapshot()
    assert snap["last_factor"]["complex_lowering"] == want
    assert snap["last_factor"]["dtype"] == "complex64"
    rec = snap["recent_solves"][-1]
    assert rec["complex_lowering"] == want
    assert rec["sweeps"] == {"complex64": 1 + rec["steps"]}


def test_a_handle_keeps_the_storage_it_was_made_with(problem,
                                                     monkeypatch):
    """A solve runs the programs of its HANDLE's storage, whatever the
    rule would give a new factorization; a natively stored handle
    cannot take the pair lowering, so on a TPU it is gated."""
    from superlu_dist_tpu.ops.batched import _lu_is_pair
    a, xtrue, b = problem
    opts = Options(factor_dtype="complex128")
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    lu_pair = factorize(a, opts)
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "0")
    lu_native = factorize(a, opts)
    for hook in ("1", "0"):
        monkeypatch.setenv("SLU_COMPLEX_PAIR", hook)
        for lu, pair in ((lu_pair, True), (lu_native, False)):
            assert _lu_is_pair(lu.device_lu) is pair
            x = solve(lu, b, stats=Stats())
            assert (np.linalg.norm(x - xtrue)
                    / np.linalg.norm(xtrue)) < 1e-10
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for lu, want in ((lu_pair, "pair"), (lu_native, "cpu")):
        st = Stats()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", plat.ComplexPlacementWarning)
            solve(lu, b, stats=st)
        assert st.complex_lowering == {"SOLVE": want}


def test_a_real_system_records_no_lowering():
    import scipy.sparse as sp

    from superlu_dist_tpu import csr_from_scipy
    t = sp.diags([-1.0, 2.5, -1.2], [-1, 0, 1], shape=(12, 12))
    a = csr_from_scipy(sp.kronsum(t, t).tocsr())
    st = Stats()
    solve(factorize(a, Options(), stats=st), np.ones(a.n), stats=st)
    assert st.complex_lowering == {}
    assert "complex lowering" not in st.report()
    snap = obs.HEALTH.snapshot()
    assert snap["last_factor"]["complex_lowering"] is None
    assert snap["recent_solves"][-1]["complex_lowering"] is None


def test_pair_kernels_carry_the_real_kernels_scopes(problem,
                                                    monkeypatch):
    """The pair factor program names its operations with the scopes of
    the real one, so the trace readers of the real cells read a
    complex cell: every kernel scope is in the lowered text."""
    from superlu_dist_tpu.ops import batched
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    a, _, _ = problem
    cdt = np.dtype(np.complex64)
    plan = plan_factorization(a, Options(factor_dtype="complex64"))
    sched = batched.get_schedule(plan, 1)
    factor_fn, _ = batched._phase_fns(
        sched, cdt, batched._thresh_for(plan, cdt))
    vals = batched._pair_encode_vals(plan.scaled_values(a), cdt)
    txt = factor_fn.lower(jnp.asarray(vals)).as_text(debug_info=True)
    for scope in ("slu.assemble", "slu.extend_add", "slu.partial_lu",
                  "slu.tri_inverse", "slu.schur", "slu.store"):
        assert scope in txt, scope
    # schur and the block inverses sit INSIDE partial_lu, as in the
    # real kernel (the innermost scope is the operation's kernel)
    assert "slu.partial_lu/" in txt.replace("jit(partial_lu_pair)/", "")
    assert "complex<" not in txt            # an all-real program


def test_the_codec_has_a_span_of_its_own(problem, monkeypatch):
    """The host's plane encode and decode of a pair sweep are the leaf
    span `solve.codec`, outside `solve.sweep`; a native solve has
    none."""
    a, xtrue, b = problem
    opts = Options(factor_dtype="complex64", refine_dtype="complex128")
    seen = []
    real_span = obs.span

    def spying(name, **kw):
        seen.append(name)
        return real_span(name, **kw)

    from superlu_dist_tpu.ops import batched
    monkeypatch.setattr(batched.obs, "span", spying)
    for hook, codecs in (("1", 2), ("0", 0)):
        monkeypatch.setenv("SLU_COMPLEX_PAIR", hook)
        lu = factorize(a, opts)
        del seen[:]
        st = Stats()
        solve(lu, b, stats=st)
        sweeps = sum(st.sweeps.values())
        assert seen.count("solve.sweep") == sweeps
        assert seen.count("solve.codec") == codecs * sweeps
        if codecs:
            # encode, (the first solve's pack,) the sweep with its
            # fetch inside, decode: the codec is outside the sweep
            assert seen.index("solve.codec") < seen.index("solve.sweep")
            mine = [n for n in seen if n.startswith("solve.")]
            assert mine[-2:] == ["solve.fetch", "solve.codec"]
