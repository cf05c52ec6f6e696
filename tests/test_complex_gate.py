"""Complex on a TPU (utils/platform.py): the lowering rule's
consequences for placement.

Measured basis: on a TPU v5e under jax 0.9.0 a tiny jitted native
complex128 LU/GEMM program aborts the process inside the TPU
compiler (tools/complex_probe.py, PR 23).  Since PR 32 a complex
factorization and solve on a TPU default backend run ON the chip in
the pair lowering (`complex_lowering`), with no environment variable
and no warning; only a path that cannot store pairs is still placed
on the host CPU backend — loudly: one warning, and the placement on
Stats.

These tests run on a CPU host, so the TPU condition is simulated by
patching jax.default_backend — what is pinned is the decision logic,
its overrides, that the pair path is taken and is silent, that a
gated placement still happens where it must (pair_capable=False: the
host oracle, a natively stored handle) and is not silent, and that
both still solve correctly."""

import numpy as np
import pytest
import scipy.sparse as sp

import jax

from superlu_dist_tpu import Options, csr_from_scipy, gssvx
from superlu_dist_tpu.utils.platform import (ComplexPlacementWarning,
                                             complex_device_gate,
                                             complex_lowering,
                                             complex_needs_cpu)


def _cmat(n=16):
    rng = np.random.default_rng(5)
    t = sp.diags([-1.0, 2.5, -1.2], [-1, 0, 1], shape=(n, n))
    a = sp.kronsum(t, t).tocsr().astype(np.complex128)
    a = a + 1j * sp.diags(rng.standard_normal(a.shape[0]) * 0.1)
    return csr_from_scipy(a.tocsr())


def test_gate_decision_logic(monkeypatch):
    """On a TPU a complex dtype takes the pair lowering and stays on
    the chip; only a caller that cannot store pairs needs the CPU."""
    monkeypatch.delenv("SLU_COMPLEX_PAIR", raising=False)
    monkeypatch.delenv("SLU_COMPLEX_TPU", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for dt in (np.complex128, np.complex64):
        assert complex_lowering(dt) == "pair"
        assert not complex_needs_cpu(dt)
        assert complex_needs_cpu(dt, pair_capable=False)
    for dt in (np.float32, np.float64):
        assert complex_lowering(dt) == "native"
        assert not complex_needs_cpu(dt)
        assert not complex_needs_cpu(dt, pair_capable=False)
    monkeypatch.setenv("SLU_COMPLEX_TPU", "1")
    assert complex_lowering(np.complex128) == "native"
    assert not complex_needs_cpu(np.complex128)
    assert not complex_needs_cpu(np.complex128, pair_capable=False)


def test_gate_inactive_on_cpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not complex_needs_cpu(np.complex128)
    with complex_device_gate(np.complex128) as engaged:
        assert not engaged


def _recording_gate(monkeypatch):
    """Record what every complex_device_gate of the driver yields."""
    import superlu_dist_tpu.utils.platform as platform_mod
    engaged = []
    real_gate = platform_mod.complex_device_gate

    def recording_gate(*dtypes, **kw):
        cm = real_gate(*dtypes, **kw)

        class Wrap:
            def __enter__(self):
                v = cm.__enter__()
                engaged.append(v)
                return v

            def __exit__(self, *exc):
                return cm.__exit__(*exc)
        return Wrap()

    monkeypatch.setattr(platform_mod, "complex_device_gate",
                        recording_gate)
    return engaged


def test_gated_solve_places_on_cpu_and_is_correct(monkeypatch):
    """With the backend claiming to be TPU and no variable set: (a) a
    complex factorize + solve through the jax backend does NOT engage
    the gate: the handle is pair-stored and solves to full accuracy;
    (b) where the placement still happens — a natively stored handle,
    which cannot take the pair lowering — the gate engages, every
    device buffer it makes is on a CPU device, and the answer is as
    good."""
    import warnings

    from superlu_dist_tpu.models.gssvx import factorize, solve
    from superlu_dist_tpu.ops.batched import _lu_is_pair
    monkeypatch.delenv("SLU_COMPLEX_PAIR", raising=False)
    monkeypatch.delenv("SLU_COMPLEX_TPU", raising=False)
    a = _cmat()
    rng = np.random.default_rng(0)
    xtrue = rng.standard_normal(a.n) + 1j * rng.standard_normal(a.n)
    b = a.to_scipy() @ xtrue
    lu_native = factorize(a, Options(), backend="jax")   # on the CPU
    assert not _lu_is_pair(lu_native.device_lu)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engaged = _recording_gate(monkeypatch)
    with warnings.catch_warnings():
        warnings.simplefilter("error", ComplexPlacementWarning)
        lu = factorize(a, Options(), backend="jax")
        x = solve(lu, b)
    assert engaged == [False, False], \
        "the pair path must not engage the gate"
    assert _lu_is_pair(lu.device_lu)
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-12
    # (b) the gated placement, where it still happens
    del engaged[:]
    with pytest.warns(ComplexPlacementWarning):
        x = solve(lu_native, b)
    assert engaged == [True], \
        "complex_device_gate did not engage on a native handle"
    leaves = [v for v in vars(lu_native.device_lu).values()
              if hasattr(v, "devices")]
    assert leaves, "expected device buffers on the LU handle"
    for v in leaves:
        assert all(d.platform == "cpu" for d in v.devices()), v.devices()
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-12


def test_gated_gssvx_end_to_end_is_not_silent(monkeypatch):
    """The gate says what it did where it still engages (the host
    oracle has no pair storage): a placement warns, naming the dtype
    and where it went, and every gated phase is on Stats.placement,
    on Stats.complex_lowering and in the report.  The pair path of
    the same call is silent and says "pair"."""
    import warnings
    monkeypatch.delenv("SLU_COMPLEX_PAIR", raising=False)
    monkeypatch.delenv("SLU_COMPLEX_TPU", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    a = _cmat()
    rng = np.random.default_rng(1)
    xtrue = rng.standard_normal(a.n) + 1j * rng.standard_normal(a.n)
    with pytest.warns(ComplexPlacementWarning,
                      match="complex128 programs are placed on the "
                            "host CPU backend"):
        x, lu, st = gssvx(Options(), a, a.to_scipy() @ xtrue,
                          backend="host")
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    assert relerr < 1e-12
    assert st.placement == {"FACT": "cpu", "SOLVE": "cpu"}
    assert st.complex_lowering == {"FACT": "cpu", "SOLVE": "cpu"}
    assert "placed off-default:   FACT on cpu, SOLVE on cpu" \
        in st.report()
    assert "complex lowering:     FACT cpu, SOLVE cpu" in st.report()
    # the normal path: on the chip, in pair storage, without a word
    with warnings.catch_warnings():
        warnings.simplefilter("error", ComplexPlacementWarning)
        x, lu, st = gssvx(Options(), a, a.to_scipy() @ xtrue)
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-12
    assert st.placement == {}
    assert st.complex_lowering == {"FACT": "pair", "SOLVE": "pair"}
    assert "complex lowering:     FACT pair, SOLVE pair" in st.report()
    # a real system on the same backend is placed nowhere special
    ar = csr_from_scipy(a.to_scipy().real.tocsr())
    _, _, st_r = gssvx(Options(), ar, np.ones(ar.n))
    assert st_r.placement == {} and st_r.complex_lowering == {}


def test_accel_amalg_defaults(monkeypatch):
    """apply_accel_amalg_defaults: measured TPU values as env
    DEFAULTS (user env wins), and Options built afterwards pick them
    up."""
    import os

    from superlu_dist_tpu.options import Options as Opt
    from superlu_dist_tpu.utils.platform import (
        apply_accel_amalg_defaults)

    # first-touch each key THROUGH monkeypatch so teardown restores
    # the pre-test state even though apply_* writes via os.environ
    # directly (setenv records "absent" as the original; a bare
    # delenv(raising=False) on an unset var records nothing and the
    # values would leak into every later test's Options())
    for k in ("SUPERLU_AMALG_TAU_PCT", "SUPERLU_AMALG_CAP"):
        monkeypatch.setenv(k, "tracked")
        monkeypatch.delenv(k)
    apply_accel_amalg_defaults()
    assert os.environ["SUPERLU_AMALG_TAU_PCT"] == "400"
    assert os.environ["SUPERLU_AMALG_CAP"] == "1024"
    o = Opt()
    assert o.amalg_tau == 4.0 and o.amalg_cap == 1024
    # user env wins
    monkeypatch.setenv("SUPERLU_AMALG_TAU_PCT", "150")
    monkeypatch.delenv("SUPERLU_AMALG_CAP")
    apply_accel_amalg_defaults()
    assert os.environ["SUPERLU_AMALG_TAU_PCT"] == "150"
    assert os.environ["SUPERLU_AMALG_CAP"] == "1024"
