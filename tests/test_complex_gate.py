"""Complex-on-TPU platform gate (utils/platform.py).

Measured basis: on a TPU v5e under jax 0.9.0 a tiny jitted native
complex128 LU/GEMM program aborts the process inside the TPU
compiler (tools/complex_probe.py, PR 23), so complex programs are
placed on the host CPU backend instead of taking the caller down —
loudly: one warning, and the placement on Stats.

These tests run on a CPU host, so the TPU condition is simulated by
patching jax.default_backend — what is pinned is the gate's decision
logic, its override, that it is not silent, and that a gated gssvx
still solves correctly with every device buffer actually resident on
a CPU device."""

import numpy as np
import pytest
import scipy.sparse as sp

import jax

from superlu_dist_tpu import Options, csr_from_scipy, gssvx
from superlu_dist_tpu.utils.platform import (ComplexPlacementWarning,
                                             complex_device_gate,
                                             complex_needs_cpu)


def _cmat(n=16):
    rng = np.random.default_rng(5)
    t = sp.diags([-1.0, 2.5, -1.2], [-1, 0, 1], shape=(n, n))
    a = sp.kronsum(t, t).tocsr().astype(np.complex128)
    a = a + 1j * sp.diags(rng.standard_normal(a.shape[0]) * 0.1)
    return csr_from_scipy(a.tocsr())


def test_gate_decision_logic(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert complex_needs_cpu(np.complex128)
    assert complex_needs_cpu(np.complex64)
    assert not complex_needs_cpu(np.float32)
    assert not complex_needs_cpu(np.float64)
    monkeypatch.setenv("SLU_COMPLEX_TPU", "1")
    assert not complex_needs_cpu(np.complex128)


def test_gate_inactive_on_cpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not complex_needs_cpu(np.complex128)
    with complex_device_gate(np.complex128) as engaged:
        assert not engaged


def test_gated_solve_places_on_cpu_and_is_correct(monkeypatch):
    """With the backend claiming to be TPU, a complex gssvx must (a)
    engage the gate, (b) keep every factor buffer on a CPU device,
    (c) solve to full accuracy."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    a = _cmat()
    rng = np.random.default_rng(0)
    xtrue = rng.standard_normal(a.n) + 1j * rng.standard_normal(a.n)
    from superlu_dist_tpu.models.gssvx import factorize, solve
    # pin that the gate ENGAGES on this host (where all buffers are
    # CPU-resident anyway, so the placement assertions alone would
    # stay green if the gate were dropped from factorize)
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
    lu = factorize(a, Options(), backend="jax")
    assert engaged and engaged[0] is True, \
        "complex_device_gate did not engage on the factorize path"
    # device buffers must be committed to the CPU backend
    leaves = [x for x in vars(lu.device_lu).values()
              if hasattr(x, "devices")]
    assert leaves, "expected device buffers on the LU handle"
    for x in leaves:
        assert all(d.platform == "cpu" for d in x.devices()), x.devices()
    x = solve(lu, a.to_scipy() @ xtrue)
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    assert relerr < 1e-12


def test_gated_gssvx_end_to_end_is_not_silent(monkeypatch):
    """The gate says what it did: a placement warns, naming the dtype
    and where it went, and every gated phase is on Stats.placement
    (and in the report)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    a = _cmat()
    rng = np.random.default_rng(1)
    xtrue = rng.standard_normal(a.n) + 1j * rng.standard_normal(a.n)
    with pytest.warns(ComplexPlacementWarning,
                      match="complex128 programs are placed on the "
                            "host CPU backend"):
        x, lu, st = gssvx(Options(), a, a.to_scipy() @ xtrue)
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    assert relerr < 1e-12
    assert st.placement == {"FACT": "cpu", "SOLVE": "cpu"}
    assert "placed off-default:   FACT on cpu, SOLVE on cpu" \
        in st.report()
    # a real system on the same backend is placed nowhere special
    ar = csr_from_scipy(a.to_scipy().real.tocsr())
    _, _, st_r = gssvx(Options(), ar, np.ones(ar.n))
    assert st_r.placement == {}


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


def test_complex_tpu_mesh_rejected(monkeypatch):
    """backend='dist' with a TPU mesh and a complex dtype must fail
    fast with the documented message, not hang in compilation."""
    from superlu_dist_tpu.models.gssvx import factorize

    class FakeDev:
        platform = "tpu"

    class FakeMesh:
        devices = np.array([FakeDev()])

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    a = _cmat()
    with pytest.raises(ValueError, match="complex factorization on a "
                                         "TPU mesh is disabled"):
        factorize(a, Options(), backend="dist", grid=FakeMesh())
