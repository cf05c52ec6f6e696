"""C ABI binding layer (csrc/slu_capi.cpp) — the Fortran-interface
slot (FORTRAN/superlu_c2f_dwrap.c:142 analog): builds the embedded-
interpreter library and drives the solver from a PURE C host program
(one-call driver, opaque-handle factorize/solve, transpose solve),
the f_5x5.F90-style hand-checkable smoke test."""

import os
import shutil
import subprocess

import pytest

CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "csrc")


@pytest.mark.skipif(shutil.which("python3-config") is None
                    or shutil.which("make") is None,
                    reason="embedding toolchain unavailable")
def test_capi_demo_from_c_host():
    # the demo binary is not under version control (csrc/capi_demo is
    # git-ignored): build it here, from source, every time
    try:
        os.unlink(os.path.join(CSRC, "capi_demo"))
    except FileNotFoundError:
        pass
    r = subprocess.run(["make", "libslu_tpu_c.so", "capi_demo"],
                       cwd=CSRC, capture_output=True, text=True,
                       timeout=300)
    if r.returncode != 0:
        # python3-config may describe a different interpreter than the
        # one running pytest (bare system python without Python.h) —
        # an environment gap, not a solver bug
        pytest.skip(f"embedding build unavailable: {r.stderr[-400:]}")
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)   # prove the repo-path arg suffices
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(CSRC)
    r = subprocess.run([os.path.join(CSRC, "capi_demo"), repo],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=CSRC)
    if "ModuleNotFoundError" in r.stderr:
        pytest.skip("embedded interpreter lacks the scientific stack "
                    "(python3-config points at a different python)")
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-1500:])
    assert "CAPI_OK" in r.stdout
