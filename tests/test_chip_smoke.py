"""chip_smoke.py refuses to speak for a device it does not
have, and the smoke's CPU rehearsal keeps the script itself alive.

What these pin is the contract the driver checks on every PR: with no
accelerator the entry point exits non-zero and prints NO result; a
rehearsal prints records that name `cpu` and never an `"ok"`.  The
chip pass itself cannot be tested here — it is what `python
chip_smoke.py` through the chip tool is for.
"""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(argv, cwd=ROOT, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _json_lines(text):
    out = []
    for ln in text.splitlines():
        try:
            out.append(json.loads(ln))
        except ValueError:
            pass
    return out


def test_chip_smoke_fails_without_tpu():
    p = _run([SMOKE])
    assert p.returncode != 0
    assert _json_lines(p.stdout) == []          # no result of any kind
    assert "needs a TPU" in p.stderr


def test_chip_smoke_mesh_fails_without_four_tpus():
    p = _run([SMOKE, "--mesh", "2x2x1"])
    assert p.returncode != 0
    assert _json_lines(p.stdout) == []


def test_chip_smoke_fails_alone(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the program is not importable: non-zero, no result."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p = _run([str(tmp_path / "chip_smoke.py"), "--rehearse-cpu"],
             cwd=str(tmp_path), PYTHONPATH="")
    assert p.returncode != 0
    assert _json_lines(p.stdout) == []
    assert "cannot import the program" in p.stderr


def test_chip_smoke_rehearsal_names_cpu_and_never_ok(tmp_path):
    out = tmp_path / "report.json"
    p = _run([SMOKE, "--rehearse-cpu", "--k", "5", "--out", str(out)])
    assert p.returncode == 0, p.stderr[-3000:]
    recs = _json_lines(p.stdout)
    last = recs[-1]
    assert last == {"rehearsal": True, "checks_passed": True,
                    "device": last["device"]}
    assert last["device"]["platform"] == "cpu"
    assert not any("ok" in r for r in recs)     # never a device pass
    phases = [r["phase"] for r in recs[:-1]]
    assert phases == [
        "setup", "pallas_kernels", "gssvx_cold",
        "refactor_same_rowperm",
        "solve_factored_nrhs1_first", "solve_factored_nrhs1_warm",
        "solve_factored_nrhs8_first", "solve_factored_nrhs8_warm",
        "serve_prefactor", "serve_requests", "summary"]
    by = {r["phase"]: r for r in recs[:-1]}
    assert by["setup"]["device"]["platform"] == "cpu"
    assert by["setup"]["n"] == 125
    assert by["setup"]["native_library_loaded"] is True
    assert by["pallas_kernels"]["interpret"] is True
    assert by["gssvx_cold"]["factor_dtype"] == "float32"
    assert by["gssvx_cold"]["answer"]["berr"] <= by["setup"]["berr_max"]
    assert by["refactor_same_rowperm"]["compile"]["watch_misses"] == 0
    assert by["serve_requests"]["requests_solved"] == 9
    assert by["summary"]["failed"] == []
    assert json.loads(out.read_text()) == recs[:-1]
