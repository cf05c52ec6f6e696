"""The one command, rehearsed on the CPU at k=6: both generator kinds,
the grid on four virtual devices, traced and untraced; and its
refusals.  A rehearsal prints no metric."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

RUN = [sys.executable, os.path.join(BENCH, "run.py")]


def command(workload, *extra, devices=1, cwd=ROOT, run=RUN):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                        f"{devices}")
    return subprocess.run(
        run + ["--workload", workload, "--seed", "2147483659",
               "--seconds", "2", *extra],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=900)


@pytest.mark.parametrize("workload,devices,trace,expects", [
    ("lap3d_k30.step", 1, "0", {"step_s", "setup_s"}),
    ("lap3d_k30.step", 1, "1", {"factor_s", "solve_s.step", "plan_s",
                                "compile_s", "window_compiles.step",
                                "refine_steps.step", "step_median_s"}),
    ("lap3d_k30.serve", 1, "0", {"serve_p50_s", "serve_p95_s",
                                 "setup_s"}),
    ("lap3d_k30.serve", 1, "1", {"device_solve_s.serve",
                                 "queue_wait_s.serve",
                                 "batch_occupancy.serve",
                                 "generator_lag_ms.serve", "compile_s",
                                 "window_compiles.serve"}),
    ("lap3d_k30_grid2x2.step", 4, "0", {"step_s", "setup_s"}),
])
def test_rehearsal(workload, devices, trace, expects):
    r = command(workload, "--trace", trace, "--rehearse-cpu",
                devices=devices)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "metrics" not in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # device metrics need the chip's trace, so a rehearsal has none
    assert set(line["metric_names"]) == expects
    assert line["device"]["count"] == devices


def test_refuses_without_a_tpu():
    r = command("lap3d_k30.step", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_refuses_with_too_few_chips():
    r = command("lap3d_k30_grid2x2.step", "--trace", "0",
                "--rehearse-cpu", devices=1)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env_run = [sys.executable, str(tmp_path / "benchmark" / "run.py")]
    r = command("lap3d_k30.step", "--trace", "0", "--rehearse-cpu",
                cwd=tmp_path, run=env_run)
    assert r.returncode != 0 and r.stdout.strip() == ""
