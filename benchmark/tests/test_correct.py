"""`correct` has to come out false when it should: for the
configuration's controls (the refinement residual in float32,
refinement off), at a size a test can hold, and for a timed path that
is broken underneath the harness."""

import time

import numpy as np
import pytest

import harness


def rehearsal_run(workload, control=None, seconds=1.0):
    spec = harness.load_cell(workload)
    run = harness.Run(spec, seed=2147483777, seconds=seconds,
                      trace=False, rehearse=True,
                      t_start=time.perf_counter(), control=control)
    run.open()
    return run


def drive(run, before_window=None):
    kind = harness.load_module("kind_" + run.traffic["kind"], "kinds",
                               run.traffic["kind"] + ".py")
    state = kind.setup(run)
    try:
        if before_window:
            before_window(run, state)
        kind.window(run, state)
        verdict = kind.check(run, state)
    finally:
        kind.close(run, state)
    return harness.result_line(run, verdict, {})


@pytest.mark.parametrize("workload", ["lap3d_k30.step",
                                      "lap3d_k30.serve"])
def test_sound_run_is_correct(workload):
    line = drive(rehearsal_run(workload))
    assert line["correct"] is True and line["attempted"] > 0


@pytest.mark.parametrize("control", ["refine_float32", "no_refine"])
@pytest.mark.parametrize("workload", ["lap3d_k30.step",
                                      "lap3d_k30.serve"])
def test_control_is_not_correct(workload, control):
    line = drive(rehearsal_run(workload, control))
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    worst = {c["name"]: c for c in line["compared"]}
    assert worst["berr_max"]["value"] > worst["berr_max"]["limit"]


def test_altered_answer_is_not_correct(monkeypatch):
    """An answer altered where it is produced."""
    run = rehearsal_run("lap3d_k30.step")
    real = run.slu.solve

    def altered(lu, b, stats=None):
        x = np.array(real(lu, b, stats=stats))
        x[0] *= 1.0 + 1e-6
        return x

    line = drive(run, lambda r, s: monkeypatch.setattr(r.slu, "solve",
                                                       altered))
    assert line["correct"] is False and line["failed"] > 0


def test_step_that_keeps_its_state_is_not_correct(monkeypatch):
    """A step that returns its state unchanged: the refactorization
    hands back the first step's factors whatever the new values."""
    run = rehearsal_run("lap3d_k30.step")
    real = run.slu.factorize
    kept = []

    def stale(a, options=None, **kw):
        if not kept:
            kept.append(real(a, options, **kw))
        return kept[0]

    line = drive(run, lambda r, s: monkeypatch.setattr(
        r.slu, "factorize", stale))
    assert line["correct"] is False
    # step 0 and every return to value set 0 are still right
    assert 0 < line["failed"] < line["attempted"]


def test_refused_request_counts_as_failed(monkeypatch):
    run = rehearsal_run("lap3d_k30.serve")

    def shrink(r, s):
        s["svc"].config.max_queue_depth = 1
        r.traffic = dict(r.traffic, rate_per_s=400.0)

    line = drive(run, shrink)
    assert line["correct"] is False and line["failed"] > 0
