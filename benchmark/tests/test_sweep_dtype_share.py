"""The reader of `sweep_factor_dtype_share.step`: on made-up health
rings, on a parent that has no such counter, and through the program
on the CPU rehearsal of each step cell (a rehearsal reports none, so
the share is read by its own function)."""

import json
import os
import types

import pytest

import harness
from conftest import ROOT
from test_correct import drive, rehearsal_run

NAME = "sweep_factor_dtype_share.step"
CELLS = ["lap3d_k30.step", "lap3d_k30_grid2x2.step", "elas3d_q1.step"]


def made_up(recent, steps=0, factor_dtype="float32"):
    snap = {} if recent is None else {"recent_solves": recent}
    return types.SimpleNamespace(
        rehearse=False, notes={}, readings={"refine_steps": [3] * steps},
        config={"options": {"factor_dtype": factor_dtype}},
        slu=types.SimpleNamespace(obs=types.SimpleNamespace(
            HEALTH=types.SimpleNamespace(snapshot=lambda: snap))))


def test_reads_the_programs_counter():
    read = harness.metric_reader(NAME).read
    ring = [{"steps": 3, "sweeps": {"float32": 4},
             "berr_trajectory": [1e-7, 1e-13, 2e-16, 1e-16]}] * 5
    run = made_up(ring)
    assert read(run) == 100.0
    assert run.notes["sweeps_by_dtype"] == {"float32": 20}
    assert run.notes["berr_trajectory"] == [1e-7, 1e-13, 2e-16, 1e-16]
    # a complex system on real factors sweeps in the complex dtype of
    # the factor's width: that is the factor's precision
    assert read(made_up([{"sweeps": {"complex64": 4}}])) == 100.0
    # the old operand: f64 sweeps on f32 factors
    assert read(made_up([{"sweeps": {"float64": 4}}])) == 0.0
    assert read(made_up([{"sweeps": {"float64": 3, "float32": 1}}])) == 25.0
    # only the window's steps count: the ring's older records are
    # warm-up's
    ring = [{"sweeps": {"float64": 4}}] * 2 + [{"sweeps": {"float32": 4}}] * 3
    assert read(made_up(ring, steps=3)) == 100.0
    assert read(made_up(ring)) == 60.0


def test_reads_nothing_where_nothing_is():
    """The parent of the PR that brought the counter has no ring in
    its snapshot, or records without `sweeps`: None, no error."""
    read = harness.metric_reader(NAME).read
    for recent in (None, [], [{"steps": 3, "berr": 1e-16}],
                   [{"sweeps": {}}]):
        run = made_up(recent)
        assert read(run) is None and not run.notes


@pytest.mark.parametrize("cell", CELLS[::2])
def test_through_the_program_on_the_rehearsal(cell):
    reader = harness.metric_reader(NAME)
    run = rehearsal_run(cell)
    line = drive(run)
    assert line["correct"] is True and line["attempted"] > 0
    assert reader.share(run) == 100.0
    by = run.notes["sweeps_by_dtype"]
    assert set(by) == {"float32"}
    # every step of the window: one sweep for x0, one a pass
    steps = run.readings["refine_steps"][-64:]
    assert by["float32"] == len(steps) + sum(steps)
    traj = run.notes["berr_trajectory"]
    assert len(traj) == 1 + steps[-1] and traj[-1] <= 64 * 2.0 ** -52
    assert reader.read(run) is None         # a rehearsal reports none


def test_declared_for_the_three_step_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert per_layer[-1] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "triangular solve + refinement", "moves": "step_s",
        "workloads": CELLS}
