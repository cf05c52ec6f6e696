"""The reader of `pack_at_factor_share.step`: on made-up health rings,
on a parent whose records have no `pack`, and through the program on
the CPU rehearsal of a one-chip step cell under either sweep (a
rehearsal reports none, so the share is read by its own function).  The reader
is a file the harness finds; its entry in `per_layer` waits for a
`benchmark` PR (PERF.md section 7)."""

import json
import os
import types

import pytest

import harness
from conftest import ROOT
from test_correct import drive, rehearsal_run

NAME = "pack_at_factor_share.step"


def made_up(events, steps=0):
    snap = {} if events is None else {"factor_events": events}
    return types.SimpleNamespace(
        rehearse=False, notes={}, readings={"refine_steps": [3] * steps},
        slu=types.SimpleNamespace(obs=types.SimpleNamespace(
            HEALTH=types.SimpleNamespace(snapshot=lambda: snap))))


def rec(pack):
    return {"tiny_pivots": 0, "dtype": "float32", "pack": pack}


def test_reads_the_programs_ring():
    read = harness.metric_reader(NAME).read
    run = made_up([rec("at_factor")] * 5)
    assert read(run) == 100.0
    assert run.notes["packs"] == {"at_factor": 5}
    # every first solve still packs: the parent's behaviour, said by a
    # program that has the field
    assert read(made_up([rec("at_solve")] * 4)) == 0.0
    run = made_up([rec("at_factor"), rec("at_solve"), rec("none"),
                   rec("at_factor")])
    assert read(run) == 50.0
    assert run.notes["packs"] == {"at_factor": 2, "at_solve": 1,
                                  "none": 1}
    # a path that never packs (the mesh, the legacy sweep)
    assert read(made_up([rec("none")] * 3)) == 0.0
    # only the window's steps count: older records are warm-up's
    ring = [rec("at_solve")] * 2 + [rec("at_factor")] * 3
    assert read(made_up(ring, steps=3)) == 100.0
    assert read(made_up(ring)) == 60.0


def test_reads_nothing_where_nothing_is():
    """The parent of the PR that brought the field has records without
    `pack`, or no ring at all: None, no error, no note."""
    read = harness.metric_reader(NAME).read
    for events in (None, [], [{"tiny_pivots": 0, "dtype": "float32"}],
                   [rec("at_factor"), {"tiny_pivots": 0}]):
        run = made_up(events)
        assert read(run) is None and not run.notes


@pytest.mark.parametrize("arm, where", [("merged", "at_factor"),
                                        ("legacy", "none")])
def test_through_the_program_on_the_rehearsal(monkeypatch, arm, where):
    """Under the merged sweep every factorization of the window packs
    itself; under the legacy sweep nothing packs, ever."""
    monkeypatch.setenv("SLU_TRISOLVE", arm)
    reader = harness.metric_reader(NAME)
    run = rehearsal_run("lap3d_k30.step")
    line = drive(run)
    assert line["correct"] is True and line["attempted"] > 0
    assert reader.share(run) == (100.0 if where == "at_factor" else 0.0)
    steps = len(run.readings["refine_steps"][-64:])
    assert run.notes["packs"] == {where: steps}
    assert reader.read(run) is None         # a rehearsal reports none


def test_the_reader_is_a_file_and_not_yet_an_entry():
    assert hasattr(harness.metric_reader(NAME), "read")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert NAME not in names
